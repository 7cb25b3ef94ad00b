"""RWKV6 (Finch) WKV chunked-scan Pallas TPU kernel.

The recurrence  out_t = r_t·(S_t + diag(u) k_t v_tᵀ);  S_{t+1} = diag(w_t) S_t + k_t v_tᵀ
is re-blocked for the MXU instead of ported as a per-step GPU loop:

* The grid is (B, H, T/L): chunks are the innermost (sequential) dim, so the
  (K, V) f32 state lives in VMEM scratch across the whole sequence sweep.
* Within a chunk of L steps the recurrence is closed-form: pairwise decays
  (exp of log-space cumsum differences, always ≤ 1 so f32-safe) build an
  (L, L) attention matrix one source column at a time on the VPU, and the
  intra-chunk part is one dense (L×L)·(L×V) matmul — MXU work — while the
  inter-chunk part is one (L×K)·(K×V) matmul against the carried state.
  The log-space cumsum is itself a lower-triangular (L×L)·(L×K) matmul.
* L defaults to 32, so the column loop unrolls to 31 (L, K) passes.

Validated against kernels.ref.rwkv6_scan_ref with interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref, s_scr, *, L: int, n_chunks: int
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)  # (L, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = jnp.log(jnp.clip(w_ref[0, 0].astype(jnp.float32), 1e-38, 1.0))
    u = u_ref[0].astype(jnp.float32)  # (1, K)
    s = s_scr[...]  # (K, V)

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # inclusive cumsum as a lower-triangular matmul (Mosaic has no cumsum)
    cum = jax.lax.dot_general(
        (row >= col).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    # intra-chunk pairwise decays exp(cum_{t-1} - cum_s), strict s < t, always
    # <= 1; built one source column s at a time (Mosaic has no 3D relayouts)
    prev = cum - lw  # cum_{t-1}
    att = (row == col).astype(jnp.float32) * jnp.sum(
        r * u * k, axis=-1, keepdims=True
    )  # u-bonus on the diagonal, s == t
    for s_ in range(L - 1):
        dec_s = jnp.exp(jnp.minimum(prev - cum[s_ : s_ + 1], 0.0))  # (L, K)
        a_s = jnp.sum(r * dec_s * k[s_ : s_ + 1], axis=-1, keepdims=True)  # (L, 1)
        att = jnp.where((col == s_) & (row > s_), a_s, att)
    intra = jax.lax.dot_general(
        att, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dec = jnp.exp(cum - lw)  # prior-state decay at step t: exp(cum_{t-1})
    inter = jax.lax.dot_general(
        r * dec, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0, 0, ...] = (intra + inter).astype(o_ref.dtype)
    # carry: S' = exp(cum_{L-1}) ⊙ S + Σ_s exp(cum_{L-1} - cum_s) k_s v_sᵀ
    last = cum[L - 1 : L, :]  # (1, K) = cum_{L-1}
    dend = jnp.exp(last - cum)  # (L, K)
    # the same total as a (K, 1) column, to scale the state's rows
    last_col = jax.lax.dot_general(
        lw, jnp.ones((L, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    s_scr[...] = jnp.exp(last_col) * s + jax.lax.dot_general(
        k * dend, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ci == n_chunks - 1)
    def _finish():
        sT_ref[0, 0, ...] = s_scr[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    state: jax.Array,
    *,
    chunk: int = 32,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """r,k,v,w: (B,T,H,K); u: (H,K); state: (B,H,K,V) -> (out (B,T,H,V), state)."""
    B, T, H, K = r.shape
    V = state.shape[-1]
    L = min(chunk, T)
    assert T % L == 0, f"T={T} must be a multiple of chunk={L}"
    n_chunks = T // L
    rt, kt, vt, wt = (a.transpose(0, 2, 1, 3) for a in (r, k, v, w))  # (B,H,T,K)

    kernel = functools.partial(_rwkv6_kernel, L=L, n_chunks=n_chunks)
    out, sT = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, L, K), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, L, K), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, L, K), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, L, K), lambda b, h, ci: (b, h, ci, 0)),
            # u viewed as (H, 1, K): a (1, K) block of a unit dim meets (8, 128)
            pl.BlockSpec((1, 1, K), lambda b, h, ci: (h, 0, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, V), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, V), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), state.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, wt, u.reshape(H, 1, K), state)
    return out.transpose(0, 2, 1, 3), sT
