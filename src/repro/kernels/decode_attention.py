"""Decode attention Pallas TPU kernel — one new token vs. a KV cache.

Flash-decoding adapted to the TPU memory system:
* Decode is HBM-bandwidth-bound (the whole KV cache is read once per token,
  arithmetic intensity ≈ 1 FLOP/byte), so the kernel's job is to stream K/V
  tiles HBM→VMEM at full bandwidth while the VPU does the mask/softmax work.
* GQA rows are batched: the grid is (batch, kv_heads, kv_blocks) and the q
  tile holds all G = Hq/Hkv rows that share one KV head, so each streamed KV
  tile is reused G times (a GPU warp-level trick re-expressed as tile shape).
* Ring-buffer SWA caches are handled by slot-position masking: pos_ids[b, s]
  carries the absolute position held in cache slot s (-1 = empty), the same
  contract as kernels.ref.decode_attention_ref.
* Mosaic tiling: cur_pos is scalar-prefetched into SMEM (a (1,) VMEM block
  breaks the 128-lane rule), and pos_ids is viewed as (B, 1, S) so its
  (1, block_s) block spans a full unit dim for any batch (the (8, 128) rule).

Validated against the ref oracle with interpret=True in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    cur_ref,
    q_ref,
    k_ref,
    v_ref,
    pos_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    window: Optional[int],
    softcap: Optional[float],
    n_blocks: int,
):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cur = cur_ref[pl.program_id(0)]
    pos = pos_ref[0]  # (1, block_s) int32 slot positions
    ok = (pos >= 0) & (pos <= cur)
    if window is not None:
        ok &= pos > cur - window

    @pl.when(jnp.any(ok))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_s, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, block_s)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(si == n_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "scale", "block_s", "interpret"),
)
def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos_ids: jax.Array,
    cur_pos: jax.Array,
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, D); caches: (B, S, Hkv, D); pos_ids: (B, S); cur_pos: (B,)."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    block_s = min(block_s, S)
    pad_s = -S % block_s
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, Hkv, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    pos = pos_ids
    if pad_s:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        pos = jnp.pad(pos, ((0, 0), (0, pad_s)), constant_values=-1)
    n_blocks = (S + pad_s) // block_s
    qt = q.reshape(B, Hkv, G, D)
    pos = pos.reshape(B, 1, S + pad_s)

    kernel = functools.partial(
        _decode_kernel,
        scale=scale,
        window=window,
        softcap=softcap,
        n_blocks=n_blocks,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, n_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, si, cur: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_s, D), lambda b, h, si, cur: (b, h, si, 0)),
                pl.BlockSpec((1, 1, block_s, D), lambda b, h, si, cur: (b, h, si, 0)),
                pl.BlockSpec((1, 1, block_s), lambda b, h, si, cur: (b, 0, si)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, si, cur: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(cur_pos.astype(jnp.int32), qt, kt, vt, pos.astype(jnp.int32))
    return out.reshape(B, Hq, D)
