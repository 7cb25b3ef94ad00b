"""Pure-jnp oracles + production fallback paths for every kernel.

Two tiers per op:

* ``*_ref`` — the simplest correct implementation (full materialisation).
  Ground truth for the Pallas kernels' allclose sweeps.  Test-scale only.
* ``*_chunked`` / ``*_local`` — the memory-bounded pure-jnp production path
  used on CPU and in the multi-pod dry-run (Pallas→Mosaic only lowers on real
  TPU).  Numerically equivalent (same f32 accumulation), FLOP/byte-equivalent
  to the Pallas kernels, so the roofline derived from the dry-run HLO is
  representative of the TPU execution.

Shape conventions:
  attention   q: (B, Sq, Hq, D);  k, v: (B, Skv, Hkv, D);  Hq % Hkv == 0
  decode      q: (B, Hq, D);      cache: (B, S, Hkv, D);   pos_ids: (B, S)
  gmm         x: (M, D) sorted by group;  w: (G, D, F);  group_sizes: (G,)
  rwkv6       r,k,v,w: (B, T, H, K);  u: (H, K);  state: (B, H, K, V)
  mamba       x,dt: (B, T, DI);   B,C: (B, T, N);  A: (DI, N);  state: (B, DI, N)
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-finite: avoids NaN from (-inf) - (-inf) in fully-masked rows


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap else x


# ---------------------------------------------------------------------------
# Attention — naive oracle
# ---------------------------------------------------------------------------


def mha_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
) -> jax.Array:
    """Full-materialisation attention oracle (GQA/causal/SWA/softcap)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qr = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k.astype(jnp.float32)) * scale
    s = _softcap(s, softcap)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention — chunked flash (production fallback; blueprint of the kernel)
# ---------------------------------------------------------------------------


def flash_attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_k: int = 512,
) -> jax.Array:
    """Online-softmax attention, lax.scan over KV blocks.  O(Sq·block_k) live."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    block_k = min(block_k, Sk)
    n_blocks = -(-Sk // block_k)
    pad = n_blocks * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, n_blocks, block_k, Hkv, D).swapaxes(0, 1)
    vb = v.reshape(B, n_blocks, block_k, Hkv, D).swapaxes(0, 1)
    qr = (q.reshape(B, Sq, Hkv, G, D) * scale).astype(jnp.float32)
    qpos = jnp.arange(Sq) + q_offset

    def body(carry, blk):
        m, l, acc = carry
        kb_i, vb_i, start = blk
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, kb_i.astype(jnp.float32))
        s = _softcap(s, softcap)
        kpos = start + jnp.arange(block_k)
        ok = kpos[None, :] < Sk
        if causal:
            ok &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vb_i.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    starts = jnp.arange(n_blocks) * block_k
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


def local_window_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
) -> jax.Array:
    """Sliding-window attention by overlapping KV gather: O(Sq·window).

    Each q block of ``block_q`` rows attends to the KV slice
    [blk_start - window + 1, blk_start + block_q) — total width window+block_q.
    FLOPs scale with Sq·(window+block_q) instead of Sq².  Self-attention only
    (q and k aligned, causal).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Sq == Sk, "local attention is for aligned self-attention"
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    bq = block_q or min(max(window, 128), 1024)
    n_blocks = -(-Sq // bq)
    pad_q = n_blocks * bq - Sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    width = window - 1 + bq
    # Gather absolute kv index for (block, offset); clip and mask out-of-range.
    blk_start = jnp.arange(n_blocks) * bq
    kv_idx = blk_start[:, None] - (window - 1) + jnp.arange(width)[None, :]
    valid = (kv_idx >= 0) & (kv_idx < Sk)
    kv_idx_c = jnp.clip(kv_idx, 0, Sk - 1)
    kg = jnp.take(k, kv_idx_c.reshape(-1), axis=1).reshape(B, n_blocks, width, Hkv, D)
    vg = jnp.take(v, kv_idx_c.reshape(-1), axis=1).reshape(B, n_blocks, width, Hkv, D)
    qb = q.reshape(B, n_blocks, bq, Hkv, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qb, kg.astype(jnp.float32))
    s = _softcap(s, softcap)
    qpos = blk_start[:, None] + jnp.arange(bq)[None, :]  # (n, bq) absolute
    kpos = kv_idx  # (n, width) absolute
    ok = (
        valid[:, None, :]
        & (kpos[:, None, :] <= qpos[:, :, None])
        & (kpos[:, None, :] > qpos[:, :, None] - window)
    )
    s = jnp.where(ok[None, :, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnhgqk,bnkhd->bnqhgd", p, vg.astype(jnp.float32))
    out = out.reshape(B, n_blocks * bq, Hq, D)[:, :Sq]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (one new token vs. a cache with explicit slot positions)
# ---------------------------------------------------------------------------


def decode_attention_ref(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos_ids: jax.Array,
    cur_pos: jax.Array,
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    return_stats: bool = False,
):
    """Single-step attention against a (possibly ring-buffer) KV cache.

    pos_ids[b, s] is the absolute position stored in cache slot s (-1 = empty),
    which uniformly handles full caches and SWA ring buffers.  cur_pos: (B,).

    ``return_stats``: return the flash-decoding partials ``(acc, m, l)`` with
    out = acc / l — the combinable form for split-KV (sequence-sharded caches).
    """
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qr = q.reshape(B, Hkv, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bshd->bhgs", qr, k_cache.astype(jnp.float32))
    s = _softcap(s, softcap)
    ok = (pos_ids >= 0) & (pos_ids <= cur_pos[:, None])
    if window is not None:
        ok &= pos_ids > cur_pos[:, None] - window
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)  # (B, Hkv, G)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    if return_stats:
        return acc, m, l
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Grouped expert matmul (MoE)
# ---------------------------------------------------------------------------


def gmm_ref(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Rows of group g times w[g], f32 accumulation; rows past the groups are 0.

    Every row against every group's weight, masked to its own: G times the
    work, for test-scale oracles only.
    """
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(x.shape[0])[:, None]
    member = ((row >= ends - group_sizes) & (row < ends)).astype(jnp.float32)  # (M, G)
    out = jnp.einsum("md,gdf,mg->mf", x.astype(jnp.float32), w.astype(jnp.float32), member)
    return out.astype(x.dtype)


def gmm_ragged(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """The production jnp path of the grouped matmul: XLA's ``ragged_dot``."""
    return jax.lax.ragged_dot(
        x, w, group_sizes.astype(jnp.int32), preferred_element_type=jnp.float32
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# RWKV6 (Finch) WKV scan
# ---------------------------------------------------------------------------


def rwkv6_scan_ref(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    state: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Naive per-step recurrence oracle.

      out_t = r_t · (S_t + diag(u) k_t v_tᵀ);   S_{t+1} = diag(w_t) S_t + k_t v_tᵀ

    r,k,v,w: (B,T,H,K); u: (H,K); state: (B,H,K,V).  Returns (out (B,T,H,V), state).
    """
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r, k, v, w))
    uf, sf = u.astype(jnp.float32), state.astype(jnp.float32)

    def step(s, xs):
        rt, kt, vt, wt = xs  # (B,H,K)
        kv = kt[..., :, None] * vt[..., None, :]  # (B,H,K,V)
        out = jnp.einsum("bhk,bhkv->bhv", rt, s + uf[None, :, :, None] * kv)
        s = wt[..., None] * s + kv
        return s, out

    xs = tuple(a.swapaxes(0, 1) for a in (rf, kf, vf, wf))  # (T,B,H,K)
    sf, out = jax.lax.scan(step, sf, xs)
    return out.swapaxes(0, 1).astype(r.dtype), sf.astype(state.dtype)


def rwkv6_scan_chunked(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    state: jax.Array,
    *,
    chunk: int = 32,
    remat_chunks: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Chunked matmul formulation (production path / Pallas blueprint).

    Within a chunk of L steps (log-space stable, pairwise decay tensor
    (L, L, K) stays in f32):

      out_t = r_t·(P_t ⊙ S₀) + Σ_{s<t} r_t·(D_{ts} ⊙ k_s) v_s + (r_t·(u ⊙ k_t)) v_t
      D_{ts} = exp(cum_t − cum_{s+1}) ≤ 1,   P_t = exp(cum_t),  cum = cumsum(log w)
    """
    B, T, H, K = r.shape
    L = min(chunk, T)
    assert T % L == 0, f"T={T} must be a multiple of chunk={L}"
    n = T // L
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    lw = jnp.log(jnp.clip(w.astype(jnp.float32), 1e-38, 1.0))
    uf, s0 = u.astype(jnp.float32), state.astype(jnp.float32)

    def chunk_body(s, xs):
        rc, kc, vc, lwc = xs  # each (B,L,H,K)
        cum = jnp.cumsum(lwc, axis=1)  # inclusive: cum_t = Σ_{i<=t} lw_i
        # Recurrence (matches the oracle): out_t reads S_t, then S_{t+1} = w_t S_t + k_t v_t.
        # kv_s's coefficient when read at t (s < t) is Π_{i=s+1}^{t-1} w_i
        #   = exp(cum_{t-1} - cum_s) = exp(cum_t - lw_t - cum_s)  ≤ 1.
        dmat = (cum - lwc)[:, :, None] - cum[:, None, :]  # (B,L,L,H,K): t=dim1, s=dim2
        tri = jnp.tril(jnp.ones((L, L), bool), k=-1)  # strict s < t
        dmat = jnp.where(tri[None, :, :, None, None], dmat, NEG_INF)
        att = jnp.einsum("bthk,btshk,bshk->bths", rc, jnp.exp(dmat), kc)
        diag = jnp.einsum("bthk,hk,bthk->bth", rc, uf, kc)  # u-bonus at s == t
        att = att + diag[..., None] * jnp.eye(L)[None, :, None, :]
        intra = jnp.einsum("bths,bshv->bthv", att, vc)
        # Prior-chunk state read at local t decays by Π_{i<t} w_i = exp(cum_{t-1}).
        dec = jnp.exp(cum - lwc)
        inter = jnp.einsum("bthk,bhkv->bthv", rc * dec, s)
        # Chunk-end state: S_L = exp(cum_{L-1}) ⊙ S₀ + Σ_s exp(cum_{L-1} - cum_s) k_s v_s.
        dend = jnp.exp(cum[:, -1:, :, :] - cum)  # (B,L,H,K)
        s = jnp.exp(cum[:, -1])[..., None] * s + jnp.einsum(
            "bshk,bshv->bhkv", kc * dend, vc
        )
        return s, intra + inter

    def reshape_c(a):
        return a.reshape(B, n, L, H, K).swapaxes(0, 1)

    xs = tuple(reshape_c(a) for a in (rf, kf, vf, lw))
    # remat_chunks (§Perf, mirrors the flash VJP): AD saves only (B, H, K, V)
    # chunk-boundary states, not the (L, L, K) pairwise tensors per chunk.
    body = (
        jax.checkpoint(chunk_body, policy=jax.checkpoint_policies.nothing_saveable)
        if remat_chunks else chunk_body
    )
    s, out = jax.lax.scan(body, s0, xs)
    out = out.swapaxes(0, 1).reshape(B, T, H, K)
    return out.astype(r.dtype), s.astype(state.dtype)


# ---------------------------------------------------------------------------
# Mamba selective scan
# ---------------------------------------------------------------------------


def mamba_scan_ref(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    C: jax.Array,
    D: jax.Array,
    state: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Naive selective-scan oracle.

      h_t = exp(dt_t ⊙ A) h_{t-1} + dt_t (B_t ⊗ x_t);  y_t = C_t·h_t + D ⊙ x_t

    x, dt: (B,T,DI); A: (DI,N); Bm, C: (B,T,N); D: (DI,); state: (B,DI,N).
    """
    xf, dtf, Bf, Cf = (a.astype(jnp.float32) for a in (x, dt, Bm, C))
    Af, Df, sf = A.astype(jnp.float32), D.astype(jnp.float32), state.astype(jnp.float32)

    def step(h, xs):
        xt, dtt, bt, ct = xs  # (B,DI) (B,DI) (B,N) (B,N)
        da = jnp.exp(dtt[..., None] * Af[None])  # (B,DI,N)
        h = da * h + (dtt * xt)[..., None] * bt[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, ct) + Df[None] * xt
        return h, y

    xs = tuple(a.swapaxes(0, 1) for a in (xf, dtf, Bf, Cf))
    sf, y = jax.lax.scan(step, sf, xs)
    return y.swapaxes(0, 1).astype(x.dtype), sf.astype(state.dtype)


def mamba_scan_chunked(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    C: jax.Array,
    D: jax.Array,
    state: jax.Array,
    *,
    chunk: int = 128,
    remat_chunks: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Chunked scan: lax.scan over chunks × associative_scan within a chunk.

    Live memory is O(B·L·DI·N) per chunk instead of O(B·T·DI·N).
    """
    B, T, DI = x.shape
    N = A.shape[1]
    L = min(chunk, T)
    assert T % L == 0, f"T={T} must be a multiple of chunk={L}"
    n = T // L
    xf, dtf, Bf, Cf = (a.astype(jnp.float32) for a in (x, dt, Bm, C))
    Af, Df, s0 = A.astype(jnp.float32), D.astype(jnp.float32), state.astype(jnp.float32)

    def chunk_body(h0, xs):
        xc, dtc, bc, cc = xs  # (B,L,DI) (B,L,DI) (B,L,N) (B,L,N)
        a = jnp.exp(dtc[..., None] * Af[None, None])  # (B,L,DI,N)
        b = (dtc * xc)[..., None] * bc[:, :, None, :]  # (B,L,DI,N)
        # prepend carry as step 0 with a=1
        a_full = jnp.concatenate([jnp.ones_like(a[:, :1]), a], axis=1)
        b_full = jnp.concatenate([h0[:, None], b], axis=1)

        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        _, h = jax.lax.associative_scan(combine, (a_full, b_full), axis=1)
        h = h[:, 1:]  # (B,L,DI,N)
        y = jnp.einsum("bldn,bln->bld", h, cc) + Df[None, None] * xc
        return h[:, -1], y

    def reshape_c(a):
        return a.reshape((B, n, L) + a.shape[2:]).swapaxes(0, 1)

    xs = tuple(reshape_c(a) for a in (xf, dtf, Bf, Cf))
    # remat_chunks (§Perf, the Mamba analogue of the flash VJP): AD saves only
    # the (B, DI, N) chunk-boundary states, not (B, L, DI, N) per-step stacks.
    body = (
        jax.checkpoint(chunk_body, policy=jax.checkpoint_policies.nothing_saveable)
        if remat_chunks else chunk_body
    )
    hT, y = jax.lax.scan(body, s0, xs)
    y = y.swapaxes(0, 1).reshape(B, T, DI)
    return y.astype(x.dtype), hT.astype(state.dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_ref(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))).astype(
        x.dtype
    )
