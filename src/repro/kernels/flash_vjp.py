"""Flash attention with a custom VJP — the memory-roofline optimization.

Plain AD through the chunked-attention lax.scan stacks per-block softmax
residuals: the backward sees full (B, H, Sq, Sk) f32 tensors in HBM
(~64 GB/device/layer for the 4k-train cells — the №1 memory-term item found
by the dry-run analyzer).  The flash backward recomputes block scores from
(q, k, v, out, lse) instead: live memory O(Sq·block_k), HBM traffic O(S·D)
tiles rather than O(S²) residuals.

Matches kernels.ref.mha_ref forward AND backward (tests/test_kernels_vjp.py).
This is the TPU-production semantic of the flash_attention Pallas kernel,
whose custom VJP reuses ``_bwd_rule`` below; the jnp forward here is what
the dry-run lowers, keeping the compiled HLO representative.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30
# KV block of the recompute scans (also used by the Pallas forward's VJP)
BWD_BLOCK_K = 512


def _blocks(x: jax.Array, n: int, block: int, axis: int = 1):
    B = x.shape[0]
    shape = x.shape[:axis] + (n, block) + x.shape[axis + 1 :]
    return x.reshape(shape).swapaxes(0, axis)  # (n, B, block, ...)


def _mask(q_pos, k_pos, sk, causal, window):
    ok = k_pos[None, :] < sk
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return ok


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def flash_attention_fused(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_k: int = BWD_BLOCK_K,
) -> jax.Array:
    out, _ = _fwd_impl(q, k, v, causal, window, softcap, scale, q_offset, block_k)
    return out


def _fwd_impl(q, k, v, causal, window, softcap, scale, q_offset, block_k):
    """Online-softmax forward; returns (out, lse)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale_ = 1.0 / math.sqrt(D) if scale is None else scale
    bk = min(block_k, Sk)
    n = -(-Sk // bk)
    pad = n * bk - Sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    kb, vb = _blocks(kp, n, bk), _blocks(vp, n, bk)
    qr = (q.reshape(B, Sq, Hkv, G, D) * scale_).astype(jnp.float32)
    q_pos = jnp.arange(Sq) + q_offset

    def body(carry, xs):
        m, l, acc = carry
        kb_i, vb_i, start = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, kb_i.astype(jnp.float32))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        ok = _mask(q_pos, start + jnp.arange(bk), Sk, causal, window)
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vb_i.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    starts = jnp.arange(n) * bk
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, starts))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
    lse = m + jnp.log(l)  # (B, Hkv, G, Sq)
    return out.astype(q.dtype), lse


def _fwd_rule(q, k, v, causal, window, softcap, scale, q_offset, block_k):
    out, lse = _fwd_impl(q, k, v, causal, window, softcap, scale, q_offset, block_k)
    return out, (q, k, v, out, lse)


def _bwd_rule(causal, window, softcap, scale, q_offset, block_k, res, dout):
    q, k, v, out, lse = res
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale_ = 1.0 / math.sqrt(D) if scale is None else scale
    bk = min(block_k, Sk)
    n = -(-Sk // bk)
    pad = n * bk - Sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    kb, vb = _blocks(kp, n, bk), _blocks(vp, n, bk)
    qr = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    do = dout.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    of = out.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    # delta_i = Σ_d dout_i · out_i  (flash-backward rowsum term)
    delta = jnp.einsum("bqhgd,bqhgd->bhgq", do, of)
    q_pos = jnp.arange(Sq) + q_offset

    def body(dq_acc, xs):
        kb_i, vb_i, start = xs
        kf, vf = kb_i.astype(jnp.float32), vb_i.astype(jnp.float32)
        s_raw = jnp.einsum("bqhgd,bkhd->bhgqk", qr * scale_, kf)
        s = jnp.tanh(s_raw / softcap) * softcap if softcap else s_raw
        ok = _mask(q_pos, start + jnp.arange(bk), Sk, causal, window)
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # (B,Hkv,G,Sq,bk)
        dv_i = jnp.einsum("bhgqk,bqhgd->bkhd", p, do)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", do, vf)
        ds = p * (dp - delta[..., None])
        if softcap:
            ds = ds * (1.0 - jnp.square(s / softcap))
        ds = jnp.where(ok[None, None, None], ds, 0.0)
        dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kf) * scale_
        dk_i = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qr) * scale_
        return dq_acc, (dk_i, dv_i)

    starts = jnp.arange(n) * bk
    dq0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(body, dq0, (kb, vb, starts))
    dk = dk_b.swapaxes(0, 1).reshape(B, n * bk, Hkv, D)[:, :Sk]
    dv = dv_b.swapaxes(0, 1).reshape(B, n * bk, Hkv, D)[:, :Sk]
    return (
        dq.reshape(B, Sq, Hq, D).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


flash_attention_fused.defvjp(_fwd_rule, _bwd_rule)
