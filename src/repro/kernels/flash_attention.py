"""Flash attention Pallas TPU kernel (causal / sliding-window / GQA / softcap).

TPU-native design (not a CUDA port):
* Blocks are MXU-aligned (block_q × block_k = 128×128 by default, multiples of
  128 on the contracting dims) so the s = q·kᵀ and p·v matmuls map to the
  systolic array at full utilisation.
* The grid is (batch, q_heads, q_blocks, kv_blocks); on TPU the grid is
  executed sequentially with the last dim fastest, so the f32 running-softmax
  state (m, l, acc) lives in VMEM scratch and persists across the kv_block
  sweep — the HBM→VMEM pipeline streams one (block_k, head_dim) K/V tile per
  step while the previous tile is being consumed (double-buffered by Mosaic).
* Fully-masked tiles (above the causal diagonal, or outside the sliding
  window) skip their matmuls via pl.when — the same work-skipping a GPU kernel
  would get from early-exiting thread blocks.
* The kernel also writes each row's log-sum-exp, so the custom VJP can run
  the flash backward (kernels.flash_vjp) from (q, k, v, out, lse) without
  differentiating through the pallas_call, which Mosaic cannot do.
* The residuals out and lse carry the checkpoint names FLASH_OUT and
  FLASH_LSE, so a remat policy can keep them (models.lm._remat) and the
  backward never re-runs the kernel to rebuild them.

Validated against kernels.ref.mha_ref with interpret=True in
tests/test_kernels.py (CPU container; TPU is the lowering target).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import flash_vjp

NEG_INF = -1e30
# checkpoint names of the forward's residuals that only the kernel computes
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"


def _fa_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
    sq: int,
    sk: int,
    q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < sk  # kv padding
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window

    # Tile-level skip: first q row is the latest, last k col the earliest.
    any_live = jnp.any(mask)

    @pl.when(any_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask, s, NEG_INF)
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

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, ...] = (m_scr[...] + jnp.log(l))[None, :]


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "softcap",
        "scale",
        "block_q",
        "block_k",
        "q_offset",
        "interpret",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    q_offset: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D).

    Differentiable: the backward is the recompute of kernels.flash_vjp.
    """
    return _flash(q, k, v, causal, window, softcap, scale, block_q, block_k,
                  q_offset, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 11)))
def _flash(q, k, v, causal, window, softcap, scale, block_q, block_k, q_offset,
           interpret):
    out, _ = _flash_fwd(q, k, v, causal, window, softcap, scale, block_q,
                        block_k, q_offset, interpret)
    return out


def _flash_fwd(q, k, v, causal, window, softcap, scale, block_q, block_k,
               q_offset, interpret):
    """Runs the kernel; returns (out, lse (B, Hkv, G, Sq) f32)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    # head-major layout for clean 2D tiles
    qt = q.transpose(0, 2, 1, 3)  # (B, Hq, Sq, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    pad_q = -Sq % block_q
    pad_k = -Sk % block_k
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = qt.shape[2] // block_q
    n_k = kt.shape[2] // block_k

    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_k,
        sq=Sq,
        sk=Sk,
        q_offset=q_offset,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            # (1, block_q) rows of a unit dim: satisfies the (8, 128) rule
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, qi, ki: (b, h, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, n_q * block_q, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, n_q * block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    lse = lse[:, :, 0, :Sq].reshape(B, Hkv, G, Sq)
    return out[:, :, :Sq].transpose(0, 2, 1, 3), lse


def _flash_fwd_rule(q, k, v, causal, window, softcap, scale, block_q, block_k,
                    q_offset, interpret):
    out, lse = _flash_fwd(q, k, v, causal, window, softcap, scale, block_q,
                          block_k, q_offset, interpret)
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, softcap, scale, block_q, block_k, q_offset,
                    interpret, res, dout):
    return flash_vjp._bwd_rule(causal, window, softcap, scale, q_offset,
                               flash_vjp.BWD_BLOCK_K, res, dout)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)
