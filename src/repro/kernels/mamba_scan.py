"""Mamba-1 selective-scan chunked Pallas TPU kernel.

Mamba-1's decay exp(Δ_t ⊙ A) is (d_inner, N)-shaped per step, so the Mamba-2
matmul re-blocking does not apply; the honest TPU mapping is a VPU kernel
that keeps the recurrent state resident in VMEM:

* The grid is (B, DI/bdi, T/L): chunks innermost, so the (bdi, N) f32 state
  persists in VMEM scratch for the whole sequence sweep of one channel block.
* Each grid step streams an (L, bdi) x/Δ tile and an (N, L) B/C tile
  HBM→VMEM, then runs the L recurrence steps on the VPU with zero HBM
  traffic for the state — the selective scan is memory-bound, and this
  tiling reads x/Δ/B/C exactly once (roofline-optimal bytes).
* Channels sit on the 128 lanes: the state is (N, bdi), so each step's x/Δ
  row broadcasts over sublanes and its B/C column over lanes.  On the chip
  the chunk L must be a multiple of 128 (or the whole sequence).
* Channel blocks (bdi = 512 default) keep state at 512×16×4 B = 32 KB,
  leaving VMEM room for double-buffered input tiles.

Validated against kernels.ref.mamba_scan_ref with interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(
    x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s0_ref, y_ref, sT_ref,
    s_scr, x_scr, dt_scr, y_scr, *, L, n_chunks
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    # f32 copies of the chunk's x/Δ rows: the step loop reads one row each
    x_scr[...] = x_ref[0].astype(jnp.float32)  # (L, bdi)
    dt_scr[...] = dt_ref[0].astype(jnp.float32)
    bm = b_ref[0].astype(jnp.float32)  # (N, L)
    cm = c_ref[0].astype(jnp.float32)  # (N, L)
    A = a_ref[...].astype(jnp.float32)  # (N, bdi)
    D = d_ref[...].astype(jnp.float32)  # (1, bdi)
    lane = jax.lax.broadcasted_iota(jnp.int32, bm.shape, 1)

    def step(t, h):  # h: (N, bdi)
        xt = x_scr[pl.ds(t, 1), :]  # (1, bdi)
        dtt = dt_scr[pl.ds(t, 1), :]
        bt = jnp.sum(jnp.where(lane == t, bm, 0.0), axis=1, keepdims=True)  # (N, 1)
        ct = jnp.sum(jnp.where(lane == t, cm, 0.0), axis=1, keepdims=True)
        h = jnp.exp(dtt * A) * h + (dtt * xt) * bt
        y_scr[pl.ds(t, 1), :] = jnp.sum(h * ct, axis=0, keepdims=True) + D * xt
        return h

    h = jax.lax.fori_loop(0, L, step, s_scr[...])
    s_scr[...] = h
    y_ref[0, ...] = y_scr[...].astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _finish():
        sT_ref[0, ...] = h.astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_di", "interpret"))
def mamba_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    C: jax.Array,
    D: jax.Array,
    state: jax.Array,
    *,
    chunk: int = 128,
    block_di: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """x, dt: (B,T,DI); A: (DI,N); Bm, C: (B,T,N); D: (DI,); state: (B,DI,N)."""
    B, T, DI = x.shape
    N = A.shape[1]
    L = min(chunk, T)
    assert T % L == 0, f"T={T} must be a multiple of chunk={L}"
    n_chunks = T // L
    bdi = min(block_di, DI)
    assert DI % bdi == 0, f"DI={DI} must be a multiple of block_di={bdi}"
    n_di = DI // bdi

    # channels on lanes throughout: the state is held as (N, DI), and B/C as
    # (N, T) so each step's (N, 1) column is a lane select, not a transpose
    kernel = functools.partial(_mamba_kernel, L=L, n_chunks=n_chunks)
    y, sT = pl.pallas_call(
        kernel,
        grid=(B, n_di, n_chunks),
        in_specs=[
            pl.BlockSpec((1, L, bdi), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, L, bdi), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, N, L), lambda b, di, ci: (b, 0, ci)),
            pl.BlockSpec((1, N, L), lambda b, di, ci: (b, 0, ci)),
            pl.BlockSpec((N, bdi), lambda b, di, ci: (0, di)),
            pl.BlockSpec((1, bdi), lambda b, di, ci: (0, di)),
            pl.BlockSpec((1, N, bdi), lambda b, di, ci: (b, 0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, bdi), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, N, bdi), lambda b, di, ci: (b, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, DI), x.dtype),
            jax.ShapeDtypeStruct((B, N, DI), state.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, bdi), jnp.float32),
            pltpu.VMEM((L, bdi), jnp.float32),
            pltpu.VMEM((L, bdi), jnp.float32),
            pltpu.VMEM((L, bdi), jnp.float32),
        ],
        interpret=interpret,
    )(
        x, dt, Bm.transpose(0, 2, 1), C.transpose(0, 2, 1), A.T,
        D.reshape(1, DI), state.transpose(0, 2, 1),
    )
    return y, sT.transpose(0, 2, 1)
