"""Ragged grouped expert matmul (GMM) for dropless MoE layers, on TPU Pallas.

    gmm(x (M, K), w (G, K, N), group_sizes (G,)) -> (M, N)

Rows are sorted by group: rows ``[off_g, off_g + group_sizes[g])`` are
multiplied by ``w[g]``, with f32 accumulation.  Rows past
``sum(group_sizes)`` (the pairs routed to experts held elsewhere, and
padding) come out zero, and so do their gradients.

The three products are megablox's Pallas kernels (``gmm`` and ``tgmm`` of
``jax.experimental.pallas.ops.tpu.megablox``): the grid walks only the row
tiles that hold a group (the tile-to-group map is scalar-prefetched), so
the work follows the routed rows and not a capacity.  Each product is
called from a jit of its own, which names its HLO op, and so its event in a
profile's XLA Ops line:

* ``moe_gmm_fwd``: ``x @ w[g]``;
* ``moe_gmm_dx``: ``dy @ w[g]^T``, the input gradient;
* ``moe_gmm_dw``: ``x_g^T @ dy_g`` for each group, the weight gradient.

Rows past the groups form one more group that has no weight: megablox then
computes only the weighted groups and zeroes every row outside them.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the package re-exports megablox's custom-VJP ``gmm`` under the module's name
_mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

ROW_TILE = 512


def _tile(dim: int) -> int:
    return 512 if dim % 512 == 0 else dim


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    return min(ROW_TILE, m), _tile(k), _tile(n)


def _sizes(group_sizes: jax.Array, m: int) -> jax.Array:
    """The groups, then one group of the rows past them."""
    group_sizes = group_sizes.astype(jnp.int32)
    return jnp.concatenate([group_sizes, (m - group_sizes.sum())[None]])


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm_fwd(x, w, sizes, *, interpret: bool = False):
    """x @ w[g] per group; rows outside the weighted groups are 0."""
    return _mb.gmm.__wrapped__(x, w, sizes, x.dtype,
                               _tiling(x.shape[0], x.shape[1], w.shape[2]),
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm_dx(dy, w, sizes, *, interpret: bool = False):
    """dy @ w[g]^T per group: the gradient of ``moe_gmm_fwd``'s input."""
    return _mb.gmm.__wrapped__(dy, w, sizes, dy.dtype,
                               _tiling(dy.shape[0], dy.shape[1], w.shape[1]),
                               transpose_rhs=True, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm_dw(x, dy, sizes, *, interpret: bool = False):
    """x_g^T @ dy_g per weighted group: the gradient of ``moe_gmm_fwd``'s weight."""
    groups = sizes.shape[0] - 1
    return _mb.tgmm.__wrapped__(x.swapaxes(0, 1), dy, sizes, x.dtype,
                                _tiling(x.shape[0], x.shape[1], dy.shape[1]),
                                num_actual_groups=groups, interpret=interpret)


def _padded(a: jax.Array, m: int) -> jax.Array:
    return jnp.pad(a, ((0, m - a.shape[0]), (0, 0))) if m != a.shape[0] else a


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(x, w, group_sizes, interpret):
    return _gmm_fwd(x, w, group_sizes, interpret)[0]


def _gmm_fwd(x, w, group_sizes, interpret):
    M = x.shape[0]
    m = -(-M // ROW_TILE) * ROW_TILE if M > ROW_TILE else M
    sizes = _sizes(group_sizes, m)
    xp = _padded(x, m)
    out = moe_gmm_fwd(xp, w, sizes, interpret=interpret)[:M]
    return out, (xp, w, sizes)


def _gmm_bwd(interpret, res, dy):
    xp, w, sizes = res
    M = dy.shape[0]
    dyp = _padded(dy.astype(xp.dtype), xp.shape[0])
    dx = moe_gmm_dx(dyp, w, sizes, interpret=interpret)[:M]
    dw = moe_gmm_dw(xp, dyp, sizes, interpret=interpret)
    return dx, dw.astype(w.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
        interpret: bool = False) -> jax.Array:
    """x: (M, K) sorted by group; w: (G, K, N); group_sizes: (G,) -> (M, N)."""
    return _gmm(x, w, group_sizes, interpret)
