"""Dispatch layer: one public op per hot-spot, backend chosen by ``impl``.

``impl='auto'`` picks the Pallas kernel on real TPU and the pure-jnp
chunked/production path elsewhere (CPU container, and the multi-pod dry-run —
Pallas→Mosaic only lowers for TPU targets, while the chunked jnp paths lower
everywhere with equivalent FLOPs/bytes, keeping the roofline honest).

``impl='pallas'`` forces the kernel (with interpret=True off-TPU) — used by
the per-kernel allclose sweeps.  ``impl='ref'`` forces the naive oracle.
"""
from __future__ import annotations

import functools
import os
from contextlib import contextmanager
from typing import Any, Callable, Iterator, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.dispatch.profiles import encode_config
from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _fa_pallas
from repro.kernels.mamba_scan import mamba_scan as _mamba_pallas
from repro.kernels.moe_gmm import gmm as _gmm_pallas
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv6_pallas

# Global default, overridable for tests/benchmarks.
_IMPL = os.environ.get("REPRO_KERNEL_IMPL", "auto")


def set_default_impl(impl: str) -> None:
    global _IMPL
    assert impl in ("auto", "pallas", "ref", "chunked")
    _IMPL = impl


# ---------------------------------------------------------------------------
# Tuned kernel configs (repro.tune)
#
# ``_TUNED[op][impl]`` is a kwargs dict overriding that entry point's
# block/tile/chunk knobs.  The table is set by the tuner (sweep winners or a
# fleet-pulled cache) and takes precedence over hand-picked values — including
# ones callers pass explicitly, since replacing hand-picked configs with
# measured ones is the point.  Overrides apply at trace time, so they must be
# installed before jit compilation (the launch drivers tune before building
# the engine / train step).
# ---------------------------------------------------------------------------

_TUNED: dict[str, dict[str, dict[str, Any]]] = {}


def set_tuned_configs(table: Mapping[str, Mapping[str, Mapping[str, Any]]]) -> None:
    """Install tuned config overrides: ``{op: {impl: {param: value}}}``."""
    global _TUNED
    _TUNED = {
        op: {impl: dict(params) for impl, params in impls.items()}
        for op, impls in table.items()
    }


def clear_tuned_configs() -> None:
    global _TUNED
    _TUNED = {}


def tuned_overrides(op: str, impl: str) -> dict[str, Any]:
    return dict(_TUNED.get(op, {}).get(impl, {}))


def active_config(op: str, impl: str) -> str:
    """Canonical ``"k=v,..."`` encoding of the active overrides ("" = default)."""
    return encode_config(_TUNED.get(op, {}).get(impl, {}))


def config_tag(impl: str) -> str:
    """Cross-op summary of active overrides for one backend tier.

    Dispatch profile keys are per (op, backend); this folds every tuned op's
    config for ``impl`` into one stable tag (``"op:k=v;op2:k=v"``) so a
    coarse-grained dispatch target ("decode_step", "train_step") lands its
    samples in a bucket distinct from the untuned default.
    """
    parts = [
        f"{op}:{encode_config(impls[impl])}"
        for op, impls in sorted(_TUNED.items())
        if impls.get(impl)
    ]
    return ";".join(parts)


@contextmanager
def tuned_scope(
    table: Mapping[str, Mapping[str, Mapping[str, Any]]],
) -> Iterator[None]:
    """Temporarily install tuned overrides (sweep measurement, tests)."""
    global _TUNED
    prev = _TUNED
    set_tuned_configs(table)
    try:
        yield
    finally:
        _TUNED = prev


def _scan_chunk(op: str, impl: str, chunk: int, T: int) -> int:
    """Tuned chunk for a scan op, kept only when it divides the seq length.

    The chunked scans require ``T % min(chunk, T) == 0``; a winner swept on
    one workload shape must not crash another, so an indivisible override
    falls back to the caller's value.
    """
    tuned = _TUNED.get(op, {}).get(impl, {}).get("chunk")
    if tuned is not None and T % min(int(tuned), T) == 0:
        return int(tuned)
    return chunk


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: Optional[str]) -> str:
    impl = impl or _IMPL
    if impl == "auto":
        return "pallas" if _on_tpu() else "chunked"
    return impl


def _interp() -> bool:
    return not _on_tpu()


def _per_shard(kernel, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Run a Pallas attention kernel once per shard of the ambient mesh.

    XLA cannot partition a Mosaic kernel, so under a mesh of several devices
    the call goes through ``shard_map`` in the activation layout that
    :mod:`repro.distributed.sharding` gives (B, S, H, D) arrays.  Every mesh
    axis must carry a share: a batch or head count the mesh does not divide
    is an error, since replicating it would have each device compute all of
    it.  GQA groups stay whole because Hq and Hkv are cut into the same
    number of contiguous chunks.
    """
    from jax.interpreters import pxla

    from repro.distributed.sharding import ACT_RULES, spec_for

    mesh = pxla.thread_resources.env.physical_mesh
    if mesh.empty or mesh.size == 1:
        return kernel(q, k, v)
    spec = spec_for(q.shape, "batch,seq,heads,head_dim", ACT_RULES, mesh)
    kv_spec = spec_for(k.shape, "batch,seq,kv_heads,head_dim", ACT_RULES, mesh)
    used = {a for part in spec if part
            for a in ((part,) if isinstance(part, str) else part)}
    if spec != kv_spec or used != set(mesh.axis_names):
        raise ValueError(
            f"Pallas attention runs per shard: q {q.shape} and k {k.shape} "
            f"(B, S, H, D) must split over every axis of mesh "
            f"{dict(mesh.shape)}, got {spec} and {kv_spec}")
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    impl: Optional[str] = None,
) -> jax.Array:
    """Training/prefill attention.  Routes SWA to the O(S·window) local path."""
    impl = _resolve(impl)
    Sq, Sk = q.shape[1], k.shape[1]
    local_ok = (
        window is not None and causal and Sq == Sk and window * 2 < Sk and q_offset == 0
    )
    if impl == "pallas":
        kernel = functools.partial(
            _fa_pallas, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, interpret=_interp(),
            **tuned_overrides("flash_attention", "pallas"),
        )
        return _per_shard(kernel, q, k, v)
    if impl == "ref":
        return _ref.mha_ref(
            q, k, v, causal=causal, window=window, softcap=softcap, q_offset=q_offset
        )
    if local_ok:
        return _ref.local_window_attention(q, k, v, window=window, softcap=softcap)
    return _ref.flash_attention_chunked(
        q, k, v, causal=causal, window=window, softcap=softcap, q_offset=q_offset,
        **tuned_overrides("flash_attention", "chunked"),
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
    impl: Optional[str] = None,
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "pallas":
        return _decode_pallas(
            q, k_cache, v_cache, pos_ids, cur_pos,
            window=window, softcap=softcap, interpret=_interp(),
            **tuned_overrides("decode_attention", "pallas"),
        )
    return _ref.decode_attention_ref(
        q, k_cache, v_cache, pos_ids, cur_pos, window=window, softcap=softcap
    )


def decode_attention_seq_sharded(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos_ids: jax.Array,
    cur_pos: jax.Array,
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    seq_axes: tuple[str, ...] = ("model",),
    batch_axes: tuple[str, ...] = (),
) -> Optional[jax.Array]:
    """Split-KV decode over a sequence-sharded cache (flash-decoding combine).

    Left to sharding propagation, XLA may gather the seq-sharded K/V caches
    every decode step.  This shard_map computes rank-local partial softmax
    stats over each cache shard and combines (pmax/psum over ``seq_axes``)
    only the (B, H, D)-sized partials — the §Perf fix for collective-bound
    decode.  ``batch_axes``: mesh axes the batch dim is sharded over.

    Returns None when no ambient mesh / axes absent (caller falls back).
    """
    from jax.interpreters import pxla
    from jax.sharding import PartitionSpec as P

    mesh = pxla.thread_resources.env.physical_mesh
    if mesh.empty or any(a not in mesh.shape for a in seq_axes):
        return None
    b_ax = tuple(a for a in batch_axes if a in mesh.shape) or None
    seq_spec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    bspec = P(b_ax) if b_ax else P()

    def local(q_, k_, v_, pos_, cur_):
        acc, m, l = _ref.decode_attention_ref(
            q_, k_, v_, pos_, cur_, window=window, softcap=softcap,
            return_stats=True,
        )
        m_g = jax.lax.pmax(m, seq_axes)
        scale = jnp.exp(m - m_g)
        acc = jax.lax.psum(acc * scale[..., None], seq_axes)
        l_g = jax.lax.psum(l * scale, seq_axes)
        out = acc / jnp.maximum(l_g, 1e-30)[..., None]
        B, Hkv, G, D = out.shape
        return out.reshape(B, Hkv * G, D).astype(q.dtype)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(b_ax, None, None),            # q (B, Hq, D) replicated on seq axes
            P(b_ax, seq_spec, None, None),  # k cache: seq sharded
            P(b_ax, seq_spec, None, None),  # v cache
            P(b_ax, seq_spec),              # pos_ids
            bspec,                          # cur_pos
        ),
        out_specs=P(b_ax, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, pos_ids, cur_pos)


def gmm(
    x: jax.Array, w: jax.Array, group_sizes: jax.Array, *, impl: Optional[str] = None
) -> jax.Array:
    """Ragged grouped matmul: x (M, K) sorted by group, w (G, K, N) -> (M, N)."""
    impl = _resolve(impl)
    if impl == "pallas":
        return _gmm_pallas(x, w, group_sizes, interpret=_interp())
    if impl == "ref":
        return _ref.gmm_ref(x, w, group_sizes)
    return _ref.gmm_ragged(x, w, group_sizes)


def moe_ffn(
    x: jax.Array,
    w1: jax.Array,
    w3: jax.Array,
    w2: jax.Array,
    group_sizes: jax.Array,
    *,
    act: Callable[[jax.Array], jax.Array] = jax.nn.silu,
    impl: Optional[str] = None,
) -> jax.Array:
    """Per-expert gated FFN over rows sorted by expert: act(x@w1) * (x@w3) @ w2.

    Rows past ``sum(group_sizes)`` come out zero.
    """
    mm = functools.partial(gmm, group_sizes=group_sizes, impl=impl)
    h = act(mm(x, w1).astype(jnp.float32)) * mm(x, w3).astype(jnp.float32)
    return mm(h.astype(x.dtype), w2)


def rwkv6_scan(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    state: jax.Array,
    *,
    chunk: int = 32,
    remat_chunks: bool = False,
    impl: Optional[str] = None,
) -> tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    chunk = _scan_chunk("rwkv6_scan", impl, chunk, r.shape[1])
    if impl == "pallas":
        return _rwkv6_pallas(r, k, v, w, u, state, chunk=chunk, interpret=_interp())
    if impl == "ref":
        return _ref.rwkv6_scan_ref(r, k, v, w, u, state)
    return _ref.rwkv6_scan_chunked(
        r, k, v, w, u, state, chunk=chunk, remat_chunks=remat_chunks
    )


def rwkv6_step(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array, state: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Single decode step: r,k,v,w: (B,H,K); state: (B,H,K,V)."""
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r, k, v, w))
    sf = state.astype(jnp.float32)
    kv = kf[..., :, None] * vf[..., None, :]
    out = jnp.einsum("bhk,bhkv->bhv", rf, sf + u.astype(jnp.float32)[None, :, :, None] * kv)
    s_new = wf[..., None] * sf + kv
    return out.astype(r.dtype), s_new.astype(state.dtype)


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
    remat_chunks: bool = False,
    impl: Optional[str] = None,
) -> tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    chunk = _scan_chunk("mamba_scan", impl, chunk, x.shape[1])
    if impl == "pallas":
        return _mamba_pallas(x, dt, A, Bm, C, D, state, chunk=chunk, interpret=_interp())
    if impl == "ref":
        return _ref.mamba_scan_ref(x, dt, A, Bm, C, D, state)
    return _ref.mamba_scan_chunked(
        x, dt, A, Bm, C, D, state, chunk=chunk, remat_chunks=remat_chunks
    )


def mamba_step(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    C: jax.Array,
    D: jax.Array,
    state: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Single decode step: x, dt: (B,DI); Bm, C: (B,N); state: (B,DI,N)."""
    xf, dtf, bf, cf = (a.astype(jnp.float32) for a in (x, dt, Bm, C))
    Af, Df, hf = A.astype(jnp.float32), D.astype(jnp.float32), state.astype(jnp.float32)
    da = jnp.exp(dtf[..., None] * Af[None])
    h = da * hf + (dtf * xf)[..., None] * bf[:, None, :]
    y = jnp.einsum("bdn,bn->bd", h, cf) + Df[None] * xf
    return y.astype(x.dtype), h.astype(state.dtype)


def rmsnorm(
    x: jax.Array, scale: jax.Array, *, eps: float = 1e-6, impl: Optional[str] = None
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "pallas":
        return _rmsnorm_pallas(x, scale, eps=eps, interpret=_interp())
    return _ref.rmsnorm_ref(x, scale, eps=eps)
