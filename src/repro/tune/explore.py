"""The Explorer: parallel design-space sweeps feeding the ProfileStore.

A sweep is the lumos-style allocation-grid enumeration from the ROADMAP:
enumerate candidate config points per kernel (:mod:`repro.tune.space`), cut
the obviously-bad ones with the roofline model (:mod:`repro.tune.prune`),
then time the survivors across a multiprocessing worker pool with per-point
warmup/repeat control.  Every measurement lands in the
:class:`~repro.dispatch.profiles.ProfileStore` as an ordinary sample under
the point's ``(op, backend, sig, config)`` key — so a driver-attached
:class:`~repro.fleet.client.FleetPusher` delta-pushes tuned winners with no
tuner-specific fleet plumbing, and a later run's fleet pull makes every
already-measured point *warm*, which the Explorer skips (``--tune sweep``
on a warm-started run reports ``sweep_points == 0``).

Sweep modes:

* ``real``       time actual kernel executions; Pallas spaces only on TPU;
* ``interpret``  same, but Pallas spaces run under ``interpret=True``
                 off-TPU (functional sweep of the full space on CPU);
* ``synthetic``  deterministic analytic pseudo-measurements, no jax import —
                 CI smoke and the determinism tests.

The whole sweep is one ``tune_run`` lifecycle span; each pruned or measured
point is a ``tune`` event under it, and each per-space winner a ``tune``
event with ``winner: true`` — the metrics sink derives
``repro_tune_points_total{op,pruned}`` and ``repro_tune_best_speedup{op}``
from exactly these.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Mapping, Optional

from repro.core.events import GLOBAL_LOG, EventLog
from repro.dispatch.profiles import ProfileStore, decode_config, encode_config
from repro.hw.specs import ChipSpec, host_chip, stamp_chip, tpu_host
from repro.tune.prune import DEFAULT_PRUNE_RATIO, RooflinePruner
from repro.tune.space import KernelSpace, default_spaces

MODES = ("real", "interpret", "synthetic")


@dataclasses.dataclass(frozen=True)
class SweepSettings:
    mode: str = "interpret"
    warmup: int = 1
    repeats: int = 3
    workers: int = 0  # 0 = in-process (deterministic single stream)
    prune_ratio: float = DEFAULT_PRUNE_RATIO

    def __post_init__(self) -> None:
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")


# ---------------------------------------------------------------------------
# Measurement (runs in-process or inside spawn workers)
# ---------------------------------------------------------------------------


def _arr(shape: tuple[int, ...], seed: int):
    """Deterministic float32 inputs in (-0.5, 0.5) without an RNG dependency."""
    import jax.numpy as jnp

    n = math.prod(shape)
    x = (jnp.arange(n, dtype=jnp.float32) * 0.6180339887 + seed * 0.37) % 1.0
    return (x - 0.5).reshape(shape)


def _run_flash(space: KernelSpace, impl: str) -> Callable[[], Any]:
    import jax

    from repro.kernels import ops

    w = space.workload
    shape = (w["B"], w["S"], w["H"], w["D"])
    q, k, v = _arr(shape, 1), _arr(shape, 2), _arr(shape, 3)
    # fresh closure per point (each config must trace — and so read the tuned
    # table — on its own jit cache entry); inputs passed as arguments, not
    # captured constants, or XLA constant-folds the whole workload away
    fn = jax.jit(lambda a, b, c: ops.attention(a, b, c, causal=True, impl=impl))
    return lambda: fn(q, k, v)


def _run_decode(space: KernelSpace, impl: str) -> Callable[[], Any]:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    w = space.workload
    B, S, H, D = w["B"], w["S"], w["H"], w["D"]
    q = _arr((B, H, D), 1)
    k_cache, v_cache = _arr((B, S, H, D), 2), _arr((B, S, H, D), 3)
    pos_ids = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cur_pos = jnp.full((B,), S, dtype=jnp.int32)
    fn = jax.jit(
        lambda *a: ops.decode_attention(*a, impl=impl)
    )
    return lambda: fn(q, k_cache, v_cache, pos_ids, cur_pos)


def _run_rwkv6(space: KernelSpace, impl: str) -> Callable[[], Any]:
    import jax

    from repro.kernels import ops

    wl = space.workload
    B, T, H, K, V = wl["B"], wl["T"], wl["H"], wl["K"], wl["V"]
    r, k, v = _arr((B, T, H, K), 1), _arr((B, T, H, K), 2), _arr((B, T, H, K), 3)
    w = 0.5 + 0.45 * _arr((B, T, H, K), 4)  # decay factors in (0.275, 0.725)
    u = _arr((H, K), 5)
    state = _arr((B, H, K, V), 6)
    fn = jax.jit(lambda *a: ops.rwkv6_scan(*a, impl=impl))
    return lambda: fn(r, k, v, w, u, state)


def _run_mamba(space: KernelSpace, impl: str) -> Callable[[], Any]:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    wl = space.workload
    B, T, DI, N = wl["B"], wl["T"], wl["DI"], wl["N"]
    x = _arr((B, T, DI), 1)
    dt = 0.01 + 0.1 * jnp.abs(_arr((B, T, DI), 2))
    A = -0.1 - jnp.abs(_arr((DI, N), 3))
    Bm, C = _arr((B, T, N), 4), _arr((B, T, N), 5)
    D = _arr((DI,), 6)
    state = _arr((B, DI, N), 7)
    fn = jax.jit(lambda *a: ops.mamba_scan(*a, impl=impl))
    return lambda: fn(x, dt, A, Bm, C, D, state)


_RUNNERS: dict[str, Callable[[KernelSpace, str], Callable[[], Any]]] = {
    "flash_attention": _run_flash,
    "decode_attention": _run_decode,
    "rwkv6_scan": _run_rwkv6,
    "mamba_scan": _run_mamba,
}


def _measure(space: KernelSpace, params: Mapping[str, int], mode: str,
             warmup: int, repeats: int) -> list[float]:
    """Per-rep wall-times of one config point (synthetic: analytic, exact)."""
    if mode == "synthetic":
        return [space.synthetic_s(params)] * max(repeats, 1)
    import jax

    from repro.kernels import ops

    # the override table must be live while jit TRACES the thunk (first call),
    # so the whole warmup+timing loop runs inside the scope
    with ops.tuned_scope({space.op: {space.impl: dict(params)}}):
        thunk = _RUNNERS[space.op](space, space.impl)
        for _ in range(max(warmup, 0)):
            jax.block_until_ready(thunk())
        out: list[float] = []
        for _ in range(max(repeats, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(thunk())
            out.append(time.perf_counter() - t0)
    return out


def _worker_measure(task: tuple) -> tuple[str, str, list[float]]:
    """Pool entry point (module-level: spawn workers pickle by reference)."""
    space_key, params, mode, warmup, repeats = task
    space = default_spaces()[space_key]
    return space_key, encode_config(params), _measure(space, params, mode, warmup, repeats)


# ---------------------------------------------------------------------------
# Explorer
# ---------------------------------------------------------------------------


class Explorer:
    """Sweep design spaces, feed the store, report winners."""

    def __init__(
        self,
        store: ProfileStore,
        *,
        chip: Optional[ChipSpec] = None,
        spaces: Optional[dict[str, KernelSpace]] = None,
        log: Optional[EventLog] = None,
        settings: Optional[SweepSettings] = None,
    ) -> None:
        self.store = store
        self.settings = settings or SweepSettings()
        # a synthetic sweep measures on no device, so it takes none
        self.chip = chip or (stamp_chip() if self.settings.mode == "synthetic"
                             else host_chip())
        self.spaces = spaces if spaces is not None else default_spaces()
        self.log = GLOBAL_LOG if log is None else log
        # sweep samples carry the same provenance stamps dispatcher samples
        # do, so age_out treats tuned points identically
        from repro.trace.session import git_sha

        self.store.set_stamp(git_sha=git_sha(), chip=self.chip.name)

    def _selected(self, ops_filter: Optional[list[str]]) -> list[KernelSpace]:
        spaces = [
            s for s in self.spaces.values()
            if ops_filter is None or s.op in ops_filter
        ]
        if self.settings.mode == "real":
            # off-TPU, Pallas only lowers under interpret=True; a "real"
            # sweep must not publish interpret timings as pallas winners
            import jax

            if jax.default_backend() != "tpu":
                spaces = [s for s in spaces if s.backend != "pallas"]
        return spaces

    def sweep(self, ops_filter: Optional[list[str]] = None) -> dict[str, Any]:
        st = self.settings
        # a point is only usable by the dispatcher once warm; never measure
        # fewer reps than the warmth threshold
        repeats = max(st.repeats, self.store.min_samples)
        spaces = self._selected(ops_filter)
        pruner = RooflinePruner(self.chip, st.prune_ratio)

        summary: dict[str, Any] = {
            "mode": st.mode, "workers": st.workers, "prune_ratio": st.prune_ratio,
            "spaces": len(spaces), "points_total": 0, "pruned": 0,
            "skipped_warm": 0, "sweep_points": 0, "winners": {},
        }
        tasks: list[tuple] = []
        by_key = {s.key: s for s in spaces}
        with self.log.lifecycle("tune_run", {
            "mode": st.mode, "spaces": sorted(by_key), "workers": st.workers,
        }):
            for space in spaces:
                points = space.points(self.chip)
                kept, cut = pruner.prune(space, points)
                summary["points_total"] += len(points)
                summary["pruned"] += len(cut)
                for c in cut:
                    self.log.record("tune", space.op, {
                        "op": space.op, "backend": space.backend,
                        "sig": space.sig, "config": c.point.config,
                        "pruned": True, "predicted_s": c.predicted_s,
                        "bound_s": c.bound_s,
                    })
                for p in kept:
                    if self.store.warm(space.op, space.backend, space.sig, p.config):
                        summary["skipped_warm"] += 1
                    else:
                        tasks.append((space.key, dict(p.params), st.mode,
                                      st.warmup, repeats))
            summary["sweep_points"] = len(tasks)

            if st.workers > 0 and len(tasks) > 1:
                import multiprocessing

                if tpu_host():
                    # spawned workers would each need the chip this process
                    # may already hold: one process per chip
                    raise RuntimeError(
                        "tune --workers > 0 cannot run on a TPU host (one "
                        "process per chip); use --workers 0")

                ctx = multiprocessing.get_context("spawn")
                with ctx.Pool(min(st.workers, len(tasks))) as pool:
                    results = pool.map(_worker_measure, tasks)
            else:
                results = [_worker_measure(t) for t in tasks]

            # record in sorted (space, config) order: the store's content must
            # not depend on worker scheduling
            for space_key, config, reps in sorted(results, key=lambda r: (r[0], r[1])):
                space = by_key[space_key]
                for s in reps:
                    self.store.record(space.op, space.backend, space.sig, s,
                                      config=config)
                self.log.record("tune", space.op, {
                    "op": space.op, "backend": space.backend, "sig": space.sig,
                    "config": config, "pruned": False, "reps": len(reps),
                    "min_s": min(reps),
                })

            for space in spaces:
                win = self._winner(space)
                if win is not None:
                    summary["winners"][space.key] = win
                    self.log.record("tune", space.op, {"winner": True, **win})
        return summary

    def _winner(self, space: KernelSpace) -> Optional[dict[str, Any]]:
        best = self.store.best_config(space.op, space.backend, space.sig)
        if best is None:
            return None
        config, best_s = best
        default_e = self.store.entry(space.op, space.backend, space.sig,
                                     space.default_config)
        default_s = default_e.min_s if default_e and default_e.count else None
        win: dict[str, Any] = {
            "op": space.op, "backend": space.backend, "sig": space.sig,
            "config": config, "best_s": best_s,
        }
        if default_s is not None:
            win["default_s"] = default_s
            # >= 1.0 by construction: the default point is always enumerated,
            # never pruned, and competes in the same argmin
            win["speedup"] = default_s / best_s if best_s > 0 else 1.0
        return win


# ---------------------------------------------------------------------------
# Winner application (the consumer side)
# ---------------------------------------------------------------------------


def winners_from_store(
    store: ProfileStore, spaces: Optional[dict[str, KernelSpace]] = None
) -> tuple[dict[str, dict[str, dict[str, Any]]], dict[str, dict[str, Any]]]:
    """Argmin config per space from whatever the store holds (this run's
    sweep, a ``--profile-in`` file, or a fleet pull).

    Returns ``(table, details)``: ``table`` is the ``kernels.ops`` override
    table ``{op: {impl: params}}`` (empty-config winners — the hand-picked
    default won — contribute nothing), ``details`` records per-space
    provenance for driver JSON.
    """
    spaces = spaces if spaces is not None else default_spaces()
    table: dict[str, dict[str, dict[str, Any]]] = {}
    details: dict[str, dict[str, Any]] = {}
    for space in spaces.values():
        best = store.best_config(space.op, space.backend, space.sig)
        if best is None:
            continue
        config, best_s = best
        details[space.key] = {"config": config, "best_s": best_s}
        if not config:
            continue  # legacy/default point won: nothing to override
        table.setdefault(space.op, {})[space.impl] = decode_config(config)
    return table, details


def apply_winners(table: Mapping[str, Mapping[str, Mapping[str, Any]]]) -> int:
    """Install winners into ``kernels.ops`` (call before jit tracing).

    Returns the number of (op, impl) overrides applied.  Imports ops lazily:
    jax-free callers (CLI summaries) can compute winners without applying.
    """
    from repro.kernels import ops

    ops.set_tuned_configs(table)
    return sum(len(impls) for impls in table.values())


def driver_tune(
    policy: str,
    dispatcher: Any,
    log: EventLog,
    *,
    ops_filter: Optional[list[str]] = None,
    mode: str = "interpret",
    workers: int = 0,
    warmup: int = 1,
    repeats: int = 3,
    prune_ratio: float = DEFAULT_PRUNE_RATIO,
) -> dict[str, Any]:
    """The ``--tune {cached,sweep}`` wiring shared by both launch drivers.

    Call after the fleet warm-start (pulled config points make sweep points
    warm — a fed fleet means ``sweep_points == 0``) and before the engine /
    train-step variants are built (winners must be installed before jit
    traces them).  ``cached`` only applies winners already in the store;
    ``sweep`` measures what's missing first.  Sweep samples land in the
    dispatcher's own store, so the driver's FleetPusher delta-pushes tuned
    winners with no extra plumbing.
    """
    rec: dict[str, Any] = {"mode": policy, "sweep_points": 0, "pruned": 0}
    if policy == "sweep":
        explorer = Explorer(
            dispatcher.store, chip=dispatcher.chip, log=log,
            settings=SweepSettings(mode=mode, warmup=warmup, repeats=repeats,
                                   workers=workers, prune_ratio=prune_ratio),
        )
        summary = explorer.sweep(ops_filter)
        rec["sweep_points"] = summary["sweep_points"]
        rec["pruned"] = summary["pruned"]
        rec["skipped_warm"] = summary["skipped_warm"]
        rec["winners"] = summary["winners"]
    table, _ = winners_from_store(dispatcher.store)
    rec["applied"] = apply_winners(table)
    rec["configs"] = {
        op: {impl: encode_config(params) for impl, params in impls.items()}
        for op, impls in table.items()
    }
    return rec
