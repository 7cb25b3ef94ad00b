"""Live device profiling: duty-cycled ``jax.profiler`` capture windows.

The post-hoc ``--device-trace`` merge (:mod:`repro.trace.device`) answers
the paper's device-side question once, after the run.  This module answers
it *while the run is alive*, the same way the adaptive controller keeps
host-span tracing affordable: capture runs in **windows** scheduled by a
second, device-specific budget loop
(:class:`repro.metrics.controller.DeviceCaptureBudget`).  Each cycle:

1. ``backend.start(window_dir)`` opens a profiler window
   (``jax.profiler.start_trace`` for the real backend);
2. after the planned on-time, ``backend.stop()`` closes it, the dump is
   parsed (:func:`~repro.trace.device.load_profiler_trace`) and aligned
   (:func:`~repro.trace.device.align_device_slices`) against the host
   events recorded so far — **in-process**, so span ids come from the live
   counter and annotated slices bind exactly;
3. the merged ``device`` events are re-recorded through the collector, so
   they ride the normal sink path into the live
   :class:`~repro.trace.stream.StreamingSession` and the metrics plane;
4. the whole window's machinery cost (start+stop+parse+align wall time) is
   fed to the budget loop, which widens/narrows the window-on fraction —
   and stretches the off time, because the per-window cost is largely
   fixed — to hold measured overhead under ``--trace-overhead-budget-pct``.

Alignment is exact wherever a slice carries its host span id, and falls
back to time-window containment elsewhere.  The dispatch path wraps device
work in ``jax.profiler.TraceAnnotation(f"span={sid}")`` (via
:func:`device_annotation`).  Every lifecycle span (the engine's ``prefill``
and ``decode_tick`` among them) is a ``repro.<name>`` annotation with a
``span`` stat (:mod:`repro.core.events`), which ``DeviceSlice.span_hint``
reads as an integer or as the text the trace viewer's JSON writes.

Degradation is graceful: a missing/failing profiler backend (or a CPU-only
jax whose dump holds raw xplane protos and no chrome trace) records **one**
warning event on the essential controller track and the run proceeds
untraced on the device side.  CI never needs real TPU/GPU hardware: the
:class:`SyntheticProfilerBackend` snoops the collector during a window and
writes a TensorBoard-shaped chrome-trace dump of its own, exercising every
byte of the window/parse/align/merge path.
"""
from __future__ import annotations

import contextlib
import gzip
import json
import os
import threading
import time
from typing import Any, Callable, Optional

from repro.core.events import next_span_id
from repro.metrics.controller import DEFAULT_BUDGET_PCT, DeviceCaptureBudget
from repro.trace.device import align_device_slices, load_profiler_trace

BACKENDS = ("auto", "jax", "synthetic")
DEFAULT_PERIOD_S = 2.0


class DeviceCaptureUnavailable(RuntimeError):
    """No usable profiler backend — the run proceeds without device capture."""


# -- span annotations ---------------------------------------------------------

# Annotation stamping is enabled only while a LiveDeviceProfiler is active:
# the dispatch/engine hot paths consult one module flag instead of threading
# a profiler handle everywhere.
_ANNOTATE = False
_ANNOTATION_CLS: Optional[Any] = None


def set_annotations(on: bool) -> None:
    global _ANNOTATE, _ANNOTATION_CLS
    if on and _ANNOTATION_CLS is None:
        try:
            import jax

            _ANNOTATION_CLS = jax.profiler.TraceAnnotation
        except Exception:
            _ANNOTATION_CLS = None
    _ANNOTATE = bool(on) and _ANNOTATION_CLS is not None


def annotations_enabled() -> bool:
    return _ANNOTATE


def device_annotation(span_id: int) -> Any:
    """Context manager naming the enclosed device work after its host span.

    Inside an active profiler window this wraps the region in
    ``jax.profiler.TraceAnnotation(f"span={span_id}")`` so every XLA slice
    launched under it carries the host span id; when no profiler is active
    (or ``span_id`` is 0) it is a free null context.
    """
    if not _ANNOTATE or not span_id or _ANNOTATION_CLS is None:
        return contextlib.nullcontext()
    return _ANNOTATION_CLS(f"span={span_id}")


# -- backends -----------------------------------------------------------------


class JaxProfilerBackend:
    """The real thing: ``jax.profiler.start_trace``/``stop_trace``.

    ``offset_s = None`` — the profiler dump runs on its own clock, so the
    aligner estimates the offset from trace starts.
    """

    name = "jax"
    offset_s: Optional[float] = None

    def __init__(self) -> None:
        try:
            import jax.profiler

            self._profiler = jax.profiler
        except Exception as exc:  # pragma: no cover - environment-dependent
            raise DeviceCaptureUnavailable(
                f"jax.profiler unavailable: {type(exc).__name__}: {exc}")
        if not hasattr(self._profiler, "start_trace"):
            raise DeviceCaptureUnavailable(
                "jax.profiler has no start_trace/stop_trace")

    def start(self, window_dir: str) -> None:
        self._profiler.start_trace(window_dir)

    def stop(self) -> None:
        self._profiler.stop_trace()


class SyntheticProfilerBackend:
    """Profiler stub for CI: snoops the collector, dumps a chrome trace.

    During a window it registers as a sampled sink on the collector and
    turns completed host lifecycles (``prefill``/``decode_tick`` by default)
    plus measured dispatch decisions into device slices on a pretend
    ``/device:SYNTH:0``.  ``stop()`` writes them as a gzipped TensorBoard
    layout (``plugins/profile/<run>/local.trace.json.gz``) — byte-compatible
    with what :func:`~repro.trace.device.load_profiler_trace` expects from a
    real dump — so the entire window/parse/align/merge path runs in CI with
    no accelerator.  Slices from spanned host events are named
    ``span=<sid> <op>`` (the TraceAnnotation analogue); span-less events
    produce unhinted slices, which is what the mixed alignment tests lean
    on.  Timestamps are host-monotonic, hence ``offset_s = 0``.
    """

    name = "synthetic"
    offset_s = 0.0
    device = "/device:SYNTH:0"

    def __init__(self, collector: Any,
                 op_names: tuple[str, ...] = ("prefill", "decode_tick",
                                              "step")) -> None:
        self.collector = collector
        self.op_names = frozenset(op_names)
        self._open: dict[tuple[str, int], float] = {}
        self._slices: list[tuple[str, int, float, float]] = []
        self._dir: Optional[str] = None
        self._lock = threading.Lock()

    def _on_event(self, e: Any) -> None:
        if e.kind == "spawn" and e.name in self.op_names:
            with self._lock:
                self._open[(e.name, e.span)] = e.t
        elif e.kind == "exit" and e.name in self.op_names:
            with self._lock:
                t0 = self._open.pop((e.name, e.span), None)
                if t0 is not None:
                    self._slices.append((e.name, e.span, t0, e.t))
        elif e.kind == "dispatch" and isinstance(e.payload, dict):
            dur = e.payload.get("measured_s")
            if isinstance(dur, (int, float)) and dur >= 0:
                op = str(e.payload.get("op") or e.name)
                with self._lock:
                    self._slices.append((op, e.span, e.t - dur, e.t))

    def start(self, window_dir: str) -> None:
        self._dir = window_dir
        with self._lock:
            self._open.clear()
            self._slices.clear()
        self.collector.add_sink(self._on_event, sampled=True)

    def stop(self) -> None:
        self.collector.remove_sink(self._on_event)
        assert self._dir is not None
        with self._lock:
            slices = list(self._slices)
        rows: list[dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": self.device}},
        ]
        for op, span, t0, t1 in slices:
            name = f"span={span} {op}" if span else op
            rows.append({
                "ph": "X", "pid": 1, "tid": 1, "name": name,
                "ts": t0 * 1e6, "dur": max(0.0, t1 - t0) * 1e6,
            })
        out = os.path.join(self._dir, "plugins", "profile", "synth")
        os.makedirs(out, exist_ok=True)
        with gzip.open(os.path.join(out, "local.trace.json.gz"), "wt") as f:
            json.dump({"traceEvents": rows}, f)


def make_backend(kind: str, collector: Any) -> Any:
    """Resolve a ``--jax-profile-backend`` value to a backend instance."""
    if kind == "synthetic":
        return SyntheticProfilerBackend(collector)
    if kind in ("jax", "auto"):
        return JaxProfilerBackend()
    raise DeviceCaptureUnavailable(
        f"unknown device-profiler backend {kind!r} (choose from {BACKENDS})")


# -- the live profiler --------------------------------------------------------


class LiveDeviceProfiler:
    """Duty-cycled device capture, merging each window into the live trace.

    Thread lifecycle mirrors the AdaptiveController: ``start()`` launches a
    daemon loop that alternates profiler-on windows and budget-stretched
    off gaps; ``stop()`` force-closes any open window (so even a run
    shorter than one period merges at least one window) and exports the
    end-state gauges.  ``open_window()``/``close_window()`` are public and
    deterministic so tests and benchmarks can drive cycles themselves.

    ``snapshot()`` doubles as the :class:`~repro.trace.stream
    .StreamingSession` ``device_provider``: every rotation records
    per-window coverage in the manifest.
    """

    def __init__(
        self,
        collector: Any,
        out_dir: str,
        *,
        budget: Optional[DeviceCaptureBudget] = None,
        registry: Optional[Any] = None,
        backend: str = "auto",
        budget_pct: float = DEFAULT_BUDGET_PCT,
        period_s: float = DEFAULT_PERIOD_S,
        id_alloc: Callable[[], int] = next_span_id,
    ) -> None:
        self.collector = collector
        self.out_dir = out_dir
        self.budget = budget if budget is not None else DeviceCaptureBudget(
            registry, budget_pct=budget_pct, period_s=period_s)
        self.backend_kind = backend
        self.backend: Optional[Any] = None
        self.degraded: Optional[str] = None
        self.windows: list[dict[str, Any]] = []
        self.merged_events = 0
        self.align_stats: dict[str, int] = {}
        self._id_alloc = id_alloc
        self._window_open = False
        self._window_dir: Optional[str] = None
        self._window_t0 = 0.0
        self._window_cost = 0.0
        self._started_t: Optional[float] = None
        self._last_cycle_t: Optional[float] = None
        self._lock = threading.RLock()
        self._stop_ev = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._g_coverage = self._g_quality = None
        if registry is not None:
            self._g_coverage = registry.gauge(
                "repro_device_capture_coverage",
                "fraction of run wall time covered by capture windows")
            self._g_quality = registry.gauge(
                "repro_device_alignment_annotated_fraction",
                "device slices bound by span= annotation / total merged")
        os.makedirs(out_dir, exist_ok=True)
        try:
            self.backend = make_backend(backend, collector)
        except DeviceCaptureUnavailable as exc:
            self._degrade(str(exc))

    # -- degradation ---------------------------------------------------------

    def _degrade(self, reason: str) -> None:
        """One warning event (essential controller track), then proceed."""
        if self.degraded is not None:
            return
        self.degraded = reason
        self.budget.capture_enabled = False
        self.budget.export()
        try:
            self.collector.record("mark", "device_window", {
                "warning": f"device capture disabled: {reason}",
                "backend": self.backend_kind,
            })
        except Exception:
            pass
        import sys

        print(f"live device profiling disabled: {reason}; run proceeds "
              "host-side only", file=sys.stderr)

    # -- window mechanics ----------------------------------------------------

    def open_window(self) -> bool:
        """Start one capture window; False if degraded/already open."""
        with self._lock:
            if self.degraded or self._window_open or self.backend is None:
                return False
            wdir = os.path.join(self.out_dir, f"window-{len(self.windows):04d}")
            os.makedirs(wdir, exist_ok=True)
            t0 = time.perf_counter()
            try:
                self.backend.start(wdir)
            except Exception as exc:
                self._degrade(f"{type(exc).__name__}: {exc}")
                return False
            self._window_cost = time.perf_counter() - t0
            self._window_dir = wdir
            self._window_t0 = time.monotonic()
            self._window_open = True
            if self._started_t is None:
                self._started_t = self._window_t0
            return True

    def close_window(self) -> int:
        """Stop the open window, parse + align + merge its dump live.

        Returns the number of device events merged into the collector (and,
        through its sink, the streaming session).  The full machinery cost
        is wall-clocked and fed to the budget loop.
        """
        with self._lock:
            if not self._window_open:
                return 0
            self._window_open = False
            wdir = self._window_dir
            t0 = time.perf_counter()
            merged = 0
            stats: dict[str, int] = {}
            try:
                self.backend.stop()
                slices = load_profiler_trace(wdir)
                evs = align_device_slices(
                    self.collector.events(), slices,
                    offset_s=getattr(self.backend, "offset_s", None),
                    id_alloc=self._id_alloc, stats=stats,
                )
                for ev in evs:
                    self.collector.record("device", ev.name, ev.payload,
                                          span=ev.span, parent=ev.parent,
                                          t=ev.t)
                merged = len(evs)
            except Exception as exc:
                self._degrade(f"{type(exc).__name__}: {exc}")
            self._window_cost += time.perf_counter() - t0
            now = time.monotonic()
            win = {
                "dir": os.path.basename(wdir or ""),
                "t0": round(self._window_t0, 6),
                "t1": round(now, 6),
                "on_s": round(now - self._window_t0, 6),
                "cost_s": round(self._window_cost, 6),
                "events": merged,
                "align": stats,
            }
            self.windows.append(win)
            self.merged_events += merged
            for k, v in stats.items():
                self.align_stats[k] = self.align_stats.get(k, 0) + v
            ref = self._last_cycle_t if self._last_cycle_t is not None \
                else self._started_t
            elapsed = max(now - (ref or now), win["on_s"], 1e-9)
            self._last_cycle_t = now
            overhead = self.budget.observe(self._window_cost, elapsed)
            if self.degraded is None:
                self.collector.record("mark", "device_window", {
                    **win, "overhead_pct": round(overhead, 4),
                })
            self._export_gauges(now)
            return merged

    def _export_gauges(self, now: float) -> None:
        if self._g_coverage is not None and self._started_t is not None:
            run_s = max(now - self._started_t, 1e-9)
            cov = min(1.0, sum(w["on_s"] for w in self.windows) / run_s)
            self._g_coverage.set(round(cov, 4))
        if self._g_quality is not None:
            total = self.align_stats.get("total", 0)
            if total:
                self._g_quality.set(
                    round(self.align_stats.get("span", 0) / total, 4))

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "LiveDeviceProfiler":
        if self._thread is not None or self.degraded is not None:
            set_annotations(self.degraded is None)
            return self
        set_annotations(True)
        self._started_t = time.monotonic()
        self.collector.record("mark", "device_window", {
            "phase": "start",
            "backend": getattr(self.backend, "name", self.backend_kind),
            "budget_pct": self.budget.budget_pct,
            "period_s": self.budget.period_s,
            "out_dir": self.out_dir,
        })
        self._stop_ev.clear()
        self._thread = threading.Thread(
            target=self._run, name="repro-device-capture", daemon=True)
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop_ev.is_set():
            try:
                on_s, off_s = self.budget.plan()
                if on_s > 0 and self.degraded is None:
                    if self.open_window():
                        if self._stop_ev.wait(on_s):
                            break  # stop() force-closes the window
                        self.close_window()
                if self.degraded is not None:
                    return  # measure-only: nothing left to schedule
                if self._stop_ev.wait(max(off_s, 0.01)):
                    break
            except Exception:  # the capture loop must never kill the run
                return

    def stop(self) -> None:
        self._stop_ev.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        if self._window_open:
            self.close_window()  # short runs still merge their one window
        set_annotations(False)
        self._export_gauges(time.monotonic())
        self.budget.export()

    # -- reporting -----------------------------------------------------------

    def snapshot(self) -> dict[str, Any]:
        """Coverage + alignment summary; the stream's ``device_provider``."""
        with self._lock:
            now = time.monotonic()
            run_s = (now - self._started_t) if self._started_t else 0.0
            on_s = sum(w["on_s"] for w in self.windows)
            total = self.align_stats.get("total", 0)
            return {
                "backend": getattr(self.backend, "name", self.backend_kind),
                "out_dir": self.out_dir,
                "degraded": self.degraded,
                "windows": len(self.windows),
                "merged_events": self.merged_events,
                "align": {
                    **self.align_stats,
                    "annotated_fraction": (
                        self.align_stats.get("span", 0) / total if total
                        else 0.0),
                },
                "coverage": {
                    "captured_s": round(on_s, 6),
                    "run_s": round(run_s, 6),
                    "fraction": round(min(1.0, on_s / run_s), 4)
                    if run_s > 0 else 0.0,
                },
                "budget": self.budget.snapshot(),
                "window_log": self.windows[-64:],
            }
