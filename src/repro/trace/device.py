"""Device-timeline adapter: fold ``jax.profiler`` traces under host spans.

The collector only sees *host* lifecycle events — a request span covers the
wall time of its prefill, but says nothing about what the accelerator ran
inside it.  ``jax.profiler.trace(dir)`` captures exactly that missing half:
its TensorBoard dump contains a Chrome-format trace (``*.trace.json.gz``
under ``plugins/profile/<run>/``) whose per-device processes list every XLA
op executed.  This module parses that dump and merges the device slices into
a :class:`~repro.trace.session.Session` as ``device``-kind events **parented
to the host span that was open when they ran**, so ``report --tree`` shows
accelerator time nested under the request/step that caused it and the
Perfetto export renders host tracks above per-device tracks.

Alignment is two-level:

* **explicit span hints** — a slice whose name or args carry ``span=<id>``
  (e.g. from ``jax.profiler.TraceAnnotation(f"span={sid}")`` around the
  dispatched call) binds to that span directly;
* **time-window containment** — otherwise the slice's midpoint (after
  shifting by ``offset_s``; estimated by aligning trace starts when not
  given — profiler clocks and our monotonic clock share no epoch) picks the
  innermost host span whose window contains it.  Slices matching no span
  become device-track roots rather than being dropped.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Any, Iterable, Optional

from repro.core.events import Event
from repro.trace.collector import resolve_spans

DEVICE_KIND = "device"

# process names jax/XLA give device rows in its chrome dump ("/device:TPU:0",
# "GPU:0 Stream #12", "TPU:0 XLA Ops", ...)
_DEVICE_PID_RE = re.compile(r"device|tpu|gpu|xla|stream", re.IGNORECASE)
_SPAN_HINT_RE = re.compile(r"\bspan[=:](\d+)\b")


@dataclasses.dataclass(frozen=True)
class DeviceSlice:
    """One complete event from the profiler dump, in its own clock (seconds)."""

    name: str
    t0: float
    t1: float
    device: str
    args: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    @property
    def span_hint(self) -> int:
        """Host span id embedded by a TraceAnnotation, 0 when absent."""
        v = self.args.get("span")
        if isinstance(v, int) and v > 0:
            return v
        if isinstance(v, str) and v.isdigit() and int(v) > 0:
            return int(v)  # trace-viewer JSON writes stats as text
        for text in (str(v) if v is not None else "", self.name):
            m = _SPAN_HINT_RE.search(text)
            if m:
                return int(m.group(1))
        return 0


def _find_trace_files(path: str) -> list[str]:
    """Resolve a profiler dump directory to its chrome trace file(s).

    A one-shot ``jax.profiler.trace`` dump holds a single file; a duty-cycled
    live-capture directory (:mod:`repro.trace.liveprof`) holds one per
    window — all of them belong to the run, so all are returned.
    """
    if os.path.isfile(path):
        return [path]
    for pattern in ("*.trace.json.gz", "*.trace.json", "*.json.gz", "*.json"):
        hits = sorted(glob.glob(os.path.join(path, "**", pattern), recursive=True))
        if hits:
            return hits
    xplanes = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if xplanes:
        raise ValueError(
            f"{path} holds only raw xplane protos ({os.path.basename(xplanes[0])}); "
            "install xprof/tensorboard-plugin-profile to convert them, or point "
            "at the *.trace.json.gz it produces"
        )
    raise FileNotFoundError(f"no chrome trace (*.trace.json[.gz]) under {path}")


def _find_trace_file(path: str) -> str:
    return _find_trace_files(path)[0]


def _parse_trace_file(file: str) -> list[DeviceSlice]:
    opener = gzip.open if file.endswith(".gz") else open
    with opener(file, "rt") as f:
        doc = json.load(f)
    rows = doc["traceEvents"] if isinstance(doc, dict) else doc
    pid_names: dict[Any, str] = {}
    for r in rows:
        if r.get("ph") == "M" and r.get("name") == "process_name":
            pid_names[r.get("pid")] = str(r.get("args", {}).get("name", ""))
    out: list[DeviceSlice] = []
    for r in rows:
        if r.get("ph") != "X" or not isinstance(r.get("ts"), (int, float)):
            continue
        device = pid_names.get(r.get("pid")) or f"pid:{r.get('pid')}"
        t0 = r["ts"] * 1e-6
        dur = r.get("dur", 0) or 0
        out.append(DeviceSlice(
            name=str(r.get("name", "?")),
            t0=t0,
            t1=t0 + dur * 1e-6,
            device=device,
            args=r.get("args") or {},
        ))
    return out


def load_profiler_trace(path: str, *, device_only: bool = True) -> list[DeviceSlice]:
    """Parse a ``jax.profiler`` dump (file or TensorBoard dir) into slices.

    Reads the Chrome Trace Event JSON (gzipped or plain), maps ``pid`` rows
    to their ``process_name`` metadata, and returns every complete (``X``)
    event as a :class:`DeviceSlice` with timestamps in seconds.  Directories
    holding several trace files (one per duty-cycled capture window) are
    merged.  ``device_only`` keeps only device-looking processes when the
    dump names any (host python threads stay host-side — the collector
    already has them); dumps with no recognisable device rows are returned
    whole.
    """
    out: list[DeviceSlice] = []
    for file in _find_trace_files(path):
        out.extend(_parse_trace_file(file))
    if device_only:
        dev = [s for s in out if _DEVICE_PID_RE.search(s.device)]
        if dev:  # host-only dumps (pure-CPU smoke runs) are returned whole
            out = dev
    out.sort(key=lambda s: s.t0)
    return out


def align_device_slices(
    host_events: Iterable[Event],
    slices: Iterable[DeviceSlice],
    *,
    offset_s: Optional[float] = None,
    id_alloc: Optional[Any] = None,
    stats: Optional[dict[str, int]] = None,
) -> list[Event]:
    """Turn profiler slices into ``device`` events parented to host spans.

    Each returned event carries ``kind="device"``, a fresh span id of its
    own (so device slices are real span-tree nodes), and
    ``payload={"dur_s", "device", "align", ...}`` — exactly what
    :func:`repro.trace.collector.resolve_spans` needs to rebuild the device
    span and :mod:`repro.trace.export` needs to render per-device tracks.
    ``payload["align"]`` records how the parent was found: ``"span"``
    (explicit annotation hint), ``"window"`` (time containment fallback) or
    ``"none"`` (device-track root).

    ``id_alloc`` is a zero-arg callable producing fresh span ids.  Live
    merges (same process as the recording run) must pass
    :func:`repro.core.events.next_span_id` so device ids share the host
    counter; the default — allocate strictly above every id the host events
    mention — is for post-hoc merges where the recording process's counter
    is gone.  ``stats``, when given, accumulates counts per alignment mode
    (keys ``span``/``window``/``none``/``total``).
    """
    host_events = sorted(host_events, key=lambda e: e.t)
    slices = list(slices)
    if not slices:
        return []
    if offset_s is None:
        host_t0 = host_events[0].t if host_events else 0.0
        offset_s = host_t0 - slices[0].t0  # align trace starts
    spans = [s for s in resolve_spans(host_events) if s.span]
    by_id = {s.span: s for s in spans}

    if id_alloc is None:
        # Device span ids must not collide with the session's host ids: the
        # session was recorded in another process, so this process's global
        # counter is meaningless here — allocate strictly above every id the
        # host events mention (span_tree treats parent >= own id as corrupt).
        base = 1 + max((max(e.span, e.parent) for e in host_events), default=0)
        counter = iter(range(base, base + len(slices)))
        id_alloc = lambda: next(counter)

    # innermost-containing-span lookup via a single time sweep: spans enter
    # the active set at t0 and leave at t1, so each slice midpoint consults
    # only the handful of concurrently-open spans instead of scanning all of
    # them (real profiler dumps carry 10k+ slices).
    mids = sorted(range(len(slices)),
                  key=lambda i: (slices[i].t0 + slices[i].t1) / 2)
    starts = sorted(spans, key=lambda s: s.t0)
    active: dict[int, Any] = {}
    owners: dict[int, int] = {}
    modes: dict[int, str] = {}
    si = 0
    for i in mids:
        mid = (slices[i].t0 + slices[i].t1) / 2 + offset_s
        while si < len(starts) and starts[si].t0 <= mid:
            active[starts[si].span] = starts[si]
            si += 1
        for sid in [sid for sid, s in active.items() if s.t1 < mid]:
            del active[sid]
        hint = slices[i].span_hint
        if hint and hint in by_id:
            owners[i] = hint
            modes[i] = "span"
        elif active:
            owners[i] = min(active.values(), key=lambda s: s.dur).span
            modes[i] = "window"
        else:
            owners[i] = 0
            modes[i] = "none"

    out: list[Event] = []
    for i, sl in enumerate(slices):
        t0, t1 = sl.t0 + offset_s, sl.t1 + offset_s
        payload: dict[str, Any] = {"dur_s": max(0.0, t1 - t0),
                                   "device": sl.device, "align": modes[i]}
        if sl.args:
            payload["args"] = {k: v for k, v in sl.args.items()
                               if isinstance(v, (int, float, str, bool))}
        out.append(Event(t0, DEVICE_KIND, sl.name, payload,
                         span=id_alloc(), parent=owners[i]))
        if stats is not None:
            stats[modes[i]] = stats.get(modes[i], 0) + 1
            stats["total"] = stats.get("total", 0) + 1
    return out


def alignment_summary(events: Iterable[Event]) -> dict[str, Any]:
    """Per-mode counts + annotated fraction over merged ``device`` events."""
    counts = {"span": 0, "window": 0, "none": 0, "total": 0}
    for e in events:
        if e.kind != DEVICE_KIND or not isinstance(e.payload, dict):
            continue
        mode = e.payload.get("align")
        if mode not in counts:
            mode = "none"
        counts[mode] += 1
        counts["total"] += 1
    counts["annotated_fraction"] = (
        counts["span"] / counts["total"] if counts["total"] else 0.0
    )
    return counts


def merge_device_trace(
    session: Any, path: str, *, offset_s: Optional[float] = None
) -> int:
    """Merge a profiler dump into a loaded Session, in place.

    Returns the number of device events merged; records the dump path,
    count and per-mode alignment stats under
    ``session.meta["device_trace"]``.
    """
    stats: dict[str, int] = {}
    merged = align_device_slices(
        session.events, load_profiler_trace(path), offset_s=offset_s,
        stats=stats,
    )
    session.events = sorted(session.events + merged, key=lambda e: e.t)
    session.meta["device_trace"] = {
        "path": path, "events": len(merged), "align": stats,
    }
    return len(merged)
