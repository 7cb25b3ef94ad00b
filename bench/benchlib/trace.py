"""Device trace of the measured window, and its reduction to numbers.

``Capture(True)`` starts the JAX profiler when the window opens and stops it
when it closes; ``reduce()`` reads the ``.xplane.pb`` it wrote with
``jax.profiler.ProfileData`` and deletes it.  ``reduce_xplane`` is the
reduction itself:

* device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
  event per operation run on that chip, named by its HLO instruction
  (``%fusion.12 = ...``), and events nest: a ``while`` holds its body's
  operations;
* the host's spans are the benchmark's ``TraceAnnotation`` events, named
  ``bench.*``, on the ``/host:CPU`` plane;
* the window runs from the first host span's start to the last host span's
  or device operation's end, whichever is later;
* busy time is the union of a chip's operation intervals in the window,
  averaged over the chips; an idle gap is a stretch of the window in which
  chip 0 runs nothing, named by the host span that overlaps it most
  (``host`` where none does);
* per-operation time is the sum of its events' self time (duration less the
  events nested in it), averaged over the chips.

The profiler runs with Python call tracing off: the benchmark's spans are
``TraceAnnotation`` events, and tracing every call of the data pipeline's
loop would cost more than it tells.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
TOP = 10


class Capture:
    def __init__(self, enabled: bool) -> None:
        self.enabled = enabled
        self.dir = None

    def start(self) -> None:
        if self.enabled and self.dir is None:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        if self.enabled and self.dir is not None:
            import jax

            jax.profiler.stop_trace()

    def reduce(self) -> dict:
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
            if len(paths) != 1:
                raise RuntimeError(f"expected one xplane file, found {paths}")
            return reduce_xplane(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _overlap(a: tuple[int, int], b: tuple[int, int]) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gap(gap: tuple[int, int], spans: list[tuple[str, int, int]]) -> str:
    best, name = 0, "host"
    for n, s, e in spans:
        o = _overlap(gap, (s, e))
        if o > best:
            best, name = o, n
    return name


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events: list[tuple[str, int, int]]) -> list[tuple[str, int]]:
    """(op, duration less the events nested in it) for events that nest."""
    out, stack = [], []  # stack: [op, start, end, child time]
    for op, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += e - s
        stack.append([op, s, e, 0])
    out += [(op, e - s - c) for op, s, e, c in stack]
    return out


def read_planes(path: str) -> tuple[dict, list]:
    """({device plane: [(op, start_ns, end_ns)]}, [(span, start_ns, end_ns)])."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), int(e.start_ns), int(e.end_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns), int(e.end_ns))
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return devices, spans


def reduce_xplane(path: str) -> dict:
    devices, spans = read_planes(path)
    if not devices or not any(devices.values()):
        raise RuntimeError(f"no device operations in {path}")
    if not spans:
        raise RuntimeError(f"no {SPAN_PREFIX}* host spans in {path}")
    lo = min(s for _, s, _ in spans)
    hi = max([e for _, _, e in spans] + [e for ops in devices.values() for _, _, e in ops])
    n = len(devices)
    busy, ops = 0, {}
    chip0 = None
    for name in sorted(devices):
        events = [(op, s, e) for op, s, e in devices[name] if e > lo and s < hi]
        u = union(clip([(s, e) for _, s, e in events], lo, hi))
        busy += sum(e - s for s, e in u)
        chip0 = u if chip0 is None else chip0
        for op, t_self in self_times(events):
            t, c = ops.get(op, (0, 0))
            ops[op] = (t + t_self, c + 1)
    idle = sorted(((name_gap(g, spans), g[1] - g[0]) for g in gaps(chip0, lo, hi)),
                  key=lambda x: -x[1])
    by_time = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "chips": n,
        "ops": {op: {"s": t / n / 1e9, "count": c / n} for op, (t, c) in ops.items()},
        "breakdown": {
            "device_ops": [[op, t / n / 1e9] for op, (t, _) in by_time[:TOP]],
            "idle_gaps": [[name, d / 1e9] for name, d in idle[:TOP]],
        },
    }
