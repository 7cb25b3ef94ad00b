"""The program's own spans in a device trace, and the step-loop numbers they give.

Every ``EventLog.lifecycle`` span of the program is a host event
``repro.<name>`` on the ``/host:CPU`` plane, with its ``span`` and ``parent``
ids as stats (``repro.core.events``), on the same time base as the device's
operations.  ``reduce(path)`` reads them from an ``.xplane.pb`` beside what
``benchlib.trace`` reads (the ``bench.*`` spans and chip 0's operations):

* counted are the spans that open at or after the window's start (the first
  ``bench.*`` span); the profiler keeps only spans that also closed before
  it stopped, so a step whose ``repro.step`` opened before the capture is
  not there, and each mean is over the spans recorded;
* ``spans``: each span name's count and total;
* ``step_idle_s``: chip 0's idle time inside each ``repro.step``, put down
  to the innermost of the step's descendant spans over it, or to
  ``unattributed`` where none is; idle under ``repro.device_wait`` is the
  device's own gaps between its operations;
* ``batch_pairs``: each ``repro.batch`` beside the ``bench.batch_fn`` span it
  encloses, which times the same call from outside the program.

``summary`` turns that into ``batch_ms`` (mean ``repro.batch``),
``dispatch_ms`` (mean ``repro.dispatch``) and ``host_idle_ms`` (chip-0 idle
per recorded step outside its ``repro.device_wait``).  The benchmark's
capture deletes its trace inside ``trace.Capture.reduce``, so a run reports
these once that reduction also calls ``reduce``; until then they are read
from a kept trace.
"""
from __future__ import annotations

import warnings

from benchlib import trace

PROGRAM_PREFIX = "repro."
STEP = "repro.step"
WAIT = "repro.device_wait"
UNATTRIBUTED = "unattributed"
# the program's batch span, and the benchmark's span inside it around the same call
BATCH, BATCH_FN = "repro.batch", "bench.batch_fn"
DISPATCH = "repro.dispatch"


def stats(event) -> dict:
    """An event's stats by name."""
    with warnings.catch_warnings():
        # the first read builds the binding's type, which warns that it has
        # no __module__ (DeprecationWarning)
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def read_program(path: str) -> list[tuple[str, int, int, int, int]]:
    """[(program span, start_ns, end_ns, span id, parent id)] of the host plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        ids = stats(e)
                        out.append((e.name, int(e.start_ns), int(e.end_ns),
                                    int(ids.get("span", 0)), int(ids.get("parent", 0))))
    return out


def attribute(gap: tuple[int, int], spans: list[tuple[str, int, int]]) -> list[tuple[str, int]]:
    """Split ``gap`` among the innermost (latest-opened) of ``spans`` over
    each part of it; parts under none go to ``unattributed``."""
    lo, hi = gap
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e) if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [(s, -e, n) for n, s, e in spans if s <= a and e >= b]
        out.append((max(over)[2] if over else UNATTRIBUTED, b - a))
    return out


def descendants(program: list, span: int) -> list[tuple[str, int, int]]:
    """The spans of ``program`` whose chain of parents reaches ``span``."""
    parent = {p[3]: p[4] for p in program}
    out = []
    for name, s, e, sid, pid in program:
        while pid and pid != span:
            pid = parent.get(pid, 0)
        if pid:
            out.append((name, s, e))
    return out


def reduce_program(program: list, bench: list, busy: list[tuple[int, int]], lo: int) -> dict:
    """The program's spans that open at or after ``lo``, with chip 0's
    ``busy`` intervals (a union) split inside each step."""
    program = [p for p in program if p[1] >= lo]
    totals: dict = {}
    for name, s, e, _, _ in program:
        c, t = totals.get(name, (0, 0))
        totals[name] = (c + 1, t + e - s)
    steps = [p for p in program if p[0] == STEP]
    idle: dict = {}
    for _, s, e, sid, _ in steps:
        inner = descendants(program, sid)
        for g in trace.gaps(trace.clip(busy, s, e), s, e):
            for name, d in attribute(g, inner):
                idle[name] = idle.get(name, 0) + d
    batch_pairs = [[(e - s) / 1e9, (b - a) / 1e9]
                   for n, s, e, _, _ in program if n == BATCH
                   for m, a, b in bench if m == BATCH_FN and s <= a and b <= e]
    return {
        "spans": {n: {"count": c, "s": t / 1e9} for n, (c, t) in sorted(totals.items())},
        "steps": len(steps),
        "step_idle_s": {n: d / 1e9 for n, d in sorted(idle.items())},
        "batch_pairs": batch_pairs,
    }


def reduce(path: str) -> dict:
    """``reduce_program`` of a trace, over the window ``benchlib.trace`` reads."""
    devices, bench = trace.read_planes(path)
    if not bench:
        raise RuntimeError(f"no {trace.SPAN_PREFIX}* host spans in {path}")
    busy0 = trace.union([(s, e) for _, s, e in devices[sorted(devices)[0]]]) if devices else []
    return reduce_program(read_program(path), bench, busy0, min(s for _, s, _ in bench))


def summary(red: dict) -> dict:
    """``batch_ms``, ``dispatch_ms`` and ``host_idle_ms`` of a reduction, each
    ``None`` where the trace holds none of its spans."""
    spans = red.get("spans", {})

    def mean_ms(name):
        span = spans.get(name)
        return 1e3 * span["s"] / span["count"] if span else None

    idle = red.get("step_idle_s", {})
    host_idle = (1e3 * (sum(idle.values()) - idle.get(WAIT, 0.0)) / red["steps"]
                 if red.get("steps") else None)
    return {"batch_ms": mean_ms(BATCH), "dispatch_ms": mean_ms(DISPATCH),
            "host_idle_ms": host_idle}
