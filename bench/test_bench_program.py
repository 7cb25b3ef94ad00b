"""The program's own spans in the device trace (``benchlib.program``), and the
two checkpoint metrics read from the program's log.

``testdata/program_spans.xplane.pb`` was recorded by ``jax.profiler`` on the
CPU around ``EventLog.lifecycle`` spans: a ``step`` 0 opened before the
capture with a ``fetch`` child opened after it, then a whole ``step`` 1
holding ``batch`` (around a ``bench.batch_fn`` annotation) and ``dispatch``,
with a ``bench.other`` annotation between them.  It holds no device plane.

``testdata/train_spans.xplane.pb`` is a traced run of the train driver on
one TPU v5e (qwen2-0.5b widths, one layer, a 4,096-token vocabulary,
1 x 1024, a window of two steps), cut down to the device's ``XLA Ops`` line,
each operation named by its HLO instruction alone, and the host's
``bench.*`` and ``repro.*`` events with their stats, as recorded.
"""
from __future__ import annotations

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import program as prog  # noqa: E402
from benchlib import trace  # noqa: E402

FIXTURE = os.path.join(HERE, "testdata", "program_spans.xplane.pb")
CHIP_FIXTURE = os.path.join(HERE, "testdata", "train_spans.xplane.pb")
MS = 1_000_000  # ns


def metric(name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_program_spans_collected_from_a_cpu_capture():
    devices, bench = trace.read_planes(FIXTURE)
    program = prog.read_program(FIXTURE)
    assert devices == {}
    assert [n for n, _, _ in bench] == ["bench.other", "bench.batch_fn"]
    by_name = {p[0]: p for p in program}
    # step 0 opened before the capture: only its fetch child was recorded
    assert sorted(by_name) == ["repro.batch", "repro.dispatch", "repro.fetch", "repro.step"]
    step = by_name["repro.step"]
    for child in ("repro.batch", "repro.dispatch"):
        _, s, e, _, parent = by_name[child]
        assert parent == step[3] and step[1] <= s < e <= step[2]
    assert by_name["repro.fetch"][4] not in {p[3] for p in program}
    # counted are the spans that open inside the window: here step 1 opens
    # before its first bench span, and its children after it
    lo = min(s for _, s, _ in bench)
    assert "repro.step" not in prog.reduce_program(program, bench, [], lo)["spans"]
    red = prog.reduce_program(program, bench, [], step[1])
    assert red["spans"] == {
        "repro.batch": {"count": 1, "s": pytest.approx((by_name["repro.batch"][2] - by_name["repro.batch"][1]) / 1e9)},
        "repro.dispatch": {"count": 1, "s": pytest.approx((by_name["repro.dispatch"][2] - by_name["repro.dispatch"][1]) / 1e9)},
        "repro.step": {"count": 1, "s": pytest.approx((step[2] - step[1]) / 1e9)},
    }
    (pair,) = red["batch_pairs"]
    assert pair[0] >= pair[1] > 0


def test_program_spans_on_a_chip_trace():
    red = trace.reduce_xplane(CHIP_FIXTURE)
    # the existing numbers read as they do without program spans
    assert red["window_s"] == pytest.approx(0.031205423, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.006075966, rel=1e-9)
    assert [g[0] for g in red["breakdown"]["idle_gaps"][:2]] == ["bench.batch_fn"] * 2
    assert "program" not in red
    program = prog.reduce(CHIP_FIXTURE)
    # the first window step opened before the capture: its dispatch, wait
    # and fetch are recorded, its step and batch are not
    assert {n: v["count"] for n, v in program["spans"].items()} == {
        "repro.batch": 1, "repro.device_wait": 2, "repro.dispatch": 2,
        "repro.fetch": 2, "repro.step": 1}
    assert program["steps"] == 1
    idle = program["step_idle_s"]
    assert idle["repro.batch"] == pytest.approx(0.012294794, rel=1e-9)
    assert idle["repro.device_wait"] == pytest.approx(0.001747947, rel=1e-9)
    got = prog.summary(program)
    host_idle = got["host_idle_ms"]
    assert host_idle == pytest.approx(1e3 * (sum(idle.values()) - idle["repro.device_wait"]))
    named = sum(idle[k] for k in ("repro.batch", "repro.dispatch", "repro.fetch"))
    assert 1e3 * named >= 0.95 * host_idle
    assert got["batch_ms"] == pytest.approx(12.433)
    assert got["dispatch_ms"] == pytest.approx(1e3 * 0.002209509 / 2)
    for outer, inner in program["batch_pairs"]:
        assert outer - inner <= max(0.05 * inner, 1e-3)


def _hand_made():
    """One step of 100 ms, its four children, a write on another thread."""
    program = [
        ("repro.step", 0, 100 * MS, 1, 0),
        ("repro.batch", 0, 20 * MS, 2, 1),
        ("repro.dispatch", 20 * MS, 25 * MS, 3, 1),
        ("repro.device_wait", 25 * MS, 90 * MS, 4, 1),
        ("repro.fetch", 90 * MS, 98 * MS, 5, 1),
        ("repro.ckpt.write", 95 * MS, 120 * MS, 9, 0),  # not the step's: takes no idle
        ("repro.step", -50 * MS, -1 * MS, 7, 0),  # opened before the window
    ]
    bench = [("bench.batch_fn", 1 * MS, 19 * MS)]
    busy = [(22 * MS, 40 * MS), (45 * MS, 88 * MS), (93 * MS, 94 * MS)]
    return program, bench, busy


def test_idle_inside_a_step_goes_to_its_innermost_span():
    program, bench, busy = _hand_made()
    red = prog.reduce_program(program, bench, busy, lo=0)
    idle = {k: v * 1e3 for k, v in red["step_idle_s"].items()}
    # gaps 0-22, 40-45, 88-93, 94-100; the step's last 2 ms are under no child
    assert idle == {"repro.batch": pytest.approx(20), "repro.dispatch": pytest.approx(2),
                    "repro.device_wait": pytest.approx(7), "repro.fetch": pytest.approx(7),
                    prog.UNATTRIBUTED: pytest.approx(2)}
    assert red["steps"] == 1 and red["spans"]["repro.step"]["count"] == 1
    assert red["batch_pairs"] == [[pytest.approx(0.020), pytest.approx(0.018)]]
    assert prog.summary(red) == {"batch_ms": pytest.approx(20),
                                 "dispatch_ms": pytest.approx(5),
                                 "host_idle_ms": pytest.approx(31)}


def test_attribute_splits_a_gap_at_span_edges():
    spans = [("a", 0, 10), ("b", 4, 6)]
    assert prog.attribute((2, 12), spans) == [("a", 2), ("b", 2), ("a", 4),
                                               (prog.UNATTRIBUTED, 2)]
    assert prog.descendants([("s", 0, 9, 1, 0), ("c", 1, 2, 2, 1), ("g", 1, 2, 3, 2),
                              ("x", 3, 4, 4, 0)], 1) == [("c", 1, 2), ("g", 1, 2)]


@pytest.mark.parametrize("name", ["batch_ms", "dispatch_ms", "host_idle_ms"])
def test_trace_readers_silent_without_program_spans(name):
    empty = prog.reduce_program([], [], [], 0)
    assert prog.summary(empty)[name] is None
    assert prog.summary({})[name] is None


@pytest.mark.parametrize("name,span", [("ckpt_snapshot_s", "ckpt.snapshot"),
                                       ("ckpt_write_s", "ckpt.write")])
def test_checkpoint_readers(name, span, tmp_path, monkeypatch):
    """A checkpointer made without a log, as the train driver makes its own,
    records into ``GLOBAL_LOG``; the reader takes the last save's span."""
    import jax.numpy as jnp

    from repro.checkpoint import AsyncCheckpointer
    from repro.core import events

    ckpt = AsyncCheckpointer(str(tmp_path))
    assert ckpt.log is events.GLOBAL_LOG
    before = len(events.GLOBAL_LOG.durations(span))
    for step in (1, 2):
        ckpt.save(step, {"w": jnp.arange(4.0) * step})
    ckpt.wait()
    spans = events.GLOBAL_LOG.durations(span)
    assert len(spans) == before + 2
    assert metric(name)({}) == spans[-1] > 0
    monkeypatch.setattr(events, "GLOBAL_LOG", events.EventLog())
    assert metric(name)({}) is None


def test_checkpoint_readers_after_a_run(monkeypatch):
    """A whole tiny run of the train driver on the CPU: both readers give the
    spans of its closing save, which lie inside the save's stall."""
    import argparse

    import jax

    import test_bench_check as tb
    from repro.core import events
    from repro.launch import cache

    monkeypatch.setattr(cache, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(events, "GLOBAL_LOG", events.EventLog())
    from repro.checkpoint import store

    monkeypatch.setattr(store, "GLOBAL_LOG", events.GLOBAL_LOG)
    bench, cell, config, traffic, limits = tb.tiny_cell()
    args = argparse.Namespace(seed=2**31 + 29, seconds=0.5, trace=0)
    out = tb.bench_run.run_cell(args, bench, cell, config, traffic, limits,
                                jax.devices()[:1])
    snapshot, write = metric("ckpt_snapshot_s")({}), metric("ckpt_write_s")({})
    # the step-0 save is skipped by the driver: one save, the closing one
    assert [len(events.GLOBAL_LOG.durations(n)) for n in
            ("ckpt.wait_prev", "ckpt.snapshot", "ckpt.write")] == [1, 1, 1]
    assert 0 < snapshot < out["metrics"]["ckpt_stall_s"]["value"]
    assert write > 0
