"""Hierarchical span-tree model: parent links end-to-end.

Covers the contextvars span stack (thread/async safety), orphan handling
(parent or exit evicted from the ring), the nested exporters (speedscope
evented + Perfetto async grouping + flow links), the ``report --tree`` view,
and the jax.profiler device-trace merge — with golden exports where the
format is load-bearing for external viewers.
"""
import gzip
import json
import os
import threading

import pytest

from repro.core.events import Event, EventLog, current_span, next_span_id, span_scope
from repro.dispatch import DispatchConfig, Dispatcher
from repro.trace import (
    Session,
    TraceCollector,
    align_device_slices,
    load_profiler_trace,
    merge_device_trace,
    resolve_spans,
    span_tree,
    to_chrome_trace,
    to_folded,
    to_speedscope,
)


# ---------------------------------------------------------------------------
# contextvars span stack: nesting, overrides, thread isolation
# ---------------------------------------------------------------------------


def test_lifecycle_nesting_sets_parents():
    log = EventLog()
    with log.lifecycle("step", 0) as outer:
        assert current_span() == outer
        with log.lifecycle("checkpoint", 0) as inner:
            assert current_span() == inner
            log.record("mark", "m")
        assert current_span() == outer
    assert current_span() == 0
    spawns = {e.name: e for e in log.events(kind="spawn")}
    assert spawns["step"].parent == 0
    assert spawns["checkpoint"].parent == outer
    mark = log.events(kind="mark")[0]
    assert mark.parent == inner


def test_record_explicit_parent_overrides_context():
    log = EventLog()
    with log.lifecycle("step", 0) as s:
        log.record("mark", "ctx")
        log.record("mark", "explicit", parent=999)
        log.record("mark", "root", parent=0)
    by_name = {e.name: e for e in log.events(kind="mark")}
    assert by_name["ctx"].parent == s
    assert by_name["explicit"].parent == 999
    assert by_name["root"].parent == 0


def test_span_scope_reparents_detached_work():
    """A span whose bracket events live elsewhere (serving request) still
    adopts children recorded under its span_scope."""
    log = EventLog()
    rid = next_span_id()
    log.record("spawn", "request", 1, span=rid)
    with span_scope(rid):
        with log.lifecycle("prefill", 1) as pf:
            log.record("dispatch", "serve_prefill", {"backend": "ref"})
    log.record("exit", "request", 1, span=rid)
    spawns = {e.name: e for e in log.events(kind="spawn")}
    assert spawns["prefill"].parent == rid
    assert log.events(kind="dispatch")[0].parent == pf


def test_concurrent_threads_do_not_cross_parent():
    """Each thread's contextvars stack is its own: spans opened concurrently
    on one shared ring must parent only within their own thread."""
    col = TraceCollector(capacity=4096)
    n_threads, per_thread = 8, 25
    errors: list[str] = []

    def work(tid: int) -> None:
        for i in range(per_thread):
            with col.lifecycle("request", (tid, i)) as rid:
                if current_span() != rid:
                    errors.append(f"thread {tid}: context leaked")
                with col.lifecycle("prefill", (tid, i)):
                    col.record("mark", "m", (tid, i))
            if current_span() != 0:
                errors.append(f"thread {tid}: stack not unwound")

    threads = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    spawn_parent = {e.span: e.parent for e in col.events(kind="spawn")}
    payload_of = {e.span: e.payload for e in col.events(kind="spawn")}
    prefills = [e for e in col.events(kind="spawn") if e.name == "prefill"]
    assert len(prefills) == n_threads * per_thread
    for e in prefills:
        # the prefill's parent is the request from the SAME (tid, i)
        assert spawn_parent[e.span] != 0
        assert payload_of[e.parent] == e.payload
    marks = col.events(kind="mark")
    for e in marks:
        assert payload_of[e.parent] == e.payload


def test_async_tasks_inherit_and_isolate_context():
    """contextvars are copied into asyncio tasks: concurrent coroutines nest
    under their own lifecycle, not each other's."""
    import asyncio

    log = EventLog()

    async def one_request(i: int) -> None:
        with log.lifecycle("request", i) as rid:
            await asyncio.sleep(0)  # force interleaving
            log.record("mark", "tick", i)
            await asyncio.sleep(0)
            assert current_span() == rid

    async def main() -> None:
        await asyncio.gather(*(one_request(i) for i in range(5)))

    asyncio.run(main())
    payload_of = {e.span: e.payload for e in log.events(kind="spawn")}
    for e in log.events(kind="mark"):
        assert payload_of[e.parent] == e.payload


# ---------------------------------------------------------------------------
# resolve_spans: orphan close + accounting; span_tree fallback
# ---------------------------------------------------------------------------


def test_orphaned_spawn_closes_at_last_event_time_and_is_counted():
    """A spawn whose exit was evicted must not leak: it closes (truncated) at
    the last observed event time and lands in dropped_by_track."""
    evs = [
        Event(1.0, "spawn", "request", "lost-exit", 7),
        Event(2.0, "spawn", "request", "ok", 8),
        Event(3.0, "exit", "request", "ok", 8),
        Event(4.0, "mark", "m", None),
    ]
    orphans: dict = {}
    spans = resolve_spans(evs, orphans=orphans)
    lost = next(s for s in spans if s.payload == "lost-exit")
    assert lost.truncated and lost.t1 == pytest.approx(4.0)
    assert orphans == {"request": 1}
    ok = next(s for s in spans if s.payload == "ok")
    assert not ok.truncated and ok.dur == pytest.approx(1.0)


def test_truncated_spans_excluded_from_latency_report():
    """A force-closed span is a cut artifact, not a measurement: it must not
    inflate the latency tables (and through them the diff CI gate)."""
    evs = [
        Event(0.0, "spawn", "request", "lost", 11),   # exit evicted
        Event(1.0, "spawn", "request", "ok", 12),
        Event(1.5, "exit", "request", "ok", 12),
        Event(100.0, "mark", "late", None),           # would close "lost" at t=100
    ]
    rep = Session(meta={}, events=evs).report()
    row = rep["latency"]["request/request"]
    assert row["count"] == 1
    assert row["max_ms"] == pytest.approx(500.0)  # the 100s orphan excluded
    assert rep["truncated_spans"] == 1


def test_collector_dropped_by_track_includes_orphans():
    col = TraceCollector(capacity=64)
    col.record("spawn", "request", "A", span=next_span_id())  # exit never comes
    with col.lifecycle("request", "B"):
        pass
    col.record("mark", "m")
    assert col.dropped_by_track().get("request") == 1
    assert col.stats()["dropped_by_track"]["request"] == 1


def test_span_tree_orphan_parent_falls_back_to_root():
    """Parent evicted before child: the child keeps its subtree as a new
    root instead of vanishing."""
    pid, cid, gid = next_span_id(), next_span_id(), next_span_id()
    evs = [
        # parent's spawn/exit both evicted: only the child + grandchild remain
        Event(2.0, "spawn", "prefill", 1, cid, pid),
        Event(2.5, "mark", "probe", None, gid, cid),
        Event(3.0, "exit", "prefill", 1, cid, pid),
    ]
    roots = span_tree(resolve_spans(evs))
    assert len(roots) == 1
    assert roots[0].span.span == cid  # orphan promoted to root
    assert [c.span.span for c in roots[0].children] == [gid]


def test_span_tree_nests_and_computes_exclusive():
    log = EventLog()
    with log.lifecycle("step", 0):
        with log.lifecycle("checkpoint", 0):
            pass
    roots = span_tree(resolve_spans(log.events()))
    assert len(roots) == 1
    step = roots[0]
    assert step.span.name == "step" and len(step.children) == 1
    ckpt = step.children[0]
    assert ckpt.span.name == "checkpoint"
    assert step.exclusive == pytest.approx(step.span.dur - ckpt.span.dur)
    assert ckpt.exclusive == pytest.approx(ckpt.span.dur)


# ---------------------------------------------------------------------------
# golden exports: speedscope evented + Perfetto nesting/flows
# ---------------------------------------------------------------------------


def _golden_events() -> list[Event]:
    """Deterministic two-request trace with a dispatch child."""
    return [
        Event(0.0, "spawn", "request", "A", 1),
        Event(1.0, "spawn", "prefill", "A", 2, 1),
        Event(2.0, "exit", "prefill", "A", 2, 1),
        Event(3.0, "spawn", "request", "B", 3),   # overlaps A
        Event(4.0, "dispatch", "serve_decode",
              {"op": "serve_decode", "backend": "ref", "measured_s": 0.5}, 4, 3),
        Event(5.0, "exit", "request", "A", 1),
        Event(6.0, "exit", "request", "B", 3),
    ]


def test_speedscope_evented_golden():
    doc = to_speedscope(_golden_events())
    request = next(p for p in doc["profiles"] if p["name"] == "request")
    frames = [f["name"] for f in doc["shared"]["frames"]]
    named = [(e["type"], frames[e["frame"]], e["at"]) for e in request["events"]]
    # A opens, prefill nests inside it, B opens inside A's window; when A
    # closes while B is on top, B is closed/reopened (rebalancing) so the
    # profile stays a valid strict stack
    assert named == [
        ("O", "request", 0.0),
        ("O", "prefill", 1.0),
        ("C", "prefill", 2.0),
        ("O", "request", 3.0),
        ("C", "request", 5.0),  # B closed to let A pop...
        ("C", "request", 5.0),  # ...A closes...
        ("O", "request", 5.0),  # ...B reopens
        ("C", "request", 6.0),
    ]
    assert all(p["type"] == "evented" for p in doc["profiles"])


def test_chrome_subtree_shares_root_async_id_and_flows():
    doc = to_chrome_trace(_golden_events())
    rows = doc["traceEvents"]
    be = [r for r in rows if r["ph"] in ("b", "e")]
    # request A (span 1) and its prefill (span 2) group under root id "1"
    a_rows = [r for r in be if r["args"].get("span") in (1, 2)]
    assert len(a_rows) == 4 and {r["id"] for r in a_rows} == {"1"}
    # request B groups under its own root
    b_rows = [r for r in be if r["args"].get("span") == 3]
    assert {r["id"] for r in b_rows} == {"3"}
    # parent links surface in args
    prefill = next(r for r in be if r["name"] == "prefill" and r["ph"] == "b")
    assert prefill["args"]["parent"] == 1
    # the dispatch under request B gets a flow arrow from B's spawn
    flows = [r for r in rows if r.get("cat") == "flow"]
    assert {r["ph"] for r in flows} == {"s", "f"}
    s = next(r for r in flows if r["ph"] == "s")
    f = next(r for r in flows if r["ph"] == "f")
    assert s["id"] == f["id"]
    b_spawn = next(r for r in be if r["args"].get("span") == 3 and r["ph"] == "b")
    assert s["ts"] == b_spawn["ts"] and s["tid"] == b_spawn["tid"]


def test_folded_export_uses_ancestor_paths():
    text = to_folded(_golden_events())
    lines = dict(ln.rsplit(" ", 1) for ln in text.splitlines() if ln)
    assert "request;request;prefill" in lines
    assert "request;request;serve_decode;ref" in lines
    # exclusive weighting: request A's self time excludes the 1s prefill
    assert int(lines["request;request;prefill"]) == 1_000_000


# ---------------------------------------------------------------------------
# report --tree
# ---------------------------------------------------------------------------


def test_tree_report_groups_and_depths():
    sess = Session(meta={}, events=_golden_events())
    rows = sess.tree_report()
    by_name = {(r["depth"], r["name"]): r for r in rows}
    req = by_name[(0, "request")]
    assert req["count"] == 2
    assert req["inclusive_ms"] == pytest.approx(8000.0)  # 5s + 3s
    pf = by_name[(1, "prefill")]
    assert pf["count"] == 1 and pf["inclusive_ms"] == pytest.approx(1000.0)
    disp = by_name[(1, "serve_decode")]
    assert disp["track"] == "dispatch"
    # exclusive subtracts children: A(5s) - prefill(1s) + B(3s) - dispatch(.5s)
    assert req["exclusive_ms"] == pytest.approx(6500.0)


def test_cli_report_tree(tmp_path, capsys):
    from repro.trace.cli import main

    path = Session(meta={}, events=_golden_events()).save(str(tmp_path / "s.json"))
    assert main(["report", path, "--tree"]) == 0
    out = capsys.readouterr().out
    assert "request/request" in out
    assert "  dispatch/serve_decode" in out  # indented child
    assert main(["report", path, "--tree", "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert any(r["depth"] > 0 for r in rows)


# ---------------------------------------------------------------------------
# acceptance: serving engine produces a real tree (dispatch under request)
# ---------------------------------------------------------------------------


def test_engine_dispatch_decisions_are_children_of_requests(key):
    from repro.configs import get_config, reduced
    from repro.models import lm
    from repro.serving.engine import Engine, ServeConfig

    cfg = reduced(get_config("smollm-360m"))
    params = lm.init_params(cfg, key)
    col = TraceCollector()
    disp = Dispatcher(DispatchConfig(policy="profiled", min_samples=1), log=col)
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_seq=64),
                 log=col, dispatcher=disp)
    with col.lifecycle("serve_run", {"requests": 3}):
        for _ in range(3):
            eng.submit([1, 2, 3, 4], max_new=4)
        eng.run_to_completion()

    spawn_of = {e.span: e for e in col.events(kind="spawn")}
    prefill_dispatches = [e for e in col.events(kind="dispatch")
                          if e.payload["op"] == "serve_prefill"]
    assert prefill_dispatches
    for e in prefill_dispatches:
        # dispatch -> prefill -> request -> serve_run: depth 3
        pf = spawn_of[e.parent]
        assert pf.name == "prefill"
        req = spawn_of[pf.parent]
        assert req.name == "request"
        assert spawn_of[req.parent].name == "serve_run"
    decode_dispatches = [e for e in col.events(kind="dispatch")
                         if e.payload["op"] == "serve_decode"]
    assert decode_dispatches
    for e in decode_dispatches:
        assert spawn_of[e.parent].name == "decode_tick"

    # the tree view agrees: non-zero depth everywhere below the root
    rows = Session.capture(col, dispatcher=disp).tree_report()
    disp_rows = [r for r in rows if r["track"] == "dispatch"]
    assert disp_rows and all(r["depth"] >= 2 for r in disp_rows)


# ---------------------------------------------------------------------------
# device timelines: synthetic jax.profiler dump merged under host spans
# ---------------------------------------------------------------------------


def _write_profiler_dump(tmp_path, rows) -> str:
    """A TensorBoard-style profiler dir holding a gzipped chrome trace."""
    run_dir = tmp_path / "plugins" / "profile" / "2026_07_30_00_00_00"
    run_dir.mkdir(parents=True)
    path = run_dir / "host.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": rows}, f)
    return str(tmp_path)


def _device_dump_rows():
    return [
        {"ph": "M", "pid": 10, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 99, "name": "process_name",
         "args": {"name": "python host threads"}},
        # two device ops inside the host prefill window (1.0s..2.0s below),
        # timestamps in µs in the profiler's own clock starting at 0
        {"ph": "X", "pid": 10, "tid": 1, "name": "fusion.1",
         "ts": 1_100_000, "dur": 300_000},
        {"ph": "X", "pid": 10, "tid": 1, "name": "copy.2",
         "ts": 1_500_000, "dur": 100_000, "args": {"bytes": 4096}},
        # a host-side X row that must be filtered out (device_only)
        {"ph": "X", "pid": 99, "tid": 2, "name": "python_gc",
         "ts": 1_200_000, "dur": 50_000},
        # a hinted slice: binds to span 2 regardless of its window
        {"ph": "X", "pid": 10, "tid": 1, "name": "span=2 rms_norm",
         "ts": 5_900_000, "dur": 50_000},
    ]


def test_load_profiler_trace_parses_dump(tmp_path):
    dump = _write_profiler_dump(tmp_path, _device_dump_rows())
    slices = load_profiler_trace(dump)
    assert [s.name for s in slices] == ["fusion.1", "copy.2", "span=2 rms_norm"]
    assert all(s.device == "/device:TPU:0" for s in slices)
    assert slices[0].dur == pytest.approx(0.3)
    assert slices[2].span_hint == 2


def test_device_events_align_under_host_spans(tmp_path):
    host = _golden_events()  # dump shares the host clock -> explicit offset 0
    dump = _write_profiler_dump(tmp_path, _device_dump_rows())
    merged = align_device_slices(host, load_profiler_trace(dump), offset_s=0.0)
    assert len(merged) == 3
    by_name = {e.name: e for e in merged}
    # window containment: both ops sit inside prefill (span 2), the innermost
    assert by_name["fusion.1"].parent == 2
    assert by_name["copy.2"].parent == 2
    # the hint overrides the window (its ts lies outside every span)
    assert by_name["span=2 rms_norm"].parent == 2
    assert all(e.kind == "device" for e in merged)
    assert all(e.span != 0 for e in merged)  # real tree nodes
    # device ids must sit strictly above every host id (the session comes
    # from another process, so this process's span counter is meaningless —
    # colliding ids would trip span_tree's corrupt-parent guard)
    host_max = max(max(e.span, e.parent) for e in host)
    assert all(e.span > host_max for e in merged)
    assert len({e.span for e in merged}) == len(merged)


def test_span_stat_as_text_binds_through_align(tmp_path):
    """A lifecycle annotation's ``span`` stat, written as text by the trace
    viewer's JSON, binds its slice to that span outside every window."""
    rows = _device_dump_rows()[:2] + [
        {"ph": "X", "pid": 10, "tid": 1, "name": "repro.prefill",
         "ts": 5_900_000, "dur": 50_000, "args": {"span": "2", "parent": "1"}},
    ]
    slices = load_profiler_trace(_write_profiler_dump(tmp_path, rows))
    assert [s.span_hint for s in slices] == [2]
    merged = align_device_slices(_golden_events(), slices, offset_s=0.0)
    assert [(e.name, e.parent, e.payload["align"]) for e in merged] == [
        ("repro.prefill", 2, "span")]

def test_merge_device_trace_into_session_report_and_export(tmp_path):
    sess = Session(meta={}, events=_golden_events())
    dump = _write_profiler_dump(tmp_path, _device_dump_rows())
    n = merge_device_trace(sess, dump, offset_s=0.0)
    assert n == 3 and sess.meta["device_trace"]["events"] == 3

    rows = sess.tree_report()
    dev_rows = [r for r in rows if r["track"].startswith("device:")]
    assert dev_rows and all(r["depth"] >= 2 for r in dev_rows)

    doc = to_chrome_trace(sess.events)
    names = {r["args"]["name"]: r["tid"] for r in doc["traceEvents"]
             if r["ph"] == "M" and r["name"] == "thread_name"}
    assert "device:/device:TPU:0" in names
    # host tracks render above (lower tid than) device tracks
    assert names["request"] < names["device:/device:TPU:0"]
    dev_x = [r for r in doc["traceEvents"]
             if r["ph"] == "X" and r.get("cat") == "device"]
    assert len(dev_x) == 3 and all(r["dur"] > 0 for r in dev_x)

    # latency tables pick the device track up too
    rep = sess.report()
    assert any(k.startswith("device:") for k in rep["latency"])


def test_cli_report_device_trace_flag(tmp_path, capsys):
    from repro.trace.cli import main

    path = Session(meta={}, events=_golden_events()).save(str(tmp_path / "s.json"))
    dump = _write_profiler_dump(tmp_path, _device_dump_rows())
    assert main(["report", path, "--tree", "--device-trace", dump]) == 0
    out = capsys.readouterr().out
    assert "device:/device:TPU:0" in out


def test_profiler_dump_xplane_only_errors(tmp_path):
    d = tmp_path / "dump" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"\x00")
    with pytest.raises(ValueError, match="xplane"):
        load_profiler_trace(str(tmp_path / "dump"))
