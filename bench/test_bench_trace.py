"""The device-trace reduction, on a small trace recorded on a TPU v5e.

``testdata/train_step.xplane.pb`` holds two steps of the program's train
step (qwen2-0.5b widths, one layer, a 4,096-token vocabulary, 1 x 1024),
each after a ``bench.batch_fn`` span with a 5 ms sleep, recorded by
``jax.profiler`` on one v5e chip and cut down to the lines the reduction
reads (the device's ``XLA Ops`` and ``XLA Modules``, the host's ``bench.*``
spans and step dispatches), every event's name, start and duration as
recorded.  Reading it loads no TPU library.
"""
from __future__ import annotations

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import flops, trace  # noqa: E402
from benchlib.peaks import peaks_for  # noqa: E402

FIXTURE = os.path.join(HERE, "testdata", "train_step.xplane.pb")


def metric(name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_xplane(FIXTURE)


def test_window_busy_and_idle(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.019702734, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.006054157, rel=1e-9)
    # self times partition the busy time: nested events are not counted twice
    assert sum(v["s"] for v in reduced["ops"].values()) == pytest.approx(reduced["busy_s"])
    idle = metric("device_idle_frac")({"trace": reduced})
    assert idle == pytest.approx(100 * (1 - 0.006054157 / 0.019702734))


def test_kernel_time_and_roofline(reduced):
    flash = {k: v for k, v in reduced["ops"].items() if k.startswith("flash_attention")}
    assert flash == {"flash_attention.6": {"s": pytest.approx(0.001311031), "count": 2.0},
                     "flash_attention.7": {"s": pytest.approx(0.001346112), "count": 2.0}}
    rec = {"trace": reduced, "flash_fwd_call": flops.flash_fwd_cost(1, 1024, 14, 2, 64),
           "peaks": peaks_for("TPU v5 lite")}
    share = metric("flash_fwd_roofline")(rec)
    least = 4 * 64 * 14 * (1024 * 1025 / 2) / 197e12  # FLOPs bound this call
    assert share == pytest.approx(100 * 4 * least / (0.001311031 + 0.001346112))
    assert metric("flash_fwd_roofline")(dict(rec, trace={"ops": {}})) is None


def test_gaps_named_by_host_span(reduced):
    gaps = reduced["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps[:2]] == ["bench.batch_fn", "bench.batch_fn"]
    assert gaps[0][1] == pytest.approx(0.007228467)
    assert len(gaps) <= trace.TOP and len(reduced["breakdown"]["device_ops"]) <= trace.TOP
    assert reduced["breakdown"]["device_ops"][0][0] == "flash_attention.7"


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert trace.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    nested = [("while", 0, 10), ("a", 1, 3), ("b", 4, 9), ("c", 5, 6), ("d", 12, 13)]
    assert sorted(trace.self_times(nested)) == [
        ("a", 2), ("b", 4), ("c", 1), ("d", 1), ("while", 3)]
    spans = [("bench.batch_fn", 0, 5), ("bench.step_dispatch", 5, 6)]
    assert trace.name_gap((1, 6), spans) == "bench.batch_fn"
    assert trace.name_gap((7, 8), spans) == "host"
    assert trace.op_name("%fusion.12 = bf16[8] fusion(...)") == "fusion.12"


def test_step_mfu_and_data_wait():
    rec = {"steps": 4, "window_s": 2.0, "step_flops": 197e12, "chips": 1,
           "peaks": peaks_for("TPU v5 lite"), "batch_fn_s": [0.1, 0.3]}
    assert metric("step_mfu")(rec) == pytest.approx(200.0)
    assert metric("data_wait_ms")(rec) == pytest.approx(200.0)
    assert metric("data_wait_ms")(dict(rec, batch_fn_s=[])) is None
    with pytest.raises(ValueError):
        peaks_for("TPU v9")
