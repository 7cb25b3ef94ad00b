"""The MoE training cell, driven on the CPU at a tiny size, and its readers.

As ``test_bench_check.py`` does for the dense cells: a whole run of
``deepseek-moe-16b.train-4k`` through ``run.run_cell`` with the look for a
chip skipped, at tiny widths (the program's config reduced, the reference
given the same sizes).  The cell's limits were read on the chip at its
published widths; at these widths the bf16 program strays further from the
f32 reference (its change reads 1.4e-3 here against 3.05e-4 at most there),
so this test judges with ``TINY_LIMITS``, read here as the cell's were,
from the weights of the traffic's ``model_seed``: between the program's
largest readings (seeds 2**31 + 7, 11, 12: 7.4e-5, 2.3e-3, 1.4e-3) and the
float8 control's least (seeds 5, 6, 7: 1.4e-4, 9.1e-3, 5.0e-3; seed 5's
loss 3.8e-4).  Unbroken, the program
must match ``references/moe_lm.py`` on the losses, the first gradient's
norms and the parameters' change, and the checked steps' routed rows must
equal the reference's count of pairs on held experts but for routing ties;
a step that returns
its state unchanged, or leaves half of the batch out, must be judged not
correct, and so must the float8 control, on every number.

Then the readers: ``moe_flops`` counts the routed experts' FLOPs from the
counted rows, and ``moe_gmm_roofline`` reads the grouped matmuls' events of
a synthetic trace, and nothing where the trace has none.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import os
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

_spec = importlib.util.spec_from_file_location("bench_run", os.path.join(HERE, "run.py"))
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)

CELL = "deepseek-moe-16b.train-4k"
TINY = {"hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_hidden_layers": 3, "vocab_size": 256, "router_experts": 16,
        "n_routed_experts": 4, "held_first_expert": 4}
TINY_LIMITS = {"loss_gap": 1.5e-4, "grad_gap": 5e-3, "change_gap": 2.5e-3}
PROGRAM = {"d_model": 64, "d_ff": 160, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
           "n_layers": 3, "vocab_size": 256, "loss_chunk": 128, "attn_chunk": 128}


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Keep this test's CPU programs out of the checkout's compile cache."""
    from repro.launch import cache

    monkeypatch.setattr(cache, "enable_compile_cache", lambda: None)


def tiny_cell():
    from repro.configs import get_config

    bench = bench_run.load_json(os.path.dirname(HERE), "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    config = bench_run.load_json(HERE, "configs", f"{cell['config']}.json")
    config["model"].update(TINY)
    moe = dataclasses.replace(get_config(config["program"]["arch"]).moe, n_experts=16,
                              d_expert=32, held_first=4, n_held=4)
    config["program"]["overrides"] = dict(PROGRAM, moe=moe)
    traffic = dict(bench_run.load_json(HERE, "traffic", f"{cell['traffic']}.json"),
                   seq_len=128, batch=4)
    config["rows_per_step"] = traffic["batch"]
    assert set(TINY_LIMITS) == set(
        bench_run.load_json(HERE, "checks", f"{CELL}.json")["limits"])
    return bench, cell, config, traffic, TINY_LIMITS


def _unchanged_state(step):
    def broken(state, batch):
        return state, step(state, batch)[1]
    return broken


def _half_batch(step):
    def broken(state, batch):
        return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    return broken


@pytest.mark.parametrize("fault", [None, _unchanged_state, _half_batch],
                         ids=["sound", "unchanged_state", "half_batch"])
def test_run_judges_the_timed_step(fault, monkeypatch):
    import jax

    from benchlib import load
    from repro.training import step as step_mod

    if fault is not None:
        real = step_mod.make_train_step
        monkeypatch.setattr(step_mod, "make_train_step",
                            lambda cfg, tcfg: fault(real(cfg, tcfg)))
    bench, cell, config, traffic, limits = tiny_cell()
    args = argparse.Namespace(seed=2**31 + 7, seconds=1.0, trace=0)
    out = bench_run.run_cell(args, bench, cell, config, traffic, limits,
                             jax.devices()[:1])
    assert out["correct"] is (fault is None), out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "ckpt_stall_s", "setup_s"}
    assert out["attempted"] >= 1 and out["compiles_in_window"] == 0
    # the stall is the median of the closing saves, each timed alone
    stalls = out["notes"]["ckpt_stalls"]
    assert len(stalls) == load("drivers", "train_moe").SAVES
    assert out["metrics"]["ckpt_stall_s"]["value"] == statistics.median(stalls)
    rows = out["notes"]["moe_rows"]
    if fault is None:
        # no pair is dropped: the pairs computed are those the reference routes
        # to held experts, but for the tokens whose top-k choice the bf16
        # program and the f32 reference break differently (routing ties)
        for got, want in zip(rows["program"], rows["reference"]):
            assert want > 0 and abs(got - want) <= 0.005 * want, rows


def test_rows_take_the_chain_from_the_model_seed():
    from benchlib import load

    rows = load("drivers", "train_moe").Rows
    a, b, c = rows(256, 64, 2, 11, 0), rows(256, 64, 2, 12, 0), rows(256, 64, 2, 11, 1)
    assert (a.mult, a.add) == (b.mult, b.add) != (c.mult, c.add)
    assert (a.batch(0)["tokens"] != b.batch(0)["tokens"]).any()
    assert (rows(256, 64, 2, 11, 0).batch(3)["tokens"] == a.batch(3)["tokens"]).all()


def test_program_config_checks_the_rows():
    from benchlib import load

    driver = load("drivers", "train_moe")
    _, _, config, traffic, _ = tiny_cell()
    assert driver.program_config(config, traffic).moe.held == (4, 4)
    with pytest.raises(SystemExit):
        driver.program_config(config, dict(traffic, batch=traffic["batch"] + 1))


def test_control_fails_the_limits():
    from benchlib import compare, load

    driver = load("drivers", "train_moe")
    _, _, config, traffic, limits = tiny_cell()
    source = driver.Rows(config["model"]["vocab_size"], traffic["seq_len"],
                         traffic["batch"], 5, traffic["model_seed"])
    ref = driver.reference_readings(config, traffic, 5, source)
    control = driver.reference_readings(config, traffic, 5, source, precision="fp8")
    correct, checks = compare.judge(compare.gaps(control, ref), limits)
    assert not correct, checks
    assert all(c["value"] > c["limit"] for c in checks.values()), checks


def test_step_flops_count_routed_rows():
    from benchlib import moe_flops

    _, _, config, traffic, _ = tiny_cell()
    m = config["model"]
    B, S = traffic["batch"], traffic["seq_len"]
    base = moe_flops.train_step_flops(m, B, S, rows=0)
    more = moe_flops.train_step_flops(m, B, S, rows=1000)
    assert more - base == pytest.approx(6.0 * 3 * 64 * 32 * 1000)
    flops, nbytes = moe_flops.gmm_cost(m, rows=100)
    assert flops == 2.0 * 100 * 64 * 32
    assert nbytes == 2 * (100 * (64 + 32) + 4 * 64 * 32)


def _record(ops):
    from benchlib import moe_flops

    m = tiny_cell()[2]["model"]
    return {"trace": {"ops": ops}, "moe_gmm_call": moe_flops.gmm_cost(m, rows=1e6),
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def metric(name: str):
    from benchlib import load

    return load("metrics", name).read


def test_moe_gmm_roofline_reads_the_products():
    read = metric("moe_gmm_roofline")
    # at these widths a call is bound by its bytes
    least = 2 * (1e6 * (64 + 32) + 4 * 64 * 32) / 819e9
    ops = {"moe_gmm_fwd.3": {"s": 2 * least, "count": 2.0},
           "transpose_jvp_jit_moe_gmm_dx___.1": {"s": least, "count": 1.0},
           "moe_gmm_dw.1": {"s": 4 * least, "count": 1.0},
           "fusion.7": {"s": 1.0, "count": 1.0}}
    assert read(_record(ops)) == pytest.approx(100.0 * 4 / 7)


def test_moe_gmm_roofline_is_silent_without_the_kernel():
    read = metric("moe_gmm_roofline")
    assert read(_record({"fusion.7": {"s": 1.0, "count": 3.0}})) is None
    record = _record({"moe_gmm_fwd.3": {"s": 1.0, "count": 3.0}})
    del record["moe_gmm_call"]  # a program that counts no rows
    assert read(record) is None
