"""The training cells' comparison, driven on the CPU at a tiny size.

A whole run goes through ``run.run_cell`` with the look for a chip skipped:
set-up, window under the supervisor, closing save, reference and judgement,
with the cell's own limits.  The timed step is broken underneath in the ways
a one-chip training step can be (its state returned unchanged; half of the
batch left out, the mean taken over the rest) and ``correct`` must come out
false; unbroken it must come out true.  The control, the reference computed
with float8 matmuls in the program's place, must fail the limits too.
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

_spec = importlib.util.spec_from_file_location("bench_run", os.path.join(HERE, "run.py"))
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)

CELL = "qwen2-0.5b.train-8k"
TINY = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
        "vocab_size": 512}
PROGRAM = {"d_model": 128, "d_ff": 256, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
           "n_layers": 2, "vocab_size": 512, "loss_chunk": 128, "attn_chunk": 128}


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Keep this test's CPU programs out of the checkout's compile cache."""
    from repro.launch import cache

    monkeypatch.setattr(cache, "enable_compile_cache", lambda: None)


def tiny_cell():
    bench = bench_run.load_json(os.path.dirname(HERE), "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    config = bench_run.load_json(HERE, "configs", f"{cell['config']}.json")
    config["model"].update(TINY)
    config["program"]["overrides"] = dict(config["program"]["overrides"], **PROGRAM)
    traffic = dict(bench_run.load_json(HERE, "traffic", f"{cell['traffic']}.json"),
                   seq_len=256, batch=4)
    limits = bench_run.load_json(HERE, "checks", f"{CELL}.json")["limits"]
    return bench, cell, config, traffic, limits


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
def test_run_judges_the_timed_step(fault, monkeypatch, capsys):
    import jax

    from repro.training import step as step_mod

    if fault is not None:
        real = step_mod.make_train_step
        monkeypatch.setattr(step_mod, "make_train_step",
                            lambda cfg, tcfg: fault(real(cfg, tcfg)))
    bench, cell, config, traffic, limits = tiny_cell()
    args = argparse.Namespace(seed=2**31 + 11, seconds=1.0, trace=0)
    out = bench_run.run_cell(args, bench, cell, config, traffic, limits,
                             jax.devices()[:1])
    assert out["correct"] is (fault is None), out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "ckpt_stall_s", "setup_s"}
    assert out["attempted"] >= 1 and out["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"
    assert capsys.readouterr().err.rstrip().splitlines()[-1].startswith("check ")


def test_control_fails_the_limits():
    from benchlib import compare, data, load

    train = load("drivers", "train")
    _, _, config, traffic, limits = tiny_cell()
    source = data.SyntheticLM(config["model"]["vocab_size"], traffic["seq_len"],
                              traffic["batch"], 5)
    ref = train.reference_readings(config, traffic, 5, source)
    control = train.reference_readings(config, traffic, 5, source, precision="fp8")
    correct, checks = compare.judge(compare.gaps(control, ref), limits)
    assert not correct, checks
