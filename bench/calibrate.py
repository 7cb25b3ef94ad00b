"""Readings that the limits of a training cell's comparison are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --control-seeds 4,5,6
    python bench/calibrate.py --workload <cell> --readings <saved output>

In one process on the cell's chip, at the cell's own sizes, for each seed:

* ``program``: the program's three checked steps (the window's step and
  feed, as a run makes them) against the float32 reference;
* ``control`` (``--control-seeds``): the reference computed with float8
  matmuls, put in the program's place;
* ``half_batch`` (the same seeds): the reference taking half of each batch's
  rows, the mean over them alone, in the program's place.

A step that returns its state unchanged reads 1 on ``grad_gap`` and
``change_gap`` by their definition and needs no run.  Prints one JSON line
per reading, with ``correct`` as the cell's committed limits
(``bench/checks/<cell>.json``) judge it, and, last, the largest program
reading and the smallest control and fault readings of each number.
``--readings`` judges the reading lines of a saved output again, against
the limits as they now stand, without a chip.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import run as bench_run  # noqa: E402
from benchlib import load  # noqa: E402

NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def readings(ctx: dict, seeds, control_seeds, emit=print) -> list[dict]:
    from benchlib import compare

    driver = load("drivers", ctx["traffic"]["driver"])
    out = []

    def record(kind, seed, prog, ref):
        g = compare.gaps(prog, ref)
        rec = {"kind": kind, "seed": seed, **{k: g[k] for k in NUMBERS},
               "correct": compare.judge(g, ctx["limits"])[0],
               "leaf": g["leaf"], "losses": prog["losses"], "ref_losses": ref["losses"]}
        out.append(rec)
        emit(json.dumps(rec))

    for seed in seeds:
        t0 = time.perf_counter()
        ctx = dict(ctx, seed=seed)
        state, step_call, feed = driver.build(ctx)
        state, prog = driver.program_readings(step_call, feed, state,
                                              ctx["traffic"]["optimizer"],
                                              ctx["config"]["reference"])
        del state, step_call
        gc.collect()
        t1 = time.perf_counter()
        ref = driver.reference_readings(ctx["config"], ctx["traffic"], seed, feed.source)
        emit(json.dumps({"seed": seed, "program_s": t1 - t0,
                         "reference_s": time.perf_counter() - t1}))
        record("program", seed, prog, ref)
        if seed in control_seeds:
            half = list(range(ctx["traffic"]["batch"] // 2))
            for kind, kw in (("control", {"precision": "fp8"}),
                             ("half_batch", {"rows": half})):
                bad = driver.reference_readings(ctx["config"], ctx["traffic"], seed,
                                                 feed.source, **kw)
                record(kind, seed, bad, ref)
    return out


def summary(recs: list[dict]) -> dict:
    def pick(kind, fn):
        vals = [r for r in recs if r["kind"] == kind]
        return {k: fn(r[k] for r in vals) for k in NUMBERS} if vals else None

    def verdicts(kind):
        return {r["seed"]: r["correct"] for r in recs if r["kind"] == kind}

    return {"lower": pick("program", max), "control": pick("control", min),
            "half_batch": pick("half_batch", min),
            "unchanged_state": {"loss_gap": None, "grad_gap": 1.0, "change_gap": 1.0},
            "correct": {k: verdicts(k) for k in ("program", "control", "half_batch")}}


def rejudge(path: str, limits: dict) -> list[dict]:
    """The reading lines of a saved output, judged against ``limits``."""
    from benchlib import compare

    recs = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line) if line.startswith("{") else {}
            if "kind" in rec:
                rec["correct"] = compare.judge(rec, limits)[0]
                recs.append(rec)
                print(json.dumps({k: rec[k] for k in ("kind", "seed", *NUMBERS, "correct")}))
    return recs


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--readings", help="a saved output to judge again, without a chip")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    bench = bench_run.load_json(os.path.dirname(HERE), "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    limits = bench_run.load_json(HERE, "checks", f"{cell['name']}.json")["limits"]
    if args.readings:
        print(json.dumps({"summary": summary(rejudge(args.readings, limits))}))
        return
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        bench_run.fail(f"no TPU: JAX found {devices[0].platform}")
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    ctx = {"cell": cell, "devices": devices[: cell["chips"]], "trace": False,
           "limits": limits,
           "config": bench_run.load_json(HERE, "configs", f"{cell['config']}.json"),
           "traffic": bench_run.load_json(HERE, "traffic", f"{cell['traffic']}.json")}
    recs = readings(ctx, seeds + sorted(control - set(seeds)), control)
    print(json.dumps({"summary": summary(recs)}))


if __name__ == "__main__":
    main()
