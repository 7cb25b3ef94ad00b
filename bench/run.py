"""Run one benchmark cell once, on the chips of the machine it starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the cell's
configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, the driver that mix names in
``bench/drivers/<driver>.py``, the limits of its comparison in
``bench/checks/<cell>.json``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

The run fails, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for.  Its last line on standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number the
comparison used, beside its limit.  The same numbers end standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` that this cell reports."""
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(2)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        fail(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    cell = cells[args.workload]
    config = load_json(HERE, "configs", f"{cell['config']}.json")
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    limits = load_json(HERE, "checks", f"{cell['name']}.json")["limits"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < cell["chips"]:
        fail(f"{cell['name']} needs {cell['chips']} chips, found {len(devices)}")
    devices = devices[: cell["chips"]]
    run_cell(args, bench, cell, config, traffic, limits, devices)


def run_cell(args, bench, cell, config, traffic, limits, devices) -> dict:
    """Drive the cell and print its result line; returns the result."""
    for path in (os.path.join(ROOT, "src"), HERE):
        if path not in sys.path:
            sys.path.insert(0, path)
    from benchlib import load
    from benchlib.peaks import peaks_for
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    driver = load("drivers", traffic["driver"])
    ctx = {"seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
           "cell": cell, "config": config, "traffic": traffic, "limits": limits,
           "devices": devices, "t_process": T_PROCESS}
    res = driver.run(ctx)
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics = {}
    if args.trace:
        record = dict(res["record"], chips=len(devices), peaks=peaks_for(kind))
        trace = record["trace"]
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            value = load("metrics", m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": res["end_to_end"][m["name"]], "unit": m["unit"]}
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace:
        out["breakdown"] = res["record"]["trace"]["breakdown"]
    out["compiles_in_window"] = res["compiles_in_window"]
    out["notes"] = res["notes"]
    out["checks"] = res["checks"]
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
