"""Serving driver: continuous-batching engine over synthetic requests.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \\
      --requests 12 --max-new 16

Observability (repro.trace): --trace-out t.json snapshots the whole run —
events, dispatch decisions, measured profiles, chip + git metadata — for
`python -m repro.trace {report,export,diff}`; --trace-dir D streams events
durably as rotated JSONL segments while the server runs (a crash loses at
most the open segment; `python -m repro.trace compact D` recovers);
--profile-in warm-starts the profiled dispatcher from a previous session
(skips exploration; entries stamped with a different git SHA or chip are
aged out first); --profile-out writes the bare ProfileStore for the next run.

Fleet mode (repro.fleet): --fleet <url|dir> pulls the best matching profile
snapshot at startup (exact (git SHA, chip) match, falling back through
chip-only to nothing — stale-stamped entries re-explore), pushes measured
deltas at shutdown, and — with --trace-dir — at every streaming rotation, so
a long-lived server continuously feeds the central store.

Live metrics (repro.metrics): --metrics-port P scrapes Prometheus text at
http://127.0.0.1:P/metrics while the server runs; --trace-overhead-budget-pct
B starts the adaptive controller, which self-measures record-path overhead
and duty-cycles span capture to keep it under B% (0 = always-on: measure but
never shed).  Either flag activates the controller; metric snapshots land in
--trace-dir at every rotation and in the final JSON under "metrics".

Live device profiling (repro.trace.liveprof): --jax-profile DIR runs
jax.profiler capture in duty-cycled windows under a second, device-specific
budget loop sharing --trace-overhead-budget-pct (budget 0 = one calibration
window then measure-only); each closed window is parsed, span-aligned and
merged into the live trace/stream, and feeds repro_device_* series on
/metrics.  --jax-profile-backend synthetic exercises the same path with no
accelerator (CI); on CPU-only jax the real backend degrades gracefully with
one warning.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.dispatch import DispatchConfig, Dispatcher
from repro.launch.cache import enable_compile_cache
from repro.models import lm
from repro.serving.engine import Engine, ServeConfig
from repro.trace import (
    Session,
    StreamingSession,
    TraceCollector,
    age_out_profiles,
    load_profile_stores,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--dispatch", choices=("off", "static", "roofline", "profiled"), default="off",
        help="profile-guided backend placement for prefill/decode (repro.dispatch)",
    )
    ap.add_argument("--dispatch-backend", default="chunked",
                    help="backend pinned by --dispatch static")
    ap.add_argument("--tune", choices=("off", "cached", "sweep"), default="off",
                    help="kernel autotuning (repro.tune): cached applies "
                         "winners already in the profile store (e.g. fleet-"
                         "pulled) with zero sweep cost; sweep measures "
                         "missing design-space points first")
    ap.add_argument("--tune-ops", default=None, metavar="OP[,OP]",
                    help="restrict --tune sweep to these ops")
    ap.add_argument("--tune-mode", choices=("real", "interpret", "synthetic"),
                    default="interpret",
                    help="sweep measurement mode (synthetic = model-only, CI)")
    ap.add_argument("--tune-workers", type=int, default=0, metavar="N",
                    help="sweep worker processes (0 = in-process)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a repro.trace session snapshot of this run")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="stream events durably as rotated JSONL segments "
                         "(crash loses at most the open segment; recover with "
                         "`python -m repro.trace compact DIR`)")
    ap.add_argument("--trace-rotate", type=int, default=2048, metavar="N",
                    help="events per streaming segment before rotation+fsync")
    ap.add_argument("--trace-rotate-keep", type=int, default=None, metavar="N",
                    help="segment retention: delete the oldest closed segments "
                         "past N so --trace-dir stays bounded on long runs")
    ap.add_argument("--fleet", default=None, metavar="URL|DIR",
                    help="central profile service (repro.fleet): pull matching "
                         "profiles at startup, push measured deltas at "
                         "shutdown and every streaming rotation")
    ap.add_argument("--fleet-token", default=None, metavar="TOKEN",
                    help="bearer token for a --token-protected fleet daemon")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="trace ring-buffer capacity (events); evictions are counted")
    ap.add_argument("--profile-in", action="append", default=None, metavar="PATH",
                    help="warm-start dispatch profiles from a session/store JSON "
                         "(repeatable; multiple files are merged)")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="write the measured ProfileStore for the next run")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve Prometheus /metrics on this port while the "
                         "run is live (0 picks a free port)")
    ap.add_argument("--trace-overhead-budget-pct", type=float, default=None,
                    metavar="PCT",
                    help="adaptive tracing: duty-cycle span capture to keep "
                         "self-measured record-path overhead under PCT%% "
                         "(0 = always-on: measure, never shed; default 5 "
                         "when --metrics-port is given)")
    ap.add_argument("--ready-file", default=None, metavar="PATH",
                    help="announce the /metrics URL here once the listener "
                         "is up (requires --metrics-port; shared handshake "
                         "with repro.fleet serve and repro.router)")
    ap.add_argument("--metrics-linger-s", type=float, default=0.0, metavar="S",
                    help="keep the /metrics listener up S seconds after the "
                         "run completes (scrape windows for CI/cron)")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="live device profiling: duty-cycled jax.profiler "
                         "capture windows dumped under DIR, parsed and merged "
                         "into the live trace under the overhead budget")
    ap.add_argument("--jax-profile-backend", default="auto",
                    choices=("auto", "jax", "synthetic"),
                    help="profiler backend: jax.profiler (auto/jax; degrades "
                         "gracefully without one) or the synthetic CI stub")
    ap.add_argument("--jax-profile-period-s", type=float, default=2.0,
                    metavar="S", help="device capture window period (on+off)")
    args = ap.parse_args()
    if args.fleet and args.dispatch == "off":
        # a fleet-less run would silently neither warm-start nor push
        ap.error("--fleet requires --dispatch (static|roofline|profiled)")
    if args.tune != "off" and args.dispatch == "off":
        # tune winners live in the dispatcher's profile store
        ap.error("--tune requires --dispatch (static|roofline|profiled)")
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = lm.init_params(cfg, key)
    log = TraceCollector(capacity=args.trace_capacity)
    # metrics plane: always attached (near-zero cost, exact counts even under
    # shedding); the controller only runs when explicitly asked for, so plain
    # traced runs keep today's always-on capture behaviour
    from repro.metrics import (
        DEFAULT_BUDGET_PCT,
        AdaptiveController,
        MetricsPlane,
        serve_metrics,
    )

    plane = MetricsPlane(log)
    controller = mserver = None
    if args.metrics_port is not None or args.trace_overhead_budget_pct is not None:
        budget = (DEFAULT_BUDGET_PCT if args.trace_overhead_budget_pct is None
                  else args.trace_overhead_budget_pct)
        controller = AdaptiveController(log, plane.registry,
                                        budget_pct=budget).start()
    if args.metrics_port is not None:
        mserver = serve_metrics(plane, port=args.metrics_port)
        import sys

        print(f"metrics: {mserver.url}/metrics", file=sys.stderr)
        if args.ready_file:
            from repro.utils.ready import write_ready_file

            write_ready_file(args.ready_file, mserver.url)
    elif args.ready_file:
        ap.error("--ready-file requires --metrics-port (nothing to announce)")
    prof = None
    if args.jax_profile:
        from repro.trace.liveprof import LiveDeviceProfiler

        prof = LiveDeviceProfiler(
            log, args.jax_profile,
            registry=plane.registry,
            backend=args.jax_profile_backend,
            budget_pct=(DEFAULT_BUDGET_PCT
                        if args.trace_overhead_budget_pct is None
                        else args.trace_overhead_budget_pct),
            period_s=args.jax_profile_period_s,
        )
    dispatcher = None
    aged = []
    if args.dispatch != "off":
        store = load_profile_stores(args.profile_in) if args.profile_in else None
        dispatcher = Dispatcher(
            DispatchConfig(policy=args.dispatch, static_backend=args.dispatch_backend),
            log=log,
            store=store,
        )
        if args.profile_in:
            aged = age_out_profiles(dispatcher.store, dispatcher.chip.name)
    fleet_rec = pusher = None
    run_meta = {"driver": "serve", "arch": cfg.name, "requests": args.requests}
    if args.fleet and dispatcher is not None:
        from repro.fleet import warm_start_from_fleet

        fleet_rec, pusher = warm_start_from_fleet(args.fleet, dispatcher,
                                                  token=args.fleet_token)
        # recorded in session/manifest metadata: push-profiles refuses to
        # re-push artifacts of runs that already fed a fleet live
        run_meta["fleet"] = args.fleet
    tune_rec = None
    if args.tune != "off" and dispatcher is not None:
        # after the fleet pull (pulled config points make sweep points warm
        # — a fed fleet means sweep_points == 0) and before the engine
        # compiles its variants (winners must be installed before jit traces
        # them); sweep samples land in dispatcher.store, so the pusher
        # delta-pushes tuned winners at shutdown like any other measurement
        from repro.tune import driver_tune

        tune_rec = driver_tune(
            args.tune, dispatcher, log,
            ops_filter=args.tune_ops.split(",") if args.tune_ops else None,
            mode=args.tune_mode, workers=args.tune_workers,
        )
    stream = None
    if args.trace_dir:
        stream = StreamingSession(
            args.trace_dir,
            rotate_events=args.trace_rotate,
            max_segments=args.trace_rotate_keep,
            meta=run_meta,
            store_provider=(lambda: dispatcher.store) if dispatcher is not None else None,
            fleet_push=pusher.push if pusher is not None else None,
            metrics_provider=plane.snapshot,
            device_provider=prof.snapshot if prof is not None else None,
        ).attach(log)
    eng = Engine(
        cfg,
        params,
        ServeConfig(
            max_batch=args.max_batch,
            max_seq=args.max_seq,
            temperature=args.temperature,
            seed=args.seed,
        ),
        log=log,
        dispatcher=dispatcher,
        metrics=plane.registry,
    )
    rng = np.random.default_rng(args.seed)
    if prof is not None:
        prof.start()
    t0 = time.time()
    # root span of the whole run: every request (and transitively every
    # prefill/dispatch) nests under it in report --tree and the exporters
    with log.lifecycle("serve_run", {"arch": cfg.name, "requests": args.requests}):
        for _ in range(args.requests):
            prompt = rng.integers(0, cfg.vocab_size, args.prompt_len).tolist()
            eng.submit(prompt, max_new=args.max_new)
        results = eng.run_to_completion()
    wall = time.time() - t0
    if prof is not None:
        prof.stop()  # force-closes the open window: short runs still merge
    total_new = sum(len(v) for v in results.values())
    durations = log.durations("prefill")
    rec = {
        "arch": cfg.name,
        "requests": len(results),
        "generated_tokens": total_new,
        "tokens_per_s": round(total_new / wall, 1),
        "mean_prefill_ms": round(1e3 * float(np.mean(durations)), 2) if durations else None,
        "wall_s": round(wall, 2),
        "sample": results[min(results)][:8],
    }
    if dispatcher is not None:
        rec["dispatch"] = dispatcher.summary()
        rec["dispatch_events"] = len(log.events(kind="dispatch"))
        if args.profile_in:
            rec["profile_in"] = args.profile_in
            rec["profile_aged_out"] = len(aged)
    if tune_rec is not None:
        rec["tune"] = tune_rec
    if controller is not None:
        controller.stop()  # final overhead reading lands in the gauges
        rec["trace_controller"] = controller.snapshot()
    if prof is not None:
        rec["device_capture"] = prof.snapshot()
        run_meta["device_capture"] = rec["device_capture"]
    rec["metrics"] = plane.summary()
    trace_stats = log.stats()  # stats() resolves spans; compute once
    rec["trace"] = trace_stats
    if stream is not None:
        rec["trace_dir"] = stream.close(stats=trace_stats)
    if pusher is not None:
        final = pusher.push()  # remaining delta (no-op if a rotation covered it)
        fleet_rec["push"] = {"pushed_samples": pusher.pushed_samples}
        if "error" in final:
            fleet_rec["push"]["error"] = final["error"]
    if fleet_rec is not None:
        rec["fleet"] = fleet_rec
    if args.trace_out:
        sess = Session.capture(log, dispatcher=dispatcher,
                               meta={**run_meta, "metrics": plane.snapshot(),
                                     "drops": log.drop_counters()},
                               collector_stats=trace_stats)
        rec["trace_out"] = sess.save(args.trace_out)
    if args.profile_out and dispatcher is not None:
        doc = json.loads(dispatcher.store.to_json())
        if args.fleet:
            # marks the artifact as already fed to a fleet live, so
            # push-profiles refuses to double-count it later
            doc["fleet"] = args.fleet
        with open(args.profile_out, "w") as f:
            json.dump(doc, f, indent=1)
        rec["profile_out"] = args.profile_out
    print(json.dumps(rec), flush=True)
    if mserver is not None:
        if args.metrics_linger_s > 0:
            # the run JSON is already out (flushed): scrapers poll for it,
            # then hit /metrics while we linger
            time.sleep(args.metrics_linger_s)
        mserver.stop()


if __name__ == "__main__":
    main()
