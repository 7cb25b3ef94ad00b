"""Training driver.

Local smoke (1 device, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced --steps 20

Real sharded execution on N host devices (exercises the same pjit path as TPU):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --mesh 2x4 --steps 20 --batch 8

Fault-tolerance demo: --fail-at 7,17 injects node failures; the supervisor
restarts from the latest checkpoint and replays deterministically.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dispatch import DispatchConfig, Dispatcher, with_impl
from repro.distributed import sharding as shd
from repro.launch.cache import enable_compile_cache
from repro.runtime.supervisor import FailureInjector, Supervisor, SupervisorConfig
from repro.trace import (
    Session,
    StreamingSession,
    TraceCollector,
    age_out_profiles,
    load_profile_stores,
)
from repro.training.step import (
    TrainConfig,
    abstract_train_state,
    init_train_state,
    make_train_step,
    train_state_axes,
)


def build_mesh(spec: str) -> Mesh:
    dims = tuple(int(x) for x in spec.split("x"))
    n = int(np.prod(dims))
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(
            f"mesh {spec} needs {n} devices, have {len(devs)}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return Mesh(np.asarray(devs[:n]).reshape(dims), axes)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1", help="e.g. 2x4 = data2 x model4")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", default="", help="comma list of steps to inject failures")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--dispatch", choices=("off", "static", "roofline", "profiled"), default="off",
        help="profile-guided kernel-backend placement per train step (repro.dispatch)",
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
    import dataclasses

    from repro.training import optim

    tcfg = TrainConfig(
        opt=optim.AdamWConfig(peak_lr=args.lr, warmup_steps=max(10, args.steps // 10),
                              total_steps=args.steps),
        microbatches=args.microbatches,
    )
    mesh = build_mesh(args.mesh)
    rules = shd.DEFAULT_RULES
    key = jax.random.PRNGKey(args.seed)

    with mesh:
        state_abs = abstract_train_state(cfg, tcfg)
        state_shd = shd.tree_shardings(train_state_axes(cfg), state_abs, rules.param, mesh)
        init_jit = jax.jit(
            lambda k: init_train_state(cfg, tcfg, k), out_shardings=state_shd
        )
        state = init_jit(key)
        step_fn = jax.jit(
            make_train_step(cfg, tcfg),
            in_shardings=(state_shd, None),
            out_shardings=(state_shd, None),
            donate_argnums=(0,),
        )
        dispatcher = None
        step_variants = None
        aged = []
        if args.dispatch != "off":
            store = load_profile_stores(args.profile_in) if args.profile_in else None
            dispatcher = Dispatcher(
                DispatchConfig(policy=args.dispatch, static_backend=args.dispatch_backend),
                store=store,
            )
            if args.profile_in:
                aged = age_out_profiles(dispatcher.store, dispatcher.chip.name)
            step_variants = {
                t.name: jax.jit(
                    with_impl(t.impl, make_train_step(cfg, tcfg)),
                    in_shardings=(state_shd, None),
                    out_shardings=(state_shd, None),
                    donate_argnums=(0,),
                )
                for t in dispatcher.registry.targets()
            }
        fleet_rec = pusher = None
        run_meta = {"driver": "train", "arch": cfg.name, "mesh": args.mesh,
                    "steps": args.steps}
        if args.fleet and dispatcher is not None:
            from repro.fleet import warm_start_from_fleet

            fleet_rec, pusher = warm_start_from_fleet(args.fleet, dispatcher,
                                                      token=args.fleet_token)
            # recorded in session/manifest metadata: push-profiles refuses to
            # re-push artifacts of runs that already fed a fleet live
            run_meta["fleet"] = args.fleet

        data = SyntheticLM(
            DataConfig(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
        )

        def batch_fn(i):
            b = data.batch(i)
            return {k: jnp.asarray(v) for k, v in b.items()}

        log = TraceCollector(capacity=args.trace_capacity)
        if dispatcher is not None:
            dispatcher.log = log
        tune_rec = None
        if args.tune != "off" and dispatcher is not None:
            # after the fleet pull (pulled config points make sweep points
            # warm — a fed fleet means sweep_points == 0) and before the
            # first step traces the jitted variants (winners must be
            # installed first); sweep samples land in dispatcher.store, so
            # the pusher delta-pushes tuned winners like any measurement
            from repro.tune import driver_tune

            tune_rec = driver_tune(
                args.tune, dispatcher, log,
                ops_filter=args.tune_ops.split(",") if args.tune_ops else None,
                mode=args.tune_mode, workers=args.tune_workers,
            )
        from repro.metrics import (
            DEFAULT_BUDGET_PCT,
            AdaptiveController,
            MetricsPlane,
            serve_metrics,
        )

        plane = MetricsPlane(log)
        controller = mserver = None
        if (args.metrics_port is not None
                or args.trace_overhead_budget_pct is not None):
            budget = (DEFAULT_BUDGET_PCT
                      if args.trace_overhead_budget_pct is None
                      else args.trace_overhead_budget_pct)
            controller = AdaptiveController(log, plane.registry,
                                            budget_pct=budget).start()
        if args.metrics_port is not None:
            import sys

            mserver = serve_metrics(plane, port=args.metrics_port)
            print(f"metrics: {mserver.url}/metrics", file=sys.stderr)
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
        fail_at = tuple(int(s) for s in args.fail_at.split(",") if s)
        sup = Supervisor(
            SupervisorConfig(
                ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every,
                max_steps=args.steps,
            ),
            step_fn,
            batch_fn,
            state,
            state_shardings=state_shd,
            log=log,
            failures=FailureInjector(fail_at),
            dispatcher=dispatcher,
            step_variants=step_variants,
            stream=stream,
        )
        if prof is not None:
            prof.start()
        t0 = time.time()
        # root span: steps (and their checkpoint/dispatch children) nest
        # under the run in report --tree and the exporters
        with log.lifecycle("train_run", {"arch": cfg.name, "mesh": args.mesh}):
            out = sup.run()
        wall = time.time() - t0
        if prof is not None:
            prof.stop()  # force-closes the open window: short runs still merge

    losses = [float(m["loss"]) for m in out["metrics"]]
    tok_per_step = args.batch * args.seq
    rec = {
        "arch": cfg.name,
        "mesh": args.mesh,
        "steps": out["steps"],
        "restarts": out["restarts"],
        "stragglers": out["stragglers"],
        "first_loss": round(losses[0], 4),
        "last_loss": round(losses[-1], 4),
        "tokens_per_s": round(out["steps"] * tok_per_step / wall),
        "wall_s": round(wall, 1),
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
        mserver.stop()


if __name__ == "__main__":
    main()
