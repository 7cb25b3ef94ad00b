"""One serve replica: an HTTP front over a continuous-batching engine.

``python -m repro.router.replica`` turns the batch-driven
:class:`repro.serving.engine.Engine` into a long-lived process the router can
spawn, poll and route to:

* ``POST /v1/generate`` ``{"prompt": [...], "max_new": N}`` — submit one
  request and block until its tokens are ready (the engine keeps batching
  underneath: concurrent requests share decode ticks);
* ``GET /healthz`` — liveness + identity (pid, chip, git SHA) + occupancy;
* ``GET /metrics`` / ``/metrics.json`` — the replica's own metrics plane.

Startup follows the shared ready-file handshake (:mod:`repro.utils.ready`):
bind ``--port 0``, then atomically write a JSON ready file carrying the URL
plus the identity the router needs for fleet profile seeding.

``--synthetic`` swaps in :class:`SyntheticEngine` — same scheduling shape
(bounded slots, per-tick token production) with **deterministic** outputs
(:func:`expected_synthetic_tokens`) and a configurable per-tick sleep, and no
jax import anywhere.  That is what CI's router-smoke runs: a client can
recompute every expected token, so a request re-executed after a replica
SIGKILL is provably identical — exactly-once is verifiable, not assumed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional
from urllib.parse import urlparse

from repro.core.events import (EventLog, SpanContext, TRACEPARENT_HEADER,
                               current_span, next_span_id, span_scope)
from repro.metrics import MetricsPlane
from repro.trace import TraceCollector
from repro.utils.ready import write_ready_file

SYNTHETIC_VOCAB = 50257


def expected_synthetic_tokens(prompt: list[int], max_new: int) -> list[int]:
    """The tokens a synthetic replica will emit for ``prompt`` — any replica,
    any restart.  Clients recompute this to verify exactly-once retries."""
    seed = sum(prompt) % 65521
    return [(seed * 31 + i * 7 + 11) % SYNTHETIC_VOCAB for i in range(max_new)]


@dataclasses.dataclass
class _SynRequest:
    rid: int
    prompt: list[int]
    max_new: int
    out: list[int] = dataclasses.field(default_factory=list)
    span: int = 0
    parent: int = 0
    t_active: float = 0.0  # monotonic instant the request won a decode slot


class SyntheticEngine:
    """Engine-shaped synthetic server core: slots, ticks, deterministic tokens.

    Mirrors the real engine's client surface (``submit`` / ``step`` /
    ``pending``) and its request lifecycle events, but each decode tick
    sleeps ``ms_per_token`` instead of running a model — so scheduling,
    batching pressure and tail behaviour are exercised with zero accelerator
    (and zero jax import).
    """

    def __init__(self, *, max_batch: int = 4, ms_per_token: float = 2.0,
                 log: Optional[EventLog] = None,
                 metrics: Optional[Any] = None) -> None:
        self.max_batch = max_batch
        self.ms_per_token = ms_per_token
        self.log = log if log is not None else EventLog()
        self._lock = threading.Lock()
        self.queue: list[_SynRequest] = []
        self.active: list[Optional[_SynRequest]] = [None] * max_batch
        self._rid = 0
        self._g_queue = self._g_slots = None
        if metrics is not None:
            self._g_queue = metrics.gauge(
                "repro_serve_queue_depth", "requests waiting for a decode slot")
            self._g_slots = metrics.gauge(
                "repro_serve_active_slots", "occupied decode slots")

    def submit(self, prompt: list[int], max_new: int = 32) -> int:
        with self._lock:
            rid = self._rid
            self._rid += 1
            req = _SynRequest(rid, list(prompt), max_new,
                              span=next_span_id(), parent=current_span())
            self.queue.append(req)
            depth = len(self.queue)
        self.log.record("spawn", "request", req.rid, span=req.span,
                        parent=req.parent)
        if self._g_queue is not None:
            self._g_queue.set(depth)
        return rid

    def pending(self) -> int:
        with self._lock:
            return len(self.queue) + sum(r is not None for r in self.active)

    def step(self) -> list[_SynRequest]:
        with self._lock:
            for slot in range(self.max_batch):
                if self.active[slot] is None and self.queue:
                    req = self.queue.pop(0)
                    req.t_active = time.monotonic()
                    self.active[slot] = req
            live = [r for r in self.active if r is not None]
            if self._g_queue is not None:
                self._g_queue.set(len(self.queue))
                self._g_slots.set(len(live))
        if not live:
            return []
        if self.ms_per_token > 0:
            time.sleep(self.ms_per_token / 1e3)  # one shared "decode tick"
        finished: list[_SynRequest] = []
        with self._lock:
            for slot, r in enumerate(self.active):
                if r is None:
                    continue
                expected = expected_synthetic_tokens(r.prompt, r.max_new)
                r.out.append(expected[len(r.out)])
                if len(r.out) >= r.max_new:
                    self.active[slot] = None
                    finished.append(r)
            if finished and self._g_slots is not None:
                self._g_slots.set(sum(r is not None for r in self.active))
        for r in finished:
            self.log.record("exit", "request", r.rid, span=r.span,
                            parent=r.parent)
        return finished


class ReplicaServer:
    """HTTP serving wrapper around an engine (real or synthetic).

    One daemon engine-loop thread owns ``step()``; HTTP handler threads
    ``submit()`` (both engines are submit-thread-safe) and block on a shared
    condition until the loop publishes their rid's tokens.  Each handler
    opens an ``rpc`` span under the run root; the engine's request spawn/exit
    bracket nests inside it, so the replica's trace reads rpc → request →
    prefill → dispatch.  When the front door sent an ``X-Repro-Traceparent``
    header, the rpc span carries that :class:`SpanContext` as its *remote*
    parent — ``repro.trace stitch`` re-links it under the frontdoor's route
    span once both sessions are merged.
    """

    def __init__(self, engine: Any, *, name: str, log: EventLog,
                 plane: Optional[MetricsPlane] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 info: Optional[dict[str, Any]] = None) -> None:
        self.engine = engine
        self.name = name
        self.origin = f"{name}:{os.getpid()}"
        self.log = log
        self.plane = plane
        self.info = dict(info or {})
        self.completed = 0
        self._results: dict[int, Any] = {}  # rid -> finished request object
        self._cond = threading.Condition()
        self._stop = threading.Event()
        self.run_span = 0
        self._httpd = _ReplicaHTTPServer((host, port), _ReplicaHandler)
        self._httpd.replica = self
        self._loop_thread: Optional[threading.Thread] = None
        self._http_thread: Optional[threading.Thread] = None

    @property
    def url(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "ReplicaServer":
        # long-lived run root: every request span nests under it, mirroring
        # the driver's `with lifecycle("serve_run")` envelope
        self.run_span = next_span_id()
        self.log.record("spawn", "serve_run",
                        {"replica": self.name, **self.info}, span=self.run_span)
        self._loop_thread = threading.Thread(
            target=self._engine_loop, name=f"{self.name}-engine", daemon=True)
        self._loop_thread.start()
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, name=f"{self.name}-http",
            daemon=True)
        self._http_thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=5.0)
        self.log.record("exit", "serve_run",
                        {"replica": self.name, "completed": self.completed},
                        span=self.run_span)

    def _engine_loop(self) -> None:
        while not self._stop.is_set():
            if self.engine.pending() == 0:
                with self._cond:
                    self._cond.wait(timeout=0.02)
                continue
            finished = self.engine.step()
            if finished:
                now = time.monotonic()
                with self._cond:
                    for r in finished:
                        r.t_done = now  # plain dataclasses: setattr is fine
                        self._results[r.rid] = r
                        self.completed += 1
                    self._cond.notify_all()

    def submit_and_wait(self, prompt: list[int], max_new: int,
                        timeout_s: float = 120.0,
                        ctx: Optional[SpanContext] = None,
                        ) -> tuple[int, list[int], dict[str, Any]]:
        """Submit one request, block for its tokens; returns ``(rid, tokens,
        meta)`` where ``meta`` carries the rpc span id plus the queue/service
        split (``queue_ms`` = submit → decode-slot admission, ``service_ms``
        = admission → final token) the front door folds into its per-hop
        latency decomposition.
        """
        t_sub = time.monotonic()
        payload: dict[str, Any] = {"replica": self.name}
        if ctx is not None:
            payload["trace"] = ctx.trace
            payload["remote"] = ctx.to_payload()
        # the rpc span is this process's anchor for the cross-process chain:
        # locally it nests under the run root (single-session trees are
        # unchanged); its payload's "remote" ref names the frontdoor's route
        # span, and the engine's request bracket nests inside it
        with span_scope(self.run_span), \
                self.log.lifecycle("rpc", payload) as rpc_span:
            rid = self.engine.submit(prompt, max_new=max_new)
            with self._cond:
                self._cond.notify_all()  # wake the engine loop
                deadline = time.monotonic() + timeout_s
                while rid not in self._results:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0 or self._stop.is_set():
                        raise TimeoutError(
                            f"request {rid} not completed within {timeout_s}s")
                    self._cond.wait(timeout=min(remaining, 0.25))
                r = self._results.pop(rid)
            t_done = getattr(r, "t_done", time.monotonic())
            t_active = getattr(r, "t_active", 0.0) or t_done
            meta = {
                "span": rpc_span,
                "queue_ms": round(max(0.0, t_active - t_sub) * 1e3, 3),
                "service_ms": round(max(0.0, t_done - t_active) * 1e3, 3),
            }
            return rid, r.out, meta

    def health(self) -> dict[str, Any]:
        return {
            "ok": True,
            "replica": self.name,
            "pid": os.getpid(),
            "completed": self.completed,
            "pending": self.engine.pending(),
            **self.info,
        }


class _ReplicaHTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True
    replica: Any = None


class _ReplicaHandler(BaseHTTPRequestHandler):
    def log_message(self, fmt: str, *args: Any) -> None:
        pass

    def _send(self, code: int, doc: Any) -> None:
        body = json.dumps(doc, default=repr).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self) -> None:  # noqa: N802 (stdlib naming)
        path = urlparse(self.path).path
        rep = self.server.replica
        try:
            if path == "/healthz":
                self._send(200, rep.health())
            elif path == "/metrics" and rep.plane is not None:
                body = rep.plane.render().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif path == "/metrics.json" and rep.plane is not None:
                self._send(200, rep.plane.snapshot())
            else:
                self._send(404, {"error": "not found"})
        except Exception as exc:
            self._send(500, {"error": repr(exc)})

    def do_POST(self) -> None:  # noqa: N802 (stdlib naming)
        path = urlparse(self.path).path
        rep = self.server.replica
        if path != "/v1/generate":
            self._send(404, {"error": "not found"})
            return
        recv_unix = time.time()  # replica-side handshake stamp (wall clock)
        try:
            n = int(self.headers.get("Content-Length") or 0)
            body = json.loads(self.rfile.read(n) or b"{}")
            prompt = body.get("prompt")
            max_new = int(body.get("max_new", 16))
            if (not isinstance(prompt, list) or not prompt
                    or not all(isinstance(t, int) for t in prompt)):
                self._send(400, {"error": "prompt must be a non-empty list of ints"})
                return
            if max_new < 1:
                self._send(400, {"error": "max_new must be >= 1"})
                return
            ctx = SpanContext.extract(self.headers.get(TRACEPARENT_HEADER))
            t0 = time.perf_counter()
            rid, tokens, meta = rep.submit_and_wait(prompt, max_new, ctx=ctx)
            handler_ms = round((time.perf_counter() - t0) * 1e3, 3)
            self._send(200, {
                "rid": rid,
                "tokens": tokens,
                "replica": rep.name,
                "latency_ms": handler_ms,
                # everything the front door needs to decompose this hop and
                # to skew-correct this replica's clock at stitch time
                "ctx": {
                    "origin": rep.origin,
                    "span": meta["span"],
                    "trace": ctx.trace if ctx else None,
                    "recv_unix": recv_unix,
                    "sent_unix": time.time(),
                    "handler_ms": handler_ms,
                    "queue_ms": meta["queue_ms"],
                    "service_ms": meta["service_ms"],
                },
            })
        except TimeoutError as exc:
            self._send(504, {"error": str(exc)})
        except Exception as exc:
            self._send(500, {"error": repr(exc)})


def _build_real_engine(args: argparse.Namespace, log: EventLog,
                       plane: MetricsPlane) -> tuple[Any, dict[str, Any]]:
    """Construct a jax-backed Engine (imports deferred: synthetic replicas
    and the router process itself must never pay jax startup)."""
    import jax

    from repro.configs import get_config, reduced
    from repro.models import lm
    from repro.serving.engine import Engine, ServeConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    dispatcher = None
    info: dict[str, Any] = {"arch": cfg.name}
    if args.dispatch != "off":
        from repro.dispatch import DispatchConfig, Dispatcher

        dispatcher = Dispatcher(
            DispatchConfig(policy=args.dispatch,
                           static_backend=args.dispatch_backend),
            log=log)
        info["chip"] = dispatcher.chip.name
        if args.fleet:
            from repro.fleet import warm_start_from_fleet

            fleet_rec, _pusher = warm_start_from_fleet(
                args.fleet, dispatcher, token=args.fleet_token)
            info["fleet"] = fleet_rec
    engine = Engine(
        cfg, params,
        ServeConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                    seed=args.seed),
        log=log, dispatcher=dispatcher, metrics=plane.registry)
    return engine, info


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.router.replica", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--name", default=f"replica-{os.getpid()}")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (announced via --ready-file)")
    ap.add_argument("--ready-file", default=None, metavar="PATH",
                    help="announce the bound URL + identity here once serving")
    ap.add_argument("--synthetic", action="store_true",
                    help="deterministic no-accelerator engine (CI/tests)")
    ap.add_argument("--synthetic-ms-per-token", type=float, default=2.0,
                    metavar="MS", help="synthetic decode-tick sleep")
    ap.add_argument("--arch", default=None,
                    help="model config for a real engine (required unless "
                         "--synthetic)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--dispatch",
                    choices=("off", "static", "roofline", "profiled"),
                    default="off")
    ap.add_argument("--dispatch-backend", default="chunked")
    ap.add_argument("--fleet", default=None, metavar="URL|DIR",
                    help="warm-start dispatch profiles from a fleet target")
    ap.add_argument("--fleet-token", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir-root", default=None, metavar="DIR",
                    help="stream this replica's trace into DIR/<name>-<pid>/ "
                         "(a fresh dir per incarnation so supervisor restarts "
                         "never collide); the dir is announced in the ready "
                         "file for `repro.trace stitch` auto-discovery")
    ap.add_argument("--trace-rotate", type=int, default=2048, metavar="N",
                    help="events per streamed segment")
    args = ap.parse_args(argv)
    if not args.synthetic and not args.arch:
        ap.error("--arch is required unless --synthetic")

    from repro.trace.session import git_sha

    log = TraceCollector()
    plane = MetricsPlane(log)
    if args.synthetic:
        engine: Any = SyntheticEngine(
            max_batch=args.max_batch,
            ms_per_token=args.synthetic_ms_per_token,
            log=log, metrics=plane.registry)
        info: dict[str, Any] = {"chip": "synthetic"}  # runs on no device
    else:
        from repro.hw.specs import host_chip

        engine, info = _build_real_engine(args, log, plane)
        info.setdefault("chip", host_chip().name)
    info.update({"git_sha": git_sha(), "synthetic": bool(args.synthetic)})

    stream = None
    if args.trace_dir_root:
        from repro.trace.stream import StreamingSession

        trace_dir = os.path.join(args.trace_dir_root,
                                 f"{args.name}-{os.getpid()}")
        stream = StreamingSession(
            trace_dir, rotate_events=args.trace_rotate,
            meta={"driver": "replica", "replica": args.name,
                  "origin": f"{args.name}:{os.getpid()}"},
            metrics_provider=plane.snapshot,
        ).attach(log)
        info["trace_dir"] = trace_dir

    server = ReplicaServer(engine, name=args.name, log=log, plane=plane,
                           host=args.host, port=args.port, info=info).start()
    announce = {"url": server.url, "pid": os.getpid(), "name": args.name,
                **info}
    print(json.dumps({"replica": args.name, **announce}), flush=True)
    if args.ready_file:
        write_ready_file(args.ready_file, announce)

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    while not stop.is_set():
        stop.wait(0.2)
    server.stop()
    if stream is not None:
        stream.close(stats=log.stats())
    print(json.dumps({"replica": args.name, "completed": server.completed,
                      "shutdown": True}), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
