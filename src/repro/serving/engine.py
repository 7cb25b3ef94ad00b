"""Continuous-batching serving engine (fixed decode slots).

vLLM-style scheduling reduced to its TPU-friendly core: a static
(max_batch)-slot decode batch whose caches live donated on device, per-slot
prefill that scatters a new request's cache into its slot, and one fused
decode step for all active slots per tick.  Static shapes everywhere — no
recompilation as requests come and go (slot masks handle liveness).

Request lifecycle events (spawn/exit) flow into the EventLog — the paper's
thread/process tracing, where the unit of concurrency is the request.

Prefill compiles per distinct prompt length (callers should bucket lengths);
a production deployment would add a masked fixed-length prefill on top of the
same cache contract.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.events import GLOBAL_LOG, EventLog, current_span, next_span_id, span_scope
from repro.dispatch.cost import estimate_callable
from repro.dispatch.dispatcher import Dispatcher, with_impl
from repro.dispatch.profiles import signature
from repro.models import lm


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_seq: int = 512
    temperature: float = 0.0  # 0 = greedy
    eos_id: int = -1  # -1 = never; synthetic workloads run to max_new
    seed: int = 0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    out: list[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    done: bool = False
    span: int = 0  # trace span id shared by the request's spawn/exit events
    parent: int = 0  # enclosing span at submit time (e.g. the driver's run span)
    t_active: float = 0.0  # monotonic instant the request won a decode slot


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        scfg: ServeConfig,
        *,
        log: Optional[EventLog] = None,
        dispatcher: Optional[Dispatcher] = None,
        metrics: Optional[Any] = None,
    ) -> None:
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.log = GLOBAL_LOG if log is None else log
        self.dispatcher = dispatcher
        # live occupancy gauges (a repro.metrics MetricsRegistry): queue depth
        # and decode-slot usage are states, not events — the trace can't
        # answer "how full is the batch right now" without replaying it
        self._g_queue = self._g_slots = None
        if metrics is not None:
            self._g_queue = metrics.gauge(
                "repro_serve_queue_depth", "requests waiting for a decode slot")
            self._g_slots = metrics.gauge(
                "repro_serve_active_slots", "occupied decode slots")
        B, S = scfg.max_batch, scfg.max_seq
        self.caches = lm.init_caches(cfg, B, S)
        self.cur_pos = np.zeros(B, np.int32)  # next position per slot
        self.active: list[Optional[Request]] = [None] * B
        self.queue: list[Request] = []
        # submit() is called from HTTP handler threads when the engine runs
        # behind a repro.router replica; the queue hand-off is the only state
        # shared with the engine-loop thread (active/caches stay loop-owned)
        self._queue_lock = threading.Lock()
        self._rid = itertools.count()
        self._key = jax.random.PRNGKey(scfg.seed)

        # compiled surfaces (static shapes).  With a dispatcher, one compiled
        # variant per backend target (kernel impl baked in at trace time) and
        # the dispatcher routes each call to the argmin-cost variant.
        prefill_fn = lambda p, t: lm.prefill(p, cfg, t, max_seq=S)  # noqa: E731
        decode_fn = lambda p, t, c, ch: lm.decode_step(p, cfg, t, c, ch)  # noqa: E731
        if dispatcher is None:
            self._prefill = jax.jit(prefill_fn, static_argnums=())
            self._decode = jax.jit(decode_fn, donate_argnums=(3,))
        else:
            self._prefill_variants = {
                t.name: jax.jit(with_impl(t.impl, prefill_fn))
                for t in dispatcher.registry.targets()
            }
            self._decode_variants = {
                t.name: jax.jit(with_impl(t.impl, decode_fn), donate_argnums=(3,))
                for t in dispatcher.registry.targets()
            }
            self._canonical = {"serve_prefill": prefill_fn, "serve_decode": decode_fn}
            self._est_cache: dict = {}
            # per-backend tuned-config tags, resolved at first dispatch (the
            # drivers install repro.tune winners before the engine runs);
            # keys every recorded sample to the config actually executing
            self._configs: Optional[dict] = None
            self._prefill = lambda p, t: self._dispatched("serve_prefill", self._prefill_variants, p, t)
            self._decode = lambda p, t, c, ch: self._dispatched(
                "serve_decode", self._decode_variants, p, t, c, ch
            )

    def _dispatched(self, op: str, variants: dict, *args: Any) -> Any:
        """Route one compiled-surface call through the dispatcher.

        A-priori costs come from pricing the op's canonical (chunked)
        formulation per backend via the SDFG/roofline machinery, cached per
        argument signature; the dispatcher folds measured wall-times on top.
        The profile key is the token array's signature — params/caches are
        fixed per engine, and walking their pytree every tick would cost more
        than a decode step.
        """
        sig = signature(args[1])  # tokens: distinguishes prefill buckets
        if self._configs is None:
            self._configs = self.dispatcher.active_configs()
        if self.dispatcher.cfg.policy == "static":
            # pinned backend: the SDFG pricing would be computed only to be
            # logged — skip the extra trace per prompt-length bucket
            return self.dispatcher.dispatch(op, variants, *args, sig=sig,
                                            configs=self._configs)
        key = (op, sig)
        if key not in self._est_cache:
            canonical = with_impl("chunked", self._canonical[op])
            self._est_cache[key] = {
                t.name: estimate_callable(
                    canonical, *args, target=t, chip=self.dispatcher.chip
                ).seconds
                for t in self.dispatcher.registry.targets()
            }
        return self.dispatcher.dispatch(
            op, variants, *args, estimates=self._est_cache[key], sig=sig,
            configs=self._configs,
        )

    # -- client API ----------------------------------------------------------

    def submit(self, prompt: list[int], max_new: int = 32) -> int:
        req = Request(next(self._rid), list(prompt), max_new,
                      span=next_span_id(), parent=current_span())
        with self._queue_lock:
            self.queue.append(req)
            depth = len(self.queue)
        # span id pairs this spawn with the exit in _decode_tick even when
        # requests interleave (exporters and durations() pair by span first);
        # the parent captured at submit keeps the request under the driver's
        # run span even though its exit lands ticks later on another path
        self.log.record("spawn", "request", req.rid, span=req.span, parent=req.parent)
        if self._g_queue is not None:
            self._g_queue.set(depth)
        return req.rid

    def pending(self) -> int:
        """Requests not yet delivered (queued + occupying a decode slot)."""
        with self._queue_lock:
            return len(self.queue) + sum(r is not None for r in self.active)

    def run_to_completion(self) -> dict[int, list[int]]:
        results: dict[int, list[int]] = {}
        while self.queue or any(self.active):
            for r in self.step():
                results[r.rid] = r.out
        return results

    # -- engine tick ----------------------------------------------------------

    def step(self) -> list[Request]:
        """One tick: admit to free slots (prefill), then batched decode."""
        self._admit()
        finished = self._decode_tick()
        return finished

    def _admit(self) -> None:
        for slot in range(self.scfg.max_batch):
            if self.active[slot] is not None:
                continue
            with self._queue_lock:
                if not self.queue:
                    break
                req = self.queue.pop(0)
            req.slot = slot
            req.t_active = time.monotonic()
            # the prefill (and the dispatch decision it triggers) must nest
            # under the request span, whose bracket events live elsewhere;
            # the lifecycle's profiler span carries the prefill span id
            with span_scope(req.span), self.log.lifecycle("prefill", req.rid):
                tokens = jnp.asarray(req.prompt, jnp.int32)[None]
                logits, new_caches = self._prefill(self.params, tokens)
                self.caches = jax.tree.map(
                    lambda c, n: c.at[slot].set(n[0].astype(c.dtype)),
                    self.caches,
                    new_caches,
                )
                first = self._sample(logits)[0]
                req.out.append(int(first))
                self.cur_pos[slot] = len(req.prompt)
            self.active[slot] = req
        if self._g_queue is not None:
            self._g_queue.set(len(self.queue))
            self._g_slots.set(sum(r is not None for r in self.active))

    def _decode_tick(self) -> list[Request]:
        live = [r for r in self.active if r is not None]
        if not live:
            return []
        B = self.scfg.max_batch
        tokens = np.zeros(B, np.int32)
        for r in live:
            tokens[r.slot] = r.out[-1]
        with self.log.lifecycle("decode_tick", len(live)):
            logits, self.caches = self._decode(
                self.params,
                jnp.asarray(tokens),
                jnp.asarray(self.cur_pos),
                self.caches,
            )
            nxt = np.asarray(self._sample(logits))
        finished: list[Request] = []
        for r in live:
            self.cur_pos[r.slot] += 1
            tok = int(nxt[r.slot])
            r.out.append(tok)
            hit_eos = tok == self.scfg.eos_id
            out_of_room = self.cur_pos[r.slot] + 1 >= self.scfg.max_seq
            if len(r.out) >= r.max_new or hit_eos or out_of_room:
                r.done = True
                self.active[r.slot] = None
                self.log.record("exit", "request", r.rid, span=r.span, parent=r.parent)
                finished.append(r)
        if finished and self._g_slots is not None:
            self._g_slots.set(sum(r is not None for r in self.active))
        return finished

    def _sample(self, logits: jax.Array) -> jax.Array:
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._key, sub = jax.random.split(self._key)
        return jax.random.categorical(sub, logits / self.scfg.temperature, axis=-1).astype(
            jnp.int32
        )
