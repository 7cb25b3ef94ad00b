"""Training supervisor: checkpoint/restart, failure injection, stragglers,
elastic re-mesh.

The control loop a 1000+-node deployment needs, exercised here with simulated
faults (the CPU container has one real device; the *mechanisms* are identical):

* **Failure detection + restart** — any step raising :class:`NodeFailure`
  (injected by :class:`FailureInjector`, or real XLA errors) rolls back to the
  last checkpoint and replays.  Because the data pipeline is stateless-indexed
  by the step counter, replay is bit-deterministic.
* **Straggler mitigation** — per-step deadline = ``k×`` the rolling median
  step time; breaches are recorded as ``straggler`` lifecycle events and
  counted (on a real cluster the action is re-scheduling the slow host; the
  detection side is what lives in software).
* **Elastic re-mesh** — ``resize(new_mesh)`` re-shards the live train state
  onto a different mesh (checkpoints are unsharded, so this is a device_put,
  not a format migration).
* **Lifecycle tracing** — step / checkpoint / restart spawn-exit events flow
  into the paper's EventLog (Adaptyst's thread/process-tracing analogue).
  Each ``step`` span holds four children, in order: ``batch`` (the
  ``batch_fn`` call), ``dispatch`` (the jitted or dispatched step, until it
  returns), ``device_wait`` (``block_until_ready`` of its metrics) and
  ``fetch`` (``device_get`` of the metrics, the straggler check and the
  bookkeeping before the next step).  A ``checkpoint`` span holds the
  checkpointer's ``ckpt.*`` spans (:mod:`repro.checkpoint.store`).  Under a
  profiler each is also the host span ``repro.<name>``
  (:mod:`repro.core.events`).
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable, Iterator, Mapping, Optional

import jax

from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.core.events import GLOBAL_LOG, EventLog
from repro.dispatch.dispatcher import Dispatcher
from repro.dispatch.profiles import signature

PyTree = Any


class NodeFailure(RuntimeError):
    """Simulated (or surfaced) loss of a worker during a step."""


@dataclasses.dataclass
class FailureInjector:
    """Deterministic failure schedule: fail just before the listed steps."""

    fail_at_steps: tuple[int, ...] = ()
    _already: set = dataclasses.field(default_factory=set)

    def maybe_fail(self, step: int) -> None:
        if step in self.fail_at_steps and step not in self._already:
            self._already.add(step)
            raise NodeFailure(f"injected node failure at step {step}")


@dataclasses.dataclass
class SupervisorConfig:
    ckpt_dir: str
    ckpt_every: int = 50
    max_steps: int = 200
    straggler_factor: float = 3.0  # deadline = factor × rolling median
    straggler_window: int = 20
    max_restarts: int = 10


class Supervisor:
    """Runs ``train_step`` under fault tolerance.

    ``train_step(state, batch) -> (state, metrics)`` must be pure (jitted);
    ``batch_fn(step) -> batch`` must be stateless-indexed (resumable).
    """

    def __init__(
        self,
        cfg: SupervisorConfig,
        train_step: Callable,
        batch_fn: Callable[[int], Any],
        init_state: PyTree,
        *,
        state_shardings: Optional[PyTree] = None,
        log: Optional[EventLog] = None,
        failures: Optional[FailureInjector] = None,
        dispatcher: Optional[Dispatcher] = None,
        step_variants: Optional[Mapping[str, Callable]] = None,
        stream: Optional[Any] = None,
    ) -> None:
        self.cfg = cfg
        self.train_step = train_step
        self.batch_fn = batch_fn
        # profile-guided placement: when both are given, each step routes to
        # the argmin-cost compiled variant (see repro.dispatch)
        self.dispatcher = dispatcher
        self.step_variants = dict(step_variants) if step_variants else None
        # per-backend tuned-config tags, resolved lazily at the first
        # dispatched step (tune winners are installed before run())
        self._configs: Optional[dict] = None
        # durable trace sink (repro.trace.StreamingSession): rotated at every
        # checkpoint so the on-disk trace is never staler than the on-disk
        # model state — a crash recovers both to the same point
        self.stream = stream
        self.state = init_state
        self.state_shardings = state_shardings
        self.log = GLOBAL_LOG if log is None else log
        self.failures = failures or FailureInjector()
        self.ckpt = AsyncCheckpointer(cfg.ckpt_dir, log=self.log)
        self.step = 0
        self.restarts = 0
        self.stragglers = 0
        self._durations: list[float] = []

    # -- fault handling ------------------------------------------------------

    def _restore_latest(self) -> None:
        last = latest_step(self.cfg.ckpt_dir)
        with self.log.lifecycle("restart", {"from_step": last}):
            if last is None:
                self.step = 0  # restart from scratch
                return
            self.state = restore(
                self.cfg.ckpt_dir, last, self.state, self.state_shardings
            )
            self.step = last

    def resize(self, new_mesh, reshard_fn: Callable[[PyTree, Any], PyTree]) -> None:
        """Elastic re-mesh: move the live state onto ``new_mesh``."""
        with self.log.lifecycle("elastic_resize", {"mesh": str(new_mesh.shape)}):
            self.state, self.state_shardings = reshard_fn(self.state, new_mesh)

    # -- main loop -----------------------------------------------------------

    def _deadline(self) -> Optional[float]:
        if len(self._durations) < 5:
            return None
        window = self._durations[-self.cfg.straggler_window:]
        return self.cfg.straggler_factor * statistics.median(window)

    def _call(self, batch: Any) -> tuple[PyTree, Any]:
        """One step on ``batch``: the dispatched variant, else ``train_step``."""
        if self.dispatcher is not None and self.step_variants:
            if self._configs is None:
                self._configs = self.dispatcher.active_configs()
            return self.dispatcher.dispatch(
                "train_step", self.step_variants, self.state, batch,
                sig=signature(batch),  # state pytree is fixed-shape
                configs=self._configs,
            )
        return self.train_step(self.state, batch)

    def run(self) -> dict[str, Any]:
        metrics_hist = []
        if latest_step(self.cfg.ckpt_dir) is None:
            # step-0 checkpoint BEFORE the first (donating) step: restart-from-
            # scratch must never reference donated buffers.
            with self.log.lifecycle("checkpoint", 0):
                self.ckpt.save(0, self.state)
        while self.step < self.cfg.max_steps:
            try:
                with self.log.lifecycle("step", self.step) as step_span:
                    self.failures.maybe_fail(self.step)
                    t0 = time.monotonic()
                    with self.log.lifecycle("batch"):
                        batch = self.batch_fn(self.step)
                    with self.log.lifecycle("dispatch"):
                        # the dispatcher's decision event lands in the span
                        # tree under this span, inside the step
                        self.state, metrics = self._call(batch)
                    with self.log.lifecycle("device_wait"):
                        jax.block_until_ready(metrics)
                    dt = time.monotonic() - t0
                    with self.log.lifecycle("fetch"):
                        deadline = self._deadline()
                        if deadline is not None and dt > deadline:
                            self.stragglers += 1
                            self.log.record("straggler", "step",
                                            {"step": self.step, "s": dt},
                                            parent=step_span)
                        self._durations.append(dt)
                        metrics_hist.append(jax.device_get(metrics))
                        self.step += 1
                if self.step % self.cfg.ckpt_every == 0:
                    with self.log.lifecycle("checkpoint", self.step, parent=step_span):
                        self.ckpt.save(self.step, self.state)
                    if self.stream is not None:
                        self.stream.rotate()
            except NodeFailure:
                self.restarts += 1
                if self.restarts > self.cfg.max_restarts:
                    raise
                self._restore_latest()
        self.ckpt.wait()
        with self.log.lifecycle("checkpoint", self.step):
            self.ckpt.save(self.step, self.state)
            self.ckpt.wait()
        if self.stream is not None:
            self.stream.rotate()
        return {
            "steps": self.step,
            "restarts": self.restarts,
            "stragglers": self.stragglers,
            "metrics": metrics_hist,
        }
