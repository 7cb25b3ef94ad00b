"""The ``train`` driver: the program's training supervisor over its jitted step.

One run of a training cell, in this order:

1. Set-up: the train state is made on the device from the seed, in one
   jitted call; the program's ``make_train_step`` is jitted with the state
   donated; three steps go through the same call and feed as the window (the
   first compiles).  They are the steps the comparison checks: their losses,
   the first clipped gradient (read back from the optimizer's first moment),
   and the parameters' change after the third.
2. Window: ``Supervisor.run`` drives the same step for ``seconds`` from the
   first batch, fed by the benchmark's seeded ``SyntheticLM`` copy, with no
   save inside.  The supervisor's step-0 save, made before its first step,
   is skipped: the window is a stretch of a job that has saved before.
3. The supervisor's closing save, in a temporary directory: the time its
   ``save`` blocks the loop (waiting for the previous write, then the host
   snapshot of the whole state) is ``ckpt_stall_s``.  The write is then
   waited for and removed.
4. The device's peak memory is read (the allocator's peak of buffers in
   use plus its peak of reserved memory), the program's state freed, and
   the plain reference runs the same three steps from the same weights and
   rows.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import compare, data, flops, load
from benchlib.trace import Capture

CHECKED_STEPS = 3


def program_config(config: dict, traffic: dict):
    """The program's ModelConfig for this configuration, checked against it."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog.get("overrides", {}),
                              z_loss_weight=traffic["z_loss_weight"])
    m = config["model"]
    want = {
        "d_model": m["hidden_size"], "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "d_ff": m["intermediate_size"], "vocab_size": m["vocab_size"],
        "n_layers": m["num_hidden_layers"], "qkv_bias": m["attention_bias"],
        "tied_embeddings": m["tie_word_embeddings"], "rope_theta": m["rope_theta"],
        "norm_eps": m["rms_norm_eps"], "act": m["hidden_act"],
        "param_dtype": m["torch_dtype"], "activation_dtype": m["torch_dtype"],
    }
    got = {k: getattr(cfg, k) for k in want}
    if got != want or cfg.period != 1 or cfg.layer_pattern[0].mixer != "ga":
        raise SystemExit(f"program config {prog} is not {config['name']}: {got} vs {want}")
    return cfg


def reference_model(config: dict, traffic: dict) -> dict:
    return {**config["model"], "z_loss_weight": traffic["z_loss_weight"]}


class Feed:
    """``batch_fn`` for the supervisor: the seeded batch of step i, on device."""

    def __init__(self, source: data.SyntheticLM) -> None:
        self.source = source
        self.spans: list[tuple[float, float]] = []
        self.on_batch = None

    def __call__(self, i: int) -> dict:
        if self.on_batch is not None:
            self.on_batch(i)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.batch_fn"):
            batch = {k: jnp.asarray(v) for k, v in self.source.batch(i).items()}
        self.spans.append((t0, time.perf_counter()))
        return batch


class SaveProbe:
    """Stands in for the supervisor's checkpointer and times its blocking part.

    The first call after the window marks the window's end; ``save`` is the
    program's own ``AsyncCheckpointer.save``.  The save of step 0, which
    the supervisor makes before its first step, is skipped.
    """

    def __init__(self, ckpt, on_close) -> None:
        self.ckpt, self.on_close = ckpt, on_close
        self.stalls: list[float] = []
        self.closed = False

    def _close(self) -> None:
        if not self.closed:
            self.closed = True
            self.on_close()

    def wait(self) -> None:
        self._close()
        self.ckpt.wait()

    def save(self, step: int, state) -> None:
        if step == 0 and not self.closed:
            return
        self._close()
        t0 = time.perf_counter()
        self.ckpt.save(step, state)
        self.stalls.append(time.perf_counter() - t0)


class CompileCount:
    """Compilations (or persistent-cache loads) this process has made."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


def program_readings(step_call, feed, state, opt: dict, reference: str):
    """Run the checked steps; returns (state, readings)."""
    ref = load("references", reference)
    flatten, leaf_norms = ref.flatten, ref.leaf_norms

    b1 = opt["b1"]
    grad_norms = jax.jit(lambda mu: leaf_norms(
        {k: v / (1 - b1) for k, v in flatten(mu).items()}))
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a}))
    p0 = jax.device_get(flatten(state["params"]))
    losses, grad = [], None
    for i in range(CHECKED_STEPS):
        state, metrics = step_call(state, feed(i))
        losses.append(float(metrics["loss"]))
        if grad is None:
            grad = jax.device_get(grad_norms(state["opt"]["mu"]))
    change = jax.device_get(diff_norms(flatten(state["params"]), p0))
    return state, {"losses": losses, "grad": grad, "change": change}


def reference_readings(config: dict, traffic: dict, seed: int, source, *,
                       precision: str = "f32", rows=None) -> dict:
    reference = load("references", config["reference"])
    m = reference_model(config, traffic)
    params = reference.init_state(m, seed)["params"]
    batches = [source.batch(i) for i in range(CHECKED_STEPS)]
    return reference.run_steps(m, traffic["optimizer"], params, batches,
                                precision=precision, rows=rows)


def build(ctx: dict):
    """State, jitted step and feed of one cell, as the window will use them."""
    from repro.training import optim
    from repro.training.step import TrainConfig, abstract_train_state, make_train_step

    config, traffic = ctx["config"], ctx["traffic"]
    if config["mesh"] != "1x1":
        raise SystemExit(f"mesh {config['mesh']}: this driver runs one chip")
    cfg = program_config(config, traffic)
    tcfg = TrainConfig(opt=optim.AdamWConfig(**traffic["optimizer"]))
    m = reference_model(config, traffic)
    state = load("references", config["reference"]).init_state(m, ctx["seed"])
    want = abstract_train_state(cfg, tcfg)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    if jax.tree.structure(got) != jax.tree.structure(want) or jax.tree.leaves(got) != [
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in jax.tree.leaves(want)]:
        raise SystemExit("the benchmark's weights do not fit the program's train state")
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))

    def step_call(state, batch):
        with jax.profiler.TraceAnnotation("bench.step_dispatch"):
            return step(state, batch)

    source = data.SyntheticLM(m["vocab_size"], traffic["seq_len"], traffic["batch"],
                              ctx["seed"])
    return state, step_call, Feed(source)


def run(ctx: dict) -> dict:
    from repro.checkpoint import AsyncCheckpointer
    from repro.core.events import EventLog
    from repro.runtime.supervisor import Supervisor, SupervisorConfig

    traffic, devices = ctx["traffic"], ctx["devices"]
    compiles = CompileCount()
    state, step_call, feed = build(ctx)
    state, prog = program_readings(step_call, feed, state, traffic["optimizer"],
                                   ctx["config"]["reference"])

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    sup = Supervisor(SupervisorConfig(ckpt_dir=ckpt_dir, ckpt_every=1 << 30,
                                      max_steps=1 << 30),
                     step_call, feed, state, log=EventLog())
    del state
    sup.step = CHECKED_STEPS
    trace = Capture(ctx["trace"])
    window: dict = {}

    def on_batch(i: int) -> None:
        now = time.perf_counter()
        if "t0" not in window:
            window.update(t0=now, compiles=compiles.n, step0=i, spans=len(feed.spans))
            trace.start()
        elif now - window["t0"] >= ctx["seconds"]:
            sup.cfg.max_steps = i + 1  # this step is the window's last

    def on_close() -> None:
        window.update(t1=time.perf_counter(), compiles=compiles.n - window["compiles"],
                      steps=sup.step - window["step0"])
        trace.stop()

    feed.on_batch = on_batch
    probe = SaveProbe(AsyncCheckpointer(ckpt_dir), on_close)
    sup.ckpt = probe
    try:
        out = sup.run()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)
                      for st in stats)
    failed = sum(not np.isfinite(float(x["loss"])) for x in out["metrics"])
    del sup, out
    seconds = window["t1"] - window["t0"]
    tokens = window["steps"] * traffic["batch"] * traffic["seq_len"]
    spans = feed.spans[window["spans"]:]
    losses = prog["losses"]

    t_ref = time.perf_counter()
    ref = reference_readings(ctx["config"], traffic, ctx["seed"], feed.source)
    t_ref = time.perf_counter() - t_ref
    found = compare.gaps(prog, ref)
    correct, checks = compare.judge(found, ctx["limits"])
    m = reference_model(ctx["config"], traffic)
    n_params = flops.dense_param_counts(m)["total"]
    return {
        "end_to_end": {
            "train_tokens_per_s": tokens / seconds,
            "ckpt_stall_s": probe.stalls[0],
            "setup_s": window["t0"] - ctx["t_process"],
        },
        "correct": correct and all(np.isfinite(losses)),
        "checks": checks,
        "attempted": window["steps"],
        "failed": failed,
        "memory_peak_bytes": memory_peak,
        "compiles_in_window": window["compiles"],
        "notes": {"losses": losses, "ref_losses": ref["losses"], "leaf": found["leaf"],
                  "params": n_params, "steps": window["steps"], "window_s": seconds,
                  "reference_s": t_ref,
                  "peak_bytes_in_use": max(st.get("peak_bytes_in_use", 0) for st in stats),
                  "peak_bytes_reserved": max(st.get("peak_bytes_reserved", 0) for st in stats)},
        "record": {
            "steps": window["steps"],
            "window_s": seconds,
            "step_flops": flops.train_step_flops(m, traffic["batch"], traffic["seq_len"]),
            "batch_fn_s": [b - a for a, b in spans],
            "flash_fwd_call": flops.flash_fwd_cost(
                traffic["batch"], traffic["seq_len"], m["num_attention_heads"],
                m["num_key_value_heads"], m["head_dim"]),
            "trace": trace.reduce() if ctx["trace"] else None,
        },
    }
