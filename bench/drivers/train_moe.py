"""The ``train_moe`` driver: the ``train`` driver for one chip's share of an
MoE model (``bench/drivers/train.py`` says what a run does, in order).

It reuses ``train``'s ``Feed``, ``SaveProbe`` and ``CompileCount``.  What
differs:

* what the seed drives: the weights, and the synthetic language's chain
  (``SyntheticLM``'s multiplier and increment), come from the traffic's
  ``model_seed``, the same in every run; ``--seed`` draws the rows (each
  row's first token, its resets and its noise).  On a share, the routed
  work of a step follows the training: only the held experts' gates reach
  the loss, so the share of pairs on them moves as the model learns, by as
  much and in the direction that the weights and the chain set (over the
  checked steps it fell by 14% under one seed and rose by 11% under
  another), and the step's time with it.  A fixed model gives every run
  the same routed work, so that runs differ by their rows alone;
* ``reference_readings`` wraps ``train``'s with the weights of
  ``model_seed``;
* ``program_config`` checks the program's config against the MoE keys and
  the share: the router's width, the experts held, the shared experts, the
  routing, the leading dense layers, and the traffic's rows against the
  configuration's ``rows_per_step``;
* ``program_readings`` (a copy of ``train.program_readings``) also keeps the
  checked steps' ``moe_rows``, and takes the norms of the parameters'
  change on the host: uploading the first weights beside the state, as
  ``train``'s does, would set the chip's peak of buffers in use 1.76 GB
  above the training step's own;
* ``build`` is a copy of ``train.build`` with the seeds above;
* ``run`` (a copy of ``train.run``) records ``step_flops`` from the rows the
  program counted in the window (``benchlib.moe_flops``), ``flash_fwd_call``
  at the model's heads, ``moe_gmm_call`` (one grouped-matmul call's FLOPs
  and bytes at the window's mean rows per layer and dispatch chunk), the
  window's mean counters under ``record["moe"]``, and both counts of the
  checked steps' pairs, and the window's mean, under ``notes["moe_rows"]``;
* ``ckpt_stall_s`` is the median stall of ``SAVES`` closing saves, not of
  the first alone: after the supervisor's closing save the same state is
  saved again through the same ``AsyncCheckpointer``, each time after the
  previous write has been waited for and its files removed, so that every
  save starts as the first did.  One snapshot of this cell's 8.79 GB state
  varies by about a second between runs on a host shared with others; a
  job saves again and again, and the median of several saves is the stall
  it pays.  Each save's stall is under ``notes["ckpt_stalls"]``.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import statistics
import tempfile
import time

import jax
import numpy as np

from benchlib import compare, data, flops, load, moe_flops
from benchlib.trace import Capture

train = load("drivers", "train")
Feed, SaveProbe, CompileCount = train.Feed, train.SaveProbe, train.CompileCount
reference_model, CHECKED_STEPS = train.reference_model, train.CHECKED_STEPS
SAVES = 5  # closing saves timed; ckpt_stall_s is their median


class Rows(data.SyntheticLM):
    """``SyntheticLM`` whose chain comes from ``chain_seed`` and whose rows
    (first tokens, resets, noise) come from ``seed``."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int, chain_seed: int):
        super().__init__(vocab, seq, batch, seed)
        chain = data.SyntheticLM(vocab, seq, batch, chain_seed)
        self.mult, self.add = chain.mult, chain.add


def reference_readings(config: dict, traffic: dict, seed: int, source, **kw) -> dict:
    """``train.reference_readings`` from the weights of the traffic's
    ``model_seed``; ``seed``, the run's, reaches the reference through the
    rows of ``source`` alone."""
    return train.reference_readings(config, traffic, traffic["model_seed"], source, **kw)


def _host_norm(path: str, a, b) -> np.ndarray:
    """The references' ``leaf_norms`` of a - b for one leaf, in numpy on the host."""
    v = np.asarray(a, np.float32) - np.asarray(b, np.float32)
    axes = tuple(range(1, v.ndim)) if path.startswith("blocks/") else None
    return np.sqrt(np.sum(np.square(v, dtype=np.float64), axis=axes))


def program_readings(step_call, feed, state, opt: dict, reference: str):
    """Run the checked steps; returns (state, readings), the readings with
    each checked step's ``moe_rows``."""
    ref = load("references", reference)
    flatten, leaf_norms = ref.flatten, ref.leaf_norms

    b1 = opt["b1"]
    grad_norms = jax.jit(lambda mu: leaf_norms(
        {k: v / (1 - b1) for k, v in flatten(mu).items()}))
    p0 = jax.device_get(flatten(state["params"]))
    losses, rows, grad = [], [], None
    for i in range(CHECKED_STEPS):
        state, metrics = step_call(state, feed(i))
        losses.append(float(metrics["loss"]))
        rows.append(int(metrics["moe_rows"]))
        if grad is None:
            grad = jax.device_get(grad_norms(state["opt"]["mu"]))
    p3 = jax.device_get(flatten(state["params"]))
    change = {k: _host_norm(k, p3[k], p0[k]) for k in p0}
    return state, {"losses": losses, "grad": grad, "change": change, "moe_rows": rows}


def program_config(config: dict, traffic: dict):
    """The program's ModelConfig for this configuration, checked against it."""
    from repro.configs import LayerSpec, get_config

    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog.get("overrides", {}),
                              z_loss_weight=traffic["z_loss_weight"])
    m = config["model"]
    want = {
        "d_model": m["hidden_size"], "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "d_ff": m["intermediate_size"], "vocab_size": m["vocab_size"],
        "n_layers": m["num_hidden_layers"], "first_k_dense": m["first_k_dense_replace"],
        "qkv_bias": m["attention_bias"], "tied_embeddings": m["tie_word_embeddings"],
        "rope_theta": m["rope_theta"], "norm_eps": m["rms_norm_eps"], "act": m["hidden_act"],
        "param_dtype": m["torch_dtype"], "activation_dtype": m["torch_dtype"],
        "layer_pattern": (LayerSpec("ga", "moe"),),
    }
    want_moe = {
        "n_experts": m["router_experts"], "held": (m["held_first_expert"], m["n_routed_experts"]),
        "top_k": m["num_experts_per_tok"], "n_shared": m["n_shared_experts"],
        "d_expert": m["moe_intermediate_size"], "norm_topk": m["norm_topk_prob"],
        "router_aux_weight": m["aux_loss_alpha"], "router_z_weight": 0.0,
    }
    got = {k: getattr(cfg, k) for k in want}
    got_moe = {k: getattr(cfg.moe, k) for k in want_moe} if cfg.moe else None
    published = (m["scoring_func"], m["seq_aux"], m["moe_layer_freq"]) == ("softmax", True, 1)
    rows = traffic["batch"] == config["rows_per_step"]
    if got != want or got_moe != want_moe or not published or not rows:
        raise SystemExit(f"program config {prog} is not {config['name']}: "
                         f"{got} {got_moe} vs {want} {want_moe}")
    return cfg


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
    state = load("references", config["reference"]).init_state(m, traffic["model_seed"])
    want = abstract_train_state(cfg, tcfg)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    if jax.tree.structure(got) != jax.tree.structure(want) or jax.tree.leaves(got) != [
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in jax.tree.leaves(want)]:
        raise SystemExit("the benchmark's weights do not fit the program's train state")
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))

    def step_call(state, batch):
        with jax.profiler.TraceAnnotation("bench.step_dispatch"):
            return step(state, batch)

    source = Rows(m["vocab_size"], traffic["seq_len"], traffic["batch"], ctx["seed"],
                  traffic["model_seed"])
    return state, step_call, Feed(source)


def param_count(m: dict) -> int:
    shapes = load("references", "moe_lm").param_shapes(m)
    return int(sum(np.prod(shape) for shape, _, _ in shapes.values()))


def run(ctx: dict) -> dict:
    from repro.checkpoint import AsyncCheckpointer
    from repro.core.events import EventLog
    from repro.nn.ffn import dispatch_chunks
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
        for _ in range(SAVES - 1):  # the closing save again, into an empty directory
            for d in os.listdir(ckpt_dir):
                shutil.rmtree(os.path.join(ckpt_dir, d))
            probe.save(sup.step, sup.state)
            probe.wait()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)
                      for st in stats)
    failed = sum(not np.isfinite(float(x["loss"])) for x in out["metrics"])
    steps_metrics = out["metrics"][-window["steps"]:]
    rows = float(np.mean([float(x["moe_rows"]) for x in steps_metrics]))
    max_rows = float(np.mean([float(x["moe_max_rows"]) for x in steps_metrics]))
    del sup, out
    seconds = window["t1"] - window["t0"]
    batch, seq = traffic["batch"], traffic["seq_len"]
    tokens = window["steps"] * batch * seq
    spans = feed.spans[window["spans"]:]
    losses = prog["losses"]

    t_ref = time.perf_counter()
    ref = reference_readings(ctx["config"], traffic, ctx["seed"], feed.source)
    t_ref = time.perf_counter() - t_ref
    found = compare.gaps(prog, ref)
    correct, checks = compare.judge(found, ctx["limits"])
    m = reference_model(ctx["config"], traffic)
    layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    chunks = dispatch_chunks(batch * seq, m["num_experts_per_tok"])
    return {
        "end_to_end": {
            "train_tokens_per_s": tokens / seconds,
            "ckpt_stall_s": statistics.median(probe.stalls),
            "setup_s": window["t0"] - ctx["t_process"],
        },
        "correct": correct and all(np.isfinite(losses)),
        "checks": checks,
        "attempted": window["steps"],
        "failed": failed,
        "memory_peak_bytes": memory_peak,
        "compiles_in_window": window["compiles"],
        "notes": {"losses": losses, "ref_losses": ref["losses"], "leaf": found["leaf"],
                  "moe_rows": {"program": prog["moe_rows"], "reference": ref["rows"],
                               "window_mean": rows},
                  "ckpt_stalls": probe.stalls,
                  "params": param_count(m), "steps": window["steps"], "window_s": seconds,
                  "reference_s": t_ref,
                  "peak_bytes_in_use": max(st.get("peak_bytes_in_use", 0) for st in stats),
                  "peak_bytes_reserved": max(st.get("peak_bytes_reserved", 0) for st in stats)},
        "record": {
            "steps": window["steps"],
            "window_s": seconds,
            "step_flops": moe_flops.train_step_flops(m, batch, seq, rows),
            "batch_fn_s": [b - a for a, b in spans],
            "flash_fwd_call": flops.flash_fwd_cost(
                batch, seq, m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"]),
            "moe_gmm_call": moe_flops.gmm_cost(m, rows / layers / chunks),
            "moe": {"rows": rows, "max_rows": max_rows, "layers": layers, "chunks": chunks},
            "trace": trace.reduce() if ctx["trace"] else None,
        },
    }
