"""Bring-up check on the chip: serve and train at published widths.

    python chip_smoke.py               # one TPU: qwen2-0.5b serve + train
    python chip_smoke.py --four-chips  # four TPUs: gemma3-4b sharded train, 2x2

Everything runs in this one process, through the serving engine and the
training supervisor that the launch drivers use: a chip belongs to one
process.  Weights and data are random, made from a seed.

Each phase prints one JSON line with its compile and run seconds, tokens and
losses; these are bring-up facts, not benchmark numbers.  ``compile_s`` is
every second the phase spent in XLA compilation or loading from the
persistent cache (JAX's own compile event), so a warm cache shows there.
The last line, ``{"ok": true, "device": {...}}``, is printed only on a TPU
and only when every check passed.  Anything else exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Tolerances.  The model is bf16, so a kernel and its plain-jnp counterpart
# round differently layer by layer.  LOSS_RTOL sits about 8x above the widest
# gap the chip has shown (1.2e-5, 2x2 against 1x1); PERF.md lists the planted
# faults each check rejects.
LOGIT_RTOL = 5e-2  # max |pallas - ref| logit, relative to max |ref| logit
LOSS_RTOL = 1e-4  # first loss vs the chunked path / the 1x1 mesh, relative
GRAD_RTOL = 5e-2  # |g_pallas - g_chunked| / |g_chunked| (L2), worst parameter;
#                   first-step grad norm 2x2 vs 1x1, relative
MEM_BALANCE = 0.5  # least over most bytes_in_use across the mesh's devices


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


class CompileMeter:
    """Seconds spent compiling (or loading from the persistent cache)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, fn, *args, **kwargs) -> dict:
        """Run one phase; add its compile seconds and persistent-cache hits."""
        s0, h0 = self.seconds, self.cache_hits
        rec = fn(*args, **kwargs)
        rec.update(compile_s=self.seconds - s0, cache_hits=self.cache_hits - h0)
        emit(rec)
        return rec


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def serve_phase(cfg, *, requests=8, prompt_len=128, max_new=32, max_batch=8,
                max_seq=2048, seed=0) -> dict:
    """Engine run, its compiled programs, and their logits against ``ref``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dispatch import with_impl
    from repro.models import lm
    from repro.serving.engine import Engine, ServeConfig

    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    eng = Engine(cfg, params, ServeConfig(max_batch=max_batch, max_seq=max_seq,
                                          seed=seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]
    prompt = jnp.asarray(prompts[0], jnp.int32)[None]
    slots = jnp.zeros(max_batch, jnp.int32)

    # the engine's own prefill and decode programs, to look inside
    prefill = eng._prefill.lower(params, prompt).compile()
    decode = eng._decode.lower(params, slots, slots, eng.caches).compile()

    t0 = time.perf_counter()
    for p in prompts:
        eng.submit(p, max_new=max_new)
    results = eng.run_to_completion()
    run_s = time.perf_counter() - t0

    # the same steps through the plain reference ops, on the same device: one
    # prefill, and one decode on a cache full to max_seq with random K/V, so
    # every KV block of the decode kernel is live
    ref_prefill = jax.jit(with_impl(
        "ref", lambda p, t: lm.prefill(p, cfg, t, max_seq=max_seq)))
    ref_decode = jax.jit(with_impl(
        "ref", lambda p, t, c, ch: lm.decode_step(p, cfg, t, c, ch)))
    leaves, tree = jax.tree.flatten(eng.caches)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    full = tree.unflatten([
        jnp.broadcast_to(jnp.arange(max_seq, dtype=c.dtype), c.shape)  # pos_ids
        if jnp.issubdtype(c.dtype, jnp.integer)
        else jax.random.normal(k, c.shape, c.dtype)
        for c, k in zip(leaves, keys)
    ])
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, max_batch), jnp.int32)
    cur = jnp.full(max_batch, max_seq - 1, jnp.int32)
    err_prefill = rel_err(prefill(params, prompt)[0], ref_prefill(params, prompt)[0])
    want = jax.device_get(ref_decode(params, toks, cur, full)[0])
    err_decode = rel_err(decode(params, toks, cur, full)[0], want)  # donates

    return {
        "phase": "serve", "arch": cfg.name, "requests": len(results),
        "prompt_len": prompt_len, "max_batch": max_batch, "max_seq": max_seq,
        "tokens_per_request": sorted({len(v) for v in results.values()}),
        "generated_tokens": sum(len(v) for v in results.values()),
        "run_s": run_s,
        "kernels": {"prefill": "tpu_custom_call" in prefill.as_text(),
                    "decode": "tpu_custom_call" in decode.as_text()},
        "logit_rel_err": {"prefill": err_prefill, "decode": err_decode},
        "logit_rtol": LOGIT_RTOL,
    }


def grad_gap(cfg, params, batch) -> dict:
    """First-batch loss and gradient: Pallas kernels against the chunked path.

    The Pallas path differentiates through the kernels' custom VJPs, as the
    train step does on a TPU.  Returns the chunked loss, the Pallas loss, and
    the worst parameter's relative L2 gradient error.
    """
    import jax
    import jax.numpy as jnp

    from repro.dispatch import with_impl
    from repro.models import lm

    def value_and_grad(impl):
        return jax.jit(with_impl(impl, jax.value_and_grad(
            lambda p, b: lm.loss_fn(p, cfg, b["tokens"], b["labels"])[0])))

    def rel(x, y):
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        return jnp.linalg.norm(x - y) / jnp.maximum(jnp.linalg.norm(y), 1e-30)

    loss_c, g_c = value_and_grad("chunked")(params, batch)
    loss_p, g_p = value_and_grad("pallas")(params, batch)
    errs = jax.device_get(jax.jit(lambda a, b: jax.tree.map(rel, a, b))(g_p, g_c))
    del g_c, g_p
    leaf, worst = max(((jax.tree_util.keystr(k), float(e)) for k, e in
                       jax.tree_util.tree_flatten_with_path(errs)[0]),
                      key=lambda t: t[1])
    return {"chunked_first_loss": float(loss_c), "pallas_first_loss": float(loss_p),
            "grad_rel_err": worst, "grad_rel_err_leaf": leaf}


def train_phase(cfg, *, mesh="1x1", batch=4, seq=1024, steps=4, seed=0,
                supervised=True, reference=True) -> dict:
    """Train steps on ``mesh``; optionally checked against the chunked path.

    ``supervised`` runs the steps under the training supervisor with a fresh
    checkpoint directory, as ``launch.train`` does; otherwise the compiled
    step is called directly (a four-chip state is too large to checkpoint
    within a smoke run).
    """
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.distributed import sharding as shd
    from repro.launch.train import build_mesh
    from repro.runtime.supervisor import Supervisor, SupervisorConfig
    from repro.trace import TraceCollector
    from repro.training import optim
    from repro.training.step import (
        TrainConfig,
        abstract_train_state,
        init_train_state,
        make_train_step,
        train_state_axes,
    )

    tcfg = TrainConfig(opt=optim.AdamWConfig(
        peak_lr=3e-4, warmup_steps=max(10, steps // 10), total_steps=steps))
    data = SyntheticLM(DataConfig(cfg.vocab_size, seq, batch, seed=seed))

    def batch_fn(i):
        return {k: jnp.asarray(v) for k, v in data.batch(i).items()}

    rec = {"phase": "train", "arch": cfg.name, "n_layers": cfg.n_layers,
           "mesh": mesh, "batch": batch, "seq": seq}
    mesh_ = build_mesh(mesh)
    with mesh_:
        state_abs = abstract_train_state(cfg, tcfg)
        state_shd = shd.tree_shardings(
            train_state_axes(cfg), state_abs, shd.DEFAULT_RULES.param, mesh_)
        state = jax.jit(lambda k: init_train_state(cfg, tcfg, k),
                        out_shardings=state_shd)(jax.random.PRNGKey(seed))
        b0 = batch_fn(0)
        if reference:
            rec.update(grad_gap(cfg, state["params"], b0))  # before donation
        step_jit = jax.jit(make_train_step(cfg, tcfg),
                           in_shardings=(state_shd, None),
                           out_shardings=(state_shd, None), donate_argnums=(0,))
        step = step_jit.lower(state, b0).compile()
        rec["kernels"] = "tpu_custom_call" in step.as_text()

        t0 = time.perf_counter()
        if supervised:
            with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
                sup = Supervisor(
                    SupervisorConfig(ckpt_dir=ckpt, max_steps=steps),
                    step, batch_fn, state, state_shardings=state_shd,
                    log=TraceCollector())
                metrics = sup.run()["metrics"]
            state = sup.state
        else:
            metrics = []
            for i in range(steps):
                state, m = step(state, batch_fn(i))
                metrics.append(jax.device_get(m))
        rec["run_s"] = time.perf_counter() - t0
        rec["losses"] = [float(m["loss"]) for m in metrics]
        rec["grad_norms"] = [float(m["grad_norm"]) for m in metrics]
        # every device of the mesh should hold its share of the live state
        rec["bytes_in_use"] = [(d.memory_stats() or {}).get("bytes_in_use")
                               for d in mesh_.devices.flat]
        del state
    return rec


def check_train(rec: dict, steps: int) -> None:
    losses = rec["losses"]
    require(len(losses) == steps and all(math.isfinite(x) for x in losses),
            f"train losses not {steps} finite values: {losses}")
    require(rec["kernels"], "train step holds no Pallas kernel")
    if "chunked_first_loss" in rec:
        want = rec["chunked_first_loss"]
        require(abs(losses[0] - want) <= LOSS_RTOL * abs(want),
                f"first loss {losses[0]} vs chunked path {want}")
        require(rec["grad_rel_err"] <= GRAD_RTOL,
                f"Pallas gradient of {rec['grad_rel_err_leaf']} off the chunked "
                f"path's by {rec['grad_rel_err']}")


def one_chip(meter: CompileMeter) -> None:
    from repro.configs import get_config

    cfg = get_config("qwen2-0.5b")
    rec = meter.phase(serve_phase, cfg, requests=8, prompt_len=128, max_new=32,
                      max_batch=8, max_seq=2048)
    require(rec["requests"] == 8 and rec["tokens_per_request"] == [32],
            "not every request returned its 32 tokens")
    require(all(rec["kernels"].values()), "a serving program holds no Pallas kernel")
    require(max(rec["logit_rel_err"].values()) <= LOGIT_RTOL,
            "Pallas logits disagree with the ref path")

    rec = meter.phase(train_phase, cfg, mesh="1x1", batch=4, seq=1024, steps=4)
    check_train(rec, 4)


def four_chips(meter: CompileMeter) -> None:
    """gemma3-4b at full depth on a 2x2 mesh, checked against one period.

    Its bf16 params plus f32 Adam moments (~47 GB) fit no single chip.  The
    reference is one 6-layer period: its first loss on the 1x1 mesh and on
    the 2x2 mesh must agree.
    """
    from repro.configs import get_config

    cfg = get_config("gemma3-4b")
    one = dataclasses.replace(cfg, n_layers=cfg.period)
    base = meter.phase(train_phase, one, mesh="1x1", batch=2, seq=1024, steps=1,
                       supervised=False, reference=False)
    check_train(base, 1)
    sharded = meter.phase(train_phase, one, mesh="2x2", batch=2, seq=1024,
                          steps=1, supervised=False, reference=False)
    check_train(sharded, 1)
    want = base["losses"][0]
    require(abs(sharded["losses"][0] - want) <= LOSS_RTOL * abs(want),
            f"2x2 first loss {sharded['losses'][0]} vs 1x1 {want}")
    want = base["grad_norms"][0]
    require(abs(sharded["grad_norms"][0] - want) <= GRAD_RTOL * abs(want),
            f"2x2 first grad norm {sharded['grad_norms'][0]} vs 1x1 {want}")

    full = meter.phase(train_phase, cfg, mesh="2x2", batch=4, seq=1024, steps=4,
                       supervised=False, reference=False)
    check_train(full, 4)
    held = full["bytes_in_use"]
    require(min(held) >= MEM_BALANCE * max(held),
            f"state not spread over the mesh: bytes_in_use {held}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded training phase")
    args = ap.parse_args()
    need = 4 if args.four_chips else 1

    import jax

    devices = jax.devices()
    dev = devices[0]
    require(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    require(len(devices) >= need, f"needs {need} chips, found {len(devices)}")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.hw.specs import host_chip
    from repro.launch.cache import enable_compile_cache

    emit({"phase": "setup", "chip": host_chip().name,
          "compile_cache": enable_compile_cache()})
    meter = CompileMeter()
    if args.four_chips:
        four_chips(meter)
    else:
        one_chip(meter)
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}})


if __name__ == "__main__":
    main()
