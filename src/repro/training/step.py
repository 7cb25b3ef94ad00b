"""Train-step builder: microbatch accumulation + AdamW + sharding constraints.

The returned ``train_step(state, batch)`` is pure and jit/pjit-ready:
* microbatch gradient accumulation via lax.scan (accumulator dtype is
  configurable — bf16 accumulation is the gradient-compression knob that
  halves accumulation HBM and cross-pod all-reduce bytes);
* static tracepoints fire at step level (the USDT analogue);
* lifecycle events (step spawn/exit) are recorded by the caller
  (repro.runtime.supervisor), keeping the step function pure.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import tracepoints as tp
from repro.models import lm
from repro.training import optim

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: optim.AdamWConfig = optim.AdamWConfig()
    microbatches: int = 1
    grad_accum_dtype: str = "float32"  # 'bfloat16' = compressed accumulation


def init_train_state(cfg: ModelConfig, tcfg: TrainConfig, key: jax.Array) -> dict:
    params = lm.init_params(cfg, key)
    opt_cfg = dataclasses.replace(tcfg.opt, moment_dtype=cfg.moment_dtype)
    return {"params": params, "opt": optim.init_opt_state(params, opt_cfg)}


def abstract_train_state(cfg: ModelConfig, tcfg: TrainConfig) -> dict:
    return jax.eval_shape(lambda k: init_train_state(cfg, tcfg, k), jax.random.PRNGKey(0))


def train_state_axes(cfg: ModelConfig) -> dict:
    """Logical axes for the whole train state (opt moments mirror params)."""
    p_axes = lm.param_axes(cfg)
    return {
        "params": p_axes,
        "opt": {"mu": p_axes, "nu": p_axes, "step": ""},
    }


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig):
    """Returns train_step(state, batch) -> (state, metrics).

    batch: {"tokens": (B, S) int32, "labels": (B, S) int32,
            optional "frontend_embed": (B, S, D)}.
    """
    opt_cfg = dataclasses.replace(tcfg.opt, moment_dtype=cfg.moment_dtype)
    n_micro = tcfg.microbatches
    acc_dtype = jnp.dtype(tcfg.grad_accum_dtype)

    def loss_for(params, tokens, labels, fe):
        loss, metrics = lm.loss_fn(params, cfg, tokens, labels, fe)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_for, has_aux=True)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        params = state["params"]
        tokens, labels = batch["tokens"], batch["labels"]
        fe = batch.get("frontend_embed")

        if n_micro == 1:
            (loss, metrics), grads = grad_fn(params, tokens, labels, fe)
        else:
            B = tokens.shape[0]
            assert B % n_micro == 0, f"batch {B} % microbatches {n_micro}"
            mb = B // n_micro

            def split(x):
                return x.reshape((n_micro, mb) + x.shape[1:])

            mb_batch = jax.tree.map(split, {"t": tokens, "l": labels, "f": fe})

            def body(carry, xs):
                acc, loss_sum, rows, max_rows = carry
                (loss, m), g = grad_fn(params, xs["t"], xs["l"], xs.get("f"))
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(acc_dtype), acc, g
                )
                if cfg.moe is not None:
                    rows = rows + m["moe_rows"]
                    max_rows = jnp.maximum(max_rows, m["moe_max_rows"])
                return (acc, loss_sum + loss, rows, max_rows), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params
            )
            count = jnp.zeros((), jnp.int32)
            (grads, loss_sum, rows, max_rows), _ = jax.lax.scan(
                body, (zero, jnp.zeros((), jnp.float32), count, count), mb_batch
            )
            grads = jax.tree.map(lambda g: (g / n_micro).astype(jnp.float32), grads)
            loss = loss_sum / n_micro
            metrics = {"ce": loss, "z_loss": jnp.zeros(()), "aux": jnp.zeros(()),
                       "tokens": jnp.float32(tokens.size)}
            if cfg.moe is not None:
                metrics.update(moe_rows=rows, moe_max_rows=max_rows)

        new_params, new_opt, opt_metrics = optim.adamw_update(
            params, grads, state["opt"], opt_cfg
        )
        tp.point("train.loss", loss)
        tp.point("train.grad_norm", opt_metrics["grad_norm"])
        out_metrics = {"loss": loss, **metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, out_metrics

    return train_step
