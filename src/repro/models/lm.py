"""Unified decoder-only LM over a per-layer pattern spec.

One model covers all 10 assigned architectures (dense / MoE / hybrid-SSM /
RWKV / VLM-stub / audio-stub) via ``ModelConfig.layer_pattern``.  Layers are
stacked per pattern position and **scanned over periods**, keeping the HLO
size O(period) instead of O(n_layers) — essential for fast multi-pod
compilation at 512 devices.

Execution surfaces:
  * ``forward``      — hidden states for a full sequence (train / prefill).
  * ``loss_fn``      — token-chunked cross-entropy (never materialises the
                       (B·S, vocab) logits; each chunk is rematerialised in
                       the backward pass).
  * ``prefill``      — forward + KV/SSM cache construction + last-pos logits.
  * ``decode_step``  — one token per sequence against the caches.

Static tracepoints (the paper's USDT analogue, repro.core.tracepoints) are
compiled in at the graph-level boundaries: embed, after the layer stack,
final hidden, loss.  (Markers must stay outside lax.scan bodies — the tape is
functional trace-time state; per-layer taps are provided by the uprobes-style
jaxpr injection instead, which attaches by named_scope.)
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.core import tracepoints as tp
from repro.kernels.flash_attention import FLASH_LSE, FLASH_OUT
from repro.nn import attention as attn
from repro.nn import core as nn
from repro.nn import ffn as ffn_mod
from repro.nn import frontend as frontend_mod
from repro.nn import mamba as mamba_mod
from repro.nn import rwkv as rwkv_mod

PyTree = Any


# ---------------------------------------------------------------------------
# Parameter construction (single source of truth for values / axes / shapes)
# ---------------------------------------------------------------------------


def _block_init(pf: nn.ParamFactory, cfg: ModelConfig, spec: LayerSpec) -> dict:
    p: dict = {"norm1": nn.rmsnorm_init(pf, "norm1", cfg.d_model)}
    with pf.scope("mixer"):
        if spec.mixer in ("ga", "swa"):
            p["mixer"] = attn.attention_init(pf, cfg)
        elif spec.mixer == "mamba":
            p["mixer"] = mamba_mod.mamba_init(pf, cfg)
        elif spec.mixer == "rwkv":
            p["mixer"] = rwkv_mod.time_mix_init(pf, cfg)
        else:
            raise ValueError(spec.mixer)
    if cfg.post_block_norms:
        p["norm1_post"] = nn.rmsnorm_init(pf, "norm1_post", cfg.d_model)
    if spec.ffn != "none":
        p["norm2"] = nn.rmsnorm_init(pf, "norm2", cfg.d_model)
        with pf.scope("ffn"):
            if spec.ffn == "dense":
                p["ffn"] = ffn_mod.ffn_init(pf, cfg)
            elif spec.ffn == "moe":
                p["ffn"] = ffn_mod.moe_init(pf, cfg)
            elif spec.ffn == "rwkv_ffn":
                p["ffn"] = rwkv_mod.channel_mix_init(pf, cfg)
            else:
                raise ValueError(spec.ffn)
        if cfg.post_block_norms:
            p["norm2_post"] = nn.rmsnorm_init(pf, "norm2_post", cfg.d_model)
    return p


def _unscanned_layers(cfg: ModelConfig) -> list[tuple[str, LayerSpec]]:
    """(scope_name, spec) for layers outside the scanned periods."""
    out = []
    for i in range(cfg.first_k_dense):
        out.append((f"head{i}", cfg.layer_spec(i)))
    tail_start = cfg.first_k_dense + cfg.n_periods * cfg.period
    for i in range(tail_start, cfg.n_layers):
        out.append((f"tail{i}", cfg.layer_spec(i)))
    return out


def build_params(cfg: ModelConfig, pf: nn.ParamFactory) -> dict:
    p: dict = {"embed": nn.embedding_init(pf, "embed", cfg.vocab_size, cfg.d_model)}
    if cfg.frontend != "text":
        with pf.scope("frontend"):
            p["frontend"] = frontend_mod.frontend_init(pf, cfg)
    for name, spec in _unscanned_layers(cfg):
        with pf.scope(name):
            p[name] = _block_init(pf, cfg, spec)
    if cfg.n_periods > 0:
        p["blocks"] = {}
        for pos, spec in enumerate(cfg.layer_pattern):
            with pf.scope(f"pos{pos}"):
                p["blocks"][f"pos{pos}"] = _stacked_init(pf, cfg, spec, cfg.n_periods)
    p["final_norm"] = nn.rmsnorm_init(pf, "final_norm", cfg.d_model)
    if not cfg.tied_embeddings:
        p["lm_head"] = nn.embedding_init(pf, "lm_head", cfg.vocab_size, cfg.d_model)
    return p


def _stacked_init(pf: nn.ParamFactory, cfg: ModelConfig, spec: LayerSpec, n: int):
    """Stack one pattern position's params over the n periods (scan axis)."""
    if isinstance(pf, nn.AxesFactory):
        sub = _block_init(pf, cfg, spec)
        return jax.tree.map(lambda axes: "layers," + axes, sub)
    if isinstance(pf, nn.ValueFactory):
        keys = jax.random.split(pf._key, n)

        def one(key):
            sub_pf = nn.ValueFactory(key, pf.param_dtype)
            sub_pf._scope = list(pf._scope)
            return _block_init(sub_pf, cfg, spec)

        return jax.vmap(one)(keys)
    if isinstance(pf, nn.ShapeFactory):
        sub = _block_init(pf, cfg, spec)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), sub
        )
    raise TypeError(type(pf))


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    return build_params(cfg, nn.ValueFactory(key, jnp.dtype(cfg.param_dtype)))


def param_axes(cfg: ModelConfig) -> dict:
    return build_params(cfg, nn.AxesFactory())


def abstract_params(cfg: ModelConfig) -> dict:
    """Allocation-free param skeleton (dry-run)."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def _block_cache(
    cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int, dtype
) -> dict:
    c: dict = {}
    if spec.mixer in ("ga", "swa"):
        c["mixer"] = attn.init_cache(cfg, spec.mixer, batch, max_seq, dtype)
    elif spec.mixer == "mamba":
        c["mixer"] = mamba_mod.init_cache(cfg, batch, dtype)
    elif spec.mixer == "rwkv":
        c["mixer"] = rwkv_mod.init_time_cache(cfg, batch, dtype)
    if spec.ffn == "rwkv_ffn":
        c["ffn"] = rwkv_mod.init_channel_cache(cfg, batch, dtype)
    return c


def init_caches(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    dtype = jnp.dtype(cfg.activation_dtype)
    caches: dict = {}
    for name, spec in _unscanned_layers(cfg):
        caches[name] = _block_cache(cfg, spec, batch, max_seq, dtype)
    if cfg.n_periods > 0:
        caches["blocks"] = {}
        for pos, spec in enumerate(cfg.layer_pattern):
            one = _block_cache(cfg, spec, batch, max_seq, dtype)
            caches["blocks"][f"pos{pos}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape), one
            )
    return caches


def abstract_caches(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_seq))


def cache_axes(cfg: ModelConfig) -> dict:
    """Logical axes for cache leaves (mirrors init_caches structure)."""
    A = nn.axes_str

    def block_axes(spec: LayerSpec):
        c = {}
        if spec.mixer in ("ga", "swa"):
            c["mixer"] = {
                "k": A(("batch", "cache_seq", "kv_heads", "head_dim")),
                "v": A(("batch", "cache_seq", "kv_heads", "head_dim")),
                "pos_ids": A(("batch", "cache_seq")),
            }
        elif spec.mixer == "mamba":
            c["mixer"] = {
                "conv": A(("batch", None, "mlp")),
                "ssm": A(("batch", "mlp", None)),
            }
        elif spec.mixer == "rwkv":
            c["mixer"] = {
                "shift": A(("batch", "embed")),
                "wkv": A(("batch", "heads", "head_dim", "head_dim")),
            }
        if spec.ffn == "rwkv_ffn":
            c["ffn"] = {"shift": A(("batch", "embed"))}
        return c

    axes: dict = {}
    for name, spec in _unscanned_layers(cfg):
        axes[name] = block_axes(spec)
    if cfg.n_periods > 0:
        axes["blocks"] = {}
        for pos, spec in enumerate(cfg.layer_pattern):
            axes["blocks"][f"pos{pos}"] = jax.tree.map(
                lambda a: "layers," + a, block_axes(spec)
            )
    return axes


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _aux_zero() -> dict:
    """What a block adds to the step's metrics: its auxiliary loss, and the
    MoE layer's routing counters (pairs computed on held experts, summed over
    layers; the fullest held expert's rows, the max over layers)."""
    return {"loss": jnp.zeros((), jnp.float32),
            "moe_rows": jnp.zeros((), jnp.int32),
            "moe_max_rows": jnp.zeros((), jnp.int32)}


def _aux_add(a: dict, b: dict) -> dict:
    return {"loss": a["loss"] + b["loss"],
            "moe_rows": a["moe_rows"] + b["moe_rows"],
            "moe_max_rows": jnp.maximum(a["moe_max_rows"], b["moe_max_rows"])}


def _block_apply(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    spec: LayerSpec,
    positions: jax.Array,
    *,
    mode: str,
    cache: Optional[dict],
) -> tuple[jax.Array, dict, Optional[dict]]:
    """Returns (x, aux, new_cache); aux as ``_aux_zero`` gives it."""
    aux = _aux_zero()
    new_cache: dict = {}
    h = nn.rmsnorm(p["norm1"], x, cfg.norm_eps)
    mixer_cache = cache.get("mixer") if cache else None
    with jax.named_scope(f"mixer_{spec.mixer}"):
        if spec.mixer in ("ga", "swa"):
            h, mc = attn.attention_apply(
                p["mixer"], h, cfg, spec.mixer, positions, mode=mode, cache=mixer_cache
            )
        elif spec.mixer == "mamba":
            h, mc = mamba_mod.mamba_apply(p["mixer"], h, cfg, mode=mode, cache=mixer_cache)
        elif spec.mixer == "rwkv":
            h, mc = rwkv_mod.time_mix_apply(
                p["mixer"], h, cfg, mode=mode, cache=mixer_cache
            )
    if mc is not None:
        new_cache["mixer"] = mc
    if "norm1_post" in p:
        h = nn.rmsnorm(p["norm1_post"], h, cfg.norm_eps)
    x = x + h
    if spec.ffn != "none":
        h = nn.rmsnorm(p["norm2"], x, cfg.norm_eps)
        ffn_cache = cache.get("ffn") if cache else None
        with jax.named_scope(f"ffn_{spec.ffn}"):
            if spec.ffn == "dense":
                h = ffn_mod.ffn_apply(p["ffn"], h, cfg)
            elif spec.ffn == "moe":
                h, moe_aux = ffn_mod.moe_apply(p["ffn"], h, cfg)
                aux = {
                    "loss": moe_aux["moe_load_balance"] + moe_aux["moe_z_loss"],
                    "moe_rows": moe_aux["moe_rows"],
                    "moe_max_rows": moe_aux["moe_max_rows"],
                }
            elif spec.ffn == "rwkv_ffn":
                h, fc = rwkv_mod.channel_mix_apply(p["ffn"], h, cfg, cache=ffn_cache)
                if fc is not None:
                    new_cache["ffn"] = fc
        if "norm2_post" in p:
            h = nn.rmsnorm(p["norm2_post"], h, cfg.norm_eps)
        x = x + h
    return x, aux, (new_cache or None)


def _remat(fn, policy: str):
    if policy == "everything":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    # "nothing": recompute the period body, but keep the Pallas flash forward's
    # out and lse, which only a second run of the kernel could rebuild.
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(FLASH_OUT, FLASH_LSE)
    )


def _unscanned_apply(p, x, cfg, spec, positions, mode, cache):
    """A layer outside the scanned periods, under the same remat policy."""

    def block(p, x, cache):
        return _block_apply(p, x, cfg, spec, positions, mode=mode, cache=cache)

    return _remat(block, cfg.remat_policy)(p, x, cache)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    positions: Optional[jax.Array] = None,
    frontend_embed: Optional[jax.Array] = None,
    *,
    mode: str = "full",
    caches: Optional[dict] = None,
) -> tuple[jax.Array, dict, Optional[dict]]:
    """tokens: (B, S) -> (hidden (B, S, D), aux (``_aux_zero``), new_caches)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    with jax.named_scope("embed"):
        x = nn.embed(params["embed"], tokens, scale_by_dim=cfg.scale_embedding)
        x = x.astype(jnp.dtype(cfg.activation_dtype))
        if cfg.frontend != "text" and frontend_embed is not None:
            x = x + frontend_mod.frontend_apply(
                params["frontend"], frontend_embed.astype(x.dtype)
            )
    tp.point("lm.embed_out", x)
    aux = _aux_zero()
    new_caches: dict = {}

    # head layers (unscanned)
    unscanned = _unscanned_layers(cfg)
    for name, spec in unscanned:
        if not name.startswith("head"):
            continue
        with jax.named_scope(name):
            x, a, c = _unscanned_apply(
                params[name], x, cfg, spec, positions, mode, (caches or {}).get(name)
            )
        aux = _aux_add(aux, a)
        if c is not None:
            new_caches[name] = c

    # scanned periods
    if cfg.n_periods > 0:
        block_params = params["blocks"]
        block_caches = (caches or {}).get("blocks")
        want_cache = block_caches is not None

        def period_body(carry, xs):
            x, aux = carry
            pp, pc = xs
            out_caches = {}
            for pos, spec in enumerate(cfg.layer_pattern):
                with jax.named_scope(f"pos{pos}_{spec.mixer}_{spec.ffn}"):
                    x, a, c = _block_apply(
                        pp[f"pos{pos}"], x, cfg, spec, positions, mode=mode,
                        cache=pc[f"pos{pos}"] if pc is not None else None,
                    )
                aux = _aux_add(aux, a)
                if c is not None:
                    out_caches[f"pos{pos}"] = c
            return (x, aux), (out_caches if want_cache else None)

        body = _remat(period_body, cfg.remat_policy)
        if cfg.scan_layers:
            (x, aux), scan_caches = jax.lax.scan(
                body, (x, aux), (block_params, block_caches)
            )
        else:
            # unrolled (analysis/dry-run): same math, every period explicit in
            # the HLO so cost_analysis prices all layers.
            per_period = []
            for i in range(cfg.n_periods):
                xs_i = jax.tree.map(lambda a: a[i], (block_params, block_caches))
                (x, aux), c_i = body((x, aux), xs_i)
                per_period.append(c_i)
            scan_caches = (
                jax.tree.map(lambda *xs: jnp.stack(xs), *per_period)
                if want_cache else None
            )
        if want_cache:
            new_caches["blocks"] = scan_caches

    # tail layers (unscanned)
    for name, spec in unscanned:
        if not name.startswith("tail"):
            continue
        with jax.named_scope(name):
            x, a, c = _unscanned_apply(
                params[name], x, cfg, spec, positions, mode, (caches or {}).get(name)
            )
        aux = _aux_add(aux, a)
        if c is not None:
            new_caches[name] = c

    tp.point("lm.stack_out", x)
    with jax.named_scope("final_norm"):
        x = nn.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux, (new_caches or None)


def _logits(params: dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    table = params["embed"] if cfg.tied_embeddings else params["lm_head"]
    logits = nn.unembed(table, hidden)  # f32
    return nn.softcap(logits, cfg.final_logit_softcap)


# ---------------------------------------------------------------------------
# Loss (token-chunked cross-entropy)
# ---------------------------------------------------------------------------


def loss_fn(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    labels: jax.Array,
    frontend_embed: Optional[jax.Array] = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Next-token CE over all positions; logits never fully materialised."""
    hidden, aux, _ = forward(params, cfg, tokens, frontend_embed=frontend_embed)
    B, S, D = hidden.shape
    T = B * S
    chunk = min(cfg.loss_chunk, T)
    n_chunks = T // chunk if T % chunk == 0 else 1
    if T % chunk != 0:
        chunk = T
    h = hidden.reshape(n_chunks, chunk, D)
    y = labels.reshape(n_chunks, chunk)
    table = params["embed"] if cfg.tied_embeddings else params["lm_head"]
    if cfg.loss_table_replicated:
        # §Perf: the FSDP ('data') shard of the table's embed dim would force
        # a partial-sum all-reduce of every chunk's logits (n_chunks of them);
        # replicating the embed dim here hoists ONE all-gather of the table
        # out of the loss loop instead.  Vocab stays TP-sharded.
        from repro.distributed.constrain import constrain

        table = {"table": constrain(table["table"], "vocab", None)}

    @jax.checkpoint
    def chunk_loss(carry, xs):
        h_c, y_c = xs
        logits = nn.unembed(table, h_c)  # (chunk, V) f32
        logits = nn.softcap(logits, cfg.final_logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_c[:, None], axis=-1)[:, 0]
        nll = (lse - gold).sum()
        zl = (lse**2).sum() * cfg.z_loss_weight
        nll_sum, z_sum = carry
        return (nll_sum + nll, z_sum + zl), None

    (nll_sum, z_sum), _ = jax.lax.scan(
        chunk_loss, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (h, y)
    )
    ce = nll_sum / T
    z = z_sum / T
    loss = ce + z + aux["loss"]
    tp.point("lm.loss", loss)
    metrics = {"ce": ce, "z_loss": z, "aux": aux["loss"], "tokens": jnp.float32(T)}
    if cfg.moe is not None:
        metrics.update(moe_rows=aux["moe_rows"], moe_max_rows=aux["moe_max_rows"])
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving surfaces
# ---------------------------------------------------------------------------


def prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    frontend_embed: Optional[jax.Array] = None,
    *,
    max_seq: Optional[int] = None,
) -> tuple[jax.Array, dict]:
    """Process the prompt; returns (last-position logits (B, V), caches)."""
    B, S = tokens.shape
    caches = init_caches(cfg, B, max_seq or S)
    hidden, _, new_caches = forward(
        params, cfg, tokens, frontend_embed=frontend_embed, mode="full", caches=caches
    )
    logits = _logits(params, cfg, hidden[:, -1:])[:, 0]
    tp.point("lm.prefill_logits", logits)
    return logits, new_caches


def decode_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    cur_pos: jax.Array,
    caches: dict,
    frontend_embed: Optional[jax.Array] = None,
) -> tuple[jax.Array, dict]:
    """tokens: (B,) new token ids; cur_pos: (B,) absolute positions.

    Returns (logits (B, V), updated caches).
    """
    positions = cur_pos[:, None].astype(jnp.int32)
    hidden, _, new_caches = forward(
        params,
        cfg,
        tokens[:, None],
        positions,
        frontend_embed=frontend_embed,
        mode="decode",
        caches=caches,
    )
    logits = _logits(params, cfg, hidden[:, -1])
    tp.point("lm.decode_logits", logits)
    return logits, new_caches
