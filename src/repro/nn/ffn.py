"""FFN blocks: dense gated-GLU and a dropless MoE over the experts held here.

The MoE layer is told which experts it holds (``MoEConfig.held``: a
contiguous block, by default all of them).  It routes every token over all
``n_experts`` (softmax, top-k, renormalised where the config says), sorts
the (token, choice) pairs that fall on its experts into ragged groups, one
per expert, computes them with a grouped matmul (kernels.ops.moe_ffn: the
megablox Pallas kernels on TPU), and adds each result back to its token
scaled by its gate.  Pairs routed to experts held elsewhere add nothing
here: under expert parallelism the chips that hold them add their part.
Nothing is dropped, so there is no capacity.  Shared experts are computed by
every chip alike.

The auxiliary losses (a sequence-level load balance over all experts'
probabilities and counts, and the router z-loss) and the routing counters
(``moe_rows``: pairs computed here; ``moe_max_rows``: the fullest held
expert's rows) are returned functionally and carried through the layer scan.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.nn import core as nn


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------


def ffn_init(pf: nn.ParamFactory, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    out_scale = 0.02 / max(1, 2 * cfg.n_layers) ** 0.5
    return {
        "w1": nn.linear_init(pf, "w1", (D,), (F,), ("embed",), ("mlp",)),
        "w3": nn.linear_init(pf, "w3", (D,), (F,), ("embed",), ("mlp",)),
        "w2": nn.linear_init(pf, "w2", (F,), (D,), ("mlp",), ("embed",), scale=out_scale),
    }


def ffn_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = nn.ACTIVATIONS[cfg.act]
    h = act(nn.linear(p["w1"], x).astype(jnp.float32)) * nn.linear(p["w3"], x).astype(
        jnp.float32
    )
    return nn.linear(p["w2"], h.astype(x.dtype))


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

# (token, choice) pairs dispatched at once: a layer's tokens go through the
# routed experts in chunks of at most this many pairs, each chunk recomputed
# in the backward, which bounds the sorted buffers (a chunk's T*K rows, as
# many as all its pairs could need) whatever the batch
DISPATCH_ROWS = 1 << 16


def moe_init(pf: nn.ParamFactory, cfg: ModelConfig) -> dict:
    assert cfg.moe is not None
    m = cfg.moe
    D, F = cfg.d_model, m.d_expert or cfg.d_ff
    _, E = m.held
    out_scale = 0.02 / max(1, 2 * cfg.n_layers) ** 0.5
    p = {
        "router": nn.linear_init(
            pf, "router", (D,), (m.n_experts,), ("embed",), ("experts",), scale=0.02
        ),
        "w1": pf.param("w1", (E, D, F), ("experts", "embed", "expert_mlp")),
        "w3": pf.param("w3", (E, D, F), ("experts", "embed", "expert_mlp")),
        "w2": pf.param(
            "w2", (E, F, D), ("experts", "expert_mlp", "embed"), scale=out_scale
        ),
    }
    if m.n_shared:
        p["shared"] = ffn_init(pf, cfg, d_ff=m.n_shared * F)
    return p


@jax.custom_vjp
def _combine(out: jax.Array, order: jax.Array, slot: jax.Array, w: jax.Array) -> jax.Array:
    """y[t] = sum_k w[t, k] * out[slot[t, k]], in f32.

    out: (T*K, D) expert rows in ``order`` (the pairs, sorted); slot: (T, K)
    each pair's row; w: (T, K) gates.  Neither pass forms a (T, K, D) array:
    the forward adds one choice at a time, the backward works on the sorted
    rows.
    """
    y = 0.0
    for k in range(slot.shape[1]):
        y = y + out.at[slot[:, k]].get(unique_indices=True).astype(jnp.float32) * w[:, k, None]
    return y


def _combine_fwd(out, order, slot, w):
    return _combine(out, order, slot, w), (out, order, slot, w)


def _combine_bwd(res, dy):
    out, order, slot, w = res
    dy_rows = dy.astype(out.dtype)[order // slot.shape[1]]  # each row's token's dy
    gate = w.reshape(-1)[order].astype(out.dtype)
    d_gate = jnp.sum(out.astype(jnp.float32) * dy_rows.astype(jnp.float32), axis=-1)
    return dy_rows * gate[:, None], None, None, d_gate[slot]


_combine.defvjp(_combine_fwd, _combine_bwd)


def dispatch_chunks(T: int, K: int) -> int:
    """How many chunks a layer's T tokens are dispatched in: the fewest that
    divide T and hold at most ``DISPATCH_ROWS`` (token, choice) pairs each."""
    n = -(-T * K // DISPATCH_ROWS)
    while T % n:
        n += 1
    return n


def _routed(p, chunk, *, first: int, n_held: int, act: str):
    """(y (T, D), rows per held expert) of one chunk's routed experts:
    ``chunk`` is its tokens (T, D), their gates and their choices (T, K)."""
    xt, gates, idx = chunk
    T, K = idx.shape
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(-1) - first  # (T*K,) the held expert of each pair
        held = (local >= 0) & (local < n_held)
        group = jnp.where(held, local, n_held)  # absent experts sort last
        order = jnp.argsort(group, stable=True)  # pairs, grouped by expert
        sizes = jnp.zeros((n_held + 1,), jnp.int32).at[group].add(1)[:n_held]
        rows = xt[order // K]  # (T*K, D)

    with jax.named_scope("moe.experts"):
        out = ops.moe_ffn(
            rows, p["w1"], p["w3"], p["w2"], sizes, act=nn.ACTIVATIONS[act]
        )

    with jax.named_scope("moe.combine"):
        slot = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * K, dtype=order.dtype), unique_indices=True
        )  # each pair's row in ``out``
        w = jnp.where(held, gates.reshape(-1), 0.0).reshape(T, K)
        y = _combine(out, order, slot.reshape(T, K), w).astype(xt.dtype)
    return y, sizes


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """x: (B, S, D) -> (y, aux): the losses ``moe_load_balance`` and
    ``moe_z_loss`` and the counters ``moe_rows`` and ``moe_max_rows``."""
    assert cfg.moe is not None
    m = cfg.moe
    B, S, D = x.shape
    T, E, K = B * S, m.n_experts, m.top_k
    first, n_held = m.held
    xt = x.reshape(T, D)

    with jax.named_scope("moe.route"):
        logits = jax.lax.dot_general(
            xt, p["router"]["w"], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, K)  # (T, K)
        if m.norm_topk:
            gates = gates / gates.sum(-1, keepdims=True)
        # per sequence: alpha * sum_e f_e P_e, f_e = E/(S K) * the row's pairs
        # on e, P_e = the row's mean probability of e; then the mean over rows
        counts = jax.nn.one_hot(idx, E, dtype=jnp.float32).reshape(B, S * K, E).sum(1)
        f = counts * (E / (S * K))
        balance = jnp.mean(jnp.sum(f * probs.reshape(B, S, E).mean(1), axis=-1))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    n = dispatch_chunks(T, K)
    routed = jax.checkpoint(partial(_routed, p, first=first, n_held=n_held, act=cfg.act))
    y, sizes = jax.lax.map(routed, (xt.reshape(n, T // n, D), gates.reshape(n, T // n, K),
                                    idx.reshape(n, T // n, K)))
    y = y.reshape(B, S, D)
    sizes = sizes.sum(0)

    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + ffn_apply(p["shared"], x, cfg)

    aux = {
        "moe_load_balance": balance * m.router_aux_weight,
        "moe_z_loss": z * m.router_z_weight,
        "moe_rows": sizes.sum(),
        "moe_max_rows": sizes.max(),
    }
    return y, aux
