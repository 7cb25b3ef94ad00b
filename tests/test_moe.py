"""The dropless expert layer (``nn/ffn.py::moe_apply``) against the plain
float32 reference's MoE FFN (``bench/references/moe_lm.py``), at a small
size on the CPU on seeded random weights, and the expert share against the
uncut layer."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ops
from repro.nn import core as nn
from repro.nn import ffn as ffn_mod

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
B, S = 2, 16


def _cfg(**moe):
    cfg = reduced(get_config("deepseek-moe-16b"))
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe))


def _layer(cfg, seed=0):
    p = ffn_mod.moe_init(nn.ValueFactory(jax.random.PRNGKey(seed), jnp.float32), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, cfg.d_model))
    return p, x


def _reference():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from benchlib import load

    return load("references", "moe_lm")


def _ref_model(cfg) -> dict:
    m = cfg.moe
    first, n_held = m.held
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.d_ff, "first_k_dense_replace": cfg.first_k_dense,
            "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab_size,
            "router_experts": m.n_experts, "n_routed_experts": n_held,
            "held_first_expert": first, "moe_intermediate_size": m.d_expert,
            "n_shared_experts": m.n_shared, "num_experts_per_tok": m.top_k,
            "norm_topk_prob": m.norm_topk}


@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_layer_holding_every_expert_matches_reference(impl, monkeypatch):
    """Outputs, balance loss and gradients (input, router, experts, shared)."""
    monkeypatch.setattr(ops, "_IMPL", impl)
    cfg = _cfg()
    p, x = _layer(cfg)
    r = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    ref = _reference()
    m = _ref_model(cfg)
    alpha = cfg.moe.router_aux_weight

    def program(p, x):
        y, aux = ffn_mod.moe_apply(p, x, cfg)
        return jnp.sum(y * r) + aux["moe_load_balance"], (y, aux)

    def reference(p, x):
        ein = ref.dl._einsum("f32")
        outs = [ref.moe_ffn(x[b], p, m, ein) for b in range(B)]
        y = jnp.stack([o[0] for o in outs])
        balance = alpha * jnp.mean(jnp.stack([o[1] for o in outs]))
        return jnp.sum(y * r) + balance, (y, balance, sum(o[2] for o in outs))

    (_, (y, aux)), g = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(p, x)
    with jax.default_matmul_precision("highest"):
        (_, (y_ref, bal_ref, held)), g_ref = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True)(p, x)
    np.testing.assert_allclose(y, y_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(aux["moe_load_balance"], bal_ref, rtol=1e-5)
    assert int(aux["moe_rows"]) == int(held) == B * S * cfg.moe.top_k
    for got, want in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares' routed parts, the shared experts counted once, give the
    whole layer; their pairs give all of its pairs."""
    cfg = _cfg()
    E = cfg.moe.n_experts
    p, x = _layer(cfg, seed=3)
    y_full, aux_full = ffn_mod.moe_apply(p, x, cfg)
    shared = ffn_mod.ffn_apply(p["shared"], x, cfg)
    total, rows = -3 * shared, 0
    for first in range(0, E, E // 4):
        part = _cfg(held_first=first, n_held=E // 4)
        held = {k: p[k][first:first + E // 4] for k in ("w1", "w3", "w2")}
        y, aux = ffn_mod.moe_apply(dict(p, **held), x, part)
        np.testing.assert_allclose(aux["moe_load_balance"], aux_full["moe_load_balance"])
        total, rows = total + y, rows + int(aux["moe_rows"])
    np.testing.assert_allclose(total, y_full, atol=2e-5, rtol=2e-5)
    assert rows == int(aux_full["moe_rows"]) == B * S * cfg.moe.top_k


def test_chunked_dispatch_equals_one_chunk(monkeypatch):
    cfg = _cfg(held_first=2, n_held=4)
    p, x = _layer(cfg, seed=5)

    def run():
        return jax.value_and_grad(
            lambda p, x: jnp.sum(ffn_mod.moe_apply(p, x, cfg)[0] ** 2), argnums=(0, 1))(p, x)

    one = run()
    monkeypatch.setattr(ffn_mod, "DISPATCH_ROWS", 8 * cfg.moe.top_k)
    assert ffn_mod.dispatch_chunks(B * S, cfg.moe.top_k) == B * S // 8
    chunked = run()
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(chunked)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
