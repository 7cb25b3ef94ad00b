"""Flash custom-VJP: forward AND gradients match plain-AD-through-oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_vjp import flash_attention_fused

KEY = jax.random.PRNGKey(3)


@pytest.mark.parametrize(
    "window,softcap,q_offset",
    [(None, None, 0), (16, None, 0), (None, 30.0, 0), (16, 50.0, 0), (None, None, 24)],
)
def test_flash_vjp_matches_oracle_grads(window, softcap, q_offset):
    B, Sq, Hq, Hkv, D = 2, 40, 4, 2, 16
    Sk = Sq + q_offset
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D))
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D))
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D))
    cot = jax.random.normal(ks[3], (B, Sq, Hq, D))

    def loss_ref(q, k, v):
        o = ref.mha_ref(q, k, v, causal=True, window=window, softcap=softcap, q_offset=q_offset)
        return jnp.sum(o * cot)

    def loss_flash(q, k, v):
        o = flash_attention_fused(
            q, k, v, True, window, softcap, None, q_offset, 16
        )
        return jnp.sum(o * cot)

    o_ref = ref.mha_ref(q, k, v, causal=True, window=window, softcap=softcap, q_offset=q_offset)
    o_fl = flash_attention_fused(q, k, v, True, window, softcap, None, q_offset, 16)
    np.testing.assert_allclose(o_fl, o_ref, atol=2e-5, rtol=2e-5)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name}")


def test_flash_vjp_no_quadratic_residuals():
    """The point of the custom VJP: no (Sq, Sk) tensor survives to backward.
    Verified structurally: residual sizes scale O(S·D), not O(S²)."""
    B, S, H, D = 1, 256, 2, 8

    def run(S):
        q = jnp.ones((B, S, H, D))
        out, vjp = jax.vjp(
            lambda q: flash_attention_fused(q, q, q, True, None, None, None, 0, 64), q
        )
        res_bytes = sum(
            np.prod(x.shape) * x.dtype.itemsize
            for x in jax.tree.leaves(vjp)
            if hasattr(x, "shape")
        )
        return res_bytes

    b1, b2 = run(S), run(2 * S)
    assert b2 < b1 * 3, (b1, b2)  # linear-ish growth, not 4x (quadratic)


@pytest.mark.parametrize(
    "window,softcap,q_offset",
    [(None, None, 0), (16, None, 0), (None, 30.0, 0), (16, 50.0, 0), (None, None, 24)],
)
def test_pallas_flash_grads_match_oracle(window, softcap, q_offset):
    """The Pallas kernel's custom VJP (kernel forward + its log-sum-exp,
    recompute backward) differentiates like plain AD through the oracle."""
    from repro.kernels.flash_attention import flash_attention

    B, Sq, Hq, Hkv, D = 2, 40, 4, 2, 16
    Sk = Sq + q_offset
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D))
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D))
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D))
    cot = jax.random.normal(ks[3], (B, Sq, Hq, D))
    kw = dict(causal=True, window=window, softcap=softcap, q_offset=q_offset)

    def loss_ref(q, k, v):
        return jnp.sum(ref.mha_ref(q, k, v, **kw) * cot)

    def loss_pallas(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=16, block_k=16,
                                       interpret=True, **kw) * cot)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_pl = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_pl, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name}")
