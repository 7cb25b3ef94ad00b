"""Property-based tests on the system's invariants (hypothesis API; offline
fallback harness in tests/prop.py)."""
import jax
import jax.numpy as jnp
import numpy as np

from prop import given, settings, st

from repro.kernels import ref
from repro.nn import core as nn

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# attention invariants
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=12)
@given(
    st.integers(1, 3),                # batch
    st.integers(2, 24),               # seq
    st.sampled_from([(2, 1), (2, 2), (4, 2)]),  # (Hq, Hkv)
    st.integers(0, 2),                # window selector
)
def test_attention_causality(B, S, heads, wsel):
    """Output at position t must not change when future tokens change."""
    Hq, Hkv = heads
    D = 8
    window = [None, 4, S][wsel] if S > 1 else None
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = ref.mha_ref(q, k, v, causal=True, window=window)
    t = S // 2
    k2 = k.at[:, t + 1 :].set(jax.random.normal(ks[3], (B, S - t - 1, Hkv, D)))
    v2 = v.at[:, t + 1 :].set(jax.random.normal(ks[3], (B, S - t - 1, Hkv, D)) * 3)
    out2 = ref.mha_ref(q, k2, v2, causal=True, window=window)
    np.testing.assert_allclose(out[:, : t + 1], out2[:, : t + 1], atol=1e-5, rtol=1e-5)


@settings(deadline=None, max_examples=10)
@given(st.integers(1, 2), st.integers(4, 32))
def test_attention_probability_convexity(B, S):
    """Attention output lies in the convex hull of V rows: bounded by per-dim
    min/max of the visible prefix."""
    Hq = Hkv = 2
    D = 4
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = ref.mha_ref(q, k, v, causal=True)
    for t in (0, S // 2, S - 1):
        vis = np.asarray(v[:, : t + 1])
        lo = vis.min(axis=1) - 1e-4  # (B, Hkv, D)... v is (B,S,Hkv,D) -> min over S
        hi = vis.max(axis=1) + 1e-4
        got = np.asarray(out[:, t]).reshape(B, Hkv, Hq // Hkv, D)
        assert (got >= lo[:, :, None]).all() and (got <= hi[:, :, None]).all()


@settings(deadline=None, max_examples=8)
@given(st.floats(5.0, 100.0))
def test_softcap_bounds(cap):
    x = jnp.linspace(-1e4, 1e4, 101)
    y = nn.softcap(x, cap)
    assert float(jnp.max(jnp.abs(y))) <= cap + 1e-3
    # monotone
    assert bool(jnp.all(jnp.diff(y) >= -1e-6))


# ---------------------------------------------------------------------------
# rmsnorm / rope invariants
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=10)
@given(st.integers(2, 64), st.floats(0.1, 10.0))
def test_rmsnorm_scale_invariance(D, alpha):
    """RMSNorm(αx) == RMSNorm(x) (up to eps)."""
    x = jax.random.normal(KEY, (3, D)) + 0.5
    s = jnp.zeros(D)
    a = ref.rmsnorm_ref(x, s)
    b = ref.rmsnorm_ref(x * alpha, s)
    np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)


@settings(deadline=None, max_examples=10)
@given(st.integers(2, 32), st.integers(0, 1000))
def test_rope_preserves_norm_and_relative_position(D2, pos0):
    D = D2 * 2
    x = jax.random.normal(KEY, (1, 4, 2, D))
    pos = jnp.arange(4)[None] + pos0
    y = nn.apply_rope(x, pos, theta=10000.0)
    np.testing.assert_allclose(
        jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-4
    )
    # relative property: <rope(q,m), rope(k,n)> depends only on m-n
    q = jax.random.normal(KEY, (1, 1, 1, D))
    k = jax.random.normal(jax.random.PRNGKey(8), (1, 1, 1, D))
    def dot_at(m, n):
        qm = nn.apply_rope(q, jnp.full((1, 1), m), 10000.0)
        kn = nn.apply_rope(k, jnp.full((1, 1), n), 10000.0)
        return float(jnp.sum(qm * kn))
    assert abs(dot_at(pos0 + 3, pos0) - dot_at(3, 0)) < 1e-2


# ---------------------------------------------------------------------------
# MoE invariants
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=6)
@given(st.integers(0, 2**31 - 1), st.sampled_from([(4, 1, 0, 2), (4, 2, 0, 4), (8, 2, 2, 4)]))
def test_moe_drops_no_pair_under_skewed_routing(seed, case):
    """Routing skewed onto expert 0: every pair on a held expert is computed
    (``moe_rows``, the output against a dense mask over the held experts)."""
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.nn import ffn as ffn_mod

    E, K, first, n_held = case
    cfg = reduced(get_config("dbrx-132b"))
    cfg = dataclasses.replace(cfg, param_dtype="float32", moe=dataclasses.replace(
        cfg.moe, n_experts=E, top_k=K, held_first=first, n_held=n_held))
    p = ffn_mod.moe_init(nn.ValueFactory(jax.random.PRNGKey(seed), jnp.float32), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 8, cfg.d_model)) + 1.0
    w = p["router"]["w"].at[:, 0].set(1.0)  # expert 0 wins every token
    p = dict(p, router={"w": w})
    y, aux = ffn_mod.moe_apply(p, x, cfg)

    probs = jax.nn.softmax(x.reshape(-1, cfg.d_model) @ w, axis=-1)
    gates, idx = jax.lax.top_k(probs, K)
    gates = gates / gates.sum(-1, keepdims=True)
    local = idx - first
    held = (local >= 0) & (local < n_held)
    assert int(aux["moe_rows"]) == int(held.sum())
    counts = np.bincount(np.asarray(local[held]), minlength=n_held)
    assert int(aux["moe_max_rows"]) == counts.max()
    # dense: every token through every held expert, weighted by its gate there
    gate = jnp.einsum("tke,tk->te", jax.nn.one_hot(local, n_held) * held[..., None], gates)
    xt = x.reshape(-1, cfg.d_model)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w1"])) * jnp.einsum(
        "td,edf->tef", xt, p["w3"])
    want = jnp.einsum("tef,efd,te->td", h, p["w2"], gate)
    np.testing.assert_allclose(y.reshape(-1, cfg.d_model), want, atol=2e-4, rtol=2e-4)


@settings(deadline=None, max_examples=6)
@given(st.integers(1, 64), st.sampled_from([1, 2, 6, 8]))
def test_moe_dispatch_chunks_divide(tokens, k):
    from repro.nn.ffn import DISPATCH_ROWS, dispatch_chunks

    T = tokens * 4096
    n = dispatch_chunks(T, k)
    assert T % n == 0 and ((T // n) * k <= DISPATCH_ROWS or T // n == 1)


# ---------------------------------------------------------------------------
# scan-state invariants (rwkv/mamba chunking == arbitrary re-chunking)
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=6)
@given(st.sampled_from([8, 16, 32]), st.sampled_from([4, 8, 16]))
def test_rwkv_chunk_size_independence(T, L):
    B, H, K = 1, 2, 4
    ks = jax.random.split(KEY, 6)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, K)) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * 0.3))
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, K, K)) * 0.1
    o1, s1 = ref.rwkv6_scan_chunked(r, k, v, w, u, s0, chunk=L)
    o2, s2 = ref.rwkv6_scan_chunked(r, k, v, w, u, s0, chunk=T)
    np.testing.assert_allclose(o1, o2, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s1, s2, atol=2e-5, rtol=2e-5)


@settings(deadline=None, max_examples=6)
@given(st.sampled_from([16, 32]), st.sampled_from([8, 16]))
def test_mamba_chunk_size_independence(T, L):
    B, DI, N = 1, 6, 3
    ks = jax.random.split(KEY, 7)
    x = jax.random.normal(ks[0], (B, T, DI))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, DI)))
    A = -jnp.exp(jax.random.normal(ks[2], (DI, N)) * 0.3)
    Bm, C = jax.random.normal(ks[3], (B, T, N)), jax.random.normal(ks[4], (B, T, N))
    D = jax.random.normal(ks[5], (DI,))
    h0 = jax.random.normal(ks[6], (B, DI, N)) * 0.1
    y1, h1 = ref.mamba_scan_chunked(x, dt, A, Bm, C, D, h0, chunk=L)
    y2, h2 = ref.mamba_scan_chunked(x, dt, A, Bm, C, D, h0, chunk=T)
    np.testing.assert_allclose(y1, y2, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(h1, h2, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# sharding invariants
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=20)
@given(
    st.sampled_from([(2, 2), (4, 2), (4, 16), (16, 16)]),
    st.tuples(st.integers(1, 512), st.integers(1, 512)),
)
def test_spec_dims_always_divisible(mesh_shape, dims):
    """Whatever the shape, emitted specs never violate divisibility."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.distributed import sharding as shd

    devs = np.array(jax.devices() * int(np.prod(mesh_shape)))[: int(np.prod(mesh_shape))]
    mesh = Mesh(devs.reshape(mesh_shape), ("data", "model"))
    spec = shd.spec_for(tuple(dims), "embed,mlp", shd.PARAM_RULES, mesh)
    for dim, part in zip(dims, tuple(spec) + (None,) * (2 - len(spec))):
        if part is None:
            continue
        parts = part if isinstance(part, tuple) else (part,)
        total = int(np.prod([mesh.shape[a] for a in parts]))
        assert dim % total == 0
