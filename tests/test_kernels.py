"""Per-kernel allclose sweeps: Pallas (interpret=True) vs. the pure-jnp oracle,
plus the chunked production paths vs. the same oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_gmm import gmm
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rwkv6_scan import rwkv6_scan

KEY = jax.random.PRNGKey(42)


def tols(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,Hq,Hkv,D,window,softcap",
    [
        (2, 64, 64, 4, 4, 16, None, None),      # MHA
        (2, 64, 64, 4, 2, 16, None, None),      # GQA
        (1, 96, 96, 4, 1, 32, None, None),      # MQA, non-pow2 seq
        (2, 64, 64, 4, 2, 16, 16, None),        # sliding window
        (2, 64, 64, 4, 2, 16, None, 30.0),      # softcap (gemma2)
        (2, 64, 64, 4, 2, 16, 16, 50.0),        # both
        (1, 40, 40, 2, 2, 8, None, None),       # ragged (padding path)
    ],
)
def test_flash_attention_vs_oracle(B, Sq, Sk, Hq, Hkv, D, window, softcap, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), dtype)
    want = ref.mha_ref(q, k, v, causal=True, window=window, softcap=softcap)
    got = flash_attention(
        q, k, v, causal=True, window=window, softcap=softcap,
        block_q=32, block_k=32, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tols(dtype)
    )


def test_flash_attention_q_offset():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 16, 4, 16))
    k = jax.random.normal(ks[1], (2, 80, 2, 16))
    v = jax.random.normal(ks[2], (2, 80, 2, 16))
    want = ref.mha_ref(q, k, v, causal=True, q_offset=64)
    got = flash_attention(q, k, v, causal=True, q_offset=64, block_q=16, block_k=32, interpret=True)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_chunked_and_local_vs_oracle():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    for window, cap in [(None, None), (24, None), (24, 40.0)]:
        want = ref.mha_ref(q, k, v, causal=True, window=window, softcap=cap)
        got = ref.flash_attention_chunked(q, k, v, causal=True, window=window, softcap=cap, block_k=24)
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
        if window:
            got2 = ref.local_window_attention(q, k, v, window=window, softcap=cap, block_q=16)
            np.testing.assert_allclose(got2, want, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window,softcap", [(None, None), (8, None), (None, 30.0)])
def test_decode_attention_vs_oracle(window, softcap, dtype):
    B, Hq, Hkv, D, S = 2, 4, 2, 16, 40
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kc = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    vc = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    cur = jnp.array([S - 1, 17])
    want = ref.decode_attention_ref(q, kc, vc, pos, cur, window=window, softcap=softcap)
    got = decode_attention(
        q, kc, vc, pos, cur, window=window, softcap=softcap, block_s=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tols(dtype)
    )


def test_decode_attention_ring_buffer_semantics():
    """Slot-position masking must equal attention over the positions present."""
    B, Hq, Hkv, D, S, W = 1, 2, 1, 8, 8, 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, D))
    kc = jax.random.normal(ks[1], (B, S, Hkv, D))
    vc = jax.random.normal(ks[2], (B, S, Hkv, D))
    # ring holding positions 10..17 in wrapped order, cur=17, window 6
    pos = jnp.asarray([[16, 17, 10, 11, 12, 13, 14, 15]])
    cur = jnp.array([17])
    got = ref.decode_attention_ref(q, kc, vc, pos, cur, window=6)
    # manual: valid slots are pos in (11..17]
    mask = (pos[0] > 17 - 6)
    qf = q.reshape(B, Hkv, 2, D) / np.sqrt(D)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, kc)
    s = jnp.where(mask[None, None, None], s, -1e30)
    want = jnp.einsum("bhgs,bshd->bhgd", jax.nn.softmax(s, -1), vc).reshape(B, Hq, D)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------


# groups: empty, uneven, and rows past the last group (pairs routed to
# experts held elsewhere), which must come out zero
GROUPS = {
    "uneven": [5, 0, 17, 2],
    "empty_first_last": [0, 9, 14, 0],
    "rows_past_groups": [3, 4, 0, 6],
}


def _gmm_case(sizes, dtype, D=24, F=40):
    M = 32
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (M, D), dtype)
    w = jax.random.normal(ks[1], (len(sizes), D, F), dtype)
    dy = jax.random.normal(ks[2], (M, F), dtype)
    return x, w, jnp.asarray(sizes, jnp.int32), dy


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("groups", list(GROUPS))
def test_gmm_vs_oracle(groups, dtype):
    x, w, sizes, _ = _gmm_case(GROUPS[groups], dtype)
    want = ref.gmm_ref(x, w, sizes)
    got = gmm(x, w, sizes, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tols(dtype)
    )
    assert not np.any(np.asarray(got, np.float32)[int(sizes.sum()):])


@pytest.mark.parametrize("groups", list(GROUPS))
def test_gmm_grads_vs_oracle(groups):
    """Both backward products (moe_gmm_dx, moe_gmm_dw) against the oracle's VJP."""
    x, w, sizes, dy = _gmm_case(GROUPS[groups], jnp.float32)
    _, vjp = jax.vjp(lambda a, b: gmm(a, b, sizes, interpret=True), x, w)
    _, vjp_ref = jax.vjp(lambda a, b: ref.gmm_ref(a, b, sizes), x, w)
    for got, want in zip(vjp(dy), vjp_ref(dy)):
        np.testing.assert_allclose(got, want, **tols(jnp.float32))


def test_gmm_pads_rows_to_its_tile():
    """More rows than one tile, not a multiple of it: the padding stays out."""
    from repro.kernels import moe_gmm

    M = moe_gmm.ROW_TILE + 40
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (M, 16))
    w = jax.random.normal(ks[1], (3, 16, 8))
    sizes = jnp.asarray([200, 0, M - 250], jnp.int32)
    got = gmm(x, w, sizes, interpret=True)
    np.testing.assert_allclose(got, ref.gmm_ref(x, w, sizes), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("groups", list(GROUPS))
def test_gmm_ragged_path_vs_oracle(groups):
    """The jnp production path (XLA's ragged_dot), forward and gradients."""
    x, w, sizes, dy = _gmm_case(GROUPS[groups], jnp.float32)
    out, vjp = jax.vjp(lambda a, b: ref.gmm_ragged(a, b, sizes), x, w)
    want, vjp_ref = jax.vjp(lambda a, b: ref.gmm_ref(a, b, sizes), x, w)
    np.testing.assert_allclose(out, want, **tols(jnp.float32))
    for got, exp in zip(vjp(dy), vjp_ref(dy)):
        np.testing.assert_allclose(got, exp, **tols(jnp.float32))


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,K,chunk", [(2, 64, 3, 8, 16), (1, 32, 2, 16, 32), (2, 48, 1, 8, 16)])
def test_rwkv6_vs_oracle(B, T, H, K, chunk, dtype):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, T, H, K), dtype)
    k = jax.random.normal(ks[1], (B, T, H, K), dtype)
    v = jax.random.normal(ks[2], (B, T, H, K), dtype)
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * 0.5)).astype(jnp.float32)
    u = (jax.random.normal(ks[4], (H, K)) * 0.5).astype(dtype)
    s0 = (jax.random.normal(ks[5], (B, H, K, K)) * 0.1).astype(jnp.float32)
    want_o, want_s = ref.rwkv6_scan_ref(r, k, v, w.astype(dtype), u, s0)
    got_o, got_s = rwkv6_scan(r, k, v, w.astype(dtype), u, s0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_o, np.float32), np.asarray(want_o, np.float32), **tols(dtype)
    )
    np.testing.assert_allclose(got_s, want_s, **tols(dtype))
    # chunked jnp production path too
    got2_o, got2_s = ref.rwkv6_scan_chunked(r, k, v, w.astype(dtype), u, s0, chunk=chunk)
    np.testing.assert_allclose(
        np.asarray(got2_o, np.float32), np.asarray(want_o, np.float32), **tols(dtype)
    )


# ---------------------------------------------------------------------------
# mamba scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,DI,N,chunk,bdi", [(2, 64, 12, 4, 16, 4), (1, 32, 8, 8, 32, 8)])
def test_mamba_vs_oracle(B, T, DI, N, chunk, bdi, dtype):
    ks = jax.random.split(KEY, 7)
    x = jax.random.normal(ks[0], (B, T, DI), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, DI))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (DI, N)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N), dtype)
    C = jax.random.normal(ks[4], (B, T, N), dtype)
    D = jax.random.normal(ks[5], (DI,), jnp.float32)
    h0 = (jax.random.normal(ks[6], (B, DI, N)) * 0.1).astype(jnp.float32)
    want_y, want_h = ref.mamba_scan_ref(x, dt, A, Bm, C, D, h0)
    got_y, got_h = mamba_scan(x, dt, A, Bm, C, D, h0, chunk=chunk, block_di=bdi, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_y, np.float32), np.asarray(want_y, np.float32), **tols(dtype)
    )
    np.testing.assert_allclose(got_h, want_h, **tols(dtype))
    got2_y, got2_h = ref.mamba_scan_chunked(x, dt, A, Bm, C, D, h0, chunk=chunk)
    np.testing.assert_allclose(
        np.asarray(got2_y, np.float32), np.asarray(want_y, np.float32), **tols(dtype)
    )


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 6, 32), (16, 128), (3, 7)])
def test_rmsnorm_vs_oracle(shape, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], shape, dtype)
    s = (jax.random.normal(ks[1], (shape[-1],)) * 0.1).astype(jnp.float32)
    want = ref.rmsnorm_ref(x, s)
    got = rmsnorm(x, s, block_rows=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tols(dtype)
    )


# ---------------------------------------------------------------------------
# ops dispatch + decode single-step helpers
# ---------------------------------------------------------------------------


def test_ops_decode_steps_match_scans():
    from repro.kernels import ops

    B, T, H, K = 2, 8, 2, 8
    ks = jax.random.split(KEY, 6)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, K)) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * 0.5))
    u = jax.random.normal(ks[4], (H, K)) * 0.5
    s = jnp.zeros((B, H, K, K))
    want, want_s = ref.rwkv6_scan_ref(r, k, v, w, u, s)
    out = []
    st = s
    for t in range(T):
        o, st = ops.rwkv6_step(r[:, t], k[:, t], v[:, t], w[:, t], u, st)
        out.append(o)
    np.testing.assert_allclose(jnp.stack(out, 1), want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(st, want_s, atol=2e-5, rtol=2e-5)

    DI, N = 8, 4
    x = jax.random.normal(ks[0], (B, T, DI))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, DI)))
    A = -jnp.exp(jax.random.normal(ks[2], (DI, N)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N))
    C = jax.random.normal(ks[4], (B, T, N))
    D = jax.random.normal(ks[5], (DI,))
    h = jnp.zeros((B, DI, N))
    want_y, want_h = ref.mamba_scan_ref(x, dt, A, Bm, C, D, h)
    ys = []
    for t in range(T):
        y, h = ops.mamba_step(x[:, t], dt[:, t], A, Bm[:, t], C[:, t], D, h)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(h, want_h, atol=2e-5, rtol=2e-5)
