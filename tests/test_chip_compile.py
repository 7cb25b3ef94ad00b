"""Main-path Pallas kernels, compiled for a described TPU v5e at published widths.

Interpret-mode tests cannot see Mosaic's refusals (unaligned block shapes,
too much VMEM).  Each case here lowers a kernel for a ``v5e:2x2`` topology
that is described, not attached, and asserts the compiled program holds a
``tpu_custom_call`` — the Pallas kernel itself, not an XLA fallback.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and a test worker that loads it at
collection time would stop the others from collecting this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_gmm import gmm
from repro.kernels.rwkv6_scan import rwkv6_scan

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for a described device are written to the persistent cache but
    # cannot be read back without a chip: keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


QWEN = get_config("qwen2-0.5b")
GEMMA = get_config("gemma3-4b")


@pytest.mark.parametrize(
    "B,S,cfg,window",
    [
        (1, 128, QWEN, None),  # serve prefill
        (1, 2048, QWEN, None),
        (4, 1024, QWEN, None),  # train step
        (1, 2048, GEMMA, 1024),  # head_dim 256, sliding window
    ],
    ids=["qwen2-prefill128", "qwen2-prefill2048", "qwen2-train", "gemma3-swa"],
)
def test_flash_attention_compiles(one_chip, B, S, cfg, window):
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    text = _compile_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=window),
        [((B, S, Hq, D), BF16), ((B, S, Hkv, D), BF16), ((B, S, Hkv, D), BF16)],
        one_chip,
    )
    assert "tpu_custom_call" in text


def test_flash_attention_grad_compiles(one_chip):
    B, S, cfg = 4, 1024, QWEN
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(F32).sum()

    text = _compile_text(
        jax.grad(loss, argnums=(0, 1, 2)),
        [((B, S, Hq, D), BF16), ((B, S, Hkv, D), BF16), ((B, S, Hkv, D), BF16)],
        one_chip,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("window", [None, 1024], ids=["global", "swa"])
def test_decode_attention_compiles(one_chip, window):
    B, S, cfg = 8, 2048, QWEN
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    text = _compile_text(
        lambda q, k, v, pos, cur: decode_attention(q, k, v, pos, cur, window=window),
        [((B, Hq, D), BF16), ((B, S, Hkv, D), BF16), ((B, S, Hkv, D), BF16),
         ((B, S), jnp.int32), ((B,), jnp.int32)],
        one_chip,
    )
    assert "tpu_custom_call" in text


def test_gmm_compiles(one_chip):
    """The three grouped-matmul products at deepseek-moe's expert widths."""
    M, E, D, F = 49152, 16, 2048, 1408  # one dispatch chunk's rows, experts held

    def loss(x, w, sizes):
        return gmm(x, w, sizes).astype(F32).sum()

    text = _compile_text(
        jax.value_and_grad(loss, argnums=(0, 1)),
        [((M, D), BF16), ((E, D, F), BF16), ((E,), jnp.int32)],
        one_chip,
    )
    for product in ("moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw"):
        assert any(product in line for line in text.splitlines()
                   if "tpu_custom_call" in line), product
    assert "tpu_custom_call" in text


def test_rwkv6_scan_compiles(one_chip):
    cfg = get_config("rwkv6-7b")
    B, T, H, K = 1, 512, cfg.d_model // cfg.rwkv.head_dim, cfg.rwkv.head_dim
    text = _compile_text(
        lambda r, k, v, w, u, s: rwkv6_scan(r, k, v, w, u, s),
        [((B, T, H, K), BF16)] * 4 + [((H, K), F32), ((B, H, K, K), F32)],
        one_chip,
    )
    assert "tpu_custom_call" in text


def test_mamba_scan_compiles(one_chip):
    cfg = get_config("jamba-1.5-large")
    B, T, N = 1, 512, cfg.mamba.d_state
    DI = cfg.mamba.expand * cfg.d_model
    text = _compile_text(
        lambda x, dt, a, bm, c, d, s: mamba_scan(x, dt, a, bm, c, d, s),
        [((B, T, DI), BF16), ((B, T, DI), BF16), ((DI, N), F32),
         ((B, T, N), BF16), ((B, T, N), BF16), ((DI,), F32), ((B, DI, N), F32)],
        one_chip,
    )
    assert "tpu_custom_call" in text
