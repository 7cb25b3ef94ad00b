"""Sharding rules: divisibility fallback, shape-conditional overrides."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import sharding as shd


def fake_mesh(shape=(4, 2), axes=("data", "model")):
    """Abstract mesh over fake devices (no allocation) — spec logic only."""
    devs = np.array(jax.devices() * int(np.prod(shape)))[: int(np.prod(shape))]
    return Mesh(devs.reshape(shape), axes)


def test_basic_param_specs():
    mesh = fake_mesh()
    # (vocab, embed): vocab->model(2), embed->data(4)
    spec = shd.spec_for((256, 64), "vocab,embed", shd.PARAM_RULES, mesh)
    assert spec == P("model", "data")


def test_divisibility_fallback_drops_mapping():
    mesh = fake_mesh((4, 16))
    # 15 heads on a 16-way model axis: dropped (smollm case)
    spec = shd.spec_for((960, 15, 64), "embed,heads,head_dim", shd.PARAM_RULES, mesh)
    assert spec == P("data")  # trailing Nones stripped
    # but divisible ffn shards
    spec = shd.spec_for((960, 2560), "embed,mlp", shd.PARAM_RULES, mesh)
    assert spec == P("data", "model")


def test_axis_used_once():
    mesh = fake_mesh((4, 2))
    # both dims logical-map to 'model': only the first gets it
    rules = {"a": "model", "b": "model"}
    spec = shd.spec_for((8, 8), "a,b", rules, mesh)
    assert spec == P("model")


def test_multi_axis_assignment():
    mesh = fake_mesh((2, 4, 2), ("pod", "data", "model"))
    spec = shd.spec_for((16, 128), "batch,seq", shd.ACT_RULES, mesh)
    assert spec == P(("pod", "data"))


def test_rules_for_shape_decode_overrides():
    mesh = fake_mesh((4, 16), ("data", "model"))
    # kv_heads=8 not divisible by 16 -> split-KV over model
    r = shd.rules_for_shape("decode", global_batch=128, seq_len=32768, mesh=mesh, n_kv_heads=8)
    assert r.act["cache_seq"] == "model" and r.act["kv_heads"] is None
    # kv_heads=16 divisible -> defaults untouched
    r = shd.rules_for_shape("decode", global_batch=128, seq_len=32768, mesh=mesh, n_kv_heads=16)
    assert r.act["cache_seq"] is None
    # batch=1 (long context) -> sequence parallel over data
    r = shd.rules_for_shape("decode", global_batch=1, seq_len=524288, mesh=mesh, n_kv_heads=16)
    assert r.act["cache_seq"] == "data" and r.act["batch"] is None


def test_tree_specs_align_with_param_tree():
    from repro.configs import get_config, reduced
    from repro.models import lm

    cfg = reduced(get_config("deepseek-moe-16b"))
    mesh = fake_mesh((2, 2))
    axes = lm.param_axes(cfg)
    abs_params = lm.abstract_params(cfg)
    assert jax.tree.structure(axes) == jax.tree.structure(abs_params)
    specs = shd.tree_specs(axes, abs_params, shd.PARAM_RULES, mesh)
    n = len(jax.tree.leaves(abs_params))
    assert len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))) == n


def test_cache_axes_align_with_caches():
    from repro.configs import get_config, reduced
    from repro.models import lm

    for arch in ("gemma3-4b", "jamba-1.5-large", "rwkv6-7b"):
        cfg = reduced(get_config(arch))
        axes = lm.cache_axes(cfg)
        caches = lm.abstract_caches(cfg, 2, 32)
        assert jax.tree.structure(axes) == jax.tree.structure(caches), arch
        for a, c in zip(jax.tree.leaves(axes), jax.tree.leaves(caches)):
            assert len(a.split(",")) == len(c.shape), (arch, a, c.shape)


def test_shard_bytes_per_device():
    mesh = fake_mesh((4, 2))
    abs_t = {"w": jax.ShapeDtypeStruct((64, 64), jax.numpy.float32)}
    specs = {"w": P("data", "model")}
    assert shd.shard_bytes_per_device(abs_t, specs, mesh) == 64 * 64 * 4 // 8


_PER_SHARD_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.kernels import ops, ref
mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (4, 32, 4, 16))
k = jax.random.normal(ks[1], (4, 32, 2, 16))
v = jax.random.normal(ks[2], (4, 32, 2, 16))
want = ref.mha_ref(q, k, v, causal=True)
loss = lambda q, k, v: ops.attention(q, k, v, impl="pallas").sum()
with mesh:
    got = jax.jit(lambda q, k, v: ops.attention(q, k, v, impl="pallas"))(q, k, v)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
want_g = jax.grad(lambda q, k, v: ref.mha_ref(q, k, v, causal=True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
for a, b in zip(grads, want_g):
    np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)
# a batch the data axis does not divide is refused, never replicated
try:
    with mesh:
        jax.jit(lambda q, k, v: ops.attention(q, k, v, impl="pallas"))(
            q[:3], k[:3], v[:3])
except ValueError as e:
    assert "per shard" in str(e), e
else:
    raise AssertionError("indivisible batch was replicated")
print("OK")
"""


def test_pallas_attention_runs_per_shard_under_a_mesh():
    """XLA cannot partition a Pallas kernel: under a 2x2 mesh, attention (and
    its gradient) goes through shard_map and still matches the oracle; a
    batch that does not split over the mesh is refused."""
    import os
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", _PER_SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("OK")
