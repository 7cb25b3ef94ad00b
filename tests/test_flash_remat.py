"""The layer remat keeps the Pallas flash forward's out and lse.

Under ``remat_policy="nothing"`` the period body is recomputed in the
backward, but the kernel's outputs are saved under their checkpoint names
(``FLASH_OUT``, ``FLASH_LSE``), so the gradient runs the kernel once per
period body instead of twice, and computes the same numbers."""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import reduced
from repro.dispatch.dispatcher import with_impl
from repro.kernels.flash_attention import FLASH_LSE, FLASH_OUT
from repro.models import lm


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (scan bodies,
    remat, shard_map) included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _count(jaxpr, primitive):
    return sum(e.primitive.name == primitive for e in _eqns(jaxpr))


def _names(jaxpr):
    return {e.params["name"] for e in _eqns(jaxpr) if e.primitive.name == "name"}


def _setup(policy, impl="pallas"):
    """A 3-period reduced qwen2 with bf16 activations: its params and the
    gradient of its loss, traced with the given attention impl."""
    cfg = dataclasses.replace(
        reduced(get_config("qwen2-0.5b"), layers=3),
        activation_dtype="bfloat16", remat_policy=policy,
    )
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    loss = with_impl(impl, lambda p: lm.loss_fn(p, cfg, tokens, tokens)[0])
    return cfg, jax.grad(loss), params


def test_pallas_forward_runs_once_per_period_under_full_remat():
    cfg, grad, params = _setup("nothing")
    assert cfg.scan_layers and cfg.n_periods == 3
    jaxpr = jax.make_jaxpr(grad)(params).jaxpr
    assert _count(jaxpr, "scan") >= 2  # a forward and a backward period scan
    assert _count(jaxpr, "pallas_call") == 1
    assert {FLASH_OUT, FLASH_LSE} <= _names(jaxpr)


def test_saving_the_pallas_residuals_changes_no_gradient_bit():
    _, grad_nothing, params = _setup("nothing")
    _, grad_everything, _ = _setup("everything")
    got = jax.jit(grad_nothing)(params)
    want = jax.jit(grad_everything)(params)
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(leaves) == len(jax.tree.leaves(want))
    for (path, a), b in zip(leaves, jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


def test_chunked_attention_saves_no_flash_residual():
    """Only the Pallas kernel names residuals: on the chunked path "nothing"
    still saves nothing and runs no kernel."""
    _, grad, params = _setup("nothing", impl="chunked")
    jaxpr = jax.make_jaxpr(grad)(params).jaxpr
    assert not _names(jaxpr) & {FLASH_OUT, FLASH_LSE}
    assert _count(jaxpr, "pallas_call") == 0


_MESH_SCRIPT = r"""
import jax, numpy as np
from jax.sharding import Mesh
import test_flash_remat as t
mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
_, grad, params = t._setup("nothing")
_, grad_everything, _ = t._setup("everything")
with mesh:
    jaxpr = jax.make_jaxpr(grad)(params).jaxpr
    got = jax.jit(grad)(params)
    want = jax.jit(grad_everything)(params)
assert t._count(jaxpr, "shard_map") >= 1, "attention did not run per shard"
n = t._count(jaxpr, "pallas_call")
assert n == 1, n
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_array_equal(a, b)
print("OK")
"""


def test_pallas_forward_runs_once_per_period_per_shard_under_a_mesh():
    """The checkpoint names survive ``_per_shard``'s shard_map on a 2x2 mesh."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [here, os.environ.get("PYTHONPATH", "")])}
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("OK")
