"""Checkpoint store + supervisor: restart determinism, async, elastic reshard."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, latest_step, restore, save
from repro.configs import get_config, reduced
from repro.core.events import EventLog
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.runtime.supervisor import FailureInjector, NodeFailure, Supervisor, SupervisorConfig
from repro.training.step import TrainConfig, init_train_state, make_train_step


def test_save_restore_roundtrip(tmp_path, key):
    state = {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.int32(7)}}
    save(str(tmp_path), 3, state)
    assert latest_step(str(tmp_path)) == 3
    got = restore(str(tmp_path), 3, jax.eval_shape(lambda: state))
    np.testing.assert_array_equal(got["a"], state["a"])
    assert int(got["b"]["c"]) == 7


def test_async_checkpointer_and_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, {"x": jnp.full((8,), float(s))})
    ck.wait()
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_00000003", "step_00000004"]
    got = restore(str(tmp_path), 4, jax.eval_shape(lambda: {"x": jnp.zeros(8)}))
    np.testing.assert_array_equal(got["x"], np.full(8, 4.0))


def test_atomic_write_no_partial_visible(tmp_path):
    save(str(tmp_path), 1, {"x": jnp.zeros(4)})
    # a stale tmp dir from a "killed writer" must not count as a checkpoint
    os.makedirs(tmp_path / ".tmp_step_00000002")
    assert latest_step(str(tmp_path)) == 1


def _mk(key, arch="smollm-360m", steps=20, **sup_kw):
    cfg = reduced(get_config(arch))
    tcfg = TrainConfig()
    state = init_train_state(cfg, tcfg, key)
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    data = SyntheticLM(DataConfig(cfg.vocab_size, 32, 4, seed=5))

    def batch_fn(i):
        b = data.batch(i)
        return {k: jnp.asarray(v) for k, v in b.items()}

    return cfg, tcfg, state, step, batch_fn


def test_supervisor_restart_is_deterministic(tmp_path, key):
    """Same data + restored state ⇒ the replayed run converges to the same
    params as a failure-free run (stateless-indexed pipeline property)."""
    cfg, tcfg, state0, step, batch_fn = _mk(key)
    log = EventLog()
    sup_a = Supervisor(
        SupervisorConfig(ckpt_dir=str(tmp_path / "a"), ckpt_every=5, max_steps=12),
        step, batch_fn, jax.tree.map(jnp.copy, state0), log=log,
    )
    out_a = sup_a.run()
    sup_b = Supervisor(
        SupervisorConfig(ckpt_dir=str(tmp_path / "b"), ckpt_every=5, max_steps=12),
        step, batch_fn, jax.tree.map(jnp.copy, state0), log=log,
        failures=FailureInjector((7,)),
    )
    out_b = sup_b.run()
    assert out_b["restarts"] == 1
    for a, b in zip(jax.tree.leaves(sup_a.state["params"]), jax.tree.leaves(sup_b.state["params"])):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-5, rtol=1e-5
        )


def test_supervisor_gives_up_after_max_restarts(tmp_path, key):
    cfg, tcfg, state, step, batch_fn = _mk(key)
    sup = Supervisor(
        SupervisorConfig(ckpt_dir=str(tmp_path), ckpt_every=100, max_steps=10, max_restarts=2),
        step, batch_fn, state,
        failures=FailureInjector((1, 2, 3, 4)),
    )
    # failures at steps 1..4 but restart restores to step 0 and _already-fired
    # steps don't refire; with max_restarts=2 the 3rd failure raises
    with pytest.raises(NodeFailure):
        sup.run()


def test_elastic_reshard_across_meshes(tmp_path, key):
    """A checkpoint written under one sharding restores onto a different mesh
    (the 16×16 → 8×16 elastic-resize story, at 1-device scale: 1x1 -> CPU)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    state = {"w": jnp.arange(64.0).reshape(8, 8)}
    save(str(tmp_path), 1, state)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    shd = {"w": NamedSharding(mesh, P("data", "model"))}
    got = restore(str(tmp_path), 1, jax.eval_shape(lambda: state), shardings=shd)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(state["w"]))
    assert got["w"].sharding == shd["w"]


def test_straggler_detection(tmp_path, key):
    import time

    cfg, tcfg, state, step, batch_fn = _mk(key)
    slow = {15}

    def slow_batch(i):
        if i in slow:
            time.sleep(1.0)  # injected host-level straggle
        return batch_fn(i)

    sup = Supervisor(
        SupervisorConfig(
            ckpt_dir=str(tmp_path), ckpt_every=100, max_steps=18, straggler_factor=3.0
        ),
        step, slow_batch, state,
    )
    out = sup.run()
    assert out["stragglers"] >= 1
    assert sup.log.events("straggler")


def test_supervisor_step_and_checkpoint_spans(tmp_path):
    """Each step holds batch, dispatch, device_wait, fetch in that order; the
    closing checkpoint holds the save's spans, the write's across its thread."""
    step = jax.jit(lambda s, b: ({"w": s["w"] + b}, {"loss": jnp.sum(b)}))
    log = EventLog()
    sup = Supervisor(SupervisorConfig(ckpt_dir=str(tmp_path), ckpt_every=100, max_steps=3),
                     step, lambda i: jnp.full((4,), float(i)), {"w": jnp.zeros(4)},
                     log=log)
    assert sup.ckpt.log is log
    out = sup.run()
    assert out["steps"] == 3 and "trace" not in out
    spawns = log.events(kind="spawn")

    def children(span):
        return [e.name for e in spawns if e.parent == span]

    steps = [e for e in spawns if e.name == "step"]
    assert [e.payload for e in steps] == [0, 1, 2]
    for s in steps:
        assert children(s.span) == ["batch", "dispatch", "device_wait", "fetch"]
    closing = [e for e in spawns if e.name == "checkpoint"][-1]
    assert closing.payload == 3
    assert children(closing.span) == ["ckpt.wait_prev", "ckpt.snapshot", "ckpt.write"]
    for name in ("ckpt.wait_prev", "ckpt.snapshot", "ckpt.write"):
        assert len(log.durations(name)) == 2  # the step-0 save and the closing one
    assert latest_step(str(tmp_path)) == 3
