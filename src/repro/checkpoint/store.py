"""Checkpoint store: manifest + npz payloads, async writer, elastic restore.

Layout (one directory per step):

    <dir>/step_000042/
        manifest.json     tree structure, per-leaf path/shape/dtype
        arrays.npz        one entry per flattened leaf

Properties needed at cluster scale, preserved here:
* **Async save** — the train loop is blocked only for the device→host
  snapshot; serialisation/fsync happens on a writer thread
  (:class:`AsyncCheckpointer`), overlapping the next steps.
* **Elastic restore** — payloads are stored *unsharded* (host-gathered);
  restore ``device_put``s against whatever sharding the *new* mesh dictates,
  so a 16×16 checkpoint restores onto 8×16 unchanged (tested in
  tests/test_runtime.py).  A production deployment would swap the payload
  format for per-shard files (e.g. OCDBT) without touching this interface.
* **Atomicity** — writes land in ``<dir>/.tmp_stepN`` and are renamed only
  after fsync, so a killed writer never leaves a half checkpoint visible.

Spans: an :class:`AsyncCheckpointer` records each save in its ``EventLog``
(``GLOBAL_LOG`` unless given one) as ``ckpt.wait_prev`` (joining the
previous write) then ``ckpt.snapshot`` (the device→host copy), both in the
caller's thread, and ``ckpt.write`` (serialise, fsync, rename, gc) on the
writer thread, parented to the span that was open when ``save`` was called.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np

from repro.core.events import GLOBAL_LOG, EventLog, current_span

PyTree = Any


def _flatten(tree: PyTree) -> tuple[list[tuple[str, Any]], Any]:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p)))) for p in path)
        out.append((key, leaf))
    return out, treedef


def save(directory: str, step: int, state: PyTree) -> str:
    """Synchronous checkpoint write.  Returns the final path."""
    host_state = jax.device_get(state)
    return _write(directory, step, host_state)


def _write(directory: str, step: int, host_state: PyTree) -> str:
    flat, _ = _flatten(host_state)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = os.path.join(directory, f".tmp_step_{step:08d}")
    os.makedirs(tmp, exist_ok=True)
    manifest = {
        "step": step,
        "leaves": [
            {"key": k, "shape": list(np.shape(v)), "dtype": str(np.asarray(v).dtype)}
            for k, v in flat
        ],
    }
    np.savez(os.path.join(tmp, "arrays.npz"), **{k: np.asarray(v) for k, v in flat})
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    return final


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(d.split("_")[1])
        for d in os.listdir(directory)
        if d.startswith("step_") and os.path.isdir(os.path.join(directory, d))
    ]
    return max(steps) if steps else None


def restore(
    directory: str,
    step: int,
    like: PyTree,
    shardings: Optional[PyTree] = None,
) -> PyTree:
    """Restore into the structure of ``like``; re-shard onto ``shardings``.

    ``like`` may be abstract (ShapeDtypeStructs) — only its treedef is used.
    Elastic: the stored payload is unsharded, so any target mesh works.
    """
    path = os.path.join(directory, f"step_{step:08d}")
    flat_like, treedef = _flatten(like)
    with np.load(os.path.join(path, "arrays.npz")) as z:
        leaves = [z[k] for k, _ in flat_like]
    if shardings is not None:
        flat_shd = jax.tree.leaves(
            shardings, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)
        )
        leaves = [jax.device_put(l, s) for l, s in zip(leaves, flat_shd)]
    else:
        leaves = [jax.numpy.asarray(l) for l in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves)


class AsyncCheckpointer:
    """Overlaps checkpoint serialisation with training.

    ``save()`` snapshots to host (blocking, bounded by PCIe) and hands the
    write to a daemon thread; ``wait()`` joins the in-flight write.  One
    in-flight checkpoint at a time (back-pressure, matching real stores).
    """

    def __init__(self, directory: str, keep: int = 3,
                 log: Optional[EventLog] = None) -> None:
        self.directory = directory
        self.keep = keep
        self.log = GLOBAL_LOG if log is None else log
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def save(self, step: int, state: PyTree) -> None:
        with self.log.lifecycle("ckpt.wait_prev", step):
            self.wait()
        with self.log.lifecycle("ckpt.snapshot", step):
            host_state = jax.device_get(state)
        parent = current_span()  # contextvars do not cross into the thread

        def _run():
            try:
                with self.log.lifecycle("ckpt.write", step, parent=parent):
                    _write(self.directory, step, host_state)
                    self._gc()
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=_run, daemon=True, name="ckpt-writer")
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _gc(self) -> None:
        steps = sorted(
            d for d in os.listdir(self.directory) if d.startswith("step_")
        )
        for d in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, d), ignore_errors=True)
