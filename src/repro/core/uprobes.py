"""Dynamic probes — the Uprobes analogue.

A uprobe attaches to an *unmodified* binary at a symbol/offset: the kernel
patches a trap into the text page, and the handler runs on every hit.  A TPU
program cannot be patched after compilation, so the TPU-idiomatic equivalent
attaches at the two places that still exist at runtime:

1. **Python symbol interception** (``attach`` / ``detach_all``): wrap a
   function *in its defining module* with an instrumented version — no source
   change, exactly like attaching to an ELF symbol.  Entry/exit host events
   are recorded, and (optionally) a host callback is inserted into the traced
   computation at the function's dataflow position (the "trap").
2. **jaxpr equation interception** (``inject_probes``): re-interpret the
   program's jaxpr, firing a probe at every equation matched by name-stack or
   primitive — the jaxpr plays the role of the symbol table.

Both mechanisms route events through host callbacks, which is why uprobe-mode
instrumentation shifts cost into *system/host* time in the overhead study —
mirroring the paper's Fig. 2 finding that "Uprobes incurs more system time".
"""
from __future__ import annotations

import dataclasses
import time
from functools import wraps
from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax.extend import core as jcore

from repro.core.events import GLOBAL_LOG, EventLog

# --------------------------------------------------------------------------
# 1. Python-symbol interception
# --------------------------------------------------------------------------


@dataclasses.dataclass
class _Attachment:
    module: Any
    name: str
    original: Callable


class ProbeRegistry:
    """Attach/detach dynamic probes on module-level functions."""

    def __init__(self, log: EventLog | None = None) -> None:
        self.log = GLOBAL_LOG if log is None else log  # (EventLog is falsy when empty)
        self._attached: list[_Attachment] = []

    def attach(self, module: Any, name: str, *, tap_output: bool = True) -> None:
        """Instrument ``module.name`` in place.  No source change required."""
        original = getattr(module, name)
        if getattr(original, "__repro_probe__", False):
            return  # already attached
        log = self.log
        target = f"{getattr(module, '__name__', module)}.{name}"

        @wraps(original)
        def probed(*args: Any, **kwargs: Any):
            log.record("probe", target + ":enter", time.monotonic())
            out = original(*args, **kwargs)
            if tap_output:
                leaf = next(
                    (l for l in jax.tree.leaves(out) if hasattr(l, "dtype")), None
                )
                if leaf is not None and jnp.issubdtype(leaf.dtype, jnp.floating):
                    # register-sized probe argument (uprobes tap a register, not
                    # a reduction over the tensor): first element only.
                    summary = leaf.ravel()[0].astype(jnp.float32)

                    def _sink(v, _t=target, _log=log):
                        _log.record("probe", _t + ":ret", v)

                    jax.debug.callback(_sink, summary)
            log.record("probe", target + ":exit", time.monotonic())
            return out

        probed.__repro_probe__ = True  # type: ignore[attr-defined]
        setattr(module, name, probed)
        self._attached.append(_Attachment(module, name, original))

    def detach_all(self) -> None:
        while self._attached:
            a = self._attached.pop()
            setattr(a.module, a.name, a.original)

    def __enter__(self) -> "ProbeRegistry":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.detach_all()


# --------------------------------------------------------------------------
# 2. jaxpr equation interception
# --------------------------------------------------------------------------


def by_primitive(*names: str) -> Callable:
    names_set = set(names)

    def matcher(eqn) -> bool:
        return eqn.primitive.name in names_set

    return matcher


def by_scope(substring: str) -> Callable:
    """Match equations whose named_scope stack contains ``substring``."""

    def matcher(eqn) -> bool:
        try:
            return substring in str(eqn.source_info.name_stack)
        except AttributeError:
            return False

    return matcher


def _is_dropvar(v) -> bool:
    return type(v).__name__ == "DropVar"


def eval_jaxpr_with_probes(jaxpr, consts, *args, matcher: Callable, probe: Callable):
    """Interpret ``jaxpr``, firing ``probe(eqn, outvals)`` at matched equations.

    ``probe`` runs at trace time and may insert host callbacks / tape points.
    Higher-order equations (scan, pjit, cond) are bound opaquely — probes
    attach at the granularity the symbol table (name stack) exposes, like
    uprobes on inlined functions.
    """
    env: dict = {}

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    def write(v, val):
        if not _is_dropvar(v):
            env[v] = val

    for v, c in zip(jaxpr.constvars, consts):
        write(v, c)
    for v, a in zip(jaxpr.invars, args):
        write(v, a)
    for eqn in jaxpr.eqns:
        invals = [read(v) for v in eqn.invars]
        outvals = eqn.primitive.bind(*invals, **eqn.params)
        if not eqn.primitive.multiple_results:
            outvals = [outvals]
        if matcher(eqn):
            outvals = probe(eqn, outvals)
        for v, val in zip(eqn.outvars, outvals):
            write(v, val)
    return [read(v) for v in jaxpr.outvars]


def inject_probes(
    fn: Callable,
    matcher: Callable,
    *,
    mode: str = "callback",
    log: EventLog | None = None,
) -> Callable:
    """Return ``fn`` with probes attached at matched jaxpr equations.

    ``mode="callback"`` emits host events (uprobe trap semantics);
    ``mode="tap"`` returns collected {probe_name: scalar} as a second output
    (useful for deterministic tests).
    """
    log = GLOBAL_LOG if log is None else log

    def probed(*args: Any, **kwargs: Any):
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
        taps: dict[str, Any] = {}
        counter = [0]

        def probe(eqn, outvals):
            name = f"{eqn.primitive.name}#{counter[0]}"
            counter[0] += 1
            leaf = next(
                (
                    o
                    for o in outvals
                    if hasattr(o, "dtype") and jnp.issubdtype(o.dtype, jnp.floating)
                ),
                None,
            )
            if leaf is None:
                return outvals
            # register-sized argument, not a tensor reduction (uprobe semantics)
            summary = leaf.ravel()[0].astype(jnp.float32)
            if mode == "callback":

                def _sink(v, _name=name, _log=log):
                    _log.record("probe", _name, v)

                jax.debug.callback(_sink, summary)
            else:
                taps[name] = summary
            return outvals

        flat_args = jax.tree.leaves((args, kwargs))
        out = eval_jaxpr_with_probes(
            closed.jaxpr, closed.consts, *flat_args, matcher=matcher, probe=probe
        )
        out = jax.tree.unflatten(jax.tree.structure(jax.eval_shape(fn, *args, **kwargs)), out)
        if mode == "tap":
            return out, taps
        return out

    return probed
