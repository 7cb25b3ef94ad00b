"""Backend registry: the dispatchable implementation variants.

Every op in :mod:`repro.kernels.ops` already exists in several
implementations — the Pallas kernel, the chunked/production jnp path, and the
naive full-materialisation reference.  This module names those variants as
*dispatch targets* and attaches a static cost model to each, derived from the
:class:`~repro.hw.specs.ChipSpec` constants (the Adaptyst "backend module"
idea: one model per system component, priced a priori, corrected by profiles).

The static model per target is three numbers applied on top of the chip's
roofline terms:

    ``flop_efficiency``     fraction of peak FLOP/s the variant sustains
                            (per SDFG component class — MXU work runs closer
                            to peak in a fused Pallas kernel than in the
                            reference einsum chain)
    ``byte_amplification``  multiplier on HBM traffic (the reference paths
                            materialise O(S²) score matrices the fused paths
                            never write)
    ``launch_overhead_s``   fixed per-call cost (grid setup, chunk-loop
                            bookkeeping) — dominates for tiny shapes, which
                            is exactly why the *reference* path wins there
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Mapping, Optional

import jax

from repro.hw.specs import ChipSpec, host_chip

# SDFG component classes (mirrors repro.core.sdfg constants; string-typed to
# avoid importing jax-heavy modules at registry-definition time).
MXU, VPU, HBM, ICI, HOST = "MXU", "VPU", "HBM", "ICI", "HOST"


@dataclasses.dataclass(frozen=True)
class BackendTarget:
    """One dispatchable implementation variant with its static cost factors."""

    name: str  # registry key, e.g. "pallas"
    impl: str  # repro.kernels.ops impl string this target maps to
    description: str = ""
    flop_efficiency: Mapping[str, float] = dataclasses.field(
        default_factory=lambda: {MXU: 0.7, VPU: 0.5}
    )
    byte_amplification: float = 1.0
    launch_overhead_s: float = 1e-6
    requires_tpu: bool = False  # Pallas→Mosaic only lowers on real TPU

    def efficiency(self, component: str) -> float:
        """Sustained fraction of peak for work bound by ``component``."""
        return float(self.flop_efficiency.get(component, self.flop_efficiency.get(VPU, 0.5)))

    def available(self) -> bool:
        return not self.requires_tpu or jax.default_backend() == "tpu"


class BackendRegistry:
    """Named set of dispatch targets bound to one chip model.

    The chip defaults to the one this process runs on: its name stamps every
    measured sample, and its peaks price the a-priori estimates.
    """

    def __init__(self, chip: Optional[ChipSpec] = None) -> None:
        self.chip = chip or host_chip()
        self._targets: dict[str, BackendTarget] = {}

    def register(self, target: BackendTarget) -> BackendTarget:
        if target.name in self._targets:
            raise ValueError(f"backend {target.name!r} already registered")
        self._targets[target.name] = target
        return target

    def get(self, name: str) -> BackendTarget:
        try:
            return self._targets[name]
        except KeyError:
            raise KeyError(
                f"unknown backend {name!r}; registered: {sorted(self._targets)}"
            ) from None

    def names(self) -> list[str]:
        return list(self._targets)

    def targets(self, names: Optional[Iterable[str]] = None) -> list[BackendTarget]:
        if names is None:
            return list(self._targets.values())
        return [self.get(n) for n in names]

    def available(self) -> list[BackendTarget]:
        """Targets executable in this process (Pallas excluded off-TPU)."""
        return [t for t in self._targets.values() if t.available()]

    def __contains__(self, name: str) -> bool:
        return name in self._targets

    def __len__(self) -> int:
        return len(self._targets)


def default_registry(chip: Optional[ChipSpec] = None) -> BackendRegistry:
    """The three implementation tiers that exist for every hot-spot op.

    Factor rationale (priced against the TPU-v5e ChipSpec):

    * ``pallas`` — fused VMEM-resident kernels: near-peak MXU, no score
      materialisation, but a per-call grid-launch cost.
    * ``chunked`` — the production jnp fallback: same asymptotic bytes as the
      kernels (chunked softmax never materialises S²) with a small constant
      re-read amplification and per-chunk loop overhead.
    * ``ref`` — naive full-materialisation oracle: negligible launch cost
      (one einsum chain), heavy byte amplification — the right choice only
      for tiny shapes, which is precisely the dispatcher's opening move.
    """
    reg = BackendRegistry(chip)
    reg.register(
        BackendTarget(
            name="pallas",
            impl="pallas",
            description="fused Pallas kernels (Mosaic; TPU-only lowering)",
            flop_efficiency={MXU: 0.85, VPU: 0.6, HBM: 0.6, HOST: 0.1, ICI: 0.6},
            byte_amplification=1.0,
            launch_overhead_s=2e-6,
            requires_tpu=True,
        )
    )
    reg.register(
        BackendTarget(
            name="chunked",
            impl="chunked",
            description="chunked pure-jnp production path (lowers everywhere)",
            flop_efficiency={MXU: 0.65, VPU: 0.45, HBM: 0.5, HOST: 0.1, ICI: 0.5},
            byte_amplification=1.15,
            launch_overhead_s=4e-6,
        )
    )
    reg.register(
        BackendTarget(
            name="ref",
            impl="ref",
            description="naive full-materialisation oracle (tiny shapes only)",
            flop_efficiency={MXU: 0.6, VPU: 0.4, HBM: 0.4, HOST: 0.1, ICI: 0.4},
            byte_amplification=6.0,
            launch_overhead_s=2e-7,
        )
    )
    return reg


def host_registry(chip: Optional[ChipSpec] = None) -> BackendRegistry:
    """Registry restricted to targets that execute on this process's devices.

    On the CPU container that is {chunked, ref}; on TPU all three.  Used by
    the runtime integrations (serving engine / train supervisor) so the
    dispatcher never routes a request to a backend that cannot run.
    """
    full = default_registry(chip)
    reg = BackendRegistry(full.chip)
    for t in full.available():
        reg.register(t)
    return reg
