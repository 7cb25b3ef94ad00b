"""Design spaces: the candidate config grid per (kernel op, backend tier).

A :class:`KernelSpace` names the tunable knobs of one kernel entry point
(block/tile sizes, scan chunk lengths), the hand-picked defaults they ship
with, and a fixed *sweep workload* — the shape the tuner measures on, chosen
to match the kernel-suite benchmarks.  Enumeration is constraint-aware:

* **alignment** — Pallas matmul block dims must be MXU_ALIGN (128) multiples
  for full systolic-array utilisation; chunked-path loop lengths need only
  VPU sublane (8) alignment;
* **divisibility** — a block/chunk must tile the workload dim it walks
  (the chunked scans assert ``T % chunk == 0``);
* **VMEM feasibility** — a config whose double-buffered tiles + scratch
  exceed the chip's VMEM budget (:func:`repro.core.roofline.fits_vmem`)
  is never enumerated, let alone timed.

Each space also prices a point a priori (:meth:`KernelSpace.roofline_s`):
compute + memory roofline terms plus a per-block launch/loop overhead and
the padding waste of blocks that don't divide evenly.  That surface is what
the :class:`~repro.tune.prune.RooflinePruner` cuts against and what the
``synthetic`` sweep mode returns as a deterministic pseudo-measurement.

This module is deliberately jax-free: the fleet daemon, CI smoke jobs, and
multiprocessing sweep workers in ``synthetic`` mode all enumerate and price
spaces without paying for a jax import.  Real measurement lives in
:mod:`repro.tune.explore`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import math
from typing import Any, Callable, Mapping, Optional

from repro.core.roofline import fits_vmem, vmem_footprint_bytes
from repro.dispatch.profiles import encode_config
from repro.hw.specs import MXU_ALIGN, VPU_SUBLANES, ChipSpec, default_chip

# Static per-tier cost factors (mirrors repro.dispatch.registry, which is
# jax-importing): sustained fraction of peak FLOP/s, sustained fraction of
# HBM bandwidth, per-launch/per-loop-iteration overhead in seconds.
_TIER = {
    "pallas": (0.85, 0.6, 2e-6),
    "chunked": (0.65, 0.5, 4e-6),
    "ref": (0.6, 0.4, 2e-7),
}

F32 = 4  # sweep workloads are float32


def _sig(*arrays: tuple[str, tuple[int, ...]]) -> str:
    """Analytic ``repro.dispatch.profiles.signature`` of a workload, computed
    without materialising arrays (or importing jax)."""
    return ";".join(
        f"{dtype}[{','.join(map(str, shape))}]" for dtype, shape in arrays
    )


def _pad(n: int, block: int) -> int:
    """Elements after padding ``n`` up to a multiple of ``block``."""
    return ((n + block - 1) // block) * block


@dataclasses.dataclass(frozen=True)
class ConfigPoint:
    """One candidate configuration of one (op, backend)."""

    op: str
    backend: str
    params: Mapping[str, Any]

    @property
    def config(self) -> str:
        return encode_config(self.params)


@dataclasses.dataclass(frozen=True)
class KernelSpace:
    """The tunable design space of one kernel entry point on one backend.

    ``grid`` maps each knob to its candidate values; ``defaults`` is the
    hand-picked shipping config (always enumerated, never pruned — the tuner
    must beat it on equal terms, not by forgetting it).  ``divides`` maps a
    knob to the workload dim it must tile exactly.  ``cost`` returns
    ``(flops, hbm_bytes, launches)`` for a param dict; ``tiles`` returns
    ``(tiles, scratch)`` rows for the VMEM footprint model.
    """

    op: str
    backend: str
    impl: str
    grid: Mapping[str, tuple[int, ...]]
    defaults: Mapping[str, int]
    align: Mapping[str, int]
    divides: Mapping[str, str]
    workload: Mapping[str, int]
    sig: str
    cost: Callable[[Mapping[str, int], Mapping[str, int]], tuple[float, float, float]]
    tiles: Callable[[Mapping[str, int], Mapping[str, int]], tuple[list, list]]

    @property
    def key(self) -> str:
        return f"{self.op}/{self.backend}"

    @property
    def default_config(self) -> str:
        return encode_config(self.defaults)

    def feasible(self, params: Mapping[str, int],
                 chip: Optional[ChipSpec] = None) -> bool:
        chip = chip or default_chip()
        for name, value in params.items():
            if value % self.align.get(name, 1) != 0:
                return False
            dim = self.divides.get(name)
            if dim is not None and self.workload[dim] % min(value, self.workload[dim]) != 0:
                return False
            if value <= 0:
                return False
        tiles, scratch = self.tiles(params, self.workload)
        return fits_vmem(vmem_footprint_bytes(tiles, scratch), chip)

    def points(self, chip: Optional[ChipSpec] = None) -> list[ConfigPoint]:
        """Feasible candidate points, defaults included, deterministic order."""
        chip = chip or default_chip()
        names = sorted(self.grid)
        seen: list[ConfigPoint] = []
        for values in itertools.product(*(self.grid[n] for n in names)):
            params = dict(zip(names, values))
            if self.feasible(params, chip):
                seen.append(ConfigPoint(self.op, self.backend, params))
        if not any(p.params == dict(self.defaults) for p in seen):
            # hand-picked defaults are known-good: enumerate them even if the
            # grid was narrowed past them
            seen.append(ConfigPoint(self.op, self.backend, dict(self.defaults)))
        return seen

    def roofline_s(self, params: Mapping[str, int],
                   chip: Optional[ChipSpec] = None) -> float:
        """A-priori cost of one point: roofline terms + launch overhead."""
        chip = chip or default_chip()
        flop_eff, hbm_eff, launch_s = _TIER[self.backend]
        flops, hbm_bytes, launches = self.cost(params, self.workload)
        return (
            flops / (flop_eff * chip.peak_flops_f32)
            + hbm_bytes / (hbm_eff * chip.hbm_bw)
            + launches * launch_s
        )

    def synthetic_s(self, params: Mapping[str, int],
                    chip: Optional[ChipSpec] = None) -> float:
        """Deterministic pseudo-measurement for ``--tune-mode synthetic``.

        The roofline prediction perturbed by a stable per-config hash (±5%),
        so sweeps are reproducible across processes and worker counts while
        still exercising the measured-beats-predicted argmin path.
        """
        digest = hashlib.sha1(
            f"{self.op}|{self.backend}|{encode_config(params)}".encode()
        ).digest()
        jitter = 1.0 + 0.05 * (digest[0] / 255.0)
        return self.roofline_s(params, chip) * jitter


# ---------------------------------------------------------------------------
# Per-kernel space definitions
# ---------------------------------------------------------------------------


def _flash_cost(backend: str):
    def cost(p: Mapping[str, int], w: Mapping[str, int]):
        B, S, H, D = w["B"], w["S"], w["H"], w["D"]
        bq = p.get("block_q", S)
        bk = p["block_k"]
        sq, sk = _pad(S, bq), _pad(S, bk)
        flops = 4.0 * B * H * sq * sk * D  # qk^T + pv, causal ~x0.5 folded out
        if backend == "chunked":
            flops /= 2.0  # lax.scan skips fully-masked KV blocks' second half
        hbm = F32 * B * H * S * D * 4  # q, k, v read + o write
        launches = B * H * math.ceil(S / bq) * math.ceil(S / bk)
        return flops, hbm, launches

    return cost


def _flash_tiles(p: Mapping[str, int], w: Mapping[str, int]):
    D = w["D"]
    bq = p.get("block_q", 128)
    bk = p["block_k"]
    tiles = [((bq, D), F32), ((bk, D), F32), ((bk, D), F32), ((bq, D), F32)]
    scratch = [((bq, bk), F32), ((bq,), F32), ((bq,), F32)]  # scores, m, l
    return tiles, scratch


def _decode_cost(p: Mapping[str, int], w: Mapping[str, int]):
    B, S, H, D = w["B"], w["S"], w["H"], w["D"]
    bs = p["block_s"]
    s_eff = _pad(S, bs)
    flops = 4.0 * B * H * s_eff * D
    hbm = F32 * B * (2 * S * H * D + 2 * H * D)  # caches + q/o
    launches = B * math.ceil(S / bs)
    return flops, hbm, launches


def _decode_tiles(p: Mapping[str, int], w: Mapping[str, int]):
    H, D = w["H"], w["D"]
    bs = p["block_s"]
    tiles = [((H, D), F32), ((bs, H, D), F32), ((bs, H, D), F32), ((H, D), F32)]
    scratch = [((H, bs), F32)]
    return tiles, scratch


def _scan_cost(state_cols: str):
    """Chunked linear-scan cost: within-chunk pairwise work is O(T·L), the
    chunk loop costs one launch per T/L iterations — the classic small-chunk
    (loop-bound) vs large-chunk (compute/memory-bound) trade."""

    def cost(p: Mapping[str, int], w: Mapping[str, int]):
        B, T = w["B"], w["T"]
        width = w[state_cols]
        rows = w.get("K", w.get("DI"))
        L = min(p["chunk"], T)
        flops = 4.0 * B * T * L * rows + 2.0 * B * T * rows * width
        hbm = F32 * B * T * rows * 6
        launches = math.ceil(T / L)
        return flops, hbm, launches

    return cost


def _rwkv_tiles(p: Mapping[str, int], w: Mapping[str, int]):
    H, K, V = w["H"], w["K"], w["V"]
    L = min(p["chunk"], w["T"])
    tiles = [((L, H, K), F32)] * 4 + [((L, H, V), F32)]
    scratch = [((L, L, K), F32), ((H, K, V), F32)]  # pairwise decay + state
    return tiles, scratch


def _mamba_tiles(p: Mapping[str, int], w: Mapping[str, int]):
    DI, N = w["DI"], w["N"]
    L = min(p["chunk"], w["T"])
    tiles = [((L, DI), F32)] * 2 + [((L, N), F32)] * 2
    scratch = [((L, DI, N), F32)]  # per-chunk expanded state
    return tiles, scratch


def default_spaces() -> dict[str, KernelSpace]:
    """The shipped design spaces, keyed ``"op/backend"``.

    Workload shapes mirror ``benchmarks/kernel_bench.py`` so tuned winners
    transfer directly to the bench suite and the serving/training drivers.
    """
    spaces = [
        KernelSpace(
            op="flash_attention", backend="pallas", impl="pallas",
            grid={"block_q": (128, 256, 512), "block_k": (128, 256, 512)},
            defaults={"block_q": 128, "block_k": 128},
            align={"block_q": MXU_ALIGN, "block_k": MXU_ALIGN},
            divides={"block_q": "S", "block_k": "S"},
            workload={"B": 1, "S": 512, "H": 4, "D": 64},
            sig=_sig(("float32", (1, 512, 4, 64)), ("float32", (1, 512, 4, 64)),
                     ("float32", (1, 512, 4, 64))),
            cost=_flash_cost("pallas"), tiles=_flash_tiles,
        ),
        KernelSpace(
            op="flash_attention", backend="chunked", impl="chunked",
            grid={"block_k": (32, 64, 128, 256, 512)},
            defaults={"block_k": 512},
            align={"block_k": VPU_SUBLANES},
            divides={"block_k": "S"},
            workload={"B": 1, "S": 512, "H": 4, "D": 64},
            sig=_sig(("float32", (1, 512, 4, 64)), ("float32", (1, 512, 4, 64)),
                     ("float32", (1, 512, 4, 64))),
            cost=_flash_cost("chunked"), tiles=_flash_tiles,
        ),
        KernelSpace(
            op="decode_attention", backend="pallas", impl="pallas",
            grid={"block_s": (128, 256, 512, 1024)},
            defaults={"block_s": 512},
            align={"block_s": MXU_ALIGN},
            divides={"block_s": "S"},
            workload={"B": 4, "S": 1024, "H": 4, "D": 64},
            sig=_sig(("float32", (4, 4, 64)), ("float32", (4, 1024, 4, 64)),
                     ("float32", (4, 1024, 4, 64)), ("int32", (4, 1024)),
                     ("int32", (4,))),
            cost=_decode_cost, tiles=_decode_tiles,
        ),
        KernelSpace(
            op="rwkv6_scan", backend="chunked", impl="chunked",
            grid={"chunk": (8, 16, 32, 64, 128)},
            defaults={"chunk": 32},
            align={"chunk": VPU_SUBLANES},
            divides={"chunk": "T"},
            workload={"B": 1, "T": 256, "H": 4, "K": 64, "V": 64},
            sig=_sig(("float32", (1, 256, 4, 64)), ("float32", (1, 256, 4, 64)),
                     ("float32", (1, 256, 4, 64)), ("float32", (1, 256, 4, 64)),
                     ("float32", (4, 64)), ("float32", (1, 4, 64, 64))),
            cost=_scan_cost("V"), tiles=_rwkv_tiles,
        ),
        KernelSpace(
            op="mamba_scan", backend="chunked", impl="chunked",
            grid={"chunk": (16, 32, 64, 128, 256)},
            defaults={"chunk": 128},
            align={"chunk": VPU_SUBLANES},
            divides={"chunk": "T"},
            workload={"B": 1, "T": 256, "DI": 256, "N": 16},
            sig=_sig(("float32", (1, 256, 256)), ("float32", (1, 256, 256)),
                     ("float32", (256, 16)), ("float32", (1, 256, 16)),
                     ("float32", (1, 256, 16)), ("float32", (256,)),
                     ("float32", (1, 256, 16))),
            cost=_scan_cost("N"), tiles=_mamba_tiles,
        ),
    ]
    return {s.key: s for s in spaces}
