"""Hardware backend models.

This is the Adaptyst-style "backend module" registry: every SDFG node is
eventually assigned to one of these component models (MXU / VPU / HBM / ICI /
HOST), and the roofline engine prices a node's work against the component it
was assigned to.  The numbers below are published peaks of the target
hardware, never measured.  A process learns which chip it runs on from
:func:`host_chip`, keyed by the ``device_kind`` JAX reports; off-TPU the v5e
peaks stay the modelling target but under the platform's own name, so no CPU
sample is ever stamped as a TPU one.  A process that owns no device stamps
through :func:`stamp_chip`, which never starts a JAX backend.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip hardware constants for one accelerator generation."""

    name: str
    # Compute units.
    peak_flops_bf16: float  # FLOP/s, MXU systolic arrays
    peak_flops_f32: float
    # Memory hierarchy (HBM -> VMEM -> VREG).
    hbm_bytes: int
    hbm_bw: float  # bytes/s
    vmem_bytes: int
    # Interconnect.
    ici_link_bw: float  # bytes/s per link, one direction
    ici_links: int  # links per chip (2D torus on v5e: 4)
    # Host link (PCIe) — the "system" side of the sys/user split.
    host_bw: float

    @property
    def ici_bisection_bw(self) -> float:
        return self.ici_link_bw * self.ici_links


# Peaks: Google Cloud documentation, "TPU v5e" (per chip).
TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=98.5e12,
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    vmem_bytes=128 * 1024**2,
    ici_link_bw=50e9,
    ici_links=4,
    host_bw=32e9,
)

# Registry keyed by name so configs can select hardware symbolically.
CHIPS: dict[str, ChipSpec] = {"tpu_v5e": TPU_V5E}

# MXU tile alignment: matmul dims should be multiples of this for full
# systolic-array utilisation; Pallas BlockSpecs in kernels/ honour it.
MXU_ALIGN = 128
# VPU lane/sublane shape for fp32 (8, 128); bf16 packs (16, 128).
VPU_LANES = 128
VPU_SUBLANES = 8


# ``jax.Device.device_kind`` -> the chip it names.  A TPU missing here is an
# error, never a default.
DEVICE_KINDS: dict[str, ChipSpec] = {"TPU v5 lite": TPU_V5E}


def default_chip() -> ChipSpec:
    """The modelling target that a-priori cost estimates are priced against."""
    return TPU_V5E


def chip_for_device(platform: str, device_kind: str) -> ChipSpec:
    """Identity and peaks of a device, as JAX reports its platform and kind."""
    if platform != "tpu":
        return dataclasses.replace(TPU_V5E, name=platform)
    try:
        return DEVICE_KINDS[device_kind]
    except KeyError:
        raise ValueError(
            f"unknown TPU device_kind {device_kind!r}; known: {sorted(DEVICE_KINDS)}"
        ) from None


# Google's PCI vendor id, and the device ids of its TPU chips: a copy of the
# table in jax._src.hardware_utils (tests/test_hw.py holds the two equal), kept
# here so that a parent can ask without importing JAX.  Other Google devices
# are NICs.
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = frozenset(
    {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"}
)


def tpu_host() -> bool:
    """True where a new JAX process would take a TPU chip.

    Reads sysfs only and never initialises a JAX backend, so a parent can ask
    before it starts children: a chip belongs to one process, and a second
    process that needs it fails or hangs.
    """
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(vendor) as f:
                if f.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            with open(os.path.join(os.path.dirname(vendor), "device")) as f:
                if f.read().strip() in _TPU_PCI_DEVICES:
                    return True
        except OSError:
            continue
    return False


def host_chip() -> ChipSpec:
    """The chip this process runs on (``jax.devices()[0]``)."""
    import jax

    dev = jax.devices()[0]
    return chip_for_device(dev.platform, dev.device_kind)


def _backend_started() -> bool:
    """True once this process has started a JAX backend (never starts one)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def stamp_chip() -> ChipSpec:
    """The chip to stamp on what this process records, taking none.

    A process whose JAX backend is up stamps the chip it runs on.  One that
    has started no backend (the router front door, a synthetic replica, the
    fleet and tune command lines) owns no device and stamps ``host`` with the
    v5e peaks: asking JAX would start a backend, which on a TPU host takes
    the chip from the process that needs it.
    """
    if _backend_started():
        return host_chip()
    return dataclasses.replace(TPU_V5E, name="host")
