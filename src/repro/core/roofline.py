"""Three-term roofline engine (the `linuxperf` cache-aware-roofline analogue).

For a compiled (SPMD-partitioned) step this derives, per chip:

    compute term    = HLO_FLOPs      / peak_FLOP/s          [seconds]
    memory term     = HLO_bytes      / HBM_bandwidth        [seconds]
    collective term = collective_bytes / ICI_link_bandwidth [seconds]

Sources: ``compiled.cost_analysis()`` provides FLOPs and bytes accessed of the
*per-device* program (GSPMD compiles one partitioned module).  Collective
bytes are NOT in cost_analysis: we parse the optimized HLO text and price
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute with ring-algorithm byte counts (group size parsed from
replica_groups).

The dominant term is the bottleneck; MODEL_FLOPS/HLO_FLOPs measures how much
compiled compute is "useful" (catches remat and dispatch-einsum waste).
"""
from __future__ import annotations

import math
import re
from typing import Any, Optional

import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.hw.specs import ChipSpec, default_chip

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [num_groups, group_size]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str, n_devices: int) -> dict[str, float]:
    """Per-device bytes moved over the interconnect, ring-algorithm pricing.

    For a full tensor of S bytes over an n-member group:
      all-gather        S·(n−1)/n     (result = S)
      reduce-scatter    S·(n−1)/n     (result = S/n ⇒ result·(n−1))
      all-reduce        2·S·(n−1)/n   (RS + AG)
      all-to-all        S·(n−1)/n
      collective-permute S            (result = S)
    """
    per_op: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        tuple_types, single_type, op = m.groups()
        result_bytes = _shape_bytes(tuple_types if tuple_types else single_type)
        n = max(2, _group_size(line, n_devices))
        if op == "all-gather":
            b = result_bytes * (n - 1) / n
        elif op == "reduce-scatter":
            b = result_bytes * (n - 1)
        elif op == "all-reduce":
            b = 2 * result_bytes * (n - 1) / n
        elif op == "all-to-all":
            b = result_bytes * (n - 1) / n
        else:  # collective-permute
            b = result_bytes
        per_op[op] = per_op.get(op, 0.0) + b
    per_op["total"] = sum(per_op.values())
    return per_op


def analyze_compiled(lowered, compiled, mesh, chip: Optional[ChipSpec] = None) -> dict:
    """Roofline record for one compiled step (per-chip terms, seconds).

    Costs come from repro.core.hloanalysis — a trip-count-aware walk of the
    optimized per-device HLO (XLA's own cost_analysis prices while bodies
    once, undercounting layer-scanned models by the trip count).
    """
    from repro.core.hloanalysis import analyze_hlo_text

    chip = chip or default_chip()
    n_dev = mesh.devices.size
    hlo = compiled.as_text()
    costs = analyze_hlo_text(hlo, n_dev)
    flops = costs["flops"]
    bytes_accessed = costs["mem_bytes"]
    xla_cost = compiled.cost_analysis() or {}
    if isinstance(xla_cost, (list, tuple)):
        xla_cost = xla_cost[0] if xla_cost else {}

    t_compute = flops / chip.peak_flops_bf16
    t_memory = bytes_accessed / chip.hbm_bw
    t_collective = costs["coll_bytes"] / chip.ici_link_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_collective}
    bottleneck = max(terms, key=terms.get)

    rec = {
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_accessed,
        "collective_bytes_per_dev": costs["coll_bytes"],
        "collective_breakdown": {k: round(v) for k, v in costs["coll_by_op"].items()},
        "xla_cost_flops_per_dev": float(xla_cost.get("flops", 0.0)),  # loop bodies ×1
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "bottleneck": bottleneck,
        "step_time_bound_s": max(terms.values()),
    }
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
            }
    except Exception:
        pass
    rec["memory_analysis"] = mem
    return rec


# ---------------------------------------------------------------------------
# VMEM footprint (design-space feasibility for repro.tune)
# ---------------------------------------------------------------------------

# Mosaic double-buffers the HBM→VMEM pipeline: while one input tile is being
# consumed the next is in flight, so a tile's VMEM cost is ~2x its size.
PIPELINE_BUFFERS = 2
# Fraction of VMEM a single kernel may claim for its tiles + scratch; the
# rest is headroom for the compiler's own temporaries and constants.
VMEM_BUDGET_FRACTION = 0.8


def vmem_footprint_bytes(
    tiles: Any, scratch: Any = (), *, buffers: int = PIPELINE_BUFFERS
) -> int:
    """VMEM bytes a kernel config point needs resident at once.

    ``tiles``/``scratch`` are iterables of ``(shape, dtype_bytes)``; input and
    output tiles are multiplied by ``buffers`` (pipeline double-buffering),
    scratch is single-buffered (it persists across grid steps).  This is the
    feasibility half of the roofline model: a config whose tiles don't fit
    never reaches the timing sweep (see :mod:`repro.tune.space`).
    """
    def _bytes(rows: Any) -> int:
        total = 0
        for shape, dtype_bytes in rows:
            n = 1
            for d in shape:
                n *= int(d)
            total += n * int(dtype_bytes)
        return total

    return buffers * _bytes(tiles) + _bytes(scratch)


def fits_vmem(
    footprint_bytes: float,
    chip: Optional[ChipSpec] = None,
    *,
    fraction: float = VMEM_BUDGET_FRACTION,
) -> bool:
    """Whether a config point's working set fits the chip's VMEM budget."""
    chip = chip or default_chip()
    return footprint_bytes <= chip.vmem_bytes * fraction


# ---------------------------------------------------------------------------
# MODEL_FLOPS (the "useful work" yardstick)
# ---------------------------------------------------------------------------


def count_params(abs_params: Any, *, active: bool, cfg: ModelConfig) -> int:
    """Param count; ``active`` scales expert tensors by (top_k / n_experts)."""
    import jax

    total = 0.0
    flat = jax.tree_util.tree_flatten_with_path(abs_params)[0]
    for path, leaf in flat:
        n = int(np.prod(leaf.shape, dtype=np.int64)) if leaf.shape else 1
        keys = "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)
        if active and cfg.moe and "/ffn/w" in keys and leaf.ndim >= 3 and leaf.shape[-3] == cfg.moe.held[1]:
            n = n * cfg.moe.top_k / cfg.moe.n_experts
        total += n
    return int(total)


def model_flops(cfg: ModelConfig, shape: ShapeConfig, abs_params: Any) -> float:
    """Analytic useful FLOPs per step: 6·N_active·T (+backward-free for serve)
    + attention quadratic term + unembed matmul.  Embedding lookup excluded.
    """
    n_active = count_params(abs_params, active=True, cfg=cfg)
    n_embed = cfg.vocab_size * cfg.d_model * (1 if cfg.tied_embeddings else 2)
    n_matmul = max(n_active - n_embed, 0)
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        T = B * S
        base = 6.0 * n_matmul * T + 3 * 2.0 * T * cfg.d_model * cfg.vocab_size
        attn_mult = 3  # fwd + bwd
        tokens_sq = _attn_token_pairs(cfg, S, causal=True) * B
    elif shape.kind == "prefill":
        T = B * S
        base = 2.0 * n_matmul * T + 2.0 * T * cfg.d_model * cfg.vocab_size
        attn_mult = 1
        tokens_sq = _attn_token_pairs(cfg, S, causal=True) * B
    else:  # decode: one token vs cache of S
        T = B
        base = 2.0 * n_matmul * T + 2.0 * T * cfg.d_model * cfg.vocab_size
        attn_mult = 1
        tokens_sq = _attn_token_pairs(cfg, S, causal=False, decode=True) * B
    attn = attn_mult * 4.0 * cfg.n_heads * cfg.head_dim * tokens_sq
    return base + attn


def _attn_token_pairs(
    cfg: ModelConfig, S: int, *, causal: bool, decode: bool = False
) -> float:
    """Σ over attention layers of (q, kv) pair count."""
    pairs = 0.0
    for i in range(cfg.n_layers):
        spec = cfg.layer_spec(i)
        if spec.mixer not in ("ga", "swa"):
            continue
        w = cfg.sliding_window if spec.mixer == "swa" else None
        if decode:
            pairs += min(w, S) if w else S
        elif w and w < S:
            pairs += S * w - w * (w - 1) / 2  # causal within window
        else:
            pairs += S * (S + 1) / 2 if causal else S * S
    return pairs
