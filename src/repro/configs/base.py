"""Config schema for all architectures and input shapes.

One unified decoder-LM schema covers the 10 assigned architectures via a
*layer pattern*: a periodic sequence of (mixer, ffn) block kinds.  The model
stacks parameters per pattern-position and scans over periods, which keeps the
HLO size O(period) instead of O(n_layers) — essential for fast multi-pod
compilation.
"""
from __future__ import annotations

import dataclasses
from typing import Literal, Optional

Mixer = Literal["ga", "swa", "mamba", "rwkv"]  # global attn / sliding-window attn / SSM / RWKV6
Ffn = Literal["dense", "moe", "rwkv_ffn", "none"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: Mixer = "ga"
    ffn: Ffn = "dense"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8  # routed experts: the router's width
    top_k: int = 2
    n_shared: int = 0  # always-on shared experts (DeepSeekMoE)
    d_expert: int = 0  # per-expert FFN width (fine-grained experts)
    norm_topk: bool = True  # renormalise the top-k gates to sum to 1
    router_aux_weight: float = 0.01  # sequence-level balance loss weight (alpha)
    router_z_weight: float = 1e-3
    # the experts this chip holds, [held_first, held_first + n_held); 0 = all
    held_first: int = 0
    n_held: int = 0
    # jitter etc. omitted: deterministic routing for reproducibility

    @property
    def held(self) -> tuple[int, int]:
        """(first expert held here, number held)."""
        return self.held_first, self.n_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)
    chunk: int = 256  # chunked-scan block length


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    decay_lora: int = 64  # low-rank dim of the data-dependent decay MLP (RWKV6 "Finch")
    mix_lora: int = 32  # low-rank dim of the token-shift mix MLPs
    chunk: int = 128


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    layer_pattern: tuple[LayerSpec, ...] = (LayerSpec(),)
    first_k_dense: int = 0  # first k layers forced to (pattern[0].mixer, dense) (DeepSeekMoE)
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    qkv_bias: bool = False  # Qwen2
    qk_norm: bool = False  # Chameleon
    attn_logit_softcap: Optional[float] = None  # Gemma-2
    final_logit_softcap: Optional[float] = None  # Gemma-2
    post_block_norms: bool = False  # Gemma-2/3 post-attn/post-ffn RMSNorms
    scale_embedding: bool = False  # Gemma: multiply embeddings by sqrt(d_model)
    z_loss_weight: float = 1e-4  # final-logit z-loss (stability at scale)
    tied_embeddings: bool = True
    norm_eps: float = 1e-6
    act: str = "silu"
    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None
    frontend: str = "text"  # text | vlm_stub | audio_stub
    # numerics / memory policy
    param_dtype: str = "bfloat16"
    activation_dtype: str = "bfloat16"
    moment_dtype: str = "float32"  # bf16 for the very large archs (398B on 16GiB chips)
    # nothing (recompute each period, but keep the Pallas attention's out and
    # lse) | dots | everything (= no remat)
    remat_policy: str = "nothing"
    # True: lax.scan over periods (fast compiles, small HLO).  False: unrolled
    # Python loop — used by the dry-run so cost_analysis counts every layer
    # (XLA prices a while-loop body ONCE, not × trip count).
    scan_layers: bool = True
    # --- beyond-paper §Perf optimizations (default off = faithful baseline) ---
    # custom-VJP flash attention: backward recomputes block scores instead of
    # stacking O(S²) softmax residuals through the KV-block scan.
    fused_attention_vjp: bool = False
    # pad attention Q-heads (activations only, params untouched) up to this
    # count so the S² compute shards over 'model' when n_heads doesn't divide
    # it (smollm 15H / qwen2 14H on a 16-way axis); 0 = off.
    pad_heads_to: int = 0
    # explicit activation sharding constraints at module boundaries (helps
    # GSPMD propagation pick batch/model shardings instead of replicating).
    activation_constraints: bool = False
    # replicate the unembed table's embed dim across 'data' inside the loss
    # (one hoisted all-gather instead of a partial-sum all-reduce per chunk).
    loss_table_replicated: bool = False
    # split-KV decode combine (shard_map flash-decoding) when the KV cache is
    # sequence-sharded — otherwise XLA all-gathers the cache every step.
    decode_split_kv: bool = False
    # checkpoint the chunk bodies of the mamba/rwkv chunked scans: AD saves
    # chunk-boundary states only (the SSM analogue of the flash VJP).
    chunk_scan_remat: bool = False
    decode_seq_axes: tuple = ("model",)  # mesh axes the cache seq dim shards over
    decode_batch_axes: tuple = ("pod", "data")  # mesh axes the batch shards over
    loss_chunk: int = 1024  # token-chunked cross-entropy chunk size
    attn_chunk: int = 1024  # KV block length of the lax chunked-attention path
    # profiling (the paper's technique): static tracepoints compiled into the
    # step when enabled; see repro.core.tracepoints
    tracepoints: bool = False

    @property
    def period(self) -> int:
        return len(self.layer_pattern)

    def layer_spec(self, i: int) -> LayerSpec:
        if i < self.first_k_dense:
            return LayerSpec(mixer=self.layer_pattern[i % self.period].mixer, ffn="dense")
        return self.layer_pattern[i % self.period]

    @property
    def n_periods(self) -> int:
        return (self.n_layers - self.first_k_dense) // self.period

    @property
    def n_tail(self) -> int:
        """Layers after first_k_dense not covered by full periods (handled unscanned)."""
        return (self.n_layers - self.first_k_dense) % self.period

    @property
    def uses_attention(self) -> bool:
        return any(s.mixer in ("ga", "swa") for s in self.layer_pattern)

    @property
    def pure_full_attention(self) -> bool:
        """True if every mixer is global attention (no locality / recurrence)."""
        return all(s.mixer == "ga" for s in self.layer_pattern)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


# The four assigned LM shapes; `decode_*`/`long_*` lower serve_step.
SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def supports_shape(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """long_500k requires sub-quadratic attention (see DESIGN.md §5)."""
    if shape.name == "long_500k" and cfg.pure_full_attention:
        return False, (
            f"{cfg.name} is pure full-attention; a 512k dense KV cache has no "
            "locality/recurrence structure — skipped per assignment"
        )
    return True, ""


def share(
    cfg: ModelConfig,
    *,
    name: str,
    moe_layers: int,
    experts: tuple[int, int],
    vocab_size: int,
) -> ModelConfig:
    """One chip's share of an expert-parallel deployment, as one pipeline stage.

    Keeps the leading dense layers and the ``moe_layers`` layers after them
    (the others lie on further stages), holds ``experts`` = (first, count), a
    contiguous block of every MoE layer's routed experts (the router still
    scores all of them; the experts held elsewhere add nothing here), and a
    vocabulary slice of ``vocab_size`` ids.  Every width is as published.
    """
    first, count = experts
    if cfg.moe is None or not 0 <= first < first + count <= cfg.moe.n_experts:
        raise ValueError(f"{cfg.name}: cannot hold experts {experts}")
    return dataclasses.replace(
        cfg,
        name=name,
        n_layers=cfg.first_k_dense + moe_layers,
        vocab_size=vocab_size,
        moe=dataclasses.replace(cfg.moe, held_first=first, n_held=count),
    )


def reduced(cfg: ModelConfig, *, layers: int | None = None) -> ModelConfig:
    """Smoke-test variant: same family/pattern, tiny dims, runs on 1 CPU."""
    n_layers = layers if layers is not None else max(cfg.first_k_dense + cfg.period, 2)
    changes: dict = dict(
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        sliding_window=16,
        loss_chunk=32,
        attn_chunk=16,
        param_dtype="float32",
        activation_dtype="float32",
        moment_dtype="float32",
        remat_policy="everything",
    )
    if cfg.moe is not None:
        n = min(cfg.moe.n_experts, 8)
        first, held = cfg.moe.held
        changes["moe"] = dataclasses.replace(
            cfg.moe,
            n_experts=n,
            top_k=min(cfg.moe.top_k, 2),
            n_shared=min(cfg.moe.n_shared, 1),
            d_expert=32 if cfg.moe.d_expert else 0,
            held_first=first * n // cfg.moe.n_experts,
            n_held=max(1, held * n // cfg.moe.n_experts) if cfg.moe.n_held else 0,
        )
    if cfg.mamba is not None:
        changes["mamba"] = dataclasses.replace(cfg.mamba, d_state=8, chunk=16)
    if cfg.rwkv is not None:
        changes["rwkv"] = dataclasses.replace(cfg.rwkv, head_dim=16, decay_lora=8, mix_lora=8, chunk=16)
    return dataclasses.replace(cfg, **changes)
