"""Operations and bytes the algorithm needs, computed from shapes alone.

``train_step_flops`` is the useful work of one training step of a dense
decoder: 6 FLOPs per matmul parameter per token (forward and backward), the
unembedding matmul, and the causal attention pairs (QK^T and PV, forward and
backward).  Recomputed operations do not count, and the embedding lookup is
free.  Sizes come from the configuration file's published widths.
"""
from __future__ import annotations


def dense_param_counts(m: dict) -> dict[str, int]:
    """Parameter counts of a dense GQA decoder from its published widths."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // hq
    matmul = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    bias = (hq + 2 * hkv) * hd if m.get("attention_bias") else 0
    layer = matmul + bias + 2 * d  # two norm scales
    embed = m["vocab_size"] * d * (1 if m["tie_word_embeddings"] else 2)
    L = m["num_hidden_layers"]
    return {"layer": layer, "matmul": L * matmul, "embed": embed,
            "total": L * layer + embed + d}


def causal_pairs(S: int) -> float:
    """(query, key) pairs a causal attention over S positions computes."""
    return S * (S + 1) / 2


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    """Useful FLOPs of one training step (forward + backward, no remat)."""
    n = dense_param_counts(m)
    d, hq = m["hidden_size"], m["num_attention_heads"]
    hd = m.get("head_dim") or d // hq
    T = batch * seq
    matmul = 6.0 * n["matmul"] * T
    unembed = 6.0 * T * d * m["vocab_size"]
    attn = 3 * 4.0 * hq * hd * causal_pairs(seq) * batch * m["num_hidden_layers"]
    return matmul + unembed + attn


def flash_fwd_cost(batch: int, seq: int, hq: int, hkv: int, hd: int,
                   dtype_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one causal flash-attention forward call needs.

    FLOPs: QK^T and PV over the causal pairs.  Bytes: q, k, v read once,
    the output written once and the f32 log-sum-exp row written once: the
    least traffic any kernel computing this call must move.
    """
    flops = 4.0 * hd * hq * causal_pairs(seq) * batch
    elems = batch * seq * hd * (2 * hq + 2 * hkv)
    return flops, elems * dtype_bytes + 4.0 * batch * hq * seq
