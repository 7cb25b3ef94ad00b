"""Operations and bytes of a DeepSeekMoE share's training step, from its
published widths and the routed rows the program counted.

``train_step_flops`` is the useful work of one step, as
``benchlib.flops.train_step_flops`` counts it for a dense decoder: 6 FLOPs
per matmul parameter per token (forward and backward), the unembedding, and
the causal attention pairs.  The routed experts are counted by rows: 6 FLOPs
per expert matmul parameter for each (token, expert) pair that the program
computed on a held expert (``moe_rows``, summed over the MoE layers), never
by a capacity or by the kernel's tile padding.  Recomputed operations do not
count, and the embedding lookup is free.
"""
from __future__ import annotations

from benchlib.flops import causal_pairs


def widths(m: dict) -> dict:
    """The published widths and the share's sizes, under short names (``L``
    the MoE layers); the reference ``moe_lm`` reads them here too."""
    d, hq = m["hidden_size"], m["num_attention_heads"]
    dense = m["first_k_dense_replace"]
    return dict(D=d, H=hq, K=m["num_key_value_heads"], hd=m.get("head_dim") or d // hq,
                F=m["intermediate_size"], Fe=m["moe_intermediate_size"],
                Fs=m["n_shared_experts"] * m["moe_intermediate_size"],
                E=m["router_experts"], N=m["n_routed_experts"], dense=dense,
                L=m["num_hidden_layers"] - dense, V=m["vocab_size"])


def train_step_flops(m: dict, batch: int, seq: int, rows: float) -> float:
    """Useful FLOPs of one step whose MoE layers computed ``rows`` pairs in all."""
    w = widths(m)
    D, hd, H, K = w["D"], w["hd"], w["H"], w["K"]
    T = batch * seq
    layers = w["dense"] + w["L"]
    attn_proj = D * hd * (2 * H + 2 * K)
    per_token = (layers * attn_proj
                 + w["dense"] * 3 * D * w["F"]
                 + w["L"] * (3 * D * w["Fs"] + D * w["E"])
                 + D * w["V"])
    attn = 3 * 4.0 * H * hd * causal_pairs(seq) * batch * layers
    return 6.0 * per_token * T + 6.0 * 3 * D * w["Fe"] * rows + attn


def gmm_cost(m: dict, rows: float, dtype_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one grouped-matmul call over ``rows`` rows needs.

    Every product of the expert FFN (each forward matmul, its input gradient
    and its weight gradient) multiplies the rows by one (D, Fe) matrix per
    held expert: 2 * rows * D * Fe FLOPs, and the least traffic is the two
    row operands once and the held experts' weights once.
    """
    w = widths(m)
    D, Fe = w["D"], w["Fe"]
    return (2.0 * rows * D * Fe,
            dtype_bytes * (rows * (D + Fe) + w["N"] * D * Fe))
