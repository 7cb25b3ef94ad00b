"""The grouped expert matmuls' share of their roofline, in %.

Each call's least time is max(FLOPs / peak FLOP/s, bytes / HBM bandwidth),
from the rows the program counted per MoE layer and call and the published
widths (``benchlib.moe_flops.gmm_cost``, recorded by the driver as
``moe_gmm_call``), never from tile padding.  The share is that least time
times the calls in the trace, over the device time of the products' events
(``moe_gmm_fwd``, ``moe_gmm_dx`` and ``moe_gmm_dw`` in the XLA Ops line;
a transformed program may prefix the name, as ``jvp_jit_moe_gmm_fwd__.2``),
forward, recomputed forward and backward alike.  Nothing is returned where
the trace holds no such event.
"""

KERNEL = "moe_gmm_"


def read(record: dict):
    ops = record["trace"]["ops"]
    calls = [v for k, v in ops.items() if KERNEL in k]
    seconds = sum(v["s"] for v in calls)
    if not seconds or "moe_gmm_call" not in record:
        return None
    flops, nbytes = record["moe_gmm_call"]
    peaks = record["peaks"]
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * sum(v["count"] for v in calls) / seconds
