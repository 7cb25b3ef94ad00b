"""The Pallas flash-attention forward kernel's share of its roofline, in %.

Each call's least time is max(FLOPs / peak FLOP/s, bytes / HBM bandwidth),
from the call's shapes (``benchlib.flops.flash_fwd_cost``); at the cells'
sequence lengths the FLOPs bound it.  The share is that least time times
the calls in the trace, over the device time of the kernel's events
(``flash_attention*`` in the XLA Ops line), forward and rematerialised
forward alike.  Nothing is returned where the trace holds no such event.
"""

KERNEL = "flash_attention"


def read(record: dict):
    ops = record["trace"]["ops"]
    calls = [v for k, v in ops.items() if k.startswith(KERNEL)]
    seconds = sum(v["s"] for v in calls)
    if not seconds:
        return None
    flops, nbytes = record["flash_fwd_call"]
    peaks = record["peaks"]
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * sum(v["count"] for v in calls) / seconds
