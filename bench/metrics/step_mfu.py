"""Whole training step's share of the chips' peak bf16 FLOP/s, in %.

Useful FLOPs per step (``benchlib.flops.train_step_flops``: no recompute)
times the steps of the window, over the window and the chips' peak.
"""


def read(record: dict):
    if not record["steps"]:
        return None
    achieved = record["step_flops"] * record["steps"] / record["window_s"]
    return 100.0 * achieved / (record["chips"] * record["peaks"]["bf16_flops"])
