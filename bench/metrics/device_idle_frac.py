"""Share of the traced window in which the chips ran no operation, in %.

1 - (union of the XLA Ops intervals, averaged over the chips) / window.
"""


def read(record: dict):
    t = record["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
