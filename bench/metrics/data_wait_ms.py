"""Mean time per window step that the supervisor's loop spent in ``batch_fn``.

Source: the benchmark's own host-clock span around each call; nothing
overlaps it, so the chip waits for every millisecond of it.
"""


def read(record: dict):
    spans = record["batch_fn_s"]
    return 1e3 * sum(spans) / len(spans) if spans else None
