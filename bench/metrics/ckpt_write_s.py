"""The closing save's write, in s: the program's ``ckpt.write`` span on the
checkpointer's writer thread (serialise, fsync, rename, gc), on the host
clock.  It sets the next save's stall whenever it outlasts the save
interval.

Read, as ``ckpt_snapshot_s`` is, from the program's ``GLOBAL_LOG``, where
the train driver's checkpointer records; the driver waits for the write
before the metrics are read.  Nothing is returned where the program records
no such span.
"""
from repro.core import events

SPAN = "ckpt.write"


def read(record: dict):
    spans = events.GLOBAL_LOG.durations(SPAN)
    return spans[-1] if spans else None
