"""The closing save's device-to-host snapshot, in s: the program's
``ckpt.snapshot`` span (``AsyncCheckpointer.save``), on the host clock.

The train driver's closing save goes through an ``AsyncCheckpointer`` made
without a log, which records its spans in the program's ``GLOBAL_LOG``; the
last such span is this run's closing save.  With ``ckpt.wait_prev`` it is
the whole of the save's stall.  Nothing is returned where the program
records no such span.
"""
from repro.core import events

SPAN = "ckpt.snapshot"


def read(record: dict):
    spans = events.GLOBAL_LOG.durations(SPAN)
    return spans[-1] if spans else None
