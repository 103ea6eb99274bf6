"""Median over the window's batched calls of the device loop's time, in
ms: self time of ``batch.loop`` (start, segments, final norms; every
chunk) and of the ``ipm.segment`` spans of its drive, which nest in it;
the compaction gathers (``batch.compact``) are left out."""

from benchmark import batch_calls


def read(run):
    return batch_calls.self_ms(run, "batch.loop", "ipm.segment")
