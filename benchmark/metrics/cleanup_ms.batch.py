"""Median over the window's batched calls of the solo clean-up's time, in
ms: self time of ``batch.cleanup``, every chunk (0 where no member needed
it)."""

from benchmark import batch_calls


def read(run):
    return batch_calls.self_ms(run, "batch.cleanup")
