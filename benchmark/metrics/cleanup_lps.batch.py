"""Median over the window's batched calls of the LPs the solo clean-up
re-solved (counter ``cleanup_lps``)."""

from benchmark import batch_calls


def read(run):
    return batch_calls.median(
        run, lambda r: batch_calls.counter(r, "cleanup_lps"))
