"""Median over the window's batched calls of the host's share around the
loop, in ms: self time of ``batch.prepare`` (cast, transfer, problem
data) and ``batch.readback`` (device to host), every chunk."""

from benchmark import batch_calls


def read(run):
    return batch_calls.self_ms(run, "batch.prepare", "batch.readback")
