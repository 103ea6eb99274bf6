"""Median over the window's batched calls of the masked loop's occupancy,
in %: 100 x ``member_iters`` / ``lane_iters``, the share of the member
steps the loop ran (iterations x program size, summed over segments)
that advanced an unfinished member."""

from benchmark import batch_calls


def _share(record):
    done = batch_calls.counter(record, "member_iters")
    lanes = batch_calls.counter(record, "lane_iters")
    return 100.0 * done / lanes if done is not None and lanes else None


def read(run):
    return batch_calls.median(run, _share)
