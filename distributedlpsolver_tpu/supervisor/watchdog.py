"""Dispatch watchdog: run a device step under a wall-clock deadline.

The production failure this closes (ROUND5_NOTES.md): a single hung device
dispatch — a wedged runtime, a device in a bad state — blocks
``block_until_ready`` forever and wedges the worker for hours with no
status. There is no portable way to cancel an in-flight XLA dispatch, so
the watchdog runs the step in a daemon worker thread and abandons it on
deadline: the host classifies the fault, rolls back, and retries (possibly
on a degraded backend) while the stuck dispatch either eventually
completes into the void or dies with the process. Abandonment, not
cancellation, is the honest contract — the alternative is the observed
≥1h wedge.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

from distributedlpsolver_tpu.obs import metrics as obs_metrics


class StepDeadlineExceeded(RuntimeError):
    """A device step exceeded its watchdog deadline (FaultKind.HANG)."""

    def __init__(self, iteration: int, timeout: float):
        self.iteration = iteration
        self.timeout = timeout
        super().__init__(
            f"device step for iteration {iteration} exceeded its "
            f"{timeout:g}s deadline; dispatch abandoned"
        )


def run_with_deadline(
    fn: Callable,
    timeout: Optional[float],
    iteration: int = -1,
):
    """Run ``fn()`` with at most ``timeout`` seconds of wall clock.

    ``timeout`` of None or <= 0 disables the watchdog (direct call — no
    thread overhead on the hot path). On deadline the worker thread is
    abandoned (daemonized, so it cannot block interpreter exit) and
    :class:`StepDeadlineExceeded` raises on the caller's thread. Exceptions
    from ``fn`` re-raise on the caller's thread unchanged.
    """
    if not timeout or timeout <= 0:
        return fn()

    box: dict = {}

    def _target():
        try:
            box["value"] = fn()
        except BaseException as e:  # re-raised on the supervising thread
            box["error"] = e

    t = threading.Thread(
        target=_target, daemon=True, name=f"dlps-step-it{iteration}"
    )
    t.start()
    t.join(timeout)
    if t.is_alive():
        # Off the hot path by construction: a deadline hit already costs
        # a full recovery cycle, so the instrument resolve is fine here
        # (and the happy path above pays nothing).
        obs_metrics.get_registry().counter(
            "watchdog_timeouts_total",
            help="device dispatches abandoned past their deadline",
        ).inc()
        raise StepDeadlineExceeded(iteration, timeout)
    if "error" in box:
        raise box["error"]
    return box["value"]
