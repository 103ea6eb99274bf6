"""Accelerator-presence guard for measurement entry points.

A figure measured on the wrong platform is worse than none, so
``bench.py`` and the ``scripts/run_*`` TPU envelopes call
:func:`require_tpu` before measuring anything: no TPU means a non-zero
exit, never a switch to the CPU.
"""

from __future__ import annotations

import sys

REQUIRE_TPU_EXIT = 4  # distinct from solve-failure (2/3) exit codes


def require_tpu() -> None:
    """Hard-fail (``SystemExit`` with code :data:`REQUIRE_TPU_EXIT`)
    unless jax's default backend is TPU."""
    import jax

    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        print(
            f"accelerator initialization failed ({e}); refusing to "
            "measure on the CPU",
            file=sys.stderr,
        )
        raise SystemExit(REQUIRE_TPU_EXIT)
    if backend != "tpu":
        print(
            f"default backend is {backend!r}, not TPU — aborting before "
            "any figure is produced",
            file=sys.stderr,
        )
        raise SystemExit(REQUIRE_TPU_EXIT)
