"""Reference objectives for chip_smoke.py's phases (a) and (b).

HiGHS needs minutes on these instances, so the objectives are computed
once here, on the CPU, from the generator calls and seeds chip_smoke.py
uses, and committed to scripts/chip_smoke_refs.json. chip_smoke.py
rebuilds each instance from its seed and compares against this file.

Usage: JAX_PLATFORMS=cpu python scripts/chip_smoke_refs.py
"""

import json
import os
import sys
import time

import numpy as np
import scipy.optimize as sopt
import scipy.sparse as sp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402
from distributedlpsolver_tpu.models.generators import (  # noqa: E402
    block_angular_lp,
    random_dense_lp,
)
from distributedlpsolver_tpu.models.problem import (  # noqa: E402
    to_interior_form,
)


def highs(problem, method: str) -> float:
    """HiGHS on the interior form (min cᵀx + c0, Ax = b, 0 ≤ x ≤ u) —
    the same objective ``ipm.solve`` reports in the original space."""
    inf = to_interior_form(problem)
    A = inf.A if sp.issparse(inf.A) else sp.csr_matrix(inf.A)
    ub = np.where(np.isfinite(inf.u), inf.u, np.inf)
    res = sopt.linprog(
        inf.c, A_eq=A, b_eq=inf.b,
        bounds=np.c_[np.zeros_like(ub), ub], method=method,
    )
    if res.status != 0:
        raise RuntimeError(f"HiGHS ({method}) failed: {res.message}")
    return float(res.fun) + float(inf.c0)


def main() -> int:
    d, s = chip_smoke.DENSE, chip_smoke.STORM
    storm = block_angular_lp(
        s["num_blocks"], s["block_m"], s["block_n"], s["link_m"],
        seed=s["seed"], sparse=True, density=s["density"],
    )
    jobs = [
        # Dense: the interior-point variant with crossover is far faster
        # than simplex on 21M nonzeros.
        (chip_smoke.dense_key(**d), random_dense_lp(**d), "highs-ipm"),
        (chip_smoke.storm_key(**s), storm, "highs"),
    ]
    refs = {}
    for key, problem, method in jobs:
        t0 = time.time()
        refs[key] = highs(problem, method)
        print(f"{key}: {refs[key]!r} ({method}, {time.time() - t0:.0f} s)",
              flush=True)
    with open(chip_smoke.REFS_PATH, "w") as fh:
        json.dump(refs, fh, indent=1)
        fh.write("\n")
    print(f"wrote {chip_smoke.REFS_PATH}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
