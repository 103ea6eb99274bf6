"""Host/CPU reference backend (NumPy/SciPy, eager).

The reference's baseline execution is CPU ranks with LAPACK-backed dense
kernels (SURVEY.md §2 "CPU dense backend", [INFERRED] LAPACK/NumPy
potrf/trsv). This backend runs the *same* algorithm core as the device
backends (ipm/core.py with ``xp=numpy``) — it exists to (a) be the
measured baseline the TPU path is compared against, (b)
cross-check the JAX backends with a fully independent execution engine,
and (c) carry the native C++ kernels (backends/cpu_native.py) the way the
reference's CPU path sits on LAPACK.

Keeps scipy-sparse constraint matrices sparse for the matvecs and the
normal-equations assembly; only the m×m normal matrix is densified for
the Cholesky.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np
import scipy.linalg as sla
import scipy.sparse as sp

from distributedlpsolver_tpu.backends.base import SolverBackend, register_backend
from distributedlpsolver_tpu.ipm import core
from distributedlpsolver_tpu.ipm.config import SolverConfig
from distributedlpsolver_tpu.ipm.state import IPMState, StepStats
from distributedlpsolver_tpu.models.problem import InteriorForm


@register_backend("cpu", "numpy", "scipy")
class CpuBackend(SolverBackend):
    """Eager NumPy/SciPy execution of the shared IPM core."""

    def __init__(self):
        self._reg = 0.0
        self._cfg = None

    # seam for the native-kernel subclass -----------------------------------
    def _factorize(self, d: np.ndarray, reg: float):
        A = self._A
        if sp.issparse(A):
            M = (A.multiply(d)) @ A.T
            M = np.asarray(M.todense())
        else:
            M = (A * d[None, :]) @ A.T
        M[np.diag_indices_from(M)] *= 1.0 + reg
        return sla.cho_factor(M, lower=True, check_finite=False)

    def _solve(self, factors, rhs: np.ndarray) -> np.ndarray:
        return sla.cho_solve(factors, rhs, check_finite=False)

    # ----------------------------------------------------------------------
    def setup(self, inf: InteriorForm, config: SolverConfig) -> None:
        self._cfg = config
        self._reg = config.reg_dual
        self._params = config.step_params()
        if sp.issparse(inf.A):
            self._A = sp.csr_matrix(inf.A, dtype=np.float64)
        else:
            self._A = np.asarray(inf.A, dtype=np.float64)
        A = self._A
        self._data = core.make_problem_data(np, inf.c, inf.b, inf.u, np.float64)
        self._ops_template = dict(
            xp=np,
            matvec=lambda v: np.asarray(A @ v).ravel(),
            rmatvec=lambda v: np.asarray(A.T @ v).ravel(),
        )

    def _ops(self) -> core.LinOps:
        reg = self._reg
        return core.LinOps(
            factorize=lambda d: self._factorize(d, reg),
            solve=self._solve,
            **self._ops_template,
        )

    def starting_point(self) -> IPMState:
        return core.starting_point(self._ops(), self._data, self._params)

    def iterate(self, state: IPMState) -> Tuple[IPMState, StepStats]:
        try:
            new_state, stats = core.mehrotra_step(
                self._ops(), self._data, self._params, state
            )
        except np.linalg.LinAlgError:
            bad = np.bool_(True)
            nan = np.float64(np.nan)
            return state, StepStats(
                mu=nan, gap=nan, rel_gap=nan, pinf=nan, dinf=nan, pobj=nan,
                dobj=nan, alpha_p=nan, alpha_d=nan, sigma=nan, bad=bad,
            )
        return new_state, stats

    def bump_regularization(self) -> bool:
        if self._reg * self._cfg.reg_grow > 1e-2:
            return False
        self._reg = max(self._reg, 1e-12) * self._cfg.reg_grow
        return True
