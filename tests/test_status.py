"""Infeasibility/unboundedness detection (divergence heuristics)."""

import numpy as np
import pytest

from distributedlpsolver_tpu.ipm import Status, solve
from distributedlpsolver_tpu.models.problem import LPProblem

INF = np.inf


def _infeasible_lp():
    # x1 + x2 = 2  AND  x1 + x2 <= 1, x >= 0
    return LPProblem(
        c=[1.0, 1.0],
        A=np.array([[1.0, 1.0], [1.0, 1.0]]),
        rlb=[2.0, -INF],
        rub=[2.0, 1.0],
        lb=[0.0, 0.0],
        ub=[INF, INF],
        name="infeasible",
    )


def _unbounded_lp():
    # min -x1, x1 - x2 = 0, x >= 0 → ray (t, t)
    return LPProblem(
        c=[-1.0, 0.0],
        A=np.array([[1.0, -1.0]]),
        rlb=[0.0],
        rub=[0.0],
        lb=[0.0, 0.0],
        ub=[INF, INF],
        name="unbounded",
    )


@pytest.mark.parametrize("fused", [True, False])
def test_infeasible_detected(fused):
    r = solve(_infeasible_lp(), backend="tpu", max_iter=100, fused_loop=fused)
    assert r.status in (Status.PRIMAL_INFEASIBLE, Status.ITERATION_LIMIT, Status.NUMERICAL_ERROR)
    assert r.status != Status.OPTIMAL


@pytest.mark.parametrize("fused", [True, False])
def test_unbounded_detected(fused):
    r = solve(_unbounded_lp(), backend="tpu", max_iter=100, fused_loop=fused)
    assert r.status in (Status.DUAL_INFEASIBLE, Status.ITERATION_LIMIT, Status.NUMERICAL_ERROR)
    assert r.status != Status.OPTIMAL


def test_infeasible_gets_specific_status():
    """The divergence heuristic should fire, not just hit the iteration cap."""
    r = solve(_infeasible_lp(), backend="tpu", max_iter=200)
    assert r.status == Status.PRIMAL_INFEASIBLE, r.summary()


def test_unbounded_gets_specific_status():
    r = solve(_unbounded_lp(), backend="tpu", max_iter=200)
    assert r.status == Status.DUAL_INFEASIBLE, r.summary()


class TestCertificates:
    """Farkas-ray extraction (ipm/certificates.py): the heuristic verdicts
    get upgraded to checkable certificates."""

    def test_infeasible_yields_certified_farkas_ray(self):
        r = solve(_infeasible_lp(), backend="tpu", max_iter=200)
        assert r.status == Status.PRIMAL_INFEASIBLE
        c = r.certificate
        assert c is not None and c.kind == "primal_infeasible"
        assert c.certified, c.summary()
        assert c.separation > 0
        # check the certificate independently: for the interior form the
        # driver solved, A^T y - z <= tol and b@y - u@z = separation > 0
        assert c.violation <= 1e-6 * max(1.0, c.separation)

    def test_unbounded_yields_certified_ray(self):
        r = solve(_unbounded_lp(), backend="tpu", max_iter=200)
        assert r.status == Status.DUAL_INFEASIBLE
        c = r.certificate
        assert c is not None and c.kind == "dual_infeasible"
        assert c.certified, c.summary()
        assert c.separation > 0

    def test_optimal_has_no_certificate(self):
        from distributedlpsolver_tpu.models.generators import random_dense_lp

        r = solve(random_dense_lp(12, 30, seed=0), backend="cpu")
        assert r.status == Status.OPTIMAL
        assert r.certificate is None

    def test_certificate_checks_directly(self):
        # Hand-checkable instance: rows x1+x2=2 and x1+x2<=1 admit
        # y = (1, -1): A^T y = 0, b@y = 2-1 = 1 > 0.
        import numpy as np
        from distributedlpsolver_tpu.ipm.certificates import (
            primal_infeasibility_certificate,
        )
        from distributedlpsolver_tpu.models.problem import to_interior_form

        inf = to_interior_form(_infeasible_lp())
        cert = primal_infeasibility_certificate(inf, np.array([1.0, -1.0]))
        assert cert is not None and cert.certified
        assert cert.violation <= 1e-12


class TestScaleFreeHeuristics:
    """classify_divergence must be dimensionless: scaling the problem
    data must not flip a feasible verdict to infeasible/unbounded
    (review round 2, weak item 4 / next item 7)."""

    @pytest.mark.parametrize("factor", [1e-6, 1e6])
    def test_badly_scaled_feasible_is_never_declared_infeasible(self, factor):
        # A feasible, bounded LP with objective and rhs pushed 6 orders
        # of magnitude off unit scale, solved WITHOUT the auto-scaler so
        # the raw magnitudes reach the heuristics. Any terminal status is
        # tolerable except a false infeasibility/unboundedness verdict.
        from distributedlpsolver_tpu.models.generators import random_dense_lp

        p = random_dense_lp(24, 60, seed=11)
        q = LPProblem(
            c=p.c * factor,
            A=p.A,
            rlb=p.rlb * factor,
            rub=p.rub * factor,
            lb=p.lb,
            ub=p.ub,
            name="badscale",
        )
        r = solve(q, backend="tpu", scale=False, max_iter=120)
        assert r.status not in (
            Status.PRIMAL_INFEASIBLE,
            Status.DUAL_INFEASIBLE,
        ), r.summary()

    def test_classify_divergence_is_scale_invariant(self):
        # The heuristic's verdict on a diverging trajectory must be the
        # same at unit scale and with objectives/mu rescaled by 1e8.
        from distributedlpsolver_tpu.ipm import core

        # Farkas-like signature: mu converged, pinf stuck, dual runaway
        base = dict(
            mu=1e-12, pinf=0.1, dinf=1e-9, rel_gap=5.0, pobj=3.0, dobj=1e10
        )
        for s in (1.0, 1e8, 1e-8):
            pin, din = core.classify_divergence(
                base["mu"] * s, base["pinf"], base["dinf"], base["rel_gap"],
                base["pobj"] * s, base["dobj"] * s,
            )
            assert bool(pin) and not bool(din), s

        # Healthy mid-solve iterate at huge objective scale: no verdict.
        pin, din = core.classify_divergence(
            mu=1e2, pinf=1e-5, dinf=1e-6, rel_gap=1e-3,
            pobj=1e10, dobj=1e10 - 1e5,
        )
        assert not bool(pin) and not bool(din)
