"""Time the f64c linking downdate at the pds-10 layout on the chip.

    python scripts/probe_downdate.py

Builds the benchmark's pds10 LP (``benchmark/generators/pds_network``)
with auto's detected block hint, then times one f64c factorization
(``_block_ops_f64c.factorize``) and its downdate's parts alone: W
gathered from Gk's nonzeros (``_gathered_w``), W by one GEMM, the Gram
product WᵀW, and the three-GEMM ``Gk·(Lk⁻ᵀ·(Lk⁻¹·Gkᵀ))`` form it
replaced. ``gather_mad_cost`` is the gather's time per multiply-add over
the W GEMM's time per flop: the reading that ``_GATHER_MAD_COST`` stands
for. Prints one JSON line.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.generators import pds_network  # noqa: E402
from distributedlpsolver_tpu.backends import block_angular as B  # noqa: E402
from distributedlpsolver_tpu.models.problem import to_interior_form  # noqa: E402
from distributedlpsolver_tpu.models.structure import detect_block_structure  # noqa: E402


def median_ms(f, *args, reps=5):
    jax.block_until_ready(f(*args))  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(ts))


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "pds10.json")) as f:
        params = json.load(f)["params"]
    inf = to_interior_form(pds_network.build(params, 0, 0))
    inf.block_structure = detect_block_structure(inf.A)
    rep = {}
    t, lay = B.build_tensors(inf, jnp.float64, assembly=rep)
    K, mb, link = lay.K, lay.mb, lay.link
    d = jnp.asarray(np.random.default_rng(7).uniform(0.5, 2.0, lay.n))
    reg = jnp.asarray(1e-10, jnp.float64)

    factorize = jax.jit(
        lambda t, d: B._block_ops_f64c(t, lay, reg).factorize(d))
    Lki, _, Gk = factorize(t, d)
    out = {"device": jax.devices()[0].device_kind, "layout": lay._asdict(),
           "assembly": rep, "factorize_ms": median_ms(factorize, t, d)}
    gemm_w = jax.jit(lambda L, G: jnp.einsum("kli,kpi->klp", G, L))
    gram = jax.jit(lambda W: jnp.einsum("klp,kjp->lj", W, W))
    three = jax.jit(lambda L, G: jnp.einsum("klm,kmp->lp", G, jnp.einsum(
        "kpm,kpl->kml", L, jnp.einsum("kmp,klp->kml", L, G))))
    parts = {"gemm_w": median_ms(gemm_w, Lki, Gk),
             "gram": median_ms(gram, gemm_w(Lki, Gk)),
             "three_gemms": median_ms(three, Lki, Gk)}
    rows = getattr(t.pairs, "w_row", None)  # a tree before the gather has none
    if rows is not None:
        parts["gather_w"] = median_ms(jax.jit(B._gathered_w), Lki, Gk, rows)
        per_mad = parts["gather_w"] / (K * link * rows.shape[2] * mb)
        per_flop = parts["gemm_w"] / (2.0 * K * mb * mb * link)
        out["gather_mad_cost"] = per_mad / per_flop
    out["parts_ms"] = parts
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
