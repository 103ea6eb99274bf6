"""storm-100k A/B harness for the f64 program-class fault (ROUND5_NOTES
lever 4, review round-4 item 8): the ≥100k-row storm class binds on an
f64 phase KERNEL fault — the worker crashes on the big-K batched f64
programs — not on HBM, while chunk ≤128 program shapes stay healthy.
This script runs the SAME hint-less storm-class instance through two
arms and records, per arm, the per-phase program-class stamp
(backends.block_angular.phase_program_class) plus outcome/timing:

* ``oneshot``  — grouping off (DLPS_BLOCK_K_GROUP=0): the pre-lever-4
  one-shot f64 phase programs, the arm that reproduces the fault class;
* ``kgroup``   — per-K-group sequential chunking at the default ≤128
  (DLPS_BLOCK_K_GROUP=128), the lever-4 fix.

Each arm runs in its OWN SUBPROCESS: ``_K_GROUP`` is read once at
import and jit traces key on operand shapes, not module globals — two
arms sharing a process would silently share compiled programs and
measure nothing.

Default shape: K=1024 blocks of 96x192 with 64 linking rows
= 98,368 + 64 rows (~100k), sparse, arriving hint-less.

A chip belongs to one process, so the parent never touches JAX: each
arm claims the chip itself and aborts with exit 4 when there is no TPU
(no figure is measured on the CPU). The platform each arm ran on is
recorded in its row.

Writes /root/repo/.storm100k.json. Optional argv: K mb nb link density.
"""
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, "/root/repo")


def _shape():
    if len(sys.argv) > 4:
        K, mb, nb, link = (int(a) for a in sys.argv[1:5])
        density = float(sys.argv[5]) if len(sys.argv) > 5 else 0.06
    else:
        K, mb, nb, link, density = 1024, 96, 192, 64, 0.06
    return K, mb, nb, link, density


def _arm_main(out_path):
    """One arm: build, detect, solve — grouping already fixed by the
    parent's DLPS_BLOCK_K_GROUP before this interpreter imported jax."""
    import jax
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as ba
    from distributedlpsolver_tpu.ipm import solve
    from distributedlpsolver_tpu.models.generators import block_angular_lp
    from distributedlpsolver_tpu.models.structure import detect_block_structure
    from distributedlpsolver_tpu.utils.accel import require_tpu

    require_tpu()
    K, mb, nb, link, density = _shape()
    out = {"k_group": ba._K_GROUP, "platform": jax.devices()[0].platform}
    print(f"[arm k_group={ba._K_GROUP}] building K={K} {mb}x{nb} "
          f"link={link} density={density}...", flush=True)
    t0 = time.time()
    p = block_angular_lp(K, mb, nb, link, seed=3, sparse=True,
                         density=density)
    p.block_structure = None  # hint-less, like a real MPS file
    out.update({
        "rows": int(p.A.shape[0]), "cols": int(p.A.shape[1]),
        "nnz": int(p.A.nnz), "build_s": round(time.time() - t0, 1),
    })
    try:
        t0 = time.time()
        hint = detect_block_structure(p)
        assert hint is not None, "detection declined the structure"
        out["detect_s"] = round(time.time() - t0, 2)
        out["detected_blocks"] = int(hint["num_blocks"])
        p.block_structure = hint
        # Per-phase program-class stamps — the quantity this harness
        # exists to A/B: the f32 phase keeps one-shot shapes in both
        # arms; the f64 phases are the lever-4 target.
        out["phase_program_class"] = {
            "f32": ba.phase_program_class(K, jnp.float32),
            "f64": ba.phase_program_class(K, jnp.float64),
        }

        solve(p, backend="block", max_iter=3)  # warm compile
        t0 = time.time()
        r = solve(p, backend="block", max_iter=120)
        wall = time.time() - t0
        out.update({
            "status": r.status.value, "objective": r.objective,
            "iters": int(r.iterations), "rel_gap": float(r.rel_gap),
            "pinf": float(r.pinf), "dinf": float(r.dinf),
            "time_s": round(r.solve_time, 2), "wall_s": round(wall, 1),
        })
        print(f"[arm k_group={ba._K_GROUP}] {r.status.name} "
              f"obj={r.objective:.6f} iters={r.iterations} "
              f"solve={r.solve_time:.2f}s wall={wall:.1f}s", flush=True)
    except Exception as e:  # record WHERE it binds instead of dying silently
        out["failed"] = f"{type(e).__name__}: {str(e)[:500]}"
        print(f"[arm k_group={ba._K_GROUP}] FAILED:", out["failed"],
              flush=True)
    with open(out_path, "w") as fh:
        json.dump(out, fh, indent=1)


if "--arm" in sys.argv:
    i = sys.argv.index("--arm")
    path = sys.argv[i + 1]
    sys.argv = sys.argv[:i] + sys.argv[i + 2:]
    _arm_main(path)
    sys.exit(0)


K, mb, nb, link, density = _shape()
out = {
    "config": f"storm100k-class block_angular(K={K},{mb}x{nb},link={link},"
              f"density={density}), HINT-LESS, A/B oneshot vs kgroup",
    "arms": {},
}
for name, group in (("oneshot", "0"), ("kgroup", "128")):
    arm_path = f"/root/repo/.storm100k.{name}.json"
    env = dict(os.environ, DLPS_BLOCK_K_GROUP=group)
    print(f"=== arm {name} (DLPS_BLOCK_K_GROUP={group}) ===", flush=True)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--arm", arm_path]
        + sys.argv[1:],
        env=env,
    )
    arm = {"exit_code": proc.returncode,
           "harness_wall_s": round(time.time() - t0, 1)}
    # A crashed worker (the fault class under A/B) leaves no JSON — the
    # exit code IS the datum then.
    if os.path.exists(arm_path):
        with open(arm_path) as fh:
            arm.update(json.load(fh))
        os.remove(arm_path)
    out["arms"][name] = arm

a, b = out["arms"].get("oneshot", {}), out["arms"].get("kgroup", {})
if "time_s" in a and "time_s" in b:
    out["kgroup_speedup"] = round(a["time_s"] / max(b["time_s"], 1e-9), 3)
if "objective" in a and "objective" in b:
    out["arms_agree"] = bool(
        abs(a["objective"] - b["objective"])
        <= 1e-6 * (1 + abs(a["objective"]))
    )

with open("/root/repo/.storm100k.json", "w") as fh:
    json.dump(out, fh, indent=1)
print("wrote .storm100k.json", flush=True)
