"""graftcheck analyzer tests: every rule family catches its seeded
fixture and stays silent on the clean twin, suppression-comment
semantics, the interprocedural v2 families (spmd-*, lock-order,
blocking-under-lock) with their fixture twins, the static-vs-dynamic
lock-order cross-check on a live 3-thread SolveService drain, the
--baseline incremental diff-gate, the stdlib-only analyzer contract,
the no-TPU envelope guard (bench.py, require_tpu), and the tier-1 gate — `cli check
distributedlpsolver_tpu/` must exit 0 with zero unsuppressed findings
on the landed tree (and against the committed empty baseline)."""

import json
import os
import sys
import tempfile
import threading
import time

import pytest

from distributedlpsolver_tpu.analysis import (
    LockOrderRecorder,
    LockOrderViolation,
    all_rules,
    check_file,
    check_paths,
)

pytestmark = pytest.mark.check

_HERE = os.path.dirname(os.path.abspath(__file__))
_FIX = os.path.join(_HERE, "graftcheck_fixtures")
_PKG = os.path.join(os.path.dirname(_HERE), "distributedlpsolver_tpu")


def _rules_hit(path, pkg_path, only=None):
    findings = check_file(os.path.join(_FIX, path), pkg_path=pkg_path, rules=only)
    return (
        sorted({f.rule for f in findings if not f.suppressed}),
        [f for f in findings if not f.suppressed],
    )


class TestRuleFamilies:
    def test_jit_family_catches_seeded(self):
        rules, findings = _rules_hit("fx_jit_bad.py", "backends/batched.py")
        assert rules == ["jit-donate", "jit-nonhoisted", "jit-scalar-default"]
        # both the per-call jit() and the nested bare decorator are caught
        assert sum(f.rule == "jit-nonhoisted" for f in findings) == 2

    def test_jit_family_clean_twin_silent(self):
        rules, _ = _rules_hit("fx_jit_clean.py", "backends/batched.py")
        assert rules == []

    def test_host_sync_catches_seeded(self):
        rules, findings = _rules_hit("fx_host_sync_bad.py", "serve/service.py")
        assert rules == ["host-sync"]
        # float / .item / block_until_ready / np.asarray-in-closure; the
        # non-hot-scope float() must NOT be flagged
        assert len(findings) == 4
        assert all("cold_path" not in f.message for f in findings)

    def test_host_sync_clean_twin_silent(self):
        rules, _ = _rules_hit("fx_host_sync_clean.py", "serve/service.py")
        assert rules == []

    def test_host_sync_out_of_scope_file_silent(self):
        # The same seeded file under a non-hot pkg_path is silent: the
        # rule is scope-keyed, not pattern-global.
        rules, _ = _rules_hit("fx_host_sync_bad.py", "models/problem.py")
        assert rules == []

    def test_dtype_family_catches_seeded(self):
        rules, findings = _rules_hit("fx_dtype_bad.py", "ipm/fx.py")
        assert rules == ["dtype-explicit", "dtype-narrow"]
        assert sum(f.rule == "dtype-explicit" for f in findings) == 3
        assert sum(f.rule == "dtype-narrow" for f in findings) == 2

    def test_dtype_family_clean_twin_silent(self):
        rules, _ = _rules_hit("fx_dtype_clean.py", "ipm/fx.py")
        assert rules == []

    def test_dtype_narrow_sanctioned_module_exempt(self):
        rules, _ = _rules_hit(
            "fx_dtype_bad.py", "ops/chol_mxu.py", only=["dtype-narrow"]
        )
        assert rules == []

    def test_dtype_out_of_scope_dir_silent(self):
        rules, _ = _rules_hit("fx_dtype_bad.py", "serve/fx.py")
        assert rules == []

    def test_df32_pack_narrowing_flagged_outside_sanctioned_module(self):
        # The df32 pack idiom (f64 → hi/lo f32 split) is exactly the
        # narrowing the rule exists to catch when it leaks out of the
        # two-float module.
        rules, findings = _rules_hit("fx_df32_bad.py", "ipm/fx.py")
        assert rules == ["dtype-narrow"]
        assert len(findings) == 2

    def test_df32_module_sanctioned_for_narrowing(self):
        # The identical idiom under ops/df32.py — the sanctioned
        # mixed-precision schedule owner — is exempt, twin stays clean.
        rules, _ = _rules_hit("fx_df32_clean.py", "ops/df32.py")
        assert rules == []

    def test_sparse_ops_narrowing_flagged_outside_sanctioned_module(self):
        # The sparse tier's idioms (unpinned ELL pad buffers, f32 probe
        # factors) seeded outside the sanctioned matrix-free modules.
        rules, findings = _rules_hit("fx_sparse_bad.py", "ipm/fx.py")
        assert rules == ["dtype-explicit", "dtype-narrow"]
        assert sum(f.rule == "dtype-explicit" for f in findings) == 2
        assert sum(f.rule == "dtype-narrow" for f in findings) == 1

    def test_sparse_ops_module_sanctioned_for_narrowing(self):
        # The identical idioms under ops/pcg.py — a sanctioned
        # matrix-free module — with pinned constructors: silent.
        rules, _ = _rules_hit("fx_sparse_clean.py", "ops/pcg.py")
        assert rules == []

    def test_locks_catches_seeded(self):
        rules, findings = _rules_hit("fx_locks_bad.py", "serve/fx.py")
        assert rules == ["guarded-by"]
        assert len(findings) == 3  # unguarded read, write, wrong lock
        kinds = sorted(f.message.split(" ")[0] for f in findings)
        assert kinds == ["read", "read", "write"]

    def test_locks_clean_twin_silent(self):
        # direct lock, Condition alias, `# holds:`, __init__ exemption
        rules, _ = _rules_hit("fx_locks_clean.py", "serve/fx.py")
        assert rules == []

    def test_schema_catches_seeded(self):
        rules, findings = _rules_hit("fx_schema_bad.py", "serve/fx.py")
        assert rules == ["jsonl-fields", "jsonl-stamp"]
        assert sum(f.rule == "jsonl-fields" for f in findings) == 2

    def test_schema_clean_twin_silent(self):
        rules, _ = _rules_hit("fx_schema_clean.py", "serve/fx.py")
        assert rules == []

    def test_journal_schema_catches_seeded(self):
        # Crash-safe fabric additions: an uncatalogued replay tally, a
        # misspelled drain event type, an unstamped WAL append.
        rules, findings = _rules_hit("fx_journal_bad.py", "serve/fx.py")
        assert rules == ["jsonl-fields", "jsonl-stamp"]
        assert sum(f.rule == "jsonl-fields" for f in findings) == 2

    def test_scenario_catches_seeded(self):
        # Stochastic scenario tier: a per-call jit around the Schur
        # batch, unpinned pad-lane buffers, an uncatalogued record field.
        rules, findings = _rules_hit(
            "fx_scenario_bad.py", "backends/scenario_fx.py"
        )
        assert rules == ["dtype-explicit", "jit-nonhoisted", "jsonl-fields"]
        assert sum(f.rule == "dtype-explicit" for f in findings) == 2

    def test_scenario_clean_twin_silent(self):
        rules, _ = _rules_hit(
            "fx_scenario_clean.py", "backends/scenario_fx.py"
        )
        assert rules == []

    def test_distsparse_catches_seeded(self):
        # Row-sharded matrix-free tier: unpinned ELL row-block pad
        # buffers, an out-of-sanctuary f32 factor narrowing, and a
        # default-device rhs entering the mesh-programmed PCG.
        rules, findings = _rules_hit(
            "fx_distsparse_bad.py", "backends/fx.py"
        )
        assert rules == [
            "dtype-explicit",
            "dtype-narrow",
            "spmd-uncommitted-input",
        ]
        assert sum(f.rule == "dtype-explicit" for f in findings) == 2
        assert sum(f.rule == "dtype-narrow" for f in findings) == 1
        assert sum(f.rule == "spmd-uncommitted-input" for f in findings) == 1

    def test_distsparse_clean_twin_silent(self):
        # Pinned pad dtypes, f64 factors, put_global/shard_rows-committed
        # entries, mesh-None-guarded single-device fallback: silent.
        rules, _ = _rules_hit("fx_distsparse_clean.py", "backends/fx.py")
        assert rules == []

    def test_journal_schema_clean_twin_silent(self):
        # journal_replay / drain / registry_write with catalogued
        # fields + a stamped WAL write: silent.
        rules, _ = _rules_hit("fx_journal_clean.py", "serve/fx.py")
        assert rules == []

    def test_multihost_catches_seeded(self):
        # Multi-host runtime: an unlocked read of a guarded-by counter
        # and uncatalogued world_reinit / heartbeat record fields.
        rules, findings = _rules_hit(
            "fx_multihost_bad.py", "distributed/fx.py"
        )
        assert rules == ["guarded-by", "jsonl-fields"]
        assert sum(f.rule == "jsonl-fields" for f in findings) == 2

    def test_multihost_clean_twin_silent(self):
        rules, _ = _rules_hit(
            "fx_multihost_clean.py", "distributed/fx.py"
        )
        assert rules == []

    def test_elastic_catches_seeded(self):
        # Closed-loop elasticity: a scale action under an uncatalogued
        # event type and a breaker trip carrying an uncatalogued field.
        rules, findings = _rules_hit("fx_elastic_bad.py", "serve/fx.py")
        assert rules == ["jsonl-fields"]
        assert sum(f.rule == "jsonl-fields" for f in findings) == 2
        msgs = " | ".join(f.message for f in findings)
        assert "pool_resize" in msgs
        assert "trip_rate" in msgs

    def test_elastic_clean_twin_silent(self):
        # scale_out/scale_in/scale_veto, brownout_enter/exit, and
        # breaker_open/close with catalogued fields only: silent.
        rules, _ = _rules_hit("fx_elastic_clean.py", "serve/fx.py")
        assert rules == []

    def test_tail_catches_seeded(self):
        # Tail tolerance: a hedge resolution under an uncatalogued
        # event type and a cancellation carrying an uncatalogued field.
        rules, findings = _rules_hit("fx_tail_bad.py", "net/fx.py")
        assert rules == ["jsonl-fields"]
        assert sum(f.rule == "jsonl-fields" for f in findings) == 2
        msgs = " | ".join(f.message for f in findings)
        assert "speculative_retry" in msgs
        assert "verdict_state" in msgs

    def test_tail_clean_twin_silent(self):
        # hedge/route(hedge leg)/cancel/retry_budget/deadline_expired
        # with catalogued fields only: silent.
        rules, _ = _rules_hit("fx_tail_clean.py", "net/fx.py")
        assert rules == []

    def test_trace_catches_seeded(self):
        # Distributed tracing: a hedge record carrying the raw
        # traceparent under an uncatalogued key and a request record
        # with an uncatalogued span-linkage field.
        rules, findings = _rules_hit("fx_trace_bad.py", "net/fx.py")
        assert rules == ["jsonl-fields"]
        assert sum(f.rule == "jsonl-fields" for f in findings) == 2
        msgs = " | ".join(f.message for f in findings)
        assert "traceparent" in msgs
        assert "span_ref" in msgs

    def test_trace_clean_twin_silent(self):
        # hedge/request/batch/journal_replay records stamped with the
        # catalogued trace identity keys only: silent.
        rules, _ = _rules_hit("fx_trace_clean.py", "net/fx.py")
        assert rules == []

    def test_spmd_family_catches_seeded(self):
        # graftcheck v2: rank-gated collective, early rank exit, rank
        # fact through a call argument, rank-filtered comprehension,
        # unsorted listdir + set-order publication, uncommitted mesh
        # input.
        rules, findings = _rules_hit("fx_spmd_bad.py", "distributed/fx.py")
        assert rules == [
            "spmd-divergent-collective",
            "spmd-uncommitted-input",
            "spmd-unordered-dispatch",
        ]
        assert sum(f.rule == "spmd-divergent-collective" for f in findings) == 4
        assert sum(f.rule == "spmd-unordered-dispatch" for f in findings) == 2
        assert sum(f.rule == "spmd-uncommitted-input" for f in findings) == 1
        # the interprocedural variants are among them: the call-argument
        # taint, the early-return divergence, and the comprehension-
        # filter divergence the statement walk cannot see
        msgs = " | ".join(f.message for f in findings)
        assert "passed as `primary`" in msgs
        assert "early_exit_skips_collective" in msgs
        assert "comprehension filter" in msgs

    def test_spmd_clean_twin_silent(self):
        # world-size branches, sorted scans, committed placements, and
        # the mesh-None fallback all pass.
        rules, _ = _rules_hit("fx_spmd_clean.py", "distributed/fx.py")
        assert rules == []

    def test_deadlock_family_catches_seeded(self):
        rules, findings = _rules_hit("fx_deadlock_bad.py", "serve/fx.py")
        assert rules == ["blocking-under-lock", "lock-order"]
        cyc = next(f for f in findings if f.rule == "lock-order")
        # the cycle names both locks and both witness sites
        assert "Pipeline._a" in cyc.message and "Pipeline._b" in cyc.message
        blk = next(f for f in findings if f.rule == "blocking-under-lock")
        assert "urlopen" in blk.message

    def test_deadlock_clean_twin_silent(self):
        rules, _ = _rules_hit("fx_deadlock_clean.py", "serve/fx.py")
        assert rules == []


class TestSuppressions:
    SRC = "import jax.numpy as jnp\n\ndef f():\n    return jnp.zeros((2, 2))%s\n"

    def _check(self, src):
        return check_file("fx.py", source=src, pkg_path="ops/fx.py")

    def test_line_directive_suppresses(self):
        fs = self._check(self.SRC % "  # graftcheck: disable=dtype-explicit")
        assert [f.rule for f in fs] == ["dtype-explicit"]
        assert fs[0].suppressed  # still reported, marked suppressed

    def test_disable_all(self):
        fs = self._check(self.SRC % "  # graftcheck: disable=all")
        assert fs[0].suppressed

    def test_other_rule_does_not_suppress(self):
        fs = self._check(self.SRC % "  # graftcheck: disable=host-sync")
        assert not fs[0].suppressed

    def test_preceding_comment_line_suppresses(self):
        src = (
            "import jax.numpy as jnp\n\ndef f():\n"
            "    # graftcheck: disable=dtype-explicit (twin test)\n"
            "    return jnp.zeros((2, 2))\n"
        )
        fs = self._check(src)
        assert fs[0].suppressed

    def test_def_line_directive_covers_body(self):
        src = (
            "import jax.numpy as jnp\n\n"
            "def f():  # graftcheck: disable=dtype-explicit\n"
            "    a = jnp.zeros((2, 2))\n"
            "    return a, jnp.ones(3)\n"
        )
        fs = self._check(src)
        assert len(fs) == 2 and all(f.suppressed for f in fs)

    def test_file_wide_directive(self):
        src = "# graftcheck: disable-file=dtype-explicit\n" + self.SRC % ""
        fs = self._check(src)
        assert fs[0].suppressed

    def test_unsuppressed_without_directive(self):
        fs = self._check(self.SRC % "")
        assert [f.suppressed for f in fs] == [False]

    def test_unknown_rule_rejected(self):
        with pytest.raises(ValueError, match="unknown graftcheck rule"):
            self._check_rules = check_file(
                "fx.py", source="x = 1\n", rules=["no-such-rule"]
            )


class TestLockOrderRecorder:
    def test_consistent_order_passes(self):
        rec = LockOrderRecorder()
        a = rec.wrap(threading.Lock(), "a")
        b = rec.wrap(threading.Lock(), "b")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert ("a", "b") in rec.edges()
        rec.check()  # no cycle

    def test_inversion_detected(self):
        rec = LockOrderRecorder()
        a = rec.wrap(threading.Lock(), "a")
        b = rec.wrap(threading.Lock(), "b")
        with a:
            with b:
                pass
        with b:
            with a:  # opposite order: a->b and b->a both observed
                pass
        with pytest.raises(LockOrderViolation, match="a -> b -> a|b -> a -> b"):
            rec.check()

    def test_condition_compatible(self):
        rec = LockOrderRecorder()
        lk = rec.wrap(threading.Lock(), "svc")
        cond = threading.Condition(lk)
        hit = []

        def waiter():
            with cond:
                cond.wait_for(lambda: hit, timeout=5.0)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cond:
            hit.append(1)
            cond.notify_all()
        t.join(5.0)
        assert not t.is_alive()
        rec.check()


@pytest.mark.serve
def test_lock_order_live_service_drain(tmp_path):
    """Wrap the live locks of a real 3-thread SolveService, push traffic
    through scheduler -> pack -> solve, and assert the observed lock
    acquisition graph is acyclic (no lock-order inversion across
    _lock/_span_lock/tracer/logger/metrics locks). The tracer emits
    under the service lock on every submit, so the drain is guaranteed
    to record nested acquisitions."""
    from distributedlpsolver_tpu.models.generators import random_dense_lp
    from distributedlpsolver_tpu.obs.metrics import MetricsRegistry
    from distributedlpsolver_tpu.obs.trace import Tracer
    from distributedlpsolver_tpu.serve import ServiceConfig, SolveService

    rec = LockOrderRecorder()
    svc = SolveService(
        ServiceConfig(batch=4, flush_s=0.02),
        metrics=MetricsRegistry(),
        tracer=Tracer(str(tmp_path / "trace.json")),
        auto_start=False,
    )
    # _wake/_idle are Conditions over _lock; rebuild them over the
    # wrapped lock so every acquisition path records.
    svc._lock = rec.wrap(svc._lock, "service_lock")
    svc._wake = threading.Condition(svc._lock)
    svc._idle = threading.Condition(svc._lock)
    svc._span_lock = rec.wrap(svc._span_lock, "span_lock")
    svc._logger._lock = rec.wrap(svc._logger._lock, "logger_lock")
    svc.metrics._lock = rec.wrap(svc.metrics._lock, "metrics_lock")
    svc.tracer._lock = rec.wrap(svc.tracer._lock, "tracer_lock")
    svc.start()
    try:
        futs = [
            svc.submit(random_dense_lp(6, 10, seed=s), name=f"r{s}")
            for s in range(8)
        ]
        assert svc.drain(timeout=120.0)
        assert all(f.result(timeout=5.0) is not None for f in futs)
    finally:
        svc.shutdown()
    edges = rec.edges()
    assert ("service_lock", "tracer_lock") in edges, edges
    rec.check()


@pytest.mark.serve
def test_static_vs_dynamic_lock_order_cross_check(tmp_path):
    """graftcheck v2 cross-check: the STATIC lock-order graph (built
    from the package call graph, no execution) and the DYNAMIC edges a
    live 3-thread SolveService drain records must agree — the union of
    the two edge sets stays acyclic, and the service->tracer nesting
    the drain is guaranteed to observe is an edge the static analysis
    already knew about. A divergence in either direction means one of
    the two analyses has gone blind."""
    from distributedlpsolver_tpu.analysis import iter_py_files
    from distributedlpsolver_tpu.analysis.core import (
        FileContext,
        ProjectContext,
    )
    from distributedlpsolver_tpu.models.generators import random_dense_lp
    from distributedlpsolver_tpu.obs.metrics import MetricsRegistry
    from distributedlpsolver_tpu.obs.trace import Tracer
    from distributedlpsolver_tpu.serve import ServiceConfig, SolveService

    contexts = []
    for p in iter_py_files([_PKG]):
        with open(p) as fh:
            contexts.append(FileContext(p, fh.read()))
    static_edges = set(ProjectContext(contexts).locks.order_edges())

    # Dynamic half: wrap the live locks under their STATIC node names so
    # the two graphs share a vocabulary.
    rec = LockOrderRecorder()
    svc = SolveService(
        ServiceConfig(batch=4, flush_s=0.02),
        metrics=MetricsRegistry(),
        tracer=Tracer(str(tmp_path / "trace.json")),
        auto_start=False,
    )
    svc._lock = rec.wrap(svc._lock, "SolveService._lock")
    svc._wake = threading.Condition(svc._lock)
    svc._idle = threading.Condition(svc._lock)
    svc._span_lock = rec.wrap(svc._span_lock, "SolveService._span_lock")
    svc._logger._lock = rec.wrap(svc._logger._lock, "IterLogger._lock")
    svc.metrics._lock = rec.wrap(svc.metrics._lock, "MetricsRegistry._lock")
    svc.tracer._lock = rec.wrap(svc.tracer._lock, "Tracer._lock")
    svc.start()
    try:
        futs = [
            svc.submit(random_dense_lp(6, 10, seed=s), name=f"x{s}")
            for s in range(6)
        ]
        assert svc.drain(timeout=120.0)
        assert all(f.result(timeout=5.0) is not None for f in futs)
    finally:
        svc.shutdown()
    dynamic_edges = rec.edges()

    # The guaranteed runtime nesting is statically known.
    assert ("SolveService._lock", "Tracer._lock") in dynamic_edges
    assert ("SolveService._lock", "Tracer._lock") in static_edges

    # Union stays acyclic: neither analysis contradicts the other's
    # acquisition order.
    graph = {}
    for a, b in static_edges | dynamic_edges:
        graph.setdefault(a, set()).add(b)
    WHITE, GRAY, BLACK = 0, 1, 2
    color = {}

    def dfs(n):
        color[n] = GRAY
        for m in sorted(graph.get(n, ())):
            c = color.get(m, WHITE)
            if c == GRAY:
                return [n, m]
            if c == WHITE:
                found = dfs(m)
                if found:
                    return found
        color[n] = BLACK
        return []

    for n in sorted(graph):
        if color.get(n, WHITE) == WHITE:
            assert not dfs(n), (
                "static+dynamic lock graphs disagree (cycle)",
                static_edges,
                dynamic_edges,
            )


class TestEnvelopeGuard:
    def test_bench_refuses_cpu_before_measuring(self):
        # No accelerator is a non-zero exit, never a switch to the CPU.
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        from distributedlpsolver_tpu.utils.accel import REQUIRE_TPU_EXIT

        proc = subprocess.run(
            [sys.executable, "bench.py"], cwd=repo,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=240,
        )
        assert proc.returncode == REQUIRE_TPU_EXIT, proc.stderr[-2000:]
        assert proc.stdout.strip() == ""

    def test_bench_rejects_unknown_backend(self):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "bench.py", "--backend", "no-such-backend"],
            cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=240,
        )
        assert proc.returncode == 2 and "no-such-backend" in proc.stderr

    def test_require_tpu_fails_on_cpu(self):
        # conftest pins JAX_PLATFORMS=cpu, so the guard must abort with
        # the distinct envelope exit code.
        from distributedlpsolver_tpu.utils.accel import (
            REQUIRE_TPU_EXIT,
            require_tpu,
        )

        with pytest.raises(SystemExit) as exc:
            require_tpu()
        assert exc.value.code == REQUIRE_TPU_EXIT


class TestBaseline:
    """--baseline incremental mode: the cheap diff-gate."""

    BAD_ONE = (
        "import jax\n\ndef f(v):\n"
        "    return jax.jit(lambda x: x + 1)(v)\n"
    )
    BAD_TWO = BAD_ONE + (
        "\ndef g(v):\n    return jax.jit(lambda x: x * 2)(v)\n"
    )

    def test_known_findings_covered_new_ones_fail(self, tmp_path, capsys):
        from distributedlpsolver_tpu.cli import main

        bad = tmp_path / "fx.py"
        bad.write_text(self.BAD_ONE)
        base = tmp_path / "base.json"
        # Adopt: write the current findings as the baseline, exit 0.
        assert main(["check", str(bad), "--write-baseline", str(base)]) == 0
        doc = json.loads(base.read_text())
        assert doc["schema"] == 1 and len(doc["findings"]) == 1
        capsys.readouterr()
        # Ratchet: same tree passes against its baseline...
        assert main(["check", str(bad), "--baseline", str(base)]) == 0
        capsys.readouterr()
        # ...but a NEW finding (different function, distinct identity)
        # fails even though the old one is still present.
        bad.write_text(self.BAD_TWO)
        assert main(["check", str(bad), "--baseline", str(base)]) == 1
        capsys.readouterr()

    def test_baseline_keys_are_line_number_independent(self, tmp_path, capsys):
        from distributedlpsolver_tpu.cli import main

        bad = tmp_path / "fx.py"
        bad.write_text(self.BAD_ONE)
        base = tmp_path / "base.json"
        assert main(["check", str(bad), "--write-baseline", str(base)]) == 0
        capsys.readouterr()
        # Shifting the finding by three lines must not defeat coverage.
        bad.write_text("# pad\n# pad\n# pad\n" + self.BAD_ONE)
        assert main(["check", str(bad), "--baseline", str(base)]) == 0
        capsys.readouterr()

    def test_unreadable_baseline_exits_2(self, tmp_path, capsys):
        from distributedlpsolver_tpu.cli import main

        bad = tmp_path / "fx.py"
        bad.write_text("x = 1\n")
        rc = main(["check", str(bad), "--baseline", str(tmp_path / "nope.json")])
        capsys.readouterr()
        assert rc == 2


def test_analyzer_is_stdlib_only():
    """The stdlib-only contract, asserted structurally: no module under
    analysis/ imports anything outside the standard library and the
    package itself — the gate must run on CPU CI with no jax/numpy
    import (and does: this smoke is what keeps it true)."""
    import ast as ast_mod

    std = set(getattr(sys, "stdlib_module_names", ())) or {
        "ast", "os", "re", "json", "dataclasses", "typing", "threading",
        "time", "tokenize", "collections", "functools", "itertools",
    }
    adir = os.path.join(_PKG, "analysis")
    for fname in sorted(os.listdir(adir)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(adir, fname)) as fh:
            tree = ast_mod.parse(fh.read(), filename=fname)
        for node in ast_mod.walk(tree):
            mods = []
            if isinstance(node, ast_mod.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast_mod.ImportFrom) and node.module:
                mods = [node.module]
            for m in mods:
                top = m.split(".")[0]
                assert top == "distributedlpsolver_tpu" or top in std, (
                    f"analysis/{fname} imports non-stdlib module {m!r} — "
                    "the analyzer must stay stdlib-only"
                )


class TestGate:
    """The tier-1 CI gate itself."""

    def test_package_tree_is_clean(self):
        t0 = time.perf_counter()
        findings = check_paths([_PKG])
        elapsed = time.perf_counter() - t0
        bad = [f.render() for f in findings if not f.suppressed]
        assert bad == [], "unsuppressed graftcheck findings:\n" + "\n".join(bad)
        # Deliberate exceptions stay visible (and annotated) — the
        # sanctioned IPM watchdog sync and the serve demux floats.
        assert sum(1 for f in findings if f.suppressed) >= 2
        # Full-package budget including the interprocedural v2 families
        # (call graph + taint + lock model over ~100 files).
        assert elapsed < 45.0, f"graftcheck took {elapsed:.1f}s (budget 45s)"

    def test_gate_against_committed_empty_baseline(self, capsys):
        """The tier-1 gate's incremental form: the committed baseline is
        EMPTY, so the diff-gate degenerates to zero tolerated findings —
        but future consumers can adopt-then-ratchet a non-empty one."""
        from distributedlpsolver_tpu.cli import main

        base = os.path.join(
            os.path.dirname(_PKG), "BASELINE_GRAFTCHECK.json"
        )
        assert os.path.exists(base), "committed baseline missing"
        assert json.load(open(base))["findings"] == {}
        rc = main(["check", _PKG, "--baseline", base])
        capsys.readouterr()
        assert rc == 0

    def test_cli_check_json_gate(self, capsys):
        from distributedlpsolver_tpu.cli import main

        rc = main(["check", _PKG, "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["counts"]["findings"] == 0
        assert set(out["rules"]) == set(all_rules())
        # the v2 families are registered and documented
        for name in (
            "spmd-divergent-collective",
            "spmd-unordered-dispatch",
            "spmd-uncommitted-input",
            "lock-order",
            "blocking-under-lock",
        ):
            assert name in out["rules"]
        # suppressed inventory is machine-readable for audits
        assert all("rule" in f and "line" in f for f in out["suppressed"])
        # The gate's machine-readable artifact is persisted for CI
        # upload (DLPS_CHECK_ARTIFACT overrides the destination).
        artifact = os.environ.get("DLPS_CHECK_ARTIFACT") or os.path.join(
            tempfile.gettempdir(), "graftcheck_report.json"
        )
        with open(artifact, "w") as fh:
            json.dump(out, fh, indent=2)
        assert json.load(open(artifact))["counts"]["findings"] == 0

    def test_cli_check_nonzero_on_violation(self, tmp_path, capsys):
        # jit-nonhoisted is not directory-scoped, so a violation in any
        # path fails the gate with exit 1.
        bad = tmp_path / "fx.py"
        bad.write_text(
            "import jax\n\ndef f(v):\n"
            "    return jax.jit(lambda x: x + 1)(v)\n"
        )
        from distributedlpsolver_tpu.cli import main

        rc = main(["check", str(bad)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "jit-nonhoisted" in out

    def test_cli_check_unknown_rule_exit_2(self, capsys):
        from distributedlpsolver_tpu.cli import main

        rc = main(["check", _PKG, "--rules", "no-such-rule"])
        capsys.readouterr()
        assert rc == 2

    def test_cli_list_rules(self, capsys):
        from distributedlpsolver_tpu.cli import main

        assert main(["check", "--list-rules"]) == 0
        out = capsys.readouterr().out
        for name in all_rules():
            assert name in out
