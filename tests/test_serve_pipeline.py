"""Data-parallel, pipelined serve dispatch tests: mesh-sharded bucket
solves (equivalence + divisibility + zero-warm-recompile per mesh),
pipeline ordering (batch k results never wait on batch k+1's pack),
ladder autotuning (split/merge/cap + the online drain→swap→warm epoch),
and elastic mesh shrink mid-service — all on the 8-virtual-CPU-device
rig."""

import json
import time

import jax
import numpy as np
import pytest

from distributedlpsolver_tpu.backends.batched import (
    bucket_cache_size,
    place_bucket,
    solve_bucket,
)
from distributedlpsolver_tpu.ipm import Status, solve
from distributedlpsolver_tpu.models.generators import (
    random_batched_lp,
    random_request_stream,
)
from distributedlpsolver_tpu.parallel import make_mesh
from distributedlpsolver_tpu.serve import (
    AutotuneConfig,
    BucketSpec,
    BucketTable,
    ServiceConfig,
    SolveService,
    autotune_ladder,
    ladder_from_json,
    ladder_to_json,
)
from distributedlpsolver_tpu.serve.autotune import load_request_shapes

pytestmark = pytest.mark.serve


def _batch_mesh(k: int):
    return make_mesh((k,), axis_names=("batch",), devices=jax.devices()[:k])


class TestMeshBucketDispatch:
    def test_sharded_matches_unsharded_to_1e8(self):
        """ISSUE acceptance: sharded bucket results match unsharded to
        1e-8 on the tier-1 CPU mesh (they are the same compiled math —
        placement only — so the agreement is near-bitwise)."""
        batch = random_batched_lp(8, 10, 30, seed=11)
        active = np.array([True] * 6 + [False] * 2)
        r0 = solve_bucket(batch, active)
        r1 = solve_bucket(batch, active, mesh=_batch_mesh(4))
        for k in range(6):
            assert r1.status[k] == r0.status[k] == Status.OPTIMAL
        np.testing.assert_allclose(r1.x[:6], r0.x[:6], atol=1e-8, rtol=1e-8)
        np.testing.assert_allclose(
            r1.objective[:6], r0.objective[:6], atol=1e-8, rtol=1e-8
        )

    def test_batch_not_divisible_by_mesh_raises(self):
        with pytest.raises(ValueError, match="divisible"):
            solve_bucket(
                random_batched_lp(6, 8, 24, seed=1),
                np.ones(6, bool),
                mesh=_batch_mesh(4),
            )

    def test_preplaced_bucket_reuses_program(self):
        """place_bucket (the pack stage) + solve_bucket must land on the
        same compiled program as the direct call — the pipeline cannot
        fork the cache."""
        mesh = _batch_mesh(2)
        batch = random_batched_lp(8, 8, 24, seed=2)
        active = np.ones(8, bool)
        solve_bucket(batch, active, mesh=mesh)  # compile
        size0 = bucket_cache_size()
        placed, act = place_bucket(batch, active, mesh=mesh)
        r = solve_bucket(placed, act, mesh=mesh)
        assert bucket_cache_size() == size0
        assert r.n_optimal == 8

    def test_bucket_table_enforces_device_divisibility(self):
        # auto batch rounds up to a devices multiple
        t = BucketTable(batch=6, devices=4)
        assert t.batch == 8
        assert t.spec_for(8, 24).batch == 8
        # explicit non-divisible buckets are a loud config error
        with pytest.raises(ValueError, match="divisible"):
            BucketTable([BucketSpec(8, 32, 6)], devices=4)


class TestPipeline:
    def test_batch_k_results_never_wait_on_pack_k1(self):
        """ISSUE acceptance: with the two-deep pipeline, batch k's
        futures resolve while batch k+1 is still packing — a slow pack
        must never serialize behind-the-device work."""
        shape = ((8, 24),)  # one shape → one bucket → deterministic batches
        svc = SolveService(ServiceConfig(batch=4, flush_s=0.01))
        try:
            # Warm the bucket so solve time is not compile-dominated.
            warm = [
                svc.submit(p)
                for p in random_request_stream(4, shapes=shape, seed=1)
            ]
            assert svc.drain(timeout=300)
            for f in warm:
                assert f.result(timeout=30).status is Status.OPTIMAL

            orig = svc._pack_bucket
            packs = []

            def slow_pack(key, live):
                if packs:  # pack of every batch after the first is slow
                    time.sleep(1.0)
                out = orig(key, live)
                packs.append(time.perf_counter())
                return out

            svc._pack_bucket = slow_pack
            futs = [
                svc.submit(p)
                for p in random_request_stream(8, shapes=shape, seed=2)
            ]
            assert svc.drain(timeout=300)
            rs = [f.result(timeout=30) for f in futs]
            assert all(r.status is Status.OPTIMAL for r in rs)
            assert len(packs) >= 2
            batch1 = [r for r in rs if r.dispatch_index == rs[0].dispatch_index]
            assert len(batch1) == 4
            # batch 1 completed before batch 2's (artificially slow) pack
            # finished — its results never waited on the next pack.
            assert max(r.t_done for r in batch1) < packs[1]
        finally:
            svc.shutdown()

    def test_dispatch_report_records_stage_split(self):
        svc = SolveService(ServiceConfig(batch=4, flush_s=0.01))
        try:
            futs = [svc.submit(p) for p in random_request_stream(8, seed=3)]
            assert svc.drain(timeout=300)
            rs = [f.result(timeout=30) for f in futs]
            report = svc.dispatch_report()
            assert report, "bucket dispatches must produce timing rows"
            for row in report:
                for field in (
                    "pack_ms", "compile_ms", "solve_ms", "overlap_ms",
                    "mesh_devices",
                ):
                    assert field in row
                assert row["pack_ms"] > 0 and row["solve_ms"] > 0
            # the same split is stamped on every bucketed request record
            assert all(r.pack_ms > 0 for r in rs if r.bucket)
            stats = svc.stats()
            assert stats["pack_ms_total"] > 0
            assert "idle" in stats and stats["idle"]["waits"] >= 0
        finally:
            svc.shutdown()

    def test_drain_is_event_driven(self):
        """drain() must return promptly once the last result lands (no
        fixed poll tick) and report False on timeout while work remains."""
        svc = SolveService(ServiceConfig(batch=4, flush_s=0.01))
        try:
            fut = svc.submit(next(random_request_stream(1, seed=9)))
            # immediately-expiring drain on a busy service: False, fast
            t0 = time.perf_counter()
            assert svc.drain(timeout=0.001) in (False, True)
            assert svc.drain(timeout=300)
            assert fut.result(timeout=30).status is Status.OPTIMAL
        finally:
            svc.shutdown()


class TestAutotune:
    def test_split_hot_merge_cold_cap_programs(self):
        # 90% of traffic at (10, 48): its pow2 bucket (16, 64) wastes
        # >50% of every A-cell — the autotuner must give it a tighter
        # bucket; the 2% tail shape merges away; the cap holds.
        shapes = [(10, 48)] * 90 + [(30, 100)] * 8 + [(5, 9)] * 2
        current = [BucketSpec(16, 64, 8), BucketSpec(32, 128, 8)]
        specs, report = autotune_ladder(
            shapes,
            current=current,
            config=AutotuneConfig(max_programs=2, devices=2, batch=8),
        )
        assert 1 <= len(specs) <= 2
        table = BucketTable(specs, devices=2)
        for m, n in {(10, 48), (30, 100), (5, 9)}:
            s = table.spec_for(m, n)  # every observed shape still fits
            assert s.batch % 2 == 0
        hot = table.spec_for(10, 48)
        assert hot.m * hot.n < 16 * 64  # strictly tighter than the pow2 bucket
        assert report["mean_shape_waste_after"] < report["mean_shape_waste_before"]
        assert report["split_buckets"], "the wasteful hot bucket is reported"

    def test_empty_telemetry_keeps_ladder(self):
        current = [BucketSpec(16, 64, 8)]
        specs, report = autotune_ladder([], current=current)
        assert specs == current
        assert report["requests"] == 0

    def test_ladder_json_roundtrip(self):
        specs = [BucketSpec(16, 56, 8), BucketSpec(32, 104, 8)]
        assert ladder_from_json(ladder_to_json(specs)) == specs
        # bare-triple form parses too
        assert ladder_from_json("[[16, 56, 8]]") == [BucketSpec(16, 56, 8)]

    def test_load_request_shapes_skips_solo_and_junk(self, tmp_path):
        p = tmp_path / "t.jsonl"
        p.write_text(
            json.dumps({"event": "request", "bucket": [16, 32, 8],
                        "m": 9, "n": 25}) + "\n"
            + json.dumps({"event": "request", "bucket": None,
                          "m": 6, "n": 10}) + "\n"  # solo path: skipped
            + json.dumps({"event": "batch"}) + "\n"
            + "not json\n"
        )
        assert load_request_shapes(str(p)) == [(9, 25)]


class TestServiceIntegration:
    def test_mesh_dispatch_autotune_swap_zero_warm_recompiles_200(
        self, tmp_path
    ):
        """ISSUE acceptance: bucket_cache_size() stays flat across a warm
        200-request run under BOTH mesh-sharded dispatch and a
        post-autotune ladder, and the service answers match reference
        single-solves at 1e-8."""
        log = tmp_path / "svc.jsonl"
        cfg = ServiceConfig(
            batch=8, flush_s=0.02, mesh_devices=2, log_jsonl=str(log)
        )
        with SolveService(cfg) as svc:
            assert svc.mesh_devices == 2
            # Cold wave: builds the telemetry the autotuner folds back in.
            cold = [svc.submit(p) for p in random_request_stream(48, seed=31)]
            assert svc.drain(timeout=600)
            for f in cold:
                assert f.result(timeout=30).status is Status.OPTIMAL

            specs, report = autotune_ladder(
                load_request_shapes(str(log)),
                current=list(svc.scheduler.table.specs()),
                config=AutotuneConfig(devices=2, batch=8),
            )
            assert (
                report["mean_shape_waste_after"]
                <= report["mean_shape_waste_before"]
            )
            # Online swap at the epoch boundary: drain → swap → warm.
            warmed = svc.apply_ladder(specs)
            assert warmed == len(specs)

            # Warm 200-request run on the new ladder over the mesh:
            # zero recompiles, all optimal, no compile_ms on any record.
            cache0 = bucket_cache_size()
            problems = list(random_request_stream(200, seed=32))
            futs = [svc.submit(p) for p in problems]
            assert svc.drain(timeout=600)
            rs = [f.result(timeout=30) for f in futs]
            assert bucket_cache_size() == cache0
            assert all(r.status is Status.OPTIMAL for r in rs)
            assert all(r.compile_ms == 0.0 for r in rs)
            # the refined ladder actually serves (bucketed, not solo)
            assert all(r.bucket is not None for r in rs)

            # sharded-dispatch answers agree with solo reference solves
            for p, r in list(zip(problems, rs))[:8]:
                ref = solve(p, backend="tpu")
                assert ref.status == Status.OPTIMAL
                assert abs(r.objective - ref.objective) <= 1e-8 * (
                    1.0 + abs(ref.objective)
                )

            events = [
                json.loads(l) for l in log.read_text().splitlines()
            ]
            assert any(e["event"] == "ladder_swap" for e in events)
            assert any(e["event"] == "warmup" for e in events)

    def test_reshard_mid_service_keeps_serving(self):
        """Elastic recovery under the service: losing a mesh device
        re-forms the batch mesh over survivors (clamped so bucket batches
        stay divisible) and dispatch continues; the re-formed mesh pays
        one compile per bucket (per-(bucket, mesh) invariant), then stays
        warm."""
        cfg = ServiceConfig(batch=8, flush_s=0.02, mesh_devices=4)
        with SolveService(cfg) as svc:
            futs = [svc.submit(p) for p in random_request_stream(16, seed=41)]
            assert svc.drain(timeout=600)
            for f in futs:
                assert f.result(timeout=30).status is Status.OPTIMAL
            # lose one of the 4 devices: 3 survivors, clamped to 2 so the
            # batch-of-8 buckets stay shardable
            assert svc.reshard(exclude=[jax.devices()[3]]) == 2
            assert svc.mesh_devices == 2
            futs = [svc.submit(p) for p in random_request_stream(16, seed=42)]
            assert svc.drain(timeout=600)
            rs = [f.result(timeout=30) for f in futs]
            assert all(r.status is Status.OPTIMAL for r in rs)
            # warm again on the new mesh: no further compiles
            cache0 = bucket_cache_size()
            futs = [svc.submit(p) for p in random_request_stream(8, seed=43)]
            assert svc.drain(timeout=600)
            assert all(
                f.result(timeout=30).status is Status.OPTIMAL for f in futs
            )
            assert bucket_cache_size() == cache0


def test_cli_logs_cache_hit_miss_line(tmp_path, capsys):
    """The startup cache line reads the directory JAX was given (here set
    through JAX's own config, as JAX_COMPILATION_CACHE_DIR would)."""
    import jax

    from distributedlpsolver_tpu.cli import main

    req = tmp_path / "req.jsonl"
    req.write_text(json.dumps({"m": 8, "n": 24, "seed": 0, "id": "q0"}) + "\n")
    out = tmp_path / "res.jsonl"
    cache = tmp_path / "xla-cache"
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(cache))
    try:
        rc = main(
            [
                "serve", "--requests", str(req), "--out", str(out),
                "--batch", "4", "--flush-ms", "5",
            ]
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert rc == 0
    err = capsys.readouterr().err
    assert f"jax compilation cache: {cache}" in err and "cold start" in err


def test_cli_autotune_roundtrip(tmp_path):
    """cli autotune consumes a telemetry stream and writes a ladder file
    cli serve --buckets accepts."""
    from distributedlpsolver_tpu.cli import main

    telem = tmp_path / "telemetry.jsonl"
    telem.write_text(
        "".join(
            json.dumps(
                {"event": "request", "bucket": [16, 64, 8], "m": 10, "n": 48}
            )
            + "\n"
            for _ in range(20)
        )
    )
    ladder = tmp_path / "ladder.json"
    rc = main(
        [
            "autotune", "--telemetry", str(telem), "--out", str(ladder),
            "--batch", "8", "--devices", "2",
        ]
    )
    assert rc == 0
    specs = ladder_from_json(ladder.read_text())
    assert specs and all(s.batch % 2 == 0 for s in specs)
    t = BucketTable(specs, devices=2)
    assert t.spec_for(10, 48).m * t.spec_for(10, 48).n < 16 * 64
