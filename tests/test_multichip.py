"""Multi-chip serving: replica-per-chip and sharded-batch dispatch (ISSUE 7).

Runs on the suite's 8 fake XLA host devices (conftest forces
``--xla_force_host_platform_device_count=8``), so every contract here is
proven without TPU hardware:

- the ``[parallel]`` plan selects devices, overrides per-model modes, and
  sizes the sharded data axis;
- EVERY replica receives batches under sustained load (least-loaded pick +
  least-loaded fallback — the fixed index-order scan starved high-index
  replicas);
- sharded-batch results are bit-identical to replica-mode results;
- publish/rollback under load is version-atomic across replicas: no
  response ever reflects a mix, and no replica lags on the old tree;
- the staged canary proves the candidate on every replica;
- per-chip attribution (replica_batches_total / replica_inflight /
  per_replica occupancy) is live in /stats and /metrics.
"""

import asyncio
import io

import jax
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from tpuserve.batcher import ModelBatcher
from tpuserve.config import ModelConfig, ParallelConfig, ServerConfig
from tpuserve.models import build
from tpuserve.obs import Metrics
from tpuserve.parallel.mesh import select_devices
from tpuserve.runtime import build_runtime
from tpuserve.server import ServerState, make_app

N_DEV = len(jax.devices())


def toy_cfg(**kw) -> ModelConfig:
    base = dict(name="toy", family="toy", batch_buckets=[1, 2],
                deadline_ms=2.0, dtype="float32", num_classes=10,
                parallelism="replica", request_timeout_ms=30_000.0,
                max_queue=4096)
    base.update(kw)
    return ModelConfig(**base)


def npy_bytes(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, arr)
    return buf.getvalue()


# -- [parallel] plan ---------------------------------------------------------

def test_parallel_config_validation():
    assert ParallelConfig().mode == ""
    with pytest.raises(ValueError, match="parallel.mode"):
        ParallelConfig(mode="pipeline")
    with pytest.raises(ValueError, match="parallel.mode"):
        ParallelConfig(mode="bogus")
    with pytest.raises(ValueError, match="n_chips"):
        ParallelConfig(n_chips=-1)


def test_select_devices():
    assert len(select_devices(0)) == N_DEV
    assert len(select_devices(4)) == 4
    # The first n in stable order, so replica indices map to the same
    # physical chips across restarts.
    assert select_devices(4) == jax.devices()[:4]
    with pytest.raises(ValueError, match="n_chips"):
        select_devices(N_DEV + 1)


def test_n_chips_bounds_replica_and_sharded_meshes():
    rt4 = build_runtime(build(toy_cfg(name="toy4", batch_buckets=[1])),
                        parallel=ParallelConfig(n_chips=4))
    assert rt4.n_replicas == 4 and rt4.n_chips == 4

    # `data` alone sizes a sharded mesh to exactly data*tp*sp chips.
    rts = build_runtime(
        build(toy_cfg(name="toys", parallelism="sharded", batch_buckets=[4])),
        parallel=ParallelConfig(data=4))
    assert rts.n_replicas == 1 and rts.n_chips == 4
    assert rts.meshes[0].shape["data"] == 4
    assert rts.parallel_signature == "sharded@d4"


def test_server_parallel_mode_overrides_models():
    cfg = ServerConfig(
        models=[toy_cfg(parallelism="single", batch_buckets=[1])],
        parallel=ParallelConfig(mode="replica"),
        decode_threads=2, startup_canary=False)
    state = ServerState(cfg)
    state.build()
    rt = state.runtimes["toy"]
    assert rt.mode == "replica"
    assert rt.n_replicas == N_DEV
    assert cfg.models[0].parallelism == "replica"  # config-level override


# -- least-loaded replica pick ------------------------------------------------

def test_pick_replica_least_loaded_and_tie_rotation():
    rt = build_runtime(build(toy_cfg(batch_buckets=[1])))
    assert rt.n_replicas == N_DEV
    # Least-loaded wins outright.
    loads = [3] * N_DEV
    loads[5] = 0
    assert rt.pick_replica(loads) == 5
    # Ties rotate via the round-robin cursor: equal loads must not pin to
    # one replica.
    picks = {rt.pick_replica([0] * N_DEV) for _ in range(N_DEV)}
    assert len(picks) > 1
    # No loads = plain round-robin (prewarm/canary path).
    assert 0 <= rt.pick_replica() < N_DEV


class _FakeStagedRuntime:
    """n-replica runtime stub for batcher staging tests: pick_replica is
    pinned so the test controls the first choice."""

    def __init__(self, n: int, first: int) -> None:
        self.n_replicas = n
        self._first = first
        self.h2d_sync = False

    def pick_replica(self, loads=None) -> int:
        return self._first

    def replica_batches(self):
        return [0.0] * self.n_replicas


def test_acquire_staging_falls_back_least_loaded_not_index_order():
    """When the first-choice pool is exhausted, the fallback must take the
    LEAST-LOADED remaining pool — the old fixed (first+k)%n scan handed the
    batch to the next index, starving high-index replicas under bursts."""
    model = build(toy_cfg(batch_buckets=[1]))
    rt = _FakeStagedRuntime(3, first=0)

    async def go():
        b = ModelBatcher(model, rt, Metrics())
        await b.start()
        try:
            assert len(b._staging) == 3
            # Exhaust pool 0 (the pinned first choice); load pool 1 with
            # one batch; leave pool 2 empty.
            while b._staging[0].try_acquire() is not None:
                pass
            b._staging[1].try_acquire()
            replica, slot = await b._acquire_staging([])
            assert replica == 2, (
                f"fallback took replica {replica}; index-order scan would "
                "take 1, least-loaded must take 2")
            b._release_staging(replica, slot)
        finally:
            await b.stop()

    asyncio.run(go())


# -- every replica serves under load ------------------------------------------

def test_every_replica_receives_batches_under_sustained_load():
    model = build(toy_cfg(batch_buckets=[1]))
    metrics = Metrics()
    rt = build_runtime(build(toy_cfg(batch_buckets=[1])), metrics=metrics)
    assert rt.n_replicas == N_DEV

    async def go():
        b = ModelBatcher(model, rt, metrics)
        await b.start()
        # Replica-aware admission: before a measurement the gate counts
        # every chip's device section (depth x replicas); after, it goes
        # by the device time queued on the chip that runs dry first, with
        # at most assemble_ahead batches past the device sections.
        assert b._close_wait_s(b.depth * N_DEV - 1, False) == 0
        assert b._close_wait_s(b.depth * N_DEV, False) == float("inf")
        try:
            rng = np.random.default_rng(0)
            items = [rng.integers(0, 255, (8, 8, 3), np.uint8)
                     for _ in range(12 * N_DEV)]
            results = await asyncio.gather(*[b.submit(it) for it in items])
            assert len(results) == 12 * N_DEV
            assert all(r["top_k"] for r in results)
            cap = b.depth * N_DEV + b.pipeline_cfg.assemble_ahead
            assert b._close_wait_s(cap, True) == float("inf")
            assert b._inflight_peak <= cap
            assert b.pipeline_stats()["admission"] == cap
        finally:
            await b.stop()

    asyncio.run(go())
    batches = rt.replica_batches()
    assert len(batches) == N_DEV
    assert all(v > 0 for v in batches), (
        f"starved replica(s): {batches} — the batcher must keep every "
        "chip's staging slots fed")
    # Occupancy gauges exist per replica and ended drained.
    for i in range(N_DEV):
        assert metrics.gauge(
            f"replica_inflight{{model=toy,replica={i}}}").value == 0


# -- sharded vs replica parity ------------------------------------------------

# Two partitionings of one model are two XLA programs, and XLA promises no
# bit identity between them: float32 reductions may associate differently,
# which is worth a few ulp (f32 eps 1.2e-7; the observed difference is 1 ulp).
# 1e-5 relative is ~80 ulp of slack and still ~400x below bfloat16's 2^-8
# step, so a layout that silently computed in lower precision fails it.
# chip_smoke.py's four-chip phase applies the same rule (PROB_RTOL).
PARTITION_PROB_RTOL = 1e-5


def test_sharded_batch_results_match_replica_mode():
    bucket = (N_DEV,)
    rng = np.random.default_rng(7)
    items = [rng.integers(0, 255, (8, 8, 3), np.uint8) for _ in range(N_DEV)]

    rt_rep = build_runtime(
        build(toy_cfg(name="t-rep", batch_buckets=[N_DEV])))
    rt_sh = build_runtime(
        build(toy_cfg(name="t-sh", parallelism="sharded",
                      batch_buckets=[N_DEV])))
    assert rt_sh.meshes[0].shape["data"] == N_DEV
    model = build(toy_cfg(batch_buckets=[N_DEV]))
    batch = model.assemble(items, bucket)
    out_sh = rt_sh.fetch(rt_sh.run(bucket, batch))
    for replica in range(rt_rep.n_replicas):
        out_rep = rt_rep.fetch(rt_rep.run(bucket, batch, replica=replica))
        np.testing.assert_allclose(out_sh["probs"], out_rep["probs"],
                                   rtol=PARTITION_PROB_RTOL, atol=0)
        np.testing.assert_array_equal(out_sh["indices"], out_rep["indices"])


def test_variant_key_parallelism_composes_with_quantize():
    """The parallelism dimension of the VariantKey carries the device
    layout (ISSUE 7) and composes with dtype/quantize — and version churn
    across a replica set recompiles NOTHING (the zero-recompile proof
    obligation extends to multi-chip)."""
    metrics = Metrics()
    rt = build_runtime(
        build(toy_cfg(batch_buckets=[1], quantize="int8",
                      quantize_min_size=16)),
        metrics=metrics)
    assert rt.parallel_signature == f"replica@{N_DEV}"
    key = rt.variant_key((1,))
    assert key.parallelism == f"replica@{N_DEV}"
    assert key.label == f"1/float32/int8/replica@{N_DEV}"
    before = rt.compiles_total
    assert before == len(rt.model.buckets()) * N_DEV
    staged = rt.stage_params()
    rt.publish(staged)
    rt.rollback()
    assert rt.ensure_compiled() == 0
    assert rt.compiles_total == before


# -- lifecycle atomicity across replicas --------------------------------------

def _scaled(trees, factor):
    return [jax.tree_util.tree_map(lambda x: x * factor, t) for t in trees]


def test_publish_rollback_under_load_never_serves_torn_versions():
    """Sustained single-item load over all replicas while a publish and a
    rollback land mid-flight: every response must equal EXACTLY the v1 or
    the v2 reference (never a mix, never a third value), and after each
    transition the steady state must be the new version on every replica."""
    model = build(toy_cfg(batch_buckets=[1]))
    rt = build_runtime(build(toy_cfg(batch_buckets=[1])))
    assert rt.n_replicas == N_DEV
    item = np.random.default_rng(3).integers(0, 255, (8, 8, 3), np.uint8)

    def probs(r):
        return np.array([e["prob"] for e in r["top_k"]], np.float64)

    def version_of(r, ref_v1, ref_v2):
        """1 or 2 when the response matches exactly one version reference
        (tight tolerance — replica executables are compiled per device);
        fails the test for a torn/mixed/third answer."""
        m1 = np.allclose(probs(r), probs(ref_v1), rtol=1e-6, atol=1e-9)
        m2 = np.allclose(probs(r), probs(ref_v2), rtol=1e-6, atol=1e-9)
        assert m1 != m2, (
            f"response matches {'both versions' if m1 else 'neither version'}"
            f" — torn or mixed weights served: {r}")
        return 1 if m1 else 2

    async def go():
        b = ModelBatcher(model, rt, Metrics())
        await b.start()
        try:
            ref_v1 = await b.submit(item.copy())
            staged = _scaled(rt.params_per_mesh, 1.5)

            async def burst(n):
                return await asyncio.gather(
                    *[b.submit(item.copy()) for _ in range(n)])

            # Publish races a burst across every replica.
            burst_task = asyncio.ensure_future(burst(6 * N_DEV))
            await asyncio.sleep(0.01)
            rt.publish(staged)
            mixed = await burst_task
            ref_v2 = await b.submit(item.copy())
            # The two versions are far apart relative to the match
            # tolerance: scaling by 1.5 moves the softmax visibly.
            assert not np.allclose(probs(ref_v1), probs(ref_v2), rtol=1e-3)
            for r in mixed:
                version_of(r, ref_v1, ref_v2)
            # Steady state post-publish: EVERY replica answers v2.
            for _ in range(2 * N_DEV):
                r = await b.submit(item.copy())
                assert version_of(r, ref_v1, ref_v2) == 2
            assert all(v > 0 for v in rt.replica_batches())

            # Rollback races a burst the same way.
            burst_task = asyncio.ensure_future(burst(6 * N_DEV))
            await asyncio.sleep(0.01)
            rt.rollback()
            mixed = await burst_task
            for r in mixed:
                version_of(r, ref_v1, ref_v2)
            for _ in range(2 * N_DEV):
                r = await b.submit(item.copy())
                assert version_of(r, ref_v1, ref_v2) == 1
        finally:
            await b.stop()

    asyncio.run(go())


def test_staged_canary_proves_every_replica():
    """A candidate copy corrupted on ONE replica must fail the staged
    canary gate — serving an eighth of the traffic from a poisoned tree is
    exactly the torn state the lifecycle exists to prevent."""
    from tpuserve.config import LifecycleConfig
    from tpuserve.lifecycle import ModelLifecycle

    model = build(toy_cfg(batch_buckets=[1]))
    rt = build_runtime(model)
    assert rt.n_replicas == N_DEV
    lc = ModelLifecycle("toy", rt, model, LifecycleConfig(), Metrics())
    poisoned = rt.n_replicas - 1  # high replica: replica-0-only canaries miss it
    staged = _scaled(rt.params_per_mesh, 1.0)
    staged[poisoned] = jax.tree_util.tree_map(
        lambda x: x * np.nan, staged[poisoned])
    with pytest.raises(ValueError, match=f"replica {poisoned}"):
        lc._staged_canary_sync(staged)
    # A clean candidate passes on all replicas.
    lc._staged_canary_sync(_scaled(rt.params_per_mesh, 1.5))


# -- observability over HTTP ---------------------------------------------------

def test_stats_parallel_block_and_per_replica_over_http():
    cfg = ServerConfig(
        models=[toy_cfg(batch_buckets=[1])],
        decode_threads=2, startup_canary=False)
    state = ServerState(cfg)
    state.build()
    app = make_app(state)
    loop = asyncio.new_event_loop()
    try:
        async def go():
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                rng = np.random.default_rng(5)
                for _ in range(4 * N_DEV):
                    r = await client.post(
                        "/v1/models/toy:classify",
                        data=npy_bytes(
                            rng.integers(0, 255, (8, 8, 3), np.uint8)),
                        headers={"Content-Type": "application/x-npy"})
                    assert r.status == 200
                stats = await (await client.get("/stats")).json()
                metrics_text = await (await client.get("/metrics")).text()
                models = await (await client.get("/v1/models")).json()
                return stats, metrics_text, models
            finally:
                await client.close()

        stats, metrics_text, models = loop.run_until_complete(go())
    finally:
        loop.close()

    par = stats["parallel"]["toy"]
    assert par["mode"] == "replica"
    assert par["signature"] == f"replica@{N_DEV}"
    assert par["n_chips"] == N_DEV and par["replicas"] == N_DEV
    assert len(par["replica_batches_total"]) == N_DEV
    assert sum(par["replica_batches_total"]) > 0
    assert par["batches_per_chip"] == pytest.approx(
        sum(par["replica_batches_total"]) / N_DEV)

    per_rep = stats["pipeline"]["models"]["toy"]["per_replica"]
    assert [row["replica"] for row in per_rep] == list(range(N_DEV))
    for row in per_rep:
        assert 0.0 <= row["occupancy"] <= 1.0
        assert row["batches_total"] is not None

    assert 'replica_batches_total{model="toy",replica="0"}' in metrics_text
    assert 'replica_inflight{model="toy",replica="0"}' in metrics_text
    assert models["toy"]["n_chips"] == N_DEV
    assert models["toy"]["parallel"] == f"replica@{N_DEV}"


# -- bench helpers -------------------------------------------------------------

def test_build_roofline_aggregate_chip_ceiling():
    from tpuserve.bench import roofline as rl

    latency = {
        "latency_ms{model=m,phase=compute}": {"n": 10, "p50_ms": 100.0},
    }
    block = rl.build_roofline(
        latency, "m", buckets=[8], raw_ms_by_bucket={8: 10.0},
        link_mbps=10.0, img_bytes=1000, chip_img_s=1000.0,
        value_img_s=4000.0, n_chips=8)
    assert block["chip_ceiling_img_s"] == 1000.0
    assert block["aggregate_chip_ceiling_img_s"] == 8000.0
    assert block["n_chips"] == 8
    # 4000 of 8x1000: half the MESH's ceiling, not 400% of one chip's.
    assert block["pct_of_chip_ceiling"] == pytest.approx(50.0)
    # Single-chip default unchanged (back-compat with every prior BENCH_r).
    single = rl.build_roofline(
        latency, "m", buckets=[8], raw_ms_by_bucket={8: 10.0},
        link_mbps=10.0, img_bytes=1000, chip_img_s=1000.0,
        value_img_s=500.0)
    assert single["pct_of_chip_ceiling"] == pytest.approx(50.0)
    assert single["n_chips"] == 1


# -- the driver's entries (__graft_entry__) -----------------------------------

def test_graft_entry_traces_to_top_five_of_eight():
    """`entry()` hands the driver a forward that traces: compiled and run by
    the slow tests of test_train.py only."""
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.eval_shape(fn, *args)
    assert out["indices"].shape == (8, 5)


def test_graft_dryrun_replica_serving_on_eight_devices():
    import __graft_entry__ as g

    g._dryrun_replica_serving(8)
