"""Compiled-variant registry, roofline attribution, and quantized serving
parity over the real HTTP path (ISSUE 6).

Covers the compute fast path's contracts:
- the registry enumerates every specialized variant (bucket x dtype x
  quantize x parallelism) and ``runtime_compiles_total`` counts exactly the
  compiles that happened — repeat buckets, prewarm, probes, and lifecycle
  churn all leave it flat (steady state recompiles NOTHING);
- ``device_preprocess`` is a real seam: forward == net(device_preprocess),
  and the wire signature stays raw uint8;
- the raw-executable probe yields per-bucket device-time ceilings and the
  /stats roofline block splits the serving compute phase against them;
- the int8 weight-only variant serves over the real HTTP path within
  tolerance of the fp path, with zero recompiles across the load;
- the bench-side variance windowing helpers (best consecutive window,
  spread, CV) pick settled windows, not lucky passes.
"""

import asyncio
import io

import jax
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from tpuserve.bench import roofline as rl
from tpuserve.config import ModelConfig, PipelineConfig, ServerConfig
from tpuserve.models import build
from tpuserve.obs import Metrics
from tpuserve.runtime import VariantKey, build_runtime
from tpuserve.server import ServerState, make_app


def _toy_cfg(**kw) -> ModelConfig:
    base = dict(name="toy", family="toy", batch_buckets=[1, 2, 4],
                deadline_ms=5.0, dtype="float32", num_classes=10,
                parallelism="single", request_timeout_ms=10_000.0)
    base.update(kw)
    return ModelConfig(**base)


def npy_bytes(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, arr)
    return buf.getvalue()


# -- registry ----------------------------------------------------------------

def test_registry_enumerates_variants_and_counts_compiles():
    metrics = Metrics()
    model = build(_toy_cfg())
    rt = build_runtime(model, metrics=metrics)
    # One variant per bucket, keyed by the full specialization.
    assert set(rt.variants) == {
        VariantKey(bucket=(b,), dtype="float32", quantize=None,
                   parallelism="single") for b in (1, 2, 4)}
    assert rt.compiles_total == 3  # 3 buckets x 1 replica
    assert metrics.counter(
        "runtime_compiles_total{model=toy}").value == 3
    assert metrics.gauge("runtime_variants{model=toy}").value == 3
    summaries = rt.variants_summary()
    assert [s["bucket"] for s in summaries] == [[1], [2], [4]]
    assert all(s["quantize"] is None and s["dtype"] == "float32"
               and s["replicas"] == 1 for s in summaries)
    assert all(s["compile_ms"] > 0 for s in summaries)
    # describe() exposes the enumeration (TF-Serving P2: variants are
    # cheaply-listable artifacts).
    d = rt.describe()
    assert len(d["variants"]) == 3 and d["compiles_total"] == 3


def test_repeat_buckets_and_reload_churn_recompile_nothing():
    metrics = Metrics()
    model = build(_toy_cfg())
    rt = build_runtime(model, metrics=metrics)
    rt.prewarm()
    before = rt.compiles_total
    img = np.random.default_rng(0).integers(0, 255, (8, 8, 3), np.uint8)
    for bucket in rt.executables:
        batch = model.assemble([img] * bucket[0], bucket)
        for _ in range(3):
            rt.fetch(rt.run(bucket, batch))
    # Version churn swaps trees under unchanged shapes: same variants.
    staged = rt.stage_params()
    rt.publish(staged)
    rt.rollback()
    assert rt.ensure_compiled() == 0
    assert rt.compiles_total == before
    # Per-variant serving counters are live (the smoke's "specialized
    # variant actually served" signal).
    assert metrics.counter(
        "runtime_variant_batches_total{model=toy,variant=1/float32/fp/single}"
    ).value > 0


def test_ensure_compiled_restores_missing_variant():
    model = build(_toy_cfg())
    rt = build_runtime(model)
    before = rt.compiles_total
    key = rt.variant_key((2,))
    del rt.variants[key]
    del rt.executables[(2,)]
    assert rt.ensure_compiled() == 1
    assert rt.compiles_total == before + 1
    img = np.zeros((8, 8, 3), np.uint8)
    out = rt.fetch(rt.run((2,), model.assemble([img, img], (2,))))
    assert np.isfinite(out["probs"]).all()


def test_lifecycle_stage_compiles_missing_variant_before_canary():
    """The reload pipeline's variant-completeness gate: a bucket whose
    executable went missing is recompiled at STAGE time, so the staged
    canary (and the first post-publish request) never pays first-compile."""
    from tpuserve.lifecycle import ModelLifecycle
    from tpuserve.config import LifecycleConfig

    metrics = Metrics()
    model = build(_toy_cfg())
    rt = build_runtime(model, metrics=metrics)
    lc = ModelLifecycle("toy", rt, model, LifecycleConfig(), metrics)
    del rt.variants[rt.variant_key((4,))]
    del rt.executables[(4,)]
    info = asyncio.run(lc.reload())
    assert info["version"] == 2
    assert (4,) in rt.executables  # back before the canary ran


# -- fused-preproc seam ------------------------------------------------------

def test_forward_routes_through_device_preprocess_seam():
    """forward(params, wire) == net(device_preprocess(wire)), and the wire
    signature stays raw uint8 — the fused-preproc contract."""
    model = build(_toy_cfg())
    params = model.init_params(jax.random.key(0))
    batch = np.random.default_rng(1).integers(
        0, 255, (2, 8, 8, 3), np.uint8)
    sig = model.input_signature((2,))
    assert sig.dtype == np.uint8  # raw bytes cross the wire
    x = np.asarray(model.device_preprocess(jax.numpy.asarray(batch)))
    assert x.dtype == np.float32 and x.max() <= 1.0  # cast happened on device
    out = model.forward(params, jax.numpy.asarray(batch))
    # Recompute the net over the seam's output by hand.
    h = np.tanh(x @ np.asarray(params["w1"]) + np.asarray(params["b1"]))
    logits = h @ np.asarray(params["w2"]) + np.asarray(params["b2"])
    e = np.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = e / e.sum(axis=-1, keepdims=True)
    top3 = np.sort(probs, axis=-1)[:, ::-1][:, :3]
    np.testing.assert_allclose(np.asarray(out["probs"]), top3, atol=1e-5)


def test_vision_prepare_batch_is_device_preprocess():
    from tpuserve.models.resnet import ResNet50Serving

    m = ResNet50Serving(ModelConfig(
        name="r", family="resnet50", dtype="float32", image_size=16,
        wire_size=16, num_classes=10))
    batch = jax.numpy.asarray(np.random.default_rng(2).integers(
        0, 255, (1, 16, 16, 3), np.uint8))
    np.testing.assert_array_equal(np.asarray(m.prepare_batch(batch)),
                                  np.asarray(m.device_preprocess(batch)))


# -- roofline probes + /stats ------------------------------------------------

def test_probe_raw_ms_and_h2d_sync():
    model = build(_toy_cfg())
    rt = build_runtime(model)
    before = rt.compiles_total
    ms = rt.probe_raw_ms((2,), iters=4)
    assert ms is not None and ms > 0
    assert rt.raw_ms_per_batch[(2,)] == pytest.approx(ms, abs=1e-3)
    all_ms = rt.probe_all_raw(iters=2)
    assert set(all_ms) == {(1,), (2,), (4,)}
    assert rt.compiles_total == before  # probing compiles nothing
    # h2d transfer-completion gate: same values either way; the flag only
    # moves where the wall time is attributed.
    img = np.zeros((8, 8, 3), np.uint8)
    batch = model.assemble([img, img], (2,))
    rt.h2d_sync = True
    dev_sync = rt.h2d((2,), batch)
    rt.h2d_sync = False
    dev_async = rt.h2d((2,), batch)
    np.testing.assert_array_equal(np.asarray(dev_sync), np.asarray(dev_async))


def test_batcher_start_propagates_h2d_sync(toy_cfg):

    from tpuserve.batcher import ModelBatcher

    model = build(toy_cfg)
    rt = build_runtime(model)

    async def go(sync: bool) -> bool:
        b = ModelBatcher(model, rt, Metrics(),
                         pipeline_cfg=PipelineConfig(h2d_sync=sync))
        await b.start()
        try:
            return rt.h2d_sync
        finally:
            await b.stop()

    assert asyncio.run(go(True)) is True
    assert asyncio.run(go(False)) is False


def test_stats_roofline_block_over_http():
    cfg = ServerConfig(
        models=[_toy_cfg()], decode_threads=2, startup_canary=False,
        roofline_probe_iters=2,
    )
    state = ServerState(cfg)
    state.build()
    app = make_app(state)
    loop = asyncio.new_event_loop()
    try:
        async def go():
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                img = np.random.default_rng(3).integers(
                    0, 255, (8, 8, 3), np.uint8)
                r = await client.post(
                    "/v1/models/toy:classify", data=npy_bytes(img),
                    headers={"Content-Type": "application/x-npy"})
                assert r.status == 200
                r = await client.get("/stats")
                return await r.json()
            finally:
                await client.close()

        stats = loop.run_until_complete(go())
    finally:
        loop.close()
    roof = stats["roofline"]["toy"]
    assert len(roof["variants"]) == 3
    assert roof["compiles_total"] == 3
    # Startup probe armed: every bucket has a raw device-time ceiling.
    assert set(roof["raw_ms_per_batch"]) == {"[1]", "[2]", "[4]"}
    assert all(v and v > 0 for v in roof["raw_ms_per_batch"].values())
    split = roof["compute_split"]
    assert split["observed_p50_ms"] > 0 and split["device_ms"] > 0
    assert split["host_wait_ms"] >= 0
    assert 0 < split["pct_of_ceiling"] <= 100


# -- int8 over the real HTTP path -------------------------------------------

def test_int8_http_parity_with_fp_and_zero_recompiles():
    """The quantized variant on the measured serving path: identical
    requests through two real HTTP servers (fp vs int8 weight-only) agree
    within quantization tolerance, and the int8 server's compile counter
    stays flat across the whole load (repeat buckets, zero recompiles)."""

    def build_state(quantize):
        cfg = ServerConfig(
            models=[_toy_cfg(quantize=quantize, quantize_min_size=1024,
                             batch_buckets=[1, 2])],
            decode_threads=2, startup_canary=False,
        )
        state = ServerState(cfg)
        state.build()
        return state

    imgs = [np.random.default_rng(s).integers(0, 255, (8, 8, 3), np.uint8)
            for s in range(6)]
    loop = asyncio.new_event_loop()
    try:
        async def serve_and_query(state):
            client = TestClient(TestServer(make_app(state)))
            await client.start_server()
            try:
                out = []
                for img in imgs:
                    r = await client.post(
                        "/v1/models/toy:classify", data=npy_bytes(img),
                        headers={"Content-Type": "application/x-npy"})
                    assert r.status == 200
                    out.append(await r.json())
                # A client batch exercises the second bucket too.
                r = await client.post(
                    "/v1/models/toy:classify",
                    data=npy_bytes(np.stack(imgs[:2])),
                    headers={"Content-Type": "application/x-npy"})
                assert r.status == 200
                return out
            finally:
                await client.close()

        state_fp = build_state(None)
        out_fp = loop.run_until_complete(serve_and_query(state_fp))

        state_q = build_state("int8")
        rt_q = state_q.runtimes["toy"]
        # Something really is int8 on device.
        leaves = jax.tree_util.tree_leaves(rt_q.params_per_mesh[0])
        assert any(x.dtype == np.int8 for x in leaves)
        assert rt_q.variants_summary()[0]["quantize"] == "int8"
        compiles_after_startup = rt_q.compiles_total
        out_q = loop.run_until_complete(serve_and_query(state_q))
        assert rt_q.compiles_total == compiles_after_startup
    finally:
        loop.close()

    for a, b in zip(out_fp, out_q):
        assert a["top_k"][0]["class"] == b["top_k"][0]["class"]  # top-1
        pa = np.array([e["prob"] for e in a["top_k"]])
        pb = np.array([e["prob"] for e in b["top_k"]])
        np.testing.assert_allclose(pa, pb, atol=5e-3)


# -- bench variance + roofline helpers ---------------------------------------

def test_best_window_prefers_consecutive_settled_passes():
    vals = [480.0, 658.6, 606.0, 610.0, 600.0]
    start, win = rl.best_window(vals, k=3)
    assert start == 2 and win == [606.0, 610.0, 600.0]
    assert rl.spread_pct(win) < 2.0
    # Bimodal runs cannot fake convergence by cherry-picking.
    bimodal = [400.0, 800.0, 410.0, 790.0, 395.0]
    _, w = rl.best_window(bimodal, k=3)
    assert rl.spread_pct(w) > 15.0
    assert rl.best_window([], k=3) == (0, [])
    assert rl.best_window([100.0], k=3) == (0, [100.0])


def test_spread_and_cv():
    assert rl.spread_pct([100.0, 90.0, 95.0]) == pytest.approx(10.0)
    assert rl.spread_pct([]) == 0.0
    assert rl.cv_pct([5.0, 5.0, 5.0]) == 0.0
    assert rl.cv_pct([90.0, 110.0]) == pytest.approx(10.0)


def test_build_roofline_block_shape():
    latency = {
        "latency_ms{model=m,phase=compute}": {"n": 10, "p50_ms": 465.6},
        "latency_ms{model=m,phase=h2d}": {"n": 10, "p50_ms": 15.5},
        "latency_ms{model=m,phase=preproc}": {"n": 10, "p50_ms": 5.7},
    }
    block = rl.build_roofline(
        latency, "m", buckets=[64, 128],
        raw_ms_by_bucket={64: 12.0, 128: 24.1},
        link_mbps=14.3, img_bytes=38400, chip_img_s=10628.5,
        value_img_s=606.0)
    assert set(block["per_bucket"]) == {"64", "128"}
    b128 = block["per_bucket"]["128"]
    assert b128["raw_ms_per_batch"] == 24.1
    assert b128["raw_img_s"] == pytest.approx(128 / 24.1 * 1e3, rel=1e-3)
    assert b128["wire_ms_per_batch"] == pytest.approx(
        128 * 38400 / 14.3e6 * 1e3, rel=1e-3)
    comp = block["phases"]["compute"]
    assert comp["ceiling_ms"] == 24.1 and comp["ceiling_kind"] == "device"
    assert comp["pct_of_ceiling"] == pytest.approx(100 * 24.1 / 465.6, abs=0.1)
    split = block["compute_split"]
    assert split["device_ms"] == 24.1
    assert split["host_wait_ms"] == pytest.approx(441.5, abs=0.1)
    assert block["binding_phase"] == "compute"
    assert block["pct_of_chip_ceiling"] == pytest.approx(5.7, abs=0.1)
    # Postproc never observed: reported as null, no ceiling invented.
    assert block["phases"]["postproc"]["p50_ms"] is None
