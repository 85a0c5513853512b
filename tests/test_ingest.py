"""Parallel ingest (ISSUE 11): [server] ingest_loops SO_REUSEPORT accept
loops, the loop-safe batcher entry, per-loop balance metrics, the
native-decode fallback counter, and the multi-process loadgen merge."""

import asyncio
import json
import socket
import threading
import urllib.request

import numpy as np
import pytest

from tpuserve import frame, preproc
from tpuserve.batcher import QueueFull
from tpuserve.bench.loadgen import (merge_load_summaries, synthetic_frame,
                                    synthetic_frame_pool)
from tpuserve.config import CacheConfig, ModelConfig, ServerConfig, load_config
from tpuserve.server import ServerState, serve_async

EDGE = 8
N_LOOPS = 3


# -- config -------------------------------------------------------------------

def test_ingest_loops_validation():
    with pytest.raises(ValueError, match="ingest_loops"):
        ServerConfig(ingest_loops=0)


def test_ingest_loops_toml(tmp_path):
    p = tmp_path / "cfg.toml"
    p.write_text('ingest_loops = 3\n[[model]]\nname = "toy"\nfamily = "toy"\n')
    cfg = load_config(str(p))
    assert cfg.ingest_loops == 3
    cfg2 = load_config(str(p), overrides=["ingest_loops=2"])
    assert cfg2.ingest_loops == 2


# -- real multi-loop server ---------------------------------------------------

@pytest.fixture(scope="module")
def multi_loop_server():
    """A REAL serve_async server with 3 accept loops (1 main + 2 ingest
    threads) on an ephemeral SO_REUSEPORT port, driven from this thread
    over plain blocking HTTP (every request a fresh connection, so the
    kernel spreads them across listeners)."""
    if not hasattr(socket, "SO_REUSEPORT"):
        pytest.skip("SO_REUSEPORT unavailable")
    cfg = ServerConfig(
        host="127.0.0.1", port=0, ingest_loops=N_LOOPS,
        startup_canary=False, decode_threads=2,
        cache=CacheConfig(enabled=True, capacity=64),
        models=[ModelConfig(name="toy", family="toy", batch_buckets=[1, 2, 4],
                            deadline_ms=2.0, dtype="float32", num_classes=10,
                            parallelism="single",
                            request_timeout_ms=10_000.0)],
    )
    state = ServerState(cfg)
    state.build()
    holder = {}
    ready = threading.Event()

    def run_server():
        async def main():
            a_ready = asyncio.Event()
            a_stop = asyncio.Event()
            holder["loop"] = asyncio.get_running_loop()
            holder["stop"] = a_stop
            task = asyncio.ensure_future(serve_async(state, a_ready, a_stop))
            await a_ready.wait()
            ready.set()
            await task

        asyncio.run(main())

    t = threading.Thread(target=run_server, daemon=True)
    t.start()
    assert ready.wait(60), "server did not come up"
    port = state.serving_addresses[0][1]
    yield state, f"http://127.0.0.1:{port}"
    holder["loop"].call_soon_threadsafe(holder["stop"].set)
    t.join(30)
    assert not t.is_alive()


def post(base, path, body, ctype):
    req = urllib.request.Request(
        f"{base}{path}", data=body,
        headers={"Content-Type": ctype, "Connection": "close"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def get(base, path):
    req = urllib.request.Request(f"{base}{path}",
                                 headers={"Connection": "close"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, r.read()


def test_every_ingest_loop_serves(multi_loop_server):
    """Fresh-connection requests spread across ALL accept loops; per-loop
    prebound counters prove the balance, and every response is correct no
    matter which loop carried it (the main-loop hop)."""
    state, base = multi_loop_server
    rng = np.random.default_rng(0)
    n = 90
    bodies = [frame.encode_frame(
        [rng.integers(0, 255, (EDGE, EDGE, 3), dtype=np.uint8)
         for _ in range(2)], frame.KIND_RGB8, EDGE) for _ in range(n)]
    oks = 0
    for body in bodies:
        status, raw = post(base, "/v1/models/toy:classify", body,
                           frame.CONTENT_TYPE)
        assert status == 200, raw
        out = json.loads(raw)
        assert len(out["results"]) == 2
        oks += 1
    assert oks == n
    per_loop = [state.ingest[i].requests.value for i in range(N_LOOPS)]
    assert len(state.ingest) == N_LOOPS
    assert sum(per_loop) == n, per_loop
    # 90 fresh connections over 3 SO_REUSEPORT listeners: a silent loop
    # means the spread (or a listener) is broken.
    assert all(v > 0 for v in per_loop), per_loop
    per_loop_bytes = [state.ingest[i].bytes.value for i in range(N_LOOPS)]
    assert sum(per_loop_bytes) == sum(len(b) for b in bodies)


def test_cache_and_stats_work_from_ingest_loops(multi_loop_server):
    """The single-flight cache lives on the main loop; identical framed
    uploads from whatever loop answer identically (the second from cache),
    and /stats (a main-loop-hopped handler) reports the ingest block."""
    state, base = multi_loop_server
    body = synthetic_frame(EDGE, 2, "rgb8", seed=12345)
    hits0 = state.metrics.counter("cache_hits_total{model=toy}").value
    answers = {post(base, "/v1/models/toy:classify", body,
                    frame.CONTENT_TYPE)[1] for _ in range(6)}
    assert len(answers) == 1  # byte-identical regardless of serving loop
    hits1 = state.metrics.counter("cache_hits_total{model=toy}").value
    assert hits1 - hits0 >= 4  # first fills (maybe once per race), rest hit
    status, raw = get(base, "/stats")
    assert status == 200
    stats = json.loads(raw)
    assert set(stats["ingest"]["loops"]) == {str(i) for i in range(N_LOOPS)}
    assert "frame_errors_total" in stats["ingest"]
    assert "native_decode_fallback_total" in stats["ingest"]


def test_malformed_frame_400_from_any_loop(multi_loop_server):
    state, base = multi_loop_server
    for _ in range(6):  # enough fresh connections to land off-main too
        status, raw = post(base, "/v1/models/toy:classify", b"garbage",
                           frame.CONTENT_TYPE)
        assert status == 400, raw
        assert json.loads(raw)["error"].startswith("frame:")


# -- loop-safe batcher entry --------------------------------------------------

def test_submit_threadsafe_from_worker_thread():
    """ModelBatcher.submit_threadsafe: a thread that is NOT the batcher's
    event loop submits and receives the result through a concurrent
    future; QueueFull propagates the same way."""
    from tpuserve.models import build as build_model
    from tpuserve.obs import Metrics
    from tpuserve.runtime import build_runtime
    from tpuserve.batcher import ModelBatcher

    cfg = ModelConfig(name="toy", family="toy", batch_buckets=[1, 2],
                      deadline_ms=2.0, dtype="float32", num_classes=10,
                      parallelism="single", max_queue=4)
    model = build_model(cfg)
    rt = build_runtime(model)
    b = ModelBatcher(model, rt, Metrics())
    item = np.zeros((EDGE, EDGE, 3), dtype=np.uint8)

    async def go():
        await b.start()
        loop = asyncio.get_running_loop()

        def from_thread():
            fut = b.submit_threadsafe(item)
            return fut.result(timeout=10)

        res = await loop.run_in_executor(None, from_thread)
        assert "top_k" in res

        # QueueFull crosses the loop boundary through the future.
        def flood():
            futs = [b.submit_threadsafe(item) for _ in range(64)]
            outcomes = []
            for f in futs:
                try:
                    outcomes.append(f.result(timeout=10))
                except QueueFull:
                    outcomes.append("shed")
            return outcomes

        outcomes = await loop.run_in_executor(None, flood)
        assert any(o == "shed" for o in outcomes)
        assert any(isinstance(o, dict) for o in outcomes)
        await b.stop()

    asyncio.new_event_loop().run_until_complete(go())


def test_submit_threadsafe_before_start_raises():
    from tpuserve.models import build as build_model
    from tpuserve.obs import Metrics
    from tpuserve.runtime import build_runtime
    from tpuserve.batcher import ModelBatcher

    cfg = ModelConfig(name="toy", family="toy", dtype="float32",
                      num_classes=10, parallelism="single")
    b = ModelBatcher(build_model(cfg), build_runtime(build_model(cfg)),
                     Metrics())
    with pytest.raises(RuntimeError, match="not started"):
        b.submit_threadsafe(np.zeros((EDGE, EDGE, 3), dtype=np.uint8))


# -- native-decode fallback observability -------------------------------------

def test_native_fallback_hook_counts(monkeypatch):
    """decode_image_yuv420 reports every PIL fallback on a native-eligible
    request through the installed hook (the server routes it to
    native_decode_fallback_total{model=})."""
    from tpuserve import native
    from tpuserve.bench.loadgen import synthetic_image_jpeg

    seen = []
    preproc.set_native_fallback_hook(seen.append)
    try:
        monkeypatch.setattr(native, "decode_yuv420",
                            lambda payload, edge: None)
        jpeg = synthetic_image_jpeg(16)
        y, u, v = preproc.decode_image_yuv420(jpeg, "image/jpeg", 16,
                                              model="m1")
        assert y.shape == (16, 16)
        assert seen == ["m1"]  # fallback on a native-eligible request
        # npy bodies never try the native path: no fallback counted.
        arr = np.zeros((16, 16, 3), dtype=np.uint8)
        import io
        buf = io.BytesIO()
        np.save(buf, arr)
        preproc.decode_image_yuv420(buf.getvalue(), "application/x-npy", 16,
                                    model="m1")
        assert seen == ["m1"]
    finally:
        preproc.set_native_fallback_hook(None)


# -- loadgen: frame pools + multi-process merge -------------------------------

def test_synthetic_frame_pool_distinct_and_parseable():
    pool = synthetic_frame_pool(4, edge=16, n_items=3, kind="yuv420")
    assert len(set(pool)) == 4  # distinct bodies
    for body in pool:
        items = frame.parse_frame(body, kind=frame.KIND_YUV420, edge=16,
                                  max_items=8)
        assert len(items) == 3
    # Disjoint seed ranges never collide with the base pool.
    other = synthetic_frame_pool(4, edge=16, n_items=3, kind="yuv420",
                                 seed_base=4)
    assert not set(pool) & set(other)


def test_merge_load_summaries_exact_percentiles():
    parts = [
        {"summary": {"mode": "closed", "n_ok": 3, "n_err": 1, "n_late": 0,
                     "duration_s": 10.0, "throughput_per_s": 30.0,
                     "p50_ms": 1.0, "p90_ms": 1.0, "p99_ms": 1.0,
                     "items_per_request": 8},
         "latencies_ms": [1.0, 2.0, 3.0]},
        {"summary": {"mode": "closed", "n_ok": 3, "n_err": 0, "n_late": 2,
                     "duration_s": 10.0, "throughput_per_s": 40.0,
                     "p50_ms": 100.0, "p90_ms": 100.0, "p99_ms": 100.0},
         "latencies_ms": [100.0, 200.0, 300.0]},
    ]
    out = merge_load_summaries(parts)
    assert out["n_ok"] == 6 and out["n_err"] == 1 and out["n_late"] == 2
    assert out["throughput_per_s"] == 70.0
    assert out["load_workers"] == 2
    assert out["items_per_request"] == 8
    # Exact percentile over the CONCATENATED samples, not an average of
    # the workers' percentiles (which would report ~50 here).
    assert out["p50_ms"] == 3.0
    assert out["p99_ms"] == 300.0


def test_merge_load_summaries_empty():
    with pytest.raises(ValueError):
        merge_load_summaries([])


# -- ingest-aware roofline ----------------------------------------------------

def test_roofline_ingest_phases_and_body_read_ceiling():
    """body_read/parse join the per-phase attribution; body_read is priced
    at the ACTUAL framed request-body bytes against the measured link."""
    from tpuserve.bench import roofline as rl

    latency = {
        "latency_ms{model=m,phase=body_read}": {"n": 10, "p50_ms": 4.0},
        "latency_ms{model=m,phase=parse}": {"n": 10, "p50_ms": 0.05},
        "latency_ms{model=m,phase=compute}": {"n": 10, "p50_ms": 100.0},
    }
    req_bytes = frame.frame_nbytes(frame.KIND_YUV420, 160, 8)
    block = rl.build_roofline(
        latency, "m", buckets=[8], raw_ms_by_bucket={8: 10.0},
        link_mbps=100.0, img_bytes=38400, chip_img_s=None,
        value_img_s=None, req_bytes=req_bytes)
    br = block["phases"]["body_read"]
    assert br["p50_ms"] == 4.0
    assert br["ceiling_kind"] == "wire"
    assert br["ceiling_ms"] == pytest.approx(req_bytes / 100e6 * 1e3,
                                             rel=1e-3)
    assert block["phases"]["parse"]["p50_ms"] == 0.05
    assert block["ingest_req_bytes"] == req_bytes
    # compute still binds here (100 ms >> everything else).
    assert block["binding_phase"] == "compute"
    # Without req_bytes the block is unchanged (back-compat, /stats path).
    naked = rl.build_roofline(
        latency, "m", buckets=[8], raw_ms_by_bucket={8: 10.0},
        link_mbps=100.0, img_bytes=38400, chip_img_s=None, value_img_s=None)
    assert "ingest_req_bytes" not in naked
    assert "ceiling_ms" not in naked["phases"]["body_read"]
