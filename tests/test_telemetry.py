"""Fleet telemetry plane (ISSUE 14; docs/OBSERVABILITY.md "The telemetry
plane").

Layers, smallest first:

- store units: bounded rings, reset-safe counter rates (a worker respawn
  must never read as a negative rate), histogram window-delta quantiles;
- SLO units: objective interpolation, the two-window ok/pending/firing
  machine over synthetic history;
- fleet-merge units: counters summed, gauges proc-labeled, histograms
  merged bucket-wise EXACTLY, stale sources marked and never fatal;
- config: [telemetry] / [model.slo] TOML + validation + dot overrides;
- HTTP e2e on a real toy server: /metrics content negotiation + # EOF
  (ISSUE 14 satellite), /stats/history, /alerts alert lifecycle,
  /debug/profile, the /stats telemetry/utilization blocks, and the
  sampler thread's clean shutdown on drain.
"""

import asyncio
import io
import json
import threading
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from tpuserve.config import (ModelConfig, ServerConfig, SloConfig,
                             TelemetryConfig, load_config)
from tpuserve.obs import Metrics
from tpuserve.server import ServerState, make_app
from tpuserve.telemetry import merge_expositions, parse_exposition
from tpuserve.telemetry.fleet import sum_counter
from tpuserve.telemetry.slo import SloEngine, UtilizationDeriver, good_fraction
from tpuserve.telemetry.store import (MetricSampler, TimeSeriesStore,
                                      quantile_from_counts)

# ---------------------------------------------------------------------------
# Time-series store
# ---------------------------------------------------------------------------


def test_rings_are_bounded():
    m = Metrics(16)
    c = m.counter("x_total")
    store = TimeSeriesStore(m, capacity=8)
    for i in range(50):
        c.inc()
        store.sample(now=1000.0 + i)
    h = store.history("x_total")
    assert len(h["t"]) == 8  # deque maxlen: newest kept
    assert h["v"][-1] == 50.0 and h["v"][0] == 43.0


def test_counter_rate_handles_resets_without_negative_rates():
    """A respawned process's counter restarts at 0 — the increase across
    the reset is the NEW value, and no derived rate is ever negative."""
    m = Metrics(16)
    c = m.counter("req_total")
    store = TimeSeriesStore(m, capacity=32)
    values = [10.0, 20.0, 30.0, 3.0, 6.0]  # reset between 30 -> 3
    for i, v in enumerate(values):
        c.value = v
        store.sample(now=100.0 + i)
    h = store.history("req_total")
    assert all(r >= 0 for r in h["rate_per_s"])
    # 10 + 10 + (reset: 3) + 3 of genuine increase
    assert h["increase"] == pytest.approx(10 + 10 + 3 + 3)
    assert store.counter_increase("req_total") == pytest.approx(26.0)


def test_counter_window_selects_left_edge_sample():
    m = Metrics(16)
    c = m.counter("w_total")
    store = TimeSeriesStore(m, capacity=32)
    t0 = time.time()
    for i in range(10):
        c.value = float(i)
        store.sample(now=t0 - 9 + i)  # one sample per second, ending now
    inc = store.counter_increase("w_total", window_s=3.0)
    # window covers the last ~3 s of samples plus the left-edge sample
    assert 3.0 <= inc <= 4.0


def test_histogram_window_delta_and_quantiles():
    m = Metrics(16)
    h = m.histogram("lat_ms{model=t,phase=total}")
    store = TimeSeriesStore(m, capacity=32)
    store.sample(now=time.time() - 1.0)
    for _ in range(100):
        h.observe(5.0)
    for _ in range(10):
        h.observe(500.0)
    store.sample(now=time.time())
    out = store.history("lat_ms{model=t,phase=total}")
    assert out["kind"] == "histogram"
    d = out["delta"]
    assert d["n"] == 110
    assert d["p50_ms"] < 10.0
    assert d["p99_ms"] > 100.0
    # the delta ignores anything observed before the first sample
    reset = store.histogram_delta("lat_ms{model=t,phase=total}")
    assert reset["n"] == 110


def test_histogram_delta_survives_reset():
    m = Metrics(16)
    h = m.histogram("r_ms{model=t}")
    store = TimeSeriesStore(m, capacity=32)
    for _ in range(5):
        h.observe(1.0)
    store.sample(now=200.0)
    # simulate a respawned process: fresh histogram under the same name
    with m._lock:
        m._histograms.clear()
    h2 = m.histogram("r_ms{model=t}")
    h2.observe(2.0)
    store.sample(now=201.0)
    d = store.histogram_delta("r_ms{model=t}")
    assert d["n"] == 1  # the reset contributes its new counts, not -4
    assert all(c >= 0 for c in d["counts"])


def test_quantile_from_counts_empty_and_overflow():
    assert quantile_from_counts([1.0, 2.0], [0, 0, 0], 0.5) is None
    assert quantile_from_counts([1.0, 2.0], [0, 0, 5], 0.99) == float("inf")


def test_match_by_base_name():
    m = Metrics(16)
    m.counter("req_total{model=a}")
    m.counter("req_total{model=b}")
    m.counter("other_total")
    store = TimeSeriesStore(m, capacity=4)
    store.sample()
    assert sorted(store.match("req_total")) == [
        "req_total{model=a}", "req_total{model=b}"]
    assert store.match("req_total{model=a}") == ["req_total{model=a}"]
    assert store.match("nope") == []


def test_sampler_thread_stops_cleanly():
    """The sampler correctness satellite's shutdown half: stop() joins the
    thread promptly and is idempotent."""
    m = Metrics(16)
    m.counter("x_total")
    store = TimeSeriesStore(m, capacity=8)
    s = MetricSampler(store, 0.02)
    s.start()
    deadline = time.time() + 5.0
    while store.samples_total < 3 and time.time() < deadline:
        time.sleep(0.01)
    assert store.samples_total >= 3
    s.stop()
    assert not s.is_alive()
    s.stop()  # idempotent
    # no stray telemetry thread left behind
    assert all("tpuserve-telemetry" != t.name
               for t in threading.enumerate())


class _LateEvent(threading.Event):
    """An event whose timed wait comes back ``late_s`` after it was due, as a
    thread's does when the machine or the process is held."""

    def __init__(self, late_s: float) -> None:
        super().__init__()
        self.late_s = late_s

    def wait(self, timeout=None):
        woke = super().wait(timeout)
        if not woke:
            time.sleep(self.late_s)
        return woke


def test_sampler_counts_how_late_it_woke():
    """``host_stall_seconds_total`` (ISSUE 66): what a wait of ``interval_s``
    took beyond ``interval_s``, summed. A thread whose wake-up is held back 50
    ms a tick counts 50 ms a tick; one that wakes on time counts the
    scheduler's own lateness, a small part of that."""
    def run(late_s: float, ticks: int = 4):
        m = Metrics(16)
        store = TimeSeriesStore(m, capacity=8)
        s = MetricSampler(store, 0.02)
        s._stop_ev = _LateEvent(late_s)
        s.start()
        deadline = time.time() + 10.0
        while store.samples_total < ticks and time.time() < deadline:
            time.sleep(0.005)
        s.stop()
        assert not s.is_alive() and store.samples_total >= ticks
        return m.counter("host_stall_seconds_total").value / store.samples_total

    held, quiet = run(0.05), run(0.0)
    assert 0.045 <= held < 0.2, held
    assert 0.0 <= quiet < 0.02, quiet


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------


def test_good_fraction_interpolates_inside_bucket():
    bounds = [10.0, 20.0, 30.0]
    # 10 requests in (10, 20] bucket; objective mid-bucket at 15 -> half
    counts = [0, 10, 0, 0]
    assert good_fraction(bounds, counts, 15.0) == pytest.approx(0.5)
    assert good_fraction(bounds, counts, 20.0) == pytest.approx(1.0)
    assert good_fraction(bounds, counts, 9.0) == pytest.approx(0.0)
    assert good_fraction(bounds, [0, 0, 0, 0], 15.0) is None


def _slo_rig(windows=(0.5, 1.0, 30.0), latency_ms=20.0, burn_alert=10.0):
    m = Metrics(16)
    store = TimeSeriesStore(m, capacity=64)
    eng = SloEngine(m, store, list(windows))
    assert eng.register("toy", SloConfig(latency_ms=latency_ms,
                                         availability=0.999,
                                         burn_alert=burn_alert))
    h = m.histogram("latency_ms{model=toy,phase=total}")
    return m, store, eng, h


def test_slo_disabled_model_not_registered():
    m = Metrics(16)
    eng = SloEngine(m, TimeSeriesStore(m, 8), [1.0, 2.0])
    assert not eng.register("off", SloConfig())  # latency_ms = 0
    assert eng.state_of("off") == "ok"
    assert eng.alerts()["models"] == {}


def test_burn_fires_and_clears():
    """The two-window machine: all-bad traffic fires (burn ~1000 over
    budget 0.001), and once the bad window ages out the alert returns to
    ok — fast to fire, fast to clear."""
    m, store, eng, h = _slo_rig(windows=(0.4, 0.8, 30.0))
    store.sample()
    for _ in range(50):
        h.observe(500.0)  # objective is 20 ms: every one bad
    store.sample()
    eng.tick()
    assert eng.state_of("toy") == "firing"
    alerts = eng.alerts()
    assert alerts["status"] == "firing"
    row = alerts["models"]["toy"]
    assert row["burn"]["0.4s"] > 100
    assert m.gauge("slo_alert_state{model=toy}").value == 2.0
    # good traffic + the bad samples aging past the windows -> ok
    time.sleep(1.0)
    for _ in range(50):
        h.observe(1.0)
    store.sample()
    eng.tick()
    assert eng.state_of("toy") == "ok", eng.alerts()
    assert m.gauge("slo_alert_state{model=toy}").value == 0.0
    # burn gauges exist per window
    assert "slo_burn_rate{model=toy,window=0.4s}" in m._gauges


def test_burn_pending_on_short_window_only():
    """Bad traffic only inside the short window (the mid window still
    mostly good) -> pending, not firing."""
    m, store, eng, h = _slo_rig(windows=(0.4, 30.0, 60.0))
    store.sample()
    for _ in range(1000):
        h.observe(1.0)  # long-window history: good
    store.sample()
    time.sleep(0.5)
    for _ in range(5):
        h.observe(500.0)
    store.sample()
    eng.tick()
    # short window: 5/5 bad -> burn 1000; mid window: 5/1005 bad -> ~5
    assert eng.state_of("toy") == "pending", eng.alerts()


def test_no_evidence_holds_ok():
    m, store, eng, h = _slo_rig()
    eng.tick()  # zero samples: no deltas anywhere
    assert eng.state_of("toy") == "ok"
    assert all(b is None for b in eng.burn_rates("toy").values())


# ---------------------------------------------------------------------------
# Utilization derivation
# ---------------------------------------------------------------------------


def test_utilization_from_device_seconds_rate():
    m = Metrics(16)
    store = TimeSeriesStore(m, capacity=32)
    util = UtilizationDeriver(m, store, window_s=10.0)
    c0 = m.device_seconds_counter("toy", 0)
    c1 = m.device_seconds_counter("toy", 1)
    t0 = time.time() - 4.0
    for i in range(5):
        c0.value = 0.9 * i   # ~90% busy chip
        c1.value = 0.1 * i   # ~10% busy chip
        store.sample(now=t0 + i)
    util.tick()
    g0 = m.gauge("device_utilization{model=toy,replica=0}")
    g1 = m.gauge("device_utilization{model=toy,replica=1}")
    assert g0.value == pytest.approx(0.9, abs=0.05)
    assert g1.value == pytest.approx(0.1, abs=0.05)
    stats = util.stats()
    assert stats["toy"]["device_seconds_total"] == pytest.approx(4.0)
    assert stats["toy"]["mean_utilization"] == pytest.approx(0.5, abs=0.05)


# ---------------------------------------------------------------------------
# Fleet merge
# ---------------------------------------------------------------------------


def _registry(reqs: int, lat_ms: list, depth: float) -> str:
    m = Metrics(16)
    c = m.counter("requests_total{model=toy}")
    c.inc(reqs)
    h = m.histogram("latency_ms{model=toy,phase=total}")
    for v in lat_ms:
        h.observe(v, trace_id="ab" * 16)  # exemplars must not break parse
    m.gauge("queue_depth{model=toy}").set(depth)
    return m.render_prometheus()


def test_merge_sums_counters_exactly():
    a = _registry(7, [1.0], 2.0)
    b = _registry(35, [2.0], 3.0)
    merged = merge_expositions([("worker0", a), ("worker1", b)])
    assert sum_counter(merged, "requests_total",
                       'model="toy"') == pytest.approx(42.0)
    # exact equality against the per-source sum — the smoke's gate
    per_source = sum_counter(a, "requests_total") + \
        sum_counter(b, "requests_total")
    assert sum_counter(merged, "requests_total") == per_source


def test_merge_labels_gauges_per_process():
    merged = merge_expositions([("worker0", _registry(1, [], 2.0)),
                                ("worker1", _registry(1, [], 5.0))])
    samples = parse_exposition(merged)["samples"]
    depths = {ls: v for b, ls, v in samples if b == "queue_depth"}
    assert depths == {'model="toy",proc="worker0"': 2.0,
                      'model="toy",proc="worker1"': 5.0}


def test_merge_histograms_bucketwise_exact():
    a = _registry(0, [1.0, 1.0, 50.0], 0)
    b = _registry(0, [1.0, 500.0], 0)
    merged = merge_expositions([("w0", a), ("w1", b)])
    parsed = parse_exposition(merged)
    assert parsed["types"]["latency_ms"] == "histogram"
    count = [v for base, ls, v in parsed["samples"]
             if base == "latency_ms_count"]
    assert count == [5.0]
    # every bucket's merged cumulative count == the sum of the sources'
    def buckets(text):
        return {ls: v for base, ls, v in parse_exposition(text)["samples"]
                if base == "latency_ms_bucket"}
    ba, bb, bm = buckets(a), buckets(b), buckets(merged)
    for ls, v in bm.items():
        assert v == ba.get(ls, 0.0) + bb.get(ls, 0.0), ls


def test_merge_marks_stale_sources_never_raises():
    merged = merge_expositions([("worker0", _registry(3, [1.0], 1.0)),
                                ("worker1", None), ("router1", None)])
    assert 'fleet_source_up{proc="worker0"} 1' in merged
    assert 'fleet_source_up{proc="worker1"} 0' in merged
    assert "# STALE worker1" in merged and "# STALE router1" in merged
    assert merged.rstrip().endswith("# EOF")
    # the live source's data still merged
    assert sum_counter(merged, "requests_total") == 3.0


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


def test_telemetry_and_slo_toml(tmp_path):
    p = tmp_path / "c.toml"
    p.write_text("""
[telemetry]
sample_interval_s = 0.5
history_s = 60.0
burn_windows_s = [2.0, 5.0, 30.0]

[[model]]
name = "toy"
family = "toy"

[model.slo]
latency_ms = 50.0
availability = 0.99
burn_alert = 5.0
""")
    cfg = load_config(str(p))
    assert cfg.telemetry.sample_interval_s == 0.5
    assert cfg.telemetry.burn_windows_s == [2.0, 5.0, 30.0]
    assert cfg.models[0].slo.latency_ms == 50.0
    assert cfg.models[0].slo.availability == 0.99
    cfg2 = load_config(str(p), overrides=["model.toy.slo.latency_ms=75.0",
                                          "telemetry.sample_interval_s=0.1"])
    assert cfg2.models[0].slo.latency_ms == 75.0
    assert cfg2.telemetry.sample_interval_s == 0.1


def test_telemetry_config_validation():
    with pytest.raises(ValueError, match="sample_interval_s"):
        TelemetryConfig(sample_interval_s=0.0)
    with pytest.raises(ValueError, match="burn_windows_s"):
        TelemetryConfig(burn_windows_s=[60.0])  # needs >= 2 windows
    with pytest.raises(ValueError, match="burn_windows_s"):
        TelemetryConfig(burn_windows_s=[300.0, 60.0])  # must ascend
    with pytest.raises(ValueError, match="availability"):
        SloConfig(latency_ms=10.0, availability=1.0)
    with pytest.raises(ValueError, match="burn_alert"):
        SloConfig(latency_ms=10.0, burn_alert=0.0)
    with pytest.raises(ValueError, match="latency_ms"):
        SloConfig(latency_ms=-1.0)


# ---------------------------------------------------------------------------
# HTTP e2e (real toy server, manual sampler ticks for determinism)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture(scope="module")
def client(loop):
    cfg = ServerConfig(
        models=[ModelConfig(name="toy", family="toy", batch_buckets=[1, 2],
                            deadline_ms=5.0, dtype="float32", num_classes=10,
                            parallelism="single",
                            request_timeout_ms=10_000.0, wire_size=8,
                            slo=SloConfig(latency_ms=20.0,
                                          availability=0.999))],
        decode_threads=2,
        telemetry=TelemetryConfig(sample_interval_s=30.0,  # manual ticks
                                  burn_windows_s=[0.5, 1.0, 30.0]),
    )
    state = ServerState(cfg)
    state.build()
    app = make_app(state)

    async def setup():
        client = TestClient(TestServer(app))
        await client.start_server()
        return client

    c = loop.run_until_complete(setup())
    yield lambda coro: loop.run_until_complete(coro), c, state
    loop.run_until_complete(c.close())


def npy_bytes(seed: int = 0) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.random.default_rng(seed).integers(
        0, 255, (8, 8, 3), dtype=np.uint8))
    return buf.getvalue()


NPY = "application/x-npy"


def test_metrics_content_negotiation_and_eof(client):
    """ISSUE 14 satellite: /metrics ends with `# EOF` and negotiates the
    OpenMetrics content type from Accept."""
    run, c, state = client

    async def go():
        async with c.get("/metrics") as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            body = await r.text()
        assert body.rstrip().endswith("# EOF")
        accept = ("application/openmetrics-text; version=1.0.0,"
                  "text/plain;q=0.5")
        async with c.get("/metrics", headers={"Accept": accept}) as r:
            assert r.headers["Content-Type"].startswith(
                "application/openmetrics-text; version=1.0.0")
            body_om = await r.text()
        assert body_om.rstrip().endswith("# EOF")

    run(go())


def test_history_endpoint(client):
    run, c, state = client

    async def go():
        # bracket some traffic between two sampler ticks so the window
        # DELTA (not just the lifetime counts) has something in it
        state.sampler.tick()
        for i in range(4):
            async with c.post("/v1/models/toy:classify", data=npy_bytes(i),
                              headers={"Content-Type": NPY}) as r:
                assert r.status == 200
        state.sampler.tick()
        async with c.get("/stats/history") as r:
            inv = await r.json()
            assert r.status == 200
        assert "requests_total{model=toy}" in inv["metrics"]
        assert inv["samples_total"] >= 2
        async with c.get("/stats/history",
                         params={"metric": "requests_total"}) as r:
            data = await r.json()
            assert r.status == 200
        (series,) = data["series"]
        assert series["kind"] == "counter"
        assert len(series["t"]) >= 2
        assert "rate_per_s" in series and "increase" in series
        # histogram series carry the window-delta quantiles
        async with c.get(
                "/stats/history",
                params={"metric": "latency_ms{model=toy,phase=total}",
                        "window_s": "60"}) as r:
            data = await r.json()
            assert r.status == 200
        assert data["series"][0]["delta"]["n"] >= 1
        async with c.get("/stats/history",
                         params={"metric": "nope_total"}) as r:
            assert r.status == 404
        async with c.get("/stats/history",
                         params={"metric": "requests_total",
                                 "window_s": "-3"}) as r:
            assert r.status == 400

    run(go())


def test_alerts_lifecycle_over_http(client):
    """Bad latency inside the burn windows -> /alerts firing (and the
    slo_alert_state gauge follows); once the bad window ages out under
    good traffic -> ok."""
    run, c, state = client

    async def go():
        h = state.metrics.histogram("latency_ms{model=toy,phase=total}")
        state.sampler.tick()
        for _ in range(50):
            h.observe(500.0)  # objective 20 ms
        state.sampler.tick()
        async with c.get("/alerts") as r:
            alerts = await r.json()
            assert r.status == 200
        assert alerts["models"]["toy"]["state"] == "firing", alerts
        assert alerts["status"] == "firing"
        assert alerts["models"]["toy"]["burn"]["0.5s"] > 100
        # /stats mirrors the alert view + telemetry heartbeat
        async with c.get("/stats") as r:
            stats = await r.json()
        assert stats["slo"]["models"]["toy"]["state"] == "firing"
        assert stats["telemetry"]["samples_total"] >= 1
        await asyncio.sleep(1.2)  # bad samples age past the 1.0 s window
        for _ in range(20):
            h.observe(1.0)
        state.sampler.tick()
        await asyncio.sleep(0.05)
        state.sampler.tick()
        async with c.get("/alerts") as r:
            alerts = await r.json()
        assert alerts["models"]["toy"]["state"] == "ok", alerts

    run(go())


def test_utilization_gauges_after_traffic(client):
    run, c, state = client

    async def go():
        for i in range(6):
            async with c.post("/v1/models/toy:classify",
                              data=npy_bytes(100 + i),
                              headers={"Content-Type": NPY}) as r:
                assert r.status == 200
        state.sampler.tick()
        await asyncio.sleep(0.05)
        state.sampler.tick()
        async with c.get("/stats") as r:
            stats = await r.json()
        util = stats["utilization"]["toy"]
        assert "0" in util["per_replica"]
        assert util["device_seconds_total"] > 0
        # the gauge itself is on /metrics
        async with c.get("/metrics") as r:
            text = await r.text()
        assert "device_utilization{" in text
        assert "device_seconds_total{" in text

    run(go())


def test_profile_endpoint(client):
    run, c, state = client

    async def go():
        async with c.post("/debug/profile",
                          params={"duration_ms": "junk"}) as r:
            assert r.status == 400
        async with c.post("/debug/profile",
                          params={"duration_ms": "99999999"}) as r:
            assert r.status == 400
        async with c.post("/debug/profile",
                          params={"duration_ms": "150"}) as r:
            data = await r.json()
            assert r.status == 200, data
        assert isinstance(data["traceEvents"], list)
        meta = data["tpuserve_profile"]
        assert meta["duration_ms"] == 150.0
        assert meta["device_trace"]  # "ok" or an explicit unavailable note
        # one capture at a time: armed -> 409
        state.profiler._armed = True
        try:
            async with c.post("/debug/profile",
                              params={"duration_ms": "50"}) as r:
                assert r.status == 409
        finally:
            state.profiler._armed = False
        async with c.get("/stats") as r:
            stats = await r.json()
        assert stats["telemetry"]["profile"]["captures_total"] >= 1

    run(go())


def test_sampler_stops_on_drain():
    """The satellite's drain half: a real server's sampler thread joins
    during drain() — no orphan thread keeps ticking a dying registry."""
    loop = asyncio.new_event_loop()
    try:
        cfg = ServerConfig(
            models=[ModelConfig(name="toy", family="toy",
                                batch_buckets=[1], deadline_ms=2.0,
                                dtype="float32", num_classes=10,
                                parallelism="single", wire_size=8)],
            decode_threads=2, startup_canary=False,
            telemetry=TelemetryConfig(sample_interval_s=0.05),
        )
        state = ServerState(cfg)
        state.build()

        async def go():
            await state.start()
            assert state.sampler.is_alive()
            deadline = time.time() + 5.0
            while state.store.samples_total < 2 and time.time() < deadline:
                await asyncio.sleep(0.02)
            assert state.store.samples_total >= 2
            ok = await state.drain()
            assert ok
            assert not state.sampler.is_alive()
            await state.stop()  # idempotent sampler stop

        loop.run_until_complete(go())
    finally:
        loop.close()


def test_scheduler_slo_hook():
    """The shed-on-burn seam: a scheduler with an attached engine reads
    each model's live alert state; without one, everything is ok."""
    from tpuserve.config import SchedulerConfig
    from tpuserve.scheduler import FleetScheduler

    m = Metrics(16)
    sched = FleetScheduler(SchedulerConfig(enabled=True), m)
    assert sched.slo_state("toy") == "ok"
    store = TimeSeriesStore(m, 32)
    eng = SloEngine(m, store, [0.5, 1.0, 30.0])
    eng.register("toy", SloConfig(latency_ms=10.0))
    sched.slo = eng
    h = m.histogram("latency_ms{model=toy,phase=total}")
    store.sample()
    for _ in range(20):
        h.observe(400.0)
    store.sample()
    eng.tick()
    assert sched.slo_state("toy") == "firing"
