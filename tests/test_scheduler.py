"""Fleet scheduler suite (ISSUE 10): cross-model SLO admission, priority
classes over the device-seconds ledger, warm/cold weight paging, and the
isolation-drill logic.

Three layers, mirroring the chaos/lifecycle suites:

- pure units against stub batchers (predictor math, saturation, the
  priority floor, the ledger window, the warm/cold state machine);
- real-batcher units (the raw-vs-clamped queue-clear split the scheduler
  depends on — ISSUE 10's bugfix satellite);
- HTTP end-to-end against real toy-family servers (unmeetable-deadline
  504 before enqueue, cold boot -> first-request warm-up -> idle demotion
  -> zero-recompile re-warm, the ``:warm`` admin endpoint, the
  ``/stats scheduler`` block, priority shed under saturation, and the
  fleet isolation drill).
"""

import asyncio
import io
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from tpuserve.batcher import clamp_retry_after_s
from tpuserve.config import (ModelConfig, SchedulerConfig, ServerConfig,
                             load_config)
from tpuserve.obs import Metrics
from tpuserve.scheduler import FleetScheduler, run_fleet_drill
from tpuserve.server import ServerState, make_app

pytestmark = pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnraisableExceptionWarning")

NPY = {"Content-Type": "application/x-npy"}


def npy_image(seed: int = 0, edge: int = 8) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.random.default_rng(seed).integers(
        0, 200, (edge, edge, 3), dtype=np.uint8))
    return buf.getvalue()


def toy_model_cfg(name: str = "toy", **over) -> ModelConfig:
    base = dict(family="toy", batch_buckets=[1, 2, 4], deadline_ms=5.0,
                dtype="float32", num_classes=10, parallelism="single",
                request_timeout_ms=10_000.0, wire_size=8)
    base.update(over)
    return ModelConfig(name=name, **base)


def sched_server_cfg(models, **over) -> ServerConfig:
    base = dict(models=models, decode_threads=2, startup_canary=False,
                scheduler=SchedulerConfig(enabled=True))
    base.update(over)
    return ServerConfig(**base)


class StubBatcher:
    """Minimal batcher surface the scheduler consumes."""

    def __init__(self, clear=None, service=None, pending=0):
        self.clear = clear
        self.service = service
        self.pending = pending
        self.device_time_cb = None

    def estimate_clear_s(self):
        return self.clear

    def predicted_service_s(self, n_items=1):
        return self.service


def make_sched(**cfg_over) -> FleetScheduler:
    base = dict(enabled=True)
    base.update(cfg_over)
    return FleetScheduler(SchedulerConfig(**base), Metrics())


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


# ---------------------------------------------------------------------------
# Satellite bugfix: raw estimate vs clamped Retry-After hint
# ---------------------------------------------------------------------------

def test_estimate_clear_raw_and_clamped_hint(loop):
    """estimate_clear_s stays RAW for the scheduler's admission math;
    clamp_retry_after_s owns the [1, 30] s client hint. A 90 s backlog
    clamped to 30 would admit work that provably cannot meet a 45 s
    deadline — the two must be separate numbers."""
    cfg = ServerConfig(models=[toy_model_cfg()], decode_threads=2,
                       startup_canary=False)
    state = ServerState(cfg)
    state.build()

    async def go():
        await state.start()
        b = state.batchers["toy"]
        b._ewma_ms[(1,)] = 1000.0  # 1 item/s demonstrated
        b._pending = 90
        assert b.estimate_clear_s() == pytest.approx(90.0)  # raw, unclamped
        assert clamp_retry_after_s(b.estimate_clear_s()) == 30  # the hint
        assert state.queue_retry_after("toy") == 30
        b._pending = 2
        assert b.estimate_clear_s() == pytest.approx(2.0)
        assert clamp_retry_after_s(b.estimate_clear_s()) == 2
        b._pending = 1
        b._ewma_ms[(1,)] = 10.0
        assert b.estimate_clear_s() == pytest.approx(0.01)
        assert clamp_retry_after_s(b.estimate_clear_s()) == 1  # floor
        assert clamp_retry_after_s(None) is None
        await state.stop()

    loop.run_until_complete(go())


def test_predicted_service_picks_covering_bucket(loop):
    """predicted_service_s: the EWMA of the smallest bucket covering the
    request; largest-observed fallback; None before evidence."""
    cfg = ServerConfig(models=[toy_model_cfg()], decode_threads=2,
                       startup_canary=False)
    state = ServerState(cfg)
    state.build()

    async def go():
        await state.start()
        b = state.batchers["toy"]
        assert b.predicted_service_s() is None
        b._ewma_ms[(1,)] = 10.0
        b._ewma_ms[(4,)] = 40.0
        assert b.predicted_service_s(1) == pytest.approx(0.010)
        assert b.predicted_service_s(3) == pytest.approx(0.040)
        # Nothing covers 8 items: fall back to the largest observed.
        assert b.predicted_service_s(8) == pytest.approx(0.040)
        await state.stop()

    loop.run_until_complete(go())


# ---------------------------------------------------------------------------
# Predictor + admission units (stub batchers)
# ---------------------------------------------------------------------------

def test_predict_completion_combines_clear_and_service():
    sched = make_sched()
    sched.register("m", StubBatcher(clear=2.0, service=0.5),
                   toy_model_cfg("m"))
    assert sched.predict_completion_s("m") == pytest.approx(2.5)
    sched.register("empty", StubBatcher(clear=None, service=None),
                   toy_model_cfg("empty"))
    assert sched.predict_completion_s("empty") is None  # no evidence: admit
    sched.register("idle", StubBatcher(clear=None, service=0.3),
                   toy_model_cfg("idle"))
    assert sched.predict_completion_s("idle") == pytest.approx(0.3)


def test_deadline_unmeetable_shed_unit():
    sched = make_sched()
    sched.register("m", StubBatcher(clear=2.0, service=1.0, pending=5),
                   toy_model_cfg("m"))
    now = time.perf_counter()
    shed = sched.check_deadline("m", now + 1.0)  # 1 s left, 3 s predicted
    assert shed is not None and shed.status == 504
    assert shed.reason == "deadline_unmeetable"
    assert shed.retry_after == 2  # clamp of the raw 2.0 s clear estimate
    assert sched._entries["m"].shed_counters[
        "deadline_unmeetable"].value == 1
    assert sched.check_deadline("m", now + 10.0) is None  # meetable
    assert sched.check_deadline("m", None) is None  # no deadline stamped


def test_deadline_headroom_grace():
    """headroom_ms is grace BEYOND the prediction before the shed fires."""
    sched = make_sched(headroom_ms=2000.0)
    sched.register("m", StubBatcher(clear=2.0, service=1.0),
                   toy_model_cfg("m"))
    now = time.perf_counter()
    # 1.5 s remaining vs 3.0 s predicted: within the 2 s grace -> admit.
    assert sched.check_deadline("m", now + 1.5) is None
    assert sched.check_deadline("m", now + 0.5) is not None


def test_priority_shed_and_floor_under_saturation(loop):
    """Under saturation batch-class sheds first; the min_share floor
    sheds the device-time hog's traffic while a starved model with
    queued work catches up — and stops shedding once it has."""
    async def go():
        sched = make_sched(overload_clear_s=0.5, min_share=0.2)
        hot = StubBatcher(clear=5.0, service=0.5, pending=10)
        quiet = StubBatcher(clear=0.0, service=0.01, pending=1)
        sched.register("hot", hot, toy_model_cfg("hot"))
        sched.register("quiet", quiet, toy_model_cfg("quiet"))
        # Feed the ledger: hot consumed ~99% of the windowed device time.
        hot.device_time_cb(0.99)
        quiet.device_time_cb(0.01)
        assert sched.saturated()
        assert sched.share("hot") > 0.9

        shed = sched.check_admission("hot", "batch")
        assert shed is not None and shed.reason == "priority_shed"
        assert shed.status == 503 and shed.retry_after >= 1
        shed = sched.check_admission("quiet", "batch")
        assert shed is not None and shed.reason == "priority_shed"

        # The floor: quiet has pending work below min_share, hot is over
        # its allowance (1 - 0.2) -> hot's interactive sheds too...
        shed = sched.check_admission("hot", "interactive")
        assert shed is not None and shed.reason == "share_exceeded"
        # ...while quiet's interactive is never starved.
        assert sched.check_admission("quiet", "interactive") is None

        # Once quiet caught up past the floor, hot admits again.
        quiet.device_time_cb(0.5)
        assert sched.share("quiet") > 0.2
        assert sched.check_admission("hot", "interactive") is None

    loop.run_until_complete(go())


def test_unsaturated_fleet_admits_everything(loop):
    async def go():
        sched = make_sched(overload_clear_s=1.0)
        sched.register("m", StubBatcher(clear=0.2, service=0.1, pending=1),
                       toy_model_cfg("m"))
        assert not sched.saturated()
        assert sched.check_admission("m", "batch") is None
        assert sched.check_admission("m", "interactive") is None

    loop.run_until_complete(go())


def test_ledger_window_trims_and_counts():
    sched = make_sched(window_s=0.1)
    b = StubBatcher()
    sched.register("m", b, toy_model_cfg("m"))
    b.device_time_cb(0.5)
    assert sched._entries["m"].window_sum == pytest.approx(0.5)
    assert sched._entries["m"].device_seconds_total.value == pytest.approx(0.5)
    time.sleep(0.15)
    assert sched.share("m") == 0.0  # window expired
    assert sched._entries["m"].window_sum == pytest.approx(0.0)
    # The monotonic counter never trims.
    assert sched._entries["m"].device_seconds_total.value == pytest.approx(0.5)


def test_resolve_priority_header_default_and_junk():
    sched = make_sched()
    sched.register("m", StubBatcher(),
                   toy_model_cfg("m", priority="batch"))
    assert sched.resolve_priority("m", None) == "batch"  # model default
    assert sched.resolve_priority("m", "Interactive") == "interactive"
    assert sched.resolve_priority("m", "batch") == "batch"
    with pytest.raises(ValueError, match="X-Priority"):
        sched.resolve_priority("m", "urgent")


def test_scheduler_config_validation_and_toml(tmp_path):
    with pytest.raises(ValueError, match="min_share"):
        SchedulerConfig(min_share=0.6)
    with pytest.raises(ValueError, match="window_s"):
        SchedulerConfig(window_s=0.0)
    with pytest.raises(ValueError, match="priority"):
        ModelConfig(name="m", priority="urgent")
    p = tmp_path / "sched.toml"
    p.write_text(
        "[scheduler]\n"
        "enabled = true\n"
        "overload_clear_s = 0.25\n"
        "min_share = 0.1\n"
        "idle_demote_s = 3.0\n"
        "[[model]]\n"
        "name = \"toy\"\n"
        "family = \"toy\"\n"
        "priority = \"batch\"\n"
        "cold_start = true\n")
    cfg = load_config(str(p))
    assert cfg.scheduler.enabled and cfg.scheduler.min_share == 0.1
    assert cfg.scheduler.idle_demote_s == 3.0
    assert cfg.models[0].priority == "batch" and cfg.models[0].cold_start
    cfg2 = load_config(str(p), overrides=["scheduler.overload_clear_s=2.0"])
    assert cfg2.scheduler.overload_clear_s == 2.0


# ---------------------------------------------------------------------------
# Warm/cold state machine units
# ---------------------------------------------------------------------------

def test_warm_cold_state_machine(loop):
    async def go():
        sched = make_sched(warm_retry_after_s=0.2)
        calls = []

        async def warm_fn():
            calls.append(1)
            await asyncio.sleep(0.02)
            return {"version": 2}

        sched.register("m", StubBatcher(), toy_model_cfg("m", cold_start=True),
                       warm_fn=warm_fn, cold=True)
        assert sched.state_of("m") == "cold"
        assert not sched.is_warm("m")
        shed = sched.check_admission("m", "interactive")
        assert shed is not None and shed.status == 503
        assert shed.reason == "model_warming" and shed.retry_after >= 1
        info = await sched.warm("m")  # joins the kicked warm task
        assert info["state"] == "warm" and calls == [1]
        assert sched.is_warm("m")
        assert sched.check_admission("m", "interactive") is None
        again = await sched.warm("m")
        assert again.get("already_warm") and calls == [1]  # idempotent

    loop.run_until_complete(go())


def test_failed_warm_backs_off_then_explicit_retry(loop):
    async def go():
        sched = make_sched(warm_retry_after_s=5.0)
        attempts = []

        async def bad_warm():
            attempts.append(1)
            raise RuntimeError("corrupt checkpoint")

        sched.register("m", StubBatcher(), toy_model_cfg("m", cold_start=True),
                       warm_fn=bad_warm, cold=True)
        with pytest.raises(RuntimeError, match="corrupt"):
            await sched.warm("m")
        assert sched.state_of("m") == "cold" and len(attempts) == 1
        # Request-triggered warms back off; no new task spins.
        sched.check_admission("m", "interactive")
        await asyncio.sleep(0.01)
        assert len(attempts) == 1
        # An explicit :warm overrides the backoff and retries.
        with pytest.raises(RuntimeError):
            await sched.warm("m")
        assert len(attempts) == 2

    loop.run_until_complete(go())


def test_idle_sweep_demotes_via_runtime(loop):
    async def go():
        sched = make_sched(idle_demote_s=0.05)

        class StubRuntime:
            released = 0

            def release_params(self):
                StubRuntime.released += 1

        async def warm_fn():
            return {}

        b = StubBatcher(pending=0)
        sched.register("m", b, toy_model_cfg("m", cold_start=True),
                       runtime=StubRuntime(), warm_fn=warm_fn)
        assert sched.state_of("m") == "warm"
        sched._entries["m"].last_used = time.monotonic() - 1.0
        b.pending = 3
        assert sched.sweep_idle() == 0  # queued work blocks demotion
        b.pending = 0
        assert sched.sweep_idle() == 1
        assert sched.state_of("m") == "cold"
        assert StubRuntime.released == 1
        # Non-cold_start models never demote.
        sched.register("pinned", StubBatcher(), toy_model_cfg("pinned"),
                       runtime=StubRuntime(), warm_fn=warm_fn)
        sched._entries["pinned"].last_used = time.monotonic() - 1.0
        assert sched.sweep_idle() == 0

    loop.run_until_complete(go())


# ---------------------------------------------------------------------------
# HTTP end-to-end
# ---------------------------------------------------------------------------

def test_unmeetable_deadline_shed_504_before_enqueue(loop):
    """Clockwork admission over HTTP: with a 5 s service EWMA on the
    books, a 200 ms-deadline request sheds with a FAST 504
    (deadline_unmeetable + Retry-After) before decode or enqueue — the
    batcher never sees it."""
    cfg = sched_server_cfg([toy_model_cfg()])
    state = ServerState(cfg)
    state.build()

    async def go():
        server = TestServer(make_app(state))
        async with TestClient(server) as client:
            b = state.batchers["toy"]
            b._ewma_ms[(4,)] = 5000.0  # every bucket "takes" 5 s
            batches_before = b._c_batches.value
            t0 = time.perf_counter()
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(), headers=NPY,
                                  params={"timeout_ms": "200"})
            elapsed = time.perf_counter() - t0
            body = await r.json()
            assert r.status == 504, body
            assert body["reason"] == "deadline_unmeetable"
            assert "Retry-After" in r.headers
            assert elapsed < 0.15, "shed must be fast, not at the deadline"
            assert b._c_batches.value == batches_before  # never enqueued
            m = state.metrics.counter(
                "sched_sheds_total{model=toy,reason=deadline_unmeetable}")
            assert m.value == 1
            # A roomy deadline admits and serves normally.
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(), headers=NPY,
                                  params={"timeout_ms": "30000"})
            assert r.status == 200

    loop.run_until_complete(go())


def test_priority_shed_and_queue_wait_split_http(loop):
    """Saturated fleet over HTTP: batch-class sheds 503 priority_shed
    with Retry-After; interactive admits; the queue-wait histogram is
    split by priority; junk X-Priority 400s."""
    cfg = sched_server_cfg(
        [toy_model_cfg()],
        scheduler=SchedulerConfig(enabled=True, overload_clear_s=0.5))
    state = ServerState(cfg)
    state.build()

    async def go():
        server = TestServer(make_app(state))
        async with TestClient(server) as client:
            # Serve one real request per class so the split histograms see
            # traffic (the fleet is not saturated yet).
            for prio in ("interactive", "batch"):
                r = await client.post("/v1/models/toy:predict",
                                      data=npy_image(), headers={
                                          **NPY, "X-Priority": prio})
                assert r.status == 200
            for prio in ("interactive", "batch"):
                h = state.metrics.queue_wait_histogram("toy", prio)
                assert h.n >= 1, f"queue_wait_ms missing for {prio}"

            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(),
                                  headers={**NPY, "X-Priority": "urgent"})
            assert r.status == 400

            # Saturate: a 5 s backlog on the books.
            b = state.batchers["toy"]
            b._ewma_ms[(1,)] = 1000.0
            b._pending = 5
            assert state.scheduler.saturated()
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(),
                                  headers={**NPY, "X-Priority": "batch"})
            body = await r.json()
            assert r.status == 503 and body["reason"] == "priority_shed"
            assert "Retry-After" in r.headers
            b._pending = 0  # restore before teardown accounting

            async with client.get("/stats") as r:
                stats = await r.json()
            srow = stats["scheduler"]
            assert srow["models"]["toy"]["sheds"]["priority_shed"] == 1
            assert srow["min_share"] == cfg.scheduler.min_share

    loop.run_until_complete(go())


def _poll_until_200(client, path, body, deadline_s=30.0):
    async def go():
        t0 = time.monotonic()
        statuses = []
        while time.monotonic() - t0 < deadline_s:
            r = await client.post(path, data=body, headers=NPY)
            statuses.append(r.status)
            if r.status == 200:
                return statuses, await r.json()
            assert r.status == 503, await r.text()  # warming sheds only
            await asyncio.sleep(0.05)
        raise AssertionError(f"never warmed: {statuses}")
    return go()


def test_cold_start_warm_demote_rewarm_zero_recompiles(loop):
    """The weight-paging acceptance path: a cold-declared model boots
    with zero device params and zero compiled variants; the first request
    sheds 503 model_warming and triggers staging through the lifecycle
    path (no request is ever answered by unstaged weights — everything is
    a shed or a real 200); idle demotion frees the params; the next
    request re-warms through the SAME compiled variants with a
    runtime_compiles_total delta of 0."""
    cfg = sched_server_cfg(
        [toy_model_cfg(cold_start=True)],
        scheduler=SchedulerConfig(enabled=True, idle_demote_s=0.3,
                                  sweep_interval_s=0.05))
    state = ServerState(cfg)
    state.build()
    rt = state.runtimes["toy"]
    assert not rt.params_resident, "cold boot must not load device params"
    assert rt.compiles_total == 0, "cold boot must not compile variants"

    async def go():
        server = TestServer(make_app(state))
        async with TestClient(server) as client:
            assert state.metrics.gauge("model_state{model=toy}").value == 0.0
            statuses, body = await _poll_until_200(
                client, "/v1/models/toy:predict", npy_image())
            assert statuses[0] == 503, "first request sheds while warming"
            assert "top_k" in body
            assert rt.params_resident
            compiles_after_warm = rt.compiles_total
            assert compiles_after_warm > 0
            version_after_warm = rt.version

            # Idle out; the sweep demotes and frees the params.
            t0 = time.monotonic()
            while rt.params_resident and time.monotonic() - t0 < 10.0:
                await asyncio.sleep(0.05)
            assert not rt.params_resident, "idle demotion must free params"
            assert state.scheduler.state_of("toy") == "cold"
            assert state.metrics.gauge("model_state{model=toy}").value == 0.0

            # Re-warm on demand: same variants, zero new compiles.
            statuses, body = await _poll_until_200(
                client, "/v1/models/toy:predict", npy_image())
            assert "top_k" in body
            assert rt.compiles_total == compiles_after_warm, \
                "warm->cold->warm churn must not recompile"
            assert rt.version > version_after_warm  # a fresh publish
            m = state.metrics.counter(
                "sched_sheds_total{model=toy,reason=model_warming}")
            assert m.value >= 2  # both warming windows shed

    loop.run_until_complete(go())


def test_warm_endpoint_http(loop):
    """POST :warm stages a cold model to serving synchronously; /stats
    reflects the state; :warm on a scheduler-less server 409s."""
    cfg = sched_server_cfg([toy_model_cfg(cold_start=True)])
    state = ServerState(cfg)
    state.build()

    async def go():
        server = TestServer(make_app(state))
        async with TestClient(server) as client:
            async with client.get("/stats") as r:
                stats = await r.json()
            assert stats["scheduler"]["models"]["toy"]["state"] == "cold"
            assert stats["scheduler"]["models"]["toy"]["cold_start"] is True

            r = await client.post("/admin/models/toy:warm")
            body = await r.json()
            assert r.status == 200, body
            assert body["state"] == "warm" and body["warm_ms"] > 0
            assert state.runtimes["toy"].params_resident

            # Immediately serves — no warming shed after an explicit warm.
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(), headers=NPY)
            assert r.status == 200

            r = await client.post("/admin/models/toy:warm")
            body = await r.json()
            assert r.status == 200 and body.get("already_warm")

            r = await client.post("/admin/models/nope:warm")
            assert r.status == 404

    loop.run_until_complete(go())

    # Scheduler disabled: the endpoint refuses rather than pretending.
    cfg2 = ServerConfig(models=[toy_model_cfg()], decode_threads=2,
                        startup_canary=False)
    state2 = ServerState(cfg2)
    state2.build()

    async def go2():
        server = TestServer(make_app(state2))
        async with TestClient(server) as client:
            r = await client.post("/admin/models/toy:warm")
            assert r.status == 409

    loop.run_until_complete(go2())


def test_quiet_model_survives_hot_neighbor_saturation(loop):
    """The cross-model isolation property in-process: a hot model with
    slow compute and a deep backlog must not starve a quiet model's
    interactive traffic — every quiet request answers 200 while the hot
    model is saturated."""
    from tpuserve.config import FaultRuleConfig, FaultsConfig

    cfg = sched_server_cfg(
        [toy_model_cfg("hot"), toy_model_cfg("quiet")],
        scheduler=SchedulerConfig(enabled=True, overload_clear_s=0.2),
        faults=FaultsConfig(enabled=True, rules=[FaultRuleConfig(
            kind="slow_compute", model="hot", probability=1.0,
            delay_ms=60.0)]))
    state = ServerState(cfg)
    state.build()

    async def go():
        server = TestServer(make_app(state))
        async with TestClient(server) as client:
            async def flood_hot(n):
                async def one(i):
                    return await client.post("/v1/models/hot:predict",
                                             data=npy_image(i), headers=NPY)
                return await asyncio.gather(*(one(i) for i in range(n)))

            flood = asyncio.ensure_future(flood_hot(24))
            await asyncio.sleep(0.2)  # let the hot backlog form
            quiet_statuses = []
            for i in range(10):
                r = await client.post("/v1/models/quiet:predict",
                                      data=npy_image(100 + i), headers=NPY)
                quiet_statuses.append(r.status)
            await flood
            assert quiet_statuses == [200] * 10, quiet_statuses

    loop.run_until_complete(go())


# ---------------------------------------------------------------------------
# Fleet isolation drill logic
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_drill_victim_contained_survivors_hold(loop):
    """run_fleet_drill: 3 toy models, one poisoned with device_error at
    100% — the victim's breaker opens and every survivor holds
    availability >= 99% (the summary's gated `availability` is the worst
    survivor's)."""
    cfg = sched_server_cfg(
        [toy_model_cfg("victim", breaker_threshold=3),
         toy_model_cfg("ok_a"), toy_model_cfg("ok_b")])

    summary = loop.run_until_complete(run_fleet_drill(
        cfg, victim="victim", duration_s=4.0, warmup_s=0.5, concurrency=4))

    assert summary["victim"] == "victim"
    assert summary["victim_breaker_open"], summary["victim_breaker"]
    assert summary["availability"] >= 0.99, summary["availability"]
    for name in ("ok_a", "ok_b"):
        row = summary["models"][name]
        assert row["role"] == "survivor"
        assert row["availability"] >= 0.99, (name, row)
        assert row["n_ok"] > 0
    assert summary["models"]["victim"]["availability"] < 0.5
    assert summary["models"]["victim"]["role"] == "victim"
    assert any(f["kind"] == "device_error" and f["fired"] > 0
               for f in summary["faults"])


def test_fleet_drill_requires_three_models(loop):
    cfg = sched_server_cfg([toy_model_cfg("a"), toy_model_cfg("b")])
    with pytest.raises(ValueError, match=">= 3 models"):
        loop.run_until_complete(run_fleet_drill(cfg))
