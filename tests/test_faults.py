"""Chaos suite (ISSUE 1): deterministic fault injection, circuit breaking,
batch retry under load, watchdog recovery, graceful drain/SIGTERM.

Everything runs on CPU with the toy family. The HTTP tests drive real
aiohttp servers (TestServer or serve_async on an ephemeral port) and, for
the availability bound, the real load generator via faults.run_chaos —
the same harness `python -m tpuserve chaos` uses.
"""

import asyncio
import io
import os
import signal
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from tpuserve.config import (FaultRuleConfig, FaultsConfig, ModelConfig,
                             ServerConfig, load_config)
from tpuserve.faults import (CircuitBreaker, FaultInjected, FaultInjector,
                             Watchdog, run_chaos)
from tpuserve.obs import Metrics, percentile
from tpuserve.server import ServerState, make_app, serve_async


def toy_model_cfg(**over) -> ModelConfig:
    base = dict(name="toy", family="toy", batch_buckets=[1, 2, 4],
                deadline_ms=5.0, dtype="float32", num_classes=10,
                parallelism="single", request_timeout_ms=10_000.0)
    base.update(over)
    return ModelConfig(**base)


def toy_server_cfg(model_over=None, **over) -> ServerConfig:
    base = dict(models=[toy_model_cfg(**(model_over or {}))], decode_threads=2)
    base.update(over)
    return ServerConfig(**base)


def npy_image(seed: int = 0) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.random.default_rng(seed).integers(
        0, 200, (8, 8, 3), dtype=np.uint8))
    return buf.getvalue()


NPY = {"Content-Type": "application/x-npy"}


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


# ---------------------------------------------------------------------------
# FaultInjector unit behavior
# ---------------------------------------------------------------------------

def test_injector_is_deterministic():
    """Same config + seed => identical firing sequence (replayable chaos)."""
    def draws(seed):
        inj = FaultInjector.single("batch_error", probability=0.3, seed=seed)
        return [inj.fire("batch_error", "m") is not None for _ in range(200)]

    a, b = draws(7), draws(7)
    assert a == b
    assert draws(8) != a
    rate = sum(a) / len(a)
    assert 0.15 < rate < 0.45  # ~0.3, loose bound


def test_injector_count_budget():
    inj = FaultInjector.single("batch_error", count=2)
    fired = [inj.fire("batch_error", "m") is not None for _ in range(10)]
    assert fired == [True, True] + [False] * 8
    assert inj.snapshot()[0]["fired"] == 2
    assert inj.snapshot()[0]["remaining"] == 0


def test_injector_model_and_kind_filters():
    inj = FaultInjector.single("batch_error", model="a")
    assert inj.fire("batch_error", "b") is None
    assert inj.fire("slow_dispatch", "a") is None
    assert inj.fire("batch_error", "a") is not None
    star = FaultInjector.single("canary_fail", model="*")
    assert star.fire("canary_fail", "anything") is not None


def test_injector_disabled_and_toggle():
    inj = FaultInjector.single("batch_error")
    inj.set_enabled(False)
    assert inj.fire("batch_error", "m") is None
    inj.set_enabled(True)
    with pytest.raises(FaultInjected):
        inj.check("batch_error", "m")


def test_injector_delay_and_metrics():
    m = Metrics()
    inj = FaultInjector.single("slow_dispatch", delay_ms=250.0, metrics=m)
    assert inj.delay_s("slow_dispatch", "m") == pytest.approx(0.25)
    assert m.counter(
        "faults_injected_total{model=m,kind=slow_dispatch}").value == 1


def test_unknown_fault_kind_rejected():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultRuleConfig(kind="nope")


def test_faults_config_from_toml(tmp_path):
    p = tmp_path / "chaos.toml"
    p.write_text(
        "port = 8001\n"
        "[faults]\n"
        "enabled = true\n"
        "seed = 42\n"
        "[[faults.rule]]\n"
        'kind = "batch_error"\n'
        'model = "toy"\n'
        "probability = 0.1\n"
        "[[faults.rule]]\n"
        'kind = "slow_dispatch"\n'
        "delay_ms = 50.0\n"
        "count = 3\n")
    cfg = load_config(str(p))
    assert cfg.faults.enabled and cfg.faults.seed == 42
    assert len(cfg.faults.rules) == 2
    assert cfg.faults.rules[0].kind == "batch_error"
    assert cfg.faults.rules[0].probability == 0.1
    assert cfg.faults.rules[1].count == 3


# ---------------------------------------------------------------------------
# CircuitBreaker unit behavior
# ---------------------------------------------------------------------------

def test_breaker_opens_half_opens_closes():
    m = Metrics()
    br = CircuitBreaker("m", threshold=3, metrics=m)
    assert br.allow()
    br.record_failure()
    br.record_failure()
    assert br.allow()  # under threshold
    br.record_failure()
    assert br.state == "open" and not br.allow()
    assert m.gauge("breaker_state{model=m}").value == 2.0
    br.probe()  # canary admitted
    assert br.state == "half_open" and not br.allow()
    br.record_failure()  # failed probe: back to open
    assert br.state == "open"
    br.probe()
    br.record_success()
    assert br.state == "closed" and br.allow()
    assert br.consecutive_errors == 0
    assert br.describe()["opened_total"] == 1


def test_breaker_threshold_zero_disables():
    br = CircuitBreaker("m", threshold=0)
    for _ in range(10):
        br.record_failure()
    assert br.allow() and br.state == "closed"


def test_breaker_success_resets_consecutive_count():
    br = CircuitBreaker("m", threshold=3)
    for _ in range(2):
        br.record_failure()
    br.record_success()
    for _ in range(2):
        br.record_failure()
    assert br.state == "closed"  # never 3 consecutive


# ---------------------------------------------------------------------------
# Availability under injected faults (the acceptance bound)
# ---------------------------------------------------------------------------

def test_availability_with_10pct_batch_failures(loop):
    """10% injected batch-failure rate: >= 99% of loadgen requests still
    succeed via the one-shot retry, and the breaker never trips."""
    cfg = toy_server_cfg(faults=FaultsConfig(enabled=True, seed=1, rules=[
        FaultRuleConfig(kind="batch_error", model="toy", probability=0.10)]))
    state = ServerState(cfg)
    state.build()
    summary = loop.run_until_complete(run_chaos(
        state, "toy", duration_s=1.5, warmup_s=0.3, concurrency=8, edge=8))
    assert summary["n_ok"] > 100, summary
    assert summary["availability"] >= 0.99, summary
    fired = sum(r["fired"] for r in summary["faults"])
    assert fired > 5, summary  # chaos actually ran
    assert summary["breakers"]["toy"]["state"] == "closed"
    assert summary["breakers"]["toy"]["opened_total"] == 0


def test_reload_drill_availability(loop):
    """The ISSUE 2 acceptance bound: with reload_corrupt injected at 100%
    and :reload hammered throughout the run, every reload is rejected at
    the integrity gate, the original version keeps serving, and
    availability stays >= 99%."""
    cfg = toy_server_cfg(faults=FaultsConfig(enabled=True, seed=3, rules=[
        FaultRuleConfig(kind="reload_corrupt", model="toy")]))
    state = ServerState(cfg)
    state.build()
    summary = loop.run_until_complete(run_chaos(
        state, "toy", duration_s=1.5, warmup_s=0.3, concurrency=8, edge=8,
        drill="reload", drill_interval_s=0.1))
    assert summary["n_ok"] > 100, summary
    assert summary["availability"] >= 0.99, summary
    drill = summary["reload_drill"]
    assert drill["attempts"] >= 5, drill  # the drill actually hammered
    assert drill["ok"] == 0 and drill["rolled_back"] == 0
    assert drill["rejected"] == drill["attempts"] - drill["errors"]
    # The original version never left service; no candidate ever published.
    lc = summary["lifecycle"]["toy"]
    assert lc["live_version"] == 1
    assert all(h["status"] in ("live", "rejected") for h in lc["history"])


def test_reload_nan_drill_keeps_serving(loop):
    """Same bound for the NaN gate (reload_nan at 100%)."""
    cfg = toy_server_cfg(faults=FaultsConfig(enabled=True, seed=4, rules=[
        FaultRuleConfig(kind="reload_nan", model="toy")]))
    state = ServerState(cfg)
    state.build()
    summary = loop.run_until_complete(run_chaos(
        state, "toy", duration_s=1.0, warmup_s=0.2, concurrency=8, edge=8,
        drill="reload", drill_interval_s=0.1))
    assert summary["availability"] >= 0.99, summary
    assert summary["lifecycle"]["toy"]["live_version"] == 1
    assert summary["reload_drill"]["ok"] == 0


# ---------------------------------------------------------------------------
# Circuit breaker over HTTP: fast 503 + Retry-After, canary-driven recovery
# ---------------------------------------------------------------------------

def test_breaker_trips_fast_503_and_recovers_via_canary(loop):
    interval = 0.25
    cfg = toy_server_cfg(model_over=dict(breaker_threshold=2),
                         canary_interval_s=interval)
    state = ServerState(cfg)
    state.build()
    app = make_app(state)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # Total outage below the HTTP layer: every dispatch fails.
            state.batchers["toy"].injector = FaultInjector.single("batch_error")
            for _ in range(2):  # threshold consecutive failed dispatches
                r = await client.post("/v1/models/toy:predict",
                                      data=npy_image(), headers=NPY)
                assert r.status == 500
            assert state.breakers["toy"].state == "open"

            # While open: fast shed, never a dispatch. < 5 ms p50 per the
            # acceptance bound (loopback, body never read).
            lat_ms = []
            for _ in range(40):
                t0 = time.perf_counter()
                r = await client.post("/v1/models/toy:predict",
                                      data=npy_image(), headers=NPY)
                lat_ms.append((time.perf_counter() - t0) * 1e3)
                assert r.status == 503
                assert r.headers["Retry-After"] == "1"  # ceil(canary interval)
                assert "circuit open" in (await r.json())["error"]
            assert percentile(lat_ms, 0.5) < 5.0, lat_ms
            assert state.breakers["toy"].shed_total == 40

            # Injection stops: the periodic canary (which kept riding the
            # batcher while open) closes the breaker within 2 intervals.
            state.batchers["toy"].injector = None
            t_stop = time.perf_counter()
            deadline = t_stop + 2 * interval + 0.1  # +grace for canary exec
            while time.perf_counter() < deadline:
                r = await client.post("/v1/models/toy:predict",
                                      data=npy_image(), headers=NPY)
                if r.status == 200:
                    break
                await asyncio.sleep(0.01)
            assert r.status == 200, await r.text()
            assert time.perf_counter() - t_stop <= 2 * interval + 0.1
            assert state.breakers["toy"].state == "closed"

            # /metrics carries the breaker gauge + shed counter.
            text = await (await client.get("/metrics")).text()
            assert 'breaker_state{model="toy"}' in text
            assert 'breaker_shed_total{model="toy"}' in text
        finally:
            await client.close()

    loop.run_until_complete(go())


# ---------------------------------------------------------------------------
# Shed responses carry Retry-After; /stats surfaces breaker + shed state
# ---------------------------------------------------------------------------

def test_429_carries_retry_after_and_stats_robustness(loop):
    cfg = toy_server_cfg(model_over=dict(max_queue=1, deadline_ms=200.0))
    state = ServerState(cfg)
    state.build()
    app = make_app(state)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            first = asyncio.ensure_future(client.post(
                "/v1/models/toy:predict", data=npy_image(), headers=NPY))
            await asyncio.sleep(0.05)  # queued, batch not yet flushed
            shed = await client.post("/v1/models/toy:predict",
                                     data=npy_image(), headers=NPY)
            assert shed.status == 429
            assert shed.headers["Retry-After"] == "1"
            assert (await (await first).json())["top_k"]

            stats = await (await client.get("/stats")).json()
            rob = stats["robustness"]
            assert rob["draining"] is False
            assert rob["breakers"]["toy"]["state"] == "closed"
            assert "shed_total" in rob["breakers"]["toy"]
        finally:
            await client.close()

    loop.run_until_complete(go())


# ---------------------------------------------------------------------------
# Watchdog: dead group loop is detected and revived
# ---------------------------------------------------------------------------

def test_watchdog_revives_killed_group_loop(loop):
    # The server starts with the watchdog off (interval 0), so that the dead
    # task is seen before any sweep can revive it; the periodic sweep is
    # started by hand once it has been seen. Every wait is on its condition
    # with a deadline, never a fixed sleep: beside five other workers the
    # loop's death and the sweep each took longer than a sleep allowed.
    cfg = toy_server_cfg(watchdog_interval_s=0.0)
    state = ServerState(cfg)
    state.build()
    app = make_app(state)

    async def until(cond, what: str, deadline_s: float = 5.0) -> None:
        for _ in range(int(deadline_s / 0.001)):
            if cond():
                return
            await asyncio.sleep(0.001)
        raise AssertionError(f"not within {deadline_s} s: {what}")

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            b = state.batchers["toy"]
            # Arm a one-shot loop kill: it fires at the top of the NEXT
            # accumulation iteration, i.e. right after this batch flushes.
            b.injector = FaultInjector.single("kill_group_loop", count=1)
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(), headers=NPY)
            assert r.status == 200
            (task,) = b._tasks.values()
            await until(task.done, "the group loop's task is dead")
            assert isinstance(task.exception(), FaultInjected)
            restarts = state.metrics.counter(
                "watchdog_restarts_total{model=toy,component=group_loop}")
            assert restarts.value == 0  # dead and NOT yet revived

            state.watchdog.interval_s = 0.05
            state.watchdog.start()
            await until(lambda: restarts.value >= 1, "a sweep revived it")
            (task,) = b._tasks.values()
            assert not task.done()  # revived
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(), headers=NPY)
            assert r.status == 200  # serving again through the revived loop
        finally:
            await client.close()

    loop.run_until_complete(go())


def test_watchdog_sweep_unit():
    """Sweeps aggregate restart counts into the labeled counter; a raising
    sweep is contained."""
    m = Metrics()
    wd = Watchdog(1.0, m)
    wd.register("a", "group_loop", lambda: 2)
    wd.register("a", "worker", lambda: 0)

    def boom() -> int:
        raise RuntimeError("sweep failed")

    wd.register("b", "group_loop", boom)
    assert wd.sweep() == 2
    assert m.counter(
        "watchdog_restarts_total{model=a,component=group_loop}").value == 2


# ---------------------------------------------------------------------------
# Graceful drain + SIGTERM: zero accepted requests dropped
# ---------------------------------------------------------------------------

def test_drain_completes_accepted_rejects_new(loop):
    cfg = toy_server_cfg(
        faults=FaultsConfig(enabled=True, rules=[
            FaultRuleConfig(kind="slow_dispatch", delay_ms=150.0)]),
        drain_timeout_s=5.0)
    state = ServerState(cfg)
    state.build()
    app = make_app(state)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            inflight = [asyncio.ensure_future(client.post(
                "/v1/models/toy:predict", data=npy_image(i), headers=NPY))
                for i in range(5)]
            await asyncio.sleep(0.05)  # all accepted, dispatch mid-sleep
            drain_task = asyncio.ensure_future(state.drain())
            await asyncio.sleep(0)  # draining flag set synchronously

            late = await client.post("/v1/models/toy:predict",
                                     data=npy_image(), headers=NPY)
            assert late.status == 503
            assert late.headers["Retry-After"] == "1"
            assert "draining" in (await late.json())["error"]
            health = await client.get("/healthz")
            assert health.status == 503
            assert (await health.json())["status"] == "draining"
            stats = await (await client.get("/stats")).json()
            assert stats["robustness"]["draining"] is True

            for resp in await asyncio.gather(*inflight):
                assert resp.status == 200  # every accepted request finished
            assert await drain_task is True
        finally:
            await client.close()

    loop.run_until_complete(go())


def test_sigterm_drains_under_load():
    """End-to-end serve_async: SIGTERM during load completes every accepted
    request (with responses), then the server exits cleanly."""
    import aiohttp

    cfg = toy_server_cfg(
        host="127.0.0.1", port=0, startup_canary=False,
        faults=FaultsConfig(enabled=True, rules=[
            FaultRuleConfig(kind="slow_dispatch", delay_ms=150.0)]),
        drain_timeout_s=10.0)
    state = ServerState(cfg)
    state.build()
    loop = asyncio.new_event_loop()

    async def go():
        ready = asyncio.Event()
        server = asyncio.ensure_future(serve_async(state, ready=ready))
        await ready.wait()
        port = state.serving_addresses[0][1]
        url = f"http://127.0.0.1:{port}/v1/models/toy:predict"
        async with aiohttp.ClientSession() as session:

            async def one(i: int):
                async with session.post(url, data=npy_image(i),
                                        headers=NPY) as resp:
                    return resp.status, await resp.json()

            reqs = [asyncio.ensure_future(one(i)) for i in range(4)]
            await asyncio.sleep(0.05)  # accepted, still in flight
            os.kill(os.getpid(), signal.SIGTERM)
            results = await asyncio.gather(*reqs)
        for status, body in results:
            assert status == 200, body
            assert "top_k" in body
        await server  # clean exit, no hang
        assert state.draining

    try:
        loop.run_until_complete(go())
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# Below-the-batcher faults: runtime device errors are retried too
# ---------------------------------------------------------------------------

def test_device_error_below_batcher_retried(loop):
    cfg = toy_server_cfg(faults=FaultsConfig(enabled=True, rules=[
        FaultRuleConfig(kind="device_error", model="toy", count=1)]))
    state = ServerState(cfg)
    state.build()
    app = make_app(state)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(), headers=NPY)
            assert r.status == 200, await r.text()  # retry absorbed it
        finally:
            await client.close()

    loop.run_until_complete(go())


def test_slow_compute_below_batcher_still_serves(loop):
    """slow_compute injects a sleep inside ModelRuntime.dispatch — on a
    stage-executor thread, below the batcher. The request must still answer
    200, just slower, and the injected delay must show up in the dispatch
    wall time (the fault existed since ISSUE 1 but had no test: surfaced by
    `tpuserve lint` TPS403)."""
    cfg = toy_server_cfg(startup_canary=False,
                         faults=FaultsConfig(enabled=True, rules=[
                             FaultRuleConfig(kind="slow_compute", model="toy",
                                             count=1, delay_ms=300.0)]))
    state = ServerState(cfg)
    state.build()
    app = make_app(state)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            t0 = time.perf_counter()
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(), headers=NPY)
            elapsed = time.perf_counter() - t0
            assert r.status == 200, await r.text()
            assert elapsed >= 0.3, elapsed  # the injected sleep was real
            snap = state.injector.snapshot()
            fired = [r for r in snap if r["kind"] == "slow_compute"]
            assert fired and fired[0]["fired"] == 1, snap
        finally:
            await client.close()

    loop.run_until_complete(go())


def test_decode_corrupt_maps_to_400(loop):
    cfg = toy_server_cfg(faults=FaultsConfig(enabled=True, rules=[
        FaultRuleConfig(kind="decode_corrupt", count=1)]))
    state = ServerState(cfg)
    state.build()
    app = make_app(state)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(), headers=NPY)
            assert r.status == 400
            r = await client.post("/v1/models/toy:predict",
                                  data=npy_image(), headers=NPY)
            assert r.status == 200  # count budget spent
        finally:
            await client.close()

    loop.run_until_complete(go())
