"""Router/worker process split (ISSUE 8): real multi-process fleets.

Three layers of coverage, all against REAL worker processes (spawned, own
PJRT sessions, loopback HTTP) — the process boundary is the point, so
nothing here is mocked across it:

- single-process drain sequencing + live Retry-After derivation (the
  in-process satellites the cross-process drain builds on);
- a module-scoped router fleet (2 workers, chaos-armed models) proving
  deadline propagation across the boundary (504 at the same absolute
  instant whether the request dies in the router, on the wire, or inside a
  worker), retry-never-extends-deadline, no-double-execution after a
  definitive answer, hedging over a wedged worker, the worker_slow fault,
  the atomic reload fan-out, and the router-owned cache;
- a function-scoped fleet where worker_crash kills every worker
  (degradation to 503 + live Retry-After, then supervised respawn back to
  health).

No pytest-asyncio in the image: a module-level event loop drives
everything explicitly (the test_http idiom).
"""

import asyncio
import io
import signal
import time

import numpy as np
import pytest

from tpuserve.config import (
    FaultRuleConfig,
    FaultsConfig,
    ModelConfig,
    RouterConfig,
    ServerConfig,
)

pytestmark = pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnraisableExceptionWarning")

NPY = "application/x-npy"


def npy(seed: int = 0, edge: int = 8) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.random.default_rng(seed).integers(
        0, 255, (edge, edge, 3), dtype=np.uint8))
    return buf.getvalue()


def _toy(name: str, **kw) -> ModelConfig:
    base = dict(family="toy", batch_buckets=[1, 2], deadline_ms=2.0,
                dtype="float32", num_classes=10, parallelism="single",
                request_timeout_ms=10_000.0, wire_size=8, max_inflight=2)
    base.update(kw)
    return ModelConfig(name=name, **base)


def _parse_metrics(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or " " not in line:
            continue
        k, v = line.rsplit(" ", 1)
        try:
            out[k] = float(v)
        except ValueError:
            pass
    return out


@pytest.fixture(scope="module")
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


# ---------------------------------------------------------------------------
# Single-process satellites
# ---------------------------------------------------------------------------

def test_drain_stops_revival_machinery_before_flush(loop):
    """SIGTERM sequencing (ISSUE 8 satellite): drain() must stop the
    watchdog and the periodic canary BEFORE quiescing the batchers, so a
    sweep can never revive a group loop that the shutdown is intentionally
    stopping, and no canary can inject new work after admission closed."""
    from tpuserve.server import ServerState

    cfg = ServerConfig(models=[_toy("toy")], decode_threads=2,
                       startup_canary=False, canary_interval_s=0.5,
                       watchdog_interval_s=0.1)
    state = ServerState(cfg)
    state.build()

    async def go():
        await state.start()
        assert state._canary_task is not None
        assert state.watchdog._task is not None
        sweeps = []
        state.watchdog.register("probe", "probe",
                                lambda: sweeps.append(1) or 0)
        ok = await state.drain()
        assert ok
        # Both revival mechanisms are gone by the time drain() returns —
        # not merely "will be stopped later in stop()".
        assert state.watchdog._task is None
        assert state._canary_task is None
        n = len(sweeps)
        await asyncio.sleep(0.35)  # > 3 watchdog intervals
        assert len(sweeps) == n, "watchdog swept after drain() returned"
        assert state.draining
        await state.stop()

    loop.run_until_complete(go())


def test_retry_after_derived_from_live_state(loop):
    """429/503 Retry-After hints derive from live state (ISSUE 8
    satellite): queue-full 429s from the batcher's queue-clear estimate,
    breaker 503s from the next periodic-canary (recovery probe) ETA."""
    from tpuserve.server import ServerState

    cfg = ServerConfig(models=[], canary_interval_s=10.0)
    state = ServerState(cfg)

    class StubBatcher:
        def __init__(self, est):
            self.est = est

        def estimate_clear_s(self):
            return self.est

    state.batchers["m"] = StubBatcher(4.2)
    assert state.queue_retry_after("m") == 5  # ceil of the live estimate
    state.batchers["m"] = StubBatcher(9999.0)
    assert state.queue_retry_after("m") == 30  # clamped
    state.batchers["m"] = StubBatcher(None)
    assert state.queue_retry_after("m") == 1  # fallback: shed_retry_after_s

    # Breaker hint = time to the NEXT canary probe, not a constant.
    state._next_canary_at = time.monotonic() + 3.4
    assert state.breaker_retry_after("m") in (3, 4)
    state._next_canary_at = time.monotonic() - 1.0
    assert state.breaker_retry_after("m") == 1  # probe due now
    state._next_canary_at = None
    assert state.breaker_retry_after("m") == 10  # loop not armed yet


def test_estimate_clear_s_from_ewma(loop):
    """ModelBatcher.estimate_clear_s: pending over the best demonstrated
    bucket rate; None with no EWMA or an empty queue."""
    from tpuserve.server import ServerState

    cfg = ServerConfig(models=[_toy("toy")], decode_threads=2,
                       startup_canary=False)
    state = ServerState(cfg)
    state.build()

    async def go():
        await state.start()
        b = state.batchers["toy"]
        assert b.estimate_clear_s() is None  # empty queue
        b._ewma_ms[(2,)] = 100.0  # 2 items / 100 ms -> 20 items/s
        b._pending = 10
        est = b.estimate_clear_s()
        assert est == pytest.approx(0.5)
        b._pending = 0
        assert b.estimate_clear_s() is None
        await state.stop()

    loop.run_until_complete(go())


def test_worker_config_derivation():
    """Worker configs derive once from the deployment config: loopback
    bind, router recursion and the router-owned cache forced off."""
    from tpuserve.workerproc.worker import worker_config

    cfg = ServerConfig(models=[_toy("toy")],
                       router=RouterConfig(enabled=True, workers=2))
    cfg.cache.enabled = True
    wcfg = worker_config(cfg, 1)
    assert wcfg.host == "127.0.0.1" and wcfg.port == 0
    assert wcfg.router.enabled is False
    assert wcfg.cache.enabled is False
    assert cfg.cache.enabled is True  # the deployment config is untouched

    cfg.worker.port_base = 9200
    assert worker_config(cfg, 3).port == 9203
    cfg.worker.drain_timeout_s = 2.0
    assert worker_config(cfg, 0).drain_timeout_s == 2.0


# ---------------------------------------------------------------------------
# The router fleet (module-scoped: 2 real worker processes)
# ---------------------------------------------------------------------------

def _fleet_cfg() -> ServerConfig:
    return ServerConfig(
        decode_threads=2,
        startup_canary=False,
        # Short drain: the toyhang test deliberately leaves wedged handlers
        # inside the workers, and the supervisor's SIGKILL-after-budget is
        # exactly how a real deployment evicts them — just don't wait the
        # production 30 s for it in a test teardown.
        drain_timeout_s=3.0,
        router=RouterConfig(enabled=True, workers=2, retry_max=2,
                            hedge_ms=150.0, health_interval_s=0.2,
                            unhealthy_after=2, respawn_initial_s=0.3,
                            respawn_max_s=2.0),
        models=[
            _toy("toy"),
            # slow_compute fires INSIDE the worker's runtime: the request
            # must 504 at its router-stamped deadline, not at 600 ms.
            _toy("toyslow"),
            # worker_hang wedges the worker's handler: no response ever.
            _toy("toyhang"),
            # worker_slow delays the worker's handler by delay_ms.
            _toy("toylag"),
            # batch_error + no worker-side retry: every execution is a
            # definitive 500 (the no-double-execution probe).
            _toy("toyerr", batch_retry=False, retry_split=False,
                 breaker_threshold=0),
            # Same, but with a router breaker armed (threshold 2).
            _toy("toytrip", batch_retry=False, retry_split=False,
                 breaker_threshold=2, breaker_retry_after_s=1.0),
        ],
        faults=FaultsConfig(enabled=True, seed=7, rules=[
            FaultRuleConfig(kind="slow_compute", model="toyslow",
                            delay_ms=600.0),
            FaultRuleConfig(kind="worker_hang", model="toyhang"),
            FaultRuleConfig(kind="worker_slow", model="toylag",
                            delay_ms=300.0),
            FaultRuleConfig(kind="batch_error", model="toyerr"),
            FaultRuleConfig(kind="batch_error", model="toytrip"),
        ]),
    )


@pytest.fixture(scope="module")
def fleet(loop):
    import aiohttp
    from aiohttp import web

    from tpuserve.workerproc.router import RouterState, make_router_app

    cfg = _fleet_cfg()
    cfg.cache.enabled = True
    cfg.cache.capacity = 64
    state = RouterState(cfg)
    runner = web.AppRunner(make_router_app(state), access_log=None)

    async def setup():
        await runner.setup()  # on_startup spawns the fleet
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        return aiohttp.ClientSession()

    session = loop.run_until_complete(setup())
    base = f"http://127.0.0.1:{runner.addresses[0][1]}"

    def run(coro):
        return loop.run_until_complete(coro)

    yield run, session, base, state

    async def teardown():
        await session.close()
        await runner.cleanup()

    loop.run_until_complete(teardown())


async def _post(session, base, model, body, verb="classify", timeout_ms=None,
                total=30.0):
    import aiohttp

    params = {"timeout_ms": str(timeout_ms)} if timeout_ms else None
    async with session.post(f"{base}/v1/models/{model}:{verb}", data=body,
                            params=params,
                            headers={"Content-Type": NPY},
                            timeout=aiohttp.ClientTimeout(total=total)) as r:
        return r.status, await r.read(), dict(r.headers)


async def _worker_metric_sum(session, base, key, n=2) -> float:
    """Sum one Prometheus metric across every worker's own /metrics."""
    total = 0.0
    for i in range(n):
        async with session.get(f"{base}/workers/{i}/metrics") as r:
            assert r.status == 200, await r.text()
            total += _parse_metrics(await r.text()).get(key, 0.0)
    return total


def test_router_predict_and_introspection(fleet):
    run, session, base, state = fleet

    async def go():
        status, body, _ = await _post(session, base, "toy", npy(1))
        assert status == 200, body
        assert b"top_k" in body
        async with session.get(f"{base}/healthz") as r:
            health = await r.json()
            assert r.status == 200 and health["status"] == "ok"
        async with session.get(f"{base}/stats") as r:
            stats = await r.json()
        assert stats["workers"]["healthy"] == 2
        assert stats["workers"]["configured"] == 2
        assert {row["state"] for row in stats["workers"]["workers"]} == {"ready"}
        assert stats["router"]["generations"]["toy"] == 1
        async with session.get(f"{base}/metrics") as r:
            m = _parse_metrics(await r.text())
        assert m.get('worker_up{worker="0"}') == 1.0
        assert m.get('worker_up{worker="1"}') == 1.0
        # The workers really are separate processes serving real models.
        async with session.get(f"{base}/workers/1/stats") as r:
            wstats = await r.json()
        assert "pipeline" in wstats

    run(go())


def test_router_cache_hit_and_single_execution(fleet):
    """The PR-5 cache lives in the ROUTER: a byte-identical re-upload is
    answered without any worker executing a second time."""
    run, session, base, state = fleet

    async def go():
        body = npy(42)
        before = await _worker_metric_sum(
            session, base, 'requests_total{model="toy"}')
        s1, b1, _ = await _post(session, base, "toy", body)
        s2, b2, _ = await _post(session, base, "toy", body)
        assert s1 == 200 and s2 == 200
        assert b1 == b2  # the hit serves the exact cached bytes
        after = await _worker_metric_sum(
            session, base, 'requests_total{model="toy"}')
        assert after - before == 1, "cache hit must not reach a worker"
        async with session.get(f"{base}/stats") as r:
            stats = await r.json()
        assert stats["cache"]["toy"]["hits"] >= 1

    run(go())


def test_priority_relayed_end_to_end_and_not_in_cache_key(fleet):
    """ISSUE 10 satellite: X-Priority rides header -> worker -> batcher
    (the worker's queue-wait split records the relayed class), and the
    router's wire cache key NEVER sees it — same bytes, same entry,
    whatever the priority."""
    run, session, base, state = fleet

    async def go():
        body = npy(777)
        qkey = 'queue_wait_ms_count{model="toy",priority="batch"}'
        before_q = await _worker_metric_sum(session, base, qkey)
        before_req = await _worker_metric_sum(
            session, base, 'requests_total{model="toy"}')
        async with session.post(
                f"{base}/v1/models/toy:classify", data=body,
                headers={"Content-Type": NPY, "X-Priority": "batch"}) as r:
            assert r.status == 200, await r.text()
            first = await r.read()
        after_q = await _worker_metric_sum(session, base, qkey)
        assert after_q - before_q == 1, \
            "relayed X-Priority must reach the worker's batcher split"
        # Same bytes, DIFFERENT priority: must hit the router cache — no
        # second worker execution, byte-identical answer.
        async with session.post(
                f"{base}/v1/models/toy:classify", data=body,
                headers={"Content-Type": NPY,
                         "X-Priority": "interactive"}) as r:
            assert r.status == 200
            assert await r.read() == first
        after_req = await _worker_metric_sum(
            session, base, 'requests_total{model="toy"}')
        assert after_req - before_req == 1, \
            "priority must not enter the cache key (same bytes, same key)"

    run(go())


def test_router_records_worker_shed_reason():
    """The router remembers the machine-readable `reason` workers answer
    on scheduler sheds, and carries it on its own breaker 503s."""
    from tpuserve.workerproc.router import RouterState, _Answer

    cfg = ServerConfig(models=[_toy("toy")],
                       router=RouterConfig(enabled=True, workers=1))
    state = RouterState(cfg)
    state.note_shed_reason("toy", _Answer(
        503, "application/json",
        b'{"error": "warming", "reason": "model_warming"}', None))
    assert state.last_shed_reason["toy"] == "model_warming"
    # Non-shed statuses and junk bodies never overwrite it.
    state.note_shed_reason("toy", _Answer(200, "application/json",
                                          b'{"reason": "nope"}', None))
    state.note_shed_reason("toy", _Answer(503, "text/plain",
                                          b"not json", None))
    assert state.last_shed_reason["toy"] == "model_warming"


def test_deadline_expires_inside_worker(fleet):
    """Deadline propagation (ISSUE 8 satellite): the router stamps the
    absolute deadline at admission and forwards the remaining budget; a
    request that dies inside a worker (600 ms injected compute) 504s at
    ~its 250 ms deadline — not after the slow compute, and not stretched
    by the hedge that fires meanwhile."""
    run, session, base, state = fleet

    async def go():
        t0 = time.perf_counter()
        status, body, _ = await _post(session, base, "toyslow", npy(2),
                                      timeout_ms=250)
        elapsed = time.perf_counter() - t0
        assert status == 504, body
        assert 0.2 <= elapsed < 1.5, elapsed

    run(go())


def test_deadline_expires_on_wire_and_retry_never_extends(fleet):
    """Both workers SIGSTOPped: attempts connect but never answer, so the
    request expires 'on the wire'. The router hedges and retries within
    the budget, and the answer still lands at the stamped deadline (+ the
    backstop grace) — re-dispatch never extends it."""
    run, session, base, state = fleet
    pids = [h.pid for h in state.supervisor.slots if h is not None]
    assert len(pids) == 2

    async def go():
        for pid in pids:
            import os

            os.kill(pid, signal.SIGSTOP)
        try:
            t0 = time.perf_counter()
            status, body, _ = await _post(session, base, "toy", npy(3),
                                          timeout_ms=400)
            elapsed = time.perf_counter() - t0
            assert status == 504, body
            # deadline 0.4 s + 0.25 s grace + scheduling slack; far below
            # any retry-stretched horizon.
            assert 0.35 <= elapsed < 1.5, elapsed
        finally:
            import os

            for pid in pids:
                os.kill(pid, signal.SIGCONT)
        # Health probes may have marked the stopped workers unhealthy;
        # wait for the fleet to report fully healthy again.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            async with session.get(f"{base}/healthz") as r:
                health = await r.json()
            if health["status"] == "ok":
                break
            await asyncio.sleep(0.1)
        assert health["status"] == "ok", health

    run(go())


def test_no_double_execution_after_definitive_answer(fleet):
    """A 500 from a worker is DEFINITIVE — the work executed and failed.
    The router must relay it without re-dispatching: across both workers,
    exactly one execution is observed, and the router retry counter does
    not move."""
    run, session, base, state = fleet

    async def go():
        key = 'requests_total{model="toyerr"}'
        before = await _worker_metric_sum(session, base, key)
        async with session.get(f"{base}/metrics") as r:
            retries_before = _parse_metrics(await r.text()).get(
                'router_retries_total{model="toyerr"}', 0.0)
        status, body, _ = await _post(session, base, "toyerr", npy(4))
        assert status == 500, body
        after = await _worker_metric_sum(session, base, key)
        assert after - before == 1, "definitive 500 was re-dispatched"
        async with session.get(f"{base}/metrics") as r:
            retries_after = _parse_metrics(await r.text()).get(
                'router_retries_total{model="toyerr"}', 0.0)
        assert retries_after == retries_before

    run(go())


def test_worker_hang_hedged_then_504_at_deadline(fleet):
    """worker_hang wedges the handling worker silently. The hedge races a
    duplicate on the other worker after hedge_ms; with both wedged (the
    rule is armed in every worker) the request still 504s AT its deadline."""
    run, session, base, state = fleet

    async def go():
        async with session.get(f"{base}/metrics") as r:
            hedges_before = _parse_metrics(await r.text()).get(
                'router_hedges_total{model="toyhang"}', 0.0)
        t0 = time.perf_counter()
        status, body, _ = await _post(session, base, "toyhang", npy(5),
                                      timeout_ms=600)
        elapsed = time.perf_counter() - t0
        assert status == 504, body
        assert 0.55 <= elapsed < 2.0, elapsed
        async with session.get(f"{base}/metrics") as r:
            hedges_after = _parse_metrics(await r.text()).get(
                'router_hedges_total{model="toyhang"}', 0.0)
        assert hedges_after == hedges_before + 1

    run(go())


def test_worker_slow_fault_delays_but_serves(fleet):
    """worker_slow injects latency inside the worker process; within the
    deadline the request still answers."""
    run, session, base, state = fleet

    async def go():
        t0 = time.perf_counter()
        status, body, _ = await _post(session, base, "toylag", npy(6),
                                      timeout_ms=5000)
        elapsed = time.perf_counter() - t0
        assert status == 200, body
        assert elapsed >= 0.3, elapsed  # the injected delay really applied

    run(go())


def test_router_breaker_sheds_with_live_probe_eta(fleet):
    """Router-side breaker (threshold 2 on toytrip): consecutive definitive
    500s trip it; shed 503s carry the half-open probe ETA as Retry-After,
    and one request per interval is let through as the probe."""
    run, session, base, state = fleet

    async def go():
        for _ in range(3):
            status, body, _ = await _post(session, base, "toytrip", npy(7))
            assert status in (500, 503), body
        # Tripped + probe consumed: the next request sheds fast.
        status, body, headers = await _post(session, base, "toytrip", npy(7))
        assert status == 503, body
        assert b"circuit open" in body
        assert int(headers["Retry-After"]) >= 1
        assert state.breakers["toytrip"].state in ("open", "half_open")

    run(go())


def test_reload_fans_out_atomically(fleet):
    """Admin :reload reaches EVERY worker; success bumps the router cache
    generation (atomic fleet-wide invalidation) and the fleet reports one
    consistent version."""
    run, session, base, state = fleet

    async def go():
        body = npy(77)
        s1, _, _ = await _post(session, base, "toy", body)  # populate cache
        assert s1 == 200
        gen_before = state.generations["toy"]
        async with session.post(f"{base}/admin/models/toy:reload") as r:
            info = await r.json()
            assert r.status == 200, info
        assert info["fleet_consistent"] is True
        assert len(info["workers"]) == 2
        versions = {w["version"] for w in info["workers"].values()}
        assert len(versions) == 1
        assert state.generations["toy"] == gen_before + 1
        async with session.get(f"{base}/stats") as r:
            stats = await r.json()
        assert stats["cache"]["toy"]["entries"] == 0  # invalidated
        # Per-worker versions agree over the fan-out endpoint too.
        async with session.get(f"{base}/admin/models/toy/versions") as r:
            vers = await r.json()
            assert r.status == 200
        live = {w["live_version"] for w in vers["workers"].values()}
        assert len(live) == 1

    run(go())


def test_router_drain_sheds_with_retry_after(fleet):
    run, session, base, state = fleet

    async def go():
        state.begin_drain()
        try:
            status, body, headers = await _post(session, base, "toy", npy(8))
            assert status == 503 and b"draining" in body
            assert int(headers["Retry-After"]) >= 1
            async with session.get(f"{base}/healthz") as r:
                assert r.status == 503
                assert (await r.json())["status"] == "draining"
        finally:
            state.draining = False

    run(go())


# ---------------------------------------------------------------------------
# worker_crash: degradation and supervised recovery (own fleet — destructive)
# ---------------------------------------------------------------------------

def test_worker_crash_degrades_then_respawns(loop):
    """worker_crash os._exits a worker mid-request (native-crash stand-in).
    With every worker down the front door answers fast 503s whose
    Retry-After comes from the live respawn backoff — lost capacity, never
    lost availability (no hang, no connection error) — and the supervisor
    respawns the fleet back to health within its backoff budget."""
    import aiohttp
    from aiohttp import web

    from tpuserve.workerproc.router import RouterState, make_router_app

    cfg = ServerConfig(
        decode_threads=2, startup_canary=False, drain_timeout_s=3.0,
        router=RouterConfig(enabled=True, workers=2, retry_max=2,
                            health_interval_s=0.2, unhealthy_after=2,
                            respawn_initial_s=0.3, respawn_max_s=2.0),
        models=[
            _toy("toy"),
            _toy("toyboom"),
        ],
        faults=FaultsConfig(enabled=True, rules=[
            # One shot per PROCESS: the first toyboom request each worker
            # sees kills that worker.
            FaultRuleConfig(kind="worker_crash", model="toyboom", count=1),
        ]),
    )
    state = RouterState(cfg)
    runner = web.AppRunner(make_router_app(state), access_log=None)

    async def go():
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        base = f"http://127.0.0.1:{runner.addresses[0][1]}"
        async with aiohttp.ClientSession() as session:
            try:
                # Crashes worker 1 (transport error), retries onto worker 2,
                # which crashes too: the whole fleet is down. The answer
                # must still be a FAST, clean 503.
                t0 = time.perf_counter()
                status, body, headers = await _post(
                    session, base, "toyboom", npy(9), total=30.0)
                elapsed = time.perf_counter() - t0
                assert status == 503, body
                assert int(headers["Retry-After"]) >= 1
                assert elapsed < 10.0, elapsed
                # Detection is asynchronous (health probes / watchdog
                # sweep), so poll rather than assert instantly.
                deadline = time.monotonic() + 5.0
                while (state.supervisor.deaths_total < 2
                       and time.monotonic() < deadline):
                    await asyncio.sleep(0.1)
                assert state.supervisor.deaths_total >= 2

                # Supervised recovery: both slots respawn (backoff 0.3 s +
                # boot) and the fleet serves again.
                deadline = time.monotonic() + 90.0
                while time.monotonic() < deadline:
                    async with session.get(f"{base}/healthz") as r:
                        health = await r.json()
                    if r.status == 200 and health["status"] == "ok":
                        break
                    await asyncio.sleep(0.2)
                assert health["status"] == "ok", health
                status, body, _ = await _post(session, base, "toy", npy(10))
                assert status == 200, body

                async with session.get(f"{base}/metrics") as r:
                    m = _parse_metrics(await r.text())
                respawns = (m.get('worker_respawns_total{worker="0"}', 0.0)
                            + m.get('worker_respawns_total{worker="1"}', 0.0))
                assert respawns >= 2, m
            finally:
                await runner.cleanup()

    loop.run_until_complete(go())


def test_trace_propagates_across_router_worker_hop(fleet):
    """ISSUE 12: one trace id end-to-end — the response header, the router
    /debug/slow reservoir, and a stitched /debug/trace whose span tree
    crosses the process boundary (router spans on pid 0, worker spans on
    pid = worker id + 1, the worker's root parented under the router's
    attempt span)."""
    import json

    run, session, base, state = fleet

    async def go():
        # toylag's worker_slow fault (300 ms) makes this the slowest toylag
        # request by far — guaranteed into both recorders' slow reservoirs.
        status, body, headers = await _post(session, base, "toylag", npy(91))
        assert status == 200, body
        tid = headers["X-Trace-Id"]
        assert len(tid) == 32 and int(tid, 16) >= 0

        async with session.get(f"{base}/debug/slow") as r:
            assert r.status == 200
            dump = await r.json()
        lag_ids = {rec["trace_id"] for rec in dump["slow"].get("toylag", [])}
        assert tid in lag_ids, sorted(dump["slow"])

        async with session.get(f"{base}/debug/trace?trace_id={tid}") as r:
            assert r.status == 200
            data = json.loads(await r.text())
        evs = data["traceEvents"]
        assert evs and all(e["args"]["trace_id"] == tid for e in evs)
        by_pid: dict = {}
        for e in evs:
            by_pid.setdefault(e["pid"], set()).add(e["name"])
        # Router lane: the root request span + at least one relay attempt.
        assert {"request", "attempt"} <= by_pid[0], by_pid
        # Worker lane(s): the full single-process serving tree.
        worker_pids = [p for p in by_pid if p >= 1]
        assert worker_pids, by_pid
        worker_names = set().union(*(by_pid[p] for p in worker_pids))
        assert {"request", "body_read", "queue", "compute"} <= worker_names

        # Raw record form: the worker's root span parents under the
        # router's attempt span (the X-Parent-Span relay).
        async with session.get(
                f"{base}/debug/trace?trace_id={tid}&format=record") as r:
            rec = await r.json()
        spans = rec["spans"]
        attempts = {s["span_id"] for s in spans if s["name"] == "attempt"}
        worker_roots = [s for s in spans
                        if s["name"] == "request" and s["pid"] >= 1]
        assert worker_roots
        assert all(s["parent_id"] in attempts for s in worker_roots)
        assert "router" in rec["sources"] and len(rec["sources"]) >= 2

    run(go())


def test_router_error_bodies_carry_trace_id(fleet):
    """Error paths across the tier: a router-side 404 and a worker-side
    504 both answer with trace_id in the JSON body matching X-Trace-Id —
    and the relayed 504's id is the ONE id the router stamped (the worker
    adopted it, never minted its own)."""
    import json

    run, session, base, state = fleet

    async def go():
        status, body, headers = await _post(session, base, "ghost", npy(1))
        assert status == 404
        js = json.loads(body)
        assert js["trace_id"] == headers["X-Trace-Id"]

        # slow_compute (600 ms) vs a 250 ms deadline: 504s inside the
        # worker; the body the client sees was built by the WORKER with
        # the router-minted trace id.
        status, body, headers = await _post(session, base, "toyslow",
                                            npy(92), timeout_ms=250)
        assert status == 504, body
        js = json.loads(body)
        assert js.get("trace_id") == headers["X-Trace-Id"], js
        # Errored request retained by the router's flight recorder.
        assert state.recorder.get(headers["X-Trace-Id"]) is not None

    run(go())


# ---------------------------------------------------------------------------
# Stream termination reasons (TPS404 contract)
# ---------------------------------------------------------------------------

def test_stream_error_terminal_encodings():
    """_stream_error_bytes builds the terminal the router appends when the
    worker no longer can — SSE error event for text streams, a KIND_EVENT
    frame for binary — naming the reason ("idle_timeout",
    "upstream_error") that router_stream_terminated_total keys on."""
    import json

    from tpuserve import frame
    from tpuserve.workerproc.router import _stream_error_bytes

    sse = _stream_error_bytes("text/event-stream", "idle_timeout",
                              "no bytes for 5000 ms")
    assert sse.startswith(b"event: error\ndata: ")
    assert sse.endswith(b"\n\n")
    assert json.loads(sse.split(b"data: ", 1)[1]) == {
        "error": "idle_timeout", "message": "no bytes for 5000 ms"}

    raw = _stream_error_bytes(frame.CONTENT_TYPE, "upstream_error",
                              "worker died")
    events = list(frame.StreamFrameReader().feed(raw))
    assert len(events) == 1
    payload = json.loads(events[0][1])
    assert payload == {"type": "error", "error": "upstream_error",
                       "message": "worker died"}


def test_router_termination_vocabulary_is_closed():
    """The router's stream-termination counter is guarded by the closed
    ROUTER_STREAM_REASONS vocabulary: "client_disconnect" and friends
    tick; an off-list reason raises instead of minting a new label."""
    import types

    from tpuserve.obs import ROUTER_STREAM_REASONS, Metrics
    from tpuserve.workerproc.router import RouterState

    dummy = types.SimpleNamespace(metrics=Metrics())
    for reason in ROUTER_STREAM_REASONS:
        RouterState._count_stream_termination(dummy, "toy", reason)
    assert dummy.metrics.counter(
        "router_stream_terminated_total{model=toy,"
        "reason=client_disconnect}").value == 1
    with pytest.raises(ValueError, match="unknown stream-termination"):
        RouterState._count_stream_termination(dummy, "toy", "freestyle")
