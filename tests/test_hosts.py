"""Host failure domains (ISSUE 13): real host-agent subprocesses, each
owning its worker fleet in its own process group.

Layers of coverage, like test_router.py all against REAL processes:

- pure units: host-aware pick (hedge never lands on the primary's host),
  the host breaker trip/half-open machine, consistent wid -> host math;
- a module-scoped host fleet (2 hosts x 2 workers, toy model) proving the
  topology boots and serves, a SINGLE worker death is a HOST-local event
  (the agent respawns it; the router just learns the new port), and the
  tentpole sequence: killpg one entire host mid-serving -> requests keep
  answering on the survivor -> a fleet :reload is REFUSED 409 with
  per-host outcomes while the domain is down -> the host re-absorbs and
  a reload then succeeds fleet-wide.

No pytest-asyncio in the image: a module-level event loop drives
everything explicitly (the test_router idiom).
"""

import asyncio
import io
import os
import signal
import time

import numpy as np
import pytest

from tpuserve.config import ModelConfig, RouterConfig, ServerConfig

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
# Pure units (no processes spawned)
# ---------------------------------------------------------------------------

def _bare_supervisor(hosts=2, workers=2):
    """A HostSupervisor with hand-built refs and NO processes: pick() and
    the breaker never touch the agent handles' procs."""
    from tpuserve.obs import Metrics
    from tpuserve.workerproc.hosts import HostHandle, HostSupervisor, WorkerRef

    cfg = ServerConfig(
        models=[_toy("toy")],
        router=RouterConfig(enabled=True, workers=workers, hosts=hosts,
                            host_breaker_threshold=2,
                            host_breaker_cooldown_s=0.2))
    sup = HostSupervisor(cfg, Metrics(16))
    for hid in range(hosts):
        h = object.__new__(HostHandle)
        h.hid = hid
        h.pgid = h.pid = 1000 + hid
        h.proc = type("P", (), {"is_alive": lambda self: True})()
        h.conn = None
        h.workers = {}
        h.started_at = time.monotonic()
        for wid in sup._host_wids(hid):
            ref = WorkerRef(wid, hid, 9000 + wid, 2000 + wid, "127.0.0.1")
            h.workers[wid] = ref
            sup._refs[wid] = ref
        sup.hosts[hid] = h
    return sup


def test_pick_excludes_whole_hosts():
    """The hedge rule: pick(exclude_hosts={primary's host}) never returns a
    worker on that host, and returns None when every other domain is
    excluded — the relay then simply doesn't hedge."""
    sup = _bare_supervisor(hosts=2, workers=2)
    w = sup.pick(exclude_hosts={0})
    assert w is not None and w.host == 1
    assert sup.pick(exclude_hosts={0, 1}) is None
    # exclude wids composes with exclude_hosts
    other = sup.pick(exclude={w.wid}, exclude_hosts={0})
    assert other is not None and other.host == 1 and other.wid != w.wid


def test_pick_is_least_loaded_across_hosts():
    sup = _bare_supervisor(hosts=2, workers=2)
    for wid, ref in sup._refs.items():
        ref.inflight = 5 if ref.host == 0 else 1
    assert sup.pick().host == 1


def test_host_breaker_trips_and_half_opens():
    """threshold consecutive transport failures shed the WHOLE host from
    pick(); after the cooldown the next pick is the probe, and a success
    closes it."""
    sup = _bare_supervisor(hosts=2, workers=2)
    victim = sup.hosts[0].workers[0]
    assert not sup.host_tripped(0)
    sup.note_transport_failure(victim)
    assert not sup.host_tripped(0)  # threshold 2
    sup.note_transport_failure(victim)
    assert sup.host_tripped(0)
    assert all(w.host == 1 for w in [sup.pick() for _ in range(4)])
    time.sleep(0.25)  # cooldown 0.2
    assert not sup.host_tripped(0)  # half-open: picks allowed again
    # a new failure re-trips immediately (fails still >= threshold)...
    sup.note_transport_failure(victim)
    assert sup.host_tripped(0)
    # ...and a success closes it outright.
    sup.note_success(victim)
    assert not sup.host_tripped(0)
    assert {sup.pick(exclude={w.wid for w in sup.healthy_workers()
                              if w.host == 1}).host} == {0}


def test_down_domains_names_hosts_and_agent_respawns():
    from tpuserve.workerproc.hosts import host_name

    sup = _bare_supervisor(hosts=2, workers=2)

    class DeadProc:
        def is_alive(self):
            return False

    for h in sup.hosts:
        h.proc = type("P", (), {"is_alive": lambda self: True})()
    assert sup.down_domains() == []
    sup.hosts[1].proc = DeadProc()
    assert sup.down_domains() == [host_name(1)]
    # a worker the agent is re-booting is its own (sub-)domain
    sup.hosts[0].workers[1].up = False
    assert set(sup.down_domains()) == {host_name(1), "host0:worker1"}


# ---------------------------------------------------------------------------
# The host fleet (module-scoped: 2 real host agents x 2 real workers)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hostfleet(loop):
    import aiohttp
    from aiohttp import web

    from tpuserve.workerproc.router import RouterState, make_router_app

    cfg = ServerConfig(
        decode_threads=2, startup_canary=False, drain_timeout_s=3.0,
        watchdog_interval_s=0.2,
        router=RouterConfig(enabled=True, workers=2, hosts=2, retry_max=3,
                            hedge_ms=150.0, health_interval_s=0.2,
                            unhealthy_after=2, respawn_initial_s=0.3,
                            respawn_max_s=2.0),
        models=[_toy("toy")],
    )
    state = RouterState(cfg)
    runner = web.AppRunner(make_router_app(state), access_log=None)

    async def setup():
        await runner.setup()  # on_startup spawns agents + workers
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


async def _post(session, base, model, body, timeout_ms=None, total=30.0):
    import aiohttp

    params = {"timeout_ms": str(timeout_ms)} if timeout_ms else None
    async with session.post(f"{base}/v1/models/{model}:classify", data=body,
                            params=params, headers={"Content-Type": NPY},
                            timeout=aiohttp.ClientTimeout(total=total)) as r:
        return r.status, await r.read(), dict(r.headers)


async def _wait_health(session, base, want="ok", budget=60.0):
    deadline = time.monotonic() + budget
    health = {}
    while time.monotonic() < deadline:
        async with session.get(f"{base}/healthz") as r:
            health = await r.json()
        if health.get("status") == want:
            return health
        await asyncio.sleep(0.2)
    return health


def test_host_topology_boots_and_serves(hostfleet):
    run, session, base, state = hostfleet

    async def go():
        status, body, _ = await _post(session, base, "toy", npy(1))
        assert status == 200, body
        async with session.get(f"{base}/healthz") as r:
            health = await r.json()
            assert r.status == 200 and health["status"] == "ok"
        assert health["hosts"] == {"configured": 2, "up": 2}
        async with session.get(f"{base}/stats") as r:
            stats = await r.json()
        w = stats["workers"]
        assert w["configured"] == 4 and w["healthy"] == 4
        assert w["hosts_up"] == 2 and w["hosts_configured"] == 2
        assert [h["name"] for h in w["hosts"]] == ["host0", "host1"]
        assert all(h["state"] == "up" and len(h["workers"]) == 2
                   for h in w["hosts"])
        assert stats["topology"]["hosts_configured"] == 2
        assert stats["topology"]["workers_per_domain"] == 2
        async with session.get(f"{base}/metrics") as r:
            m = _parse_metrics(await r.text())
        assert m.get('host_up{host="0"}') == 1.0
        assert m.get('host_up{host="1"}') == 1.0
        for wid in range(4):
            assert m.get(f'worker_up{{worker="{wid}"}}') == 1.0
        # every worker is a REAL process on a live host; the global-wid
        # proxy reaches each one's own introspection endpoints
        async with session.get(f"{base}/workers/3/stats") as r:
            assert r.status == 200
            assert "pipeline" in await r.json()
        # workers report the topology seam on their own /stats (ISSUE 13
        # satellite: parallel/distributed.process_info wired in)
        async with session.get(f"{base}/workers/0/stats") as r:
            topo = (await r.json())["topology"]
        assert topo["process_count"] == 1 and topo["worker_id"] == 0
        assert topo["platform"] == "cpu"

    run(go())


def test_single_worker_death_is_host_local(hostfleet):
    """SIGKILL one WORKER (not its host): the host agent respawns it and
    reports the new port up the pipe; the host never goes down and the
    router keeps serving throughout."""
    run, session, base, state = hostfleet

    async def go():
        h0 = state.supervisor.hosts[0]
        victim = h0.workers[1]
        old_pid = victim.pid
        os.kill(old_pid, signal.SIGKILL)
        # serve across the death — the survivor fleet absorbs
        for i in range(10):
            status, body, _ = await _post(session, base, "toy", npy(100 + i))
            assert status == 200, body
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            ref = state.supervisor.hosts[0].workers.get(1)
            if ref is not None and ref.up and ref.pid != old_pid \
                    and ref.healthy:
                break
            await asyncio.sleep(0.1)
        ref = state.supervisor.hosts[0].workers[1]
        assert ref.pid != old_pid and ref.up, (ref.pid, old_pid)
        # the HOST never died: same agent, zero host respawns
        assert state.supervisor.hosts[0] is h0
        async with session.get(f"{base}/metrics") as r:
            m = _parse_metrics(await r.text())
        assert m.get('host_respawns_total{host="0"}', 0.0) == 0.0
        assert m.get('worker_respawns_total{worker="1"}') == 1.0
        # the respawned worker actually serves
        status, _, _ = await _post(session, base, "toy", npy(111))
        assert status == 200

    run(go())


def test_host_kill_degrades_then_reabsorbs(hostfleet):
    """The tentpole sequence, in-test scale: killpg one ENTIRE host (agent
    + both workers — one syscall, a machine death). Requests keep
    answering on the survivor host; a fleet :reload is refused 409 with
    per-host outcomes while the domain is down (degraded-fleet contract);
    /healthz says degraded but stays 200 (an LB must not pull the
    replica); the domain re-absorbs within the backoff budget and a
    reload then succeeds fleet-wide."""
    run, session, base, state = hostfleet

    async def go():
        victim = state.supervisor.hosts[0]
        pgid = victim.pgid
        os.killpg(pgid, signal.SIGKILL)

        # 1) availability through the kill: every request answers 200.
        for i in range(20):
            status, body, _ = await _post(session, base, "toy", npy(200 + i))
            assert status == 200, (i, status, body)

        # 2) degraded-fleet reload: FAST 409, per-host outcomes, nobody
        # touched — the fleet stays on one version.
        t0 = time.monotonic()
        async with session.post(f"{base}/admin/models/toy:reload") as r:
            info = await r.json()
            assert r.status == 409, info
        assert time.monotonic() - t0 < 5.0, "degraded reload must not hang"
        assert "host0" in info["down"], info
        assert "per_host" in info
        async with session.get(f"{base}/admin/models/toy/versions") as r:
            vers = await r.json()
        live = {w["live_version"] for w in vers["workers"].values()}
        assert len(live) == 1, vers  # survivors still on ONE version

        # 3) /healthz: degraded, not down.
        health = await _wait_health(session, base, want="degraded",
                                    budget=10.0)
        assert health["status"] == "degraded", health
        assert health["hosts"]["up"] == 1

        # 4) re-absorb: agent + both workers back, healthz ok again.
        health = await _wait_health(session, base, want="ok", budget=90.0)
        assert health["status"] == "ok", health
        assert health["hosts"] == {"configured": 2, "up": 2}
        async with session.get(f"{base}/metrics") as r:
            m = _parse_metrics(await r.text())
        assert m.get('host_respawns_total{host="0"}') == 1.0
        assert m.get('host_up{host="0"}') == 1.0
        assert state.supervisor.hosts[0].pgid != pgid
        assert state.supervisor.host_deaths_total == 1
        assert state.supervisor.deaths_total >= 2  # both workers went too

        # 5) the healed fleet reloads atomically, per-host outcomes green.
        async with session.post(f"{base}/admin/models/toy:reload") as r:
            info = await r.json()
            assert r.status == 200, info
        assert info["fleet_consistent"] is True
        assert sorted(info["per_host"]) == ["host0", "host1"]
        assert len(info["workers"]) == 4
        status, _, _ = await _post(session, base, "toy", npy(250))
        assert status == 200

    run(go())


def test_fleet_scrape_degrades_stale_never_500(hostfleet):
    """Fleet-aggregation degradation (ISSUE 14 satellite): a healthy
    scrape sums counters EXACTLY across workers; SIGKILLing an entire
    host mid-poll stale-marks that domain's sources in /metrics/fleet
    and /stats/fleet — never a 5xx — and after the PR-13 respawn the
    scrape is whole again."""
    from tpuserve.telemetry.fleet import sum_counter

    run, session, base, state = hostfleet

    async def scrape():
        async with session.get(f"{base}/metrics/fleet") as r:
            text = await r.text()
            assert r.status == 200, text  # the never-5xx contract
        async with session.get(f"{base}/stats/fleet") as r:
            rollup = await r.json()
            assert r.status == 200, rollup
        return text, rollup

    async def go():
        # 1) healthy fleet: serve some traffic, then prove exact summing.
        for i in range(8):
            status, body, _ = await _post(session, base, "toy", npy(300 + i))
            assert status == 200, body
        merged, rollup = await scrape()
        per_worker = 0.0
        for wid in range(4):
            async with session.get(f"{base}/workers/{wid}/metrics") as r:
                assert r.status == 200
                per_worker += sum_counter(await r.text(), "requests_total",
                                          'model="toy"')
        fleet_sum = sum_counter(merged, "requests_total", 'model="toy"')
        assert fleet_sum == per_worker > 0, (fleet_sum, per_worker)
        assert rollup["models"]["toy"]["requests_total"] == fleet_sum
        assert rollup["stale"] == [] and rollup["down_domains"] == []
        assert all(v == "up" for v in rollup["sources"].values())
        # gauges are per-process, worker_up stays the router's own
        assert 'proc="worker0"' in merged
        # true fleet latency quantiles from the merged buckets
        assert rollup["models"]["toy"]["fleet_latency_p99_ms"] is not None

        # 2) kill host 1 (agent + workers, one process group) mid-poll.
        victim = state.supervisor.hosts[1]
        os.killpg(victim.pgid, signal.SIGKILL)
        merged, rollup = await scrape()  # immediately: must not 5xx
        stale = set(rollup["stale"])
        assert {"worker2", "worker3"} <= stale, rollup
        assert 'fleet_source_up{proc="worker2"} 0' in merged
        assert "# STALE worker2" in merged
        # the survivor host's counters still merge
        assert sum_counter(merged, "requests_total", 'model="toy"') > 0
        # availability through the scrape window
        status, body, _ = await _post(session, base, "toy", npy(333))
        assert status == 200, body

        # 3) recover: the domain re-absorbs and the scrape is whole.
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            merged, rollup = await scrape()
            if not rollup["stale"] and not rollup["down_domains"]:
                break
            await asyncio.sleep(0.5)
        assert rollup["stale"] == [], rollup
        assert all(v == "up" for v in rollup["sources"].values())
        # Respawned workers restart their counters at 0 — the merged sum
        # is the CURRENT fleet truth, smaller than before the kill; the
        # reset-aware compensation lives in the history layer
        # (TimeSeriesStore), not in the instantaneous merge. The healed
        # fleet still serves and still sums.
        status, _, _ = await _post(session, base, "toy", npy(334))
        assert status == 200
        merged, _ = await scrape()
        assert sum_counter(merged, "requests_total", 'model="toy"') > 0

    run(go())


def test_retry_after_reflects_min_respawn_eta(hostfleet):
    """With hosts respawning, respawn_eta_s() is the MINIMUM ETA across
    dead domains — the honest Retry-After when the whole fleet is down."""
    run, session, base, state = hostfleet
    sup = state.supervisor

    async def go():
        # Healthy fleet: the fallback is the health interval.
        assert sup.respawn_eta_s() == pytest.approx(
            state.rcfg.health_interval_s)
        sup._respawning.add(0)
        sup._next_up_at[0] = time.monotonic() + 7.0
        sup._respawning.add(1)
        sup._next_up_at[1] = time.monotonic() + 3.0
        try:
            assert 2.0 < sup.respawn_eta_s() <= 3.0
        finally:
            sup._respawning.clear()

    run(go())
