"""Worker supervision: spawn, health-check, reap, respawn with backoff.

The supervisor owns N worker slots. Each slot holds one worker process
(a full loopback-bound tpuserve server, ``tpuserve.workerproc.worker``) or
is empty while a respawn is pending. Three loops keep the fleet honest:

- **Process liveness** — ``sweep()`` is registered with the router's
  Watchdog (extending PR 1's revive machinery to whole processes): a slot
  whose process exited any way other than supervisor stop is reaped and
  scheduled for respawn, counted in
  ``watchdog_restarts_total{model=_router,component=worker}``.
- **HTTP health** — an async probe loop GETs each worker's ``/healthz`` on
  ``health_interval_s``; ``unhealthy_after`` consecutive bad probes route
  traffic around a live-but-wedged worker without killing it (it may be
  draining, compiling, or briefly overloaded).
- **Respawn with exponential backoff** — a dead slot respawns after
  ``min(respawn_max_s, respawn_initial_s * respawn_multiplier^fails)``;
  a successful boot resets the slot's failure count. A crash-looping
  worker therefore converges to one (cheap) boot attempt per
  ``respawn_max_s`` instead of a fork bomb, and ``respawn_eta_s()`` gives
  the router an honest ``Retry-After`` when no worker is healthy.

Thread/loop ownership: every roster field is mutated on the event loop
only; the blocking parts of a spawn (``Process.start`` + the ready-pipe
handshake) run on executor threads and hand the finished handle back to
the loop. There is deliberately no lock to witness.

Workers are daemonic: if the router process itself is SIGKILLed (no drain
path runs), the children are torn down by the interpreter instead of being
orphaned on loopback ports.
"""

from __future__ import annotations

import asyncio
import logging
import multiprocessing as mp
import time

from tpuserve.config import ServerConfig
from tpuserve.obs import Metrics
from tpuserve.workerproc.worker import worker_config, worker_main

log = logging.getLogger("tpuserve.workerproc")


class WorkerHandle:
    """Supervisor-side handle for one live worker process."""

    __slots__ = ("wid", "proc", "conn", "port", "pid", "base_url",
                 "healthy", "health_fails", "inflight", "picked_seq",
                 "started_at", "host")

    def __init__(self, wid: int, proc, conn, port: int, pid: int,
                 host: str) -> None:
        self.wid = wid
        self.proc = proc
        self.conn = conn
        self.port = port
        self.pid = pid
        self.base_url = f"http://{host}:{port}"
        # Healthy until probed otherwise: the ready handshake proves the
        # listener is up, which is a stronger signal than one HTTP probe.
        self.healthy = True
        self.health_fails = 0
        self.inflight = 0
        self.picked_seq = 0
        self.started_at = time.monotonic()
        # Failure-domain id. The flat supervisor has no host layer: every
        # worker is its own domain (host-aware hedging degrades to the
        # PR-8 different-worker rule). HostSupervisor's refs carry a real
        # host id here (tpuserve.workerproc.hosts).
        self.host: int | None = None

    def close(self) -> None:
        try:
            self.conn.close()
        except OSError:
            pass


def spawn_worker_blocking(wcfg, wid: int, spawn_timeout_s: float):
    """Spawn one worker process and wait for its ready handshake. Blocking
    (Process.start + the pipe poll) — call from an executor thread in the
    router, or from the host agent's own process (tpuserve.workerproc.hosts,
    which runs the same handshake one level down).

    Returns ``(proc, parent_conn, port, pid)``; raises on boot failure with
    the child killed and the pipe closed."""
    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe()
    proc = ctx.Process(target=worker_main, args=(wcfg, wid, child),
                       daemon=True, name=f"tpuserve-worker-{wid}")
    proc.start()
    child.close()
    try:
        if not parent.poll(spawn_timeout_s):
            raise TimeoutError(
                f"worker {wid} not ready after {spawn_timeout_s:.0f}s")
        msg = parent.recv()
        if msg.get("op") != "ready":
            raise RuntimeError(f"worker {wid} failed at boot: {msg}")
    except BaseException:
        if proc.is_alive():
            proc.kill()
        proc.join(5.0)
        parent.close()
        raise
    return proc, parent, int(msg["port"]), int(msg.get("pid", proc.pid))


class WorkerSupervisor:
    """Owns the worker fleet for one router process.

    ``postmortems`` (ISSUE 15): when the router's event plane is on, every
    reaped worker death is folded into a forensics record — exit
    code/signal, the slot's stderr-capture tail, and its last black-box
    snapshot — on an executor thread (the file reads must not block the
    loop the sweep runs on)."""

    def __init__(self, cfg: ServerConfig, metrics: Metrics,
                 postmortems=None) -> None:
        self.cfg = cfg
        self.rcfg = cfg.router
        self.metrics = metrics
        self.postmortems = postmortems
        self.n = cfg.router.workers
        # Derived once so every respawn serves an identical config.
        self._worker_cfgs = [worker_config(cfg, i) for i in range(self.n)]
        self.slots: list[WorkerHandle | None] = [None] * self.n
        self._fails = [0] * self.n          # consecutive failed boots
        self._next_up_at = [0.0] * self.n   # respawn ETA (monotonic)
        self._respawning: set[int] = set()
        self._bg: set[asyncio.Task] = set()
        self._health_task: asyncio.Task | None = None
        self._session = None  # aiohttp.ClientSession for health probes
        self._stopping = False
        self._pick_seq = 0
        self.deaths_total = 0
        # Prebound per-slot metrics (never formatted per probe/pick).
        self._g_up = [metrics.worker_up_gauge(i) for i in range(self.n)]
        self._g_backoff = [metrics.worker_backoff_gauge(i)
                           for i in range(self.n)]
        self._g_inflight = [metrics.worker_inflight_gauge(i)
                            for i in range(self.n)]
        self._c_respawns = [metrics.worker_respawns_counter(i)
                            for i in range(self.n)]

    # -- lifecycle -----------------------------------------------------------
    async def start(self) -> None:
        """Spawn the fleet and start the health loop. The first worker
        boots alone so it populates the persistent compile cache and the
        rest (and every future respawn) hit it."""
        import aiohttp

        loop = asyncio.get_running_loop()
        self._session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(
                total=self.rcfg.health_timeout_ms / 1e3))
        first_alone = self.n > 1
        rest = range(self.n)
        if first_alone:
            self.slots[0] = await loop.run_in_executor(
                None, self._spawn_blocking, 0)
            self._g_up[0].set(1.0)
            rest = range(1, self.n)
        spawned = await asyncio.gather(
            *(loop.run_in_executor(None, self._spawn_blocking, i)
              for i in rest))
        for h in spawned:
            self.slots[h.wid] = h
            self._g_up[h.wid].set(1.0)
        self._health_task = loop.create_task(self._health_loop())
        log.info("worker fleet up: %s",
                 [f"{h.wid}@{h.port}" for h in self.slots if h])

    def _spawn_blocking(self, wid: int) -> WorkerHandle:
        """Spawn one worker and wait for its ready handshake (executor
        thread — Process.start and the pipe poll both block)."""
        proc, parent, port, pid = spawn_worker_blocking(
            self._worker_cfgs[wid], wid, self.rcfg.spawn_timeout_s)
        if self._stopping:
            # The supervisor stopped while this spawn was in flight on its
            # executor thread (the awaiting task was cancelled, so nobody
            # will adopt the handle): tear the fresh worker down instead of
            # orphaning a live server on a loopback port.
            proc.kill()
            proc.join(5.0)
            parent.close()
            raise RuntimeError(f"supervisor stopping; discarded worker {wid}")
        return WorkerHandle(wid, proc, parent, port, pid,
                            self.cfg.worker.host)

    async def stop(self, drain: bool = True) -> None:
        """SIGTERM the fleet and wait for graceful exits (each worker runs
        its own accepted-work drain), then SIGKILL stragglers. The router
        sequences this AFTER it stopped admitting and its in-flight relays
        resolved, so the cross-process drain drops zero accepted requests."""
        self._stopping = True
        if self._health_task is not None:
            self._health_task.cancel()
            try:
                await self._health_task
            except asyncio.CancelledError:
                pass
            self._health_task = None
        for t in list(self._bg):
            t.cancel()
        if self._bg:
            await asyncio.gather(*self._bg, return_exceptions=True)
        live = [h for h in self.slots if h is not None and h.proc.is_alive()]
        for h in live:
            h.proc.terminate()
        budget = self.cfg.drain_timeout_s if drain else 2.0
        deadline = time.monotonic() + budget
        while any(h.proc.is_alive() for h in live) \
                and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        killed = 0
        for h in live:
            if h.proc.is_alive():
                h.proc.kill()
                killed += 1
        if killed:
            log.warning("%d worker(s) outlived the %.1fs drain budget and "
                        "were killed", killed, budget)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self._join_all, live)
        for i, h in enumerate(self.slots):
            if h is not None:
                h.close()
            self._g_up[i].set(0.0)
        if self._session is not None:
            await self._session.close()
            self._session = None

    @staticmethod
    def _join_all(handles: list[WorkerHandle]) -> None:
        for h in handles:
            h.proc.join(10.0)

    # -- liveness / health ---------------------------------------------------
    def sweep(self) -> int:
        """Watchdog hook (event loop, non-blocking): reap worker slots
        whose process exited and schedule their backoff respawns. Returns
        how many newly-dead workers were found — these are real failures
        (supervisor stop goes through stop(), not here)."""
        if self._stopping:
            return 0
        died = 0
        for i, h in enumerate(self.slots):
            if h is not None and not h.proc.is_alive():
                died += 1
                self._on_dead(i, h, f"process exited (code {h.proc.exitcode})")
        return died

    def _on_dead(self, wid: int, h: WorkerHandle, why: str) -> None:
        log.error("worker %d (pid %d) died: %s", wid, h.pid, why)
        self.deaths_total += 1
        self._schedule_postmortem(wid, h)
        h.close()
        self.slots[wid] = None
        self._g_up[wid].set(0.0)
        self._g_inflight[wid].set(0.0)
        self._schedule_respawn(wid)

    def _schedule_postmortem(self, wid: int, h: WorkerHandle) -> None:
        """Fold the dead worker's black box into a postmortem record on an
        executor thread (sweep/_on_dead run on the event loop and must not
        read files there). The capture races the eventual respawn's boot
        banner by the whole backoff window, so the tail it reads is the
        dead incarnation's."""
        if self.postmortems is None:
            return
        ecfg = self._worker_cfgs[wid].events
        exitcode = h.proc.exitcode
        loop = asyncio.get_running_loop()

        async def _capture() -> None:
            await loop.run_in_executor(
                None, lambda: self.postmortems.capture_blocking(
                    "worker", f"worker{wid}", h.pid, exitcode,
                    stderr_path=ecfg.stderr_path or None,
                    snapshot_path=ecfg.snapshot_path or None,
                    worker=wid))

        t = loop.create_task(_capture())
        self._bg.add(t)
        t.add_done_callback(self._bg.discard)

    def _schedule_respawn(self, wid: int) -> None:
        if self._stopping or wid in self._respawning:
            return
        self._respawning.add(wid)
        t = asyncio.get_running_loop().create_task(self._respawn(wid))
        self._bg.add(t)
        t.add_done_callback(self._bg.discard)

    async def _respawn(self, wid: int) -> None:
        """Respawn one slot with exponential backoff until it boots or the
        supervisor stops; a successful boot resets the slot's failure
        count."""
        loop = asyncio.get_running_loop()
        try:
            while not self._stopping:
                delay = min(self.rcfg.respawn_max_s,
                            self.rcfg.respawn_initial_s
                            * self.rcfg.respawn_multiplier ** self._fails[wid])
                self._g_backoff[wid].set(delay)
                self._next_up_at[wid] = time.monotonic() + delay
                await asyncio.sleep(delay)
                if self._stopping:
                    return
                try:
                    h = await loop.run_in_executor(
                        None, self._spawn_blocking, wid)
                except Exception:
                    self._fails[wid] += 1
                    log.exception("worker %d respawn failed (consecutive "
                                  "failures: %d)", wid, self._fails[wid])
                    continue
                self.slots[wid] = h
                self._fails[wid] = 0
                self._g_backoff[wid].set(0.0)
                self._g_up[wid].set(1.0)
                self._c_respawns[wid].inc()
                log.info("worker %d respawned (pid %d, port %d)",
                         wid, h.pid, h.port)
                return
        except asyncio.CancelledError:
            raise
        finally:
            self._respawning.discard(wid)

    async def _health_loop(self) -> None:
        while True:
            await asyncio.sleep(self.rcfg.health_interval_s)
            try:
                await self._probe_all()
            except asyncio.CancelledError:
                raise
            except Exception:  # one bad cycle must not end health checking
                log.exception("worker health probe cycle failed")

    async def _probe_all(self) -> None:
        # Liveness first (no HTTP needed to notice a corpse), then the
        # probes run concurrently so one slow worker can't stale the rest.
        for i, h in enumerate(self.slots):
            if h is not None and not h.proc.is_alive():
                self._on_dead(i, h, f"process exited (code {h.proc.exitcode})")
        await asyncio.gather(
            *(self._probe(h) for h in self.slots if h is not None))

    async def _probe(self, h: WorkerHandle) -> None:
        try:
            async with self._session.get(f"{h.base_url}/healthz") as r:
                ok = r.status == 200
                await r.read()
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — refused/reset/timeout all count
            ok = False
        if ok:
            if not h.healthy:
                log.info("worker %d healthy again", h.wid)
            h.health_fails = 0
            h.healthy = True
        else:
            h.health_fails += 1
            if h.healthy and h.health_fails >= self.rcfg.unhealthy_after:
                log.warning("worker %d unhealthy after %d failed probes — "
                            "routing around it", h.wid, h.health_fails)
                h.healthy = False
        self._g_up[h.wid].set(1.0 if h.healthy else 0.0)

    # -- routing -------------------------------------------------------------
    def healthy_workers(self) -> list[WorkerHandle]:
        return [h for h in self.slots if h is not None and h.healthy]

    def live_workers(self) -> list[WorkerHandle]:
        """Every slot with a live process — admin fan-outs must reach
        unhealthy-but-alive workers too, or the fleet's versions diverge."""
        return [h for h in self.slots
                if h is not None and h.proc.is_alive()]

    def worker_by_id(self, wid: int) -> WorkerHandle | None:
        if not 0 <= wid < self.n:
            return None
        return self.slots[wid]

    def down_domains(self) -> list[str]:
        """Failure domains currently dead/respawning — a fleet-wide reload
        must refuse while any exists (a dead slot respawns from the boot
        config and would diverge from a freshly published version)."""
        return [f"worker{i}" for i, h in enumerate(self.slots)
                if h is None or not h.proc.is_alive()]

    def host_of(self, h: WorkerHandle) -> int | None:
        return h.host

    def note_transport_failure(self, h: WorkerHandle) -> None:
        """Host-breaker food (tpuserve.workerproc.hosts). The flat
        supervisor has no host layer: health probes + retry already route
        around a dead worker, so this is a no-op."""

    def note_success(self, h: WorkerHandle) -> None:
        pass

    def pick(self, exclude: set[int] = frozenset(),
             exclude_hosts: set[int] = frozenset()) -> WorkerHandle | None:
        """Least-loaded healthy worker not in ``exclude``; ties break to
        the least-recently-picked so equal load round-robins instead of
        piling onto slot 0. ``exclude_hosts`` is the host-aware hedging
        seam — with no host layer every worker's host is None, so the
        different-worker rule (``exclude``) is the whole constraint."""
        best: WorkerHandle | None = None
        for h in self.slots:
            if h is None or not h.healthy or h.wid in exclude:
                continue
            if h.host is not None and h.host in exclude_hosts:
                continue
            if best is None \
                    or (h.inflight, h.picked_seq) < (best.inflight,
                                                     best.picked_seq):
                best = h
        if best is not None:
            self._pick_seq += 1
            best.picked_seq = self._pick_seq
        return best

    def track_inflight(self, h: WorkerHandle, delta: int) -> None:
        h.inflight += delta
        self._g_inflight[h.wid].set(h.inflight)

    def respawn_eta_s(self) -> float:
        """Soonest respawn ETA across dead slots — the live Retry-After
        basis when no worker is healthy. Falls back to the health interval
        (the soonest a wedged-but-alive worker can be probed healthy)."""
        now = time.monotonic()
        etas = [max(0.0, self._next_up_at[i] - now)
                for i in self._respawning]
        if etas:
            return min(etas)
        return self.rcfg.health_interval_s

    # -- introspection -------------------------------------------------------
    def stats(self) -> dict:
        """The /stats ``workers`` block (docs/ROBUSTNESS.md)."""
        now = time.monotonic()
        rows = []
        for i in range(self.n):
            h = self.slots[i]
            if h is None:
                rows.append({
                    "worker": i,
                    "state": "respawning" if i in self._respawning
                    else "down",
                    "consecutive_boot_failures": self._fails[i],
                    "respawn_eta_s": round(
                        max(0.0, self._next_up_at[i] - now), 3),
                    "respawns_total": self._c_respawns[i].value,
                })
            else:
                rows.append({
                    "worker": i,
                    "state": "ready" if h.healthy else "unhealthy",
                    "pid": h.pid,
                    "port": h.port,
                    "inflight": h.inflight,
                    "health_fails": h.health_fails,
                    "uptime_s": round(now - h.started_at, 1),
                    "respawns_total": self._c_respawns[i].value,
                })
        return {
            "configured": self.n,
            "healthy": len(self.healthy_workers()),
            "deaths_total": self.deaths_total,
            "workers": rows,
        }
