"""Worker-process entry for the router split (docs/ROBUSTNESS.md).

A worker is deliberately NOT a new kind of server: it is the existing
single-process server (``tpuserve.server``) — batcher, hostpipe, runtime,
lifecycle, watchdog, graceful SIGTERM drain — built in its own process and
bound to loopback, so every property the single-process tests prove holds
unchanged behind the boundary. What the process split adds lives in the
supervisor and router, not here.

Differences from a standalone server, all applied to the config before
build:

- binds ``[worker] host`` (loopback) on ``port_base + id`` or an ephemeral
  port, and reports the bound port to the supervisor over a pipe handshake
  (``{"op": "ready", "port": ...}``);
- the result cache is forced OFF: caching + single-flight coalescing are
  router-owned (one shared cache beats N private ones, and a cached answer
  must survive the worker that computed it);
- ``[router]`` is forced off (a worker must never recurse into spawning
  its own workers).

Deadlines cross the boundary as REMAINING budget (the gRPC convention):
the router stamps the absolute deadline at admission and forwards
``X-Timeout-Ms`` = time left at dispatch, which the existing
``_requested_timeout_ms`` path re-stamps against this process's clock —
so a request 504s at the same absolute instant whether it dies in the
router, on the wire, or in here.

SIGTERM drains gracefully via ``serve_async`` exactly as a standalone
server does: stop admitting -> flush accepted -> exit. The supervisor
sequences this after the router itself stopped admitting, so a rolling
restart of the whole deployment drops zero accepted requests.
"""

from __future__ import annotations

import copy
import os
import time

from tpuserve.config import ServerConfig
from tpuserve.telemetry.events import redirect_stderr, resolve_blackbox_dir


def worker_config(cfg: ServerConfig, worker_id: int) -> ServerConfig:
    """Derive one worker's ServerConfig from the deployment config."""
    wcfg = copy.deepcopy(cfg)
    wcfg.host = cfg.worker.host
    wcfg.port = (cfg.worker.port_base + worker_id
                 if cfg.worker.port_base else 0)
    if cfg.worker.drain_timeout_s > 0:
        wcfg.drain_timeout_s = cfg.worker.drain_timeout_s
    # Router-owned layers never run in the worker. Tenancy admits at the
    # tier that fronts clients: the router resolves X-Api-Key once and
    # relays the tenant as the loopback X-Tenant header — a worker-side
    # ledger would 401 every relay (no key crosses the hop) and
    # double-charge the window.
    wcfg.router.enabled = False
    wcfg.cache.enabled = False
    wcfg.tenants.enabled = False
    wcfg.autopilot.enabled = False
    # Black box (ISSUE 15, docs/OBSERVABILITY.md "The third pillar"): the
    # supervisor resolves ONE black-box directory for the deployment
    # (stable across respawns — it runs in the supervisor's process) and
    # assigns the slot's stderr capture + postmortem-snapshot files. The
    # worker redirects its own fd 2 at spawn and checkpoints snapshots;
    # the supervisor reads both back at reap time.
    if cfg.events.enabled and not wcfg.events.stderr_path:
        bb = resolve_blackbox_dir(cfg.events)
        wcfg.events.dir = bb
        wcfg.events.stderr_path = os.path.join(
            bb, f"worker{worker_id}.stderr")
        wcfg.events.snapshot_path = os.path.join(
            bb, f"worker{worker_id}.snapshot.json")
    return wcfg


def worker_main(cfg: ServerConfig, worker_id: int, conn) -> None:
    """Process entry (multiprocessing spawn target).

    ``cfg`` is the WORKER config (worker_config already applied — the
    supervisor derives it once so every respawn serves identical config).
    ``conn`` carries the ready handshake; it stays open afterward purely so
    an EOF can tell this worker the supervisor vanished.
    """
    # Black box step 1 (ISSUE 15): redirect fd 2 to the slot's capture
    # file BEFORE any import can write to it — a native crash's abort
    # message, an OOM killer's aftermath, a Python traceback: all of it
    # lands in a file the supervisor folds into the postmortem instead of
    # interleaving onto the supervisor's tty and dying with the process.
    redirect_stderr(cfg.events.stderr_path,
                    f"worker {worker_id} boot pid {os.getpid()} "
                    f"ts {time.time():.3f}")
    import asyncio
    import logging

    from tpuserve.server import ServerState, configure_logging, serve_async

    configure_logging(cfg)
    logging.getLogger("tpuserve.workerproc").info(
        "worker %d: building models (pid %d)", worker_id, os.getpid())
    try:
        state = ServerState(cfg)
        state.worker_id = worker_id
        if state.injector is not None:
            # Worker-pinned [[faults.rule]] entries (rule.worker >= 0) only
            # fire in the matching worker process.
            state.injector.worker_id = worker_id
        if state.events is not None:
            # Events carry the same process-lane vocabulary as spans
            # (0 = router, worker id + 1 behind it) so a stitched trace's
            # interleaved events land on the right lane.
            state.events.pid = worker_id + 1
        state.build()
    except Exception as e:  # noqa: BLE001 — report any boot death upward
        try:
            conn.send({"op": "died", "error": f"{type(e).__name__}: {e}"})
        finally:
            conn.close()
        raise

    async def _serve() -> None:
        loop = asyncio.get_running_loop()
        ready = asyncio.Event()
        serve_task = loop.create_task(serve_async(state, ready))
        ready_task = loop.create_task(ready.wait())
        # First of: listener up (-> handshake) or an early serve failure
        # (port bind, startup canary) — the latter must surface as a
        # "died" message, not a supervisor handshake timeout.
        await asyncio.wait({serve_task, ready_task},
                           return_when=asyncio.FIRST_COMPLETED)
        if serve_task.done():
            ready_task.cancel()
            serve_task.result()  # raises the boot failure
            return
        conn.send({"op": "ready", "port": state.serving_addresses[0][1],
                   "pid": os.getpid()})
        await serve_task

    try:
        asyncio.run(_serve())
    except Exception as e:  # noqa: BLE001 — report any death upward
        try:
            conn.send({"op": "died", "error": f"{type(e).__name__}: {e}"})
        except (BrokenPipeError, OSError):
            pass
        raise
    finally:
        conn.close()
