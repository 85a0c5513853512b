"""Multi-host seam: host-major mesh grid, DistributedConfig, real
single-process jax.distributed.initialize (SURVEY.md §5 "Distributed comm
backend").

Real multi-host needs multiple processes; what IS testable here: the grid
layout math on stub devices with fake process_index values (the property that
tp/sp blocks never cross a host), config plumbing, the no-op path, and — in a
subprocess, so this process's backend stays untouched — an actual
jax.distributed.initialize handshake with num_processes=1.
"""

import os
import subprocess
import sys
from dataclasses import dataclass
from pathlib import Path

import pytest

from tpuserve.config import DistributedConfig, load_config
from tpuserve.parallel import host_major_grid, init_distributed, make_mesh
from tpuserve.parallel.mesh import MeshPlan


@dataclass(frozen=True)
class FakeDev:
    id: int
    process_index: int


def _devs(n_hosts: int, per_host: int) -> list[FakeDev]:
    return [FakeDev(id=h * per_host + i, process_index=h)
            for h in range(n_hosts) for i in range(per_host)]


def test_grid_single_host_is_plain_reshape():
    devs = _devs(1, 8)
    grid = host_major_grid(devs, dp=2, tp=2, sp=2)
    assert grid.shape == (2, 2, 2)
    assert [d.id for d in grid.reshape(-1)] == list(range(8))


def test_grid_tp_sp_blocks_stay_on_one_host():
    # 4 hosts x 4 devices, tp=2 sp=2 -> each dp row must be one host's block.
    devs = _devs(4, 4)
    grid = host_major_grid(devs, dp=4, tp=2, sp=2)
    for dp_row in grid:
        hosts = {d.process_index for d in dp_row.reshape(-1)}
        assert len(hosts) == 1, f"tp/sp block crosses hosts: {hosts}"


def test_grid_data_axis_is_host_major():
    devs = _devs(2, 8)  # 2 hosts x 8 -> dp=4 with tp=2 sp=2
    grid = host_major_grid(devs, dp=4, tp=2, sp=2)
    row_hosts = [grid[i, 0, 0].process_index for i in range(4)]
    assert row_hosts == sorted(row_hosts), "data axis must walk hosts in rank order"


def test_grid_rejects_tp_sp_crossing_dcn():
    devs = _devs(4, 2)  # 2 devices per host cannot hold tp*sp=4
    with pytest.raises(ValueError, match="must divide each host"):
        host_major_grid(devs, dp=2, tp=2, sp=2)


def test_grid_rejects_ragged_hosts():
    devs = _devs(2, 4) + [FakeDev(id=99, process_index=2)]
    with pytest.raises(ValueError, match="unequal"):
        host_major_grid(devs, dp=9, tp=1, sp=1)


def test_make_mesh_still_builds_on_real_fake_devices():
    # The host-major path is the identity for single-host: existing meshes
    # (8 fake CPU devices, all process_index 0) keep working.
    mesh = make_mesh(MeshPlan(tp=2, sp=2))
    assert dict(mesh.shape) == {"data": 2, "model": 2, "seq": 2}


def test_init_distributed_disabled_is_noop():
    assert init_distributed(DistributedConfig()) is False


def test_distributed_config_from_toml(tmp_path):
    p = tmp_path / "c.toml"
    p.write_text(
        'port = 9999\n\n[distributed]\ncoordinator_address = "10.0.0.1:8476"\n'
        "num_processes = 4\nprocess_id = 2\n"
    )
    cfg = load_config(str(p))
    assert cfg.distributed.coordinator_address == "10.0.0.1:8476"
    assert cfg.distributed.num_processes == 4
    assert cfg.distributed.process_id == 2
    # default stays disabled
    assert load_config(None).distributed.coordinator_address == ""


def _cpu_subprocess_env(fake_devices: int | None = None) -> dict:
    """Env for child JAX processes that must stay on fake CPU devices."""
    repo_root = str(Path(__file__).resolve().parents[1])
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", "")}
    if fake_devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={fake_devices}"
    return env


@pytest.mark.slow
def test_two_process_collectives_across_the_dcn_seam():
    """The real thing, minus the hardware: two OS processes (4 fake CPU
    devices each) form one 8-device jax.distributed cluster through
    init_distributed, build the host-major (data, model, seq) mesh, and a
    jitted global reduction crosses the process boundary — the exact
    topology a 2-host TPU pod serves with, DCN seam included."""
    port = 17000 + os.getpid() % 2000
    code = (
        "import sys\n"
        "rank = int(sys.argv[1])\n"
        "import jax, jax.numpy as jnp\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "from tpuserve.config import DistributedConfig\n"
        "from tpuserve.parallel import init_distributed, make_mesh, process_info\n"
        "from tpuserve.parallel.mesh import MeshPlan\n"
        f"cfg = DistributedConfig(coordinator_address='127.0.0.1:{port}',"
        " num_processes=2, process_id=rank)\n"
        "assert init_distributed(cfg) is True\n"
        "info = process_info()\n"
        "assert (info['process_count'], info['global_devices']) == (2, 8), info\n"
        "mesh = make_mesh(MeshPlan(tp=2))\n"
        "for block in mesh.devices.reshape(-1, 2):\n"
        "    hosts = {d.process_index for d in block}\n"
        "    assert len(hosts) == 1, f'tp block crosses hosts: {hosts}'\n"
        "sh = NamedSharding(mesh, P('data'))\n"
        "y = jax.jit(lambda: jnp.arange(8.0), out_shardings=sh)()\n"
        "total = jax.jit(jnp.sum)(y)  # cross-process (DCN-seam) reduction\n"
        "print(f'RANK{rank} OK total={float(total)} "
        "hosts={len(set(d.process_index for d in jax.devices()))}')\n"
    )
    env = _cpu_subprocess_env(fake_devices=4)
    # File-backed output: draining two interdependent children through pipes
    # sequentially can deadlock on a full pipe buffer mid-handshake.
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        logs = [open(f"{td}/rank{r}.log", "w+") for r in range(2)]
        procs = [subprocess.Popen([sys.executable, "-c", code, str(r)],
                                  stdout=logs[r], stderr=subprocess.STDOUT,
                                  text=True, env=env) for r in range(2)]
        try:
            for p in procs:
                p.wait(timeout=180)
        finally:
            for p in procs:  # reap stragglers so no orphan holds the port
                if p.poll() is None:
                    p.kill()
                    p.wait()
            outs = []
            for lg in logs:
                lg.seek(0)
                outs.append(lg.read())
                lg.close()
        for r, out in enumerate(outs):
            assert f"RANK{r} OK total=28.0 hosts=2" in out, (r, out[-2000:])


@pytest.mark.slow
def test_real_initialize_single_process_subprocess():
    """jax.distributed.initialize actually handshakes (1-process cluster).

    Runs in a subprocess because initialize() must precede backend init and
    this test process's backend is already up.
    """
    port = 18000 + os.getpid() % 2000  # avoid collisions across parallel runs
    code = (
        "import jax\n"
        "from tpuserve.config import DistributedConfig\n"
        "from tpuserve.parallel import init_distributed, process_info\n"
        f"cfg = DistributedConfig(coordinator_address='127.0.0.1:{port}',"
        " num_processes=1, process_id=0)\n"
        "assert init_distributed(cfg) is True\n"
        "info = process_info()\n"
        "assert info['process_count'] == 1, info\n"
        "assert info['global_devices'] >= 1, info\n"
        "print('DIST_OK')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120, env=_cpu_subprocess_env(),
    )
    assert "DIST_OK" in out.stdout, out.stderr[-2000:]


def test_process_info_single_host_facts():
    """ISSUE 13 satellite: process_info() is the multi-machine seam's
    introspection — exercised BEFORE anyone needs a pod. Single-process:
    rank 0 of 1, local == global devices, a real platform string."""
    from tpuserve.parallel import process_info

    info = process_info()
    assert info["process_index"] == 0
    assert info["process_count"] == 1
    assert info["global_devices"] == info["local_devices"] >= 1
    assert info["platform"] in ("cpu", "tpu", "gpu")


def test_init_distributed_pins_only_explicit_coordinates(monkeypatch):
    """init_distributed forwards exactly the coordinates the config pins:
    -1 means 'let jax read the cluster environment' and must NOT be
    passed through."""
    import tpuserve.parallel.distributed as dist

    calls = []
    monkeypatch.setattr(dist.jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    monkeypatch.setattr(dist.jax, "process_index", lambda: 0)
    monkeypatch.setattr(dist.jax, "process_count", lambda: 1)

    assert dist.init_distributed(
        DistributedConfig(coordinator_address="h:1")) is True
    assert calls[-1] == {"coordinator_address": "h:1"}

    assert dist.init_distributed(DistributedConfig(
        coordinator_address="h:1", num_processes=4, process_id=2)) is True
    assert calls[-1] == {"coordinator_address": "h:1",
                         "num_processes": 4, "process_id": 2}


def test_stats_topology_block_over_http():
    """ISSUE 13 satellite: process_info() is wired into the server's
    /stats as the `topology` block, so every worker behind the router tier
    reports its process coordinates next to its serving state."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from tpuserve.config import ModelConfig, ServerConfig
    from tpuserve.server import ServerState, make_app

    cfg = ServerConfig(
        models=[ModelConfig(name="toy", family="toy", batch_buckets=[1],
                            deadline_ms=2.0, dtype="float32", num_classes=10,
                            parallelism="single")],
        decode_threads=2, startup_canary=False)
    state = ServerState(cfg)
    state.build()
    state.worker_id = 7  # what worker_main stamps behind the router tier

    async def go():
        client = TestClient(TestServer(make_app(state)))
        await client.start_server()
        try:
            resp = await client.get("/stats")
            assert resp.status == 200
            topo = (await resp.json())["topology"]
            assert topo["process_index"] == 0
            assert topo["process_count"] == 1
            assert topo["worker_id"] == 7
            assert topo["distributed"] is False
            assert topo["platform"] in ("cpu", "tpu", "gpu")
        finally:
            await client.close()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    finally:
        loop.close()
