#!/usr/bin/env python
"""Measure the HOST side of the serving path: can HTTP + decode + batcher +
scatter carry the 12k img/s north star? (VERDICT r3 next 1; SURVEY.md §7
hard part 6, §2 C11/C12.)

Two measurements, both TPU-free:

1. **Decode microbench** (in-process, single core): items/s for each host
   decode operation on identical inputs — PIL JPEG->RGB, the native libjpeg
   C shim JPEG->YUV420 planes, the PIL YUV fallback, and npy tensor parse.
   This is the C12 justification number (shim vs PIL).

2. **Serving loopback bench**: the real aiohttp server + batcher serving the
   toy model on the CPU backend over 127.0.0.1, driven by the out-of-process
   load generator with single-image JPEG POSTs, single-image npy, and
   batched npy bodies. The key metric is **items per server-CPU-second**
   (utime+stime deltas from /proc/<pid>/stat), which is contention-free even
   though the load generator shares this 1-vCPU box: it answers "how many
   images does ONE host core push through the full HTTP->decode->batch->
   scatter->respond path", which extrapolates to any core count.

The toy model's device compute is a ~6k-param MLP (negligible), so server
CPU time is host-path work. Its 8x8 wire shape means the host ALSO pays a
PIL resize per JPEG that the real yuv420 path does not — the extrapolation
is conservative. Results land in BASELINE.md ("Host-path ceiling").

Usage: python scripts/bench_host_path.py   (prints one JSON line; ~2 min)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PORT = int(os.environ.get("HOSTBENCH_PORT", 18471))
EDGE = int(os.environ.get("HOSTBENCH_EDGE", 160))  # the compact wire
DURATION = float(os.environ.get("HOSTBENCH_DURATION", 8))
CLIENT_BATCH = int(os.environ.get("HOSTBENCH_CLIENT_BATCH", 64))


def synth_jpeg(edge: int) -> bytes:
    from tpuserve.bench.loadgen import synthetic_image_jpeg

    return synthetic_image_jpeg(edge)


# -- 1. decode microbench -----------------------------------------------------

def microbench(fn, payload, min_s: float = 1.5) -> float:
    """items/s for fn(payload) on this core (adaptive iteration count)."""
    fn(payload)  # warm (imports, shim dlopen)
    n, t0 = 0, time.perf_counter()
    while True:
        for _ in range(20):
            fn(payload)
        n += 20
        dt = time.perf_counter() - t0
        if dt >= min_s:
            return n / dt


def run_microbench() -> dict:
    import numpy as np

    from tpuserve import native, preproc
    from tpuserve.bench.loadgen import synthetic_image_npy

    jpeg = synth_jpeg(EDGE)
    npy = synthetic_image_npy(EDGE)
    out = {
        "jpeg_bytes": len(jpeg),
        "pil_jpeg_to_rgb_per_s": microbench(
            lambda p: preproc.decode_image(p, "image/jpeg", edge=EDGE), jpeg),
        "npy_parse_per_s": microbench(
            lambda p: preproc.decode_image(p, "application/x-npy", edge=EDGE), npy),
        "pil_yuv420_fallback_per_s": microbench(
            lambda p: preproc.rgb_to_yuv420(
                preproc.decode_image(p, "image/jpeg", edge=EDGE)), jpeg),
    }
    if native.decode_yuv420(jpeg, EDGE) is not None:
        out["native_yuv420_per_s"] = microbench(
            lambda p: native.decode_yuv420(p, EDGE), jpeg)
        out["native_vs_pil_yuv_speedup"] = round(
            out["native_yuv420_per_s"] / out["pil_yuv420_fallback_per_s"], 2)
    else:
        out["native_yuv420_per_s"] = None  # shim not built on this host
    return out


# -- 2. serving loopback bench ------------------------------------------------

SERVER_TOML = """
port = %(port)d
decode_threads = 2
decode_inline = true
startup_canary = false

[[model]]
name = "toy"
family = "toy"
batch_buckets = [64, 128]
deadline_ms = 2.0
dtype = "float32"
num_classes = 10
parallelism = "single"
request_timeout_ms = 30000.0
max_inflight = 4
"""


def cpu_seconds(pid: int) -> float:
    with open(f"/proc/{pid}/stat") as f:
        parts = f.read().rsplit(") ", 1)[1].split()
    utime, stime = int(parts[11]), int(parts[12])
    return (utime + stime) / os.sysconf("SC_CLK_TCK")


def fetch_stats() -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{PORT}/stats", timeout=5) as r:
        return json.loads(r.read())


def phase_totals(stats: dict) -> dict:
    """{phase: (n, total_ms)} for the toy model."""
    out = {}
    for key, v in stats["latency"].items():
        if "model=toy" in key:
            phase = key.split("phase=")[1].rstrip("}")
            out[phase] = (v["n"], v["n"] * v["mean_ms"])
    return out


def run_loadgen(payload_path: str, ctype: str, duration: float, warmup: float,
                concurrency: int, batch: int = 0, rate: float = 0) -> dict:
    args = [sys.executable, "-m", "tpuserve", "bench",
            "--url", f"http://127.0.0.1:{PORT}", "--model", "toy",
            "--verb", "classify", "--duration", str(duration),
            "--warmup", str(warmup), "--concurrency", str(concurrency),
            "--payload", payload_path, "--content-type", ctype]
    if batch > 1:
        args += ["--batch", str(batch)]
    if rate:
        args += ["--rate", str(rate)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(args, capture_output=True, text=True, cwd=REPO,
                         env=env, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(
            f"loadgen failed: stdout={out.stdout[-400:]} "
            f"stderr={out.stderr[-400:]}")
    return json.loads(out.stdout)


def run_serving_bench() -> dict:
    from tpuserve.bench.loadgen import (
        synthetic_image_npy,
        synthetic_image_npy_batch,
    )

    with tempfile.TemporaryDirectory() as td:
        cfg_path = os.path.join(td, "host.toml")
        with open(cfg_path, "w") as f:
            f.write(SERVER_TOML % {"port": PORT})
        log_path = os.environ.get("HOSTBENCH_SRV_LOG", "/tmp/hostbench_srv.log")
        srv_log = open(log_path, "w")
        srv = subprocess.Popen(
            [sys.executable, "-m", "tpuserve", "serve", "--config", cfg_path],
            cwd=REPO, stdout=srv_log, stderr=subprocess.STDOUT,
            # A host-path count: the CPU backend on purpose.
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        srv_log.close()  # the child holds the fd now
        try:
            for _ in range(120):
                if srv.poll() is not None:
                    raise RuntimeError(
                        f"server exited rc={srv.returncode} at startup "
                        f"(see {log_path}; stale process on port {PORT}?)")
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{PORT}/healthz", timeout=1):
                        break
                except Exception:  # noqa: BLE001
                    time.sleep(0.5)
            else:
                raise RuntimeError("server never became healthy")

            payloads = {
                "jpeg_single": (synth_jpeg(EDGE), "image/jpeg", 0),
                "npy_single": (synthetic_image_npy(EDGE), "application/x-npy", 0),
                "npy_batch": (synthetic_image_npy_batch(EDGE, CLIENT_BATCH),
                              "application/x-npy", CLIENT_BATCH),
            }
            results = {}
            for name, (payload, ctype, batch) in payloads.items():
                ppath = os.path.join(td, f"{name}.bin")
                with open(ppath, "wb") as f:
                    f.write(payload)
                # Concurrency is in REQUESTS: batched bodies carry batch x
                # items each, so scale down to keep ~2-4 device buckets in
                # flight instead of flooding the queue into shedding.
                conc = 256 if batch <= 1 else max(2, 512 // batch)
                # Priming run (compiles nothing — warms sockets/paths), then
                # the measured run with zero warmup so the CPU window is
                # exactly the measurement window.
                run_loadgen(ppath, ctype, 2, 1, conc // 2, batch)
                s0, c0, t0 = fetch_stats(), cpu_seconds(srv.pid), time.time()
                res = run_loadgen(ppath, ctype, DURATION, 0, conc, batch)
                s1, c1, t1 = fetch_stats(), cpu_seconds(srv.pid), time.time()
                items = res["throughput_per_s"] * res.get("duration_s", DURATION)
                cpu = c1 - c0
                p0, p1 = phase_totals(s0), phase_totals(s1)
                phases = {}
                for ph in p1:
                    dn = p1[ph][0] - p0.get(ph, (0, 0))[0]
                    dt_ms = p1[ph][1] - p0.get(ph, (0, 0))[1]
                    if dn > 0:
                        phases[ph] = round(dt_ms / dn, 3)
                results[name] = {
                    "throughput_per_s": res["throughput_per_s"],
                    "p50_ms": res["p50_ms"],
                    "p99_ms": res["p99_ms"],
                    "errors": res["n_err"],
                    "server_cpu_s": round(cpu, 2),
                    "wall_s": round(t1 - t0, 2),
                    "server_cpu_ms_per_item": round(1e3 * cpu / items, 3)
                    if items else None,
                    "items_per_cpu_core_s": round(items / cpu, 1) if cpu else None,
                    "phase_mean_ms": phases,
                }
            # Batcher-added latency at a non-saturating rate (feeds the
            # latency budget): open loop at ~40% of jpeg saturation.
            rate = max(1, int(0.4 * results["jpeg_single"]["throughput_per_s"]))
            ppath = os.path.join(td, "jpeg_single.bin")
            open_res = run_loadgen(ppath, "image/jpeg", min(DURATION, 6), 1,
                                   256, 0, rate=rate)
            results["jpeg_open_loop"] = {
                "offered_per_s": open_res.get("offered_rate_per_s"),
                "throughput_per_s": open_res["throughput_per_s"],
                "p50_ms": open_res["p50_ms"],
                "p99_ms": open_res["p99_ms"],
            }
            return results
        finally:
            srv.terminate()
            srv.wait(timeout=10)


def main() -> int:
    out = {"edge": EDGE, "microbench": run_microbench(),
           "serving": run_serving_bench()}
    target = 12_000.0
    for fmt in ("jpeg_single", "npy_single", "npy_batch"):
        per_core = out["serving"][fmt]["items_per_cpu_core_s"]
        if per_core:
            out["serving"][fmt]["cores_for_12k_img_s"] = round(
                target / per_core, 1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
