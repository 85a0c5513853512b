#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpuserve still starts, serves and
stops on the chip.

    python chip_smoke.py

Drives the system's main path once through the entry points a user calls
(``python -m tpuserve serve --config ...`` and plain HTTP), at the full width
of ResNet-50, and checks what comes out. It claims no speed: three requests
are not a measurement.

Rules it keeps (PERF.md "Layers"):

- This parent never imports JAX. A chip belongs to one process at a time, so
  every phase is ONE child that owns the chip and has exited before the next
  starts; the parent talks to servers over HTTP only.
- It fails — non-zero exit, no result line — unless JAX in a child reports
  platform ``tpu`` with a ``device_kind`` the peaks table knows. There is no
  CPU fallback to pass by accident.
- Every phase failure reaches the exit code. After the device phase the
  remaining phases all run, so one call reports everything that is broken.
- The last stdout line is one JSON object with exactly ``ok`` and ``device``
  (``platform``, ``kind``, ``count`` as JAX reports them): the driver's
  contract. The line before it is the report: the jax version and per phase
  ``ok|failed|skipped`` with set-up and run seconds, also written to
  ``chiprun_out/chip_smoke/report.json`` beside the child logs.

Phases: device, native build, batched (ResNet-50 through
examples/latency_12k.toml's single-chip cut, then a restart that must add
nothing to the compile cache), generation (textgen through the paged engine),
kernels (the Pallas attention kernel BERT's (x, 512) buckets run, compiled
for real against the dense reference), four chips (replica + sharded
serving; skipped below 4 devices).
"""

from __future__ import annotations

import io
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
STARTUP_TIMEOUT_S = 900.0
FRAME_CTYPE = "application/x-tpuserve-frame"

# Agreement rule between two programs of one model (another bucket, another
# partitioning): every class's probability matches to a tolerance set by the
# compute dtype, so the classes match exactly except where two probabilities
# tie inside it. tests/test_multichip.py states it for the float32 toy (1e-5:
# reassociated f32 reductions, ~400x below a bfloat16 step, indices exact).
# ResNet-50 here computes in bfloat16, and two programs round their
# intermediates at different points. A probability's relative deviation is its
# logit's absolute one; the logits are sums over bf16 activations and reach
# |z| ~ 6 here, where a bf16 step is 2^-8 * 4 = 2^-6. Measured on the v5e:
# 2.0e-3 between the 8- and the 32-bucket, 2.1e-3 between sharded@d4 and one
# chip, 0 between a replica and one chip. Random weights put some of an
# image's top-5 within 1e-3 of each other, hence the tie clause. The frame's
# lanes are built to answer further apart than twice the tolerance (checked
# below), so a lane that read a neighbour's pixels or a wrong shard cannot
# pass.
PROB_RTOL = 2.0 ** -6

# textgen block of examples/genserve.toml, cut to the one model (sd15's
# 20-step UNet does not belong in a smoke), paged KV and chunked prefill on so
# the engine's donated prefill/step programs are the ones that cycle. The
# result cache stays off: the same-seed pair below must generate twice.
GEN_TOML = """
host = "127.0.0.1"
port = {port}
decode_threads = 8

[genserve]
enabled = true
slots = 8
kv_paging = true
prefill_chunk = 8
{parallel}
[[model]]
name = "textgen"
family = "textgen"
batch_buckets = [1, 4, 8]
dtype = "bfloat16"
parallelism = "single"
request_timeout_ms = 30000.0

[model.options]
layers = 4
d_model = 256
heads = 4
prompt_len = 32
max_new_tokens = 64
"""


class PhaseFailed(Exception):
    """A phase's check did not hold; the message says which."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# -- children -----------------------------------------------------------------

def child_env() -> dict:
    """The environment every child runs under: the caller's own (so
    JAX_PLATFORMS and JAX_COMPILATION_CACHE_DIR mean what the caller set),
    the checkout importable, and the retrace witness armed as the repo's own
    smokes run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["TPUSERVE_RETRACE_WITNESS"] = "1"
    return env


def stop_child(proc: subprocess.Popen) -> None:
    """Kill the child's whole process group if it is still running."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)


def log_tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<no log: {e}>"


def run_child(name: str, argv: list[str], timeout: float) -> str:
    """Run one chip-owning child to its end; returns its stdout. A non-zero
    exit or a timeout fails the phase, with the log tail in the message."""
    path = os.path.join(LOG_DIR, f"{name}.log")
    with open(path, "w") as log:
        proc = subprocess.Popen(argv, cwd=REPO, env=child_env(),
                                stdout=subprocess.PIPE, stderr=log,
                                text=True, start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{name}: no exit within {timeout:.0f}s\n"
                              + log_tail(path)) from None
        finally:
            stop_child(proc)
    require(proc.returncode == 0,
            f"{name}: exit code {proc.returncode}\n{log_tail(path)}")
    return out


# -- HTTP ---------------------------------------------------------------------

def http(method: str, url: str, body: bytes | None = None,
         ctype: str | None = None, timeout: float = 60.0) -> tuple[int, bytes]:
    req = urllib.request.Request(url, data=body, method=method)
    if ctype:
        req.add_header("Content-Type", ctype)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def post_json(url: str, body: bytes, ctype: str) -> dict:
    """POST that must answer 200 with a JSON object."""
    status, raw = http("POST", url, body, ctype)
    require(status == 200, f"POST {url} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def scrape(base: str) -> tuple[dict, dict]:
    """(/metrics as {series: value}, /stats as a dict)."""
    status, raw = http("GET", f"{base}/metrics")
    require(status == 200, f"/metrics -> {status}")
    metrics = {}
    for line in raw.decode().splitlines():
        if line.startswith("#") or " " not in line:
            continue
        key, val = line.rsplit(" ", 1)
        try:
            metrics[key] = float(val)
        except ValueError:
            pass
    status, raw = http("GET", f"{base}/stats")
    require(status == 200, f"/stats -> {status}")
    return metrics, json.loads(raw)


def no_server_errors(metrics: dict, model: str) -> None:
    """Every response this script read was a 200 (post_json); this is the
    server's side of the same claim: no failed batch, no timed-out request."""
    for name in ("batch_errors_total", "timeouts_total"):
        key = f'{name}{{model="{model}"}}'
        require(metrics.get(key, 0) == 0, f"{key} = {metrics.get(key)}")


# -- the server child ---------------------------------------------------------

class Server:
    """One ``python -m tpuserve serve`` child and its life cycle."""

    def __init__(self, name: str, args: list[str], port: int) -> None:
        self.name = name
        self.base = f"http://127.0.0.1:{port}"
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tpuserve", "serve", *args],
            cwd=REPO, env=child_env(), stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)
        self.t_spawn = time.monotonic()
        self.setup_s = 0.0

    def fail(self, msg: str) -> PhaseFailed:
        return PhaseFailed(f"{self.name}: {msg}\n--- {self.log_path} ---\n"
                           + log_tail(self.log_path))

    def wait_healthy(self, models: list[str]) -> None:
        """Until /healthz says ok with every model true. A failed startup
        canary is only logged by the server; here it fails the phase."""
        while True:
            if self.proc.poll() is not None:
                raise self.fail(f"exited {self.proc.returncode} during "
                                "start-up")
            if time.monotonic() - self.t_spawn > STARTUP_TIMEOUT_S:
                raise self.fail(f"not healthy in {STARTUP_TIMEOUT_S:.0f}s")
            try:
                status, raw = http("GET", f"{self.base}/healthz", timeout=5)
            except (urllib.error.URLError, OSError):
                time.sleep(0.5)
                continue
            health = json.loads(raw)
            if status != 200 or health.get("status") != "ok" \
                    or any(health["models"].get(m) is not True
                           for m in models):
                raise self.fail(f"/healthz -> {status} {health}")
            self.setup_s = time.monotonic() - self.t_spawn
            return

    def terminate(self) -> None:
        """SIGTERM: the child must drain and exit 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise self.fail("did not exit within 120s of SIGTERM") from None
        if rc != 0:
            raise self.fail(f"exit code {rc} after SIGTERM")

    def close(self) -> None:
        stop_child(self.proc)
        self._log.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- payloads (seeded; PIL and numpy only) ------------------------------------

EDGE = 160          # examples/latency_12k.toml wire_size
TOP_BUCKET = 32     # its largest batch bucket
PROBE_LANE = 5      # the frame lane re-posted alone


def jpeg_420(seed: int) -> bytes:
    """An exact-size baseline 4:2:0 JPEG: the native decoder's fast path."""
    import numpy as np
    from PIL import Image

    rgb = np.random.default_rng(seed).integers(
        0, 256, (EDGE // 8, EDGE // 8, 3), dtype=np.uint8)
    img = Image.fromarray(rgb).resize((EDGE, EDGE), Image.BILINEAR)
    buf = io.BytesIO()
    img.save(buf, "JPEG", quality=90, subsampling="4:2:0")
    return buf.getvalue()


def yuv_items(n: int) -> list:
    """Lane i is faint noise around luma 128 + 4i: the random-weight net's
    top probability climbs ~10% per lane, so every lane answers differently
    (lanes of plain uniform noise answer within 1% of each other, and no
    tolerance could tell a swapped lane)."""
    import numpy as np

    rng = np.random.default_rng(1234)
    half = EDGE // 2

    def plane(shape: tuple, level: float):
        return np.clip(rng.normal(level, 3.0, shape), 0, 255).astype(np.uint8)

    return [(plane((EDGE, EDGE), 128 + 4 * i), plane((half, half), 128),
             plane((half, half), 128)) for i in range(n)]


def check_top_k(top_k: list, where: str) -> None:
    probs = [e["prob"] for e in top_k]
    require(len(top_k) == 5, f"{where}: expected 5 entries, got {top_k}")
    require(all(p == p and 0.0 < p <= 1.0 for p in probs),
            f"{where}: probabilities not finite in (0, 1]: {probs}")
    require(probs == sorted(probs, reverse=True),
            f"{where}: probabilities not descending: {probs}")
    require(all(0 <= e["class"] < 1000 for e in top_k),
            f"{where}: class out of range: {top_k}")


def check_agree(a: list, b: list, where: str) -> float:
    """Two top-k lists agree under the rule at PROB_RTOL; returns the largest
    relative deviation seen (reported, so the tolerance stays honest). A
    class one list lacks scored at most that list's last probability there,
    so it must tie with it."""
    pa = {e["class"]: e["prob"] for e in a}
    pb = {e["class"]: e["prob"] for e in b}
    worst = 0.0
    for cls in sorted(pa.keys() | pb.keys()):
        x = pa.get(cls, a[-1]["prob"])
        y = pb.get(cls, b[-1]["prob"])
        dev = abs(x - y) / y
        require(dev <= PROB_RTOL, f"{where}: class {cls} scored {x!r} vs "
                                  f"{y!r}, beyond rtol {PROB_RTOL}: {a} vs {b}")
        worst = max(worst, dev)
    return worst


def check_same_answers(got: dict, ref: dict, where: str) -> float:
    """A layout's answers to the fixed payloads against the single chip's."""
    worst = check_agree(got["jpeg"], ref["jpeg"], f"{where}, jpeg")
    for i, (a, b) in enumerate(zip(got["frame"], ref["frame"])):
        worst = max(worst, check_agree(a, b, f"{where}, frame[{i}]"))
    return worst


# -- phases -------------------------------------------------------------------

def phase_device(times: dict) -> dict:
    """What JAX sees, from a ``describe`` child. Nothing is compiled."""
    from tpuserve.bench.probes import PEAK_TFLOPS_S

    out = run_child("describe", [sys.executable, "-m", "tpuserve",
                                 "describe"], timeout=300)
    desc = json.loads(out)
    require(desc["platform"] == "tpu",
            f"JAX found platform {desc['platform']!r} "
            f"({desc['device_kind']!r} x{desc['device_count']}), not a tpu")
    require(desc["device_kind"] in PEAK_TFLOPS_S,
            f"device_kind {desc['device_kind']!r} is not in the peaks table "
            f"{sorted(PEAK_TFLOPS_S)}")
    return desc


def phase_native_build(times: dict) -> None:
    """Rebuild the JPEG shim unconditionally: whatever .so the tree carried
    is not the one that serves."""
    path = os.path.join(LOG_DIR, "native_build.log")
    with open(path, "w") as log:
        rc = subprocess.run(["make", "-B", "-C",
                             os.path.join(REPO, "native", "decode")],
                            stdout=log, stderr=subprocess.STDOUT,
                            timeout=300).returncode
    require(rc == 0, f"make exited {rc}\n{log_tail(path)}")


def cache_files() -> set:
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(REPO, ".jaxcache")
    return set(os.listdir(d)) if os.path.isdir(d) else set()


def serve_resnet(name: str, extra_sets: list[str]) -> Server:
    port = free_port()
    sets = [f"port={port}", 'host="127.0.0.1"',
            # Not a latency check: the example's 100 ms budget would turn a
            # slow first request into a failure of the wrong kind.
            "model.resnet50.request_timeout_ms=30000", *extra_sets]
    args = ["--config", os.path.join(REPO, "examples", "latency_12k.toml")]
    for s in sets:
        args += ["--set", s]
    return Server(name, args, port)


def drive_resnet(srv: Server, desc: dict) -> dict:
    """The batched checks against a healthy ResNet-50 server; returns the
    answers later layouts are compared with."""
    from tpuserve import frame

    url = f"{srv.base}/v1/models/resnet50:classify"
    m0, stats = scrape(srv.base)
    topo = stats["topology"]
    require(topo["platform"] == desc["platform"]
            and topo.get("device_kind") == desc["device_kind"],
            f"/stats topology {topo} is not the device phase's {desc}")

    jpeg_top = post_json(url, jpeg_420(7), "image/jpeg")["top_k"]
    check_top_k(jpeg_top, "jpeg")
    m1, _ = scrape(srv.base)

    items = yuv_items(TOP_BUCKET)
    full = post_json(url, frame.encode_frame(items, frame.KIND_YUV420, EDGE),
                     FRAME_CTYPE)["results"]
    require(len(full) == TOP_BUCKET, f"frame answered {len(full)} results")
    for i, r in enumerate(full):
        check_top_k(r["top_k"], f"frame[{i}]")
    tops = sorted(r["top_k"][0]["prob"] for r in full)
    gap = min((b - a) / a for a, b in zip(tops, tops[1:]))
    require(gap > 2 * PROB_RTOL,
            f"two frame lanes answer within {gap:.2e} of each other: the "
            f"agreement checks (rtol {PROB_RTOL}) could not tell them apart")
    alone = post_json(url, frame.encode_frame([items[PROBE_LANE]],
                                              frame.KIND_YUV420, EDGE),
                      FRAME_CTYPE)["results"]
    # Padded-lane invariance: the image alone (7 padded lanes beside it in
    # the small bucket's program) and in the full frame must answer alike.
    dev = check_agree(alone[0]["top_k"], full[PROBE_LANE]["top_k"],
                      "alone vs in-frame")

    m2, stats = scrape(srv.base)
    no_server_errors(m2, "resnet50")
    key = 'runtime_compiles_total{model="resnet50"}'
    require(m0.get(key, 0) > 0, f"no start-up compiles recorded: {m0.get(key)}")
    require(m1[key] == m0[key] and m2[key] == m0[key],
            f"requests compiled: {key} {m0[key]} -> {m1[key]} -> {m2[key]}")
    for k in ('batches_total{model="resnet50"}',
              'items_total{model="resnet50"}'):
        require(m2.get(k, 0) > m0.get(k, 0), f"{k} did not move")
    fb = stats["ingest"]["native_decode_fallback_total"]["resnet50"]
    require(fb == 0, f"native_decode_fallback_total = {fb}: the JPEG took "
                     "the PIL path")
    return {"jpeg": jpeg_top, "frame": [r["top_k"] for r in full],
            "alone_rel_dev": dev, "stats": stats}


def phase_batched(desc: dict, times: dict) -> dict:
    srv = serve_resnet("batched_cold", ["model.resnet50.parallelism=single"])
    try:
        srv.wait_healthy(["resnet50"])
        times["setup_s"] = round(srv.setup_s, 1)
        t0 = time.monotonic()
        answers = drive_resnet(srv, desc)
        srv.terminate()
        times["run_s"] = round(time.monotonic() - t0, 1)
        times["alone_vs_frame_rel_dev"] = answers["alone_rel_dev"]
    finally:
        srv.close()
    before = cache_files()
    require(before, "the compile cache is empty after a cold start")
    srv = serve_resnet("batched_warm", ["model.resnet50.parallelism=single"])
    try:
        srv.wait_healthy(["resnet50"])
        times["warm_setup_s"] = round(srv.setup_s, 1)
        srv.terminate()
    finally:
        srv.close()
    added = cache_files() - before
    require(not added, f"the restart added {len(added)} compile-cache "
                       f"file(s): {sorted(added)[:5]}")
    return answers


def generate(base: str, body: dict) -> dict:
    return post_json(f"{base}/v1/models/textgen:generate",
                     json.dumps(body).encode(), "application/json")


def drive_textgen(srv: Server) -> dict:
    """Eight concurrent requests of mixed lengths: the longest goes first and
    the rest follow 5 ms apart, so later ones fold into a block that is
    already generating and short ones exit before it ends. Requests 1 and 6
    are one request sent twice."""
    m0, _ = scrape(srv.base)
    twin = {"prompt": "the same seed twice", "seed": 11,
            "max_new_tokens": 24, "temperature": 0.7}
    lens = [64, 24, 4, 48, 8, 32, 24, 2]
    bodies = [twin if i in (1, 6) else
              {"prompt": f"smoke prompt number {i}", "seed": 100 + i,
               "max_new_tokens": n, "temperature": 0.7}
              for i, n in enumerate(lens)]
    results: list = [None] * len(bodies)

    def one(i: int) -> None:
        time.sleep(0.005 * i)
        try:
            results[i] = generate(srv.base, bodies[i])
        except Exception as e:  # noqa: BLE001 — reported below, per request
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i, r in enumerate(results):
        require(isinstance(r, dict), f"request {i} failed: {r!r}")
        require(r["n_tokens"] == lens[i] and len(r["tokens"]) == lens[i],
                f"request {i}: wanted {lens[i]} tokens, got {r}")
        require(isinstance(r["text"], str), f"request {i}: no text in {r}")
    require(results[1]["tokens"] == results[6]["tokens"]
            and results[1]["text"] == results[6]["text"],
            f"same seed, different text: {results[1]} vs {results[6]}")

    m1, stats = scrape(srv.base)
    no_server_errors(m1, "textgen")
    key = 'runtime_compiles_total{model="textgen"}'
    require(m0.get(key, 0) > 0, "no start-up compiles recorded")
    require(m1[key] == m0[key],
            f"generation compiled: {key} {m0[key]} -> {m1[key]}")
    for k in ('gen_fold_ins_total{model="textgen"}',
              'gen_early_exits_total{model="textgen"}'):
        require(m1.get(k, 0) > 0, f"{k} = {m1.get(k)}: it never happened")
    # The programs that cycled are the donating ones (donation is off on the
    # CPU backend, so no CPU test ever ran them): the whole state block is
    # consumed by every prefill and step, and the tokens above came out right.
    donated = {v["bucket"][0]: v["donated"]
               for v in stats["roofline"]["textgen"]["variants"]}
    require(donated.get("prefill") and donated.get("step"),
            f"prefill/step do not donate the state block: {donated}")
    return {"metrics": m1, "before": m0, "stats": stats}


def serve_textgen(name: str, parallel: str = "") -> Server:
    port = free_port()
    path = os.path.join(LOG_DIR, f"{name}.toml")
    with open(path, "w") as f:
        f.write(GEN_TOML.format(port=port, parallel=parallel))
    return Server(name, ["--config", path], port)


def phase_generation(times: dict) -> None:
    srv = serve_textgen("generation")
    try:
        srv.wait_healthy(["textgen"])
        times["setup_s"] = round(srv.setup_s, 1)
        t0 = time.monotonic()
        drive_textgen(srv)
        srv.terminate()
        times["run_s"] = round(time.monotonic() - t0, 1)
    finally:
        srv.close()


def phase_kernels(times: dict) -> None:
    out = run_child("kernels", [sys.executable, os.path.abspath(__file__),
                                "--kernels-child"], timeout=900)
    report = json.loads(out.strip().splitlines()[-1])
    times["max_abs_err"] = {k: v["plain_abs"] for k, v in report.items()
                            if k != "ok"}


def phase_four_chips(desc: dict, single: dict | None, times: dict) -> None:
    require(single is not None, "no single-chip answers to compare with: "
                                "the batched phase failed")
    t0 = time.monotonic()
    setup = 0.0
    n = 4
    par = ["parallel.n_chips=4"]

    srv = serve_resnet("four_replica", ['parallel.mode="replica"', *par])
    try:
        srv.wait_healthy(["resnet50"])
        setup += srv.setup_s
        before, _ = scrape(srv.base)
        got = drive_resnet(srv, desc)
        # Prewarm already ran every replica once, so only the delta over
        # real requests shows a chip that serves. Idle replicas are picked
        # round-robin: eight requests in a row reach all four.
        url = f"{srv.base}/v1/models/resnet50:classify"
        for i in range(8):
            post_json(url, jpeg_420(20 + i), "image/jpeg")
        after, stats = scrape(srv.base)
        srv.terminate()
    finally:
        srv.close()
    for k in range(n):
        key = f'replica_batches_total{{model="resnet50",replica="{k}"}}'
        require(after.get(key, 0) > before.get(key, 0),
                f"replica {k} served no batch: {key} {before.get(key)} -> "
                f"{after.get(key)}")
    require(stats["parallel"]["resnet50"]["signature"] == f"replica@{n}",
            f"/stats parallel: {stats['parallel']}")
    times["replica_rel_dev"] = check_same_answers(got, single,
                                                  "replica vs single")

    srv = serve_resnet("four_sharded", ['parallel.mode="sharded"', *par])
    try:
        srv.wait_healthy(["resnet50"])
        setup += srv.setup_s
        got = drive_resnet(srv, desc)
        srv.terminate()
    finally:
        srv.close()
    block = got["stats"]["parallel"]["resnet50"]
    require(block["signature"] == f"sharded@d{n}" and block["n_chips"] == n,
            f"/stats parallel: {block}")
    times["sharded_rel_dev"] = check_same_answers(got, single,
                                                  "sharded vs single")

    srv = serve_textgen("four_generation",
                        '\n[parallel]\nmode = "replica"\nn_chips = 4\n')
    try:
        srv.wait_healthy(["textgen"])
        setup += srv.setup_s
        first = drive_textgen(srv)
        second = drive_textgen(srv)  # sixteen requests over four engines
        srv.terminate()
    finally:
        srv.close()
    for k in range(n):
        key = f'gen_replica_steps_total{{model="textgen",replica="{k}"}}'
        require(second["metrics"].get(key, 0) > first["before"].get(key, 0),
                f"engine replica {k} took no step: {key} "
                f"{first['before'].get(key)} -> "
                f"{second['metrics'].get(key)}")
    times["setup_s"] = round(setup, 1)
    times["run_s"] = round(time.monotonic() - t0 - setup, 1)


# -- the kernels child (the only code here that imports JAX) ------------------

def kernels_child() -> int:
    """Compile ``fused_attention`` for the chip, never the interpreter, at
    the shapes BERT's (x, 512) buckets give it in both cells, and compare
    with float32 ``_masked_attention`` computed on the same chip at full
    matmul precision.

    Tolerance: atol 0.03 on the rows of live queries, what
    tests/test_fused_attention.py holds the bf16 kernel to against plain
    float64 attention. Both products take bf16 operands with float32
    accumulation and the answer is rounded to bf16 once, 1.6e-2 for |out| <
    4; a wrong block index or mask is off by the size of the values
    themselves, ~1. A padded query's row must be finite and means nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserve.models.bert import _masked_attention
    from tpuserve.ops.fused_attention import fused_attention
    from tpuserve.runtime import configure_backend

    configure_backend()

    shapes = {  # name: (B, S, H, D)
        "bert_base_s512": (8, 512, 12, 64),
        "bert_large_s512": (8, 512, 16, 64),
    }
    report: dict = {}
    for name, shape in shapes.items():
        b, s, _, _ = shape
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32)
                   .astype(jnp.bfloat16) for kk in ks)
        live = np.ones((b, s), bool)   # odd rows padded past three quarters
        live[1::2, 3 * s // 4:] = False
        bias = jnp.asarray(np.where(live, 0.0, -1e9), jnp.float32)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(_masked_attention)(
                *(x.astype(jnp.float32) for x in (q, k, v)),
                bias[:, None, None, :])
        out = fused_attention(q, k, v, jnp.asarray(live), interpret=False)
        f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
        err = float(np.max(np.abs(f32(out) - f32(ref))[live]))
        finite = bool(np.isfinite(f32(out)).all())
        ok = (finite and out.shape == shape and out.dtype == jnp.bfloat16
              and err <= 0.03)
        report[name] = {"ok": ok, "finite": finite, "plain_abs": err}
        print(f"[kernels] {name}: {report[name]}", file=sys.stderr, flush=True)
    report["ok"] = all(r["ok"] for r in report.values())
    print(json.dumps(report))
    return 0 if report["ok"] else 1


# -- main ---------------------------------------------------------------------

def result_line(ok: bool, desc: dict) -> str:
    """The last stdout line. The driver reads exactly these keys: ``ok`` and
    the device as JAX reported it to the ``describe`` child."""
    return json.dumps({
        "ok": ok,
        "device": {"platform": desc["platform"], "kind": desc["device_kind"],
                   "count": desc["device_count"]},
    })


def main() -> int:
    try:
        import tpuserve.frame  # noqa: F401 — JAX-free; the program is here
    except ImportError as e:
        say(f"the program is not beside this script: {e}")
        return 1
    os.makedirs(LOG_DIR, exist_ok=True)
    phases: dict = {}

    def run(name: str, fn, *args):
        """One phase: its status and seconds land in ``phases``."""
        times: dict = {}
        phases[name] = times
        t0 = time.monotonic()
        say(f"{name}: start")
        try:
            result = fn(*args, times)
            times["status"] = "ok"
        except PhaseFailed as e:
            result = None
            times["status"] = "failed"
            times["error"] = str(e).splitlines()[0][:300]
            say(f"{name}: FAILED\n{e}")
        times.setdefault("run_s", round(time.monotonic() - t0, 1))
        say(f"{name}: {times['status']} {times}")
        return result

    desc = run("device", phase_device)
    if desc is None:
        # No accelerator (or an unknown one): no result line, by contract.
        return 1
    run("native build", phase_native_build)
    single = run("batched", phase_batched, desc)
    run("generation", phase_generation)
    run("kernels", phase_kernels)
    if desc["device_count"] >= 4:
        run("four chips", phase_four_chips, desc, single)
    else:
        phases["four chips"] = {
            "status": f"skipped: n_devices={desc['device_count']}"}

    ok = all(p["status"] == "ok" or p["status"].startswith("skipped")
             for p in phases.values())
    if "jax" in sys.modules:
        raise RuntimeError("the parent imported jax; it must stay off the chip")
    report = json.dumps({"jax_version": desc["jax_version"],
                         "phases": phases})
    with open(os.path.join(LOG_DIR, "report.json"), "w") as f:
        f.write(report + "\n")
    print(report)
    print(result_line(ok, desc), flush=True)  # nothing follows it on stdout
    return 0 if ok else 1


if __name__ == "__main__":
    if sys.argv[1:] == ["--kernels-child"]:
        sys.exit(kernels_child())
    sys.exit(main())
