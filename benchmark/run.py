#!/usr/bin/env python3
"""Run one cell of the benchmark once, in a new process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Earlier lines of standard output are free text; the LAST line is one JSON
object with `correct`, `attempted`, `failed`, `metrics`, `device` and, traced,
`breakdown` (BENCHMARK.json's contract). benchmark/README.md says how a run
works and how a later PR adds a cell without editing anything here.

This process never opens the chip. It holds itself to the CPU backend (for
the reference and the trace reduction) and starts the server as one child
without that setting; the child owns the chip until it has exited.
"""

from __future__ import annotations

import time

T_RUN0 = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402
from dataclasses import dataclass  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark import budget as budget_mod  # noqa: E402
from benchmark import spec  # noqa: E402

WORK = os.path.join(REPO, ".benchmark_work")
MODEL_NAME = "model"


def compile_cache_dir() -> str:
    """Where the machine places the compile cache, else a fixed path in the
    checkout: parent and child read what the cell's first run wrote."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jaxcache"))


def say(msg: str) -> None:
    print(f"[benchmark +{time.monotonic() - T_RUN0:6.1f}s] {msg}", flush=True)


class NoAccelerator(Exception):
    """No result line may be printed: the run did not reach a TPU."""


class RunFailed(Exception):
    """The run reached the device and failed: a failing result line."""


# -- the server child ----------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def toml_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return repr(v)
    if isinstance(v, str):
        return json.dumps(v)
    if isinstance(v, list):
        return "[" + ", ".join(toml_value(x) for x in v) + "]"
    raise TypeError(f"cannot write {v!r} to TOML")


def write_serve_toml(path: str, cfg: dict, port: int, weights: str | None,
                     options_extra: dict) -> None:
    """The cell's serve file from the configuration file: the `serve.server`
    keys at the top, each table of `serve.tables` (`genserve`, `pipeline`, ...:
    scalars and lists), one [[model]] from `serve.model`, and its options from
    the published keys that `serve.options_from` names. `weights` and
    `num_classes` are written where there are any: no checkpoint means the
    program draws its weights itself, and a family that generates has no
    classes."""
    serve = cfg["serve"]
    model = {"name": MODEL_NAME, "family": cfg["family"]}
    if weights is not None:
        model["weights"] = weights
    if "num_classes" in cfg.get("assumed", {}):
        model["num_classes"] = cfg["assumed"]["num_classes"]
    model.update(serve["model"])
    options = {opt: cfg[key] for opt, key in serve.get("options_from", {}).items()}
    options.update(options_extra)
    lines = [f"{k} = {toml_value(v)}" for k, v in
             {"host": "127.0.0.1", "port": port, **serve.get("server", {})}.items()]
    for name, table in serve.get("tables", {}).items():
        lines += ["", f"[{name}]"]
        lines += [f"{k} = {toml_value(v)}" for k, v in table.items()]
    lines += ["", "[[model]]"]
    lines += [f"{k} = {toml_value(v)}" for k, v in model.items()]
    lines += ["", "[model.options]"]
    lines += [f"{k} = {toml_value(v)}" for k, v in options.items()]
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")


def child_env(rehearse: bool) -> dict:
    env = dict(os.environ)
    if not rehearse:
        env.pop("JAX_PLATFORMS", None)  # the child takes the accelerator
    env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


class Server:
    def __init__(self, work: str, toml: str, trace_ms: float, rehearse: bool) -> None:
        self.work = work
        self.log = open(os.path.join(work, "server.log"), "wb")
        argv = [sys.executable, os.path.join(HERE, "serve_child.py"),
                "--config", toml, "--out", work, "--trace-ms", str(trace_ms)]
        self.proc = subprocess.Popen(argv, cwd=REPO, env=child_env(rehearse),
                                     stdout=self.log, stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 25) -> str:
        self.log.flush()
        with open(os.path.join(self.work, "server.log"), "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode("utf-8", "replace")

    def cpu_s(self) -> float | None:
        """utime + stime of the process, every thread, in seconds."""
        try:
            with open(f"/proc/{self.proc.pid}/stat", encoding="utf-8") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return None

    def stop(self, timeout_s: float) -> int | None:
        """SIGTERM and wait for the drain; SIGKILL when the wait runs out."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=max(1.0, timeout_s))
            except subprocess.TimeoutExpired:
                self.kill()
                return None
        self.log.close()
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                pass
        if not self.log.closed:
            self.log.close()


def http_get(url: str, timeout: float) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def http_post_json(url: str, body: bytes, timeout: float) -> tuple[int, dict]:
    req = urllib.request.Request(url, data=body, method="POST",
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode("utf-8", "replace")}


def wait_ready(server: Server, base: str, bud: budget_mod.Budget, reserve_s: float) -> None:
    while True:
        if server.proc.poll() is not None:
            raise NoAccelerator(
                f"the server exited with {server.proc.returncode} before it "
                f"was ready:\n{server.log_tail()}")
        try:
            status, raw = http_get(f"{base}/healthz", timeout=2.0)
            if status == 200 and json.loads(raw).get("status") == "ok":
                return
        except (OSError, ValueError):
            pass
        bud.wait_s("server start-up", reserve_s=reserve_s)
        time.sleep(0.25)


# -- one run, phase by phase ----------------------------------------------------

@dataclass
class Cell:
    """What one run works with, looked up by name from BENCHMARK.json."""
    bench: dict
    name: str
    listed: str | None  # its name in BENCHMARK.json; None for a run that is no cell
    chips: int
    cfg: dict
    mix: dict
    family: object   # benchmark/reference/<family>.py
    flops: object    # benchmark/flops/<family>.py
    traffic: object  # benchmark/traffic/<kind>.py
    sizes: dict


def load_cell(args) -> Cell:
    bench = spec.load_benchmark()
    if args.config:  # a configuration that is no cell
        entry = {"name": args.workload, "config": args.config,
                 "traffic": args.traffic, "chips": 1}
    else:
        entry = spec.find(bench["workloads"], args.workload, "workload")
    cfg = spec.load_config(bench, entry["config"])
    mix = spec.load_mix(entry["traffic"])
    family = spec.load_module("reference", cfg["family"])
    return Cell(bench, entry["name"], None if args.config else entry["name"],
                entry["chips"], cfg, mix, family,
                spec.load_module("flops", cfg["family"]),
                spec.load_module("traffic", mix["traffic"]),
                family.sizes_from_config(cfg))


def checkpoint_from_seed(cell: Cell, args, state: dict):
    """How weights reach the server and the reference where the family's
    module has no `prepare` of its own: every tensor drawn from the seed in
    one jitted call (`make_params`), written as a checkpoint
    (`save_checkpoint`) that the server restores through its ordinary
    `weights =` path, and handed to the reference as they are."""
    import jax

    params = jax.block_until_ready(cell.family.make_params(args.seed, cell.sizes))
    say(f"weights drawn from seed {args.seed}")
    # Under TMPDIR: up to 0.7 GB that is read once and removed as soon as the
    # server has restored it.
    weights_dir = state["weights"] = os.path.join(
        tempfile.mkdtemp(prefix="tpuserve-benchmark-"), "weights")
    cell.family.save_checkpoint(weights_dir, params, cell.sizes, cell.cfg)
    say("checkpoint written")
    return weights_dir, {}, params


def start_server(cell: Cell, args, work: str, trace_ms: float, state: dict):
    """Weights and vocabulary, then the server child. The family's `prepare`
    says how the weights reach the server (a checkpoint's directory, or None
    where the program draws them itself by the recipe the configuration
    states) and what the reference gets (`ref`: whatever the family's
    `reference_answers` takes). Returns the server, its base URL, `ref` and
    the vocabulary."""
    import jax

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if hasattr(cell.family, "prepare"):
        weights_dir, family_options, ref = cell.family.prepare(
            args.seed, cell.sizes, cell.cfg, work)
        say("weights: " + (f"checkpoint {weights_dir}" if weights_dir else
                           "none handed over, the program draws them by assumed.weights"))
    else:
        weights_dir, family_options, ref = checkpoint_from_seed(cell, args, state)
    vocab, options_extra = cell.traffic.prepare(work, cell.cfg)
    port = free_port()
    toml = os.path.join(work, "serve.toml")
    write_serve_toml(toml, cell.cfg, port, weights_dir, {**family_options, **options_extra})
    server = state["server"] = Server(work, toml, trace_ms, args.rehearse)
    say(f"server child {server.proc.pid} started on port {port}")
    return server, f"http://127.0.0.1:{port}", ref, vocab


def answers_of(cell: Cell):
    """What the answers of one response are: the traffic kind's
    `answers_of(obj) -> list`, else the classifier's (`loadgen.answers_of`)."""
    from benchmark import loadgen

    return getattr(cell.traffic, "answers_of", loadgen.answers_of)


def make_load(cell: Cell, args, vocab, url: str, seconds: float, on_window):
    """The window's traffic from the seed, as the coroutine that sends it."""
    from benchmark import loadgen

    mix, traffic = cell.mix, cell.traffic
    warmup_s, drain_s = float(mix["warmup_s"]), float(mix["drain_s"])
    if mix["loop"] == "open":
        n_window = max(1, round(mix["rate_per_s"] * seconds))
        n_warm = max(1, round(mix["rate_per_s"] * warmup_s))
        requests = traffic.make_requests(mix, args.seed, vocab, n_window)
        warm = traffic.make_requests(mix, args.seed + 1, vocab, n_warm)
        due = traffic.due_times(mix, args.seed, n_window, seconds)
        warm_due = traffic.due_times(mix, args.seed + 1, n_warm, warmup_s)
        say(f"traffic made: {n_warm} + {n_window} requests at their due times")
        return loadgen.open_loop(url, warm, warm_due, requests, due, seconds,
                                 drain_s, on_window, answers_of(cell))
    requests = traffic.make_requests(mix, args.seed, vocab, int(mix["pool_requests"]))
    say(f"traffic made: a pool of {len(requests)} requests")
    return loadgen.closed_loop(url, requests, int(mix["clients"]), warmup_s,
                               seconds, drain_s, on_window, answers_of(cell))


def read_device(cell: Cell, args, base: str, bud) -> tuple[dict, dict | None]:
    """Platform, kind and count as the server's JAX reports them, and the
    kind's peaks. Anything but enough TPU chips of a known kind: no result."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        peaks_table = json.load(f)["devices"]
    _status, raw = http_get(f"{base}/stats", timeout=bud.wait_s("/stats", 10))
    topo = json.loads(raw)["topology"]
    platform, kind, count = topo["platform"], topo["device_kind"], topo["global_devices"]
    peaks = peaks_table.get(kind)
    if not args.rehearse:
        if platform != "tpu" or count < cell.chips:
            raise NoAccelerator(f"the server runs on platform={platform!r} with "
                                f"{count} device(s); the cell asks for {cell.chips} TPU chip(s)")
        if peaks is None:
            raise NoAccelerator(f"device_kind {kind!r} is not in benchmark/peaks.json")
    return {"platform": platform, "kind": kind, "count": count,
            "memory_peak_bytes": 0}, peaks


def read_memory_peak(base: str, bud) -> int:
    """Peak bytes the fullest chip had committed, from `/stats`
    `topology.devices[i].memory` (the program reads `device.memory_stats()`
    when asked): its live buffers at their peak plus what the runtime reserved
    for the loaded programs' scratch. On the TPU `peak_bytes_in_use` counts
    buffers alone (parameters, inputs, outputs); a program's temporaries are
    `peak_bytes_reserved` (seen on the chip, PR 24: a program whose compiler
    analysis says 3,221,257,728 bytes of temp moved `peak_bytes_reserved` by
    3,221,241,856 and `peak_bytes_in_use` by 2 MB). A peak never falls, so
    read once, when the window has closed. 0 where the backend reports no
    memory (the CPU)."""
    _status, raw = http_get(f"{base}/stats", timeout=bud.wait_s("/stats", 10))
    return max((int(m.get("peak_bytes_in_use", 0)) + int(m.get("peak_bytes_reserved", 0))
                for m in (d.get("memory") or {} for d in json.loads(raw)["topology"]["devices"])),
               default=0)


def check_outputs(cell: Cell, sample, reference, url: str, bud, state: dict) -> bool:
    """Send the sample, compare its answers with the reference's by the
    family's `compare(served, reference, cfg) -> (statistic, line)` (else
    `check.compare_class_probs`), print the number compared beside its
    limit."""
    from benchmark import check as check_mod

    answers = answers_of(cell)
    served = []
    for req, _texts in sample:
        status, obj = http_post_json(url, req.body, bud.wait_s("a check request", 60))
        if status != 200:
            raise RunFailed(f"check request answered {status}: {obj}")
        got = answers(obj)
        if len(got) != req.items:
            raise RunFailed(f"check request of {req.items} items got {len(got)} answers")
        served += got
    compare = getattr(cell.family, "compare", check_mod.compare_class_probs)
    stat, line = compare(served, reference, cell.cfg)
    limit = float(cell.cfg["check"]["limit"])
    state["check_line"] = (f"check: {line} limit={limit:.6g} -> "
                           f"{'ok' if stat <= limit else 'NOT CORRECT'}")
    say(state["check_line"])
    return stat <= limit


def wait_for_trace(server: Server, work: str, bud) -> dict:
    done_path = os.path.join(work, "trace_done.json")
    while not os.path.exists(done_path):
        bud.wait_s("the tracer to write its file", reserve_s=15.0)
        if server.proc.poll() is not None:
            raise RunFailed(f"server died while tracing:\n{server.log_tail()}")
        time.sleep(0.2)
    with open(done_path, encoding="utf-8") as f:
        info = json.load(f)
    if not info.get("ok"):
        raise RunFailed(f"the profiler failed: {info}")
    return info


def named_idle_gaps(run_info: dict, top: int = 10) -> list | None:
    """The device's longest idle gaps, each named by where the batch that
    ended it spent most of the gap (`slot_wait`, `staging_wait`, `tokenize`,
    ...: host_spans.py has the rule), longest first, for `breakdown.idle_gaps`.
    None where the trace holds no span of the program: the gaps then stay
    `host:unknown`."""
    from benchmark import host_spans

    hs = host_spans.for_run(run_info)
    if not hs:
        return None
    named = [[max(g["parts_ms"], key=g["parts_ms"].get) if g["parts_ms"] else "unknown",
              g["ms"] / 1e3] for g in hs["gaps"]]
    # Gaps under a millisecond have no name of their own: the rule sums them as `unknown`.
    short = [["unknown", s] for _n, s in run_info["trace"]["idle_gaps"]
             if s < host_spans.LONG_GAP_NS / 1e9]
    return (named + short)[:top]


def per_layer_metrics(cell: Cell, args, work: str, trace_info: dict, device: dict,
                      run_info: dict) -> tuple[dict, dict | None]:
    """Reduce the trace, then let each of the cell's per-layer readers take its
    number from the run. A reader that finds nothing returns None and its
    metric is left out of the line; the run prints which were."""
    from benchmark import trace_reduce

    xplane = trace_reduce.find_xplane(os.path.join(work, "trace"))
    reduced = breakdown = None
    if xplane:
        say(f"reducing {xplane} ({os.path.getsize(xplane) / 2**20:.1f} MiB)")
        reduced = trace_reduce.reduce_file(xplane, trace_info["window_s"])
    if reduced is None:
        if not args.rehearse:
            raise RunFailed("the trace holds no operation on any device")
        say("rehearsal: no device plane in the trace (CPU backend)")
    else:
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        say("modules in the trace: " + json.dumps(reduced["modules"]))
    run_info.update(trace=reduced, xplane=xplane, notes=[])
    out, left_out = {}, []
    for m in spec.cell_metrics(cell.bench, "per_layer", cell.listed):
        v = spec.load_module("layer_metrics", m["name"]).read(run_info)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            left_out.append(m["name"])
    for note in run_info["notes"]:
        say(note)
    if left_out:
        say("per_layer readers that found nothing to read in this run, left out of the "
            "line: " + ", ".join(left_out))
    if breakdown:
        breakdown["idle_gaps"] = named_idle_gaps(run_info) or breakdown["idle_gaps"]
    return out, breakdown


def run(args, bud: budget_mod.Budget, state: dict) -> dict:
    cell = load_cell(args)
    if not os.path.isdir(os.path.join(REPO, "tpuserve")):
        raise NoAccelerator("the system under test (tpuserve/) is not in this checkout")
    mix = cell.mix
    seconds = float(args.seconds)
    trace_ms = float(mix["trace_ms"]) if args.trace else 0.0
    warmup_s, drain_s = float(mix["warmup_s"]), float(mix["drain_s"])
    # What must still fit after the server is ready: check, warm-up, window,
    # drain, stopping the server, and (traced) writing and reducing the trace.
    after_ready_s = 5.0 + warmup_s + seconds + drain_s + 10.0 + (20.0 if args.trace else 0.0)
    bud.need(after_ready_s + 5.0, "the run after the server is ready")

    work = state["work"] = os.path.join(WORK, cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    server, base, ref, vocab = start_server(cell, args, work, trace_ms, state)
    url = f"{base}/v1/models/{MODEL_NAME}:{mix.get('verb', 'classify')}"

    # While it starts: the traffic and the reference's answers for the sample.
    from benchmark import loadgen, prom

    scrapes: dict[str, dict] = {}
    cpu: dict[str, float | None] = {}
    t_window: dict[str, float] = {}

    async def on_window(which: str) -> None:
        t_window[which] = time.monotonic()
        cpu[which] = server.cpu_s()
        _s, raw = await asyncio.to_thread(http_get, f"{base}/metrics", 10.0)
        scrapes[which] = prom.parse(raw.decode())
        if which == "start" and trace_ms > 0:
            # A little into the window, so the trace sees the steady state.
            asyncio.get_running_loop().call_later(
                min(1.0, seconds / 4), server.proc.send_signal, signal.SIGUSR1)

    load_coro = make_load(cell, args, vocab, url, seconds, on_window)
    sample = cell.traffic.make_check(mix, args.seed, vocab)
    inputs = cell.traffic.check_inputs(sample, vocab)
    reference = getattr(cell.family, "reference_answers", None) or cell.family.class_log_probs
    reference = reference(ref, inputs, cell.sizes)
    del ref
    say(f"reference computed for {len(inputs)} texts")

    wait_ready(server, base, bud, reserve_s=after_ready_s)
    say("server ready")
    if state.get("weights"):
        shutil.rmtree(os.path.dirname(state["weights"]), ignore_errors=True)  # restored
    device, peaks = read_device(cell, args, base, bud)
    state["device"] = device
    check_ok = check_outputs(cell, sample, reference, url, bud, state)
    del reference

    bud.need(warmup_s + seconds + drain_s + 10.0, "warm-up, window and drain")
    load = asyncio.run(asyncio.wait_for(
        load_coro, timeout=warmup_s + seconds + drain_s + 20.0))
    say(f"window done: attempted={load.attempted} failed={load.failed} "
        f"items_in_window={load.items_in_window} errors={load.errors}"
        + (" (request pool reused)" if load.wrapped else ""))
    delta = prom.delta(scrapes["end"], scrapes["start"])
    moved: dict[str, float] = {}
    for k, v in delta.items():
        family = k.partition("{")[0]
        if family.endswith("_total") and v > 0:
            moved[family] = moved.get(family, 0.0) + v
    say("counters that moved in the window: "
        + ", ".join(f"{k}={v:.6g}" for k, v in sorted(moved.items())))
    compiles = sum(prom.select(delta, "runtime_compiles_total").values())
    if compiles > 0:
        say(f"NOT CORRECT: {compiles:.0f} program(s) compiled inside the window")

    trace_info = wait_for_trace(server, work, bud) if trace_ms > 0 else None
    device["memory_peak_bytes"] = read_memory_peak(base, bud)
    rc = server.stop(bud.wait_s("the server to drain", 40.0, reserve_s=5.0))
    if rc != 0:
        raise RunFailed(f"the server did not drain to exit 0 (exit {rc}):\n{server.log_tail()}")
    say(f"server exited 0; device {device}")
    floor = 0.25 * peaks["hbm_bytes"] if peaks else 0
    say(f"memory: peak {device['memory_peak_bytes'] / 2**30:.2f} GiB on the fullest "
        f"chip (live buffers + reserved program scratch); a cell's floor is "
        f"{floor / 2**30:.2f} GiB")

    lat = load.latencies_ms
    say(f"latency sample: {len(lat)} requests; by class "
        + ", ".join(f"{c}: n={len(v)} p50={loadgen.percentile(v, 0.5):.1f} ms"
                    for c, v in sorted(load.latencies_by_class.items())))
    values = {
        "items_per_s": load.items_in_window / seconds,
        "latency_p50_ms": loadgen.percentile(lat, 0.5),
        "latency_p95_ms": loadgen.percentile(lat, 0.95),
        "setup_s": t_window["start"] - T_RUN0,
    }
    say("end to end: " + ", ".join(f"{k}={v:.6g}" for k, v in values.items()))
    breakdown = None
    if args.trace:
        cpu_s = (cpu["end"] - cpu["start"]
                 if cpu.get("end") is not None and cpu.get("start") is not None else None)
        metrics_out, breakdown = per_layer_metrics(cell, args, work, trace_info, device, {
            "metrics_delta": delta, "model_name": MODEL_NAME, "load": load,
            "server_cpu_s": cpu_s, "compiles_in_window": compiles,
            "peaks": peaks, "flops": cell.flops, "sizes": cell.sizes})
    else:
        metrics_out = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                       for m in spec.cell_metrics(cell.bench, "end_to_end", cell.listed)}
    say(("per_layer: " if args.trace else "end_to_end: ") + json.dumps(metrics_out))
    if args.keep:
        say(f"work directory kept: {work}")
    else:
        shutil.rmtree(work, ignore_errors=True)
    state["check_line"] += f"; compiles_in_window={compiles:.0f} limit=0"
    result = {"correct": bool(check_ok and compiles == 0),
              "attempted": load.attempted, "failed": load.failed,
              "metrics": metrics_out, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    return result


def main() -> int:
    # Importing orbax takes 13 s on the chip's machine (3 s for jax): start it
    # now, beside the imports and the draw of the weights that come first.
    threading.Thread(target=importlib.import_module, args=("orbax.checkpoint",),
                     daemon=True).start()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    # Not part of the driver's command. --config with --traffic runs a
    # configuration that is no cell (a control, a rehearsal size) under a mix;
    # --rehearse lets the server run on the CPU backend, for
    # benchmark/rehearse.sh: its result line says platform "cpu" and is no
    # measurement.
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--config", default=None)
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--budget-s", type=float, default=budget_mod.RUN_BUDGET_S)
    ap.add_argument("--keep", action="store_true",
                    help="keep .benchmark_work/<workload>/ (logs, trace)")
    args = ap.parse_args()
    if bool(args.config) != bool(args.traffic):
        ap.error("--config and --traffic come together")

    os.environ["JAX_PLATFORMS"] = "cpu"  # this process never opens the chip
    bud = budget_mod.Budget(args.budget_s, start=T_RUN0)
    state: dict = {}
    try:
        result = run(args, bud, state)
        rc = 0 if result["correct"] else 1
    except NoAccelerator as e:
        say(f"no result: {e}")
        if state.get("server"):
            state["server"].kill()
        return 3
    except (budget_mod.OverBudget, RunFailed, asyncio.TimeoutError) as e:
        say(f"FAILED: {type(e).__name__}: {e}")
        if state.get("server"):
            state["server"].kill()
        result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
                  "device": state.get("device") or {
                      "platform": "unknown", "kind": "unknown", "count": 0,
                      "memory_peak_bytes": 0}}
        rc = 1
    finally:
        if state.get("weights"):  # half a gigabyte; never left behind
            shutil.rmtree(os.path.dirname(state["weights"]), ignore_errors=True)
    if state.get("check_line"):  # each number compared beside its limit, here too
        print(f"[benchmark] {state['check_line']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
