#!/usr/bin/env python
"""The hybrid family's two programs alone, on the chip: one decode step of
every slot and one packed prefill launch on a cell's drawn weights, timed by
the host's clock around a dependent read, then traced, with what the trace
says of the operations under the program's `ssm_update` / `ssm_scan` scopes
(their names, every statistic they carry, their device time a launch).

    chiprun -- python scripts/bench_hybrid.py
    python scripts/bench_hybrid.py --rehearse --config benchmark/configs/rehearsal-hybrid-tiny.json

This is where PERF.md section 3's note on what identifies a scope's
operations in a device trace comes from, and section 5's table of a launch and
a step by operation (`scripts/op_table.py`, printed last, with the rows the
expert layers' dispatch carried beside `t * k` and the branch that ran). One
JSON line a case on stdout and in `chiprun_out/bench_hybrid/`. Off the TPU it
walks the path (`--rehearse`) and prints no time.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import op_table  # noqa: E402  (scripts/, beside this file)
from benchmark import spec  # noqa: E402
from tpuserve.config import ModelConfig  # noqa: E402
from tpuserve.genserve.model import PrefillPiece  # noqa: E402
from tpuserve.models import build  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmark", "configs", "nemotron-3-super-q4-l11.json"))
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit("bench_hybrid: no TPU here; a time from another backend is no device number")
    out_dir = os.path.join(REPO, "chiprun_out", "bench_hybrid")
    os.makedirs(out_dir, exist_ok=True)
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    sz = spec.load_module("reference", "hybrid").sizes_from_config(cfg)
    arch_path = os.path.join(out_dir, "arch.json")
    with open(arch_path, "w", encoding="utf-8") as f:
        json.dump(sz["arch"], f)
    model = build(ModelConfig(
        name="m", family="hybrid", dtype=cfg["serve"]["model"]["dtype"], batch_buckets=[1],
        options={"config_file": arch_path, "draw_weights_seed": args.seed,
                 "max_prompt_tokens": sz["max_prompt"], "max_new_tokens": sz["max_new"]}))
    slots, pages, P, chunk = sz["slots"], sz["kv_pages"], sz["page_tokens"], sz["prefill_chunk"]
    plan = model.kv_plan(slots, P, pages)
    pps = plan.pages_per_slot
    k = model.kv_prefill_pieces(chunk, P)
    tile = chunk // k
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init_params(None))
    print(f"weights drawn in {time.perf_counter() - t0:.1f} s", flush=True)
    state = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), plan.state)
    prefill = jax.jit(lambda p, s, l: model.prefill_chunk(p, s, l, chunk=chunk),
                      donate_argnums=(1,))
    step = jax.jit(model.step, donate_argnums=(1,))
    rng = np.random.default_rng(args.seed)
    n_prompt = min(tile - 3, sz["max_prompt"])

    def piece(slot: int, n: int) -> PrefillPiece:
        ids = np.zeros((sz["max_prompt"],), np.int32)
        ids[:n] = rng.integers(0, sz["vocab"], n)
        item = (ids, np.int32(n), np.int32(slot), np.int32(sz["max_new"]), np.float32(0.0),
                np.int32(8))
        row = np.zeros((pps,), np.int32)
        row[0] = 1 + slot   # one page a slot is enough for these short prompts
        return PrefillPiece(slot, item, 0, n, row)

    rows = []

    def emit(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    def dispatch(case: str, phase: int, tokens: int, before) -> None:
        d = (np.asarray(state["acc"]) - before)[phase].astype(np.int64)
        emit(case=case, **op_table.dispatch_of(model, tokens, d))

    # every lane armed: launches of K prompts, each a tile short of three rows
    times, before = [], np.asarray(state["acc"])
    for first in range(0, slots, k):
        launch = model.pack_prefill([piece(s, n_prompt) for s in range(first, min(slots, first + k))],
                                    chunk, k)
        t0 = time.perf_counter()
        state = prefill(params, state, launch)
        np.asarray(state["pos"])
        times.append(time.perf_counter() - t0)
    if on_tpu:
        emit(case=f"prefill launch, {k} pieces of {n_prompt}", launches=len(times),
             first_s=times[0], median_ms=statistics.median(times[1:] or times) * 1e3)
    dispatch("prefill launches: the dispatch", 0, chunk, before)
    times, before = [], np.asarray(state["acc"])
    for _ in range(args.iters + 2):
        t0 = time.perf_counter()
        state, out = step(params, state)
        np.asarray(out["n_new"])
        times.append(time.perf_counter() - t0)
    assert int(np.sum(np.asarray(out["n_new"]) > 1)) == slots, "every lane decodes"
    dispatch("decode steps: the dispatch", 1, slots, before)
    if on_tpu:
        emit(case=f"decode step, {slots} live lanes", first_s=times[0],
             median_ms=statistics.median(times[2:]) * 1e3)
        stats = jax.devices()[0].memory_stats() or {}
        emit(case="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             peak_bytes_reserved=stats.get("peak_bytes_reserved"))
    # the trace: three steps and two launches, and what their operations carry
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    trace_dir = os.path.join(out_dir, "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for _ in range(3):
        state, out = step(params, state)
    np.asarray(out["n_new"])
    for first in (0, k):
        state = prefill(params, state, model.pack_prefill(
            [piece(s, n_prompt) for s in range(first, first + k)], chunk, k))
    np.asarray(state["pos"])
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    # Every operation's event of the LAST launch of each program, with what it carries.
    with open(os.path.join(out_dir, "ops.jsonl"), "w", encoding="utf-8") as f:
        for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True):
            if os.path.getsize(path) < 32 * 2 ** 20:   # small enough to bring back and read by hand
                shutil.copy(path, os.path.join(out_dir, "mini.xplane.pb"))
            for plane in ProfileData.from_file(path).planes:
                lines = {line.name: line for line in plane.lines}
                f.write(json.dumps({"plane": plane.name, "lines": list(lines)}) + "\n")
                if "XLA Ops" not in lines or "XLA Modules" not in lines:
                    continue
                last = {}
                for ev in lines["XLA Modules"].events:
                    last[ev.name.split("(")[0]] = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                                                   ev.name)
                for mod, (lo, hi, full) in last.items():
                    f.write(json.dumps({"module": full, "ns": hi - lo}) + "\n")
                    for ev in lines["XLA Ops"].events:
                        if lo <= ev.start_ns < hi:
                            st = {str(a): (b if isinstance(b, (int, float, str)) else repr(b))
                                  for a, b in ev.stats if "ps" not in str(a)}
                            f.write(json.dumps({"in": mod, "name": ev.name[:700],
                                                "ns": int(ev.duration_ns), "stats": st}) + "\n")
            op_table.print_tables(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with open(os.path.join(out_dir, "report.jsonl"), "w", encoding="utf-8") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    print(f"operation events written to {out_dir}/ops.jsonl", flush=True)


if __name__ == "__main__":
    main()
