#!/usr/bin/env python
"""The mla_sc family's two programs alone, on the chip, at the cell's sizes and
the mix's contexts: every slot filled with a prompt drawn from the mix's
lengths and advanced a drawn part of its answer, then a decode step of every
lane and ONE attention of it alone at BOTH walks (the kernel's, at several
cells, beside the fallback in XLA, `mla`'s lanes one after another, at two
widths of key block: `scripts/bench_mla.py` `decode_walks`, each row with the
cache rows walked over the rows attended) and packed prefill launches AT SEVERAL
SETTINGS of what the family sets for itself (`key_block`, `TILE_ROWS`) and at
`mla`'s own (one prompt a launch in a tile as wide as the launch), timed by the
host's clock around a dependent read; then one trace, with a table by
operation of one step and one launch at the family's own settings
(`scripts/bench_mla.py` `by_operation`).

    chiprun -- python scripts/bench_mla_sc.py [--only step|prefill]
    python scripts/bench_mla_sc.py --rehearse --config benchmark/configs/rehearsal-mla_sc-tiny.json

One JSON line a case on stdout and in `chiprun_out/bench_mla_sc/`. Off the TPU
it walks the path (`--rehearse`) and prints no time.
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
sys.path[:0] = [REPO, os.path.join(REPO, "scripts")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import bench_mla  # noqa: E402
from benchmark import spec  # noqa: E402
from tpuserve.config import ModelConfig  # noqa: E402
from tpuserve.genserve.model import PrefillPiece  # noqa: E402
from tpuserve.models import mla_sc  # noqa: E402

bench_mla.KINDS = (("moe_layer", "the routed layer (scope moe_layer)"),) + bench_mla.KINDS


# The step's walk left in XLA under this family's layer: `mla`'s fallback, the
# lanes one after another, each over key blocks of `key_block` positions.
InXla = bench_mla.xla_walk(mla_sc.ShortcutLatentServing)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmark", "configs", "longcat-flash-chat-e16-l4.json"))
    ap.add_argument("--mix", default="agent-closed")
    ap.add_argument("--only", choices=("step", "prefill"), default=None)
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit("bench_mla_sc: no TPU here; a time from another backend is no device number")
    out_dir = os.path.join(REPO, "chiprun_out", "bench_mla_sc")
    os.makedirs(out_dir, exist_ok=True)
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    sz = spec.load_module("reference", "mla_sc").sizes_from_config(cfg)
    arch_path = os.path.join(out_dir, "arch.json")
    with open(arch_path, "w", encoding="utf-8") as f:
        json.dump(sz["arch"], f)

    def make(cls=mla_sc.ShortcutLatentServing, **attrs):
        m = cls(ModelConfig(
            name="m", family="mla_sc", dtype=cfg["serve"]["model"]["dtype"], batch_buckets=[1],
            options={"config_file": arch_path, "draw_weights_seed": args.seed,
                     "max_prompt_tokens": sz["max_prompt"], "max_new_tokens": sz["max_new"]}))
        for k, v in attrs.items():
            setattr(m, k, v)
        return m

    model = make()
    slots, pages, P = sz["slots"], sz["kv_pages"], sz["page_tokens"]
    plan = model.kv_plan(slots, P, pages)
    chunk, pps = sz["prefill_chunk"], plan.pages_per_slot
    if args.rehearse:   # a toy's tiles are pages
        model.TILE_ROWS = model.key_block = P
    rng = np.random.default_rng(args.seed)
    length = spec.load_module("traffic", "text").length_grid
    cls_ = spec.load_mix(args.mix)["classes"][0] if not args.rehearse else {
        "prompt_tokens": {"dist": "fixed", "value": 9, "min": 9, "max": 9},
        "max_new_tokens": {"dist": "fixed", "value": 8, "min": 8, "max": 8}}
    prompts = rng.permutation(length(cls_["prompt_tokens"], slots)).astype(int)
    done = (rng.permutation(length(cls_["max_new_tokens"], slots)) * rng.random(slots)).astype(int)
    # pages for a slot's prompt, the part of its answer it is advanced by, and the steps timed
    need = [min(pps, -(-(int(p) + int(d) + 10 * args.iters) // P)) for p, d in zip(prompts, done)]
    first_page = 1 + np.concatenate([[0], np.cumsum(need)[:-1]])
    assert first_page[-1] + need[-1] <= pages, "the pool holds every slot's context"
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init_params(None))
    print(f"weights drawn in {time.perf_counter() - t0:.1f} s; {slots} lanes, prompts median "
          f"{int(np.median(prompts))}, max {int(prompts.max())}", flush=True)
    state = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), plan.state)
    items = []
    for slot in range(slots):
        ids = np.zeros((sz["max_prompt"],), np.int32)
        ids[:prompts[slot]] = rng.integers(0, sz["vocab"], prompts[slot])
        items.append((ids, np.int32(prompts[slot]), np.int32(slot), np.int32(sz["max_new"]),
                      np.float32(0.0), np.int32(8)))

    def piece(slot: int, start: int, most: int) -> PrefillPiece:
        row = np.zeros((pps,), np.int32)
        row[:need[slot]] = first_page[slot] + np.arange(need[slot])
        return PrefillPiece(slot, items[slot], start, min(most, int(prompts[slot]) - start), row)

    def launches(m, width):
        """Every slot's prompt in launches of `width` rows, packed as the
        engine packs them: pieces of whole tiles, in order, as many as fit."""
        k = m.kv_prefill_pieces(width, P)
        tile, cur, used = width // k, [], 0
        for slot in range(slots):
            start = 0
            while start < prompts[slot]:
                room = (k - used) * tile
                if room == 0 or len(cur) == k:
                    yield m.pack_prefill(cur, width, k), cur
                    cur, used, room = [], 0, width
                p = piece(slot, start, room)
                cur.append(p)
                used += -(-p.length // tile)
                start += p.length
        if cur:
            yield m.pack_prefill(cur, width, k), cur

    rows = []

    def emit(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    def timed_prefill(m, width, what):
        nonlocal state
        fn = jax.jit(lambda p, s, l: m.prefill_chunk(p, s, l, chunk=width), donate_argnums=(1,))
        times, live = [], []
        for launch, pieces in launches(m, width):
            t0 = time.perf_counter()
            state = fn(params, state, launch)
            np.asarray(state["pos"])
            times.append(time.perf_counter() - t0)
            live.append(sum(p.length for p in pieces))
        if on_tpu:
            emit(case=f"prefill, {what}: launches of {width} rows in "
                 f"{m.kv_prefill_pieces(width, P)} tiles, key blocks of {m.key_block or 1024}",
                 launches=len(times), live_tokens_a_launch=statistics.mean(live),
                 first_s=times[0], median_ms=statistics.median(times[1:]) * 1e3,
                 ms_per_1000_live_tokens=sum(times[1:]) / sum(live[1:]) * 1e6)
        return fn

    own_prefill = timed_prefill(model, chunk, "the family's own settings")
    # advance every lane by its drawn part of an answer: positions alone (rows past the prompt
    # hold zeros, which cost what any rows cost)
    state = dict(state, pos=state["pos"] + jnp.asarray(done, jnp.int32))
    context = np.asarray(state["pos"])
    print(f"contexts: mean {context.mean():.0f}, median {np.median(context):.0f}, "
          f"max {context.max()}", flush=True)

    own_step = None
    if args.only != "prefill":
        walks = {f"the family's own walk, cells of {model.step_keys} keys": model,
                 f"the walk left in XLA: lane by lane, key blocks of {model.key_block}":
                 make(InXla, key_block=model.key_block)}
        if not args.rehearse:
            for keys in (256, 1024):
                walks[f"the family's own walk, cells of {keys} keys"] = make(step_keys=keys)
            walks["the walk left in XLA: lane by lane, key blocks of 1,024"] = make(
                InXla, key_block=None)
        state, steps = bench_mla.decode_walks(walks, params, params["layer0"]["attn0"], state,
                                              args.iters, emit, on_tpu)
        own_step = next(iter(steps.values()))
    if args.only != "step" and not args.rehearse:
        timed_prefill(make(), 2 * chunk, "twice the width")
        timed_prefill(make(TILE_ROWS=chunk, key_block=None), chunk,
                      "mla's settings (a tile as wide as the launch)")
    if on_tpu:
        stats = jax.devices()[0].memory_stats() or {}
        emit(case="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             peak_bytes_reserved=stats.get("peak_bytes_reserved"))
    # the trace, at the family's own settings: three steps and two launches
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    trace_dir = os.path.join(out_dir, "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    if own_step is not None:
        for _ in range(3):
            state, out = own_step(params, state)
        np.asarray(out["n_new"])
    for launch, _pieces in list(launches(model, chunk))[:2]:
        state = own_prefill(params, state, launch)
    np.asarray(state["pos"])
    jax.profiler.stop_trace()
    with open(os.path.join(out_dir, "ops.jsonl"), "w", encoding="utf-8") as f:
        for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True):
            for row in bench_mla.by_operation(path, f):
                emit(**row)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with open(os.path.join(out_dir, "report.jsonl"), "w", encoding="utf-8") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    print(f"operation events written to {out_dir}/ops.jsonl", flush=True)


if __name__ == "__main__":
    main()
