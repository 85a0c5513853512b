#!/usr/bin/env python
"""The decoder's prefill program alone, on the chip: one launch of the static
width on a cell's drawn weights, carrying 1, 2, 4 and K prompts' pieces.

This is the go / no-go measurement behind ISSUE 31 (a prefill launch is
filled): what a launch costs as it fills, against the one-prompt launch it
replaces. One jitted call a case with the state block donated and fed back,
median of `--iters` timed calls after two warm-up calls, by the host's clock
around a dependent read.

    chiprun -- python scripts/bench_prefill.py
    chiprun -- python scripts/bench_prefill.py --parent .scratch/parent

`--parent DIR` also times `DIR/tpuserve/models/decoder.py` (a `git archive` of
a commit whose program takes one prompt a launch) on the same weights.
`--ops` (with `--cases K` to time only the full launches) then traces two
launches of the last case and prints that launch by operation
(`scripts/op_table.py`) under the rows its expert layers' dispatch carried
beside `t * k` and the branch that ran.
One JSON line a case on stdout and in `chiprun_out/bench_prefill.jsonl`. It
refuses to run off the TPU: a time from the CPU is no device number.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import numpy as np  # noqa: E402

import op_table  # noqa: E402  (scripts/, beside this file)

from benchmark.reference.decoder import arch_from_config  # noqa: E402
from tpuserve.config import ModelConfig  # noqa: E402
from tpuserve.genserve.model import PrefillPiece  # noqa: E402
from tpuserve.models import decoder as dec  # noqa: E402

# (name, [(tokens, context behind the piece), ...]) for a launch of 1,024
# (scaled to the configuration's): the mix's lengths (a short prompt's median
# 384, a long one's tail) at contexts of 0 and 4,096.
CASES = [
    ("1x690@0", [(690, 0)]),
    ("1x1024@0", [(1024, 0)]),
    ("1x1024@4096", [(1024, 4096)]),
    ("1x384@0", [(384, 0)]),
    ("2: 512@4096 + 384@0", [(512, 4096), (384, 0)]),
    ("2: 384@0 x2", [(384, 0), (384, 0)]),
    ("4: 256@4096 + 384@0 + 256@0 + 128@0", [(256, 4096), (384, 0), (256, 0), (128, 0)]),
    ("4: 256@0 x4", [(256, 0)] * 4),
    ("K: 128@0 x8", [(128, 0)] * 8),
    ("K: 128@4096 + 128@0 x7", [(128, 4096)] + [(128, 0)] * 7),
    ("K: 100@4096 x8", [(100, 4096)] * 8),
]


def timed(fn, state, args, iters: int):
    for _ in range(2):
        state = fn(state, *args)
    np.asarray(state["pos"])
    ms = []
    for _ in range(iters):
        t0 = time.perf_counter()
        state = fn(state, *args)
        np.asarray(state["pos"])
        ms.append((time.perf_counter() - t0) * 1e3)
    return state, statistics.median(ms), min(ms)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmark", "configs", "laguna-s-2.1-half-l5.json"))
    ap.add_argument("--parent", help="a checkout whose decoder takes one prompt a launch")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--key-block", type=int, nargs="*", default=[dec.KEY_BLOCK],
                    help="key positions a block of a full layer's attention, each tried")
    ap.add_argument("--cases", default="", help="only the cases whose name starts so")
    ap.add_argument("--ops", action="store_true",
                    help="trace two launches of the last case: the launch by operation")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk every case anywhere, once, and print no time")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.rehearse:
        sys.exit("bench_prefill.py measures the chip: no TPU here")
    if args.rehearse:
        args.iters = 1
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    arch_path = os.path.join(out_dir, "bench_prefill_model_config.json")
    with open(arch_path, "w", encoding="utf-8") as f:
        json.dump(arch_from_config(cfg), f)
    gen, served = cfg["serve"]["tables"]["genserve"], cfg["assumed"]["served"]
    slots, P, C = gen["slots"], gen["kv_page_tokens"], gen["prefill_chunk"]
    mcfg = ModelConfig(name="m", family="decoder", dtype=cfg["serve"]["model"]["dtype"],
                       batch_buckets=[1],
                       options={"config_file": arch_path, "draw_weights_seed": args.seed,
                                "max_prompt_tokens": served["max_prompt_tokens"],
                                "max_new_tokens": served["max_new_tokens"]})
    model = dec.create(mcfg)
    params = jax.block_until_ready(model._drawn())
    plan = model.kv_plan(slots, P, gen["kv_pages"])
    pps, K, struct = plan.pages_per_slot, model.kv_prefill_pieces(C, P), plan.state
    state = jax.tree_util.tree_map(lambda s: jax.numpy.zeros(s.shape, s.dtype), struct)
    dev = jax.devices()[0]
    lines = []

    def emit(**row):
        if args.rehearse:
            row = {k: v for k, v in row.items() if not k.startswith("ms_")}
        row = {"device": {"platform": dev.platform, "kind": dev.device_kind}, "chunk": C, **row}
        lines.append(row)
        print(json.dumps(row), flush=True)

    def item_of(n: int):
        ids = np.arange(served["max_prompt_tokens"], dtype=np.int32) % model.vocab
        return (ids, np.int32(n), np.int32(3), np.int32(64), np.float32(0.0), np.int32(0))

    def cache_of(slot: int):
        return {"pages": np.arange(1 + slot * pps, 1 + (slot + 1) * pps, dtype=np.int32),
                "ring": np.int32(slot + 1)}

    def scaled(n: int, ctx: int) -> tuple[int, int]:
        n = max(1, n * C // 1024)
        return n, min(ctx * C // 1024, served["max_prompt_tokens"] - n)

    for key_block in args.key_block:
        dec.KEY_BLOCK = key_block   # read when the program is traced
        packed = jax.jit(lambda p, s, launch: model.prefill_chunk(p, s, launch, chunk=C),
                         donate_argnums=(1,))
        for name, pieces in CASES:
            if not name.startswith(args.cases):
                continue
            pieces = [scaled(n, ctx) for n, ctx in pieces]
            if sum(-(-n // (C // K)) for n, _ in pieces) > K:
                continue
            launch = model.pack_prefill(
                [PrefillPiece(j, item_of(ctx + n), ctx, n, cache_of(j))
                 for j, (n, ctx) in enumerate(pieces)], C, K)
            last = name
            state, med, best = timed(lambda s, l: packed(params, s, l), state, (launch,),
                                     args.iters)
            emit(program="packed", pieces_max=K, key_block=dec.KEY_BLOCK, case=name,
                 tokens=sum(n for n, _ in pieces), ms_median=round(med, 3),
                 ms_min=round(best, 3))
    if args.ops and not lines:
        sys.exit(f"bench_prefill.py --ops: no case starts with {args.cases!r} and fits a launch")
    if args.ops:
        before = np.asarray(state["acc"])
        trace_dir = os.path.join(out_dir, "bench_prefill_trace")
        jax.profiler.start_trace(trace_dir)
        for _ in range(2):
            state = packed(params, state, launch)
        np.asarray(state["pos"])
        jax.profiler.stop_trace()
        d = (np.asarray(state["acc"]) - before)[0].astype(np.int64)
        emit(case=f"{last}: the dispatch of two launches", **op_table.dispatch_of(model, C, d))
        for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True):
            op_table.print_tables(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_decoder", os.path.join(args.parent, "tpuserve", "models", "decoder.py"))
        old = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(old)
        parent = old.create(mcfg)
        one = jax.jit(lambda p, s, slot, item, start, row: parent.prefill_chunk(
            p, s, slot, item, start, row, chunk=C), donate_argnums=(1,))
        for n, ctx in ((690, 0), (1024, 0), (1024, 4096), (384, 0), (512, 4096)):
            n, ctx = scaled(n, ctx)
            state, med, best = timed(
                lambda s, *a: one(params, s, *a), state,
                (np.int32(0), item_of(ctx + n), np.int32(ctx), cache_of(0)), args.iters)
            emit(program="parent", case=f"1x{n}@{ctx}", tokens=n,
                 ms_median=round(med, 3), ms_min=round(best, 3))
    with open(os.path.join(out_dir, "bench_prefill.jsonl"), "a", encoding="utf-8") as f:
        for row in lines:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
