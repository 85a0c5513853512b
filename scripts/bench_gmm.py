#!/usr/bin/env python
"""One grouped product alone, on the chip, at each routed cell's shapes: the
in-product (K = the experts' input width, N = their hidden width) and the
out-product (the two swapped) of a prefill launch and of a decode step, at the
rows the layer's dispatch carries (`ops/moe.py` `_row_bound`), through
megablox `gmm` at the tiles the PARENT of ISSUE 48 chose (`today`), at the
rule's (`ops/moe.py` `_row_tile`, `_kernel_tiles`: `rule`), and at a small
sweep around them: row tiles 128/256 (`--tms`), K whole or cut, N's tile. Group
sizes uniform (every held expert its expected rows), drawn (a multinomial over
equal shares, what a router with drawn weights gives: groups of the expected
size that start anywhere in a tile) and skewed (a multinomial over Zipf-like
shares, so some experts get several tiles and many a few rows).

A row of the table a case: ms a call ON THE DEVICE (one program a case, each
under its own name in ONE profiler session a shape; the median of its launches
on the chip's `XLA Modules` line, and of the kernel's own events inside them),
beside the two floors: the held kernels read once over the chip's bytes a
second, and the live rows' products over its peak (`benchmark/peaks.json`).

    chiprun -- python scripts/bench_gmm.py [--cells joyai-llm-flash-l5,...] [--phases launch,step]
    python scripts/bench_gmm.py --rehearse

One JSON line a case on stdout and in `chiprun_out/bench_gmm/cases.jsonl`. Off
the TPU it walks a toy shape through the interpreter (`--rehearse`) and prints
no time. Not code a cell runs.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import shutil
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.pallas.ops.tpu.megablox import gmm  # noqa: E402

from benchmark.trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,  # noqa: E402
                                    find_xplane, op_name)
from tpuserve.ops import moe  # noqa: E402

CELLS = ("laguna-s-2.1-half-l5", "nemotron-3-super-q4-l11", "joyai-llm-flash-l5",
         "longcat-flash-chat-e16-l4", "xing4.0-29b-a4b-l8")
# No cell gives an expert a thousand rows: two made-up launches that do, to see
# from where a wider row tile pays (name, K, N, experts, rows).
WIDE = (("wide-16x1024", 2048, 2048, 16, 16384), ("wide-8x4096", 2048, 1024, 8, 32768))


def today(picks: int, count: int, of: int, k: int, n: int) -> tuple[int, tuple[int, int, int]]:
    """The rows carried and the tiles before ISSUE 48: the row tile from the
    launch's rows (the bound rounded to it), each kernel dimension cut to at
    most 1024."""
    rows = picks
    if count < of:
        want = math.ceil(picks * count / of * moe.COMPACT_SLACK)
        tm = 128 if want <= 4096 else 256
        rows = min(picks, -(-want // tm) * tm)
    return rows, (128 if rows <= 4096 else 256, moe._tile(k), moe._tile(n))


def rule(shape: dict) -> tuple[int, int, int]:
    tk, tn = moe._kernel_tiles(shape["k"], shape["n"])
    return (moe._row_tile(shape["expects"], tk, tn), tk, tn)


def shapes_of(name: str) -> list[dict]:
    """A cell's four grouped products: {cell, phase, product, picks, rows, count,
    of, k, n, held (the live rows expected), expects (rows an expert expects)}."""
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json"), encoding="utf-8") as f:
        cfg = json.load(f)
    s = importlib.import_module("benchmark.reference." + cfg["family"]).sizes_from_config(cfg)
    d, f_ = int(s.get("latent") or s["d_model"]), int(s["expert_width"])
    count, of = int(s["experts_held"]), int(s["num_experts"]) + int(s.get("zero_experts", 0))
    out = []
    for phase, t in (("launch", int(s["prefill_chunk"])), ("step", int(s["slots"]))):
        picks = t * int(s["top_k"])
        for product, k, n in (("in", d, f_), ("out", f_, d)):
            out.append({"cell": name, "phase": phase, "product": product, "picks": picks,
                        "rows": moe._row_bound(picks, count, of), "count": count, "of": of,
                        "k": k, "n": n, "held": picks * count // of, "expects": picks / of})
    return out


def sizes_of(held: int, count: int, draw: str, rng) -> np.ndarray:
    if draw == "uniform":   # as even as whole rows allow
        return (held // count + (np.arange(count) < held % count)).astype(np.int32)
    share = np.ones(count) if draw == "drawn" else \
        rng.permutation(1.0 / np.sqrt(np.arange(1, count + 1)))
    return rng.multinomial(held, share / share.sum()).astype(np.int32)


def candidates(shape: dict, sweep: bool, tms: list[int]) -> list[tuple[str, int, tuple]]:
    """[(what, rows, (tm, tk, tn))]: today's, the rule's, and around them."""
    k, n = shape["k"], shape["n"]
    rows_today, tiles_today = today(shape["picks"], shape["count"], shape["of"], k, n)
    out = [("today", rows_today, tiles_today), ("rule", shape["rows"], rule(shape))]
    if sweep:
        tns = [t for t in range(n, 0, -128) if n % t == 0]
        for tm in tms:
            out.append(("sweep", shape["rows"], (tm, *tiles_today[1:])))
            fit = [t for t in tns if moe._tile_bytes(tm, k, t) <= 15 * 2 ** 20][:3]
            out += [("sweep", shape["rows"], (tm, k, t)) for t in fit]
        if k % 256 == 0:   # the same N tile under K in two pieces: is the kernel fetched again?
            out.append(("sweep", shape["rows"], (128, k // 2, rule(shape)[2])))
    seen, uniq = set(), []
    for what, rows, tiles in out:
        if rows % tiles[0] == 0 and all(tiles) and (what != "sweep" or (rows, tiles) not in seen):
            uniq.append((what, rows, tiles))
            seen.add((rows, tiles))
    return uniq


def device_ms(path: str, iters: int) -> list[tuple[float, float]]:
    """[(median ms a launch, median ms of its `gmm` operations a launch)] a
    case, in the order the cases ran, `iters` launches each, on the first chip
    of the trace at `path`. By ORDER, not by the program's name: two cases of
    one text are one compiled program under the first one's name."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if not DEVICE_PLANE.match(plane.name) or MODULES_LINE not in lines:
            continue
        mods = sorted((int(e.start_ns), int(e.duration_ns)) for e in lines[MODULES_LINE].events
                      if e.name.startswith("jit_case"))
        kern = sorted((int(e.start_ns), int(e.duration_ns)) for e in lines[OPS_LINE].events
                      if op_name(e.name).startswith("gmm")) if OPS_LINE in lines else []
        per, i = [], 0
        for lo, ns in mods:
            while i < len(kern) and kern[i][0] < lo:
                i += 1
            inside = 0
            while i < len(kern) and kern[i][0] < lo + ns:
                inside += kern[i][1]
                i += 1
            per.append((ns, inside))
        if len(per) % iters:
            raise SystemExit(f"bench_gmm: {len(per)} launches in {path}: no whole cases of {iters}")
        return [(statistics.median(a for a, _b in per[j:j + iters]) / 1e6,
                 statistics.median(b for _a, b in per[j:j + iters]) / 1e6)
                for j in range(0, len(per), iters)]
    raise SystemExit(f"bench_gmm: no device plane in {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS + tuple(w[0] for w in WIDE)))
    ap.add_argument("--phases", default="launch,step")
    ap.add_argument("--no-sweep", action="store_true", help="today's tiles and the rule's alone")
    ap.add_argument("--tms", default="128,256", help="row tiles of the sweep")
    ap.add_argument("--draws", default="uniform,drawn,skewed")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit("bench_gmm: no TPU here; a time from another backend is no device number")
    peak = {}
    if on_tpu:   # a device that is not in the table is an error, not a default
        with open(os.path.join(REPO, "benchmark", "peaks.json"), encoding="utf-8") as f:
            peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    out_dir = os.path.join(REPO, "chiprun_out", "bench_gmm")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "cases.jsonl"), "w", encoding="utf-8")

    def emit(**row):
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")

    shapes = [s for c in args.cells.split(",") if c in CELLS
              for s in shapes_of(c) if s["phase"] in args.phases.split(",")]
    shapes += [{"cell": name, "phase": "launch", "product": "in", "picks": rows, "rows": rows,
                "count": g, "of": g, "k": k, "n": n, "held": rows, "expects": rows / g}
               for name, k, n, g, rows in WIDE if name in args.cells.split(",")]
    if not on_tpu:
        shapes = [{"cell": "toy", "phase": "launch", "product": "in", "picks": 1024, "rows": 512,
                   "count": 4, "of": 8, "k": 256, "n": 256, "held": 400, "expects": 100.0}]
    rng = np.random.default_rng(48)
    tms = [int(t) for t in args.tms.split(",")]
    for shape in shapes:
        count, k, n = shape["count"], shape["k"], shape["n"]
        rhs = jax.random.normal(jax.random.key(1), (count, k, n), jnp.bfloat16) / np.sqrt(k)
        sweep = shape["phase"] == "launch" and not args.no_sweep
        both = (rule(shape)[1:], today(shape["picks"], count, shape["of"], k, n)[1][1:])
        lhs, cases = {}, []
        for draw in args.draws.split(","):
            sizes = sizes_of(shape["held"], count, draw, rng)
            live, on_chip = int(sizes.sum()), jnp.asarray(sizes)
            want = None
            # the first draw takes the whole sweep, the others both kernel tiles at each row tile
            for what, rows, tiles in [c for c in candidates(shape, sweep, tms) if not cases
                                      or c[0] != "sweep" or c[2][1:] in both]:
                if rows not in lhs:
                    lhs[rows] = jnp.asarray(rng.standard_normal((rows, k), np.float32),
                                            jnp.bfloat16)

                def case(lhs, rhs, sizes, tiles=tiles):
                    return gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32, tiling=tiles,
                               interpret=not on_tpu)
                case.__name__ = f"case{len(cases)}"
                row = dict(shape, what=what, rows=rows, tiles=list(tiles), draw=draw, live=live,
                           largest=int(sizes.max()), fast_bytes=moe._tile_bytes(*tiles))
                try:
                    run = jax.jit(case)
                    got = jax.block_until_ready(run(lhs[rows], rhs, on_chip))
                except Exception as e:  # tiles the compiler refuses are a row of the table too
                    emit(**row, refused=f"{type(e).__name__}: {str(e)[:200]} ... {str(e)[-400:]}")
                    continue
                if want is None or want[0] != rows:   # the first case of a draw at these rows
                    want = (rows, got[:live])
                row["largest_gap"] = float(jnp.max(jnp.abs(got[:live] - want[1]))) if live else 0.0
                cases.append((lhs[rows], run, on_chip, row))
        if not on_tpu:
            for _lhs, _run, _sizes, row in cases:
                emit(**row, rehearsed=True)
            continue
        trace = os.path.join(out_dir, "trace")
        shutil.rmtree(trace, ignore_errors=True)
        with jax.profiler.trace(trace):
            for rows_in, run, sizes, _row in cases:
                for _ in range(args.iters):
                    jax.block_until_ready(run(rows_in, rhs, sizes))
        ms = device_ms(find_xplane(trace), args.iters)
        assert len(ms) == len(cases), (len(ms), len(cases))
        for (call, kernel), (_lhs, _run, _sizes, row) in zip(ms, cases):
            bytes_ms = 2.0 * count * k * n / peak["hbm_bytes_per_s"] * 1e3
            flop_ms = 2.0 * row["live"] * k * n / peak["bf16_flops_per_s"] * 1e3
            emit(**row, ms_a_call=call, kernel_ms=kernel, kernels_bytes_floor_ms=round(bytes_ms, 4),
                 products_floor_ms=round(flop_ms, 4))
        del lhs, rhs, cases
    log.close()


if __name__ == "__main__":
    main()
