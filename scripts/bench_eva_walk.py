#!/usr/bin/env python
"""A decode step's EVA attention alone, on the chip (ISSUE 55): ONE layer's walk
of every lane's virtual block table (its closed windows' summary pages, then its
ring's pages) over a synthetic pool at the cell's shape (32 KV heads of 128, 24
slots' rings of 16 pages and 160 summary pages of 128 rows), timed ON THE DEVICE
(the program's own line in a trace, not the host's clock), the lanes' contexts
drawn from the mix or all alike, in both walks the repo has:

- `ops/lane_attention.py` `head_walk` with a key in ONE part (ISSUE 56; what
  `tpuserve/models/eva.py` `_walk` calls) over the table as a flat work list of
  the (lane, key block) items that exist, at several pages a block
  (`EvaServing.walk_block` comes from here);
- jax's `paged_attention` at several `pages_per_compute_block`, the yardstick:
  what `_walk` called until ISSUE 56 (at 8 pages);

beside (1) a plain pass over the pages the walk had to read and (2) the least
time by `benchmark/flops/eva.py` `attend_decode` for one layer.

    chiprun -- python scripts/bench_eva_walk.py
    python scripts/bench_eva_walk.py --rehearse

One JSON line a case on stdout and in `chiprun_out/bench_eva_walk/`. Off the TPU
it walks a toy shape once (`--rehearse`) and prints no time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench_head_walk import device_ms  # noqa: E402

from benchmark import spec  # noqa: E402
from tpuserve.ops import lane_attention as la  # noqa: E402


def tables(pos: np.ndarray, W: int, c: int, P: int, pps: int, slots: int, wide: int):
    """Each lane's virtual table and length as `eva._step_plan` builds them:
    lane b holds ring b + 1 and the ledger's pages 1 + b * pps onwards."""
    first = c * (slots + 1)
    n, j = pos // W, pos % W
    table = np.zeros((len(pos), wide), np.int32)
    for b in range(len(pos)):
        table[b, :n[b]] = first + 1 + b * pps + np.arange(n[b])
        table[b, n[b]:n[b] + c] = c * (b + 1) + np.arange(c)
    return table, (n * P + j + 1).astype(np.int32)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--blocks", default="1,2,4,8")
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearse:
        print("bench_eva_walk: no TPU here; --rehearse walks a toy shape", file=sys.stderr)
        return 2
    out_dir = os.path.join(REPO, "chiprun_out", "bench_eva_walk")
    os.makedirs(out_dir, exist_ok=True)
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "rehearsal-eva-tiny" if args.rehearse else "evabyte-6.5b-l8")
    sz = spec.load_module("reference", "eva").sizes_from_config(cfg)
    flops = spec.load_module("flops", "eva")
    peaks = spec.load_json("peaks.json")["devices"].get("TPU v5 lite", {})
    kv, hd, W, c, P = sz["kv_heads"], sz["head_dim"], sz["win_tokens"], sz["chunk"], sz["summary_rows"]
    slots, pps, pages = sz["slots"], sz["pages_per_slot"], sz["kv_pages"]
    if args.rehearse:
        hd = 128   # the kernels' lane width, whatever the toy's heads
    dtype = jnp.bfloat16
    rng = np.random.default_rng(0)
    pool_pages = c * (slots + 1) + max(pages, slots * pps + 1)
    kp = jnp.asarray(rng.standard_normal((kv, pool_pages, P, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((kv, pool_pages, P, hd)), dtype)
    q = jnp.asarray(rng.standard_normal((slots, kv, hd)), dtype)
    mix = np.clip(np.exp(rng.normal(np.log(6144), 0.6, slots)), 1024, 24576).astype(int) \
        + rng.integers(0, 256, slots)
    cases = {"mix": np.minimum(mix, sz["max_ctx"] - 1),
             "half-window": np.full(slots, 3 * W + W // 2),
             "first-window": np.full(slots, W // 2)}
    if args.rehearse:
        cases = {"toy": rng.integers(1, sz["max_ctx"], slots)}
    from jax.experimental.pallas.ops.tpu.paged_attention import paged_attention

    lines = []
    for name, pos in cases.items():
        rows = float(np.sum(pos % W + 1 + pos // W * P))
        ops, nbytes = flops.attend_decode({**sz, "layers": 1, "head_dim": hd, "d_model": kv * hd},
                                          float(slots), rows)
        least = max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]) * 1e3 \
            if peaks else None
        base = {"case": name, "lanes": slots, "rows": rows, "least_ms": least}
        for block in [int(b) for b in args.blocks.split(",")]:
            wide = -(-(pps + c) // block) * block
            table, seen = tables(pos, W, c, P, pps, slots, wide)
            t, n = jnp.asarray(table), jnp.asarray(seen)
            if on_tpu:
                fn = jax.jit(lambda q, kp, vp, n, t, block=block: paged_attention(
                    q, kp, vp, n, t, pages_per_compute_block=block))
                ms = device_ms(fn, (q, kp, vp, n, t), None, out_dir, args.iters)
                lines.append({**base, "walk": "paged_attention", "block_pages": block, "ms": ms})
            # the same table as a flat work list of (lane, key block) items
            work = la.work_list(jnp.asarray(seen - 1), t, P, block)
            fn = jax.jit(lambda q, kp, vp, work: la.head_walk(
                q, None, kp, None, vp, work, scale=hd ** -0.5, interpret=not on_tpu))
            if on_tpu or block == 4:
                ms = device_ms(fn, (q, kp, vp, work), None, out_dir, args.iters)
                lines.append({**base, "walk": "head_walk (a key in one part)",
                              "block_pages": block, "items": int(work["items"]), "ms": ms})
        if on_tpu:   # a plain pass over the pages the walk had to read: in and out
            table, _ = tables(pos, W, c, P, pps, slots, pps + c)
            need = np.unique(np.concatenate([row[:-(-int(r) // P)] for row, r in zip(
                table, pos // W * P + pos % W + 1)]))
            idx = jnp.asarray(need)
            fn = jax.jit(lambda kp, vp, idx: (jnp.take(kp, idx, axis=1) + 1, jnp.take(vp, idx, axis=1) + 1))
            lines.append({**base, "walk": "plain pass over the pages read (in and out)",
                          "pages": int(len(need)), "ms": device_ms(fn, (kp, vp, idx), None, out_dir,
                                                                   args.iters)})
    for line in lines:
        print(json.dumps(line), flush=True)
    with open(os.path.join(out_dir, "lines.jsonl"), "w", encoding="utf-8") as f:
        f.write("\n".join(json.dumps(line) for line in lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
