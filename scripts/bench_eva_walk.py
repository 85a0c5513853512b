#!/usr/bin/env python
"""A decode step's EVA attention alone, on the chip (ISSUE 55): ONE layer's walk
of every lane's virtual block table (its closed windows' summary pages, then its
ring's pages) over a synthetic pool at the cell's shape (24 slots' rings of 16
pages and 160 summary pages of 128 rows, a row a position's 32 KV heads of 128
side by side: the family's pools since ISSUE 63), timed ON THE DEVICE (the
program's own line in a trace, not the host's clock), the lanes' contexts drawn
from the mix or all alike: `ops/lane_attention.py` `head_walk` with a key in ONE
part (ISSUE 56; what `tpuserve/models/eva.py` `_walk` calls, `kv=`) over the
table as a flat work list of the (lane, key block) items that exist, at several
pages a block (`EvaServing.walk_block` comes from here), beside (1) a plain pass
over the pages the walk had to read and (2) the least time by
`benchmark/flops/eva.py` `attend_decode` for one layer. (jax's `paged_attention`,
the yardstick until ISSUE 63, reads pools by head, which the family has no more:
3.07 / 1.88 / 1.34 / 1.04 ms at 1 / 2 / 4 / 8 pages a block where this walk took
0.61, PERF.md, PR 56.)

    chiprun -- python scripts/bench_eva_walk.py
    python scripts/bench_eva_walk.py --rehearse

AND A LAUNCH'S ATTENTION ALONE (ISSUE 58; `--only launch`): ONE layer's tiles
of a prefill launch of the cell's shape (1,024 rows in 8 tiles of 128) over the
same pool, through the model's own plan and `_attend_tiles`, on both paths:

- `ops/launch_attention.py` `launch_walk` over the launch's flat work list of the
  (tile, key page) items that exist (ring pages that hold the tile's window, the
  launch's own rows seen as pages, summary pages);
- `_tile` in XLA a tile at a time (`lax.map`), the fallback and what a launch
  ran until ISSUE 58;

at the mix's launches: a full launch of one piece at window offsets 0 and 1,024
with 0, 3 and 11 closed windows behind it, and a launch of two pieces of two
prompts; beside the items, the pages' bytes at the chip's rate (the least a cell
can take: it is bound by its page's arrival) and `flops/eva.py` `attend_prefill`'s
least for one layer, the pooling apart; `paths_differ_by` is the largest gap
between the two paths' live rows (bfloat16 products, float32 sums: some 2e-3).

One JSON line a case on stdout and in `chiprun_out/bench_eva_walk/`. Off the TPU
it walks a toy shape once (`--rehearse`) and prints no time.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
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
from tpuserve.config import ModelConfig  # noqa: E402
from tpuserve.models import build  # noqa: E402
from tpuserve.ops import lane_attention as la  # noqa: E402
from tpuserve.ops import launch_attention as lat  # noqa: E402


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


def launch_cases(sz: dict, flops, peaks, kp, vp, out_dir: str, iters: int, on_tpu: bool,
                 rng) -> list:
    """ONE layer's attention of a prefill launch (ISSUE 58) through the model's
    own `_prefill_plan` and `_attend_tiles`, the kernel and `_tile` in XLA."""
    arch_path = os.path.join(out_dir, "arch.json")
    with open(arch_path, "w", encoding="utf-8") as f:
        json.dump(sz["arch"], f)
    model = build(ModelConfig(   # for its plan and its tiles; no weight is drawn
        name="m", family="eva", dtype="bfloat16", batch_buckets=[1],
        options={"config_file": arch_path, "max_prompt_tokens": sz["max_prompt"],
                 "max_new_tokens": sz["max_new"]}))
    W, P, c, slots, pps = model.window, model.rows, model.chunk, sz["slots"], sz["pages_per_slot"]
    C = min(sz["prefill_chunk"], W)
    K = model.kv_prefill_pieces(C, P)
    T, (H, kv, hd) = C // K, (model.heads[0], model.kv, model.hd)
    if kp.shape[-1] != kv * hd:   # the rehearsal's toy heads: the interpreter takes any width
        kp, vp = (jnp.asarray(rng.standard_normal(kp.shape[:2] + (kv * hd,)), kp.dtype)
                  for _ in range(2))
    state = {"bt": jnp.asarray(1 + np.arange(slots * pps).reshape(slots, pps) % (
        kp.shape[0] - c * (slots + 1) - 1), jnp.int32), "pos": jnp.zeros((slots,), jnp.int32),
        "kf": [kp]}
    bt = np.asarray(state["bt"])
    # (slot, start, length) a piece: one full piece at an offset of its window with
    # closed windows behind it, and two pieces of two prompts
    cases = {f"full-at-{off}-after-{n}-windows": [(1, n * W + off, C)]
             for off in (0, W // 2) for n in (0, 3, 11) if n * W + off + C <= sz["max_ctx"]}
    cases["two-pieces"] = [(1, 3 * W + W // 2, C // 2), (2, W // 4, C // 2 - T // 2)]
    lines = []
    for name, pieces in cases.items():
        launch = {f: np.zeros((K,), np.int32) for f in ("slot", "start", "length", "ring")}
        launch["pages"] = np.zeros((K, pps), np.int32)
        for j, (slot, start, length) in enumerate(pieces):
            launch["slot"][j], launch["start"][j], launch["length"][j] = slot, start, length
            launch["ring"][j], launch["pages"][j] = slot + 1, bt[slot]
        launch = {f: jnp.asarray(x) for f, x in launch.items()}
        q = jnp.asarray(2.0 * rng.standard_normal((C, H, hd)), jnp.bfloat16)
        k, v = (jnp.asarray(rng.standard_normal((C, kv, hd)), jnp.bfloat16) for _ in range(2))
        # what the tokens attend, and the least the layer's attention needs for it
        pos = np.concatenate([np.arange(s, s + n) for _, s, n in pieces])
        rows = float(np.sum(pos % W + 1 + pos // W * P))
        ops, nbytes = flops.attend_prefill({**sz, "layers": 1, "head_dim": hd, "d_model": H * hd},
                                           float(len(pos)), rows)
        ops -= 4.0 * H * hd * len(pos)   # the pooling's, which is not in this call
        least = max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]) * 1e3 \
            if peaks else None
        base = {"case": name, "tokens": len(pos), "rows": rows, "least_ms": least}
        outs = {}
        for path in ("tile_kernel", "xla"):
            def attend(q, k, v, kp, vp, launch, path=path):
                with steered(path, on_tpu):
                    m = model._prefill_plan(state, launch, model._tiles(launch, C))
                    assert m["tile_path"] == path
                    o = model._attend_tiles(q, k, v, kp, vp, m)
                return o, (m["work"]["items"] if m["work"] else 0)
            fn = jax.jit(attend)
            outs[path], items = fn(q, k, v, kp, vp, launch)
            line = {**base, "path": path,
                    "ms": device_ms(lambda *a, fn=fn: fn(*a)[0], (q, k, v, kp, vp, launch), None,
                                    out_dir, iters)}
            if path == "tile_kernel":   # a cell is bound by its page's arrival: K and V
                page_ms = 2.0 * kv * P * hd * 2 / peaks["hbm_bytes_per_s"] * 1e3 if peaks else None
                line.update(items=int(items), pages_ms=page_ms and int(items) * page_ms,
                            kernel_ms=device_ms(lambda *a, fn=fn: fn(*a)[0],
                                                (q, k, v, kp, vp, launch), "launch_walk",
                                                out_dir, iters))
            lines.append(line)
        if len(outs) == 2:
            live = np.concatenate([np.arange(j * T, j * T + n) for j, n in zip(
                np.cumsum([0] + [-(-n // T) for _, _, n in pieces[:-1]]),
                [n for _, _, n in pieces])])
            a, b = (np.asarray(o, np.float32)[live] for o in outs.values())
            lines[-1]["paths_differ_by"] = float(np.abs(a - b).max())
            assert np.isfinite(np.asarray(outs["tile_kernel"])).all()
    return lines


class _Named:
    """`jax` as `eva` sees it with the backend named: the family's trace-time
    choice takes that backend's branch, and nothing else does."""

    def __init__(self, backend: str) -> None:
        self.default_backend = lambda: backend

    def __getattr__(self, name):
        return getattr(jax, name)


@contextlib.contextmanager
def steered(path: str, on_tpu: bool):
    """What is traced inside takes `path`: the kernel where the backend is named
    the TPU (off it, in the Pallas interpreter at whatever shapes), `_tile` in XLA
    where it is named another."""
    from tpuserve.models import eva
    was = eva.jax, lat.launch_walk, eva.EvaServing._tiles_fit
    eva.jax = _Named("tpu" if path == "tile_kernel" else "cpu")
    if not on_tpu:
        eva.EvaServing._tiles_fit = lambda model, T: True
        lat.launch_walk = functools.partial(was[1], interpret=True)
    try:
        yield
    finally:
        eva.jax, lat.launch_walk, eva.EvaServing._tiles_fit = was


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--blocks", default="1,2,4,8")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--only", choices=("step", "launch"), help="one phase's cases alone")
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
    kp = jnp.asarray(rng.standard_normal((pool_pages, P, kv * hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((pool_pages, P, kv * hd)), dtype)
    q = jnp.asarray(rng.standard_normal((slots, kv, hd)), dtype)
    mix = np.clip(np.exp(rng.normal(np.log(6144), 0.6, slots)), 1024, 24576).astype(int) \
        + rng.integers(0, 256, slots)
    cases = {"mix": np.minimum(mix, sz["max_ctx"] - 1),
             "half-window": np.full(slots, 3 * W + W // 2),
             "first-window": np.full(slots, W // 2)}
    if args.rehearse:
        cases = {"toy": rng.integers(1, sz["max_ctx"], slots)}
    lines = []
    if args.only != "step":
        lines += launch_cases(sz, flops, peaks, kp, vp, out_dir, args.iters, on_tpu, rng)
    for name, pos in cases.items() if args.only != "launch" else ():
        rows = float(np.sum(pos % W + 1 + pos // W * P))
        ops, nbytes = flops.attend_decode({**sz, "layers": 1, "head_dim": hd, "d_model": kv * hd},
                                          float(slots), rows)
        least = max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]) * 1e3 \
            if peaks else None
        base = {"case": name, "lanes": slots, "rows": rows, "least_ms": least}
        for block in [int(b) for b in args.blocks.split(",")]:
            wide = -(-(pps + c) // block) * block
            table, seen = tables(pos, W, c, P, pps, slots, wide)
            # the table as a flat work list of (lane, key block) items
            work = la.work_list(jnp.asarray(seen - 1), jnp.asarray(table), P, block)
            fn = jax.jit(lambda q, kp, vp, work: la.head_walk(
                q, None, kp, None, vp, work, scale=hd ** -0.5, kv=kv, interpret=not on_tpu))
            if on_tpu or block == 4:
                ms = device_ms(fn, (q, kp, vp, work), None, out_dir, args.iters)
                lines.append({**base, "walk": "head_walk (a key in one part)",
                              "block_pages": block, "items": int(work["items"]), "ms": ms})
        if on_tpu:   # a plain pass over the pages the walk had to read: in and out
            table, _ = tables(pos, W, c, P, pps, slots, pps + c)
            need = np.unique(np.concatenate([row[:-(-int(r) // P)] for row, r in zip(
                table, pos // W * P + pos % W + 1)]))
            idx = jnp.asarray(need)
            fn = jax.jit(lambda kp, vp, idx: (jnp.take(kp, idx, axis=0) + 1,
                                              jnp.take(vp, idx, axis=0) + 1))
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
