#!/usr/bin/env python3
"""Time ONE picked prefill tile's block scores ALONE on the chip
(``tpuserve/ops/block_scores.py``, ISSUE 69): the kernel beside the plain form
(``BlockSelectAttention._block_scores``), and the whole of a tile's picks
(``_tile_keep``: the scores, the threshold's search and the ties) on both paths,
at the MiniCPM-SALA cell's shapes: a tile of 512 rows, 32 heads of 128 on 2 KV
groups, bfloat16, a block table of 1,029 pages of 64 positions (4,116 windows of
32 keys at stride 16, 1,029 blocks of 64), with the tile's last position at 10k,
20k, 40k and 65k.

    chiprun -- python scripts/bench_block_scores.py [--last 10000,20000,40000,65535]
    python scripts/bench_block_scores.py --rehearse   # a toy table in the interpreter, no time

Prints a line a position, a path and a part: ms a tile ON THE DEVICE (the median
of the program's ``--calls`` launches on a profiler session's module line, not
the host's clock), for the scores the share of the products' peak (2 KV x 16 heads
x 512 rows x the windows the tile can see x 128 x 2 FLOP over the chip's bfloat16
peak in ``benchmark/peaks.json``), the kernel's largest difference from the plain
form over the live rows' finite scores, whether ``+inf`` and ``-inf`` stand in the
same places, and the (row, KV group) pairs whose picks differ. It refuses to run
off the TPU unless ``--rehearse``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64, "init_blocks": 1,
          "window_size": 2048, "dense_len": 8192}


def device_ms(fn, operands, calls: int) -> float:
    """The median launch, in ms, of the program that took most of the first
    chip's time while ``fn`` ran ``calls`` times under a profiler session."""
    import jax
    from jax.profiler import ProfileData

    from benchmark.trace_reduce import DEVICE_PLANE, MODULES_LINE, find_xplane

    trace = os.path.join(REPO, "chiprun_out", "bench_block_scores", "trace")
    shutil.rmtree(trace, ignore_errors=True)
    with jax.profiler.trace(trace):
        for _ in range(calls):
            jax.block_until_ready(fn(*operands))
    for plane in ProfileData.from_file(find_xplane(trace)).planes:
        lines = {line.name: line for line in plane.lines}
        if DEVICE_PLANE.match(plane.name) and MODULES_LINE in lines:
            by_program: dict[str, list[int]] = {}
            for ev in lines[MODULES_LINE].events:
                by_program.setdefault(ev.name, []).append(int(ev.duration_ns))
            return statistics.median(max(by_program.values(), key=sum)) / 1e6
    raise SystemExit("bench_block_scores: no device plane in the trace")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--last", default="10000,20000,40000,65535",
                    help="the tile's last position, comma-separated")
    ap.add_argument("--rows", default="", help="row sub-tiles of the kernel to time, "
                    "comma-separated (the module's ROWS where empty)")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserve.models import mixers
    from tpuserve.ops import block_scores as bs

    T, H, KV, hd, P, pages, dtype = 512, 32, 2, 128, 64, 1029, jnp.bfloat16
    sparse, lasts = SPARSE, [int(x) for x in args.last.split(",")]
    if args.rehearse:
        T, pages, dtype, lasts = 32, 140, jnp.float32, [600, 8700]
        sparse = {**SPARSE, "topk": 8, "window_size": 256, "dense_len": 512}
        bs.block_scores = functools.partial(bs.block_scores, interpret=True)
    elif jax.default_backend() != "tpu":
        print(f"bench_block_scores: needs a TPU, found {jax.default_backend()}")
        return 2
    with open(os.path.join(REPO, "benchmark", "peaks.json"), encoding="utf-8") as f:
        peaks = json.load(f)
    peak = peaks["devices"].get(jax.devices()[0].device_kind, {}).get("bf16_flops_per_s")

    class Plain(mixers.BlockSelectAttention):
        name = "bench"

        def __init__(self):
            self.heads, self.kv, self.hd, self.dtype = H, KV, hd, jnp.dtype(dtype)
            self._blk_setup(self.name, sparse)

        def _scale(self):
            return hd ** -0.5

    model = Plain()
    sub_tiles = [int(x) for x in args.rows.split(",") if x] or [bs.ROWS]
    per, kb = P // model.b_stride, 16
    spans = -(-pages // kb) * kb * P // model.b_block
    if not args.rehearse and model._select_path(T, pages, P) != "kernel":
        print("bench_block_scores: the kernel does not take the cell's shape")
        return 1
    rng = np.random.default_rng(69)
    # q and k normed a head with gains about 2, as the cell draws them: scores of
    # standard deviation about 4 over the keys, flatter over the pooled ones
    unit = lambda x: x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True))  # noqa: E731
    q = jnp.asarray(2 * unit(rng.standard_normal((T, H, hd))), dtype)
    kc = jnp.asarray(unit(rng.standard_normal(((pages + 3) * per, KV, hd))).reshape(-1, KV * hd),
                     dtype)
    row = jnp.asarray(rng.permutation(np.arange(1, pages + 3))[:pages], jnp.int32)

    def scores(path, q, kc, row, qpos, last):
        if path == "kernel":
            return bs.block_scores(
                q, kc, row, qpos, last, spans=spans, page=P, kernel=model.b_kernel,
                stride=model.b_stride, block=model.b_block, init=model.b_init,
                local=model.b_local, scale=model._scale())
        return model._block_scores(q, kc, row, qpos, spans, P)

    def keep(path, q, kc, row, qpos, last):
        return model._tile_keep(q, kc, row, qpos, spans, P, last, path)

    for last in lasts:
        qpos = jnp.arange(last - T + 1, last + 1, dtype=jnp.int32)
        a = (q, kc, row, qpos, jnp.int32(last))
        windows = (last + 1) // model.b_stride
        flop = 2.0 * H * T * windows * hd
        want: dict = {}
        for part, fn in (("scores", scores), ("keep", keep)):
            for path, rows in [("xla", 0)] + [("kernel", n) for n in sub_tiles]:
                bs.ROWS = rows or bs.ROWS
                run = jax.jit(functools.partial(fn, path))
                path += f" (sub-tiles of {rows} rows)" if rows else ""
                try:
                    got = np.asarray(run(*a))
                    want.setdefault(part, got)
                    if part == "scores":
                        fin = np.isfinite(want[part])
                        note = (f"largest gap {np.abs(got[fin] - want[part][fin]).max():.2e} of "
                                f"{want[part][fin].max():.3f}, infinities in the same places "
                                f"{np.array_equal(np.isposinf(got), np.isposinf(want[part]))} "
                                f"{np.array_equal(np.isneginf(got), np.isneginf(want[part]))}")
                    else:
                        moved = int((got != want[part]).any(axis=-1).sum())
                        note = (f"{moved} of {got.shape[0] * got.shape[1]} (group, row) pairs "
                                f"pick other blocks than the plain form's")
                    if args.rehearse:
                        took = "rehearsed"
                    else:
                        ms = device_ms(run, a, args.calls)
                        took = f"{ms:.3f} ms a tile on the device"
                        if part == "scores" and peak:
                            took += f", {100 * flop / peak / (ms / 1e3):.1f}% of the products' peak"
                    print(f"last {last} ({windows} windows), {part}, {path}: {took}; {note}",
                          flush=True)
                except Exception as e:  # noqa: BLE001 - what the chip's compiler refuses
                    print(f"last {last}, {part}, {path}: refused: {str(e)[:600]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
