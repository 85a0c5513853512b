#!/usr/bin/env python
"""The hyper-connection of one sublayer alone, on the chip, at a cell's widths:
what a sublayer does BEFORE its function (the maps and the mix it reads:
`hyper.enter`, or `hyper.maps` + `hyper.mix_in` in XLA) and AFTER it (the mix it
leaves: `hyper.leave`, or `hyper.mix_out`), each path at a launch's rows and at
a step's, a row of the table a case: ms a call, the bytes the call must move
(the stream read once by each half and written once, `u`, `y` and the maps
beside them) over the chip's peak (`benchmark/peaks.json`), and their ratio. The kernels' rows are
repeated at other row tiles (`--tiles`); a case at rows that `hyper.fits`
refuses says so, it is there to decide whether it should.

Timed ON THE DEVICE: a case is one program of `--reps` calls (a leave feeds the
next; an enter takes a stream of its own), run under a profiler session, and its
time the median of its launches on the chip's `XLA Modules` line over the calls
(the host's clock around the same program read 0.53 ms a call where the device
took 0.32: PERF.md section 6, PR 47).

    chiprun -- python scripts/bench_hyper.py [--rows 4096,64] [--tiles 128,256,512]
    python scripts/bench_hyper.py --rehearse

One JSON line a case on stdout and in `chiprun_out/bench_hyper/`. Off the TPU it
walks the path at a toy size through the interpreter (`--rehearse`) and prints
no time.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.trace_reduce import DEVICE_PLANE, MODULES_LINE, find_xplane  # noqa: E402
from tpuserve.ops import hyper  # noqa: E402


def launch_ms(path: str) -> float:
    """The median launch, in ms, of the program that took most of the first
    chip's time in the trace at `path` (the case's: nothing else runs)."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if DEVICE_PLANE.match(plane.name) and MODULES_LINE in lines:
            by_program: dict[str, list[int]] = {}
            for ev in lines[MODULES_LINE].events:
                by_program.setdefault(ev.name, []).append(int(ev.duration_ns))
            return statistics.median(max(by_program.values(), key=sum)) / 1e6
    raise SystemExit(f"bench_hyper: no device plane in {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(REPO, "benchmark", "configs",
                                                     "xing4.0-29b-a4b-l8.json"))
    ap.add_argument("--rows", default="4096,64")
    ap.add_argument("--tiles", default="128,256,512")
    ap.add_argument("--mix-rows", default=str(hyper.MIX_ROWS),
                    help="rows a mix spreads its maps for, several to compare (hyper.MIX_ROWS)")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit("bench_hyper: no TPU here; a time from another backend is no device number")
    if on_tpu:   # a device that is not in the table is an error, not a default
        with open(os.path.join(REPO, "benchmark", "peaks.json"), encoding="utf-8") as f:
            peak_bytes_s = json.load(f)["devices"][jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    n, d = int(cfg["hc_mult"]), int(cfg["hidden_size"])
    rows_list = [int(r) for r in args.rows.split(",")]
    if not on_tpu:
        d, rows_list, args.reps, args.iters = 256, [hyper.ROW_TILE, 64], 2, 1
    eps, iters, hc_eps = float(cfg["rms_norm_eps"]), int(cfg["hc_sinkhorn_iters"]), \
        float(cfg["hc_eps"])
    clamp = (float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"]))
    nd, cols = n * d, 2 * n + n * n
    rng = np.random.default_rng(1)
    hp = {"phi": jnp.asarray(rng.standard_normal((nd, cols)) / np.sqrt(nd), jnp.bfloat16),
          "alpha": jnp.asarray([3.0, 3.0, 0.45], jnp.float32),
          "b_pre": jnp.zeros((n,), jnp.float32), "b_post": jnp.full((n,), -3.0, jnp.float32),
          "b_res": 1.25 * jnp.eye(n, dtype=jnp.float32)}
    out_dir = os.path.join(REPO, "chiprun_out", "bench_hyper")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "cases.jsonl"), "w", encoding="utf-8")

    def emit(**row):
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")

    def timed(what: str, rows: int, fn, operands, least_bytes: float, **more):
        try:
            run = jax.jit(fn)
            jax.block_until_ready(run(*operands))
        except Exception as e:  # a shape the compiler refuses is a row of the table too
            emit(case=what, rows=rows, refused=f"{type(e).__name__}: {str(e)[:300]}", **more)
            return
        if not on_tpu:
            emit(case=what, rows=rows, rehearsed=True, **more)
            return
        trace = os.path.join(out_dir, "trace")
        shutil.rmtree(trace, ignore_errors=True)
        with jax.profiler.trace(trace):
            for _ in range(args.iters):
                jax.block_until_ready(run(*operands))
        ms, least = launch_ms(find_xplane(trace)) / args.reps, least_bytes / peak_bytes_s * 1e3
        emit(case=what, rows=rows, ms_a_call=round(ms, 4), least_ms=round(least, 4),
             of_bytes_pct=round(100 * least / ms, 1), **more)

    a = (n, eps, iters, hc_eps, clamp)
    interpret = not on_tpu

    def against_xla(rows: int, x, y, maps, u, h):
        """The kernels' answers beside XLA's on the same backend: the maps' largest
        difference, and of `u` and `X'` the share of values that differ at all (a
        float32 sum that lands on the other side of a rounding) and the largest
        difference."""
        x1 = hyper.leave(x, y, h, n, tile=min(rows, hyper.ROW_TILE), interpret=interpret)

        def off(got, want):
            gap = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
            return {"differ_pct": round(100 * float(jnp.mean(gap > 0)), 3),
                    "largest": float(jnp.max(gap))}

        emit(case="kernels against xla", rows=rows,
             maps=max(float(jnp.max(jnp.abs(g - w))) for g, w in zip(hyper.unpack(h, n), maps)),
             u=off(u, hyper.mix_in(x, maps[0])), x=off(x1, hyper.mix_out(x, maps[2], maps[1], y)))

    for rows in rows_list:
        # a stream of its own a call: a slice of one array would be copied for a kernel
        xs = [jnp.asarray(rng.standard_normal((rows, nd)), jnp.bfloat16) for _ in range(args.reps)]
        y = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
        u, h = hyper.enter(xs[0], hp, *a, tile=min(rows, hyper.ROW_TILE), interpret=interpret)
        h_pre, h_post, h_res = hyper.maps(xs[0], hp, *a)
        enter_bytes = 2.0 * rows * (nd + d) + 4.0 * rows * hyper.LANES + 2.0 * nd * cols
        leave_bytes = 2.0 * rows * 2 * nd + 4.0 * rows * (d + hyper.LANES)
        fits = hyper.fits(rows, n, d, xs[0].dtype)

        def chain(step, x0):
            x = x0
            for _ in range(args.reps):
                x = step(x)
            return x

        def before_xla(xs):
            outs = []
            for x in xs:
                maps = hyper.maps(x, hp, *a)
                outs.append((hyper.mix_in(x, maps[0]), maps[1], maps[2]))
            return outs

        timed("before, xla: maps + mix_in", rows, before_xla, (xs,), enter_bytes)
        timed("after, xla: mix_out", rows,
              lambda x: chain(lambda x: hyper.mix_out(x, h_res, h_post, y), x), (xs[0],),
              leave_bytes)
        against_xla(rows, xs[0], y, (h_pre, h_post, h_res), u, h)
        for tile, mix_rows in dict.fromkeys((min(rows, int(t)), int(r))
                                            for t in args.tiles.split(",")
                                            for r in args.mix_rows.split(",")):
            if rows % tile or tile % mix_rows:
                continue
            hyper.MIX_ROWS = mix_rows   # read when a kernel is traced
            more = {"tile": tile, "mix_rows": mix_rows, "fits": fits}
            timed("before, kernel: enter", rows, lambda xs, tile=tile: [
                hyper.enter(x, hp, *a, tile=tile, interpret=interpret) for x in xs], (xs,),
                enter_bytes, **more)
            timed("after, kernel: leave", rows, lambda x, tile=tile: chain(
                lambda x: hyper.leave(x, y, h, n, tile=tile, interpret=interpret), x), (xs[0],),
                leave_bytes, **more)
    log.close()


if __name__ == "__main__":
    main()
