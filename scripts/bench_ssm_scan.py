#!/usr/bin/env python3
"""Time one Mamba-2 layer's chunked scan of one prefill launch ALONE on the chip
(``tpuserve/ops/ssm_scan.py``, ISSUE 67): ``Mamba2Mixer._scan_slots`` (the kernel,
on the slots' block in place) beside ``_scan_pieces`` (the plain XLA form between
a gather and a scatter of the pieces' states), from the projections' rows to y
and the slots' states (the convolution, the decays and the pieces' last rows
are in both), at the three Mamba-2 cells' shapes: 8 tiles of 128 rows, heads of 64
channels, a state of 128, bfloat16; 64 heads in one group (Granite-micro), 128 in
one (Granite-small), 32 in two (Nemotron). A launch of 3 live tiles (one piece of
two tiles, one of one: Granite-micro's launches carry 290 tokens in 1.66 pieces)
and one of 8 (three pieces), each piece's last tile partly live.

    chiprun -- python scripts/bench_ssm_scan.py [--shapes 64x1,128x1,32x2] [--live 3,8]
    python scripts/bench_ssm_scan.py --rehearse   # a toy shape in the interpreter, no time

Prints a line a shape, a launch and a path: ms a layer ON THE DEVICE (the median
of the program's ``--calls`` launches on a profiler session's module line, not the
host's clock: a call's dispatch costs the host as long as the layer costs the
chip) and the kernel's largest difference from the plain form in y's live rows
and in the slots' states, each beside the largest value. It refuses to
run off the TPU unless ``--rehearse``.
"""

from __future__ import annotations

import argparse
import functools
import os
import shutil
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LAUNCHES = {3: [250, 120], 8: [380, 250, 380]}   # live tiles -> the pieces' lengths at T = 128


def device_ms(fn, ssm, operands, calls: int) -> float:
    """The median launch, in ms, of the program that took most of the first
    chip's time while ``fn`` ran ``calls`` times under a profiler session, the
    slots' block donated to each call and taken from it again, as the engine's
    state is."""
    import jax
    from jax.profiler import ProfileData

    from benchmark.trace_reduce import DEVICE_PLANE, MODULES_LINE, find_xplane

    trace = os.path.join(REPO, "chiprun_out", "bench_ssm_scan", "trace")
    shutil.rmtree(trace, ignore_errors=True)
    with jax.profiler.trace(trace):
        for _ in range(calls):
            _y, ssm = jax.block_until_ready(fn(ssm, *operands))
    for plane in ProfileData.from_file(find_xplane(trace)).planes:
        lines = {line.name: line for line in plane.lines}
        if DEVICE_PLANE.match(plane.name) and MODULES_LINE in lines:
            by_program: dict[str, list[int]] = {}
            for ev in lines[MODULES_LINE].events:
                by_program.setdefault(ev.name, []).append(int(ev.duration_ns))
            return statistics.median(max(by_program.values(), key=sum)) / 1e6
    raise SystemExit("bench_ssm_scan: no device plane in the trace")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="64x1,128x1,32x2", help="heads x groups, comma-separated")
    ap.add_argument("--live", default="3,8", help=f"live tiles of 8: of {sorted(LAUNCHES)}")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserve.models import mixers
    from tpuserve.models.paged_lm import PagedLM
    from tpuserve.ops import ssm_scan as ss

    K, T, P, N, taps, slots, dtype = 8, 128, 64, 128, 4, 80, jnp.bfloat16
    shapes = [tuple(int(x) for x in s.split("x")) for s in args.shapes.split(",")]
    if args.rehearse:
        T, P, N, dtype, shapes = 16, 16, 16, jnp.float32, [(8, 2)]
        ss.ssm_scan = functools.partial(ss.ssm_scan, interpret=True)
    elif jax.default_backend() != "tpu":
        print(f"bench_ssm_scan: needs a TPU, found {jax.default_backend()}")
        return 2

    class Plain(mixers.Mamba2Mixer):
        name, conv_k = "bench", taps

        def __init__(self, heads: int, groups: int):
            self.mh, self.mp, self.mg, self.mn, self.dtype = heads, P, groups, N, jnp.dtype(dtype)
            self.conv_ch = heads * P + 2 * groups * N

    for H, G in shapes:
        model = Plain(H, G)
        if not args.rehearse and not ss.supported(T, H, P, N, G, jnp.float32):
            print(f"{H} heads in {G} groups: the kernel does not take the shape")
            continue
        rng = np.random.default_rng(H)
        f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
        ch = model.conv_ch
        lp = {"conv_w": jnp.asarray(0.5 * f(taps, ch), dtype),
              "conv_b": jnp.asarray(0.1 * f(ch), dtype),
              "dt_bias": jnp.asarray(rng.uniform(-6.9, -2.2, H), jnp.float32),
              "A_log": jnp.asarray(rng.uniform(0.0, np.log(16.0), H), jnp.float32),
              "D": jnp.asarray(1.0 + 0.3 * f(H))}
        xbc, dt = jnp.asarray(f(K * T, ch), dtype), jnp.asarray(f(K * T, H))
        ssm, conv = jnp.asarray(f(slots, H, P, N)), jnp.asarray(f(slots, taps - 1, ch), dtype)
        for live in (int(x) for x in args.live.split(",")):
            lengths = [n * T // 128 for n in LAUNCHES[live]] + [0] * K
            launch = {"slot": jnp.arange(K, dtype=jnp.int32),
                      "start": jnp.asarray([5, 0, 9] + [0] * (K - 3), jnp.int32),
                      "length": jnp.asarray(lengths[:K], jnp.int32),
                      "pages": jnp.zeros((K, 1), jnp.int32)}

            def scan(path, ssm, lp, xbc, dt, conv, launch):
                t = PagedLM._tiles(launch, K * T)
                run = model._scan_slots if path == "kernel" else model._scan_pieces
                y, ssm, _conv = run(lp, xbc, dt, t, ssm, conv, launch["slot"], launch["start"],
                                    launch["length"])
                return jnp.where(t["valid"][:, None, None], y, 0.0), ssm

            a = (lp, xbc, dt, conv, launch)
            want = None
            for path in ("xla", "kernel"):
                fn = jax.jit(functools.partial(scan, path), donate_argnums=(0,))
                try:
                    y, s = fn(ssm + 0.0, *a)
                    if want is None:
                        want = (y, s)
                    gap = (f"; largest gap y {float(jnp.max(jnp.abs(y - want[0]))):.2e} of "
                           f"{float(jnp.max(jnp.abs(want[0]))):.2e}, state "
                           f"{float(jnp.max(jnp.abs(s - want[1]))):.2e} of "
                           f"{float(jnp.max(jnp.abs(want[1]))):.2e}, finite "
                           f"{bool(jnp.isfinite(y).all() & jnp.isfinite(s).all())}")
                    took = "rehearsed" if args.rehearse else \
                        f"{device_ms(fn, ssm + 0.0, a, args.calls):.3f} ms a layer on the device"
                    print(f"{H} heads in {G} groups, {live} live tiles of {K}, {path}: {took}{gap}",
                          flush=True)
                except Exception as e:  # noqa: BLE001 - what the chip's compiler refuses
                    print(f"{H} heads in {G} groups, {live} live tiles, {path}: refused: "
                          f"{str(e)[:600]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
