#!/usr/bin/env python3
"""Time one layer's chunked delta rule of one prefill launch ALONE on the chip
(``tpuserve/ops/delta_scan.py``, ISSUE 54): the kernel at several blocks of
heads beside the plain XLA form (``mixers.DeltaMixer._delta_heads`` and
``_delta_chunks``, with the transposes ``_delta_tiles`` makes for it), at the
cell's shape: 8 tiles of 128 rows, 64 heads of 128 channels, float32; two
pieces of three tiles and one of two, a quarter of the channels decaying fast.

    chiprun -- python scripts/bench_delta_scan.py [--tiles 8] [--blocks 8]
    python scripts/bench_delta_scan.py --rehearse   # a toy shape in the interpreter, no time

Prints a line a candidate: ms a call (the median of ``--repeat`` chains of
``--calls`` calls, one wait at the end) and the largest difference from the
plain form in o and in the pieces' ending states, each beside the largest
value. It refuses to run off the TPU unless ``--rehearse``.
"""

from __future__ import annotations

import argparse
import functools
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiles", type=int, default=8)
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--blocks", default="8")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserve.models import mixers
    from tpuserve.ops import delta_scan as ds

    if args.rehearse:
        args.tiles, args.rows, args.heads, args.calls, args.repeat = 3, 32, 2, 1, 1
    elif jax.default_backend() != "tpu":
        print(f"bench_delta_scan: needs a TPU, found {jax.default_backend()}")
        return 2
    K, H, T, D = args.tiles, args.heads, args.rows, 128
    rng = np.random.default_rng(0)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731

    class Plain(mixers.DeltaMixer):
        kd, kh = D, H

    conv = jnp.asarray(f(K, T, 3 * H * D))                 # what the convolution gives, by row
    g = -0.05 * np.abs(f(K, T, H, D))
    g[..., ::4] = -2.0
    # pieces of three tiles (the last with what is left); a padded tail on every piece's last tile
    piece = np.minimum(np.arange(K) // 3, K - 1)
    opens = np.arange(K) % 3 == 0
    last = np.append(piece[1:] != piece[:-1], True)
    live = np.ones((K, T, 1), bool)
    live[last, T - 5:] = False
    g, beta = g * live[..., None], rng.uniform(0, 2, (K, T, H)).astype(np.float32) * live
    rows = [conv, *(jnp.asarray(x) for x in (g, beta, f(K, H, D, D), opens,
                                            piece.astype(np.int32)))]
    ends = np.flatnonzero(last)

    def plain(conv, g, b, s0, opens, piece):
        """The tiles by head, as `_delta_tiles` hands them to the plain form."""
        q, k, v = Plain()._delta_heads(conv)
        o, s_out = Plain()._delta_chunks(*(x.transpose(0, 2, 1, 3) for x in (q, k, v, g)),
                                         b.transpose(0, 2, 1), opens, s0[piece])
        return o.transpose(0, 2, 1, 3), s_out[ends]

    def kernel(hb, *a):
        o, s_end = ds.delta_scan(*a, l2_eps=Plain.L2_EPS, heads_block=hb,
                                 interpret=args.rehearse)
        return o, s_end[piece[ends]]

    o_want, s_want = jax.jit(plain)(*rows)
    cands = {"plain XLA (_delta_heads, _delta_chunks)": (jax.jit(plain), rows)}
    for hb in (int(x) for x in args.blocks.split(",")):
        if H % min(hb, H) == 0:
            cands[f"kernel, {hb} heads a cell"] = (jax.jit(functools.partial(kernel, hb)), rows)
    for name, (fn, a) in cands.items():
        try:
            o, s = fn(*a)
            gap = (f"; largest gap o {float(jnp.max(jnp.abs(o - o_want))):.2e} of "
                   f"{float(jnp.max(jnp.abs(o_want))):.2e}, state "
                   f"{float(jnp.max(jnp.abs(s - s_want))):.2e} of "
                   f"{float(jnp.max(jnp.abs(s_want))):.2e}, finite "
                   f"{bool(jnp.isfinite(o).all() & jnp.isfinite(s).all())}")
            times = []
            for _ in range(args.repeat):
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    out = fn(*a)
                jax.block_until_ready(out)
                times.append((time.perf_counter() - t0) / args.calls)
            ms = statistics.median(times) * 1e3
            print(f"{name}: " + ("rehearsed" if args.rehearse else f"{ms:.3f} ms a call") + gap,
                  flush=True)
        except Exception as e:  # noqa: BLE001 - what the chip's compiler refuses, it refuses here
            print(f"{name}: refused: {str(e)[:600]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
