#!/usr/bin/env python3
"""Time one decode step's delta-rule state update ALONE on the chip
(``tpuserve/ops/delta_update.py``, ISSUE 53): the kernel at several blocks of
heads beside the plain XLA form and beside one pass over the same states (read
once, written once: what memory allows), at the cell's shape, 192 lanes of 64
heads of 128 x 128 float32.

    chiprun -- python scripts/bench_delta.py [--lanes 192] [--blocks 8,16,32]
    python scripts/bench_delta.py --rehearse      # a toy shape in the interpreter, no time

Prints a line a candidate: ms a call (the median of ``--repeat`` chains of
``--calls`` calls, the state donated from one to the next, one wait at the
end), the GB/s of state in and out that is, and the largest difference from the
plain form. It refuses to run off the TPU unless ``--rehearse``.
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
    ap.add_argument("--lanes", type=int, default=192)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--blocks", default="8,16,32")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserve.ops import delta_update as du

    if args.rehearse:
        args.lanes, args.heads, args.calls, args.repeat = 2, 8, 1, 1
    elif jax.default_backend() != "tpu":
        print(f"bench_delta: needs a TPU, found {jax.default_backend()}")
        return 2
    b, h, d = args.lanes, args.heads, 128
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    k = f(b, h, d)
    vecs = (f(b, h, d) * d ** -0.5, k / jnp.linalg.norm(k, axis=-1, keepdims=True), f(b, h, d),
            jnp.exp(-0.05 * jnp.abs(f(b, h, d))),
            jnp.asarray(rng.uniform(0, 2, (b, h)), jnp.float32))
    live = jnp.ones((b,), jnp.bool_).at[1].set(False)
    state0 = f(b, h, d, d)
    o_want, s_want = jax.jit(du.delta_step)(state0, *vecs, live)
    moved = 2 * state0.size * 4

    cands = {"one pass over the states (s + 1)": jax.jit(lambda s, *_: (None, s + 1.0),
                                                         donate_argnums=0),
             "plain XLA (delta_step)": jax.jit(du.delta_step, donate_argnums=0)}
    for hb in (int(x) for x in args.blocks.split(",")):
        if h % hb == 0:
            cands[f"kernel, {hb} heads a cell"] = jax.jit(functools.partial(
                du.delta_update, heads_block=hb, interpret=args.rehearse), donate_argnums=0)
    for name, fn in cands.items():
        try:
            o, s = fn(jnp.array(state0), *vecs, live)
            gap = "" if o is None else (
                f"; largest gap o {float(jnp.max(jnp.abs((o - o_want) * live[:, None, None]))):.2e}"
                f", state {float(jnp.max(jnp.abs(s - s_want))):.2e}")
            times = []
            for _ in range(args.repeat):
                s = jnp.array(state0)
                jax.block_until_ready(s)
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    _, s = fn(s, *vecs, live)
                jax.block_until_ready(s)
                times.append((time.perf_counter() - t0) / args.calls)
            ms = statistics.median(times) * 1e3
            print(f"{name}: " + ("rehearsed" if args.rehearse else
                                 f"{ms:.3f} ms a call, {moved / ms / 1e6:.0f} GB/s of state") + gap,
                  flush=True)
        except Exception as e:  # noqa: BLE001 - what the chip's compiler refuses, it refuses here
            print(f"{name}: refused: {str(e)[:300]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
