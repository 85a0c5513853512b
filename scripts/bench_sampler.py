#!/usr/bin/env python3
"""Time a step's sampler ALONE on the chip (``paged_lm.PagedLM._sample``, ISSUE
60): the body it had until PR 59 (a Gumbel draw and two argmaxes a lane whatever
its temperature, ``log_softmax`` of the whole block, ``top_k`` over the whole
vocabulary) beside today's (the top 8 from group maxima, the log-probabilities
of those eight, the draw under a ``cond``), at every generating cell's lanes x
held vocabulary, all greedy and with ONE lane at temperature 0.8.

    chiprun -- python scripts/bench_sampler.py [--shapes 512x65536,24x320] [--groups 256,512]
    python scripts/bench_sampler.py --rehearse      # toy shapes on the CPU, no time

Prints a line a shape and a case: ms a call of each body ON THE DEVICE (the
median launch of ``--calls`` under a profiler session, the program's own line in
the trace, not the host's clock: a chain of calls timed from the host reads 0.45
ms a call whatever the shape), what two reads of the float32 logits take at the
chip's 819 GB/s, and whether tokens and ids are equal and how far the
log-probabilities lie apart; then today's body BY OPERATION at the first shape.
``--groups`` times today's body at other widths of a group too. It refuses to
run off the TPU unless ``--rehearse``.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# lanes x held vocabulary: LFM2, MiMo, Nemotron, Granite, Xing, EvaByte (ISSUE 60)
SHAPES = "512x65536,384x19072,256x32768,80x100352,64x131072,24x320"


def parent_sample(logits, seed, position, temp):
    """``_sample`` as it was until PR 59."""
    import jax
    import jax.numpy as jnp

    from tpuserve.models.paged_lm import LOGPROBS

    def one(lg, sd, pos, t):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), sd), pos)
        g = jax.random.gumbel(key, lg.shape, jnp.float32)
        sampled = jnp.argmax(lg / jnp.where(t > 0, t, 1.0) + g)
        return jnp.where(t > 0, sampled, jnp.argmax(lg)).astype(jnp.int32)

    tok = jax.vmap(one)(logits, seed, position, temp)
    lp, ids = jax.lax.top_k(jax.nn.log_softmax(logits, axis=-1), LOGPROBS)
    return tok, ids.astype(jnp.int32), lp


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=SHAPES)
    ap.add_argument("--groups", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import op_table
    from bench_hyper import launch_ms
    from benchmark.trace_reduce import find_xplane
    from tpuserve.models import paged_lm

    if args.rehearse:
        args.shapes = "6x1000,3x320"
    elif jax.default_backend() != "tpu":
        print(f"bench_sampler: needs a TPU, found {jax.default_backend()}")
        return 2
    trace = os.path.join(REPO, "chiprun_out", "bench_sampler", "trace")

    def traced(fn, *a) -> str:
        """``--calls`` calls under a profiler session -> the trace's file."""
        jax.block_until_ready(fn(*a))
        shutil.rmtree(trace, ignore_errors=True)
        with jax.profiler.trace(trace):
            for _ in range(args.calls):
                jax.block_until_ready(fn(*a))
        return find_xplane(trace)

    def timed(fn, *a) -> float:
        return launch_ms(traced(fn, *a))

    def todays(group: int):
        """Today's body traced at groups of ``group`` logits."""
        def fn(*a):
            kept, paged_lm.TOP_GROUP = paged_lm.TOP_GROUP, group
            try:
                return paged_lm.PagedLM._sample(*a)
            finally:
                paged_lm.TOP_GROUP = kept
        return jax.jit(fn)

    for shape in args.shapes.split(","):
        lanes, v = (int(x) for x in shape.split("x"))
        logits = 3.0 * jax.random.normal(jax.random.key(lanes), (lanes, v), jnp.float32)
        seed, pos = jnp.arange(lanes, dtype=jnp.int32), jnp.full((lanes,), 700, jnp.int32)
        for case, temp in (("greedy", jnp.zeros((lanes,), jnp.float32)),
                           ("one lane drawn", jnp.zeros((lanes,), jnp.float32).at[1].set(0.8))):
            drawn = jnp.any(temp > 0)
            want = jax.jit(parent_sample)(logits, seed, pos, temp)
            got = todays(paged_lm.TOP_GROUP)(logits, seed, pos, temp, drawn)
            same = (f"tokens {'equal' if bool((got[0] == want[0]).all()) else 'DIFFER'}, ids "
                    f"{'equal' if bool((got[1] == want[1]).all()) else 'DIFFER'}, "
                    f"log-probabilities {float(jnp.max(jnp.abs(got[2] - want[2]))):.1e} apart")
            if args.rehearse:
                print(f"{shape} {case}: rehearsed; {same}", flush=True)
                continue
            line = (f"{shape} {case}: until PR 59 "
                    f"{timed(jax.jit(parent_sample), logits, seed, pos, temp):.3f} ms, today "
                    f"{timed(todays(paged_lm.TOP_GROUP), logits, seed, pos, temp, drawn):.3f} ms")
            for group in (int(g) for g in args.groups.split(",") if g):
                line += (f", groups of {group} "
                         f"{timed(todays(group), logits, seed, pos, temp, drawn):.3f} ms")
            print(f"{line}; two reads of the logits {2 * lanes * v * 4 / 819e9 * 1e3:.3f} ms; "
                  f"{same}", flush=True)
    if not args.rehearse:
        lanes, v = (int(x) for x in args.shapes.split(",")[0].split("x"))
        logits = 3.0 * jax.random.normal(jax.random.key(lanes), (lanes, v), jnp.float32)
        i32 = jnp.zeros((lanes,), jnp.int32)
        path = traced(todays(paged_lm.TOP_GROUP), logits, i32, i32, jnp.zeros((lanes,)), False)
        for m in op_table.last_launches(path).values():
            print(f"-- today's body at {lanes}x{v}, greedy, by operation: {m['ns'] / 1e6:.3f} ms")
            for inst, ns, traced_as in sorted(m["ops"], key=lambda op: -op[1])[:12]:
                print(f"   {ns / 1e6:.3f} ms  {inst}  {traced_as.split('sample/')[-1]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
