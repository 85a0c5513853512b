"""Device time of attention under the picks in one prefill launch, in ms: the
operations of the prefill program (`jit_prefill_fn`) that carry the program's
`blk_attend` scope (every attention layer's walks of the tiles past `dense_len`,
every key block under the rows' picks as a mask a block), as the union of their
intervals a launch, median over the launches that lie whole inside the traced
window (benchmark/ssm_window.py). None where the program has no such scope."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "blk_attend")
    return m["launch_s"] * 1e3 if m else None
