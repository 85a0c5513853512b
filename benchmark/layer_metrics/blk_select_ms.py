"""Device time of block selection in one prefill launch, in ms: the operations
of the prefill program (`jit_prefill_fn`) that carry the program's `blk_select`
scope (every attention layer's picked tiles: the rows' scores against the
prompt's pooled keys, a softmax a head over the windows, the sum over a KV
group's heads, a block's maximum over its windows, the `topk`-th largest as a
threshold and the ties at it by index), as the union of their intervals a
launch, median over the launches that lie whole inside the traced window
(benchmark/ssm_window.py). None where the program has no such scope."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "blk_select")
    return m["launch_s"] * 1e3 if m else None
