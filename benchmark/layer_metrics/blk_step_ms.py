"""Device time of the pooled keys, block selection and attention over the picks
in one decode step, in ms: the operations of the step program (`jit_step`) that
carry the program's `blk_pool` scope (the window a lane's new key completes, its
mean into the third page leaf), its `blk_select` scope (every lane's scores
against its context's pooled keys and its `topk` blocks a KV group) or its
`blk_attend` scope (the gather of those blocks' K and V through the block table
and the softmax over them), the three unions of intervals a launch added (a
step's operations run one after another), each the median over the steps that
lie whole inside the traced window (benchmark/ssm_window.py). None where the
program has no such scope."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    parts = [ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, scope)
             for scope in ("blk_pool", "blk_select", "blk_attend")]
    if not all(parts):
        return None
    return sum(m["launch_s"] for m in parts) * 1e3
