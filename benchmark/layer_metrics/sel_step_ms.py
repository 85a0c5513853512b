"""Device time of the indexer and of attention over the picks in one decode
step, in ms: the operations of the step program (`jit_step`) that carry the
program's `sel_index` scope (every lane's index scores over its block table and
its threshold) or its `sel_attend` scope (the walk under the lanes' picks), the
two unions of intervals a launch added (a step's operations run one after
another), each the median over the steps that lie whole inside the traced
window (benchmark/ssm_window.py). None where the program has no such scope."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    parts = [ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, scope)
             for scope in ("sel_index", "sel_attend")]
    if not all(parts):
        return None
    return sum(m["launch_s"] for m in parts) * 1e3
