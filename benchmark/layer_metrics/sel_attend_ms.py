"""Device time of attention over the picks in one prefill launch, in ms: the
operations of the prefill program (`jit_prefill_fn`) that carry the program's
`sel_attend` scope (every layer's walks of the tiles past `index_topk`, under
their rows' picks), as the union of their intervals a launch, median over the
launches that lie whole inside the traced window (benchmark/ssm_window.py).
None where the program has no such scope."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "sel_attend")
    return m["launch_s"] * 1e3 if m else None
