"""Device time of the indexer in one prefill launch, in ms: the operations of
the prefill program (`jit_prefill_fn`) that carry the program's `sel_index`
scope (every layer's index scores of the tiles past `index_topk` over their
block tables, and each row's threshold: the exact `index_topk`-th largest), as
the union of their intervals a launch, median over the launches that lie whole
inside the traced window (benchmark/ssm_window.py). None where the program has
no such scope (the parent of the PR that added it)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "sel_index")
    return m["launch_s"] * 1e3 if m else None
