"""Device time of the routed experts' own operations in one prefill launch, in
ms: the operations of the prefill program (`jit_prefill_fn`) that carry the
program's `moe_experts` scope (`tpuserve/ops/moe.py` `held_experts`: every
sparse layer's grouped products, in-kernels and out-kernel, and the expert's
body between them; the dispatch's sort, gathers and way back are `moe_dispatch`
and not counted), as the union of their intervals a launch, median over the
launches that lie whole inside the traced window (benchmark/ssm_window.py).
None where the program has no such scope (a family with no routed layer)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "moe_experts")
    return m["launch_s"] * 1e3 if m else None
