"""Device time of the routed experts' own operations in one decode step, in ms:
the operations of the step program (`jit_step`) that carry the program's
`moe_experts` scope (`tpuserve/ops/moe.py` `held_experts`: every routed layer's
grouped products, in-kernels and out-kernel, and the expert's body between
them; the router and the dispatch's sort, gathers and way back are not
counted), as the union of their intervals a launch, median over the launches
that lie whole inside the traced window (benchmark/ssm_window.py).
`moe_experts_prefill_ms` reads the same scope in a prefill launch. None where
the program has no such scope (a family with no routed layer)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "moe_experts")
    return m["launch_s"] * 1e3 if m else None
