"""Device time of the shared experts in one decode step, in ms: the operations
of the step program (`jit_step`) that carry the program's `moe_shared` scope
(every layer's shared SwiGLU on the rows the router read, beside the routed
block), as the union of their intervals a launch, median over the launches that
lie whole inside the traced window (benchmark/ssm_window.py). None where the
program has no such scope."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "moe_shared")
    return m["launch_s"] * 1e3 if m else None
