"""Device time of the routed layers in one decode step, in ms: the operations
of the step program (`jit_step`) that carry the program's `moe_layer` scope
(every layer's routed layer on its shortcut: the router, the picks, the
dispatch, the held experts' products and the zero-compute term together), as
the union of their intervals a launch, median over the launches that lie whole
inside the traced window (benchmark/ssm_window.py)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "moe_layer")
    return m["launch_s"] * 1e3 if m else None
