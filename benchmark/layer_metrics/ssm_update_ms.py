"""Device time of the state updates in one decode step, in ms: the operations
of the step program (`jit_step`) that carry the program's `ssm_update` scope
(every Mamba-2 layer's mixer from its in-projection's split to its
out-projection: the convolution's step, the state's update and read, the
gated norm), as the union of their intervals a launch, median over the
launches that lie whole inside the traced window (benchmark/ssm_window.py)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "ssm_update")
    return m["launch_s"] * 1e3 if m else None
