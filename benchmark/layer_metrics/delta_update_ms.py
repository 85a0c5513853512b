"""Device time of the delta-rule state's update and read in one decode step,
in ms: the operations of the step program (`jit_step`) that carry the
program's `delta_update` scope (`tpuserve/models/mixers.py` `_delta_step`: on the
TPU one call of `tpuserve/ops/delta_update.py` a layer, the state read once and
written once in place; the plain form elsewhere), every delta-rule layer
together, as the union of their intervals a launch, median over the launches
that lie whole inside the traced window (benchmark/ssm_window.py). None where
the program has no such scope (another family, the parent of the PR that
added it)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "delta_update")
    return m["launch_s"] * 1e3 if m else None
