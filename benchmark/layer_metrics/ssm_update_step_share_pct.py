"""The state updates' part of a decode step's device time, in percent:
`ssm_update_ms` (the union, a launch, of the step's operations under the
program's `ssm_update` scope) over `gen_step_ms` (the step program's launch),
both medians over the launches that lie whole inside the traced window.
`higher` is declared because a cell that exists to guard the update is doing
what it is for when the update is most of its step; it is no goal in itself (a
faster update lowers it)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "ssm_update")
    step = gen_window.module(run, gen_window.STEP_MODULE)
    if not m or not step or not step.get("launch_s"):
        return None
    return 100.0 * m["launch_s"] / step["launch_s"]
