"""The routed blocks' part of a decode step's device time, in percent: the
step's operations under the program's `moe_layer` scope (router, picks,
dispatch and the held experts' products) plus those under `moe_shared` (the
shared expert), over `gen_step_ms` (the step program's launch), each a median
over the launches that lie whole inside the traced window. `higher` is declared
because a cell that exists to guard the routed block behind a recurrent mixer is
doing what it is for when that block is most of its step; it is no goal in
itself (a faster block lowers it). None where the program has neither scope."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    parts = [ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, scope)
             for scope in ("moe_layer", "moe_shared")]
    step = gen_window.module(run, gen_window.STEP_MODULE)
    if not any(parts) or not step or not step.get("launch_s"):
        return None
    return 100.0 * sum(m["launch_s"] for m in parts if m) / step["launch_s"]
