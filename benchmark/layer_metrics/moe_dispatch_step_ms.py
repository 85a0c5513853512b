"""Device time of the routed blocks' routing and dispatch in one decode step, in
ms: the operations of the step program (`jit_step`) that carry the program's
`moe_route` scope (`tpuserve/ops/moe.py` `topk_route`: scores, the k largest,
their weights) or its `moe_dispatch` scope (`held_experts`: the sort of the
picks by expert, the gather of their rows, the way back and the weighted sum),
every layer; the experts' products (`moe_experts`) are not counted. Each scope
is the union of its intervals a launch, median over the launches that lie whole
inside the traced window (benchmark/ssm_window.py `scoped_launch_s`); the two
are added, since the device runs one operation at a time and no operation
carries both. None where the program has neither scope."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    parts = [ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, scope)
             for scope in ("moe_route", "moe_dispatch")]
    if not any(parts):
        return None
    return sum(m["launch_s"] for m in parts if m) * 1e3
