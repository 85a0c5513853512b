"""Held experts that got at least one live token in a decode step, over held
experts times sparse layers times steps, in percent:
`moe_experts_hit_total{phase=decode}` over `moe_expert_steps_total{phase=decode}`.
An expert that is hit is read whole, so this is the share of the experts'
bytes a decode step cannot avoid."""

from benchmark import gen_window


def read(run: dict):
    ran = gen_window.total(run, "moe_expert_steps_total", phase="decode")
    if ran <= 0:
        return None
    return 100.0 * gen_window.total(run, "moe_experts_hit_total", phase="decode") / ran
