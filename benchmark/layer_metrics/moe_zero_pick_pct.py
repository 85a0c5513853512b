"""Live tokens' picks that landed on ZERO-COMPUTE outputs of the router, over
all their picks, both phases together, in percent: `moe_routed_zero_total` over
itself plus `moe_tokens_routed_total` (held and absent). A zero-compute pick
costs the chip nothing, so this is the share of the routed layer's nominal
work that the router itself takes away; under a flat router it is the share of
the router's outputs that are zero-compute. None where the program has no such
counter (a family without zero-compute outputs, the parent of the PR that
added it)."""

from benchmark import gen_window


def read(run: dict):
    zero = gen_window.total(run, "moe_routed_zero_total")
    real = gen_window.total(run, "moe_tokens_routed_total")
    if zero <= 0:
        return None
    return 100.0 * zero / (zero + real)
