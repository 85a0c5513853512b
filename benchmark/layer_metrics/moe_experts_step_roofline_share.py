"""The least time the chip could take for the routed experts' own work in the
window's mean decode step (its picks on held experts and the held experts they
hit, the window's own counters: the HIT experts' matrices read once, a pick's
row in and out, the picks' three products; the family's `flops/<family>.py`
`experts_step`) over `moe_experts_step_ms`, in percent: the grouped products'
share of their roofline at the step's tokens an expert. None where the family's
flops file has no `experts_step`."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "moe_experts")
    mean = gen_window.per_launch(run, "decode")
    fn = getattr(run.get("flops"), "experts_step", None)
    if not m or not mean or fn is None or mean["experts_hit"] <= 0:
        return None
    return ssm_window.roofline_share(
        run, f"moe_experts in a step ({mean['tokens']:.1f} live lanes, {mean['held_picks']:.1f} "
        f"held picks on {mean['experts_hit']:.1f} expert-layers hit)",
        fn(run["sizes"], mean["tokens"], mean["held_picks"], mean["experts_hit"]), m["launch_s"])
