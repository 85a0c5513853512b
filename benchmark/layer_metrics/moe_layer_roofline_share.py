"""The least time the chip could take for the routed layers in the window's
mean decode step (its live lanes, their picks on held experts and the held
experts they hit, the window's own counters: the router and the HIT experts
read once, the held picks' products, zero-compute picks at no cost;
benchmark/flops/mla_sc.py `routed_layer`) over `moe_layer_ms`, in percent."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "moe_layer")
    mean = gen_window.per_launch(run, "decode")
    fn = getattr(run.get("flops"), "routed_layer", None)
    if not m or not mean or fn is None:
        return None
    return ssm_window.roofline_share(
        run, f"moe_layer ({mean['tokens']:.1f} live lanes, {mean['held_picks']:.1f} held picks on "
        f"{mean['experts_hit']:.1f} expert-layers hit)",
        fn(run["sizes"], mean["tokens"], mean["held_picks"], mean["experts_hit"]), m["launch_s"])
