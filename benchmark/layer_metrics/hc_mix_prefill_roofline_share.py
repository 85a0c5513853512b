"""The least time the chip could take for the hyper-connection in the window's
mean prefill launch (its live rows through every sublayer's maps and mixes:
the product with `Phi`, the two mixes and the Sinkhorn's passes; the stream
read once and written once a sublayer, `Phi` once a launch: what a FUSED
implementation moves; benchmark/flops/mla_hc.py `hyper_maps`) over
`hc_mix_prefill_ms`, in percent. The program computes the maps and the mixes in
plain XLA, several passes over a float32 copy of the stream: the share says
what that costs."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "hc_mix")
    mean = gen_window.per_launch(run, "prefill")
    fn = getattr(run.get("flops"), "hyper_maps", None)
    if not m or not mean or fn is None:
        return None
    return ssm_window.roofline_share(
        run, f"hc_mix ({mean['tokens']:.0f} live rows a launch)",
        fn(run["sizes"], mean["tokens"]), m["launch_s"])
