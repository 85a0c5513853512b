"""The least time the chip could take for latent attention in the window's
mean prefill launch (its live tokens, the (query, key) pairs they attend over
and the distinct rows its pieces attend over, `mla_rows_attended_total`: the
form with the fewer operations at those sizes, each cached row expanded and
read once a launch; benchmark/flops/mla.py `attend_prefill`) over
`mla_prefill_ms`, in percent. The program expands a cached key block once a
TILE, not once a launch: the share says what that costs."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "mla_prefill")
    mean = gen_window.per_launch(run, "prefill")
    fn = getattr(run.get("flops"), "attend_prefill", None)
    if not m or not mean or fn is None:
        return None
    rows = gen_window.total(run, "mla_rows_attended_total", phase="prefill") / mean["launches"]
    if rows <= 0:
        return None
    return ssm_window.roofline_share(
        run, f"mla_prefill ({mean['tokens']:.0f} live tokens over {rows:.0f} rows)",
        fn(run["sizes"], mean["tokens"], mean["context"], rows), m["launch_s"])
