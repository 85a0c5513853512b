"""The least time the chip could take for latent attention in the window's
mean decode step (its live lanes and the context they attend from, the
window's own counters: the five MLA matrices once a layer, each lane's cached
rows read ONCE at one row a token, absorbed operations; benchmark/flops/mla.py
`attend_decode`) over `mla_decode_ms`, in percent. The program gathers each
lane's rows in whole key blocks and reads what it gathered again for the
scores and for the context: the share says what that costs
(`mla_rows_walked_total` over `mla_rows_attended_total` is the blocks' part)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "mla_decode")
    mean = gen_window.per_launch(run, "decode")
    fn = getattr(run.get("flops"), "attend_decode", None)
    if not m or not mean or fn is None:
        return None
    return ssm_window.roofline_share(
        run, f"mla_decode ({mean['tokens']:.1f} live lanes at mean context "
        f"{mean['context'] / mean['tokens']:.0f})",
        fn(run["sizes"], mean["tokens"], mean["context"]), m["launch_s"])
