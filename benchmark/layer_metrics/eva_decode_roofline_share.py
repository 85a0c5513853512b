"""The least time the chip could take for the EVA attention of the window's
mean decode step (its live lanes and the rows they attended, exact and summary:
`gen_decode_tokens_total` and `gen_context_tokens_total{phase=decode}` over
`gen_iterations_total`; the family's `flops/<family>.py` `attend_decode`: every
attended row's K and V once a layer) over `eva_decode_ms`, in percent: the
walk's share of its roofline, whichever kernel walks. None where the program
has no such scope or the family no such function."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "eva_decode")
    mean = gen_window.per_launch(run, "decode")
    fn = getattr(run.get("flops"), "attend_decode", None)
    if not m or not mean or fn is None:
        return None
    return ssm_window.roofline_share(
        run, f"eva_decode ({mean['tokens']:.1f} live lanes, {mean['context']:.0f} rows)",
        fn(run["sizes"], mean["tokens"], mean["context"]), m["launch_s"])
