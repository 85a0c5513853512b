"""The least time the chip could take for the EVA attention and pooling of the
window's mean prefill launch (its live tokens and the rows they attended:
`gen_prefill_tokens_total` and `gen_context_tokens_total{phase=prefill}` over
`gen_prefill_chunks_total`; the family's `flops/<family>.py` `attend_prefill`)
over `eva_prefill_ms`, in percent. None where the program has no such scope or
the family no such function."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "eva_")
    mean = gen_window.per_launch(run, "prefill")
    fn = getattr(run.get("flops"), "attend_prefill", None)
    if not m or not mean or fn is None:
        return None
    return ssm_window.roofline_share(
        run, f"eva_prefill ({mean['tokens']:.1f} live tokens, {mean['context']:.0f} rows)",
        fn(run["sizes"], mean["tokens"], mean["context"]), m["launch_s"])
