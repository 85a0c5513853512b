"""The least time the chip could take for attention by head in the window's
mean decode step (its live lanes and the context they attend from, the
window's own counters: the four attention matrices once a layer, each lane's
own K and V rows read ONCE, the new rows written; the family's
`flops/<family>.py` `attend_decode`) over `attn_decode_ms`, in percent. The
program reads whole compute blocks of pages a lane, and where a page's row
packs two heads multiplies every query over both: the share says what that
costs."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "attn_decode")
    mean = gen_window.per_launch(run, "decode")
    fn = getattr(run.get("flops"), "attend_decode", None)
    if not m or not mean or fn is None or "n_attn" not in (run.get("sizes") or {}):
        return None
    return ssm_window.roofline_share(
        run, f"attn_decode ({mean['tokens']:.1f} live lanes at mean context "
        f"{mean['context'] / mean['tokens']:.0f})",
        fn(run["sizes"], mean["tokens"], mean["context"]), m["launch_s"])
