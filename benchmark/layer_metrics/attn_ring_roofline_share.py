"""The least time the chip could take for the window layers' ring attention in
the window's mean decode step (its live lanes and the context they attend from,
the window's own counters: `min(context, window)` ring rows read ONCE a window
layer at 320 values a KV head, the new rows written, the two products over the
same rows; the family's `flops/<family>.py` `ring_read`) over `attn_ring_ms`, in
percent. The program gathers each lane's whole ring, reshapes a 1,536-lane row
into heads of 192 and reads what it gathered again for the scores and for the
context, in plain XLA: the share says what that costs."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "attn_ring")
    mean = gen_window.per_launch(run, "decode")
    fn = getattr(run.get("flops"), "ring_read", None)
    if not m or not mean or fn is None:
        return None
    return ssm_window.roofline_share(
        run, f"attn_ring ({mean['tokens']:.1f} live lanes at mean context "
        f"{mean['context'] / mean['tokens']:.0f})",
        fn(run["sizes"], mean["tokens"], mean["context"]), m["launch_s"])
