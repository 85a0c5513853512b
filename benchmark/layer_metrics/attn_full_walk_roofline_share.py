"""The least time the chip could take for the global layers' decode walk in the
window's mean step (its live lanes and the context they attend from, the
window's own counters: each lane's own K and V rows read ONCE a global layer at
320 values a KV head, the new rows written, the two products over the same
rows; the family's `flops/<family>.py` `full_walk`) over `attn_full_walk_ms`, in
percent: the walk kernel's share of its roofline. The kernel reads whole cells
of key blocks a lane (`attn_rows_walked_total` over `attn_rows_attended_total`
is the cells' part) and runs its small products one KV head after another:
the share says what that costs."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "attn_full_walk")
    mean = gen_window.per_launch(run, "decode")
    fn = getattr(run.get("flops"), "full_walk", None)
    if not m or not mean or fn is None:
        return None
    return ssm_window.roofline_share(
        run, f"attn_full_walk ({mean['tokens']:.1f} live lanes at mean context "
        f"{mean['context'] / mean['tokens']:.0f})",
        fn(run["sizes"], mean["tokens"], mean["context"]), m["launch_s"])
