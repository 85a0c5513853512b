"""The least time the chip could take for attention over the picked blocks in
the window's mean prefill launch (the (query, key) pairs the program says its
picked queries attended, `blk_keys_attended_total{phase=prefill}`, scores and
context by every head; the K and V rows one query attends read once;
benchmark/flops/hybrid_blk.py `blk_attend`) over `blk_attend_ms`, in percent. A
walk of every key block under a mask reads a low share here: `blk_rows_overread`
says by how much."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "blk_attend")
    fn = getattr(run.get("flops"), "blk_attend", None)
    launches = gen_window.total(run, "gen_prefill_chunks_total")
    attended = gen_window.total(run, "blk_keys_attended_total", phase="prefill")
    queries = gen_window.total(run, "blk_queries_total", phase="prefill", path="picked")
    n = (run.get("sizes") or {}).get("n_attn")
    if not m or fn is None or launches <= 0 or attended <= 0 or queries <= 0 or not n:
        return None
    return ssm_window.roofline_share(
        run, f"blk_attend in a launch ({attended / n / launches:.4g} pairs a layer of "
        f"{queries / n / launches:.4g} picked queries)",
        fn(run["sizes"], attended / n / launches, queries / n / launches), m["launch_s"])
