"""The least time the chip could take for pooling, selection and the walk over
the picks in the window's mean decode step (the blocks the program says it
scored and the keys it attended, `blk_blocks_scored_total` and
`blk_keys_attended_total{phase=decode}`: each picked lane's pooled keys read
once and its picked blocks' K and V alone; benchmark/flops/hybrid_blk.py
`blk_step`) over `blk_step_ms`, in percent. It stands where
`attn_decode_roofline_share` stands in the cells that walk every key."""

from benchmark import gen_window, spec, ssm_window

blk_step_ms = spec.load_module("layer_metrics", "blk_step_ms")


def read(run: dict):
    ms = blk_step_ms.read(run)
    steps = gen_window.total(run, "gen_iterations_total")
    fn = getattr(run.get("flops"), "blk_step", None)
    scored = gen_window.total(run, "blk_blocks_scored_total", phase="decode")
    attended = gen_window.total(run, "blk_keys_attended_total", phase="decode")
    n = (run.get("sizes") or {}).get("n_attn")
    if not ms or steps <= 0 or fn is None or attended <= 0 or not n:
        return None
    return ssm_window.roofline_share(
        run, f"blk_pool, blk_select and blk_attend in a step ({scored / n / steps:.4g} blocks "
        f"scored, {attended / n / steps:.4g} keys attended a layer)",
        fn(run["sizes"], scored / n / steps, attended / n / steps), ms / 1e3)
