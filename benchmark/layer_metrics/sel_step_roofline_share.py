"""The least time the chip could take for the indexer and attention over the
picks in the window's mean decode step (the pairs the program says it scored
and kept, `sel_pairs_scored_total` and `sel_pairs_kept_total{phase=decode}`: each
picked lane's index keys read once and its picked latent rows alone, absorbed
operations; benchmark/flops/mla_sel.py `step`) over `sel_step_ms`, in percent."""

from benchmark import gen_window, spec, ssm_window

sel_step_ms = spec.load_module("layer_metrics", "sel_step_ms")


def read(run: dict):
    ms = sel_step_ms.read(run)
    steps = gen_window.total(run, "gen_iterations_total")
    fn = getattr(run.get("flops"), "step", None)
    scored = gen_window.total(run, "sel_pairs_scored_total", phase="decode")
    kept = gen_window.total(run, "sel_pairs_kept_total", phase="decode")
    if not ms or steps <= 0 or fn is None or kept <= 0:
        return None
    return ssm_window.roofline_share(
        run, f"sel_index and sel_attend in a step ({scored / steps:.4g} pairs scored, "
        f"{kept / steps:.4g} kept a layer)",
        fn(run["sizes"], scored / steps, kept / steps), ms / 1e3)
