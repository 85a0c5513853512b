"""The least time the chip could take for attention over the picks in the
window's mean prefill launch (the picked (query, key) pairs the program says
it kept, `sel_pairs_kept_total{phase=prefill}`, in the absorbed form; the
latent rows its pieces attend over read once, in the picked queries' share of
them; benchmark/flops/mla_sel.py `attend`) over `sel_attend_ms`, in percent. A
walk that scores every cached key under a mask reads a low share here:
`sel_rows_overread` says by how much."""

from benchmark import spec

launch_share = spec.load_module("layer_metrics", "sel_index_roofline_share").launch_share


def read(run: dict):
    return launch_share(run, "sel_attend", "sel_pairs_kept_total", "attend")
