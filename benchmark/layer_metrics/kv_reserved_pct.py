"""Bytes of cache reserved over the bytes both pools hold, in percent, as the
mean over the window's steps: full pages (`gen_kv_page_steps_total`) and
window rings (`gen_kv_ring_steps_total`) held while a step ran, over
`gen_iterations_total`, each times its size from the configuration. (A peak
would need a third scrape; the closed loop holds the pools steady, and the
program's `gen_kv_pages_free` gauge has the instant.)"""

from benchmark import gen_window


def read(run: dict):
    sz = run.get("sizes") or {}
    steps = gen_window.total(run, "gen_iterations_total")
    if steps <= 0 or "kv_pages" not in sz:
        return None
    per_pos = 2 * sz["kv_heads"] * sz["head_dim"] * sz["weight_bytes"]
    n_full = sz["layer_types"].count("full_attention")
    n_win = len(sz["layer_types"]) - n_full
    page, ring = per_pos * sz["page_tokens"] * n_full, per_pos * sz["window"] * n_win
    pool = (sz["kv_pages"] - 1) * page + sz["slots"] * ring
    held = (gen_window.total(run, "gen_kv_page_steps_total") * page
            + gen_window.total(run, "gen_kv_ring_steps_total") * ring) / steps
    return 100.0 * held / pool if pool > 0 and held > 0 else None
