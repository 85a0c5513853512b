"""Sublayers that took their hyper-connection maps, a live token, both phases
together: `hc_maps_total` over the live tokens THE DEVICE COUNTED in the same
launches. Two a layer: 16.0 at eight layers, and anything else means a
sublayer ran without its maps. The program sums `hc_maps_total` on the device
and has no plain count of tokens there, so the tokens are the routed picks it
sums beside it (`moe_tokens_routed_total`, every `held`: `top_k` a token a
sparse layer); where a configuration has no sparse layer, the engine's
`gen_prefill_tokens_total` + `gen_decode_tokens_total`. Those two are counted
on the host when a launch is DISPATCHED, the device's sums when a later step's
block is read: a window that opens while the first prompts of 64 callers are
still queued (this cell's does: 49 launches of prefill against 5 s of warm-up)
reads 16.24 over them, four launches' worth of lag at one edge and one at the
other (my chip run, PR 46). None where the program has no such counter
(another family, the parent of the PR that added it)."""

from benchmark import gen_window


def read(run: dict):
    maps = gen_window.total(run, "hc_maps_total")
    sz = run.get("sizes") or {}
    a_token = sz.get("top_k", 0) * sz.get("n_sparse", 0)
    picks = gen_window.total(run, "moe_tokens_routed_total")
    tokens = picks / a_token if picks > 0 and a_token else \
        gen_window.total(run, "gen_prefill_tokens_total") \
        + gen_window.total(run, "gen_decode_tokens_total")
    if maps <= 0 or tokens <= 0:
        return None
    return maps / tokens
