"""What ``acc`` holds is stated once a family (``paged_lm.Column``, ISSUE 45):
the state's width, the row a launch adds, the counters ``bind_metrics`` binds
and what ``observe_step`` feeds all follow the family's ``COLUMNS``. Here, for
each of the thirteen generating families at its toy size: one prefill launch and
one step on the CPU, then the device's sums into a registry. The names below
are the series as they have been served since each family came (the benchmark's
readers find them by these letters), written down apart from the code."""

from __future__ import annotations

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.genserve.model import PrefillPiece
from tpuserve.obs import GEN_PHASES, Metrics

EXPERTS = ["moe_tokens_routed_total{model=M,phase=PH,held=yes}",
           "moe_tokens_routed_total{model=M,phase=PH,held=no}",
           "moe_experts_hit_total{model=M,phase=PH}", "moe_expert_steps_total{model=M,phase=PH}"]
CONTEXT = ["gen_context_tokens_total{model=M,phase=PH}"]
COMPACT = ["moe_layers_compact_total{model=M,phase=PH}"]
SAMPLE = ["decode:gen_sample_steps_total{model=M,path=greedy}",
          "decode:gen_sample_steps_total{model=M,path=drawn}"]
SSM = ["ssm_tokens_total{model=M,phase=PH}", "ssm_state_rows_total{model=M,phase=PH}",
       "prefill:ssm_pieces_total{model=M,start=zero}",
       "prefill:ssm_pieces_total{model=M,start=carried}"]
SCANS = ["prefill:ssm_scans_total{model=M,phase=prefill,path=kernel}",
         "prefill:ssm_scans_total{model=M,phase=prefill,path=xla}"]
MLA = EXPERTS + CONTEXT + [
    "mla_rows_attended_total{model=M,phase=PH}", "mla_rows_walked_total{model=M,phase=PH}",
    "mla_launches_total{model=M,phase=PH,form=absorbed}",
    "mla_launches_total{model=M,phase=PH,form=expanded}"] + COMPACT + [
    "mla_tiles_total{model=M,phase=PH,walk=kernel}", "mla_tiles_total{model=M,phase=PH,walk=xla}"] \
    + SAMPLE
SERIES = {
    "decoder": EXPERTS + CONTEXT + COMPACT + SAMPLE,
    "hybrid": EXPERTS + CONTEXT + SSM + COMPACT + SCANS + SAMPLE,
    "hybrid_ffn": CONTEXT + SSM + SCANS + SAMPLE,
    "mla": MLA,
    "mla_sc": MLA + ["moe_routed_zero_total{model=M,phase=PH}"],
    "mla_hc": MLA + ["hc_maps_total{model=M,phase=PH,path=kernel}",
                     "hc_maps_total{model=M,phase=PH,path=xla}"],
    "mla_sel": MLA + ["sel_pairs_scored_total{model=M,phase=PH}",
                      "sel_pairs_kept_total{model=M,phase=PH}",
                      "sel_rows_walked_total{model=M,phase=PH}",
                      "sel_queries_total{model=M,phase=PH,path=dense}",
                      "sel_queries_total{model=M,phase=PH,path=picked}",
                      "sel_threshold_tiles_total{model=M,phase=PH,path=kernel}",
                      "sel_threshold_tiles_total{model=M,phase=PH,path=xla}"],
    "decoder_sink": EXPERTS + CONTEXT + COMPACT + SAMPLE + [
        "attn_rows_attended_total{model=M,phase=PH}", "attn_rows_walked_total{model=M,phase=PH}",
        "attn_walks_total{model=M,phase=PH,walk=kernel}",
        "attn_walks_total{model=M,phase=PH,walk=xla}"],
    "hybrid_delta": EXPERTS + CONTEXT + SSM + COMPACT + [
        "decode:delta_steps_total{model=M,phase=decode,path=kernel}",
        "decode:delta_steps_total{model=M,phase=decode,path=xla}",
        "prefill:delta_scans_total{model=M,phase=prefill,path=kernel}",
        "prefill:delta_scans_total{model=M,phase=prefill,path=xla}"] + SAMPLE,
    "hybrid_conv": EXPERTS + CONTEXT + SSM + COMPACT + SAMPLE,
    "hybrid_ffn_moe": EXPERTS + CONTEXT + SSM + COMPACT + SCANS + SAMPLE,
    "hybrid_blk": CONTEXT + SSM + SCANS + [
        "blk_blocks_scored_total{model=M,phase=PH}", "blk_keys_visible_total{model=M,phase=PH}",
        "blk_keys_attended_total{model=M,phase=PH}", "blk_rows_read_total{model=M,phase=PH}",
        "blk_queries_total{model=M,phase=PH,path=dense}",
        "blk_queries_total{model=M,phase=PH,path=picked}",
        "blk_selects_total{model=M,phase=PH,path=kernel}",
        "blk_selects_total{model=M,phase=PH,path=xla}"] + SAMPLE,
    "eva": CONTEXT + [
        "eva_rows_attended_total{model=M,phase=PH,kind=exact}",
        "eva_rows_attended_total{model=M,phase=PH,kind=summary}",
        "eva_chunks_summarised_total{model=M,phase=PH}", "eva_windows_closed_total{model=M,phase=PH}",
        "decode:eva_decode_steps_total{model=M,phase=decode,path=head_walk}",
        "decode:eva_decode_steps_total{model=M,phase=decode,path=gather}",
        "prefill:eva_prefill_tiles_total{model=M,phase=prefill,path=tile_kernel}",
        "prefill:eva_prefill_tiles_total{model=M,phase=prefill,path=xla}"] + SAMPLE,
}
# The fourth expert column sums held experts x expert layers run, so it feeds
# the layers' counter too, over the experts held.
ALSO = {"moe_expert_steps_total": "moe_layers_total"}


def fed_by(template: str, model, ph: str, moved: float) -> dict:
    """{series: what it should have moved by} for one column in one phase."""
    only, _, name = template.rpartition(":")
    if only and only != ph:
        return {}
    name = name.replace("M", model.name, 1).replace("PH", ph)
    fed = {name: moved}
    for steps, layers in ALSO.items():
        if name.startswith(steps):
            fed[name.replace(steps, layers)] = moved / model.e_count
    return fed


@pytest.mark.parametrize("family", sorted(SERIES))
def test_every_column_of_acc_moves_its_own_counter_and_no_other(family, tmp_path):
    t = importlib.import_module(f"tests.test_{family}")
    model = t.make_model(str(tmp_path))
    names, n = SERIES[family], len(model.COLUMNS)
    metrics = Metrics()
    model.bind_metrics(metrics)
    plan = model.kv_plan(t.SLOTS, t.PAGE)
    pps, sig = plan.pages_per_slot, plan.state
    # one list says it all: the state's width, the counters bound, a launch's row
    assert len(names) == n and sig["acc"].shape == (len(GEN_PHASES), n)
    assert [len(row) for row in model._counters] == [n] * len(GEN_PHASES)

    params = model.init_params(jax.random.key(0))
    item = model.host_decode(json.dumps({
        "prompt_ids": [model.v_first + i for i in (5, 3, 9, 1, 7, 2)], "max_new_tokens": 3,
    }).encode(), "application/json")
    row = np.arange(1, pps + 1, dtype=np.int32)
    cache = {"pages": row, "ring": np.int32(1)} if plan.ring_tokens else row
    k = model.kv_prefill_pieces(t.CHUNK, t.PAGE)
    launch = model.pack_prefill([PrefillPiece(0, item, 0, 6, cache)], t.CHUNK, k)
    state = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), sig)
    state = jax.jit(model.prefill_chunk, static_argnames=("chunk",))(
        params, state, launch, chunk=t.CHUNK)
    state, out = jax.jit(model.step)(params, state)
    acc = np.array(out["acc"])
    assert acc.shape == (len(GEN_PHASES), n) and acc.dtype == np.uint32
    at = names.index(CONTEXT[0])
    assert acc[0, at] == 6 * 7 // 2 and acc[1, at] == 7   # six prompt tokens, one step at 6

    # the real launch and step: what moved on the device moved its own series, and no other
    model.observe_step(out)
    want: dict = {}
    for ph, sums in zip(GEN_PHASES, acc.astype(float)):
        for template, moved in zip(names, sums):
            if moved:
                assert fed_by(template, model, ph, moved), (template, ph, "moved in this phase")
                want.update(fed_by(template, model, ph, moved))
    assert {k: v for k, v in metrics.counter_values().items() if v} == pytest.approx(want)

    # and every column alone, the ones this launch left at zero too
    for p, ph in enumerate(GEN_PHASES):
        for j, template in enumerate(names):
            before = metrics.counter_values()
            acc[p, j] += 12
            model.observe_step({"acc": acc.copy()})
            after = metrics.counter_values()
            assert {k: after[k] - before.get(k, 0.0) for k in after
                    if after[k] != before.get(k, 0.0)} == pytest.approx(
                        fed_by(template, model, ph, 12.0)), (template, ph)


def test_the_picks_layers_count_by_where_their_block_scores_were_made(tmp_path):
    """ISSUE 69's two columns, `blk_selects_total{path=kernel|xla}`, in both phases
    (a launch's picked tiles by the path its trace chose, a step's lanes the
    plain form's), of the one family that picks blocks."""
    t = importlib.import_module("tests.test_hybrid_blk")
    model = t.make_model(str(tmp_path))
    metrics = Metrics()
    model.bind_metrics(metrics)
    names = SERIES["hybrid_blk"]
    acc = np.zeros((len(GEN_PHASES), len(names)), np.uint32)
    j = names.index("blk_selects_total{model=M,phase=PH,path=kernel}")
    assert names[j + 1] == "blk_selects_total{model=M,phase=PH,path=xla}"
    acc[0, j], acc[1, j + 1] = 3, 5
    model.observe_step({"acc": acc})
    assert {k: v for k, v in metrics.counter_values().items() if v} == {
        f"blk_selects_total{{model={model.name},phase=prefill,path=kernel}}": 3.0,
        f"blk_selects_total{{model={model.name},phase=decode,path=xla}}": 5.0}
    assert not [f for f, series in SERIES.items()
                if f != "hybrid_blk" and any("blk_selects_total" in s for s in series)]
