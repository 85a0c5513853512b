"""The cell `longcat-flash-chat-e16-l4.agent-closed` and the files it brought:
the cut configuration against the catalog and against the issue's arithmetic,
the program's config file with its share, the mix to the letter, the control,
the least counts of operations and bytes (eight attentions, zero-compute picks
at no cost), and the new readers on a run that has nothing."""

import json
import os

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "longcat-flash-chat-e16-l4.agent-closed"
NAME = "longcat-flash-chat-e16-l4"
CFG = spec.load_config(BENCH, NAME)
ref = spec.load_module("reference", "mla_sc")
flops = spec.load_module("flops", "mla_sc")
tokens = spec.load_module("traffic", "token_prompts")
SZ = ref.sizes_from_config(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# Keys that are widths: never in `reduced`, never changed.
WIDTHS = {"hidden_size": 6144, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
          "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128, "moe_topk": 12}
NEW = ["moe_zero_pick_pct", "moe_layer_ms", "moe_layer_roofline_share"]


def test_the_cut_configuration_keeps_every_width_and_says_what_it_cut():
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert entry["reduced"] == CFG["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == CFG["source"] and entry["file"] == f"benchmark/configs/{NAME}.json"
    assert CFG["family"] == "mla_sc" and len(entry["why"]) <= 200
    for key in ("source", "published", "reduced", "assumed", "deployment", "serve", "check"):
        assert key in CFG
    assert {k: CFG[k] for k in WIDTHS} == WIDTHS
    assert (CFG["num_attention_heads"], CFG["zero_expert_num"], CFG["routed_scaling_factor"]) == \
        (64, 256, 6)
    assert CFG["published"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    # the guide's floors: four layers, at least 8 experts a layer, an eighth of the vocabulary
    assert (CFG["num_layers"], CFG["n_routed_experts"], CFG["vocab_size"]) == (4, 16, 16384)
    assert CFG["deployment_share"] == {"index": 0, "of": 32, "experts_first": 0, "vocab_first": 0}
    for said in ("32 v5e chips SHARE EACH LAYER", "WHOLE on every chip", "zero-compute term",
                 "counts once", "without their exchange", "would see 8"):
        assert said in CFG["deployment"], said
    assumed = json.dumps(CFG["assumed"])
    for said in ("DOUBLE", "joins at the layer's END", "sqrt(hidden_size / q_lora_rank)",
                 "3.4641", "NOT over the picks' sum", "(2i, 2i + 1)", "float32", "untied",
                 "zero-compute", "groups of 32", "expert_out"):
        assert said in assumed, said
    assert CFG["assumed"]["served"] == {**CFG["assumed"]["served"], "max_prompt_tokens": 2048,
                                        "max_new_tokens": 768}


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_no_other_key_of_the_published_config_differs():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Chat")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] != value and CFG["published"][key] == value, key
        else:
            assert CFG[key] == value and type(CFG[key]) is type(value), key
    assert set(ref.ARCH_KEYS) == set(row["config"])


def test_the_arithmetic_of_the_cut_to_the_megabyte():
    m = flops._matrices(SZ)
    assert m["mla"] == 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 8192 * 6144
    assert round(m["mla"] / 1e4) / 100 == 90.57 and round(m["dense"] / 1e4) / 100 == 226.49
    assert m["router"] == 6144 * 768
    norms = 4 * 6144 + 2 * (1536 + 512)
    layer = 2 * m["mla"] + 2 * m["dense"] + m["router"] + 768 + norms
    assert round(layer / 1e5) / 10 == 638.9 and round(2 * layer / 1e6) == 1278
    experts = 16 * m["expert"]
    assert round(experts / 1e5) / 10 == 604.0 and round(2 * experts / 1e6) == 1208
    vocab = 2 * 16384 * 6144
    held = 4 * (layer + experts) + vocab + 6144
    assert round(4 * (layer + experts) / 1e6) == 4971 and round(vocab / 1e5) / 10 == 201.3
    assert round(held / 1e6) == 5173 and round(2 * held / 2 ** 20) == 9866   # MiB: 9.63 GiB
    # the cache: one row of 576 values a token an ATTENTION, 8 a position, 2.25 GiB of 2,048 pages
    assert flops.row_bytes(SZ) == 1152 and SZ["row"] == 576 and SZ["n_attn"] == 8
    page = SZ["n_attn"] * SZ["page_tokens"] * flops.row_bytes(SZ)
    assert page == 1179648 and round(SZ["kv_pages"] * page / 2 ** 20) == 2304
    assert SZ["pages_per_slot"] == 22 and SZ["slots"] == 256 and SZ["kv_pages"] == 2048
    # what kv_reserved_pct reckons a position at: eight rows, not K and V by head
    per = 2 * SZ["kv_heads"] * SZ["head_dim"] * SZ["weight_bytes"]
    assert per * SZ["layer_types"].count("full_attention") == 8 * 1152


def test_the_programs_config_file_is_the_published_one_with_the_share():
    arch = ref.arch_from_config(CFG)
    assert arch["num_layers"] == 4 and arch["n_routed_experts"] == 512
    assert arch["vocab_size"] == 131072 and arch["zero_expert_num"] == 256
    assert arch["share"] == {"experts_held": [0, 16], "vocab_rows": [0, 16384]}
    assert "family" not in arch and "serve" not in arch and "published" not in arch
    assert arch["weight_scales"] == CFG["assumed"]["weights"]["scales"]
    assert (SZ["num_experts"], SZ["zero_experts"], SZ["experts_held"], SZ["vocab"]) == \
        (512, 256, 16, 16384)
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 16384]


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and cell["traffic"] == "agent-closed" and cell["config"] == NAME
    assert len(cell["why"]) <= 200
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)]
    assert {"items_per_s", "setup_s"} <= set(e2e) <= {"items_per_s", "setup_s", "latency_p50_ms"}
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= listed
    assert {"gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
            "gen_prefill_roofline_share", "gen_lanes_active_pct", "kv_reserved_pct",
            "moe_experts_hit_pct", "moe_dispatch_compact_pct", "mla_decode_ms",
            "mla_decode_roofline_share", "mla_prefill_ms", "mla_prefill_roofline_share",
            "idle_gen_loop_pct", "gen_step_ahead_pct"} <= listed
    # the gap readers go with the median latency, PR 34's rule
    assert ("gen_token_gap_ms_p50" in listed) == ("latency_p50_ms" in e2e)
    assert not [n for n in listed if n.startswith(("ssm_", "exec_roofline", "attn_decode"))]
    for name in NEW:   # every new metric has its reader, and ends the list it was appended to
        assert callable(spec.load_module("layer_metrics", name).read)
    assert [m["name"] for m in BENCH["per_layer"] if m["name"] in NEW] == NEW


def test_the_mix_is_the_issues_to_the_letter_and_every_seed_sends_the_same_lengths():
    mix = spec.load_mix("agent-closed")
    assert (mix["traffic"], mix["verb"], mix["loop"], mix["clients"]) == \
        ("token_prompts", "generate", "closed", 256)
    assert mix["clients"] == SZ["slots"]
    (cls,) = mix["classes"]
    assert cls["share"] == 1.0 and "temperature" not in cls      # greedy
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.7,
                                    "min": 32, "max": 2048}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 320, "sigma": 0.5,
                                     "min": 64, "max": 768}
    assert (mix["warmup_s"], mix["drain_s"], mix["trace_ms"], mix["check_logprobs"]) == \
        (5.0, 20.0, 3000, 8)
    # a prompt inside one page, one whose decode crosses a page's edge (256), one across the
    # edge of a prefill launch (1,024)
    chunk, page = SZ["prefill_chunk"], SZ["page_tokens"]
    (a, na), (b, nb), (c, _nc) = [(e["prompt_tokens"], e["max_new_tokens"]) for e in mix["check"]]
    assert a + na < page and b < 2 * page <= b + nb and c > chunk
    rows, _ = tokens.prepare("", CFG)
    x, y = (tokens.make_requests(mix, seed, rows, 512) for seed in (3000000019, 7))
    lx, ly = ([r.tokens[0] for r in reqs] for reqs in (x, y))
    assert sorted(lx) == sorted(ly) and lx != ly        # the same lengths in another order
    assert min(lx) >= 32 and max(lx) <= 2048 and 230 < float(np.median(lx)) < 285
    assert 64 <= min(r.max_new for r in x) and max(r.max_new for r in x) <= 768
    assert 290 < float(np.median([r.max_new for r in x])) < 350
    assert abs(sum(lx[:256]) - sum(lx[256:])) < 0.2 * sum(lx[:256])
    assert max(r.tokens[0] + r.max_new for r in x) <= SZ["max_ctx"]
    ids = json.loads(x[0].body)["prompt_ids"]
    assert 0 <= min(ids) and max(ids) < 16384
    # the pool holds what 256 lanes reserve: a request's prompt and its whole answer
    need = sorted(-(-(r.tokens[0] + r.max_new) // page) for r in x)
    assert sum(need[-256:]) / 2 < SZ["kv_pages"] and float(np.mean(need)) * 256 < SZ["kv_pages"] - 1


def test_the_control_differs_from_the_cell_by_the_check_alone():
    low = spec.load_config(BENCH, f"{NAME}-lowp")
    assert low["cell"] is False and low["check"]["reference_inputs"] == "3-bit-mantissa"
    strip = lambda c: {k: v for k, v in c.items() if k not in ("name", "base", "cell", "why", "check")}  # noqa: E731
    assert strip(low) == strip(CFG)
    assert {k: v for k, v in low["check"].items() if k != "reference_inputs"} == CFG["check"]
    assert low["name"] not in [w["config"] for w in BENCH["workloads"]]
    # each limit at least 2x from the nearest sound and control reading
    r = CFG["check"]["readings"]
    assert 2 * max(r["sound_q25"]) <= CFG["check"]["limit"] <= min(r["control_q25"]) / 2
    assert 2 * max(r["sound_rms"]) <= CFG["check"]["rms_limit"] <= min(r["control_rms"]) / 2


def test_the_least_counts_hold_eight_attentions_and_a_zero_pick_costs_nothing():
    mla = spec.load_module("flops", "mla")
    one = {**SZ, "layers": 1, "n_dense": 0, "n_sparse": 0, "shared_width": 0}
    # the eight `mla_decode` scopes are eight times one attention's count
    lanes, ctx = 256.0, 256 * 420.0
    ops8, bytes8 = flops.attend_decode(SZ, lanes, ctx)
    ops1, bytes1 = mla.attend_decode(one, lanes, ctx)
    assert ops8 == 8 * ops1 and bytes8 == 8 * bytes1
    assert abs(bytes8 - 8 * (2 * flops._matrices(SZ)["mla"] + 1152 * ctx)) < 1e3
    # ... and a launch's rows attended, summed over the eight by the program, are one's
    p8 = flops.attend_prefill(SZ, 1024, 1024 * 700.0, 8 * 1500.0)
    p1 = mla.attend_prefill(one, 1024, 1024 * 700.0, 1500.0)
    assert p8 == (8 * p1[0], 8 * p1[1])
    # the routed layer: the router and the HIT experts read once; no term grows with zero picks
    m = flops._matrices(SZ)
    r_ops, r_bytes = flops.routed_layer(SZ, 256, 64 * 4, 16 * 4)
    assert r_ops == 2 * 256 * 4 * m["router"] + 2 * 256 * m["expert"]
    assert r_bytes == 2 * (4 * m["router"] + 64 * m["expert"]) + 4 * 256 * 6144 * 6
    assert 4.8e9 < r_bytes < 5.0e9       # the issue's 4.8 GB of held experts a step, and the router
    # a step of 256 lanes at context 420: attention and dense kernels 5.1 GB, latents about 1 GB
    ops, nbytes = flops.decode_step(SZ, 256, ctx, 64 * 4, 16 * 4)
    always = 2 * (8 * m["mla"] + 4 * (2 * m["dense"] + m["router"]))
    assert 5.0e9 < always < 5.2e9 and nbytes > always + 2 * 64 * m["expert"] + 8 * 1152 * ctx
    assert 13 < nbytes / 819e9 * 1e3 < 15 and ops / 197e12 < nbytes / 819e9
    # a prefill launch of 1,024 live rows is bound by its products: 5.2 GFLOP a row
    p_ops, p_bytes = flops.prefill_chunk(SZ, 1024, 1024 * 300.0, 256 * 4, 16 * 4)
    assert 5.1e9 < p_ops / 1024 < 5.5e9 and p_ops / 197e12 > p_bytes / 819e9
    assert flops.ops_and_bytes(SZ, 256, 420)[1] > 0


def test_the_readers_return_nothing_and_do_not_raise_where_the_program_has_no_scope():
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "flops": flops,
           "sizes": SZ, "notes": []}
    for name in NEW:
        assert spec.load_module("layer_metrics", name).read(dict(run)) is None
    # the parent's program: expert picks counted, no zero-compute counter
    run["metrics_delta"] = {'moe_tokens_routed_total{model="model",phase="decode",held="yes"}': 5.0}
    assert spec.load_module("layer_metrics", "moe_zero_pick_pct").read(dict(run)) is None
