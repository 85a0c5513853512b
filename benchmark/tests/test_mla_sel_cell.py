"""The cell `deepseek-v3.2-e16-l5.longctx-closed-16` and the files it brought:
the cut configuration against the catalog and against the issue's arithmetic,
the program's config file with its share, the mix to the letter, the control,
the least counts of operations and bytes (the indexer's pairs, attention over
the picks alone), and the new readers on a run that has nothing."""

import json
import os

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "deepseek-v3.2-e16-l5.longctx-closed-16"
NAME = "deepseek-v3.2-e16-l5"
CFG = spec.load_config(BENCH, NAME)
ref = spec.load_module("reference", "mla_sel")
flops = spec.load_module("flops", "mla_sel")
tokens = spec.load_module("traffic", "token_prompts")
SZ = ref.sizes_from_config(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# Keys that are widths or counts of the mechanism: never in `reduced`, never changed.
WIDTHS = {"hidden_size": 7168, "intermediate_size": 18432, "moe_intermediate_size": 2048,
          "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts_per_tok": 8,
          "num_attention_heads": 128, "n_group": 8, "topk_group": 4, "index_n_heads": 64,
          "index_head_dim": 128, "index_topk": 2048, "n_shared_experts": 1}
NEW = ["sel_index_ms", "sel_index_roofline_share", "sel_attend_ms", "sel_attend_roofline_share",
       "sel_step_ms", "sel_step_roofline_share", "sel_keys_kept_pct", "sel_rows_overread"]


def test_the_cut_configuration_keeps_every_width_and_says_what_it_cut():
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                                   "n_routed_experts", "vocab_size"]
    assert entry["source"] == CFG["source"] and entry["file"] == f"benchmark/configs/{NAME}.json"
    assert CFG["family"] == "mla_sel" and len(entry["why"]) <= 200
    for key in ("source", "published", "reduced", "assumed", "deployment", "deployment_table",
                "serve", "check"):
        assert key in CFG
    assert {k: CFG[k] for k in WIDTHS} == WIDTHS
    assert CFG["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                "n_routed_experts": 256, "vocab_size": 129280}
    # the guide's floors: the leading dense layers once and four after them, at least 8 experts
    # a layer, an eighth of the vocabulary
    assert (CFG["num_hidden_layers"], CFG["first_k_dense_replace"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (5, 1, 16, 16160) and 8 * 16160 == 129280
    assert CFG["deployment_share"] == {"index": 0, "of": 16, "experts_first": 0, "vocab_first": 0}
    for said in ("16 v5e chips SHARE EACH LAYER", "WHOLE on every chip", "half of group 0",
                 "without their exchange", "no code stands in", "a sixteenth of its tokens",
                 "THREE page leaves", "8.63 GiB", "3.54 GiB"):
        assert said in CFG["deployment"], said
    assumed = json.dumps(CFG["assumed"])
    for said in ("1.874", "LayerNorm", "Hadamard", "FP8", "EXACTLY", "(i, i + 32)", "float32",
                 "8 groups of 32", "two largest", "STANDARD DEVIATION 0.58", "1,408 B"):
        assert said in assumed, said
    assert CFG["assumed"]["served"] == {**CFG["assumed"]["served"], "max_prompt_tokens": 32768,
                                        "max_new_tokens": 256}


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_no_other_key_of_the_published_config_differs():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3.2")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] != value and CFG["published"][key] == value, key
        else:
            assert CFG[key] == value and type(CFG[key]) is type(value), key
    assert set(ref.ARCH_KEYS) >= set(row["config"])
    assert {k for k in ref.ARCH_KEYS if k in CFG} == set(row["config"])


def test_the_arithmetic_of_the_cut_to_the_megabyte():
    m, table = flops._matrices(SZ), CFG["deployment_table"]
    assert m["mla"] == table["attention_a_layer"] == 187105280
    assert m["index"] == table["indexer_a_layer"] == 12582912 + 917504 + 458752
    assert m["expert"] == table["routed_expert"] == table["shared_expert"] == 44040192
    assert m["sparse_always"] == table["router"] + table["shared_expert"]
    assert m["dense"] == table["dense_swiglu"] == 396361728
    assert round(table["routed_layer"] / 1e5) / 10 == 951.6
    assert round(table["dense_layer"] / 1e5) / 10 == 597.4
    assert round(table["embedding_and_head"] / 1e5) / 10 == 231.7
    assert round(table["total"] / 1e5) / 10 == 4635.5
    assert round(2 * table["total"] / 2 ** 30 * 100) / 100 == 8.63
    # the cache: 1,408 B a token a layer in three leaves, a page 901,120 B, 3.54 GiB of 4,224 pages
    assert SZ["cache_row"] == 704 and SZ["row"] == 576 and SZ["index_dim"] == 128
    page = SZ["layers"] * SZ["page_tokens"] * SZ["cache_row"] * SZ["weight_bytes"]
    assert page == 901120 and round(SZ["kv_pages"] * page / 2 ** 30 * 100) / 100 == 3.54
    assert SZ["pages_per_slot"] == 258 and SZ["slots"] == 16 and SZ["kv_pages"] == 4224
    assert SZ["kv_pages"] == 16 * 258 + 96 and SZ["max_ctx"] == 32768 + 256
    # what kv_reserved_pct reckons a position at: five rows of 704 values
    per = 2 * SZ["kv_heads"] * SZ["head_dim"] * SZ["weight_bytes"]
    assert per * SZ["layer_types"].count("full_attention") == 5 * 1408


def test_the_programs_config_file_is_the_published_one_with_the_share():
    arch = ref.arch_from_config(CFG)
    assert arch["num_hidden_layers"] == 5 and arch["first_k_dense_replace"] == 1
    assert arch["n_routed_experts"] == 256 and arch["vocab_size"] == 129280
    assert arch["share"] == {"experts_held": [0, 16], "vocab_rows": [0, 16160]}
    assert (arch["n_group"], arch["topk_group"], arch["index_topk"]) == (8, 4, 2048)
    assert "family" not in arch and "serve" not in arch and "published" not in arch
    assert arch["weight_scales"] == CFG["assumed"]["weights"]["scales"]
    assert (SZ["num_experts"], SZ["experts_held"], SZ["vocab"], SZ["n_dense"], SZ["n_sparse"]) == \
        (256, 16, 16160, 1, 4)
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 16160]
    model = ref.Model(arch, 1)
    assert abs(model.score_scale * 192 ** 0.5 - 1.874) < 1e-3 and model.on_cos_sin == 1.0
    assert (model.e, model.e_first, model.e_count, model.vocab, model.vocab_full) == \
        (256, 0, 16, 16160, 129280)


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and cell["traffic"] == "longctx-closed-16" and cell["config"] == NAME
    assert len(cell["why"]) <= 200
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)]
    assert {"items_per_s", "setup_s"} <= set(e2e) <= {"items_per_s", "setup_s", "latency_p50_ms"}
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= listed
    assert {"gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
            "gen_prefill_roofline_share", "gen_lanes_active_pct", "kv_reserved_pct",
            "moe_experts_hit_pct", "mla_decode_ms", "mla_decode_roofline_share", "mla_prefill_ms",
            "mla_prefill_roofline_share", "idle_gen_loop_pct", "gen_step_ahead_pct"} <= listed
    # the gap readers go with the median latency, PR 34's rule
    assert ("gen_token_gap_ms_p50" in listed) == ("latency_p50_ms" in e2e)
    assert not [n for n in listed if n.startswith(("ssm_", "exec_roofline", "attn_decode", "hc_"))]
    for name in NEW:   # every new metric has its reader and lists this cell (a later cell may join)
        assert callable(spec.load_module("layer_metrics", name).read)
        assert CELL in spec.find(BENCH["per_layer"], name, "metric")["workloads"]
    assert [m["name"] for m in BENCH["per_layer"] if m["name"] in NEW] == NEW


def test_the_mix_is_the_issues_to_the_letter_and_every_seed_sends_the_same_lengths():
    mix = spec.load_mix("longctx-closed-16")
    assert (mix["traffic"], mix["verb"], mix["loop"], mix["clients"]) == \
        ("token_prompts", "generate", "closed", 16)
    assert mix["clients"] == SZ["slots"]
    (cls,) = mix["classes"]
    assert cls["share"] == 1.0 and "temperature" not in cls      # greedy
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 8192, "sigma": 0.6,
                                    "min": 3072, "max": 32768}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 128, "sigma": 0.5,
                                     "min": 32, "max": 256}
    assert (mix["warmup_s"], mix["drain_s"], mix["trace_ms"], mix["check_logprobs"]) == \
        (5.0, 20.0, 3000, 8)
    # the sample: a prompt inside one page; every other crosses the indexer's 2,048 inside a
    # launch's second piece, and the longest ends past 3,072, so that its prefill rows and its
    # decode steps attend over picks that drop a third of their keys
    chunk, topk = SZ["prefill_chunk"], SZ["index_topk"]
    sample = [(e["prompt_tokens"], e["max_new_tokens"]) for e in mix["check"]]
    assert sample[0][0] + sample[0][1] < SZ["page_tokens"]
    assert all(chunk == topk < n for n, _ in sample[1:]) and max(n for n, _ in sample) > 3072
    rows, _ = tokens.prepare("", CFG)
    x, y = (tokens.make_requests(mix, seed, rows, 256) for seed in (3000000019, 7))
    lx, ly = ([r.tokens[0] for r in reqs] for reqs in (x, y))
    assert sorted(lx) == sorted(ly) and lx != ly        # the same lengths in another order
    assert min(lx) >= 3072 and max(lx) <= 32768 and 7500 < float(np.median(lx)) < 9000
    assert 32 <= min(r.max_new for r in x) and max(r.max_new for r in x) <= 256
    assert 115 < float(np.median([r.max_new for r in x])) < 140
    assert abs(sum(lx[:128]) - sum(lx[128:])) < 0.2 * sum(lx[:128])
    assert max(r.tokens[0] + r.max_new for r in x) <= SZ["max_ctx"]
    ids = json.loads(x[0].body)["prompt_ids"]
    assert 0 <= min(ids) and max(ids) < 16160
    # the pool holds 16 prompts of the longest kind with their answers
    assert 16 * SZ["pages_per_slot"] < SZ["kv_pages"]


def test_the_control_differs_from_the_cell_by_the_check_alone():
    low = spec.load_config(BENCH, f"{NAME}-lowp")
    assert low["cell"] is False and low["check"]["reference_inputs"] == "3-bit-mantissa"
    apart = ("name", "base", "cell", "why", "check")
    strip = lambda c: {k: v for k, v in c.items() if k not in apart}  # noqa: E731
    assert strip(low) == strip(CFG)
    assert {k: v for k, v in low["check"].items() if k != "reference_inputs"} == CFG["check"]
    assert low["name"] not in [w["config"] for w in BENCH["workloads"]]
    # each limit between the sound and the control readings, with room on both sides
    r = CFG["check"]["readings"]
    assert 1.5 * max(r["sound_q25"]) <= CFG["check"]["limit"] <= min(r["control_q25"]) / 1.5
    assert 1.5 * max(r["sound_rms"]) <= CFG["check"]["rms_limit"] <= min(r["control_rms"]) / 1.5


def test_the_least_counts_hold_the_indexers_pairs_and_attention_over_the_picks_alone():
    m, k = flops._matrices(SZ), 2048
    # a step of 16 lanes at context 16,384: every lane past index_topk
    lanes, ctx = 16.0, 16 * 16384.0
    assert flops.lane_pairs(SZ, lanes, ctx) == (16 * k, ctx)
    assert flops.lane_pairs(SZ, lanes, 16 * 1000.0) == (16 * 1000.0, 0.0)   # under it: none scored
    ops, nbytes = flops.attend_decode(SZ, lanes, ctx)
    a_ops = 2 * 128 * (2 * 512 + 64) * 16 * k            # absorbed, over the picks
    i_ops = 2 * 64 * 128 * ctx                           # the indexer, over every key
    assert ops == 5 * (2 * 16 * (m["mla"] + m["index"]) + a_ops + i_ops)
    # a lane a layer: 4 MB of index keys and 2.36 MB of picked latents, the issue's
    assert round(16384 * 128 * 2 / 1e6, 1) == 4.2 and round(k * 576 * 2 / 1e6, 2) == 2.36
    assert nbytes == 5 * 2 * (m["mla"] + m["index"] + 576 * 16 * k + 128 * ctx + 704 * 16)
    # a launch of 2,048 rows at position 14,336 of a prompt of 16k
    tokens_, first = 2048.0, 14336.0
    pairs = tokens_ * (first + (tokens_ + 1) / 2)
    kept, scored = flops.run_pairs(SZ, tokens_, pairs)
    assert (kept, scored) == (tokens_ * k, pairs)
    # ... and the prompt's first launch keeps every key and scores none
    assert flops.run_pairs(SZ, tokens_, tokens_ * (tokens_ + 1) / 2) == \
        (tokens_ * (tokens_ + 1) / 2, 0.0)
    # a launch that crosses index_topk: its rows under it are dense
    kept, scored = flops.run_pairs(SZ, 2048.0, 2048.0 * (1024 + 2049 / 2))
    assert kept == sum(min(k, t + 1) for t in range(1024, 3072))
    assert scored == sum(t + 1 for t in range(2048, 3072))
    p_ops, p_bytes = flops.prefill_chunk(SZ, tokens_, pairs, 1024 * 4, 16 * 4)
    per_row = 2 * (5 * (m["mla"] + m["index"]) + m["dense"] + 4 * m["sparse_always"]
                   + 2 * m["expert"])
    assert abs(p_ops - (tokens_ * per_row + 5 * (flops.attend_ops(SZ, tokens_ * k)
                                                 + flops.index_ops(SZ, pairs))
                        + 2 * 7168 * 16160)) < 1e6
    assert p_ops / 197e12 > p_bytes / 819e9               # bound by its products
    # the issue's 0.57 GFLOP of attention a query over its picks, 16 k operations an index pair
    assert round(flops.attend_ops(SZ, k) / 1e9, 2) == 0.57 and flops.index_ops(SZ, 1) == 16384
    # the scopes' own counts
    assert flops.index(SZ, 1e6, 3e4) == (5 * 16384e6, 5 * 2 * 128 * 3e4)
    assert flops.attend(SZ, 1e6, 3e4) == (5 * flops.attend_ops(SZ, 1e6), 5 * 2 * 576 * 3e4)
    assert flops.step(SZ, ctx, 16 * k)[1] == 5 * 2 * (128 * ctx + 576 * 16 * k)
    assert flops.ops_and_bytes(SZ, 16, 8192)[1] > 0


def test_the_readers_return_nothing_and_do_not_raise_where_the_program_has_no_scope():
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "flops": flops,
           "sizes": SZ, "notes": []}
    for name in NEW:
        assert spec.load_module("layer_metrics", name).read(dict(run)) is None
    # the parent's program: tokens counted, no indexer's counter; another family's flops file
    run["metrics_delta"] = {'gen_iterations_total{model="model"}': 5.0,
                            'gen_decode_tokens_total{model="model"}': 50.0}
    run["flops"] = spec.load_module("flops", "mla")
    for name in NEW:
        assert spec.load_module("layer_metrics", name).read(dict(run)) is None
    # the counters alone give the two ratios
    run["metrics_delta"] = {
        'sel_pairs_scored_total{model="model",phase="decode"}': 8000.0,
        'sel_pairs_kept_total{model="model",phase="decode"}': 2000.0,
        'sel_rows_walked_total{model="model",phase="decode"}': 9000.0}
    assert spec.load_module("layer_metrics", "sel_keys_kept_pct").read(dict(run)) == 25.0
    assert spec.load_module("layer_metrics", "sel_rows_overread").read(dict(run)) == 4.5
