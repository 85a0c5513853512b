"""The cell `joyai-llm-flash-l5.longdoc-closed-16` and the files it brought: the
cut configuration against the catalog and against the issue's arithmetic, the
program's config file, the mix to the letter, the control, the least counts
of operations and bytes, and the `mla_*` readers on a run that has nothing."""

import json
import os

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "joyai-llm-flash-l5.longdoc-closed-16"
NAME = "joyai-llm-flash-l5"
CFG = spec.load_config(BENCH, NAME)
mla = spec.load_module("reference", "mla")
flops = spec.load_module("flops", "mla")
tokens = spec.load_module("traffic", "token_prompts")
SZ = mla.sizes_from_config(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# Keys that are widths: never in `reduced`, never changed.
WIDTHS = {"hidden_size": 2048, "intermediate_size": 7168, "moe_intermediate_size": 768,
          "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128, "head_dim": 64,
          "num_experts_per_tok": 8}


def test_the_cut_configuration_keeps_every_width_head_expert_and_row_and_says_what_it_cut():
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CFG["source"] and entry["file"] == f"benchmark/configs/{NAME}.json"
    assert CFG["family"] == "mla"
    for key in ("source", "published", "reduced", "assumed", "deployment", "serve", "check"):
        assert key in CFG
    assert {k: CFG[k] for k in WIDTHS} == WIDTHS
    assert (CFG["num_attention_heads"], CFG["n_routed_experts"], CFG["vocab_size"]) == (32, 256, 129280)
    assert CFG["published"] == {"num_hidden_layers": 40} and CFG["num_hidden_layers"] == 5
    # the leading dense layer and the guide's floor of four layers after it
    assert CFG["first_k_dense_replace"] == 1 and SZ["n_dense"] == 1 and SZ["n_sparse"] == 4
    for said in ("eight v5e chips", "PIPELINE STAGES", "WHOLE", "stage 0", "embedding and the head"):
        assert said in CFG["deployment"], said
    assumed = json.dumps(CFG["assumed"])
    for said in ("sigmoid", "does not enter the weight", "(2i, 2i + 1)", "float32",
                 "multi-token prediction not served", "absorbed", "expanded", "expert_out"):
        assert said in assumed, said
    assert CFG["assumed"]["served"] == {**CFG["assumed"]["served"], "max_prompt_tokens": 24576,
                                        "max_new_tokens": 256}


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_no_other_key_of_the_published_config_differs():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "JoyAI-LLM-Flash")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] != value and CFG["published"][key] == value, key
        else:
            assert CFG[key] == value, key
    assert set(mla.ARCH_KEYS) == set(row["config"])


def test_the_arithmetic_of_the_cut_to_the_megabyte():
    m = flops._matrices(SZ)
    assert m["mla"] == 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048 == 26345472
    experts = 4 * 256 * m["expert"]
    assert round(experts / 1e5) / 10 == 4831.8 and round(2 * experts / 1e6) == 9664
    assert round(4 * m["sparse_always"] / 1e5) / 10 == 21.0
    assert round(5 * m["mla"] / 1e5) / 10 == 131.7 and round(m["dense"] / 1e5) / 10 == 44.0
    held = experts + 4 * m["sparse_always"] + 5 * m["mla"] + m["dense"] + 2 * 2048 * 129280
    assert round(held / 1e6) == 5558 and round(2 * held / 2 ** 20) == 10601   # MiB: 10.35 GiB
    # the cache: one row of 576 values a token a layer, 737,280 B a page, 2.20 GiB of 3,200 pages
    assert flops.row_bytes(SZ) == 1152 and SZ["row"] == 576
    page = SZ["layers"] * SZ["page_tokens"] * flops.row_bytes(SZ)
    assert page == 737280 and round(SZ["kv_pages"] * page / 2 ** 20) == 2250
    assert SZ["pages_per_slot"] == 194 and SZ["slots"] * 194 < SZ["kv_pages"] == 3200
    # what kv_reserved_pct reckons a position at: the row, not K and V by head
    assert 2 * SZ["kv_heads"] * SZ["head_dim"] * SZ["weight_bytes"] == 1152
    # plain K and V of the same heads: 17.8x
    assert round(32 * (192 + 128) * 2 / 1152, 1) == 17.8


def test_the_programs_config_file_is_the_published_one_with_the_depth_cut():
    arch = mla.arch_from_config(CFG)
    assert arch["num_hidden_layers"] == 5 and arch["n_routed_experts"] == 256
    assert "share" not in arch and "family" not in arch and "serve" not in arch
    assert arch["rope_scaling"] is None and arch["rope_interleave"] is True
    assert arch["weight_scales"]["expert_out"] == 0.35 and arch["weight_scales"]["q_b"] == 2.0


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc-closed-16" and cell["config"] == NAME
    assert len(cell["why"]) <= 200
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)]
    # not `latency_p50_ms`: 113 answers a window spread the median by 5% over six seeds, and a
    # new cell is admitted under half that metric's bound (PERF.md section 6, PR 34)
    assert sorted(e2e) == ["items_per_s", "setup_s"]
    mine = [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == ["mla_decode_ms", "mla_decode_roofline_share", "mla_prefill_ms",
                    "mla_prefill_roofline_share"]
    shared = [m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in mine]
    assert sorted(shared) == sorted([
        "gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
        "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
        "moe_experts_hit_pct", "kv_reserved_pct", "idle_gen_loop_pct"])
    for m in BENCH["per_layer"]:
        assert not (m["name"].startswith(("ssm_", "exec_roofline")) and CELL in m.get("workloads", []))
    for name in mine:   # every listed metric has its reader
        assert callable(spec.load_module("layer_metrics", name).read)


def test_the_mix_is_the_issues_to_the_letter_and_every_seed_sends_the_same_lengths():
    mix = spec.load_mix("longdoc-closed-16")
    assert (mix["traffic"], mix["verb"], mix["loop"], mix["clients"]) == \
        ("token_prompts", "generate", "closed", 16)
    (cls,) = mix["classes"]
    assert cls["share"] == 1.0
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 8192, "sigma": 0.5,
                                    "min": 2048, "max": 24576}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 96, "sigma": 0.5,
                                     "min": 16, "max": 256}
    assert (mix["pool_requests"], mix["warmup_s"], mix["drain_s"], mix["trace_ms"],
            mix["check_logprobs"]) == (1024, 5.0, 20.0, 3000, 8)
    assert [(c["prompt_tokens"], c["max_new_tokens"]) for c in mix["check"]] == \
        [(96, 24), (3050, 32), (4210, 24)]
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 129280]
    a, b = (tokens.make_requests(mix, seed, rows, 64) for seed in (3000000019, 7))
    la, lb = ([r.tokens[0] for r in reqs] for reqs in (a, b))
    assert sorted(la) == sorted(lb) and la != lb        # the same lengths in another order
    assert min(la) >= 2048 and max(la) <= 24576 and 7000 < float(np.median(la)) < 9500
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    # balanced: either half of the pool's head carries the same work to within a fifth
    assert abs(sum(la[:32]) - sum(la[32:])) < 0.2 * sum(la[:32])
    assert a[0].body != b[0].body and max(r.tokens[0] + r.max_new for r in a) <= SZ["max_ctx"]


def test_the_control_differs_from_the_cell_by_the_check_alone():
    low = spec.load_config(BENCH, "joyai-llm-flash-l5-lowp")
    assert low["cell"] is False and low["check"]["reference_inputs"] == "3-bit-mantissa"
    strip = lambda c: {k: v for k, v in c.items() if k not in ("name", "base", "cell", "why", "check")}  # noqa: E731
    assert strip(low) == strip(CFG)
    assert {k: v for k, v in low["check"].items() if k != "reference_inputs"} == CFG["check"]
    assert all(n not in [w["config"] for w in BENCH["workloads"]] for n in (low["name"],))


def test_the_least_counts_take_the_cheaper_form_and_read_a_row_once():
    # a decode step is absorbed, a launch over a long context expanded; they break even at 171 rows
    assert flops.attention(SZ, 16 * 9000.0, 16 * 9000.0 - 16)[1] == "absorbed"
    assert flops.attention(SZ, 2048 * 5000.0, 4000.0)[1] == "expanded"
    per_pair = {f: flops.attention(SZ, 1.0, c)[0] for f, c in (("expanded", 0.0),)}
    assert per_pair["expanded"] == 2 * 32 * 320 == 20480
    assert 2 * 32 * (2 * 512 + 64) == 69632 and 2 * 512 * 32 * 256 == 8388608
    assert 170 < 8388608 / (69632 - 20480) < 171
    # a step of 16 lanes at 9,300: the experts that are hit and the latents, read once
    ops, nbytes = flops.decode_step(SZ, 16, 16 * 9300.0, 16 * 8 * 4, 0.39 * 256 * 4)
    latents = 5 * 1152 * 16 * 9300.0
    assert 0.8e9 < latents < 0.9e9 and nbytes > latents + 0.39 * 1024 * flops._matrices(SZ)["expert"] * 2
    d_ops, d_bytes = flops.attend_decode(SZ, 16, 16 * 9300.0)
    assert d_bytes < nbytes and abs(d_bytes - latents - 5 * 2 * 26345472) < 1e6
    # the generic readers' prefill count never reckons more cached rows than the launch's pieces began at
    assert flops.earlier_rows(2048, 2048 * 4096 + 2048 * 2049 / 2) == 4096
    assert flops.earlier_rows(2048, 2 * (1024 * 3000 + 1024 * 1025 / 2)) <= 6000
    p_ops, p_bytes = flops.attend_prefill(SZ, 2048, 2048 * 5120.5, 6144)
    assert p_bytes == 5 * (2 * 26345472 + 1152 * 6144)
    assert p_ops < flops.prefill_chunk(SZ, 2048, 2048 * 5120.5, 2048 * 32, 1024)[0]
    assert flops.ops_and_bytes(SZ, 16, 9300)[1] > 0


def test_the_readers_return_nothing_and_do_not_raise_where_the_program_has_no_scope():
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "flops": flops,
           "sizes": SZ, "notes": []}
    for name in ("mla_decode_ms", "mla_prefill_ms", "mla_decode_roofline_share",
                 "mla_prefill_roofline_share"):
        assert spec.load_module("layer_metrics", name).read(dict(run)) is None
