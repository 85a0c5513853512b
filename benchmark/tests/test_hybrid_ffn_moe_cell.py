"""The cell `granite-4.0-h-small-e2-l10.support-closed-96` and the files it
brought: the cut configuration against the catalog and against the issue's
arithmetic, the program's config file with its share, the mix to the letter, the
control, the least counts of operations and bytes against counts by hand, the
three new readers on a run that has nothing, and the check's pass in two calls
against one. What is asserted of `BENCHMARK.json` is what the harness needs (the
cell is listed, the metrics it should report name it), not where in a list an
entry stands: a later cell appends to the same lists."""

import json
import os

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "granite-4.0-h-small-e2-l10.support-closed-96"
NAME = "granite-4.0-h-small-e2-l10"
CFG = spec.load_config(BENCH, NAME)
fam = spec.load_module("reference", "hybrid_ffn_moe")
flops = spec.load_module("flops", "hybrid_ffn_moe")
tokens = spec.load_module("traffic", "token_prompts")
SZ = fam.sizes_from_config(CFG)
MIX = spec.load_mix("support-closed-96")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]

JOINED = {"gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
          "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
          "gen_loop_serial_ms_per_iter", "gen_step_ahead_pct", "gen_loop_cpu_share_pct",
          "gen_account_trees_pct", "gen_sample_ms", "gen_first_token_ms_p50",
          "gen_token_gap_ms_p50", "gen_token_gap_ms_p95", "idle_gen_loop_pct",
          "idle_gen_fetch_pct", "idle_gen_launch_pct", "idle_gen_hop_pct", "idle_gen_retire_pct",
          "idle_gen_host_pct", "idle_gen_no_work_pct", "idle_gen_unknown_pct", "kv_reserved_pct",
          "ssm_update_ms", "ssm_update_roofline_share", "ssm_update_step_share_pct", "ssm_scan_ms",
          "ssm_scan_roofline_share", "ssm_state_carried_pct", "attn_decode_ms",
          "attn_decode_roofline_share", "moe_experts_hit_pct", "moe_experts_prefill_ms",
          "moe_experts_step_ms", "moe_experts_step_roofline_share", "moe_tokens_per_expert_step",
          "moe_dispatch_compact_pct", "moe_layer_ms", "moe_layer_roofline_share"}
NEW = {"moe_dispatch_step_ms": "ms", "moe_shared_step_ms": "ms", "moe_layer_step_share_pct": "%"}


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "support-closed-96", 1)
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200      # the driver's limit on a line
    for said in ("96", "13 each", "half the pair's", "10/40"):
        assert said in cell["why"], said
    assert entry["reduced"] == REDUCED == CFG["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and entry["source"] == CFG["source"]
    reported = {m["name"] for m in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert JOINED | set(NEW) <= reported
    assert not {n for n in reported if n.startswith(("mla_", "delta_", "eva_", "sel_", "hc_",
                                                     "moe_zero", "tokenize", "exec_roofline"))}
    end = {m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)}
    assert end == {"items_per_s", "latency_p50_ms", "setup_s"}
    for name, unit in NEW.items():
        m = spec.find(BENCH["per_layer"], name, "metric")
        assert m["workloads"] == [CELL] and m["moves"] == "items_per_s" \
            and m["source"] == "device_trace" and m["unit"] == unit and m["layer"] == "models"
        assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{name}.py"))


def test_the_configuration_is_cut_as_it_says_and_says_what_it_assumed():
    assert CFG["family"] == "hybrid_ffn_moe" and CFG["reduced"] == REDUCED
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["num_local_experts"], pub["vocab_size"]) \
        == (40, 72, 100352) and len(pub["layer_types"]) == 40
    assert CFG["layer_types"] == pub["layer_types"][:10]           # ONE WHOLE PERIOD
    assert "".join(k[0] for k in CFG["layer_types"]) == "mmmmmammmm"
    assert (CFG["num_hidden_layers"], CFG["num_local_experts"], CFG["vocab_size"]) \
        == (10, 36, 50176)
    # no width is cut
    assert (CFG["hidden_size"], CFG["intermediate_size"], CFG["shared_intermediate_size"],
            CFG["num_experts_per_tok"], CFG["mamba_n_heads"], CFG["mamba_d_head"],
            CFG["mamba_d_state"], CFG["mamba_n_groups"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"]) == (4096, 768, 1536, 10, 128, 64, 128, 1, 32, 8)
    assert (CFG["embedding_multiplier"], CFG["residual_multiplier"], CFG["attention_multiplier"],
            CFG["logits_scaling"]) == (12, 0.22, 0.0078125, 16)
    assert CFG["deployment_share"] == {"index": 0, "of": 2, "experts_first": 0, "vocab_first": 0}
    for said in ("TWO v5e chips SHARE EACH LAYER", "four pipeline stages of ten layers",
                 "experts 0-35", "4,757,211,776", "no code stands in"):
        assert said in CFG["deployment"], said
    assumed = json.dumps(CFG["assumed"])
    for said in ("no clamp", "mamba_chunk_size", "float32", "end_of_sequence", "NO bias",
                 "intermediate_size = 768", "softmax over those ten alone", "expert_out 0.5",
                 "router 1.0", "qk 6.73", "lower expert number"):
        assert said in assumed, said
    served = CFG["assumed"]["served"]
    assert (served["max_prompt_tokens"], served["max_new_tokens"]) == (4096, 512)
    gen = CFG["serve"]["tables"]["genserve"]
    assert (gen["slots"], gen["kv_page_tokens"], gen["kv_pages"], gen["prefill_chunk"],
            gen["admit_per_step"]) == (MIX["clients"], 128, 2048, 1024, 4)
    assert CFG["serve"]["model"]["dtype"] == "bfloat16"
    check = CFG["check"]
    sound, control = check["readings"]["sound_q25"], check["readings"]["control_q25"]
    assert len(sound) >= 6 and len(control) >= 1
    assert 2 * max(sound) <= check["limit"] <= min(control) / 2
    assert 2 * max(check["readings"]["sound_rms"]) <= check["rms_limit"] \
        <= min(check["readings"]["control_rms"]) / 2
    lowp = spec.load_config(BENCH, f"{NAME}-lowp")
    assert lowp["check"]["reference_inputs"] == "3-bit-mantissa" and lowp["cell"] is False \
        and lowp["check"]["limit"] == check["limit"] and lowp["family"] == "hybrid_ffn_moe"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_every_key_of_the_published_config_is_in_the_file_as_published_or_reduced():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-small")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CFG["published"][key] == value, key
        else:
            assert CFG[key] == value, key
    # ... and every one of them reaches the program's config file, the counts put back
    arch = SZ["arch"]
    assert set(row["config"]) <= set(fam.ARCH_KEYS) and set(row["config"]) <= set(arch)
    assert (arch["num_local_experts"], arch["vocab_size"]) == (72, 100352)
    assert arch["share"] == {"experts_held": [0, 36], "vocab_rows": [0, 50176]}


def test_the_sizes_are_the_issues_arithmetic():
    t, d = CFG["deployment_table"], 4096
    assert t["mamba_mixer"] == d * 16768 + 8192 * d + 8448 * 5 + 3 * 128 + 8192
    assert 16768 == 2 * 8192 + 2 * 128 + 128
    assert t["routed_expert"] == 3 * d * 768 == 9_437_184 and t["shared_expert"] == 3 * d * 1536
    assert t["total"] == 9 * t["mamba_layer"] + t["attention_layer"] \
        + t["embedding_also_head"] + t["final_norm"] == 4_757_211_776
    assert round(t["total"] * 2 / 2 ** 30, 2) == 8.86
    assert SZ["state_bytes_per_slot"] == 38_204_928 == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert round(96 * SZ["state_bytes_per_slot"] / 2 ** 30, 2) == 3.42
    assert (SZ["n_mamba"], SZ["n_attn"], SZ["n_expert"], SZ["layers"], SZ["head_dim"]) \
        == (9, 1, 10, 10, 128)
    assert (SZ["num_experts"], SZ["experts_held"], SZ["top_k"], SZ["expert_width"],
            SZ["shared_width"], SZ["vocab"], SZ["vocab_first"]) == (72, 36, 10, 768, 1536, 50176, 0)
    assert SZ["pages_per_slot"] == 36 and SZ["max_ctx"] == 4608 and SZ["kv_pages"] == 2048
    row = 2 * SZ["kv_heads"] * SZ["head_dim"] * 2
    assert row == 4096 and row * 128 * 2048 == 2 ** 30          # 4 KiB a token, 1.0 GiB of pages
    assert 96 * 10 / 72 == pytest.approx(13.33, abs=0.01)        # tokens an expert a step


def test_the_least_counts_are_counts_by_hand():
    d, wb = 4096, 2
    expert, shared, router = 3 * d * 768, 3 * d * 1536, d * 72
    lanes, ctx = 96.0, 96 * 1000.0
    picks, hit = 96 * 10 * 0.5 * 10, 360.0     # half the picks land here; every held expert hit
    ops, nbytes = flops.experts_step(SZ, lanes, picks, hit)
    assert ops == 2 * picks * expert == pytest.approx(90.6e9, rel=1e-3)    # 18.9 MFLOP a pick
    assert nbytes == wb * hit * expert + picks * (d * 2 + d * 4)           # 8 KiB in, 16 KiB out
    assert nbytes == pytest.approx(6.91e9, rel=2e-3)
    r_ops, r_bytes = flops.routed_layer(SZ, lanes, picks, hit)
    assert r_ops == ops + 2 * lanes * 10 * router
    assert r_bytes == nbytes + wb * 10 * (router + lanes * d)
    s_ops, s_bytes = flops.decode_step(SZ, lanes, ctx, picks, hit)
    # the weights once (the embedding as the head), the states twice, live K and V, the picks' rows
    weights = CFG["deployment_table"]["total"] * wb
    want = weights + 2 * 96 * SZ["state_bytes_per_slot"] + ctx * 4096 + picks * d * 6
    assert s_bytes == pytest.approx(want, rel=5e-3)
    assert s_bytes / 819e9 > s_ops / 197e12          # a step is bound by memory: about 21 ms
    assert 0.019 < s_bytes / 819e9 < 0.022
    # the routed block in a step: the router, the shared expert and the held picks' products
    m_ops, _ = flops.decode_step(dict(SZ, n_expert=0), lanes, ctx, 0.0, 0.0)
    assert s_ops - m_ops == 2 * lanes * 10 * (router + shared) + ops
    u_ops, u_bytes = flops.update(SZ, lanes)
    assert u_bytes == pytest.approx(2 * 96 * SZ["state_bytes_per_slot"]
                                    + wb * 9 * (d * 16768 + 8192 * d), rel=1e-6)
    a_ops, a_bytes = flops.attend_decode(SZ, lanes, ctx)
    assert a_bytes == pytest.approx(wb * (2 * d * 4096 + 2 * d * 1024) + ctx * 4096 + lanes * 4096)
    p_ops, p_bytes = flops.prefill_chunk(SZ, 1024.0, 1024 * 600.0, 1024 * 50.0, hit)
    assert p_ops > 1024 * 2 * (9 * 102.3e6 + 41.9e6 + 10 * (shared + router)) + 2 * 51200 * expert
    assert 3.0e12 < p_ops < 3.6e12 and p_bytes > weights    # about 3.3 TFLOP a full launch
    assert flops.ops_and_bytes(SZ, 96, 1000)[1] == pytest.approx(s_bytes, rel=1e-3)
    assert flops.scan(SZ, 900, 5)[0] == 9 * 4.0 * 900 * 128 * 64 * 128


def test_the_mix_is_the_issues_traffic():
    assert (MIX["traffic"], MIX["verb"], MIX["loop"], MIX["clients"]) == \
        ("token_prompts", "generate", "closed", 96)
    (cls,) = MIX["classes"]
    assert cls["share"] == 1.0 and "temperature" not in cls
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.8,
                                    "min": 128, "max": 4096}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.5,
                                     "min": 48, "max": 448}
    assert (MIX["pool_requests"], MIX["warmup_s"], MIX["drain_s"], MIX["trace_ms"],
            MIX["check_logprobs"]) == (8192, 5.0, 20.0, 3000, 8)
    check = [(c["prompt_tokens"], c["max_new_tokens"]) for c in MIX["check"]]
    assert check == [(40, 24), (300, 48), (1100, 40)]
    assert all(p + n <= SZ["max_ctx"] and p <= SZ["max_prompt"] for p, n in check)
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 50176]                                     # the held rows
    reqs = tokens.make_requests(MIX, 7, rows, 1024)
    lens = np.asarray([r.tokens[0] for r in reqs])
    news = np.asarray([r.max_new for r in reqs])
    assert lens.min() >= 128 and lens.max() <= 4096 and news.min() >= 48 and news.max() <= 448
    assert 700 < np.median(lens) < 840 and 175 < np.median(news) < 210
    assert 940 < lens.mean() < 1060 and 200 < news.mean() < 225   # about 1,000 in, 212 out
    # every caller's mean request holds its pages with room; the longest is admitted alone
    assert 96 * -(-(1000 + 212) // 128) < 2048 and SZ["pages_per_slot"] < 2048


def test_the_new_readers_return_none_and_never_raise_on_a_run_that_has_nothing():
    for name in NEW:
        read = spec.load_module("layer_metrics", name).read
        assert read({}) is None
        assert read({"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
                     "peaks": None, "flops": flops, "sizes": SZ}) is None


@pytest.mark.parametrize("low", [False, True])
def test_the_pass_in_two_calls_is_the_pass_in_one(low):
    """The check's pass, the prompts first and the served tokens continued
    from what they left, against ONE call over the whole sequences, at the
    rehearsal's toy size with its share (experts 4-7, rows 16-79): the same
    recurrence over the same tokens (float32 sums over other row counts: 1e-5;
    the control rounds to 3 bits, where a row count can move a rounding)."""
    import jax.numpy as jnp

    cfg = spec.load_config(BENCH, "rehearsal-hybrid_ffn_moe-tiny")
    toy = fam.sizes_from_config(cfg)
    assert toy["arch"]["share"] == {"experts_held": [4, 4], "vocab_rows": [16, 64]}
    assert (toy["vocab"], toy["vocab_first"], toy["experts_held"], toy["num_experts"]) \
        == (64, 16, 4, 8)
    m = fam.Model(toy["arch"], 5, "float32")
    assert m.embed().shape == (64, 128) and m.layer(0)["e_gate"].shape == (4, 128, 32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, n) for n in (2, 9, 17)]
    rest = [rng.integers(0, 64, n) for n in (11, 4, 0)]
    whole = fam.hidden_states(m, [np.concatenate(pr) for pr in zip(prompts, rest)], low)
    layers, last, carry = fam.prompt_pass(m, prompts, low)
    hs, _ = fam.forward(m, layers, rest, carry, low)
    for w, p, h0, h in zip(whole, prompts, last, hs):
        got = jnp.concatenate([h0, h], axis=0)
        np.testing.assert_allclose(got, w[len(p) - 1:], atol=1e-5 if not low else 2e-2)
    if low:   # the control's rounding is seen
        sound = fam.hidden_states(m, [np.concatenate(pr) for pr in zip(prompts, rest)], False)
        assert float(np.abs(np.asarray(sound[2]) - np.asarray(whole[2])).max()) > 1e-2
