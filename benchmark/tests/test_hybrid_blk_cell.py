"""The cell `minicpm-sala-l4.longsel-closed-16` and the files it brought: the cut
configuration against the catalog and against the issue's arithmetic, the
program's config file with the published depth, the mix to the letter, the
control, the least counts of operations and bytes against counts by hand, the
seven new readers on a run that has nothing, the check's pass in two calls
against one, and the check rejecting altered tokens. What is asserted of
`BENCHMARK.json` is what the harness needs (the cell is listed, the metrics it
should report name it), not where in a list an entry stands: a later cell
appends to the same lists."""

import json
import os

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "minicpm-sala-l4.longsel-closed-16"
NAME = "minicpm-sala-l4"
CFG = spec.load_config(BENCH, NAME)
fam = spec.load_module("reference", "hybrid_blk")
flops = spec.load_module("flops", "hybrid_blk")
tokens = spec.load_module("traffic", "token_prompts")
SZ = fam.sizes_from_config(CFG)
MIX = spec.load_mix("longsel-closed-16")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "mixer_types"]

JOINED = {"gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
          "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
          "gen_loop_serial_ms_per_iter", "gen_step_ahead_pct", "gen_loop_cpu_share_pct",
          "gen_account_trees_pct", "gen_sample_ms", "gen_step_unscoped_pct",
          "gen_prefill_unscoped_pct", "gen_proj_step_ms", "gen_proj_prefill_ms", "gen_ffn_step_ms",
          "gen_ffn_prefill_ms", "gen_glue_step_ms", "gen_glue_prefill_ms", "gen_head_step_ms",
          "idle_gen_loop_pct", "idle_gen_fetch_pct", "idle_gen_launch_pct", "idle_gen_hop_pct",
          "idle_gen_retire_pct", "idle_gen_host_pct", "idle_gen_no_work_pct",
          "idle_gen_unknown_pct", "kv_reserved_pct", "ssm_update_ms", "ssm_update_roofline_share",
          "ssm_update_step_share_pct", "ssm_scan_ms", "ssm_scan_roofline_share",
          "ssm_scan_kernel_pct", "ssm_state_carried_pct", "attn_decode_ms"}
NEW = {"blk_select_ms": ("ms", "device_trace", "models"),
       "blk_attend_ms": ("ms", "device_trace", "models"),
       "blk_attend_roofline_share": ("%", "device_trace", "kernels"),
       "blk_step_ms": ("ms", "device_trace", "models"),
       "blk_step_roofline_share": ("%", "device_trace", "kernels"),
       "blk_keys_kept_pct": ("%", "program_counter", "models"),
       "blk_rows_overread": ("x", "program_counter", "models")}


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "longsel-closed-16", 1)
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200      # the driver's limit on a line
    for said in ("16 closed callers", "dense_len", "64 blocks", "4/32", "more idle", "more host"):
        assert said in cell["why"], said
    assert entry["reduced"] == REDUCED == CFG["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and entry["source"] == CFG["source"]
    reported = {m["name"] for m in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert JOINED | set(NEW) <= reported
    # a walk over every key is not this cell's: blk_step_roofline_share stands in its place
    assert "attn_decode_roofline_share" not in reported
    assert not {n for n in reported if n.startswith(("mla_", "delta_", "eva_", "sel_", "hc_",
                                                     "moe_", "tokenize", "exec_roofline"))}
    end = {m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)}
    assert end == {"items_per_s", "setup_s"}
    # a metric that lists the cell moves a number the cell reports: the first token's wait and the
    # token gaps move `latency_p50_ms`, which this cell leaves out, so their lists leave it out
    assert not [m["name"] for m in BENCH["per_layer"]
                if CELL in m.get("workloads", ()) and m["moves"] not in end]
    assert not {"gen_first_token_ms_p50", "gen_token_gap_ms_p50", "gen_token_gap_ms_p95"} & reported
    for name, (unit, source, layer) in NEW.items():
        m = spec.find(BENCH["per_layer"], name, "metric")
        assert m["workloads"] == [CELL] and m["moves"] == "items_per_s" \
            and m["source"] == source and m["unit"] == unit and m["layer"] == layer
        assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{name}.py"))
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_the_configuration_is_cut_as_it_says_and_says_what_it_assumed():
    assert CFG["family"] == "hybrid_blk" and CFG["reduced"] == REDUCED
    pub = CFG["published"]
    assert pub["num_hidden_layers"] == 32 and len(pub["mixer_types"]) == 32
    assert [i for i, k in enumerate(pub["mixer_types"]) if k == "minicpm4"] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert CFG["mixer_types"] == pub["mixer_types"][:4] \
        == ["minicpm4"] + ["lightning-attn"] * 3                          # ONE WHOLE PERIOD, 1:3
    assert CFG["num_hidden_layers"] == 4
    # no width is cut
    assert (CFG["hidden_size"], CFG["intermediate_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["head_dim"], CFG["lightning_nh"], CFG["lightning_nkv"],
            CFG["lightning_head_dim"], CFG["vocab_size"], CFG["max_position_embeddings"]) \
        == (4096, 16384, 32, 2, 128, 32, 32, 128, 73448, 524288)
    assert (CFG["scale_emb"], CFG["scale_depth"], CFG["dim_model_base"]) == (12, 1.4, 256)
    assert CFG["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                                    "topk": 64, "init_blocks": 1, "window_size": 2048,
                                    "dense_len": 8192}
    assert set(CFG["sparse_config"]) <= set(CFG["assumed"]["sparse_config"])
    for said in ("STAGE 0 of 8", "one whole period", "0, 9, 16, 17, 22, 29, 30, 31",
                 "1,711,117,696", "idle share are larger", "PUBLISHED depth"):
        assert said in CFG["deployment"], said
    assumed = json.dumps(CFG["assumed"])
    for said in ("mup_denominator is read by nothing", "ONE gain of 128", "norm FIRST",
                 "exp(-2^(-8 (h + 1) / 32))", "arXiv:2401.04658", "arXiv:2509.24663",
                 "taken A QUERY", "coarser pooling", "lower index", "end_of_sequence",
                 "NO convolution rows", "head 16"):
        assert said in assumed, said
    served = CFG["assumed"]["served"]
    assert (served["max_prompt_tokens"], served["max_new_tokens"]) == (65536, 320)
    gen = CFG["serve"]["tables"]["genserve"]
    assert (gen["slots"], gen["kv_page_tokens"], gen["kv_pages"], gen["prefill_chunk"]) \
        == (MIX["clients"], 64, 16 * 1029 + 1, 4096)
    assert CFG["serve"]["model"]["dtype"] == "bfloat16"
    check = CFG["check"]
    sound, control = check["readings"]["sound_q25"], check["readings"]["control_q25"]
    assert len(sound) >= 6 and len(control) >= 1
    assert max(sound) < check["limit"] < min(control)
    assert max(check["readings"]["sound_rms"]) < check["rms_limit"] \
        < min(check["readings"]["control_rms"])
    lowp = spec.load_config(BENCH, f"{NAME}-lowp")
    assert lowp["check"]["reference_inputs"] == "3-bit-mantissa" and lowp["cell"] is False \
        and lowp["check"]["limit"] == check["limit"] and lowp["family"] == "hybrid_blk"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_every_key_of_the_published_config_is_in_the_file_as_published_or_reduced():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CFG["published"][key] == value, key
        else:
            assert CFG[key] == value, key
    # ... and every one the program reads reaches its config file, with the published depth
    arch = SZ["arch"]
    assert set(row["config"]) - {"rand_init"} <= set(fam.ARCH_KEYS)
    assert set(row["config"]) - {"rand_init"} <= set(arch)
    assert arch["scale_depth_layers"] == 32 and arch["num_hidden_layers"] == 4
    assert arch["weight_scales"]["head"] == 16.0


def test_the_sizes_are_the_issues_arithmetic():
    t, d = CFG["deployment_table"], 4096
    assert t["minicpm4_layer"] == 3 * d * d + 2 * d * 256 + 3 * d * 16384 + 2 * d + 2 * 128
    assert t["lightning_layer"] == 5 * d * d + 3 * d * 16384 + 2 * d + 3 * 128
    assert t["embedding_head_gain"] == 2 * 73448 * d + d
    assert t["held"] == t["minicpm4_layer"] + 3 * t["lightning_layer"] + t["embedding_head_gain"] \
        == 1_711_117_696
    assert t["model"] == 8 * t["minicpm4_layer"] + 24 * t["lightning_layer"] \
        + t["embedding_head_gain"] and round(t["model"] / 1e9, 3) == 9.477
    assert round(t["held"] * 2 / 2 ** 30, 2) == 3.19
    assert SZ["state_bytes_per_slot"] == 3 * 32 * 128 * 128 * 4 == 6_291_456
    assert 16 * SZ["state_bytes_per_slot"] == 96 * 2 ** 20
    assert (SZ["n_mamba"], SZ["n_attn"], SZ["layers"], SZ["head_dim"], SZ["kv_heads"]) \
        == (3, 1, 4, 128, 2)
    assert SZ["pages_per_slot"] == 1029 and SZ["max_ctx"] == 65856 and SZ["kv_pages"] == 16465
    row = 2 * SZ["kv_heads"] * SZ["head_dim"] * 2
    assert row == 1024 and round(row * 64 * 16465 / 2 ** 30, 3) == 1.005
    assert round(16465 * 4 * 256 * 2 / 2 ** 30, 3) == 0.031            # the pooled keys
    # a token's dense products: 2.22 GFLOP a period
    assert 2 * (t["held"] - t["embedding_head_gain"]) == pytest.approx(2.22e9, rel=2e-3)


def test_the_least_counts_are_counts_by_hand():
    d, wb = 4096, 2
    lin, attn, dense = 5 * d * d, 3 * d * d + 2 * d * 256, 3 * d * 16384
    always = 3 * lin + attn + 4 * dense
    lanes, ctx = 16.0, 16 * 20000.0
    ratio = (63 * 64 + 1) / 65856
    assert flops.attended_floor(SZ, ctx) == pytest.approx(ctx * ratio)
    s_ops, s_bytes = flops.decode_step(SZ, lanes, ctx)
    want = wb * (always + d * 73448) + wb * lanes * d + 3 * 2 * lanes * 2 ** 21 \
        + 1024 * lanes + 1024 * ctx * ratio
    assert s_bytes == pytest.approx(want, rel=1e-9)
    assert s_bytes / 819e9 > s_ops / 197e12          # a step is bound by memory: about 3.7 ms
    assert 0.0035 < s_bytes / 819e9 < 0.0040
    assert s_ops == pytest.approx(2 * lanes * (always + d * 73448) + 3 * lanes * 4 * 32 * 128 ** 2
                                  + 4 * ctx * ratio * 32 * 128, rel=1e-9)
    u_ops, u_bytes = flops.update(SZ, lanes)
    assert u_bytes == 3 * (wb * lin + 2 * lanes * 2 ** 21)
    assert u_ops == 3 * (2 * lanes * lin + 4 * lanes * 32 * 128 ** 2)
    sc_ops, sc_bytes = flops.scan(SZ, 4096, 2)
    assert sc_ops == 3 * 4.0 * 4096 * 32 * 128 * 128
    assert sc_bytes == 3 * (2 * 2 * 2 ** 21 + 4096 * 4 * 32 * 128 * 2)
    p_ops, p_bytes = flops.prefill_chunk(SZ, 4096.0, 4096 * 20000.0)
    assert 9.0e12 < p_ops < 9.6e12 and p_bytes > wb * always   # 2.22 GFLOP a token and a little
    a_ops, a_bytes = flops.blk_attend(SZ, 4096 * 4064.0, 4096.0)
    assert a_ops == 4 * 4096 * 4064 * 32 * 128 and a_bytes == 1024 * 4064
    b_ops, b_bytes = flops.blk_step(SZ, 16 * 2 * 313.0, 16 * 4064.0)
    # 313 blocks a group: 1,252 pooled rows of 256 B a group; 4,064 keys of 1 KiB
    assert b_bytes == 16 * 2 * 313 * 4 * 128 * 2 + 16 * 4064 * 1024
    assert b_ops == 2 * 16 * 2 * 313 * 4 * 16 * 128 + 4 * 16 * 4064 * 32 * 128
    assert flops.ops_and_bytes(SZ, 16, 20000)[1] == pytest.approx(s_bytes)


def test_the_mix_is_the_issues_traffic():
    assert (MIX["traffic"], MIX["verb"], MIX["loop"], MIX["clients"]) == \
        ("token_prompts", "generate", "closed", 16)
    (cls,) = MIX["classes"]
    assert cls["share"] == 1.0 and "temperature" not in cls
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 20480, "sigma": 0.5,
                                    "min": 10240, "max": 65536}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 160, "sigma": 0.5,
                                     "min": 48, "max": 320}
    assert (MIX["warmup_s"], MIX["drain_s"], MIX["trace_ms"], MIX["check_logprobs"]) \
        == (5.0, 20.0, 3000, 8)
    check = [(c["prompt_tokens"], c["max_new_tokens"]) for c in MIX["check"]]
    assert check[0] == (96, 24) and check[1][0] >= 8192 + 512 and check[1][1] == 24
    assert all(p + n <= SZ["max_ctx"] and p <= SZ["max_prompt"] for p, n in check)
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 73448]                                     # the whole vocabulary
    reqs = tokens.make_requests(MIX, 7, rows, 256)
    lens = np.asarray([r.tokens[0] for r in reqs])
    news = np.asarray([r.max_new for r in reqs])
    assert lens.min() >= 10240 and lens.max() <= 65536 and news.min() >= 48 and news.max() <= 320
    assert lens.min() > CFG["sparse_config"]["dense_len"]        # every prompt ends past dense_len
    assert 18000 < np.median(lens) < 23000 and 140 < np.median(news) < 180
    past = np.maximum(lens - 8192, 0).sum() / lens.sum()
    assert 0.55 < past < 0.75                                     # about two rows in three
    assert SZ["pages_per_slot"] * 16 < SZ["kv_pages"]


def test_the_new_readers_return_none_and_never_raise_on_a_run_that_has_nothing():
    for name in NEW:
        read = spec.load_module("layer_metrics", name).read
        assert read({}) is None
        assert read({"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
                     "peaks": None, "flops": flops, "sizes": SZ}) is None


TOY = spec.load_config(BENCH, "rehearsal-hybrid_blk-tiny")


@pytest.mark.parametrize("low", [False, True])
def test_the_pass_in_two_calls_is_the_pass_in_one(low):
    """The check's pass, the prompts first and the served tokens continued from
    what they left (keys, values, states), against ONE call over the whole
    sequences, at the rehearsal's toy size on sequences that cross its dense_len
    of 64 in the prompt and in the continuation."""
    import jax.numpy as jnp

    toy = fam.sizes_from_config(TOY)
    assert toy["arch"]["scale_depth_layers"] == 32 and toy["sparse"]["dense_len"] == 64
    m = fam.Model(toy["arch"], 5, "float32")
    assert m.r == pytest.approx(1.4 / 32 ** 0.5) and m.s == 4.0
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, n) for n in (2, 60, 90)]
    rest = [rng.integers(0, 96, n) for n in (11, 11, 0)]
    whole = fam.hidden_states(m, [np.concatenate(pr) for pr in zip(prompts, rest)], low)
    layers, last, carry = fam.prompt_pass(m, prompts, low)
    hs, _ = fam.forward(m, layers, rest, carry, low)
    for w, p, h0, h in zip(whole, prompts, last, hs):
        got = jnp.concatenate([h0, h], axis=0)
        np.testing.assert_allclose(got, w[len(p) - 1:], atol=2e-5 if not low else 5e-2)
    if low:   # the control's rounding is seen
        sound = fam.hidden_states(m, [np.concatenate(pr) for pr in zip(prompts, rest)], False)
        assert float(np.abs(np.asarray(sound[2]) - np.asarray(whole[2])).max()) > 1e-2


def test_the_check_rejects_altered_tokens_and_a_short_answer():
    """`compare` on the reference's own answers reads nothing; on answers whose
    log-probabilities are another position's, over the limit; on an answer one
    token short, infinity."""
    toy = fam.sizes_from_config(TOY)
    rng = np.random.default_rng(4)
    inputs = [{"ids": rng.integers(0, 96, n), "max_new": 6} for n in (9, 80)]
    m = fam.Model(toy["arch"], 5, "float32")

    def answers(shift: int):
        out = []
        for inp in inputs:
            ids = list(inp["ids"])
            toks, top_ids, top_vals = [], [], []
            for _ in range(inp["max_new"]):
                lp = fam.log_probs(m, [np.asarray(ids)], [len(ids) - 1])[0][0]
                top = np.argsort(-lp, kind="stable")[:fam.LOGPROBS]
                toks.append(int(top[0]))
                top_ids.append(top.tolist())
                top_vals.append(np.roll(lp[top], shift).tolist())
                ids.append(int(top[0]))
            out.append({"tokens": toks, "n_tokens": len(toks),
                        "logprobs": {"ids": top_ids, "values": top_vals}})
        return out

    ref = fam.reference_answers({"seed": 5, "dtype": "float32"}, inputs, toy)
    stat, line = fam.compare(answers(0), ref, TOY)
    assert stat < TOY["check"]["limit"] and "picks_moved=0/" in line
    ref = fam.reference_answers({"seed": 5, "dtype": "float32"}, inputs, toy)
    stat, _ = fam.compare(answers(3), ref, TOY)
    assert stat > 100 * TOY["check"]["limit"]
    short = answers(0)
    short[1]["tokens"].pop()
    ref = fam.reference_answers({"seed": 5, "dtype": "float32"}, inputs, toy)
    assert fam.compare(short, ref, TOY)[0] == float("inf")
