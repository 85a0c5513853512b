"""The cell `granite-4.0-h-micro.shortchat-closed` and the files it brought: the
whole configuration against the catalog and against the issue's arithmetic,
the program's config file, the mix to the letter, the control, the least
counts of operations and bytes, and the three new readers on a run that has
nothing. What is asserted of `BENCHMARK.json` is what the harness needs (the
cell is listed, the metrics it should report name it), not where in a list an
entry stands: a later cell appends to the same lists."""

import json
import os

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "granite-4.0-h-micro.shortchat-closed"
NAME = "granite-4.0-h-micro"
CFG = spec.load_config(BENCH, NAME)
fam = spec.load_module("reference", "hybrid_ffn")
flops = spec.load_module("flops", "hybrid_ffn")
tokens = spec.load_module("traffic", "token_prompts")
SZ = fam.sizes_from_config(CFG)
MIX = spec.load_mix("shortchat-closed")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

JOINED = {"gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
          "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
          "kv_reserved_pct", "idle_gen_loop_pct", "idle_gen_fetch_pct", "idle_gen_launch_pct",
          "idle_gen_hop_pct", "idle_gen_retire_pct", "idle_gen_host_pct", "idle_gen_no_work_pct",
          "idle_gen_unknown_pct", "gen_loop_serial_ms_per_iter", "ssm_update_ms",
          "ssm_update_roofline_share", "ssm_scan_ms", "ssm_scan_roofline_share",
          "ssm_state_carried_pct"}
NEW = {"ssm_update_step_share_pct", "attn_decode_ms", "attn_decode_roofline_share"}


def test_the_cell_is_listed_and_the_metrics_it_should_report_name_it():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "shortchat-closed", 1)
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200      # the driver's limit on a line
    assert entry["reduced"] == [] and entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == CFG["source"]
    reported = {m["name"] for m in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert JOINED | NEW <= reported
    assert not {n for n in reported if n.startswith(("moe_", "mla_", "exec_roofline", "tokenize"))}
    end = {m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)}
    assert {"items_per_s", "setup_s"} <= end
    for name in NEW:
        m = spec.find(BENCH["per_layer"], name, "metric")
        assert CELL in m["workloads"] and m["moves"] == "items_per_s" \
            and m["source"] == "device_trace"
        assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{name}.py"))
    assert "nemotron-3-super-q4-l11.chat-closed-256" in spec.find(
        BENCH["per_layer"], "ssm_update_step_share_pct", "metric")["workloads"]
    # a metric that moves the median latency names the cell only where the cell reports it
    for m in BENCH["per_layer"]:
        if m["moves"] == "latency_p50_ms" and CELL in m.get("workloads", []):
            assert "latency_p50_ms" in end, m["name"]


def test_the_configuration_is_whole_and_says_what_it_assumed():
    assert CFG["family"] == "hybrid_ffn" and CFG["reduced"] == []
    assert all(CFG[k] == v for k, v in CFG["published"].items())     # published = what is served
    for said in ("one v5e chip holds the whole model", "40 layers", "no exchange is left out"):
        assert said in CFG["deployment"], said
    assumed = json.dumps(CFG["assumed"])
    for said in ("no clamp", "mamba_chunk_size", "float32", "zero-padded", "end_of_sequence",
                 "embedding_multiplier", "qk 5.66", "ssm_bc 2.0", "residual_multiplier"):
        assert said in assumed, said
    assert CFG["assumed"]["served"]["max_prompt_tokens"] == 1536 \
        and CFG["assumed"]["served"]["max_new_tokens"] == 512
    gen = CFG["serve"]["tables"]["genserve"]
    assert (gen["slots"], gen["kv_page_tokens"], gen["kv_pages"], gen["prefill_chunk"],
            gen["admit_per_step"]) == (MIX["clients"], 128, 1280, 1024, 4)
    assert CFG["serve"]["model"]["dtype"] == "bfloat16"
    check = CFG["check"]
    assert 0 < check["limit"] and 0 < check["rms_limit"]
    lowp = spec.load_config(BENCH, f"{NAME}-lowp")
    assert lowp["check"]["reference_inputs"] == "3-bit-mantissa" and lowp["cell"] is False \
        and lowp["check"]["limit"] == check["limit"] and lowp["family"] == "hybrid_ffn"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_every_key_of_the_published_config_is_in_the_file_as_published():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == NAME)
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CFG[key] == value, key
    # ... and every one of them reaches the program's config file
    assert set(row["config"]) <= set(fam.ARCH_KEYS) and set(row["config"]) <= set(SZ["arch"])


def test_the_sizes_are_the_issues_arithmetic():
    d, f = 2048, 8192
    mamba = d * 8512 + 4352 * 4 + 4352 + 4096 * d + 4096 + 3 * 64
    attn = 2 * d * 2048 + 2 * d * 512
    total = 36 * mamba + 4 * attn + 40 * 3 * d * f + 100352 * d + 81 * d
    assert abs(total - 3.191e9) < 1e6
    assert SZ["state_bytes_per_slot"] == 76_437_504 == 36 * (2_097_152 + 26_112)
    assert (SZ["n_mamba"], SZ["n_attn"], SZ["layers"], SZ["head_dim"]) == (36, 4, 40, 64)
    assert SZ["pages_per_slot"] == 16 and SZ["max_ctx"] == 2048
    # a slot's state costs what 9,216 tokens of KV cost
    row = 4 * 2 * SZ["kv_heads"] * SZ["head_dim"] * 2
    assert row == 8192 and SZ["state_bytes_per_slot"] // row == 9330   # 9,216 without the conv rows


def test_the_least_counts_are_the_issues():
    ops, nbytes = flops.update(SZ, 80)
    assert abs(nbytes - 14.1e9) < 0.1e9          # 80 x 76.4 MB x 2 + W_in and W_out of 36 layers
    ops, nbytes = flops.decode_step(SZ, 80, 80 * 300.0)
    # weights 6.38 GB once (the embedding as the head) + the states twice + live K and V
    assert abs(nbytes - (6.38e9 + 2 * 80 * 76.44e6 + 80 * 300 * 8192)) < 0.05e9
    assert nbytes / 819e9 > ops / 197e12         # a step is bound by memory
    a_ops, a_bytes = flops.attend_decode(SZ, 80, 80 * 300.0)
    assert abs(a_bytes - (4 * 2 * 10.49e6 + 80 * 300 * 8192 + 80 * 8192)) < 1e6
    assert a_bytes < nbytes / 20 and a_ops < ops
    p_ops, p_bytes = flops.prefill_chunk(SZ, 900, 900 * 120.0)
    assert p_ops > 900 * 2 * 3.0e9 and p_bytes > 6.38e9     # 2 x parameters a token, less the embedding
    s_ops, s_bytes = flops.scan(SZ, 900, 5)
    assert s_ops == 36 * 4.0 * 900 * 64 * 64 * 128
    assert flops.ops_and_bytes(SZ, 80, 300) == flops.decode_step(SZ, 80, 80 * 300.0)


def test_the_mix_is_the_issues_traffic():
    assert (MIX["traffic"], MIX["verb"], MIX["loop"], MIX["clients"]) == \
        ("token_prompts", "generate", "closed", 80)
    (cls,) = MIX["classes"]
    assert cls["share"] == 1.0
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 128, "sigma": 0.8,
                                    "min": 16, "max": 1024}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.5,
                                     "min": 32, "max": 512}
    assert (MIX["pool_requests"], MIX["warmup_s"], MIX["drain_s"], MIX["trace_ms"],
            MIX["check_logprobs"]) == (8192, 5.0, 20.0, 3000, 8)
    # the check: shorter than a scan chunk; several tiles with a padded tail; across a launch's edge
    check = [(c["prompt_tokens"], c["max_new_tokens"]) for c in MIX["check"]]
    assert check[0][0] < 128 and check[1][0] % 128 and check[2][0] > 1024
    assert sum(n for _p, n in check) >= 96 and all(p + n <= SZ["max_ctx"] and p <= SZ["max_prompt"] for p, n in check)
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 100352]
    reqs = tokens.make_requests(MIX, 7, rows, 256)
    lens = np.asarray([r.tokens[0] for r in reqs])
    news = np.asarray([r.max_new for r in reqs])
    assert lens.min() >= 16 and lens.max() <= 1024 and news.min() >= 32 and news.max() <= 512
    assert 100 < np.median(lens) < 160 and 220 < np.median(news) < 300
    assert news.mean() > lens.mean()              # decode-heavy: answers longer than prompts
    # 1,280 pages hold every caller's longest request (12 pages) with room
    assert 80 * 12 < 1280


def test_the_new_readers_return_none_and_never_raise_on_a_run_that_has_nothing():
    for name in NEW:
        read = spec.load_module("layer_metrics", name).read
        assert read({}) is None
        assert read({"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
                     "peaks": None, "flops": flops, "sizes": SZ}) is None


def test_the_pass_in_two_calls_is_the_pass_in_one():
    """The check's pass, the prompts first and the served tokens continued
    from what they left, against ONE call over the whole sequences, at the
    rehearsal's toy size: the same recurrence over the same tokens (float32
    sums over other row counts: 1e-5)."""
    import jax.numpy as jnp

    toy = fam.sizes_from_config(spec.load_config(BENCH, "rehearsal-hybrid_ffn-tiny"))
    for low in (False, True):
        m = fam.Model(toy["arch"], 5, "float32")
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 96, n) for n in (2, 9, 17)]
        rest = [rng.integers(0, 96, n) for n in (11, 4, 0)]
        whole = fam.hidden_states(m, [np.concatenate(pr) for pr in zip(prompts, rest)], low)
        layers, last, carry = fam.prompt_pass(m, prompts, low)
        hs, _ = fam.forward(m, layers, rest, carry, low)
        for w, p, h0, h in zip(whole, prompts, last, hs):
            got = jnp.concatenate([h0, h], axis=0)
            np.testing.assert_allclose(got, w[len(p) - 1:], atol=1e-5 if not low else 2e-2)
