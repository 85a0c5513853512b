"""The cell `lfm2-24b-a2b-l10.assist-closed-512` and the files it brought: the
cut configuration against the catalog and against the issue's arithmetic, the
program's config file as published, the mix to the letter, the control, the
least counts of operations and bytes against a count by hand, the three new
readers on a run that has nothing and on a window's counters, and the rehearsal
with a served answer altered. What is asserted of `BENCHMARK.json` is what the
harness needs (the cell is listed, the metrics it should report name it), not
where in a list an entry stands."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "lfm2-24b-a2b-l10.assist-closed-512"
NAME = "lfm2-24b-a2b-l10"
CFG = spec.load_config(BENCH, NAME)
fam = spec.load_module("reference", "hybrid_conv")
flops = spec.load_module("flops", "hybrid_conv")
tokens = spec.load_module("traffic", "token_prompts")
SZ = fam.sizes_from_config(CFG)
MIX = spec.load_mix("assist-closed-512")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REPO = spec.REPO
REDUCED = ["num_hidden_layers", "layer_types"]
KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv",
         "conv", "conv"]

JOINED = {"gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
          "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
          "moe_experts_hit_pct", "moe_experts_prefill_ms", "kv_reserved_pct", "idle_gen_loop_pct",
          "idle_gen_fetch_pct", "idle_gen_launch_pct", "idle_gen_hop_pct", "idle_gen_retire_pct",
          "idle_gen_host_pct", "idle_gen_no_work_pct", "idle_gen_unknown_pct",
          "gen_loop_serial_ms_per_iter", "gen_step_ahead_pct", "gen_loop_cpu_share_pct",
          "gen_account_trees_pct", "ssm_update_ms", "ssm_update_roofline_share", "ssm_scan_ms",
          "ssm_scan_roofline_share", "ssm_state_carried_pct", "ssm_update_step_share_pct",
          "attn_decode_ms", "attn_decode_roofline_share"}
NEW = {"moe_experts_step_ms": ("device_trace", "models"),
       "moe_experts_step_roofline_share": ("device_trace", "kernels"),
       "moe_tokens_per_expert_step": ("program_counter", "models")}


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "assist-closed-512", 1)
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200      # the driver's limit on a line
    for said in ("512", "4 KiB a token", "32 tokens each"):
        assert said in cell["why"], said
    assert entry["reduced"] == REDUCED == CFG["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and entry["source"] == CFG["source"]
    reported = {m["name"] for m in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert JOINED | set(NEW) <= reported
    # every expert is held: the dispatch has no second branch for the compact reader to count
    assert not {n for n in reported if n.startswith((
        "mla_", "hc_", "attn_ring", "attn_full", "exec_roofline", "tokenize", "delta_", "eva_",
        "moe_dispatch_compact"))}
    assert {"items_per_s", "setup_s"} <= {m["name"] for m in
                                          spec.cell_metrics(BENCH, "end_to_end", CELL)}
    for name, (source, layer) in NEW.items():
        m = spec.find(BENCH["per_layer"], name, "metric")
        assert m["workloads"] == [CELL] and m["moves"] == "items_per_s"
        assert (m["source"], m["layer"]) == (source, layer)
        assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{name}.py"))
    end = {m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)}
    for m in BENCH["per_layer"]:
        if m["moves"] == "latency_p50_ms" and CELL in m.get("workloads", []):
            assert "latency_p50_ms" in end, m["name"]


def test_the_cut_configuration_keeps_every_width_and_says_what_it_cut():
    assert CFG["family"] == "hybrid_conv"
    assert (CFG["num_hidden_layers"], CFG["layer_types"]) == (10, KINDS)
    assert CFG["published"] == {"num_hidden_layers": 40,
                                "layer_types": (["conv", "conv", "full_attention", "conv"] * 10)}
    for said in ("4 PIPELINE STAGES", "STAGE 0 of 4", "all 64 experts", "layers 0-9",
                 "5,267.1 M parameters", "9.81 GiB", "65,536 B", "4,096 B", "micro-batches"):
        assert said in CFG["deployment"], said
    assumed = json.dumps(CFG["assumed"])
    for said in ("NO FILE ON THIS MACHINE", "tie_word_embeddings true", "ORDER of in_proj's thirds",
                 "no activation between the convolution and the gate", "tap 2 on the current row",
                 "BEFORE the rotary", "ONE gain of 64", "(j, j + 32)", "1 / sqrt(64)",
                 "w1 gate, w3 up, w2 down", "1e-6", "float32", "end_of_sequence",
                 "commutes with the rotary", "no float32 leaf"):
        assert said in assumed, said
    served = CFG["assumed"]["served"]
    assert (served["max_prompt_tokens"], served["max_new_tokens"]) == (2304, 768)
    gen = CFG["serve"]["tables"]["genserve"]
    assert (gen["slots"], gen["kv_page_tokens"], gen["kv_pages"], gen["prefill_chunk"],
            gen["admit_per_step"]) == (MIX["clients"], 128, 4608, 2048, 8)
    assert CFG["serve"]["model"]["dtype"] == "bfloat16"
    check = CFG["check"]
    assert 0 < check["limit"] and 0 < check["rms_limit"]
    sound, control = check["readings"]["sound_q25"], check["readings"]["control_q25"]
    assert len(sound) >= 6 and len(control) >= 2 and max(sound) < check["limit"] < min(control)
    assert max(check["readings"]["sound_rms"]) < check["rms_limit"] \
        < min(check["readings"]["control_rms"])


def test_the_control_differs_from_the_cell_by_the_check_alone():
    lowp = spec.load_config(BENCH, f"{NAME}-lowp")
    assert lowp["check"]["reference_inputs"] == "3-bit-mantissa" and lowp["cell"] is False
    assert fam.sizes_from_config(lowp)["arch"] == SZ["arch"]
    differs = {k for k in set(lowp) | set(CFG) if lowp.get(k) != CFG.get(k)}
    assert differs == {"name", "cell", "why", "check"}
    assert {k: v for k, v in lowp["check"].items() if k != "reference_inputs"} == CFG["check"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_no_other_key_of_the_published_config_differs():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CFG[key] == value or key in REDUCED, key
        assert CFG["published"].get(key, CFG[key]) == value, key
    # ... and every one of them reaches the program's config file
    assert set(row["config"]) <= set(fam.ARCH_KEYS) and set(row["config"]) <= set(SZ["arch"])


def test_the_programs_config_file_is_the_published_one_cut_in_depth_alone():
    a = SZ["arch"]
    assert (a["num_experts"], a["vocab_size"], a["num_hidden_layers"], a["layer_types"]) == \
        (64, 65536, 10, KINDS)
    assert "share" not in a and a["tie_word_embeddings"] is True
    # the family's defaults but the embedding, which is also the head: 1 / sqrt(2048)
    assert a["weight_scales"] == CFG["assumed"]["weights"]["scales"] == {
        **fam.DEFAULT_SCALES, "embed": 0.0221}
    assert (SZ["n_mamba"], SZ["n_attn"], SZ["n_dense"], SZ["n_expert"], SZ["layers"]) == \
        (8, 2, 2, 8, 10)
    assert (SZ["experts_held"], SZ["num_experts"], SZ["top_k"], SZ["vocab"]) == (64, 64, 4, 65536)


def test_the_arithmetic_of_the_cut_to_the_megabyte():
    d = 2048
    conv = d * 3 * d + d * d + 3 * d
    attn = 2 * d * d + 2 * d * 512 + 2 * 64
    dense = 3 * d * 11776
    routed = d * 64 + 64 + 64 * 3 * d * 1536
    total = 8 * conv + 2 * attn + 2 * dense + 8 * routed + 65536 * d + 21 * d
    assert (conv, attn, dense, routed) == (16_783_360, 10_485_888, 72_351_744, 604_110_912)
    assert abs(total - 5267.1e6) < 0.1e6 and abs(total * 2 / 2 ** 30 - 9.81) < 0.005
    assert SZ["state_bytes_per_slot"] == 65_536 == 8 * 2 * 2048 * 2
    assert SZ["pages_per_slot"] == 24 and SZ["max_ctx"] == 3072 and SZ["prefill_chunk"] == 2048
    row = 2 * SZ["kv_heads"] * SZ["head_dim"] * 2 * SZ["n_attn"]
    assert row == 4096 and row * 128 == 524_288 and 4608 * 524_288 == int(2.25 * 2 ** 30)
    # a slot's state costs what 16 tokens of K and V cost: memory does not bound the lanes
    assert SZ["state_bytes_per_slot"] // row == 16


def test_the_least_counts_against_a_count_by_hand():
    d, lanes, ctx = 2048, 500.0, 500 * 600.0
    conv = d * 3 * d + d * d + 3 * d
    u_ops, u_bytes = flops.update(SZ, lanes)
    assert u_ops == 8 * (2 * lanes * (conv - 3 * d) + lanes * 8 * d)      # 2 x 16.78 M a lane a layer
    assert u_bytes == 8 * (2 * conv + 2 * lanes * 2 * d * 2)              # two rows in, two out
    # 500 rows through a dense matrix are past the chip's ridge (240 operations a byte): compute
    assert u_ops / 197e12 > u_bytes / 819e9 and flops.update(SZ, 100.0)[0] / 197e12 < 0.41e-3
    s_ops, s_bytes = flops.scan(SZ, 1000, 1.5)
    assert s_ops == 8 * 1000 * (2 * 3 + 2) * d
    assert s_bytes == 8 * (2 * 1.5 * 8192 + 1000 * 3 * d * 2)
    picks, hit = lanes * 4 * 8, 64.0 * 8
    e_ops, e_bytes = flops.experts_step(SZ, lanes, picks, hit)
    assert e_ops == picks * 6 * d * 1536
    assert e_bytes == 2 * (hit * 3 * d * 1536 + picks * 2 * d)
    assert abs(e_bytes - 9.795e9) < 0.001e9 and e_bytes / 819e9 > 2 * e_ops / 197e12   # 9.66 GB of experts
    a_ops, a_bytes = flops.attend_decode(SZ, lanes, ctx)
    attn = 2 * d * d + 2 * d * 512
    assert a_bytes == 2 * (2 * attn + 2048 * lanes) + ctx * 4096
    assert a_ops == 2 * (2 * lanes * attn + 4 * ctx * 32 * 64)
    st_ops, st_bytes = flops.decode_step(SZ, lanes, ctx, picks, hit)
    # the 10.53 GB of matrices once (the embedding as the head; of its rows the lanes' alone
    # gathered), the rows in and out, the live K and V, the new rows
    weights = 2 * (8 * conv + 2 * attn + 2 * 3 * d * 11776 + 8 * d * 64 + hit * 3 * d * 1536
                   + 65536 * d)
    assert st_bytes == weights + 2 * lanes * d + 8 * 2 * lanes * 8192 + 2 * 2048 * lanes \
        + ctx * 4096
    assert abs(st_bytes - 11.8e9) < 0.1e9 and st_bytes / 819e9 > st_ops / 197e12
    p_ops, p_bytes = flops.prefill_chunk(SZ, 1000, 1000 * 500.0, 1000 * 4 * 8.0, hit)
    assert abs(p_ops - 1000 * 2 * 602e6) < 0.03e12 and p_bytes > 10.5e9
    assert p_bytes / 819e9 > p_ops / 197e12                  # a launch of 1,000 rows too
    assert flops.ops_and_bytes(SZ, 512, 600) == flops.decode_step(
        SZ, 512, 512 * 600.0, 512 * 4 * 8, 64 * 8)


def test_the_mix_is_the_issues_to_the_letter_and_every_seed_sends_the_same_lengths():
    assert (MIX["traffic"], MIX["verb"], MIX["loop"], MIX["clients"]) == \
        ("token_prompts", "generate", "closed", 512)
    (cls,) = MIX["classes"]
    assert cls["share"] == 1.0
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.8,
                                    "min": 32, "max": 2048}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.5,
                                     "min": 96, "max": 768}
    assert (MIX["pool_requests"], MIX["warmup_s"], MIX["drain_s"], MIX["trace_ms"],
            MIX["check_logprobs"]) == (8192, 5.0, 30.0, 3000, 8)
    # the check: inside one page and one tile; a decode across a page's edge; across a launch's edge
    check = [(c["prompt_tokens"], c["max_new_tokens"]) for c in MIX["check"]]
    assert check == [(40, 24), (100, 160), (2100, 24)] and sum(p + n for p, n in check) == 2448
    assert all(p + n <= SZ["max_ctx"] and p <= SZ["max_prompt"] for p, n in check)
    assert check[2][0] > SZ["prefill_chunk"]
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 65536]
    reqs = tokens.make_requests(MIX, 7, rows, 1024)
    lens = np.asarray([r.tokens[0] for r in reqs])
    news = np.asarray([r.max_new for r in reqs])
    assert lens.min() >= 32 and lens.max() <= 2048 and news.min() >= 96 and news.max() <= 768
    assert 230 < np.median(lens) < 280 and 360 < np.median(news) < 410
    assert 320 < lens.mean() < 380 and 395 < news.mean() < 440
    again = tokens.make_requests(MIX, 8, rows, 1024)
    assert sorted(r.tokens[0] for r in again) == sorted(lens.tolist())
    assert sorted(r.max_new for r in again) == sorted(news.tolist())
    # 512 callers' requests at their mean (7 pages) reserve about three quarters of the 4,608 pages
    assert 0.6 * 4608 < 512 * np.mean(-(-(lens + news) // 128)) < 0.85 * 4608


def test_the_readers_return_nothing_and_do_not_raise_where_the_program_has_no_counter():
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
           "peaks": None, "flops": flops, "sizes": SZ}
    for name in NEW:
        read = spec.load_module("layer_metrics", name).read
        assert read({}) is None and read(dict(run)) is None
    run["metrics_delta"] = {
        'moe_tokens_routed_total{model="model",phase="decode",held="yes"}': 1_600_000.0,
        'moe_tokens_routed_total{model="model",phase="prefill",held="yes"}': 999.0,
        'moe_experts_hit_total{model="model",phase="decode"}': 51_200.0}
    assert spec.load_module("layer_metrics", "moe_tokens_per_expert_step").read(dict(run)) == 31.25
    # no trace: the two device readers still find nothing
    for name in ("moe_experts_step_ms", "moe_experts_step_roofline_share"):
        assert spec.load_module("layer_metrics", name).read(dict(run)) is None


def rehearse(*extra, env=None):
    """The rehearsal's command (benchmark/rehearsals/hybrid_conv-closed.json), untraced."""
    want = spec.load_json("rehearsals", "hybrid_conv-closed.json")
    args = [a for a in want["args"]]
    args[args.index("--trace") + 1] = "0"
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "rehearsal-hybrid_conv",
                        "--rehearse", "--seconds", "2", *args, *extra], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    return p.returncode, json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_rehearsal_runs_correct_and_a_served_answer_altered_comes_out_not_correct(tmp_path):
    """The whole command on the CPU at the toy size: correct, the counters among
    those that moved; then the rest of a run with the timed path broken
    underneath: the server's steps forget the slot's stored rows (a
    sitecustomize that acts in the child only, the harness as it is): NOT
    correct, by the statistic's own limit."""
    rc, line, out = rehearse()
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    moved = next(ln for ln in out.splitlines() if "counters that moved in the window" in ln)
    for counter in spec.load_json("rehearsals", "hybrid_conv-closed.json")["counters"]:
        assert f"{counter}=" in moved, counter
    (tmp_path / "sitecustomize.py").write_text(
        "import os\n"
        "if os.environ.get('FORGET_THE_ROWS'):\n"
        "    import jax.numpy as jnp\n"
        "    from tpuserve.models import mixers\n"
        "    step = mixers.ConvMixer._conv_step\n"
        "    mixers.ConvMixer._conv_step = lambda self, lp, u, live, conv: step(\n"
        "        self, lp, u, live, jnp.zeros_like(conv))\n")
    rc, line, out = rehearse(env={"FORGET_THE_ROWS": "1", "PYTHONPATH": str(tmp_path)
                                  + os.pathsep + os.environ.get("PYTHONPATH", "")})
    assert rc == 1 and line["correct"] is False
    assert any("NOT CORRECT" in ln and "logprob_q25=" in ln for ln in out.splitlines())
