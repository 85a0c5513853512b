"""The cell `solar-open2-250b-e8-l4.docchat-closed-192` and the files it brought:
the cut configuration against the catalog and against the issue's arithmetic,
the program's config file with the published counts and the share, the mix to
the letter, the control, the least counts of operations and bytes against a
count by hand, the three new readers on a run that has nothing and on a
window's counters, and the rehearsal with a served answer altered. What is
asserted of `BENCHMARK.json` is what the harness needs (the cell is listed, the
metrics it should report name it), not where in a list an entry stands."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "solar-open2-250b-e8-l4.docchat-closed-192"
NAME = "solar-open2-250b-e8-l4"
CFG = spec.load_config(BENCH, NAME)
fam = spec.load_module("reference", "hybrid_delta")
flops = spec.load_module("flops", "hybrid_delta")
tokens = spec.load_module("traffic", "token_prompts")
SZ = fam.sizes_from_config(CFG)
MIX = spec.load_mix("docchat-closed-192")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REPO = spec.REPO
REDUCED = ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]

JOINED = {"gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
          "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
          "moe_experts_hit_pct", "moe_dispatch_compact_pct", "moe_experts_prefill_ms",
          "kv_reserved_pct", "idle_gen_loop_pct", "idle_gen_fetch_pct", "idle_gen_launch_pct",
          "idle_gen_hop_pct", "idle_gen_retire_pct", "idle_gen_host_pct", "idle_gen_no_work_pct",
          "idle_gen_unknown_pct", "gen_loop_serial_ms_per_iter", "gen_step_ahead_pct",
          "gen_loop_cpu_share_pct", "gen_account_trees_pct", "ssm_update_ms",
          "ssm_update_roofline_share", "ssm_scan_ms", "ssm_scan_roofline_share",
          "ssm_state_carried_pct", "ssm_update_step_share_pct", "attn_decode_ms",
          "attn_decode_roofline_share"}
NEW = {"delta_update_ms": ("device_trace", "models"),
       "delta_update_roofline_share": ("device_trace", "kernels"),
       "delta_update_kernel_pct": ("program_counter", "models")}


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "docchat-closed-192", 1)
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200      # the driver's limit on a line
    for said in ("192", "4.8 tokens a held expert", "8x their share"):
        assert said in cell["why"], said
    assert entry["reduced"] == REDUCED == CFG["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and entry["source"] == CFG["source"]
    reported = {m["name"] for m in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert JOINED | set(NEW) <= reported
    assert not {n for n in reported if n.startswith(("mla_", "hc_", "attn_ring", "attn_full",
                                                     "exec_roofline", "tokenize"))}
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
    assert CFG["family"] == "hybrid_delta"
    assert (CFG["num_hidden_layers"], CFG["gqa_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (4, [0], 40, 24576)
    assert CFG["published"] == {"num_hidden_layers": 48, "gqa_layers": list(range(0, 48, 4)),
                                "n_routed_experts": 320, "vocab_size": 196608}
    assert CFG["deployment_share"] == {"index": 0, "of": 8, "experts_first": 0, "vocab_first": 0}
    for said in ("8 v5e chips SHARE EACH LAYER", "40 each", "WHOLE on every chip",
                 "rows 0-24,575", "Layers 0-3 of 48", "13,025,280 B", "8 times their share"):
        assert said in CFG["deployment"], said
    assumed = json.dumps(CFG["assumed"])
    for said in ("scoring_func is not in the config", "ELEMENTWISE", "no query/key norm",
                 "A_log a head and dt_bias a channel", "1e-6 under the root", "NO bias",
                 "b_g the one bias", "ONE gain of 128", "float32", "prefill tile",
                 "intermediate_size 10,240 is read by nothing", "end_of_sequence",
                 "BEFORE the correction", "linear_attn_config.head_dim"):
        assert said in assumed, said
    served = CFG["assumed"]["served"]
    assert (served["max_prompt_tokens"], served["max_new_tokens"]) == (8192, 512)
    gen = CFG["serve"]["tables"]["genserve"]
    assert (gen["slots"], gen["kv_page_tokens"], gen["kv_pages"], gen["prefill_chunk"],
            gen["admit_per_step"]) == (MIX["clients"], 128, 4096, 1024, 4)
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
        row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CFG[key] == value or key in REDUCED, key
        assert CFG["published"].get(key, CFG[key]) == value, key
    # ... and every one of them reaches the program's config file
    assert set(row["config"]) <= set(fam.ARCH_KEYS) and set(row["config"]) <= set(SZ["arch"])


def test_the_programs_config_file_has_the_published_counts_and_the_share():
    a = SZ["arch"]
    assert (a["n_routed_experts"], a["vocab_size"], a["num_hidden_layers"], a["gqa_layers"]) == \
        (320, 196608, 4, [0])
    assert a["share"] == {"experts_held": [0, 40], "vocab_rows": [0, 24576]}
    assert a["weight_scales"] == CFG["assumed"]["weights"]["scales"] == {
        **fam.DEFAULT_SCALES, **a["weight_scales"]}
    assert (SZ["n_mamba"], SZ["n_attn"], SZ["layers"], SZ["experts_held"], SZ["vocab"]) == \
        (3, 1, 4, 40, 24576)


def test_the_arithmetic_of_the_cut_to_the_megabyte():
    d, inner = 4096, 8192
    kda = 4 * d * inner + 2 * (d * 128 + 128 * inner) + d * 64 + 3 * inner * 4 + 64 + 2 * inner + 128
    softmax = 3 * d * inner + 2 * d * 1024
    always = d * 320 + 320 + 3 * d * 1280
    routed = 40 * 3 * d * 1280
    total = 3 * kda + softmax + 4 * (always + routed) + 2 * 24576 * d + 9 * d
    assert abs(kda - 137.73e6) < 0.02e6 and abs(softmax - 109.05e6) < 0.01e6
    assert abs(total - 3308.4e6) < 0.2e6                       # 6.617 GB in bfloat16
    assert SZ["state_bytes_per_slot"] == 13_025_280 == 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert SZ["pages_per_slot"] == 68 and SZ["max_ctx"] == 8704
    row = 2 * SZ["kv_heads"] * SZ["head_dim"] * 2
    assert row == 4096 and row * 128 == 524_288
    # a slot's state costs what 3,180 tokens of K and V cost, whatever the prompt's length
    assert SZ["state_bytes_per_slot"] // row == 3180


def test_the_least_counts_against_a_count_by_hand():
    lanes, ctx = 190.0, 190 * 1650.0
    ops, nbytes = flops.delta_update(SZ, lanes)
    assert ops == 3 * lanes * 3 * 2 * 64 * 128 * 128
    assert nbytes == 3 * lanes * (2 * 4 * 64 * 128 * 128 + 4 * (5 * 8192 + 64))
    assert nbytes / 819e9 > ops / 197e12                       # bound by memory, 50 to 1
    u_ops, u_bytes = flops.update(SZ, lanes)
    per_layer = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    assert u_bytes == 3 * (2 * per_layer + 2 * lanes * 13_025_280 / 3)
    assert u_ops == 3 * 2 * lanes * per_layer + ops
    assert abs(u_bytes - (0.826e9 + 4.95e9)) < 0.01e9          # the matrices and 4.8-5.0 GB of state
    s_ops, s_bytes = flops.decode_step(SZ, lanes, ctx, lanes * 8 * 4 / 8, 160.0)
    # weights 6.62 GB once (all 160 held expert-layers hit) but the embedding, of which
    # the lanes' rows alone, + state in and out + live K and V
    assert abs(s_bytes - (6.617e9 - 2 * 24576 * 4096 + 4.95e9 + ctx * 4096)) < 0.03e9
    assert s_bytes / 819e9 > s_ops / 197e12                    # a step is bound by memory
    a_ops, a_bytes = flops.attend_decode(SZ, lanes, ctx)
    assert a_bytes == 2 * 109_051_904 + lanes * 4096 + ctx * 4096
    assert a_ops == 2 * lanes * 109_051_904 + 4 * ctx * 64 * 128
    p_ops, p_bytes = flops.prefill_chunk(SZ, 1000, 1000 * 900.0, 4000.0, 160.0)
    assert p_ops > 1000 * 2 * 0.59e9 and p_bytes > 6.4e9     # 2 x the mixers and what every token reads
    sc_ops, sc_bytes = flops.scan(SZ, 1000, 1.5)
    assert sc_ops == 3 * 1000 * 3 * 2 * 64 * 128 * 128
    assert sc_bytes == 3 * (2 * 1.5 * 13_025_280 / 3 + 1000 * (2 * 4 * 8192 + 4 * (8192 + 64)))
    assert flops.ops_and_bytes(SZ, 192, 1000) == flops.decode_step(
        SZ, 192, 192000.0, 192 * 8 / 8 * 4, 40 * 4)


def test_the_mix_is_the_issues_to_the_letter_and_every_seed_sends_the_same_lengths():
    assert (MIX["traffic"], MIX["verb"], MIX["loop"], MIX["clients"]) == \
        ("token_prompts", "generate", "closed", 192)
    (cls,) = MIX["classes"]
    assert cls["share"] == 1.0
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.9,
                                    "min": 128, "max": 8192}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 224, "sigma": 0.45,
                                     "min": 64, "max": 384}
    assert (MIX["pool_requests"], MIX["warmup_s"], MIX["drain_s"], MIX["trace_ms"],
            MIX["check_logprobs"]) == (8192, 5.0, 30.0, 3000, 8)
    # the check: shorter than a chunk; tiles with a padded tail, a decode across a page's
    # edge; across a launch's edge
    check = [(c["prompt_tokens"], c["max_new_tokens"]) for c in MIX["check"]]
    assert check == [(40, 24), (200, 160), (1100, 24)]
    assert all(p + n <= SZ["max_ctx"] and p <= SZ["max_prompt"] for p, n in check)
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 24576]
    reqs = tokens.make_requests(MIX, 7, rows, 512)
    lens = np.asarray([r.tokens[0] for r in reqs])
    news = np.asarray([r.max_new for r in reqs])
    assert lens.min() >= 128 and lens.max() <= 8192 and news.min() >= 64 and news.max() <= 384
    assert 900 < np.median(lens) < 1150 and 200 < np.median(news) < 250
    assert 1300 < lens.mean() < 1700 and 220 < news.mean() < 260
    again = tokens.make_requests(MIX, 8, rows, 512)
    assert sorted(r.tokens[0] for r in again) == sorted(lens.tolist())
    assert sorted(r.max_new for r in again) == sorted(news.tolist())
    # 4,096 pages hold 192 callers' requests at their mean (15 pages) with room to spare
    assert 192 * -(-int(lens.mean() + news.mean()) // 128) < 0.75 * 4096


def test_the_readers_return_nothing_and_do_not_raise_where_the_program_has_no_counter():
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
           "peaks": None, "flops": flops, "sizes": SZ}
    for name in NEW:
        read = spec.load_module("layer_metrics", name).read
        assert read({}) is None and read(dict(run)) is None
    run["metrics_delta"] = {'delta_steps_total{model="model",phase="decode",path="kernel"}': 5700.0,
                            'delta_steps_total{model="model",phase="decode",path="xla"}': 0.0}
    assert spec.load_module("layer_metrics", "delta_update_kernel_pct").read(dict(run)) == 100.0
    run["metrics_delta"]['delta_steps_total{model="model",phase="decode",path="xla"}'] = 1900.0
    assert spec.load_module("layer_metrics", "delta_update_kernel_pct").read(dict(run)) == 75.0


def rehearse(*extra, env=None):
    """The rehearsal's command (benchmark/rehearsals/hybrid_delta-closed.json), untraced."""
    want = spec.load_json("rehearsals", "hybrid_delta-closed.json")
    args = [a for a in want["args"]]
    args[args.index("--trace") + 1] = "0"
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "rehearsal-hybrid_delta",
                        "--rehearse", "--seconds", "2", *args, *extra], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    return p.returncode, json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_rehearsal_runs_correct_and_a_served_answer_altered_comes_out_not_correct(tmp_path):
    """The whole command on the CPU at the toy size: correct, the new counters
    among those that moved; then the rest of a run with the timed path broken
    underneath: the server's steps lose the correction's read of the decayed
    state (a sitecustomize that acts in the child only, the harness as it is):
    NOT correct, by the statistic's own limit."""
    rc, line, out = rehearse()
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    moved = next(ln for ln in out.splitlines() if "counters that moved in the window" in ln)
    for counter in spec.load_json("rehearsals", "hybrid_delta-closed.json")["counters"]:
        assert f"{counter}=" in moved, counter
    (tmp_path / "sitecustomize.py").write_text(
        "import os\n"
        "if os.environ.get('DROP_THE_CORRECTION'):\n"
        "    import jax.numpy as jnp\n"
        "    from tpuserve.ops import delta_update as du\n"
        "    def no_correction(state, q, k, v, a, beta, live):\n"
        "        new = a[..., None] * state + (beta[..., None] * k)[..., None] * v[..., None, :]\n"
        "        return (jnp.sum(new * q[..., None], axis=-2),\n"
        "                jnp.where(live[:, None, None, None], new, state))\n"
        "    du.delta_step = no_correction\n")
    rc, line, out = rehearse(env={"DROP_THE_CORRECTION": "1", "PYTHONPATH": str(tmp_path)
                                  + os.pathsep + os.environ.get("PYTHONPATH", "")})
    assert rc == 1 and line["correct"] is False
    assert any("NOT CORRECT" in ln and "logprob_q25=" in ln for ln in out.splitlines())
