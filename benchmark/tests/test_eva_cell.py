"""The cell `evabyte-6.5b-l8.bytedoc-closed-24` and the files it brought: the cut
configuration against the catalog and against the issue's arithmetic, the
program's config file, the mix to the letter, the control, the least counts of
operations and bytes against a count by hand, the five new readers on a run
that has nothing and on a window's counters, and the rehearsal with a served
answer altered. What is asserted of `BENCHMARK.json` is what the harness needs
(the cell is listed, the metrics it should report name it), not where in a list
an entry stands."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "evabyte-6.5b-l8.bytedoc-closed-24"
NAME = "evabyte-6.5b-l8"
CFG = spec.load_config(BENCH, NAME)
fam = spec.load_module("reference", "eva")
flops = spec.load_module("flops", "eva")
tokens = spec.load_module("traffic", "token_prompts")
SZ = fam.sizes_from_config(CFG)
MIX = spec.load_mix("bytedoc-closed-24")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REPO = spec.REPO

JOINED = {"gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
          "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
          "kv_reserved_pct", "idle_gen_loop_pct", "idle_gen_fetch_pct", "idle_gen_launch_pct",
          "idle_gen_hop_pct", "idle_gen_retire_pct", "idle_gen_host_pct", "idle_gen_no_work_pct",
          "idle_gen_unknown_pct", "gen_loop_serial_ms_per_iter", "gen_step_ahead_pct",
          "gen_loop_cpu_share_pct", "gen_account_trees_pct"}
NEW = {"eva_decode_ms": ("device_trace", "models"),
       "eva_decode_roofline_share": ("device_trace", "kernels"),
       "eva_prefill_ms": ("device_trace", "models"),
       "eva_prefill_roofline_share": ("device_trace", "kernels"),
       "eva_summary_rows_pct": ("program_counter", "models")}


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "bytedoc-closed-24", 1)
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200      # the driver's limit on a line
    for said in ("24 closed-loop callers", "rings of 256 MiB", "summary pages"):
        assert said in cell["why"], said
    assert entry["reduced"] == ["num_hidden_layers"] == CFG["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and entry["source"] == CFG["source"]
    reported = {m["name"] for m in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert JOINED | set(NEW) <= reported
    assert not {n for n in reported if n.startswith(("mla_", "hc_", "attn_", "moe_", "ssm_", "delta_",
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
    assert CFG["family"] == "eva"
    assert CFG["num_hidden_layers"] == 8 and CFG["published"] == {"num_hidden_layers": 32}
    for said in ("4 PIPELINE STAGES of 8 layers", "STAGE 0: layers 0-7", "202,391,552 a layer",
                 "3.262 GB = 3.04 GiB", "256 MiB", "6.25 GiB", "16 MiB", "2.5 GiB",
                 "1 KiB a position a layer", "16 layers as 2 stages"):
        assert said in CFG["deployment"], said
    assert "prediction heads 1-7" in CFG["not_served"] and "DRAWN AND HELD" in CFG["not_served"]
    assumed = json.dumps(CFG["assumed"])
    for said in ("adaptive_phi", "adaptive_mu_k", "no file on this machine", "AFTER the rotary",
                 "no position term of their own", "mixedp_attn", "1 / sqrt(128)", "no log 16",
                 "block 0 is the next byte", "fp32_ln false", "no query/key norm", "(j, j + 64)",
                 "init_fn, init_std, init_cutoff_factor, lazy_init", "end_of_sequence"):
        assert said in assumed, said
    served = CFG["assumed"]["served"]
    assert (served["max_prompt_tokens"], served["max_new_tokens"]) == (24576, 512)
    gen = CFG["serve"]["tables"]["genserve"]
    assert (gen["slots"], gen["kv_page_tokens"], gen["kv_pages"], gen["prefill_chunk"],
            gen["admit_per_step"]) == (MIX["clients"], 128, 160, 1024, 2)
    model = CFG["serve"]["model"]
    assert (model["dtype"], model["parallelism"], model["request_timeout_ms"],
            model["max_queue"]) == ("bfloat16", "single", 180000.0, 256)
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
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CFG[key] == value or key == "num_hidden_layers", key
        assert CFG["published"].get(key, CFG[key]) == value, key
    # ... and every one of them reaches the program's config file
    assert set(row["config"]) <= set(fam.ARCH_KEYS) and set(row["config"]) <= set(SZ["arch"])


def test_the_programs_config_file_and_the_sizes():
    a = SZ["arch"]
    assert (a["num_hidden_layers"], a["window_size"], a["chunk_size"], a["vocab_size"],
            a["num_pred_heads"]) == (8, 2048, 16, 320, 8)
    assert "share" not in a
    assert a["weight_scales"] == CFG["assumed"]["weights"]["scales"] == {
        **fam.DEFAULT_SCALES, **a["weight_scales"]}
    assert (SZ["layers"], SZ["n_attn"], SZ["heads"], SZ["kv_heads"], SZ["head_dim"], SZ["vocab"],
            SZ["win_tokens"], SZ["chunk"], SZ["summary_rows"]) == (8, 8, 32, 32, 128, 320, 2048,
                                                                   16, 128)
    assert SZ["pages_per_slot"] == 13 and SZ["max_ctx"] == 25088 and SZ["kv_pages"] == 160


def test_the_arithmetic_of_the_cut_to_the_megabyte():
    d, f = 4096, 11008
    layer = 4 * d * d + 3 * d * f + 2 * 32 * 128 + 2 * d
    assert layer == 202_391_552
    total = 8 * layer + 320 * d + d * 8 * 320 + d
    assert abs(total - 1630.9e6) < 0.1e6 and abs(2 * total / 2 ** 30 - 3.04) < 0.005
    row = 2 * SZ["kv_heads"] * SZ["head_dim"] * 2
    assert row == 16 * 1024                                          # 16 KiB a position a layer
    assert 8 * row * 2048 == 256 * 2 ** 20 and 25 * 256 / 1024 == 6.25   # a ring; 25 of them, GiB
    assert 8 * row * 128 == 16 * 2 ** 20 and 160 * 16 / 1024 == 2.5      # a page; 160 of them
    assert row * 128 // 2048 == 1024                                 # 1 KiB a position a layer
    # what kv_reserved_pct reckons from the sizes: a page and a ring, all layers
    per_pos = 2 * SZ["kv_heads"] * SZ["head_dim"] * SZ["weight_bytes"]
    n_full = SZ["layer_types"].count("full_attention")
    assert per_pos * SZ["page_tokens"] * n_full == 16 * 2 ** 20
    assert per_pos * SZ["window"] * (len(SZ["layer_types"]) - n_full) == 256 * 2 ** 20


def test_the_least_counts_against_a_count_by_hand():
    d, f, n, row = 4096, 11008, 8, 16384
    lanes = 24.0
    rows = lanes * (1024 + 3 * 128)                    # a window half full over three closed ones
    assert flops.rows_at(SZ, 3 * 2048 + 1023) == 1024 + 3 * 128
    assert flops.rows_at(SZ, 2047) == 2048 and flops.rows_at(SZ, 2048) == 1 + 128
    matrices = n * (4 * d * d + 3 * d * f)
    ops, nbytes = flops.decode_step(SZ, lanes, rows)
    assert ops == 2 * lanes * matrices + 4 * d * rows * n + 4 * d * lanes * n + 2 * lanes * d * 320
    assert nbytes == 2 * (matrices + d * 320) + 4 * lanes * d + n * row * lanes * (1 + 1 / 16) \
        + n * row * rows
    assert abs(n * row * rows - 4.43e9) < 0.01e9      # the issue's "4.6 GB of cache", at 22 MiB a lane a layer
    assert nbytes / 819e9 > ops / 197e12               # a step is bound by memory
    assert flops.ops_and_bytes(SZ, 24, 3 * 2048 + 1023) == flops.decode_step(SZ, 24, rows)
    a_ops, a_bytes = flops.attend_decode(SZ, lanes, rows)
    assert a_ops == 4 * d * rows * n and a_bytes == n * (row * rows + lanes * d * 6)
    tokens_, seen = 1024.0, 1024 * 1250.0              # a launch whose tokens attend 1,250 rows each
    p_ops, p_bytes = flops.prefill_chunk(SZ, tokens_, seen)
    assert p_ops == 2 * tokens_ * matrices + 4 * d * seen * n + 4 * d * tokens_ * n + 2 * d * 320
    earlier = 1250 - 1025 / 2
    assert p_bytes == 2 * (matrices + d * 320) + 4 * tokens_ * d \
        + n * row * tokens_ * (1 + 1 / 16) + n * row * earlier
    assert abs(2 * tokens_ * matrices - 3.31e12) < 0.01e12 and p_ops / 197e12 > p_bytes / 819e9
    t_ops, t_bytes = flops.attend_prefill(SZ, tokens_, seen)
    assert t_ops == (4 * d * seen + 4 * d * tokens_) * n
    assert t_bytes == n * (row * (tokens_ * (1 + 1 / 16) + earlier) + tokens_ * d * 6)


def test_the_mix_is_the_issues_to_the_letter_and_every_seed_sends_the_same_lengths():
    assert (MIX["traffic"], MIX["verb"], MIX["loop"], MIX["clients"]) == \
        ("token_prompts", "generate", "closed", 24)
    (cls,) = MIX["classes"]
    assert cls["share"] == 1.0
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 6144, "sigma": 0.6,
                                    "min": 1024, "max": 24576}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.4,
                                     "min": 96, "max": 384}
    assert (MIX["pool_requests"], MIX["warmup_s"], MIX["drain_s"], MIX["trace_ms"],
            MIX["check_logprobs"]) == (2048, 5.0, 30.0, 3000, 8)
    # the check: a window that closes during decode (at step 18 of 96); one that closes in
    # prefill; a prompt shorter than a tile
    check = [(c["prompt_tokens"], c["max_new_tokens"]) for c in MIX["check"]]
    assert check == [(2030, 96), (2100, 24), (40, 24)]
    assert 2030 + 18 == 2048 and sum(p + n for p, n in check) == 4314
    assert all(p + n <= SZ["max_ctx"] and p <= SZ["max_prompt"] for p, n in check)
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 320]
    reqs = tokens.make_requests(MIX, 7, rows, 512)
    lens = np.asarray([r.tokens[0] for r in reqs])
    news = np.asarray([r.max_new for r in reqs])
    assert lens.min() >= 1024 and lens.max() <= 24576 and news.min() >= 96 and news.max() <= 384
    assert 5600 < np.median(lens) < 6700 and 235 < np.median(news) < 275
    assert 6900 < lens.mean() < 7900 and 250 < news.mean() < 280
    again = tokens.make_requests(MIX, 8, rows, 512)
    assert sorted(r.tokens[0] for r in again) == sorted(lens.tolist())
    assert sorted(r.max_new for r in again) == sorted(news.tolist())
    # 159 usable pages hold 24 callers' requests at the mean (4 to 5 windows each) with room
    pages = -(-(lens + news) // 2048)
    assert 3.8 < pages.mean() < 4.8 and pages.max() <= 13 and 24 * pages.mean() < 0.75 * 159


def test_the_readers_return_nothing_and_do_not_raise_where_the_program_has_no_counter():
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
           "peaks": None, "flops": flops, "sizes": SZ}
    for name in NEW:
        read = spec.load_module("layer_metrics", name).read
        assert read({}) is None and read(dict(run)) is None
    run["metrics_delta"] = {
        'eva_rows_attended_total{model="model",phase="decode",kind="exact"}': 7200.0,
        'eva_rows_attended_total{model="model",phase="decode",kind="summary"}': 2800.0,
        'eva_rows_attended_total{model="model",phase="prefill",kind="summary"}': 9e9}
    assert spec.load_module("layer_metrics", "eva_summary_rows_pct").read(dict(run)) == 28.0
    # the parent's flops file has no such function: the share's reader says nothing
    run["flops"] = spec.load_module("flops", "decoder")
    assert spec.load_module("layer_metrics", "eva_decode_roofline_share").read(dict(run)) is None


def rehearse(*extra, env=None):
    """The rehearsal's command (benchmark/rehearsals/eva-closed.json), untraced."""
    want = spec.load_json("rehearsals", "eva-closed.json")
    args = [a for a in want["args"]]
    args[args.index("--trace") + 1] = "0"
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "rehearsal-eva",
                        "--rehearse", "--seconds", "2", *args, *extra], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    return p.returncode, json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_rehearsal_runs_correct_and_a_served_answer_altered_comes_out_not_correct(tmp_path):
    """The whole command on the CPU at the toy size: correct, the new counters
    among those that moved; then the rest of a run with the timed path broken
    underneath: the server pools a chunk by a plain mean (a sitecustomize that
    acts in the child only, the harness as it is): NOT correct, by the
    statistic's own limit."""
    rc, line, out = rehearse()
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    moved = next(ln for ln in out.splitlines() if "counters that moved in the window" in ln)
    for counter in spec.load_json("rehearsals", "eva-closed.json")["counters"]:
        assert f"{counter}=" in moved, counter
    (tmp_path / "sitecustomize.py").write_text(
        "import os\n"
        "if os.environ.get('POOL_BY_A_PLAIN_MEAN'):\n"
        "    import jax.numpy as jnp\n"
        "    from tpuserve.models import eva\n"
        "    def mean_pool(self, lp, k, v):\n"
        "        ks = jnp.mean(k.astype(jnp.float32), axis=-3) + lp['mu'].astype(jnp.float32)\n"
        "        return ks.astype(self.dtype), jnp.mean(v.astype(jnp.float32), axis=-3).astype(self.dtype)\n"
        "    eva.EvaServing._pool = mean_pool\n")
    rc, line, out = rehearse(env={"POOL_BY_A_PLAIN_MEAN": "1", "PYTHONPATH": str(tmp_path)
                                  + os.pathsep + os.environ.get("PYTHONPATH", "")})
    assert rc == 1 and line["correct"] is False
    assert any("NOT CORRECT" in ln and "logprob_q25=" in ln for ln in out.splitlines())
