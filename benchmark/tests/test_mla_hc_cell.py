"""The cell `xing4.0-29b-a4b-l8.rag-closed-64` and the files it brought: the cut
configuration against the catalog and against the issue's arithmetic, the
program's config file, the mix to the letter, the control, the least counts of
operations and bytes against a count by hand, the new readers on a run that
has nothing, and the rehearsal with a served answer altered."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "xing4.0-29b-a4b-l8.rag-closed-64"
NAME = "xing4.0-29b-a4b-l8"
CFG = spec.load_config(BENCH, NAME)
ref = spec.load_module("reference", "mla_hc")
flops = spec.load_module("flops", "mla_hc")
tokens = spec.load_module("traffic", "token_prompts")
SZ = ref.sizes_from_config(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Keys that are widths: never in `reduced`, never changed.
WIDTHS = {"hidden_size": 3584, "intermediate_size": 9216, "moe_intermediate_size": 1024,
          "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts_per_tok": 4, "hc_mult": 4}
NEW = ["hc_mix_prefill_ms", "hc_mix_prefill_roofline_share", "hc_mix_step_ms", "hc_maps_per_token"]


def test_the_cut_configuration_keeps_every_width_and_says_what_it_cut():
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CFG["source"] and entry["file"] == f"benchmark/configs/{NAME}.json"
    assert CFG["family"] == "mla_hc" and len(entry["why"]) <= 200
    for key in ("source", "published", "reduced", "assumed", "deployment", "serve", "check"):
        assert key in CFG
    assert {k: CFG[k] for k in WIDTHS} == WIDTHS
    assert CFG["published"] == {"num_hidden_layers": 40} and CFG["num_hidden_layers"] == 8
    # both leading dense layers and six sparse ones (the guide's floor is four), every expert, the
    # whole vocabulary, ep_size 1: no share
    assert (CFG["first_k_dense_replace"], CFG["n_routed_experts"], CFG["vocab_size"],
            CFG["ep_size"]) == (2, 64, 131072, 1)
    assert "deployment_share" not in CFG and "share" not in ref.arch_from_config(CFG)
    for said in ("PIPELINE STAGES", "stage 0 of five", "WHOLE", "ep_size 1", "10.55 GiB",
                 "2.25 GiB", "larger here than in a deployment"):
        assert said in CFG["deployment"], said
    assumed = json.dumps(CFG["assumed"])
    for said in ("arXiv:2512.24880", "arXiv:2409.19606", "NO GAIN", "ADDED TO THE SUMS",
                 "COLUMNS THEN ROWS", "OUTGOING", "ENTRY BY COPY", "EXIT BY SUM", "FLOAT32",
                 "SCALARS", "2.0048", "(i, i + 32)", "multi-token prediction", "H_pre", "H_res",
                 "expert_out", "hc_phi"):
        assert said in assumed, said
    assert CFG["assumed"]["served"] == {**CFG["assumed"]["served"], "max_prompt_tokens": 8192,
                                        "max_new_tokens": 256}
    gen = CFG["serve"]["tables"]["genserve"]
    assert (gen["slots"], gen["kv_page_tokens"], gen["kv_pages"], gen["prefill_chunk"],
            gen["admit_per_step"]) == (64, 128, 2048, 4096, 4)
    assert CFG["serve"]["model"]["dtype"] == "bfloat16"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_no_other_key_of_the_published_config_differs():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] != value and CFG["published"][key] == value, key
        else:
            assert CFG[key] == value and type(CFG[key]) is type(value), key
    assert set(ref.ARCH_KEYS) == set(row["config"])


def test_the_arithmetic_of_the_cut_to_the_megabyte():
    m = flops._matrices(SZ)
    assert m["mla"] == 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 4096 * 3584
    assert round(m["mla"] / 1e4) / 100 == 28.41 and round(m["dense"] / 1e4) / 100 == 99.09
    assert 64 * m["expert"] == 704643072 and m["sparse_always"] == 3584 * 64 + 3 * 3584 * 1024
    phi = 4 * 3584 * 24
    assert phi == 344064 and (SZ["streams"], SZ["sublayers"], SZ["hc_iters"]) == (4, 16, 20)
    held = 8 * m["mla"] + 16 * phi + 2 * m["dense"] + 6 * (64 * m["expert"] + m["sparse_always"]) \
        + 2 * 131072 * 3584
    assert round(8 * m["mla"] / 1e5) / 10 == 227.3 and round(16 * phi / 1e5) / 10 == 5.5
    assert round(6 * 64 * m["expert"] / 1e5) / 10 == 4227.9
    assert round(6 * m["sparse_always"] / 1e5) / 10 == 67.4
    assert round(held / 1e5) / 10 == 5665.8 and round(2 * held / 2 ** 30 * 100) / 100 == 10.55
    # the cache: mla's one row of 576 values a token a layer, 2.25 GiB of 2,048 pages
    assert flops.row_bytes(SZ) == 1152 and SZ["row"] == 576 and SZ["layers"] == 8
    page = SZ["layers"] * SZ["page_tokens"] * flops.row_bytes(SZ)
    assert page == 1179648 and SZ["kv_pages"] * page == 2.25 * 2 ** 30
    assert SZ["pages_per_slot"] == 66 and SZ["slots"] == 64 and SZ["prefill_chunk"] == 4096
    per = 2 * SZ["kv_heads"] * SZ["head_dim"] * SZ["weight_bytes"]
    assert per * SZ["layer_types"].count("full_attention") == 8 * 1152


def test_the_programs_config_file_is_the_published_one():
    arch = ref.arch_from_config(CFG)
    assert arch["num_hidden_layers"] == 8 and arch["hc_mult"] == 4
    assert arch["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                                    "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                                    "type": "yarn"}
    assert (arch["hc_sinkhorn_iters"], arch["hc_eps"], arch["mhc_h_res_clamp_min"],
            arch["mhc_h_res_clamp_max"]) == (20, 1e-06, -30, 30)
    assert "family" not in arch and "serve" not in arch and "published" not in arch
    assert arch["weight_scales"] == CFG["assumed"]["weights"]["scales"]
    # a score's deviation about 4 AFTER the 2.0048: q, k_nope and k_r of RMS 1.41
    s = arch["weight_scales"]
    assert s["q_b"] == s["k_b"] == s["k_rope"] and abs(2.0048 * s["q_b"] * s["k_b"] - 4) < 0.05
    assert CFG["assumed"]["weights"]["centres"] == {
        "POST_BIAS": ref.POST_BIAS, "RES_DIAGONAL": ref.RES_DIAGONAL, "RES_ALPHA": ref.RES_ALPHA}
    rows, _ = tokens.prepare("", CFG)
    assert rows == [0, 131072]
    m = ref.Model(arch, 7)
    assert m.n == 4 and m.on_cos_sin == 1.0
    assert abs(m.score_scale * 192 ** 0.5 - 2.00474) < 1e-5


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and cell["traffic"] == "rag-closed-64" and cell["config"] == NAME
    assert len(cell["why"]) <= 200 and "mid-length contexts only" in cell["why"]
    for said in ("64 closed-loop callers", "2,048", "256-8,192", "16-256", "4,096 rows"):
        assert said in cell["why"], said
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)]
    assert {"items_per_s", "setup_s"} <= set(e2e) <= {"items_per_s", "setup_s", "latency_p50_ms"}
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= listed
    assert {"gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
            "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
            "kv_reserved_pct", "moe_experts_hit_pct", "mla_decode_ms",
            "mla_decode_roofline_share", "mla_prefill_ms", "mla_prefill_roofline_share",
            "mla_walk_kernel_pct", "mla_decode_kernel_pct", "idle_gen_loop_pct",
            "idle_gen_fetch_pct", "gen_loop_serial_ms_per_iter", "gen_step_ahead_pct"} <= listed
    # the gap readers go with the median latency, PR 34's rule
    assert ("gen_token_gap_ms_p50" in listed) == ("latency_p50_ms" in e2e)
    assert not [n for n in listed if n.startswith(("ssm_", "exec_roofline", "attn_decode",
                                                   "moe_layer", "moe_zero"))]
    for name in NEW:   # every new metric has its reader and came listing this cell (a later one may join)
        assert callable(spec.load_module("layer_metrics", name).read)
        assert spec.find(BENCH["per_layer"], name, "metric")["workloads"][0] == CELL
    assert [m["name"] for m in BENCH["per_layer"] if m["name"] in NEW] == NEW


def test_the_mix_is_the_issues_to_the_letter_and_every_seed_sends_the_same_lengths():
    mix = spec.load_mix("rag-closed-64")
    assert (mix["traffic"], mix["verb"], mix["loop"], mix["clients"]) == \
        ("token_prompts", "generate", "closed", 64)
    assert mix["clients"] == SZ["slots"] and mix["pool_requests"] == 8192
    (cls,) = mix["classes"]
    assert cls["share"] == 1.0 and "temperature" not in cls      # greedy
    assert cls["prompt_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.6,
                                    "min": 256, "max": 8192}
    assert cls["max_new_tokens"] == {"dist": "lognormal", "median": 64, "sigma": 0.5,
                                     "min": 16, "max": 256}
    assert (mix["warmup_s"], mix["drain_s"], mix["trace_ms"], mix["check_logprobs"]) == \
        (5.0, 20.0, 3000, 8)
    # a prompt inside one page, and one that crosses a launch's edge (4,096) whose decode crosses
    # a page's edge (4,224)
    chunk, page = SZ["prefill_chunk"], SZ["page_tokens"]
    (a, na), (b, nb) = [(e["prompt_tokens"], e["max_new_tokens"]) for e in mix["check"]]
    assert a + na < page and b > chunk and b < 33 * page <= b + nb
    rows, _ = tokens.prepare("", CFG)
    x, y = (tokens.make_requests(mix, seed, rows, 1024) for seed in (3000000019, 7))
    lx, ly = ([r.tokens[0] for r in reqs] for reqs in (x, y))
    assert sorted(lx) == sorted(ly) and lx != ly        # the same lengths in another order
    assert min(lx) >= 256 and max(lx) <= 8192 and 1900 < float(np.median(lx)) < 2200
    assert 2300 < float(np.mean(lx)) < 2600             # the issue's mean prompt of 2,450
    assert 16 <= min(r.max_new for r in x) and max(r.max_new for r in x) <= 256
    assert 58 < float(np.median([r.max_new for r in x])) < 70
    assert abs(sum(lx[:512]) - sum(lx[512:])) < 0.1 * sum(lx[:512])
    assert max(r.tokens[0] + r.max_new for r in x) <= SZ["max_ctx"]
    ids = json.loads(x[0].body)["prompt_ids"]
    assert 0 <= min(ids) and max(ids) < 131072 and max(ids) > 65536
    # the pool holds what 64 lanes reserve: a request's prompt and its whole answer
    need = sorted(-(-(r.tokens[0] + r.max_new) // page) for r in x)
    assert float(np.mean(need)) * 64 < 0.8 * SZ["kv_pages"] and sum(need[-64:]) / 2 < SZ["kv_pages"]


def test_the_control_differs_from_the_cell_by_the_check_alone():
    low = spec.load_config(BENCH, f"{NAME}-lowp")
    assert low["cell"] is False and low["check"]["reference_inputs"] == "3-bit-mantissa"
    strip = lambda c: {k: v for k, v in c.items() if k not in ("name", "base", "cell", "why", "check")}  # noqa: E731
    assert strip(low) == strip(CFG)
    assert {k: v for k, v in low["check"].items() if k != "reference_inputs"} == CFG["check"]
    assert low["name"] not in [w["config"] for w in BENCH["workloads"]]
    # each limit between the readings, with room on both sides
    r = CFG["check"]["readings"]
    assert 1.5 * max(r["sound_q25"]) <= CFG["check"]["limit"] <= min(r["control_q25"]) / 1.5
    assert 1.5 * max(r["sound_rms"]) <= CFG["check"]["rms_limit"] <= min(r["control_rms"]) / 1.5
    # a map frozen to its bias in the PROGRAM reads over the limit on the chip, each of the three
    assert min(r["frozen_stat"].values()) > CFG["check"]["limit"] and set(r["frozen_stat"]) == \
        {"p", "q", "r"}


def test_the_least_counts_of_the_maps_against_a_count_by_hand():
    """A token a sublayer at n = 4, d = 3584: the product with Phi 2 x 14336 x 24
    = 688,128; the mixes 2 x 14336 x 6 = 172,032 (n d multiply-adds in, n (n + 1)
    d out); twenty Sinkhorn iterations of two passes of 16 sums and 16
    divisions, 1,280: 861,440 operations. Bytes: the stream of 14,336 bfloat16
    values read once and written once, 57,344 B, and Phi's 688,128 B once a
    launch. Sixteen sublayers a launch."""
    ops, nbytes = flops.hyper_maps(SZ, 1.0)
    assert ops == 16 * (688128 + 172032 + 1280) == 13783040
    assert nbytes == 16 * (57344 + 688128)
    ops, nbytes = flops.hyper_maps(SZ, 4096.0)
    assert ops == 4096 * 13783040 and nbytes == 16 * (4096 * 57344 + 688128)
    # bound by memory: 3.77 GB a launch of 4,096 rows, 4.6 ms at 819 GB/s against 0.29 ms of products
    assert 4.5 < nbytes / 819e9 * 1e3 < 4.7 and ops / 197e12 * 1e3 < 0.3
    # the programs' counts are mla's and the maps'
    mla = spec.load_module("flops", "mla")
    args = (SZ, 4096.0, 4096 * 1500.0, 4 * 4096 * 6.0, 64 * 6.0)
    (a, b), (c, d) = flops.prefill_chunk(*args), mla.prefill_chunk(*args)
    assert (a - c, b - d) == flops.hyper_maps(SZ, 4096.0)
    (a, b), (c, d) = flops.decode_step(SZ, 40.0, 40 * 2500.0, 160 * 6.0, 60 * 6.0), \
        mla.decode_step(SZ, 40.0, 40 * 2500.0, 160 * 6.0, 60 * 6.0)
    assert (a - c, b - d) == flops.hyper_maps(SZ, 40.0)
    # the issue's reckoning of a launch: about 6.3 TFLOP of products (7.3 with attention at a mean context of 1,500) against 10.4 GB of weights read once
    ops, nbytes = flops.prefill_chunk(*args)
    assert 6.0e12 < ops < 7.5e12 and 10.3e9 < nbytes < 15e9 and ops / 197e12 > nbytes / 819e9
    assert flops.attend_prefill is mla.attend_prefill and flops.attend_decode is mla.attend_decode
    assert flops.ops_and_bytes(SZ, 40, 2500)[1] > 0


def test_the_readers_return_nothing_and_do_not_raise_where_the_program_has_no_scope():
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "flops": flops,
           "sizes": SZ, "notes": []}
    for name in NEW:
        assert spec.load_module("layer_metrics", name).read(dict(run)) is None
    # the parent's program: tokens counted, no hc_maps_total; another family's flops module
    run["metrics_delta"] = {'gen_prefill_tokens_total{model="model"}': 5.0}
    assert spec.load_module("layer_metrics", "hc_maps_per_token").read(dict(run)) is None
    run["flops"] = spec.load_module("flops", "mla")
    assert spec.load_module("layer_metrics", "hc_mix_prefill_roofline_share").read(dict(run)) is None
    # and with the counter: sixteen a token
    run["metrics_delta"] = {'gen_prefill_tokens_total{model="model"}': 4096.0,
                            'gen_decode_tokens_total{model="model"}': 40.0,
                            'hc_maps_total{model="model",phase="prefill"}': 16 * 4096.0,
                            'hc_maps_total{model="model",phase="decode"}': 16 * 40.0}
    assert spec.load_module("layer_metrics", "hc_maps_per_token").read(dict(run)) == 16.0
    # and over the tokens the DEVICE counted beside them (the picks of six sparse layers, four a
    # token), where the host's count, taken at dispatch, lies a few launches off at a window's edge
    run["metrics_delta"].update({
        'gen_prefill_tokens_total{model="model"}': 4096.0 - 3 * 1024,
        'moe_tokens_routed_total{model="model",phase="prefill",held="yes"}': 24 * 4096.0,
        'moe_tokens_routed_total{model="model",phase="decode",held="yes"}': 24 * 40.0})
    assert (SZ["top_k"], SZ["n_sparse"]) == (4, 6)
    assert spec.load_module("layer_metrics", "hc_maps_per_token").read(dict(run)) == 16.0


def rehearse(*extra, env=None):
    """The rehearsal's command (benchmark/rehearsals/mla_hc-closed.json), untraced."""
    want = spec.load_json("rehearsals", "mla_hc-closed.json")
    args = [a for a in want["args"]]
    args[args.index("--trace") + 1] = "0"
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "rehearsal-mla_hc",
                        "--rehearse", "--seconds", "2", *args, *extra], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    return p.returncode, json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_rehearsal_runs_correct_and_a_served_answer_altered_comes_out_not_correct(tmp_path):
    """The whole command on the CPU at the toy size: correct, `hc_maps_total`
    among the counters that moved; then the rest of a run with the timed path
    broken underneath: the server's residual map frozen to its bias (`r` held at
    zero: the smallest of the three faults; a sitecustomize that acts in the
    child only, the harness as it is): NOT correct, by the statistic's own
    limit."""
    rc, line, out = rehearse()
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    moved = next(ln for ln in out.splitlines() if "counters that moved in the window" in ln)
    for counter in spec.load_json("rehearsals", "mla_hc-closed.json")["counters"]:
        assert f"{counter}=" in moved, counter
    (tmp_path / "sitecustomize.py").write_text(
        "import os\n"
        "if os.environ.get('FREEZE_H_RES'):\n"
        "    from tpuserve.ops import hyper\n"
        "    real = hyper.maps\n"
        "    def frozen(x, hp, n, *a):\n"
        "        return real(x, dict(hp, phi=hp['phi'].at[:, 2 * n:].set(0)), n, *a)\n"
        "    hyper.maps = frozen\n")
    rc, line, out = rehearse(env={"FREEZE_H_RES": "1", "PYTHONPATH": str(tmp_path) + os.pathsep
                                  + os.environ.get("PYTHONPATH", "")})
    assert rc == 1 and line["correct"] is False
    assert any("NOT CORRECT" in ln and "logprob_q25=" in ln for ln in out.splitlines())
