"""The cell `mimo-v2.5-e16-l7.reason-closed-384` and the files it brought: the cut
configuration against the catalog's row, the arithmetic of what is held, the
program's config file, the cell's metrics, the mix, the control, the least
counts of `flops/decoder_sink.py` against a count by hand, the new readers on a
window that has nothing for them, and the rehearsal with a served answer
altered."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = spec.load_benchmark()
NAME, CELL = "mimo-v2.5-e16-l7", "mimo-v2.5-e16-l7.reason-closed-384"
CFG = spec.load_config(BENCH, NAME)
family = spec.load_module("reference", "decoder_sink")
flops = spec.load_module("flops", "decoder_sink")
tokens = spec.load_module("traffic", "token_prompts")
SZ = family.sizes_from_config(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("attn_full_walk_ms", "attn_full_walk_roofline_share", "attn_ring_ms",
       "attn_ring_roofline_share", "attn_walk_kernel_pct")
# Keys that are widths: never in `reduced`, never changed.
WIDTHS = {"hidden_size": 4096, "intermediate_size": 16384, "moe_intermediate_size": 2048,
          "head_dim": 192, "v_head_dim": 128, "swa_head_dim": 192, "swa_v_head_dim": 128,
          "num_experts_per_tok": 8, "sliding_window": 128, "partial_rotary_factor": 0.334}


def test_the_cut_configuration_keeps_every_width_and_says_what_it_cut():
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert entry["reduced"] == CFG["reduced"] and entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and CFG["family"] == "decoder_sink"
    for key in ("source", "published", "reduced", "assumed", "deployment", "not_served", "serve",
                "check"):
        assert key in CFG
    assert not set(CFG["reduced"]) & set(WIDTHS)
    assert {k: CFG[k] for k in WIDTHS} == WIDTHS
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"], pub["vocab_size"]) == (48, 256, 152576)
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"], CFG["vocab_size"]) == (7, 16, 19072)
    # the floors of the model-configs guide: a whole period and four layers after the dense one,
    # eight experts, an eighth of the vocabulary
    assert CFG["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert CFG["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert CFG["n_routed_experts"] >= 8 and CFG["vocab_size"] * 8 == pub["vocab_size"]
    assert CFG["deployment_share"] == {"index": 0, "of": 16, "experts_first": 0, "vocab_first": 0}
    for said in ("multi-token-prediction", "vision", "audio"):
        assert said in CFG["not_served"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_no_other_key_of_the_published_config_differs():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in ("hybrid_layer_pattern", "moe_layer_freq"):
            assert CFG[key] == value[:7] != value, key           # the lists' first seven entries
        elif key in CFG["reduced"]:
            assert CFG[key] != value, key
        else:
            assert key in CFG and CFG[key] == value, key


def test_the_arithmetic_of_the_cut_to_the_megabyte():
    """The issue's table of what is held, from the sizes."""
    d = 4096
    g = d * 64 * 192 + d * 4 * 192 + d * 4 * 128 + 64 * 128 * d
    w = d * 64 * 192 + d * 8 * 192 + d * 8 * 128 + 64 * 128 * d + 64
    assert (round(g / 1e4), round(w / 1e4)) == (8913, 9437)
    assert flops._attention(SZ, "global") == g and flops._attention(SZ, "window") == w - 64
    dense, experts = 3 * d * 16384, 16 * 3 * d * 2048
    routers = 6 * (d * 256 + 256)
    vocab = 2 * 19072 * d
    held = 2 * g + 5 * w + dense + routers + 6 * experts + vocab
    assert round(held / 1e5) == 34299 and round(held * 2 / 2 ** 30, 2) == 6.39
    assert flops._always(SZ) == 2 * g + 5 * (w - 64) + dense + 6 * d * 256
    # the cache: 5,120 B a token in the pages, 655,360 B a page, 3,276,800 B of rings a slot
    assert 2 * flops._row_bytes(SZ, "global") == 5120 and 5120 * 128 == 655360
    assert 5 * 128 * flops._row_bytes(SZ, "window") == 3276800
    gen = CFG["serve"]["tables"]["genserve"]
    assert (gen["slots"], gen["kv_pages"], gen["kv_page_tokens"], gen["prefill_chunk"],
            gen["admit_per_step"]) == (384, 4608, 128, 1024, 4)
    assert round(4608 * 655360 / 2 ** 30, 2) == 2.81 and 1.17 < 385 * 3276800 / 2 ** 30 < 1.18


def test_the_programs_config_file_has_the_published_counts_and_the_share():
    arch = family.arch_from_config(CFG)
    assert (arch["n_routed_experts"], arch["vocab_size"], arch["num_hidden_layers"]) == (256, 152576, 7)
    assert arch["share"] == {"experts_held": [0, 16], "vocab_rows": [0, 19072]}
    assert arch["weight_scales"] == CFG["assumed"]["weights"]["scales"]
    for key in ("hybrid_layer_pattern", "moe_layer_freq", "swa_num_key_value_heads",
                "add_swa_attention_sink_bias", "attention_value_scale", "swa_rope_theta"):
        assert arch[key] == CFG[key]
    assert SZ["kinds"] == ["global"] + ["window"] * 4 + ["global", "window"]
    assert SZ["by_kind"]["global"] == {"heads": 64, "kv_heads": 4, "dk": 192, "dv": 128, "sink": False}
    assert SZ["by_kind"]["window"] == {"heads": 64, "kv_heads": 8, "dk": 192, "dv": 128, "sink": True}
    assert (SZ["vocab"], SZ["experts_held"], SZ["num_experts"], SZ["top_k"]) == (19072, 16, 256, 8)
    assert (SZ["max_ctx"], SZ["pages_per_slot"], SZ["kv_pages"], SZ["slots"]) == (3072, 24, 4608, 384)
    # what kv_reserved_pct looks up gives the pools' true bytes
    per_pos = 2 * SZ["kv_heads"] * SZ["head_dim"] * SZ["weight_bytes"]
    n_full = SZ["layer_types"].count("full_attention")
    assert per_pos * SZ["page_tokens"] * n_full == 655360
    assert per_pos * SZ["window"] * (len(SZ["layer_types"]) - n_full) == 3276800


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert cell == BENCH["workloads"][-1] and cell["chips"] == 1
    assert cell["traffic"] == "reason-closed-384" and len(cell["why"]) <= 200
    for said in ("384", "12 tokens a held expert", "16x its share"):
        assert said in cell["why"]
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)]
    assert set(e2e) >= {"items_per_s", "setup_s"}
    mine = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(NEW) == [m["name"] for m in BENCH["per_layer"][-5:]]
    assert all(m["moves"] == "items_per_s" for m in mine)
    assert [m["layer"] for m in mine] == ["models", "kernels", "models", "kernels", "models"]
    listed = {m["name"] for m in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert listed >= {"gen_step_ms", "gen_prefill_chunk_ms", "kv_reserved_pct", "moe_experts_hit_pct",
                      "moe_dispatch_compact_pct", "moe_experts_prefill_ms", "attn_decode_ms",
                      "attn_decode_roofline_share", "gen_loop_serial_ms_per_iter",
                      "idle_gen_loop_pct", "gen_step_ahead_pct", *NEW}
    assert not listed & {"mla_decode_ms", "ssm_update_ms", "hc_mix_step_ms", "batch_fill_ratio"}
    for m in spec.cell_metrics(BENCH, "per_layer", CELL):
        assert callable(spec.load_module("layer_metrics", m["name"]).read)
        assert m["moves"] in e2e or "workloads" not in m   # a general reader of another metric


def test_the_mix_is_the_issues_to_the_letter_and_every_seed_sends_the_same_lengths():
    mix = spec.load_mix("reason-closed-384")
    assert (mix["traffic"], mix["verb"], mix["loop"], mix["clients"], mix["pool_requests"]) == \
        ("token_prompts", "generate", "closed", 384, 8192)
    assert (mix["warmup_s"], mix["drain_s"], mix["trace_ms"]) == (5.0, 30.0, 3000)
    (one,) = mix["classes"]
    assert one["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.8,
                                    "min": 32, "max": 2048}
    assert one["max_new_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.5,
                                     "min": 128, "max": 1024}
    rows, extra = tokens.prepare("/nowhere", CFG)
    assert rows == [0, 19072] and extra == {}
    a, b = (tokens.make_requests(mix, seed, rows, 8192) for seed in (3000000049, 7))
    for reqs in (a, b):
        assert all(32 <= r.tokens[0] <= 2048 and 128 <= r.max_new <= 1024 for r in reqs)
    assert sorted(r.tokens[0] for r in a) == sorted(r.tokens[0] for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.tokens[0] for r in a] != [r.tokens[0] for r in b]
    assert len({r.body for r in a[:1024]}) == 1024, "no request is repeated"
    body = json.loads(a[0].body)
    assert max(body["prompt_ids"]) < 19072 and body["temperature"] == 0.0
    # 384 requests as the slots hold them (length-biased by the tokens asked for: a long
    # answer holds its slot longer) reserve 7.7 pages each, about two thirds of the ledger
    need = np.array([-(-(r.tokens[0] + r.max_new) // 128) for r in a])
    by_life = np.array([r.max_new for r in a], float)
    assert 7.0 < float((need * by_life).sum() / by_life.sum()) < 9.0
    assert 0.6 < 384 * float((need * by_life).sum() / by_life.sum()) / 4607 < 0.8
    # the sample: inside one page; a DECODE that crosses the window's wrap at 128 and a page's
    # edge at 256; a prompt across a launch's edge
    lengths = [(c["prompt_tokens"], c["max_new_tokens"]) for c in mix["check"]]
    assert lengths == [(40, 24), (200, 160), (1100, 24)]
    assert sum(n + m for n, m in lengths) == 1548


def test_the_control_differs_from_the_cell_by_the_check_alone():
    low = spec.load_config(BENCH, f"{NAME}-lowp")
    assert low["cell"] is False and low["check"]["reference_inputs"] == "3-bit-mantissa"
    strip = lambda c: {k: v for k, v in c.items() if k not in ("name", "base", "cell", "why", "check")}  # noqa: E731
    assert strip(low) == strip(CFG)
    assert {k: v for k, v in low["check"].items() if k != "reference_inputs"} == CFG["check"]
    assert low["name"] not in [w["config"] for w in BENCH["workloads"]]
    # the limit between the readings, with room on both sides
    r = CFG["check"]["readings"]
    assert 1.5 * max(r["sound"]) <= CFG["check"]["limit"] <= min(r["control"]) / 1.5


def test_the_least_counts_against_a_count_by_hand():
    """One lane at context 700 (its 700 positions attended from: context_sum
    700). A global layer: 2 x 64 heads x (192 + 128) = 40,960 operations a key
    row and 4 x 320 x 2 = 2,560 B a row; a window layer sees min(700, 128) = 128
    rows of 8 x 320 x 2 = 5,120 B."""
    ops, nbytes = flops.full_walk(SZ, 1.0, 700.0)
    assert ops == 2 * 40960 * 700 and nbytes == 2 * 2560 * (700 + 1)
    ops, nbytes = flops.ring_read(SZ, 1.0, 700.0)
    assert ops == 5 * 40960 * 128 and nbytes == 5 * 5120 * (128 + 1)
    ops, nbytes = flops.ring_read(SZ, 2.0, 100.0)            # contexts of 50: under the window
    assert ops == 5 * 40960 * 100 and nbytes == 5 * 5120 * (100 + 2)
    mats = 2 * flops._attention(SZ, "global") + 5 * flops._attention(SZ, "window")
    a, b = flops.attend_decode(SZ, 1.0, 700.0)
    assert a == 2 * mats + 2 * 40960 * 700 + 5 * 40960 * 128
    assert b == 2 * mats + 2 * 2560 * 701 + 5 * 5120 * 129
    # the issue's step: 384 lanes at 700: pages 1.38 GB, rings 1.26 GB, attention matrices 1.3 GB
    lanes, ctx = 384.0, 384 * 700.0
    assert round(flops.full_walk(SZ, lanes, ctx)[1] / 1e9, 2) == 1.38
    assert round(flops.ring_read(SZ, lanes, ctx)[1] / 1e9, 2) == 1.27
    assert round(2 * mats / 1e9, 1) == 1.3
    # every held expert hit in six layers: 4.8 GB; the whole step about 9.3 GB, 11.4 ms at 819 GB/s
    ops, nbytes = flops.decode_step(SZ, lanes, ctx, 384 * 8 * 6 / 16, 16 * 6.0)
    assert 9.2e9 < nbytes < 9.5e9 and nbytes / 819e9 > ops / 197e12
    assert round(100 * flops.attend_decode(SZ, lanes, ctx)[1] / nbytes) in (42, 43)
    assert flops.ops_and_bytes(SZ, 384, 700) == (ops, nbytes)
    # a launch of 1,024 rows at a mean context of 300 is compute's: 12 tokens... 512 a held expert
    ops, nbytes = flops.prefill_chunk(SZ, 1024.0, 1024 * 300.0, 1024 * 8 * 6 / 16, 96.0)
    assert ops / 197e12 < nbytes / 819e9 < 2 * ops / 197e12 or ops / 197e12 >= nbytes / 819e9


def test_the_readers_return_nothing_and_do_not_raise_where_the_program_has_no_scope():
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "flops": flops,
           "sizes": SZ, "notes": []}
    for name in NEW + ("attn_decode_ms", "attn_decode_roofline_share"):
        assert spec.load_module("layer_metrics", name).read(dict(run)) is None
    # another family's window (no such counter, no such function): nothing, and no raise
    run["flops"] = spec.load_module("flops", "decoder")
    run["metrics_delta"] = {'gen_iterations_total{model="model"}': 10.0,
                            'gen_decode_tokens_total{model="model"}': 3800.0}
    for name in NEW:
        assert spec.load_module("layer_metrics", name).read(dict(run)) is None
    # with the counter: the share of the global layers' lanes that took the kernel
    run["metrics_delta"] = {'attn_walks_total{model="model",phase="decode",walk="kernel"}': 7600.0,
                            'attn_walks_total{model="model",phase="decode",walk="xla"}': 0.0,
                            'attn_walks_total{model="model",phase="prefill",walk="xla"}': 55.0}
    assert spec.load_module("layer_metrics", "attn_walk_kernel_pct").read(dict(run)) == 100.0
    run["metrics_delta"]['attn_walks_total{model="model",phase="decode",walk="xla"}'] = 7600.0
    assert spec.load_module("layer_metrics", "attn_walk_kernel_pct").read(dict(run)) == 50.0


def rehearse(*extra, env=None):
    """The rehearsal's command (benchmark/rehearsals/decoder_sink-closed.json), untraced."""
    want = spec.load_json("rehearsals", "decoder_sink-closed.json")
    args = [a for a in want["args"]]
    args[args.index("--trace") + 1] = "0"
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "rehearsal-decoder_sink",
                        "--rehearse", "--seconds", "2", *args, *extra], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    return p.returncode, json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_rehearsal_runs_correct_and_a_served_answer_altered_comes_out_not_correct(tmp_path):
    """The whole command on the CPU at the toy size: correct, the new counters
    among those that moved; then the rest of a run with the timed path broken
    underneath: the server's window layers lose their sink (a sitecustomize that
    acts in the child only, the harness as it is): NOT correct, by the
    statistic's own limit."""
    rc, line, out = rehearse()
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    moved = next(ln for ln in out.splitlines() if "counters that moved in the window" in ln)
    for counter in spec.load_json("rehearsals", "decoder_sink-closed.json")["counters"]:
        assert f"{counter}=" in moved, counter
    (tmp_path / "sitecustomize.py").write_text(
        "import os\n"
        "if os.environ.get('DROP_THE_SINK'):\n"
        "    from tpuserve.models import paged_lm\n"
        "    real = paged_lm.PagedLM._attend\n"
        "    paged_lm.PagedLM._attend = lambda self, q, k, v, mask, sink=None: "
        "real(self, q, k, v, mask)\n")
    rc, line, out = rehearse(env={"DROP_THE_SINK": "1", "PYTHONPATH": str(tmp_path) + os.pathsep
                                  + os.environ.get("PYTHONPATH", "")})
    assert rc == 1 and line["correct"] is False
    assert any("NOT CORRECT" in ln and "logprob_rms=" in ln for ln in out.splitlines())
