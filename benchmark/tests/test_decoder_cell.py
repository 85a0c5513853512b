"""The cell `laguna-s-2.1-half-l5.code-mixed-128` and the files it brought: what
holds for a configuration that is CUT (so `test_spec.py`'s `reduced == []`
cannot), the traffic kind, the check on a toy size with its control, the count
of operations and bytes, and the readers of a generating cell."""

import json
import os

import numpy as np
import pytest

from benchmark import gen_window, spec

BENCH = spec.load_benchmark()
CELL = "laguna-s-2.1-half-l5.code-mixed-128"
CFG = spec.load_config(BENCH, "laguna-s-2.1-half-l5")
TINY = spec.load_config(BENCH, "rehearsal-decoder-tiny")
decoder = spec.load_module("reference", "decoder")
flops = spec.load_module("flops", "decoder")
tokens = spec.load_module("traffic", "token_prompts")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# Keys that are widths: never in `reduced`, never changed.
WIDTHS = ("hidden_size", "intermediate_size", "head_dim", "moe_intermediate_size",
          "shared_expert_intermediate_size", "num_experts_per_tok", "sliding_window")


def test_the_cut_configuration_keeps_every_width_and_says_what_it_cut():
    entry = spec.find(BENCH["configs"], "laguna-s-2.1-half-l5", "config")
    assert entry["reduced"] == CFG["reduced"] and entry["source"] == CFG["source"]
    for key in ("source", "published", "reduced", "assumed", "deployment", "serve", "check"):
        assert key in CFG
    assert not set(CFG["reduced"]) & set(WIDTHS)
    assert [CFG[k] for k in WIDTHS] == [3072, 12288, 128, 1024, 1024, 10, 512]
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["num_key_value_heads"],
            pub["vocab_size"]) == (48, 256, 8, 100352)
    # every key of `reduced` differs from what was published
    assert CFG["num_hidden_layers"] == 5 and CFG["num_experts"] == 128
    assert CFG["num_key_value_heads"] == 4 and CFG["vocab_size"] == 50176
    assert CFG["num_attention_heads_per_layer"] == [24, 36, 36, 36, 24]
    for key in ("layer_types", "mlp_layer_types", "gating_types"):
        assert len(CFG[key]) == 5
    # the floors of the model-configs guide
    assert CFG["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert CFG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4      # four layers after the dense one
    assert CFG["num_experts"] >= 8 and CFG["vocab_size"] * 8 >= pub["vocab_size"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_no_other_key_of_the_published_config_differs():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] != value, key
        else:
            assert CFG[key] == value, key


def test_the_programs_config_file_has_the_published_counts_and_the_share():
    arch = decoder.arch_from_config(CFG)
    assert arch["num_experts"] == 256 and arch["num_key_value_heads"] == 8
    assert arch["vocab_size"] == 100352 and arch["num_hidden_layers"] == 5
    assert arch["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert arch["share"] == {"experts_held": [0, 128], "attention_heads": [0, 2],
                             "vocab_rows": [0, 50176]}
    assert arch["rope_parameters"] == CFG["rope_parameters"]
    sz = decoder.sizes_from_config(CFG)
    assert sz["heads"] == [24, 36, 36, 36, 24] and sz["kv_heads"] == 4 and sz["vocab"] == 50176
    # the issue's table: 5,433 M parameters held here
    m = flops._matrices(sz)
    held = sum(m["attn"]) + sum(m["dense"]) + sum(m["shared"]) + sum(m["router"]) \
        + 4 * 128 * m["expert"] + 2 * 3072 * 50176
    assert round(held / 1e6) == 5433


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and cell["traffic"] == "code-mixed-128"
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)]
    assert sorted(e2e) == ["items_per_s", "latency_p50_ms", "setup_s"]
    mine = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == sorted([
        "gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
        "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
        "moe_experts_hit_pct", "kv_reserved_pct", "idle_gen_loop_pct"])
    assert all(m["moves"] == "items_per_s" for m in mine)
    for m in spec.cell_metrics(BENCH, "per_layer", CELL):
        assert m["moves"] in e2e and callable(spec.load_module("layer_metrics", m["name"]).read)


def test_the_mix_is_the_issues_and_every_seed_gets_the_same_work():
    mix = spec.load_mix("code-mixed-128")
    assert (mix["loop"], mix["clients"], mix["pool_requests"]) == ("closed", 128, 2048)
    assert (mix["warmup_s"], mix["drain_s"], mix["trace_ms"]) == (5.0, 15.0, 3000)
    rows, extra = tokens.prepare("/nowhere", CFG)
    assert rows == [0, 50176] and extra == {}
    a, b = (tokens.make_requests(mix, seed, rows, 2048) for seed in (3000000001, 7))
    for reqs in (a, b):
        short = [r for r in reqs if r.cls == "short"]
        assert len(short) == 1536 and len(reqs) == 2048
        assert all(64 <= r.tokens[0] <= 1536 for r in short)
        assert all(1536 <= r.tokens[0] <= 8192 for r in reqs if r.cls == "long")
        assert all(32 <= r.max_new <= 512 for r in reqs)
    assert sorted(r.tokens[0] for r in a) == sorted(r.tokens[0] for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.tokens[0] for r in a] != [r.tokens[0] for r in b]
    body = json.loads(a[0].body)
    assert len(body["prompt_ids"]) == a[0].tokens[0] and max(body["prompt_ids"]) < 50176
    assert body["temperature"] == 0.0 and "logprobs" not in body
    assert len({r.body for r in a}) == 2048, "no request is repeated"
    sample = tokens.make_check(mix, 5, rows)
    assert [json.loads(r.body)["logprobs"] for r, _ in sample] == [8, 8, 8]
    lengths = [(r.tokens[0], r.max_new) for r, _ in sample]
    page, window, chunk = (CFG["serve"]["tables"]["genserve"]["kv_page_tokens"],
                           CFG["sliding_window"], CFG["serve"]["tables"]["genserve"]["prefill_chunk"])
    assert any(n < page for n, _ in lengths)                              # shorter than a page
    assert any(n < window < n + m for n, m in lengths)                    # the ring wraps in decode
    assert any(n > chunk for n, _ in lengths)                             # crosses a chunk's edge
    assert tokens.answers_of({"tokens": [1], "n_tokens": 1}) and not tokens.answers_of({"error": "x"})


@pytest.mark.parametrize("run", [8, 32, 128, 512])
def test_any_stretch_of_the_pool_is_the_same_work_for_every_seed(run):
    """A window consumes the pool's head only, so the head has to be the mix
    in small: every aligned run of 2**k requests holds the classes by share
    and nearly the same tokens, whatever the seed."""
    mix = spec.load_mix("code-mixed-128")
    sums = []
    for seed in (3000000001, 7, 2**31 + 12345):
        reqs = tokens.make_requests(mix, seed, [0, 50176], 2048)
        for k in range(0, 2048, run):
            part = reqs[k:k + run]
            assert sum(r.cls == "long" for r in part) == run // 4
            sums.append((sum(r.tokens[0] for r in part) / run, sum(r.max_new for r in part) / run))
    prompt, new = (np.asarray(v) for v in zip(*sums))
    # the limits are twice what five seeds read; a plain shuffle's runs of 128 differ by 48% in prompt tokens
    assert np.ptp(prompt) / prompt.mean() < {8: 1.4, 32: 0.2, 128: 0.035, 512: 0.005}[run]
    assert np.ptp(new) / new.mean() < {8: 0.7, 32: 0.13, 128: 0.015, 512: 0.004}[run]


@pytest.mark.parametrize("n", [1, 2, 3, 7, 96, 1536, 2048])
def test_balanced_order_is_a_permutation_spread_evenly(n):
    order = tokens.balanced_order(np.random.default_rng(n), n)
    assert sorted(order) == list(range(n))
    for run in (2, 4, 16):
        if n % run == 0:
            for k in range(0, n, run):  # one index from each evenly cut part
                assert sorted(i * run // n for i in order[k:k + run]) == list(range(run))
    if n >= 96:
        assert order != tokens.balanced_order(np.random.default_rng(n + 1), n)


# -- the check, on the toy size: sound, a fault, the control -------------------------------------

def _served_by_the_reference(sz, ref, inputs, low=False):
    """Answers as a sound server would give them: the reference's own greedy
    tokens and top-8 log-probabilities, a full pass a token."""
    model = decoder.Model(sz["arch"], ref["seed"], ref["dtype"])
    out = []
    for inp in inputs:
        ids, toks, lp_ids, lp_vals = list(inp["ids"] - sz["vocab_first"]), [], [], []
        for _ in range(inp["max_new"]):
            lp = decoder.log_probs(model, [np.asarray(ids)], [len(ids) - 1], low)[0][0]
            top = np.argsort(-lp, kind="stable")[:8]
            toks.append(int(top[0]))
            lp_ids.append((top + sz["vocab_first"]).tolist())
            lp_vals.append(lp[top].tolist())
            ids.append(toks[-1])
        out.append({"tokens": [t + sz["vocab_first"] for t in toks], "n_tokens": len(toks),
                    "logprobs": {"ids": lp_ids, "values": lp_vals}})
    return out


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    sz = decoder.sizes_from_config(TINY)
    work = str(tmp_path_factory.mktemp("work"))
    weights, options, ref = decoder.prepare(21, sz, TINY, work)
    assert weights is None and options["draw_weights_seed"] == 21
    with open(options["config_file"], encoding="utf-8") as f:
        assert json.load(f)["share"] == {"experts_held": [4, 4], "attention_heads": [1, 2],
                                         "vocab_rows": [96, 96]}
    mix = {"check": [{"prompt_tokens": 11, "max_new_tokens": 5}, {"prompt_tokens": 3, "max_new_tokens": 4}]}
    rows, _ = tokens.prepare(work, TINY)
    inputs = tokens.check_inputs(tokens.make_check(mix, 4, rows), rows)
    return sz, ref, decoder.reference_answers(ref, inputs, sz)


def test_the_check_passes_a_sound_server_and_fails_faults_and_the_control(toy):
    sz, ref, reference = toy
    served = _served_by_the_reference(sz, ref, reference["inputs"])
    stat, line = decoder.compare(served, reference, TINY)
    assert stat < 1e-5 < TINY["check"]["limit"] and line.startswith("logprob_rms=")
    swapped = [served[0], dict(served[1], logprobs=served[0]["logprobs"])]
    assert decoder.compare(swapped, reference, TINY)[0] == float("inf")    # wrong count of positions
    shifted = [dict(a) for a in served]
    lp = shifted[0]["logprobs"]
    shifted[0]["logprobs"] = {"ids": lp["ids"], "values": lp["values"][1:] + lp["values"][:1]}
    assert decoder.compare(shifted, reference, TINY)[0] > 50 * TINY["check"]["limit"]
    short = [dict(served[0], tokens=served[0]["tokens"][:-1])] + served[1:]
    assert decoder.compare(short, reference, TINY)[0] == float("inf")
    outside = [dict(served[0], tokens=[0] + served[0]["tokens"][1:])] + served[1:]
    assert decoder.compare(outside, reference, TINY)[0] == float("inf")
    # the control: the reference's expert products at 3 mantissa bits
    control = dict(TINY, check=dict(TINY["check"], reference_inputs="3-bit-mantissa"))
    ctl, ctl_line = decoder.compare(served, reference, control)
    assert ctl > 20 * max(stat, 1e-6) and "control" in ctl_line


# -- operations and bytes ----------------------------------------------------------------------------

def test_the_least_bytes_of_a_decode_step_are_the_weights_that_are_hit():
    sz = decoder.sizes_from_config(CFG)
    ops, nbytes = flops.decode_step(sz, 128, 128 * 1400.0, 128 * 10 * 4 * 0.5, 4 * 128)
    assert 10.8e9 < nbytes < 12.5e9            # 10.87 GB of weights, all hit, and the caches read
    few, few_bytes = flops.decode_step(sz, 128, 128 * 1400.0, 128 * 10 * 4 * 0.5, 4 * 64)
    assert nbytes - few_bytes == pytest.approx(4 * 64 * 3 * 3072 * 1024 * 2)
    # of a prefilled token's matrix operations here the held experts are over a third
    pops, _ = flops.prefill_chunk(sz, 2048, 2048 * 1024.5, 2048 * 10 * 4 * 0.5, 4 * 128)
    experts = 2 * 2048 * 10 * 4 * 0.5 * 3 * 3072 * 1024
    assert 0.3 < experts / pops < 0.8
    assert flops.ops_and_bytes(sz, 128, 1400)[1] == pytest.approx(nbytes)
    assert ops > 0


# -- the readers on a generating run ----------------------------------------------------------------

def test_the_readers_read_a_generating_window_and_nothing_else():
    sz = decoder.sizes_from_config(CFG)
    d = {
        'gen_iterations_total{model="model"}': 1000.0, 'gen_prefill_chunks_total{model="model"}': 400.0,
        'gen_decode_tokens_total{model="model"}': 110000.0, 'gen_prefill_tokens_total{model="model"}': 300000.0,
        'gen_context_tokens_total{model="model",phase="decode"}': 110000.0 * 1300,
        'gen_context_tokens_total{model="model",phase="prefill"}': 300000.0 * 700,
        'moe_tokens_routed_total{model="model",phase="decode",held="yes"}': 110000.0 * 20,
        'moe_tokens_routed_total{model="model",phase="decode",held="no"}': 110000.0 * 20,
        'moe_tokens_routed_total{model="model",phase="prefill",held="yes"}': 300000.0 * 20,
        'moe_experts_hit_total{model="model",phase="decode"}': 1000.0 * 4 * 126,
        'moe_expert_steps_total{model="model",phase="decode"}': 1000.0 * 4 * 128,
        'moe_experts_hit_total{model="model",phase="prefill"}': 400.0 * 4 * 128,
        'gen_kv_page_steps_total{model="model"}': 1000.0 * 1500, 'gen_kv_ring_steps_total{model="model"}': 1000.0 * 120,
    }
    trace = {"window_s": 3.0, "modules": {
        "jit_step(123)": {"launches": 60, "device_s": 1.5, "whole_launches": 58, "launch_s": 0.025},
        "jit_prefill_fn(456)": {"launches": 20, "device_s": 1.0, "whole_launches": 20, "launch_s": 0.05}}}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"metrics_delta": d, "model_name": "model", "sizes": sz, "trace": trace, "peaks": peaks,
           "flops": flops, "xplane": None, "notes": []}
    read = lambda name: spec.load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("gen_step_ms") == pytest.approx(25.0) and read("gen_prefill_chunk_ms") == pytest.approx(50.0)
    assert read("gen_prefill_device_share") == pytest.approx(40.0)
    assert read("gen_lanes_active_pct") == pytest.approx(100 * 110 / 128)
    assert read("moe_experts_hit_pct") == pytest.approx(100 * 126 / 128)
    assert 40 < read("kv_reserved_pct") < 50
    assert 40 < read("gen_step_roofline_share") < 100
    assert 10 < read("gen_prefill_roofline_share") < 100
    assert read("idle_gen_loop_pct") is None          # no trace file
    assert gen_window.per_launch(run, "decode")["tokens"] == pytest.approx(110.0)
    # the parent of this PR has none of the counters and none of the programs: nothing, and no raise
    bare = dict(run, metrics_delta={'gen_iterations_total{model="model"}': 10.0},
                trace={"window_s": 3.0, "modules": {"jit_forward(1)": trace["modules"]["jit_step(123)"]}})
    for m in BENCH["per_layer"]:
        if m.get("workloads") == [CELL]:
            assert spec.load_module("layer_metrics", m["name"]).read(bare) is None, m["name"]
