"""The cell `nemotron-3-super-q4-l11.chat-closed-256` and the files it brought:
the cut configuration against the catalog, the program's config file with its
share, the mix to the letter, the check on a toy size with its control, the
least counts of operations and bytes, the `ssm_*` readers, and the CPU
rehearsal of the whole command."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "nemotron-3-super-q4-l11.chat-closed-256"
NAME = "nemotron-3-super-q4-l11"
CFG = spec.load_config(BENCH, NAME)
TINY = spec.load_config(BENCH, "rehearsal-hybrid-tiny")
hybrid = spec.load_module("reference", "hybrid")
flops = spec.load_module("flops", "hybrid")
tokens = spec.load_module("traffic", "token_prompts")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Keys that are widths: never in `reduced`, never changed.
WIDTHS = {"hidden_size": 4096, "intermediate_size": 2688, "head_dim": 128, "mamba_head_dim": 64,
          "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128, "expand": 2,
          "moe_intermediate_size": 2688, "moe_latent_size": 1024,
          "moe_shared_expert_intermediate_size": 5376, "num_experts_per_tok": 22}


def test_the_cut_configuration_keeps_every_width_and_says_what_it_cut():
    entry = spec.find(BENCH["configs"], NAME, "config")
    assert entry["reduced"] == CFG["reduced"] and entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and CFG["family"] == "hybrid"
    for key in ("source", "published", "reduced", "assumed", "deployment", "deployment_share",
                "serve", "check"):
        assert key in CFG
    assert not set(CFG["reduced"]) & set(WIDTHS)
    assert {k: CFG[k] for k in WIDTHS} == WIDTHS
    pub = CFG["published"]
    assert sorted(pub) == sorted(CFG["reduced"])
    for key in CFG["reduced"]:   # every key of `reduced` differs from what was published
        assert CFG[key] != pub[key], key
    # one whole period in the published ratio, taken from the front of the published pattern
    assert CFG["hybrid_override_pattern"] == pub["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert CFG["num_hidden_layers"] == 11 == len(CFG["hybrid_override_pattern"])
    assert [CFG["hybrid_override_pattern"].count(c) for c in "ME*"] == [5, 5, 1]
    assert pub["hybrid_override_pattern"].count("M") == 40 == pub["hybrid_override_pattern"].count("E")
    # a quarter of each layer: the same head count a group, a KV head shared with one more chip
    assert CFG["mamba_num_heads"] * 4 == pub["mamba_num_heads"] and CFG["n_groups"] * 4 == pub["n_groups"]
    assert CFG["num_attention_heads"] * 4 == pub["num_attention_heads"]
    assert (CFG["num_key_value_heads"], pub["num_key_value_heads"]) == (1, 2)
    # the floors of the model-configs guide
    assert CFG["n_routed_experts"] == 128 >= 8 and CFG["vocab_size"] * 8 >= pub["vocab_size"]
    assumed = " ".join(str(v) for v in CFG["assumed"].values())
    for said in ("sigmoid", "bias", "NO rotary", "softplus", "float32", "multi-token prediction not served",
                 "whole on each chip"):
        assert said in assumed, said


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide here")
def test_no_other_key_of_the_published_config_differs():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] != value and CFG["published"][key] == value, key
        else:
            assert CFG[key] == value, key


def test_the_programs_config_file_has_the_published_counts_and_the_share():
    arch = hybrid.arch_from_config(CFG)
    assert (arch["n_routed_experts"], arch["mamba_num_heads"], arch["n_groups"]) == (512, 128, 8)
    assert (arch["num_attention_heads"], arch["num_key_value_heads"], arch["vocab_size"]) == \
        (32, 2, 131072)
    assert arch["hybrid_override_pattern"] == "MEMEMEM*EME" and arch["num_hidden_layers"] == 11
    assert arch["share"] == {"experts_held": [0, 128], "attention_heads": [0, 4],
                             "mamba_heads": [0, 4], "vocab_rows": [0, 32768]}
    assert "family" not in arch and "serve" not in arch
    sz = hybrid.sizes_from_config(CFG)
    assert (sz["heads"], sz["kv_heads"], sz["mamba_heads"], sz["mamba_groups"]) == (8, 1, 32, 2)
    assert sz["conv_channels"] == 2560 and sz["vocab"] == 32768
    # the issue's table: 4,211 M parameters held here; 5.32 MB of state a slot
    m = flops._matrices(sz)
    held = 5 * (m["mamba_in"] + m["mamba_out"]) + m["attn"] + 5 * m["expert_always"] \
        + 5 * 128 * m["expert"] + 2 * 4096 * 32768
    assert round(held / 1e6) == 4211
    assert sz["state_bytes_per_slot"] == 5 * (32 * 64 * 128 * 4 + 3 * 2560 * 2) == 5319680
    assert flops.state_bytes(sz) * 5 == sz["state_bytes_per_slot"]


def test_the_cell_and_its_metrics_are_listed_as_the_harness_needs():
    cell = spec.find(BENCH["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and cell["traffic"] == "chat-closed-256" and cell["config"] == NAME
    assert BENCH["workloads"][-1] is cell and BENCH["configs"][-1]["name"] == NAME
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, "end_to_end", CELL)]
    assert sorted(e2e) == ["items_per_s", "latency_p50_ms", "setup_s"]
    mine = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "ssm_update_ms", "ssm_update_roofline_share", "ssm_scan_ms", "ssm_scan_roofline_share",
        "ssm_state_carried_pct"] == [m["name"] for m in BENCH["per_layer"][-5:]]
    shared = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", []) and m not in mine]
    assert sorted(shared) == sorted([
        "gen_step_ms", "gen_prefill_chunk_ms", "gen_step_roofline_share",
        "gen_prefill_roofline_share", "gen_prefill_device_share", "gen_lanes_active_pct",
        "moe_experts_hit_pct", "kv_reserved_pct", "idle_gen_loop_pct"])
    # The one accepted metric without a list whose reader finds nothing here
    # (no (batch, sequence, width) shape in the prefill program): it lists the
    # three older cells, which the driver takes as no change.
    generic = spec.find(BENCH["per_layer"], "exec_roofline_share", "metric")
    assert generic["workloads"] == [w["name"] for w in BENCH["workloads"][:3]]
    for m in BENCH["per_layer"]:   # a list this cell joined has it LAST
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    for m in spec.cell_metrics(BENCH, "per_layer", CELL):
        assert m["moves"] in e2e and callable(spec.load_module("layer_metrics", m["name"]).read)
    assert all(len(x["why"]) <= 200 for x in BENCH["workloads"] + BENCH["configs"])


def test_the_mix_is_the_issues_to_the_letter_and_every_seed_gets_the_same_work():
    mix = spec.load_mix("chat-closed-256")
    assert (mix["traffic"], mix["verb"], mix["loop"], mix["clients"]) == \
        ("token_prompts", "generate", "closed", 256)
    assert (mix["pool_requests"], mix["warmup_s"], mix["drain_s"], mix["trace_ms"],
            mix["check_logprobs"]) == (4096, 5.0, 20.0, 3000, 8)
    new = {"dist": "lognormal", "median": 192, "sigma": 0.5, "min": 32, "max": 512}
    assert mix["classes"] == [
        {"name": "turn", "share": 0.9, "max_new_tokens": new,
         "prompt_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 32, "max": 4096}},
        {"name": "history", "share": 0.1, "max_new_tokens": new,
         "prompt_tokens": {"dist": "lognormal", "median": 6144, "sigma": 0.4, "min": 4096,
                           "max": 16384}}]
    assert mix["check"] == [{"prompt_tokens": 40, "max_new_tokens": 24},
                            {"prompt_tokens": 700, "max_new_tokens": 64},
                            {"prompt_tokens": 2100, "max_new_tokens": 24}]
    rows, extra = tokens.prepare("/nowhere", CFG)
    assert rows == [0, 32768] and extra == {}
    a, b = (tokens.make_requests(mix, seed, rows, 4096) for seed in (3000000001, 7))
    for reqs in (a, b):
        assert len(reqs) == 4096 and sum(r.cls == "history" for r in reqs) == 409
        assert all(32 <= r.tokens[0] <= 4096 for r in reqs if r.cls == "turn")
        assert all(4096 <= r.tokens[0] <= 16384 for r in reqs if r.cls == "history")
        assert all(32 <= r.max_new <= 512 for r in reqs)
    assert sorted(r.tokens[0] for r in a) == sorted(r.tokens[0] for r in b)
    assert [r.tokens[0] for r in a] != [r.tokens[0] for r in b]
    assert len({r.body for r in a}) == 4096, "no request is repeated"
    body = json.loads(a[0].body)
    assert body["temperature"] == 0.0 and max(body["prompt_ids"]) < 32768
    served = CFG["assumed"]["served"]
    assert max(r.tokens[0] for r in a) <= served["max_prompt_tokens"] == 16384
    assert max(r.max_new for r in a) <= served["max_new_tokens"] == 512
    gen = CFG["serve"]["tables"]["genserve"]
    tile = gen["prefill_chunk"] // 8
    assert (gen["slots"], gen["kv_page_tokens"], gen["prefill_chunk"], tile) == (256, 128, 1024, 128)
    lengths = [c["prompt_tokens"] for c in mix["check"]]
    assert lengths[0] < tile                                   # shorter than a scan chunk
    assert lengths[1] > 4 * tile and lengths[1] % tile         # several tiles, a padded tail
    assert lengths[2] > 2 * gen["prefill_chunk"]               # crosses two launch edges
    # every request's pages fit the pool beside 255 others of the mean
    assert gen["kv_pages"] > 256 * np.mean([-(-(r.tokens[0] + r.max_new) // 128) for r in a])


# -- the check, on the toy size: sound, a fault, the control -------------------------------------

def _served_by_the_reference(sz, ref, inputs, low=False):
    """Answers as a sound server would give them: the reference's own greedy
    tokens and top-8 log-probabilities, a full pass a token."""
    model = hybrid.Model(sz["arch"], ref["seed"], ref["dtype"])
    out = []
    for inp in inputs:
        ids, toks, lp_ids, lp_vals = list(inp["ids"] - sz["vocab_first"]), [], [], []
        for _ in range(inp["max_new"]):
            lp = hybrid.log_probs(model, [np.asarray(ids)], [len(ids) - 1], low)[0][0]
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
    sz = hybrid.sizes_from_config(TINY)
    work = str(tmp_path_factory.mktemp("work"))
    weights, options, ref = hybrid.prepare(21, sz, TINY, work)
    assert weights is None and options["draw_weights_seed"] == 21
    with open(options["config_file"], encoding="utf-8") as f:
        assert json.load(f)["share"] == {
            "experts_held": [8, 4], "attention_heads": [2, 4], "mamba_heads": [2, 4],
            "vocab_rows": [192, 96]}
    mix = {"check": [{"prompt_tokens": 11, "max_new_tokens": 5}, {"prompt_tokens": 3, "max_new_tokens": 4}]}
    rows, _ = tokens.prepare(work, TINY)
    inputs = tokens.check_inputs(tokens.make_check(mix, 4, rows), rows)
    return sz, ref, hybrid.reference_answers(ref, inputs, sz)


def test_the_check_passes_a_sound_server_and_fails_faults_and_the_control(toy):
    sz, ref, reference = toy
    served = _served_by_the_reference(sz, ref, reference["inputs"])
    stat, line = hybrid.compare(served, reference, TINY)
    assert stat < 1e-5 < TINY["check"]["limit"] and line.startswith("logprob_q25=")
    assert "logprob_rms=" in line and "limit 0.004" in line
    # ONE position of nine wrong: a request's lower quartile does not see it, the RMS's bound does
    one = [dict(a) for a in served]
    vals = [list(v) for v in one[0]["logprobs"]["values"]]
    vals[2] = vals[2][::-1]
    one[0]["logprobs"] = {"ids": one[0]["logprobs"]["ids"], "values": vals}
    sparse, sparse_line = hybrid.compare(one, reference, TINY)
    assert sparse > TINY["check"]["limit"]
    no_guard = dict(TINY, check={k: v for k, v in TINY["check"].items() if k != "rms_limit"})
    assert hybrid.compare(one, reference, no_guard)[0] < 1e-5
    shifted = [dict(a) for a in served]
    lp = shifted[0]["logprobs"]
    shifted[0]["logprobs"] = {"ids": lp["ids"], "values": lp["values"][1:] + lp["values"][:1]}
    assert hybrid.compare(shifted, reference, TINY)[0] > 50 * TINY["check"]["limit"]
    short = [dict(served[0], tokens=served[0]["tokens"][:-1])] + served[1:]
    assert hybrid.compare(short, reference, TINY)[0] == float("inf")
    outside = [dict(served[0], tokens=[0] + served[0]["tokens"][1:])] + served[1:]
    assert hybrid.compare(outside, reference, TINY)[0] == float("inf")
    # the control: matrix inputs at 3 mantissa bits, the state in bfloat16
    control = dict(TINY, check=dict(TINY["check"], reference_inputs="3-bit-mantissa"))
    ctl, ctl_line = hybrid.compare(served, reference, control)
    assert ctl > 20 * max(stat, 1e-6) and "control" in ctl_line
    lowp = spec.load_config(BENCH, NAME + "-lowp")
    assert lowp["check"]["reference_inputs"] == "3-bit-mantissa" and lowp["cell"] is False
    assert lowp["check"]["limit"] == CFG["check"]["limit"] and lowp["family"] == "hybrid"


def test_a_bfloat16_state_alone_is_seen_by_the_reference(toy):
    """The control's second half: the recurrence with its state rounded to
    bfloat16 after every token drifts from the float32 one."""
    sz, ref, _ = toy
    import jax.numpy as jnp

    m = hybrid.Model(sz["arch"], ref["seed"], "float32")
    w = m.layer(0)
    u = np.random.default_rng(0).standard_normal((40, sz["d_model"])).astype(np.float32)
    sound, _ = hybrid.mamba(m, w, jnp.asarray(u))
    low, _ = hybrid.mamba(m, w, jnp.asarray(u), jnp.bfloat16)
    assert 1e-4 < float(np.abs(np.asarray(sound) - np.asarray(low)).max()) < 0.5


# -- operations and bytes ----------------------------------------------------------------------------

def test_the_least_bytes_of_a_step_are_the_weights_hit_and_the_live_lanes_state():
    sz = hybrid.sizes_from_config(CFG)
    picks = 256 * 22 * 5 * 0.25
    ops, nbytes = flops.decode_step(sz, 256, 256 * 900.0, picks, 5 * 128)
    # 8.42 GB of weights, all hit; 2 x 1.36 GB of state; the one KV head's pages
    assert 10.8e9 < nbytes < 11.4e9   # the embedding is gathered, not read whole
    few, few_bytes = flops.decode_step(sz, 256, 256 * 900.0, picks, 5 * 64)
    assert nbytes - few_bytes == pytest.approx(5 * 64 * 2 * 1024 * 2688 * 2)
    half, half_bytes = flops.decode_step(sz, 128, 128 * 900.0, picks / 2, 5 * 128)
    assert nbytes - half_bytes > 2 * 128 * sz["state_bytes_per_slot"] > 1.3e9
    assert flops.ops_and_bytes(sz, 256, 900)[1] == pytest.approx(nbytes) and ops > 0
    # the state updates alone: W_in and W_out once a layer, the live lanes' state twice
    uops, ubytes = flops.update(sz, 256)
    assert ubytes == pytest.approx(5 * 2 * (4096 * 4640 + 2048 * 4096) + 2 * 256 * 5319680)
    assert ubytes < nbytes and uops < ops
    assert flops.update(sz, 100)[1] < ubytes
    # a launch's scans: a piece's state twice, a token's rows once; the recurrence's operations
    sops, sbytes = flops.scan(sz, 1000, 3)
    assert sops == 5 * 4 * 1000 * 32 * 64 * 128
    assert sbytes == pytest.approx(2 * 3 * 5319680 + 5 * 1000 * (2 * (4096 + 2560) + 128))
    pops, pbytes = flops.prefill_chunk(sz, 1000, 1000 * 700.0, 1000 * 22 * 5 * 0.25, 5 * 128)
    assert sops < pops and sbytes < pbytes
    # of a prefilled token's matrix operations the held experts are a minority here
    assert 0.1 < 2 * 1000 * 22 * 5 * 0.25 * 2 * 1024 * 2688 / pops < 0.5


# -- the readers ------------------------------------------------------------------------------------------

def test_the_readers_read_a_window_of_this_cell_and_nothing_of_a_parents():
    sz = hybrid.sizes_from_config(CFG)
    d = {
        'gen_iterations_total{model="model"}': 1000.0, 'gen_prefill_chunks_total{model="model"}': 500.0,
        'gen_decode_tokens_total{model="model"}': 240000.0, 'gen_prefill_tokens_total{model="model"}': 450000.0,
        'gen_context_tokens_total{model="model",phase="decode"}': 240000.0 * 1100,
        'gen_context_tokens_total{model="model",phase="prefill"}': 450000.0 * 1500,
        'moe_tokens_routed_total{model="model",phase="decode",held="yes"}': 240000.0 * 27.5,
        'moe_tokens_routed_total{model="model",phase="prefill",held="yes"}': 450000.0 * 27.5,
        'moe_experts_hit_total{model="model",phase="decode"}': 1000.0 * 5 * 127,
        'moe_expert_steps_total{model="model",phase="decode"}': 1000.0 * 5 * 128,
        'moe_experts_hit_total{model="model",phase="prefill"}': 500.0 * 5 * 128,
        'gen_kv_page_steps_total{model="model"}': 1000.0 * 3000,
        'ssm_tokens_total{model="model",phase="decode"}': 240000.0 * 5,
        'ssm_tokens_total{model="model",phase="prefill"}': 450000.0 * 5,
        'ssm_state_rows_total{model="model",phase="prefill"}': 500.0 * 3 * 5,
        'ssm_pieces_total{model="model",start="zero"}': 1000.0,
        'ssm_pieces_total{model="model",start="carried"}': 500.0,
    }
    trace = {"window_s": 3.0, "modules": {
        "jit_step(123)": {"launches": 60, "device_s": 1.5, "whole_launches": 58, "launch_s": 0.022},
        "jit_prefill_fn(456)": {"launches": 20, "device_s": 1.0, "whole_launches": 20, "launch_s": 0.024}}}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"metrics_delta": d, "model_name": "model", "sizes": sz, "trace": trace, "peaks": peaks,
           "flops": flops, "xplane": None, "notes": []}
    read = lambda name: spec.load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("ssm_state_carried_pct") == pytest.approx(100 / 3)
    assert read("gen_lanes_active_pct") == pytest.approx(100 * 240 / 256)
    assert read("moe_experts_hit_pct") == pytest.approx(100 * 127 / 128)
    assert 30 < read("kv_reserved_pct") < 40             # pages only: this family has no ring
    assert 40 < read("gen_step_roofline_share") < 100
    assert 10 < read("gen_prefill_roofline_share") < 100
    # no trace file: the device readers have nothing
    for name in ("ssm_update_ms", "ssm_scan_ms", "ssm_update_roofline_share", "ssm_scan_roofline_share"):
        assert read(name) is None, name
    # with the scope's time at hand the shares are least work over it, under 100%
    ssm_window = sys.modules["benchmark.ssm_window"]
    run["_scoped"] = {("jit_step", "ssm_update"): {"launch_s": 0.0045},
                      ("jit_prefill_fn", "ssm_scan"): {"launch_s": 0.003}}
    assert read("ssm_update_ms") == pytest.approx(4.5) and read("ssm_scan_ms") == pytest.approx(3.0)
    lanes = ssm_window.tokens_per_launch(run, "decode")
    assert lanes == pytest.approx(240.0)
    want = 100 * flops.update(sz, 240.0)[1] / 819e9 / 0.0045
    assert read("ssm_update_roofline_share") == pytest.approx(want) and 50 < want < 100
    assert 0 < read("ssm_scan_roofline_share") < 100
    # the parent of this PR has none of the counters and no scope: nothing, and no raise
    bare = dict(run, _scoped={}, metrics_delta={'gen_iterations_total{model="model"}': 10.0},
                trace={"window_s": 3.0, "modules": {"jit_forward(1)": trace["modules"]["jit_step(123)"]}})
    for m in BENCH["per_layer"][-5:]:
        assert spec.load_module("layer_metrics", m["name"]).read(bare) is None, m["name"]
    empty = {"metrics_delta": {}, "model_name": "model"}
    for m in BENCH["per_layer"][-5:]:
        assert spec.load_module("layer_metrics", m["name"]).read(dict(empty)) is None, m["name"]


def test_a_scopes_time_is_read_from_a_recorded_trace_without_one_as_nothing():
    """The recorded v5e trace of another family has no `ssm_*` scope: the
    reader walks it whole and returns None."""
    from benchmark import ssm_window, trace_reduce

    path = os.path.join(REPO, "benchmark", "fixtures", "recorded_v5e.xplane.pb")
    run = {"xplane": path, "trace": trace_reduce.reduce_file(path, 0.6)}
    assert run["trace"] is not None
    assert ssm_window.scoped_launch_s(run, "jit_forward", "ssm_update") is None
    assert ssm_window.scoped_launch_s(run, "jit_step", "ssm_update") is None


# -- the rehearsal: the whole command on the CPU at the toy size ----------------------------------------

def test_the_rehearsal_runs_the_tiny_configuration_end_to_end_and_correct():
    want = spec.load_json("rehearsals", "hybrid-closed.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload", "rehearsal-hybrid-closed",
         "--rehearse", "--seconds", "3", *want["args"]],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert set(want["metrics"]) <= set(r["metrics"])
    assert not set(want["not_metrics"]) & set(r["metrics"])
    moved = next(line for line in proc.stdout.splitlines() if "counters that moved" in line)
    for c in want["counters"]:
        assert f"{c}=" in moved, c


def test_a_traces_own_program_text_says_which_operations_a_scope_holds(tmp_path):
    """`ssm_window.scope_map` on a trace made here: the `/host:metadata` plane's
    `Hlo Proto` gives every instruction of a program its `op_name`."""
    import jax
    import jax.numpy as jnp

    from benchmark import ssm_window, trace_reduce

    @jax.jit
    def step(x):
        with jax.named_scope("ssm_update"):
            y = jnp.tanh(x) * 3.0
        return jnp.sum(y @ y.T)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    step(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    step(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    names = ssm_window.scope_map(path)
    assert "jit_step" in names
    under = [n for n, op in names["jit_step"].items() if "ssm_update" in op]
    outside = [n for n, op in names["jit_step"].items() if op and "ssm_update" not in op]
    assert under and outside
    assert all("ssm_update/" in names["jit_step"][n] for n in under)
    # no device plane on the CPU backend: the reader has nothing, and says so by None
    assert ssm_window.scoped_launch_s({"xplane": path, "trace": {"modules": {}}},
                                      "jit_step", "ssm_update") is None
