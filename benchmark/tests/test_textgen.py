"""The family that generates, at the rehearsal's toy size: its reference's
teacher-forced check holds for the reference's own greedy tokens and fails
for a token altered where it is produced; its traffic kind gives every seed
the same work; its count of operations stays under XLA's; and the whole
command serves it through the generation engine on the CPU backend."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec
from benchmark import vocab as vocab_mod

textgen = spec.load_module("reference", "textgen")
prompts = spec.load_module("traffic", "prompts")
CFG = spec.load_config(spec.load_benchmark(), "rehearsal-gen-tiny")
MIX = spec.load_mix("rehearsal-gen-closed")
SZ = textgen.sizes_from_config(CFG)


@pytest.fixture(scope="module")
def reference():
    table = vocab_mod.make_vocab(CFG["vocab_size"])
    sample = prompts.make_check(MIX, 7, table)
    _none, _options, ref = textgen.prepare(7, SZ, CFG, "/nowhere")
    return textgen.reference_answers(ref, prompts.check_inputs(sample, table), SZ)


def _greedy(reference, inp):
    """The reference's own greedy tokens, one full pass a token: the slow,
    obvious way, which the teacher-forced check must agree with."""
    ids, out = list(inp["ids"]), []
    n = len(ids)
    while len(out) < inp["max_new"]:
        mask = textgen.attention_mask(n, len(ids), SZ["prefill_chunk"])
        with jax.default_matmul_precision("highest"):
            lg = textgen.logits(reference["params"], jnp.asarray(ids, jnp.int32),
                                jnp.asarray(mask), SZ)
        out.append(int(np.argmax(np.asarray(lg[-1]))))
        ids.append(out[-1])
        if out[-1] == inp["eos_id"]:
            break
    return {"tokens": out, "n_tokens": len(out), "text": ""}


def test_the_check_passes_greedy_tokens_and_fails_an_altered_one(reference):
    served = [_greedy(reference, inp) for inp in reference["inputs"]]
    stat, line = textgen.compare(served, reference, CFG)
    assert stat <= 1e-4 < CFG["check"]["limit"] and line.startswith("served_token_gap=")
    # a token altered where it is produced: the last of the longest answer
    broken = [dict(a) for a in served]
    longest = max(range(len(broken)), key=lambda i: broken[i]["n_tokens"])
    broken[longest]["tokens"] = broken[longest]["tokens"][:-1] + [
        (broken[longest]["tokens"][-1] + 1) % CFG["vocab_size"]]
    stat, _line = textgen.compare(broken, reference, CFG)
    assert stat > 10 * CFG["check"]["limit"]
    # two lanes swapped: each prompt gets the other's text
    swapped = [served[1], served[0]] + served[2:]
    stat, _line = textgen.compare(swapped, reference, CFG)
    assert stat > 10 * CFG["check"]["limit"]
    # a short answer that does not end the text
    short = [dict(served[0], tokens=served[0]["tokens"][:2], n_tokens=2)] + served[1:]
    assert textgen.compare(short, reference, CFG)[0] == float("inf")


def test_the_mask_is_bidirectional_inside_a_chunk_and_causal_across():
    m = textgen.attention_mask(n_prompt=5, total=7, chunk=4)
    assert m[0].tolist() == [True] * 4 + [False] * 3, "first chunk sees itself whole"
    assert m[4].tolist() == [True] * 5 + [False] * 2, "second chunk: the first and itself, up to n"
    assert m[5].tolist() == [True] * 6 + [False], "generated: everything up to itself"
    assert textgen.attention_mask(5, 5, 32)[0].all(), "one chunk: plain bidirectional"


def test_every_seed_gets_the_same_work_in_another_order():
    table = vocab_mod.make_vocab(CFG["vocab_size"])
    a, b = (prompts.make_requests(MIX, seed, table, 64) for seed in (1, 2**31 + 5))
    key = lambda reqs: sorted((r.cls, r.tokens[0]) for r in reqs)  # noqa: E731
    assert key(a) == key(b) and sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.body for r in a] != [r.body for r in b]
    assert all(r.items == 1 and json.loads(r.body)["temperature"] == 0.0 for r in a)
    assert max(r.tokens[0] for r in a) <= SZ["prompt_len"]
    assert max(r.max_new for r in a) <= SZ["max_new"]
    assert prompts.answers_of({"text": "x", "tokens": [5], "n_tokens": 1}) != []
    assert prompts.answers_of({"results": []}) == []


def test_operations_counted_stay_under_xlas():
    flops = spec.load_module("flops", "textgen")
    n = SZ["max_ctx"]
    params = textgen.draw_params(CFG["assumed"]["weights"], SZ)
    mask = jnp.asarray(textgen.attention_mask(n, n, n))
    fn = jax.jit(lambda p, i: textgen.logits(p, i, mask, SZ))
    xla = fn.lower(params, jnp.zeros((n,), jnp.int32)).compile().cost_analysis()["flops"]
    ops, nbytes = flops.ops_and_bytes(SZ, 1, n)
    assert 0.85 * xla <= ops <= xla and nbytes > 0


RUN = [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload", "rehearsal-gen-test",
       "--config", "rehearsal-gen-tiny", "--traffic", "rehearsal-gen-closed", "--rehearse",
       "--seconds", "2", "--trace", "0", "--seed", str(2**31 + 27)]


def _run(env_extra=None):
    p = subprocess.run(RUN, capture_output=True, text=True, timeout=280,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {})))
    return p.returncode, p.stdout.strip().splitlines(), p.stderr.strip().splitlines()


def test_the_whole_command_serves_it_through_the_engine():
    rc, lines, err = _run()
    r = json.loads(lines[-1])
    assert rc == 0 and r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"items_per_s", "latency_p50_ms", "setup_s"}
    moved = next(ln for ln in lines if "counters that moved in the window" in ln)
    assert "gen_iterations_total=" in moved and "gen_prefill_chunks_total=" in moved
    assert "batcher_flushes_total" not in moved, "the batcher served nothing"
    assert "served_token_gap=" in err[-1] and "limit=" in err[-1], \
        "the number compared beside its limit, as the last line of standard error too"


def test_a_token_altered_where_it_is_produced_comes_out_not_correct(tmp_path):
    """The rest of a run with the timed path broken underneath: the server's
    model answers every token but the first one higher (sitecustomize in the
    child only; the harness is as it is)."""
    (tmp_path / "sitecustomize.py").write_text(
        "import os\n"
        "if os.environ.get('BREAK_TEXTGEN'):\n"
        "    import numpy as np\n"
        "    from tpuserve.models import textgen\n"
        "    _result = textgen.TextGenServing._result\n"
        "    def broken(self, tokens, n_new):\n"
        "        t = np.asarray(tokens).copy()\n"
        "        t[1:] = (t[1:] + 1) % self.vocab_size\n"
        "        return _result(self, t, n_new)\n"
        "    textgen.TextGenServing._result = broken\n")
    rc, lines, _err = _run({"BREAK_TEXTGEN": "1", "PYTHONPATH": str(tmp_path) + os.pathsep
                            + os.environ.get("PYTHONPATH", "")})
    r = json.loads(lines[-1])
    assert rc == 1 and r["correct"] is False
    assert any("NOT CORRECT" in ln and "served_token_gap=" in ln for ln in lines)
