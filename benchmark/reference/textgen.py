"""The plain reference for the `textgen` family: the program's prefix-LM
decoder (`tpuserve/models/textgen.py`) written down again from its
description, in straightforward `jax.numpy` float32 under
`jax.default_matmul_precision("highest")`. It imports nothing of the program.

The model: learned token and position embeddings; pre-LayerNorm blocks
(multi-head attention without biases, tanh-GELU feed-forward); a final
LayerNorm and an untied head. A prompt of n tokens is encoded bidirectionally
inside a chunk of `prefill_chunk` positions and causally across chunks (one
chunk, so plain bidirectional, where `prefill_chunk` is 0 or at least
`prompt_len`); generated tokens attend to everything before them. The token
at position p >= n is predicted from position p - 1.

This family is served through the generation engine and shows the three
functions a family that generates gives the harness (benchmark/README.md):

- `prepare`: no checkpoint. The program draws its weights itself, by the
  recipe under `assumed.weights` in the configuration file, and
  `reference_answers` draws the same tensors here.
- `reference_answers`: what `compare` needs later (the tensors and the
  sample's prompts); the forward pass waits for the served tokens.
- `compare`: teacher-forced. ONE pass of the reference over each prompt with
  its served tokens; at every generated position the gap by which the served
  token's logit lies below the reference's best. The statistic is the widest
  gap, in logits. A greedy token that the reference also puts first reads 0;
  one that lost a near tie to the program's rounding reads that rounding; a
  wrong token (another lane's, another prompt's, a shifted position) reads
  the distance between two unrelated logits, 2 to 5 here. Valid for greedy
  tokens only: the sample is sent with temperature 0.

THE RECIPE `program-init` is the program's own initialisation, the only one
it has without a checkpoint: `jax.random.key(0)` split into 6 x layers + 4
keys, taken in order: embedding N(0, 0.02), positions N(0, 0.01), head
N(0, 1/d); then per layer wq, wk, wv, wo, w_up, w_down, each N(0, 1/rows);
LayerNorm gains 1 and offsets 0. It does NOT meet what README.md asks of a
cell's recipe: the key is fixed (`--seed` moves the prompts only), there is
one key per tensor but only in the order above, and float32 normals go
through `erf_inv`, which two backends may round differently in the last
place. For a toy whose check is greedy tokens at float32 that is enough, and
the check itself says so in every run: a tensor that differed by more than
rounding would put the served tokens far from the reference's best.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def sizes_from_config(cfg: dict) -> dict:
    """The sizes this file and flops/textgen.py need, by the configuration
    file's own key names."""
    gen = cfg["serve"].get("tables", {}).get("genserve", {})
    prompt_len, max_new = int(cfg["prompt_len"]), int(cfg["max_new_tokens"])
    chunk = int(gen.get("prefill_chunk", 0)) if gen.get("kv_paging") else 0
    return {
        "layers": int(cfg["num_hidden_layers"]),
        "d_model": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "d_ff": int(cfg["intermediate_size"]),
        "vocab_size": int(cfg["vocab_size"]),
        "prompt_len": prompt_len,
        "max_new": max_new,
        "max_ctx": prompt_len + max_new,
        "prefill_chunk": chunk if 0 < chunk < prompt_len else prompt_len,
        "slots": int(gen.get("slots", 0)) or max(cfg["serve"]["model"]["batch_buckets"]),
    }


def draw_params(recipe: dict, sz: dict) -> dict:
    """Every tensor by the recipe the configuration states (header)."""
    if recipe.get("recipe") != "program-init":
        raise ValueError(f"reference/textgen.py knows no weights recipe {recipe!r}")
    L, d, f, v = sz["layers"], sz["d_model"], sz["d_ff"], sz["vocab_size"]
    keys = iter(jax.random.split(jax.random.key(int(recipe["key"])), 6 * L + 4))

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    params = {"embed": normal((v, d), 0.02), "pos": normal((sz["max_ctx"], d), 0.01),
              "head": normal((d, v), 1.0 / math.sqrt(d)), "layers": []}
    for _ in range(L):
        params["layers"].append({
            "wq": normal((d, d), 1.0 / math.sqrt(d)), "wk": normal((d, d), 1.0 / math.sqrt(d)),
            "wv": normal((d, d), 1.0 / math.sqrt(d)), "wo": normal((d, d), 1.0 / math.sqrt(d)),
            "w_up": normal((d, f), 1.0 / math.sqrt(d)),
            "w_down": normal((f, d), 1.0 / math.sqrt(f))})
    return params


def _norm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS)  # gains 1, offsets 0


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention_mask(n_prompt: int, total: int, chunk: int) -> np.ndarray:
    """(total, total) True where position i may attend to position j: a
    prompt position sees its own chunk whole and every chunk before it, a
    generated position everything up to itself."""
    i = np.arange(total)[:, None]
    j = np.arange(total)[None, :]
    prompt_limit = np.minimum((i // chunk + 1) * chunk, n_prompt)
    return np.where(i < n_prompt, j < prompt_limit, j <= i)


def logits(params: dict, ids: jax.Array, mask: jax.Array, sz: dict) -> jax.Array:
    """(total,) token ids of ONE sequence, prompt then generated tokens, and
    its attention mask -> (total, vocab) logits: row p predicts position p + 1."""
    h = sz["heads"]
    t = ids.shape[0]
    x = params["embed"][ids] + params["pos"][:t]
    bias = jnp.where(mask, 0.0, -1e9)
    for w in params["layers"]:
        hx = _norm(x)
        q = (hx @ w["wq"]).reshape(t, h, -1)
        k = (hx @ w["wk"]).reshape(t, h, -1)
        v = (hx @ w["wv"]).reshape(t, h, -1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1]) + bias
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(t, -1)
        x = x + ctx @ w["wo"]
        x = x + _gelu(_norm(x) @ w["w_up"]) @ w["w_down"]
    return _norm(x) @ params["head"]


def served_token_gaps(params: dict, prompt_ids: np.ndarray, tokens: list[int],
                      sz: dict) -> np.ndarray:
    """For one request: at each generated position, the reference's best
    logit less its logit of the served token (0 where they agree)."""
    n, m = len(prompt_ids), len(tokens)
    ids = np.concatenate([prompt_ids, np.asarray(tokens[:-1], np.int32)]).astype(np.int32)
    mask = attention_mask(n, n + m - 1, sz["prefill_chunk"])
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(logits(params, jnp.asarray(ids), jnp.asarray(mask), sz))[n - 1:]
    return lg.max(axis=-1) - lg[np.arange(m), np.asarray(tokens)]


# -- what the harness calls (benchmark/README.md, "a family that generates") ------

def prepare(seed: int, sizes: dict, cfg: dict, work: str):
    """No checkpoint and no option: the program draws its weights itself by
    `assumed.weights`, and `reference_answers` draws them again here."""
    return None, {}, dict(cfg["assumed"]["weights"])


def reference_answers(ref: dict, inputs: list[dict], sizes: dict) -> dict:
    """`inputs` are the traffic kind's `check_inputs`: per request the prompt's
    ids and the tokens asked for. The tensors are drawn now, while the server
    starts; the pass over prompt and served tokens is `compare`'s."""
    return {"params": jax.block_until_ready(draw_params(ref, sizes)), "inputs": inputs,
            "sizes": sizes}


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    sz, worst, n_tokens = reference["sizes"], 0.0, 0
    for answer, inp in zip(served, reference["inputs"], strict=True):
        tokens = [int(t) for t in answer["tokens"]]
        # As many tokens as asked for, or fewer that end in [SEP], the end of text.
        if not 1 <= len(tokens) <= inp["max_new"] or answer.get("n_tokens") != len(tokens) or (
                len(tokens) < inp["max_new"] and tokens[-1] != inp["eos_id"]):
            return float("inf"), (f"served_token_gap=inf: {len(tokens)} tokens for a request "
                                  f"of {inp['max_new']} that do not end the text: {answer}")
        gaps = served_token_gaps(reference["params"], inp["ids"], tokens, sz)
        worst, n_tokens = max(worst, float(gaps.max())), n_tokens + len(tokens)
    return worst, (f"served_token_gap={worst:.6g} logits, the widest of {n_tokens} served "
                   f"tokens of {len(served)} requests below the reference's best")
