"""The plain reference for the `eva` family: a decoder-only language model whose
attention is EVA (Zheng, Wang, Kong: "Efficient Attention via Control
Variates", ICLR 2023, arXiv:2302.04542, its final form, as the EvaByte release
serves it), written down from the published `config.json` in straightforward
float32 (`jax.numpy` under `jax.default_matmul_precision("highest")`), with no
cache, no ring, no pages, no tiles and no kernel: all positions' k and v, then
every whole chunk's summary row from them, then each position's softmax over
the two sets its index gives, by the definition of E and S and nothing else.
It imports nothing of the program.

THE LAYER. d = `hidden_size`, H = `num_attention_heads` heads of hd = d / H on
`num_key_value_heads` KV heads (query head h reads KV head h // (H / KV)), W =
`window_size`, c = `chunk_size` (`num_chunks` null: `chunk_size` sets the
chunks), eps `rms_norm_eps`, no bias anywhere. `N(x; g) = x / sqrt(mean(x^2) +
eps) * (1 + g)` (`norm_add_unit_offset`). The stream x is float32 from the
embedding's row to the last norm (`fp32_skip_add`). Layer l, position i
(0-based), its window n = i // W:

    u = N(x_i; g1)
    q = rope(u W_q, i)    k_i = rope(u W_k, i)    v_i = u W_v     rotary over all hd columns,
                                                                  pairs (j, j + hd/2), `rope_theta`
    chunk m holds positions c m .. c m + c - 1; when its last position has its k and v:
      w_t     = softmax over t in the chunk of ( phi[h] . k_t[h] )       phi (H, hd): `adaptive_phi`
      ks_m[h] = sum_t w_t k_t[h] + mu[h]                                 mu (H, hd): `adaptive_mu_k`
      vs_m[h] = sum_t w_t v_t[h]
    E = { t : n W <= t <= i }        its own window so far, EXACT rows
    S = { m : m < n W / c }          every chunk of every EARLIER window, SUMMARY rows
    a = softmax over E and S TOGETHER of q[h] . k_t[h] / sqrt(hd) and q[h] . ks_m[h] / sqrt(hd)
    o[h] = sum_{t in E} a_t v_t[h] + sum_{m in S} a_m vs_m[h]
    x_i <- x_i + concat_h(o[h]) W_o
    u2 = N(x_i; g2);   x_i <- x_i + ( silu(u2 W_gate) * (u2 W_up) ) W_down

After the last layer z = N(x_i; g_f), logits = z W_head in float32
(`fp32_logits`), W_head (d, `num_pred_heads` x `vocab_size`); the served logits
are columns 0 .. `vocab_size` - 1. A chunk's summary is NOT visible to its own
window, whole or not; a chunk weighs in the softmax as ONE key (no log c).

ASSUMED, because the published config has no key for it (the configuration file
repeats this under `assumed`). THE POOLING'S FORM: it is the paper's estimate of
a chunk's value, sum_t exp(omega . k_t) v_t / sum_t exp(omega . k_t) with one
sample omega a chunk, the sample replaced by a learned vector a head, written
here from the public module's PARAMETER NAMES (`adaptive_phi`, `adaptive_mu_k`)
with no file on this machine to hold it against: whoever has the file can
check this paragraph. The pooled key is taken from keys AFTER the rotary and
the summaries carry no position term of their own; no factor on phi . k but
what is drawn into phi; mu is added to the pooled KEY alone; the pooling and
both softmaxes in float32 (`mixedp_attn`); the score's 1 / sqrt(hd); the head's
blocks lie one after another and block 0 is the next byte; `fp32_ln` false is
read as "the norm's result in the served type", its arithmetic in float32; no
query/key norm; the rotary pairs halves; `init_fn`, `init_std`,
`init_cutoff_factor`, `lazy_init` are read by nothing.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`, roles `phi` and
`mu` added): `reference/hybrid.py`'s few lines (`draw`); `std` is the
role's scale over sqrt(fan-in) (fan-in 1 for the embedding, phi and mu). The
norms' g are float32 vectors inside [-`gain`, `gain`]: the four summed bytes
over their range (0 to 1) mapped into it. A layer is drawn alone and dropped
after use.

THE CHECK (`compare`): `reference/hybrid.py`'s statistic (`logprob_q25` beside
`logprob_rms`, centred top-8 log-probabilities, teacher-forced on the served
tokens) over ONE full pass, a sublayer one compiled program a sequence length.
`check.reference_inputs = "3-bit-mantissa"` (a control, never a cell) rounds the
inputs of the reference's matrix products (every kernel, the normed stream that
enters a sublayer, the context before W_o, the hidden rows before the
down-projection) to 3 explicit mantissa bits, keeps the summary rows at 3, and
rounds the stream to bfloat16 between sublayers. `check.wrong_reading` (one of
`WRONG`; a control too) computes that wrong reading of the layer instead.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import spec

hy = base = spec.load_module("reference", "hybrid")   # the recipe's draw, the control's rounding
LOGPROBS = hy.LOGPROBS
DEFAULT_SCALES = {"embed": 1.0, "head": 1.0, "qk": 1.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
                  "ffn_out": 1.0, "phi": 0.18, "mu": 1.0, "gain": 0.25}
WRONG = ("no_summaries", "own_summaries", "sliding", "mean_pool", "no_mu", "mu_on_v",
         "split_softmax", "no_unit_offset", "pool_unturned", "bf16_stream")
EXACT = ("phi", "mu", "g1", "g2")   # the control leaves these as drawn: no matrix product's input
# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (
    "attention_bias", "attention_class", "chunk_size", "fp32_ln", "fp32_logits", "fp32_skip_add",
    "hidden_act", "hidden_size", "init_cutoff_factor", "init_fn", "init_std", "intermediate_size",
    "lazy_init", "max_position_embeddings", "max_seq_length", "mixedp_attn", "model_type",
    "norm_add_unit_offset", "num_attention_heads", "num_chunks", "num_hidden_layers",
    "num_key_value_heads", "num_pred_heads", "rms_norm_eps", "rope_scaling", "rope_theta",
    "tie_word_embeddings", "vocab_size", "window_size")


class Model:
    """The architecture's numbers and its tensors' shapes; draws one tensor or
    one layer at a time."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16", wrong: str = "") -> None:
        assert wrong in ("",) + WRONG
        a = self.a = arch
        self.wrong, self.seed, self.dtype = wrong, int(seed), jnp.dtype(served_dtype)
        self.d, self.n_layers = int(a["hidden_size"]), int(a["num_hidden_layers"])
        self.heads, self.kv = int(a["num_attention_heads"]), int(a["num_key_value_heads"])
        self.hd = self.d // self.heads
        self.f = int(a["intermediate_size"])
        self.eps = float(a.get("rms_norm_eps", 1e-5))
        self.theta = float(a["rope_theta"])
        self.window, self.chunk = int(a["window_size"]), int(a["chunk_size"])
        self.vocab, self.n_pred = int(a["vocab_size"]), int(a.get("num_pred_heads") or 1)
        self.v_first = 0
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    def tensor(self, name: str, shape: tuple, role: str, fan_in: int) -> np.ndarray:
        return np.asarray(base.draw(self.seed, name, shape, self.scales[role] / math.sqrt(fan_in),
                                    self.dtype, shape, (0,) * len(shape)))

    def gain(self, name: str) -> np.ndarray:
        """A norm's g, float32 inside [-gain, gain]: the four summed bytes over
        their range, then the range."""
        g = float(self.scales["gain"])
        u = jnp.float32(0.5) + base.draw(self.seed, name, (self.d,), base.BELL_STD / 1020.0,
                                         jnp.float32, (self.d,), (0,))
        return np.asarray(jnp.float32(-g) + jnp.float32(2 * g) * u)

    def embed(self) -> np.ndarray:
        return self.tensor("embed", (self.vocab, self.d), "embed", 1)

    def head(self) -> np.ndarray:
        """Block 0 of the head's `num_pred_heads` blocks: the next id's columns."""
        wide = self.n_pred * self.vocab
        return self.tensor("head", (self.d, wide), "head", self.d)[:, :self.vocab]

    def layer(self, i: int) -> dict:
        d, h, kv, hd, f, L = self.d, self.heads, self.kv, self.hd, self.f, f"layer{i}"
        return {"wq": self.tensor(f"{L}/wq", (d, h, hd), "qk", d),
                "wk": self.tensor(f"{L}/wk", (d, kv, hd), "qk", d),
                "wv": self.tensor(f"{L}/wv", (d, kv, hd), "v", d),
                "wo": self.tensor(f"{L}/wo", (h, hd, d), "o", h * hd),
                "phi": self.tensor(f"{L}/phi", (kv, hd), "phi", 1),   # a KV head's
                "mu": self.tensor(f"{L}/mu", (kv, hd), "mu", 1),
                "w_gate": self.tensor(f"{L}/w_gate", (d, f), "ffn_in", d),
                "w_up": self.tensor(f"{L}/w_up", (d, f), "ffn_in", d),
                "w_down": self.tensor(f"{L}/w_down", (f, d), "ffn_out", f),
                "g1": self.gain(f"{L}/norm1"), "g2": self.gain(f"{L}/norm2")}


# -- the forward pass ----------------------------------------------------------------

def _rope(x, theta: float):
    """x (T, H, hd), positions 0..T-1: every column turns, pairs (j, j + hd/2)."""
    hd = x.shape[-1]
    inv = jnp.asarray((1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
                      .astype(np.float32))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _norm(x, g, eps: float, wrong: str):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (g if wrong == "no_unit_offset" else 1.0 + g)


def _rnd(low: bool):
    return base._round3_whole if low else (lambda z: z)


def _stream(x, low: bool, wrong: str):
    """The stream after a sublayer: float32, as it is."""
    if low or wrong == "bf16_stream":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def eva(u, w: dict, theta: float, W: int, c: int, wrong: str = "", low: bool = False):
    """The EVA mixer on the normed stream `u` (T, d) -> (T, d): the index sets
    E (exact rows of the query's own window so far) and S (summary rows of
    every chunk of every earlier window), by their definition."""
    n, (_, h, hd), kv = u.shape[0], w["wq"].shape, w["wk"].shape[1]
    q = _rope(jnp.einsum("td,dhk->thk", u, w["wq"]), theta)
    k_raw = jnp.einsum("td,dhk->thk", u, w["wk"])
    k, v = _rope(k_raw, theta), jnp.einsum("td,dhk->thk", u, w["wv"])
    rep = functools.partial(jnp.repeat, repeats=h // kv, axis=1)
    k, k_raw, v = rep(k), rep(k_raw), rep(v)
    phi, mu = (jnp.repeat(w[x], h // kv, axis=0) for x in ("phi", "mu"))
    chunks = n // c                                             # whole chunks
    by_chunk = (chunks, c, h, hd)
    kc = (k_raw if wrong == "pool_unturned" else k)[:chunks * c].reshape(by_chunk)
    vc = v[:chunks * c].reshape(by_chunk)
    if wrong == "mean_pool":
        wt = jnp.full((chunks, c, h), 1.0 / c, jnp.float32)
    else:
        wt = jax.nn.softmax(jnp.einsum("mchd,hd->mch", kc, phi), axis=1)
    ks = jnp.einsum("mch,mchd->mhd", wt, kc) + (0.0 if wrong == "no_mu" else mu)
    vs = jnp.einsum("mch,mchd->mhd", wt, vc) + (mu if wrong == "mu_on_v" else 0.0)
    ks, vs = _rnd(low)(ks), _rnd(low)(vs)                      # the control keeps them at 3 bits
    i, t, m = jnp.arange(n)[:, None], jnp.arange(n)[None, :], jnp.arange(chunks)[None, :]
    in_e = (t <= i) & ((i - t < W) if wrong == "sliding" else (t // W == i // W))
    in_s = m < (i // W) * (W // c)
    if wrong == "own_summaries":
        in_s = m < (i + 1) // c
    if wrong == "no_summaries":
        in_s = jnp.zeros_like(in_s)
    a_e = jnp.where(in_e[None], jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd), -jnp.inf)
    a_s = jnp.where(in_s[None], jnp.einsum("qhd,mhd->hqm", q, ks) / math.sqrt(hd), -jnp.inf)
    if wrong == "split_softmax":
        p_e = jax.nn.softmax(a_e, axis=-1)
        p_s = jnp.where(in_s[None], jax.nn.softmax(jnp.where(
            jnp.any(in_s, axis=-1)[None, :, None], a_s, 0.0), axis=-1), 0.0)
    else:
        p = jax.nn.softmax(jnp.concatenate([a_e, a_s], axis=-1), axis=-1)
        p_e, p_s = p[..., :n], p[..., n:]
    o = jnp.einsum("hqk,khd->qhd", p_e, v) + jnp.einsum("hqm,mhd->qhd", p_s, vs)
    return jnp.einsum("qhd,hdo->qo", _rnd(low)(o), w["wo"])


# A sublayer is ONE compiled program a sequence length, float32 products at
# full precision.

@functools.partial(jax.jit, static_argnames=("theta", "W", "c", "eps", "wrong", "low"))
def eva_sublayer(x, w: dict, *, theta, W, c, eps, wrong, low):
    with jax.default_matmul_precision("highest"):
        u = _rnd(low)(_norm(x, w["g1"], eps, wrong))
        return _stream(x + eva(u, w, theta, W, c, wrong, low), low, wrong)


@functools.partial(jax.jit, static_argnames=("eps", "wrong", "low"))
def dense_sublayer(x, w: dict, *, eps, wrong, low):
    with jax.default_matmul_precision("highest"):
        u = _rnd(low)(_norm(x, w["g2"], eps, wrong))
        hidden = _rnd(low)(jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"]))
        return _stream(x + hidden @ w["w_down"], low, wrong)


def hidden_states(m: Model, sequences: list[np.ndarray], low: bool = False) -> list:
    """Final hidden states (before the last norm) of each sequence of ids;
    layers outermost, so each layer is drawn once and dropped."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) for ids in sequences]
    del embed
    for i in range(m.n_layers):
        w = m.layer(i)
        if low:   # the control: every kernel of a matrix product
            w = {k: (v if k in EXACT else np.asarray(base._round3_whole(v))) for k, v in w.items()}
        mixer = {k: w[k] for k in ("wq", "wk", "wv", "wo", "phi", "mu", "g1")}
        dense = {k: w[k] for k in ("w_gate", "w_up", "w_down", "g2")}
        for n, x in enumerate(xs):
            x = eva_sublayer(x, mixer, theta=m.theta, W=m.window, c=m.chunk, eps=m.eps,
                             wrong=m.wrong, low=low)
            xs[n] = dense_sublayer(x, dense, eps=m.eps, wrong=m.wrong, low=low)
        del w
    return xs


def logits(m: Model, sequences: list[np.ndarray], first_rows: list[int],
           low: bool = False) -> list[np.ndarray]:
    """Per sequence: float32 logits over the vocabulary at positions
    `first_row` onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low)
    head, g = _rnd(low)(jnp.asarray(m.head())), jnp.asarray(m.gain("norm_f"))
    with jax.default_matmul_precision("highest"):
        return [np.asarray(_rnd(low)(_norm(h[r:], g, m.eps, m.wrong)) @ head)
                for h, r in zip(hs, first_rows)]


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low: bool = False) -> list[np.ndarray]:
    return [np.asarray(jax.nn.log_softmax(jnp.asarray(z), axis=-1))
            for z in logits(m, sequences, first_rows, low)]


# -- the configuration, for the harness ------------------------------------------------

def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the benchmark:
    the published keys as the file holds them (the layers it states are the
    layers held here), and the drawn scales."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """What this file, flops/eva.py and the per-layer readers need."""
    gen = cfg["serve"]["tables"]["genserve"]
    served = cfg["assumed"]["served"]
    a = arch_from_config(cfg)
    m = Model(a, 0)
    max_ctx = int(served["max_prompt_tokens"]) + int(served["max_new_tokens"])
    rows, slots = int(gen["kv_page_tokens"]), int(gen["slots"])
    assert rows == m.window // m.chunk, "a page is a window's summary rows"
    pps = -(-max_ctx // m.window)
    return {
        "arch": a, "d_model": m.d, "layers": m.n_layers, "n_attn": m.n_layers,
        "heads": m.heads, "kv_heads": m.kv, "head_dim": m.hd, "dense_width": m.f,
        "win_tokens": m.window, "chunk": m.chunk, "summary_rows": rows,
        "vocab": m.vocab, "vocab_first": 0, "pred_heads": m.n_pred,
        "max_prompt": int(served["max_prompt_tokens"]), "max_new": int(served["max_new_tokens"]),
        "max_ctx": max_ctx, "slots": slots, "page_tokens": rows, "pages_per_slot": pps,
        "kv_pages": int(gen.get("kv_pages") or 0) or slots * pps + 1,
        "prefill_chunk": int(gen.get("prefill_chunk") or 0) or int(served["max_prompt_tokens"]),
        "weight_bytes": 2 if cfg["serve"]["model"]["dtype"] == "bfloat16" else 4,
        # What kv_reserved_pct looks up. It reckons 2 x kv_heads x head_dim values a row,
        # a page of `page_tokens` rows a "full_attention" entry and a ring of `window`
        # rows a "sliding_attention" entry: here EVERY layer keeps both, so the list
        # names each layer twice, once by each kind (nothing else reads it).
        "layer_types": ["full_attention"] * m.n_layers + ["sliding_attention"] * m.n_layers,
        "window": m.window,
    }


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

prepare = hy.prepare   # no checkpoint: the published keys as the program's config file
reference_answers = hy.reference_answers   # nothing heavy yet: the pass waits for the served tokens


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    """`reference/hybrid.py`'s statistic over this family's pass: a generated
    position's number is the RMS of its eight centred differences; the
    statistic is the largest, over the requests, of the lower quartile of a
    request's positions, beside the RMS over all positions against
    `check.rms_limit` (the number compared with `check.limit` is the larger of
    the quartile and the RMS scaled by `limit / rms_limit`)."""
    sz, ref = reference["sizes"], reference["ref"]
    seqs, rows = [], []
    for answer, inp in zip(served, reference["inputs"], strict=True):
        tokens = [int(t) for t in answer.get("tokens", [])]
        lp = answer.get("logprobs") or {}
        if len(tokens) != inp["max_new"] or answer.get("n_tokens") != len(tokens) \
                or np.shape(lp.get("ids")) != (len(tokens), LOGPROBS) \
                or np.shape(lp.get("values")) != (len(tokens), LOGPROBS):
            return float("inf"), (f"logprob_rms=inf: a request of {inp['max_new']} tokens with "
                                  f"logprobs {LOGPROBS} got {len(tokens)} tokens, logprobs of "
                                  f"shape {np.shape(lp.get('ids'))}")
        ids = np.concatenate([inp["ids"], np.asarray(tokens[:-1], np.int64)])
        if ids.min() < 0 or ids.max() >= sz["vocab"]:
            return float("inf"), "logprob_rms=inf: a served token lies outside the vocabulary"
        seqs.append(ids)
        rows.append(len(inp["ids"]) - 1)
    low = cfg["check"].get("reference_inputs") == "3-bit-mantissa"
    wrong = cfg["check"].get("wrong_reading", "")
    model = Model(sz["arch"], ref["seed"], ref["dtype"], wrong=wrong)
    t0 = time.monotonic()
    gaps = [hy.centred_gap(a, lp, 0) for a, lp in zip(served, log_probs(model, seqs, rows, low))]
    print(f"[reference] {sum(len(s) for s in seqs)} positions through {model.n_layers} layers in "
          f"{time.monotonic() - t0:.1f} s", flush=True)
    per = [np.sqrt(np.mean(g ** 2, axis=-1)) for g in gaps]
    quartile = max(float(np.quantile(p, 0.25)) for p in per)
    rms = float(np.sqrt(np.mean(np.concatenate(per) ** 2)))
    limit, rms_limit = float(cfg["check"]["limit"]), float(cfg["check"].get("rms_limit", 0) or 0)
    stat = max(quartile, rms * limit / rms_limit) if rms_limit > 0 else quartile
    by_request = ", ".join(f"{float(np.quantile(p, 0.25)):.4g}/{float(np.sqrt(np.mean(p ** 2))):.4g}"
                           for p in per)
    return stat, (f"logprob_q25={quartile:.6g} (the largest of the requests' lower quartiles of a "
                  f"position's RMS gap; limit {limit:.6g}) logprob_rms={rms:.6g}"
                  + (f" (limit {rms_limit:.6g}, as {rms * limit / rms_limit:.6g} of the first)"
                     if rms_limit > 0 else "")
                  + f" over {sum(len(p) for p in per)} generated positions of {len(served)} requests "
                  f"(quartile/RMS by request: {by_request}; widest position "
                  f"{max(float(p.max()) for p in per):.4g})"
                  + (" [the reference's matrix inputs and summary rows at 3 mantissa bits, its "
                     "stream in bfloat16: a control]" if low else "")
                  + (f" [the reference computes the wrong reading {wrong}: a control]"
                     if wrong else ""))
