"""The plain reference for the `hybrid_conv` family: a language model whose
layers are two sublayers each (an operator chosen by `layer_types`: a GATED SHORT
CONVOLUTION, or grouped-query softmax attention with an RMSNorm a head on
queries and keys and then a rotary embedding; then a SwiGLU, dense in the first
`num_dense_layers` layers and sigmoid-routed over experts with no shared one in
the rest), written down from its published `config.json` in straightforward
float32 (`jax.numpy` under `jax.default_matmul_precision("highest")`; the
experts' products, whose groups have every size, in `numpy` float32), with no
cache, no stored rows, no pages, no tiles, no dispatch and no kernel. It imports
nothing of the program. The recipe of the weights, the roundings of the control
and the check's statistic are `reference/hybrid.py`'s own functions, imported
and not edited.

THE BLOCK IS WRITTEN FROM THE PUBLIC `lfm2_moe` MODULE'S NAMES WITH NO FILE ON
THIS MACHINE TO HOLD IT AGAINST: whoever has the file can check this paragraph.
`d` = `hidden_size`, eps `norm_eps`, no bias anywhere (`conv_bias` false). `N(x;
g) = x / sqrt(mean(x^2) + eps) * g`. `x_0 = E[ids]`. Layer l, position i
(0-based), `u = N(x_i; g_op)` (`operator_norm`):

    conv layer (`layer_types[l] == "conv"`; k = `conv_L_cache`):
      [B | C | z] = u W_in                       W_in (d, 3d): `in_proj`, the thirds in that order
      b_i = B * z                                elementwise
      c_i = sum_{j=0..k-1} w[j] * b_{i-k+1+j}    w (k, d): depthwise, causal, b_t = 0 for t < 0, NO activation
      y   = (C * c_i) W_out                      W_out (d, d): `out_proj`

    attention layer (`"full_attention"`; H = `num_attention_heads` over KV = `num_key_value_heads`
    heads of hd = d / H; query head h reads KV head h // (H / KV)):
      q = u W_q (H, hd)    k_i = u W_k (KV, hd)    v_i = u W_v (KV, hd)
      q <- rope(N(q; g_q), i)    k_i <- rope(N(k_i; g_k), i)     N over a head's hd columns, ONE gain of hd for
                                                 all heads (`q_layernorm`, `k_layernorm`); the norm FIRST, then the
                                                 rotary over all hd columns, pairs (j, j + hd / 2), `rope_theta`
      a = softmax over t <= i of q[h] . k_t[h // (H / KV)] / sqrt(hd)       o[h] = sum_t a_t v_t[h // (H / KV)]
      y = concat_h(o[h]) W_o

    x_i <- x_i + y;   u2 = N(x_i; g_ffn)  (`ffn_norm`)
    l < `num_dense_layers`:   x_i <- x_i + (silu(u2 W_1) * (u2 W_3)) W_2          at `intermediate_size`
    else:  r = u2 W_r (float32, `num_experts` logits)     s = sigmoid(r)
           picks = the `num_experts_per_tok` largest of s + bias     (`use_expert_bias`: it moves picks, never weights)
           w_e = s_e / (sum over the picks of s + 1e-6) * `routed_scaling_factor`       (`norm_topk_prob`)
           x_i <- x_i + sum over the picks of w_e (silu(u2 W1_e) * (u2 W3_e)) W2_e  at `moe_intermediate_size`

`logits = N(x_i; g_f) E^T`: the final norm is the model's `embedding_norm`, the
head the embedding (`tie_word_embeddings`, true where the key is absent).

ASSUMED (the configuration file repeats this under `assumed`): the head tied;
the ORDER of `in_proj`'s thirds (B, C, then the convolved input); no activation
between the convolution and the gate; the taps' order (tap k - 1 on the current
row); the norm of q and k BEFORE the rotary and one gain of hd shared by the
heads; the rotary's pairing (halves); the score's `1 / sqrt(hd)`; SwiGLU's naming
(`w1` gate, `w3` up, `w2` down); the 1e-6 in the weights' denominator; the
router's logits, the scores and the convolution's sum in float32; the bias drawn
small; the drawn scales.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`): the names,
shapes, fan-ins and ranges are the program's (`tpuserve/models/mixers.py`
`ConvMixer._conv_tensors`, `RotaryAttention._qk_gains`, `PlainAttention.
_attention_tensors`; `hybrid_delta.RoutedExperts`; `hybrid_conv._tensors`). The
norms' gains over the stream are ones; the query/key norms' gains are float32
vectors drawn inside `qk_gain`.

THE CHECK (`compare`): `reference/hybrid.py`'s statistic (`logprob_q25` beside
`logprob_rms`, centred top-8 log-probabilities, teacher-forced on the served
tokens) over ONE full pass, a sublayer one compiled program a sequence length.
`check.reference_inputs = "3-bit-mantissa"` (a control, never a cell) rounds the
inputs of the reference's matrix products (every kernel but the router's, the
normed stream that enters a sublayer, the gated rows before `W_out`, the context
before `W_o`, the hidden rows before a down-projection) AND the rows `b` the
convolution reads to 3 explicit mantissa bits. `check.wrong_reading` (one of
`WRONG`, or a list of them; a control too) computes that wrong reading of the
layer instead: `no_history` (`c_i = w[k-1] b_i` everywhere), `step_forgets` (so
at the positions a decode step computes: the stored rows left out of a step),
`piece_forgets` (a prompt's later piece starts from zeros: the stored rows not
carried across a launch's edge), `gate_first` (C applied before the convolution),
`no_b` (`b = z`), `silu` (a SiLU on the convolution), `taps4` (a fourth tap),
`no_rope`, `no_qk_norm`, `rope_first` (the rotary before the norm),
`softmax_router`, `bias_in_weights`, `no_sum` (weights not divided by their
sum), `all_routed` (layers 0.. routed too), `dense_more` (one more dense layer).
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import spec

hy = spec.load_module("reference", "hybrid")
LOGPROBS = hy.LOGPROBS
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 1.0, "qk_gain": [1.0, 3.0], "v": 1.0, "o": 1.0, "ffn_in": 1.0,
    "ffn_out": 1.0, "router": 1.0, "router_bias": 0.02, "conv_in": 1.0, "conv_tap": 1.0,
    "conv_out": 1.0}
ROUTE_EPS = 1e-6
# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (
    "model_type", "conv_L_cache", "conv_bias", "hidden_size", "intermediate_size", "layer_types",
    "max_position_embeddings", "moe_intermediate_size", "norm_eps", "norm_topk_prob",
    "num_attention_heads", "num_dense_layers", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "rope_parameters", "routed_scaling_factor",
    "use_expert_bias", "vocab_size", "tie_word_embeddings")
# What the control leaves alone: the router decides in float32 in the program
# too, and the float32 vectors are no matrix product's input.
EXACT = ("router", "e_bias", "q_norm", "k_norm")
WRONG = ("no_history", "step_forgets", "piece_forgets", "gate_first", "no_b", "silu", "taps4",
         "no_rope", "no_qk_norm", "rope_first", "softmax_router", "bias_in_weights", "no_sum",
         "all_routed", "dense_more")


# -- weights by recipe -------------------------------------------------------------

class Model:
    """The architecture's numbers and its tensors' shapes; draws one tensor
    or one layer at a time. `wrong`: a wrong reading (module docstring);
    `chunk`: the launch's rows, which `piece_forgets` alone reads."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16", wrong: str = "",
                 chunk: int = 0) -> None:
        assert wrong in ("",) + WRONG, wrong
        a = self.a = arch
        self.wrong, self.chunk = wrong, int(chunk)
        self.seed, self.dtype = int(seed), jnp.dtype(served_dtype)
        self.d, self.n_layers = int(a["hidden_size"]), int(a["num_hidden_layers"])
        self.kinds = list(a["layer_types"])
        assert len(self.kinds) == self.n_layers
        self.eps = float(a.get("norm_eps", 1e-5))
        self.conv_k = int(a.get("conv_L_cache", 3)) + (wrong == "taps4")
        self.heads, self.kv = int(a["num_attention_heads"]), int(a["num_key_value_heads"])
        self.hd = int(a.get("head_dim") or self.d // self.heads)
        rope = a.get("rope_parameters") or {}
        self.theta = float(rope["rope_theta"])
        self.n_dense = {"all_routed": 0, "dense_more": int(a.get("num_dense_layers", 0)) + 1}.get(
            wrong, int(a.get("num_dense_layers", 0)))
        self.ffn = int(a["intermediate_size"])
        self.e_full, self.top_k = int(a["num_experts"]), int(a["num_experts_per_tok"])
        self.f = int(a["moe_intermediate_size"])
        assert a.get("use_expert_bias", True), "use_expert_bias = false is not served"
        self.norm_topk = bool(a.get("norm_topk_prob", True)) and wrong != "no_sum"
        self.route_scale = float(a.get("routed_scaling_factor") or 1.0)
        self.vocab = int(a["vocab_size"])
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    def tensor(self, name: str, shape, role: str, fan_in: int) -> np.ndarray:
        shape = tuple(shape)
        return np.asarray(hy.draw(self.seed, name, shape, self.scales[role] / math.sqrt(fan_in),
                                  self.dtype, shape, (0,) * len(shape)))

    def vector(self, name: str, shape, lo: float, hi: float) -> np.ndarray:
        """A float32 vector inside [lo, hi]: the four summed bytes over their
        range, then the range."""
        shape = tuple(shape)
        u = jnp.float32(0.5) + hy.draw(self.seed, name, shape, hy.BELL_STD / 1020.0, jnp.float32,
                                       shape, (0,) * len(shape))
        return np.asarray(jnp.float32(lo) + jnp.float32(hi - lo) * u)

    def embed(self) -> np.ndarray:
        return self.tensor("embed", (self.vocab, self.d), "embed", 1)

    def head(self) -> np.ndarray:
        if self.a.get("tie_word_embeddings", True):
            return self.embed().T
        return self.tensor("head", (self.d, self.vocab), "head", self.d)

    def layer(self, i: int) -> dict:
        t, L, d, s = self.tensor, f"layer{i}", self.d, self.scales
        if self.kinds[i] == "full_attention":
            h, kv, hd = self.heads, self.kv, self.hd
            w = {"wq": t(f"{L}/wq", (d, h, hd), "qk", d), "wk": t(f"{L}/wk", (d, kv, hd), "qk", d),
                 "wv": t(f"{L}/wv", (d, kv, hd), "v", d), "wo": t(f"{L}/wo", (h, hd, d), "o", h * hd),
                 "q_norm": self.vector(f"{L}/q_norm", (hd,), *s["qk_gain"]),
                 "k_norm": self.vector(f"{L}/k_norm", (hd,), *s["qk_gain"])}
        else:
            k = self.conv_k
            w = {"w_in": t(f"{L}/w_in", (d, 3 * d), "conv_in", d),
                 "conv_w": t(f"{L}/conv_w", (k, d), "conv_tap", k),
                 "w_out": t(f"{L}/w_out", (d, d), "conv_out", d)}
        if i < self.n_dense:
            for name in ("w1", "w3"):
                w[name] = t(f"{L}/{name}", (d, self.ffn), "ffn_in", d)
            w["w2"] = t(f"{L}/w2", (self.ffn, d), "ffn_out", self.ffn)
            return w
        e, f, b3 = self.e_full, self.f, 3.0 * s["router_bias"]
        w["router"] = t(f"{L}/router", (d, e), "router", d)
        w["e_bias"] = self.vector(f"{L}/e_bias", (e,), -b3, b3)
        for name in ("e_gate", "e_up"):
            w[name] = t(f"{L}/{name}", (e, d, f), "ffn_in", d)
        w["e_down"] = t(f"{L}/e_down", (e, f, d), "ffn_out", f)
        return w


# -- the forward pass ----------------------------------------------------------------

# One compiled program a sublayer and a sequence length (not one an operation).

def _rnd(low: bool):
    return hy._round3_whole if low else (lambda z: z)


@functools.partial(jax.jit, static_argnums=(0, 1))
def conv_sublayer(dims: tuple, low: bool, w: dict, x, reach):
    """`x + short_conv(N(x))` over a whole sequence x (T, d): `b` and its
    convolution by k shifted sums. `reach` (T,): the earlier rows a position
    may read, k - 1 everywhere unless a wrong reading forgets some. `low`: the
    control's roundings, `b` among them."""
    k, eps, wrong = dims
    t, d, rnd = x.shape[0], x.shape[1], _rnd(low)
    with jax.default_matmul_precision("highest"):
        u = rnd(hy._rms(x, eps))
        bcz = u @ w["w_in"]
        B, C, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
        b = z if wrong == "no_b" else B * z
        if wrong == "gate_first":
            b = C * b
        padded = jnp.concatenate([jnp.zeros((k - 1, d), b.dtype), rnd(b)], axis=0)
        c = sum(w["conv_w"][j] * padded[j:j + t] * (reach >= k - 1 - j)[:, None] for j in range(k))
        if wrong == "silu":
            c = jax.nn.silu(c)
        y = c if wrong == "gate_first" else C * c
        return x + rnd(y) @ w["w_out"]


def _rope(x, theta: float):
    """x (T, heads, hd) turned by its row's position, pairs (j, j + hd / 2)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0])[:, None] * theta ** (-jnp.arange(half) / half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    return jnp.concatenate([x[..., :half] * cos - x[..., half:] * sin,
                            x[..., half:] * cos + x[..., :half] * sin], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def attention_sublayer(dims: tuple, low: bool, w: dict, x):
    """`x + attention(N(x))`: one full causal pass; q and k normed by head,
    then turned by position."""
    heads, kv, hd, theta, eps, wrong = dims
    t, rnd = x.shape[0], _rnd(low)

    def placed(z, g):
        if wrong == "rope_first":
            return hy._rms(_rope(z, theta), eps) * g
        z = z if wrong == "no_qk_norm" else hy._rms(z, eps) * g
        return z if wrong == "no_rope" else _rope(z, theta)

    with jax.default_matmul_precision("highest"):
        u = rnd(hy._rms(x, eps))
        q = placed(jnp.einsum("td,dhk->thk", u, w["wq"]), w["q_norm"])
        k = placed(jnp.einsum("td,dhk->thk", u, w["wk"]), w["k_norm"])
        k = jnp.repeat(k, heads // kv, axis=1)
        v = jnp.repeat(jnp.einsum("td,dhk->thk", u, w["wv"]), heads // kv, axis=1)
        see = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        o = jnp.einsum("hqk,khd->qhd",
                       jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1), v)
        return x + jnp.einsum("qhd,hdo->qo", rnd(o), w["wo"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _normed(eps: float, low: bool, x):
    return _rnd(low)(hy._rms(x, eps))


@functools.partial(jax.jit, static_argnums=(0,))
def _swiglu(low: bool, u, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return _rnd(low)(jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def picks(m: Model, w: dict, u: np.ndarray):
    """The router on the normed rows `u` (float32, never rounded) -> (the
    picked experts (T, k), their weights)."""
    with jax.default_matmul_precision("highest"):
        r = np.asarray(jnp.asarray(u) @ jnp.asarray(w["router"])).astype(np.float32)
    if m.wrong == "softmax_router":
        s = np.exp(r - r.max(axis=-1, keepdims=True))
        s = (s / s.sum(axis=-1, keepdims=True)).astype(np.float32)
    else:
        s = (1.0 / (1.0 + np.exp(-r))).astype(np.float32)
    chosen_by = s + w["e_bias"][None, :]
    top = np.argsort(-chosen_by, axis=-1, kind="stable")[:, :m.top_k]
    wt = np.take_along_axis(chosen_by if m.wrong == "bias_in_weights" else s, top, axis=-1)
    if m.norm_topk:
        wt = wt / (wt.sum(axis=-1, keepdims=True) + np.float32(ROUTE_EPS))
    return top, wt * np.float32(m.route_scale)


def experts(m: Model, w: dict, u: np.ndarray, routed_on: np.ndarray, low: bool = False) -> np.ndarray:
    """The routed sum, in numpy float32: each expert's body on the rows that
    picked it. `routed_on` (T, d) is what the router reads (never rounded), `u`
    what the experts read."""
    top, wt = picks(m, w, routed_on)
    rnd = hy._round3 if low else (lambda z: z)
    y = np.zeros_like(u)
    for e in range(m.e_full):
        tok, slot = np.nonzero(top == e)
        if tok.size == 0:
            continue
        g = u[tok] @ w["e_gate"][e]
        h = g / (1.0 + np.exp(-g)) * (u[tok] @ w["e_up"][e])
        y[tok] += wt[tok, slot][:, None] * (rnd(h.astype(np.float32)) @ w["e_down"][e])
    return y


def _reach(m: Model, length: int, prompt: int) -> np.ndarray:
    """The earlier rows each position's convolution may read: k - 1, unless
    the wrong reading forgets the stored rows somewhere."""
    full, at = m.conv_k - 1, np.arange(length)
    if m.wrong == "no_history":
        return np.zeros(length, np.int32)
    if m.wrong == "step_forgets":
        return np.where(at >= prompt, 0, full).astype(np.int32)
    if m.wrong == "piece_forgets" and m.chunk:
        return np.where(at < prompt, np.minimum(at % m.chunk, full), full).astype(np.int32)
    return np.full(length, full, np.int32)


def hidden_states(m: Model, sequences: list[np.ndarray], low: bool = False,
                  prompts: list[int] | None = None) -> list:
    """Final hidden states (before the last norm) of each sequence of ids;
    layers outermost, so each layer is drawn once and dropped. `prompts`: each
    sequence's prompt length (what the cache-forgetting wrong readings go by)."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) for ids in sequences]
    del embed
    prompts = prompts or [len(s) for s in sequences]
    reach = [jnp.asarray(_reach(m, len(s), p)) for s, p in zip(sequences, prompts)]
    took = {"conv": 0.0, "full_attention": 0.0, "ffn": 0.0}
    for i in range(m.n_layers):
        w = m.layer(i)
        if low:  # the control: every kernel but the router's
            w = {k: (v if k in EXACT else np.asarray(hy._round3_whole(v))) for k, v in w.items()}
        kind = m.kinds[i]
        mixer = {k: jnp.asarray(v) for k, v in w.items()
                 if k in ("w_in", "conv_w", "w_out", "wq", "wk", "wv", "wo", "q_norm", "k_norm")}
        dense = [jnp.asarray(w[k]) for k in ("w1", "w3", "w2")] if i < m.n_dense else None
        for n, x in enumerate(xs):
            t0 = time.monotonic()
            if kind == "full_attention":
                x = attention_sublayer((m.heads, m.kv, m.hd, m.theta, m.eps, m.wrong), low, mixer, x)
            else:
                x = conv_sublayer((m.conv_k, m.eps, m.wrong), low, mixer, x, reach[n])
            x.block_until_ready()
            t1 = time.monotonic()
            u = _normed(m.eps, low, x)
            if dense:
                y = _swiglu(low, u, *dense)
            else:
                u = np.asarray(u)
                y = jnp.asarray(experts(
                    m, w, u, np.asarray(_normed(m.eps, False, x)) if low else u, low))
            xs[n] = (x + y).block_until_ready()
            took[kind] += t1 - t0
            took["ffn"] += time.monotonic() - t1
        del w, mixer, dense
    print("[reference] " + str(sum(len(s) for s in sequences)) + f" tokens through {m.n_layers} "
          "layers: " + ", ".join(f"{k} {v:.1f} s" for k, v in took.items()), flush=True)
    return xs


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the vocabulary at positions `first_row`
    onwards (row p predicts position p + 1); a sequence's prompt ends at its
    `first_row`."""
    hs = hidden_states(m, sequences, low, [r + 1 for r in first_rows])
    head = jnp.asarray(m.head())
    with jax.default_matmul_precision("highest"):
        return [np.asarray(jax.nn.log_softmax(hy._rms(h[r:], m.eps) @ head, axis=-1))
                for h, r in zip(hs, first_rows)]


# -- the configuration, for the harness ------------------------------------------------

def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the benchmark:
    the published keys as the file holds them (`reduced` cuts the depth alone:
    every layer kept is whole), and the drawn scales."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    arch.setdefault("tie_word_embeddings", True)
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """What this file, flops/hybrid_conv.py and the per-layer readers need
    (`flops/hybrid.py`'s names where the quantity is the same: `n_mamba` is the
    count of RECURRENT layers, which `ssm_window.tokens_per_launch` and the
    `ssm_*` readers divide by)."""
    gen = cfg["serve"]["tables"]["genserve"]
    served = cfg["assumed"]["served"]
    a = arch_from_config(cfg)
    m = Model(a, 0)
    max_ctx = int(served["max_prompt_tokens"]) + int(served["max_new_tokens"])
    page, slots = int(gen["kv_page_tokens"]), int(gen["slots"])
    wb = 2 if cfg["serve"]["model"]["dtype"] == "bfloat16" else 4
    n_a = m.kinds.count("full_attention")
    n_m = m.n_layers - n_a
    return {
        "arch": a, "d_model": m.d, "layers": m.n_layers, "n_mamba": n_m, "n_attn": n_a,
        "n_dense": m.n_dense, "n_expert": m.n_layers - m.n_dense, "ffn_width": m.ffn,
        "heads": m.heads, "kv_heads": m.kv, "head_dim": m.hd,
        "conv_kernel": m.conv_k, "conv_channels": m.d,
        "state_bytes_per_slot": n_m * (m.conv_k - 1) * m.d * wb,
        "num_experts": m.e_full, "experts_held": m.e_full, "top_k": m.top_k,
        "expert_width": m.f, "shared_width": 0, "tied": bool(a["tie_word_embeddings"]),
        "vocab": m.vocab, "vocab_first": 0,
        # what kv_reserved_pct (pages only) and the generic readers look up
        "layer_types": ["full_attention"] * n_a, "window": 0,
        "max_prompt": int(served["max_prompt_tokens"]), "max_new": int(served["max_new_tokens"]),
        "max_ctx": max_ctx, "slots": slots, "page_tokens": page,
        "pages_per_slot": -(-max_ctx // page),
        "kv_pages": int(gen.get("kv_pages") or 0) or slots * -(-max_ctx // page) + 1,
        "prefill_chunk": int(gen.get("prefill_chunk") or 0) or int(served["max_prompt_tokens"]),
        "weight_bytes": wb,
    }


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

prepare = hy.prepare   # no checkpoint: the published keys as the program's config file
reference_answers = hy.reference_answers   # nothing heavy yet: the pass waits for the served tokens


def _statistic(served: list[dict], lps: list[np.ndarray], cfg: dict) -> tuple[float, str]:
    """`reference/hybrid.py`'s statistic of the served answers against the
    reference's log-probabilities `lps` -> (the number compared with
    `check.limit`, the line's part)."""
    gaps = [hy.centred_gap(a, lp, 0) for a, lp in zip(served, lps)]
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
                  f"{max(float(p.max()) for p in per):.4g})")


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    """`reference/hybrid.py`'s statistic over this family's pass: a generated
    position's number is the RMS of its eight centred differences; the
    statistic is the largest, over the requests, of the lower quartile of a
    request's positions, beside the RMS over all positions against
    `check.rms_limit` (the number compared with `check.limit` is the larger of
    the quartile and the RMS scaled by `limit / rms_limit`). With
    `check.wrong_reading` a list, one pass a reading: the line holds each and
    the number returned is the SMALLEST (every reading has to fail)."""
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
    out = []
    for reading in wrong if isinstance(wrong, list) else [wrong]:
        model = Model(sz["arch"], ref["seed"], ref["dtype"], reading, sz["prefill_chunk"])
        stat, line = _statistic(served, log_probs(model, seqs, rows, low), cfg)
        out.append((stat, (f"[the reference computes the WRONG reading {reading}: a control] "
                           if reading else "") + line))
    stat = min(s for s, _ in out)
    return stat, " ;; ".join(line for _, line in out) + (
        " [the reference's matrix inputs and the convolution's rows at 3 mantissa bits: a "
        "control]" if low else "")
