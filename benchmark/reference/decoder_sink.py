"""The plain reference for the `decoder_sink` family: a decoder-only language
model of window and global attention layers, written down from its published
`config.json` in straightforward float32 (`jax.numpy` under
`jax.default_matmul_precision("highest")`; the experts' products, whose groups
have every size, in `numpy` float32), with no cache, no batching and no kernel:
one full causal pass, the window and the sink as a mask and an extra term. It
imports nothing of the program.

THE LAYER (x the residual stream, d = `hidden_size`, eps `layernorm_epsilon`,
no biases, RMSNorm with a gain before each sublayer and before the untied
head). Layer l is of kind t: GLOBAL where `hybrid_layer_pattern[l]` is 0
(`num_attention_heads` H query heads on `num_key_value_heads` KV heads, keys
`head_dim` dk and values `v_head_dim` dv wide, rotary base `rope_theta`, a sink
iff `add_full_attention_sink_bias`), WINDOW where it is 1 (the same under
`swa_*`, `swa_rope_theta`, `sliding_window` W, `add_swa_attention_sink_bias`).
With G = H / KV_t and dr = int(dk x `partial_rotary_factor`):

    u = RMSNorm(x; g1)
    q = u W_q  (H, dk)     k = u W_k  (KV_t, dk)     v = `attention_value_scale` (u W_v)  (KV_t, dv)
    q, k: columns [0, dr) turn by the token's position, pairs (i, i + dr/2),
          inverse frequencies theta_t ** (-2i / dr); columns [dr, dk) pass
    a[h, j] = q[h] . k_j[h // G] / sqrt(dk)   for keys j <= i, and i - j < W in a window layer
    no sink:  p[h, j] = exp(a[h, j] - m) / sum_j' exp(a[h, j'] - m)
    sink s:   p[h, j] = exp(a[h, j] - m) / (exp(s[h] - m) + sum_j' exp(a[h, j'] - m)),
              m = max(s[h], max_j a[h, j])
    o[h] = sum_j p[h, j] v_j[h // G]          (H, dv)
    x = x + concat_h(o[h]) W_o                W_o: (H x dv, d)

The sink is a logit with no value: it takes mass and adds nothing, so a row's
weights sum to less than 1. FEED-FORWARD on u2 = RMSNorm(x; g2): where
`moe_layer_freq[l]` is 0, `W_down (silu(u2 W_gate) * u2 W_up)` at
`intermediate_size`; where it is 1, r = u2 W_r over all `n_routed_experts` in
float32, sc = sigmoid(r), the `num_experts_per_tok` largest of sc + b (b the
selection bias: `noaux_tc`, one group; it moves picks, never weights), weights
sc[e] / (their sum) (`norm_topk_prob`) times `routed_scaling_factor` (null: 1),
y = sum_e w_e SwiGLU_e(u2) at `moe_intermediate_size` over the experts HELD
here; there is no shared expert (`n_shared_experts` null). x = x + y.

THE SHARE (`share` in the architecture): the same as the program is given. Of
the router's experts only `experts_held = [first, count]` are here: picks on
the others add nothing. `vocab_rows = [first, count]`: ids, logits and
log-probabilities are over those rows. Attention, the router, the norms and
the dense layer are whole.

ASSUMED, because the published config has no key for it (the configuration
file repeats this under `assumed`): the value scale multiplies v where it is
made (before a cache would hold it; after it is the same function in exact
arithmetic); `attention_chunk_size` names nothing beyond `sliding_window`; no
query/key norm; the rotary pairs (i, i + dr/2) over the LEADING dr columns;
query head h reads KV head h // G; i - j < W, so a row sees W keys with itself;
the sink in float32.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`), the same few
lines as `tpuserve/models/seeded.py`, written down again: a tensor's key is
the first four bytes of blake2s(f"{seed}/{name}"); element i of the WHOLE
tensor gets the word fmix32(i * 0x9E3779B1 + key); the word's four bytes are
summed, centred by 510, converted to float32, multiplied once by
float32(std / 147.80...) and rounded to the served type. `std` is the role's
scale over sqrt(fan-in). The float32 vectors (`e_bias`, `sink`) are the four
summed bytes over their range (0 to 1) mapped into the role's range:
[-3 x `router_bias`, 3 x `router_bias`], [`sink_low`, `sink_high`]. A layer is
drawn alone and dropped after use: the reference never holds the model.

THE CHECK (`compare`): `reference/decoder.py`'s. Each request of the sample was
served greedily with `logprobs` 8. The reference runs ONE full forward pass over
the prompt and the served tokens and reads, at every generated position, its own
log-probabilities of the eight ids the server named. Both sides are centred
(less their mean over the eight); the statistic is the RMS of the differences
over every generated position of every request (`logprob_rms`).
`check.reference_inputs = "3-bit-mantissa"` (a control, never a cell) rounds the
inputs of the reference's matrix products (every layer's kernels but the
router's, the normed stream that enters a block, the experts' hidden rows) to 3
explicit mantissa bits, float8's: the nearest precision below the served
bfloat16.
"""

from __future__ import annotations

import functools
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

BELL_STD = math.sqrt(4 * (256 ** 2 - 1) / 12.0)
LOGPROBS = 8
DEFAULT_SCALES = {"embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "gate": 1.0, "o": 1.0,
                  "ffn_in": 1.0, "ffn_out": 1.0, "router": 1.0, "router_bias": 0.02,
                  "sink_low": 8.0, "sink_high": 12.0}
GLOBAL, WINDOW = "global", "window"


# -- weights by recipe -------------------------------------------------------------

def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _draw(key, std, shape: tuple, served_dtype, full_shape: tuple, start: tuple):
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for axis in range(len(shape) - 1, -1, -1):
        coord = jax.lax.broadcasted_iota(jnp.uint32, shape, axis) + jnp.uint32(start[axis])
        idx = idx + coord * jnp.uint32(stride)
        stride *= full_shape[axis]
    h = _fmix32(idx * jnp.uint32(0x9E3779B1) + key)
    s = (h & 255) + ((h >> 8) & 255) + ((h >> 16) & 255) + (h >> 24)
    centred = (s.astype(jnp.int32) - 510).astype(jnp.float32)
    return (centred * std).astype(served_dtype).astype(jnp.float32)


_draw_compiled = jax.jit(_draw, static_argnums=(2, 3, 4, 5))  # one fused pass over every core


def draw(seed: int, name: str, shape: tuple, std: float, served_dtype,
         full_shape: tuple, start: tuple) -> jax.Array:
    """The block of tensor `name` at `start` of `full_shape`, as float32
    holding the served type's values (header)."""
    key = int.from_bytes(hashlib.blake2s(f"{int(seed)}/{name}".encode()).digest()[:4], "little")
    return _draw_compiled(jnp.uint32(key), jnp.float32(std / BELL_STD), tuple(shape),
                          jnp.dtype(served_dtype), tuple(full_shape), tuple(start))


class Model:
    """The architecture's numbers and its tensors' shapes; draws one tensor
    or one layer at a time."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        a = self.a = arch
        self.seed, self.dtype = int(seed), jnp.dtype(served_dtype)
        self.d = int(a["hidden_size"])
        self.n_layers = int(a["num_hidden_layers"])
        self.eps = float(a.get("layernorm_epsilon", 1e-6))
        self.kinds = [WINDOW if int(p) else GLOBAL for p in a["hybrid_layer_pattern"]]
        self.sparse = [bool(int(f)) for f in a["moe_layer_freq"]]
        assert len(self.kinds) == len(self.sparse) == self.n_layers

        def by_kind(key, cast=int):
            return {GLOBAL: cast(a[key]), WINDOW: cast(a.get(f"swa_{key}", a[key]))}

        self.heads, self.kv = by_kind("num_attention_heads"), by_kind("num_key_value_heads")
        self.dk, self.dv = by_kind("head_dim"), by_kind("v_head_dim")
        self.theta = by_kind("rope_theta", float)
        self.dr = {t: int(dk * float(a.get("partial_rotary_factor", 1.0)))
                   for t, dk in self.dk.items()}
        self.window = int(a.get("sliding_window") or 0)
        self.sink = {GLOBAL: bool(a.get("add_full_attention_sink_bias", False)),
                     WINDOW: bool(a.get("add_swa_attention_sink_bias", False))}
        self.v_scale = float(a.get("attention_value_scale") or 1.0)
        self.e_full = int(a.get("n_routed_experts", 0))
        self.top_k = int(a.get("num_experts_per_tok", 0))
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.e_full])
        self.v_full = int(a["vocab_size"])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.v_full])
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    def tensor(self, name: str, shape, full, start, role: str, fan_in: int) -> np.ndarray:
        return np.asarray(draw(self.seed, name, tuple(shape), self.scales[role] / math.sqrt(fan_in),
                               self.dtype, tuple(full), tuple(start)))

    def vector(self, name: str, n: int, low: float, high: float) -> np.ndarray:
        """A float32 vector inside [low, high]: the four summed bytes over their
        range, then the range."""
        u = jnp.float32(0.5) + draw(self.seed, name, (n,), BELL_STD / 1020.0, jnp.float32,
                                    (n,), (0,))
        return np.asarray(jnp.float32(low) + jnp.float32(high - low) * u)

    def embed(self) -> np.ndarray:
        return self.tensor("embed", (self.vocab, self.d), (self.v_full, self.d),
                           (self.v_first, 0), "embed", 1)

    def head(self) -> np.ndarray:
        return self.tensor("head", (self.d, self.vocab), (self.d, self.v_full),
                           (0, self.v_first), "head", self.d)

    def layer(self, i: int) -> dict:
        a, d, L, t = self.a, self.d, f"layer{i}", self.kinds[i]
        h, kv, dk, dv = self.heads[t], self.kv[t], self.dk[t], self.dv[t]

        def whole(name, shape, role, fan_in):
            return self.tensor(f"{L}/{name}", shape, shape, (0,) * len(shape), role, fan_in)

        w = {"wq": whole("wq", (d, h, dk), "qk", d), "wk": whole("wk", (d, kv, dk), "qk", d),
             "wv": whole("wv", (d, kv, dv), "v", d), "wo": whole("wo", (h, dv, d), "o", h * dv)}
        if self.sink[t]:
            w["sink"] = self.vector(f"{L}/sink", h, self.scales["sink_low"],
                                    self.scales["sink_high"])
        if not self.sparse[i]:
            f = int(a["intermediate_size"])
            w["w_gate"] = whole("w_gate", (d, f), "ffn_in", d)
            w["w_up"] = whole("w_up", (d, f), "ffn_in", d)
            w["w_down"] = whole("w_down", (f, d), "ffn_out", f)
            return w
        e, ec, e0, f = self.e_full, self.e_count, self.e_first, int(a["moe_intermediate_size"])
        b3 = 3.0 * self.scales["router_bias"]
        w["router"] = whole("router", (d, e), "router", d)
        w["e_bias"] = self.vector(f"{L}/e_bias", e, -b3, b3)
        w["e_gate"] = self.tensor(f"{L}/e_gate", (ec, d, f), (e, d, f), (e0, 0, 0), "ffn_in", d)
        w["e_up"] = self.tensor(f"{L}/e_up", (ec, d, f), (e, d, f), (e0, 0, 0), "ffn_in", d)
        w["e_down"] = self.tensor(f"{L}/e_down", (ec, f, d), (e, f, d), (e0, 0, 0), "ffn_out", f)
        return w


# -- the forward pass ----------------------------------------------------------------

KERNELS_NOT_ROUNDED = ("router", "e_bias", "sink")   # the control leaves these as drawn


def _rope(x, theta: float, dim: int):
    """x (T, H, width), positions 0..T-1: the first `dim` columns turn in pairs
    (i, i + dim/2), the rest pass."""
    inv = jnp.asarray((1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
                      .astype(np.float32))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _round3(x: np.ndarray) -> np.ndarray:
    """float32 rounded to 3 explicit mantissa bits (nearest, ties to even)."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    bits = bits + np.uint32((1 << 19) - 1) + ((bits >> np.uint32(20)) & np.uint32(1))
    return (bits & np.uint32(0xFFF00000)).view(np.float32)


@jax.jit
def _round3_whole(x):
    """The same rounding for a whole tensor of kernels, in one fused pass."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32((1 << 19) - 1) + ((bits >> 20) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFF00000), jnp.float32)


def attention(u, w: dict, theta: float, dr: int, window: int, v_scale: float):
    """One attention on the normed stream `u` (T, d): the layer's tensors `w`
    (their shapes say the heads and the widths; a `sink` where the kind has
    one), the rotary base, the columns that turn, the window (0: global)."""
    n, (_, h, dk), kv = u.shape[0], w["wq"].shape, w["wk"].shape[1]
    q = _rope(jnp.einsum("td,dhk->thk", u, w["wq"]), theta, dr)
    k = _rope(jnp.einsum("td,dhk->thk", u, w["wk"]), theta, dr)
    v = v_scale * jnp.einsum("td,dhk->thk", u, w["wv"])
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    dist = jnp.arange(n)[:, None] - jnp.arange(n)[None, :]
    see = dist >= 0
    if window:
        see = see & (dist < window)
    a = jnp.where(see[None], jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dk), -jnp.inf)
    if "sink" in w:   # a logit with no value: the denominator's extra term
        s = jnp.asarray(w["sink"])[:, None]                          # (H, 1)
        top = jnp.maximum(jnp.max(a, axis=-1), s)                    # (H, T)
        e = jnp.exp(a - top[..., None])
        p = e / (jnp.exp(s - top) + jnp.sum(e, axis=-1))[..., None]
    else:
        p = jax.nn.softmax(a, axis=-1)
    return jnp.einsum("qhd,hdo->qo", jnp.einsum("hqk,khd->qhd", p, v), w["wo"])


# A sublayer is ONE compiled program a sequence length (the host compiles a
# few programs where op by op it compiled hundreds: on a machine with no
# compile cache that was most of the pass), float32 products at full precision.

def _normed(x, eps: float, low: bool):
    u = _rms(x, eps)
    return _round3_whole(u) if low else u


@functools.partial(jax.jit, static_argnames=("theta", "dr", "window", "v_scale", "eps", "low"))
def attention_sublayer(x, w: dict, *, theta, dr, window, v_scale, eps, low):
    with jax.default_matmul_precision("highest"):
        return x + attention(_normed(x, eps, low), w, theta, dr, window, v_scale)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def dense_sublayer(x, w_gate, w_up, w_down, *, eps, low):
    with jax.default_matmul_precision("highest"):
        return x + _swiglu(_normed(x, eps, low), w_gate, w_up, w_down)


normed = jax.jit(_normed, static_argnames=("eps", "low"))


def picks(m: Model, w: dict, u: np.ndarray):
    """The experts each token picks and their weights: sigmoid scores of the
    float32 logits, the `num_experts_per_tok` largest of score + bias,
    weighted by the score alone over the picks' own sum."""
    a = m.a
    with jax.default_matmul_precision("highest"):
        r = np.asarray(jnp.asarray(u) @ jnp.asarray(w["router"]))
    sc = 1.0 / (1.0 + np.exp(-r))
    top = np.argsort(-(sc + w["e_bias"][None, :]), axis=-1, kind="stable")[:, :m.top_k]
    wt = np.take_along_axis(sc, top, axis=-1)
    if a.get("norm_topk_prob", True):
        wt = wt / wt.sum(axis=-1, keepdims=True)
    return top, wt * np.float32(a.get("routed_scaling_factor") or 1.0)


def experts(m: Model, w: dict, u: np.ndarray, low_precision: bool = False) -> np.ndarray:
    """The held experts' part of the routed sum, in numpy float32: each held
    expert over the tokens that picked it."""
    top, wt = picks(m, w, u)
    y = np.zeros_like(u)
    rnd = _round3 if low_precision else (lambda z: z)
    e_gate, e_up, e_down = w["e_gate"], w["e_up"], w["e_down"]  # rounded by the caller
    for local in range(m.e_count):
        tok, slot = np.nonzero(top == m.e_first + local)
        if tok.size == 0:
            continue
        x = rnd(u[tok])
        gate = x @ e_gate[local]
        h = (gate / (1.0 + np.exp(-gate))) * (x @ e_up[local])
        y[tok] += wt[tok, slot][:, None] * (rnd(h) @ e_down[local])
    return y


ATTENTION_TENSORS = ("wq", "wk", "wv", "wo", "sink")


def hidden_states(m: Model, sequences: list[np.ndarray], low_precision: bool = False) -> list:
    """Final hidden states (before the last norm) of each sequence of held-row
    ids; layers outermost, so each layer is drawn once and dropped."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) for ids in sequences]
    del embed
    for i in range(m.n_layers):
        w, t = m.layer(i), m.kinds[i]
        if low_precision:  # the control: every kernel but the router's and the vectors
            w = {k: (v if k in KERNELS_NOT_ROUNDED else np.asarray(_round3_whole(v)))
                 for k, v in w.items()}
        mixer = {k: w[k] for k in ATTENTION_TENSORS if k in w}
        for n, x in enumerate(xs):
            x = attention_sublayer(x, mixer, theta=m.theta[t], dr=m.dr[t],
                                   window=m.window if t == WINDOW else 0, v_scale=m.v_scale,
                                   eps=m.eps, low=low_precision)
            if m.sparse[i]:
                u = np.asarray(normed(x, eps=m.eps, low=low_precision))
                xs[n] = x + jnp.asarray(experts(m, w, u, low_precision))
            else:
                xs[n] = dense_sublayer(x, w["w_gate"], w["w_up"], w["w_down"], eps=m.eps,
                                       low=low_precision)
        del w
    return xs


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low_precision: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the held vocabulary rows at positions
    `first_row` onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low_precision)
    head = jnp.asarray(m.head())
    with jax.default_matmul_precision("highest"):
        return [np.asarray(jax.nn.log_softmax(_rms(h[r:], m.eps) @ head, axis=-1))
                for h, r in zip(hs, first_rows)]


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (
    "model_type", "attention_bias", "attention_chunk_size", "attention_value_scale",
    "attention_projection_layout", "add_full_attention_sink_bias", "add_swa_attention_sink_bias",
    "swa_num_key_value_heads", "swa_num_attention_heads", "swa_head_dim", "swa_v_head_dim",
    "head_dim", "hidden_act", "hidden_size", "hybrid_block_size", "hybrid_layer_pattern",
    "intermediate_size", "layernorm_epsilon", "max_position_embeddings", "moe_intermediate_size",
    "moe_layer_freq", "n_group", "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "partial_rotary_factor", "rope_scaling", "rope_theta", "routed_scaling_factor",
    "scoring_func", "sliding_window", "sliding_window_size", "swa_rope_theta",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim", "vocab_size")


def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the benchmark:
    the published keys, with the counts that the file states as HELD HERE
    (`reduced`: experts, vocabulary rows) put back to the published counts of
    `published` and the held part said under `share`, as the program and this
    reference read it."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    pub, held = cfg.get("published", {}), cfg.get("deployment_share", {})
    share = {}
    if "n_routed_experts" in pub:
        share["experts_held"] = [int(held.get("experts_first", 0)), int(cfg["n_routed_experts"])]
        arch["n_routed_experts"] = int(pub["n_routed_experts"])
    if "vocab_size" in pub:
        share["vocab_rows"] = [int(held.get("vocab_first", 0)), int(cfg["vocab_size"])]
        arch["vocab_size"] = int(pub["vocab_size"])
    n = int(arch["num_hidden_layers"])
    assert len(arch["hybrid_layer_pattern"]) == len(arch["moe_layer_freq"]) == n
    if share:
        arch["share"] = share
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """What this file, flops/decoder_sink.py and the per-layer readers need."""
    gen = cfg["serve"]["tables"]["genserve"]
    served = cfg["assumed"]["served"]
    a = arch_from_config(cfg)
    m = Model(a, 0)
    max_ctx = int(served["max_prompt_tokens"]) + int(served["max_new_tokens"])
    page, slots = int(gen["kv_page_tokens"]), int(gen["slots"])
    n_global = m.kinds.count(GLOBAL)
    by_kind = {t: {"heads": m.heads[t], "kv_heads": m.kv[t], "dk": m.dk[t], "dv": m.dv[t],
                   "sink": m.sink[t]} for t in (GLOBAL, WINDOW)}
    return {
        "arch": a, "d_model": m.d, "layers": m.n_layers, "kinds": list(m.kinds),
        "sparse": list(m.sparse), "by_kind": by_kind, "n_global": n_global,
        "n_window": m.n_layers - n_global, "n_attn": m.n_layers, "win_tokens": m.window,
        "vocab": m.vocab, "vocab_first": m.v_first, "experts_held": m.e_count,
        "top_k": m.top_k, "num_experts": m.e_full,
        "dense_width": int(a["intermediate_size"]),
        "expert_width": int(a.get("moe_intermediate_size", 0)),
        "max_prompt": int(served["max_prompt_tokens"]), "max_new": int(served["max_new_tokens"]),
        "max_ctx": max_ctx, "slots": slots, "page_tokens": page,
        "pages_per_slot": -(-max_ctx // page),
        "kv_pages": int(gen.get("kv_pages") or 0) or slots * -(-max_ctx // page) + 1,
        "prefill_chunk": int(gen.get("prefill_chunk") or 0) or int(served["max_prompt_tokens"]),
        "weight_bytes": 2 if cfg["serve"]["model"]["dtype"] == "bfloat16" else 4,
        # What kv_reserved_pct looks up. It reckons ONE row for every layer kind, 2 x
        # kv_heads x head_dim values a position, a page of `page_tokens` a full layer
        # and a ring of `window` a window layer. Here a global layer's row is KV x (dk
        # + dv) values, so "head_dim" is half a KV head's row and the product is the
        # row; a window layer's ring of W positions of ITS row is then `window`
        # positions' worth of a global layer's: W x its row over a global layer's.
        "layer_types": ["sliding_attention" if t == WINDOW else "full_attention"
                        for t in m.kinds],
        "kv_heads": m.kv[GLOBAL], "head_dim": (m.dk[GLOBAL] + m.dv[GLOBAL]) // 2,
        "window": m.window * (m.kv[WINDOW] * (m.dk[WINDOW] + m.dv[WINDOW]))
        // (m.kv[GLOBAL] * (m.dk[GLOBAL] + m.dv[GLOBAL])),
    }


def prepare(seed: int, sizes: dict, cfg: dict, work: str):
    """No checkpoint: the program draws its weights on the device by
    `assumed.weights`. Writes the model's config file, in the published
    layout with the share, for `options.config_file`."""
    path = os.path.join(work, "model_config.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(sizes["arch"], f, indent=1)
    served = cfg["assumed"]["served"]
    options = {"config_file": path, "draw_weights_seed": int(seed) % (2 ** 31),
               "max_prompt_tokens": int(served["max_prompt_tokens"]),
               "max_new_tokens": int(served["max_new_tokens"])}
    return None, options, {"seed": int(seed) % (2 ** 31), "dtype": cfg["serve"]["model"]["dtype"]}


def reference_answers(ref: dict, inputs: list[dict], sizes: dict) -> dict:
    """Nothing heavy yet: the pass is teacher-forced on the served tokens, so
    it waits for them (`compare`)."""
    return {"ref": ref, "inputs": inputs, "sizes": sizes}


def centred_gap(served: dict, ref_lp: np.ndarray, v_first: int) -> np.ndarray:
    """(positions, LOGPROBS) differences of served and reference centred
    log-probabilities at the ids the server named."""
    ids = np.asarray(served["logprobs"]["ids"], np.int64) - v_first
    got = np.asarray(served["logprobs"]["values"], np.float64)
    want = np.take_along_axis(ref_lp.astype(np.float64), ids, axis=-1)
    return (got - got.mean(axis=-1, keepdims=True)) - (want - want.mean(axis=-1, keepdims=True))


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    sz, ref = reference["sizes"], reference["ref"]
    v0 = sz["vocab_first"]
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
        ids = np.concatenate([inp["ids"], np.asarray(tokens[:-1], np.int64)]) - v0
        if ids.min() < 0 or ids.max() >= sz["vocab"]:
            return float("inf"), "logprob_rms=inf: a served token lies outside the held rows"
        seqs.append(ids)
        rows.append(len(inp["ids"]) - 1)
    low = cfg["check"].get("reference_inputs") == "3-bit-mantissa"
    model = Model(sz["arch"], ref["seed"], ref["dtype"])
    gaps = [centred_gap(a, lp, v0) for a, lp in zip(served, log_probs(model, seqs, rows, low))]
    flat = np.concatenate([g.ravel() for g in gaps])
    stat = float(np.sqrt(np.mean(flat ** 2)))
    per = ", ".join(f"{float(np.sqrt(np.mean(g ** 2))):.4g}" for g in gaps)
    return stat, (f"logprob_rms={stat:.6g} over {flat.size // LOGPROBS} generated positions of "
                  f"{len(served)} requests (by request: {per}; widest single gap "
                  f"{float(np.abs(flat).max()):.4g})"
                  + (" [the reference's matrix inputs at 3 mantissa bits: a control]" if low else ""))
