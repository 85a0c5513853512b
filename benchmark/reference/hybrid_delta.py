"""The plain reference for the `hybrid_delta` family: a language model whose
layers are two sublayers each (a mixer chosen by `gqa_layers`: gated delta-rule
linear attention with a decay a channel, or softmax attention with no position
term under an elementwise gate; then sigmoid-routed SwiGLU experts with a
shared one), written down from its published `config.json` in straightforward
float32 (`jax.numpy` under `jax.default_matmul_precision("highest")`; the
experts' products, whose groups have every size, in `numpy` float32), with no
cache, no batching, no chunks and no kernel. It imports nothing of the program.
The recipe of the weights, the roundings of the control and the check's
statistic are `reference/hybrid.py`'s own functions, imported and not edited.

THE LAYER (x the stream, d = `hidden_size`, eps `rms_norm_eps`, RMSNorm with a
gain before each sublayer and before the head, no bias but where said). Layer
i: `x <- x + mixer_i(RMSNorm(x; g1))`, softmax attention where i is in
`gqa_layers`, the delta rule elsewhere; then `x <- x + experts(RMSNorm(x; g2))`.

THE DELTA RULE (`linear_attn_config`: H = `num_heads`, D = `head_dim` for keys
and values alike, k = `short_conv_kernel_size`; r = D the low rank,
`kda_use_full_proj` false), u the normed row of token t:

    q~ = u W_q    k~ = u W_k    v~ = u W_v                      each (H, D)
    q, k, v = silu(conv_k(q~)), silu(conv_k(k~)), silu(conv_k(v~))   depthwise, causal, over the last k rows
                                                                 of a channel, zeros before position 0, no bias
    q[h] <- q[h] / |q[h]| / sqrt(D)      k[h] <- k[h] / |k[h]|    (1e-6 under the root)
    g[h, c] = -exp(A_log[h]) softplus(((u W_fa) W_fb)[h, c] + dt_bias[h, c])   the log-decay A CHANNEL, <= 0
    beta[h] = 2 sigmoid((u W_b)[h])      (`kda_allow_neg_eigval`; else 1 sigmoid)
    S'      = Diag(exp(g[h])) S_{t-1}[h]                         S: (D, D), zeros at position 0
    S_t[h]  = S' + beta[h] k[h] (v[h] - S'^T k[h])^T
    o[h]    = S_t[h]^T q[h]
    y[h]    = RMSNorm(o[h]; g_o) sigmoid(((u W_ga) W_gb + b_g)[h])   one gain of D for all heads
    out     = concat_h(y[h]) W_o

The decay acts BEFORE the correction, the correction reads the decayed state,
the read is of the state AFTER the token's own write. HERE THE RECURRENCE IS THE
RECURRENCE: a `lax.scan` over the tokens, one at a time (the program computes
it by chunks in prefill and a step at a time in decode).

SOFTMAX ATTENTION (i in `gqa_layers`; `use_rope` false): `num_attention_heads`
query heads on `num_key_value_heads` KV heads of `head_dim` (query head h reads
KV head h // (H / KV)), causal softmax of q.k / sqrt(head_dim) in float32, NO
position term of any kind; `out = (concat_h(o[h]) sigmoid(u W_g)) W_o`
(`use_gqa_gate`: elementwise by head, before `W_o`).

EXPERTS, every layer, on u2 = RMSNorm(x; g2): r = u2 W_r over all
`n_routed_experts` in float32, sc = sigmoid(r), the `num_experts_per_tok`
largest of sc + b (the selection bias moves picks, never weights), weights
sc[e] / (their sum) (`norm_topk_prob`) times `routed_scaling_factor`, y =
SwiGLU_shared(u2) + sum_e w_e SwiGLU_e(u2) at `moe_intermediate_size` over the
experts HELD here (`n_shared_experts` shared ones of the same width, as one).

THE SHARE (`share` in the architecture), the same as the program is given:
`experts_held = [first, count]` (picks on the others add nothing),
`vocab_rows = [first, count]`; mixers, router, shared expert and norms whole.

ASSUMED (the configuration file repeats this under `assumed`): sigmoid scores
and a selection bias (the config has no `scoring_func`); the softmax layers'
gate elementwise, 64 x 128 wide from u; no query/key norm there; `A_log` a head
and `dt_bias` a channel drawn inside ranges; the L2 norms' eps; no convolution
bias; `b_g` the one bias; o's gain shared by the heads; the low rank is
`linear_attn_config.head_dim`.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`): the names,
shapes, fan-ins and ranges are the program's (`tpuserve/models/mixers.py`
`DeltaMixer._delta_tensors`, `_delta_vectors`; `hybrid_delta._tensors`).

THE CHECK (`compare`): `reference/hybrid.py`'s statistic (`logprob_q25` beside
`logprob_rms`, centred top-8 log-probabilities, teacher-forced on the served
tokens) over ONE full pass, a sublayer one compiled program a sequence length.
`check.reference_inputs = "3-bit-mantissa"` (a control, never a cell) rounds the
inputs of the reference's matrix products (every kernel but the router's, the
normed stream that enters a sublayer, the gated rows before `W_o`, the hidden
rows before a down-projection) to 3 explicit mantissa bits AND keeps the
recurrent state in bfloat16 between tokens.
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
    "embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "gate": 1.0, "ffn_in": 1.0,
    "ffn_out": 1.0, "router": 1.0, "router_bias": 0.02, "kda_in": 1.0, "kda_decay": 1.0,
    "kda_gate": 1.0, "kda_beta": 1.0, "kda_out": 1.0, "conv": 1.0, "gate_bias": 0.1,
    "decay_rate": [0.5, 4.0], "decay_step": [0.001, 0.1]}
L2_EPS = 1e-6
# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (
    "model_type", "partial_rotary_factor", "linear_attn_config", "hidden_size",
    "num_hidden_layers", "num_attention_heads", "head_dim", "num_key_value_heads", "vocab_size",
    "intermediate_size", "moe_intermediate_size", "rms_norm_eps", "rope_theta",
    "tie_word_embeddings", "max_position_embeddings", "first_k_dense_replace", "use_rope",
    "gqa_interval", "gqa_layers", "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
    "num_experts_per_tok")
# What the control leaves alone: the router decides in float32 in the program
# too, and the float32 vectors and gains are no matrix product's input.
EXACT = ("router", "e_bias", "A_log", "dt_bias", "b_g")


# -- weights by recipe -------------------------------------------------------------

class Model:
    """The architecture's numbers and its tensors' shapes; draws one tensor
    or one layer at a time."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        a = self.a = arch
        self.seed, self.dtype = int(seed), jnp.dtype(served_dtype)
        self.d, self.n_layers = int(a["hidden_size"]), int(a["num_hidden_layers"])
        self.eps = float(a.get("rms_norm_eps", 1e-5))
        self.softmax = sorted(int(i) for i in a["gqa_layers"])
        lin = a["linear_attn_config"]
        self.kh, self.kd = int(lin["num_heads"]), int(lin["head_dim"])
        self.rank, self.conv_k = self.kd, int(lin.get("short_conv_kernel_size", 4))
        self.beta_scale = 2.0 if a.get("kda_allow_neg_eigval", False) else 1.0
        self.heads, self.kv = int(a["num_attention_heads"]), int(a["num_key_value_heads"])
        self.hd = int(a.get("head_dim") or self.d // self.heads)
        self.gated = bool(a.get("use_gqa_gate", False))
        self.e_full, self.top_k = int(a["n_routed_experts"]), int(a["num_experts_per_tok"])
        self.f = int(a["moe_intermediate_size"])
        self.fs = self.f * int(a.get("n_shared_experts") or 0)
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.e_full])
        self.v_full = int(a["vocab_size"])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.v_full])
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    def tensor(self, name: str, shape, role: str, fan_in: int, full=None, start=None):
        shape = tuple(shape)
        return np.asarray(hy.draw(self.seed, name, shape, self.scales[role] / math.sqrt(fan_in),
                                  self.dtype, tuple(full or shape), tuple(start or (0,) * len(shape))))

    def vector(self, name: str, shape, lo: float, hi: float) -> np.ndarray:
        """A float32 vector inside [lo, hi]: the four summed bytes over their
        range, then the range."""
        shape = tuple(shape)
        u = jnp.float32(0.5) + hy.draw(self.seed, name, shape, hy.BELL_STD / 1020.0, jnp.float32,
                                       shape, (0,) * len(shape))
        return np.asarray(jnp.float32(lo) + jnp.float32(hi - lo) * u)

    def embed(self) -> np.ndarray:
        return self.tensor("embed", (self.vocab, self.d), "embed", 1, (self.v_full, self.d),
                           (self.v_first, 0))

    def head(self) -> np.ndarray:
        if self.a.get("tie_word_embeddings", False):
            return self.embed().T
        return self.tensor("head", (self.d, self.vocab), "head", self.d, (self.d, self.v_full),
                           (0, self.v_first))

    def layer(self, i: int) -> dict:
        t, L, d, s = self.tensor, f"layer{i}", self.d, self.scales
        if i in self.softmax:
            h, kv, hd = self.heads, self.kv, self.hd
            w = {"wq": t(f"{L}/wq", (d, h, hd), "qk", d), "wk": t(f"{L}/wk", (d, kv, hd), "qk", d),
                 "wv": t(f"{L}/wv", (d, kv, hd), "v", d), "wo": t(f"{L}/wo", (h, hd, d), "o", h * hd)}
            if self.gated:
                w["wg"] = t(f"{L}/wg", (d, h, hd), "gate", d)
        else:
            h, D, r, k = self.kh, self.kd, self.rank, self.conv_k
            w = {}
            for part in ("q", "k", "v"):
                w[f"w{part}"] = t(f"{L}/w{part}", (d, h, D), "kda_in", d)
                w[f"conv_{part}"] = t(f"{L}/conv_{part}", (k, h, D), "conv", k)
            for part, role in (("f", "kda_decay"), ("g", "kda_gate")):
                w[f"w_{part}a"] = t(f"{L}/w_{part}a", (d, r), role, d)
                w[f"w_{part}b"] = t(f"{L}/w_{part}b", (r, h, D), role, r)
            w["w_b"] = t(f"{L}/w_b", (d, h), "kda_beta", d)
            w["w_out"] = t(f"{L}/w_out", (h, D, d), "kda_out", h * D)
            lo, hi = (hy.softplus_inverse(v) for v in s["decay_step"])
            w["A_log"] = self.vector(f"{L}/A_log", (h,), *(math.log(v) for v in s["decay_rate"]))
            w["dt_bias"] = self.vector(f"{L}/dt_bias", (h, D), lo, hi)
            w["b_g"] = self.vector(f"{L}/b_g", (h, D), -3.0 * s["gate_bias"], 3.0 * s["gate_bias"])
        e, ec, e0, f, fs = self.e_full, self.e_count, self.e_first, self.f, self.fs
        b3 = 3.0 * s["router_bias"]
        w["router"] = t(f"{L}/router", (d, e), "router", d)
        w["e_bias"] = self.vector(f"{L}/e_bias", (e,), -b3, b3)
        for name in ("e_gate", "e_up"):
            w[name] = t(f"{L}/{name}", (ec, d, f), "ffn_in", d, (e, d, f), (e0, 0, 0))
        w["e_down"] = t(f"{L}/e_down", (ec, f, d), "ffn_out", f, (e, f, d), (e0, 0, 0))
        if fs:
            for name in ("s_gate", "s_up"):
                w[name] = t(f"{L}/{name}", (d, fs), "ffn_in", d)
            w["s_down"] = t(f"{L}/s_down", (fs, d), "ffn_out", fs)
        return w


# -- the forward pass ----------------------------------------------------------------

# One compiled program a sublayer and a sequence length (not one an operation).

def _rnd(low: bool):
    return hy._round3_whole if low else (lambda z: z)


@functools.partial(jax.jit, static_argnums=(0, 1))
def delta_sublayer(dims: tuple, low: bool, w: dict, x):
    """`x + delta_rule(RMSNorm(x))` over a whole sequence x (T, d), the
    recurrence token by token from a zero state. `low`: the control's
    roundings, and the state kept in bfloat16 between tokens."""
    H, D, k, beta_scale, eps = dims
    t, rnd = x.shape[0], _rnd(low)
    kept = jnp.bfloat16 if low else jnp.float32
    with jax.default_matmul_precision("highest"):
        u = rnd(hy._rms(x, eps))
        pre = jnp.concatenate([jnp.einsum("td,dhc->thc", u, w[f"w{p}"]).reshape(t, -1)
                               for p in "qkv"], axis=1)
        cw = jnp.concatenate([w[f"conv_{p}"].reshape(k, -1) for p in "qkv"], axis=1)
        padded = jnp.concatenate([jnp.zeros((k - 1, pre.shape[1]), pre.dtype), pre], axis=0)
        act = jax.nn.silu(sum(padded[j:j + t] * cw[j] for j in range(k))).reshape(t, 3, H, D)
        q, kk, v = act[:, 0], act[:, 1], act[:, 2]
        q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / math.sqrt(D)
        kk = kk * jax.lax.rsqrt(jnp.sum(kk * kk, axis=-1, keepdims=True) + L2_EPS)
        f = jnp.einsum("tr,rhc->thc", rnd(u @ w["w_fa"]), w["w_fb"])
        a = jnp.exp(-jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(f + w["dt_bias"]))
        beta = beta_scale * jax.nn.sigmoid(u @ w["w_b"])

        def token(S, row):
            a_t, b_t, q_t, k_t, v_t = row
            S = a_t[:, :, None] * S.astype(jnp.float32)                  # the decay first
            seen = jnp.einsum("hcv,hc->hv", S, k_t)                      # S'^T k
            S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
            S = S.astype(kept)
            return S, jnp.einsum("hcv,hc->hv", S.astype(jnp.float32), q_t)

        _, o = jax.lax.scan(token, jnp.zeros((H, D, D), kept), (a, beta, q, kk, v))
        gate = jnp.einsum("tr,rhc->thc", rnd(u @ w["w_ga"]), w["w_gb"]) + w["b_g"]
        y = hy._rms(o, eps) * jax.nn.sigmoid(gate)                       # o's gain is ones
        return x + rnd(y).reshape(t, -1) @ w["w_out"].reshape(H * D, -1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def attention_sublayer(dims: tuple, low: bool, w: dict, x):
    """`x + attention(RMSNorm(x))`: one full causal pass, no position term."""
    heads, kv, hd, eps = dims
    t, rnd = x.shape[0], _rnd(low)
    with jax.default_matmul_precision("highest"):
        u = rnd(hy._rms(x, eps))
        q = jnp.einsum("td,dhk->thk", u, w["wq"])
        k = jnp.repeat(jnp.einsum("td,dhk->thk", u, w["wk"]), heads // kv, axis=1)
        v = jnp.repeat(jnp.einsum("td,dhk->thk", u, w["wv"]), heads // kv, axis=1)
        see = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        o = jnp.einsum("hqk,khd->qhd",
                       jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1), v)
        if "wg" in w:
            o = o * jax.nn.sigmoid(jnp.einsum("td,dhk->thk", u, w["wg"]))
        return x + jnp.einsum("qhd,hdo->qo", rnd(o), w["wo"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _normed(eps: float, low: bool, x):
    return _rnd(low)(hy._rms(x, eps))


@functools.partial(jax.jit, static_argnums=(0,))
def _shared(low: bool, u, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return _rnd(low)(jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def picks(m: Model, w: dict, u: np.ndarray):
    """The router on the normed rows `u` (float32, never rounded) -> (the
    picked experts (T, k), their weights)."""
    with jax.default_matmul_precision("highest"):
        r = np.asarray(jnp.asarray(u) @ jnp.asarray(w["router"]))
    s = (1.0 / (1.0 + np.exp(-r.astype(np.float32)))).astype(np.float32)
    top = np.argsort(-(s + w["e_bias"][None, :]), axis=-1, kind="stable")[:, :m.top_k]
    wt = np.take_along_axis(s, top, axis=-1)
    if m.a.get("norm_topk_prob", True):
        wt = wt / wt.sum(axis=-1, keepdims=True)
    return top, wt * np.float32(m.a.get("routed_scaling_factor") or 1.0)


def experts(m: Model, w: dict, u: np.ndarray, routed_on: np.ndarray, low: bool = False) -> np.ndarray:
    """The held experts' part of the routed sum, in numpy float32: each held
    expert over the tokens that picked it. `routed_on` (T, d) is what the
    router reads (never rounded), `u` what the experts read."""
    top, wt = picks(m, w, routed_on)
    rnd = hy._round3 if low else (lambda z: z)
    y = np.zeros_like(u)
    for local in range(m.e_count):
        tok, slot = np.nonzero(top == m.e_first + local)
        if tok.size == 0:
            continue
        g = u[tok] @ w["e_gate"][local]
        h = g / (1.0 + np.exp(-g)) * (u[tok] @ w["e_up"][local])
        y[tok] += wt[tok, slot][:, None] * (rnd(h.astype(np.float32)) @ w["e_down"][local])
    return y


def hidden_states(m: Model, sequences: list[np.ndarray], low: bool = False) -> list:
    """Final hidden states (before the last norm) of each sequence of held-row
    ids; layers outermost, so each layer is drawn once and dropped."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) for ids in sequences]
    del embed
    took = {"delta": 0.0, "softmax": 0.0, "experts": 0.0}
    for i in range(m.n_layers):
        w = m.layer(i)
        if low:  # the control: every kernel but the router's
            w = {k: (v if k in EXACT else np.asarray(hy._round3_whole(v))) for k, v in w.items()}
        kind = "softmax" if i in m.softmax else "delta"
        mixer = {k: jnp.asarray(v) for k, v in w.items()
                 if not k.startswith(("e_", "s_", "router"))}
        shared = [jnp.asarray(w[k]) for k in ("s_gate", "s_up", "s_down")] if m.fs else None
        for n, x in enumerate(xs):
            t0 = time.monotonic()
            if kind == "softmax":
                x = attention_sublayer((m.heads, m.kv, m.hd, m.eps), low, mixer, x)
            else:
                x = delta_sublayer((m.kh, m.kd, m.conv_k, m.beta_scale, m.eps), low, mixer, x)
            x.block_until_ready()
            t1 = time.monotonic()
            u = np.asarray(_normed(m.eps, low, x))
            y = experts(m, w, u, np.asarray(_normed(m.eps, False, x)) if low else u, low)
            if shared:
                y = y + np.asarray(_shared(low, jnp.asarray(u), *shared))
            xs[n] = x + jnp.asarray(y)
            took[kind] += t1 - t0
            took["experts"] += time.monotonic() - t1
        del w, mixer, shared
    print("[reference] " + str(sum(len(s) for s in sequences)) + f" tokens through {m.n_layers} "
          "layers: " + ", ".join(f"{k} {v:.1f} s" for k, v in took.items()), flush=True)
    return xs


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the held vocabulary rows at positions
    `first_row` onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low)
    head = jnp.asarray(m.head())
    with jax.default_matmul_precision("highest"):
        return [np.asarray(jax.nn.log_softmax(hy._rms(h[r:], m.eps) @ head, axis=-1))
                for h, r in zip(hs, first_rows)]


# -- the configuration, for the harness ------------------------------------------------

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
    if share:
        arch["share"] = share
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """What this file, flops/hybrid_delta.py and the per-layer readers need
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
    n_a = len(m.softmax)
    n_m = m.n_layers - n_a
    channels = 3 * m.kh * m.kd
    return {
        "arch": a, "d_model": m.d, "layers": m.n_layers, "n_mamba": n_m, "n_attn": n_a,
        "n_expert": m.n_layers, "heads": m.heads, "kv_heads": m.kv, "head_dim": m.hd,
        "attn_gate": m.gated, "delta_heads": m.kh, "delta_head_dim": m.kd, "delta_rank": m.rank,
        "conv_kernel": m.conv_k, "conv_channels": channels,
        "state_bytes_per_slot": n_m * (m.kh * m.kd * m.kd * 4 + (m.conv_k - 1) * channels * wb),
        "num_experts": m.e_full, "experts_held": m.e_count, "top_k": m.top_k,
        "expert_width": m.f, "shared_width": m.fs,
        "vocab": m.vocab, "vocab_first": m.v_first,
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


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    """`reference/hybrid.py`'s statistic over this family's pass: a generated
    position's number is the RMS of its eight centred differences; the
    statistic is the largest, over the requests, of the lower quartile of a
    request's positions, beside the RMS over all positions against
    `check.rms_limit` (the number compared with `check.limit` is the larger of
    the quartile and the RMS scaled by `limit / rms_limit`)."""
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
    gaps = [hy.centred_gap(a, lp, v0) for a, lp in zip(served, log_probs(model, seqs, rows, low))]
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
                  + (" [the reference's matrix inputs at 3 mantissa bits, its state in "
                     "bfloat16: a control]" if low else ""))
