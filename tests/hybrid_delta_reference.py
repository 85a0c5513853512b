"""The plain reference of the `hybrid_delta` family for tier-1 (ISSUE 53): a
language model whose layers are two sublayers each (gated delta-rule linear
attention with a decay a channel, or softmax attention with no position term
under an elementwise gate; then sigmoid-routed SwiGLU experts with a shared
one), in straightforward float32 under `jax.default_matmul_precision("highest")`:
the recurrence token by token, full causal attention, no cache, no chunks, no
batching, no kernel. It imports nothing of `tpuserve`. The weights' recipe and
the control's roundings are `tests/hybrid_reference.py`'s.
`benchmark/reference/hybrid_delta.py` holds the benchmark's copy of the same
forward pass (its header has the equations and what is assumed);
`tests/test_hybrid_delta.py` holds the two to the same numbers.

`Model(..., wrong=<name>)` computes a WRONG reading of the layer instead (one of
`WRONG`), so that a test can show the program is held to the right one:
`decay_head` (the decay a head, the mean of its channels' logarithms),
`no_correction` (`S' + beta k v^T`), `decay_after` (the correction reads the
state before the decay, which is applied to the result), `q_raw` and `k_raw`
(no L2 norm), `no_silu` (none after the convolution), `rope` (a rotary term on
the softmax layers' queries and keys). What a config key decides (beta's
factor, the convolution's rows, the gate, the layers' order) a test changes in
the architecture it hands this file.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from tests import hybrid_reference as hy
LOGPROBS = hy.LOGPROBS
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "gate": 1.0, "ffn_in": 1.0,
    "ffn_out": 1.0, "router": 1.0, "router_bias": 0.02, "kda_in": 1.0, "kda_decay": 1.0,
    "kda_gate": 1.0, "kda_beta": 1.0, "kda_out": 1.0, "conv": 1.0, "gate_bias": 0.1,
    "decay_rate": [0.5, 4.0], "decay_step": [0.001, 0.1]}
L2_EPS = 1e-6
# What the control leaves alone: the router decides in float32 in the program
# too, and the float32 vectors and gains are no matrix product's input.
EXACT = ("router", "e_bias", "A_log", "dt_bias", "b_g")
WRONG = ("decay_head", "no_correction", "decay_after", "q_raw", "k_raw", "no_silu", "rope")


# -- weights by recipe -------------------------------------------------------------

class Model:
    """The architecture's numbers and its tensors' shapes; draws one tensor
    or one layer at a time."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16", wrong: str = "") -> None:
        assert wrong in ("",) + WRONG
        a, self.wrong = arch, wrong
        self.a = arch
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
    H, D, k, beta_scale, eps, wrong = dims
    t, rnd = x.shape[0], _rnd(low)
    kept = jnp.bfloat16 if low else jnp.float32
    with jax.default_matmul_precision("highest"):
        u = rnd(hy._rms(x, eps))
        pre = jnp.concatenate([jnp.einsum("td,dhc->thc", u, w[f"w{p}"]).reshape(t, -1)
                               for p in "qkv"], axis=1)
        cw = jnp.concatenate([w[f"conv_{p}"].reshape(k, -1) for p in "qkv"], axis=1)
        padded = jnp.concatenate([jnp.zeros((k - 1, pre.shape[1]), pre.dtype), pre], axis=0)
        act = sum(padded[j:j + t] * cw[j] for j in range(k))
        act = (act if wrong == "no_silu" else jax.nn.silu(act)).reshape(t, 3, H, D)
        q, kk, v = act[:, 0], act[:, 1], act[:, 2]
        if wrong != "q_raw":
            q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS)
        q = q / math.sqrt(D)
        if wrong != "k_raw":
            kk = kk * jax.lax.rsqrt(jnp.sum(kk * kk, axis=-1, keepdims=True) + L2_EPS)
        f = jnp.einsum("tr,rhc->thc", rnd(u @ w["w_fa"]), w["w_fb"])
        g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(f + w["dt_bias"])
        if wrong == "decay_head":
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        a = jnp.exp(g)
        beta = beta_scale * jax.nn.sigmoid(u @ w["w_b"])

        def token(S, row):
            a_t, b_t, q_t, k_t, v_t = row
            S = S.astype(jnp.float32)
            if wrong != "decay_after":
                S = a_t[:, :, None] * S                                  # the decay first
            seen = 0.0 if wrong == "no_correction" else jnp.einsum("hcv,hc->hv", S, k_t)  # S'^T k
            S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
            if wrong == "decay_after":
                S = a_t[:, :, None] * S
            S = S.astype(kept)
            return S, jnp.einsum("hcv,hc->hv", S.astype(jnp.float32), q_t)

        _, o = jax.lax.scan(token, jnp.zeros((H, D, D), kept), (a, beta, q, kk, v))
        gate = jnp.einsum("tr,rhc->thc", rnd(u @ w["w_ga"]), w["w_gb"]) + w["b_g"]
        y = hy._rms(o, eps) * jax.nn.sigmoid(gate)                       # o's gain is ones
        return x + rnd(y).reshape(t, -1) @ w["w_out"].reshape(H * D, -1)


def _rope(x, theta: float):
    """x (T, heads, hd) turned by its row's position, pairs (i, i + hd / 2)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0])[:, None] * theta ** (-jnp.arange(half) / half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    return jnp.concatenate([x[..., :half] * cos - x[..., half:] * sin,
                            x[..., half:] * cos + x[..., :half] * sin], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def attention_sublayer(dims: tuple, low: bool, w: dict, x):
    """`x + attention(RMSNorm(x))`: one full causal pass, no position term."""
    heads, kv, hd, eps, wrong = dims
    t, rnd = x.shape[0], _rnd(low)
    with jax.default_matmul_precision("highest"):
        u = rnd(hy._rms(x, eps))
        q = jnp.einsum("td,dhk->thk", u, w["wq"])
        k = jnp.repeat(jnp.einsum("td,dhk->thk", u, w["wk"]), heads // kv, axis=1)
        v = jnp.repeat(jnp.einsum("td,dhk->thk", u, w["wv"]), heads // kv, axis=1)
        if wrong == "rope":
            q, k = (_rope(z, 10000.0) for z in (q, k))
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
                x = attention_sublayer((m.heads, m.kv, m.hd, m.eps, m.wrong), low, mixer, x)
            else:
                x = delta_sublayer((m.kh, m.kd, m.conv_k, m.beta_scale, m.eps, m.wrong), low, mixer, x)
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
