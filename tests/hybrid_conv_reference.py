"""The plain reference of the `hybrid_conv` family for tier-1 (ISSUE 59): a
language model whose layers are two sublayers each (a gated short convolution,
or grouped-query softmax attention with an RMSNorm a head on queries and keys
and then a rotary embedding; then a SwiGLU, dense in the leading layers and
sigmoid-routed over experts with no shared one in the rest), in straightforward
float32 under `jax.default_matmul_precision("highest")`: a whole sequence's `b`
and its convolution by k shifted sums, full causal attention, an expert's body
on the rows that picked it; no cache, no stored rows, no pages, no tiles, no
dispatch, no kernel. It imports nothing of `tpuserve`. The weights' recipe and
the control's roundings are `tests/hybrid_reference.py`'s.
`benchmark/reference/hybrid_conv.py` holds the benchmark's copy of the same
forward pass (its header has the equations, what is assumed and what each
wrong reading of `WRONG` computes); `tests/test_hybrid_conv.py` holds the two
to the same numbers.
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
    "embed": 1.0, "head": 1.0, "qk": 1.0, "qk_gain": [1.0, 3.0], "v": 1.0, "o": 1.0, "ffn_in": 1.0,
    "ffn_out": 1.0, "router": 1.0, "router_bias": 0.02, "conv_in": 1.0, "conv_tap": 1.0,
    "conv_out": 1.0}
ROUTE_EPS = 1e-6
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
