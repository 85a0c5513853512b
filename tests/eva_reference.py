"""The plain reference of the `eva` family for tier-1 (ISSUE 55): a decoder-only
language model whose attention is EVA (every query over the exact keys of its
own aligned window and one learned-pooled summary row a chunk of every earlier
window, in one softmax), in straightforward float32 under
`jax.default_matmul_precision("highest")`: all positions' k and v, then every
whole chunk's summary from them, then each position's softmax over the two sets
its index gives; no cache, no ring, no pages, no tiles. It imports nothing of
`tpuserve`; the weights' recipe and the control's rounding are
`tests/decoder_reference.py`'s. `benchmark/reference/eva.py` holds the
benchmark's copy of the same forward pass (its header has the equations and
what is assumed); `tests/test_eva.py` holds the two to the same numbers.

`Model(..., wrong=<name>)` computes a WRONG reading of the layer instead (one of
`WRONG`), so that a test can show the program is held to the right one:
`no_summaries` (a window alone), `own_summaries` (the current window's whole
chunks also attended through their summaries), `sliding` (the last W positions
in place of the aligned window), `mean_pool` (a plain mean for the pooling),
`no_mu`, `mu_on_v` (mu added to the pooled value too), `split_softmax` (the
exact and the summary parts each normalised alone, then added), `no_unit_offset`
(a gain is g), `pool_unturned` (the rotary left off the keys that are pooled,
kept on the exact ones), `bf16_stream` (the stream rounded to bfloat16 after
every sublayer). What a config key decides (the chunk, the window) a test
changes in the architecture it hands this file.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from tests import decoder_reference as base

LOGPROBS = base.LOGPROBS
DEFAULT_SCALES = {"embed": 1.0, "head": 1.0, "qk": 1.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
                  "ffn_out": 1.0, "phi": 0.18, "mu": 1.0, "gain": 0.25}
WRONG = ("no_summaries", "own_summaries", "sliding", "mean_pool", "no_mu", "mu_on_v",
         "split_softmax", "no_unit_offset", "pool_unturned", "bf16_stream")
EXACT = ("phi", "mu", "g1", "g2")   # the control leaves these as drawn: no matrix product's input


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
