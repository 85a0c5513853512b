"""The plain reference for the `mla_hc` family: a decoder-only language model
with latent attention (MLA) and routed SwiGLU experts under a HYPER-CONNECTED
RESIDUAL of several streams (manifold-constrained hyper-connections: Xie et
al., DeepSeek-AI, "mHC", arXiv:2512.24880, over Zhu et al., "Hyper-Connections",
arXiv:2409.19606), written down from its published `config.json` in
straightforward float32 (`jax.numpy` under `jax.default_matmul_precision(
"highest")`; the routed experts' products, whose groups have every size, in
`numpy` float32), the attention in its EXPANDED form only, with no cache of
pages, no chunking, no absorbed product and no kernel. It imports nothing of
the program; what it shares with the `mla` family's reference (the draw by
recipe, a layer's attention and feed-forward tensors, the control's rounding,
the router's picks, the comparison's gap) it takes from `reference/mla.py`.

THE STREAM of a token is `X` in R^(n x d), `n = hc_mult`, `d = hidden_size`.
Entry: `X[j] = embed[id]` for every j. Exit: `x = sum_j X[j]`, then logits =
`RMSNorm(x) W_head` (eps = `rms_norm_eps`, no biases, an untied head; norm gains
are ones in the recipe and are left out).

A LAYER has two sublayers, `F_1` latent attention and `F_2` the feed-forward
(dense SwiGLU of `intermediate_size` below `first_k_dense_replace`; routed
experts plus the shared expert after), each with ITS OWN `Phi` (n d, 2 n + n^2),
scalars `alpha_pre`, `alpha_post`, `alpha_res`, biases `b_pre`, `b_post` (n,) and
`b_res` (n, n). A token, a sublayer, maps in float32:

    vt          = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     no gain
    [p | q | r] = vt Phi                                            (n | n | n^2)
    H_pre  = sigmoid(alpha_pre p + b_pre)                           in (0, 1)
    H_post = 2 sigmoid(alpha_post q + b_post)                       in (0, 2)
    M      = exp(clip(alpha_res mat(r) + b_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    hc_sinkhorn_iters times:  M <- M / (column sums + hc_eps);  M <- M / (row sums + hc_eps)
    H_res  = M
    u      = sum_j H_pre[j] X[j]
    y      = F(RMSNorm(u))
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

- attention is `reference/mla.py`'s with two changes. (1) Rotary frequencies by
  yarn over the rotary columns (`yarn`): divided by `factor` below the
  correction range, unchanged above it, a linear ramp between, the range from
  `beta_fast` and `beta_slow` over `original_max_position_embeddings` at base
  `rope_theta`. (2) THE MAGNITUDE GOES ON THE SCORE: with `m(a) = 0.1 a
  ln(factor) + 1`, cos and sin times `m(mscale) / m(mscale_all_dim)` (1 at the
  published 1 and 1) and EVERY score, its nope and its rope part alike, times
  `m(mscale_all_dim)^2 / sqrt(qk_nope + qk_rope)` (2.0048 / 13.856): DeepSeek-V3's
  published modelling code, the family whose key names these are.
  `rope_interleave` is absent: pairs (i, i + dim / 2).
- feed-forward: `reference/mla.py`'s (sigmoid scores in float32, the
  `num_experts_per_tok` largest of score + bias, weights over their own sum
  times `routed_scaling_factor`, SwiGLU experts, the shared expert on the same
  normed u); every expert held.

THE CUT: depth alone (`num_hidden_layers`: the two leading dense layers and the
six sparse layers that follow, of 40); every width, every head, every expert
and the whole vocabulary are as published, so there is no `share`.

ASSUMED (the configuration file repeats each with its reason under `assumed`):
the flattened norm has no gain and uses `rms_norm_eps`; `hc_eps` is added to the
sums inside Sinkhorn; columns then rows inside an iteration; rows of `H_res`
index the OUTGOING stream; entry by copy and exit by sum; the maps in float32
whatever the served type; `alpha_*` scalars a sublayer; the rotary pairing; the
multi-token-prediction module is not part of the main stack's logits and is
not here; no end-of-sequence id.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`): `reference/
mla.py`'s draw, and three roles more. `Phi` (`hc_phi`): standard deviation scale
/ sqrt(n d), so p, q and r have the scale's deviation a token. The float32
vectors are the four summed bytes over their range (0 to 1) mapped into a
range: `alpha` (3,) into hc_alpha x [0.5, 1.5], its third entry (`alpha_res`) then
times RES_ALPHA; with b3 = 3 x hc_bias, `b_pre` into [-b3, b3], `b_post` into
POST_BIAS +- b3, `b_res` into [-b3, b3] plus RES_DIAGONAL on its diagonal. A sublayer's tensors are named
`layer{i}/hc1/...` (attention's) and `layer{i}/hc2/...` (the feed-forward's).

THE CHECK (`compare`) is `reference/mla.py`'s statistic (`logprob_q25` beside
`logprob_rms`) over a pass made in TWO calls of one function (`forward`), as
`reference/mla_sc.py` makes it: the prompts in a thread while the server
starts (`prompt_pass`), the served tokens after, continued from the rows the
first call cached a layer a sequence (the normed `c_kv` and the rotated `k_r`:
all a later token needs of an earlier one; the streams keep nothing between
tokens). `check.reference_inputs = "3-bit-mantissa"` (a control, never a cell)
rounds what `reference/mla.py`'s control rounds and, besides, the n streams as
a sublayer's maps and mixes read them, to 3 explicit mantissa bits.
"""

from __future__ import annotations

import atexit
import functools
import json
import math
import threading
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import spec

base = spec.load_module("reference", "mla")

LOGPROBS = base.LOGPROBS
DEFAULT_SCALES = {**base.DEFAULT_SCALES, "hc_phi": 1.0, "hc_alpha": 3.0, "hc_bias": 0.1}
POST_BIAS, RES_DIAGONAL = -3.0, 1.25   # the centres of b_post and of b_res's diagonal
RES_ALPHA = 0.15   # alpha_res's centre over the other two's


def yarn(dim: int, theta: float, rs: dict | None):
    """-> (inverse frequencies (dim / 2,), the factor on cos and sin, the
    factor on every score). Plain where `rs` is None. Yarn (Peng et al. 2023)
    in DeepSeek-V3's published convention: pair i's frequency `theta ** (-2 i /
    dim)` is divided by `factor` below the correction range (`beta_fast`
    rotations over `original_max_position_embeddings`), unchanged above it
    (`beta_slow`), a linear ramp between; with `m(a) = 0.1 a ln(factor) + 1`,
    cos and sin times `m(mscale) / m(mscale_all_dim)` and every score times
    `m(mscale_all_dim) ** 2`."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rs is None:
        return (1.0 / pos).astype(np.float32), 1.0, 1.0
    factor, orig = float(rs["factor"]), float(rs["original_max_position_embeddings"])

    def correction(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(float(rs.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction(float(rs.get("beta_slow", 1)))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high if high != low else low + 0.001) - low),
                   0, 1)
    inv = ramp / (factor * pos) + (1 - ramp) / pos

    def m(a: float) -> float:
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 and a else 1.0

    all_dim = float(rs.get("mscale_all_dim", 0) or 0)
    return inv.astype(np.float32), m(float(rs.get("mscale", 1))) / m(all_dim), m(all_dim) ** 2


class Model(base.Model):
    """`mla`'s numbers and tensors, and: the streams, the Sinkhorn's settings,
    yarn, and a sublayer's maps."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        super().__init__(arch, seed, served_dtype)
        a = arch
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self.n = int(a.get("hc_mult", 1))
        self.iters, self.hc_eps = int(a.get("hc_sinkhorn_iters", 20)), float(a.get("hc_eps", 1e-6))
        self.clamp = (float(a.get("mhc_h_res_clamp_min", -30)),
                      float(a.get("mhc_h_res_clamp_max", 30)))
        self.inv_freq, self.on_cos_sin, on_score = yarn(self.dr, self.theta, a.get("rope_scaling"))
        self.score_scale = on_score / math.sqrt(self.dn + self.dr)

    def vector(self, name: str, shape: tuple, lo: float, hi: float) -> jax.Array:
        """A float32 tensor inside [lo, hi]: the four summed bytes over their
        range (0 to 1), then the range."""
        u = jnp.float32(0.5) + base.draw(self.seed, name, shape, base.BELL_STD / 1020.0,
                                         jnp.float32, shape, (0,) * len(shape))
        return jnp.float32(lo) + jnp.float32(hi - lo) * u

    def maps(self, i: int, k: str) -> dict:
        """Sublayer `k` (`hc1`: attention's, `hc2`: the feed-forward's) of layer `i`."""
        at, n, nd = f"layer{i}/{k}", self.n, self.n * self.d
        a, b3 = self.scales["hc_alpha"], 3.0 * self.scales["hc_bias"]
        return {"phi": self.tensor(f"{at}/phi", (nd, 2 * n + n * n), "hc_phi", nd),
                "alpha": self.vector(f"{at}/alpha", (3,), 0.5 * a, 1.5 * a)
                * jnp.asarray([1.0, 1.0, RES_ALPHA], jnp.float32),
                "b_pre": self.vector(f"{at}/b_pre", (n,), -b3, b3),
                "b_post": self.vector(f"{at}/b_post", (n,), POST_BIAS - b3, POST_BIAS + b3),
                "b_res": self.vector(f"{at}/b_res", (n, n), -b3, b3)
                + jnp.float32(RES_DIAGONAL) * jnp.eye(n, dtype=jnp.float32)}

    def layer(self, i: int) -> dict:
        """Every tensor of layer `i`: drawn as a pass reaches it, kept by the
        check's first call for its second."""
        sparse = i >= self.first_dense
        return {"attn": self.attention(i), "hc1": self.maps(i, "hc1"), "hc2": self.maps(i, "hc2"),
                "ffn": self.ffn(i),
                "experts": [self.expert_block(i, first, min(base.EXPERT_BLOCK, self.e - first))
                            for first in range(0, self.e if sparse else 0, base.EXPERT_BLOCK)]}


# -- the forward pass ----------------------------------------------------------------

def sinkhorn(m, iters: int, hc_eps: float):
    """`m` (..., n, n) positive: `iters` times columns then rows, each over its
    sum plus `hc_eps`. Rows index the outgoing stream."""
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + hc_eps)
        m = m / (m.sum(axis=-1, keepdims=True) + hc_eps)
    return m


@functools.partial(jax.jit, static_argnums=(0, 1))
def _mix_in(hdims: tuple, low: bool, hp: dict, x):
    """The streams `x` (T, n, d) -> (H_pre (T, n), H_post (T, n), H_res (T, n,
    n), u (T, d)). The control rounds the streams as the maps and the mix read
    them; `Phi`'s product is float32 in the program too, as the router's."""
    n, eps, iters, hc_eps, lo, hi = hdims
    if low:
        x = base._round3_traced(x)
    t = x.shape[0]
    with jax.default_matmul_precision("highest"):
        pqr = base._rms(x.reshape(t, -1), eps) @ hp["phi"]
        h_pre = jax.nn.sigmoid(hp["alpha"][0] * pqr[:, :n] + hp["b_pre"])
        h_post = 2.0 * jax.nn.sigmoid(hp["alpha"][1] * pqr[:, n:2 * n] + hp["b_post"])
        logits = hp["alpha"][2] * pqr[:, 2 * n:].reshape(t, n, n) + hp["b_res"]
        h_res = sinkhorn(jnp.exp(jnp.clip(logits, lo, hi)), iters, hc_eps)
        return h_pre, h_post, h_res, jnp.einsum("tj,tjd->td", h_pre, x)


@functools.partial(jax.jit, static_argnums=(0,))
def _mix_out(low: bool, x, h_res, h_post, y):
    """`X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`."""
    if low:
        x = base._round3_traced(x)
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("tij,tjd->tid", h_res, x) + h_post[:, :, None] * y[:, None, :]


def _rope(x, pos, inv_freq, factor: float, interleave: bool):
    """`x` (T, ..., dim) at positions `pos` (T,): column pair i turns by `pos *
    inv_freq[i]`, cos and sin times `factor`; the pair is (2i, 2i + 1) with
    `interleave`, else (i, i + dim / 2)."""
    dim = x.shape[-1]
    ang = (pos.astype(jnp.float32)[:, None] * inv_freq).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,))
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attention(dims: tuple, low: bool, w: dict, x, pos, inv_freq, c_past, r_past, kpos):
    """The mixed stream x (T, d), new tokens at positions `pos`, after tokens
    whose cached rows are `c_past` (P, r) and `r_past` (P, rope) (P = 0: none)
    -> (MLA(RMSNorm(x)), every token's `c_kv`, every token's `k_r`): the
    expanded form, one causal pass over the keys at `kpos` (all P + T of
    them), `QUERY_BLOCK` queries at a time. What comes back beside the
    sublayer's output is what a server caches, all that a later call needs of
    these tokens."""
    h, dn, dr, dv, eps, interleave, on_cos_sin, score_scale = dims
    rnd = base._round3_traced if low else (lambda z: z)
    if low:  # the control: every kernel's values at 3 mantissa bits
        w = {k: base._round3_traced(v) for k, v in w.items()}
    t = x.shape[0]
    rope = functools.partial(_rope, inv_freq=inv_freq, factor=on_cos_sin, interleave=interleave)
    with jax.default_matmul_precision("highest"):
        u = rnd(base._rms(x, eps))
        c_q = rnd(base._rms(u @ w["w_qa"], eps))
        q_nope = jnp.einsum("tq,qhn->htn", c_q, w["w_qb_nope"])
        q_rope = rope(jnp.einsum("tq,qhr->thr", c_q, w["w_qb_rope"]), pos).transpose(1, 0, 2)
        # What a server caches: the normed latent and the rotated shared key.
        c_kv = jnp.concatenate([c_past, rnd(base._rms(u @ w["w_kva_c"], eps))])
        k_r = jnp.concatenate([r_past, rnd(rope(u @ w["w_kva_r"], pos))])
        k_nope = jnp.einsum("tr,rhn->htn", c_kv, w["w_kb"])
        v = jnp.einsum("tr,rhv->htv", c_kv, w["w_vb"])
        past, out = c_past.shape[0], []
        for lo in range(0, t, base.QUERY_BLOCK):
            hi = min(t, lo + base.QUERY_BLOCK)
            s = (jnp.einsum("hqn,hkn->hqk", q_nope[:, lo:hi], k_nope[:, :past + hi])
                 + jnp.einsum("hqr,kr->hqk", q_rope[:, lo:hi], k_r[:past + hi])) * score_scale
            s = jnp.where((kpos[None, :past + hi] <= pos[lo:hi, None])[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,hkv->hqv", jax.nn.softmax(s, axis=-1), v[:, :past + hi]))
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(t, h * dv)
        return rnd(o) @ w["wo"].reshape(h * dv, -1), c_kv, k_r


@functools.partial(jax.jit, static_argnums=(0, 1))
def _dense(eps: float, low: bool, w: dict, x):
    rnd = base._round3_traced if low else (lambda z: z)
    if low:
        w = {k: base._round3_traced(v) for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        u = rnd(base._rms(x, eps))
        return rnd(jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _sparse_whole(eps: float, low: bool, w: dict, x):
    """The parts of a sparse layer every token passes through: -> (the normed
    stream, the router's scores (float32 in the program too, so the control
    leaves them), the shared expert's output)."""
    rnd = base._round3_traced if low else (lambda z: z)
    with jax.default_matmul_precision("highest"):
        u = base._rms(x, eps)
        scores = jax.nn.sigmoid(u @ w["router"])
        u = rnd(u)
        g, up, down = (rnd(w[k]) for k in ("s_gate", "s_up", "s_down"))
        return u, scores, rnd(jax.nn.silu(u @ g) * (u @ up)) @ down


def routed(blocks: list, us: list, tops: list, wts: list, low: bool) -> list:
    """The routed experts' weighted sums of every sequence, in numpy float32:
    each expert (`blocks`: a layer's, `EXPERT_BLOCK` at a time) over the
    tokens that picked it."""
    rnd = base._round3 if low else (lambda z: z)
    ys, first = [np.zeros_like(u) for u in us], 0
    for w in blocks:
        w = {k: rnd(v) for k, v in w.items()}
        for local in range(w["e_down"].shape[0]):
            for u, top, wt, y in zip(us, tops, wts, ys):
                tok, slot = np.nonzero(top == first + local)
                if tok.size == 0:
                    continue
                ut = u[tok]
                gate = ut @ w["e_gate"][local]
                hid = gate / (1.0 + np.exp(-gate)) * (ut @ w["e_up"][local])
                y[tok] += wt[tok, slot][:, None] * (rnd(hid) @ w["e_down"][local])
        first += w["e_down"].shape[0]
    return ys


def forward(m: Model, layers, sequences: list[np.ndarray], carry=None, low: bool = False,
            seen=None):
    """The NEW tokens `sequences` of each sequence through `layers` (an
    iterable of `Model.layer` in order: each is drawn as the pass reaches it),
    after the tokens that `carry` (what an earlier call returned; None: none)
    holds the cached rows of -> (the new tokens' hidden states at the EXIT,
    the streams summed, before the last norm; the carry after them: by layer,
    a sequence, its tokens' `c_kv` and `k_r`). `low`: the control. `seen`: a
    list that takes every sublayer's maps and streams (what a test or a
    sizing script looks at)."""
    embed = m.embed()
    # The entry: every stream begins as the token's embedding.
    xs = [jnp.tile(jnp.asarray(embed[np.asarray(ids)])[:, None, :], (1, m.n, 1))
          for ids in sequences]
    del embed
    dims = (m.heads, m.dn, m.dr, m.dv, m.eps, m.interleave, m.on_cos_sin, m.score_scale)
    hdims = (m.n, m.eps, m.iters, m.hc_eps, *m.clamp)
    none = (jnp.zeros((0, m.r), jnp.float32), jnp.zeros((0, m.dr), jnp.float32))
    inv_freq, after = jnp.asarray(m.inv_freq), []

    def sublayer(hp, xs, f):
        mixed = [_mix_in(hdims, low, hp, x) for x in xs]
        ys = f([u for *_h, u in mixed])
        out = [_mix_out(low, x, h_res, h_post, y).block_until_ready()
               for x, (_pre, h_post, h_res, _u), y in zip(xs, mixed, ys)]
        if seen is not None:
            seen.append({"maps": [h[:3] for h in mixed], "y": ys, "streams": out})
        return out

    for i, w in enumerate(layers):
        kept = []

        def attend(us, i=i, w=w, kept=kept):
            ys = []
            for n, u in enumerate(us):
                c_past, r_past = carry[i][n] if carry else none
                past, t = c_past.shape[0], u.shape[0]
                y, c_kv, k_r = _attention(dims, low, w["attn"], u, past + jnp.arange(t), inv_freq,
                                          c_past, r_past, jnp.arange(past + t))
                ys.append(y)
                kept.append((c_kv, k_r))
            return ys

        def feed(us, i=i, w=w):
            if i < m.first_dense:
                return [_dense(m.eps, low, w["ffn"], u) for u in us]
            whole = [_sparse_whole(m.eps, low, {k: v for k, v in w["ffn"].items() if k != "e_bias"}, u)
                     for u in us]
            chosen = [base.picks(m, np.asarray(scores), w["ffn"]["e_bias"])
                      for _u, scores, _s in whole]
            ys = routed(w["experts"], [np.asarray(u) for u, _s, _r in whole],
                        [t for t, _ in chosen], [wt for _, wt in chosen], low)
            return [shared + jnp.asarray(y) for (_u, _s, shared), y in zip(whole, ys)]

        xs = sublayer(w["hc2"], sublayer(w["hc1"], xs, attend), feed)
        after.append(kept)
    return [x.sum(axis=1) for x in xs], after   # the exit: the streams summed


def hidden_states(m: Model, sequences: list[np.ndarray], low: bool = False, seen=None) -> list:
    """Hidden states at the exit of whole sequences, each layer drawn once and dropped."""
    return forward(m, (m.layer(i) for i in range(m.n_layers)), sequences, None, low, seen)[0]


def _log_softmax(m: Model, head, h):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.nn.log_softmax(base._rms(h, m.eps) @ head, axis=-1))


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the vocabulary at positions `first_row`
    onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low)
    head = m.head()
    return [_log_softmax(m, head, h[r:]) for h, r in zip(hs, first_rows)]


def prompt_pass(m: Model, prompts: list[np.ndarray], low: bool = False, stop=None):
    """The prompts' part of the check's pass: every layer drawn once and KEPT
    (float32 copies of every kernel: 19 GB at the cell's size), the prompts
    taken through them -> (the layers, each prompt's last hidden state, the
    carry). `stop`: an event that ends it at the next layer (`in_background`)."""
    layers = []

    def drawn():
        for i in range(m.n_layers):
            if stop is not None and stop.is_set():
                raise RuntimeError("the prompts' pass was stopped: the run is ending")
            layers.append(m.layer(i))
            yield layers[-1]

    hs, carry = forward(m, drawn(), prompts, None, low)
    return layers, [h[-1:] for h in hs], carry


def in_background(m: Model, prompts: list[np.ndarray], low: bool) -> Future:
    """`prompt_pass` in a thread of its own, because the served tokens' part
    waits for the server and this does not (`reference/hybrid_ffn.py` says why
    the thread is a daemon that the interpreter's exit stops at the next layer
    and waits for)."""
    out, stop = Future(), threading.Event()

    def work():
        try:
            out.set_result(prompt_pass(m, prompts, low, stop))
        except BaseException as e:  # handed to the caller of `result`
            out.set_exception(e)

    thread = threading.Thread(target=work, daemon=True)
    thread.start()
    atexit.register(lambda: (stop.set(), thread.join()))
    return out


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (*(k for k in base.ARCH_KEYS if k not in (
    "head_dim", "qk_head_dim", "rope_interleave")),
    "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")


def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the benchmark:
    the published keys as they are (only the depth is cut), and the draw's
    scales."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """`reference/mla.py`'s sizes (this file's keys in `arch`), the streams and
    the sublayers that have maps."""
    sz = base.sizes_from_config(cfg)
    arch = arch_from_config(cfg)
    return {**sz, "arch": arch, "streams": int(arch.get("hc_mult", 1)),
            "sublayers": 2 * sz["layers"], "hc_iters": int(arch.get("hc_sinkhorn_iters", 20))}


def prepare(seed: int, sizes: dict, cfg: dict, work: str):
    """`reference/mla.py` `prepare` (no checkpoint: the program draws its
    weights by `assumed.weights`; the model's config file in the published
    layout), and whether this run is the control."""
    weights, options, ref = base.prepare(seed, sizes, cfg, work)
    return weights, options, dict(
        ref, low=cfg["check"].get("reference_inputs") == "3-bit-mantissa")


def reference_answers(ref: dict, inputs: list[dict], sizes: dict) -> dict:
    """The pass is teacher-forced on the served tokens, so their part waits
    for them (`compare`); the PROMPTS' part starts now, beside the server's
    start-up (`in_background`)."""
    model = Model(sizes["arch"], ref["seed"], ref["dtype"])
    prompts = [np.asarray(inp["ids"], np.int64) for inp in inputs]
    return {"ref": ref, "inputs": inputs, "sizes": sizes, "model": model,
            "prompts": in_background(model, prompts, ref["low"])}


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    """`reference/mla.py` `compare`'s statistic over this family's pass, which
    is made in two calls of `forward`: the prompts while the server started,
    the served tokens now, continued from the rows the first cached."""
    sz, low, model = reference["sizes"], reference["ref"]["low"], reference["model"]
    tails = []
    for answer, inp in zip(served, reference["inputs"], strict=True):
        tokens = [int(t) for t in answer.get("tokens", [])]
        lp = answer.get("logprobs") or {}
        if len(tokens) != inp["max_new"] or answer.get("n_tokens") != len(tokens) \
                or np.shape(lp.get("ids")) != (len(tokens), LOGPROBS) \
                or np.shape(lp.get("values")) != (len(tokens), LOGPROBS):
            return float("inf"), (f"logprob_rms=inf: a request of {inp['max_new']} tokens with "
                                  f"logprobs {LOGPROBS} got {len(tokens)} tokens, logprobs of "
                                  f"shape {np.shape(lp.get('ids'))}")
        ids = np.asarray(tokens[:-1], np.int64)
        if ids.size and (ids.min() < 0 or ids.max() >= sz["vocab"]):
            return float("inf"), "logprob_rms=inf: a served token lies outside the vocabulary"
        tails.append(ids)
    t0 = time.monotonic()
    layers, last, carry = reference["prompts"].result()
    waited = time.monotonic() - t0
    some = [n for n, ids in enumerate(tails) if ids.size]   # an answer of one token has no tail
    hs, _ = forward(model, layers, [tails[n] for n in some],
                    [[per[n] for n in some] for per in carry], low)
    del layers, carry
    head, rows = model.head(), dict(zip(some, hs))
    gaps = [base.centred_gap(a, _log_softmax(
        model, head, jnp.concatenate([h0, rows[n]]) if n in rows else h0))
        for n, (a, h0) in enumerate(zip(served, last))]
    print(f"[reference] waited {waited:.1f} s for the prompts' pass; {sum(len(t) for t in tails)} "
          f"served tokens of {len(tails)} sequences through {model.n_layers} layers of "
          f"{model.n} streams in {time.monotonic() - t0 - waited:.1f} s", flush=True)
    # One number a generated position: the RMS of its eight centred differences.
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
                  + (" [the reference's matrix inputs, what a server caches and the streams as the "
                     "maps read them at 3 mantissa bits: a control]" if low else ""))
