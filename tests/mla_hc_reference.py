"""The plain reference of the `mla_hc` family for tier-1 (ISSUE 46): the
architecture's forward pass in straightforward float32 (`jax.numpy` under
`jax.default_matmul_precision("highest")`; the routed experts' products in
`numpy`), latent attention in its EXPANDED form only, with no cache of pages, no
chunking, no absorbed product and no kernel, and the weights recipe written
down again. It imports nothing of `tpuserve`; what it shares with the `mla`
family's reference (the draw, the tensors of a layer, the rounding of the
control, the router's picks) it takes from `tests/mla_reference.py`.
`benchmark/reference/mla_hc.py` holds the benchmark's copy of the same forward
pass (its header has the layer's equations and what is assumed);
`tests/test_mla_hc.py` holds the two to the same numbers.
"""

from __future__ import annotations

import atexit
import functools
import math
import threading
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from tests import mla_reference as base

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
