"""The plain reference for the `hybrid_blk` family: a language model whose layers
are two sublayers each (a mixer chosen by `mixer_types`: LINEAR ATTENTION with a
constant decay a head, or grouped-query softmax attention over the BLOCKS of
keys a query's KV group picks by scores over mean-pooled keys; then a dense
SwiGLU) under three scalar multipliers, written down from its published
`config.json` in straightforward float32 (`jax.numpy` under
`jax.default_matmul_precision("highest")`), with no cache, no pages, no tiles, no
chunked recurrence, no threshold search and no kernel. It imports nothing of the
program. The recipe of the weights, the roundings of the control and the
statistic (`reference/hybrid.py`'s centred gap, `reference/hybrid_conv.py`'s
quartile beside the RMS) are those files' own functions, imported and not edited.

THE EQUATIONS (ISSUE 68, Tentpole 1; every line marked A is ASSUMED, and the
configuration file repeats it under `assumed` with its reason). `d` =
`hidden_size`, `e` = `scale_emb`, `r` = `scale_depth / sqrt(L)` with L the
PUBLISHED `num_hidden_layers` (`scale_depth_layers` in the program's file), `s` =
`hidden_size / dim_model_base`, eps = `rms_norm_eps`, no bias anywhere,
`N(x; g) = x / sqrt(mean(x^2) + eps) * g`:

    h_0 = e E[ids]
    layer i:  h <- h + r mixer_i(N(h; g1_i));   v = N(h; g2_i);   h <- h + r (silu(v W_gate) * (v W_up)) W_down
    logits = N(h; g_f) W_head / s                (`mup_denominator` is read by nothing: A)

    "lightning-attn" (u the normed stream at position t; H = `lightning_nh` = `lightning_nkv` heads of
    D = `lightning_head_dim`):
      q, k, v = u W_q, u W_k, u W_v              (T, H, D)
      q <- N_head(q; g_q),  k <- N_head(k; g_k)   `qk_norm`: over a head's D columns, ONE gain of D for all heads (A)
      q, k turned at t                           `lightning_use_rope`: all D columns, pairs (j, j + D/2), `rope_theta`;
                                                 the norm FIRST, then the rotary
      S_t = lambda_h S_{t-1} + k_t^T v_t         (D x D float32 a head, zeros before the prompt)
      o_t = D^-1/2 q_t S_t                       `lightning_scale`
      lambda_h = exp(-2^(-8 (h + 1) / H))        h = 0..H-1, the same in every layer (A: Lightning Attention's slopes)
      o <- N_head(o; g_o)                        `use_output_norm`: ONE gain of D (A)
      o <- o * sigmoid(u W_g)                    `use_output_gate`: elementwise, from the stream, before W_o (A)
      out = o W_o

    "minicpm4" (Hq = `num_attention_heads` on KV = `num_key_value_heads` heads of hd = `head_dim`; a KV group
    g is Hq / KV query heads; `sparse_config`: kernel_size K, kernel_stride St, block_size B, topk, init_blocks,
    window_size W, dense_len):
      q, k, v = u W_q, u W_k, u W_v;  `qk_norm` as above (A: also here);  `attn_use_rope` false: NO position term
      scale = hd^-1/2,  blk(s) = floor(s / B)
      t <  dense_len:  a causal softmax over every s <= t
                       (A: the switch is taken A QUERY, by its own position)
      t >= dense_len:  Kc_g[j] = mean(k_g[St j .. St j + K - 1])   for every window whole at or before t
                       p_h[j]  = softmax_j(scale q_h(t) . Kc_g[j]) for each head h of the group
                       sc_g[j] = sum_{h in g} p_h[j]
                       B_g[b]  = max(sc_g[j] : the windows j that overlap block b, j exists)
                                 (K = 32, St = 16, B = 64: 4 b - 1 <= j <= 4 b + 3)
                       B_g[b]  = +inf for b < init_blocks and for blk(t) - b < W / B
                       P_g(t)  = the min(topk, blk(t) + 1) blocks b <= blk(t) of largest B_g[b], the lower
                                 index on a tie (`jax.lax.top_k`)
                       o_h(t)  = a softmax over {s <= t : blk(s) in P_g(t)} of scale q_h(t) . k_g(s), times v_g(s)
      o <- o * sigmoid(u W_g)                    `attn_use_output_gate`: elementwise by head before W_o
      out = o W_o
    The pooled keys, the scores and the picks are float32 here. The published kernels approximate the scores'
    softmax normaliser from a coarser pooling; this does not (A).

A sequence's rows go through a sublayer in blocks of `ROWS` queries (each over
ALL its keys at once): the same full pass, evaluated a block of rows at a time
so that 9,304 positions x 32 heads of scores need not exist together.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`): the names,
shapes, fan-ins and ranges are the program's (`tpuserve/models/mixers.py`
`LightningMixer._lightning_tensors`, `HeadNorms._qk_gains`,
`PlainAttention._attention_tensors`; `hybrid_blk._tensors`). The norms' gains
over the stream and `g_o` are ones; `g_q`, `g_k` are float32 vectors drawn inside
`qk_gain`.

THE CHECK (`compare`): `reference/mla.py`'s kind of statistic as `hybrid.py`
computes it (`logprob_q25` beside `logprob_rms`, centred top-8 log-probabilities,
teacher-forced on the served tokens), in TWO calls of `forward` (the prompts
while the server starts, the served tokens after, continued from what the prompts
left: keys, values and states, which one call over a whole sequence never
needs), and beside them `picks_moved`: of the sample's PICKED queries (t >=
dense_len, a KV group each), those whose block set differs where the reference's
own q and k are first rounded to the served type: how often a score that close
to the topk-th rank is rounded across it (`attention` counts both as it goes).
`check.reference_inputs = "3-bit-mantissa"` (a control, never a cell) rounds the
inputs of the reference's matrix products (every kernel, the normed stream that
enters a sublayer, the context before `W_o`, the hidden rows before `W_down`) to
3 explicit mantissa bits.
"""

from __future__ import annotations

import atexit
import functools
import math
import threading
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import spec

hy = spec.load_module("reference", "hybrid")
hc = spec.load_module("reference", "hybrid_conv")   # its `_statistic`: the quartile beside the RMS
LOGPROBS = hy.LOGPROBS
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 1.0, "qk_gain": [1.0, 3.0], "v": 1.0, "o": 1.0, "gate": 1.0,
    "lin_qk": 1.0, "lin_v": 1.0, "lin_o": 1.0, "lin_gate": 1.0, "ffn_in": 1.0, "ffn_out": 1.0}
# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (
    "model_type", "attention_bias", "attn_use_output_gate", "attn_use_rope", "dim_model_base",
    "head_dim", "hidden_act", "hidden_size", "intermediate_size", "lightning_head_dim",
    "lightning_nh", "lightning_nkv", "lightning_scale", "lightning_use_rope",
    "max_position_embeddings", "mixer_types", "mup_denominator", "num_attention_heads",
    "num_hidden_layers", "num_key_value_heads", "qk_norm", "rms_norm_eps", "rope_theta",
    "scale_depth", "scale_emb", "sparse_config", "tie_word_embeddings", "use_output_gate",
    "use_output_norm", "vocab_size")
EXACT = ("q_norm", "k_norm")   # float32 vectors, no matrix product's input
ROWS = 512   # query rows a block of an attention sublayer's evaluation


# -- weights by recipe -------------------------------------------------------------

class Model:
    """The architecture's numbers and its tensors' shapes; draws one tensor
    or one layer at a time."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        a = self.a = arch
        self.seed, self.dtype = int(seed), jnp.dtype(served_dtype)
        self.d, self.n_layers = int(a["hidden_size"]), int(a["num_hidden_layers"])
        self.kinds = list(a["mixer_types"])
        assert len(self.kinds) == self.n_layers
        assert set(self.kinds) <= {"minicpm4", "lightning-attn"}, self.kinds
        assert a.get("hidden_act", "silu") == "silu" and a["lightning_nkv"] == a["lightning_nh"]
        self.eps = float(a.get("rms_norm_eps", 1e-6))
        self.heads, self.kv = int(a["num_attention_heads"]), int(a["num_key_value_heads"])
        self.hd = int(a.get("head_dim") or self.d // self.heads)
        self.lh, self.ld = int(a["lightning_nh"]), int(a["lightning_head_dim"])
        scale = a.get("lightning_scale", "1/sqrt(d)")
        self.l_scale = self.ld ** -0.5 if scale == "1/sqrt(d)" else float(scale)
        self.theta = float(a.get("rope_theta", 10000.0))
        self.qk_norm = bool(a.get("qk_norm", False))
        self.attn_rope, self.attn_gate = bool(a.get("attn_use_rope", True)), \
            bool(a.get("attn_use_output_gate", False))
        self.l_rope, self.l_norm, self.l_gate = bool(a.get("lightning_use_rope", False)), \
            bool(a.get("use_output_norm", False)), bool(a.get("use_output_gate", False))
        self.sparse = {k: int(v) for k, v in a["sparse_config"].items()}
        depth = int(a.get("scale_depth_layers", self.n_layers))
        self.e = float(a.get("scale_emb", 1.0))
        self.r = float(a.get("scale_depth", math.sqrt(depth))) / math.sqrt(depth)
        self.s = self.d / float(a.get("dim_model_base", self.d))
        self.ffn = int(a["intermediate_size"])
        self.vocab = int(a["vocab_size"])
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        # [moved, pairs] over every pass made with this model (`attention`)
        self.picks = np.zeros((2,), np.int64)

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
        if self.a.get("tie_word_embeddings", False):
            return self.embed().T
        return self.tensor("head", (self.d, self.vocab), "head", self.d)

    def layer(self, i: int) -> dict:
        t, L, d, s = self.tensor, f"layer{i}", self.d, self.scales
        if self.kinds[i] == "minicpm4":
            h, kv, hd = self.heads, self.kv, self.hd
            w = {"wq": t(f"{L}/wq", (d, h, hd), "qk", d), "wk": t(f"{L}/wk", (d, kv, hd), "qk", d),
                 "wv": t(f"{L}/wv", (d, kv, hd), "v", d), "wo": t(f"{L}/wo", (h, hd, d), "o", h * hd)}
            if self.attn_gate:
                w["wg"] = t(f"{L}/wg", (d, h, hd), "gate", d)
            width = hd
        else:
            h, D = self.lh, self.ld
            w = {"wq": t(f"{L}/wq", (d, h, D), "lin_qk", d), "wk": t(f"{L}/wk", (d, h, D), "lin_qk", d),
                 "wv": t(f"{L}/wv", (d, h, D), "lin_v", d), "wo": t(f"{L}/wo", (h, D, d), "lin_o", h * D)}
            if self.l_gate:
                w["wg"] = t(f"{L}/wg", (d, h, D), "lin_gate", d)
            width = D
        if self.qk_norm:
            for name in ("q_norm", "k_norm"):
                w[name] = self.vector(f"{L}/{name}", (width,), *s["qk_gain"])
        for name in ("w_gate", "w_up"):
            w[name] = t(f"{L}/{name}", (d, self.ffn), "ffn_in", d)
        w["w_down"] = t(f"{L}/w_down", (self.ffn, d), "ffn_out", self.ffn)
        return w


# -- the forward pass ----------------------------------------------------------------

def _rnd(low: bool):
    return hy._round3_whole if low else (lambda z: z)


def _rope(x, pos, theta: float):
    """x (T, heads, D) turned at positions `pos` (T,), pairs (j, j + D / 2)."""
    half = x.shape[-1] // 2
    ang = pos[:, None] * theta ** (-jnp.arange(half) / half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    return jnp.concatenate([x[..., :half] * cos - x[..., half:] * sin,
                            x[..., half:] * cos + x[..., :half] * sin], axis=-1)


def _qkv(w: dict, u, pos, qk_norm: bool, rope: bool, theta: float, eps: float):
    q, k, v = (jnp.einsum("td,dhk->thk", u, w[n]) for n in ("wq", "wk", "wv"))
    if qk_norm:
        q, k = hy._rms(q, eps) * w["q_norm"], hy._rms(k, eps) * w["k_norm"]
    if rope:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    return q, k, v


@functools.partial(jax.jit, static_argnums=(0, 1))
def lightning(dims: tuple, low: bool, w: dict, u, s0, pos0):
    """The linear-attention mixer on the normed rows `u` (T, d) at positions
    `pos0`.., token by token from the state `s0` (H, D, D) the sequence's
    earlier tokens left (zeros from position 0). -> (out (T, d), the state it
    ends with)."""
    H, D, scale, qk_norm, rope, theta, eps, out_norm, out_gate = dims
    rnd = _rnd(low)
    with jax.default_matmul_precision("highest"):
        pos = pos0 + jnp.arange(u.shape[0])
        q, k, v = _qkv(w, u, pos, qk_norm, rope, theta, eps)
        lam = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, H + 1) / H))[:, None, None]

        def token(S, row):
            q_t, k_t, v_t = row
            S = lam * S + k_t[:, :, None] * v_t[:, None, :]
            return S, jnp.sum(q_t[:, :, None] * S, axis=1) * scale

        s_end, o = jax.lax.scan(token, s0, (q, k, v))
        if out_norm:
            o = hy._rms(o, eps)       # the gain g_o is ones
        if out_gate:
            o = o * jax.nn.sigmoid(jnp.einsum("td,dhk->thk", u, w["wg"]))
        return jnp.einsum("thk,hkd->td", rnd(o), w["wo"]), s_end


def pooled_keys(k, kernel: int, stride: int):
    """k (S, KV, hd) -> the mean of every whole window (J, KV, hd), J = (S -
    kernel) // stride + 1 (0 rows where S < kernel)."""
    n = max(0, (k.shape[0] - kernel) // stride + 1)
    at = stride * jnp.arange(n)[:, None] + jnp.arange(kernel)[None, :]
    return jnp.mean(k[at], axis=1)


def block_scores(q, k, pos, sp: tuple, scale: float):
    """The block scores `B_g[b]` of the queries q (R, Hq, hd) at positions `pos`
    (R,) over the keys k (S, KV, hd), S > max(pos) -> (R, KV, NB) float32, NB =
    ceil(S / B): +inf a forced block, -inf a block past the query's own."""
    kernel, stride, B, _topk, init, local, _dense = sp
    R, Hq, hd = q.shape
    kv = k.shape[1]
    kc = pooled_keys(k, kernel, stride)
    J, nb = kc.shape[0], -(-k.shape[0] // B)
    exists = (stride * jnp.arange(J) + kernel - 1)[None, :] <= pos[:, None]          # (R, J)
    s = jnp.einsum("rkgd,jkd->rkgj", q.reshape(R, kv, Hq // kv, hd), kc) * scale
    p = jax.nn.softmax(jnp.where(exists[:, None, None, :], s, -jnp.inf), axis=-1)
    sc = jnp.where(exists[:, None, :], jnp.sum(jnp.nan_to_num(p), axis=2), -jnp.inf)  # (R, KV, J)
    # the windows that overlap block b: stride j + kernel - 1 >= B b and stride j <= B b + B - 1
    j, b = jnp.arange(J)[None, :], jnp.arange(nb)[:, None]
    touch = (stride * j + kernel - 1 >= B * b) & (stride * j <= B * b + B - 1)        # (NB, J)
    score = jnp.max(jnp.where(touch[None, None], sc[:, :, None, :], -jnp.inf), axis=-1)
    own = (pos // B)[:, None, None]
    b = jnp.arange(nb)[None, None, :]
    forced = (b < init) | (own - b < local)
    return jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, score))


def select_blocks(q, k, pos, sp: tuple, scale: float):
    """`P_g(t)` as a mask (R, KV, NB): the min(topk, blk(t) + 1) blocks of
    largest score, the lower index on a tie (`jax.lax.top_k`)."""
    score = block_scores(q, k, pos, sp, scale)
    nb = score.shape[-1]
    val, at = jax.lax.top_k(score, min(sp[3], nb))
    picked = (at[..., None] == jnp.arange(nb)) & (val[..., None] > -jnp.inf)
    return jnp.any(picked, axis=-2)


@functools.partial(jax.jit, static_argnums=(0, 1))
def attention(dims: tuple, low: bool, w: dict, u, k0, v0):
    """The attention mixer on the normed rows `u` (T, d) over themselves and
    the sequence's earlier keys and values `k0`, `v0` (T0, KV, hd): a query
    under `dense_len` over every key, another over its picked blocks' keys. ->
    (out (T, d), all keys, all values, [moved, pairs]: of the `pairs` (query, KV
    group) that picked, those whose block set differs where q and k are first
    rounded to the served type)."""
    heads, kv, hd, qk_norm, rope, theta, eps, gate, sp, served = dims
    B, dense_len = sp[2], sp[6]
    t, t0, rnd = u.shape[0], k0.shape[0], _rnd(low)
    scale = hd ** -0.5
    as_served = lambda z: z.astype(served).astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        pos = t0 + jnp.arange(t)
        q, k, v = _qkv(w, u, pos, qk_norm, rope, theta, eps)
        k, v = jnp.concatenate([k0, k], axis=0), jnp.concatenate([v0, v], axis=0)
        S = t0 + t
        kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # (KV, S, hd)

        def rows(a):
            q_r, pos_r, real = a                                 # (R, Hq, hd), (R,), (R,)
            see = jnp.arange(S)[None, :] <= pos_r[:, None]       # (R, S)
            see = jnp.broadcast_to(see[:, None, :], (q_r.shape[0], kv, S))
            moved = jnp.zeros((2,), jnp.int32)

            def under_picks():
                blocks = select_blocks(q_r, k, pos_r, sp, scale)             # (R, KV, NB)
                of_key = jnp.take(blocks, jnp.arange(S) // B, axis=-1)       # (R, KV, S)
                picked = (real & (pos_r >= dense_len))[:, None]
                other = select_blocks(as_served(q_r), as_served(k), pos_r, sp, scale)
                return see & (of_key | (pos_r < dense_len)[:, None, None]), jnp.stack(
                    [jnp.sum(picked & jnp.any(blocks != other, axis=-1)),
                     jnp.sum(picked) * kv]).astype(jnp.int32)

            if S > dense_len:   # a static length: a short sequence has no picked query;
                # and a block of rows that all lie under dense_len picks nothing
                see, moved = jax.lax.cond(jnp.any(pos_r >= dense_len), under_picks,
                                          lambda: (see, moved))
            # a KV group's heads side by side as rows of ONE product a group (query
            # head h reads KV head h // g): the same sums, the host's fast path
            R, g = q_r.shape[0], heads // kv
            qg = q_r.reshape(R, kv, g, hd).transpose(1, 0, 2, 3).reshape(kv, R * g, hd)
            s = jnp.einsum("kmd,ksd->kms", qg, kt) * scale
            mask = jnp.repeat(see.transpose(1, 0, 2), g, axis=1)             # (KV, R g, S)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            o = jnp.einsum("kms,ksd->kmd", p, vt).reshape(kv, R, g, hd)
            return o.transpose(1, 0, 2, 3).reshape(R, heads, hd), moved

        n = -(-t // ROWS)
        pad = n * ROWS - t
        if n == 1:
            o, moved = rows((q, pos, jnp.ones((t,), bool)))
        else:
            qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(n, ROWS, heads, hd)
            pp = jnp.pad(pos, (0, pad), constant_values=S - 1).reshape(n, ROWS)
            real = (jnp.arange(n * ROWS) < t).reshape(n, ROWS)
            o, moved = jax.lax.map(rows, (qp, pp, real))
            o, moved = o.reshape(n * ROWS, heads, hd)[:t], jnp.sum(moved, axis=0)
        if gate:
            o = o * jax.nn.sigmoid(jnp.einsum("td,dhk->thk", u, w["wg"]))
        return jnp.einsum("qhd,hdo->qo", rnd(o), w["wo"]), k, v, moved


@jax.jit
def _gated(v, w_gate, w_up):
    with jax.default_matmul_precision("highest"):
        return jax.nn.silu(v @ w_gate) * (v @ w_up)


def mixer_dims(m: Model, kind: str) -> tuple:
    if kind == "minicpm4":
        sp = m.sparse
        return (m.heads, m.kv, m.hd, m.qk_norm, m.attn_rope, m.theta, m.eps, m.attn_gate,
                (sp["kernel_size"], sp["kernel_stride"], sp["block_size"], sp["topk"],
                 sp["init_blocks"], sp["window_size"] // sp["block_size"], sp["dense_len"]),
                str(m.dtype))
    return (m.lh, m.ld, m.l_scale, m.qk_norm, m.l_rope, m.theta, m.eps, m.l_norm, m.l_gate)


def forward(m: Model, layers, tokens: list[np.ndarray], carry: list | None = None,
            low: bool = False) -> tuple[list, list]:
    """The rows of `tokens` (ids, one array a sequence) through every layer,
    continued from `carry`: what the same sequences' EARLIER tokens left, a
    layer and a sequence (a linear-attention layer's state and the position it
    stands at; an attention layer's keys and values), or None from position 0.
    -> (final hidden states before the last norm, the carry they leave). One
    call over a whole sequence is the plain full pass; the check makes it in
    two, the prompt while the server starts and the served tokens after,
    because the prompt does not wait for them. `layers`: an iterable of
    `Model.layer(i)`."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) * m.e for ids in tokens]
    del embed
    rnd = _rnd(low)
    t_kind, left = {}, []
    zeros = (jnp.zeros((m.lh, m.ld, m.ld), jnp.float32), 0)
    none = (jnp.zeros((0, m.kv, m.hd), jnp.float32),) * 2
    with jax.default_matmul_precision("highest"):
        for i, (kind, w) in enumerate(zip(m.kinds, layers, strict=True)):
            t0 = time.monotonic()
            if low:  # the control: every kernel
                w = {k: (v if k in EXACT else np.asarray(hy._round3_whole(v)))
                     for k, v in w.items()}
            w = {k: jnp.asarray(v) for k, v in w.items()}
            mix = {k: v for k, v in w.items() if not k.startswith("w_")}
            left.append([])
            for n, x in enumerate(xs):
                before = carry[i][n] if carry else (zeros if kind == "lightning-attn" else none)
                if x.shape[0] == 0:   # no row of this sequence in this call
                    left[i].append(before)
                    continue
                u = rnd(hy._rms(x, m.eps))
                if kind == "lightning-attn":
                    y, s_end = lightning(mixer_dims(m, kind), low, mix, u, before[0],
                                         jnp.int32(before[1]))
                    after = (s_end, before[1] + x.shape[0])
                else:
                    y, *after, moved = attention(mixer_dims(m, kind), low, mix, u, *before)
                    m.picks = m.picks + np.asarray(moved, np.int64)
                left[i].append(tuple(after))
                x = x + m.r * y
                v = rnd(hy._rms(x, m.eps))
                f = hy._project(rnd(_gated(v, w["w_gate"], w["w_up"])), w["w_down"])
                xs[n] = (x + m.r * f).block_until_ready()
            del w, mix
            t_kind[kind] = t_kind.get(kind, 0.0) + time.monotonic() - t0
    print("[reference] " + str(sum(len(s) for s in tokens)) + " tokens through "
          + ", ".join(f"{m.kinds.count(k)} {k} layers (each with its feed-forward) in "
                      f"{t_kind[k]:.1f} s" for k in t_kind), flush=True)
    return xs, left


def hidden_states(m: Model, sequences: list[np.ndarray], low: bool = False) -> list:
    """Final hidden states of each whole sequence, in ONE pass from position
    0; layers outermost, each drawn once and dropped."""
    return forward(m, (m.layer(i) for i in range(len(m.kinds))), sequences, None, low)[0]


def _log_softmax(m: Model, head, rows) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.nn.log_softmax(hy._rms(rows, m.eps) @ head / m.s, axis=-1))


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the vocabulary at positions `first_row`
    onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low)
    head = jnp.asarray(m.head())
    return [_log_softmax(m, head, h[r:]) for h, r in zip(hs, first_rows)]


def prompt_pass(m: Model, prompts: list[np.ndarray], low: bool = False, stop=None):
    """The prompts' part of the check's pass: every layer drawn once and KEPT
    (float32 copies of every kernel, 4.4 GB at the cell's size), the prompts
    taken through them. -> (the layers, each prompt's last hidden state, the
    carry). `stop`: an event that ends it at the next layer (`in_background`)."""
    layers = []

    def drawn():
        for i in range(len(m.kinds)):
            if stop is not None and stop.is_set():
                raise RuntimeError("the prompts' pass was stopped: the run is ending")
            layers.append(m.layer(i))
            yield layers[-1]

    hs, carry = forward(m, drawn(), prompts, None, low)
    return layers, [h[-1:] for h in hs], carry


def in_background(m: Model, prompts: list[np.ndarray], low: bool) -> Future:
    """`prompt_pass` in a thread of its own, because the served tokens' part
    waits for the server and this does not (`reference/hybrid_ffn.py`'s way)."""
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


# -- the configuration, for the harness ------------------------------------------------

def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the benchmark:
    the published keys as the file holds them (`reduced` cuts the depth alone:
    every layer kept is whole), the PUBLISHED depth for `scale_depth`, and the
    drawn scales."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    depth = cfg.get("published", {}).get("num_hidden_layers")
    if depth:
        arch["scale_depth_layers"] = int(depth)
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """What this file, flops/hybrid_blk.py and the per-layer readers need
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
    n_a = m.kinds.count("minicpm4")
    n_m = m.n_layers - n_a
    return {
        "arch": a, "d_model": m.d, "layers": m.n_layers, "n_mamba": n_m, "n_attn": n_a,
        "ffn_width": m.ffn, "heads": m.heads, "kv_heads": m.kv, "head_dim": m.hd,
        "lin_heads": m.lh, "lin_head_dim": m.ld, "sparse": dict(m.sparse),
        "attn_gate": m.attn_gate, "lin_gate": m.l_gate,
        "state_bytes_per_slot": n_m * m.lh * m.ld * m.ld * 4,
        "tied": bool(a.get("tie_word_embeddings", False)), "vocab": m.vocab, "vocab_first": 0,
        "reference_inputs": cfg["check"].get("reference_inputs", ""),
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


def reference_answers(ref: dict, inputs: list[dict], sizes: dict) -> dict:
    """The pass is teacher-forced on the served tokens, so their part waits
    for them (`compare`); the prompts' part starts now, while the server
    starts."""
    model = Model(sizes["arch"], ref["seed"], ref["dtype"])
    low = sizes.get("reference_inputs") == "3-bit-mantissa"
    prompts = [np.asarray(inp["ids"], np.int64) for inp in inputs]
    return {"inputs": inputs, "sizes": sizes, "model": model, "low": low,
            "prompts": in_background(model, prompts, low)}


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    """`reference/hybrid.py`'s statistic over this family's pass: a generated
    position's number is the RMS of its eight centred differences; the
    statistic is the largest, over the requests, of the lower quartile of a
    request's positions, beside the RMS over all positions against
    `check.rms_limit` (the number compared with `check.limit` is the larger of
    the quartile and the RMS scaled by `limit / rms_limit`); and `picks_moved`."""
    sz = reference["sizes"]
    after = []
    for answer, inp in zip(served, reference["inputs"], strict=True):
        tokens = [int(t) for t in answer.get("tokens", [])]
        lp = answer.get("logprobs") or {}
        if len(tokens) != inp["max_new"] or answer.get("n_tokens") != len(tokens) \
                or np.shape(lp.get("ids")) != (len(tokens), LOGPROBS) \
                or np.shape(lp.get("values")) != (len(tokens), LOGPROBS):
            return float("inf"), (f"logprob_rms=inf: a request of {inp['max_new']} tokens with "
                                  f"logprobs {LOGPROBS} got {len(tokens)} tokens, logprobs of "
                                  f"shape {np.shape(lp.get('ids'))}")
        ids = np.asarray(tokens[:-1], np.int64)    # the last served token predicts nothing served
        if len(ids) and (ids.min() < 0 or ids.max() >= sz["vocab"]):
            return float("inf"), "logprob_rms=inf: a served token lies outside the vocabulary"
        after.append(ids)
    low, m = reference["low"], reference["model"]
    assert low == (cfg["check"].get("reference_inputs") == "3-bit-mantissa")
    layers, last, carry = reference["prompts"].result()
    # Every request's served tokens as rows of ONE length (ids of 0 behind the
    # shorter ones: the model is causal, so a row never sees a later one).
    longest = max(len(ids) for ids in after)
    hs, _ = forward(m, layers, [np.pad(ids, (0, longest - len(ids))) for ids in after], carry, low)
    hs = [h[:len(ids)] for h, ids in zip(hs, after)]
    moved, pairs = (int(n) for n in m.picks)
    del layers, carry
    # A prompt's last row predicts the first served token, a served token's row the next.
    head = jnp.asarray(m.head())
    lps = [_log_softmax(m, head, jnp.concatenate([h0, h], axis=0)) for h0, h in zip(last, hs)]
    del head
    stat, line = hc._statistic(served, lps, cfg)
    return stat, (line + f" picks_moved={moved}/{pairs} (query, KV group) pairs past dense_len, "
                  "prompts' and served tokens' (rows behind a shorter answer too), whose block "
                  "set moves under the served type's rounding of q and k"
                  + (" [the reference's matrix inputs at 3 mantissa bits: a control]"
                     if low else ""))
