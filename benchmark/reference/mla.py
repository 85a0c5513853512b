"""The plain reference for the `mla` family: a decoder-only language model with
latent attention (MLA) and routed SwiGLU experts, written down from its
published `config.json` in straightforward float32 (`jax.numpy` under
`jax.default_matmul_precision("highest")`; the routed experts' products, whose
groups have every size, in `numpy` float32), the attention in its EXPANDED form
only, one causal pass, with no cache, no chunking, no absorbed product and no
kernel. It imports nothing of the program.

THE LAYER (eps = `rms_norm_eps`, no biases, an untied head; norm gains are ones
in the recipe and are left out): `x <- x + attention(RMSNorm(x))`, `x <- x +
ffn(RMSNorm(x))`; logits = `RMSNorm(x) W_head`.

- attention, `u` the normed stream at position t: `c_q = RMSNorm(u W_qa)`
  (`q_lora_rank`); `q = c_q W_qb`, a head `[q_nope (qk_nope_head_dim) | q_rope
  (qk_rope_head_dim)]`; `[c_kv | k_r] = u W_kva` (`kv_lora_rank` | rope); `c_kv <-
  RMSNorm(c_kv)`; `q_rope <- RoPE(q_rope, t)`, `k_r <- RoPE(k_r, t)`: ONE rotary
  key for every head; `[k_nope_h | v_h] = c_kv W_kvb`; `score_h(t, s) = (q_nope_h(t)
  . k_nope_h(s) + q_rope_h(t) . k_r(s)) / sqrt(qk_nope + qk_rope)`, causal
  softmax, `o_h = sum_s p v_h(s)`, out = `concat_h(o_h) W_o`. RoPE: plain
  (`rope_scaling` null), pair i of the rotary columns turns by `t *
  rope_theta ** (-2 i / qk_rope_head_dim)`.
- feed-forward: layers below `first_k_dense_replace`: `(silu(u G) * (u U)) D`
  of `intermediate_size`; the others: `s = sigmoid(u W_r)` over all
  `n_routed_experts`; the `num_experts_per_tok` largest of `s + b`; weights `s_e /
  (their sum)` (`norm_topk_prob`) times `routed_scaling_factor`; expert e a SwiGLU
  of `moe_intermediate_size`; plus the shared expert (`n_shared_experts` times
  that width) on the same u.

THE CUT: depth alone (`num_hidden_layers`: the leading dense layer and the four
sparse layers that follow, of 40); every width, every head, every expert and
the whole vocabulary are as published, so there is no `share`.

ASSUMED (the configuration file repeats this under `assumed`): router scores are
a sigmoid and the selection bias `b` (`noaux_tc`; drawn small, a bell within
+-0.06) moves picks, never weights; one group (`n_group = topk_group = 1`: no
group limit); `rope_interleave = true` read as: columns (2i, 2i + 1) turn as
pair i, in place (with drawn weights the other pairing is a permutation of
`W_qb`'s and `W_kva`'s rotary columns); softmax and norms in float32; the
multi-token-prediction module is not part of the main stack's logits and is not
here; no end-of-sequence id.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`): the same few
lines as `tpuserve/models/seeded.py` and `reference/decoder.py`, written down
again. `W_qb` and `W_kva` are drawn in their two parts (`w_qb_nope`, `w_qb_rope`;
`w_kva_c`, `w_kva_r`), `W_kvb` in its key and its value side (`w_kb`, `w_vb`),
each a tensor of its own. The router's `e_bias` is the four summed bytes over
their range (0 to 1) mapped into [-0.06, 0.06]. A layer's routed experts are
drawn `EXPERT_BLOCK` at a time (whole, a layer's are 4.8 GB in float32).

THE CHECK (`compare`): as `reference/hybrid.py`. Each request of the sample is
served greedily with `logprobs` 8 and the reference runs ONE full pass over the
prompt and the served tokens (queries in blocks of `QUERY_BLOCK`, so that the
scores of a prompt of 6,144 fit); a generated position's number is the RMS of
its eight differences of served and reference log-probabilities, each side
centred (less its mean over the eight). THE STATISTIC is, a request, the LOWER
QUARTILE of its positions' numbers, and over the requests the largest
(`logprob_q25`): 8 picks of 256 by a sigmoid score have an 8th and a 9th
candidate a few thousandths apart, so the bfloat16 stream's own error swaps
that pair at some positions, and a swap moves its position by several times
what the arithmetic does; that is what serving this router in bfloat16 IS. A
lower precision, a cache row kept in a lower type, a dropped rotary part or a
wrong position moves EVERY position of a request and with them its lower
quartile. What moves only some positions is held by a second, looser bound on
the RMS over all positions (`check.rms_limit`); the number compared with
`check.limit` is the larger of the quartile and the RMS scaled by `limit /
rms_limit`, and the line prints both beside their limits.
`check.reference_inputs = "3-bit-mantissa"` (a control, never a cell) rounds the
inputs of the reference's matrix products (every kernel but the router's, the
normed stream, the query's latent, what a server would CACHE: the normed `c_kv`
and the rotated `k_r`, the heads' outputs, the hidden rows of every SwiGLU) to 3
explicit mantissa bits: the nearest precision below what the program serves.
"""

from __future__ import annotations

import functools
import hashlib
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

BELL_STD = math.sqrt(4 * (256 ** 2 - 1) / 12.0)
LOGPROBS = 8
DEFAULT_SCALES = {"embed": 1.0, "head": 1.0, "q_a": 1.0, "q_b": 2.0, "kv_a": 1.0,
                  "k_rope": 2.0, "k_b": 2.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
                  "ffn_out": 1.0, "expert_out": 1.0, "router": 1.0, "router_bias": 0.02}
QUERY_BLOCK = 1024   # queries a block of the causal pass (the scores of a long prompt must fit)
EXPERT_BLOCK = 32    # experts drawn at a time (a layer's experts whole are gigabytes in float32)


# -- weights by recipe -------------------------------------------------------------

def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _draw(key, std, shape: tuple, served_dtype, full_shape: tuple, start):
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for axis in range(len(shape) - 1, -1, -1):
        coord = jax.lax.broadcasted_iota(jnp.uint32, shape, axis) + start[axis]
        idx = idx + coord * jnp.uint32(stride)
        stride *= full_shape[axis]
    h = _fmix32(idx * jnp.uint32(0x9E3779B1) + key)
    s = (h & 255) + ((h >> 8) & 255) + ((h >> 16) & 255) + (h >> 24)
    centred = (s.astype(jnp.int32) - 510).astype(jnp.float32)
    return (centred * std).astype(served_dtype).astype(jnp.float32)


# One fused pass over every core; `start` is traced, so a tensor drawn a block
# at a time compiles once.
_draw_compiled = jax.jit(_draw, static_argnums=(2, 3, 4))


def draw(seed: int, name: str, shape: tuple, std: float, served_dtype,
         full_shape: tuple, start: tuple) -> jax.Array:
    """The block of tensor `name` at `start` of `full_shape`, as float32
    holding the served type's values."""
    key = int.from_bytes(hashlib.blake2s(f"{int(seed)}/{name}".encode()).digest()[:4], "little")
    return _draw_compiled(jnp.uint32(key), jnp.float32(std / BELL_STD), tuple(shape),
                          jnp.dtype(served_dtype), tuple(full_shape),
                          jnp.asarray(start, jnp.uint32))


class Model:
    """The architecture's numbers and its tensors' shapes; draws one tensor,
    one layer's matrices or one block of a layer's experts at a time."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        a = self.a = arch
        self.seed, self.dtype = int(seed), jnp.dtype(served_dtype)
        self.d, self.n_layers = int(a["hidden_size"]), int(a["num_hidden_layers"])
        self.eps = float(a.get("rms_norm_eps", 1e-6))
        self.heads = int(a["num_attention_heads"])
        self.q_rank, self.r = int(a["q_lora_rank"]), int(a["kv_lora_rank"])
        self.dn, self.dr, self.dv = (int(a[k]) for k in (
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        self.theta = float(a.get("rope_theta", 10000.0))
        self.interleave = bool(a.get("rope_interleave", False))
        self.first_dense = int(a.get("first_k_dense_replace", 0))
        self.e = int(a.get("n_routed_experts", 0))
        self.top_k = int(a.get("num_experts_per_tok", 0))
        self.f = int(a.get("moe_intermediate_size", 0))
        self.fs = self.f * int(a.get("n_shared_experts", 0))
        self.vocab = int(a["vocab_size"])
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    def tensor(self, name: str, shape, role: str, fan_in: int, full=None, start=None):
        shape = tuple(shape)
        return draw(self.seed, name, shape, self.scales[role] / math.sqrt(fan_in), self.dtype,
                    tuple(full or shape), tuple(start or (0,) * len(shape)))

    def embed(self) -> np.ndarray:
        return np.asarray(self.tensor("embed", (self.vocab, self.d), "embed", 1))

    def head(self):
        return self.tensor("head", (self.d, self.vocab), "head", self.d)

    def attention(self, i: int) -> dict:
        L, d, h, t = f"layer{i}", self.d, self.heads, self.tensor
        return {"w_qa": t(f"{L}/w_qa", (d, self.q_rank), "q_a", d),
                "w_qb_nope": t(f"{L}/w_qb_nope", (self.q_rank, h, self.dn), "q_b", self.q_rank),
                "w_qb_rope": t(f"{L}/w_qb_rope", (self.q_rank, h, self.dr), "q_b", self.q_rank),
                "w_kva_c": t(f"{L}/w_kva_c", (d, self.r), "kv_a", d),
                "w_kva_r": t(f"{L}/w_kva_r", (d, self.dr), "k_rope", d),
                "w_kb": t(f"{L}/w_kb", (self.r, h, self.dn), "k_b", self.r),
                "w_vb": t(f"{L}/w_vb", (self.r, h, self.dv), "v", self.r),
                "wo": t(f"{L}/wo", (h, self.dv, d), "o", h * self.dv)}

    def ffn(self, i: int) -> dict:
        """A dense layer's three matrices, or a sparse layer's router, bias
        and shared expert (its routed experts come a block at a time)."""
        L, d, t = f"layer{i}", self.d, self.tensor
        if i < self.first_dense:
            f = int(self.a["intermediate_size"])
            return {"w_gate": t(f"{L}/w_gate", (d, f), "ffn_in", d),
                    "w_up": t(f"{L}/w_up", (d, f), "ffn_in", d),
                    "w_down": t(f"{L}/w_down", (f, d), "ffn_out", f)}
        b3 = 3.0 * self.scales["router_bias"]
        # A float32 vector inside [-b3, b3]: the four summed bytes over their range, then the range.
        u = jnp.float32(0.5) + draw(self.seed, f"{L}/e_bias", (self.e,), BELL_STD / 1020.0,
                                    jnp.float32, (self.e,), (0,))
        return {"router": t(f"{L}/router", (d, self.e), "router", d),
                "e_bias": np.asarray(jnp.float32(-b3) + jnp.float32(2 * b3) * u),
                "s_gate": t(f"{L}/s_gate", (d, self.fs), "ffn_in", d),
                "s_up": t(f"{L}/s_up", (d, self.fs), "ffn_in", d),
                "s_down": t(f"{L}/s_down", (self.fs, d), "ffn_out", self.fs)}

    def expert_block(self, i: int, first: int, count: int) -> dict:
        L, d, e, f = f"layer{i}", self.d, self.e, self.f
        return {
            "e_gate": np.asarray(self.tensor(f"{L}/e_gate", (count, d, f), "ffn_in", d,
                                             (e, d, f), (first, 0, 0))),
            "e_up": np.asarray(self.tensor(f"{L}/e_up", (count, d, f), "ffn_in", d,
                                           (e, d, f), (first, 0, 0))),
            "e_down": np.asarray(self.tensor(f"{L}/e_down", (count, f, d), "expert_out", f,
                                             (e, f, d), (first, 0, 0)))}


# -- the forward pass ----------------------------------------------------------------

def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _round3(x: np.ndarray) -> np.ndarray:
    """float32 rounded to 3 explicit mantissa bits (nearest, ties to even)."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    bits = bits + np.uint32((1 << 19) - 1) + ((bits >> np.uint32(20)) & np.uint32(1))
    return (bits & np.uint32(0xFFF00000)).view(np.float32)


def _round3_traced(x):
    """The same rounding inside a compiled program."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32((1 << 19) - 1) + ((bits >> 20) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFF00000), jnp.float32)


def _rope(x, pos, theta: float, interleave: bool):
    """`x` (T, ..., dim) at positions `pos` (T,): column pair i turns by
    `pos * theta ** (-2 i / dim)`; the pair is (2i, 2i + 1) with `interleave`,
    else (i, i + dim / 2)."""
    dim = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * (
        1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# One compiled program a layer kind and a sequence length (not one an
# operation): a cold run has a handful of programs to build.

@functools.partial(jax.jit, static_argnums=(0, 1))
def _attention(dims: tuple, low: bool, w: dict, x, pos):
    """x (T, d) -> x + attention(RMSNorm(x)): the expanded form, one causal
    pass, QUERY_BLOCK queries at a time over the keys up to the block's end
    (heads lead every product: the host's matrix products are several times
    faster so)."""
    h, dn, dr, dv, eps, theta, interleave = dims
    rnd = _round3_traced if low else (lambda z: z)
    if low:  # the control: every kernel's values at 3 mantissa bits
        w = {k: _round3_traced(v) for k, v in w.items()}
    t = x.shape[0]   # `pos` = 0 .. t - 1, handed in: made here, the compiler folds every mask
    with jax.default_matmul_precision("highest"):
        u = rnd(_rms(x, eps))
        c_q = rnd(_rms(u @ w["w_qa"], eps))
        q_nope = jnp.einsum("tq,qhn->htn", c_q, w["w_qb_nope"])
        q_rope = _rope(jnp.einsum("tq,qhr->thr", c_q, w["w_qb_rope"]), pos, theta,
                       interleave).transpose(1, 0, 2)
        # What a server caches: the normed latent and the rotated shared key.
        c_kv = rnd(_rms(u @ w["w_kva_c"], eps))
        k_r = rnd(_rope(u @ w["w_kva_r"], pos, theta, interleave))
        k_nope = jnp.einsum("tr,rhn->htn", c_kv, w["w_kb"])
        v = jnp.einsum("tr,rhv->htv", c_kv, w["w_vb"])
        out = []
        for lo in range(0, t, QUERY_BLOCK):
            hi = min(t, lo + QUERY_BLOCK)
            s = (jnp.einsum("hqn,hkn->hqk", q_nope[:, lo:hi], k_nope[:, :hi])
                 + jnp.einsum("hqr,kr->hqk", q_rope[:, lo:hi], k_r[:hi])) / math.sqrt(dn + dr)
            s = jnp.where((pos[None, :hi] <= pos[lo:hi, None])[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,hkv->hqv", jax.nn.softmax(s, axis=-1), v[:, :hi]))
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(t, h * dv)
        return x + rnd(o) @ w["wo"].reshape(h * dv, -1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _dense(eps: float, low: bool, w: dict, x):
    rnd = _round3_traced if low else (lambda z: z)
    if low:
        w = {k: _round3_traced(v) for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        u = rnd(_rms(x, eps))
        return x + rnd(jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _sparse_whole(eps: float, low: bool, w: dict, x):
    """The parts of a sparse layer every token passes through: -> (the
    normed stream, the router's scores (float32 in the program too, so the
    control leaves them), x + the shared expert)."""
    rnd = _round3_traced if low else (lambda z: z)
    with jax.default_matmul_precision("highest"):
        u = _rms(x, eps)
        scores = jax.nn.sigmoid(u @ w["router"])
        u = rnd(u)
        g, up, down = (rnd(w[k]) for k in ("s_gate", "s_up", "s_down"))
        return u, scores, x + rnd(jax.nn.silu(u @ g) * (u @ up)) @ down


def picks(m: Model, scores: np.ndarray, e_bias: np.ndarray):
    """The experts each token picks and their weights: the `num_experts_per_tok`
    largest of score + bias, weighted by the score alone."""
    a = m.a
    top = np.argsort(-(scores + e_bias[None, :]), axis=-1, kind="stable")[:, :m.top_k]
    wt = np.take_along_axis(scores, top, axis=-1)
    if a.get("norm_topk_prob", True):
        wt = wt / wt.sum(axis=-1, keepdims=True)
    return top, wt * np.float32(a.get("routed_scaling_factor", 1.0))


def routed(m: Model, i: int, us: list, tops: list, wts: list, low: bool) -> list:
    """The routed experts' weighted sums of every sequence, in numpy float32:
    each expert over the tokens that picked it, a block of experts drawn at a
    time (once for all the sequences)."""
    rnd = _round3 if low else (lambda z: z)
    ys = [np.zeros_like(u) for u in us]
    for first in range(0, m.e, EXPERT_BLOCK):
        w = m.expert_block(i, first, min(EXPERT_BLOCK, m.e - first))
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
    return ys


def hidden_states(m: Model, sequences: list[np.ndarray], low: bool = False) -> list:
    """Final hidden states (before the last norm) of each sequence of ids;
    layers outermost, so each layer is drawn once and dropped. `low`: the
    control (header of benchmark/reference/mla.py)."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) for ids in sequences]
    del embed
    dims = (m.heads, m.dn, m.dr, m.dv, m.eps, m.theta, m.interleave)
    for i in range(m.n_layers):
        w = m.attention(i)
        xs = [_attention(dims, low, w, x, jnp.arange(x.shape[0])).block_until_ready() for x in xs]
        w = m.ffn(i)
        if i < m.first_dense:
            xs = [_dense(m.eps, low, w, x).block_until_ready() for x in xs]
            continue
        whole = [_sparse_whole(m.eps, low, {k: v for k, v in w.items() if k != "e_bias"}, x)
                 for x in xs]
        chosen = [picks(m, np.asarray(scores), w["e_bias"]) for _u, scores, _rest in whole]
        ys = routed(m, i, [np.asarray(u) for u, _s, _r in whole], [t for t, _ in chosen],
                    [wt for _, wt in chosen], low)
        xs = [rest + jnp.asarray(y) for (_u, _s, rest), y in zip(whole, ys)]
        del w, whole
    return xs


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the vocabulary at positions `first_row`
    onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low)
    head = m.head()
    with jax.default_matmul_precision("highest"):
        return [np.asarray(jax.nn.log_softmax(_rms(h[r:], m.eps) @ head, axis=-1))
                for h, r in zip(hs, first_rows)]


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (
    "attention_bias", "ep_size", "first_k_dense_replace", "head_dim", "hidden_act",
    "hidden_size", "intermediate_size", "kv_lora_rank", "max_position_embeddings",
    "model_type", "moe_intermediate_size", "moe_layer_freq", "n_group", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "num_nextn_predict_layers", "q_lora_rank",
    "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_interleave",
    "rope_scaling", "rope_theta", "routed_scaling_factor", "scoring_func",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim", "vocab_size")


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
    """What this file, flops/mla.py and the per-layer readers need."""
    gen = cfg["serve"]["tables"]["genserve"]
    served = cfg["assumed"]["served"]
    a = arch_from_config(cfg)
    layers, dense = int(a["num_hidden_layers"]), int(a.get("first_k_dense_replace", 0))
    max_ctx = int(served["max_prompt_tokens"]) + int(served["max_new_tokens"])
    page, slots = int(gen["kv_page_tokens"]), int(gen["slots"])
    row = int(a["kv_lora_rank"]) + int(a["qk_rope_head_dim"])
    return {
        "arch": a, "d_model": int(a["hidden_size"]), "layers": layers, "n_dense": dense,
        "n_sparse": layers - dense, "heads": int(a["num_attention_heads"]),
        "q_rank": int(a["q_lora_rank"]), "kv_rank": int(a["kv_lora_rank"]),
        "nope": int(a["qk_nope_head_dim"]), "rope": int(a["qk_rope_head_dim"]),
        "v_dim": int(a["v_head_dim"]), "row": row,
        # What kv_reserved_pct looks up. It reckons K and V by head, 2 x kv_heads x
        # head_dim values a position a layer; a latent row is ONE row of `row` values,
        # so "head_dim" here is half a row and the product is the row (the metric is a
        # ratio of pages, which the row's size does not move).
        "layer_types": ["full_attention"] * layers, "window": 0, "kv_heads": 1,
        "head_dim": row // 2,
        "vocab": int(a["vocab_size"]), "vocab_first": 0,
        "num_experts": int(a["n_routed_experts"]), "experts_held": int(a["n_routed_experts"]),
        "top_k": int(a["num_experts_per_tok"]), "dense_width": int(a["intermediate_size"]),
        "expert_width": int(a["moe_intermediate_size"]),
        "shared_width": int(a["moe_intermediate_size"]) * int(a.get("n_shared_experts", 0)),
        "max_prompt": int(served["max_prompt_tokens"]), "max_new": int(served["max_new_tokens"]),
        "max_ctx": max_ctx, "slots": slots, "page_tokens": page,
        "pages_per_slot": -(-max_ctx // page),
        "kv_pages": int(gen.get("kv_pages") or 0) or slots * -(-max_ctx // page) + 1,
        "prefill_chunk": int(gen.get("prefill_chunk") or 0) or int(served["max_prompt_tokens"]),
        "weight_bytes": 2 if cfg["serve"]["model"]["dtype"] == "bfloat16" else 4,
    }


def prepare(seed: int, sizes: dict, cfg: dict, work: str):
    """No checkpoint: the program draws its weights on the device by
    `assumed.weights`. Writes the model's config file, in the published
    layout, for `options.config_file`."""
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


def centred_gap(served: dict, ref_lp: np.ndarray) -> np.ndarray:
    """(positions, LOGPROBS) differences of served and reference centred
    log-probabilities at the ids the server named."""
    ids = np.asarray(served["logprobs"]["ids"], np.int64)
    got = np.asarray(served["logprobs"]["values"], np.float64)
    want = np.take_along_axis(ref_lp.astype(np.float64), ids, axis=-1)
    return (got - got.mean(axis=-1, keepdims=True)) - (want - want.mean(axis=-1, keepdims=True))


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
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
    model = Model(sz["arch"], ref["seed"], ref["dtype"])
    t0 = time.monotonic()
    gaps = [centred_gap(a, lp) for a, lp in zip(served, log_probs(model, seqs, rows, low))]
    print(f"[reference] {sum(len(s) for s in seqs)} tokens of {len(seqs)} sequences through "
          f"{model.n_layers} layers in {time.monotonic() - t0:.1f} s", flush=True)
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
                  + (" [the reference's matrix inputs and what a server caches at 3 mantissa "
                     "bits: a control]" if low else ""))
