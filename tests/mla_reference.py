"""The plain reference of the `mla` family for tier-1 (ISSUE 34): the
architecture's forward pass in straightforward float32, latent attention in
its EXPANDED form only (keys and values made from the latents for the whole
sequence, one causal pass), with no cache, no chunking, no absorbed products
and no kernel, and the weights recipe written down again. It imports nothing
of `tpuserve`. `benchmark/reference/mla.py` holds the benchmark's copy of the
same forward pass (its header has the layers' equations and what is
assumed); `tests/test_mla.py` holds the two to the same numbers.
"""

from __future__ import annotations

import functools
import hashlib
import math

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
