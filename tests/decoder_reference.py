"""The plain reference of the `decoder` family for tier-1 (ISSUE 28): the
architecture's forward pass in straightforward float32, with no cache, no
batching and no kernel, and the weights recipe written down again. It imports
nothing of `tpuserve`. `benchmark/reference/decoder.py` holds the benchmark's
copy of the same forward pass (its header has the layer's equations, the
share and what is assumed); `tests/test_decoder.py` holds the two to the same
numbers.
"""

from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

BELL_STD = math.sqrt(4 * (256 ** 2 - 1) / 12.0)
LOGPROBS = 8
DEFAULT_SCALES = {"embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "gate": 1.0, "o": 1.0,
                  "ffn_in": 1.0, "ffn_out": 1.0, "router": 4.0}


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
        self.d, self.hd = int(a["hidden_size"]), int(a["head_dim"])
        self.n_layers = int(a["num_hidden_layers"])
        self.eps = float(a.get("rms_norm_eps", 1e-6))
        share = a.get("share", {})
        self.e_full = int(a.get("num_experts", 0))
        self.e_first, self.e_count = share.get("experts_held", [0, self.e_full])
        idx, of = share.get("attention_heads", [0, 1])
        self.v_full = int(a["vocab_size"])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.v_full])
        self.heads_full = [int(h) for h in a["num_attention_heads_per_layer"]]
        self.heads = [h // of for h in self.heads_full]
        self.h_first = [idx * h for h in self.heads]
        self.kv_full = int(a["num_key_value_heads"])
        self.kv = self.kv_full // of
        self.kv_first = idx * self.kv
        self.gated = a.get("gating") in ("per-head", "per_head")
        self.window = int(a.get("sliding_window") or 0)
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self.rope = {t: rope_inv_freq(rp, self.hd) for t, rp in a["rope_parameters"].items()}

    def tensor(self, name: str, shape, full, start, role: str, fan_in: int) -> np.ndarray:
        return np.asarray(draw(self.seed, name, tuple(shape), self.scales[role] / math.sqrt(fan_in),
                               self.dtype, tuple(full), tuple(start)))

    def embed(self) -> np.ndarray:
        return self.tensor("embed", (self.vocab, self.d), (self.v_full, self.d),
                           (self.v_first, 0), "embed", 1)

    def head(self) -> np.ndarray:
        return self.tensor("head", (self.d, self.vocab), (self.d, self.v_full),
                           (0, self.v_first), "head", self.d)

    def layer(self, i: int) -> dict:
        a, d, hd, L = self.a, self.d, self.hd, f"layer{i}"
        hf, h, h0 = self.heads_full[i], self.heads[i], self.h_first[i]
        kvf, kv, kv0 = self.kv_full, self.kv, self.kv_first
        w = {
            "wq": self.tensor(f"{L}/wq", (d, h, hd), (d, hf, hd), (0, h0, 0), "qk", d),
            "wk": self.tensor(f"{L}/wk", (d, kv, hd), (d, kvf, hd), (0, kv0, 0), "qk", d),
            "wv": self.tensor(f"{L}/wv", (d, kv, hd), (d, kvf, hd), (0, kv0, 0), "v", d),
            "wo": self.tensor(f"{L}/wo", (h, hd, d), (hf, hd, d), (h0, 0, 0), "o", hf * hd),
        }
        if self.gated:
            w["wg"] = self.tensor(f"{L}/wg", (d, h), (d, hf), (0, h0), "gate", d)
        if a["mlp_layer_types"][i] == "dense":
            f = int(a["intermediate_size"])
            w["w_gate"] = self.tensor(f"{L}/w_gate", (d, f), (d, f), (0, 0), "ffn_in", d)
            w["w_up"] = self.tensor(f"{L}/w_up", (d, f), (d, f), (0, 0), "ffn_in", d)
            w["w_down"] = self.tensor(f"{L}/w_down", (f, d), (f, d), (0, 0), "ffn_out", f)
            return w
        e, ec, e0 = self.e_full, self.e_count, self.e_first
        f, fs = int(a["moe_intermediate_size"]), int(a["shared_expert_intermediate_size"])
        w["router"] = self.tensor(f"{L}/router", (d, e), (d, e), (0, 0), "router", d)
        w["e_gate"] = self.tensor(f"{L}/e_gate", (ec, d, f), (e, d, f), (e0, 0, 0), "ffn_in", d)
        w["e_up"] = self.tensor(f"{L}/e_up", (ec, d, f), (e, d, f), (e0, 0, 0), "ffn_in", d)
        w["e_down"] = self.tensor(f"{L}/e_down", (ec, f, d), (e, f, d), (e0, 0, 0), "ffn_out", f)
        w["s_gate"] = self.tensor(f"{L}/s_gate", (d, fs), (d, fs), (0, 0), "ffn_in", d)
        w["s_up"] = self.tensor(f"{L}/s_up", (d, fs), (d, fs), (0, 0), "ffn_in", d)
        w["s_down"] = self.tensor(f"{L}/s_down", (fs, d), (fs, d), (0, 0), "ffn_out", fs)
        return w


# -- the forward pass ----------------------------------------------------------------

def rope_inv_freq(rp: dict, head_dim: int):
    """(inverse frequencies (dim/2,), the factor on cos and sin, dim)."""
    dim = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    base = float(rp["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type", "default") == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0, dim
    assert rp["rope_type"] == "yarn", rp
    factor, orig = float(rp["factor"]), float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rp.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(rp.get("beta_slow", 1)))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)
    att = rp.get("attention_factor")
    return inv.astype(np.float32), float(att) if att is not None else 0.1 * math.log(factor) + 1.0, dim


def _rope(x, inv_freq, factor, dim):
    """x (T, H, head_dim), positions 0..T-1."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos, sin = (jnp.cos(ang) * factor)[:, None, :], (jnp.sin(ang) * factor)[:, None, :]
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


def attention(m: Model, w: dict, i: int, u):
    t = u.shape[0]
    kind = m.a["layer_types"][i]
    inv, factor, dim = m.rope[kind]
    q = _rope(jnp.einsum("td,dhk->thk", u, w["wq"]), inv, factor, dim)
    k = _rope(jnp.einsum("td,dhk->thk", u, w["wk"]), inv, factor, dim)
    v = jnp.einsum("td,dhk->thk", u, w["wv"])
    g = m.heads[i] // m.kv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    dist = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    see = dist >= 0
    if kind == "sliding_attention":
        see = see & (dist < m.window)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(m.hd)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1), v)
    if m.gated:
        o = o * jax.nn.sigmoid(u @ w["wg"])[:, :, None]
    return jnp.einsum("qhd,hdo->qo", o, w["wo"])


def experts(m: Model, w: dict, u: np.ndarray, low_precision: bool = False) -> np.ndarray:
    """The held experts' part of the routed sum, in numpy float32: each held
    expert over the tokens that picked it."""
    a = m.a
    with jax.default_matmul_precision("highest"):
        r = np.asarray(jnp.asarray(u) @ jnp.asarray(w["router"]))
    cap = float(a.get("moe_router_logit_softcapping", 0) or 0)
    if cap > 0:
        r = cap * np.tanh(r / cap)
    p = np.exp(r - r.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    k = int(a["num_experts_per_tok"])
    top = np.argsort(-p, axis=-1, kind="stable")[:, :k]
    wt = np.take_along_axis(p, top, axis=-1)
    if a.get("norm_topk_prob", True):
        wt = wt / wt.sum(axis=-1, keepdims=True)
    wt = wt * float(a.get("moe_routed_scaling_factor", 1.0))
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


def hidden_states(m: Model, sequences: list[np.ndarray], low_precision: bool = False) -> list:
    """Final hidden states (before the last norm) of each sequence of held-row
    ids; layers outermost, so each layer is drawn once and dropped."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) for ids in sequences]
    del embed
    with jax.default_matmul_precision("highest"):
        for i in range(m.n_layers):
            w = m.layer(i)
            if low_precision:  # the control: every kernel but the router's
                w = {k: (v if k == "router" else np.asarray(_round3_whole(v)))
                     for k, v in w.items()}
            rnd = _round3_whole if low_precision else (lambda z: z)
            for n, x in enumerate(xs):
                x = x + attention(m, w, i, rnd(_rms(x, m.eps)))
                u = rnd(_rms(x, m.eps))
                if m.a["mlp_layer_types"][i] == "dense":
                    y = _swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
                else:
                    y = _swiglu(u, w["s_gate"], w["s_up"], w["s_down"]) \
                        + jnp.asarray(experts(m, w, np.asarray(u), low_precision))
                xs[n] = x + y
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
