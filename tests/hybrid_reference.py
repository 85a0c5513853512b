"""The plain reference of the `hybrid` family for tier-1 (ISSUE 32): the
architecture's forward pass in straightforward float32, the recurrence as a
recurrence (a `lax.scan` over the tokens), with no cache, no batching, no
chunking and no kernel, and the weights recipe written down again. It imports
nothing of `tpuserve`. `benchmark/reference/hybrid.py` holds the benchmark's
copy of the same forward pass (its header has the layers' equations, the share
and what is assumed); `tests/test_hybrid.py` holds the two to the same numbers.
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
DEFAULT_SCALES = {"embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
                  "ffn_out": 1.0, "expert_out": 1.0, "router": 1.0, "router_bias": 0.02,
                  "ssm_in": 1.0, "ssm_bc": 2.0, "ssm_dt": 1.0, "ssm_out": 1.0, "conv": 1.0,
                  "conv_bias": 0.1, "ssm_d": 0.1}

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


def softplus_inverse(y: float) -> float:
    return y + math.log(-math.expm1(-y))


class Model:
    """The architecture's numbers and its tensors' shapes; draws one tensor
    or one layer at a time."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        a = self.a = arch
        self.seed, self.dtype = int(seed), jnp.dtype(served_dtype)
        self.d, self.hd = int(a["hidden_size"]), int(a["head_dim"])
        self.pattern = a["hybrid_override_pattern"]
        self.n_layers = len(self.pattern)
        self.eps = float(a.get("layer_norm_epsilon", 1e-5))
        share = a.get("share", {})
        self.e_full = int(a["n_routed_experts"])
        self.e_first, self.e_count = share.get("experts_held", [0, self.e_full])
        idx, of = share.get("attention_heads", [0, 1])
        self.heads_full, self.kv_full = int(a["num_attention_heads"]), \
            int(a["num_key_value_heads"])
        self.heads, self.h_first = self.heads_full // of, idx * (self.heads_full // of)
        self.kv, self.kv_first = max(1, self.kv_full // of), idx * self.kv_full // of
        m_idx, m_of = share.get("mamba_heads", [0, 1])
        self.mh_full, self.mg_full = int(a["mamba_num_heads"]), int(a["n_groups"])
        self.mh, self.mg = self.mh_full // m_of, self.mg_full // m_of
        self.mh_first, self.mg_first = m_idx * self.mh, m_idx * self.mg
        self.mp, self.mn = int(a["mamba_head_dim"]), int(a["ssm_state_size"])
        self.conv_k = int(a.get("conv_kernel", 4))
        self.v_full = int(a["vocab_size"])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.v_full])
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    def tensor(self, name: str, shape, full, start, role: str, fan_in: int) -> np.ndarray:
        return np.asarray(draw(self.seed, name, tuple(shape), self.scales[role] / math.sqrt(fan_in),
                               self.dtype, tuple(full), tuple(start)))

    def vector(self, name: str, shape, full, start, lo: float, hi: float) -> np.ndarray:
        """A float32 vector inside [lo, hi]: the four summed bytes over their
        range, then the range (header)."""
        u = jnp.float32(0.5) + draw(self.seed, name, tuple(shape), BELL_STD / 1020.0,
                                    jnp.float32, tuple(full), tuple(start))
        return np.asarray(jnp.float32(lo) + jnp.float32(hi - lo) * u)

    def embed(self) -> np.ndarray:
        return self.tensor("embed", (self.vocab, self.d), (self.v_full, self.d),
                           (self.v_first, 0), "embed", 1)

    def head(self) -> np.ndarray:
        return self.tensor("head", (self.d, self.vocab), (self.d, self.v_full),
                           (0, self.v_first), "head", self.d)

    def layer(self, i: int) -> dict:
        a, d, L, kind = self.a, self.d, f"layer{i}", self.pattern[i]
        t = self.tensor
        if kind == "M":
            hf, h, h0, p = self.mh_full, self.mh, self.mh_first, self.mp
            gf, g, g0, n, k = self.mg_full, self.mg, self.mg_first, self.mn, self.conv_k
            w = {"in_z": t(f"{L}/in_z", (d, h, p), (d, hf, p), (0, h0, 0), "ssm_in", d),
                 "in_x": t(f"{L}/in_x", (d, h, p), (d, hf, p), (0, h0, 0), "ssm_in", d),
                 "in_dt": t(f"{L}/in_dt", (d, h), (d, hf), (0, h0), "ssm_dt", d),
                 "conv_x": t(f"{L}/conv_x", (k, h, p), (k, hf, p), (0, h0, 0), "conv", k),
                 "conv_bias_x": t(f"{L}/conv_bias_x", (h, p), (hf, p), (h0, 0), "conv_bias", 1),
                 "w_out": t(f"{L}/w_out", (h, p, d), (hf, p, d), (h0, 0, 0), "ssm_out", hf * p)}
            for part in ("B", "C"):
                w[f"in_{part}"] = t(f"{L}/in_{part}", (d, g, n), (d, gf, n), (0, g0, 0),
                                    "ssm_bc", d)
                w[f"conv_{part}"] = t(f"{L}/conv_{part}", (k, g, n), (k, gf, n), (0, g0, 0),
                                      "conv", k)
                w[f"conv_bias_{part}"] = t(f"{L}/conv_bias_{part}", (g, n), (gf, n), (g0, 0),
                                           "conv_bias", 1)
            if not a.get("use_conv_bias", True):
                for part in ("x", "B", "C"):
                    w[f"conv_bias_{part}"] = np.zeros_like(w[f"conv_bias_{part}"])
            lo, hi = (softplus_inverse(float(a.get(key, v))) for key, v in
                      (("time_step_min", 0.001), ("time_step_max", 0.1)))
            d3 = 3.0 * self.scales["ssm_d"]
            hv = ((h,), (hf,), (h0,))
            w["dt_bias"] = self.vector(f"{L}/dt_bias", *hv, lo, hi)
            w["A_log"] = self.vector(f"{L}/A_log", *hv, 0.0, math.log(16.0))
            w["D"] = self.vector(f"{L}/D", *hv, 1.0 - d3, 1.0 + d3)
            return w
        if kind == "*":
            hd = self.hd
            return {
                "wq": t(f"{L}/wq", (d, self.heads, hd), (d, self.heads_full, hd),
                        (0, self.h_first, 0), "qk", d),
                "wk": t(f"{L}/wk", (d, self.kv, hd), (d, self.kv_full, hd),
                        (0, self.kv_first, 0), "qk", d),
                "wv": t(f"{L}/wv", (d, self.kv, hd), (d, self.kv_full, hd),
                        (0, self.kv_first, 0), "v", d),
                "wo": t(f"{L}/wo", (self.heads, hd, d), (self.heads_full, hd, d),
                        (self.h_first, 0, 0), "o", self.heads_full * hd)}
        e, ec, e0 = self.e_full, self.e_count, self.e_first
        f, fs = int(a["moe_intermediate_size"]), int(a["moe_shared_expert_intermediate_size"])
        lat = int(a.get("moe_latent_size") or d)
        b3 = 3.0 * self.scales["router_bias"]
        return {
            "router": t(f"{L}/router", (d, e), (d, e), (0, 0), "router", d),
            "e_bias": self.vector(f"{L}/e_bias", (e,), (e,), (0,), -b3, b3),
            "w_a": t(f"{L}/w_a", (d, lat), (d, lat), (0, 0), "ffn_in", d),
            "e_w1": t(f"{L}/e_w1", (ec, lat, f), (e, lat, f), (e0, 0, 0), "ffn_in", lat),
            "e_w2": t(f"{L}/e_w2", (ec, f, lat), (e, f, lat), (e0, 0, 0), "expert_out", f),
            "w_b": t(f"{L}/w_b", (lat, d), (lat, d), (0, 0), "ffn_out", lat),
            "s_w1": t(f"{L}/s_w1", (d, fs), (d, fs), (0, 0), "ffn_in", d),
            "s_w2": t(f"{L}/s_w2", (fs, d), (fs, d), (0, 0), "ffn_out", fs)}


# -- the forward pass ----------------------------------------------------------------

# The kernels the control leaves alone: the router decides in float32 in the
# program too, and the small float32 vectors are no matrix product's input.
EXACT = ("router", "e_bias", "dt_bias", "A_log", "D")


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


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


# One compiled program a layer kind and a sequence length (not one an
# operation): a cold run has a dozen programs to build, not hundreds.

@functools.partial(jax.jit, static_argnums=(0, 1))
def _mamba(dims: tuple, state_dtype: str, w: dict, u):
    H, P, G, N, k, eps = dims
    t = u.shape[0]
    with jax.default_matmul_precision("highest"):
        z = jnp.einsum("td,dhp->thp", u, w["in_z"])
        pre = jnp.concatenate([jnp.einsum("td,dhp->thp", u, w["in_x"]).reshape(t, -1),
                               jnp.einsum("td,dgn->tgn", u, w["in_B"]).reshape(t, -1),
                               jnp.einsum("td,dgn->tgn", u, w["in_C"]).reshape(t, -1)], axis=1)
        dt = u @ w["in_dt"]
    cw = jnp.concatenate([w[f"conv_{p}"].reshape(k, -1) for p in "xBC"], axis=1)
    cb = jnp.concatenate([w[f"conv_bias_{p}"].reshape(-1) for p in "xBC"])
    padded = jnp.concatenate([jnp.zeros((k - 1, pre.shape[1]), pre.dtype), pre], axis=0)
    act = jax.nn.silu(cb + sum(padded[j:j + t] * cw[j] for j in range(k)))
    x = act[:, :H * P].reshape(t, H, P)
    B = jnp.repeat(act[:, H * P:H * P + G * N].reshape(t, G, N), H // G, axis=1)   # by head
    C = jnp.repeat(act[:, H * P + G * N:].reshape(t, G, N), H // G, axis=1)
    delta = jax.nn.softplus(dt + w["dt_bias"])
    decay = jnp.exp(-jnp.exp(w["A_log"]) * delta)
    kept = jnp.dtype(state_dtype)

    def token(S, row):
        a_t, d_t, x_t, b_t, c_t = row
        S = a_t[:, None, None] * S.astype(jnp.float32) \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        S = S.astype(kept)
        return S, jnp.sum(S.astype(jnp.float32) * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), kept), (decay, delta, x, B, C))
    y = y + w["D"][:, None] * x
    g = (y * jax.nn.silu(z)).reshape(t, G, -1)
    return _rms(g, eps).reshape(t, H, P)          # the gated norm's gain is ones


def mamba(m: Model, w: dict, u, state_dtype=jnp.float32):
    """One Mamba-2 layer over a whole sequence u (T, d), the recurrence token
    by token from a zero state, up to the gated norm: (g (T, H, P), W_out).
    `state_dtype`: what the state is kept in between two tokens (float32;
    bfloat16 in the control)."""
    dims = (m.mh, m.mp, m.mg, m.mn, m.conv_k, m.eps)
    arrays = {k: jnp.asarray(v) for k, v in w.items() if k != "w_out"}
    return _mamba(dims, jnp.dtype(state_dtype).name, arrays, u), w["w_out"]


@functools.partial(jax.jit, static_argnums=(0,))
def _attention(dims: tuple, w: dict, u):
    heads, kv, hd = dims
    t = u.shape[0]
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("td,dhk->thk", u, w["wq"])
        k = jnp.einsum("td,dhk->thk", u, w["wk"])
        v = jnp.einsum("td,dhk->thk", u, w["wv"])
        k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
        see = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        o = jnp.einsum("hqk,khd->qhd",
                       jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1), v)
        return jnp.einsum("qhd,hdo->qo", o, w["wo"])


def attention(m: Model, w: dict, u):
    return _attention((m.heads, m.kv, m.hd), {k: jnp.asarray(v) for k, v in w.items()}, u)


@jax.jit
def _project(u, w):
    with jax.default_matmul_precision("highest"):
        return u @ w


@jax.jit
def _shared_hidden(u, w1):
    with jax.default_matmul_precision("highest"):
        return jnp.square(jax.nn.relu(u @ w1))


def experts(m: Model, w: dict, u: np.ndarray, lat: np.ndarray,
            low_precision: bool = False) -> np.ndarray:
    """The held experts' part of the routed sum IN THE LATENT, in numpy
    float32: each held expert over the tokens that picked it. `u` (T, d) is
    what the router reads, `lat` (T, latent) what the experts read."""
    a = m.a
    with jax.default_matmul_precision("highest"):
        r = np.asarray(jnp.asarray(u) @ jnp.asarray(w["router"]))
    s = (1.0 / (1.0 + np.exp(-r.astype(np.float32)))).astype(np.float32)
    k = int(a["num_experts_per_tok"])
    top = np.argsort(-(s + w["e_bias"][None, :]), axis=-1, kind="stable")[:, :k]
    wt = np.take_along_axis(s, top, axis=-1)
    if a.get("norm_topk_prob", True):
        wt = wt / wt.sum(axis=-1, keepdims=True)
    wt = wt * np.float32(a.get("routed_scaling_factor", 1.0))
    y = np.zeros_like(lat)
    rnd = _round3 if low_precision else (lambda z: z)
    for local in range(m.e_count):
        tok, slot = np.nonzero(top == m.e_first + local)
        if tok.size == 0:
            continue
        h = np.square(np.maximum(rnd(lat[tok]) @ w["e_w1"][local], 0.0))
        y[tok] += wt[tok, slot][:, None] * (rnd(h) @ w["e_w2"][local])
    return y


def hidden_states(m: Model, sequences: list[np.ndarray], low_precision: bool = False) -> list:
    """Final hidden states (before the last norm) of each sequence of held-row
    ids; layers outermost, so each layer is drawn once and dropped."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) for ids in sequences]
    del embed
    rnd = _round3_whole if low_precision else (lambda z: z)
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(m.pattern):
            w = m.layer(i)
            if low_precision:  # the control: every kernel but the router's
                w = {k: (v if k in EXACT else np.asarray(_round3_whole(v))) for k, v in w.items()}
            for n, x in enumerate(xs):
                u = rnd(_rms(x, m.eps))
                if kind == "M":
                    g, w_out = mamba(m, w, u, jnp.bfloat16 if low_precision else jnp.float32)
                    y = _project(rnd(g).reshape(g.shape[0], -1),
                                 jnp.asarray(w_out).reshape(-1, m.d))
                elif kind == "*":
                    y = attention(m, w, u)
                else:
                    lat = _project(u, w["w_a"])
                    routed = jnp.asarray(experts(m, w, np.asarray(u), np.asarray(lat),
                                                 low_precision))
                    y = _project(rnd(routed), w["w_b"]) \
                        + _project(rnd(_shared_hidden(u, w["s_w1"])), w["s_w2"])
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
