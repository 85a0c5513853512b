"""The plain reference for the `hybrid` family: a language model whose layers
are single mixers chosen by a pattern string, written down from its published
`config.json` in straightforward float32 (`jax.numpy` under
`jax.default_matmul_precision("highest")`; the experts' products, whose groups
have every size, in `numpy` float32), with no cache, no batching, no chunking
and no kernel. It imports nothing of the program.

THE LAYER. Layer i of kind c = `hybrid_override_pattern[i]`:
`x <- x + mixer_c(RMSNorm(x; g_i))`, eps = `layer_norm_epsilon`; logits =
`RMSNorm(x; g_f) W_head`, untied; no biases but the convolution's.

- `M`, Mamba-2 (`mamba_num_heads` H, `mamba_head_dim` P, `n_groups` G,
  `ssm_state_size` N, `conv_kernel` k): `[z | xBC | dt] = u W_in`;
  `xBC_t <- silu(sum_j w_j * xBC_{t-k+1+j} + b)`, depthwise and causal, zeros
  before position 0; `xBC` splits into x (H x P), B and C (G x N each); head h
  reads group h // (H / G); `delta_{t,h} = softplus(dt_{t,h} + dt_bias_h)`,
  `a_{t,h} = exp(-exp(A_log_h) delta_{t,h})`,
  `S_{t,h} = a_{t,h} S_{t-1,h} + delta_{t,h} x_{t,h} (x) B_{t,g(h)}` (P x N),
  `y_{t,h} = S_{t,h} C_{t,g(h)} + D_h x_{t,h}`; `y <- RMSNorm_group(y * silu(z))`
  over each group's H / G heads (gate before norm); out = `y W_out`. HERE THE
  RECURRENCE IS THE RECURRENCE: a `lax.scan` over the tokens, one at a time
  (the program computes it by chunks in prefill and a step at a time in decode).
- `*`, attention: `num_attention_heads` query heads over `num_key_value_heads`
  KV heads of `head_dim` (query head h reads KV head h // (H / KV)), causal
  softmax of q.k / sqrt(head_dim), no rotary embedding, `W_o`.
- `E`, the latent expert layer: `s = sigmoid(u W_r)` over all
  `n_routed_experts` in float32; the `num_experts_per_tok` largest of `s + b`;
  weights `s_e / (their sum)` (`norm_topk_prob`) times `routed_scaling_factor`;
  `l = u W_a` (`moe_latent_size` wide); expert e: `relu(l W1_e)^2 W2_e`; routed =
  `(sum_e w_e expert_e(l)) W_b`; shared = `relu(u V1)^2 V2` on u itself.

THE SHARE (`share` in the architecture), the same as the program is given:
`experts_held = [first, count]` (picks on the others add nothing);
`attention_heads = [index, of]` (that part of the query heads; KV head
`index * KV // of` where the chips outnumber the KV heads); `mamba_heads =
[index, of]` (that part of the heads AND of the groups; the gated norm is over
a group, so the held groups are exact); `vocab_rows = [first, count]`. What the
absent parts would have added is left out here as in the program.

ASSUMED (the configuration file repeats this under `assumed`): sigmoid router
scores and a selection bias that does not enter the weight; no rotary
embedding; no clamp on delta beyond softplus; `dt_bias`, `A_log`, `D` drawn
inside the ranges the config's keys give. The multi-token-prediction module is
not part of the main stack's logits and is not here.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`): the same few
lines as `tpuserve/models/seeded.py` and `reference/decoder.py`, written down
again. The in-projection of a Mamba-2 layer is drawn in its five parts (`in_z`,
`in_x`, `in_B`, `in_C`, `in_dt`), the convolution in three, each a tensor of its
own, so that a share is a slice of each. The float32 vectors (`dt_bias`,
`A_log`, `D`, the router's `e_bias`) are the four summed bytes over their range
(0 to 1) mapped into [low, high].

THE CHECK (`compare`): as `reference/decoder.py`, each request of the sample is
served greedily with `logprobs` 8 and the reference runs ONE full pass over the
prompt and the served tokens; a generated position's number is the RMS of its
eight differences of served and reference log-probabilities, each side centred
(less its mean over the eight). THE STATISTIC is not their RMS but, a request,
their LOWER QUARTILE, and over the requests the largest (`logprob_q25`). Why: 22
picks of 512 by a sigmoid score have a 22nd and a 23rd candidate a few
thousandths apart, and the normalised weights are nearly equal (0.2 each), so
the bfloat16 stream's own error (a percent by the eighth layer) swaps that
pair at a third to a half of the positions, and a swap moves its position by
several times what the arithmetic does (chip, PR 32, the cell's configuration:
positions without a swap read about 0.01, those with one 0.05-0.15, the RMS
over all 0.026-0.037, the control 0.12-0.13: a bound on the RMS cannot stand 2x
from both). A swap is what serving this router in bfloat16 IS, not a fault; a
lower precision, a wrong state, a wrong share or a wrong position moves EVERY
position of a request and with them its lower quartile (sound 0.009-0.013,
control 0.10). What moves only some positions of a request is held by a
second, looser bound on the RMS over all positions (`check.rms_limit`); the
number compared with `check.limit` is the larger of the quartile and the RMS
scaled by `limit / rms_limit`, and the line prints both beside their limits.
`check.reference_inputs = "3-bit-mantissa"` (a control, never a cell) rounds the inputs of the
reference's matrix products (every kernel but the router's, the normed stream
that enters a block, the experts' hidden rows) to 3 explicit mantissa bits AND
keeps the recurrent state in bfloat16 (rounded after every token): the nearest
precisions below what the program serves.
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
DEFAULT_SCALES = {"embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
                  "ffn_out": 1.0, "expert_out": 1.0, "router": 1.0, "router_bias": 0.02,
                  "ssm_in": 1.0, "ssm_bc": 2.0, "ssm_dt": 1.0, "ssm_out": 1.0, "conv": 1.0,
                  "conv_bias": 0.1, "ssm_d": 0.1}
# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (
    "model_type", "attention_bias", "chunk_size", "conv_kernel", "expand", "head_dim",
    "hidden_size", "hybrid_override_pattern", "intermediate_size", "layer_norm_epsilon",
    "mamba_head_dim", "mamba_hidden_act", "mamba_num_heads", "mamba_proj_bias",
    "max_position_embeddings", "mlp_bias", "mlp_hidden_act", "moe_intermediate_size",
    "moe_latent_size", "moe_shared_expert_intermediate_size", "moe_shared_expert_overlap",
    "mtp_hybrid_override_pattern", "n_group", "n_groups", "n_routed_experts",
    "n_shared_experts", "norm_eps", "norm_topk_prob", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads", "num_logits_to_keep",
    "num_nextn_predict_layers", "partial_rotary_factor", "rescale_prenorm_residual",
    "residual_in_fp32", "rope_theta", "routed_scaling_factor", "sliding_window",
    "ssm_state_size", "tie_word_embeddings", "time_step_floor", "time_step_max",
    "time_step_min", "topk_group", "use_bias", "use_conv_bias", "use_mamba_kernels",
    "vocab_size")


# -- the architecture ------------------------------------------------------------

def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the benchmark:
    the published keys, with the counts that the file states as HELD HERE
    (`reduced`: experts, query and KV heads, Mamba heads and groups, vocabulary
    rows) put back to the published counts of `published` and the held part
    said under `share`, as the program and this reference read it."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    pub, ds = cfg.get("published", {}), cfg.get("deployment_share", {})
    idx = int(ds.get("index", 0))
    share = {}
    if "n_routed_experts" in pub:
        share["experts_held"] = [int(ds["experts_first"]), int(cfg["n_routed_experts"])]
    if "num_attention_heads" in pub:
        share["attention_heads"] = [idx, int(pub["num_attention_heads"])
                                    // int(cfg["num_attention_heads"])]
    if "mamba_num_heads" in pub:
        share["mamba_heads"] = [idx, int(pub["mamba_num_heads"]) // int(cfg["mamba_num_heads"])]
    if "vocab_size" in pub:
        share["vocab_rows"] = [int(ds["vocab_first"]), int(cfg["vocab_size"])]
    for key in ("n_routed_experts", "num_attention_heads", "num_key_value_heads",
                "mamba_num_heads", "n_groups", "vocab_size"):
        if key in pub:
            arch[key] = int(pub[key])
    assert len(arch["hybrid_override_pattern"]) == int(arch["num_hidden_layers"])
    if share:
        arch["share"] = share
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """What this file, flops/hybrid.py and the per-layer readers need."""
    gen = cfg["serve"]["tables"]["genserve"]
    served = cfg["assumed"]["served"]
    a = arch_from_config(cfg)
    share = a.get("share", {})
    of = share.get("attention_heads", [0, 1])[1]
    m_of = share.get("mamba_heads", [0, 1])[1]
    pattern = a["hybrid_override_pattern"]
    max_ctx = int(served["max_prompt_tokens"]) + int(served["max_new_tokens"])
    page, slots = int(gen["kv_page_tokens"]), int(gen["slots"])
    wb = 2 if cfg["serve"]["model"]["dtype"] == "bfloat16" else 4
    mh, mg = int(a["mamba_num_heads"]) // m_of, int(a["n_groups"]) // m_of
    mp, mn, ck = int(a["mamba_head_dim"]), int(a["ssm_state_size"]), int(a["conv_kernel"])
    return {
        "arch": a, "d_model": int(a["hidden_size"]), "head_dim": int(a["head_dim"]),
        "layers": len(pattern), "pattern": pattern,
        "n_mamba": pattern.count("M"), "n_attn": pattern.count("*"),
        "n_expert": pattern.count("E"),
        # what kv_reserved_pct (pages only) and the generic readers look up
        "layer_types": ["full_attention"] * pattern.count("*"), "window": 0,
        "heads": int(a["num_attention_heads"]) // of,
        "kv_heads": max(1, int(a["num_key_value_heads"]) // of),
        "mamba_heads": mh, "mamba_groups": mg, "mamba_head_dim": mp, "state_size": mn,
        "conv_kernel": ck, "conv_channels": mh * mp + 2 * mg * mn,
        "state_bytes_per_slot": pattern.count("M") * (
            mh * mp * mn * 4 + (ck - 1) * (mh * mp + 2 * mg * mn) * wb),
        "vocab": share.get("vocab_rows", [0, int(a["vocab_size"])])[1],
        "vocab_first": share.get("vocab_rows", [0, 0])[0],
        "experts_held": share.get("experts_held", [0, int(a["n_routed_experts"])])[1],
        "num_experts": int(a["n_routed_experts"]), "top_k": int(a["num_experts_per_tok"]),
        "expert_width": int(a["moe_intermediate_size"]), "latent": int(a["moe_latent_size"]),
        "shared_width": int(a["moe_shared_expert_intermediate_size"]),
        "max_prompt": int(served["max_prompt_tokens"]), "max_new": int(served["max_new_tokens"]),
        "max_ctx": max_ctx, "slots": slots, "page_tokens": page,
        "pages_per_slot": -(-max_ctx // page),
        "kv_pages": int(gen.get("kv_pages") or 0) or slots * -(-max_ctx // page) + 1,
        "prefill_chunk": int(gen.get("prefill_chunk") or 0) or int(served["max_prompt_tokens"]),
        "weight_bytes": wb,
    }


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
    t_kind: dict[str, float] = {}
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(m.pattern):
            t0 = time.monotonic()
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
                xs[n] = (x + y).block_until_ready()
            del w
            t_kind[kind] = t_kind.get(kind, 0.0) + time.monotonic() - t0
    print("[reference] " + str(sum(len(s) for s in sequences)) + " tokens through "
          + ", ".join(f"{n} {k} layers in {t_kind[k]:.1f} s" for k, n in
                      ((k, m.pattern.count(k)) for k in t_kind)), flush=True)
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


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

def prepare(seed: int, sizes: dict, cfg: dict, work: str):
    """No checkpoint: the program draws its weights on the device by
    `assumed.weights`. Writes the model's config file, in the published
    layout with the share, for `options.config_file`."""
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


def centred_gap(served: dict, ref_lp: np.ndarray, v_first: int) -> np.ndarray:
    """(positions, LOGPROBS) differences of served and reference centred
    log-probabilities at the ids the server named."""
    ids = np.asarray(served["logprobs"]["ids"], np.int64) - v_first
    got = np.asarray(served["logprobs"]["values"], np.float64)
    want = np.take_along_axis(ref_lp.astype(np.float64), ids, axis=-1)
    return (got - got.mean(axis=-1, keepdims=True)) - (want - want.mean(axis=-1, keepdims=True))


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    sz, ref = reference["sizes"], reference["ref"]
    v0 = sz["vocab_first"]
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
        ids = np.concatenate([inp["ids"], np.asarray(tokens[:-1], np.int64)]) - v0
        if ids.min() < 0 or ids.max() >= sz["vocab"]:
            return float("inf"), "logprob_rms=inf: a served token lies outside the held rows"
        seqs.append(ids)
        rows.append(len(inp["ids"]) - 1)
    low = cfg["check"].get("reference_inputs") == "3-bit-mantissa"
    model = Model(sz["arch"], ref["seed"], ref["dtype"])
    gaps = [centred_gap(a, lp, v0) for a, lp in zip(served, log_probs(model, seqs, rows, low))]
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
                  + (" [the reference's matrix inputs at 3 mantissa bits, its state in "
                     "bfloat16: a control]" if low else ""))
