"""A decoder-only language model built from a published ``config.json``
(ISSUE 28), served through the generation engine with two kinds of cache.

Nothing here knows a model's name. The architecture is read, under the
published key names, from the JSON file that ``options.config_file`` names
(as a deployment points a server at the model's own file): pre-RMSNorm
blocks, an attention whose head count, mask and rotary kind go by
``layer_types`` (``full_attention`` / ``sliding_attention``,
``num_attention_heads_per_layer``, the nested ``rope_parameters``: plain,
yarn, partial), grouped KV heads, an optional per-head output gate
(``gating = "per-head"``), and a feed-forward that is a dense SwiGLU or a
routed expert layer with a shared expert by ``mlp_layer_types``
(``tpuserve.ops.moe``). No biases; an untied head.

THE SHARE. One chip of several that divide each layer holds part of it, and
the file says which under ``share``: ``experts_held = [first, count]`` of the
router's ``num_experts``, ``attention_heads = [index, of]`` (query, KV and
gate heads ``index`` of ``of`` equal parts), ``vocab_rows = [first, count]``.
The router scores every expert; picks on absent experts add nothing; the
shared expert, the norms and the dense layers are whole. The logits, the
sampling and the request's ids are over the held rows. Without ``share`` the
model is whole. On one chip the layers run without their exchange.

THE CACHE (``[genserve] kv_paging``; the only way this family serves). Full
layers keep K and V in pages of the engine's ledger, read through a block
table; window layers keep the last ``sliding_window`` positions of a slot in
one ring a slot, written at ``position % window``. A decode step of a window
layer reads its ring and nothing else; a prefill chunk reads the ring and
itself. Chunked prefill followed by decode through both caches is the same
function as one causal forward pass (tests/test_decoder.py).

Requests: ``{"prompt_ids": [...], "max_new_tokens", "seed", "temperature",
"logprobs": k}``: ids because a published config names no tokenizer here;
an id outside the held rows is a 400. There is no end-of-sequence id: a
request generates exactly its ``max_new_tokens``. Every generated position's
top-``LOGPROBS`` log-probabilities are computed in the step for every lane
(the program that is timed is the program that is checked) and returned
where ``logprobs`` asks.

WEIGHTS: ``options.draw_weights_seed`` draws every tensor on the device, in
the served type, by ``tpuserve.models.seeded`` (scales under
``weight_scales`` in the config file); without it ``init_params`` draws the
same tensors from seed 0 wherever it is called.
"""

from __future__ import annotations

import json
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tpuserve.config import ModelConfig
from tpuserve.genserve.model import GenerativeModel, PrefillPiece
from tpuserve.models import seeded
from tpuserve.obs import GEN_PHASES
from tpuserve.ops.moe import held_experts_swiglu, topk_route

LOGPROBS = 8  # top log-probabilities kept per generated position
ACC = 5       # device-side sums a phase (kv_page_signature says which)
NEG = -1e9
MAX_PIECES = 8    # prompts' pieces one prefill launch takes at most
KEY_BLOCK = 1024  # key positions a block of a full layer's prefill attention

# Standard deviations of the drawn tensors, by role (``weight_scales`` in the
# config file overrides any). Projections keep a unit-RMS stream at unit RMS;
# the router and the query/key maps are drawn wider, so that routing and
# attention are decided and a check against a reference is not blunt.
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "gate": 1.0, "o": 1.0,
    "ffn_in": 1.0, "ffn_out": 1.0, "router": 4.0,
}


def rope_inv_freq(rp: dict, head_dim: int) -> tuple[np.ndarray, float, int]:
    """One ``rope_parameters`` entry -> (inverse frequencies (dim/2,), the
    factor on cos and sin, dim): ``dim = head_dim * partial_rotary_factor``
    leading dimensions of a head turn, the rest pass. ``default``: theta ** (-2i/dim).
    ``yarn`` (Peng et al. 2023, as transformers' ``_compute_yarn_parameters``):
    interpolated frequencies (divided by ``factor``) below the correction
    range, extrapolated (unchanged) above it, a linear ramp between."""
    dim = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    base = float(rp["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0, dim
    if kind != "yarn":
        raise NotImplementedError(f"rope_type {kind!r}")
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rp.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(rp.get("beta_slow", 1)))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolated = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1 - extrapolated) \
        + (1.0 / pos_freqs) * extrapolated
    att = rp.get("attention_factor")
    att = float(att) if att is not None else 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), att, dim


def apply_rope(x: jax.Array, pos: jax.Array, inv_freq: np.ndarray,
               factor: float, dim: int) -> jax.Array:
    """``x`` (..., T, H, head_dim) at positions ``pos`` (..., T): the first
    ``dim`` dimensions turn in pairs (i, i + dim/2), in float32."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos = (jnp.cos(ang) * factor)[..., None, :]
    sin = (jnp.sin(ang) * factor)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :dim // 2], xf[..., dim // 2:dim], xf[..., dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1).astype(x.dtype)


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _mm(a: jax.Array, w: jax.Array) -> jax.Array:
    """Product in the served type with float32 accumulation."""
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)


class DecoderServing(GenerativeModel):
    supports_kv_paging = True

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        o = cfg.options
        if not o.get("config_file"):
            raise ValueError(f"{cfg.name}: family decoder needs options.config_file "
                             "(the model's config.json)")
        with open(o["config_file"], encoding="utf-8") as f:
            a = json.load(f)
        self.dtype = jnp.dtype(cfg.dtype)
        for key, want in (("attention_bias", False), ("tie_word_embeddings", False),
                          ("moe_apply_router_weight_on_input", False)):
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        self.d = int(a["hidden_size"])
        self.hd = int(a.get("head_dim") or self.d // int(a["num_attention_heads"]))
        self.n_layers = int(a["num_hidden_layers"])
        self.eps = float(a.get("rms_norm_eps", 1e-6))
        self.layer_types = list(a.get("layer_types")
                                or ["full_attention"] * self.n_layers)
        self.mlp_types = list(a.get("mlp_layer_types")
                              or ["dense"] * self.n_layers)
        heads = list(a.get("num_attention_heads_per_layer")
                     or [int(a["num_attention_heads"])] * self.n_layers)
        if not (len(self.layer_types) == len(self.mlp_types) == len(heads)
                == self.n_layers):
            raise ValueError(f"{cfg.name}: the by-layer lists must have "
                             f"num_hidden_layers = {self.n_layers} entries")
        self.window = int(a.get("sliding_window") or 0)
        self.gated = a.get("gating") in ("per-head", "per_head")
        self.dense_width = int(a["intermediate_size"])
        self.n_experts = int(a.get("num_experts", 0))
        self.top_k = int(a.get("num_experts_per_tok", 0))
        self.expert_width = int(a.get("moe_intermediate_size", 0))
        self.shared_width = int(a.get("shared_expert_intermediate_size", 0))
        self.norm_topk = bool(a.get("norm_topk_prob", True))
        self.route_scale = float(a.get("moe_routed_scaling_factor", 1.0))
        self.softcap = float(a.get("moe_router_logit_softcapping", 0) or 0)
        self.vocab_full = int(a["vocab_size"])
        kv_full = int(a["num_key_value_heads"])
        # -- the share --------------------------------------------------------
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.n_experts])
        idx, of = share.get("attention_heads", [0, 1])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.vocab_full])
        if kv_full % of or any(h % of for h in heads):
            raise ValueError(f"{cfg.name}: share.attention_heads = [{idx}, {of}] "
                             "does not divide the head counts")
        self.heads_full, self.kv_full = heads, kv_full
        self.heads = [h // of for h in heads]      # held, by layer
        self.kv = kv_full // of
        self.h_first = [idx * h for h in self.heads]
        self.kv_first = idx * self.kv
        if any(h % self.kv for h in self.heads):
            raise ValueError(f"{cfg.name}: held query heads {self.heads} do not "
                             f"group over {self.kv} held KV heads")
        rp = a.get("rope_parameters") or {
            "full_attention": {"rope_type": "default",
                               "rope_theta": a.get("rope_theta", 10000.0)}}
        if "rope_theta" in rp:  # one kind for every layer
            rp = {t: rp for t in set(self.layer_types)}
        self.rope = {t: rope_inv_freq(rp[t], self.hd) for t in set(self.layer_types)}
        self.full_layers = [i for i, t in enumerate(self.layer_types)
                            if t == "full_attention"]
        self.win_layers = [i for i, t in enumerate(self.layer_types)
                           if t == "sliding_attention"]
        if self.win_layers and self.window < 1:
            raise ValueError(f"{cfg.name}: sliding_attention layers need sliding_window")
        self.sparse_layers = [i for i, t in enumerate(self.mlp_types) if t == "sparse"]
        # -- what is served ---------------------------------------------------
        self.max_prompt = int(o.get("max_prompt_tokens", 64))
        self.max_new = int(o.get("max_new_tokens", 32))
        self.max_ctx = self.max_prompt + self.max_new
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        seed = o.get("draw_weights_seed")
        self.draw_seed = None if seed is None else int(seed)
        self._counters: dict | None = None
        self._seen = np.zeros((2, ACC), np.uint32)

    # -- params ---------------------------------------------------------------
    def _tensors(self):
        """(path, shape held here, full shape, start, role, fan-in) of every
        matrix, in a fixed order."""
        d, hd, s = self.d, self.hd, self.scales
        yield (("embed",), (self.vocab, d), (self.vocab_full, d), (self.v_first, 0),
               s["embed"], 1)
        yield (("head",), (d, self.vocab), (d, self.vocab_full), (0, self.v_first),
               s["head"], d)
        for i in range(self.n_layers):
            L = f"layer{i}"
            hf, h, h0 = self.heads_full[i], self.heads[i], self.h_first[i]
            for name, scale in (("wq", s["qk"]), ("wk", s["qk"]), ("wv", s["v"])):
                nf, n, n0 = (hf, h, h0) if name == "wq" else \
                    (self.kv_full, self.kv, self.kv_first)
                yield ((L, name), (d, n, hd), (d, nf, hd), (0, n0, 0), scale, d)
            if self.gated:
                yield ((L, "wg"), (d, h), (d, hf), (0, h0), s["gate"], d)
            yield ((L, "wo"), (h, hd, d), (hf, hd, d), (h0, 0, 0), s["o"], hf * hd)
            if self.mlp_types[i] == "dense":
                f = self.dense_width
                for name in ("w_gate", "w_up"):
                    yield ((L, name), (d, f), (d, f), (0, 0), s["ffn_in"], d)
                yield ((L, "w_down"), (f, d), (f, d), (0, 0), s["ffn_out"], f)
                continue
            e, ec, e0, f, fs = (self.n_experts, self.e_count, self.e_first,
                                self.expert_width, self.shared_width)
            yield ((L, "router"), (d, e), (d, e), (0, 0), s["router"], d)
            for name in ("e_gate", "e_up"):
                yield ((L, name), (ec, d, f), (e, d, f), (e0, 0, 0), s["ffn_in"], d)
            yield ((L, "e_down"), (ec, f, d), (e, f, d), (e0, 0, 0), s["ffn_out"], f)
            for name in ("s_gate", "s_up"):
                yield ((L, name), (d, fs), (d, fs), (0, 0), s["ffn_in"], d)
            yield ((L, "s_down"), (fs, d), (fs, d), (0, 0), s["ffn_out"], fs)

    def draw_params(self, seed: int) -> Any:
        """Jittable: every tensor by the recipe of ``tpuserve.models.seeded``,
        in the served type; norms' gains are ones."""
        p: dict = {"norm_f": jnp.ones((self.d,), self.dtype)}
        for i in range(self.n_layers):
            p[f"layer{i}"] = {"norm1": jnp.ones((self.d,), self.dtype),
                              "norm2": jnp.ones((self.d,), self.dtype)}
        for path, shape, full, start, scale, fan_in in self._tensors():
            node = p
            for key in path[:-1]:
                node = node[key]
            node[path[-1]] = seeded.draw(
                seed, "/".join(path), shape, scale / math.sqrt(fan_in),
                self.dtype, full_shape=full, start=start)
        return p

    def _drawn(self) -> Any:
        return jax.jit(self.draw_params, static_argnums=0)(self.draw_seed or 0)

    def init_params(self, rng: jax.Array) -> Any:
        return self._drawn()

    def device_params(self, device: Any) -> Any:
        """The runtime's hook for weights that never cross the host: drawn on
        ``device`` in one jitted call where ``draw_weights_seed`` is set."""
        if self.draw_seed is None or self.cfg.weights:
            return None
        with jax.default_device(device):
            return jax.block_until_ready(self._drawn())

    # -- the locked-batch contract: not served ----------------------------------
    def _paged_only(self, *_a, **_k):
        raise NotImplementedError(
            f"{self.name}: family decoder serves through the generation engine "
            "alone: set [genserve] enabled = true and kv_paging = true")

    input_signature = forward = host_postprocess = _paged_only
    state_signature = init_state = _paged_only

    # -- shapes -----------------------------------------------------------------
    def gen_item_signature(self) -> Any:
        i32 = jnp.int32
        return (jax.ShapeDtypeStruct((self.max_prompt,), i32),  # held-row ids
                jax.ShapeDtypeStruct((), i32),                  # prompt length
                jax.ShapeDtypeStruct((), i32),                  # seed
                jax.ShapeDtypeStruct((), i32),                  # max_new_tokens
                jax.ShapeDtypeStruct((), jnp.float32),          # temperature
                jax.ShapeDtypeStruct((), i32))                  # logprobs asked

    def kv_pages_per_slot(self, page_tokens: int) -> int:
        return -(-self.max_ctx // int(page_tokens))

    def kv_ring_tokens(self) -> int:
        return self.window if self.win_layers else 0

    def kv_page_signature(self, slots: int, pages: int, page_tokens: int) -> Any:
        S = jax.ShapeDtypeStruct
        i32, n = jnp.int32, self.max_new
        pps = self.kv_pages_per_slot(page_tokens)
        page = S((self.kv, pages, page_tokens, self.hd), self.dtype)
        ring = S((slots + 1, self.window, self.kv, self.hd), self.dtype)
        return {
            "kf": [page for _ in self.full_layers], "vf": [page for _ in self.full_layers],
            "kw": [ring for _ in self.win_layers], "vw": [ring for _ in self.win_layers],
            "bt": S((slots, pps), i32), "ring": S((slots,), i32),
            "pos": S((slots,), i32), "n_new": S((slots,), i32),
            "last": S((slots,), i32), "armed": S((slots,), jnp.bool_),
            "done": S((slots,), jnp.bool_), "seed": S((slots,), i32),
            "max_new": S((slots,), i32), "temp": S((slots,), jnp.float32),
            "tokens": S((slots, n), i32),
            "lp_ids": S((slots, n, LOGPROBS), i32),
            "lp": S((slots, n, LOGPROBS), jnp.float32),
            # Cumulative, wrapping; row 0 prefill chunks, row 1 decode steps:
            # picks of live tokens on held and on absent experts, held
            # experts hit, held experts x sparse layers run, and the context
            # (positions a live token attends from) summed over live tokens.
            "acc": S((2, ACC), jnp.uint32),
        }

    def pages_needed(self, item: Any, page_tokens: int) -> int:
        return -(-(int(item[1]) + int(item[3])) // int(page_tokens))

    def prompt_tokens(self, item: Any) -> int:
        return int(item[1])

    def kv_prefill_chunk(self, requested: int) -> int:
        if requested <= 0 or requested >= self.max_prompt:
            return self.max_prompt
        return int(requested)

    def gen_max_steps(self) -> int:
        return self.max_new

    # -- device math --------------------------------------------------------------
    def _qkv(self, lp: dict, i: int, u: jax.Array, pos: jax.Array):
        """``u`` (T, d) normed stream at positions ``pos`` (T,) -> rotated q
        (T, H, hd), rotated k and v (T, KV, hd), the gate (T, H) or None."""
        dt = self.dtype
        inv, factor, dim = self.rope[self.layer_types[i]]
        q = jnp.einsum("td,dhk->thk", u, lp["wq"],
                       preferred_element_type=jnp.float32).astype(dt)
        k = jnp.einsum("td,dhk->thk", u, lp["wk"],
                       preferred_element_type=jnp.float32).astype(dt)
        v = jnp.einsum("td,dhk->thk", u, lp["wv"],
                       preferred_element_type=jnp.float32).astype(dt)
        gate = jax.nn.sigmoid(_mm(u, lp["wg"])) if self.gated else None
        return (apply_rope(q, pos, inv, factor, dim),
                apply_rope(k, pos, inv, factor, dim), v, gate)

    def _attend(self, q, k, v, mask):
        """q (..., T, H, hd), k and v (..., C, KV, hd), mask (..., T, C) True
        where a query may see a key -> (..., T, H, hd) in float32. Query head
        h reads KV head h // (H / KV)."""
        kvh = k.shape[-2]
        g = q.shape[-2] // kvh
        qg = q.reshape(q.shape[:-2] + (kvh, g, q.shape[-1]))
        s = jnp.einsum("...tkgd,...ckd->...kgtc", qg, k,
                       preferred_element_type=jnp.float32) * (self.hd ** -0.5)
        s = jnp.where(mask[..., None, None, :, :], s, NEG)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("...kgtc,...ckd->...tkgd", p, v,
                       preferred_element_type=jnp.float32)
        return o.reshape(q.shape)

    def _attn_out(self, lp, o, gate):
        """o (T, H, hd) float32 -> (T, d): gated by head, through W_o."""
        if gate is not None:
            o = o * gate[..., None]
        return jnp.einsum("thk,hkd->td", o.astype(self.dtype), lp["wo"],
                          preferred_element_type=jnp.float32)

    @staticmethod
    def _write_pages(pool, page, off, rows):
        """``rows`` (T, KV, hd) into the pool (KV, pages, P, hd) at (page[t],
        off[t]) of every KV head: as ONE scatter of rows into the pool seen
        as (KV * pages * P, hd). (Scattered over two middle dimensions, the
        compiler copied the whole pool to another layout and back, eight
        times a step: 13 of a step's 33 ms, my chip run, PR 28.)"""
        kv, n_pages, p_tokens, hd = pool.shape
        at = (jnp.arange(kv)[None, :] * n_pages + page[:, None]) * p_tokens + off[:, None]
        flat = pool.reshape(kv * n_pages * p_tokens, hd)
        return flat.at[at.reshape(-1)].set(rows.reshape(-1, hd)).reshape(pool.shape)

    def _swiglu(self, u, w_gate, w_up, w_down):
        h = (jax.nn.silu(_mm(u, w_gate)) * _mm(u, w_up)).astype(self.dtype)
        return _mm(h, w_down)

    def _ffn(self, lp, i, u, live):
        """(T, d) -> ((T, d) float32, the expert layer's counts or None)."""
        if self.mlp_types[i] == "dense":
            return self._swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        r = jnp.matmul(u.astype(jnp.float32), lp["router"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        if self.softcap > 0:
            r = self.softcap * jnp.tanh(r / self.softcap)
        w, e = topk_route(r, self.top_k, normalize=self.norm_topk,
                          scale=self.route_scale)
        y, stats = held_experts_swiglu(u, w, e, self.e_first, lp["e_gate"],
                                       lp["e_up"], lp["e_down"], live=live)
        return y + self._swiglu(u, lp["s_gate"], lp["s_up"], lp["s_down"]), stats

    def _accumulate(self, acc, phase: int, stats_list, context):
        row = jnp.zeros((ACC,), jnp.uint32).at[4].set(context.astype(jnp.uint32))
        for st in stats_list:
            row = row.at[:4].add(jnp.stack([
                st["routed_held"], st["routed_absent"], st["experts_hit"],
                jnp.int32(self.e_count)]).astype(jnp.uint32))
        return acc.at[phase].add(row)

    def _head(self, params, x):
        """(T, d) -> (T, vocab held) float32 logits."""
        return _mm(rms_norm(x, params["norm_f"], self.eps), params["head"])

    def _sample(self, logits, seed, position, temp):
        """Greedy where temp == 0, Gumbel-max otherwise, keyed by the
        request's seed and the position sampled for; also the top
        log-probabilities of the distribution sampled from."""
        def one(lg, sd, pos, t):
            key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), sd), pos)
            g = jax.random.gumbel(key, lg.shape, jnp.float32)
            sampled = jnp.argmax(lg / jnp.where(t > 0, t, 1.0) + g)
            return jnp.where(t > 0, sampled, jnp.argmax(lg)).astype(jnp.int32)

        tok = jax.vmap(one)(logits, seed, position, temp)
        lp, ids = jax.lax.top_k(jax.nn.log_softmax(logits, axis=-1), LOGPROBS)
        return tok, ids.astype(jnp.int32), lp

    # -- prefill ------------------------------------------------------------------
    # One launch of the static width C carries the waiting pieces of up to K
    # prompts (ISSUE 31), in K tiles of T = C / K rows; a piece takes whole
    # tiles, so a tile belongs to one prompt. Whatever a token passes
    # through alone (embedding, norms, projections, the feed-forwards, the
    # experts) runs once over the C packed rows; attention goes tile by tile,
    # each over its own prompt's caches.

    def kv_prefill_pieces(self, chunk: int, page_tokens: int) -> int:
        """K: tiles of whole pages, as many as divide the chunk, at most
        ``MAX_PIECES``. (The window does not enter: a window layer's tile
        reads its ring and the ``window`` rows before it whatever its width.)"""
        return next((k for k in range(min(MAX_PIECES, max(1, chunk // page_tokens)), 1, -1)
                     if chunk % (k * page_tokens) == 0), 1)

    def pack_prefill(self, pieces: list[PrefillPiece], chunk: int, k: int) -> Any:
        """Host-side: what one launch is told of its pieces, each at the next
        free tile: the packed token ids and, a piece, its slot, range, block-
        table row, ring and the request's sampling parameters. Entries past
        ``len(pieces)`` have length 0 and write nothing."""
        tile = chunk // k
        if sum(-(-p.length // tile) for p in pieces) > k:
            raise ValueError(f"{self.name}: pieces of {[p.length for p in pieces]} tokens "
                             f"do not fit a launch of {k} tiles of {tile}")
        out = {"ids": np.zeros((chunk,), np.int32),
               "pages": np.zeros((k, pieces[0].cache["pages"].shape[0]), np.int32),
               **{f: np.zeros((k,), np.int32) for f in
                  ("slot", "start", "length", "n", "seed", "max_new", "ring")},
               "temp": np.zeros((k,), np.float32)}
        at = 0
        for j, p in enumerate(pieces):
            ids, n, seed, max_new, temp, _want = p.item
            out["ids"][at:at + p.length] = ids[p.start:p.start + p.length]
            at += -(-p.length // tile) * tile
            for f, v in (("slot", p.slot), ("start", p.start), ("length", p.length),
                         ("n", n), ("seed", seed), ("max_new", max_new), ("temp", temp),
                         ("ring", p.cache["ring"]), ("pages", p.cache["pages"])):
                out[f][j] = v
        return out

    def _prefill_window(self, q, k, v, ring_k, ring_v, qpos, rpos, kpos, ok):
        """A window layer's attention of one launch, tile by tile: q (K, T,
        H, hd) at positions ``qpos`` (K, T); k and v (C, KV, hd), the
        launch's own rows at positions ``kpos`` (``ok`` (K, C) where a row is
        a live token of the tile's prompt); ``ring_k``/``ring_v`` (K, W, KV,
        hd), what each tile's ring held BEFORE the launch, at positions
        ``rpos`` (K, W), negative where nothing was written. A tile sees its
        ring and the W rows before its own last: a query sees no further
        back, so the scores are (T, 2W + T) a head."""
        n_tiles, T = qpos.shape
        W = self.window
        # Row r of the launch at W + r; tile t reads rows [tT - W, tT + T).
        idx = jnp.arange(n_tiles)[:, None] * T + jnp.arange(W + T)[None, :]

        def near(a, fill):
            pad = jnp.full((W,) + a.shape[1:], fill, a.dtype)
            return jnp.take(jnp.concatenate([pad, a], axis=0), idx, axis=0)

        seen = jnp.take_along_axis(jnp.pad(ok, ((0, 0), (W, 0))), idx, axis=1)
        keys_at = jnp.concatenate([rpos, near(kpos, -1)], axis=1)       # (K, 2W + T)
        dist = qpos[:, :, None] - keys_at[:, None, :]
        mask = (dist >= 0) & (dist < W) \
            & jnp.concatenate([rpos >= 0, seen], axis=1)[:, None, :]
        return self._attend(q, jnp.concatenate([ring_k, near(k, 0)], axis=1),
                            jnp.concatenate([ring_v, near(v, 0)], axis=1), mask)

    def _prefill_full(self, q, kp, vp, row, qpos, last):
        """A full layer's attention of one tile, q (T, H, hd) at positions
        ``qpos``, over its prompt's pages (block-table row ``row``) up to the
        tile's last live position ``last``: key blocks of ``KEY_BLOCK``
        positions, as many as that position needs (a traced count: a
        prompt's first tile reads one block, not the padded context), summed
        with a running softmax in float32. Every row of the launch is in the
        pages before any tile reads them."""
        T, P, pps = q.shape[0], kp.shape[2], row.shape[0]
        kb = max(1, min(KEY_BLOCK // P, pps))     # pages a key block
        n_blocks = -(-pps // kb)
        rowp = jnp.pad(row, (0, n_blocks * kb - pps))
        g = q.shape[1] // self.kv
        qg = q.reshape(T, self.kv, g, self.hd)
        need = jnp.minimum(last // (kb * P) + 1, n_blocks)

        def body(j, carry):
            m, l, acc = carry
            pg = jax.lax.dynamic_slice(rowp, (j * kb,), (kb,))
            kblk = jnp.take(kp, pg, axis=1).reshape(self.kv, kb * P, self.hd)
            vblk = jnp.take(vp, pg, axis=1).reshape(self.kv, kb * P, self.hd)
            kpos = j * kb * P + jnp.arange(kb * P)
            see = kpos[None, :] <= qpos[:, None]
            s = jnp.einsum("tkgd,kcd->kgtc", qg, kblk,
                           preferred_element_type=jnp.float32) * (self.hd ** -0.5)
            s = jnp.where(see[None, None], s, NEG)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m2[..., None])
            scale = jnp.exp(m - m2)
            acc = acc * scale[..., None] + jnp.einsum(
                "kgtc,kcd->kgtd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return m2, l * scale + jnp.sum(p, axis=-1), acc

        m0 = jnp.full((self.kv, g, T), NEG, jnp.float32)
        _m, l, acc = jax.lax.fori_loop(
            0, need, body, (m0, jnp.zeros_like(m0),
                            jnp.zeros((self.kv, g, T, self.hd), jnp.float32)))
        return (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(q.shape)

    def prefill_chunk(self, params: Any, state: Any, launch: Any, *, chunk: int) -> Any:
        """One launch of ``pack_prefill``: piece j is tokens [start[j],
        start[j] + length[j]) of the prompt in slot[j], causal within the
        piece and over what earlier launches left in that slot's caches. A
        token sees its own prompt only, at its own positions; a piece that
        ends its prompt samples the first token at its own last row and arms
        its own lane."""
        slot, start, length, n = (launch[f] for f in ("slot", "start", "length", "n"))
        C, K = int(chunk), slot.shape[0]
        T, W = C // K, max(self.window, 1)   # W = 1: no window layer reads it
        P = state["kf"][0].shape[2] if self.full_layers else 1
        pps = state["bt"].shape[1]
        # Tile t belongs to the piece whose run of tiles holds it (K: none).
        n_tiles = -(-length // T)
        tiles_to = jnp.cumsum(n_tiles)
        tiles = jnp.arange(K)
        piece = jnp.searchsorted(tiles_to, tiles, side="right")
        has = piece < K
        piece = jnp.minimum(piece, K - 1)
        first_tile = tiles_to - n_tiles
        end = jnp.where(has, (start + length)[piece], 0)                  # (K,) by tile
        qpos = (start[piece] + (tiles - first_tile[piece]) * T)[:, None] \
            + jnp.arange(T)[None, :]                                       # (K, T)
        cpos, of_piece = qpos.reshape(C), jnp.repeat(piece, T)
        valid = (qpos < end[:, None]).reshape(C)
        last = jnp.maximum(jnp.minimum(qpos[:, -1], end - 1), 0)          # (K,) by tile
        rows, rings = launch["pages"][piece], launch["ring"][piece]       # by tile
        x = jnp.take(params["embed"], launch["ids"], axis=0)
        w_page = jnp.where(valid, jnp.take_along_axis(
            jnp.repeat(rows, T, axis=0), jnp.minimum(cpos // P, pps - 1)[:, None],
            axis=1)[:, 0], 0)
        off = cpos % P
        # Window layers: of a piece's positions that fall on one ring place
        # only the last lands; the rest, and padding, go to ring 0.
        w_ring = jnp.where(valid & (cpos >= jnp.repeat(end, T) - W),
                           jnp.repeat(rings, T), 0)
        roff = cpos % W
        # What a ring held before this launch: place r has the newest
        # position <= start - 1 that is r modulo W.
        before = (start[piece] - 1)[:, None]
        rpos = jnp.where(has[:, None], before - ((before - jnp.arange(W)[None, :]) % W), -1)
        own = valid[None, :] & (of_piece[None, :] == piece[:, None]) & has[:, None]
        kf, vf, kw, vw = (list(state[k]) for k in ("kf", "vf", "kw", "vw"))
        stats = []
        for i in range(self.n_layers):
            lp = params[f"layer{i}"]
            q, k, v, gate = self._qkv(lp, i, rms_norm(x, lp["norm1"], self.eps), cpos)
            qt = q.reshape((K, T) + q.shape[1:])
            if self.layer_types[i] == "full_attention":
                j = self.full_layers.index(i)
                kf[j] = self._write_pages(kf[j], w_page, off, k)
                vf[j] = self._write_pages(vf[j], w_page, off, v)
                o = jax.lax.map(
                    lambda a, kp=kf[j], vp=vf[j]: self._prefill_full(a[0], kp, vp, *a[1:]),
                    (qt, rows, qpos, last))
            else:
                j = self.win_layers.index(i)
                o = self._prefill_window(qt, k, v, jnp.take(kw[j], rings, axis=0),
                                         jnp.take(vw[j], rings, axis=0), qpos, rpos,
                                         cpos, own)
                kw[j] = kw[j].at[w_ring, roff].set(k)
                vw[j] = vw[j].at[w_ring, roff].set(v)
            x = x + self._attn_out(lp, o.reshape(q.shape), gate).astype(self.dtype)
            y, st = self._ffn(lp, i, rms_norm(x, lp["norm2"], self.eps), valid)
            if st is not None:
                stats.append(st)
            x = x + y.astype(self.dtype)
        # Each piece that ends its prompt samples at its own last row; a
        # piece of no tokens writes nothing (its slot is out of range).
        is_final = (length > 0) & (start + length >= n)
        h_last = jnp.take(x, jnp.clip(first_tile * T + n - 1 - start, 0, C - 1), axis=0)
        first, lp_ids, lp_vals = self._sample(
            self._head(params, h_last), launch["seed"], n, launch["temp"])
        new = dict(state, kf=kf, vf=vf, kw=kw, vw=vw,
                   acc=self._accumulate(state["acc"], 0, stats,
                                        jnp.sum(jnp.where(valid, cpos + 1, 0))))
        at = jnp.where(length > 0, slot, state["pos"].shape[0])
        lanes = {"bt": launch["pages"], "ring": launch["ring"],
                 "tokens": jnp.zeros((K, self.max_new), jnp.int32).at[:, 0].set(first),
                 "pos": jnp.where(is_final, n, 0), "n_new": jnp.where(is_final, 1, 0),
                 "last": first, "armed": is_final,
                 "done": is_final & (launch["max_new"] <= 1), "seed": launch["seed"],
                 "max_new": launch["max_new"], "temp": launch["temp"]}
        for name, val in lanes.items():
            new[name] = state[name].at[at].set(val.astype(state[name].dtype), mode="drop")
        for name, val in (("lp_ids", lp_ids), ("lp", lp_vals)):
            new[name] = state[name].at[at, 0].set(val.astype(state[name].dtype), mode="drop")
        return new

    # -- decode -------------------------------------------------------------------
    def _decode_full(self, q, kp, vp, bt, pos):
        """One full layer's decode attention through the block table: q
        (b, H, hd), pages (KV, pages, P, hd), bt (b, pps) -> (b, H, hd)
        float32. On the TPU a kernel that reads live pages only; elsewhere
        (tests, toys) a gather of the padded block table."""
        on_tpu = jax.default_backend() == "tpu" and self.dtype == jnp.bfloat16 \
            and self.hd % 128 == 0 and kp.shape[2] % 8 == 0
        if on_tpu:  # tps-ok[TPS503]: backend and static shapes, at trace time
            # The Pallas paged-attention kernel (my chip runs, PR 28: 0.8 ms a
            # layer for 128 lanes holding 172,000 positions, within 0.002 of
            # plain attention). It does not scale the scores, so the queries are.
            from jax.experimental.pallas.ops.tpu.paged_attention import \
                paged_attention

            ppcb = max(c for c in range(1, 33) if bt.shape[1] % c == 0)
            qs = (q.astype(jnp.float32) * (self.hd ** -0.5)).astype(q.dtype)
            return paged_attention(qs, kp, vp, pos + 1, bt,
                                   pages_per_compute_block=ppcb
                                   ).astype(jnp.float32)
        b, (P, pps) = q.shape[0], (kp.shape[2], bt.shape[1])
        kc = jnp.take(kp, bt, axis=1).reshape(self.kv, b, pps * P, self.hd)
        vc = jnp.take(vp, bt, axis=1).reshape(self.kv, b, pps * P, self.hd)
        mask = (jnp.arange(pps * P)[None, :] <= pos[:, None])[:, None, :]
        return self._attend(q[:, None], kc.transpose(1, 2, 0, 3),
                            vc.transpose(1, 2, 0, 3), mask)[:, 0]

    def step(self, params: Any, state: Any) -> tuple[Any, dict]:
        b = state["pos"].shape[0]
        W = max(self.window, 1)
        live = state["armed"] & ~state["done"]
        pos = jnp.clip(state["pos"], 0, self.max_ctx - 1)
        rows = jnp.arange(b)
        x = jnp.take(params["embed"], state["last"], axis=0)
        P = state["kf"][0].shape[2] if self.full_layers else 1
        page_of = jnp.take_along_axis(state["bt"], (pos // P)[:, None], axis=1)[:, 0]
        w_page = jnp.where(live, page_of, 0)
        off = pos % P
        w_ring = jnp.where(live, state["ring"], 0)
        roff = pos % W
        # Ring place r holds the newest position <= pos that is r modulo W.
        rpos = pos[:, None] - ((pos[:, None] - jnp.arange(W)[None, :]) % W)
        mask_win = (rpos >= 0)[:, None, :]
        kf, vf, kw, vw = (list(state[k]) for k in ("kf", "vf", "kw", "vw"))
        stats = []
        for i in range(self.n_layers):
            lp = params[f"layer{i}"]
            q, k, v, gate = self._qkv(lp, i, rms_norm(x, lp["norm1"], self.eps), pos)
            if self.layer_types[i] == "full_attention":
                j = self.full_layers.index(i)
                kf[j] = self._write_pages(kf[j], w_page, off, k)
                vf[j] = self._write_pages(vf[j], w_page, off, v)
                o = self._decode_full(q, kf[j], vf[j], state["bt"], pos)
            else:
                j = self.win_layers.index(i)
                kw[j] = kw[j].at[w_ring, roff].set(k)
                vw[j] = vw[j].at[w_ring, roff].set(v)
                # A free lane reads ring 0, which every free lane writes: its
                # result is discarded.
                o = self._attend(q[:, None], jnp.take(kw[j], w_ring, axis=0),
                                 jnp.take(vw[j], w_ring, axis=0), mask_win)[:, 0]
            x = x + self._attn_out(lp, o, gate).astype(self.dtype)
            y, st = self._ffn(lp, i, rms_norm(x, lp["norm2"], self.eps), live)
            if st is not None:
                stats.append(st)
            x = x + y.astype(self.dtype)
        nxt = jnp.clip(pos + 1, 0, self.max_ctx - 1)
        tok, lp_ids, lp_vals = self._sample(self._head(params, x), state["seed"],
                                            nxt, state["temp"])
        n_new = state["n_new"]
        at = jnp.clip(n_new, 0, self.max_new - 1)
        keep = ~live
        tokens = state["tokens"].at[rows, at].set(
            jnp.where(keep, state["tokens"][rows, at], tok))
        new_lp_ids = state["lp_ids"].at[rows, at].set(
            jnp.where(keep[:, None], state["lp_ids"][rows, at], lp_ids))
        new_lp = state["lp"].at[rows, at].set(
            jnp.where(keep[:, None], state["lp"][rows, at], lp_vals))
        n_new2 = jnp.where(live, n_new + 1, n_new)
        done2 = state["done"] | (live & (n_new2 >= state["max_new"]))
        acc = self._accumulate(state["acc"], 1, stats,
                               jnp.sum(jnp.where(live, pos + 1, 0)))
        new = dict(state, kf=kf, vf=vf, kw=kw, vw=vw, tokens=tokens,
                   lp_ids=new_lp_ids, lp=new_lp, n_new=n_new2, done=done2,
                   pos=jnp.where(live, nxt, state["pos"]),
                   last=jnp.where(live, tok, state["last"]), acc=acc)
        return new, {"done": done2 | ~state["armed"], "n_new": n_new2,
                     "first": tokens[:, 0], "last": new["last"], "acc": acc}

    def extract(self, params: Any, state: Any, slot: Any) -> Any:
        idx = jax.lax.dynamic_index_in_dim
        return {k: idx(state[k], slot, 0, keepdims=False)
                for k in ("tokens", "n_new", "lp_ids", "lp")}

    # -- host side ----------------------------------------------------------------
    def bind_metrics(self, metrics: Any) -> None:
        name = self.name
        self._counters = [[
            metrics.counter(f"moe_tokens_routed_total{{model={name},phase={ph},held=yes}}"),
            metrics.counter(f"moe_tokens_routed_total{{model={name},phase={ph},held=no}}"),
            metrics.counter(f"moe_experts_hit_total{{model={name},phase={ph}}}"),
            metrics.counter(f"moe_expert_steps_total{{model={name},phase={ph}}}"),
            metrics.counter(f"gen_context_tokens_total{{model={name},phase={ph}}}"),
        ] for ph in GEN_PHASES]

    def observe_step(self, step_out: dict) -> None:
        """The device's cumulative counts (prefill chunks and steps since
        the last fetch) into the program's counters."""
        if self._counters is None:
            return
        now = np.asarray(step_out["acc"], np.uint32)
        delta = now - self._seen  # wraps as the device's sums do
        # A sum that went "back" by more than half the range did not wrap:
        # the engine rebuilt its state block from zeros.
        delta = np.where(delta > np.uint32(2 ** 31), now, delta)
        self._seen = now
        for row, counters in zip(delta, self._counters):
            for v, c in zip(row, counters):
                if v:
                    c.inc(float(v))

    def host_decode(self, payload: bytes, content_type: str) -> Any:
        body = json.loads(payload.decode("utf-8"))
        ids = body.get("prompt_ids") if isinstance(body, dict) else None
        if not isinstance(ids, list) or not ids \
                or not all(isinstance(t, int) and not isinstance(t, bool) for t in ids):
            raise ValueError('JSON body must contain "prompt_ids": a non-empty '
                             "list of token ids")
        if len(ids) > self.max_prompt:
            raise ValueError(f"prompt of {len(ids)} tokens; this server takes up "
                             f"to {self.max_prompt}")
        arr = np.asarray(ids, np.int64) - self.v_first
        if arr.min() < 0 or arr.max() >= self.vocab:
            raise ValueError(
                f"prompt_ids must lie in the vocabulary rows held here, "
                f"[{self.v_first}, {self.v_first + self.vocab})")
        max_new = int(body.get("max_new_tokens", self.max_new))
        temp = float(body.get("temperature", 0.0))
        want = int(body.get("logprobs", 0) or 0)
        if not 1 <= max_new <= self.max_new:
            raise ValueError(f"max_new_tokens must be in [1, {self.max_new}], "
                             f"got {max_new}")
        if temp < 0:
            raise ValueError(f"temperature must be >= 0, got {temp}")
        if not 0 <= want <= LOGPROBS:
            raise ValueError(f"logprobs must be in [0, {LOGPROBS}], got {want}")
        padded = np.zeros((self.max_prompt,), np.int32)
        padded[: len(ids)] = arr
        # Every parameter of the answer is part of the item: the result
        # cache digests the whole tuple.
        return (padded, np.int32(len(ids)), np.int32(int(body.get("seed", 0))),
                np.int32(max_new), np.float32(temp), np.int32(want))

    def canary_item(self) -> Any:
        body = {"prompt_ids": [self.v_first], "seed": 1, "max_new_tokens": 2}
        return self.host_decode(json.dumps(body).encode(), "application/json")

    def finalize(self, extracted: Any, item: Any) -> Any:
        n = int(extracted["n_new"])
        toks = [int(t) + self.v_first for t in np.asarray(extracted["tokens"])[:n]]
        out = {"tokens": toks, "n_tokens": n}
        want = int(item[5])
        if want:
            out["logprobs"] = {
                "ids": (np.asarray(extracted["lp_ids"])[:n, :want]
                        + self.v_first).tolist(),
                "values": np.asarray(extracted["lp"])[:n, :want].astype(float).tolist()}
        return out

    def result_units(self, result: Any) -> float:
        return float(result.get("n_tokens", 1))

    def stream_units(self, step_out: dict, slot: int, stream: dict) -> list:
        """One token a step, the lane's ``last``; the first fetch of a lane
        brings the prefill's token with it."""
        n, sent = int(step_out["n_new"][slot]), int(stream.get("sent", 0))
        if n <= sent:
            return []
        stream["sent"] = n
        units = [{"type": "token", "index": n - 1,
                  "token": int(step_out["last"][slot]) + self.v_first}]
        if sent == 0 and n > 1:
            units.insert(0, {"type": "token", "index": 0,
                             "token": int(step_out["first"][slot]) + self.v_first})
        return units

    def stream_finish_reason(self, result: Any) -> str:
        return "length"

    def stream_usage(self, result: Any) -> dict:
        return {"completion_tokens": int(result.get("n_tokens", 0))}


def create(cfg: ModelConfig) -> DecoderServing:
    return DecoderServing(cfg)
