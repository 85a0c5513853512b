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

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tpuserve.config import ModelConfig
from tpuserve.genserve.model import CachePlan, pool, rings
from tpuserve.models.paged_lm import (COMPACT_COLUMN, CONTEXT_COLUMN,  # noqa: F401
                                      EXPERT_COLUMNS, KEY_BLOCK, LOGPROBS, MAX_PIECES,
                                      NEG, SAMPLE_COLUMNS, PagedLM, _mm, head_share,
                                      read_config_file, rms_norm, scoped)
from tpuserve.ops.moe import held_experts_swiglu, router_logits, topk_route

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
               factor: float, dim: int, interleave: bool = False) -> jax.Array:
    """``x`` (..., T, H, head_dim) at positions ``pos`` (..., T): the first
    ``dim`` dimensions turn in pairs (i, i + dim/2), or with ``interleave``
    in pairs (2i, 2i + 1), in float32."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos = (jnp.cos(ang) * factor)[..., None, :]
    sin = (jnp.sin(ang) * factor)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        x1, x2, rest = xf[..., 0:dim:2], xf[..., 1:dim:2], xf[..., dim:]
        turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return jnp.concatenate([turned.reshape(xf.shape[:-1] + (dim,)), rest],
                               axis=-1).astype(x.dtype)
    x1, x2, rest = xf[..., :dim // 2], xf[..., dim // 2:dim], xf[..., dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1).astype(x.dtype)


def _attn_scope(t) -> str:
    """The scope a layer's attention stands under in a trace, by the phase
    its plan is of (``t``: the tiles; None in a step)."""
    return "attn_decode" if t is None else "attn_prefill"


class DecoderServing(PagedLM):
    # The expert layers' four, the context, sparse layers whose dispatch took
    # the compact branch, and the steps by the sampler's branch.
    COLUMNS = (*EXPERT_COLUMNS, CONTEXT_COLUMN, COMPACT_COLUMN, *SAMPLE_COLUMNS)

    value_scale = 1.0   # a factor on the values before they are cached; 1: none

    def _arch(self, a: dict) -> dict:
        """The model's config file under the key names this constructor
        reads: as it is; a sibling whose published names differ translates."""
        return a

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = self._arch(read_config_file(cfg))
        self.dtype = jnp.dtype(cfg.dtype)
        for key, want in (("attention_bias", False),
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
        self.tied = bool(a.get("tie_word_embeddings", False))
        kv_full = int(a["num_key_value_heads"])
        # -- the share --------------------------------------------------------
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.n_experts])
        idx, of = share.get("attention_heads", [0, 1])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.vocab_full])
        self.heads_full, self.kv_full = heads, kv_full
        self.heads, self.h_first, self.kv, self.kv_first = head_share(  # held, by layer
            cfg.name, idx, of, heads, kv_full)
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
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self._serve_options(cfg, a)

    # -- params ---------------------------------------------------------------
    def _gains(self):
        yield ("norm_f",), (self.d,)
        for i in range(self.n_layers):
            yield (f"layer{i}", "norm1"), (self.d,)
            yield (f"layer{i}", "norm2"), (self.d,)

    def _tensors(self):
        """(path, shape held here, full shape, start, role, fan-in) of every
        matrix, in a fixed order."""
        d, s = self.d, self.scales
        yield from self._vocab_tensors()
        for i in range(self.n_layers):
            L = f"layer{i}"
            hf, h, h0 = self.heads_full[i], self.heads[i], self.h_first[i]
            kv, dk, dv = self._heads(i)
            kvf = kv * (self.kv_full // self.kv)   # of the layer whole
            yield ((L, "wq"), (d, h, dk), (d, hf, dk), (0, h0, 0), s["qk"], d)
            yield ((L, "wk"), (d, kv, dk), (d, kvf, dk), (0, self.kv_first, 0), s["qk"], d)
            yield ((L, "wv"), (d, kv, dv), (d, kvf, dv), (0, self.kv_first, 0), s["v"], d)
            if self.gated:
                yield ((L, "wg"), (d, h), (d, hf), (0, h0), s["gate"], d)
            yield ((L, "wo"), (h, dv, d), (hf, dv, d), (h0, 0, 0), s["o"], hf * dv)
            if self.mlp_types[i] == "dense":
                f = self.dense_width
                for name in ("w_gate", "w_up"):
                    yield ((L, name), (d, f), (d, f), (0, 0), s["ffn_in"], d)
                yield ((L, "w_down"), (f, d), (f, d), (0, 0), s["ffn_out"], f)
                continue
            yield from self._sparse_tensors(L)

    def _sparse_tensors(self, L: str):
        """A sparse layer's matrices: the router, the held experts, the
        shared expert."""
        d, s = self.d, self.scales
        e, ec, e0, f, fs = (self.n_experts, self.e_count, self.e_first,
                            self.expert_width, self.shared_width)
        yield ((L, "router"), (d, e), (d, e), (0, 0), s["router"], d)
        for name in ("e_gate", "e_up"):
            yield ((L, name), (ec, d, f), (e, d, f), (e0, 0, 0), s["ffn_in"], d)
        yield ((L, "e_down"), (ec, f, d), (e, f, d), (e0, 0, 0), s["ffn_out"], f)
        if fs:
            for name in ("s_gate", "s_up"):
                yield ((L, name), (d, fs), (d, fs), (0, 0), s["ffn_in"], d)
            yield ((L, "s_down"), (fs, d), (fs, d), (0, 0), s["ffn_out"], fs)

    # -- shapes -----------------------------------------------------------------
    def kv_plan(self, slots: int, page_tokens: int, pages: int = 0, **geometry) -> CachePlan:
        """And one ring a slot, of ``window`` positions, where a layer has a window."""
        return super().kv_plan(slots, page_tokens, pages, **geometry,
                               ring_tokens=self.window if self.win_layers else 0)

    def _cache_signature(self, slots: int, pages: int, page_tokens: int) -> dict:
        S = jax.ShapeDtypeStruct
        page = S(self._page_shape(pages, page_tokens), self.dtype)
        ring = S((slots + 1, self.window, self.kv, self.hd), self.dtype)
        n_full, n_win = len(self.full_layers), len(self.win_layers)
        return {   # pages of the full layers, rings of the window layers
            "kf": pool([page] * n_full), "vf": pool([page] * n_full),
            "kw": rings([ring] * n_win), "vw": rings([ring] * n_win),
            "ring": S((slots,), jnp.int32),   # a lane: the slot's ring
        }

    # -- device math --------------------------------------------------------------
    @scoped("proj")
    def _qkv(self, lp: dict, i: int, u: jax.Array, pos: jax.Array):
        """``u`` (T, d) normed stream at positions ``pos`` (T,) -> rotated q
        (T, H, dk), rotated k (T, KV, dk), v (T, KV, dv) times ``value_scale``,
        the gate (T, H) or None."""
        dt = self.dtype
        inv, factor, dim = self.rope[self.layer_types[i]]
        q = jnp.einsum("td,dhk->thk", u, lp["wq"],
                       preferred_element_type=jnp.float32).astype(dt)
        k = jnp.einsum("td,dhk->thk", u, lp["wk"],
                       preferred_element_type=jnp.float32).astype(dt)
        v = jnp.einsum("td,dhk->thk", u, lp["wv"], preferred_element_type=jnp.float32)
        if self.value_scale != 1.0:
            v = v * jnp.float32(self.value_scale)
        v = v.astype(dt)
        gate = jax.nn.sigmoid(_mm(u, lp["wg"])) if self.gated else None
        return (apply_rope(q, pos, inv, factor, dim),
                apply_rope(k, pos, inv, factor, dim), v, gate)

    @scoped("proj")
    def _attn_out(self, lp, o, gate):
        """o (T, H, dv) float32 -> (T, d): gated by head, through W_o."""
        if gate is not None:
            o = o * gate[..., None]
        return jnp.einsum("thk,hkd->td", o.astype(self.dtype), lp["wo"],
                          preferred_element_type=jnp.float32)

    scoring = "softmax"   # the router's scores: a softmax, or a sigmoid of each logit alone

    def _ffn(self, lp, i, u, live):
        """(T, d) -> ((T, d) float32, the expert layer's counts or None). A
        sparse layer: the router in float32, ``topk_route`` by ``scoring``
        (with the layer's selection bias ``e_bias`` where it has one), the
        held experts' part, and the shared expert where there is one."""
        if self.mlp_types[i] == "dense":
            return self._swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        with jax.named_scope("moe_layer"):
            r = router_logits(u, lp["router"])
            if self.softcap > 0:
                with jax.named_scope("moe_route"):
                    r = self.softcap * jnp.tanh(r / self.softcap)
            w, e = topk_route(r, self.top_k, normalize=self.norm_topk, scale=self.route_scale,
                              scoring=self.scoring, select_bias=lp.get("e_bias"))
            y, stats = held_experts_swiglu(u, w, e, self.e_first, lp["e_gate"],
                                           lp["e_up"], lp["e_down"], live=live,
                                           of=self.n_experts)
        if not self.shared_width:
            return y, stats
        return y + self._swiglu(u, lp["s_gate"], lp["s_up"], lp["s_down"]), stats

    def _prefill_window(self, q, k, v, ring_k, ring_v, qpos, rpos, kpos, ok, sink=None):
        """A window layer's attention of one launch, tile by tile: q (K, T,
        H, dk) at positions ``qpos`` (K, T); k (C, KV, dk) and v (C, KV, dv),
        the launch's own rows at positions ``kpos`` (``ok`` (K, C) where a row
        is a live token of the tile's prompt); ``ring_k``/``ring_v`` (K, W, KV,
        width), what each tile's ring held BEFORE the launch, at positions
        ``rpos`` (K, W), negative where nothing was written. A tile sees its
        ring and the W rows before its own last: a query sees no further
        back, so the scores are (T, 2W + T) a head. ``sink``: ``_attend``'s."""
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
                            jnp.concatenate([ring_v, near(v, 0)], axis=1), mask, sink)

    # -- what a launch works out once ------------------------------------------------
    def _tiles(self, launch: Any, chunk: int) -> dict:
        t = PagedLM._tiles(launch, chunk)
        return {**t, "rings": launch["ring"][t["piece"]]}   # by tile

    def _prefill_plan(self, state, launch, t: dict) -> dict:
        """And the window layers' ring places: where each row lands, and what
        each tile's ring held before the launch."""
        m = super()._prefill_plan(state, launch, t)
        T, W = t["T"], max(self.window, 1)   # W = 1: no window layer reads it
        piece, has, valid, cpos = t["piece"], t["has"], t["valid"], t["cpos"]
        # Of a piece's positions that fall on one ring place only the last
        # lands; the rest, and padding, go to ring 0.
        w_ring = jnp.where(valid & (cpos >= jnp.repeat(t["end"], T) - W),
                           jnp.repeat(t["rings"], T), 0)
        roff = cpos % W
        # What a ring held before this launch: place r has the newest
        # position <= start - 1 that is r modulo W.
        before = (launch["start"][piece] - 1)[:, None]
        rpos = jnp.where(has[:, None], before - ((before - jnp.arange(W)[None, :]) % W), -1)
        own = valid[None, :] & (t["of_piece"][None, :] == piece[:, None]) & has[:, None]
        return {**m, "w_ring": w_ring, "roff": roff, "rpos": rpos, "own": own,
                "lanes": {"ring": launch["ring"]}}

    def _step_plan(self, state, live, pos) -> dict:
        m = super()._step_plan(state, live, pos)
        W = max(self.window, 1)
        w_ring = jnp.where(live, state["ring"], 0)
        roff = pos % W
        # Ring place r holds the newest position <= pos that is r modulo W.
        rpos = pos[:, None] - ((pos[:, None] - jnp.arange(W)[None, :]) % W)
        return {**m, "w_ring": w_ring, "roff": roff, "mask_win": (rpos >= 0)[:, None, :]}

    # -- the layer ---------------------------------------------------------------------
    def _attend_full(self, q, k, v, kp, vp, m: dict):
        """A full layer's attention in either phase: the launch's rows into
        the pages, then the tiles' walks or the lanes' decode -> (o as ``q``
        lies, the two pools)."""
        t = m["t"]
        with jax.named_scope(_attn_scope(t)):
            qt = None if t is None else q.reshape((t["K"], t["T"]) + q.shape[1:])
            kp = self._write_pages(kp, m["w_page"], m["off"], k)
            vp = self._write_pages(vp, m["w_page"], m["off"], v)
            if t is None:
                return self._decode_full(q, kp, vp, m["bt"], m["pos"]), kp, vp
            return self._prefill_full_tiles(qt, (kp, vp), t).reshape(q.shape), kp, vp

    def _attend_window(self, q, k, v, rk, rv, m: dict):
        """A window layer's attention in either phase -> (o (T, H, dv), the
        two rings). A step writes its row and reads its ring (a free lane
        reads ring 0, which every free lane writes: its result is discarded);
        a launch reads what the rings held before it and itself, then writes.
        A ring is (slots + 1, W, KV, width)."""
        t, w_ring, roff = m["t"], m["w_ring"], m["roff"]

        @scoped("cache_write")
        def put(ring, rows):
            return ring.at[w_ring, roff].set(rows)

        with jax.named_scope(_attn_scope(t)):
            if t is None:
                rk, rv = put(rk, k), put(rv, v)
                return self._attend(q[:, None], jnp.take(rk, w_ring, axis=0),
                                    jnp.take(rv, w_ring, axis=0), m["mask_win"])[:, 0], rk, rv
            o = self._prefill_window(q.reshape((t["K"], t["T"]) + q.shape[1:]), k, v,
                                     jnp.take(rk, t["rings"], axis=0),
                                     jnp.take(rv, t["rings"], axis=0),
                                     t["qpos"], m["rpos"], m["pos"], m["own"])
            rk, rv = put(rk, k), put(rv, v)
            return o.reshape(q.shape[:-1] + o.shape[-1:]), rk, rv

    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        q, k, v, gate = self._qkv(lp, i, rms_norm(x, lp["norm1"], self.eps), m["pos"])
        if self.layer_types[i] == "full_attention":
            j = self.full_layers.index(i)
            o, c["kf"][j], c["vf"][j] = self._attend_full(q, k, v, c["kf"][j], c["vf"][j], m)
        else:
            j = self.win_layers.index(i)
            o, c["kw"][j], c["vw"][j] = self._attend_window(q, k, v, c["kw"][j], c["vw"][j], m)
        x = x + self._attn_out(lp, o, gate).astype(self.dtype)
        y, st = self._ffn(lp, i, rms_norm(x, lp["norm2"], self.eps), m["live"])
        return x + y.astype(self.dtype), st


def create(cfg: ModelConfig) -> DecoderServing:
    return DecoderServing(cfg)
