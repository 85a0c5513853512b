"""A language model whose layers are single mixers chosen by a pattern string,
built from a published ``config.json`` (ISSUE 32) and served through the
generation engine with paged KV AND a recurrent state a slot.

Nothing here knows a model's name. The architecture is read, under the
published key names, from the JSON file that ``options.config_file`` names.
Layer ``i`` is ONE mixer behind one norm, ``x <- x + mixer(RMSNorm(x))``, of
the kind ``hybrid_override_pattern[i]`` says:

- ``M``, a Mamba-2 state-space layer (``mamba_num_heads`` H of
  ``mamba_head_dim`` P, ``n_groups`` G, ``ssm_state_size`` N, ``conv_kernel``):
  ``[z | xBC | dt] = u W_in``; a depthwise causal convolution and SiLU over
  ``xBC``; per head ``S_t = a_t S_{t-1} + delta_t x_t (x) B_t``, ``y_t = S_t C_t +
  D x_t`` with ``delta = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log)
  delta)``; ``y <- RMSNorm_group(y silu(z))``; out ``= y W_out``.
- ``*``, attention: grouped KV heads, causal, NO rotary embedding, no bias.
- ``E``, a routed expert layer in a latent (``tpuserve.ops.moe``): sigmoid
  scores, the ``num_experts_per_tok`` largest of score + selection bias,
  weights over their own sum times ``routed_scaling_factor``; ``l = u W_a``
  (``moe_latent_size`` wide), expert e ``relu(l W1_e)^2 W2_e``, the weighted
  sum back through ``W_b``; plus the shared expert ``relu(u V1)^2 V2`` on ``u``.

THE SHARE (``share`` in the file; without it the model is whole):
``experts_held = [first, count]`` of ``n_routed_experts``;
``attention_heads = [index, of]`` (query heads ``index`` of ``of`` equal
parts; where the chips outnumber the KV heads, ONE KV head that a neighbour
holds too); ``mamba_heads = [index, of]`` (heads AND groups ``index`` of
``of``, ``W_in``'s columns, the convolution's channels and ``W_out``'s rows
with them; the gated norm is over a group, so a share of whole groups is
exact); ``vocab_rows = [first, count]``. The router, both latent projections,
the shared expert and every norm are whole. On one chip the layers run
without their exchange.

THE CACHE. An attention layer keeps K and V in pages of the engine's ledger,
as ``decoder`` does (``paged_lm.PagedLM``). A Mamba-2 layer keeps, A SLOT, a
float32 state (H, P, N) and the last ``conv_kernel - 1`` rows of its
convolution's input, the same size at token 10 and at token 16,000. The
blocks are addressed by SLOT (``ssm[l][slot]``, ``conv[l][slot]``): a slot is
the one thing a request owns from admission to retirement, a state is never
shared and never grows, so a second ledger would hand out what the arena
already does. A request's FIRST piece starts from zeros whatever the slot
held; a later piece from what the slot holds; within a launch the tiles of
one piece pass the state on and a tile of another slot does not see it;
padded rows leave it as it was (``delta = 0`` there: ``a = 1``, no input); a
decode step leaves the state of a lane that is not live untouched.

Prefill computes the recurrence by chunks (the quadratic form inside a
tile, the state passed between a piece's tiles by a ``lax.scan``), under
``jax.named_scope("ssm_scan")``; a decode step is one application, under
``jax.named_scope("ssm_update")``. Chunked prefill followed by decode is the
same function as the recurrence run token by token (tests/test_hybrid.py).

NOT SERVED: a multi-token-prediction module (``num_nextn_predict_layers``):
the engine's step yields one token a lane, and the main stack's logits do
not depend on it. Requests, weights and the served log-probabilities are
``decoder``'s (``paged_lm``).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models.paged_lm import (LOGPROBS, PagedLM, _mm,  # noqa: F401
                                      head_share, read_config_file, rms_norm)
from tpuserve.obs import GEN_PHASES
from tpuserve.ops.moe import held_experts, relu2, topk_route

# Standard deviations of the drawn tensors, by role (``weight_scales`` in the
# config file overrides any). The state's two sides (B, C) and the query/key
# maps are drawn wider, so that what the state and the attention carry is
# decisive and a check against a reference is not blunt.
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
    "ffn_out": 1.0, "expert_out": 1.0, "router": 1.0, "router_bias": 0.02,
    "ssm_in": 1.0, "ssm_bc": 2.0, "ssm_dt": 1.0, "ssm_out": 1.0, "conv": 1.0,
    "conv_bias": 0.1, "ssm_d": 0.1,
}


def softplus_inverse(y: float) -> float:
    return y + math.log(-math.expm1(-y))


class HybridServing(PagedLM):
    # Device-side sums a phase: the expert layer's four and the context, as
    # ``decoder`` has them, then live tokens through a scan layer, slot states
    # read and written, (prefill) pieces that started from zeros / from a
    # stored state, and expert layers whose dispatch took the compact branch.
    ACC = 10

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        for key, want in (("attention_bias", False), ("tie_word_embeddings", False),
                          ("mamba_proj_bias", False), ("mlp_bias", False),
                          ("use_bias", False), ("n_group", 1), ("topk_group", 1),
                          ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                          ("n_shared_experts", 1)):
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        self.d = int(a["hidden_size"])
        self.pattern = str(a["hybrid_override_pattern"])
        self.n_layers = int(a.get("num_hidden_layers", len(self.pattern)))
        if len(self.pattern) != self.n_layers or set(self.pattern) - set("M*E"):
            raise ValueError(f"{cfg.name}: hybrid_override_pattern {self.pattern!r} must have "
                             f"num_hidden_layers = {self.n_layers} letters of M, * and E")
        self.eps = float(a.get("layer_norm_epsilon", 1e-5))
        self.m_layers = [i for i, c in enumerate(self.pattern) if c == "M"]
        self.a_layers = [i for i, c in enumerate(self.pattern) if c == "*"]
        self.e_layers = [i for i, c in enumerate(self.pattern) if c == "E"]
        share = a.get("share", {})
        # -- Mamba-2 ------------------------------------------------------------
        self.mh_full, self.mp = int(a["mamba_num_heads"]), int(a["mamba_head_dim"])
        self.mg_full, self.mn = int(a["n_groups"]), int(a["ssm_state_size"])
        self.conv_k = int(a.get("conv_kernel", 4))
        self.conv_bias = bool(a.get("use_conv_bias", True))
        m_idx, m_of = share.get("mamba_heads", [0, 1])
        if self.mh_full % self.mg_full or self.mg_full % m_of:
            raise ValueError(f"{cfg.name}: share.mamba_heads = [{m_idx}, {m_of}] does not "
                             f"divide {self.mg_full} groups of {self.mh_full} heads")
        self.mh, self.mg = self.mh_full // m_of, self.mg_full // m_of
        self.mh_first, self.mg_first = m_idx * self.mh, m_idx * self.mg
        self.conv_ch = self.mh * self.mp + 2 * self.mg * self.mn
        self.dt_range = (float(a.get("time_step_min", 0.001)),
                         float(a.get("time_step_max", 0.1)))
        # -- attention ------------------------------------------------------------
        self.hd = int(a.get("head_dim") or self.d // int(a["num_attention_heads"]))
        self.heads_full, self.kv_full = int(a["num_attention_heads"]), \
            int(a["num_key_value_heads"])
        idx, of = share.get("attention_heads", [0, 1])
        (self.heads,), (self.h_first,), self.kv, self.kv_first = head_share(
            cfg.name, idx, of, [self.heads_full], self.kv_full)
        # -- the expert layer -------------------------------------------------------
        self.n_experts = int(a.get("n_routed_experts", 0))
        self.top_k = int(a.get("num_experts_per_tok", 0))
        self.expert_width = int(a.get("moe_intermediate_size", 0))
        self.latent = int(a.get("moe_latent_size") or self.d)
        self.shared_width = int(a.get("moe_shared_expert_intermediate_size", 0))
        self.norm_topk = bool(a.get("norm_topk_prob", True))
        self.route_scale = float(a.get("routed_scaling_factor", 1.0))
        self.e_first, self.e_count = share.get("experts_held", [0, self.n_experts])
        self.vocab_full = int(a["vocab_size"])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.vocab_full])
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self._serve_options(cfg, a)

    # -- params ---------------------------------------------------------------
    def _gains(self):
        yield ("norm_f",), (self.d,)
        for i in range(self.n_layers):
            yield (f"layer{i}", "norm"), (self.d,)
        for i in self.m_layers:
            yield (f"layer{i}", "gate_norm"), (self.mh, self.mp)

    def _tensors(self):
        """(path, shape held here, full shape, start, role, fan-in) of every
        matrix, in a fixed order. A Mamba-2 layer's in-projection is drawn in
        its five parts (z, x, B, C, dt), each a tensor of its own, so that a
        share is a slice of each; ``draw_params`` joins them into ``w_in``."""
        d, s = self.d, self.scales
        yield (("embed",), (self.vocab, d), (self.vocab_full, d), (self.v_first, 0),
               s["embed"], 1)
        yield (("head",), (d, self.vocab), (d, self.vocab_full), (0, self.v_first),
               s["head"], d)
        hf, h, h0, p = self.mh_full, self.mh, self.mh_first, self.mp
        gf, g, g0, n, k = self.mg_full, self.mg, self.mg_first, self.mn, self.conv_k
        for i in self.m_layers:
            L = f"layer{i}"
            for part, scale in (("z", s["ssm_in"]), ("x", s["ssm_in"])):
                yield ((L, f"in_{part}"), (d, h, p), (d, hf, p), (0, h0, 0), scale, d)
            for part in ("B", "C"):
                yield ((L, f"in_{part}"), (d, g, n), (d, gf, n), (0, g0, 0), s["ssm_bc"], d)
            yield ((L, "in_dt"), (d, h), (d, hf), (0, h0), s["ssm_dt"], d)
            yield ((L, "conv_x"), (k, h, p), (k, hf, p), (0, h0, 0), s["conv"], k)
            yield ((L, "conv_bias_x"), (h, p), (hf, p), (h0, 0), s["conv_bias"], 1)
            for part in ("B", "C"):
                yield ((L, f"conv_{part}"), (k, g, n), (k, gf, n), (0, g0, 0), s["conv"], k)
                yield ((L, f"conv_bias_{part}"), (g, n), (gf, n), (g0, 0), s["conv_bias"], 1)
            yield ((L, "w_out"), (h, p, d), (hf, p, d), (h0, 0, 0), s["ssm_out"], hf * p)
        for i in self.a_layers:
            L, hd = f"layer{i}", self.hd
            yield ((L, "wq"), (d, self.heads, hd), (d, self.heads_full, hd),
                   (0, self.h_first, 0), s["qk"], d)
            for name, scale in (("wk", s["qk"]), ("wv", s["v"])):
                yield ((L, name), (d, self.kv, hd), (d, self.kv_full, hd),
                       (0, self.kv_first, 0), scale, d)
            yield ((L, "wo"), (self.heads, hd, d), (self.heads_full, hd, d),
                   (self.h_first, 0, 0), s["o"], self.heads_full * hd)
        e, ec, e0, f, fs, lat = (self.n_experts, self.e_count, self.e_first,
                                 self.expert_width, self.shared_width, self.latent)
        for i in self.e_layers:
            L = f"layer{i}"
            yield ((L, "router"), (d, e), (d, e), (0, 0), s["router"], d)
            yield ((L, "w_a"), (d, lat), (d, lat), (0, 0), s["ffn_in"], d)
            yield ((L, "e_w1"), (ec, lat, f), (e, lat, f), (e0, 0, 0), s["ffn_in"], lat)
            yield ((L, "e_w2"), (ec, f, lat), (e, f, lat), (e0, 0, 0), s["expert_out"], f)
            yield ((L, "w_b"), (lat, d), (lat, d), (0, 0), s["ffn_out"], lat)
            yield ((L, "s_w1"), (d, fs), (d, fs), (0, 0), s["ffn_in"], d)
            yield ((L, "s_w2"), (fs, d), (fs, d), (0, 0), s["ffn_out"], fs)

    def _vectors(self):
        """(path, shape, full shape, start, low, high) of the float32 vectors
        drawn INSIDE a range (a bell over it, by the same recipe): a scan
        layer's ``dt_bias`` (softplus of it in [time_step_min, time_step_max]),
        ``A_log`` (A in [1, 16]) and ``D`` (about 1), and an expert layer's
        selection bias (small, about 0: it changes some picks)."""
        lo, hi = (softplus_inverse(v) for v in self.dt_range)
        h = ((self.mh,), (self.mh_full,), (self.mh_first,))
        d3, b3 = 3.0 * self.scales["ssm_d"], 3.0 * self.scales["router_bias"]
        for i in self.m_layers:
            yield ((f"layer{i}", "dt_bias"), *h, lo, hi)
            yield ((f"layer{i}", "A_log"), *h, 0.0, math.log(16.0))
            yield ((f"layer{i}", "D"), *h, 1.0 - d3, 1.0 + d3)
        for i in self.e_layers:
            yield ((f"layer{i}", "e_bias"), (self.n_experts,), (self.n_experts,), (0,),
                   -b3, b3)

    def draw_params(self, seed: int) -> Any:
        p = super().draw_params(seed)
        for i in self.m_layers:
            lp, flat = p[f"layer{i}"], lambda t, lead: t.reshape(t.shape[:lead] + (-1,))
            lp["w_in"] = jnp.concatenate(
                [flat(lp.pop(f"in_{part}"), 1) for part in ("z", "x", "B", "C", "dt")], axis=1)
            lp["conv_w"] = jnp.concatenate(
                [flat(lp.pop(f"conv_{part}"), 1) for part in ("x", "B", "C")], axis=1)
            bias = jnp.concatenate(
                [flat(lp.pop(f"conv_bias_{part}"), 0) for part in ("x", "B", "C")], axis=0)
            lp["conv_b"] = bias if self.conv_bias else jnp.zeros_like(bias)
        return p

    # -- shapes -----------------------------------------------------------------
    kv_slot_state = ("ssm", "conv")  # the leaves that are a block a slot

    def kv_page_signature(self, slots: int, pages: int, page_tokens: int) -> Any:
        S = jax.ShapeDtypeStruct
        page = S((self.kv, pages, page_tokens, self.hd), self.dtype)
        return {
            "kf": [page for _ in self.a_layers], "vf": [page for _ in self.a_layers],
            "ssm": [S((slots, self.mh, self.mp, self.mn), jnp.float32)
                    for _ in self.m_layers],
            "conv": [S((slots, self.conv_k - 1, self.conv_ch), self.dtype)
                     for _ in self.m_layers],
            **self._lane_signature(slots, page_tokens),
        }

    # -- device math --------------------------------------------------------------
    def _split_in(self, lp: dict, u: jax.Array):
        """``u`` (T, d) -> z (T, H, P), xBC (T, channels) before the
        convolution, dt (T, H) in float32."""
        hp = self.mh * self.mp
        zxd = _mm(u, lp["w_in"])
        z = zxd[:, :hp].reshape(-1, self.mh, self.mp)
        return z, zxd[:, hp:hp + self.conv_ch].astype(self.dtype), zxd[:, hp + self.conv_ch:]

    def _split_xbc(self, xbc: jax.Array):
        """Convolved (..., channels) float32 -> x (..., H, P), B and C (..., G, N),
        after the SiLU, in the served type."""
        hp, gn = self.mh * self.mp, self.mg * self.mn
        a = jax.nn.silu(xbc).astype(self.dtype)
        lead = a.shape[:-1]
        return (a[..., :hp].reshape(lead + (self.mh, self.mp)),
                a[..., hp:hp + gn].reshape(lead + (self.mg, self.mn)),
                a[..., hp + gn:].reshape(lead + (self.mg, self.mn)))

    def _decay(self, lp: dict, dt: jax.Array, live: jax.Array):
        """dt (..., H) float32, live (...,) -> (delta, log a), both (..., H)
        float32, zero where a row is not live: its state passes unchanged."""
        delta = jnp.where(live[..., None], jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
        return delta, -jnp.exp(lp["A_log"]) * delta

    def _gated_norm(self, lp: dict, y: jax.Array, z: jax.Array) -> jax.Array:
        """y (T, H, P) float32 gated by silu(z) and normed over each GROUP of
        heads (gate before norm) -> (T, H, P) in the served type."""
        t = y.shape[0]
        g = (y * jax.nn.silu(z)).reshape(t, self.mg, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + self.eps)
        g = g.reshape(t, self.mh, self.mp) * lp["gate_norm"].astype(jnp.float32)
        return g.astype(self.dtype)

    def _out_proj(self, lp: dict, g: jax.Array) -> jax.Array:
        return jnp.einsum("thp,hpd->td", g, lp["w_out"], preferred_element_type=jnp.float32)

    def _scan_tiles(self, lp: dict, xbc, dt, t: dict, s0, c0):
        """The chunked scan of one launch: ``xbc`` (C, channels) and ``dt`` (C,
        H) of the packed rows; ``s0`` (K, H, P, N) float32 and ``c0`` (K, k-1,
        channels) what each PIECE starts from. -> y (C, H, P) float32 and,
        by piece, the state and the convolution's rows it ends with."""
        K, T, kc = t["K"], t["T"], self.conv_k - 1
        if T < kc:
            raise ValueError(f"{self.name}: a tile of {T} rows is shorter than the "
                             f"convolution's {kc} stored rows")
        H, P, G, N = self.mh, self.mp, self.mg, self.mn
        piece, tiles = t["piece"], t["tiles"]
        opens = tiles == t["first_tile"][piece]          # a tile that opens its piece
        live = t["valid"].reshape(K, T)
        xt = xbc.reshape(K, T, -1)
        # The convolution: a tile's rows behind the k-1 rows before them, the
        # piece's stored rows for the tile that opens it, else the tile before.
        prev = jnp.where(opens[:, None, None], c0[piece],
                         jnp.roll(xt[:, T - kc:], 1, axis=0))
        seq = jnp.concatenate([prev, xt], axis=1)                         # (K, kc + T, ch)
        w = lp["conv_w"].astype(jnp.float32)
        conv = lp["conv_b"].astype(jnp.float32) + sum(
            seq[:, j:j + T].astype(jnp.float32) * w[j] for j in range(kc + 1))
        x, B, C = self._split_xbc(conv)
        delta, la = self._decay(lp, dt.reshape(K, T, H), live)
        cum = jnp.cumsum(la, axis=1)                                      # (K, T, H)
        # Inside a tile, the quadratic form: row t reads row s <= t through
        # exp(cum_t - cum_s) delta_s (C_t . B_s).
        cb = jnp.einsum("ktgn,ksgn->kgts", C, B, preferred_element_type=jnp.float32)
        diff = cum.transpose(0, 2, 1)[:, :, :, None] - cum.transpose(0, 2, 1)[:, :, None, :]
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        m = jnp.exp(jnp.where(causal, diff, -jnp.inf)) \
            * jnp.repeat(cb, H // G, axis=1) * delta.transpose(0, 2, 1)[:, :, None, :]
        y = jnp.einsum("khts,kshp->kthp", m.astype(self.dtype), x,
                       preferred_element_type=jnp.float32)
        # What a tile adds to the state, and how much of what came in is left.
        to_end = jnp.exp(cum[:, -1:, :] - cum) * delta                    # (K, T, H)
        xg = (x * to_end[..., None]).astype(self.dtype).reshape(K, T, G, H // G, P)
        add = jnp.einsum("ksgjp,ksgn->kgjpn", xg, B,
                         preferred_element_type=jnp.float32).reshape(K, H, P, N)
        keep = jnp.exp(cum[:, -1, :])                                     # (K, H)

        def pass_on(carry, tile):
            opens_j, start_j, keep_j, add_j = tile
            s_in = jnp.where(opens_j, start_j, carry)
            s_out = keep_j[:, None, None] * s_in + add_j
            return s_out, (s_in, s_out)

        _, (s_in, s_out) = jax.lax.scan(
            pass_on, jnp.zeros((H, P, N), jnp.float32), (opens, s0[piece], keep, add))
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "kgjpn,ktgn->ktgjp", s_in.astype(self.dtype).reshape(K, G, H // G, P, N), C,
            preferred_element_type=jnp.float32).reshape(K, T, H, P)
        y = y + lp["D"][:, None] * x.astype(jnp.float32)
        # By piece: its last tile's state, and the k-1 rows that end at its
        # last live row (a piece shorter than that keeps rows it came with).
        last_tile = jnp.clip(t["first_tile"] + t["n_tiles"] - 1, 0, K - 1)
        n_last = jnp.sum(live[last_tile], axis=1)
        tail = jnp.take_along_axis(
            seq[last_tile], (n_last[:, None] + jnp.arange(kc)[None, :])[:, :, None], axis=1)
        return y.reshape(K * T, H, P), s_out[last_tile], tail

    def _mamba_prefill(self, lp, u, t, ssm, conv, slot, start, length):
        """One Mamba-2 layer of a launch. The scope ``ssm_scan`` is the scan
        alone, from the convolution to the gated norm: the two projections
        are outside it."""
        z, xbc, dt = self._split_in(lp, u)
        with jax.named_scope("ssm_scan"):
            fresh = (start == 0)[:, None, None]
            at = jnp.minimum(slot, ssm.shape[0] - 1)
            s0 = jnp.where(fresh[..., None], 0.0, ssm[at].astype(jnp.float32))
            c0 = jnp.where(fresh, jnp.zeros((), conv.dtype), conv[at])
            y, s_end, c_end = self._scan_tiles(lp, xbc, dt, t, s0, c0)
            g = self._gated_norm(lp, y, z)
            # A piece of no tokens writes nothing: its slot is out of range.
            to = jnp.where(length > 0, slot, ssm.shape[0])
            ssm = ssm.at[to].set(s_end.astype(ssm.dtype), mode="drop")
            conv = conv.at[to].set(c_end.astype(conv.dtype), mode="drop")
        return self._out_proj(lp, g), ssm, conv

    def _mamba_step(self, lp, u, live, ssm, conv):
        """One application of the recurrence for every lane: the state of a
        lane that is not live stays as it was. The scope ``ssm_update`` is the
        whole mixer, from the in-projection to the out-projection."""
        with jax.named_scope("ssm_update"):
            z, xbc, dt = self._split_in(lp, u)
            seq = jnp.concatenate([conv, xbc[:, None]], axis=1)          # (b, k, ch)
            w = lp["conv_w"].astype(jnp.float32)
            x, B, C = self._split_xbc(lp["conv_b"].astype(jnp.float32) + jnp.sum(
                seq.astype(jnp.float32) * w[None], axis=1))
            delta, la = self._decay(lp, dt, live)
            rep = self.mh // self.mg
            Bh = jnp.repeat(B.astype(jnp.float32), rep, axis=1)          # (b, H, N)
            Ch = jnp.repeat(C.astype(jnp.float32), rep, axis=1)
            xf = x.astype(jnp.float32)
            s = jnp.exp(la)[..., None, None] * ssm.astype(jnp.float32) \
                + (delta[..., None] * xf)[..., None] * Bh[:, :, None, :]
            y = jnp.sum(s * Ch[:, :, None, :], axis=-1) + lp["D"][:, None] * xf
            out = self._out_proj(lp, self._gated_norm(lp, y, z))
            keep = live[:, None, None]
            new_ssm = jnp.where(keep[..., None], s.astype(ssm.dtype), ssm)
            new_conv = jnp.where(keep, seq[:, 1:], conv)
        return out, new_ssm, new_conv

    def _qkv(self, lp: dict, u: jax.Array):
        return tuple(jnp.einsum("td,dhk->thk", u, lp[w],
                                preferred_element_type=jnp.float32).astype(self.dtype)
                     for w in ("wq", "wk", "wv"))

    def _attn_out(self, lp, o):
        return jnp.einsum("thk,hkd->td", o.astype(self.dtype), lp["wo"],
                          preferred_element_type=jnp.float32)

    def _relu2(self, u, w1, w2):
        return _mm(relu2(_mm(u, w1)).astype(self.dtype), w2)

    def _experts(self, lp, u, live):
        """(T, d) -> ((T, d) float32, the expert layer's counts)."""
        r = jnp.matmul(u.astype(jnp.float32), lp["router"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        w, e = topk_route(r, self.top_k, normalize=self.norm_topk, scale=self.route_scale,
                          scoring="sigmoid", select_bias=lp["e_bias"])
        lat = _mm(u, lp["w_a"]).astype(self.dtype)
        y, stats = held_experts(lat, w, e, self.e_first, (lp["e_w1"],), lp["e_w2"], relu2,
                                live=live, of=self.n_experts)
        return _mm(y.astype(self.dtype), lp["w_b"]) \
            + self._relu2(u, lp["s_w1"], lp["s_w2"]), stats

    def _accumulate(self, acc, phase: int, stats_list, context, tokens, rows,
                    zero=0, carried=0):
        n_m = len(self.m_layers)
        row = jnp.stack([jnp.asarray(v, jnp.int32) for v in (
            *self._expert_sums(stats_list), context,
            tokens * n_m, rows * n_m, zero, carried,
            sum(st["compact"] for st in stats_list))])
        return acc.at[phase].add(row.astype(jnp.uint32))

    # -- prefill ------------------------------------------------------------------
    def prefill_chunk(self, params: Any, state: Any, launch: Any, *, chunk: int) -> Any:
        """One launch of ``pack_prefill``: piece j is tokens [start[j],
        start[j] + length[j]) of the prompt in slot[j], causal within the
        piece and over what earlier launches left in that slot's pages and
        state."""
        t = self._tiles(launch, chunk)
        K, T = t["K"], t["T"]
        slot, start, length = launch["slot"], launch["start"], launch["length"]
        valid, cpos = t["valid"], t["cpos"]
        x = jnp.take(params["embed"], launch["ids"], axis=0)
        if self.a_layers:
            w_page, off = self._page_of(t, state["kf"][0].shape[2], state["bt"].shape[1])
        kf, vf, ssm, conv = (list(state[k]) for k in ("kf", "vf", "ssm", "conv"))
        stats = []
        for i, kind in enumerate(self.pattern):
            lp = params[f"layer{i}"]
            u = rms_norm(x, lp["norm"], self.eps)
            if kind == "M":
                j = self.m_layers.index(i)
                y, ssm[j], conv[j] = self._mamba_prefill(
                    lp, u, t, ssm[j], conv[j], slot, start, length)
            elif kind == "*":
                j = self.a_layers.index(i)
                q, k, v = self._qkv(lp, u)
                kf[j] = self._write_pages(kf[j], w_page, off, k)
                vf[j] = self._write_pages(vf[j], w_page, off, v)
                o = self._prefill_full_tiles(q.reshape((K, T) + q.shape[1:]), kf[j], vf[j], t)
                y = self._attn_out(lp, o.reshape(q.shape))
            else:
                y, st = self._experts(lp, u, valid)
                stats.append(st)
            x = x + y.astype(self.dtype)
        has = length > 0
        new = dict(state, kf=kf, vf=vf, ssm=ssm, conv=conv, acc=self._accumulate(
            state["acc"], 0, stats, jnp.sum(jnp.where(valid, cpos + 1, 0)),
            jnp.sum(valid), jnp.sum(has), jnp.sum(has & (start == 0)),
            jnp.sum(has & (start > 0))))
        return self._arm(params, state, new, launch, t, x, {})

    # -- decode -------------------------------------------------------------------
    def step(self, params: Any, state: Any) -> tuple[Any, dict]:
        live = state["armed"] & ~state["done"]
        pos = jnp.clip(state["pos"], 0, self.max_ctx - 1)
        x = jnp.take(params["embed"], state["last"], axis=0)
        if self.a_layers:
            P = state["kf"][0].shape[2]
            page_of = jnp.take_along_axis(state["bt"], (pos // P)[:, None], axis=1)[:, 0]
            w_page, off = jnp.where(live, page_of, 0), pos % P
        kf, vf, ssm, conv = (list(state[k]) for k in ("kf", "vf", "ssm", "conv"))
        stats = []
        for i, kind in enumerate(self.pattern):
            lp = params[f"layer{i}"]
            u = rms_norm(x, lp["norm"], self.eps)
            if kind == "M":
                j = self.m_layers.index(i)
                y, ssm[j], conv[j] = self._mamba_step(lp, u, live, ssm[j], conv[j])
            elif kind == "*":
                j = self.a_layers.index(i)
                q, k, v = self._qkv(lp, u)
                kf[j] = self._write_pages(kf[j], w_page, off, k)
                vf[j] = self._write_pages(vf[j], w_page, off, v)
                y = self._attn_out(lp, self._decode_full(q, kf[j], vf[j], state["bt"], pos))
            else:
                y, st = self._experts(lp, u, live)
                stats.append(st)
            x = x + y.astype(self.dtype)
        n_live = jnp.sum(live)
        acc = self._accumulate(state["acc"], 1, stats,
                               jnp.sum(jnp.where(live, pos + 1, 0)), n_live, n_live)
        return self._emit(params, state, dict(state, kf=kf, vf=vf, ssm=ssm, conv=conv),
                          x, live, pos, acc)

    # -- host side ----------------------------------------------------------------
    def bind_metrics(self, metrics: Any) -> None:
        name = self.name
        pieces = [metrics.counter(f"ssm_pieces_total{{model={name},start={start}}}")
                  for start in ("zero", "carried")]
        self._counters = [self._expert_counters(metrics, ph) + [
            metrics.counter(f"ssm_tokens_total{{model={name},phase={ph}}}"),
            metrics.counter(f"ssm_state_rows_total{{model={name},phase={ph}}}"),
        ] + (pieces if ph == "prefill" else [None, None])
            + [self._compact_counter(metrics, ph)] for ph in GEN_PHASES]


def create(cfg: ModelConfig) -> HybridServing:
    return HybridServing(cfg)
