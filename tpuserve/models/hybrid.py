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

from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models.mixers import SCAN_COLUMNS, SSM_COLUMNS, PatternMixers
from tpuserve.models.paged_lm import (COMPACT_COLUMN, CONTEXT_COLUMN,  # noqa: F401
                                      EXPERT_COLUMNS, LOGPROBS, SAMPLE_COLUMNS, PagedLM, _mm,
                                      head_share, read_config_file, rms_norm, scoped)
from tpuserve.ops.moe import held_experts, relu2, router_logits, topk_route

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


class HybridServing(PatternMixers, PagedLM):
    # The expert layer's four and the context, as ``decoder`` has them, then the
    # scan layers' four, expert layers whose dispatch took the compact branch,
    # a launch's scans by where they ran, and the steps by the sampler's branch.
    COLUMNS = (*EXPERT_COLUMNS, CONTEXT_COLUMN, *SSM_COLUMNS, COMPACT_COLUMN, *SCAN_COLUMNS,
               *SAMPLE_COLUMNS)

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        for key, want in (("attention_bias", False), ("mamba_proj_bias", False),
                          ("mlp_bias", False), ("use_bias", False),
                          ("n_group", 1), ("topk_group", 1),
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
        self._mamba_setup(
            cfg.name, heads=int(a["mamba_num_heads"]), head_dim=int(a["mamba_head_dim"]),
            groups=int(a["n_groups"]), state=int(a["ssm_state_size"]),
            conv_kernel=int(a.get("conv_kernel", 4)), conv_bias=bool(a.get("use_conv_bias", True)),
            share=share.get("mamba_heads", [0, 1]),
            dt_range=(float(a.get("time_step_min", 0.001)), float(a.get("time_step_max", 0.1))))
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
        self.tied = bool(a.get("tie_word_embeddings", False))
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self._serve_options(cfg, a)

    # -- params ---------------------------------------------------------------
    def _gains(self):
        yield ("norm_f",), (self.d,)
        for i in range(self.n_layers):
            yield (f"layer{i}", "norm"), (self.d,)
        yield from self._mamba_gains()

    def _tensors(self):
        """(path, shape held here, full shape, start, role, fan-in) of every
        matrix, in a fixed order."""
        d, s = self.d, self.scales
        yield from self._vocab_tensors()
        yield from self._mamba_tensors()
        yield from self._attention_tensors()
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
        """The scan layers' float32 vectors, and an expert layer's selection
        bias (small, about 0: it changes some picks)."""
        yield from self._mamba_vectors()
        b3 = 3.0 * self.scales["router_bias"]
        for i in self.e_layers:
            yield ((f"layer{i}", "e_bias"), (self.n_experts,), (self.n_experts,), (0,),
                   -b3, b3)

    def draw_params(self, seed: int) -> Any:
        p = super().draw_params(seed)
        self._join_mamba(p)
        return p

    # -- device math --------------------------------------------------------------
    @scoped("ffn_dense")
    def _relu2(self, u, w1, w2):
        return _mm(relu2(_mm(u, w1)).astype(self.dtype), w2)

    def _experts(self, lp, u, live):
        """(T, d) -> ((T, d) float32, the expert layer's counts)."""
        with jax.named_scope("moe_layer"):
            r = router_logits(u, lp["router"])
            w, e = topk_route(r, self.top_k, normalize=self.norm_topk, scale=self.route_scale,
                              scoring="sigmoid", select_bias=lp["e_bias"])
            with jax.named_scope("proj"):
                lat = _mm(u, lp["w_a"]).astype(self.dtype)
            y, stats = held_experts(lat, w, e, self.e_first, (lp["e_w1"],), lp["e_w2"], relu2,
                                    live=live, of=self.n_experts)
            with jax.named_scope("proj"):
                y = _mm(y.astype(self.dtype), lp["w_b"])
        return y + self._relu2(u, lp["s_w1"], lp["s_w2"]), stats

    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        u = rms_norm(x, lp["norm"], self.eps)
        if self.pattern[i] == "E":
            y, st = self._experts(lp, u, m["live"])
        else:
            y, st = self._mixer(i, lp, u, c, m), None
        return x + y.astype(self.dtype), st


def create(cfg: ModelConfig) -> HybridServing:
    return HybridServing(cfg)
