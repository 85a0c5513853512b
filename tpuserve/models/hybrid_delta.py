"""A language model whose layers are TWO sublayers each, a mixer chosen by a
list (a gated delta-rule linear-attention layer with a decay a channel, or
softmax attention without a position term under an elementwise output gate) and
then sigmoid-routed SwiGLU experts with a shared one, built from a published
``config.json`` (ISSUE 53) and served through the generation engine with paged
KV AND a recurrent state a slot: ``hybrid_ffn``'s entry points, scheduler, page
ledger and slot state, ``mla``'s expert layer.

Nothing here knows a model's name. The architecture is read, under the
published key names, from the JSON file that ``options.config_file`` names:

- Layer ``i``: ``x <- x + mixer_i(RMSNorm(x; g1_i))``, softmax attention where
  ``i`` is in ``gqa_layers`` and the delta rule elsewhere; then ``x <- x +
  experts(RMSNorm(x; g2_i))``; eps ``rms_norm_eps``; no bias but the delta
  rule's gate's.
- The delta rule (``linear_attn_config``: ``num_heads`` H of ``head_dim`` D,
  ``short_conv_kernel_size``; the decay's and the gate's projections of low rank
  ``head_dim``, ``kda_use_full_proj`` false; ``kda_allow_neg_eigval``: the step is
  2 sigmoid, in (0, 2)): ``mixers.DeltaMixer`` has the equations; the state is
  (H, D, D) float32 a layer a slot.
- Softmax attention (``num_attention_heads`` over ``num_key_value_heads`` heads of
  ``head_dim``; ``use_rope`` must be false: NO position term of any kind;
  ``use_gqa_gate``: the context times ``sigmoid(u W_g)``, elementwise by head,
  before ``W_o``): ``mixers.PlainAttention``.
- Experts (every layer: ``first_k_dense_replace`` must be 0): the router's
  float32 logits over all ``n_routed_experts``, sigmoid scores, the
  ``num_experts_per_tok`` largest of score + selection bias (the bias moves picks,
  never weights), weights over their own sum where ``norm_topk_prob`` times
  ``routed_scaling_factor``, SwiGLU experts of ``moe_intermediate_size`` and a
  shared one of ``n_shared_experts`` times that on the same rows
  (``ops/moe.py`` ``topk_route`` and ``held_experts_swiglu``, as ``mla._ffn``
  calls them).
- ``logits = RMSNorm(x; g_f) W_head``, untied unless ``tie_word_embeddings``.

THE SHARE, as ``decoder`` reads it: ``share.experts_held = [first, count]`` of the
router's experts (picks on the others add nothing; the shared expert is whole
here) and ``share.vocab_rows = [first, count]``; the mixers are whole.
Requests, weights by recipe and the served log-probabilities are ``decoder``'s
(``paged_lm``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models.mixers import DELTA_COLUMNS, SSM_COLUMNS, DeltaPatternMixers
from tpuserve.models.paged_lm import (COMPACT_COLUMN, CONTEXT_COLUMN, EXPERT_COLUMNS,
                                      SAMPLE_COLUMNS, PagedLM, read_config_file, rms_norm)
from tpuserve.ops.moe import held_experts_swiglu, topk_route

# Standard deviations of the drawn tensors, by role (``weight_scales`` in the
# config file overrides any), and the ranges of the decay's vectors: a rate
# ``A`` a head in ``decay_rate`` and a step a channel in ``decay_step`` give a
# channel's log-decay ``-A step`` a token, a half-life from under two tokens to
# over a thousand. q and k are normed, so ``kda_in`` moves v alone.
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "gate": 1.0, "ffn_in": 1.0,
    "ffn_out": 1.0, "router": 1.0, "router_bias": 0.02, "kda_in": 1.0, "kda_decay": 1.0,
    "kda_gate": 1.0, "kda_beta": 1.0, "kda_out": 1.0, "conv": 1.0, "gate_bias": 0.1,
    "decay_rate": [0.5, 4.0], "decay_step": [0.001, 0.1],
}


class RoutedExperts:
    """Routed SwiGLU experts in the layers a family names (``e_layers``) and,
    where ``shared_width``, a shared expert on the same rows: their tensors,
    their biases and the sublayer (module docstring, "Experts"). A mix-in over
    ``PagedLM`` that ``hybrid_conv`` and ``hybrid_ffn_moe`` share. The family
    sets ``n_experts``, ``top_k``, ``expert_width``, ``shared_width``,
    ``norm_topk``, ``route_scale`` and the part held, ``e_first`` and ``e_count``;
    as class attributes, where its router is not this module's: ``route_scoring``
    (what ``topk_route`` scores by) and ``route_bias`` (whether a layer has a
    selection bias: a family without one draws none)."""
    route_eps = 0.0  # added to the picks' sum under their weights, where a model's block does
    route_scoring = "sigmoid"
    route_bias = True

    def _expert_tensors(self):
        """The experts' second kernel is drawn at ``expert_out`` where the
        family's scales have that role, else at ``ffn_out`` as the shared one's."""
        d, s = self.d, self.scales
        e, ec, e0, f, fs = (self.n_experts, self.e_count, self.e_first,
                            self.expert_width, self.shared_width)
        for i in self.e_layers:
            L = f"layer{i}"
            yield ((L, "router"), (d, e), (d, e), (0, 0), s["router"], d)
            for name in ("e_gate", "e_up"):
                yield ((L, name), (ec, d, f), (e, d, f), (e0, 0, 0), s["ffn_in"], d)
            yield ((L, "e_down"), (ec, f, d), (e, f, d), (e0, 0, 0),
                   s.get("expert_out", s["ffn_out"]), f)
            if fs:
                for name in ("s_gate", "s_up"):
                    yield ((L, name), (d, fs), (d, fs), (0, 0), s["ffn_in"], d)
                yield ((L, "s_down"), (fs, d), (fs, d), (0, 0), s["ffn_out"], fs)

    def _expert_vectors(self):
        """Every router's selection bias (small, about 0: it changes some picks)."""
        if not self.route_bias:
            return
        b3 = 3.0 * self.scales["router_bias"]
        for i in self.e_layers:
            yield ((f"layer{i}", "e_bias"), (self.n_experts,), (self.n_experts,), (0,), -b3, b3)

    def _routed(self, lp, u, live):
        """(T, d) -> ((T, d) float32: the held experts' part, the expert
        layer's counts)."""
        with jax.named_scope("moe_layer"):
            # Here and not under ``moe_route``: ``moe_dispatch_step_ms`` reads that
            # scope with ``moe_dispatch`` where this block's launch is the cell's.
            r = jnp.matmul(u.astype(jnp.float32), lp["router"].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)
            w, e = topk_route(r, self.top_k, normalize=self.norm_topk, scale=self.route_scale,
                              scoring=self.route_scoring,
                              select_bias=lp["e_bias"] if self.route_bias else None,
                              eps=self.route_eps)
            return held_experts_swiglu(u, w, e, self.e_first, lp["e_gate"], lp["e_up"],
                                       lp["e_down"], live=live, of=self.n_experts)

    def _shared(self, lp, u):
        """(T, d) -> (T, d) float32: the shared expert, whole here."""
        return self._swiglu(u, lp["s_gate"], lp["s_up"], lp["s_down"])

    def _ffn(self, lp, u, live):
        """(T, d) -> ((T, d) float32: the held experts' part and the shared
        expert, the expert layer's counts)."""
        y, stats = self._routed(lp, u, live)
        if self.shared_width:
            y = y + self._shared(lp, u)
        return y, stats


class HybridDeltaServing(DeltaPatternMixers, RoutedExperts, PagedLM):
    # The expert layer's four and the context, the recurrent layers' four, the
    # compact dispatches, a step's updates by where they ran, and the steps by
    # the sampler's branch.
    COLUMNS = (*EXPERT_COLUMNS, CONTEXT_COLUMN, *SSM_COLUMNS, COMPACT_COLUMN, *DELTA_COLUMNS,
               *SAMPLE_COLUMNS)

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        for key, want in (("use_rope", False), ("kda_use_full_proj", False),
                          ("first_k_dense_replace", 0), ("attention_bias", False),
                          ("hidden_act", "silu")):
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        self.d = int(a["hidden_size"])
        self.n_layers = int(a["num_hidden_layers"])
        self.eps = float(a.get("rms_norm_eps", 1e-5))
        self.a_layers = sorted(int(i) for i in a["gqa_layers"])
        if not set(self.a_layers) <= set(range(self.n_layers)):
            raise ValueError(f"{cfg.name}: gqa_layers {self.a_layers} of {self.n_layers} layers")
        self.m_layers = [i for i in range(self.n_layers) if i not in self.a_layers]
        self.e_layers = list(range(self.n_layers))
        lin = a["linear_attn_config"]
        if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
            raise NotImplementedError(f"{cfg.name}: linear_attn_config.num_kv_heads = "
                                      f"{lin['num_kv_heads']!r}")
        self._delta_setup(heads=int(lin["num_heads"]), head_dim=int(lin["head_dim"]),
                          rank=int(lin["head_dim"]),
                          conv_kernel=int(lin.get("short_conv_kernel_size", 4)),
                          beta_scale=2.0 if a.get("kda_allow_neg_eigval", False) else 1.0)
        self.heads = self.heads_full = int(a["num_attention_heads"])
        self.kv = self.kv_full = int(a["num_key_value_heads"])
        self.h_first = self.kv_first = 0
        self.hd = int(a.get("head_dim") or self.d // self.heads)
        self.attn_gate = bool(a.get("use_gqa_gate", False))
        self.n_experts = int(a["n_routed_experts"])
        self.top_k = int(a["num_experts_per_tok"])
        self.expert_width = int(a["moe_intermediate_size"])
        self.shared_width = self.expert_width * int(a.get("n_shared_experts") or 0)
        self.norm_topk = bool(a.get("norm_topk_prob", True))
        self.route_scale = float(a.get("routed_scaling_factor") or 1.0)
        self.vocab_full = int(a["vocab_size"])
        self.tied = bool(a.get("tie_word_embeddings", False))
        share = a.get("share", {})
        if set(share) - {"experts_held", "vocab_rows"}:
            raise NotImplementedError(f"{cfg.name}: share = {share!r} (the mixers are whole here)")
        self.e_first, self.e_count = share.get("experts_held", [0, self.n_experts])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.vocab_full])
        if not 0 <= self.e_first <= self.e_first + self.e_count <= self.n_experts:
            raise ValueError(f"{cfg.name}: share.experts_held = {share['experts_held']} "
                             f"of {self.n_experts} experts")
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self._serve_options(cfg, a)

    # -- params ---------------------------------------------------------------
    def _gains(self):
        yield ("norm_f",), (self.d,)
        for i in range(self.n_layers):
            yield (f"layer{i}", "norm1"), (self.d,)
            yield (f"layer{i}", "norm2"), (self.d,)
        yield from self._delta_gains()

    def _tensors(self):
        """(path, shape held here, full shape, start, role, fan-in) of every
        matrix, in a fixed order."""
        yield from self._vocab_tensors()
        yield from self._delta_tensors()
        yield from self._attention_tensors()
        yield from self._expert_tensors()

    def _vectors(self):
        """The delta rule's float32 vectors, and every router's selection bias."""
        yield from self._delta_vectors()
        yield from self._expert_vectors()

    def draw_params(self, seed: int) -> Any:
        p = super().draw_params(seed)
        self._join_delta(p)
        return p

    def share_stats(self) -> dict:
        """``/stats``: what of each layer is held here."""
        return {"experts_held": [self.e_first, self.e_count], "experts": self.n_experts,
                "vocab_rows": [self.v_first, self.vocab], "vocab": self.vocab_full}

    # -- device math --------------------------------------------------------------
    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        y = self._mixer(i, lp, rms_norm(x, lp["norm1"], self.eps), c, m)
        x = x + y.astype(self.dtype)
        y, st = self._ffn(lp, rms_norm(x, lp["norm2"], self.eps), m["live"])
        return x + y.astype(self.dtype), st


def create(cfg: ModelConfig) -> HybridDeltaServing:
    return HybridDeltaServing(cfg)
