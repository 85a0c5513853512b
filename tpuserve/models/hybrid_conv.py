"""A language model whose layers are TWO sublayers each, an operator chosen by a
list (a GATED SHORT CONVOLUTION, or grouped-query softmax attention with an
RMSNorm a head on queries and keys and a rotary embedding) and then a SwiGLU,
dense in the first layers and sigmoid-routed over experts with no shared one in
the rest, built from a published ``config.json`` (ISSUE 59) and served through
the generation engine with paged KV AND a state a slot that is the
convolution's last rows alone: ``hybrid_delta``'s sibling (its entry points,
scheduler, page ledger and expert layer; ``mixers.ConvMixer`` and
``mixers.RotaryAttention`` for the operators).

Nothing here knows a model's name. The architecture is read, under the
published key names, from the JSON file that ``options.config_file`` names.
``N(x; g) = x / sqrt(mean(x^2) + norm_eps) * g`` in float32, its result in the
served type; no bias anywhere (``conv_bias`` must be false):

- ``x_0 = E[ids]``. Layer ``i``: ``x <- x + operator_i(N(x; g_op))``
  (``operator_norm``), the short convolution where ``layer_types[i] == "conv"``
  and attention where it is ``"full_attention"``; then, with ``u2 = N(x; g_ffn)``
  (``ffn_norm``), ``x <- x + (silu(u2 W_1) * (u2 W_3)) W_2`` at
  ``intermediate_size`` where ``i < num_dense_layers`` and the routed experts'
  sum elsewhere.
- The short convolution (``conv_L_cache`` taps ``k``): ``[B | C | z] = u W_in``,
  ``b = B * z`` (the row a slot keeps, the last ``k - 1`` of them), ``c_i = sum_j
  w[j] b_{i - k + 1 + j}`` (depthwise, causal, zeros before position 0, no
  activation), ``y = (C * c) W_out``: ``mixers.ConvMixer``.
- Attention (``num_attention_heads`` over ``num_key_value_heads`` heads of
  ``hidden_size / num_attention_heads``): ``q <- rope(N(q; g_q), i)``, ``k`` alike
  (the norm over a head's columns, one gain for all heads, FIRST; the rotary
  over all columns in pairs ``(j, j + hd / 2)`` at ``rope_parameters.rope_theta``,
  ``rope_type`` must be ``default``), causal softmax of ``q . k / sqrt(hd)`` in
  float32: ``mixers.RotaryAttention``.
- Experts (``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``):
  the router's float32 logits, sigmoid scores, the largest of score + selection
  bias (``use_expert_bias`` must be true; the bias moves picks, never weights),
  weights over their own sum PLUS 1e-6 where ``norm_topk_prob``, times
  ``routed_scaling_factor``; no shared expert (``hybrid_delta.RoutedExperts``).
- ``logits = N(x; g_f) E^T`` where ``tie_word_embeddings`` (the family's default
  when the key is absent), else over a head of its own.

THE CACHE: K (after norm and rotary) and V of the attention layers in pages of
the engine's ledger; ``conv[l][slot]``, (k - 1, hidden) in the served type, for
every convolution layer, and NOTHING else a slot (``_conv_signature``'s one
``slot_block``). EVERY LAYER IS WHOLE HERE: a ``share`` is refused. Requests,
weights by recipe and the served log-probabilities are ``decoder``'s (``paged_lm``).
"""

from __future__ import annotations

import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models.hybrid_delta import RoutedExperts
from tpuserve.models.mixers import SSM_COLUMNS, ConvPatternMixers
from tpuserve.models.paged_lm import (COMPACT_COLUMN, CONTEXT_COLUMN, EXPERT_COLUMNS,
                                      SAMPLE_COLUMNS, PagedLM, read_config_file, rms_norm)

# Standard deviations of the drawn tensors, by role (``weight_scales`` in the
# config file overrides any). q and k are normed by head, so ``qk`` moves
# nothing and what decides attention is ``qk_gain``, the range both norms' gains
# are drawn inside, a column each (about 2: scores of standard deviation 4; not
# ONE value, which would commute with the rotary and hide their order).
# The taps have fan-in ``k``, so each carries about a third of ``c``'s variance.
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 1.0, "qk_gain": [1.0, 3.0], "v": 1.0, "o": 1.0,
    "ffn_in": 1.0, "ffn_out": 1.0, "router": 1.0, "router_bias": 0.02, "conv_in": 1.0,
    "conv_tap": 1.0, "conv_out": 1.0,
}
KINDS = ("conv", "full_attention")


class HybridConvServing(ConvPatternMixers, RoutedExperts, PagedLM):
    # The expert layer's four (a dense layer counts nothing in them) and the
    # context, the recurrent layers' four, the compact dispatches, and the
    # steps by the sampler's branch.
    COLUMNS = (*EXPERT_COLUMNS, CONTEXT_COLUMN, *SSM_COLUMNS, COMPACT_COLUMN, *SAMPLE_COLUMNS)
    route_eps = 1e-6  # the published block's: weights over their own sum plus this

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        rope = a.get("rope_parameters") or {}
        for key, got, want in (("conv_bias", a.get("conv_bias", False), False),
                               ("use_expert_bias", a.get("use_expert_bias", True), True),
                               ("rope_parameters.rope_type", rope.get("rope_type", "default"),
                                "default"), ("share", a.get("share", {}), {})):
            if got != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {got!r}")
        self.d = int(a["hidden_size"])
        self.kinds = [str(k) for k in a["layer_types"]]
        self.n_layers = int(a.get("num_hidden_layers", len(self.kinds)))
        if len(self.kinds) != self.n_layers or set(self.kinds) - set(KINDS):
            raise ValueError(f"{cfg.name}: layer_types must have num_hidden_layers = "
                             f"{self.n_layers} entries of {KINDS}")
        self.eps = float(a.get("norm_eps", 1e-5))
        self.m_layers = [i for i, k in enumerate(self.kinds) if k == "conv"]
        self.a_layers = [i for i, k in enumerate(self.kinds) if k == "full_attention"]
        self._conv_setup(conv_kernel=int(a.get("conv_L_cache", 3)))
        self.heads = self.heads_full = int(a["num_attention_heads"])
        self.kv = self.kv_full = int(a["num_key_value_heads"])
        self.h_first = self.kv_first = 0
        self.hd = int(a.get("head_dim") or self.d // self.heads)
        self.rope_theta = float(rope["rope_theta"])
        self.n_dense = int(a.get("num_dense_layers", 0))
        if not 0 <= self.n_dense <= self.n_layers:
            raise ValueError(f"{cfg.name}: num_dense_layers = {self.n_dense} of "
                             f"{self.n_layers} layers")
        self.e_layers = list(range(self.n_dense, self.n_layers))
        self.ffn_width = int(a["intermediate_size"])
        self.n_experts = self.e_count = int(a["num_experts"])
        self.e_first, self.shared_width = 0, 0
        self.top_k = int(a["num_experts_per_tok"])
        self.expert_width = int(a["moe_intermediate_size"])
        self.norm_topk = bool(a.get("norm_topk_prob", True))
        self.route_scale = float(a.get("routed_scaling_factor") or 1.0)
        self.tied = bool(a.get("tie_word_embeddings", True))
        self.vocab_full = self.vocab = int(a["vocab_size"])
        self.v_first = 0
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self._serve_options(cfg, a)

    # -- params ---------------------------------------------------------------
    def _gains(self):
        yield ("norm_f",), (self.d,)   # the model's ``embedding_norm``
        for i in range(self.n_layers):
            yield (f"layer{i}", "operator_norm"), (self.d,)
            yield (f"layer{i}", "ffn_norm"), (self.d,)

    def _tensors(self):
        """(path, shape held here, full shape, start, role, fan-in) of every
        matrix, in a fixed order. SwiGLU's names are the published ones: ``w1``
        the gate, ``w3`` up, ``w2`` down."""
        d, f, s = self.d, self.ffn_width, self.scales
        yield from self._vocab_tensors()
        yield from self._conv_tensors()
        yield from self._attention_tensors()
        for i in range(self.n_dense):
            L = f"layer{i}"
            for name in ("w1", "w3"):
                yield ((L, name), (d, f), (d, f), (0, 0), s["ffn_in"], d)
            yield ((L, "w2"), (f, d), (f, d), (0, 0), s["ffn_out"], f)
        yield from self._expert_tensors()

    def _vectors(self):
        """The query/key norms' gains and every router's selection bias."""
        yield from self._qk_gains()
        yield from self._expert_vectors()

    # -- device math --------------------------------------------------------------
    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        y = self._mixer(i, lp, rms_norm(x, lp["operator_norm"], self.eps), c, m)
        x = x + y.astype(self.dtype)
        u = rms_norm(x, lp["ffn_norm"], self.eps)
        if i < self.n_dense:
            y, st = self._swiglu(u, lp["w1"], lp["w3"], lp["w2"]), None
        else:
            y, st = self._ffn(lp, u, m["live"])
        return x + y.astype(self.dtype), st


def create(cfg: ModelConfig) -> HybridConvServing:
    return HybridConvServing(cfg)
