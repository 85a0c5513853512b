"""A language model whose layers are TWO sublayers each, a mixer chosen by a
list (LINEAR ATTENTION with a constant decay a head, or grouped-query softmax
attention OVER THE BLOCKS OF KEYS A QUERY'S KV GROUP PICKS) and then a dense
SwiGLU, under three scalar multipliers, built from a published ``config.json``
(ISSUE 68) and served through the generation engine with paged KV, a THIRD page
leaf of mean-pooled keys AND a state a slot: ``hybrid_ffn``'s sibling (its layer,
its multipliers), the mixers ``mixers.LightningMixer`` and
``mixers.BlockSelectAttention``.

Nothing here knows a model's name. The architecture is read, under the
published key names, from the JSON file that ``options.config_file`` names. With
``e`` = ``scale_emb``, ``r`` = ``scale_depth / sqrt(scale_depth_layers)`` (the
PUBLISHED depth, where a file holds fewer layers than the model has; by default
``num_hidden_layers``), ``s`` = ``hidden_size / dim_model_base``, eps =
``rms_norm_eps``, no bias anywhere:

- ``h_0 = e E[ids]``. Layer ``i``: ``h <- h + r mixer_i(RMSNorm(h; g1_i))``, the
  mixer by ``mixer_types[i]``; then ``h <- h + r (silu(v W_gate) * (v W_up)) W_down``,
  ``v = RMSNorm(h; g2_i)``, ``intermediate_size`` wide. ``logits = RMSNorm(h; g_f)
  W_head / s`` (``E^T`` where ``tie_word_embeddings``).
- ``"lightning-attn"`` (``lightning_nh`` heads of ``lightning_head_dim``;
  ``lightning_nkv`` must equal it): ``q, k, v = u W_q, u W_k, u W_v``; where
  ``qk_norm``, q and k normed over a head (one gain of D for all heads), THEN,
  where ``lightning_use_rope``, turned at the row's position over all D columns
  in pairs ``(j, j + D / 2)`` at ``rope_theta``; a head: ``S_t = lambda_h S_{t-1} +
  k_t^T v_t`` (D x D float32, zeros before the prompt), ``o_t = lightning_scale q_t
  S_t`` (``lightning_scale`` is ``"1/sqrt(d)"`` or a number), ``lambda_h = exp(-2^(-8
  (h + 1) / H))`` in every layer; where ``use_output_norm`` ``o`` normed over a
  head (one gain of D); where ``use_output_gate`` times ``sigmoid(u W_g)``; out
  ``= o W_o``.
- ``"minicpm4"`` (``num_attention_heads`` on ``num_key_value_heads`` KV heads of
  ``head_dim``): q, k, v alike (``qk_norm``; turned only where ``attn_use_rope``);
  scores times ``head_dim ** -0.5``; a query at ``t < sparse_config.dense_len``: a
  causal softmax over every key; another: over the keys of the ``topk`` blocks of
  ``block_size`` its KV group picks (``mixers.BlockSelectAttention``: pooled keys
  over windows of ``kernel_size`` at ``kernel_stride``, a softmax a head over the
  windows, the sum over the group's heads, a block's score the largest over the
  windows that touch it, ``init_blocks`` first and ``window_size / block_size``
  last blocks always kept); where ``attn_use_output_gate`` the context times
  ``sigmoid(u W_g)`` elementwise by head before ``W_o``. The switch is taken A
  QUERY, by its own position.

REFUSED: ``hidden_act`` not ``silu``, a bias (``attention_bias``, ``mlp_bias``),
``lightning_nkv != lightning_nh``, a ``mixer_types`` entry of a third kind, a
``share`` (every layer is whole here).

THE CACHE: K (after the norm) and V of the attention layers in pages of the
engine's ledger and ``kc``, the pooled keys, ``kv_page_tokens / kernel_stride`` rows
of KV x head_dim a page (``kv_page_tokens`` must be whole blocks); ``ssm[l][slot]``,
(H, D, D) float32, for every linear-attention layer, and NO convolution rows
(``_lightning_signature``'s one ``slot_block``). Requests, weights by recipe and the
served log-probabilities are ``decoder``'s (``paged_lm``).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models.mixers import BLK_COLUMNS, SCAN_COLUMNS, SSM_COLUMNS, BlockPatternMixers
from tpuserve.models.paged_lm import (CONTEXT_COLUMN, SAMPLE_COLUMNS, PagedLM, read_config_file,
                                      rms_norm)

# Standard deviations of the drawn tensors, by role (``weight_scales`` in the
# config file overrides any). q and k are normed by head where ``qk_norm``, so
# ``qk`` / ``lin_qk`` move nothing then and ``qk_gain``, the range both norms'
# gains are drawn inside, decides the scores.
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 1.0, "qk_gain": [1.0, 3.0], "v": 1.0, "o": 1.0, "gate": 1.0,
    "lin_qk": 1.0, "lin_v": 1.0, "lin_o": 1.0, "lin_gate": 1.0, "ffn_in": 1.0, "ffn_out": 1.0,
}
KINDS = ("minicpm4", "lightning-attn")   # the published values of ``mixer_types``


class HybridBlkServing(BlockPatternMixers, PagedLM):
    # The context, the recurrent layers' four, a launch's scans by where they
    # ran, the picked blocks' six, and the steps by the sampler's branch.
    COLUMNS = (CONTEXT_COLUMN, *SSM_COLUMNS, *SCAN_COLUMNS, *BLK_COLUMNS, *SAMPLE_COLUMNS)

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        for key, want in (("hidden_act", "silu"), ("attention_bias", False), ("mlp_bias", False),
                          ("lightning_nkv", a["lightning_nh"]), ("share", {})):
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        self.d = int(a["hidden_size"])
        self.kinds = [str(k) for k in a["mixer_types"]]
        self.n_layers = int(a.get("num_hidden_layers", len(self.kinds)))
        if len(self.kinds) != self.n_layers or set(self.kinds) - set(KINDS):
            raise NotImplementedError(f"{cfg.name}: mixer_types must have num_hidden_layers = "
                                      f"{self.n_layers} entries of {KINDS}")
        self.eps = float(a.get("rms_norm_eps", 1e-6))
        self.a_layers = [i for i, k in enumerate(self.kinds) if k == "minicpm4"]
        self.m_layers = [i for i, k in enumerate(self.kinds) if k == "lightning-attn"]
        self.qk_norm = bool(a.get("qk_norm", False))
        self.rope_theta = float(a.get("rope_theta", 10000.0))
        self.heads = self.heads_full = int(a["num_attention_heads"])
        self.kv = self.kv_full = int(a["num_key_value_heads"])
        self.h_first = self.kv_first = 0
        self.hd = int(a.get("head_dim") or self.d // self.heads)
        self.attn_rope = bool(a.get("attn_use_rope", True))
        self.attn_gate = bool(a.get("attn_use_output_gate", False))
        self._blk_setup(cfg.name, a["sparse_config"])
        ld = int(a["lightning_head_dim"])
        scale = a.get("lightning_scale", "1/sqrt(d)")
        self._lightning_setup(
            heads=int(a["lightning_nh"]), head_dim=ld,
            scale=ld ** -0.5 if scale == "1/sqrt(d)" else float(scale),
            rope=bool(a.get("lightning_use_rope", False)),
            out_norm=bool(a.get("use_output_norm", False)),
            out_gate=bool(a.get("use_output_gate", False)))
        self.embed_scale = float(a.get("scale_emb", 1.0))
        depth = int(a.get("scale_depth_layers", self.n_layers))
        self.residual_scale = float(a.get("scale_depth", math.sqrt(depth))) / math.sqrt(depth)
        self.logits_scaling = self.d / float(a.get("dim_model_base", self.d))
        self.ffn_width = int(a["intermediate_size"])
        self.tied = bool(a.get("tie_word_embeddings", False))
        self.vocab_full = self.vocab = int(a["vocab_size"])
        self.v_first = 0
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self._serve_options(cfg, a)

    # -- params ---------------------------------------------------------------
    def _gains(self):
        yield ("norm_f",), (self.d,)
        for i in range(self.n_layers):
            yield (f"layer{i}", "norm1"), (self.d,)
            yield (f"layer{i}", "norm2"), (self.d,)
        yield from self._lightning_gains()

    def _tensors(self):
        """(path, shape held here, full shape, start, role, fan-in) of every
        matrix, in a fixed order."""
        d, f, s = self.d, self.ffn_width, self.scales
        yield from self._vocab_tensors()
        yield from self._lightning_tensors()
        yield from self._attention_tensors()
        for i in range(self.n_layers):
            L = f"layer{i}"
            for name in ("w_gate", "w_up"):
                yield ((L, name), (d, f), (d, f), (0, 0), s["ffn_in"], d)
            yield ((L, "w_down"), (f, d), (f, d), (0, 0), s["ffn_out"], f)

    def _vectors(self):
        return self._qk_gains()

    # -- device math --------------------------------------------------------------
    def _embed(self, params, ids):
        x = jnp.take(params["embed"], ids, axis=0)
        return (x.astype(jnp.float32) * self.embed_scale).astype(self.dtype)

    def _add(self, x, y):
        """The stream plus a sublayer's float32 output times ``r``."""
        return x + (y * self.residual_scale).astype(self.dtype)

    def _head(self, params, x):
        return super()._head(params, x) / self.logits_scaling

    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        x = self._add(x, self._mixer(i, lp, rms_norm(x, lp["norm1"], self.eps), c, m))
        return self._add(x, self._swiglu(rms_norm(x, lp["norm2"], self.eps),
                                         lp["w_gate"], lp["w_up"], lp["w_down"])), None


def create(cfg: ModelConfig) -> HybridBlkServing:
    return HybridBlkServing(cfg)
