"""A language model whose layers are TWO sublayers each, a mixer chosen by a
list (Mamba-2 or attention without a position term) and then a dense SwiGLU
feed-forward, under four scalar multipliers, built from a published
``config.json`` (ISSUE 40) and served through the generation engine with paged
KV AND a recurrent state a slot.

Nothing here knows a model's name. The architecture is read, under the
published key names, from the JSON file that ``options.config_file`` names.
With ``e`` = ``embedding_multiplier``, ``r`` = ``residual_multiplier``, ``a`` =
``attention_multiplier``, ``s`` = ``logits_scaling`` and ``E`` the embedding:

- ``h_0 = e E[ids]``.
- Layer ``i``: ``h <- h + r mixer_i(RMSNorm(h; g1_i))``, the mixer Mamba-2 where
  ``layer_types[i] == "mamba"`` and attention where it is ``"attention"``; then
  ``h <- h + r (silu(v W_gate) * (v W_up)) W_down`` with ``v = RMSNorm(h; g2_i)``,
  ``shared_intermediate_size`` wide, no bias.
- Mamba-2 (``mamba_n_heads`` H of ``mamba_d_head`` P, ``mamba_n_groups`` G,
  ``mamba_d_state`` N, ``mamba_d_conv``, ``mamba_conv_bias``): ``mixers.Mamba2Mixer``,
  the layer ``hybrid`` serves, with no clamp on delta.
- Attention (``num_attention_heads`` over ``num_key_value_heads`` heads of
  ``hidden_size / num_attention_heads``): no rotary embedding and no position
  term of any kind (``position_embedding_type`` must say ``nope``), scores times
  ``a`` (a config key, not ``head_dim ** -0.5``), causal softmax in float32,
  ``mixers.PlainAttention``.
- ``logits = RMSNorm(h; g_f) E^T / s`` where ``tie_word_embeddings``, else over
  a head of its own.

THE CACHE is ``hybrid``'s: K and V of the attention layers in pages of the
engine's ledger (heads narrower than 128 lie side by side in a page's row,
``paged_lm``), a float32 state and the convolution's last rows A SLOT for
every Mamba-2 layer. The stream is kept in the served type; every sublayer's
output is scaled by ``r`` in float32 before it is added.

THE SHARE: ``vocab_rows = [first, count]`` alone; every layer is whole here.
Requests, weights by recipe and the served log-probabilities are ``decoder``'s
(``paged_lm``).
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models.mixers import SCAN_COLUMNS, SSM_COLUMNS, PatternMixers
from tpuserve.models.paged_lm import (CONTEXT_COLUMN, SAMPLE_COLUMNS, PagedLM, read_config_file,
                                      rms_norm)

# Standard deviations of the drawn tensors, by role (``weight_scales`` in the
# config file overrides any): ``hybrid``'s, where the roles are the same.
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
    "ffn_out": 1.0, "ssm_in": 1.0, "ssm_bc": 2.0, "ssm_dt": 1.0, "ssm_out": 1.0,
    "conv": 1.0, "conv_bias": 0.1, "ssm_d": 0.1,
}
KINDS = ("mamba", "attention")


class HybridFfnServing(PatternMixers, PagedLM):
    # The context, the scan layers' four, a launch's scans by where they ran, and
    # the steps by the sampler's branch.
    COLUMNS = (CONTEXT_COLUMN, *SSM_COLUMNS, *SCAN_COLUMNS, *SAMPLE_COLUMNS)
    # What this family refuses and a sibling with a routed block serves
    # (``hybrid_ffn_moe``): experts in the config, and a share of them.
    ROUTED = False
    SHARE_KEYS = ("vocab_rows",)

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        dense = () if self.ROUTED else (("num_local_experts", 0), ("num_experts_per_tok", 0))
        for key, want in (("attention_bias", False), ("mamba_proj_bias", False),
                          ("position_embedding_type", "nope"), ("hidden_act", "silu"),
                          ("normalization_function", "rmsnorm"), *dense):
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        share = a.get("share", {})
        if set(share) - set(self.SHARE_KEYS):
            raise NotImplementedError(f"{cfg.name}: share = {share!r} (of {self.SHARE_KEYS} here)")
        self.d = int(a["hidden_size"])
        self.kinds = [str(k) for k in a["layer_types"]]
        self.n_layers = int(a.get("num_hidden_layers", len(self.kinds)))
        if len(self.kinds) != self.n_layers or set(self.kinds) - set(KINDS):
            raise ValueError(f"{cfg.name}: layer_types must have num_hidden_layers = "
                             f"{self.n_layers} entries of {KINDS}")
        self.eps = float(a.get("rms_norm_eps", 1e-5))
        self.m_layers = [i for i, k in enumerate(self.kinds) if k == "mamba"]
        self.a_layers = [i for i, k in enumerate(self.kinds) if k == "attention"]
        self._mamba_setup(
            cfg.name, heads=int(a["mamba_n_heads"]), head_dim=int(a["mamba_d_head"]),
            groups=int(a["mamba_n_groups"]), state=int(a["mamba_d_state"]),
            conv_kernel=int(a.get("mamba_d_conv", 4)),
            conv_bias=bool(a.get("mamba_conv_bias", True)), share=[0, 1],
            dt_range=(0.001, 0.1))   # the config has no key for it: mamba2's own defaults
        if self.mh * self.mp != int(a.get("mamba_expand", 2)) * self.d:
            raise ValueError(f"{cfg.name}: mamba_n_heads x mamba_d_head = {self.mh * self.mp} "
                             f"is not mamba_expand x hidden_size")
        self.heads = self.heads_full = int(a["num_attention_heads"])
        self.kv = self.kv_full = int(a["num_key_value_heads"])
        self.h_first = self.kv_first = 0
        self.hd = int(a.get("head_dim") or self.d // self.heads)
        self.score_scale = float(a.get("attention_multiplier", self.hd ** -0.5))
        self.embed_scale = float(a.get("embedding_multiplier", 1.0))
        self.residual_scale = float(a.get("residual_multiplier", 1.0))
        self.logits_scaling = float(a.get("logits_scaling", 1.0))
        self.ffn_width = int(a["shared_intermediate_size"])
        self.tied = bool(a.get("tie_word_embeddings", False))
        self.vocab_full = int(a["vocab_size"])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.vocab_full])
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self._serve_options(cfg, a)

    # -- params ---------------------------------------------------------------
    def _gains(self):
        yield ("norm_f",), (self.d,)
        for i in range(self.n_layers):
            yield (f"layer{i}", "norm1"), (self.d,)
            yield (f"layer{i}", "norm2"), (self.d,)
        yield from self._mamba_gains()

    def _tensors(self):
        """(path, shape held here, full shape, start, role, fan-in) of every
        matrix, in a fixed order. The published ``input_linear`` is ``[W_gate |
        W_up]``: here two tensors, as ``decoder``'s dense layer has them."""
        d, f, s = self.d, self.ffn_width, self.scales
        yield from self._vocab_tensors()
        yield from self._mamba_tensors()
        yield from self._attention_tensors()
        for i in range(self.n_layers):
            L = f"layer{i}"
            for name in ("w_gate", "w_up"):
                yield ((L, name), (d, f), (d, f), (0, 0), s["ffn_in"], d)
            yield ((L, "w_down"), (f, d), (f, d), (0, 0), s["ffn_out"], f)

    def _vectors(self):
        return self._mamba_vectors()

    def draw_params(self, seed: int) -> Any:
        p = super().draw_params(seed)
        self._join_mamba(p)
        return p

    # -- device math --------------------------------------------------------------
    def _embed(self, params, ids):
        x = jnp.take(params["embed"], ids, axis=0)
        return (x.astype(jnp.float32) * self.embed_scale).astype(self.dtype)

    def _add(self, x, y):
        """The stream plus a sublayer's float32 output times the residual
        multiplier."""
        return x + (y * self.residual_scale).astype(self.dtype)

    def _ffn(self, lp, x):
        return self._add(x, self._swiglu(rms_norm(x, lp["norm2"], self.eps),
                                         lp["w_gate"], lp["w_up"], lp["w_down"]))

    def _head(self, params, x):
        return super()._head(params, x) / self.logits_scaling

    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        y = self._mixer(i, lp, rms_norm(x, lp["norm1"], self.eps), c, m)
        return self._ffn(lp, self._add(x, y)), None


def create(cfg: ModelConfig) -> HybridFfnServing:
    return HybridFfnServing(cfg)
