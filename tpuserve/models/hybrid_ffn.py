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

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models.mixers import Mamba2Mixer, PlainAttention
from tpuserve.models.paged_lm import PagedLM, read_config_file, rms_norm
from tpuserve.obs import GEN_PHASES

# Standard deviations of the drawn tensors, by role (``weight_scales`` in the
# config file overrides any): ``hybrid``'s, where the roles are the same.
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
    "ffn_out": 1.0, "ssm_in": 1.0, "ssm_bc": 2.0, "ssm_dt": 1.0, "ssm_out": 1.0,
    "conv": 1.0, "conv_bias": 0.1, "ssm_d": 0.1,
}
KINDS = ("mamba", "attention")


class HybridFfnServing(Mamba2Mixer, PlainAttention, PagedLM):
    # Device-side sums a phase: the context (positions a live token attends
    # from), live tokens through a scan layer, slot states read and written,
    # (prefill) pieces that started from zeros / from a stored state.
    ACC = 5

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        for key, want in (("attention_bias", False), ("mamba_proj_bias", False),
                          ("position_embedding_type", "nope"), ("hidden_act", "silu"),
                          ("normalization_function", "rmsnorm"), ("num_local_experts", 0),
                          ("num_experts_per_tok", 0)):
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        share = a.get("share", {})
        if set(share) - {"vocab_rows"}:
            raise NotImplementedError(f"{cfg.name}: share = {share!r} (every layer is whole here)")
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

    # -- shapes -----------------------------------------------------------------
    def kv_page_signature(self, slots: int, pages: int, page_tokens: int) -> Any:
        page = jax.ShapeDtypeStruct(self._page_shape(pages, page_tokens), self.dtype)
        return {
            "kf": [page for _ in self.a_layers], "vf": [page for _ in self.a_layers],
            **self._mamba_signature(slots), **self._lane_signature(slots, page_tokens),
        }

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

    def _accumulate(self, acc, phase: int, context, tokens, rows, zero=0, carried=0):
        row = jnp.stack([jnp.asarray(v, jnp.int32) for v in (
            context, *self._ssm_sums(tokens, rows, zero, carried))])
        return acc.at[phase].add(row.astype(jnp.uint32))

    # -- prefill ------------------------------------------------------------------
    def prefill_chunk(self, params: Any, state: Any, launch: Any, *, chunk: int) -> Any:
        """One launch of ``pack_prefill``: piece j is tokens [start[j],
        start[j] + length[j]) of the prompt in slot[j], causal within the
        piece and over what earlier launches left in that slot's pages and
        state."""
        t = self._tiles(launch, chunk)
        slot, start, length = launch["slot"], launch["start"], launch["length"]
        valid, cpos = t["valid"], t["cpos"]
        x = self._embed(params, launch["ids"])
        if self.a_layers:
            w_page, off = self._page_of(t, state["kf"][0].shape[2], state["bt"].shape[1])
        kf, vf, ssm, conv = (list(state[k]) for k in ("kf", "vf", "ssm", "conv"))
        for i, kind in enumerate(self.kinds):
            lp = params[f"layer{i}"]
            u = rms_norm(x, lp["norm1"], self.eps)
            if kind == "mamba":
                j = self.m_layers.index(i)
                y, ssm[j], conv[j] = self._mamba_prefill(
                    lp, u, t, ssm[j], conv[j], slot, start, length)
            else:
                j = self.a_layers.index(i)
                y, kf[j], vf[j] = self._attn_prefill(lp, u, t, kf[j], vf[j], w_page, off)
            x = self._ffn(lp, self._add(x, y))
        has = length > 0
        new = dict(state, kf=kf, vf=vf, ssm=ssm, conv=conv, acc=self._accumulate(
            state["acc"], 0, jnp.sum(jnp.where(valid, cpos + 1, 0)),
            jnp.sum(valid), jnp.sum(has), jnp.sum(has & (start == 0)),
            jnp.sum(has & (start > 0))))
        return self._arm(params, state, new, launch, t, x, {})

    # -- decode -------------------------------------------------------------------
    def step(self, params: Any, state: Any) -> tuple[Any, dict]:
        live = state["armed"] & ~state["done"]
        pos = jnp.clip(state["pos"], 0, self.max_ctx - 1)
        x = self._embed(params, state["last"])
        if self.a_layers:
            P = state["kf"][0].shape[2]
            page_of = jnp.take_along_axis(state["bt"], (pos // P)[:, None], axis=1)[:, 0]
            w_page, off = jnp.where(live, page_of, 0), pos % P
        kf, vf, ssm, conv = (list(state[k]) for k in ("kf", "vf", "ssm", "conv"))
        for i, kind in enumerate(self.kinds):
            lp = params[f"layer{i}"]
            u = rms_norm(x, lp["norm1"], self.eps)
            if kind == "mamba":
                j = self.m_layers.index(i)
                y, ssm[j], conv[j] = self._mamba_step(lp, u, live, ssm[j], conv[j])
            else:
                j = self.a_layers.index(i)
                y, kf[j], vf[j] = self._attn_step(lp, u, kf[j], vf[j], state["bt"], pos,
                                                   w_page, off)
            x = self._ffn(lp, self._add(x, y))
        n_live = jnp.sum(live)
        acc = self._accumulate(state["acc"], 1, jnp.sum(jnp.where(live, pos + 1, 0)),
                               n_live, n_live)
        return self._emit(params, state, dict(state, kf=kf, vf=vf, ssm=ssm, conv=conv),
                          x, live, pos, acc)

    # -- host side ----------------------------------------------------------------
    def bind_metrics(self, metrics: Any) -> None:
        self._counters = [[self._context_counter(metrics, ph)] + self._ssm_counters(metrics, ph)
                          for ph in GEN_PHASES]


def create(cfg: ModelConfig) -> HybridFfnServing:
    return HybridFfnServing(cfg)
