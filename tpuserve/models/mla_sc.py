"""A decoder-only language model whose layers are DOUBLE and carry their
routed experts on a SHORTCUT (ISSUE 42): two latent attentions (``mla``'s, by
inheritance: its projections, its two page leaves, its two forms, its map over
a launch's tiles, its step's walk and its two kernels) and two dense SwiGLUs a
layer, and one routed layer that reads the first sublayer's normed stream and
joins the stream at the layer's end.
Built from a published ``config.json`` and served through the generation
engine as ``mla`` is. Nothing here knows a model's name.

THE LAYER, with four stream norms (``norm_in0``, ``norm_post0``, ``norm_in1``,
``norm_post1``), eps ``rms_norm_eps``, no biases, an untied head::

    a0 = h  + MLA_0(RMSNorm(h;  in0))     u0 = RMSNorm(a0; post0)
    s  = Routed(u0)                        b0 = a0 + MLP_0(u0)
    a1 = b0 + MLA_1(RMSNorm(b0; in1))     u1 = RMSNorm(a1; post1)
    h' = a1 + MLP_1(u1) + s

Between its read and its join the routed layer depends on nothing else of the
layer: that is the point of the shortcut. ``MLP_j`` is a SwiGLU of
``ffn_hidden_size``.

ATTENTION is ``mla.LatentServing``'s with two factors (``mla_scale_q_lora``,
``mla_scale_kv_lora``): the query's latent times ``sqrt(hidden_size /
q_lora_rank)`` after its norm, and ``c_kv`` times ``sqrt(hidden_size /
kv_lora_rank)`` after its norm and before ``W_kvb`` (so the CACHED row
carries it, and the rotary key does not). Each layer has two, so the page
pools are ``2 x num_layers`` of each leaf, attention ``j`` of layer ``i`` at
``2 i + j``. Rotary pairs are (2i, 2i + 1) unless ``rope_interleave`` says no.

THE ROUTED LAYER (``tpuserve.ops.moe``): ``p = softmax(u W_r)`` in float32 over
``n_routed_experts + zero_expert_num`` outputs, the real experts first; the
``moe_topk`` largest of ``p + b`` (the bias moves picks, never weights);
weights ``routed_scaling_factor x p``, over their own sum only where
``norm_topk_prob`` says so; a real pick adds its weight times a SwiGLU expert
of ``expert_ffn_hidden_size``, a ZERO-COMPUTE pick (``zero_expert_type =
"identity"``) its weight times ``u``. A token's real picks number 0 to
``moe_topk``.

THE SHARE, as ``decoder`` reads it: ``share.experts_held = [first, count]`` of
the real experts and ``share.vocab_rows = [first, count]``. The router scores
every output; picks on experts held elsewhere add nothing; the zero-compute
term has no weights, so every chip of the layer computes it alike for the
tokens it has, and it is computed here. Attention, the dense SwiGLUs and the
norms are whole.

TILES AND THE DECODE WALK. A prefill tile is ``TILE_ROWS`` = 256 rows (the
forms break even at 171 at the published head sizes, so the expanded form and
its kernel stay: on the TPU one call of ``ops/tile_attention.py`` a tile walks
the tile's key blocks, as in ``mla``, through ``mla``'s own ``_attend_tiles``)
over key blocks of ``key_block`` = 256 positions: a launch of 1,024 rows
carries up to four prompts' pieces, and a short prompt's tile reads two pages,
not eight. A decode step is ``mla``'s (``_step_plan``, ``_attention``): absorbed;
on the TPU at shapes the kernel takes, ONE call of ``ops/lane_attention.py`` an
attention walks every lane's own key blocks in cells of ``step_keys`` = 512
positions, hundreds of lanes of very different lengths side by side, each as
far as its own position needs; elsewhere the lanes walk one after another in
XLA. Both programs are ``paged_lm``'s loop over ``_layer``; what is this
family's own is the layer, the routed layer's third kind of pick and one
column of ``acc``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models import mla
from tpuserve.models.paged_lm import Column, PagedLM, read_config_file, rms_norm, series, summed
from tpuserve.ops.moe import held_experts_swiglu, topk_route

# As ``mla``'s, but the router: a softmax over hundreds of outputs is flat at
# unit logits (a pick's weight would be scale / outputs and the routed layer
# nothing beside the dense one), so the router is drawn wide enough that the
# picks carry a trained router's share of the mass.
DEFAULT_SCALES = {**mla.DEFAULT_SCALES, "router": 1.75}


class ShortcutLatentServing(mla.LatentServing):
    # ``mla``'s twelve columns and the live picks on zero-compute outputs. Rows
    # attended and walked sum over every attention that ran (``_counts``).
    COLUMNS = (*mla.LatentServing.COLUMNS,
               Column(summed("routed_zero"), series("moe_routed_zero_total")))
    TILE_ROWS = 256
    key_block = 256
    step_keys = 512     # contexts of hundreds: four pages a cell read fastest (PERF.md 6, PR 44)

    def __init__(self, cfg: ModelConfig) -> None:
        PagedLM.__init__(self, cfg)
        a = read_config_file(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        for key, want in (("attention_bias", False), ("attention_method", "MLA"),
                          ("rope_scaling", None), ("zero_expert_type", "identity")):
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        if not a.get("q_lora_rank"):
            raise NotImplementedError(f"{cfg.name}: q_lora_rank = {a.get('q_lora_rank')!r} "
                                      "(queries without a low-rank projection)")
        self.n_layers = int(a["num_layers"])
        self.eps = float(a.get("rms_norm_eps", 1e-6))
        self._read_attention(a)
        self.rope_interleave = bool(a.get("rope_interleave", True))
        if a.get("mla_scale_q_lora", False):
            self.q_scale = math.sqrt(self.d / self.q_rank)
        if a.get("mla_scale_kv_lora", False):
            self.kv_scale = math.sqrt(self.d / self.r)
        self.dense_width = int(a["ffn_hidden_size"])
        self.n_experts = int(a["n_routed_experts"])      # real experts
        self.n_zero = int(a.get("zero_expert_num", 0))   # identity outputs after them
        self.top_k = int(a["moe_topk"])
        self.expert_width = int(a["expert_ffn_hidden_size"])
        self.norm_topk = bool(a.get("norm_topk_prob", False))
        self.route_scale = float(a.get("routed_scaling_factor", 1.0))
        self.vocab_full = int(a["vocab_size"])
        self.tied = bool(a.get("tie_word_embeddings", False))
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.n_experts])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.vocab_full])
        if not 0 <= self.e_first <= self.e_first + self.e_count <= self.n_experts:
            raise ValueError(f"{cfg.name}: share.experts_held = {share['experts_held']} "
                             f"of {self.n_experts} real experts")
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self._serve_options(cfg, a)

    # -- params ---------------------------------------------------------------
    def _attentions(self):
        return [(f"layer{i}", f"attn{j}") for i in range(self.n_layers) for j in (0, 1)]

    def _gains(self):
        yield ("norm_f",), (self.d,)
        for i in range(self.n_layers):
            for j in (0, 1):
                yield (f"layer{i}", f"norm_in{j}"), (self.d,)
                yield (f"layer{i}", f"norm_post{j}"), (self.d,)
        for at in self._attentions():
            yield from self._attention_gains(at)

    def _tensors(self):
        d, s, f = self.d, self.scales, self.dense_width
        yield from self._vocab_tensors()
        for at in self._attentions():
            yield from self._attention_tensors(at)
        fe, outputs = self.expert_width, self.n_experts + self.n_zero
        for i in range(self.n_layers):
            L = f"layer{i}"
            for j in (0, 1):
                for name in ("w_gate", "w_up"):
                    yield ((L, f"mlp{j}", name), (d, f), (d, f), (0, 0), s["ffn_in"], d)
                yield ((L, f"mlp{j}", "w_down"), (f, d), (f, d), (0, 0), s["ffn_out"], f)
            yield ((L, "router"), (d, outputs), (d, outputs), (0, 0), s["router"], d)
            # An expert is a tensor of its own, named by its PUBLISHED number (a layer's
            # experts in one tensor would be more elements than the recipe's 32-bit
            # counter indexes); ``draw_params`` stacks the held ones.
            for g in range(self.e_first, self.e_first + self.e_count):
                for name in ("e_gate", "e_up"):
                    yield ((L, name, str(g)), (d, fe), (d, fe), (0, 0), s["ffn_in"], d)
                yield ((L, "e_down", str(g)), (fe, d), (fe, d), (0, 0), s["expert_out"], fe)

    def draw_params(self, seed: int) -> Any:
        p = super().draw_params(seed)
        for i in range(self.n_layers):
            lp = p[f"layer{i}"]
            for name in ("e_gate", "e_up", "e_down"):
                lp[name] = jnp.stack([lp[name][str(g)] for g in range(
                    self.e_first, self.e_first + self.e_count)])
        return p

    def _vectors(self):
        """The selection bias over every output of the router, the
        zero-compute ones too: small, about 0."""
        b3, n = 3.0 * self.scales["router_bias"], self.n_experts + self.n_zero
        for i in range(self.n_layers):
            yield ((f"layer{i}", "e_bias"), (n,), (n,), (0,), -b3, b3)

    def share_stats(self) -> dict:
        """``/stats``: what of each layer is held here."""
        return {"experts_held": [self.e_first, self.e_count], "experts": self.n_experts,
                "zero_experts": self.n_zero, "vocab_rows": [self.v_first, self.vocab],
                "vocab": self.vocab_full}

    # -- device math --------------------------------------------------------------
    def _routed(self, lp, u, live):
        """The routed layer on the normed stream ``u`` (T, d) -> ((T, d)
        float32: this chip's experts' part and the zero-compute term, the
        layer's counts)."""
        r = jnp.matmul(u.astype(jnp.float32), lp["router"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        w, e = topk_route(r, self.top_k, normalize=self.norm_topk, scale=self.route_scale,
                          select_bias=lp["e_bias"])
        return held_experts_swiglu(u, w, e, self.e_first, lp["e_gate"], lp["e_up"],
                                   lp["e_down"], live=live, of=self.n_experts + self.n_zero,
                                   real=self.n_experts)

    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        """One double layer (module docstring), in either phase: attention
        ``j`` of layer ``i`` is the model's attention ``2 i + j``."""
        dt, eps, live = self.dtype, self.eps, m["live"]

        def attend(j: int, u):
            with jax.named_scope(m["scope"]):
                return self._attention(lp[f"attn{j}"], u, 2 * i + j, c, m)

        a0 = x + attend(0, rms_norm(x, lp["norm_in0"], eps)).astype(dt)
        u0 = rms_norm(a0, lp["norm_post0"], eps)
        with jax.named_scope("moe_layer"):
            s, stats = self._routed(lp, u0, live)
        b0 = a0 + self._swiglu(u0, **lp["mlp0"]).astype(dt)
        a1 = b0 + attend(1, rms_norm(b0, lp["norm_in1"], eps)).astype(dt)
        u1 = rms_norm(a1, lp["norm_post1"], eps)
        return a1 + (self._swiglu(u1, **lp["mlp1"]) + s).astype(dt), stats

    def _counts(self, m: dict) -> dict:
        n, c = 2 * self.n_layers, super()._counts(m)
        return {**c, "attended": n * c["attended"], "walked": n * c["walked"]}


def create(cfg: ModelConfig) -> ShortcutLatentServing:
    return ShortcutLatentServing(cfg)
