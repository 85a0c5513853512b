"""A decoder-only language model whose layers are DOUBLE and carry their
routed experts on a SHORTCUT (ISSUE 42): two latent attentions (``mla``'s, by
inheritance: its projections, its two page leaves, its two forms, its map over
a launch's tiles and its two kernels) and two dense SwiGLUs a layer, and one routed
layer that reads the first sublayer's normed stream and joins the stream at
the layer's end.
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
not eight. A decode step is absorbed and walks where ``mla``'s does
(``_step_walk``). On the TPU at shapes the kernel takes, ONE call of
``ops/lane_attention.py`` an attention walks every lane's own key blocks
(``mla._walk_lanes``): hundreds of lanes of very different lengths side by
side, each as far as its own position needs, the stream in the lanes' own
order. Elsewhere the step attends its lanes IN GROUPS in XLA: it runs in order
of context length (sorted once, the last stream put back before it is
sampled), and ``DECODE_GROUP`` lanes walk the key blocks side by side as far
as the longest of them needs (``_attend_lanes``), because ``mla``'s XLA walk,
one lane after another, is thousands of serial walks at hundreds of lanes and
eight attentions a step.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models import mla
from tpuserve.models.paged_lm import NEG, PagedLM, read_config_file, rms_norm
from tpuserve.obs import GEN_PHASES
from tpuserve.ops.moe import held_experts_swiglu, topk_route

# As ``mla``'s, but the router: a softmax over hundreds of outputs is flat at
# unit logits (a pick's weight would be scale / outputs and the routed layer
# nothing beside the dense one), so the router is drawn wide enough that the
# picks carry a trained router's share of the mass.
DEFAULT_SCALES = {**mla.DEFAULT_SCALES, "router": 1.75}


class ShortcutLatentServing(mla.LatentServing):
    # ``mla``'s twelve sums a phase and the live picks on zero-compute outputs.
    # Rows attended and walked sum over every attention that ran.
    ACC = 13
    TILE_ROWS = 256
    key_block = 256
    step_keys = 512     # contexts of hundreds: four pages a cell read fastest (PERF.md 6, PR 44)
    DECODE_GROUP = 32   # lanes that walk their key blocks side by side in XLA

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

    # -- shapes -----------------------------------------------------------------
    def kv_page_signature(self, slots: int, pages: int, page_tokens: int) -> Any:
        sig = super().kv_page_signature(slots, pages, page_tokens)
        return {**sig, "ckv": sig["ckv"] * 2, "kr": sig["kr"] * 2}

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

    def _layer(self, lp, x, live, attend):
        """One double layer (module docstring); ``attend(j, u)`` is attention
        ``j`` of the layer on the normed stream, (T, d) float32."""
        dt, eps = self.dtype, self.eps
        a0 = x + attend(0, rms_norm(x, lp["norm_in0"], eps)).astype(dt)
        u0 = rms_norm(a0, lp["norm_post0"], eps)
        with jax.named_scope("moe_layer"):
            s, stats = self._routed(lp, u0, live)
        b0 = a0 + self._swiglu(u0, **lp["mlp0"]).astype(dt)
        a1 = b0 + attend(1, rms_norm(b0, lp["norm_in1"], eps)).astype(dt)
        u1 = rms_norm(a1, lp["norm_post1"], eps)
        return a1 + (self._swiglu(u1, **lp["mlp1"]) + s).astype(dt), stats

    def _group(self, lanes: int) -> int:
        """Lanes a group of the decode walk: the most, up to ``DECODE_GROUP``,
        that divide the slots."""
        return next(g for g in range(min(lanes, self.DECODE_GROUP), 0, -1) if lanes % g == 0)

    def _group_blocks(self, last, P: int, pps: int):
        """Key blocks each group of the decode walk takes: what its last
        (longest) lane needs. ``last`` (B,) ascending -> (B / G,)."""
        return self._blocks_needed(last.reshape(-1, self._group(last.shape[0]))[:, -1], P, pps)

    def _attend_lanes(self, lp: dict, qn, qr, pools, bt, pos, last):
        """A step's attention, absorbed: q_nope ``qn`` (B, H, nope) and rotated
        q_rope ``qr`` (B, H, rope) of B lanes IN ORDER OF ``last``, each over
        the latent rows of its own pages (``bt`` (B, pps)) up to its position
        ``pos`` -> (B, H, v) float32. ``DECODE_GROUP`` lanes walk the key blocks
        side by side, as many as the group's last (longest) lane needs; a lane
        that needs fewer sees nothing in the others (its first block always
        holds a key it sees, so its softmax's state is sound)."""
        dt, r, h = self.dtype, self.r, self.heads
        ckv, kr = pools
        B, P, pps = qn.shape[0], ckv.shape[1], bt.shape[1]
        G = self._group(B)
        kb = self._block_pages(P, pps)
        c = kb * P
        f32 = {"preferred_element_type": jnp.float32}
        scale = (self.dn + self.dr) ** -0.5
        q_lat = jnp.einsum("bhn,rhn->bhr", qn, lp["w_kb"], **f32).astype(dt)
        btp = jnp.pad(bt, ((0, 0), (0, -pps % kb)))

        def group(a):
            ql, qro, rows, p, need = a

            def block(j):
                pg = jax.lax.dynamic_slice(rows, (0, j * kb), (G, kb))
                c_kv = jnp.take(ckv, pg, axis=0).reshape(G, c, r).astype(dt)
                k_r = jnp.take(kr, pg, axis=0).reshape(G, c, self.dr).astype(dt)
                see = (j * c + jnp.arange(c))[None, :] <= p[:, None]
                s = jnp.einsum("ghr,gcr->ghc", ql, c_kv, **f32) \
                    + jnp.einsum("ghd,gcd->ghc", qro, k_r, **f32)
                return jnp.where(see[:, None], s * scale, NEG), \
                    lambda pr: jnp.einsum("ghc,gcr->ghr", pr.astype(dt), c_kv, **f32)

            return self._over_key_blocks(need, (G, h), r, block)

        by_group = lambda v: v.reshape((B // G, G) + v.shape[1:])  # noqa: E731
        o = jax.lax.map(group, (by_group(q_lat), by_group(qr), by_group(btp), by_group(pos),
                                self._group_blocks(last, P, pps)))
        return jnp.einsum("bhr,rhv->bhv", o.reshape(B, h, r).astype(dt), lp["w_vb"], **f32)

    def _sums(self, stats_list, context, attended, walked, form: str, walks) -> tuple:
        n = 2 * self.n_layers
        return (*super()._sums(stats_list, context, n * attended, n * walked, form, walks),
                sum(st["routed_zero"] for st in stats_list))

    # -- prefill ------------------------------------------------------------------
    def prefill_chunk(self, params: Any, state: Any, launch: Any, *, chunk: int) -> Any:
        """As ``mla``'s: one launch of ``pack_prefill``, each piece causal
        within itself and over the latent rows earlier launches left in its
        slot's pages, through every layer's two attentions."""
        t = self._tiles(launch, chunk)
        start, length = launch["start"], launch["length"]
        valid, cpos = t["valid"], t["cpos"]
        P, pps = state["ckv"][0].shape[1], state["bt"].shape[1]
        form = self._form(t["T"])
        x = jnp.take(params["embed"], launch["ids"], axis=0)
        w_page, off = self._page_of(t, P, pps)
        ckv, kr, stats = list(state["ckv"]), list(state["kr"]), []

        def attend(at: int, lp: dict, u):
            with jax.named_scope("mla_prefill"):
                qn, qr, c_kv, k_r = self._project(lp, u, cpos)
                ckv[at] = self._write_pages(ckv[at], w_page, off, c_kv.astype(ckv[at].dtype))
                kr[at] = self._write_keys(kr[at], w_page, off, k_r, runs=True)
                return self._attn_out(
                    lp, self._attend_tiles(lp, qn, qr, (ckv[at], kr[at]), t, form))

        for i in range(self.n_layers):
            lp = params[f"layer{i}"]
            x, st = self._layer(lp, x, valid,
                                lambda j, u, i=i, lp=lp: attend(2 * i + j, lp[f"attn{j}"], u))
            stats.append(st)
        walked = jnp.sum(self._blocks_needed(t["last"], P, pps)) * self._block_pages(P, pps) * P
        new = dict(state, ckv=ckv, kr=kr, acc=self._accumulate(
            state["acc"], 0, stats, jnp.sum(jnp.where(valid, cpos + 1, 0)),
            jnp.sum(jnp.where(length > 0, start + length, 0)), walked, form,
            self._tile_walks(t, (ckv[0], kr[0]), form)))
        return self._arm(params, state, new, launch, t, x, {})

    # -- decode -------------------------------------------------------------------
    def step(self, params: Any, state: Any) -> tuple[Any, dict]:
        """One token a live lane. Where the kernel walks (``_step_walk``) every
        lane walks its own key blocks and the stream keeps the lanes' order.
        Where XLA walks, the layers run over the lanes in order of context
        length (``_attend_lanes`` groups neighbours), and the last stream goes
        back to the lanes' own order before it is sampled."""
        live = state["armed"] & ~state["done"]
        pos = jnp.clip(state["pos"], 0, self.max_ctx - 1)
        P, pps = state["ckv"][0].shape[1], state["bt"].shape[1]
        ckv, kr, stats = list(state["ckv"]), list(state["kr"]), []
        # A lane that is not live walks one block of whatever its row names:
        # its result is discarded.
        walk, work, walked = self._step_walk((ckv[0], kr[0]), state["bt"], jnp.where(live, pos, 0))
        order = None if walk == "kernel" else jnp.argsort(jnp.where(live, pos, 0))
        in_order = lambda v: v if order is None else v[order]  # noqa: E731
        live_o, pos_o, bt = in_order(live), in_order(pos), in_order(state["bt"])
        last = jnp.where(live_o, pos_o, 0)
        x = jnp.take(params["embed"], in_order(state["last"]), axis=0)
        page_of = jnp.take_along_axis(bt, (pos_o // P)[:, None], axis=1)[:, 0]
        w_page, off = jnp.where(live_o, page_of, 0), pos_o % P

        def attend(at: int, lp: dict, u):
            with jax.named_scope("mla_decode"):
                qn, qr, c_kv, k_r = self._project(lp, u, pos_o)
                ckv[at] = self._write_pages(ckv[at], w_page, off, c_kv.astype(ckv[at].dtype))
                kr[at] = self._write_keys(kr[at], w_page, off, k_r, runs=False)
                pools = (ckv[at], kr[at])
                return self._attn_out(lp, self._walk_lanes(lp, qn, qr, pools, work)
                                      if walk == "kernel" else
                                      self._attend_lanes(lp, qn, qr, pools, bt, pos_o, last))

        for i in range(self.n_layers):
            lp = params[f"layer{i}"]
            x, st = self._layer(lp, x, live_o,
                                lambda j, u, i=i, lp=lp: attend(2 * i + j, lp[f"attn{j}"], u))
            stats.append(st)
        context = jnp.sum(jnp.where(live, pos + 1, 0))
        if walk == "xla":   # whole key blocks as far as each group's longest lane needs
            walked = jnp.sum(self._group_blocks(last, P, pps)) \
                * self._group(pos.shape[0]) * self._block_pages(P, pps) * P
            x = jnp.take(x, jnp.argsort(order), axis=0)
        acc = self._accumulate(state["acc"], 1, stats, context, context, walked, "absorbed",
                               self._by_walk(walk, jnp.sum(live)))
        return self._emit(params, state, dict(state, ckv=ckv, kr=kr), x, live, pos, acc)

    # -- host side ----------------------------------------------------------------
    def bind_metrics(self, metrics: Any) -> None:
        super().bind_metrics(metrics)
        for ph, counters in zip(GEN_PHASES, self._counters):
            counters.append(
                metrics.counter(f"moe_routed_zero_total{{model={self.name},phase={ph}}}"))


def create(cfg: ModelConfig) -> ShortcutLatentServing:
    return ShortcutLatentServing(cfg)
