"""``decoder``'s sibling for a model whose window layers' softmax has a
learned SINK, whose keys are wider than its values, and whose KV heads go by
the layer's kind (ISSUE 49), built from a published ``config.json`` and served
through the generation engine on ``decoder``'s entry points, scheduler, page
ledger, ring plans and expert layer.

Nothing here knows a model's name. The architecture is read from the JSON file
that ``options.config_file`` names, under the key names its family publishes
(``_arch`` says each one's reading in ``decoder``'s terms):
``hybrid_layer_pattern`` (0 a GLOBAL layer, 1 a WINDOW layer of
``sliding_window`` positions), ``moe_layer_freq`` (0 a dense SwiGLU of
``intermediate_size``, 1 routed experts), and the heads by kind: a global
layer has ``num_attention_heads`` query heads on ``num_key_value_heads`` KV
heads, keys ``head_dim`` and values ``v_head_dim`` wide, rotary base
``rope_theta``; a window layer the same under ``swa_*`` and
``swa_rope_theta``. Of a key's (and a query's) columns the first ``int(head_dim
x partial_rotary_factor)`` turn, in pairs (i, i + dim / 2), and the rest pass.
RMSNorm (``layernorm_epsilon``) before each sublayer and the head; no biases.

THE LAYER, with ``u`` the normed stream: ``q = u W_q`` (H, dk), ``k = u W_k``
(KV_t, dk), ``v = attention_value_scale x (u W_v)`` (KV_t, dv): the factor on
the values BEFORE they are cached; scores ``q . k / sqrt(dk)`` over the keys a
kind sees (all before it; the last ``sliding_window`` with itself), softmax in
float32, ``o = p v`` (H, dv), out ``= concat(o) W_o`` with ``W_o`` (H x dv, d).
THE SINK (``add_swa_attention_sink_bias``: the window layers have one; one on
the global layers, ``add_full_attention_sink_bias``, is refused): a learned
logit a head, float32, that joins the
softmax's denominator and nothing else: ``p_j = exp(a_j - m) / (exp(s - m) +
sum_j' exp(a_j' - m))``, so a row's weights sum to less than 1
(``paged_lm._attend``'s ``sink``). FEED-FORWARD of a sparse layer: ``sigmoid``
scores of the router's float32 logits, the ``num_experts_per_tok`` largest of
score + selection bias (``noaux_tc``, one group: the bias moves picks, never
weights), weights over their own sum times ``routed_scaling_factor`` (null:
1), the HELD experts' part (``share.experts_held``), and NO shared expert
(``n_shared_experts`` null): ``ops/moe.py`` ``topk_route`` and
``held_experts_swiglu``, as ``mla._ffn`` calls them.

THE CACHE, by kind, every leaf's minor dimension whole 128-lane rows. A global
layer keeps a token's K and V in pages of the engine's ledger in THREE leaves:
``kn`` (KV, pages, P, dk - dr) the part of a key that passes, ``kr`` (KV / pack,
pages, P, pack x dr) the part that turns, ``pack`` KV heads side by side in a
row (``paged_lm._kv_pack``: two at 64 columns), and ``vf`` (KV, pages, P, dv):
dk + dv values a token a KV head and no more (a 192-wide leaf would be padded
to 256 on the device). A window layer keeps a slot's last ``sliding_window``
positions in one ring a slot, in three leaves as a key lies in its two parts,
a place a ROW with its heads side by side: ``kwn`` (slots + 1, W, KV x (dk -
dr)), ``kwr`` (slots + 1, W, KV x dr), ``vw`` (slots + 1, W, KV x dv): a token
is ONE row of each to write (laid out by head as the pools are, a token was 20
rows, and the scatter's cost is by row: 5.8 ms a prefill launch and 2.7 ms a
step on the chip, ISSUE 50).

WHERE A STEP ATTENDS (chosen when ``step`` is traced from the backend, the
dtype and the shapes, a kind at a time: ``_walk``; no option). On the TPU in
bfloat16 ONE call of ``ops/lane_attention.py`` ``head_walk`` a layer a step. A
global layer: every live lane over its OWN key blocks by the step's work list
(``_step_plan``: built once for all global layers), all KV heads of a page in
one cell, the softmax's state and the accumulator in fast memory from a lane's
first block to its last; JAX's stock paged-attention kernel takes neither keys
wider than values nor a key in parts (``paged_lm._decode_full``). A window
layer (ISSUE 50): every lane's ring IN PLACE through its ring index, one cell a
lane (``ring_work``), the learned sink an operand that joins the denominator
where the cell divides, the 8 query rows a KV head taken together. Everywhere
else the gather of the padded block table (``_decode_gather``) and of the
rings (``_attend_ring``), exact. Prefill is plain XLA for both kinds: a global
layer walks key blocks (``_prefill_full``), a window layer reads its ring and
the launch's own rows (``decoder``'s ``_prefill_window``).
``attn_walks_total{phase=,walk=kernel|xla}`` counts every attention layer's
lanes (a launch's tiles) by which.

In a trace: ``attn_decode`` is every attention mixer of a step from the three
projections to ``W_o``'s product (``attn_prefill`` in a launch); inside it
``attn_full_walk`` a global layer's page writes and walk, ``attn_ring`` a window
layer's ring write and read (a step: the kernel's call).

NOT SERVED: multi-token-prediction layers and input towers (vision, audio);
requests carry token ids. Requests, weights by recipe, the share and the
served log-probabilities are ``decoder``'s.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.genserve.model import LeafKind, pool, rings
from tpuserve.models import decoder as dec
from tpuserve.models.paged_lm import (Column, Heads, counted, read_config_file, rms_norm,
                                      scoped, series)
from tpuserve.ops import lane_attention as la

# What this family draws otherwise than ``decoder``: sigmoid scores are decided
# by logits of unit scale and a small selection bias (``mla``'s two); a sink is
# drawn inside [sink_low, sink_high], about the logarithm of a full window's
# summed weights at the drawn ``qk`` scale, so that it holds a visible part of
# a row's mass (the configuration file says how much was measured).
DEFAULT_SCALES = {**dec.DEFAULT_SCALES, "router": 1.0, "router_bias": 0.02,
                  "sink_low": 8.0, "sink_high": 12.0}
KINDS = ("full_attention", "sliding_attention")
WALKS = ("kernel", "xla")


class SinkDecoderServing(dec.DecoderServing):
    # ``decoder``'s columns, then the global layers' key rows: the live rows
    # they had to see, the rows of the blocks they read (whole key blocks; the
    # padded table in the gather), and their lanes (a launch's tiles) by walk.
    COLUMNS = (
        *dec.DecoderServing.COLUMNS,
        Column(counted("attended"), series("attn_rows_attended_total")),
        Column(counted("walked"), series("attn_rows_walked_total")),
        *(Column(lambda model, stats, counts, walk=walk: counts["walks"][walk],
                 series("attn_walks_total", f",walk={walk}")) for walk in WALKS))
    scoring = "sigmoid"
    # Key positions a cell of the step's kernel walks: a lane reads whole cells,
    # so small ones follow its context and large ones save cells
    # (``ops/lane_attention.py`` says what a cell costs; PERF.md section 6, PR 49).
    step_keys = 512

    def __init__(self, cfg: ModelConfig) -> None:
        a = read_config_file(cfg)
        for key, want in (("n_group", 1), ("topk_group", 1), ("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("hidden_act", "silu")):
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        if (a.get("rope_scaling") or {}).get("rope_type", "default") != "default":
            raise NotImplementedError(f"{cfg.name}: rope_scaling = {a['rope_scaling']!r}")
        if a.get("add_full_attention_sink_bias", False):
            raise NotImplementedError(f"{cfg.name}: add_full_attention_sink_bias (the global "
                                      "layers' walks take no sink)")
        if "attention_heads" in a.get("share", {}):
            raise NotImplementedError(f"{cfg.name}: share.attention_heads (attention is whole)")
        by_kind = {
            KINDS[0]: Heads(*(int(a[k]) for k in ("num_key_value_heads", "head_dim", "v_head_dim"))),
            KINDS[1]: Heads(*(int(a.get(f"swa_{k}", a[k])) for k in
                              ("num_key_value_heads", "head_dim", "v_head_dim")))}
        if by_kind[KINDS[0]].dk != by_kind[KINDS[1]].dk:
            raise NotImplementedError(f"{cfg.name}: keys of {by_kind[KINDS[0]].dk} and of "
                                      f"{by_kind[KINDS[1]].dk} columns by kind")
        super().__init__(cfg)
        self.by_kind = by_kind
        self.sinks = {KINDS[0]: False, KINDS[1]: bool(a.get("add_swa_attention_sink_bias", False))}
        self.value_scale = float(a.get("attention_value_scale") or 1.0)
        self.turning = {t: self.rope[t][2] for t in self.rope}   # a key's columns that turn
        if any(not 0 < dr < self.hd for dr in self.turning.values()):
            raise NotImplementedError(f"{cfg.name}: partial_rotary_factor = "
                                      f"{a.get('partial_rotary_factor')!r} (a key keeps a part "
                                      "that turns and a part that passes)")
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    def _arch(self, a: dict) -> dict:
        """The published keys under the names ``decoder``'s constructor reads."""
        kinds = [KINDS[1] if int(p) else KINDS[0] for p in a["hybrid_layer_pattern"]]
        heads = {KINDS[0]: int(a["num_attention_heads"]),
                 KINDS[1]: int(a.get("swa_num_attention_heads", a["num_attention_heads"]))}
        rot = float(a.get("partial_rotary_factor", 1.0))
        rope = {kind: {"rope_type": "default", "rope_theta": float(a.get(key, 10000.0)),
                       "partial_rotary_factor": rot}
                for kind, key in zip(KINDS, ("rope_theta", "swa_rope_theta"))}
        out = {
            "attention_bias": a.get("attention_bias", False),
            "hidden_size": a["hidden_size"], "head_dim": a["head_dim"],
            "num_hidden_layers": a["num_hidden_layers"],
            "rms_norm_eps": a.get("layernorm_epsilon", 1e-6),
            "layer_types": kinds, "rope_parameters": rope,
            "mlp_layer_types": ["sparse" if int(f) else "dense" for f in a["moe_layer_freq"]],
            "num_attention_heads": heads[KINDS[0]],
            "num_attention_heads_per_layer": [heads[t] for t in kinds],
            "num_key_value_heads": a["num_key_value_heads"],
            "sliding_window": a.get("sliding_window"),
            "intermediate_size": a["intermediate_size"],
            "num_experts": a.get("n_routed_experts", 0),
            "num_experts_per_tok": a.get("num_experts_per_tok", 0),
            "moe_intermediate_size": a.get("moe_intermediate_size", 0),
            "shared_expert_intermediate_size":
                int(a.get("moe_intermediate_size", 0)) * int(a.get("n_shared_experts") or 0),
            "norm_topk_prob": a.get("norm_topk_prob", True),
            "moe_routed_scaling_factor": a.get("routed_scaling_factor") or 1.0,
            "vocab_size": a["vocab_size"],
            "tie_word_embeddings": a.get("tie_word_embeddings", False),
        }
        return {**out, **{k: a[k] for k in ("share", "weight_scales") if k in a}}

    # -- params and shapes ------------------------------------------------------
    def _heads(self, i: int | None = None) -> Heads:
        """By the layer's kind (no layer named: a global layer's, the pages')."""
        return self.by_kind[KINDS[0] if i is None else self.layer_types[i]]

    def _vectors(self):
        """The routers' selection biases, small and about 0 (``mla``'s), and
        the sinks of the kinds that have one, a logit a query head."""
        b3 = 3.0 * self.scales["router_bias"]
        for i in self.sparse_layers:
            yield ((f"layer{i}", "e_bias"), (self.n_experts,), (self.n_experts,), (0,), -b3, b3)
        for i, kind in enumerate(self.layer_types):
            if self.sinks[kind]:
                h = self.heads_full[i]
                yield ((f"layer{i}", "sink"), (h,), (h,), (0,),
                       self.scales["sink_low"], self.scales["sink_high"])

    def _cache_signature(self, slots: int, pages: int, page_tokens: int) -> dict:
        S = jax.ShapeDtypeStruct
        g, w = self.by_kind[KINDS[0]], self.by_kind[KINDS[1]]
        dr = self.turning.get(KINDS[0], 0)

        def page(width):
            return [S(self._page_shape(pages, page_tokens, g.kv, width), self.dtype)
                    for _ in self.full_layers]

        def ring(width):   # a slot's ring: ``window`` places, a place a row with its heads
            return [S((slots + 1, self.window, w.kv * width), self.dtype)
                    for _ in self.win_layers]

        wr = self.turning.get(KINDS[1], 0)
        # a key's two parts (the one that passes, the one that turns), then the values
        return {"kn": pool(page(g.dk - dr)), "kr": pool(page(dr)), "vf": pool(page(g.dv)),
                "kwn": rings(ring(w.dk - wr)), "kwr": rings(ring(wr)), "vw": rings(ring(w.dv)),
                "ring": S((slots,), jnp.int32)}

    # -- the global layers' walk ------------------------------------------------------
    def _key_block(self, pools: tuple, pg, heads: Heads):
        """A key's two parts joined as a query's columns lie: the part that
        turns, then the part that passes."""
        kn, kr, vf = pools
        dr = heads.dk - kn.shape[-1]
        return (jnp.concatenate([self._pages_by_head(kr, pg, dr),
                                 self._pages_by_head(kn, pg, heads.dk - dr)], axis=-1),
                self._pages_by_head(vf, pg, heads.dv))

    def _walk(self, kind: str, page: int) -> str:
        """Where a step's layers of ``kind`` attend, chosen when the step is
        traced: ``kernel`` on the TPU at shapes ``head_walk`` takes (pages of
        ``page`` positions: a window layer's ring is one), else ``xla``."""
        if jax.default_backend() != "tpu" or kind not in self.layer_types:  # tps-ok[TPS503]
            return "xla"
        i, h, dr = self.layer_types.index(kind), self.by_kind[kind], self.turning[kind]
        fits = la.head_fits(page, self.heads[i], h.kv, h.dk - dr, self._kv_pack(h.kv, dr) * dr,
                            h.dv, self.dtype)
        return "kernel" if fits else "xla"

    def _prefill_plan(self, state, launch, t: dict) -> dict:
        return {**super()._prefill_plan(state, launch, t), "walk": "xla", "ring_walk": "xla",
                "P": self._page_tokens(state), "pps": state["bt"].shape[1]}

    def _step_plan(self, state, live, pos) -> dict:
        """And where each kind attends, chosen once for all its layers, with
        the kernel's work lists. The global layers': each live lane as far as
        ITS position needs; a lane that is not live walks one block and its
        result is discarded. The window layers': one item a lane, its ring (a
        page of ``window`` places, of which the first ``pos + 1`` hold a key
        until the ring is full)."""
        m = super()._step_plan(state, live, pos)
        P, (b, pps) = self._page_tokens(state), m["bt"].shape
        m["walk"], m["ring_walk"] = self._walk(KINDS[0], P), self._walk(KINDS[1], self.window)
        m["work"], m["walked"] = None, b * pps * P
        if m["walk"] == "kernel":
            kb = max(1, min(self.step_keys // P, pps))
            m["work"] = la.work_list(jnp.where(live, pos, 0), m["bt"], P, kb)
            m["walked"] = m["work"]["items"] * kb * P
        if m["ring_walk"] == "kernel":
            m["ring_work"] = la.ring_work(
                m["w_ring"], jnp.where(live, jnp.minimum(pos, self.window - 1), 0))
        return m

    def _attend_global(self, q, k, v, pools: tuple, m: dict, heads: Heads):
        """A global layer's attention in either phase: the launch's rows into
        the three pools, then the tiles' walks in XLA, or the lanes' decode in
        the kernel or the gather -> (o (T, H, dv), the pools)."""
        kn, kr, vf = pools
        dr, t = heads.dk - kn.shape[-1], m["t"]
        kr = self._write_pages(kr, m["w_page"], m["off"], k[..., :dr])
        kn = self._write_pages(kn, m["w_page"], m["off"], k[..., dr:])
        vf = self._write_pages(vf, m["w_page"], m["off"], v)
        pools = (kn, kr, vf)
        if t is not None:
            o = self._prefill_full_tiles(q.reshape((t["K"], t["T"]) + q.shape[1:]), pools, t, heads)
            return o.reshape(q.shape[:-1] + (heads.dv,)), pools
        if m["walk"] == "kernel":
            q_turn = self._pad_queries(q[..., :dr], heads.kv, heads.kv // kr.shape[0])
            o = la.head_walk(q[..., dr:], q_turn, kn, kr, vf, m["work"], scale=self._scale())
            return o.astype(jnp.float32), pools
        return self._decode_gather(q, pools, m["bt"], m["pos"], heads), pools

    def _attend_ring(self, q, k, v, rings: tuple, m: dict, sink, heads: Heads):
        """A window layer's attention in either phase -> (o (T, H, dv), the
        three rings: a key's part that passes, its part that turns, the
        values, each (slots + 1, W, KV x width), a place a row with its heads
        side by side, so a token is ONE row of each to write). A step writes
        its row and reads its ring: in ``head_walk`` where the plan says so, IN
        PLACE through the lane's ring index with the sink as its operand, else
        gathered in XLA (a free lane reads ring 0, which every free lane
        writes: its result is discarded). A launch reads what the rings held
        before it and itself, then writes."""
        dr, t, w_ring, roff = self.turning[KINDS[1]], m["t"], m["w_ring"], m["roff"]
        new = (k[..., dr:], k[..., :dr], v)

        @scoped("cache_write")
        def put():
            return tuple(ring.at[w_ring, roff].set(rows.reshape(rows.shape[0], -1))
                         for ring, rows in zip(rings, new))

        def held(rings, at):   # keys (n, W, KV, dk) as a query's columns lie, values (n, W, KV, dv)
            by_head = at.shape + (self.window, heads.kv, -1)
            kn, kr, vw = (jnp.take(ring, at, axis=0).reshape(by_head) for ring in rings)
            return jnp.concatenate([kr, kn], axis=-1), vw

        if t is not None:
            o = self._prefill_window(q.reshape((t["K"], t["T"]) + q.shape[1:]), k, v,
                                     *held(rings, t["rings"]), t["qpos"], m["rpos"], m["pos"],
                                     m["own"], sink)
            return o.reshape(q.shape[:-1] + o.shape[-1:]), put()
        rings = put()
        if m["ring_walk"] == "kernel":
            q_turn = self._pad_queries(q[..., :dr], heads.kv, self._kv_pack(heads.kv, dr))
            o = la.head_walk(q[..., dr:], q_turn, *rings, m["ring_work"], scale=self._scale(),
                             kv=heads.kv, sink=sink)
            return o.astype(jnp.float32), rings
        return self._attend(q[:, None], *held(rings, w_ring), m["mask_win"], sink)[:, 0], rings

    # -- the layer, its counts ---------------------------------------------------------
    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        full = self.layer_types[i] == KINDS[0]
        leaves, j = (self._leaves(LeafKind.POOL), self.full_layers.index(i)) if full \
            else (self._leaves(LeafKind.RINGS), self.win_layers.index(i))
        with jax.named_scope(dec._attn_scope(m["t"])):
            q, k, v, _ = self._qkv(lp, i, rms_norm(x, lp["norm1"], self.eps), m["pos"])
            held = tuple(c[leaf][j] for leaf in leaves)
            if full:
                with jax.named_scope("attn_full_walk"):
                    o, held = self._attend_global(q, k, v, held, m, self._heads(i))
            else:
                with jax.named_scope("attn_ring"):
                    o, held = self._attend_ring(q, k, v, held, m, lp.get("sink"),
                                                  self._heads(i))
            for leaf, pool in zip(leaves, held):
                c[leaf][j] = pool
            y = self._attn_out(lp, o, None)
        x = x + y.astype(self.dtype)
        y, st = self._ffn(lp, i, rms_norm(x, lp["norm2"], self.eps), m["live"])
        return x + y.astype(self.dtype), st

    def _counts(self, m: dict) -> dict:
        """And the global layers' key rows (attended, walked), and every
        attention layer's lanes (a launch's tiles) by the walk its kind took:
        the global layers' over their pages and the window layers' of their
        rings."""
        c, t, n = super()._counts(m), m["t"], len(self.full_layers)
        each = jnp.sum(m["live"] if t is None else t["has"])
        c = {**c, "walks": {walk: each * ((m["walk"] == walk) * n
                                          + (m["ring_walk"] == walk) * len(self.win_layers))
                            for walk in WALKS}}
        if t is None:
            return {**c, "attended": c["context"] * n, "walked": m["walked"] * n}
        P, pps = m["P"], m["pps"]
        blocks = jnp.sum(jnp.where(t["has"], self._blocks_needed(t["last"], P, pps), 0))
        attended = jnp.sum(jnp.where(m["length"] > 0, m["start"] + m["length"], 0))
        return {**c, "attended": attended * n, "walked": blocks * self._block_pages(P, pps) * P * n}


def create(cfg: ModelConfig) -> Any:
    return SinkDecoderServing(cfg)
