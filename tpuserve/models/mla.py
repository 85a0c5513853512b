"""A decoder-only language model with LATENT ATTENTION (multi-head latent
attention, MLA) and routed SwiGLU experts, built from a published
``config.json`` (ISSUE 34) and served through the generation engine with a
paged cache of ONE compressed row a token a layer.

Nothing here knows a model's name. The architecture is read, under the
published key names, from the JSON file that ``options.config_file`` names.
Layer ``i``: ``x <- x + attention(RMSNorm(x; g1))``, ``x <- x +
ffn(RMSNorm(x; g2))``, eps ``rms_norm_eps``, no biases, an untied head.

ATTENTION, with ``u`` the normed stream at position ``t``:
``c_q = RMSNorm(u W_qa; g_q)`` (``q_lora_rank``); ``q = c_q W_qb``, a head
``[q_nope (qk_nope_head_dim) | q_rope (qk_rope_head_dim)]``, ``q_rope <-
RoPE(q_rope, t)``; ``[c_kv | k_r] = u W_kva`` (``kv_lora_rank`` | rope);
``c_kv <- RMSNorm(c_kv; g_kv)``; ``k_r <- RoPE(k_r, t)``, ONE rotary key for
every head; ``[k_nope_h | v_h] = c_kv W_kvb``. ``score_h(t, s) = (q_nope_h(t) .
k_nope_h(s) + q_rope_h(t) . k_r(s)) / sqrt(qk_nope + qk_rope)``, causal
softmax in float32, ``o_h = sum_s p v_h(s)``, out ``= concat_h(o_h) W_o``.
Rotary: ``rope_theta`` over the rotary columns, plain where ``rope_scaling`` is
null; with ``rope_interleave`` the columns (2i, 2i + 1) turn as a pair. A yarn
``rope_scaling`` is read in DeepSeek's convention (``_read_attention``): yarn's
frequencies, cos and sin times ``m(mscale) / m(mscale_all_dim)`` and EVERY
score times ``m(mscale_all_dim)^2``, with ``m(a) = 0.1 a ln(factor) + 1``.

THE CACHE: the normed ``c_kv`` and the rotated ``k_r`` of a token, one row of
``kv_lora_rank + qk_rope_head_dim`` values a layer, in pages of the engine's
ledger; nothing by head. The row lies in two leaves, each a whole number of
128-lane rows: ``ckv`` (pages, P, kv_lora_rank) and ``kr`` (pages, P / g, g x
rope), ``g`` positions' rotary keys side by side in one row of 128 lanes (2
at the published 64). Why not one leaf of 576: 4.5 lanes of 128 is not a
layout the v5e compiler keeps: it turned every layer's whole pool to another
layout before the walk and back after it, twice 0.44 GiB a layer a launch (a
compile of the cell's programs for a described v5e, PR 34); widths that are
multiples of 128 stay as they lie. A prefill launch writes its rotary keys
``g`` positions at a time (a piece begins on a tile's edge); a decode step
reads a lane's row of 128, sets its own part and writes the row back.

TWO FORMS OF ONE FUNCTION, over the same walk of a block table in key blocks
(as many as a tile's last live position needs). *Absorbed*: ``q_lat_h = q_nope_h (W_kvb^K_h)^T``,
``score = [q_lat_h | q_rope_h] . [c_kv | k_r](s)``, ``o_h = (sum_s p c_kv(s))
W_kvb^V_h``: the cache is read as it lies, every head over one row whose
first ``kv_lora_rank`` columns are also the value; ``2 H (2 r + rope)``
operations a (query, key) pair. *Expanded*: a key block's ``k_nope`` and ``v``
are made from its latents, ``2 r H (nope + v)`` operations a key ONCE A TILE,
then ``2 H (nope + rope + v)`` a pair. ``_form`` takes the one with the fewer
operations at a tile's static width when the program is traced: a decode
step (a tile of one query a lane) is absorbed; a prefill tile of
``TILE_ROWS`` rows is expanded, and that is why this family's tiles are that
wide (at the published sizes the forms break even at 171 rows).

WHERE THE WALK RUNS (``_walk``, chosen when a program is traced from what it
observes: the form, the tile's width, the backend, the dtype and the shapes;
no option). On the TPU in bfloat16, at shapes the kernels take, both walks
stay on the chip and read the pages of the two pools as they lie through the
block table, each page once. An EXPANDED TILE walks in ONE call of
``ops/tile_attention.py``: the kernel's sequential grid axis runs over the
tile's key blocks, it makes ``k_nope`` and ``v`` on the chip, keeps the
softmax's state and the accumulator in fast memory from the first block to
the last and writes the tile's context once (ISSUE 43). A STEP walks in ONE
call an attention of ``ops/lane_attention.py`` for every lane (ISSUE 44): the
step builds, once for all its attentions, the list of (lane, key block) items
that exist (``_step_walk``: each lane as far as ITS position needs), the
kernel's one sequential grid axis runs over that list, a lane's softmax state
and its (H, r) float32 accumulator stay in fast memory from its first block
to its last, and a page's latents serve both products; ``q_lat = q_nope
W_kb^T`` before the call and ``ctx W_vb`` after it are whole-batch products
in XLA (``_walk_lanes``). Everything else (the CPU, float32, a shape a kernel
does not take) walks in XLA: ``paged_lm._over_key_blocks``, a ``fori_loop`` of
gathers and einsums under a running softmax, one a tile and, in a step, one a
lane after another: the exact fallbacks.
``mla_tiles_total{phase=,walk=kernel|xla}`` counts a launch's tiles and a
step's live lanes by which.

FEED-FORWARD: the first ``first_k_dense_replace`` layers a dense SwiGLU of
``intermediate_size``; the others ``s = sigmoid(u W_r)`` in float32, the
``num_experts_per_tok`` largest of ``s + b`` (``noaux_tc``: the bias moves
picks, not weights; one group), weights over their own sum times
``routed_scaling_factor``, SwiGLU experts of ``moe_intermediate_size`` plus a
shared expert of ``n_shared_experts`` times that on the same ``u``
(``tpuserve.ops.moe``). Every expert is held: the family takes no ``share``.

NOT SERVED: a multi-token-prediction module (``num_nextn_predict_layers``).
Requests, weights by recipe and the served log-probabilities are
``decoder``'s (``paged_lm``).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.genserve.model import pool
from tpuserve.models.decoder import apply_rope, rope_inv_freq
from tpuserve.models.paged_lm import (COMPACT_COLUMN, CONTEXT_COLUMN,  # noqa: F401
                                      EXPERT_COLUMNS, KEY_BLOCK, LOGPROBS, NEG, Column,
                                      SAMPLE_COLUMNS, PagedLM, _mm, counted, read_config_file,
                                      rms_norm, scoped, series)
from tpuserve.ops import lane_attention as la
from tpuserve.ops import tile_attention as ta
from tpuserve.ops.moe import held_experts_swiglu, router_logits, topk_route

# Standard deviations of the drawn tensors, by role (``weight_scales`` in the
# config file overrides any). The query's up-projection, the keys'
# up-projection and the rotary key are drawn wider (2x: scores of standard
# deviation 4), so that attention is decided over thousands of keys and what
# the cache holds is decisive.
DEFAULT_SCALES = {
    "embed": 1.0, "head": 1.0, "q_a": 1.0, "q_b": 2.0, "kv_a": 1.0, "k_rope": 2.0,
    "k_b": 2.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0, "ffn_out": 1.0, "expert_out": 1.0,
    "router": 1.0, "router_bias": 0.02,
}
FORMS = ("absorbed", "expanded")
WALKS = ("kernel", "xla")


def yarn_magnitudes(rs: dict) -> tuple[float, float]:
    """A yarn ``rope_scaling`` in DeepSeek's convention (the family whose key
    names these are) -> (the factor on cos and sin, the factor on every
    score): with ``m(a) = 0.1 a ln(factor) + 1``, ``m(mscale) /
    m(mscale_all_dim)`` and ``m(mscale_all_dim)^2``. THE MAGNITUDE GOES ON
    THE SCORE: ``rope_inv_freq``'s own default, ``0.1 ln(factor) + 1`` on cos
    and sin, would square onto the rotary part of a score only."""
    factor = float(rs["factor"])

    def m(a: float) -> float:
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 and a else 1.0

    all_dim = float(rs.get("mscale_all_dim", 0) or 0)
    return m(float(rs.get("mscale", 1))) / m(all_dim), m(all_dim) ** 2


class LatentServing(PagedLM):
    # The expert layers' four and the context (as ``decoder``), then cache rows
    # attended over (each once a piece or a lane: the least a launch reads),
    # cache rows the walk gathered (whole key blocks a tile or a lane, a
    # layer), launches by form, expert layers whose dispatch took the compact
    # branch (none where every expert is held), and the tiles whose walk over
    # key blocks ran in the kernel and in XLA (``_walk``: a launch's tiles of a
    # piece; a step's live lanes; every attention of a launch walks alike, so a
    # tile counts once), and the steps by the sampler's branch.
    COLUMNS = (
        *EXPERT_COLUMNS, CONTEXT_COLUMN,
        Column(counted("attended"), series("mla_rows_attended_total")),
        Column(counted("walked"), series("mla_rows_walked_total")),
        *(Column(lambda model, stats, counts, form=form: counts["form"] == form,
                 series("mla_launches_total", f",form={form}")) for form in FORMS),
        COMPACT_COLUMN,
        *(Column(lambda model, stats, counts, walk=walk:
                 counts["tiles"] if counts["walk"] == walk else 0,
                 series("mla_tiles_total", f",walk={walk}")) for walk in WALKS),
        *SAMPLE_COLUMNS)
    TILE_ROWS = KEY_BLOCK
    # Key positions a cell of the step's kernel walks (``ops/lane_attention.py``
    # says what a cell costs): at contexts of thousands a prefill tile's key
    # block, and anything from there up reads alike (PERF.md section 6, PR 44).
    step_keys = KEY_BLOCK
    # The one value the family takes of a key that names a mechanism; any other is refused.
    TAKES = (("attention_bias", False), ("n_group", 1), ("topk_group", 1),
             ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
             ("hidden_act", "silu"), ("moe_layer_freq", 1), ("share", None))

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.dtype = jnp.dtype(cfg.dtype)
        for key, want in self.TAKES:
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        if not a.get("q_lora_rank"):
            raise NotImplementedError(f"{cfg.name}: q_lora_rank = {a.get('q_lora_rank')!r} "
                                      "(queries without a low-rank projection)")
        self.n_layers = int(a["num_hidden_layers"])
        self.eps = float(a.get("rms_norm_eps", 1e-6))
        self._read_attention(a)
        self.first_dense = int(a.get("first_k_dense_replace", 0))
        self.sparse_layers = list(range(self.first_dense, self.n_layers))
        self.dense_width = int(a["intermediate_size"])
        self.n_experts = int(a.get("n_routed_experts", 0))
        self.e_first, self.e_count = 0, self.n_experts   # one chip a layer whole
        self.top_k = int(a.get("num_experts_per_tok", 0))
        self.expert_width = int(a.get("moe_intermediate_size", 0))
        self.shared_width = self.expert_width * int(a.get("n_shared_experts", 0))
        self.norm_topk = bool(a.get("norm_topk_prob", True))
        self.route_scale = float(a.get("routed_scaling_factor", 1.0))
        self.vocab_full = self.vocab = int(a["vocab_size"])
        self.v_first = 0
        self.tied = bool(a.get("tie_word_embeddings", False))
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self._serve_options(cfg, a)

    def _read_attention(self, a: dict) -> None:
        """The attention's numbers, under the key names every published
        latent-attention config shares."""
        self.d = int(a["hidden_size"])
        self.heads = int(a["num_attention_heads"])
        self.q_rank, self.r = int(a["q_lora_rank"]), int(a["kv_lora_rank"])
        self.dn, self.dr, self.dv = (int(a[k]) for k in (
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        self.rope_interleave = bool(a.get("rope_interleave", False))
        # What every score is multiplied by, the nope and the rope part alike:
        # both walks in XLA and both kernels' ``scale=`` take it from here.
        self.score_scale = (self.dn + self.dr) ** -0.5
        rp, rs = {"rope_theta": float(a.get("rope_theta", 10000.0))}, a.get("rope_scaling")
        if rs is not None:
            if rs.get("type", rs.get("rope_type")) != "yarn":
                raise NotImplementedError(f"{self.name}: rope_scaling = {rs!r}")
            on_cos_sin, on_score = yarn_magnitudes(rs)
            rp.update({k: rs[k] for k in ("factor", "original_max_position_embeddings",
                                          "beta_fast", "beta_slow") if k in rs},
                      rope_type="yarn", attention_factor=on_cos_sin)
            self.score_scale *= on_score
        self.rope = rope_inv_freq(rp, self.dr)

    # -- params ---------------------------------------------------------------
    # Factors on the two latents after their norms (a config that scales them
    # says so; 1: none, and the program is the one without them).
    q_scale = kv_scale = 1.0
    groups = None   # (n_group, topk_group) where the picks are group-limited (``mla_sel``)

    def _attentions(self):
        """The path of every attention's tensors: one a layer, beside the
        layer's others."""
        return [(f"layer{i}",) for i in range(self.n_layers)]

    def _gains(self):
        yield ("norm_f",), (self.d,)
        for i in range(self.n_layers):
            yield (f"layer{i}", "norm1"), (self.d,)
            yield (f"layer{i}", "norm2"), (self.d,)
            yield from self._attention_gains((f"layer{i}",))

    def _attention_gains(self, at: tuple):
        yield (*at, "q_norm"), (self.q_rank,)
        yield (*at, "kv_norm"), (self.r,)

    def _attention_tensors(self, at: tuple):
        """One attention's matrices under the path ``at``. ``W_qb`` and
        ``W_kva`` are drawn in their two parts (``w_qb_nope`` | ``w_qb_rope``,
        ``w_kva_c`` | ``w_kva_r``), ``W_kvb`` in its key and its value side
        (``w_kb``, ``w_vb``), each a tensor of its own; ``draw_params`` joins
        the first two pairs."""
        d, s, h = self.d, self.scales, self.heads
        for name, shape, role, fan_in in (
                ("w_qa", (d, self.q_rank), "q_a", d),
                ("w_qb_nope", (self.q_rank, h, self.dn), "q_b", self.q_rank),
                ("w_qb_rope", (self.q_rank, h, self.dr), "q_b", self.q_rank),
                ("w_kva_c", (d, self.r), "kv_a", d),
                ("w_kva_r", (d, self.dr), "k_rope", d),
                ("w_kb", (self.r, h, self.dn), "k_b", self.r),
                ("w_vb", (self.r, h, self.dv), "v", self.r),
                ("wo", (h, self.dv, d), "o", h * self.dv)):
            yield (*at, name), shape, shape, (0,) * len(shape), s[role], fan_in

    def _tensors(self):
        """(path, shape, full shape, start, role, fan-in) of every matrix, in
        a fixed order."""
        d, s = self.d, self.scales

        def whole(path, shape, role, fan_in):
            return path, shape, shape, (0,) * len(shape), s[role], fan_in

        yield from self._vocab_tensors()
        for i in range(self.n_layers):
            L = f"layer{i}"
            yield from self._attention_tensors((L,))
            if i < self.first_dense:
                f = self.dense_width
                for name in ("w_gate", "w_up"):
                    yield whole((L, name), (d, f), "ffn_in", d)
                yield whole((L, "w_down"), (f, d), "ffn_out", f)
                continue
            fs = self.shared_width
            yield whole((L, "router"), (d, self.n_experts), "router", d)
            yield from self._expert_tensors(L, whole)
            for name in ("s_gate", "s_up"):
                yield whole((L, name), (d, fs), "ffn_in", d)
            yield whole((L, "s_down"), (fs, d), "ffn_out", fs)

    def _expert_tensors(self, L: str, whole):
        """Layer ``L``'s routed experts: every one held, three tensors a layer."""
        d, e, f = self.d, self.n_experts, self.expert_width
        for name in ("e_gate", "e_up"):
            yield whole((L, name), (e, d, f), "ffn_in", d)
        yield whole((L, "e_down"), (e, f, d), "expert_out", f)

    def _vectors(self):
        """The router's selection bias: small, about 0, so that it changes
        some picks."""
        b3 = 3.0 * self.scales["router_bias"]
        for i in self.sparse_layers:
            yield ((f"layer{i}", "e_bias"), (self.n_experts,), (self.n_experts,), (0,), -b3, b3)

    def draw_params(self, seed: int) -> Any:
        p = super().draw_params(seed)
        for at in self._attentions():
            lp = p
            for key in at:
                lp = lp[key]
            lp["w_qb"] = jnp.concatenate([lp.pop("w_qb_nope"), lp.pop("w_qb_rope")], axis=2)
            lp["w_kva"] = jnp.concatenate([lp.pop("w_kva_c"), lp.pop("w_kva_r")], axis=1)
        return p

    # -- shapes -----------------------------------------------------------------
    def _cache_signature(self, slots: int, pages: int, page_tokens: int) -> dict:
        S = jax.ShapeDtypeStruct
        g = 128 // self.dr if 128 % self.dr == 0 else 1   # positions a row of 128 lanes
        g = g if page_tokens % g == 0 else 1
        return {"ckv": pool([S((pages, page_tokens, self.r), self.dtype)
                             for _ in self._attentions()]),
                "kr": pool([S((pages, page_tokens // g, g * self.dr), self.dtype)
                            for _ in self._attentions()])}

    # -- device math --------------------------------------------------------------
    def _form(self, tile_rows: int) -> str:
        """Which form a tile of ``tile_rows`` queries takes: the one with the
        fewer operations a key (module docstring)."""
        h, r = self.heads, self.r
        expanded = 2 * r * h * (self.dn + self.dv) \
            + tile_rows * 2 * h * (self.dn + self.dr + self.dv)
        return "expanded" if expanded < tile_rows * 2 * h * (2 * r + self.dr) else "absorbed"

    @staticmethod
    def _gain(gain: jax.Array, factor: float) -> jax.Array:
        """A norm's gain times the latent's factor, in float32 (the norm
        multiplies there, before it rounds to the served type); the gain
        itself where the factor is 1."""
        return gain if factor == 1.0 else gain.astype(jnp.float32) * jnp.float32(factor)

    @scoped("proj")
    def _query_latent(self, lp: dict, u: jax.Array) -> jax.Array:
        """``c_q`` (T, q_lora_rank), the query's normed latent."""
        return rms_norm(_mm(u, lp["w_qa"]).astype(self.dtype),
                        self._gain(lp["q_norm"], self.q_scale), self.eps)

    @scoped("proj")
    def _project(self, lp: dict, u: jax.Array, pos: jax.Array, c_q: jax.Array | None = None):
        """``u`` (T, d) normed stream at positions ``pos`` (T,) -> q_nope (T, H,
        nope), rotated q_rope (T, H, rope), and what a token keeps: the normed
        ``c_kv`` (T, r) and the rotated ``k_r`` (T, rope). ``q_scale`` (on the
        query's latent: ``W_qb`` is linear, so both parts of ``q`` carry it)
        and ``kv_scale`` (on ``c_kv`` before ``W_kvb``, so the CACHED row
        carries it and ``k_r`` does not) ride on the two norms' gains. ``c_q``:
        the query's latent where the caller has it already."""
        dt = self.dtype
        inv, factor, dim = self.rope
        c_q = self._query_latent(lp, u) if c_q is None else c_q
        q = jnp.einsum("tq,qhk->thk", c_q, lp["w_qb"],
                       preferred_element_type=jnp.float32).astype(dt)
        kva = _mm(u, lp["w_kva"]).astype(dt)
        c_kv = rms_norm(kva[:, :self.r], self._gain(lp["kv_norm"], self.kv_scale), self.eps)
        k_r = apply_rope(kva[:, None, self.r:], pos, inv, factor, dim,
                         self.rope_interleave)[:, 0]
        q_rope = apply_rope(q[..., self.dn:], pos, inv, factor, dim, self.rope_interleave)
        return q[..., :self.dn], q_rope, c_kv, k_r

    @scoped("cache_write")
    def _write_keys(self, pool, page, off, k_r, runs: bool):
        """Rotary keys ``k_r`` (T, rope) into their pool (pages, P / g, g x
        rope) at (page[t], off[t]). ``runs`` (a prefill launch): the rows come
        in runs of g positions that begin on a row's edge, written g at a
        time; a run that a prompt's end cuts writes its padding beside the
        last key, at a position no query sees before a step writes it. Else
        (a step: one position a lane) each lane's row is read, its own part
        set and the row written back."""
        n, rows, lanes = pool.shape
        g = lanes // self.dr
        flat = pool.reshape(n * rows, lanes)
        at = page * rows + off // g
        k_r = k_r.astype(pool.dtype)
        if runs:
            return flat.at[at[0::g]].set(k_r.reshape(-1, lanes)).reshape(pool.shape)
        mine = (jnp.arange(lanes)[None, :] // self.dr) == (off % g)[:, None]
        return flat.at[at].set(jnp.where(mine, jnp.tile(k_r, (1, g)), flat[at])).reshape(pool.shape)

    def _walk(self, form: str, T: int, pools, pps: int) -> str:
        """Where a tile of ``T`` queries walks its key blocks, chosen when the
        program is traced: ``kernel`` (on the TPU at shapes a kernel takes: an
        expanded tile, one call a tile, ``ops/tile_attention.py``; a step's
        absorbed tiles of one query, one call an attention for every lane,
        ``ops/lane_attention.py``) or ``xla`` (everything else:
        ``_over_key_blocks``)."""
        ckv, kr = pools
        P = ckv.shape[1]
        if jax.default_backend() != "tpu":  # tps-ok[TPS503]: at trace time
            return "xla"
        if form == "expanded":
            fits = ta.fits(T, P, self._block_pages(P, pps), self.r, self.dn, self.dv,
                           kr.shape[2], self.dtype)
        else:
            fits = T == 1 and la.fits(P, self.r, kr.shape[2], self.dtype)
        return "kernel" if fits else "xla"

    def _attend_tile(self, lp: dict, qn, qr, pools, row, qpos, last, form: str, keep=None):
        """One tile's attention: q_nope ``qn`` (T, H, nope) and rotated q_rope
        ``qr`` (T, H, rope) at positions ``qpos`` (T,), over the latent rows of
        its prompt's pages (``pools``: the layer's ``ckv`` and ``kr``;
        block-table row ``row``) up to position ``last``, in the ``form``
        given -> (T, H, v): float32 from the walk in XLA, the served type from
        the kernel (``_attn_out`` rounds to it either way). Every row of the
        launch is in the pages before any tile reads them. ``keep``, or None:
        attention over picks (``mla_sel``): for the walk in XLA (T, key blocks
        x c) float32, a row attends a key only where it is above 0; for the
        kernel the pair ``tile_walk`` takes, the rows' index scores and a
        threshold a row."""
        dt, r, h = self.dtype, self.r, self.heads
        ckv, kr = pools
        T, P = qn.shape[0], ckv.shape[1]
        kb, rowp = self._key_blocks(row, P)
        need, c = self._blocks_needed(last, P, row.shape[0]), kb * P
        f32 = {"preferred_element_type": jnp.float32}
        scale = self.score_scale
        if form == "absorbed":
            with jax.named_scope("proj"):
                qn = jnp.einsum("thn,rhn->thr", qn, lp["w_kb"], **f32).astype(dt)   # q_lat

        def latents(j):
            pg = jax.lax.dynamic_slice(rowp, (j * kb,), (kb,))
            return (jnp.take(ckv, pg, axis=0).reshape(c, r).astype(dt),
                    jnp.take(kr, pg, axis=0).reshape(c, self.dr).astype(dt))

        # On the TPU an expanded tile's whole walk is ONE kernel call, which
        # reads the pages through ``rowp`` and keeps the running softmax, the
        # accumulator and the expanded keys on the chip
        # (``ops/tile_attention.py``). A tile's positions are consecutive, so
        # ``qpos[0]`` is all it needs of them.
        if self._walk(form, T, pools, row.shape[0]) == "kernel":
            q = jnp.concatenate([qn] + [qr] * (P // kr.shape[1]), axis=-1).transpose(1, 0, 2)
            w_kvb = jnp.concatenate([lp["w_kb"], lp["w_vb"]], axis=-1).transpose(1, 0, 2)
            return ta.tile_walk(q, w_kvb, ckv, kr, rowp, need, qpos[0], block_pages=kb,
                                scale=scale, **({} if keep is None else {"keep": keep}))

        def block(j):
            c_kv, k_r = latents(j)
            see = (j * c + jnp.arange(c))[None, :] <= qpos[:, None]
            if keep is not None:
                see = see & (jax.lax.dynamic_slice(keep, (0, j * c), (T, c)) > 0)
            s = jnp.einsum("thd,cd->htc", qr, k_r, **f32)
            if form == "absorbed":
                s = s + jnp.einsum("thr,cr->htc", qn, c_kv, **f32)
                val, weigh = c_kv, "htc,cr->htr"
            else:
                k_nope = jnp.einsum("cr,rhn->chn", c_kv, lp["w_kb"], **f32).astype(dt)
                val = jnp.einsum("cr,rhv->chv", c_kv, lp["w_vb"], **f32).astype(dt)
                s = s + jnp.einsum("thn,chn->htc", qn, k_nope, **f32)
                weigh = "htc,chv->htv"
            return jnp.where(see[None], s * scale, NEG), \
                lambda p: jnp.einsum(weigh, p.astype(dt), val, **f32)

        o = self._over_key_blocks(need, (h, T), r if form == "absorbed" else self.dv, block)
        if form == "absorbed":
            with jax.named_scope("proj"):
                return jnp.einsum("htr,rhv->thv", o.astype(dt), lp["w_vb"], **f32)
        return o.transpose(1, 0, 2)

    def _attend_tiles(self, lp: dict, qn, qr, pools, t: dict, form: str):
        """A launch's attention, tile by tile (``t``: ``_tiles``): ``qn`` (C, H,
        nope), ``qr`` (C, H, rope) -> (C, H, v)."""
        K, T = t["K"], t["T"]
        tiles = (qn.reshape((K, T) + qn.shape[1:]), qr.reshape((K, T) + qr.shape[1:]),
                 t["rows"], t["qpos"], t["last"])
        one = lambda a: self._attend_tile(lp, *a[:2], pools, *a[2:], form)  # noqa: E731
        if self._walk(form, T, pools, t["rows"].shape[1]) == "kernel":
            # a kernel call a tile, side by side: nothing loops around them
            return jnp.concatenate([one([v[k] for v in tiles]) for k in range(K)])
        o = jax.lax.map(one, tiles)
        return o.reshape((K * T,) + o.shape[2:])

    def _step_walk(self, pools, bt, last):
        """A step's walk, chosen once for all its attentions (``_walk`` at a
        tile of one query): (``kernel`` or ``xla``, the kernel's work list or
        None, the cache rows the walk reads an attention: whole key blocks of
        each lane's own need, of ``step_keys`` positions in the kernel)."""
        P, pps = pools[0].shape[1], bt.shape[1]
        walk = self._walk("absorbed", 1, pools, pps)
        if walk == "kernel":
            kb = max(1, min(self.step_keys // P, pps))
            work = la.work_list(last, bt, P, kb)
            return walk, work, work["items"] * kb * P
        return walk, None, jnp.sum(self._blocks_needed(last, P, pps)) * self._block_pages(P, pps) * P

    def _walk_lanes(self, lp: dict, qn, qr, pools, work: dict, keep=None):
        """A step's attention in ONE kernel call (``ops/lane_attention.py``),
        absorbed: q_nope ``qn`` (B, H, nope) and rotated q_rope ``qr`` (B, H,
        rope), every lane over its own key blocks by the step's work list ->
        (B, H, v) float32. The two whole-batch products around the call stay
        XLA's. ``keep`` (B, key blocks x c) float32, or None: a lane attends a
        key only where it is above 0 (``mla_sel``)."""
        ckv, kr = pools
        f32 = {"preferred_element_type": jnp.float32}
        with jax.named_scope("proj"):
            q_lat = jnp.einsum("bhn,rhn->bhr", qn, lp["w_kb"], **f32).astype(self.dtype)
        o = la.lane_walk(q_lat, jnp.concatenate([qr] * (ckv.shape[1] // kr.shape[1]), axis=-1),
                         ckv, kr, work, scale=self.score_scale,
                         **({} if keep is None else {"keep": keep}))
        with jax.named_scope("proj"):
            return jnp.einsum("bhr,rhv->bhv", o, lp["w_vb"], **f32)

    @scoped("proj")
    def _attn_out(self, lp, o):
        return jnp.einsum("thv,hvd->td", o.astype(self.dtype), lp["wo"],
                          preferred_element_type=jnp.float32)

    def _ffn(self, lp, i, u, live):
        """(T, d) -> ((T, d) float32, the expert layer's counts or None)."""
        if i < self.first_dense:
            return self._swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        with jax.named_scope("moe_layer"):
            r = router_logits(u, lp["router"])
            w, e = topk_route(r, self.top_k, normalize=self.norm_topk, scale=self.route_scale,
                              scoring="sigmoid", select_bias=lp["e_bias"],
                              **({"groups": self.groups} if self.groups else {}))
            y, stats = held_experts_swiglu(u, w, e, self.e_first, lp["e_gate"], lp["e_up"],
                                           lp["e_down"], live=live, of=self.n_experts)
        return y + self._swiglu(u, lp["s_gate"], lp["s_up"], lp["s_down"]), stats

    # -- what a launch works out once, its layer, its counts -----------------------------
    def _prefill_plan(self, state, launch, t: dict) -> dict:
        """And the form and the walk of the launch's tiles."""
        pools, pps = (state["ckv"][0], state["kr"][0]), state["bt"].shape[1]
        form = self._form(t["T"])
        return {**super()._prefill_plan(state, launch, t), "scope": "mla_prefill", "form": form,
                "walk": self._walk(form, t["T"], pools, pps), "P": pools[0].shape[1], "pps": pps}

    def _step_plan(self, state, live, pos) -> dict:
        """And the step's walk, chosen once for all its attentions. A lane
        that is not live walks one block of whatever its row names: its
        result is discarded."""
        m = super()._step_plan(state, live, pos)
        last = jnp.where(live, pos, 0)
        walk, work, walked = self._step_walk((state["ckv"][0], state["kr"][0]), m["bt"], last)
        return {**m, "scope": "mla_decode", "form": self._form(1), "last": last, "walk": walk,
                "work": work, "walked": walked}

    def _attention(self, lp: dict, u, at: int, c: dict, m: dict):
        """Attention ``at`` of the model on the normed stream ``u`` in the
        phase the plan ``m`` is of: the rows' latents into the pages, then
        the launch's tiles (``_attend_tiles``) or the step's lanes, in the
        kernel (``_walk_lanes``) or one after another in XLA -> (T, d)
        float32."""
        t = m["t"]
        qn, qr, c_kv, k_r = self._project(lp, u, m["pos"])
        c["ckv"][at] = self._write_pages(c["ckv"][at], m["w_page"], m["off"],
                                         c_kv.astype(c["ckv"][at].dtype))
        c["kr"][at] = self._write_keys(c["kr"][at], m["w_page"], m["off"], k_r, runs=t is not None)
        pools = (c["ckv"][at], c["kr"][at])
        if t is not None:
            o = self._attend_tiles(lp, qn, qr, pools, t, m["form"])
        elif m["walk"] == "kernel":
            o = self._walk_lanes(lp, qn, qr, pools, m["work"])
        else:
            o = jax.lax.map(
                lambda a: self._attend_tile(lp, *a[:2], pools, *a[2:], m["form"]),
                (qn[:, None], qr[:, None], m["bt"], m["pos"][:, None], m["last"]))[:, 0]
        return self._attn_out(lp, o)

    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        with jax.named_scope(m["scope"]):
            y = self._attention(lp, rms_norm(x, lp["norm1"], self.eps), i, c, m)
        x = x + y.astype(self.dtype)
        y, st = self._ffn(lp, i, rms_norm(x, lp["norm2"], self.eps), m["live"])
        return x + y.astype(self.dtype), st

    def _counts(self, m: dict) -> dict:
        """And the rows attended over and walked, the launch's form, and its
        tiles (a step's live lanes) with the walk they took."""
        t = m["t"]
        if t is not None:
            P, pps = m["P"], m["pps"]
            walked = jnp.sum(self._blocks_needed(t["last"], P, pps)) * self._block_pages(P, pps) * P
        c = {**super()._counts(m), "form": m["form"], "walk": m["walk"]}
        if t is None:
            return {**c, "attended": c["context"], "walked": m["walked"],
                    "tiles": jnp.sum(m["live"])}
        return {**c, "attended": jnp.sum(jnp.where(m["length"] > 0, m["start"] + m["length"], 0)),
                "walked": walked, "tiles": jnp.sum(t["has"])}


def create(cfg: ModelConfig) -> LatentServing:
    return LatentServing(cfg)
