"""A decoder-only language model with latent attention OVER THE POSITIONS A
LEARNED INDEXER PICKS (ISSUE 62): ``mla``'s attention (by inheritance: its
projections, ``yarn_magnitudes``, its two page leaves, both forms, both
kernels) whose softmax runs over the ``index_topk`` keys of largest index score
a query and no others, an index key a token in a THIRD page leaf, routed
experts under GROUP-LIMITED picks, and a share of each layer as ``mla_sc`` reads
it. Built from a published ``config.json`` and served through the generation
engine as ``mla`` is. Nothing here knows a model's name.

THE INDEXER, with ``u`` the normed stream at position ``t`` and ``c_q`` the
query's normed latent (``mla``'s): index queries ``qI(t) = c_q WI_qb``,
``index_n_heads`` heads of ``index_head_dim``, the first ``qk_rope_head_dim``
columns of each head turned by RoPE at ``t`` with the attention's own
frequencies; ONE index key ``kI(t) = LayerNorm(u WI_k; gamma, beta)`` (mean and
variance, eps 1e-6, a bias) for all the heads, its first rotary columns turned
at ``t``: this is what is cached; head weights ``w(t) = (u WI_w) x
index_n_heads^-1/2 x index_head_dim^-1/2`` in float32, no activation. The score
``I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s))``, ``s <= t``; ``S(t)`` = the
``min(index_topk, t + 1)`` positions of largest ``I(t, s)``, EXACTLY
(``ops/index_select.py``: the ``index_topk``-th largest as a threshold, found by
a search over the float32's bits; no sort, no approximation). For ``t <
index_topk`` that is every position and the layer is plain ``mla``. Attention is
``mla``'s with its softmax over ``s in S(t)`` only.

THE CACHE: ``mla``'s two leaves and ``ik`` (pages, P, index_head_dim), the index
key, one whole row of 128 lanes a token at the published width, written where
``kr`` is written; ``/stats`` ``kv.row_bytes_per_token`` counts all three.

THE WALKS. A launch's tile whose last position is under ``index_topk`` is
``mla``'s own. Another: the tile's index scores over its block table AND each
row's threshold, its ``index_topk``-th largest score (ONE kernel on the TPU, the
``ik`` pages read in place, the threshold found in fast memory; ``sel_index``),
and ``mla``'s walk of every key block, a row keeping a key where its score is
at or above its threshold beside the causal mask (``keep``: the scores and the
thresholds, compared in the walk's kernel; ``sel_attend``): exact, nothing of
XLA's runs over a tile's scores (``sel_threshold_tiles_total{path=kernel}``),
and it reads and scores every cached row of the prompt where the picks are a
part of them (``sel_rows_walked_total`` over ``sel_pairs_kept_total`` says how
many). A step likewise: every lane's scores by ``lane_attention``'s work list,
the lanes' picks (``picks``: sixteen rows' thresholds in XLA and a float32
mask), ONE masked ``lane_walk`` an attention. Off the TPU, in float32 or at
shapes no kernel takes, the exact fallbacks in XLA (``picks``' mask for the
walks in XLA; ``thresholds`` where the walk alone is the kernel).

FEED-FORWARD: ``mla``'s own function, its picks group-limited (``n_group``,
``topk_group``: ``mla``'s ``groups``, ``ops/moe.py`` ``topk_route(groups=)``). THE SHARE, as ``mla_sc`` reads it:
``share.experts_held = [first, count]`` and ``share.vocab_rows = [first,
count]``; the router scores every output, picks on experts held elsewhere add
nothing, the shared expert, attention, the indexer and the norms are whole. An
expert is a tensor of its own, named by its PUBLISHED number.

NOT SERVED: the published indexer's Hadamard turn of ``qI`` and ``kI`` (an
orthogonal matrix: every ``qI . kI`` is what it was) and its FP8 values with
block scales (below the served bfloat16); a multi-token-prediction module.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.genserve.model import pool
from tpuserve.models import mla
from tpuserve.models.decoder import apply_rope
from tpuserve.models.paged_lm import Column, _mm, counted, read_config_file, scoped, series
from tpuserve.ops import index_select as ix

# ``mla``'s, the attention's three wide draws at 1.5 (every score carries the
# yarn magnitude squared besides), and the indexer's: its queries and its key
# at unit deviation a column (a product of 128 of them has deviation 11, its
# ReLU 6.6), the head weights' draw at 1 (a weight's deviation is 1 / 90.5), so
# an index score has deviation 0.58 over a query's keys, and the key's bias
# inside +-0.3.
DEFAULT_SCALES = {**mla.DEFAULT_SCALES, "q_b": 1.5, "k_rope": 1.5, "k_b": 1.5,
                  "index_q": 1.0, "index_k": 1.0, "index_w": 1.0, "index_beta": 0.1}
INDEX_EPS = 1e-6   # the index key's LayerNorm
PATHS = ("dense", "picked")
THRESHOLDS = ("kernel", "xla")


class SelectedLatentServing(mla.LatentServing):
    # ``mla``'s twelve columns (its rows attended count PICKED positions in a
    # step) and, an attention layer: the (query, key) pairs the indexer scored
    # and the picks it kept (live queries past ``index_topk``), the cache rows
    # their walks read and scored a query, the live queries by path, and a
    # launch's picked tiles by where their rows' thresholds were found
    # (``_prefill_plan``, chosen when the program is traced; a step has no tiles).
    COLUMNS = (*mla.LatentServing.COLUMNS,
               Column(counted("sel_scored"), series("sel_pairs_scored_total")),
               Column(counted("sel_kept"), series("sel_pairs_kept_total")),
               Column(counted("sel_walked"), series("sel_rows_walked_total")),
               *(Column(counted(f"sel_{path}"), series("sel_queries_total", f",path={path}"))
                 for path in PATHS),
               *(Column(lambda model, stats, counts, path=path:
                        counts["sel_tiles"] if counts["sel_threshold"] == path else 0,
                        series("sel_threshold_tiles_total", f",path={path}"))
                 for path in THRESHOLDS))
    TAKES = tuple(kv for kv in mla.LatentServing.TAKES
                  if kv[0] not in ("n_group", "topk_group", "share"))

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        if int(a.get("n_group", 1)) > 1:
            self.groups = (int(a["n_group"]), int(a["topk_group"]))
        self.i_heads, self.i_dim = int(a["index_n_heads"]), int(a["index_head_dim"])
        self.index_topk = int(a["index_topk"])
        if self.i_dim < self.dr:
            raise NotImplementedError(f"{cfg.name}: index_head_dim {self.i_dim} under the "
                                      f"{self.dr} rotary columns")
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.n_experts])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.vocab_full])
        if not 0 <= self.e_first <= self.e_first + self.e_count <= self.n_experts:
            raise ValueError(f"{cfg.name}: share.experts_held = {share['experts_held']} "
                             f"of {self.n_experts} experts")
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    # -- params ---------------------------------------------------------------
    def _attention_gains(self, at: tuple):
        yield from super()._attention_gains(at)
        yield (*at, "index_norm"), (self.i_dim,)

    def _attention_tensors(self, at: tuple):
        yield from super()._attention_tensors(at)
        d, s = self.d, self.scales
        for name, shape, role, fan_in in (
                ("wi_qb", (self.q_rank, self.i_heads, self.i_dim), "index_q", self.q_rank),
                ("wi_k", (d, self.i_dim), "index_k", d),
                ("wi_w", (d, self.i_heads), "index_w", d)):
            yield (*at, name), shape, shape, (0,) * len(shape), s[role], fan_in

    def _expert_tensors(self, L: str, whole):
        """The HELD experts, each a tensor of its own named by its published
        number (``draw_params`` stacks them)."""
        d, f = self.d, self.expert_width
        for g in range(self.e_first, self.e_first + self.e_count):
            for name in ("e_gate", "e_up"):
                yield whole((L, name, str(g)), (d, f), "ffn_in", d)
            yield whole((L, "e_down", str(g)), (f, d), "expert_out", f)

    def _vectors(self):
        """``mla``'s selection bias, and the index key's LayerNorm bias."""
        yield from super()._vectors()
        b3 = 3.0 * self.scales["index_beta"]
        for at in self._attentions():
            yield (*at, "index_beta"), (self.i_dim,), (self.i_dim,), (0,), -b3, b3

    def draw_params(self, seed: int) -> Any:
        p = super().draw_params(seed)
        for i in self.sparse_layers:
            lp = p[f"layer{i}"]
            for name in ("e_gate", "e_up", "e_down"):
                lp[name] = jnp.stack([lp[name][str(g)] for g in range(
                    self.e_first, self.e_first + self.e_count)])
        return p

    def share_stats(self) -> dict:
        """``/stats``: what of each layer is held here."""
        return {"experts_held": [self.e_first, self.e_count], "experts": self.n_experts,
                "vocab_rows": [self.v_first, self.vocab], "vocab": self.vocab_full}

    # -- shapes -----------------------------------------------------------------
    def _cache_signature(self, slots: int, pages: int, page_tokens: int) -> dict:
        S = jax.ShapeDtypeStruct
        return {**super()._cache_signature(slots, pages, page_tokens),
                "ik": pool([S((pages, page_tokens, self.i_dim), self.dtype)
                            for _ in self._attentions()])}

    # -- device math --------------------------------------------------------------
    def _turn(self, x, pos):
        """The first rotary columns of ``x`` (T, heads, index_head_dim) turned
        at ``pos``, the rest passed (``apply_rope`` turns a leading part)."""
        inv, factor, dim = self.rope
        return apply_rope(x, pos, inv, factor, dim, self.rope_interleave)

    @scoped("proj")
    def _project_index(self, lp: dict, u, c_q, pos):
        """-> the index queries (T, Hi, Di), their heads' weights (T, Hi)
        float32, and what a token keeps: its index key (T, Di)."""
        dt = self.dtype
        qi = jnp.einsum("tq,qhk->thk", c_q, lp["wi_qb"],
                        preferred_element_type=jnp.float32).astype(dt)
        k = _mm(u, lp["wi_k"])
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True) + INDEX_EPS)
        k = (k * lp["index_norm"].astype(jnp.float32) + lp["index_beta"]).astype(dt)
        w = jnp.matmul(u.astype(jnp.float32), lp["wi_w"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST) \
            * jnp.float32(self.i_heads ** -0.5 * self.i_dim ** -0.5)
        return self._turn(qi, pos), w, self._turn(k[:, None], pos)[:, 0]

    def _index_walk(self, T: int, ik) -> str:
        """Where a tile of ``T`` queries takes its index scores: ``kernel`` on
        the TPU at shapes ``ops/index_select.py`` takes, else ``xla``."""
        if jax.default_backend() != "tpu":  # tps-ok[TPS503]: at trace time
            return "xla"
        ok = ix.fits(ik.shape[1], self.i_dim, self.i_heads, self.dtype) \
            and (T == 1 or T % min(ix.ROWS, T) == 0 and T % 8 == 0)
        return "kernel" if ok else "xla"

    def _tile_keep(self, qi, wi, ik, row, qpos, last, pair: bool = False):
        """One tile's picks: its rows' index scores over the prompt's pages
        (block-table row ``row``) as far as ``last`` needs, and each row's
        ``index_topk``-th largest as a threshold -> ``_attend_tile``'s ``keep``:
        ``pair`` (the walk is the kernel's) the scores (T, key blocks x c)
        float32 and the thresholds (T, 128) float32 as ``tile_walk`` reads
        them, from the one kernel that makes both where it takes the shapes,
        else the float32 mask of ``picks``."""
        T, P, k = qi.shape[0], ik.shape[1], self.index_topk
        kb, rowp = self._key_blocks(row, P)
        need = self._blocks_needed(last, P, row.shape[0])
        with jax.named_scope("sel_index"):
            if T > 1 and self._index_walk(T, ik) == "kernel":
                scores, least = ix.tile_scores(qi, wi, ik, rowp, need, qpos[0], k=k,
                                               block_pages=kb)
                return (scores, least) if pair else ix.picks(scores, qpos, k, need, kb * P)
            scores = ix.scores_xla(qi, wi, ik, rowp)
            if pair:
                return scores, jnp.broadcast_to(ix.thresholds(scores, qpos, k)[:, None], (T, 128))
            return ix.picks(scores, qpos, k)

    def _attend_tiles(self, lp: dict, qn, qr, pools, t: dict, form: str, index: tuple):
        """``mla``'s, a tile whose last position is past ``index_topk`` under
        its rows' picks (``index``: the launch's index queries, their weights
        and the layer's ``ik`` pool)."""
        qi, wi, ik = index
        K, T = t["K"], t["T"]
        split = lambda a: a.reshape((K, T) + a.shape[1:])  # noqa: E731
        tiles = (split(qn), split(qr), split(qi), split(wi), t["rows"], t["qpos"], t["last"])
        kernel = self._walk(form, T, pools, t["rows"].shape[1]) == "kernel"

        def one(a):
            qn, qr, qi, wi, row, qpos, last = a

            def picked():
                keep = self._tile_keep(qi, wi, ik, row, qpos, last, pair=kernel)
                with jax.named_scope("sel_attend"):
                    return self._attend_tile(lp, qn, qr, pools, row, qpos, last, form, keep) \
                        .astype(self.dtype)

            return jax.lax.cond(
                last >= self.index_topk, picked,
                lambda: self._attend_tile(lp, qn, qr, pools, row, qpos, last, form)
                .astype(self.dtype))

        if kernel:
            return jnp.concatenate([one([v[k] for v in tiles]) for k in range(K)])
        o = jax.lax.map(one, tiles)
        return o.reshape((K * T,) + o.shape[2:])

    def _attend_lanes(self, lp: dict, qn, qr, qi, wi, pools, ik, m: dict):
        """A step's attention: every lane's index scores over its block table,
        its picks, and the masked walk, in the kernels (one call each an
        attention) or lane after lane in XLA -> (B, H, v) float32."""
        bt, last = m["bt"], m["last"]
        if m["walk"] == "kernel" and self._index_walk(1, ik) == "kernel":
            def picked():
                with jax.named_scope("sel_index"):
                    keep = ix.picks(ix.lane_scores(qi, wi, ik, m["work"]), last,
                                    self.index_topk)
                with jax.named_scope("sel_attend"):
                    return self._walk_lanes(lp, qn, qr, pools, m["work"], keep)

            # a step none of whose lanes is past ``index_topk`` is ``mla``'s own
            return jax.lax.cond(jnp.any(last >= self.index_topk), picked,
                                lambda: self._walk_lanes(lp, qn, qr, pools, m["work"]))

        def lane(a):
            qn, qr, qi, wi, row, qpos, last = a
            keep = self._tile_keep(qi, wi, ik, row, qpos, last)
            with jax.named_scope("sel_attend"):
                return self._attend_tile(lp, qn, qr, pools, row, qpos, last, m["form"], keep)

        return jax.lax.map(lane, (qn[:, None], qr[:, None], qi[:, None], wi[:, None], bt,
                                  m["pos"][:, None], last))[:, 0]

    def _attention(self, lp: dict, u, at: int, c: dict, m: dict):
        """``mla``'s, with the token's index key into the third leaf and the
        walks under the picks."""
        t = m["t"]
        c_q = self._query_latent(lp, u)
        qn, qr, c_kv, k_r = self._project(lp, u, m["pos"], c_q)
        qi, wi, k_i = self._project_index(lp, u, c_q, m["pos"])
        c["ckv"][at] = self._write_pages(c["ckv"][at], m["w_page"], m["off"],
                                         c_kv.astype(c["ckv"][at].dtype))
        c["kr"][at] = self._write_keys(c["kr"][at], m["w_page"], m["off"], k_r, runs=t is not None)
        c["ik"][at] = self._write_pages(c["ik"][at], m["w_page"], m["off"],
                                        k_i.astype(c["ik"][at].dtype))
        pools, ik = (c["ckv"][at], c["kr"][at]), c["ik"][at]
        if t is not None:
            o = self._attend_tiles(lp, qn, qr, pools, t, m["form"], (qi, wi, ik))
        else:
            o = self._attend_lanes(lp, qn, qr, qi, wi, pools, ik, m)
        return self._attn_out(lp, o)

    # -- a launch's counts ---------------------------------------------------------
    def _prefill_plan(self, state, launch, t: dict) -> dict:
        """And where the launch's picked tiles find their rows' thresholds:
        ``kernel`` (``tile_scores``, in fast memory) where both it and the
        walk that reads the pair are kernels, else ``xla`` (``kth_key`` over
        device memory)."""
        m = super()._prefill_plan(state, launch, t)
        both = m["walk"] == "kernel" and self._index_walk(t["T"], state["ik"][0]) == "kernel"
        return {**m, "threshold": "kernel" if both else "xla"}

    def _step_plan(self, state, live, pos) -> dict:
        """And the cache rows each lane's walk reads (whole key blocks)."""
        m = super()._step_plan(state, live, pos)
        P, pps = state["ckv"][0].shape[1], m["bt"].shape[1]
        if m["walk"] == "kernel":
            c = max(1, min(self.step_keys // P, pps)) * P
            rows = (m["last"] // c + 1) * c
        else:
            rows = self._blocks_needed(m["last"], P, pps) * self._block_pages(P, pps) * P
        return {**m, "lane_rows": rows}

    def _counts(self, m: dict) -> dict:
        """And, an attention layer: the pairs scored and kept and the rows
        walked of the live queries past ``index_topk``, and the live queries by
        path; a step's rows attended are its lanes' picks; a launch's picked
        tiles (one that holds no piece ends at 0) under their thresholds' path."""
        c, t, k = super()._counts(m), m["t"], self.index_topk
        picked = m["live"] & (m["pos"] >= k)
        if t is None:
            rows = m["lane_rows"]
            c = {**c, "attended": jnp.sum(jnp.where(m["live"], jnp.minimum(m["pos"] + 1, k), 0)),
                 "sel_tiles": 0, "sel_threshold": "xla"}
        else:
            rows = jnp.repeat(self._blocks_needed(t["last"], m["P"], m["pps"])
                              * self._block_pages(m["P"], m["pps"]) * m["P"], t["T"])
            c = {**c, "sel_tiles": jnp.sum(t["last"] >= k), "sel_threshold": m["threshold"]}
        return {**c, "sel_scored": jnp.sum(jnp.where(picked, m["pos"] + 1, 0)),
                "sel_kept": k * jnp.sum(picked), "sel_walked": jnp.sum(jnp.where(picked, rows, 0)),
                "sel_dense": jnp.sum(m["live"] & ~picked), "sel_picked": jnp.sum(picked)}


def create(cfg: ModelConfig) -> SelectedLatentServing:
    return SelectedLatentServing(cfg)
