"""A decoder-only language model whose attention is EVA (ISSUE 55): every query
attends, in ONE softmax, the exact keys of its own ALIGNED window and one
learned-pooled summary row a chunk of every earlier window. ``decoder``'s
sibling, built from the published ``config.json`` of a model with
``attention_class = "eva"`` (key names as published: ``window_size`` W,
``chunk_size`` c, ``num_chunks`` null, ``norm_add_unit_offset``,
``fp32_skip_add``, ``fp32_logits``, ``num_pred_heads``); ``decoder``'s
constructor, projections, rotary, SwiGLU, tiles and ring places are used as
they are (``_arch`` translates the names).

THE LAYER (Zheng, Wang, Kong: "Efficient Attention via Control Variates",
arXiv:2302.04542, in the form the EvaByte release serves it). Position ``i``
lies in window ``n = i // W``. With ``u`` the normed stream, ``q``, ``k`` (both
turned by the rotary at the absolute position) and ``v`` by head:

    chunk m (positions c m .. c m + c - 1), once its last row is there:
      w_t = softmax over the chunk of (phi[h] . k_t[h])       phi: ``adaptive_phi``
      ks_m[h] = sum_t w_t k_t[h] + mu[h]                      mu: ``adaptive_mu_k``
      vs_m[h] = sum_t w_t v_t[h]
    a = softmax over {k_t: n W <= t <= i} AND {ks_m: m < n W / c} TOGETHER of q . key / sqrt(hd)
    o = sum a_t v_t + sum a_m vs_m

so a chunk weighs as ONE key, a window's own chunks are never seen through
their summaries, and a window that has not ended is read exactly. The norms'
gains are ``1 + g`` (``norm_add_unit_offset``), the stream is float32 from the
embedding's row to the last norm (``fp32_skip_add``: a sublayer's output is
added in float32), the logits float32 (``fp32_logits``); the pooling and both
softmaxes are float32. The head holds ``num_pred_heads`` blocks of
``vocab_size`` columns, one after another; block 0, the next id, is served
(NOT SERVED: the further blocks are drawn and held and computed by nothing: a
step yields one token a lane).

THE CACHE. A slot holds, a layer, a RING of W exact rows written at ``i % W``
(places ``0 .. i % W`` are the current window's, the rest stale) and, a window
of its context, one PAGE of ``W / c`` summary rows (row ``(i % W) // c`` of page
``i // W``, written when position ``i`` ends a chunk). So the ledger's
``kv_page_tokens`` is ROWS a page, ``W / c`` of them, and a page stands for W positions
(``kv_plan``'s ``page_positions``): a request takes ``ceil((prompt + new) / W)`` pages.
A summary row has a token's shape, so ring and pages lie in ONE pool a layer,
``(c x (slots + 1) + pages, W / c, KV x hd)`` for K and for V, a position ONE ROW
with its KV heads side by side (ISSUE 63: a token is one row of each pool to
write, where pools by head took ``KV`` pieces a token): ring ``r`` is pages
``c r .. c r + c - 1`` (ring 0 the rings' sentinel), the ledger's page ``p`` is
pool page ``c (slots + 1) + p`` (its page 0 the summaries' sentinel).

A STEP (scope ``eva_decode``) writes its row to the ring and then attends ONE
virtual block table a lane, READ IN PLACE: the lane's ``i // W`` summary pages
followed by its ring's ``c`` pages, of virtual length ``(i // W) (W / c) + i % W
+ 1``. On the TPU in bfloat16 the repo's own ``ops/lane_attention.py``
``head_walk`` walks it (ISSUE 56): ONE flat work list a step of the (lane, key
block) items that exist, shared by the layers, a cell an item for ALL heads
over the pools as they lie (a head's columns cut out of a page's rows on whole
lane tiles: ``kv=``), a key in one part, the scores scaled in float32 inside the
cell; elsewhere the gather of the padded table (``_decode_gather``).
``eva_decode_steps_total{path=head_walk|gather}`` says which.
Where ``i % c == c - 1`` the step then pools the chunk's rows from the ring and
writes the summary row (scope ``eva_summarise``); a lane whose chunk has not
ended writes to the sentinel.

A LAUNCH (scope ``eva_prefill``) first pools every chunk that ends inside it
(``eva_summarise``: a piece starts at a tile's edge and a tile is whole chunks,
so such a chunk's rows are all the launch's own) and writes the summaries to
their pages; then each tile attends what its ring held BEFORE the launch and
the launch's own rows, both masked by WINDOW INDEX (``t // W == i // W and t <=
i``), joined under one running softmax with the summary pages of every earlier
window through the block table, a window the launch itself closes among them;
the ring is written last, a tile's rows as whole pages of it (a tile is whole
pages: one scatter of slabs, each a page's contiguous bytes). A launch is at
most W rows, so it crosses one window's edge at most and writes no ring place
twice. On the TPU in bfloat16 the attention is
ONE call a layer of ``ops/launch_attention.py`` ``launch_walk`` (ISSUE 58) over
ONE flat work list a launch of the (tile, key page) items that exist (a tile's
ring pages that hold its window, the launch's own rows seen as pages (a view of
the projections' output), its summary pages), a cell an item for all heads, ring
and pages read in place;
elsewhere a tile at a time in XLA (``_tile``).
``eva_prefill_tiles_total{path=tile_kernel|xla}`` says which.

Requests carry ``prompt_ids``; for a byte-level model an id IS a byte (0-255)
or one of the special ids above them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.genserve.model import CachePlan, pool
from tpuserve.models import decoder as dec
from tpuserve.models.paged_lm import (CONTEXT_COLUMN, NEG, SAMPLE_COLUMNS, Column, _mm, counted,
                                      read_config_file, scoped, series)
from tpuserve.ops import lane_attention as la
from tpuserve.ops import launch_attention as lat

# What this family draws otherwise than ``decoder``: ``phi`` so that a chunk's
# weights are decided (``phi . k`` of standard deviation 2: the largest of 16
# near a half), ``qk`` and ``mu`` so that summary rows hold a visible part of a
# softmax's mass against a window's exact rows (the cell's configuration file
# says how much), the gains' ``g`` inside [-gain, gain] about 0 (a gain is 1 + g).
DEFAULT_SCALES = {**dec.DEFAULT_SCALES, "qk": 1.0, "phi": 0.18, "mu": 1.0, "gain": 0.25}
PATHS = ("head_walk", "gather")      # a step's walk
TILE_PATHS = ("tile_kernel", "xla")  # a launch's tiles
WINDOW_KIND = "sliding_attention"   # what ``decoder`` calls a layer that keeps a ring


def _by_path(count: str, name: str, phase: str, paths: tuple) -> tuple:
    """A column a path, in ``phase`` alone: ``counts[count][path]`` into
    ``name{model=,phase=,path=}``."""
    return tuple(
        Column(lambda model, stats, counts, path=path: counts[count][path],
               lambda model, metrics, ph, path=path: series(name, f",path={path}")(
                   model, metrics, ph) if ph == phase else None)
        for path in paths)


class EvaServing(dec.DecoderServing):
    # The rows a token attends (exact and summary: what the cache's bytes go
    # by), each kind, chunks pooled, windows closed, a step's lanes and a
    # launch's tiles by path, and the steps by the sampler's branch.
    COLUMNS = (
        CONTEXT_COLUMN,
        Column(counted("exact"), series("eva_rows_attended_total", ",kind=exact")),
        Column(counted("summary"), series("eva_rows_attended_total", ",kind=summary")),
        Column(counted("chunks"), series("eva_chunks_summarised_total")),
        Column(counted("windows"), series("eva_windows_closed_total")),
        *_by_path("paths", "eva_decode_steps_total", "decode", PATHS),
        *_by_path("tiles", "eva_prefill_tiles_total", "prefill", TILE_PATHS),
        *SAMPLE_COLUMNS)
    # Pages a cell of the step's walk holds (``head_walk``'s key block): a cell
    # reads its pages whole whatever the lane's length, so small ones follow the
    # live rows and large ones save cells. One layer of 24 lanes at the mix's
    # contexts (26,663 rows) took 0.61 / 0.65 / 0.70 / 0.79 ms at 1 / 2 / 4 / 8 pages
    # (scripts/bench_eva_walk.py, my chip run, PR 56).
    walk_block = 1
    key_block = 512   # summary rows a block of a launch's walk

    def __init__(self, cfg: ModelConfig) -> None:
        a = read_config_file(cfg)
        for key, want in (("attention_class", "eva"), ("num_chunks", None), ("hidden_act", "silu"),
                          ("rope_scaling", None), ("norm_add_unit_offset", True),
                          ("fp32_skip_add", True), ("fp32_logits", True), ("fp32_ln", False)):
            if a.get(key, want) != want:
                raise NotImplementedError(f"{cfg.name}: {key} = {a[key]!r}")
        if "share" in a:
            raise NotImplementedError(f"{cfg.name}: share (a layer is whole on its chip)")
        super().__init__(cfg)
        self.chunk = int(a["chunk_size"])
        if self.window % self.chunk:
            raise ValueError(f"{cfg.name}: window_size {self.window} is not whole chunks "
                             f"of {self.chunk}")
        self.rows = self.window // self.chunk   # summary rows a window: a page's rows
        self.n_pred = int(a.get("num_pred_heads") or 1)
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    def _arch(self, a: dict) -> dict:
        """The published keys under the names ``decoder``'s constructor reads:
        every layer keeps a ring of ``window_size`` places."""
        n = int(a["num_hidden_layers"])
        out = {k: a[k] for k in ("attention_bias", "hidden_size", "num_hidden_layers",
                                 "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
                                 "intermediate_size", "vocab_size", "tie_word_embeddings",
                                 "weight_scales") if k in a}
        return {**out, "layer_types": [WINDOW_KIND] * n, "sliding_window": a["window_size"],
                "rope_parameters": {WINDOW_KIND: {"rope_type": "default",
                                                  "rope_theta": float(a["rope_theta"])}}}

    # -- params ---------------------------------------------------------------
    def _gains(self):
        return ()   # a gain is 1 + g: the g's are drawn (``_vectors``)

    def _vectors(self):
        g = float(self.scales["gain"])
        for path in [("norm_f",)] + [(f"layer{i}", norm) for i in range(self.n_layers)
                                     for norm in ("norm1", "norm2")]:
            yield (path, (self.d,), (self.d,), (0,), -g, g)

    def _vocab_tensors(self):
        """The embedding, and the head's ``num_pred_heads`` blocks of ``vocab``
        columns one after another (block 0 is the next id)."""
        d, s, wide = self.d, self.scales, self.n_pred * self.vocab_full
        yield (("embed",), (self.vocab, d), (self.vocab_full, d), (0, 0), s["embed"], 1)
        yield (("head",), (d, wide), (d, wide), (0, 0), s["head"], d)

    def _tensors(self):
        yield from super()._tensors()
        h, s = (self.kv, self.hd), self.scales   # a KV head's: a cached row is pooled
        for i in range(self.n_layers):
            yield ((f"layer{i}", "phi"), h, h, (0, 0), s["phi"], 1)
            yield ((f"layer{i}", "mu"), h, h, (0, 0), s["mu"], 1)

    # -- shapes: a page is ``rows`` summary rows and stands for a window ---------
    def kv_plan(self, slots: int, page_tokens: int, pages: int = 0) -> CachePlan:
        if int(page_tokens) != self.rows:
            raise ValueError(f"{self.name}: [genserve] kv_page_tokens = {page_tokens}: a page is "
                             f"a window's summary rows, window_size / chunk_size = {self.rows}")
        return super().kv_plan(slots, page_tokens, pages, page_positions=self.window,
                               ring_pages=self.chunk)

    def kv_prefill_pieces(self, chunk: int, page_tokens: int) -> int:
        k = super().kv_prefill_pieces(chunk, page_tokens)
        if chunk > self.window or (chunk // k) % self.chunk or (chunk // k) % int(page_tokens):
            raise ValueError(f"{self.name}: a prefill launch of {chunk} rows in {k} tiles: a "
                             f"launch is at most a window ({self.window}) and a tile whole "
                             f"chunks of {self.chunk} and whole pages of {page_tokens} rows")
        return k

    def _cache_signature(self, slots: int, pages: int, page_tokens: int) -> dict:
        S = jax.ShapeDtypeStruct   # ONE pool a layer: the rings' pages, then the ledger's
        both = S((self.chunk * (slots + 1) + pages, self.rows, self.kv * self.hd), self.dtype)
        return {"kf": pool([both] * self.n_layers), "vf": pool([both] * self.n_layers),
                "ring": S((slots,), jnp.int32)}

    # -- device math --------------------------------------------------------------
    @scoped("norm")
    def _norm(self, x, g):
        """RMSNorm in float32 with the gain ``1 + g``, its result in the served type."""
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (y * (1.0 + g.astype(jnp.float32))).astype(self.dtype)

    def _embed(self, params, ids):
        return jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)

    def _head(self, params, x):
        """Block 0 of the head: the next id's float32 logits."""
        return _mm(self._norm(x, params["norm_f"]), params["head"][:, :self.vocab])

    def _pool(self, lp: dict, k, v):
        """Chunks' rows k, v (..., c, KV, hd) -> their summary rows (..., KV, hd)
        in the served type: the weights a softmax of ``phi . k`` over the chunk,
        ``mu`` added to the pooled key; float32."""
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
        phi = lp["phi"].astype(jnp.float32)
        w = jax.nn.softmax(jnp.sum(kf * phi, axis=-1), axis=-2)[..., None]
        ks = jnp.sum(w * kf, axis=-3) + lp["mu"].astype(jnp.float32)
        return ks.astype(self.dtype), jnp.sum(w * vf, axis=-3).astype(self.dtype)

    # -- what a launch works out once ------------------------------------------------
    def _first_page(self, state) -> int:
        """The pool page of the ledger's page 0: past the rings."""
        return self.chunk * (state["pos"].shape[0] + 1)

    def _prefill_plan(self, state, launch, t: dict) -> dict:
        """``decoder``'s ring places, and: the pool addresses of the ring's
        rows; the launch's rows by chunk with where each whole chunk's summary
        goes (the summaries' sentinel for one that is not whole); the path the
        tiles' attention takes with (in the kernel) its work list."""
        m = super()._prefill_plan(state, launch, t)
        c, P, W, first = self.chunk, self.rows, self.window, self._first_page(state)
        ends = (t["valid"] & (t["cpos"] % c == c - 1)).reshape(-1, c)[:, -1]   # by chunk of rows
        at = t["cpos"].reshape(-1, c)[:, -1]
        rows = jnp.repeat(t["rows"], t["T"] // c, axis=0)                      # (C / c, pps)
        page = jnp.take_along_axis(rows, jnp.minimum(at // W, rows.shape[1] - 1)[:, None],
                                   axis=1)[:, 0]
        # The launch's rows by runs of P: a tile is whole pages and a piece starts at a
        # tile's edge, so a run of live rows IS one page of its ring (a padded tail fills
        # places past the prompt's end, which nothing reads before they are written
        # again); a run of no live row goes to the rings' sentinel.
        head, run_pos = t["valid"][::P], t["cpos"][::P]
        ring_runs = jnp.where(head, c * jnp.repeat(t["rings"], t["T"] // P) + run_pos % W // P, 0)
        kernel = jax.default_backend() == "tpu" and self._tiles_fit(t["T"])   # tps-ok[TPS503]: at trace time
        # ONE work list a launch, the layers' alike: the (tile, key page) items that exist
        work = lat.launch_list(
            t["has"], t["qpos"][:, 0], launch["start"][t["piece"]], t["end"],
            t["first_tile"][t["piece"]] * (t["T"] // P), c * t["rings"], first + t["rows"],
            tile=t["T"], page=P, window=W) if kernel else None
        return {**m, "first": first, "ends": ends, "ring_runs": ring_runs,
                "tile_path": "tile_kernel" if kernel else "xla", "work": work,
                "sum_page": first + jnp.where(ends, page, 0), "sum_off": (at % W) // c}

    def _step_plan(self, state, live, pos) -> dict:
        """``decoder``'s ring places, and: each lane's VIRTUAL block table (its
        closed windows' summary pages, then its ring's pages) with its virtual
        length, the path the walk takes with (in the kernel) its work list,
        and where the chunk that this step may end is pooled from and written to. A lane that is not live walks
        one row of the rings' sentinel and writes to the sentinels."""
        m = super()._step_plan(state, live, pos)
        c, P, W, first = self.chunk, self.rows, self.window, self._first_page(state)
        bt, n, j = state["bt"], pos // W, pos % W
        pps = bt.shape[1]
        i = jnp.arange(pps + c)[None, :]
        closed, ring = n[:, None], m["w_ring"][:, None]
        table = jnp.where(i < closed, first + bt[:, jnp.minimum(jnp.arange(pps + c), pps - 1)],
                          jnp.where(i < closed + c, c * ring + i - closed, 0))
        table = jnp.where(live[:, None], table, 0).astype(jnp.int32)
        rows_seen = jnp.where(live, n * P + j + 1, 1).astype(jnp.int32)
        ends = live & (j % c == c - 1)
        page = jnp.take_along_axis(bt, jnp.minimum(n, pps - 1)[:, None], axis=1)[:, 0]
        walk = jax.default_backend() == "tpu" and self._walks(P)   # tps-ok[TPS503]: at trace time
        # ONE work list a step, the layers' alike: the (lane, key block) items that exist
        work = la.work_list(rows_seen - 1, table, P, self.walk_block) if walk else None
        return {**m, "first": first, "ends": ends, "path": "head_walk" if walk else "gather",
                "table": table, "rows_seen": rows_seen, "work": work,
                "ring_page": c * m["w_ring"] + j // P, "ring_off": j % P,
                # the chunk the lane's position lies in, among the pool's runs of c rows
                "chunk_at": (c * m["w_ring"] + j // P) * (P // c) + (j % P) // c,
                "sum_page": first + jnp.where(ends, page, 0), "sum_off": j // c}

    def _counts(self, m: dict) -> dict:
        """The rows the live tokens' index sets hold, exact and summary (their
        sum is the ``context``), chunks pooled, windows closed, and a step's
        lanes x layers and a launch's tiles x layers by the path each took."""
        live, pos = m["live"], m["pos"]
        exact = jnp.sum(jnp.where(live, pos % self.window + 1, 0))
        summary = jnp.sum(jnp.where(live, (pos // self.window) * self.rows, 0))
        lanes = jnp.sum(live) * self.n_layers
        tiles = 0 if m["t"] is None else jnp.sum(m["t"]["has"]) * self.n_layers
        return {**super()._counts(m), "context": exact + summary,   # the rows, not the positions
                "exact": exact, "summary": summary,
                "chunks": jnp.sum(m["ends"]) * self.n_layers,
                "windows": jnp.sum(live & (pos % self.window == self.window - 1)),
                "paths": {p: lanes * (m.get("path") == p) for p in PATHS},
                "tiles": {p: tiles * (m.get("tile_path") == p) for p in TILE_PATHS}}

    # -- the mixer ---------------------------------------------------------------------
    def _walks(self, P: int) -> bool:
        """Shapes ``head_walk`` takes, a key in one part."""
        return la.head_fits(P, self.heads[0], self.kv, self.hd, 0, self.hd, self.dtype)

    def _walk(self, q, kp, vp, m: dict):
        """A step's attention over the virtual table, in place: q (b, H, hd) ->
        (b, H, hd) float32."""
        if m["path"] == "head_walk":
            return la.head_walk(q, None, kp, None, vp, m["work"], scale=self._scale(),
                                kv=self.kv).astype(jnp.float32)
        return self._decode_gather(q, (kp, vp), m["table"], m["rows_seen"] - 1, self._heads())

    def _rows_by_head(self, rows):
        """Rows with their heads side by side (..., KV x hd) -> (..., KV, hd)."""
        return rows.reshape(rows.shape[:-1] + (self.kv, self.hd))

    def _decode_gather(self, q, pools, bt, pos, heads):
        """``paged_lm``'s, over pools of whole rows: every lane's padded table
        gathered as flat pages, the heads split after."""
        b, pps = bt.shape
        kc, vc = (self._rows_by_head(jnp.take(pool, bt.reshape(-1), axis=0)
                                     .reshape(b, pps * self.rows, -1)) for pool in pools)
        mask = (jnp.arange(pps * self.rows)[None, :] <= pos[:, None])[:, None, :]
        return self._attend(q[:, None], kc, vc, mask)[:, 0]

    def _tiles_fit(self, T: int) -> bool:
        """Shapes ``launch_walk`` takes."""
        return lat.fits(T, self.rows, self.window, self.heads[0], self.kv, self.hd, self.dtype)

    def _tile(self, a: dict, kp, vp, k, v, kpos, first: int):
        """One tile's attention: ``a["q"]`` (T, H, hd) at positions ``a["qpos"]``
        over what its ring held before the launch (places at positions
        ``rpos``), the launch's own rows k, v (C, KV, hd) at ``kpos`` (``own``:
        the live rows of the tile's prompt), and the summary pages of its
        prompt's earlier windows (block-table row ``row``), ONE softmax -> (T,
        H, hd) float32. The exact part opens the running softmax, the pages' key
        blocks carry it on (``paged_lm._over_key_blocks``'s rule)."""
        heads, (T, H, hd) = self._heads(), a["q"].shape
        c, P, W, g = self.chunk, self.rows, self.window, H // heads.kv
        qg = a["q"].reshape(T, heads.kv, g, hd)
        win = (a["qpos"] // W)[:, None]

        def by_head(rows):   # rows as they lie, (..., KV x hd) or (n, KV, hd) -> (KV, n, hd)
            return rows.reshape(-1, heads.kv, hd).transpose(1, 0, 2)

        # The ring's pages lie one after another in the pool: ONE slice of each pool
        # (as 16 pages taken one by one they were 8.7 ms of a launch on the chip).
        rk, rv = (by_head(jax.lax.dynamic_slice_in_dim(pool, c * a["ring"], c, axis=0))
                  for pool in (kp, vp))
        ek = jnp.concatenate([rk, by_head(k)], axis=1)
        ev = jnp.concatenate([rv, by_head(v)], axis=1)
        see = jnp.concatenate(
            [(a["rpos"] >= 0)[None, :] & (a["rpos"][None, :] // W == win),
             a["own"][None, :] & (kpos[None, :] // W == win)
             & (kpos[None, :] <= a["qpos"][:, None])], axis=1)           # (T, W + C)

        def scores(keys):
            return jnp.einsum("tkgd,kcd->kgtc", qg, keys,
                              preferred_element_type=jnp.float32) * self._scale()

        def weigh(p, values):
            return jnp.einsum("kgtc,kcd->kgtd", p.astype(values.dtype), values,
                              preferred_element_type=jnp.float32)

        s = jnp.where(see[None, None], scores(ek), NEG)
        top = jnp.max(s, axis=-1)
        p = jnp.exp(s - top[..., None])
        carry = (top, jnp.sum(p, axis=-1), weigh(p, ev))
        kb, rowp = self._key_blocks(a["row"], P)

        def body(j, carry):
            top, total, acc = carry
            pg = first + jax.lax.dynamic_slice(rowp, (j * kb,), (kb,))
            sk, sv = (by_head(jnp.take(pool, pg, axis=0)) for pool in (kp, vp))  # (KV, kb x P, hd)
            of = j * kb + jnp.arange(kb * P) // P                        # the window a row sums up
            s = jnp.where((of[None, :] < win)[None, None], scores(sk), NEG)
            top2 = jnp.maximum(top, jnp.max(s, axis=-1))
            p, scale = jnp.exp(s - top2[..., None]), jnp.exp(top - top2)
            return top2, total * scale + jnp.sum(p, axis=-1), acc * scale[..., None] + weigh(p, sv)

        closed = a["last"] // W                       # windows the tile's last row sees
        _, total, acc = jax.lax.fori_loop(0, -(-closed // kb), body, carry)
        return (acc / total[..., None]).transpose(2, 0, 1, 3).reshape(T, H, hd)

    def _attend_tiles(self, q, k, v, kp, vp, m: dict):
        """A launch's attention, every tile: q (C, H, hd), the launch's own
        rows k, v (C, KV, hd), the pools -> o as ``q`` lies, float32."""
        t = m["t"]
        if m["tile_path"] == "tile_kernel":
            return lat.launch_walk(q, kp, vp, self._as_pages(k), self._as_pages(v), m["work"],
                                   scale=self._scale())
        o = jax.lax.map(
            lambda a: self._tile(a, kp, vp, k, v, m["pos"], m["first"]),
            {"q": q.reshape((t["K"], t["T"]) + q.shape[1:]), "qpos": t["qpos"],
             "ring": t["rings"], "rpos": m["rpos"], "own": m["own"], "row": t["rows"],
             "last": t["last"]})
        return o.reshape(q.shape)

    def _as_pages(self, rows):
        """Rows by head (n, KV, hd), n whole pages -> the same bytes as pages of
        whole rows, (n / P, P, KV x hd)."""
        return rows.reshape(-1, self.rows, self.kv * self.hd)

    def _attend_eva(self, lp: dict, q, k, v, kp, vp, m: dict):
        """The EVA mixer in either phase -> (o as ``q`` lies, float32, the two
        pools)."""
        t = m["t"]

        def put(page, off, rows_k, rows_v):   # a token ONE row of each pool: its heads side by side
            return (self._write_pages(kp, page, off, rows_k.reshape(rows_k.shape[0], -1)),
                    self._write_pages(vp, page, off, rows_v.reshape(rows_v.shape[0], -1)))

        if t is None:
            kp, vp = put(m["ring_page"], m["ring_off"], k, v)
            with jax.named_scope("eva_decode"):
                o = self._walk(q, kp, vp, m)
            with jax.named_scope("eva_summarise"):
                # A pool as runs of c rows (a page is whole runs: no row moves),
                # of which each lane takes one: (b, c, KV, hd).
                runs = (-1, self.chunk, self.kv * self.hd)
                ks, vs = self._pool(lp, *(self._rows_by_head(jnp.take(
                    pool.reshape(runs), m["chunk_at"], axis=0)) for pool in (kp, vp)))
            return o, *put(m["sum_page"], m["sum_off"], ks, vs)
        with jax.named_scope("eva_summarise"):
            by_chunk = (-1, self.chunk) + k.shape[1:]
            ks, vs = self._pool(lp, k.reshape(by_chunk), v.reshape(by_chunk))
        kp, vp = put(m["sum_page"], m["sum_off"], ks, vs)

        with jax.named_scope("eva_prefill"):
            # The pools hold the rings as the launch found them (its own rows
            # land there last) and, by now, this launch's summaries.
            o = self._attend_tiles(q, k, v, kp, vp, m)
        # whole pages of the rings: ONE scatter of C / P slabs a pool, a slab a page's bytes
        runs = m["ring_runs"]
        with jax.named_scope("cache_write"):
            return o, kp.at[runs].set(self._as_pages(k)), vp.at[runs].set(self._as_pages(v))

    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        q, k, v, _ = self._qkv(lp, i, self._norm(x, lp["norm1"]), m["pos"])
        o, c["kf"][i], c["vf"][i] = self._attend_eva(lp, q, k, v, c["kf"][i], c["vf"][i], m)
        x = x + self._attn_out(lp, o, None)                       # the stream stays float32
        y, _ = self._ffn(lp, i, self._norm(x, lp["norm2"]), m["live"])
        return x + y, None


def create(cfg: ModelConfig) -> EvaServing:
    return EvaServing(cfg)

