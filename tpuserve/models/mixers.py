"""The mixers that the families built from a pattern of layers share (ISSUE 40;
moved out of ``hybrid.py`` so that ``hybrid_ffn.py`` is not a copy): three
RECURRENT mixers with their state a slot (a Mamba-2 state-space layer; since
ISSUE 53 a gated delta-rule linear-attention layer with a decay a channel; since
ISSUE 59 a gated short convolution, whose whole state is its convolution's
rows), and attention by head over the paged KV, with no position term
(``PlainAttention``) or with an RMSNorm a head on queries and keys and then a
rotary embedding (``RotaryAttention``). All are mix-ins
over ``paged_lm.PagedLM``: they bring tensors, device math, the caches' shapes
and the columns of ``acc``, and know nothing of how a family orders its
layers, names its config keys or adds a mixer's output to the stream.
``PatternMixers`` (Mamba-2), ``DeltaPatternMixers`` (delta rule) and
``ConvPatternMixers`` (short convolution) are one recurrent mixer and an
attention together: layer ``i``'s mixer, whichever it is, in whichever phase
(``_mixer``), for ``paged_lm``'s loop.

WHAT A RECURRENT MIXER OWES THE LOOP (``RecurrentMixer``; all three keep it). A
layer keeps, A SLOT, THE LEAVES ITS MIXER NAMES (the ``slot_block``s of its own
signature, ``_mamba_signature`` and its like), each a block ``leaf[l][slot]``,
and nothing else is allocated: Mamba-2 and the delta rule name two, ``ssm`` and
``conv``, a float32 state and the last ``conv_kernel - 1`` rows of the convolution's
input in the served type; the short convolution names ONE, ``conv``, those rows
alone (it has no other state). A request's FIRST
piece starts from zeros whatever the slot held; a later piece from what the
slot holds; within a launch the tiles of one piece pass the state on and a tile
of another slot does not see it; padded rows leave it as it was; a piece of no
tokens writes nothing; a decode step leaves the state of a lane that is not
live untouched. Prefill computes the recurrence by chunks (a quadratic form
inside a tile, the state passed between a piece's tiles by a ``lax.scan`` or,
for Mamba-2 and the delta rule on the TPU, inside one kernel call; the short
convolution has nothing to pass on but a tile's last rows, and no scan), under
``jax.named_scope("ssm_scan")``: the scan alone, from the convolution to the
gated norm (the short convolution: to its gate); a decode step is one application, under
``jax.named_scope("ssm_update")``: the whole mixer, from the projections to the
out-projection. The four ``SSM_COLUMNS`` count all three mixers' work alike.

``Mamba2Mixer`` (a family calls ``_mamba_setup`` in its constructor and sets
``m_layers``): ``[z | xBC | dt] = u W_in``; a depthwise causal convolution and
SiLU over ``xBC``; per head ``S_t = a_t S_{t-1} + delta_t x_t (x) B_t``, ``y_t =
S_t C_t + D x_t`` with ``delta = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log)
delta)``; ``y <- RMSNorm_group(y silu(z))``; out ``= y W_out``. The state is (H,
P, N); a padded row has ``delta = 0`` (``a = 1``, no input). A launch's chunk is
a prefill tile: with ``cum`` the running sum of ``log a`` inside it, row t reads
row s <= t through ``exp(cum_t - cum_s) delta_s (C_t . B_s)`` and the state the
tile starts from through ``exp(cum_t)``; the tile leaves ``exp(cum_T) S + (x
exp(cum_T - cum) delta)^T B``. Every exponent taken is <= 0; the three products
take their operands in the served type and accumulate in float32. WHERE A
LAUNCH'S CHUNKED SCAN RUNS is chosen when the launch is traced (``_scan_path``:
the backend and the static shapes, no option): on the TPU, at tiles of 128 or
256 rows, a state of whole 128-lane registers and groups of whole blocks of
eight heads, with the slots' states in float32, ONE kernel call a layer
(``_scan_slots``: ``ops/ssm_scan.py``, ISSUE 67: it reads x, B and C out of the
activated rows where they lie and a piece's state out of its SLOT's block, which
it writes in place, holds a tile's table in fast memory, passes the state from
tile to tile inside the call and skips a tile with no live row; nothing of a
tile but y goes back to device memory, and no copy of the pieces' states is
gathered or scattered around it); elsewhere the plain form (``_scan_pieces`` and
``_scan_tiles``: XLA fusions of plain ``jnp``, a (tiles, heads, T, T) float32
table, the states stacked by a ``lax.scan`` between ``_piece_starts`` and
``_store_pieces``), which is also what the kernel is held to in the tests.
``ssm_scans_total{phase=prefill,path=kernel|xla}`` counts a launch's Mamba-2
layers by which.

``DeltaMixer`` (``_delta_setup``; ``m_layers`` too): ``q~, k~, v~ = u W_q, u W_k,
u W_v`` (H heads of D each); a depthwise causal convolution with no bias and
SiLU over each; ``q <- q / |q| / sqrt(D)``, ``k <- k / |k|`` (float32); a log-decay A
CHANNEL ``g = -exp(A_log[h]) softplus(((u W_fa) W_fb) + dt_bias)`` (H, D), ``a =
exp(g)``; ``beta = beta_scale sigmoid(u W_b)`` (2 where the step may pass 1);
``S' = Diag(a) S_{t-1}``, ``S_t = S' + beta k (v - S'^T k)^T``, ``o = S_t^T q``: the
decay BEFORE the correction, the correction reads the decayed state, the read
is of the state after the token's own write; ``y = RMSNorm(o; g_o) sigmoid((u
W_ga) W_gb + b_g)`` (one gain of D shared by the heads); out ``= y W_o``. The
state is (H, D, D) float32; a padded row has ``g = 0`` and ``beta = 0``. A step's
update is ONE kernel call (``ops/delta_update.py``, under
``jax.named_scope("delta_update")``: the state read once and written once in
place) on the TPU and the plain form elsewhere, chosen when the step is traced
(``_delta_path``); ``delta_steps_total{path=kernel|xla}`` counts live lanes x
layers by which. A launch's chunk is a prefill tile: with ``G`` the running sum
of ``g`` inside it, ``(I + A) U = beta (V - (K e^G) S_0)`` for the strictly lower
``A[t, s] = beta_t sum_c k_t k_s e^(G_t - G_s)`` (a unit triangular solve, made
ONCE a tile for the right sides ``beta V`` and ``beta K e^G``: ``U = U_0 - W S_0``;
the inverse by blocks, ``unit_lower_inverse``), ``o = (Q e^G) S_0 + B U`` with
``B[t, s] = sum_c q_t k_s e^(G_t - G_s)`` for ``s <= t``, ``S_C = M S_0 + N`` with ``M = Diag(e^(G_C)) - Kd^T W``, ``N = Kd^T U_0``, ``Kd =
K e^(G_C - G)``: the scan between a piece's tiles is one product a tile. EVERY
EXPONENT TAKEN IS <= 0: ``A`` and ``B`` are made by sub-blocks of ``SUB`` rows,
a block of the diagonal from the differences themselves (three indices), a
block under it from two factors referred to the row block's first row. WHERE A
LAUNCH'S CHUNKED RULE RUNS is chosen when the launch is traced, as the step's
is (``_scan_path``: the backend and the static shapes, no option): on the TPU,
at tiles of whole 128-row tables and heads of 128 channels, ONE kernel call a
layer (``ops/delta_scan.py``, ISSUE 54: it takes what the convolution gives and
g and beta where they lie, holds a tile's tables, inverse and products in fast
memory and passes the state from tile to tile inside the call; nothing of a
tile but o and a piece's last state goes back to device memory); elsewhere the
plain form (``_delta_heads`` and ``_delta_chunks``: XLA fusions of plain
``jnp``, some 140 device operations a layer, the state passed on by a
``lax.scan``), which is also what the kernel is held to in the tests.
``delta_scans_total{phase=prefill,path=kernel|xla}`` counts a launch's delta-rule
layers by which. Both are float32 with every product at ``HIGHEST``.

``ConvMixer`` (``_conv_setup``; ``m_layers`` too): ``[B | C | z] = u W_in``, three
thirds of ``d`` each; ``b = B * z``, THE ROW A SLOT KEEPS (the last ``conv_kernel -
1`` of them, in the served type); ``c_i = sum_j w[j] b_{i - k + 1 + j}``, depthwise
and causal, in float32, no bias and NO activation; out ``= (C * c) W_out``. A
launch's rows are independent but for their ``k - 1`` neighbours: ``_tiles_conv``
and ``_piece_ends`` are the whole of its ``ssm_scan``.

``PlainAttention`` (a family sets ``a_layers``, ``heads``, ``kv``, ``hd`` and
their full counts): grouped KV heads, causal, NO position term, no bias; K and
V in pages of the engine's ledger; where the family sets ``attn_gate``, the
context times ``sigmoid(u W_g)``, elementwise by head, before ``W_o``. A step's
whole mixer, from the projections to ``W_o``, runs under
``jax.named_scope("attn_decode")``, a launch's under ``attn_prefill``; inside
either, and inside ``ssm_update``, the projections are ``proj`` and the pages'
writes ``cache_write`` (``paged_lm``'s vocabulary). ``RotaryAttention`` is that with the rows'
positions read where q and k are made (``_qkv(lp, u, pos)``: ``m["pos"]``, a
step's lanes' or a launch's rows'): ``q <- rope(RMSNorm(q; g_q), pos)``, ``k``
alike, the norm over a head's ``hd`` columns with ONE gain for all heads, FIRST,
then the rotary over all ``hd`` columns in pairs ``(j, j + hd / 2)`` at
``rope_theta``; the pages hold k after both.

SINCE ISSUE 68, a FOURTH recurrent mixer and attention over picked blocks (their
classes' docstrings have each at length; ``BlockPatternMixers`` is the pair).
``LightningMixer`` (``_lightning_setup``; ``m_layers`` too): linear attention with
a CONSTANT decay a head, ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = scale q_t
S_t``, ``lambda_h = exp(-2^(-8 (h + 1) / H))``, q and k normed a head and turned
(``HeadNorms``), the read normed a head and gated; a slot keeps ONE leaf, ``("ssm",)``,
(H, D, D) float32, and NO convolution rows; a launch's chunk is a sub-tile of
``SUB`` rows in the plain form on every backend (every head has its own q and k:
``ops/ssm_scan.py`` takes groups of eight heads that share them).
``BlockSelectAttention``: ``PlainAttention`` whose queries at or past ``dense_len``
attend over the ``topk`` BLOCKS of keys their KV group picks by scores over
mean-pooled keys (a THIRD page leaf ``kc``), the first and the local blocks
always kept, under the scopes ``blk_pool``, ``blk_select`` and ``blk_attend`` inside
``attn_prefill`` / ``attn_decode``. WHERE A LAUNCH'S PICKED TILES' BLOCK SCORES ARE
MADE is chosen when the launch is traced (``_select_path``: the backend and the
static shapes, no option; ISSUE 69): on the TPU ONE call of
``ops/block_scores.py`` a tile (the softmax a head, the group sum and the block
maximum in fast memory, over the window blocks the tile can see), elsewhere
``_block_scores`` in XLA, which is also every step's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from tpuserve.genserve.model import CachePlan, LeafKind, pool, slot_block
from tpuserve.models.decoder import apply_rope
from tpuserve.models.paged_lm import NEG, Column, _mm, counted, rms_norm, scoped, series
from tpuserve.ops import block_scores as bsc
from tpuserve.ops import delta_scan as ds
from tpuserve.ops import delta_update as du
from tpuserve.ops import index_select as ix
from tpuserve.ops import ssm_scan as ss


def softplus_inverse(y: float) -> float:
    return y + math.log(-math.expm1(-y))


class RecurrentMixer:
    """What a recurrent mixer keeps a slot (the ``slot_block`` leaves of its
    own signature), what a launch's scan does for any of them, and what a
    launch counts of them (module docstring)."""

    def _tiles_conv(self, t: dict, rows, c0, w, bias=None):
        """The depthwise causal convolution of a launch's packed ``rows`` (C,
        channels), tile by tile: a tile's rows behind the k-1 rows before them,
        the piece's stored rows ``c0`` (K, k-1, channels) for the tile that
        opens it, else the tile before. ``w`` (k, channels), ``bias`` (channels,)
        or None -> (the tiles that open a piece (K,), the live rows (K, T), the
        rows with what is before them (K, k-1 + T, channels), the convolved rows
        (K, T, channels) float32)."""
        K, T, kc = t["K"], t["T"], self.conv_k - 1
        if T < kc:
            raise ValueError(f"{self.name}: a tile of {T} rows is shorter than the "
                             f"convolution's {kc} stored rows")
        opens = t["tiles"] == t["first_tile"][t["piece"]]   # a tile that opens its piece
        live = t["valid"].reshape(K, T)
        xt = rows.reshape(K, T, -1)
        prev = jnp.where(opens[:, None, None], c0[t["piece"]],
                         jnp.roll(xt[:, T - kc:], 1, axis=0))
        seq = jnp.concatenate([prev, xt], axis=1)                         # (K, kc + T, ch)
        w = w.astype(jnp.float32)
        b = None if bias is None else bias.astype(jnp.float32)
        conv = sum(seq[:, j:j + T].astype(jnp.float32) * w[j] for j in range(kc + 1))
        return opens, live, seq, conv if b is None else b + conv

    def _piece_ends(self, t: dict, live, seq):
        """By piece: its last tile, and the k-1 rows of ``seq`` that end at its
        last live row (a piece shorter than that keeps rows it came with).
        ``live`` (K, T)."""
        kc = self.conv_k - 1
        last_tile = jnp.clip(t["first_tile"] + t["n_tiles"] - 1, 0, t["K"] - 1)
        n_last = jnp.sum(live[last_tile], axis=1)
        tail = jnp.take_along_axis(
            seq[last_tile], (n_last[:, None] + jnp.arange(kc)[None, :])[:, :, None], axis=1)
        return last_tile, tail

    @staticmethod
    def _piece_starts(slot, start, *, rows: tuple = (), states: tuple = ()) -> tuple:
        """What each piece of a launch starts from, a leaf of its slot's state:
        zeros where it opens its prompt, else what its slot holds. ``states``:
        the leaves a scan computes on in float32, (slots, ., ., .) each;
        ``rows``: the convolution's stored rows (slots, k-1, channels), which
        stay in their own type -> (the states (K, ...) float32, the rows)."""
        fresh = (start == 0)[:, None, None]
        at = jnp.minimum(slot, (rows or states)[0].shape[0] - 1)
        return (tuple(jnp.where(fresh[..., None], 0.0, s[at].astype(jnp.float32))
                      for s in states),
                tuple(jnp.where(fresh, jnp.zeros((), r.dtype), r[at]) for r in rows))

    @staticmethod
    def _store_pieces(leaves: tuple, slot, length, ends: tuple) -> tuple:
        """Each piece's ``ends`` (a (K, ...) a leaf) into its slot. A piece of
        no tokens writes nothing: its slot is out of range."""
        to = jnp.where(length > 0, slot, leaves[0].shape[0])
        return tuple(x.at[to].set(e.astype(x.dtype), mode="drop")
                     for x, e in zip(leaves, ends))

    def _counts(self, m: dict) -> dict:
        """And live tokens (through a scan layer), slot states read and
        written and, in a launch, the pieces that started from zeros and
        from a stored state."""
        if m["t"] is None:
            n_live = jnp.sum(m["live"])
            return {**super()._counts(m), "tokens": n_live, "rows": n_live,
                    "zero": 0, "carried": 0}
        start, has = m["start"], m["length"] > 0
        return {**super()._counts(m), "tokens": jnp.sum(m["live"]), "rows": jnp.sum(has),
                "zero": jnp.sum(has & (start == 0)), "carried": jnp.sum(has & (start > 0))}


class Mamba2Mixer(RecurrentMixer):

    def _mamba_setup(self, name: str, *, heads: int, head_dim: int, groups: int, state: int,
                     conv_kernel: int, conv_bias: bool, share: list,
                     dt_range: tuple[float, float]) -> None:
        """The layer's numbers, and the part held here: ``share`` = [index,
        of], heads AND groups ``index`` of ``of`` (``W_in``'s columns, the
        convolution's channels and ``W_out``'s rows with them; the gated norm
        is over a group, so a share of whole groups is exact)."""
        self.mh_full, self.mp, self.mg_full, self.mn = heads, head_dim, groups, state
        self.conv_k, self.conv_bias, self.dt_range = conv_kernel, conv_bias, dt_range
        m_idx, m_of = share
        if self.mh_full % self.mg_full or self.mg_full % m_of:
            raise ValueError(f"{name}: share.mamba_heads = [{m_idx}, {m_of}] does not "
                             f"divide {self.mg_full} groups of {self.mh_full} heads")
        self.mh, self.mg = self.mh_full // m_of, self.mg_full // m_of
        self.mh_first, self.mg_first = m_idx * self.mh, m_idx * self.mg
        self.conv_ch = self.mh * self.mp + 2 * self.mg * self.mn

    # -- params ---------------------------------------------------------------
    def _mamba_gains(self):
        for i in self.m_layers:
            yield (f"layer{i}", "gate_norm"), (self.mh, self.mp)

    def _mamba_tensors(self):
        """A Mamba-2 layer's in-projection is drawn in its five parts (z, x,
        B, C, dt), each a tensor of its own, so that a share is a slice of
        each; ``_join_mamba`` joins them into ``w_in``."""
        d, s = self.d, self.scales
        hf, h, h0, p = self.mh_full, self.mh, self.mh_first, self.mp
        gf, g, g0, n, k = self.mg_full, self.mg, self.mg_first, self.mn, self.conv_k
        for i in self.m_layers:
            L = f"layer{i}"
            for part, scale in (("z", s["ssm_in"]), ("x", s["ssm_in"])):
                yield ((L, f"in_{part}"), (d, h, p), (d, hf, p), (0, h0, 0), scale, d)
            for part in ("B", "C"):
                yield ((L, f"in_{part}"), (d, g, n), (d, gf, n), (0, g0, 0), s["ssm_bc"], d)
            yield ((L, "in_dt"), (d, h), (d, hf), (0, h0), s["ssm_dt"], d)
            yield ((L, "conv_x"), (k, h, p), (k, hf, p), (0, h0, 0), s["conv"], k)
            yield ((L, "conv_bias_x"), (h, p), (hf, p), (h0, 0), s["conv_bias"], 1)
            for part in ("B", "C"):
                yield ((L, f"conv_{part}"), (k, g, n), (k, gf, n), (0, g0, 0), s["conv"], k)
                yield ((L, f"conv_bias_{part}"), (g, n), (gf, n), (g0, 0), s["conv_bias"], 1)
            yield ((L, "w_out"), (h, p, d), (hf, p, d), (h0, 0, 0), s["ssm_out"], hf * p)

    def _mamba_vectors(self):
        """A scan layer's float32 vectors, drawn INSIDE a range: ``dt_bias``
        (softplus of it in ``dt_range``), ``A_log`` (A in [1, 16]) and ``D``
        (about 1)."""
        lo, hi = (softplus_inverse(v) for v in self.dt_range)
        h = ((self.mh,), (self.mh_full,), (self.mh_first,))
        d3 = 3.0 * self.scales["ssm_d"]
        for i in self.m_layers:
            yield ((f"layer{i}", "dt_bias"), *h, lo, hi)
            yield ((f"layer{i}", "A_log"), *h, 0.0, math.log(16.0))
            yield ((f"layer{i}", "D"), *h, 1.0 - d3, 1.0 + d3)

    def _join_mamba(self, p: dict) -> None:
        for i in self.m_layers:
            lp, flat = p[f"layer{i}"], lambda t, lead: t.reshape(t.shape[:lead] + (-1,))
            lp["w_in"] = jnp.concatenate(
                [flat(lp.pop(f"in_{part}"), 1) for part in ("z", "x", "B", "C", "dt")], axis=1)
            lp["conv_w"] = jnp.concatenate(
                [flat(lp.pop(f"conv_{part}"), 1) for part in ("x", "B", "C")], axis=1)
            bias = jnp.concatenate(
                [flat(lp.pop(f"conv_bias_{part}"), 0) for part in ("x", "B", "C")], axis=0)
            lp["conv_b"] = bias if self.conv_bias else jnp.zeros_like(bias)

    def _mamba_signature(self, slots: int) -> dict:
        S = jax.ShapeDtypeStruct
        return {"ssm": slot_block([S((slots, self.mh, self.mp, self.mn), jnp.float32)
                                   for _ in self.m_layers]),
                "conv": slot_block([S((slots, self.conv_k - 1, self.conv_ch), self.dtype)
                                    for _ in self.m_layers])}

    # -- device math --------------------------------------------------------------
    @scoped("proj")
    def _split_in(self, lp: dict, u: jax.Array):
        """``u`` (T, d) -> z (T, H, P), xBC (T, channels) before the
        convolution, dt (T, H) in float32."""
        hp = self.mh * self.mp
        zxd = _mm(u, lp["w_in"])
        z = zxd[:, :hp].reshape(-1, self.mh, self.mp)
        return z, zxd[:, hp:hp + self.conv_ch].astype(self.dtype), zxd[:, hp + self.conv_ch:]

    def _split_xbc(self, xbc: jax.Array):
        """Convolved (..., channels) float32 -> x (..., H, P), B and C (..., G, N),
        after the SiLU, in the served type."""
        hp, gn = self.mh * self.mp, self.mg * self.mn
        a = jax.nn.silu(xbc).astype(self.dtype)
        lead = a.shape[:-1]
        return (a[..., :hp].reshape(lead + (self.mh, self.mp)),
                a[..., hp:hp + gn].reshape(lead + (self.mg, self.mn)),
                a[..., hp + gn:].reshape(lead + (self.mg, self.mn)))

    def _decay(self, lp: dict, dt: jax.Array, live: jax.Array):
        """dt (..., H) float32, live (...,) -> (delta, log a), both (..., H)
        float32, zero where a row is not live: its state passes unchanged."""
        delta = jnp.where(live[..., None], jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
        return delta, -jnp.exp(lp["A_log"]) * delta

    @scoped("norm")
    def _gated_norm(self, lp: dict, y: jax.Array, z: jax.Array) -> jax.Array:
        """y (T, H, P) float32 gated by silu(z) and normed over each GROUP of
        heads (gate before norm) -> (T, H, P) in the served type."""
        t = y.shape[0]
        g = (y * jax.nn.silu(z)).reshape(t, self.mg, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + self.eps)
        g = g.reshape(t, self.mh, self.mp) * lp["gate_norm"].astype(jnp.float32)
        return g.astype(self.dtype)

    @scoped("proj")
    def _out_proj(self, lp: dict, g: jax.Array) -> jax.Array:
        return jnp.einsum("thp,hpd->td", g, lp["w_out"], preferred_element_type=jnp.float32)

    def _scan_rows(self, lp: dict, xbc, dt, t: dict, c0):
        """What either path of a launch's scan starts from: ``xbc`` (C,
        channels) and ``dt`` (C, H) of the packed rows, ``c0`` (K, k-1, channels)
        the convolution's rows each PIECE starts from -> (the tiles that open a
        piece (K,), the live rows (K, T), the convolved rows (K, T, channels)
        float32, delta and the running sum of the log-decay down each tile (K,
        T, H) float32, by piece its last tile and the convolution's rows it
        ends with)."""
        K, T, H = t["K"], t["T"], self.mh
        opens, live, seq, conv = self._tiles_conv(t, xbc, c0, lp["conv_w"], lp["conv_b"])
        delta, la = self._decay(lp, dt.reshape(K, T, H), live)
        return (opens, live, conv, delta, jnp.cumsum(la, axis=1),
                *self._piece_ends(t, live, seq))

    def _scan_slots(self, lp: dict, xbc, dt, t: dict, ssm, conv, slot, start, length):
        """The chunked scan of one launch as ONE kernel call (``ops/ssm_scan.py``)
        ON THE SLOTS' BLOCK ``ssm`` (slots, H, P, N) float32, in place: the call
        reads a piece's state out of its slot when the piece's first tile
        begins (zeros where the piece opens its prompt) and writes what its last
        tile leaves back into it, so no (K, H, P, N) copy of the pieces' states
        is gathered before the scan nor scattered after it. -> y (C, H, P)
        float32, the block, the convolution's rows (slots, k-1, channels)."""
        _none, (c0,) = self._piece_starts(slot, start, rows=(conv,))
        opens, live, rows, delta, cum, _last, tail = self._scan_rows(lp, xbc, dt, t, c0)
        alive, piece = jnp.any(live, axis=1), t["piece"]       # live tiles come first, in a run
        # A tile begins from zeros, from its slot's state, or goes on from the
        # tile before; a launch of no live tile passes one slot's state through.
        begins = jnp.where(alive & opens, jnp.where(start[piece] == 0, ss.ZEROS, ss.STORED),
                           jnp.where(alive | (t["tiles"] > 0), ss.GOES_ON, ss.STORED))
        of_tile = jnp.clip(slot[piece], 0, ssm.shape[0] - 1)
        at = jnp.where(alive, of_tile, of_tile[jnp.maximum(jnp.sum(alive) - 1, 0)])
        y, ssm = ss.ssm_scan(jax.nn.silu(rows).astype(self.dtype), delta, cum, lp["D"], ssm,
                             begins, at, alive, head_dim=self.mp, state=self.mn)
        (conv,) = self._store_pieces((conv,), slot, length, (tail,))
        return y, ssm, conv

    def _scan_pieces(self, lp: dict, xbc, dt, t: dict, ssm, conv, slot, start, length):
        """The chunked scan of one launch in plain ``jnp``: the pieces' states
        gathered from their slots, ``_scan_tiles``, the ends scattered back. ->
        as ``_scan_slots``."""
        (s0,), (c0,) = self._piece_starts(slot, start, states=(ssm,), rows=(conv,))
        y, s_end, c_end = self._scan_tiles(lp, xbc, dt, t, s0, c0)
        return (y, *self._store_pieces((ssm, conv), slot, length, (s_end, c_end)))

    def _scan_tiles(self, lp: dict, xbc, dt, t: dict, s0, c0):
        """The plain form of one launch's chunked scan, what every backend but
        the TPU runs and what the kernel is held to: ``xbc`` (C, channels) and
        ``dt`` (C, H) of the packed rows; ``s0`` (K, H, P, N) float32 and ``c0``
        (K, k-1, channels) what each PIECE starts from. -> y (C, H, P) float32
        and, by piece, the state and the convolution's rows it ends with."""
        K, T = t["K"], t["T"]
        H, P, G, N = self.mh, self.mp, self.mg, self.mn
        piece = t["piece"]
        opens, _live, conv, delta, cum, last_tile, tail = self._scan_rows(lp, xbc, dt, t, c0)
        x, B, C = self._split_xbc(conv)
        # Inside a tile, the quadratic form: row t reads row s <= t through
        # exp(cum_t - cum_s) delta_s (C_t . B_s).
        cb = jnp.einsum("ktgn,ksgn->kgts", C, B, preferred_element_type=jnp.float32)
        diff = cum.transpose(0, 2, 1)[:, :, :, None] - cum.transpose(0, 2, 1)[:, :, None, :]
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        m = jnp.exp(jnp.where(causal, diff, -jnp.inf)) \
            * jnp.repeat(cb, H // G, axis=1) * delta.transpose(0, 2, 1)[:, :, None, :]
        y = jnp.einsum("khts,kshp->kthp", m.astype(self.dtype), x,
                       preferred_element_type=jnp.float32)
        # What a tile adds to the state, and how much of what came in is left.
        to_end = jnp.exp(cum[:, -1:, :] - cum) * delta                    # (K, T, H)
        xg = (x * to_end[..., None]).astype(self.dtype).reshape(K, T, G, H // G, P)
        add = jnp.einsum("ksgjp,ksgn->kgjpn", xg, B,
                         preferred_element_type=jnp.float32).reshape(K, H, P, N)
        keep = jnp.exp(cum[:, -1, :])                                     # (K, H)

        def pass_on(carry, tile):
            opens_j, start_j, keep_j, add_j = tile
            s_in = jnp.where(opens_j, start_j, carry)
            s_out = keep_j[:, None, None] * s_in + add_j
            return s_out, (s_in, s_out)

        _, (s_in, s_out) = jax.lax.scan(
            pass_on, jnp.zeros((H, P, N), jnp.float32), (opens, s0[piece], keep, add))
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "kgjpn,ktgn->ktgjp", s_in.astype(self.dtype).reshape(K, G, H // G, P, N), C,
            preferred_element_type=jnp.float32).reshape(K, T, H, P)
        y = y + lp["D"][:, None] * x.astype(jnp.float32)
        return y.reshape(K * T, H, P), s_out[last_tile], tail

    def _mamba_prefill(self, lp, u, t, ssm, conv, slot, start, length, path: str = "xla"):
        """One Mamba-2 layer of a launch, its scan in the kernel or in the plain
        form by ``path``. The scope ``ssm_scan`` is the scan alone, from the
        convolution to the gated norm: the two projections are outside it."""
        z, xbc, dt = self._split_in(lp, u)
        with jax.named_scope("ssm_scan"):
            scan = self._scan_slots if path == "kernel" else self._scan_pieces
            y, ssm, conv = scan(lp, xbc, dt, t, ssm, conv, slot, start, length)
            g = self._gated_norm(lp, y, z)
        return self._out_proj(lp, g), ssm, conv

    def _mamba_step(self, lp, u, live, ssm, conv):
        """One application of the recurrence for every lane: the state of a
        lane that is not live stays as it was. The scope ``ssm_update`` is the
        whole mixer, from the in-projection to the out-projection."""
        with jax.named_scope("ssm_update"):
            z, xbc, dt = self._split_in(lp, u)
            seq = jnp.concatenate([conv, xbc[:, None]], axis=1)          # (b, k, ch)
            w = lp["conv_w"].astype(jnp.float32)
            x, B, C = self._split_xbc(lp["conv_b"].astype(jnp.float32) + jnp.sum(
                seq.astype(jnp.float32) * w[None], axis=1))
            delta, la = self._decay(lp, dt, live)
            rep = self.mh // self.mg
            Bh = jnp.repeat(B.astype(jnp.float32), rep, axis=1)          # (b, H, N)
            Ch = jnp.repeat(C.astype(jnp.float32), rep, axis=1)
            xf = x.astype(jnp.float32)
            s = jnp.exp(la)[..., None, None] * ssm.astype(jnp.float32) \
                + (delta[..., None] * xf)[..., None] * Bh[:, :, None, :]
            y = jnp.sum(s * Ch[:, :, None, :], axis=-1) + lp["D"][:, None] * xf
            out = self._out_proj(lp, self._gated_norm(lp, y, z))
            keep = live[:, None, None]
            new_ssm = jnp.where(keep[..., None], s.astype(ssm.dtype), ssm)
            new_conv = jnp.where(keep, seq[:, 1:], conv)
        return out, new_ssm, new_conv

    def _mamba(self, lp, u, ssm, conv, m: dict):
        """One Mamba-2 layer in the phase the plan ``m`` is of."""
        if m["t"] is None:
            return self._mamba_step(lp, u, m["live"], ssm, conv)
        return self._mamba_prefill(lp, u, m["t"], ssm, conv, m["slot"], m["start"], m["length"],
                                   m["scan_path"])

    def _scan_path(self, t: dict, ssm) -> str:
        """Where a launch's chunked scans run, chosen when the launch is
        traced: the kernel on the TPU at shapes it takes, else the plain form."""
        on_tpu = jax.default_backend() == "tpu" and ss.supported(
            t["T"], self.mh, self.mp, self.mn, self.mg, ssm.dtype)
        return "kernel" if on_tpu else "xla"  # tps-ok[TPS503]: backend and static shapes

    def _prefill_plan(self, state, launch, t: dict) -> dict:
        """And where the launch's chunked scans run, chosen once for all its layers."""
        return {**super()._prefill_plan(state, launch, t),
                "scan_path": self._scan_path(t, state["ssm"][0])}

    def _counts(self, m: dict) -> dict:
        """And a launch itself by where its chunked scans ran."""
        return {**super()._counts(m), "scans": {p: int(m.get("scan_path") == p) for p in PATHS}}


def _pieces(start: str):
    """``ssm_pieces_total{model=,start=}``: a launch's pieces, so prefill's alone."""
    return lambda model, metrics, ph: metrics.counter(
        f"ssm_pieces_total{{model={model.name},start={start}}}") if ph == "prefill" else None


# Live tokens through a scan layer and slot states read and written (both
# times the scan layers), and (prefill) pieces that started from zeros / from a
# stored state.
SSM_COLUMNS = (
    Column(lambda model, stats, counts: counts["tokens"] * len(model.m_layers),
           series("ssm_tokens_total")),
    Column(lambda model, stats, counts: counts["rows"] * len(model.m_layers),
           series("ssm_state_rows_total")),
    Column(counted("zero"), _pieces("zero")),
    Column(counted("carried"), _pieces("carried")))


PATHS = ("kernel", "xla")   # where a launch's chunked scan or a step's delta-rule update ran
_HI = {"precision": jax.lax.Precision.HIGHEST, "preferred_element_type": jnp.float32}


def unit_lower_inverse(a: jax.Array, sub: int) -> jax.Array:
    """``(I + a)^-1`` for ``a`` (..., T, T) float32 strictly lower triangular, in
    a dozen batched products: the ``T / sub`` diagonal blocks of ``sub`` rows by
    their own finite series, ``(I - a)(I + a^2)(I + a^4)...`` (``a`` is nilpotent),
    then pairs of neighbours joined, ``[[P, 0], [-Q a21 P, Q]]``, until one block is
    left. (XLA's own triangular solve inverts a tile's blocks one row after
    another: 5.3 ms a layer a launch at the cell's 512 tiles by heads, half the
    scan: my chip run, PR 53.) Where ``T / sub`` is no power of two the whole
    matrix is one block."""
    T, lead = a.shape[-1], a.shape[:-2]
    n = T // sub if T % sub == 0 else 1
    if n & (n - 1):
        n = 1
    m = T // n
    a = a.reshape((-1, T, T))

    def blocks(first: int, step: int):
        """Blocks (i, j) of the n x n grid of m x m blocks at i n + j = first,
        first + step, ..., as ONE batch (tiles x blocks, m, m): a transpose and
        a strided slice, no gather."""
        flat = a.reshape((-1, n, m, n, m)).swapaxes(2, 3).reshape((-1, n * n, m, m))
        return flat[:, first::step].reshape((-1, m, m))

    def mm(x, y):
        return jnp.matmul(x, y, **_HI)

    diag = blocks(0, n + 1)                                                # (tiles x n, m, m)
    inv, power, terms = jnp.eye(m, dtype=a.dtype) - diag, diag, 2
    while terms < m:
        power = mm(power, power)
        inv = inv + mm(inv, power)
        terms *= 2
    while n > 1:
        inv = inv.reshape((-1, 2, m, m))
        p, q = inv[:, 0], inv[:, 1]
        low = -mm(q, mm(blocks(n, 2 * (n + 1)), p))                        # -Q a21 P
        inv = jnp.concatenate([jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
                               jnp.concatenate([low, q], axis=-1)], axis=-2)
        n, m = n // 2, 2 * m
    return inv.reshape(lead + (T, T))


class DeltaMixer(RecurrentMixer):
    SUB = 16       # rows of a sub-block of the pair tables (module docstring)
    L2_EPS = 1e-6  # under the root of q's and k's norms

    def _delta_setup(self, *, heads: int, head_dim: int, rank: int, conv_kernel: int,
                     beta_scale: float) -> None:
        """The layer's numbers: ``heads`` of ``head_dim`` for keys and values
        alike, the low ``rank`` of the decay's and the gate's projections, and
        what multiplies the sigmoid of the step (2: a step past 1, an
        eigenvalue under 0)."""
        self.kh, self.kd, self.k_rank = heads, head_dim, rank
        self.conv_k, self.beta_scale = conv_kernel, float(beta_scale)
        self.conv_ch = 3 * heads * head_dim

    # -- params ---------------------------------------------------------------
    def _delta_gains(self):
        for i in self.m_layers:
            yield (f"layer{i}", "o_norm"), (self.kd,)

    def _delta_tensors(self):
        """q, k and v are drawn as three tensors (and the convolution's three),
        ``_join_delta`` joins them into ``w_qkv`` and ``conv_w``."""
        d, s, h, D, r, k = self.d, self.scales, self.kh, self.kd, self.k_rank, self.conv_k
        for i in self.m_layers:
            L = f"layer{i}"
            for part in ("q", "k", "v"):
                yield ((L, f"w{part}"), (d, h, D), (d, h, D), (0, 0, 0), s["kda_in"], d)
                yield ((L, f"conv_{part}"), (k, h, D), (k, h, D), (0, 0, 0), s["conv"], k)
            for part, role in (("f", "kda_decay"), ("g", "kda_gate")):
                yield ((L, f"w_{part}a"), (d, r), (d, r), (0, 0), s[role], d)
                yield ((L, f"w_{part}b"), (r, h, D), (r, h, D), (0, 0, 0), s[role], r)
            yield ((L, "w_b"), (d, h), (d, h), (0, 0), s["kda_beta"], d)
            yield ((L, "w_out"), (h, D, d), (h, D, d), (0, 0, 0), s["kda_out"], h * D)

    def _delta_vectors(self):
        """A layer's float32 vectors, drawn INSIDE a range: ``A_log`` a head (A
        in ``decay_rate``), ``dt_bias`` a channel (softplus of it in
        ``decay_step``) and the gate's bias ``b_g`` a channel (about 0)."""
        h, D, s = self.kh, self.kd, self.scales
        lo, hi = (softplus_inverse(v) for v in s["decay_step"])
        for i in self.m_layers:
            L = f"layer{i}"
            yield ((L, "A_log"), (h,), (h,), (0,), *(math.log(v) for v in s["decay_rate"]))
            yield ((L, "dt_bias"), (h, D), (h, D), (0, 0), lo, hi)
            b3 = 3.0 * s["gate_bias"]
            yield ((L, "b_g"), (h, D), (h, D), (0, 0), -b3, b3)

    def _join_delta(self, p: dict) -> None:
        for i in self.m_layers:
            lp, flat = p[f"layer{i}"], lambda t: t.reshape(t.shape[0], -1)
            lp["w_qkv"] = jnp.concatenate([flat(lp.pop(f"w{c}")) for c in "qkv"], axis=1)
            lp["conv_w"] = jnp.concatenate([flat(lp.pop(f"conv_{c}")) for c in "qkv"], axis=1)

    def _delta_signature(self, slots: int) -> dict:
        S = jax.ShapeDtypeStruct
        return {"ssm": slot_block([S((slots, self.kh, self.kd, self.kd), jnp.float32)
                                   for _ in self.m_layers]),
                "conv": slot_block([S((slots, self.conv_k - 1, self.conv_ch), self.dtype)
                                    for _ in self.m_layers])}

    # -- device math --------------------------------------------------------------
    def _delta_heads(self, conv: jax.Array):
        """Convolved (..., channels) float32 -> q, k, v (..., H, D) float32 after
        the SiLU: q of length 1 / sqrt(D), k of length 1."""
        a = jax.nn.silu(conv).reshape(conv.shape[:-1] + (3, self.kh, self.kd))
        q, k, v = a[..., 0, :, :], a[..., 1, :, :], a[..., 2, :, :]

        def unit(x):
            return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + self.L2_EPS)

        return unit(q) * self.kd ** -0.5, unit(k), v

    @scoped("proj")
    def _decay_beta(self, lp: dict, u: jax.Array, live: jax.Array):
        """``u`` (T, d), ``live`` (T,) -> (the log-decay a channel ``g`` (T, H, D)
        <= 0, ``beta`` (T, H)), float32, both zero where a row is not live: its
        state passes unchanged."""
        f = jnp.einsum("tr,rhc->thc", _mm(u, lp["w_fa"]).astype(self.dtype), lp["w_fb"],
                       preferred_element_type=jnp.float32)
        g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(f + lp["dt_bias"])
        beta = self.beta_scale * jax.nn.sigmoid(_mm(u, lp["w_b"]))
        return jnp.where(live[:, None, None], g, 0.0), jnp.where(live[:, None], beta, 0.0)

    def _delta_gated(self, lp: dict, u: jax.Array, o: jax.Array) -> jax.Array:
        """o (T, H, D) float32 normed over a head (one gain of D for all heads),
        times the sigmoid gate from ``u`` -> (T, H, D) in the served type."""
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + self.eps) \
            * lp["o_norm"].astype(jnp.float32)
        gate = jnp.einsum("tr,rhc->thc", _mm(u, lp["w_ga"]).astype(self.dtype), lp["w_gb"],
                          preferred_element_type=jnp.float32) + lp["b_g"]
        return (o * jax.nn.sigmoid(gate)).astype(self.dtype)

    @scoped("proj")
    def _delta_out(self, lp: dict, y: jax.Array) -> jax.Array:
        return jnp.einsum("thp,hpd->td", y, lp["w_out"], preferred_element_type=jnp.float32)

    def _pair_tables(self, q, k, G):
        """q, k, G (..., T, D) float32, ``G`` the running sum of the log-decay
        inside a tile -> (``sum_c k_t k_s e^(G_t - G_s)`` for s < t, ``sum_c q_t k_s
        e^(G_t - G_s)`` for s <= t), each (..., T, T), zero elsewhere. No exponent
        taken is above 0: a block of ``SUB`` x ``SUB`` on the diagonal takes the
        differences themselves, a row block's part under the diagonal the two
        factors ``e^(G_t - G_i)`` and ``e^(G_i - G_s)`` about its own first row i."""
        T, D = G.shape[-2:]
        sub = self.SUB if T % self.SUB == 0 else T
        n, lead = T // sub, G.shape[:-2]
        qb, kb, Gb = (x.reshape(lead + (n, sub, D)) for x in (q, k, G))
        at = jnp.arange(sub)
        e = jnp.exp(jnp.where((at[:, None] >= at[None, :])[:, :, None],
                              Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf))
        on = [jnp.sum(x[..., :, None, :] * kb[..., None, :, :] * e, axis=-1) for x in (kb, qb)]
        strict = (at[:, None] > at[None, :])
        on[0] = jnp.where(strict, on[0], 0.0)
        if n == 1:
            return on[0][..., 0, :, :], on[1][..., 0, :, :]
        first = Gb[..., :1, :]                                             # (..., n, 1, D)
        right = k[..., None, :, :] * jnp.exp(jnp.minimum(first - G[..., None, :, :], 0.0))
        left = jnp.exp(Gb - first)
        blocks = jnp.arange(n)
        under = (blocks[:, None] > blocks[None, :])[:, None, :, None]      # (n, 1, n, 1)
        same = (blocks[:, None] == blocks[None, :])[:, None, :, None]
        out = []
        for x, d in zip((kb, qb), on):
            off = jnp.einsum("...itc,...isc->...its", x * left, right, **_HI) \
                .reshape(lead + (n, sub, n, sub))
            full = jnp.where(under, off, jnp.where(same, d[..., :, :, None, :], 0.0))
            out.append(full.reshape(lead + (T, T)))
        return tuple(out)

    def _delta_chunks(self, q, k, v, g, b, opens, s0):
        """The plain form of a launch's chunked rule (module docstring), what
        every backend but the TPU runs and what the kernel is held to: ``q``,
        ``k``, ``v``, ``g`` (K, H, T, D) float32 by tile, ``b`` (K, H, T), ``opens``
        (K,) and ``s0`` (K, H, D, D) what each TILE would start from if it opens
        its piece -> (o (K, H, T, D), the state each tile ends with)."""
        D = self.kd
        G, b = jnp.cumsum(g, axis=2), b[..., None]                              # (K, H, T, 1)
        kk, qk = self._pair_tables(q, k, G)
        eG = jnp.exp(G)
        # (I + A) [U_0 | W] = beta [V | K e^G]: one unit triangular inverse a tile.
        sol = jnp.einsum("khts,khsv->khtv", unit_lower_inverse(b * kk, self.SUB),
                         b * jnp.concatenate([v, k * eG], axis=-1), **_HI)
        u0, w = sol[..., :D], sol[..., D:]
        to_end = k * jnp.exp(G[:, :, -1:, :] - G)                               # K e^(G_C - G)
        keep = jnp.eye(D, dtype=jnp.float32) * eG[:, :, -1, :, None] \
            - jnp.einsum("khtc,khte->khce", to_end, w, **_HI)
        add = jnp.einsum("khtc,khtv->khcv", to_end, u0, **_HI)

        def pass_on(carry, tile):
            opens_j, start_j, keep_j, add_j = tile
            s_in = jnp.where(opens_j, start_j, carry)
            s_out = jnp.einsum("hce,hev->hcv", keep_j, s_in, **_HI) + add_j
            return s_out, (s_in, s_out)

        _, (s_in, s_out) = jax.lax.scan(
            pass_on, jnp.zeros(s0.shape[1:], jnp.float32), (opens, s0, keep, add))
        u = u0 - jnp.einsum("khtc,khcv->khtv", w, s_in, **_HI)
        o = jnp.einsum("khtc,khcv->khtv", q * eG, s_in, **_HI) \
            + jnp.einsum("khts,khsv->khtv", qk, u, **_HI)
        return o, s_out

    def _delta_tiles(self, lp: dict, qkv, g, beta, t: dict, s0, c0, path: str = "xla"):
        """The chunked delta rule of one launch (module docstring): ``qkv`` (C,
        channels) before the convolution, ``g`` (C, H, D) and ``beta`` (C, H) of
        the packed rows (zero where a row is not live); ``s0`` (K, H, D, D)
        float32 and ``c0`` (K, k-1, channels) what each PIECE starts from;
        ``path``: ONE kernel call (``ops/delta_scan.py``) or the plain form. -> o
        (C, H, D) float32 and, by piece, the state and the convolution's rows
        it ends with."""
        K, T, H, D = t["K"], t["T"], self.kh, self.kd
        opens, live, seq, conv = self._tiles_conv(t, qkv, c0, lp["conv_w"])
        g, beta = g.reshape(K, T, H, D), beta.reshape(K, T, H)
        last_tile, tail = self._piece_ends(t, live, seq)
        if path == "kernel":
            o, s_end = ds.delta_scan(conv, g, beta, s0, opens, t["piece"], l2_eps=self.L2_EPS)
        else:
            q, k, v = self._delta_heads(conv)                                   # (K, T, H, D)
            o, s_out = self._delta_chunks(*(x.transpose(0, 2, 1, 3) for x in (q, k, v, g)),
                                          beta.transpose(0, 2, 1), opens, s0[t["piece"]])
            o, s_end = o.transpose(0, 2, 1, 3), s_out[last_tile]
        return o.reshape(K * T, H, D), s_end, tail

    def _delta_prefill(self, lp, u, t, ssm, conv, slot, start, length, path: str):
        """One delta-rule layer of a launch. The scope ``ssm_scan`` is the scan
        alone, from the convolution to the gated norm: the projections are
        outside it."""
        with jax.named_scope("proj"):
            qkv = _mm(u, lp["w_qkv"]).astype(self.dtype)
        g, beta = self._decay_beta(lp, u, t["valid"])
        with jax.named_scope("ssm_scan"):
            (s0,), (c0,) = self._piece_starts(slot, start, states=(ssm,), rows=(conv,))
            o, s_end, c_end = self._delta_tiles(lp, qkv, g, beta, t, s0, c0, path)
            y = self._delta_gated(lp, u, o)
            ssm, conv = self._store_pieces((ssm, conv), slot, length, (s_end, c_end))
        return self._delta_out(lp, y), ssm, conv

    def _scan_path(self, t: dict) -> str:
        """Where a launch's chunked rule runs, chosen when the launch is
        traced: the kernel on the TPU at shapes it takes, else the plain form."""
        on_tpu = jax.default_backend() == "tpu" and ds.supported(t["T"], self.kh, self.kd)
        return "kernel" if on_tpu else "xla"  # tps-ok[TPS503]: backend and static shapes

    def _delta_path(self, ssm) -> str:
        """Where a step's update runs, chosen when the step is traced: the
        kernel on the TPU at shapes it takes, else the plain form."""
        on_tpu = jax.default_backend() == "tpu" and du.supported(ssm)
        return "kernel" if on_tpu else "xla"  # tps-ok[TPS503]: backend and static shapes

    def _delta_step(self, lp, u, live, ssm, conv, path: str):
        """One application of the rule for every lane: the state of a lane that
        is not live stays as it was. The scope ``ssm_update`` is the whole
        mixer, from the projections to the out-projection; inside it
        ``delta_update`` is the state's update and read alone."""
        with jax.named_scope("ssm_update"):
            with jax.named_scope("proj"):
                qkv = _mm(u, lp["w_qkv"]).astype(self.dtype)
            seq = jnp.concatenate([conv, qkv[:, None]], axis=1)          # (b, k, ch)
            q, k, v = self._delta_heads(jnp.sum(
                seq.astype(jnp.float32) * lp["conv_w"].astype(jnp.float32)[None], axis=1))
            g, beta = self._decay_beta(lp, u, live)
            with jax.named_scope("delta_update"):
                update = du.delta_update if path == "kernel" else du.delta_step
                o, new_ssm = update(ssm, q, k, v, jnp.exp(g), beta, live)
            out = self._delta_out(lp, self._delta_gated(lp, u, o))
            new_conv = jnp.where(live[:, None, None], seq[:, 1:], conv)
        return out, new_ssm, new_conv

    def _delta(self, lp, u, ssm, conv, m: dict):
        """One delta-rule layer in the phase the plan ``m`` is of."""
        if m["t"] is None:
            return self._delta_step(lp, u, m["live"], ssm, conv, m["delta_path"])
        return self._delta_prefill(lp, u, m["t"], ssm, conv, m["slot"], m["start"], m["length"],
                                   m["scan_path"])

    def _prefill_plan(self, state, launch, t: dict) -> dict:
        """And where the launch's chunked rules run, chosen once for all its layers."""
        return {**super()._prefill_plan(state, launch, t), "scan_path": self._scan_path(t)}

    def _step_plan(self, state, live, pos) -> dict:
        """And where the step's updates run, chosen once for all its layers."""
        return {**super()._step_plan(state, live, pos),
                "delta_path": self._delta_path(state["ssm"][0])}

    def _counts(self, m: dict) -> dict:
        """And, a step, its live lanes by where their updates ran; a launch,
        itself by where its chunked rules ran."""
        c = super()._counts(m)
        return {**c, "paths": {p: c["tokens"] if m.get("delta_path") == p else 0 for p in PATHS},
                "scans": {p: int(m.get("scan_path") == p) for p in PATHS}}


def _by_path(count: str, name: str, phase: str) -> tuple:
    """A column a path: ``counts[count][path]`` times the recurrent layers into
    ``name{model=,phase=,path=}``, in ``phase`` alone."""
    return tuple(
        Column(lambda model, stats, counts, path=path: counts[count][path] * len(model.m_layers),
               lambda model, metrics, ph, path=path: series(name, f",path={path}")(
                   model, metrics, ph) if ph == phase else None)
        for path in PATHS)


# A step's delta-rule updates (live lanes x layers) and a launch's chunked
# rules (layers), each by where it ran.
DELTA_COLUMNS = (*_by_path("paths", "delta_steps_total", "decode"),
                 *_by_path("scans", "delta_scans_total", "prefill"))
# A launch's Mamba-2 scans (layers), by where they ran.
SCAN_COLUMNS = _by_path("scans", "ssm_scans_total", "prefill")


class ConvMixer(RecurrentMixer):
    """The gated short convolution (module docstring): a slot's whole state is
    the convolution's last rows, so this mixer names ONE leaf."""

    def _conv_setup(self, *, conv_kernel: int) -> None:
        """The layer's number: the taps (``conv_L_cache``); its channels are
        the stream's ``d``."""
        self.conv_k = int(conv_kernel)

    # -- params ---------------------------------------------------------------
    def _conv_tensors(self):
        """``w_in`` is the published ``in_proj``, its thirds B, C and the
        convolved input in that order; the taps (k, d), tap k - 1 on the
        current row."""
        d, s, k = self.d, self.scales, self.conv_k
        for i in self.m_layers:
            L = f"layer{i}"
            yield ((L, "w_in"), (d, 3 * d), (d, 3 * d), (0, 0), s["conv_in"], d)
            yield ((L, "conv_w"), (k, d), (k, d), (0, 0), s["conv_tap"], k)
            yield ((L, "w_out"), (d, d), (d, d), (0, 0), s["conv_out"], d)

    def _conv_signature(self, slots: int) -> dict:
        return {"conv": slot_block([jax.ShapeDtypeStruct((slots, self.conv_k - 1, self.d),
                                                         self.dtype) for _ in self.m_layers])}

    # -- device math --------------------------------------------------------------
    @scoped("proj")
    def _conv_in(self, lp: dict, u: jax.Array):
        """``u`` (T, d) -> (b = B * z, the row a slot keeps, and the gate C),
        both (T, d) in the served type."""
        d = self.d
        bcz = _mm(u, lp["w_in"]).astype(self.dtype)
        return bcz[:, :d] * bcz[:, 2 * d:], bcz[:, d:2 * d]

    def _conv_gate(self, gate: jax.Array, c: jax.Array) -> jax.Array:
        """The gate C on the convolved rows ``c`` (T, d) float32 -> the served type."""
        return (gate.astype(jnp.float32) * c).astype(self.dtype)

    def _conv_prefill(self, lp, u, t, conv, slot, start, length):
        """One short-convolution layer of a launch. The scope ``ssm_scan`` is
        from ``b`` to ``C * c``: the two projections are outside it. Nothing
        passes from tile to tile but a tile's last rows."""
        b, gate = self._conv_in(lp, u)
        with jax.named_scope("ssm_scan"):
            _none, (c0,) = self._piece_starts(slot, start, rows=(conv,))
            _opens, live, seq, c = self._tiles_conv(t, b, c0, lp["conv_w"])
            _last, c_end = self._piece_ends(t, live, seq)
            (conv,) = self._store_pieces((conv,), slot, length, (c_end,))
            y = self._conv_gate(gate, c.reshape(b.shape))
        with jax.named_scope("proj"):
            return _mm(y, lp["w_out"]), conv

    def _conv_step(self, lp, u, live, conv):
        """One row a lane: the rows of a lane that is not live stay as they
        were. The scope ``ssm_update`` is the whole mixer, from ``W_in`` to
        ``W_out``."""
        with jax.named_scope("ssm_update"):
            b, gate = self._conv_in(lp, u)
            seq = jnp.concatenate([conv, b[:, None]], axis=1)            # (lanes, k, d)
            c = jnp.sum(seq.astype(jnp.float32) * lp["conv_w"].astype(jnp.float32)[None], axis=1)
            y = self._conv_gate(gate, c)
            with jax.named_scope("proj"):
                out = _mm(y, lp["w_out"])
            new_conv = jnp.where(live[:, None, None], seq[:, 1:], conv)
        return out, new_conv

    def _short_conv(self, lp, u, conv, m: dict):
        """One short-convolution layer in the phase the plan ``m`` is of."""
        if m["t"] is None:
            return self._conv_step(lp, u, m["live"], conv)
        return self._conv_prefill(lp, u, m["t"], conv, m["slot"], m["start"], m["length"])


class PlainAttention:
    attn_gate = False  # the context times sigmoid(u W_g), elementwise by head, before W_o

    def _attention_tensors(self):
        d, hd, s = self.d, self.hd, self.scales
        for i in self.a_layers:
            L = f"layer{i}"
            yield ((L, "wq"), (d, self.heads, hd), (d, self.heads_full, hd),
                   (0, self.h_first, 0), s["qk"], d)
            for name, scale in (("wk", s["qk"]), ("wv", s["v"])):
                yield ((L, name), (d, self.kv, hd), (d, self.kv_full, hd),
                       (0, self.kv_first, 0), scale, d)
            yield ((L, "wo"), (self.heads, hd, d), (self.heads_full, hd, d),
                   (self.h_first, 0, 0), s["o"], self.heads_full * hd)
            if self.attn_gate:
                yield ((L, "wg"), (d, self.heads, hd), (d, self.heads_full, hd),
                       (0, self.h_first, 0), s["gate"], d)

    @scoped("proj")
    def _qkv(self, lp: dict, u: jax.Array, pos: "jax.Array | None" = None):
        """q, k, v by head of the rows ``u`` at positions ``pos`` (T,): here the
        positions are read by nothing."""
        return tuple(jnp.einsum("td,dhk->thk", u, lp[w],
                                preferred_element_type=jnp.float32).astype(self.dtype)
                     for w in ("wq", "wk", "wv"))

    @scoped("proj")
    def _attn_out(self, lp, o):
        return jnp.einsum("thk,hkd->td", o.astype(self.dtype), lp["wo"],
                          preferred_element_type=jnp.float32)

    @scoped("proj")
    def _gated(self, lp, u, o):
        """The context ``o`` (T, H, hd) float32 times ``sigmoid(u W_g)`` where the
        family gates its attention; ``o`` itself elsewhere."""
        if not self.attn_gate:
            return o
        return o * jax.nn.sigmoid(jnp.einsum("td,dhk->thk", u, lp["wg"],
                                             preferred_element_type=jnp.float32))

    def _attn_prefill(self, lp, u, t: dict, kp, vp, pos, w_page, off):
        """One attention layer of a launch: every row of the launch is in
        the pages before any tile reads them. The scope ``attn_prefill`` is the
        whole mixer, as ``attn_decode`` is a step's."""
        with jax.named_scope("attn_prefill"):
            q, k, v = self._qkv(lp, u, pos)
            kp, vp = self._write_pages(kp, w_page, off, k), self._write_pages(vp, w_page, off, v)
            o = self._prefill_full_tiles(q.reshape((t["K"], t["T"]) + q.shape[1:]), (kp, vp), t)
            return self._attn_out(lp, self._gated(lp, u, o.reshape(q.shape))), kp, vp

    def _attn_step(self, lp, u, kp, vp, bt, pos, w_page, off):
        """One attention layer of a decode step. The scope ``attn_decode`` is
        the whole mixer, from the projections to ``W_o``'s product."""
        with jax.named_scope("attn_decode"):
            q, k, v = self._qkv(lp, u, pos)
            kp, vp = self._write_pages(kp, w_page, off, k), self._write_pages(vp, w_page, off, v)
            y = self._attn_out(lp, self._gated(lp, u, self._decode_full(q, kp, vp, bt, pos)))
        return y, kp, vp

    def _attn(self, lp, u, kp, vp, m: dict):
        """One attention layer in the phase the plan ``m`` is of."""
        if m["t"] is None:
            return self._attn_step(lp, u, kp, vp, m["bt"], m["pos"], m["w_page"], m["off"])
        return self._attn_prefill(lp, u, m["t"], kp, vp, m["pos"], m["w_page"], m["off"])


class RotaryAttention(PlainAttention):
    """``PlainAttention`` with an RMSNorm a head on q and on k (one gain of
    ``hd`` for all heads; float32 inside, its result in the served type) and
    THEN a rotary embedding over all ``hd`` columns in pairs ``(j, j + hd / 2)``
    at the rows' absolute positions. A family sets ``rope_theta`` beside
    ``PlainAttention``'s numbers and yields ``_qk_gains`` among its vectors."""

    def _qk_gains(self):
        """The two norms' gains, float32 vectors drawn INSIDE ``scales["qk_gain"]``
        (a gain that is the same in every column commutes with the rotary: a
        check would be blind to their order)."""
        lo, hi = self.scales["qk_gain"]
        for i in self.a_layers:
            for name in ("q_norm", "k_norm"):
                yield ((f"layer{i}", name), (self.hd,), (self.hd,), (0,), lo, hi)

    @scoped("proj")
    def _qkv(self, lp: dict, u: jax.Array, pos: jax.Array):
        q, k, v = super()._qkv(lp, u, pos)
        inv = self.rope_theta ** (-np.arange(0, self.hd, 2, dtype=np.float64) / self.hd)
        return tuple(apply_rope(rms_norm(x, lp[g], self.eps), pos, inv.astype(np.float32), 1.0,
                                self.hd) for x, g in ((q, "q_norm"), (k, "k_norm"))) + (v,)


class _Pattern:
    """A family whose layer ``i`` has ONE of two mixers, a recurrent one
    (``m_layers``) or attention (``a_layers``): the cache leaves (attention's
    pages, then the leaves the recurrent mixer names) and the layer's mixer.
    ``_recurrent`` (``(lp, u, *the layer's leaves, m)`` -> the output and the
    leaves as it leaves them) and ``_state_signature`` are the recurrent
    mixer's."""

    def _cache_signature(self, slots: int, pages: int, page_tokens: int) -> dict:
        page = jax.ShapeDtypeStruct(self._page_shape(pages, page_tokens), self.dtype)
        return {"kf": pool([page for _ in self.a_layers]),
                "vf": pool([page for _ in self.a_layers]), **self._state_signature(slots)}

    def _mixer(self, i: int, lp, u, c: dict, m: dict):
        """Layer ``i``'s mixer on the normed stream ``u`` -> (T, d) float32;
        the layer's caches in ``c`` are replaced."""
        if i in self.m_layers:
            j, kept = self.m_layers.index(i), self._leaves(LeafKind.SLOT)
            y, *new = self._recurrent(lp, u, *(c[leaf][j] for leaf in kept), m)
            for leaf, value in zip(kept, new):
                c[leaf][j] = value
        else:
            j = self.a_layers.index(i)
            y, c["kf"][j], c["vf"][j] = self._attn(lp, u, c["kf"][j], c["vf"][j], m)
        return y


class PatternMixers(_Pattern, Mamba2Mixer, PlainAttention):
    """Mamba-2 or plain attention."""
    _recurrent, _state_signature = Mamba2Mixer._mamba, Mamba2Mixer._mamba_signature


class DeltaPatternMixers(_Pattern, DeltaMixer, PlainAttention):
    """The gated delta rule or plain attention."""
    _recurrent, _state_signature = DeltaMixer._delta, DeltaMixer._delta_signature


class ConvPatternMixers(_Pattern, ConvMixer, RotaryAttention):
    """The gated short convolution or attention with query/key norms and a
    rotary embedding."""
    _recurrent, _state_signature = ConvMixer._short_conv, ConvMixer._conv_signature


class HeadNorms:
    """q and k by head as both mixers below make them: normed over a head's
    columns where the family's ``qk_norm`` (ONE float32 gain of the width for
    all heads), THEN turned by a rotary embedding where the mixer says so."""

    def _qk_gains(self):
        """Every layer's two gains where ``qk_norm``: float32 vectors drawn
        INSIDE ``scales["qk_gain"]`` (``RotaryAttention``'s reason)."""
        if self.qk_norm:
            lo, hi = self.scales["qk_gain"]
            for i in range(self.n_layers):
                width = self.hd if i in self.a_layers else self.ld
                for name in ("q_norm", "k_norm"):
                    yield ((f"layer{i}", name), (width,), (width,), (0,), lo, hi)

    @scoped("proj")
    def _placed_qkv(self, lp: dict, u, pos, rope: bool, width: int):
        """q, k, v by head of the rows ``u`` at positions ``pos``; the rotary
        over all ``width`` columns in pairs ``(j, j + width / 2)`` at
        ``rope_theta``."""
        q, k, v = PlainAttention._qkv(self, lp, u)
        if self.qk_norm:
            q, k = rms_norm(q, lp["q_norm"], self.eps), rms_norm(k, lp["k_norm"], self.eps)
        if rope:
            inv = (self.rope_theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)) \
                .astype(np.float32)
            q, k = apply_rope(q, pos, inv, 1.0, width), apply_rope(k, pos, inv, 1.0, width)
        return q, k, v


class LightningMixer(HeadNorms, RecurrentMixer):
    """Linear attention with a CONSTANT decay a head (module docstring): a
    slot's whole state is one (H, D, D) float32 block a layer, ``S[h, i, j] = sum_s
    lambda_h^(t - s) k_s[i] v_s[j]``, and NO convolution rows: this mixer names
    ONE leaf, ``ssm``."""
    SUB = 128   # rows of a sub-tile of a launch's chunked form

    def _lightning_setup(self, *, heads: int, head_dim: int, scale: float, rope: bool,
                         out_norm: bool, out_gate: bool) -> None:
        """The layer's numbers: ``heads`` of ``head_dim`` for q, k and v alike
        (every head its own q and k), what the read is multiplied by, and
        whether q and k are turned, the read normed a head, and gated."""
        self.lh, self.ld, self.l_scale = heads, head_dim, float(scale)
        self.l_rope, self.l_norm, self.l_gate = rope, out_norm, out_gate
        # log lambda_h = -2^(-8 (h + 1) / H): Lightning Attention's slopes, learned by nothing
        self.l_log_decay = -np.exp2(-8.0 * np.arange(1, heads + 1) / heads).astype(np.float32)

    # -- params ---------------------------------------------------------------
    def _lightning_gains(self):
        if self.l_norm:
            for i in self.m_layers:
                yield (f"layer{i}", "o_norm"), (self.ld,)

    def _lightning_tensors(self):
        d, s, h, D = self.d, self.scales, self.lh, self.ld
        for i in self.m_layers:
            L = f"layer{i}"
            for name, role in (("wq", "lin_qk"), ("wk", "lin_qk"), ("wv", "lin_v"),
                               *((("wg", "lin_gate"),) if self.l_gate else ())):
                yield ((L, name), (d, h, D), (d, h, D), (0, 0, 0), s[role], d)
            yield ((L, "wo"), (h, D, d), (h, D, d), (0, 0, 0), s["lin_o"], h * D)

    def _lightning_signature(self, slots: int) -> dict:
        return {"ssm": slot_block([jax.ShapeDtypeStruct((slots, self.lh, self.ld, self.ld),
                                                        jnp.float32) for _ in self.m_layers])}

    # -- device math --------------------------------------------------------------
    def _lightning_qkv(self, lp: dict, u: jax.Array, pos: jax.Array):
        """q, k (normed a head, then turned at ``pos``) and v of the rows ``u``
        -> (T, H, D) each, in the served type."""
        return self._placed_qkv(lp, u, pos, self.l_rope, self.ld)

    @scoped("proj")
    def _lightning_out(self, lp: dict, u: jax.Array, o: jax.Array) -> jax.Array:
        """The read ``o`` (T, H, D) float32 normed over a head (one gain of D
        for all heads), times ``sigmoid(u W_g)``, through ``W_o``."""
        if self.l_norm:
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + self.eps) \
                * lp["o_norm"].astype(jnp.float32)
        if self.l_gate:
            o = o * jax.nn.sigmoid(jnp.einsum("td,dhk->thk", u, lp["wg"],
                                              preferred_element_type=jnp.float32))
        return jnp.einsum("thk,hkd->td", o.astype(self.dtype), lp["wo"],
                          preferred_element_type=jnp.float32)

    def _lightning_tiles(self, q, k, v, t: dict, s0):
        """The plain form of one launch's chunked recurrence, what every
        backend runs today: q, k, v (C, H, D) of the packed rows; ``s0`` (K, H, D,
        D) float32 what each PIECE starts from. A tile goes by sub-tiles of
        ``SUB`` rows: with ``cum`` the running sum of ``log lambda`` over a
        sub-tile's LIVE rows (a padded row: 0, and its v zeroed), row t reads row
        s <= t through ``exp(cum_t - cum_s) (q_t . k_s)`` and the state the
        sub-tile starts from through ``exp(cum_t)``; it leaves ``exp(cum_c) S + (k
        exp(cum_c - cum))^T v``. Every exponent taken is <= 0; the products take
        their operands in the served type and accumulate in float32. -> o (C, H,
        D) float32 before ``lightning_scale`` and, by piece, the state it ends with."""
        K, T, H = t["K"], t["T"], self.lh
        c = self.SUB if T % self.SUB == 0 else T
        n = T // c
        live = t["valid"].reshape(K * n, c)
        split = lambda a: a.reshape((K * n, c) + a.shape[1:])  # noqa: E731
        q, k = split(q), split(k)
        v = jnp.where(live[..., None, None], split(v), jnp.zeros((), v.dtype))
        cum = jnp.cumsum(jnp.where(live[..., None], jnp.asarray(self.l_log_decay), 0.0), axis=1)
        qk = jnp.einsum("kthd,kshd->khts", q, k, preferred_element_type=jnp.float32)
        ch = cum.transpose(0, 2, 1)                                          # (Kn, H, c)
        causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
        m = jnp.exp(jnp.where(causal, ch[:, :, :, None] - ch[:, :, None, :], -jnp.inf)) * qk
        o = jnp.einsum("khts,kshd->kthd", m.astype(self.dtype), v,
                       preferred_element_type=jnp.float32)
        to_end = jnp.exp(cum[:, -1:, :] - cum)                               # (Kn, c, H)
        add = jnp.einsum("kshi,kshj->khij", (k * to_end[..., None]).astype(self.dtype), v,
                         preferred_element_type=jnp.float32)
        keep = jnp.exp(cum[:, -1, :])                                        # (Kn, H)
        # a sub-tile opens its piece where its tile does and it is the tile's first
        opens = jnp.repeat(t["tiles"] == t["first_tile"][t["piece"]], n) \
            & (jnp.arange(K * n) % n == 0)

        def pass_on(carry, sub):
            opens_j, piece_j, keep_j, add_j = sub
            s_in = jnp.where(opens_j, s0[piece_j], carry)
            s_out = keep_j[:, None, None] * s_in + add_j
            return s_out, (s_in, s_out)

        _, (s_in, s_out) = jax.lax.scan(
            pass_on, jnp.zeros((H, self.ld, self.ld), jnp.float32),
            (opens, jnp.repeat(t["piece"], n), keep, add))
        o = o + jnp.exp(cum)[..., None] * jnp.einsum(
            "kthi,khij->kthj", q, s_in.astype(self.dtype), preferred_element_type=jnp.float32)
        last_tile = jnp.clip(t["first_tile"] + t["n_tiles"] - 1, 0, K - 1)
        return o.reshape(K * T, H, self.ld), s_out[last_tile * n + n - 1]

    def _lightning_prefill(self, lp, u, t, pos, ssm, slot, start, length):
        """One linear-attention layer of a launch. The scope ``ssm_scan`` is the
        recurrence alone: the projections, the norms and the gate are outside."""
        q, k, v = self._lightning_qkv(lp, u, pos)
        with jax.named_scope("ssm_scan"):
            (s0,), _none = self._piece_starts(slot, start, states=(ssm,))
            o, s_end = self._lightning_tiles(q, k, v, t, s0)
            (ssm,) = self._store_pieces((ssm,), slot, length, (s_end,))
        return self._lightning_out(lp, u, o * self.l_scale), ssm

    def _lightning_step(self, lp, u, live, pos, ssm):
        """One application of the recurrence for every lane, the state read
        once and written once: the state of a lane that is not live stays as it
        was. The scope ``ssm_update`` is the whole mixer."""
        with jax.named_scope("ssm_update"):
            q, k, v = (x.astype(jnp.float32) for x in self._lightning_qkv(lp, u, pos))
            s = jnp.exp(jnp.asarray(self.l_log_decay))[None, :, None, None] * ssm \
                + k[..., :, None] * v[..., None, :]
            o = jnp.sum(q[..., :, None] * s, axis=-2) * self.l_scale
            out = self._lightning_out(lp, u, o)
            new = jnp.where(live[:, None, None, None], s, ssm)
        return out, new

    def _lightning(self, lp, u, ssm, m: dict):
        """One linear-attention layer in the phase the plan ``m`` is of."""
        if m["t"] is None:
            return self._lightning_step(lp, u, m["live"], m["pos"], ssm)
        return self._lightning_prefill(lp, u, m["t"], m["pos"], ssm, m["slot"], m["start"],
                                       m["length"])

    def _counts(self, m: dict) -> dict:
        """And a launch itself by where its chunked recurrences ran: the plain
        form on every backend (``ops/ssm_scan.py`` takes groups of eight heads
        that share q and k; here every head has its own)."""
        return {**super()._counts(m),
                "scans": {"kernel": 0, "xla": int(m["t"] is not None)}}


class BlockSelectAttention(HeadNorms, PlainAttention):
    """Attention by head, with no position term unless the family says so,
    over THE BLOCKS OF KEYS A QUERY'S KV GROUP PICKS (module docstring's last
    part; ISSUE 68). A family calls ``_blk_setup`` and sets ``PlainAttention``'s
    numbers, ``qk_norm`` and ``attn_rope``.

    A query at ``t < dense_len`` is ``PlainAttention``'s. Another: the pooled
    keys ``Kc_g[j] = mean(k_g[stride j .. stride j + kernel - 1])`` (a THIRD page
    leaf ``kc``, rows of KV x hd side by side, ``P / stride`` a page, a row
    written when the position that completes its window is: ``blk_pool``);
    ``p_h = softmax_j(scale q_h . Kc_g[j])`` over the windows that lie whole at or
    before t, summed over the group's heads; a block's score the largest over
    the windows that touch it, ``+inf`` for the first ``init`` blocks and the
    ``local`` last; the ``topk`` best blocks a (query, KV group), found exactly
    (``ops/index_select.py``'s threshold for a launch's rows, ``lax.top_k`` for a
    step's lanes: 32 rows): ``blk_select``. A launch's tile takes its block
    scores from ONE kernel call on the TPU (``ops/block_scores.py``,
    ``_select_path``), a step's lanes and every other backend from
    ``_block_scores``. Attention over the keys of those
    blocks alone: ``blk_attend``. A LAUNCH walks every key block under the picks
    as a mask a block (``paged_lm._prefill_full(keep=)``; a key block no row of
    the tile picked is skipped): with 512 rows a tile and two groups, the rows'
    picks together cover nearly every block, so a walk over picked blocks alone
    would fetch each block once a row where this fetches it once a tile. A STEP
    gathers, a (lane, KV group), its ``topk`` picked blocks through the block
    table and NOTHING else of its pages. Pooled keys in the served type (the
    mean in float32), scores, softmax, sums and picks in float32."""

    def _blk_setup(self, name: str, sparse: dict) -> None:
        g = lambda key: int(sparse[key])  # noqa: E731
        self.b_kernel, self.b_stride, self.b_block = g("kernel_size"), g("kernel_stride"), \
            g("block_size")
        self.b_topk, self.b_init, self.dense_len = g("topk"), g("init_blocks"), g("dense_len")
        if self.b_kernel % self.b_stride or self.b_block % self.b_stride \
                or self.b_kernel > self.b_block + self.b_stride \
                or g("window_size") % self.b_block or self.dense_len % self.b_block:
            raise NotImplementedError(f"{name}: sparse_config = {sparse!r}: windows of whole "
                                      "strides, blocks and a local window of whole blocks")
        self.b_local = g("window_size") // self.b_block
        if self.b_init + self.b_local > self.b_topk \
                or self.dense_len < self.b_topk * self.b_block:
            raise NotImplementedError(f"{name}: sparse_config = {sparse!r}: the forced blocks "
                                      "among the picks, every picked query with topk blocks")

    def _qkv(self, lp: dict, u, pos=None):
        return self._placed_qkv(lp, u, pos, self.attn_rope, self.hd)

    # -- the pooled keys ---------------------------------------------------------
    def _pooled_shape(self, pages: int, page_tokens: int) -> tuple:
        return (pages * (page_tokens // self.b_stride), self.kv * self.hd)

    @scoped("blk_pool")
    def _pool_write(self, kc, kp, bt, at, ok):
        """The pooled keys of the windows that END at positions ``at`` (R,) of
        the prompts whose block-table rows are ``bt`` (R, pps), where ``ok``: the
        mean, in float32, of the window's keys as the pages hold them, into
        the window's row of ``kc``; the others into the sentinel's (page 0)."""
        kv, _pages, P, hd = kp.shape
        per = P // self.b_stride
        span = at[:, None] - self.b_kernel + 1 + jnp.arange(self.b_kernel)[None, :]
        span = jnp.clip(span, 0, bt.shape[1] * P - 1)
        rows = jnp.take_along_axis(bt, span // P, axis=1) * P + span % P       # (R, kernel)
        # rows of the pool seen flat, as ``_write_pages`` scatters them: taken along
        # the pages' own dimension the compiler copies the whole pool to a layout
        # with the heads inside (0.5 GiB a step: the cell compiled for a v5e, PR 68)
        flat = (jnp.arange(kv)[:, None] * (kp.shape[1] * P) + rows.reshape(-1)[None, :])
        keys = jnp.take(kp.reshape(-1, hd), flat.reshape(-1), axis=0) \
            .reshape(kv, at.shape[0], self.b_kernel, hd)
        mean = jnp.mean(keys.astype(jnp.float32), axis=2).transpose(1, 0, 2)   # (R, KV, hd)
        j = jnp.maximum(at + 1 - self.b_kernel, 0) // self.b_stride
        to = jnp.take_along_axis(bt, (j // per)[:, None], axis=1)[:, 0] * per + j % per
        return kc.at[jnp.where(ok, to, 0)].set(mean.reshape(-1, kv * hd).astype(kc.dtype))

    def _windows_done(self, pos):
        """Positions that complete a window: its last, and the window whole."""
        return ((pos + 1) % self.b_stride == 0) & (pos + 1 >= self.b_kernel)

    def _pool_launch(self, kc, kp, t: dict):
        """A launch's: a tile holds ``T / stride`` positions that may complete a
        window, whatever the position its first row stands at."""
        T, st = t["T"], self.b_stride
        first = t["qpos"][:, 0]                                              # (K,)
        at = (first + (st - 1 - first % st))[:, None] + st * jnp.arange(T // st)[None, :]
        ok = self._windows_done(at) & (at < t["end"][:, None])
        bt = jnp.repeat(t["rows"], T // st, axis=0)
        return self._pool_write(kc, kp, bt, at.reshape(-1), ok.reshape(-1))

    # -- the picks -----------------------------------------------------------------
    def _block_scores(self, q, kc, row, qpos, spans: int, P: int):
        """The rows' block scores over ONE prompt's pooled keys: q (R, H, hd) at
        positions ``qpos`` (R,), the leaf ``kc``, the prompt's block-table row
        ``row`` (n,) of pages of ``P`` positions -> (KV, R, spans) float32,
        ``spans`` >= n x P / block: ``+inf`` a forced block, ``-inf`` a block past
        the row's own."""
        per, r = P // self.b_stride, self.b_block // self.b_stride
        extra = self.b_kernel // self.b_stride - 1
        R, H, hd = q.shape
        g = H // self.kv
        at = (row[:, None] * per + jnp.arange(per)[None, :]).reshape(-1)
        pooled = jnp.take(kc, at, axis=0).reshape(-1, self.kv, hd)             # (J, KV, hd)
        J = pooled.shape[0]
        # ... on WHOLE lane tiles (a table of 1,029 pages has 4,116 windows): windows
        # past the table exist for no row
        pooled = jnp.pad(pooled, ((0, -J % 128), (0, 0), (0, 0)))
        exists = (self.b_stride * jnp.arange(pooled.shape[0]) + self.b_kernel - 1)[None, :] \
            <= jnp.minimum(qpos, self.b_stride * J - 1)[:, None]
        # A group's heads as ROWS of one product a KV group, (g R, hd) x (hd, J): the
        # windows lie on the lanes, where the softmax's two reductions are cheap. As
        # "rkgd,jkd->krgj" the compiler laid the ROWS on the lanes and reduced over
        # sublanes: 12 ms a tile of the cell, 131 of a launch's 205 (my chip run, PR 68).
        qg = q.reshape(R, self.kv, g, hd).transpose(1, 2, 0, 3).reshape(self.kv, g * R, hd)
        s = jnp.einsum("kmd,kjd->kmj", qg, pooled.transpose(1, 0, 2),
                       preferred_element_type=jnp.float32) * self._scale()
        seen = jnp.tile(exists, (g, 1))[None]                                 # (1, g R, J)
        s = jnp.where(seen, s, NEG)
        # The rows' maxima behind a barrier: fused with the subtraction that reads
        # them, the compiler made the maximum a reduce-WINDOW as wide as two rows, a
        # row's 4,224 windows times 8,447 (123 of a launch's 197 ms; my chip run, PR 68).
        top = jax.lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - top) * seen
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        sc = jnp.where(exists[None], jnp.sum(p.reshape(self.kv, g, R, -1), axis=1),
                       -jnp.inf)[:, :, :J]                                    # (KV, R, J)
        # a block's score: the largest over the windows that touch it, r b -
        # extra .. r b + r - 1 (``extra`` windows begin in the block before)
        nb = spans
        sc = jnp.pad(sc, ((0, 0), (0, 0), (extra, (nb + 1) * r - extra - J)),
                     constant_values=-jnp.inf).reshape(self.kv, R, nb + 1, r)
        score = jnp.max(sc[:, :, :nb], axis=-1)
        if extra:
            score = jnp.maximum(score, jnp.max(sc[:, :, 1:, :extra], axis=-1))
        b, own = jnp.arange(nb)[None, :], (qpos // self.b_block)[:, None]
        forced = (b < self.b_init) | (own - b < self.b_local)
        return jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, score))

    def _select_path(self, T: int, pps: int, P: int) -> str:
        """Where a launch's picked tiles' block scores are made, chosen when the
        launch is traced: ONE kernel call a tile (``ops/block_scores.py``) on
        the TPU at shapes it takes, else the plain form."""
        on_tpu = jax.default_backend() == "tpu" and bsc.supported(
            T, self.heads, self.kv, self.hd, pps * (P // self.b_stride),
            self.b_block // self.b_stride, self.b_kernel // self.b_stride - 1, self.dtype)
        return "kernel" if on_tpu else "xla"  # tps-ok[TPS503]: backend and static shapes

    @scoped("blk_select")
    def _tile_keep(self, q, kc, row, qpos, spans: int, P: int, last=None, path: str = "xla"):
        """One tile's picks as a mask a block, (KV, T, spans) bool: a row at or
        past ``dense_len`` keeps its ``topk`` best blocks, another every block.
        The block scores by ``path`` (``_select_path``; the kernel's end at the
        window blocks that hold ``last``, the tile's last live position). NEIGHBOURING
        BLOCKS TIE OFTEN, and exactly (the window that straddles
        their edge is the largest of both): the blocks above the ``topk``-th
        largest score (``index_select.kth_key``, no sort), then of the blocks AT
        it the lowest indices, as many as are left, which is ``lax.top_k``'s
        rule and the step's."""
        if path == "kernel":
            score = bsc.block_scores(
                q, kc, row, qpos, last, spans=spans, page=P, kernel=self.b_kernel,
                stride=self.b_stride, block=self.b_block, init=self.b_init, local=self.b_local,
                scale=self._scale())
        else:
            score = self._block_scores(q, kc, row, qpos, spans, P)
        kv, T, nb = score.shape
        see = jnp.arange(nb)[None, :] <= jnp.tile(qpos // self.b_block, kv)[:, None]
        keys = jnp.where(see, ix.sort_keys(score.reshape(kv * T, nb)), jnp.uint32(0))
        kth = ix.kth_key(keys, self.b_topk)[:, None]
        above, at = keys > kth, see & (keys == kth)
        room = self.b_topk - jnp.sum(above, axis=1, keepdims=True)
        kept = above | (at & (jnp.cumsum(at, axis=1) <= room))
        return kept.reshape(kv, T, nb) | (qpos < self.dense_len)[None, :, None]

    def _attend_tiles(self, q, pools, kc, t: dict, path: str = "xla"):
        """A launch's attention tile by tile: a tile whose last live position is
        under ``dense_len`` is ``PlainAttention``'s; another walks under its
        rows' picks, their block scores by ``path``."""
        heads = self._heads()
        P, pps = pools[0].shape[2], t["rows"].shape[1]
        kb = self._block_pages(P, pps)
        spans = -(-pps // kb) * kb * P // self.b_block

        def one(a):
            qt, row, qpos, last = a

            def picked():
                keep = self._tile_keep(qt, kc, row, qpos, spans, P, last, path)
                with jax.named_scope("blk_attend"):
                    return self._prefill_full(qt, pools, row, qpos, last, heads,
                                              keep=(keep, self.b_block))

            return jax.lax.cond(last >= self.dense_len, picked,
                                lambda: self._prefill_full(qt, pools, row, qpos, last, heads))

        return jax.lax.map(one, (q, t["rows"], t["qpos"], t["last"]))

    def _decode_picked(self, q, kp, vp, kc, bt, pos):
        """A step's lanes past ``dense_len``: each lane's block scores, its
        ``topk`` blocks a KV group, and attention over those blocks' keys alone,
        gathered through the block table -> (b, H, hd) float32. A lane under
        ``dense_len`` computes on whatever it picks; its row is not used."""
        b, H, hd = q.shape
        kv, _pages, P, _ = kp.shape
        g, B, k = H // kv, self.b_block, self.b_topk
        nb = bt.shape[1] * P // B
        with jax.named_scope("blk_select"):
            score = jax.vmap(lambda ql, row, p: self._block_scores(
                ql[None], kc, row, p[None], nb, P)[:, 0])(q, bt, pos)          # (b, KV, nb)
            _, blocks = jax.lax.top_k(score, k)                                # (b, KV, k)
        with jax.named_scope("blk_attend"):
            per = P // B
            at = jnp.take_along_axis(bt, (blocks // per).reshape(b, -1), axis=1) \
                .reshape(b, kv, k) * per + blocks % per
            spos = (blocks[..., None] * B + jnp.arange(B)).reshape(b, kv, k * B)
            see = spos <= pos[:, None, None]
            out = []
            for j in range(kv):   # a KV group's blocks, out of the pool seen as blocks
                own = j * (kp.shape[1] * per) + at[:, j]
                kj, vj = (jnp.take(pool.reshape(-1, B, hd), own, axis=0).reshape(b, k * B, 1, hd)
                          for pool in (kp, vp))
                out.append(self._attend(q[:, None, j * g:(j + 1) * g], kj, vj,
                                        see[:, j][:, None])[:, 0])
            return jnp.concatenate(out, axis=1)

    def _decode_blocks(self, q, kp, vp, kc, bt, pos, live):
        """A step's attention: the lanes under ``dense_len`` over every key
        (``_decode_full``, where there is one), the others over their picks (where
        there is one)."""
        dense = pos < self.dense_len
        zero = lambda: jnp.zeros(q.shape, jnp.float32)  # noqa: E731
        o_dense = jax.lax.cond(
            jnp.any(live & dense),
            lambda: self._decode_full(q, kp, vp, bt, jnp.where(dense, pos, 0)), zero)
        o_picked = jax.lax.cond(jnp.any(live & ~dense),
                                lambda: self._decode_picked(q, kp, vp, kc, bt, pos), zero)
        return jnp.where(dense[:, None, None], o_dense, o_picked)

    def _blk_prefill(self, lp, u, t: dict, kp, vp, kc, pos, w_page, off, path: str):
        with jax.named_scope("attn_prefill"):
            q, k, v = self._qkv(lp, u, pos)
            kp, vp = self._write_pages(kp, w_page, off, k), self._write_pages(vp, w_page, off, v)
            kc = self._pool_launch(kc, kp, t)
            o = self._attend_tiles(q.reshape((t["K"], t["T"]) + q.shape[1:]), (kp, vp), kc, t,
                                   path)
            return self._attn_out(lp, self._gated(lp, u, o.reshape(q.shape))), kp, vp, kc

    def _blk_step(self, lp, u, kp, vp, kc, bt, pos, live, w_page, off):
        with jax.named_scope("attn_decode"):
            q, k, v = self._qkv(lp, u, pos)
            kp, vp = self._write_pages(kp, w_page, off, k), self._write_pages(vp, w_page, off, v)
            kc = self._pool_write(kc, kp, bt, pos, live & self._windows_done(pos))
            o = self._decode_blocks(q, kp, vp, kc, bt, pos, live)
            return self._attn_out(lp, self._gated(lp, u, o)), kp, vp, kc

    def _blk_attn(self, lp, u, kp, vp, kc, m: dict):
        """One attention layer in the phase the plan ``m`` is of."""
        if m["t"] is None:
            return self._blk_step(lp, u, kp, vp, kc, m["bt"], m["pos"], m["live"], m["w_page"],
                                  m["off"])
        return self._blk_prefill(lp, u, m["t"], kp, vp, kc, m["pos"], m["w_page"], m["off"],
                                 m["select_path"])

    def _counts(self, m: dict) -> dict:
        """And, an attention layer, of the LIVE queries at or past ``dense_len``
        (the picked): the blocks scored (a KV group each), the keys they may see
        and the keys of their picked blocks, and the key rows their walks
        fetched (a step: the picked blocks'; a launch: whole key blocks up to the
        tile's last position); the live queries by path; and the layer itself,
        where it had a picked tile or lane, by where its block scores were made
        (a step's lanes: the plain form)."""
        c, t, B, k = super()._counts(m), m["t"], self.b_block, self.b_topk
        picked = m["live"] & (m["pos"] >= self.dense_len)
        path = m.get("select_path", "xla")
        if t is None:
            rows = jnp.full(m["pos"].shape, k * B)
        else:
            P, pps = m["P"], m["pps"]
            rows = jnp.repeat(self._blocks_needed(t["last"], P, pps)
                              * self._block_pages(P, pps) * P, t["T"])
        of = lambda x: jnp.sum(jnp.where(picked, x, 0))  # noqa: E731
        return {**c, "blk_scored": of((m["pos"] // B + 1) * self.kv),
                "blk_visible": of(m["pos"] + 1), "blk_attended": of((k - 1) * B + m["pos"] % B + 1),
                "blk_read": of(rows), "blk_dense": jnp.sum(m["live"] & ~picked),
                "blk_picked": jnp.sum(picked),
                "selects": {p: jnp.any(picked) * int(p == path) for p in PATHS}}


BLK_PATHS = ("dense", "picked")
# An attention layer's picked queries' blocks scored, keys visible and attended
# and key rows fetched (each times the attention layers), the live queries by
# path, and the attention layers of a launch or a step that had a picked tile
# or lane, by where their block scores were made.
BLK_COLUMNS = (
    *(Column(lambda model, stats, counts, key=key: counts[key] * len(model.a_layers), series(name))
      for key, name in (("blk_scored", "blk_blocks_scored_total"),
                        ("blk_visible", "blk_keys_visible_total"),
                        ("blk_attended", "blk_keys_attended_total"),
                        ("blk_read", "blk_rows_read_total"))),
    *(Column(lambda model, stats, counts, path=path: counts[f"blk_{path}"] * len(model.a_layers),
             series("blk_queries_total", f",path={path}")) for path in BLK_PATHS),
    *(Column(lambda model, stats, counts, path=path: counts["selects"][path] * len(model.a_layers),
             series("blk_selects_total", f",path={path}")) for path in PATHS))


class BlockPatternMixers(_Pattern, LightningMixer, BlockSelectAttention):
    """Linear attention with a constant decay or attention over picked blocks:
    the cache leaves are the pages' three (K, V and the pooled keys) and the
    linear-attention layers' state."""
    _recurrent, _state_signature = LightningMixer._lightning, LightningMixer._lightning_signature

    def kv_plan(self, slots: int, page_tokens: int, pages: int = 0) -> CachePlan:
        if page_tokens % self.b_block:
            raise ValueError(f"{self.name}: kv_page_tokens = {page_tokens} is no whole number of "
                             f"blocks of {self.b_block}")
        return super().kv_plan(slots, page_tokens, pages)

    def _cache_signature(self, slots: int, pages: int, page_tokens: int) -> dict:
        pooled = jax.ShapeDtypeStruct(self._pooled_shape(pages, page_tokens), self.dtype)
        return {**super()._cache_signature(slots, pages, page_tokens),
                "kc": pool([pooled for _ in self.a_layers])}

    def _prefill_plan(self, state, launch, t: dict) -> dict:
        """And the pages' geometry, for the launch's counts, and where its
        picked tiles' block scores are made, chosen once for all its layers."""
        P, pps = self._page_tokens(state), state["bt"].shape[1]
        return {**super()._prefill_plan(state, launch, t), "P": P, "pps": pps,
                "select_path": self._select_path(t["T"], pps, P)}

    def _mixer(self, i: int, lp, u, c: dict, m: dict):
        if i in self.m_layers:
            return super()._mixer(i, lp, u, c, m)
        j = self.a_layers.index(i)
        y, c["kf"][j], c["vf"][j], c["kc"][j] = self._blk_attn(
            lp, u, c["kf"][j], c["vf"][j], c["kc"][j], m)
        return y
