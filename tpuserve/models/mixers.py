"""The two mixers that the families built from a pattern of layers share
(ISSUE 40; moved out of ``hybrid.py`` so that ``hybrid_ffn.py`` is not a copy):
a Mamba-2 state-space layer with its state a slot, and attention by head with
no rotary embedding over the paged KV. Both are mix-ins over
``paged_lm.PagedLM``: they bring tensors, device math, the caches' shapes and
the columns of ``acc``, and know nothing of how a family orders its layers,
names its config keys or adds a mixer's output to the stream.
``PatternMixers`` is the two together: layer ``i``'s mixer, whichever it is, in
whichever phase (``_mixer``), for ``paged_lm``'s loop.

``Mamba2Mixer`` (a family calls ``_mamba_setup`` in its constructor and sets
``m_layers``): ``[z | xBC | dt] = u W_in``; a depthwise causal convolution and
SiLU over ``xBC``; per head ``S_t = a_t S_{t-1} + delta_t x_t (x) B_t``, ``y_t =
S_t C_t + D x_t`` with ``delta = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log)
delta)``; ``y <- RMSNorm_group(y silu(z))``; out ``= y W_out``. A layer keeps, A
SLOT, a float32 state (H, P, N) and the last ``conv_kernel - 1`` rows of its
convolution's input (``ssm[l][slot]``, ``conv[l][slot]``). A request's FIRST
piece starts from zeros whatever the slot held; a later piece from what the
slot holds; within a launch the tiles of one piece pass the state on and a
tile of another slot does not see it; padded rows leave it as it was (``delta
= 0`` there: ``a = 1``, no input); a decode step leaves the state of a lane
that is not live untouched. Prefill computes the recurrence by chunks (the
quadratic form inside a tile, the state passed between a piece's tiles by a
``lax.scan``), under ``jax.named_scope("ssm_scan")``; a decode step is one
application, under ``jax.named_scope("ssm_update")``.

``PlainAttention`` (a family sets ``a_layers``, ``heads``, ``kv``, ``hd`` and
their full counts): grouped KV heads, causal, NO position term, no bias; K and
V in pages of the engine's ledger. A step's whole mixer, from the projections
to ``W_o``, runs under ``jax.named_scope("attn_decode")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from tpuserve.models.paged_lm import Column, _mm, counted, series


def softplus_inverse(y: float) -> float:
    return y + math.log(-math.expm1(-y))


class Mamba2Mixer:
    kv_slot_state = ("ssm", "conv")  # the leaves that are a block a slot

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
        return {"ssm": [S((slots, self.mh, self.mp, self.mn), jnp.float32)
                        for _ in self.m_layers],
                "conv": [S((slots, self.conv_k - 1, self.conv_ch), self.dtype)
                         for _ in self.m_layers]}

    # -- device math --------------------------------------------------------------
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

    def _gated_norm(self, lp: dict, y: jax.Array, z: jax.Array) -> jax.Array:
        """y (T, H, P) float32 gated by silu(z) and normed over each GROUP of
        heads (gate before norm) -> (T, H, P) in the served type."""
        t = y.shape[0]
        g = (y * jax.nn.silu(z)).reshape(t, self.mg, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + self.eps)
        g = g.reshape(t, self.mh, self.mp) * lp["gate_norm"].astype(jnp.float32)
        return g.astype(self.dtype)

    def _out_proj(self, lp: dict, g: jax.Array) -> jax.Array:
        return jnp.einsum("thp,hpd->td", g, lp["w_out"], preferred_element_type=jnp.float32)

    def _scan_tiles(self, lp: dict, xbc, dt, t: dict, s0, c0):
        """The chunked scan of one launch: ``xbc`` (C, channels) and ``dt`` (C,
        H) of the packed rows; ``s0`` (K, H, P, N) float32 and ``c0`` (K, k-1,
        channels) what each PIECE starts from. -> y (C, H, P) float32 and,
        by piece, the state and the convolution's rows it ends with."""
        K, T, kc = t["K"], t["T"], self.conv_k - 1
        if T < kc:
            raise ValueError(f"{self.name}: a tile of {T} rows is shorter than the "
                             f"convolution's {kc} stored rows")
        H, P, G, N = self.mh, self.mp, self.mg, self.mn
        piece, tiles = t["piece"], t["tiles"]
        opens = tiles == t["first_tile"][piece]          # a tile that opens its piece
        live = t["valid"].reshape(K, T)
        xt = xbc.reshape(K, T, -1)
        # The convolution: a tile's rows behind the k-1 rows before them, the
        # piece's stored rows for the tile that opens it, else the tile before.
        prev = jnp.where(opens[:, None, None], c0[piece],
                         jnp.roll(xt[:, T - kc:], 1, axis=0))
        seq = jnp.concatenate([prev, xt], axis=1)                         # (K, kc + T, ch)
        w = lp["conv_w"].astype(jnp.float32)
        conv = lp["conv_b"].astype(jnp.float32) + sum(
            seq[:, j:j + T].astype(jnp.float32) * w[j] for j in range(kc + 1))
        x, B, C = self._split_xbc(conv)
        delta, la = self._decay(lp, dt.reshape(K, T, H), live)
        cum = jnp.cumsum(la, axis=1)                                      # (K, T, H)
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
        # By piece: its last tile's state, and the k-1 rows that end at its
        # last live row (a piece shorter than that keeps rows it came with).
        last_tile = jnp.clip(t["first_tile"] + t["n_tiles"] - 1, 0, K - 1)
        n_last = jnp.sum(live[last_tile], axis=1)
        tail = jnp.take_along_axis(
            seq[last_tile], (n_last[:, None] + jnp.arange(kc)[None, :])[:, :, None], axis=1)
        return y.reshape(K * T, H, P), s_out[last_tile], tail

    def _mamba_prefill(self, lp, u, t, ssm, conv, slot, start, length):
        """One Mamba-2 layer of a launch. The scope ``ssm_scan`` is the scan
        alone, from the convolution to the gated norm: the two projections
        are outside it."""
        z, xbc, dt = self._split_in(lp, u)
        with jax.named_scope("ssm_scan"):
            fresh = (start == 0)[:, None, None]
            at = jnp.minimum(slot, ssm.shape[0] - 1)
            s0 = jnp.where(fresh[..., None], 0.0, ssm[at].astype(jnp.float32))
            c0 = jnp.where(fresh, jnp.zeros((), conv.dtype), conv[at])
            y, s_end, c_end = self._scan_tiles(lp, xbc, dt, t, s0, c0)
            g = self._gated_norm(lp, y, z)
            # A piece of no tokens writes nothing: its slot is out of range.
            to = jnp.where(length > 0, slot, ssm.shape[0])
            ssm = ssm.at[to].set(s_end.astype(ssm.dtype), mode="drop")
            conv = conv.at[to].set(c_end.astype(conv.dtype), mode="drop")
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
        return self._mamba_prefill(lp, u, m["t"], ssm, conv, m["slot"], m["start"], m["length"])

    # -- counters -------------------------------------------------------------------
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


class PlainAttention:
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

    def _qkv(self, lp: dict, u: jax.Array):
        return tuple(jnp.einsum("td,dhk->thk", u, lp[w],
                                preferred_element_type=jnp.float32).astype(self.dtype)
                     for w in ("wq", "wk", "wv"))

    def _attn_out(self, lp, o):
        return jnp.einsum("thk,hkd->td", o.astype(self.dtype), lp["wo"],
                          preferred_element_type=jnp.float32)

    def _attn_prefill(self, lp, u, t: dict, kp, vp, w_page, off):
        """One attention layer of a launch: every row of the launch is in
        the pages before any tile reads them."""
        q, k, v = self._qkv(lp, u)
        kp, vp = self._write_pages(kp, w_page, off, k), self._write_pages(vp, w_page, off, v)
        o = self._prefill_full_tiles(q.reshape((t["K"], t["T"]) + q.shape[1:]), (kp, vp), t)
        return self._attn_out(lp, o.reshape(q.shape)), kp, vp

    def _attn_step(self, lp, u, kp, vp, bt, pos, w_page, off):
        """One attention layer of a decode step. The scope ``attn_decode`` is
        the whole mixer, from the projections to ``W_o``'s product."""
        with jax.named_scope("attn_decode"):
            q, k, v = self._qkv(lp, u)
            kp, vp = self._write_pages(kp, w_page, off, k), self._write_pages(vp, w_page, off, v)
            y = self._attn_out(lp, self._decode_full(q, kp, vp, bt, pos))
        return y, kp, vp

    def _attn(self, lp, u, kp, vp, m: dict):
        """One attention layer in the phase the plan ``m`` is of."""
        if m["t"] is None:
            return self._attn_step(lp, u, kp, vp, m["bt"], m["pos"], m["w_page"], m["off"])
        return self._attn_prefill(lp, u, m["t"], kp, vp, m["w_page"], m["off"])


class PatternMixers(Mamba2Mixer, PlainAttention):
    """A family whose layer ``i`` has ONE of the two mixers (``m_layers``,
    ``a_layers``): the four cache leaves and the layer's mixer."""
    cache_leaves = ("kf", "vf", "ssm", "conv")

    def _cache_signature(self, slots: int, pages: int, page_tokens: int) -> dict:
        page = jax.ShapeDtypeStruct(self._page_shape(pages, page_tokens), self.dtype)
        return {"kf": [page for _ in self.a_layers], "vf": [page for _ in self.a_layers],
                **self._mamba_signature(slots)}

    def _mixer(self, i: int, lp, u, c: dict, m: dict):
        """Layer ``i``'s mixer on the normed stream ``u`` -> (T, d) float32;
        the layer's caches in ``c`` are replaced."""
        if i in self.m_layers:
            j = self.m_layers.index(i)
            y, c["ssm"][j], c["conv"][j] = self._mamba(lp, u, c["ssm"][j], c["conv"][j], m)
        else:
            j = self.a_layers.index(i)
            y, c["kf"][j], c["vf"][j] = self._attn(lp, u, c["kf"][j], c["vf"][j], m)
        return y
