"""Autoregressive text generation (ISSUE 9) — the token-by-token family the
iteration-level engine exists for.

A prefix-LM decoder: the prompt is encoded **bidirectionally** in one
prefill pass; generated tokens then decode strictly left-to-right against
the KV cache. Sampling is seeded and
positional (``fold_in(fold_in(key(0), seed), position)``), so identical
(prompt, seed, temperature, max_new_tokens) requests produce identical
token streams across processes, batch compositions, and — the property
tests/test_genserve.py leans on — across the TWO serving paths:

- ``forward`` — the locked-batch twin: prefill + a ``lax.fori_loop`` over
  the FULL ``max_new_tokens`` cap for every lane. This is what the static
  batcher serves ([genserve] off) and what the bench's locked-batch
  baseline measures: a 2-token completion pays the full loop.
- ``init_state`` / ``step`` / ``extract`` — the engine decomposition:
  prefill is the once-per-request insert, each step decodes ONE token for
  every active slot against the per-slot KV cache
  (slots, layers, ctx, heads, head_dim), and a finished slot's token
  buffer is extracted the moment its own ``done`` flag flips.

Both paths share ``_prefill`` and ``_decode_step`` verbatim, so engine ==
locked-batch token parity holds by construction. Tokenization reuses
``tpuserve.text`` WordPiece over the deterministic synthetic vocab (no
artifacts, SURVEY.md §7 hard part 8); [SEP] doubles as EOS.

Sizes come from ``cfg.options`` (layers/d_model/heads/d_ff/vocab_size/
prompt_len/max_new_tokens) with small dev defaults; tests use tiny sizes.
"""

from __future__ import annotations

import json
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpuserve.config import ModelConfig
from tpuserve.genserve.model import CachePlan, GenerativeModel, pool
from tpuserve.parallel.mesh import MODEL_AXIS, SEQ_AXIS, can_shard
from tpuserve.text import WordPieceTokenizer, synthetic_vocab


def _norm(x, scale, bias, eps=1e-5):
    """LayerNorm in f32, cast back to the compute dtype."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale + bias).astype(x.dtype)


class TextGenServing(GenerativeModel):
    """Decoder-only generation over HTTP: JSON {"prompt", "seed"?,
    "max_new_tokens"?, "temperature"?} in, {"text", "tokens", "n_tokens"}
    out. Every sampling parameter rides inside the decoded item, so the
    result cache can never alias two requests differing only in seed."""

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        o = cfg.options
        self.dtype = jnp.dtype(cfg.dtype)
        self.layers = int(o.get("layers", 4))
        self.d_model = int(o.get("d_model", 256))
        self.heads = int(o.get("heads", 4))
        self.d_ff = int(o.get("d_ff", 4 * self.d_model))
        # Prompt bucket (host pads every prompt to this) and the generation
        # cap; the KV cache spans their sum.
        self.max_prompt = int(o.get("prompt_len", 32))
        self.max_new = int(o.get("max_new_tokens", 64))
        self.max_ctx = self.max_prompt + self.max_new
        if self.d_model % self.heads:
            raise ValueError(
                f"options.d_model={self.d_model} must divide by "
                f"heads={self.heads}")
        self.head_dim = self.d_model // self.heads
        # Switch-MoE FFN variant (ISSUE 20): 0 = dense MLP (the default and
        # the historical RNG stream); >= 2 replaces every layer's MLP with
        # top-1 routing over ops.moe.switch_route.
        self.moe_experts = int(o.get("moe_experts", 0))
        if self.moe_experts == 1 or self.moe_experts < 0:
            raise ValueError("options.moe_experts must be 0 (dense MLP) "
                             f"or >= 2 experts, got {self.moe_experts}")
        vocab_file = o.get("vocab_file")
        if vocab_file:
            self.tokenizer = WordPieceTokenizer.from_vocab_file(vocab_file)
        else:
            self.tokenizer = WordPieceTokenizer(
                synthetic_vocab(int(o.get("vocab_size", 8192))))
        self.vocab_size = max(self.tokenizer.vocab.values()) + 1
        self.eos_id = self.tokenizer.sep_id

    # -- params ---------------------------------------------------------------
    def init_params(self, rng: jax.Array) -> Any:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd, h = self.head_dim, self.heads

        def dense(key, shape):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (1.0 / math.sqrt(shape[0]))).astype(jnp.float32)

        # Key budget: dense layers draw 6, MoE layers 7 — dense configs keep
        # the historical RNG stream bit-for-bit.
        per_layer = 7 if self.moe_experts else 6
        keys = iter(jax.random.split(rng, per_layer * self.layers + 4))
        params: dict = {
            "embed": jax.random.normal(next(keys), (v, d), jnp.float32) * 0.02,
            "pos": jax.random.normal(next(keys), (self.max_ctx, d),
                                     jnp.float32) * 0.01,
            "ln_f": {"scale": jnp.ones((d,), jnp.float32),
                     "bias": jnp.zeros((d,), jnp.float32)},
            "head": dense(next(keys), (d, v)),
        }
        for i in range(self.layers):
            lp = {
                "ln1": {"scale": jnp.ones((d,), jnp.float32),
                        "bias": jnp.zeros((d,), jnp.float32)},
                "wq": dense(next(keys), (d, h * hd)),
                "wk": dense(next(keys), (d, h * hd)),
                "wv": dense(next(keys), (d, h * hd)),
                "wo": dense(next(keys), (h * hd, d)),
                "ln2": {"scale": jnp.ones((d,), jnp.float32),
                        "bias": jnp.zeros((d,), jnp.float32)},
            }
            if self.moe_experts:
                e = self.moe_experts
                lp["router"] = dense(next(keys), (d, e))
                lp["moe_up"] = (
                    jax.random.normal(next(keys), (e, d, f), jnp.float32)
                    * (1.0 / math.sqrt(d)))
                lp["moe_down"] = (
                    jax.random.normal(next(keys), (e, f, d), jnp.float32)
                    * (1.0 / math.sqrt(f)))
            else:
                lp["w_up"] = dense(next(keys), (d, f))
                lp["w_down"] = dense(next(keys), (f, d))
            params[f"layer{i}"] = lp
        return params

    # -- parallelism (ISSUE 20: sharded decode) -------------------------------
    def partition_rules(self) -> list[tuple[str, P]]:
        """TP rules for sharded decode: attention QKV and the vocab head
        shard columns (the heads / vocab dim) on "model", the out
        projection shards rows (its contraction dim); MoE expert weights
        shard the leading expert dim. Embeddings, positions, and norms
        replicate — they are small and read by every shard. tp <= 1 keeps
        everything replicated (the historical layout)."""
        if self.cfg.tp <= 1:
            return [(".*", P())]
        return [
            (r"w[qkv]$", P(None, MODEL_AXIS)),
            (r"wo$", P(MODEL_AXIS, None)),
            (r"w_up$", P(None, MODEL_AXIS)),
            (r"w_down$", P(MODEL_AXIS, None)),
            (r"router$", P()),
            (r"moe_(up|down)$", P(MODEL_AXIS, None, None)),
            (r"head$", P(None, MODEL_AXIS)),
            (r".*", P()),
        ]

    def state_partition_specs(self, struct: Any, mesh: Any) -> Any:
        """PartitionSpec tree for the engine's device state block on a
        sharded mesh: the KV heads dim rides "model" next to the QKV
        column shards (each shard decodes its own heads), and the
        pages/context dim rides "seq" when sequence parallelism is on.
        Dims that don't divide the axis fall back to replication
        (``can_shard``), and an all-replicated layout returns None so the
        caller skips spec plumbing entirely. Lane bookkeeping (tokens,
        pos, done, ...) always replicates — every shard must agree on
        done flags for the emission path."""
        specs = {f: P() for f in struct}
        if "kp" in struct:  # tps-ok[TPS503]: host-side structural check
            kv = [None, None, None, None, None]  # (pages, ln, pt, h, hd)
            if can_shard(mesh, MODEL_AXIS, self.heads):
                kv[3] = MODEL_AXIS
            if can_shard(mesh, SEQ_AXIS, int(struct["kp"].shape[0])):
                kv[0] = SEQ_AXIS
            specs["kp"] = specs["vp"] = P(*kv)
        else:
            kv = [None, None, None, None, None]  # (slots, ln, ctx, h, hd)
            if can_shard(mesh, MODEL_AXIS, self.heads):
                kv[3] = MODEL_AXIS
            if can_shard(mesh, SEQ_AXIS, self.max_ctx):
                kv[2] = SEQ_AXIS
            specs["k"] = specs["v"] = P(*kv)
        if all(s == P() for s in specs.values()):
            return None
        return specs

    # -- shapes ---------------------------------------------------------------
    def input_signature(self, bucket: tuple) -> Any:
        (b,) = bucket
        p = self.max_prompt
        return (
            jax.ShapeDtypeStruct((b, p), jnp.int32),   # padded prompt ids
            jax.ShapeDtypeStruct((b,), jnp.int32),     # prompt length
            jax.ShapeDtypeStruct((b,), jnp.int32),     # seed
            jax.ShapeDtypeStruct((b,), jnp.int32),     # max_new_tokens
            jax.ShapeDtypeStruct((b,), jnp.float32),   # temperature
        )

    def gen_item_signature(self) -> Any:
        p = self.max_prompt
        return (
            jax.ShapeDtypeStruct((p,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32),
        )

    def state_signature(self, slots: int) -> Any:
        ln, c, h, hd = self.layers, self.max_ctx, self.heads, self.head_dim
        n = self.max_new
        return {
            "k": jax.ShapeDtypeStruct((slots, ln, c, h, hd), self.dtype),
            "v": jax.ShapeDtypeStruct((slots, ln, c, h, hd), self.dtype),
            "pos": jax.ShapeDtypeStruct((slots,), jnp.int32),
            "tokens": jax.ShapeDtypeStruct((slots, n), jnp.int32),
            "n_new": jax.ShapeDtypeStruct((slots,), jnp.int32),
            "last": jax.ShapeDtypeStruct((slots,), jnp.int32),
            "done": jax.ShapeDtypeStruct((slots,), jnp.bool_),
            "seed": jax.ShapeDtypeStruct((slots,), jnp.int32),
            "max_new": jax.ShapeDtypeStruct((slots,), jnp.int32),
            "temp": jax.ShapeDtypeStruct((slots,), jnp.float32),
        }

    # -- shared device math ---------------------------------------------------
    def _attend_prefill(self, q, k, v, key_bias):
        """(B, P, H, hd) bidirectional attention with an additive per-key
        padding bias (B, P)."""
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        s = s + key_bias[:, None, None, :]
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def _sample(self, logits, seed, position, temp):
        """Per-lane seeded sampling at a cache ``position``: greedy when
        temp == 0, Gumbel-max otherwise — deterministic either way, and
        identical between the locked-batch loop and the engine because the
        fold key is (seed, target cache position)."""
        def one(lg, sd, pos, t):
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.key(0), sd), pos)
            g = jax.random.gumbel(key, lg.shape, jnp.float32)
            safe_t = jnp.where(t > 0, t, 1.0)
            sampled = jnp.argmax(lg / safe_t + g)
            return jnp.where(t > 0, sampled, jnp.argmax(lg)).astype(jnp.int32)

        return jax.vmap(one)(logits.astype(jnp.float32), seed, position, temp)

    def _logits(self, params, x):
        return (_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
                .astype(jnp.float32) @ params["head"].astype(jnp.float32))

    def _mlp(self, lp, hx, dt):
        """The position-wise FFN delta for a normed hidden block ``hx``
        (..., d) — the dense gelu MLP, or the Switch-MoE twin when
        ``options.moe_experts`` > 0. One seam shared by all four forward
        bodies (prefill / decode / paged-chunk / paged-decode), so the MoE
        variant inherits every serving path at once."""
        if not self.moe_experts:
            return (jax.nn.gelu(hx @ lp["w_up"].astype(dt))
                    @ lp["w_down"].astype(dt))
        return self._moe_ffn(lp, hx, dt)

    def _moe_ffn(self, lp, hx, dt):
        """Top-1 Switch FFN over ``ops.moe.switch_route`` with GROUP SIZE
        ONE: every token routes independently with capacity 1, so no token
        is ever dropped and a lane's FFN output is a function of that lane
        alone. A batch-global capacity would let slot A's routing evict
        slot B's token — fine for training throughput, wrong for serving,
        where results must be independent of batch composition (the
        invariant every engine parity test gates on). Expert weights carry
        a leading (E, ...) dim sharded on "model" under TP — expert
        parallelism via shardings, no hand-written collectives."""
        from tpuserve.ops.moe import switch_route

        lead, d = hx.shape[:-1], hx.shape[-1]
        xt = hx.reshape(-1, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            lp["router"].astype(jnp.float32))
        dispatch, combine, _aux = jax.vmap(
            lambda lg: switch_route(lg[None, :], 1))(logits)
        dispatch = dispatch[:, 0, :, 0].astype(dt)   # (T, E) 0/1 routing
        combine = combine[:, 0, :, 0].astype(dt)     # (T, E) gate-weighted
        xe = jnp.einsum("te,td->etd", dispatch, xt)
        up = jax.nn.gelu(
            jnp.einsum("etd,edf->etf", xe, lp["moe_up"].astype(dt)))
        down = jnp.einsum("etf,efd->etd", up, lp["moe_down"].astype(dt))
        out = jnp.einsum("te,etd->td", combine, down)
        return out.reshape(*lead, d).astype(hx.dtype)

    def _prefill(self, params, ids, n, seed, max_new, temp):
        """Batched prompt prefill -> the full decode state pytree (leading
        dim B): per-layer KV for the prompt, plus the FIRST sampled token.
        Shared verbatim by forward (locked batch) and init_state (engine)."""
        b, p = ids.shape
        ln, c, h, hd = self.layers, self.max_ctx, self.heads, self.head_dim
        dt = self.dtype
        x = (jnp.take(params["embed"], ids, axis=0)
             + params["pos"][None, :p, :]).astype(dt)
        key_bias = (jnp.arange(p)[None, :] >= n[:, None]) * jnp.float32(-1e9)
        kc = jnp.zeros((b, ln, c, h, hd), dt)
        vc = jnp.zeros((b, ln, c, h, hd), dt)
        for i in range(ln):
            lp = params[f"layer{i}"]
            hx = _norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
            q = (hx @ lp["wq"].astype(dt)).reshape(b, p, h, hd)
            k = (hx @ lp["wk"].astype(dt)).reshape(b, p, h, hd)
            v = (hx @ lp["wv"].astype(dt)).reshape(b, p, h, hd)
            kc = kc.at[:, i, :p].set(k)
            vc = vc.at[:, i, :p].set(v)
            a = self._attend_prefill(q, k, v, key_bias).reshape(b, p, h * hd)
            x = x + a.astype(dt) @ lp["wo"].astype(dt)
            hx = _norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
            x = x + self._mlp(lp, hx, dt)
        h_last = jnp.take_along_axis(
            x, jnp.maximum(n - 1, 0)[:, None, None], axis=1)[:, 0, :]
        first = self._sample(self._logits(params, h_last[:, None, :])[:, 0, :],
                             seed, n, temp)
        tokens = jnp.zeros((b, self.max_new), jnp.int32)
        tokens = tokens.at[:, 0].set(first)
        done = (first == self.eos_id) | (max_new <= 1)
        return {
            "k": kc, "v": vc, "pos": n, "tokens": tokens,
            "n_new": jnp.ones((b,), jnp.int32), "last": first, "done": done,
            "seed": seed, "max_new": max_new, "temp": temp,
        }

    def _decode_step(self, params, state):
        """One decode iteration over every lane: process ``last`` at cache
        index ``pos`` (writing its K/V), sample the token for pos+1.
        Finished (and free, zero-initialized) lanes freeze via ``done``."""
        kc, vc = state["k"], state["v"]
        b = kc.shape[0]
        ln, h, hd, c = self.layers, self.heads, self.head_dim, self.max_ctx
        dt = self.dtype
        pos = state["pos"]
        rows = jnp.arange(b)
        x = (jnp.take(params["embed"], state["last"], axis=0)
             + jnp.take(params["pos"], jnp.clip(pos, 0, c - 1), axis=0)
             ).astype(dt)
        mask = (jnp.arange(c)[None, :] > pos[:, None]) * jnp.float32(-1e9)
        for i in range(ln):
            lp = params[f"layer{i}"]
            hx = _norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
            q = (hx @ lp["wq"].astype(dt)).reshape(b, h, hd)
            k = (hx @ lp["wk"].astype(dt)).reshape(b, h, hd)
            v = (hx @ lp["wv"].astype(dt)).reshape(b, h, hd)
            # A finished lane writes nothing (GenerativeModel.step: frozen
            # bit for bit; the step after its last would else fill the
            # cache row of the position behind its final token).
            at = jnp.clip(pos, 0, c - 1)
            keep = state["done"][:, None, None]
            kc = kc.at[rows, i, at].set(jnp.where(keep, kc[rows, i, at], k))
            vc = vc.at[rows, i, at].set(jnp.where(keep, vc[rows, i, at], v))
            s = (jnp.einsum("bhd,bchd->bhc", q, kc[:, i])
                 .astype(jnp.float32) * (hd ** -0.5)) + mask[:, None, :]
            a = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("bhc,bchd->bhd", a, vc[:, i]).reshape(b, h * hd)
            x = x + o @ lp["wo"].astype(dt)
            hx = _norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
            x = x + self._mlp(lp, hx, dt)
        logits = self._logits(params, x[:, None, :])[:, 0, :]
        sampled = self._sample(logits, state["seed"],
                               jnp.clip(pos + 1, 0, c - 1), state["temp"])
        done = state["done"]
        n_new = state["n_new"]
        write_idx = jnp.clip(n_new, 0, self.max_new - 1)
        tokens = state["tokens"].at[rows, write_idx].set(
            jnp.where(done, state["tokens"][rows, write_idx], sampled))
        n_new2 = jnp.where(done, n_new, n_new + 1)
        done2 = done | (sampled == self.eos_id) | (n_new2 >= state["max_new"])
        new_state = {
            "k": kc, "v": vc,
            "pos": jnp.where(done, pos, jnp.clip(pos + 1, 0, c - 1)),
            "tokens": tokens,
            "n_new": n_new2,
            "last": jnp.where(done, state["last"], sampled),
            "done": done2,
            "seed": state["seed"], "max_new": state["max_new"],
            "temp": state["temp"],
        }
        # The token buffer rides the per-step host fetch (slots x max_new
        # int32 — tens of KB) so the engine's emission channel can stream
        # each token the iteration it lands, without extra device reads.
        return new_state, {"done": done2, "n_new": n_new2, "tokens": tokens}

    # -- one-shot path (locked batch: static batcher + bench baseline) --------
    def forward(self, params: Any, batch: Any) -> dict:
        ids, n, seed, max_new, temp = batch
        state = self._prefill(params, ids, n, seed, max_new, temp)

        def body(_, st):
            st2, _out = self._decode_step(params, st)
            return st2

        # The locked batch runs the FULL cap for every lane — max_new only
        # freezes a lane's outputs, never shortens the loop. That cost gap
        # is precisely what the iteration-level engine removes.
        state = jax.lax.fori_loop(0, self.max_new - 1, body, state)
        return {"tokens": state["tokens"], "n_new": state["n_new"]}

    # -- engine decomposition (tpuserve.genserve) ------------------------------
    def init_state(self, params: Any, item: Any) -> Any:
        ids, n, seed, max_new, temp = item
        state = self._prefill(params, ids[None], n[None], seed[None],
                              max_new[None], temp[None])
        return jax.tree_util.tree_map(lambda x: x[0], state)

    def step(self, params: Any, state: Any) -> tuple[Any, dict]:
        # The state pytree's own shape selects the path (a host-side
        # structural check at trace time): a paged engine allocates the
        # ``kv_plan`` block, a dense one the state_signature block.
        if "kp" in state:  # tps-ok[TPS503]: pytree structure check at trace time
            return self._paged_decode_step(params, state)
        return self._decode_step(params, state)

    def extract(self, params: Any, state: Any, slot: Any) -> Any:
        idx = jax.lax.dynamic_index_in_dim
        return {
            "tokens": idx(state["tokens"], slot, 0, keepdims=False),
            "n_new": idx(state["n_new"], slot, 0, keepdims=False),
        }

    def gen_max_steps(self) -> int:
        return self.max_new

    # -- paged KV path (ISSUE 18; PagedAttention/vLLM) ------------------------
    # KV lives in one global pool of fixed-size pages --
    # (pages, layers, page_tokens, heads, head_dim) -- addressed through a
    # per-slot block table of TRACED page indices, so the one compiled
    # step serves every page assignment (the zero-recompile obligation
    # slot indices already carry). Global position p of a slot lives at
    # (bt[slot, p // page_tokens], p % page_tokens). Page 0 is the
    # write-sink sentinel: free and frozen lanes scribble there instead
    # of into pages the ledger may have re-handed to another request.

    def kv_plan(self, slots: int, page_tokens: int, pages: int = 0) -> CachePlan:
        ln, h, hd = self.layers, self.heads, self.head_dim
        S, i32 = jax.ShapeDtypeStruct, jnp.int32

        def signature(pages: int, pps: int) -> dict:
            page = S((pages, ln, page_tokens, h, hd), self.dtype)
            return {
                "kp": pool(page), "vp": pool(page),
                "bt": S((slots, pps), i32), "pos": S((slots,), i32),
                "tokens": S((slots, self.max_new), i32), "n_new": S((slots,), i32),
                "last": S((slots,), i32), "done": S((slots,), jnp.bool_),
                "seed": S((slots,), i32), "max_new": S((slots,), i32),
                "temp": S((slots,), jnp.float32),
            }

        return CachePlan.build(signature, slots=slots, page_tokens=page_tokens, pages=pages,
                               max_tokens=self.max_ctx)

    def context_tokens(self, item: Any) -> int:
        return int(item[1]) + int(item[3])

    def prompt_tokens(self, item: Any) -> int:
        return int(item[1])

    def kv_prefill_chunk(self, requested: int) -> int:
        if requested <= 0 or requested >= self.max_prompt:
            return self.max_prompt
        return int(requested)

    def _lane_update(self, state, slot, name, value):
        arr = state[name]
        return jax.lax.dynamic_update_index_in_dim(
            arr, jnp.asarray(value).astype(arr.dtype), slot, 0)

    def prefill_chunk(self, params: Any, state: Any, launch: Any, *,
                      chunk: int) -> Any:
        # One prompt a launch (the contract's default, K = 1).
        slot, item, start, pages = launch
        # Whole-prompt chunk (the prefill_chunk = 0 default) routes through
        # init_state VERBATIM and only changes where K/V is stored, so
        # paged == dense token parity holds by construction.
        if chunk >= self.max_prompt:
            return self._prefill_paged_single(params, state, slot, item,
                                              pages)
        return self._prefill_paged_chunk(params, state, slot, item, start,
                                         pages, chunk)

    def _scatter_pages(self, state, pages, n, positions, per_layer_kv):
        """Write per-position K/V rows into the page pool: position p goes
        to (pages[p // P], p % P); positions >= n (padding) divert to the
        sentinel. ``per_layer_kv(i) -> (k, v)`` each (len(positions), h, hd)."""
        kp, vp = state["kp"], state["vp"]
        P = kp.shape[2]
        pps = state["bt"].shape[1]
        w_pages = jnp.where(
            positions < n,
            jnp.take(pages, jnp.minimum(positions // P, pps - 1), axis=0),
            0)
        offs = positions % P
        for i in range(self.layers):
            k, v = per_layer_kv(i)
            kp = kp.at[w_pages, i, offs].set(k)
            vp = vp.at[w_pages, i, offs].set(v)
        return kp, vp

    def _prefill_paged_single(self, params, state, slot, item, pages):
        _ids, n, _seed, _max_new, _temp = item
        lane = self.init_state(params, item)  # dense prefill, b=1
        p = self.max_prompt
        kp, vp = self._scatter_pages(
            state, pages, n, jnp.arange(p),
            lambda i: (lane["k"][i, :p], lane["v"][i, :p]))
        new = {"kp": kp, "vp": vp,
               "bt": jax.lax.dynamic_update_index_in_dim(
                   state["bt"], pages, slot, 0)}
        for f in ("pos", "tokens", "n_new", "last", "done", "seed",
                  "max_new", "temp"):
            new[f] = self._lane_update(state, slot, f, lane[f])
        return new

    def _prefill_paged_chunk(self, params, state, slot, item, start, pages,
                             chunk: int):
        """One chunk of an incremental prompt prefill: BIDIRECTIONAL within
        the chunk, causal across chunks (earlier chunks' K/V are final by
        the time later chunks attend through them). Multi-chunk encoding is
        therefore NOT bit-identical to the one-pass bidirectional prefill —
        it is a deterministic function of (prompt, seed, chunk width)
        alone, independent of batch composition and of what else the
        engine interleaves (the invariant tests gate on). Non-final chunks
        leave the lane frozen (done=True, pos=0) so interleaved decode
        steps skip it; the final chunk samples the first token and arms
        the lane exactly like init_state does."""
        ids, n, seed, max_new, temp = item
        C = int(chunk)
        ln, h, hd = self.layers, self.heads, self.head_dim
        dt = self.dtype
        kp, vp = state["kp"], state["vp"]
        P = kp.shape[2]
        pps = state["bt"].shape[1]
        c_pad = pps * P
        bt = jax.lax.dynamic_update_index_in_dim(state["bt"], pages, slot, 0)
        cpos = start + jnp.arange(C)
        cids = jnp.take(ids, jnp.minimum(cpos, self.max_prompt - 1), axis=0)
        x = (jnp.take(params["embed"], cids, axis=0)
             + jnp.take(params["pos"],
                        jnp.minimum(cpos, self.max_ctx - 1), axis=0)
             ).astype(dt)
        kv_limit = jnp.minimum(start + C, n)
        w_pages = jnp.where(
            cpos < n,
            jnp.take(pages, jnp.minimum(cpos // P, pps - 1), axis=0), 0)
        offs = cpos % P
        mask = (jnp.arange(c_pad)[None, :] >= kv_limit) * jnp.float32(-1e9)
        for i in range(ln):
            lp = params[f"layer{i}"]
            hx = _norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
            q = (hx @ lp["wq"].astype(dt)).reshape(C, h, hd)
            k = (hx @ lp["wk"].astype(dt)).reshape(C, h, hd)
            v = (hx @ lp["wv"].astype(dt)).reshape(C, h, hd)
            kp = kp.at[w_pages, i, offs].set(k)
            vp = vp.at[w_pages, i, offs].set(v)
            # Gather THIS slot's context (earlier chunks + the rows just
            # written) back out of the pool; sentinel rows sit past
            # kv_limit and are masked.
            kall = jnp.take(kp[:, i], pages, axis=0).reshape(c_pad, h, hd)
            vall = jnp.take(vp[:, i], pages, axis=0).reshape(c_pad, h, hd)
            s = (jnp.einsum("qhd,khd->hqk", q, kall).astype(jnp.float32)
                 * (hd ** -0.5)) + mask
            a = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("hqk,khd->qhd", a, vall).reshape(C, h * hd)
            x = x + o @ lp["wo"].astype(dt)
            hx = _norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
            x = x + self._mlp(lp, hx, dt)
        last_off = jnp.clip(n - 1 - start, 0, C - 1)
        h_last = jax.lax.dynamic_index_in_dim(x, last_off, 0, keepdims=False)
        logits = self._logits(params, h_last[None, None, :])[0, 0]
        first = self._sample(logits[None], seed[None], n[None], temp[None])[0]
        is_final = (start + C) >= n
        first_tok = jnp.where(is_final, first, jnp.int32(0))
        new = {"kp": kp, "vp": vp, "bt": bt}
        lane = {
            "pos": jnp.where(is_final, n, jnp.int32(0)),
            "tokens": jnp.zeros((self.max_new,), jnp.int32)
                         .at[0].set(first_tok),
            "n_new": jnp.where(is_final, jnp.int32(1), jnp.int32(0)),
            "last": first_tok,
            "done": jnp.where(is_final,
                              (first == self.eos_id) | (max_new <= 1),
                              jnp.bool_(True)),
            "seed": seed, "max_new": max_new, "temp": temp,
        }
        for f, val in lane.items():
            new[f] = self._lane_update(state, slot, f, val)
        return new

    def _paged_decode_step(self, params, state):
        """The paged twin of _decode_step: identical math and sampling,
        but K/V reads gather through the block table and writes go to
        (page, offset) — frozen/free lanes' writes divert to the sentinel
        so a released slot can never scribble into re-handed pages."""
        kp, vp, bt = state["kp"], state["vp"], state["bt"]
        b, pps = bt.shape
        P = kp.shape[2]
        ln, h, hd, c = self.layers, self.heads, self.head_dim, self.max_ctx
        c_pad = pps * P
        dt = self.dtype
        pos = state["pos"]
        done = state["done"]
        rows = jnp.arange(b)
        x = (jnp.take(params["embed"], state["last"], axis=0)
             + jnp.take(params["pos"], jnp.clip(pos, 0, c - 1), axis=0)
             ).astype(dt)
        mask = (jnp.arange(c_pad)[None, :] > pos[:, None]) * jnp.float32(-1e9)
        cp = jnp.clip(pos, 0, c - 1)
        page_of = jnp.take_along_axis(bt, (cp // P)[:, None], axis=1)[:, 0]
        w_page = jnp.where(done, 0, page_of)
        offs = cp % P
        for i in range(ln):
            lp = params[f"layer{i}"]
            hx = _norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
            q = (hx @ lp["wq"].astype(dt)).reshape(b, h, hd)
            k = (hx @ lp["wk"].astype(dt)).reshape(b, h, hd)
            v = (hx @ lp["wv"].astype(dt)).reshape(b, h, hd)
            kp = kp.at[w_page, i, offs].set(k)
            vp = vp.at[w_page, i, offs].set(v)
            kc = jnp.take(kp[:, i], bt, axis=0).reshape(b, c_pad, h, hd)
            vc = jnp.take(vp[:, i], bt, axis=0).reshape(b, c_pad, h, hd)
            s = (jnp.einsum("bhd,bchd->bhc", q, kc)
                 .astype(jnp.float32) * (hd ** -0.5)) + mask[:, None, :]
            a = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("bhc,bchd->bhd", a, vc).reshape(b, h * hd)
            x = x + o @ lp["wo"].astype(dt)
            hx = _norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
            x = x + self._mlp(lp, hx, dt)
        logits = self._logits(params, x[:, None, :])[:, 0, :]
        sampled = self._sample(logits, state["seed"],
                               jnp.clip(pos + 1, 0, c - 1), state["temp"])
        n_new = state["n_new"]
        write_idx = jnp.clip(n_new, 0, self.max_new - 1)
        tokens = state["tokens"].at[rows, write_idx].set(
            jnp.where(done, state["tokens"][rows, write_idx], sampled))
        n_new2 = jnp.where(done, n_new, n_new + 1)
        done2 = done | (sampled == self.eos_id) | (n_new2 >= state["max_new"])
        new_state = {
            "kp": kp, "vp": vp, "bt": bt,
            "pos": jnp.where(done, pos, jnp.clip(pos + 1, 0, c - 1)),
            "tokens": tokens,
            "n_new": n_new2,
            "last": jnp.where(done, state["last"], sampled),
            "done": done2,
            "seed": state["seed"], "max_new": state["max_new"],
            "temp": state["temp"],
        }
        return new_state, {"done": done2, "n_new": n_new2, "tokens": tokens}

    # -- host side ------------------------------------------------------------
    def host_decode(self, payload: bytes, content_type: str) -> Any:
        if content_type.startswith("application/json"):
            body = json.loads(payload.decode("utf-8"))
            prompt = body.get("prompt")
            if not isinstance(prompt, str):
                raise ValueError('JSON body must contain "prompt": str')
            seed = int(body.get("seed", 0))
            max_new = int(body.get("max_new_tokens", self.max_new))
            temp = float(body.get("temperature", 0.0))
        else:
            prompt, seed, max_new, temp = payload.decode("utf-8"), 0, \
                self.max_new, 0.0
        if not 1 <= max_new <= self.max_new:
            raise ValueError(
                f"max_new_tokens must be in [1, {self.max_new}], "
                f"got {max_new}")
        if temp < 0:
            raise ValueError(f"temperature must be >= 0, got {temp}")
        tok = self.tokenizer
        pieces = tok.tokenize(prompt)
        ids = [tok.vocab.get(t, tok.unk_id) for t in pieces][: self.max_prompt]
        ids = ids or [tok.cls_id]  # an empty prompt still needs one position
        arr = np.full((self.max_prompt,), tok.pad_id, np.int32)
        arr[: len(ids)] = ids
        # Every sampling parameter is part of the item ON PURPOSE: the
        # result cache digests the whole tuple, so (prompt, seed=1) and
        # (prompt, seed=2) can never share a key (ISSUE 9 satellite).
        return (arr, np.int32(len(ids)), np.int32(seed), np.int32(max_new),
                np.float32(temp))

    def canary_item(self) -> Any:
        return self.host_decode(
            b'{"prompt": "canary", "seed": 1, "max_new_tokens": 2}',
            "application/json")

    def detokenize(self, token_ids: "list[int]") -> str:
        """WordPiece pieces back to text: '##' continuations merge, EOS and
        pads drop."""
        inv = self.tokenizer.inv
        words: list[str] = []
        for t in token_ids:
            piece = inv.get(int(t), "")
            if not piece or piece in ("[SEP]", "[PAD]", "[CLS]"):
                continue
            if piece.startswith("##") and words:
                words[-1] += piece[2:]
            else:
                words.append(piece)
        return " ".join(words)

    def _result(self, tokens: np.ndarray, n_new: int) -> dict:
        toks = [int(t) for t in np.asarray(tokens)[: int(n_new)]]
        return {"text": self.detokenize(toks), "tokens": toks,
                "n_tokens": len(toks)}

    def finalize(self, extracted: Any, item: Any) -> Any:
        return self._result(extracted["tokens"], int(extracted["n_new"]))

    def result_units(self, result: Any) -> float:
        """Tokens generated — the tokens/s headline unit."""
        return float(result.get("n_tokens", 1))

    # -- streaming (ISSUE 17) -------------------------------------------------
    def stream_units(self, step_out: dict, slot: int, stream: dict) -> list:
        """Token units newly landed for one slot this iteration. The text
        delta is incremental detokenize: detokenize() is append-only under
        WordPiece merges (a new word appends " w", a "##" continuation
        appends its suffix, EOS/PAD add nothing), so the concatenation of
        every unit's "text" equals the unary result's "text" byte-for-byte
        — the stream drill's audit anchor."""
        n = int(step_out["n_new"][slot])
        sent = int(stream.get("sent", 0))
        if n <= sent:
            return []
        toks = [int(t) for t in step_out["tokens"][slot][:n]]
        prev = stream.get("text", "")
        units = []
        for i in range(sent, n):
            text = self.detokenize(toks[: i + 1])
            units.append({"type": "token", "text": text[len(prev):],
                          "token": toks[i], "index": i})
            prev = text
        stream["sent"] = n
        stream["text"] = prev
        return units

    def stream_finish_reason(self, result: Any) -> str:
        toks = result.get("tokens") or []
        return "stop" if toks and toks[-1] == self.eos_id else "length"

    def stream_usage(self, result: Any) -> dict:
        return {"completion_tokens": int(result.get("n_tokens", 0))}

    def host_postprocess(self, outputs: dict, n_valid: int) -> list[dict]:
        return [self._result(outputs["tokens"][r], outputs["n_new"][r])
                for r in range(n_valid)]


def create(cfg: ModelConfig) -> TextGenServing:
    return TextGenServing(cfg)
