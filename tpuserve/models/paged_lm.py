"""What the language-model families that serve through paged KV share
(ISSUE 32; moved out of ``decoder.py`` so that ``hybrid.py`` is not a copy).

``PagedLM`` is the part of a decoder-only model that is the same whatever its
layers are: the model's ``config.json`` and what is served of it, the draw of
every tensor by recipe, the per-lane block of the paged state, a launch's
tile arithmetic (ISSUE 31's packed prefill), the walk of a block table in key
blocks under a running softmax (``_key_blocks``, ``_over_key_blocks``: what
attention by head and latent attention share, ISSUE 34), paged full attention
by head for prefill tiles and for decode, page writes (rows by head or one
latent row a token), the head, the sampler with its served
log-probabilities, the arming of a lane and a step's token bookkeeping, and
the whole host side of a ``:generate`` request.

THE TWO PROGRAMS ARE HERE, ONCE (ISSUE 45). ``prefill_chunk`` and ``step``:
tiles or live lanes, the embedding, the launch's plan, the caches out of the
state as lists, ``for i in layers: _layer``, one row into ``acc``, ``_arm`` or
``_emit``. A family supplies its parameters (``_gains``, ``_tensors``,
``_vectors``, under ``layer{i}``); its per-layer caches, each leaf ONCE with its
kind (``_cache_signature``: ``kv_plan`` is those and ``_lane_signature``);
ONE ``_layer(i, lp, x, caches, m)`` for both phases -> (the stream, the
layer's expert counts or None), where ``m`` is the launch's plan and what
differs between the phases lives in the mixers' paired methods, chosen by
``m["t"]`` (the tiles; None in a step); ``COLUMNS``, what ``acc`` holds: a
``Column`` a column says ONCE what a launch adds to it and the counter it
feeds in each phase, and the state's width, the launch's row, ``bind_metrics``
and ``observe_step`` all read that list; and, where its own differ from the
defaults, ``_embed``, ``_prefill_plan`` / ``_step_plan`` (a ring's places, a
walk's work list) and ``_counts``.

A subclass sets, in its constructor: ``dtype``, ``d``, ``eps``, ``n_layers``,
``vocab_full``, ``v_first``, ``vocab``, ``scales``, where it attends by head
``hd`` and ``kv`` (KV heads held), and calls ``_serve_options``. WHAT A LAYER'S
HEADS ARE is read in one place, ``_heads(i)`` -> ``Heads(kv, dk, dv)`` (ISSUE
49): KV heads held, a key's (and a query's) width, a value's. By default every
layer has ``kv`` heads of ``hd`` both ways; a family whose keys are wider than
its values, or whose KV heads go by the layer's kind, says so there, and the
tensors, the pools' shapes, the prefill walk and ``_attend`` follow it. It may set
``tied`` (the head is the embedding transposed: no ``head`` is drawn) and
``score_scale`` (what the scores are multiplied by, where the config says).

PAGES OF NARROW HEADS (ISSUE 40). A row of the K or V pool holds as many KV
heads side by side as fill the 128 lanes (``_kv_pack``: two heads of 64), so a
pool is ``(KV / pack, pages, P, pack * hd)`` and a page row is never half a
lane row. A write is the same scatter of rows; prefill's key blocks are taken
apart by head after the gather; decode on the TPU hands the paged-attention
kernel each query head zero-padded into its own KV head's part of the row
(the score is the same sum), and keeps that part of the context.

WHAT A TRACE CALLS THE PARTS (ISSUE 66). Every heavy operation of the two
programs lies under a ``jax.named_scope`` that names its KIND of work: the
kernels' and mixers' own (``attn_*``, ``ssm_*``, ``mla_*``, ``moe_*``, ``eva_*``,
``sel_*``, ``hc_mix``, ``delta_update``, ``sample``: each where its family says)
and, for what stands between them, eight more: ``embed`` (the rows' gather, a
family's multiplier), ``norm`` (``rms_norm`` and the families' own norms),
``proj`` (a mixer's dense in- and out-projections outside its kernel or
scan), ``ffn_dense`` (``_swiglu`` and its like: a dense feed-forward, a shared
expert), ``cache_write`` (``_write_pages`` and the rings' and pools' other
scatters), ``head`` (the last norm and the vocabulary product), ``plan`` (what a
launch works out once, before its layers) and ``emit`` (what it leaves in the
state after its sampler, and its row into ``acc``). A scope is metadata of the
compiled program and costs nothing at run time. None holds a whole layer or a
whole program; where an older scope already holds a projection or a norm
(``ssm_update`` the whole Mamba mixer, ``mla_decode`` its projections) the new
one nests inside it. ``benchmark/launch_scopes.py`` reads a trace by them, and
``tests/test_program_scopes.py`` holds every family's programs to the rule.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpuserve.config import ModelConfig
from tpuserve.genserve.model import CachePlan, GenerativeModel, Leaf, LeafKind, PrefillPiece
from tpuserve.models import seeded
from tpuserve.obs import GEN_PHASES

LOGPROBS = 8  # top log-probabilities kept per generated position
NEG = -1e9
MAX_PIECES = 8    # prompts' pieces one prefill launch takes at most
KEY_BLOCK = 1024  # key positions a block of a full layer's prefill attention
TOP_GROUP = 128   # neighbouring logits a group of the sampler's top-k (``_top_logits``)


def scoped(name: str) -> Callable:
    """``jax.named_scope(name)`` around every call of the function it decorates.
    (``jax.named_scope`` is itself a decorator, but one object that keeps the
    name stack it found on ITSELF: a function that calls itself, as
    ``_write_pages`` does, or two threads that trace at once, leave the scope
    on the stack.)"""
    def wrap(f: Callable) -> Callable:
        @functools.wraps(f)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)
        return inner
    return wrap


@scoped("norm")
def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _mm(a: jax.Array, w: jax.Array) -> jax.Array:
    """Product in the served type with float32 accumulation."""
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)


def read_config_file(cfg: ModelConfig) -> dict:
    """The model's own ``config.json``, named by ``options.config_file``."""
    if not cfg.options.get("config_file"):
        raise ValueError(f"{cfg.name}: family {cfg.family} needs options.config_file "
                         "(the model's config.json)")
    with open(cfg.options["config_file"], encoding="utf-8") as f:
        return json.load(f)


def head_share(name: str, idx: int, of: int, heads: list[int], kv_full: int):
    """``share.attention_heads = [index, of]``: chip ``index`` of ``of`` equal
    parts of the query heads -> (held query heads by layer, their first, KV
    heads held, the first of them). Where the chips outnumber the KV heads a
    chip holds ONE, head ``index * kv_full // of``, which its neighbours hold
    too (as every tensor-parallel server replicates them)."""
    if any(h % of for h in heads) or (kv_full % of and of % kv_full):
        raise ValueError(f"{name}: share.attention_heads = [{idx}, {of}] "
                         "does not divide the head counts")
    held = [h // of for h in heads]
    kv = max(1, kv_full // of)
    if any(h % kv for h in held):
        raise ValueError(f"{name}: held query heads {held} do not "
                         f"group over {kv} held KV heads")
    return held, [idx * h for h in held], kv, idx * kv_full // of


class _ExpertSteps:
    """The counters one column of ``acc`` feeds: held experts x expert layers
    run as it is summed, and expert layers run (the same over ``held``)."""

    def __init__(self, steps: Any, layers: Any, held: int) -> None:
        self.steps, self.layers, self.held = steps, layers, held

    def inc(self, amount: float) -> None:
        self.steps.inc(amount)
        self.layers.inc(amount / self.held)


class Heads(NamedTuple):
    """A layer's attention by head, as its tensors and its cache see it: KV
    heads held, the width of a key (and of a query), the width of a value."""
    kv: int
    dk: int
    dv: int


class Column(NamedTuple):
    """One column of ``acc``. ``sums(model, stats, counts)``: what one launch
    adds, from its expert layers' counts (``stats``: a dict a layer that has
    experts) and its own ``_counts``. ``counter(model, metrics, phase)``: the
    counter the column feeds in that phase, None where it means nothing
    there."""
    sums: Callable
    counter: Callable


def counted(key: str) -> Callable:
    """A launch's own count ``key`` (``_counts``)."""
    return lambda model, stats, counts: counts[key]


def summed(key: str) -> Callable:
    """The expert layers' count ``key``, over the layers that ran."""
    return lambda model, stats, counts: sum(st[key] for st in stats)


def series(name: str, labels: str = "") -> Callable:
    """The counter ``name{model=,phase=<labels>}``."""
    return lambda model, metrics, ph: metrics.counter(
        f"{name}{{model={model.name},phase={ph}{labels}}}")


# Picks of live tokens on held and on absent experts, held experts hit, held
# experts x expert layers run (which feeds ``moe_layers_total`` too, in its own
# unit): ``ops/moe.py``'s ``held_experts`` counts, summed over the layers.
EXPERT_COLUMNS = (
    Column(summed("routed_held"), series("moe_tokens_routed_total", ",held=yes")),
    Column(summed("routed_absent"), series("moe_tokens_routed_total", ",held=no")),
    Column(summed("experts_hit"), series("moe_experts_hit_total")),
    Column(lambda model, stats, counts: model.e_count * len(stats),
           lambda model, metrics, ph: _ExpertSteps(
               series("moe_expert_steps_total")(model, metrics, ph),
               series("moe_layers_total")(model, metrics, ph), model.e_count)))
# Positions attended from, summed over live tokens.
CONTEXT_COLUMN = Column(counted("context"), series("gen_context_tokens_total"))
# Expert layers run whose dispatch carried the compact row bound (``ops/moe.py``).
COMPACT_COLUMN = Column(summed("compact"), series("moe_layers_compact_total"))
# Steps by whether their sampler made its Gumbel draw (``_sample``'s branch):
# ``gen_sample_steps_total{model=,path=greedy|drawn}``.
SAMPLE_COLUMNS = tuple(
    Column(lambda model, stats, counts, path=path: counts["sample"][path],
           lambda model, metrics, ph, path=path: metrics.counter(
               f"gen_sample_steps_total{{model={model.name},path={path}}}")
           if ph == "decode" else None)
    for path in ("greedy", "drawn"))


class PagedLM(GenerativeModel):
    COLUMNS: tuple = ()  # what ``acc`` holds, a ``Column`` each: the family's
    tied = False        # the head is the embedding transposed
    score_scale = None  # attention's scores times this; None: hd ** -0.5
    # Positions a compute block of the decode kernel holds at most, where the
    # pool's rows are packed. The kernel reads a block whole whatever the
    # lane's length, so a small one follows the live context and a large one
    # saves steps of its loop: four layers of 80 lanes at 300 live positions
    # took 2.52 / 2.17 / 1.59 / 1.66 / 1.86 / 2.23 ms at 128 / 256 / 384 / 512 /
    # 768 / 1,536 positions (scripts/bench_attn_decode.py, my chip run, PR 40).
    PACKED_DECODE_BLOCK = 512

    def _scale(self) -> float:
        return self.hd ** -0.5 if self.score_scale is None else float(self.score_scale)

    def _serve_options(self, cfg: ModelConfig, a: dict) -> None:
        """What is served of the model: the context, the draw's seed and
        scales, and the counters' state (a row of ``COLUMNS`` a phase)."""
        o = cfg.options
        self.max_prompt = int(o.get("max_prompt_tokens", 64))
        self.max_new = int(o.get("max_new_tokens", 32))
        self.max_ctx = self.max_prompt + self.max_new
        seed = o.get("draw_weights_seed")
        self.draw_seed = None if seed is None else int(seed)
        self._counters: list | None = None
        self._seen = np.zeros((len(GEN_PHASES), len(self.COLUMNS)), np.uint32)

    # -- params ---------------------------------------------------------------
    def draw_params(self, seed: int) -> Any:
        """Jittable: every tensor by the recipe of ``tpuserve.models.seeded``,
        in the served type; norms' gains are ones."""
        p: dict = {}

        def put(path, value):
            node = p
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = value

        for path, shape in self._gains():
            put(path, jnp.ones(shape, self.dtype))
        for path, shape, full, start, scale, fan_in in self._tensors():
            put(path, seeded.draw(seed, "/".join(path), shape, scale / math.sqrt(fan_in),
                                  self.dtype, full_shape=full, start=start))
        for path, shape, full, start, lo, hi in self._vectors():
            # The four summed bytes over their range, in [0, 1], then the range.
            u = 0.5 + seeded.draw(seed, "/".join(path), shape, seeded.BELL_STD / 1020.0,
                                  jnp.float32, full_shape=full, start=start)
            put(path, jnp.float32(lo) + jnp.float32(hi - lo) * u)
        return p

    def _vectors(self):
        """(path, shape, full shape, start, low, high) of the float32 vectors
        drawn INSIDE a range (a bell over it, by the same recipe): none
        unless the family has some (a router's selection bias)."""
        return ()

    def _vocab_tensors(self):
        """The embedding's held rows and, unless the head is tied to it, the
        head's held columns (``_tensors``' first entries)."""
        d, s = self.d, self.scales
        yield (("embed",), (self.vocab, d), (self.vocab_full, d), (self.v_first, 0),
               s["embed"], 1)
        if not self.tied:
            yield (("head",), (d, self.vocab), (d, self.vocab_full), (0, self.v_first),
                   s["head"], d)

    def _drawn(self) -> Any:
        return jax.jit(self.draw_params, static_argnums=0)(self.draw_seed or 0)

    def init_params(self, rng: jax.Array) -> Any:
        return self._drawn()

    def device_params(self, device: Any) -> Any:
        """The runtime's hook for weights that never cross the host: drawn on
        ``device`` in one jitted call where ``draw_weights_seed`` is set."""
        if self.draw_seed is None or self.cfg.weights:
            return None
        with jax.default_device(device):
            return jax.block_until_ready(self._drawn())

    # -- the locked-batch contract: not served ----------------------------------
    def _paged_only(self, *_a, **_k):
        raise NotImplementedError(
            f"{self.name}: family {self.cfg.family} serves through the generation "
            "engine alone: set [genserve] enabled = true and kv_paging = true")

    input_signature = forward = host_postprocess = _paged_only
    state_signature = init_state = _paged_only

    # -- shapes -----------------------------------------------------------------
    def gen_item_signature(self) -> Any:
        i32 = jnp.int32
        return (jax.ShapeDtypeStruct((self.max_prompt,), i32),  # held-row ids
                jax.ShapeDtypeStruct((), i32),                  # prompt length
                jax.ShapeDtypeStruct((), i32),                  # seed
                jax.ShapeDtypeStruct((), i32),                  # max_new_tokens
                jax.ShapeDtypeStruct((), jnp.float32),          # temperature
                jax.ShapeDtypeStruct((), i32))                  # logprobs asked

    def _heads(self, i: int | None = None) -> Heads:
        """Layer ``i``'s attention by head (module docstring): here every
        layer alike, ``kv`` heads of ``hd`` for keys and values."""
        return Heads(self.kv, self.hd, self.hd)

    def _kv_pack(self, kv: int | None = None, width: int | None = None) -> int:
        """KV heads a pool's row holds side by side: as many as fill the 128
        lanes where they do so exactly, else one (``kv`` heads of ``width``
        values; by default the model's)."""
        kv, width = self.kv if kv is None else kv, self.hd if width is None else width
        pack = 128 // width if width < 128 and 128 % width == 0 else 1
        return pack if kv % pack == 0 else 1

    def _page_shape(self, pages: int, page_tokens: int, kv: int | None = None,
                    width: int | None = None) -> tuple:
        kv, width = self.kv if kv is None else kv, self.hd if width is None else width
        pack = self._kv_pack(kv, width)
        return (kv // pack, pages, page_tokens, pack * width)

    def _by_head(self, blk, width: int | None = None):
        """Gathered pages (KV / pack, n, P, pack * width) -> (KV, n * P, width)."""
        kvp, n, P, w = blk.shape
        width = self.hd if width is None else width
        if w == width:
            return blk.reshape(kvp, n * P, w)
        return blk.reshape(kvp, n * P, w // width, width).transpose(0, 2, 1, 3) \
            .reshape(kvp * (w // width), n * P, width)

    def kv_plan(self, slots: int, page_tokens: int, pages: int = 0, **geometry) -> CachePlan:
        """``_cache_signature`` and ``_lane_signature`` as one plan; ``geometry`` is a family's
        own (``CachePlan.build``): its rings' length, what a page stands for if not its rows."""
        return CachePlan.build(
            lambda pages, pps: {**self._cache_signature(slots, pages, page_tokens),
                                **self._lane_signature(slots, pps)},
            slots=slots, page_tokens=page_tokens, pages=pages, max_tokens=self.max_ctx,
            **geometry)

    def _cache_signature(self, slots: int, pages: int, page_tokens: int) -> dict:
        """THE statement of a leaf's name, kind and shapes: {leaf: ``pool`` / ``rings`` /
        ``slot_block`` of a shape a layer that keeps one} (bare: a further lane of the family's)."""
        raise NotImplementedError

    @functools.cached_property
    def _kinds(self) -> dict:
        """{cache leaf: its kind} as ``_cache_signature`` states them, in its order (the same at
        any geometry): what the programs, which have the state alone, find their leaves by."""
        return {leaf: s.kind for leaf, s in self._cache_signature(1, 1, 1).items()
                if isinstance(s, Leaf)}

    def _leaves(self, *kinds: LeafKind) -> tuple:
        """The cache leaves of ``kinds`` (of every kind where none is named)."""
        return tuple(leaf for leaf, kind in self._kinds.items() if not kinds or kind in kinds)

    def _lane_signature(self, slots: int, pps: int) -> dict:
        """The per-lane part of the paged state block: a slot's block-table
        row, its position and sampling parameters, the tokens and
        log-probabilities generated so far, and the device's sums."""
        S = jax.ShapeDtypeStruct
        i32, n = jnp.int32, self.max_new
        return {
            "bt": S((slots, pps), i32),
            "pos": S((slots,), i32), "n_new": S((slots,), i32),
            "last": S((slots,), i32), "armed": S((slots,), jnp.bool_),
            "done": S((slots,), jnp.bool_), "seed": S((slots,), i32),
            "max_new": S((slots,), i32), "temp": S((slots,), jnp.float32),
            "tokens": S((slots, n), i32),
            "lp_ids": S((slots, n, LOGPROBS), i32),
            "lp": S((slots, n, LOGPROBS), jnp.float32),
            # Cumulative, wrapping; row 0 prefill chunks, row 1 decode steps,
            # a column of ``COLUMNS`` each.
            "acc": S((len(GEN_PHASES), len(self.COLUMNS)), jnp.uint32),
        }

    def context_tokens(self, item: Any) -> int:
        return int(item[1]) + int(item[3])

    def prompt_tokens(self, item: Any) -> int:
        return int(item[1])

    def kv_prefill_chunk(self, requested: int) -> int:
        if requested <= 0 or requested >= self.max_prompt:
            return self.max_prompt
        return int(requested)

    def gen_max_steps(self) -> int:
        return self.max_new

    # -- device math --------------------------------------------------------------
    def _attend(self, q, k, v, mask, sink=None):
        """q (..., T, H, dk), k (..., C, KV, dk), v (..., C, KV, dv), mask (...,
        T, C) True where a query may see a key -> (..., T, H, dv) in float32.
        Query head h reads KV head h // (H / KV). ``sink`` (H,) float32: one
        logit a head with no value, a column beside the scores that joins the
        softmax's denominator and is dropped after it."""
        kvh = k.shape[-2]
        g = q.shape[-2] // kvh
        qg = q.reshape(q.shape[:-2] + (kvh, g, q.shape[-1]))
        s = jnp.einsum("...tkgd,...ckd->...kgtc", qg, k,
                       preferred_element_type=jnp.float32) * self._scale()
        s = jnp.where(mask[..., None, None, :, :], s, NEG)
        if sink is None:
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        else:
            col = jnp.broadcast_to(sink.astype(jnp.float32).reshape(kvh, g, 1, 1),
                                   s.shape[:-1] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, col], axis=-1),
                               axis=-1)[..., :-1].astype(v.dtype)
        o = jnp.einsum("...kgtc,...ckd->...tkgd", p, v,
                       preferred_element_type=jnp.float32)
        return o.reshape(q.shape[:-1] + (v.shape[-1],))

    @staticmethod
    @scoped("cache_write")
    def _write_pages(pool, page, off, rows):
        """``rows`` (T, KV, hd) into the pool (KV, pages, P, hd) at (page[t],
        off[t]) of every KV head: as ONE scatter of rows into the pool seen
        as (KV * pages * P, hd). A pool of packed rows, (KV / pack, pages, P,
        pack * hd), takes the same ``rows``: a token's heads lie side by
        side. (Scattered over two middle dimensions, the
        compiler copied the whole pool to another layout and back, eight
        times a step: 13 of a step's 33 ms, my chip run, PR 28.) A pool
        with no heads, (pages, P, width), takes ``rows`` (T, width): one
        latent row a token, or (``eva``, ISSUE 63) a token's KV heads side by
        side in one row, ``width`` = KV x hd: T row copies where a pool by
        head takes T x KV."""
        if pool.ndim == 3:
            return PagedLM._write_pages(pool[None], page, off, rows[:, None])[0]
        kv, n_pages, p_tokens, hd = pool.shape
        at = (jnp.arange(kv)[None, :] * n_pages + page[:, None]) * p_tokens + off[:, None]
        flat = pool.reshape(kv * n_pages * p_tokens, hd)
        return flat.at[at.reshape(-1)].set(rows.reshape(-1, hd)).reshape(pool.shape)

    @scoped("ffn_dense")
    def _swiglu(self, u, w_gate, w_up, w_down):
        h = (jax.nn.silu(_mm(u, w_gate)) * _mm(u, w_up)).astype(self.dtype)
        return _mm(h, w_down)

    def _head(self, params, x):
        """(T, d) -> (T, vocab held) float32 logits. A tied head is the
        embedding's held rows, contracted over ``d`` where they lie."""
        h = rms_norm(x, params["norm_f"], self.eps)
        if self.tied:
            return jnp.einsum("td,vd->tv", h, params["embed"],
                              preferred_element_type=jnp.float32)
        return _mm(h, params["head"])

    @staticmethod
    def _top_logits(logits):
        """(rows, V) float32 -> the ``LOGPROBS`` largest logits a row and their
        ids, both (rows, LOGPROBS), as ``jax.lax.top_k`` of the whole row gives
        them (ties to the lower id), in ONE read of the row: the maxima of its
        groups of ``TOP_GROUP`` neighbours, the ``LOGPROBS`` groups of largest
        maximum (an entry among the row's largest lies in one of them), and
        the largest of those groups' entries, the groups in ascending order so
        that a tie falls as it does over the whole row. A row that is no whole
        number of groups is padded with -inf; one of ``LOGPROBS`` groups or
        fewer goes straight to ``top_k``."""
        rows, v = logits.shape
        n = -(-v // TOP_GROUP)
        if n <= LOGPROBS:
            return jax.lax.top_k(logits, LOGPROBS)
        # Eight rows are a tile of the logits on the TPU: kept apart, the view by
        # groups, and the groups as a table of rows to take from, are the same
        # bytes. As (rows, n, TOP_GROUP) the compiler first copied every logit
        # into another layout, 134 MB of scratch at 512 x 65,536: the maxima took
        # 0.58 ms where they take 0.19 and the take 0.44 where it takes 0.04 (my
        # chip run, PR 60; ``scripts/bench_sampler.py`` by operation).
        tile = 8 if rows % 8 == 0 else 1
        groups = jnp.pad(logits, ((0, 0), (0, n * TOP_GROUP - v)), constant_values=-jnp.inf) \
            .reshape(rows // tile, tile, n, TOP_GROUP)
        _, held = jax.lax.top_k(jnp.max(groups, axis=-1).reshape(rows, n), LOGPROBS)
        held = jnp.sort(held, axis=-1)
        row = jnp.arange(rows)[:, None]
        taken = jnp.take(groups.transpose(0, 2, 1, 3).reshape(rows * n, TOP_GROUP),
                         ((row // tile) * n + held) * tile + row % tile, axis=0)
        vals, at = jax.lax.top_k(taken.reshape(rows, LOGPROBS * TOP_GROUP), LOGPROBS)
        return vals, jnp.take_along_axis(held, at // TOP_GROUP, axis=1) * TOP_GROUP \
            + at % TOP_GROUP

    @staticmethod
    def _sample(logits, seed, position, temp, drawn):
        """Greedy where temp == 0, Gumbel-max otherwise, keyed by the
        request's seed and the position sampled for; also the top
        log-probabilities of the distribution sampled from: log-softmax's own
        formula on the ``LOGPROBS`` largest logits (``_top_logits``), whose
        first is the row's maximum and the greedy token. ``drawn`` (the
        launch's: some live row has temp > 0) guards the draw: no random number
        is made in a launch whose live rows are all greedy."""
        def one(lg, sd, pos, t, greedy):
            key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), sd), pos)
            g = jax.random.gumbel(key, lg.shape, jnp.float32)
            sampled = jnp.argmax(lg / jnp.where(t > 0, t, 1.0) + g)
            return jnp.where(t > 0, sampled, greedy).astype(jnp.int32)

        with jax.named_scope("sample"):
            vals, ids = PagedLM._top_logits(logits)
            top = vals[:, :1]
            lp = (vals - top) - jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1, keepdims=True))
            tok = jax.lax.cond(
                drawn, lambda: jax.vmap(one)(logits, seed, position, temp, ids[:, 0]),
                lambda: ids[:, 0])
        return tok, ids, lp

    # -- the two programs ---------------------------------------------------------
    def prefill_chunk(self, params: Any, state: Any, launch: Any, *, chunk: int) -> Any:
        """One launch of ``pack_prefill``: piece j is tokens [start[j],
        start[j] + length[j]) of the prompt in slot[j], causal within the
        piece and over what earlier launches left in that slot's caches. A
        token sees its own prompt only, at its own positions; a piece that
        ends its prompt samples the first token at its own last row and arms
        its own lane."""
        with jax.named_scope("plan"):
            t = self._tiles(launch, chunk)
        with jax.named_scope("embed"):
            x = self._embed(params, launch["ids"])
        with jax.named_scope("plan"):
            m = dict(self._prefill_plan(state, launch, t),
                     drawn=jnp.any((launch["length"] > 0) & (launch["temp"] > 0)))
        x, new = self._layers(params, state, x, m)
        return self._arm(params, state, new, launch, t, x, m["lanes"], m["drawn"])

    def step(self, params: Any, state: Any) -> tuple[Any, dict]:
        """One token a live lane."""
        with jax.named_scope("plan"):
            live = state["armed"] & ~state["done"]
            pos = jnp.clip(state["pos"], 0, self.max_ctx - 1)
        with jax.named_scope("embed"):
            x = self._embed(params, state["last"])
        with jax.named_scope("plan"):
            m = dict(self._step_plan(state, live, pos),
                     drawn=jnp.any(live & (state["temp"] > 0)))
        x, new = self._layers(params, state, x, m)
        return self._emit(params, state, new, x, live, pos, m["drawn"])

    def _layers(self, params, state, x, m: dict):
        """The stream through every layer and the launch's row into ``acc``
        (row 0 a prefill launch, row 1 a step) -> (the stream, the state with
        the caches and ``acc`` as the launch leaves them)."""
        caches = {leaf: list(state[leaf]) for leaf in self._leaves()}
        stats = []
        for i in range(self.n_layers):
            x, st = self._layer(i, params[f"layer{i}"], x, caches, m)
            if st is not None:
                stats.append(st)
        with jax.named_scope("emit"):
            counts = self._counts(m)
            sums = [col.sums(self, stats, counts) for col in self.COLUMNS]
            row = jnp.stack([jnp.asarray(v, jnp.int32) for v in sums])
            acc = state["acc"].at[int(m["t"] is None)].add(row.astype(jnp.uint32))
        return x, dict(state, **caches, acc=acc)

    def _layer(self, i: int, lp: dict, x, caches: dict, m: dict):
        """The stream ``x`` through layer ``i`` (its parameters ``lp``) in the
        phase the plan ``m`` is of; the layer's caches, ``caches[leaf][j]``,
        are replaced in place -> (the stream, the layer's expert counts or
        None)."""
        raise NotImplementedError

    def _embed(self, params, ids):
        return jnp.take(params["embed"], ids, axis=0)

    def _page_tokens(self, state) -> int:
        """Rows a page of the first pool holds (1 where no layer keeps pages:
        nothing is written through the address then)."""
        pools = state[self._leaves(LeafKind.POOL)[0]]
        return pools[0].shape[-2] if pools else 1

    def _prefill_plan(self, state, launch, t: dict) -> dict:
        """What a prefill launch works out once, before its layers: the tiles
        ``t``, the live rows and their positions (under the names a step
        has them), the pieces, the page and offset every row is written at, and
        ``lanes``: further lanes the family arms from the launch (a ring's
        index). A family adds its own."""
        w_page, off = self._page_of(t, self._page_tokens(state), state["bt"].shape[1])
        return {"t": t, "live": t["valid"], "pos": t["cpos"], "w_page": w_page, "off": off,
                "lanes": {}, **{f: launch[f] for f in ("slot", "start", "length")}}

    def _step_plan(self, state, live, pos) -> dict:
        """What a step works out once, before its layers: the live lanes,
        their positions and block table, and the page and offset each lane's
        row is written at (a lane that is not live: the sentinel). ``t`` is
        None: no tiles, a step."""
        P = self._page_tokens(state)
        page_of = jnp.take_along_axis(state["bt"], (pos // P)[:, None], axis=1)[:, 0]
        return {"t": None, "live": live, "pos": pos, "bt": state["bt"],
                "w_page": jnp.where(live, page_of, 0), "off": pos % P}

    def _counts(self, m: dict) -> dict:
        """A launch's own counts, by the names ``COLUMNS`` reads them under
        (``counted``), after its layers: here the context, positions attended
        from summed over live tokens, and a step under the branch its sampler
        takes (a prefill launch under neither)."""
        step = m["t"] is None
        return {"context": jnp.sum(jnp.where(m["live"], m["pos"] + 1, 0)),
                "sample": {"greedy": step & ~m["drawn"], "drawn": step & m["drawn"]}}

    # -- prefill ------------------------------------------------------------------
    # One launch of the static width C carries the waiting pieces of up to K
    # prompts (ISSUE 31), in K tiles of T = C / K rows; a piece takes whole
    # tiles, so a tile belongs to one prompt. Whatever a token passes
    # through alone (embedding, norms, projections, the feed-forwards, the
    # experts) runs once over the C packed rows; what reads a prompt's own
    # caches (attention, a scan's state) goes tile by tile.

    TILE_ROWS = 0  # rows a tile has at least, where a family's tile pays a price of its own

    def kv_prefill_pieces(self, chunk: int, page_tokens: int) -> int:
        """K: tiles of whole pages (of ``TILE_ROWS`` rows or more), as many as
        divide the chunk, at most ``MAX_PIECES``. (A window does not enter: a
        window layer's tile reads its ring and the ``window`` rows before it
        whatever its width.)"""
        most = min(MAX_PIECES, max(1, chunk // max(page_tokens, self.TILE_ROWS)))
        return next((k for k in range(most, 1, -1) if chunk % (k * page_tokens) == 0), 1)

    def pack_prefill(self, pieces: list[PrefillPiece], chunk: int, k: int) -> Any:
        """Host-side: what one launch is told of its pieces, each at the next
        free tile: the packed token ids and, a piece, its slot, range, block-
        table row, ring (where the engine hands rings out) and the request's
        sampling parameters. Entries past ``len(pieces)`` have length 0 and
        write nothing."""
        tile = chunk // k
        if sum(-(-p.length // tile) for p in pieces) > k:
            raise ValueError(f"{self.name}: pieces of {[p.length for p in pieces]} tokens "
                             f"do not fit a launch of {k} tiles of {tile}")
        ringed = isinstance(pieces[0].cache, dict)
        rows = [p.cache["pages"] if ringed else p.cache for p in pieces]
        out = {"ids": np.zeros((chunk,), np.int32),
               "pages": np.zeros((k, rows[0].shape[0]), np.int32),
               **{f: np.zeros((k,), np.int32) for f in
                  ("slot", "start", "length", "n", "seed", "max_new")
                  + (("ring",) if ringed else ())},
               "temp": np.zeros((k,), np.float32)}
        at = 0
        for j, p in enumerate(pieces):
            ids, n, seed, max_new, temp, _want = p.item
            out["ids"][at:at + p.length] = ids[p.start:p.start + p.length]
            at += -(-p.length // tile) * tile
            for f, v in (("slot", p.slot), ("start", p.start), ("length", p.length),
                         ("n", n), ("seed", seed), ("max_new", max_new), ("temp", temp),
                         ("pages", rows[j])):
                out[f][j] = v
            if ringed:
                out["ring"][j] = p.cache["ring"]
        return out

    @staticmethod
    def _tiles(launch: Any, chunk: int) -> dict:
        """A launch's tile arithmetic, all traced: tile t belongs to the
        piece whose run of tiles holds it (``has`` False: to none), at
        positions ``qpos`` (K, T) of that piece's prompt; ``valid`` (C,) marks
        the rows that are live tokens, ``last`` (K,) a tile's last live
        position, ``end`` (K,) by tile where its piece ends."""
        slot, start, length = launch["slot"], launch["start"], launch["length"]
        C, K = int(chunk), slot.shape[0]
        T = C // K
        n_tiles = -(-length // T)
        tiles_to = jnp.cumsum(n_tiles)
        tiles = jnp.arange(K)
        piece = jnp.searchsorted(tiles_to, tiles, side="right")
        has = piece < K
        piece = jnp.minimum(piece, K - 1)
        first_tile = tiles_to - n_tiles
        end = jnp.where(has, (start + length)[piece], 0)                  # (K,) by tile
        qpos = (start[piece] + (tiles - first_tile[piece]) * T)[:, None] \
            + jnp.arange(T)[None, :]                                       # (K, T)
        cpos = qpos.reshape(C)
        return {"C": C, "K": K, "T": T, "piece": piece, "has": has, "tiles": tiles,
                "n_tiles": n_tiles, "first_tile": first_tile, "end": end, "qpos": qpos,
                "cpos": cpos, "of_piece": jnp.repeat(piece, T),
                "valid": (qpos < end[:, None]).reshape(C),
                "last": jnp.maximum(jnp.minimum(qpos[:, -1], end - 1), 0),
                "rows": launch["pages"][piece]}

    @staticmethod
    def _page_of(t: dict, P: int, pps: int):
        """Where a launch's rows go in the full pages: (page (C,), offset
        (C,)); padding goes to the sentinel, page 0."""
        cpos = t["cpos"]
        page = jnp.where(t["valid"], jnp.take_along_axis(
            jnp.repeat(t["rows"], t["T"], axis=0), jnp.minimum(cpos // P, pps - 1)[:, None],
            axis=1)[:, 0], 0)
        return page, cpos % P

    key_block = None  # key positions a block of this family's walk; None: KEY_BLOCK

    def _block_pages(self, P: int, pps: int) -> int:
        """Pages a key block of ``key_block`` (else ``KEY_BLOCK``) positions
        holds."""
        return max(1, min((self.key_block or KEY_BLOCK) // P, pps))

    def _key_blocks(self, row, P: int):
        """The walk of one block-table row ``row`` (pps,) of pages of ``P``
        positions in key blocks -> (pages a block, the row padded to whole
        blocks)."""
        kb = self._block_pages(P, row.shape[0])
        return kb, jnp.pad(row, (0, -row.shape[0] % kb))

    def _blocks_needed(self, last, P: int, pps: int):
        """Key blocks a walk up to position ``last`` takes (traced: a
        prompt's first tile reads one block, not the padded context)."""
        kb = self._block_pages(P, pps)
        return jnp.minimum(last // (kb * P) + 1, -(-pps // kb))

    @staticmethod
    def _over_key_blocks(need, lead: tuple, width: int, block, skip=None):
        """Attention over key blocks 0 .. need - 1 (a traced count) under a
        running softmax in float32. ``block(j)`` -> (the block's masked
        scores (*lead, c) float32, a function of its un-normalised
        probabilities (*lead, c) -> what they weigh (*lead, width) float32).
        ``skip(j)`` (a walk under picks): a traced bool, True where no query
        keeps a key of block j, which is then not fetched. -> (*lead, width)."""
        def work(j, carry):
            m, l, acc = carry
            s, weigh = block(j)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m2[..., None])
            scale = jnp.exp(m - m2)
            acc = acc * scale[..., None] + weigh(p)
            return m2, l * scale + jnp.sum(p, axis=-1), acc

        body = work if skip is None else lambda j, carry: jax.lax.cond(
            skip(j), lambda c: c, lambda c: work(j, c), carry)

        m0 = jnp.full(lead, NEG, jnp.float32)
        _m, l, acc = jax.lax.fori_loop(
            0, need, body, (m0, jnp.zeros_like(m0),
                            jnp.zeros(lead + (width,), jnp.float32)))
        return acc / l[..., None]

    def _pages_by_head(self, pool, pg, width: int):
        """The pages ``pg`` (n,) of one pool by head, (KV, n x P, width); of a
        block table ``pg`` (lanes, n), each lane's own: (KV, lanes, n x P,
        width)."""
        blk = self._by_head(jnp.take(pool, pg.reshape(-1), axis=1), width)
        return blk if pg.ndim == 1 else blk.reshape(blk.shape[0], pg.shape[0], -1, width)

    def _key_block(self, pools: tuple, pg, heads: Heads):
        """The pages ``pg`` of a layer's pools -> (keys, values) by head
        (``_pages_by_head``). ``pools``: the K and the V pool as ``_page_shape``
        lays them; a family that keeps a key in parts joins them here."""
        kp, vp = pools
        return self._pages_by_head(kp, pg, heads.dk), self._pages_by_head(vp, pg, heads.dv)

    def _prefill_full(self, q, pools, row, qpos, last, heads: Heads, keep=None):
        """A full layer's attention of one tile, q (T, H, dk) at positions
        ``qpos``, over its prompt's pages (block-table row ``row``) up to the
        tile's last live position ``last``: key blocks of ``KEY_BLOCK``
        positions, as many as that position needs (a traced count: a
        prompt's first tile reads one block, not the padded context), summed
        with a running softmax in float32 -> (T, H, dv). Every row of the
        launch is in the pages before any tile reads them. ``keep`` = (mask
        (KV, T, spans) bool, span): the walk UNDER PICKS, a row of KV group g
        sees key s only where ``mask[g, row, s // span]`` besides (spans over
        the row padded to whole key blocks); a key block none of whose spans
        any row keeps is skipped."""
        T, P = q.shape[0], pools[0].shape[2]
        kb, rowp = self._key_blocks(row, P)
        g = q.shape[1] // heads.kv
        qg = q.reshape(T, heads.kv, g, heads.dk)
        need = self._blocks_needed(last, P, row.shape[0])

        def kept(j):
            """Block j's spans of the mask, (KV, T, spans a block)."""
            mask, span = keep
            n = kb * P // span
            return jax.lax.dynamic_slice(mask, (0, 0, j * n), mask.shape[:2] + (n,))

        def block(j):
            pg = jax.lax.dynamic_slice(rowp, (j * kb,), (kb,))
            kblk, vblk = self._key_block(pools, pg, heads)
            kpos = j * kb * P + jnp.arange(kb * P)
            see = kpos[None, :] <= qpos[:, None]
            s = jnp.einsum("tkgd,kcd->kgtc", qg, kblk,
                           preferred_element_type=jnp.float32) * self._scale()
            see = see[None, None]
            if keep is not None:
                see = see & jnp.repeat(kept(j), keep[1], axis=-1)[:, None]
            return jnp.where(see, s, NEG), lambda p: jnp.einsum(
                "kgtc,kcd->kgtd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)

        skip = None if keep is None else lambda j: ~jnp.any(kept(j))
        o = self._over_key_blocks(need, (heads.kv, g, T), heads.dv, block, skip)
        return o.transpose(2, 0, 1, 3).reshape(T, q.shape[1], heads.dv)

    def _prefill_full_tiles(self, qt, pools, t: dict, heads: Heads | None = None):
        """``_prefill_full`` tile by tile: qt (K, T, H, dk) over each tile's
        own prompt's pages -> (K, T, H, dv)."""
        heads = heads or self._heads()
        return jax.lax.map(
            lambda a: self._prefill_full(a[0], pools, *a[1:], heads),
            (qt, t["rows"], t["qpos"], t["last"]))

    def _arm(self, params, state, new: dict, launch: Any, t: dict, x, extra: dict,
             drawn) -> dict:
        """The end of a prefill launch: each piece that ends its prompt
        samples at its own last row and arms its own lane; a piece of no
        tokens writes nothing (its slot is out of range). ``extra``: further
        lanes the family keeps (a ring's index); ``drawn``: the launch's."""
        slot, start, length, n = (launch[f] for f in ("slot", "start", "length", "n"))
        K, T, C = t["K"], t["T"], t["C"]
        with jax.named_scope("emit"):
            is_final = (length > 0) & (start + length >= n)
        with jax.named_scope("head"):
            h_last = jnp.take(x, jnp.clip(t["first_tile"] * T + n - 1 - start, 0, C - 1), axis=0)
            logits = self._head(params, h_last)
        first, lp_ids, lp_vals = self._sample(logits, launch["seed"], n, launch["temp"], drawn)
        with jax.named_scope("emit"):
            at = jnp.where(length > 0, slot, state["pos"].shape[0])
            lanes = {"bt": launch["pages"], **extra,
                     "tokens": jnp.zeros((K, self.max_new), jnp.int32).at[:, 0].set(first),
                     "pos": jnp.where(is_final, n, 0), "n_new": jnp.where(is_final, 1, 0),
                     "last": first, "armed": is_final,
                     "done": is_final & (launch["max_new"] <= 1), "seed": launch["seed"],
                     "max_new": launch["max_new"], "temp": launch["temp"]}
            for name, val in lanes.items():
                new[name] = state[name].at[at].set(val.astype(state[name].dtype), mode="drop")
            for name, val in (("lp_ids", lp_ids), ("lp", lp_vals)):
                new[name] = state[name].at[at, 0].set(val.astype(state[name].dtype),
                                                      mode="drop")
        return new

    # -- decode -------------------------------------------------------------------
    @staticmethod
    def _pad_queries(q, kv: int, pack: int):
        """q (b, H, hd) -> (b, H, pack * hd): each query head in the part of
        a packed row that its own KV head fills, zeros in the others, so that
        its product with the whole row is its product with its own head."""
        b, H, hd = q.shape
        own = jnp.eye(pack, dtype=q.dtype)[None, None, :, None, :, None]
        return (q.reshape(b, kv // pack, pack, H // kv, 1, hd) * own).reshape(b, H, pack * hd)

    @staticmethod
    def _own_part(o, kv: int, pack: int):
        """The context over packed rows (b, H, pack * hd) -> (b, H, hd): of
        each query head, the part that its own KV head's values fill."""
        b, H, w = o.shape
        o = o.reshape(b, kv // pack, pack, H // kv, pack, w // pack)
        return jnp.einsum("bkjgid,ji->bkjgd", o, jnp.eye(pack, dtype=o.dtype)) \
            .reshape(b, H, w // pack)

    def _decode_full(self, q, kp, vp, bt, pos):
        """One full layer's decode attention through the block table: q
        (b, H, hd), pools (KV / pack, pages, P, pack * hd), bt (b, pps) -> (b,
        H, hd) float32. On the TPU a kernel that reads live pages only;
        elsewhere (tests, toys) a gather of the padded block table
        (``_decode_gather``). The kernel is JAX's own, which takes ONE width
        for queries, keys and values and K and V pools of one shape, and the
        guard below sends every other pool to the gather: so a family whose
        keys are wider than its values, or lie in parts, never comes here on
        the chip: it brings a walk of its own and keeps the gather for the
        CPU (``decoder_sink``)."""
        pack = kp.shape[-1] // self.hd
        on_tpu = jax.default_backend() == "tpu" and self.dtype == jnp.bfloat16 \
            and kp.shape[-1] % 128 == 0 and kp.shape[2] % 8 == 0
        if on_tpu:  # tps-ok[TPS503]: backend and static shapes, at trace time
            # The Pallas paged-attention kernel (my chip runs, PR 28: 0.8 ms a
            # layer for 128 lanes holding 172,000 positions, within 0.002 of
            # plain attention). It does not scale the scores, so the queries are.
            from jax.experimental.pallas.ops.tpu.paged_attention import \
                paged_attention

            # A compute block is read whole whatever the lane's length: over
            # packed rows it is held to PACKED_DECODE_BLOCK positions, so that
            # what a step reads follows the live context and not max_ctx.
            most = 32 if pack == 1 else max(1, self.PACKED_DECODE_BLOCK // kp.shape[2])
            ppcb = max(c for c in range(1, most + 1) if bt.shape[1] % c == 0)
            qs = (q.astype(jnp.float32) * self._scale()).astype(q.dtype)
            if pack > 1:
                qs = self._pad_queries(qs, self.kv, pack)
            o = paged_attention(qs, kp, vp, pos + 1, bt,
                                pages_per_compute_block=ppcb).astype(jnp.float32)
            return self._own_part(o, self.kv, pack) if pack > 1 else o
        return self._decode_gather(q, (kp, vp), bt, pos, self._heads())

    def _decode_gather(self, q, pools, bt, pos, heads: Heads):
        """Decode attention as plain XLA: every lane's PADDED block-table row
        gathered from the pools, (KV, b, pps x P, width) of keys and of values
        written and read a layer a step -> (b, H, dv) float32. Exact, and what
        the CPU runs; no path for the chip at a cell's size."""
        P, pps = pools[0].shape[2], bt.shape[1]
        kc, vc = self._key_block(pools, bt, heads)
        mask = (jnp.arange(pps * P)[None, :] <= pos[:, None])[:, None, :]
        return self._attend(q[:, None], kc.transpose(1, 2, 0, 3),
                            vc.transpose(1, 2, 0, 3), mask)[:, 0]

    def _emit(self, params, state, new: dict, x, live, pos, drawn) -> tuple[Any, dict]:
        """The end of a decode step (``new``: the state with the caches and
        ``acc`` as the step leaves them): every live lane samples its next
        token from its last row ``x`` (b, d), keeps it with its
        log-probabilities, and moves on; the others stay as they were.
        ``drawn``: the launch's."""
        with jax.named_scope("emit"):
            rows = jnp.arange(pos.shape[0])
            nxt = jnp.clip(pos + 1, 0, self.max_ctx - 1)
        with jax.named_scope("head"):
            logits = self._head(params, x)
        tok, lp_ids, lp_vals = self._sample(logits, state["seed"], nxt, state["temp"], drawn)
        with jax.named_scope("emit"):
            n_new = state["n_new"]
            at = jnp.clip(n_new, 0, self.max_new - 1)
            keep = ~live
            tokens = state["tokens"].at[rows, at].set(
                jnp.where(keep, state["tokens"][rows, at], tok))
            new_lp_ids = state["lp_ids"].at[rows, at].set(
                jnp.where(keep[:, None], state["lp_ids"][rows, at], lp_ids))
            new_lp = state["lp"].at[rows, at].set(
                jnp.where(keep[:, None], state["lp"][rows, at], lp_vals))
            n_new2 = jnp.where(live, n_new + 1, n_new)
            done2 = state["done"] | (live & (n_new2 >= state["max_new"]))
            new = dict(new, tokens=tokens, lp_ids=new_lp_ids, lp=new_lp, n_new=n_new2,
                       done=done2, pos=jnp.where(live, nxt, state["pos"]),
                       last=jnp.where(live, tok, state["last"]))
            out = {"done": done2 | ~state["armed"], "n_new": n_new2,
                   "first": tokens[:, 0], "last": new["last"], "acc": new["acc"]}
        return new, out

    def extract(self, params: Any, state: Any, slot: Any) -> Any:
        idx = jax.lax.dynamic_index_in_dim
        return {k: idx(state[k], slot, 0, keepdims=False)
                for k in ("tokens", "n_new", "lp_ids", "lp")}

    # -- host side ----------------------------------------------------------------
    def observe_step(self, step_out: dict) -> None:
        """The device's cumulative counts (prefill chunks and steps since
        the last fetch) into the program's counters (``bind_metrics``: a
        counter a phase and a column of ``COLUMNS``, None where a column
        means nothing in a phase)."""
        if self._counters is None:
            return
        now = np.asarray(step_out["acc"], np.uint32)
        delta = now - self._seen  # wraps as the device's sums do
        # A sum that went "back" by more than half the range did not wrap:
        # the engine rebuilt its state block from zeros.
        delta = np.where(delta > np.uint32(2 ** 31), now, delta)
        self._seen = now
        for row, counters in zip(delta, self._counters):
            for v, c in zip(row, counters):
                if v and c is not None:
                    c.inc(float(v))

    def bind_metrics(self, metrics: Any) -> None:
        self._counters = [[col.counter(self, metrics, ph) for col in self.COLUMNS]
                          for ph in GEN_PHASES]

    def host_decode(self, payload: bytes, content_type: str) -> Any:
        body = json.loads(payload.decode("utf-8"))
        ids = body.get("prompt_ids") if isinstance(body, dict) else None
        if not isinstance(ids, list) or not ids \
                or not all(isinstance(t, int) and not isinstance(t, bool) for t in ids):
            raise ValueError('JSON body must contain "prompt_ids": a non-empty '
                             "list of token ids")
        if len(ids) > self.max_prompt:
            raise ValueError(f"prompt of {len(ids)} tokens; this server takes up "
                             f"to {self.max_prompt}")
        arr = np.asarray(ids, np.int64) - self.v_first
        if arr.min() < 0 or arr.max() >= self.vocab:
            raise ValueError(
                f"prompt_ids must lie in the vocabulary rows held here, "
                f"[{self.v_first}, {self.v_first + self.vocab})")
        max_new = int(body.get("max_new_tokens", self.max_new))
        temp = float(body.get("temperature", 0.0))
        want = int(body.get("logprobs", 0) or 0)
        if not 1 <= max_new <= self.max_new:
            raise ValueError(f"max_new_tokens must be in [1, {self.max_new}], "
                             f"got {max_new}")
        if temp < 0:
            raise ValueError(f"temperature must be >= 0, got {temp}")
        if not 0 <= want <= LOGPROBS:
            raise ValueError(f"logprobs must be in [0, {LOGPROBS}], got {want}")
        padded = np.zeros((self.max_prompt,), np.int32)
        padded[: len(ids)] = arr
        # Every parameter of the answer is part of the item: the result
        # cache digests the whole tuple.
        return (padded, np.int32(len(ids)), np.int32(int(body.get("seed", 0))),
                np.int32(max_new), np.float32(temp), np.int32(want))

    def canary_item(self) -> Any:
        body = {"prompt_ids": [self.v_first], "seed": 1, "max_new_tokens": 2}
        return self.host_decode(json.dumps(body).encode(), "application/json")

    def finalize(self, extracted: Any, item: Any) -> Any:
        n = int(extracted["n_new"])
        toks = [int(t) + self.v_first for t in np.asarray(extracted["tokens"])[:n]]
        out = {"tokens": toks, "n_tokens": n}
        want = int(item[5])
        if want:
            out["logprobs"] = {
                "ids": (np.asarray(extracted["lp_ids"])[:n, :want]
                        + self.v_first).tolist(),
                "values": np.asarray(extracted["lp"])[:n, :want].astype(float).tolist()}
        return out

    def result_units(self, result: Any) -> float:
        return float(result.get("n_tokens", 1))

    def stream_units(self, step_out: dict, slot: int, stream: dict) -> list:
        """One token a step, the lane's ``last``; the first fetch of a lane
        brings the prefill's token with it."""
        n, sent = int(step_out["n_new"][slot]), int(stream.get("sent", 0))
        if n <= sent:
            return []
        stream["sent"] = n
        units = [{"type": "token", "index": n - 1,
                  "token": int(step_out["last"][slot]) + self.v_first}]
        if sent == 0 and n > 1:
            units.insert(0, {"type": "token", "index": 0,
                             "token": int(step_out["first"][slot]) + self.v_first})
        return units

    def stream_finish_reason(self, result: Any) -> str:
        return "length"

    def stream_usage(self, result: Any) -> dict:
        return {"completion_tokens": int(result.get("n_tokens", 0))}
