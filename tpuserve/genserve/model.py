"""GenerativeModel: the contract between generative families and the
iteration-level engine (ISSUE 9; Orca, PAPERS.md P4).

The one-shot ``ServingModel`` contract compiles ``forward`` per batch bucket
and runs each batch to completion — a locked batch. Multi-step generative
work (autoregressive text, diffusion denoising) breaks that shape: requests
need different iteration counts, so a locked batch runs every lane for the
LONGEST member. This contract decomposes generation into the three device
programs the engine (tpuserve.genserve.engine) schedules at iteration
granularity, all compiled ONCE over a fixed slot-capacity state block so
slot churn never recompiles:

- ``init_state(params, item)``  — one request's initial per-slot state
  (prompt prefill / text encode + latent init). The engine composes it with
  a traced dynamic-update into the slot dim, so one compiled "insert"
  program serves every slot index.
- ``step(params, state)``       — ONE model iteration over the whole slot
  block, returning the new state plus a small host-fetchable out pytree
  that must carry ``"done"`` per slot. Inactive/free slots hold benign
  zeros and are stepped along harmlessly (their lanes are ignored).
- ``extract(params, state, slot)`` — the finished slot's device outputs
  (token buffer, VAE-decoded image), fetched ONLY when that slot retires,
  so per-step readback stays small even when results are megabytes.

Host-side, ``is_finished`` reads the step out-block and ``finalize`` turns
one extracted result into the JSON-able / bytes response. Decoded request
items must be pytrees of fixed-shape np arrays carrying EVERY sampling
parameter (seed, temperature, max_new_tokens) — that is what makes
generative results content-addressable: the result cache digests the item,
so two prompts differing only in seed can never alias
(tests/test_genserve.py; ``ModelConfig.cacheable`` opts a family out).
"""

from __future__ import annotations

import abc
import enum
import functools
import json
from dataclasses import dataclass
from typing import Any, Callable

import jax
import numpy as np

from tpuserve.models.base import ServingModel


@dataclass
class PrefillPiece:
    """Tokens [start, start + length) of the prompt held by ``slot``, waiting
    for a prefill launch. ``cache`` is what the program is told of the slot's
    caches: its block-table row, or ``{"pages": row, "ring": index}`` for a
    family with window rings."""
    slot: int
    item: Any
    start: int
    length: int
    cache: Any


class LeafKind(enum.Enum):
    """What a leaf of the paged state block is, by how it is addressed."""
    POOL = "pool"    # pages, reached through a slot's block table; page 0 the sentinel
    RINGS = "rings"  # a ring a slot and the sentinel's (ring 0): the last ``ring_tokens`` positions
    SLOT = "slot"    # one block a slot, the same size at any context (a recurrent layer's state)
    LANE = "lane"    # everything else: the block table, a lane's counters, its tokens


@dataclass(frozen=True)
class Leaf:
    """A cache leaf as a family's signature states it: its kind and its shapes (a list of one
    a layer that keeps it). The kind rides on the plan only: ``CachePlan.state`` is the shapes."""
    kind: LeafKind
    shapes: Any


pool, rings, slot_block = (functools.partial(Leaf, kind)
                           for kind in (LeafKind.POOL, LeafKind.RINGS, LeafKind.SLOT))


@dataclass(frozen=True)
class CachePlan:
    """What a slot keeps, said once (ISSUE 70): ``GenerativeModel.kv_plan``'s answer, which
    the engine and /stats ``kv`` read. Page 0 is the write-sink sentinel — free/done lanes
    scribble there, live lanes never attend through it."""
    state: Any            # the state block's signature: {leaf: shapes}
    kinds: dict           # {cache leaf: LeafKind}; a leaf of ``state`` not named is a lane
    slots: int
    pages: int            # the ledger's pages, the sentinel among them
    page_tokens: int      # ROWS a page
    pages_per_slot: int   # the block table's width: a slot's longest context in pages
    page_positions: int   # positions a page stands for: its rows, unless a row sums several up
    ring_tokens: int = 0  # positions a slot's ring holds; 0: the family has no rings
    ring_pages: int = 0   # pages of the POOLS a ring takes, where rings lie there (before the
    #                       ledger's pages); 0: rings are leaves of their own

    @classmethod
    def build(cls, signature: Callable[[int, int], dict], *, slots: int, page_tokens: int,
              pages: int, max_tokens: int, page_positions: int = 0, ring_tokens: int = 0,
              ring_pages: int = 0) -> "CachePlan":
        """The plan of ``signature(pages, pages_per_slot)`` -> {leaf: a ``Leaf``,
        or a lane's bare shape} for slots of at most ``max_tokens`` positions.
        ``pages = 0``: the worst case, every slot full, and the sentinel."""
        positions = int(page_positions or page_tokens)
        pps = -(-int(max_tokens) // positions)
        pages = int(pages) or slots * pps + 1
        sig = signature(pages, pps)
        return cls({k: v.shapes if isinstance(v, Leaf) else v for k, v in sig.items()},
                   {k: v.kind for k, v in sig.items() if isinstance(v, Leaf)},
                   slots, pages, int(page_tokens), pps, positions, ring_tokens, ring_pages)

    def kind(self, leaf: str) -> LeafKind:
        return self.kinds.get(leaf, LeafKind.LANE)

    def leaves(self, kind: LeafKind) -> tuple:
        return tuple(leaf for leaf in self.state if self.kind(leaf) is kind)

    def bytes_of(self, kind: LeafKind) -> int:
        """Device bytes of the leaves of ``kind``, all layers."""
        return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize for x in
                   jax.tree_util.tree_leaves([self.state[k] for k in self.leaves(kind)]))

    def pages_for(self, tokens: int) -> int:
        """Pages that cover ``tokens`` positions of one slot's context."""
        return -(-int(tokens) // self.page_positions)

    @functools.cached_property
    def pool_bytes(self) -> int:
        return self.bytes_of(LeafKind.POOL)

    @property
    def page_bytes(self) -> int:
        """ONE page of the pools, all layers."""
        return self.pool_bytes // (self.pages + (self.slots + 1) * self.ring_pages)

    @property
    def row_bytes(self) -> int:
        """ONE position of context in the pools, all layers."""
        return self.page_bytes // self.page_positions

    @property
    def ring_bytes(self) -> int:
        """The rings, the sentinel's too: leaves of their own, or their pages of the pools."""
        return self.bytes_of(LeafKind.RINGS) + (self.slots + 1) * self.ring_pages * self.page_bytes

    @functools.cached_property
    def slot_bytes(self) -> int:
        """The blocks a slot, all slots."""
        return self.bytes_of(LeafKind.SLOT)


class GenerativeModel(ServingModel):
    """A ServingModel that additionally serves through the iteration-level
    engine. Families keep their one-shot ``forward`` (the locked-batch
    path: still used by the static batcher when [genserve] is off, and as
    the bench's baseline), and add the decomposed programs below."""

    # Marker the server keys engine selection on (isinstance would also
    # work; the attribute makes duck-typed test doubles cheap).
    generative = True

    # -- device contract (jittable; compiled once via runtime.register_program)
    @abc.abstractmethod
    def state_signature(self, slots: int) -> Any:
        """Pytree of jax.ShapeDtypeStruct for the whole generative state
        block: every leaf has leading dim ``slots``. Allocated once at
        engine start (zeros) and threaded through step — KV caches, latent
        slabs, token buffers, per-slot counters and done flags all live
        here, so steady-state serving allocates nothing."""

    @abc.abstractmethod
    def gen_item_signature(self) -> Any:
        """Pytree of jax.ShapeDtypeStruct for ONE decoded request item as it
        crosses to the device (no slot dim). Fixed shapes are the contract:
        prompts pad to the prompt bucket, and every sampling parameter rides
        along as a scalar array."""

    @abc.abstractmethod
    def init_state(self, params: Any, item: Any) -> Any:
        """Jittable: one request's initial per-slot state — each leaf shaped
        like the state_signature leaf WITHOUT the slot dim. This is the
        expensive once-per-request work (prompt prefill through the stack,
        text encode, latent init from the seed)."""

    @abc.abstractmethod
    def step(self, params: Any, state: Any) -> tuple[Any, dict]:
        """Jittable: one iteration over all slots -> (new_state, out).
        ``out`` is the small per-step host fetch and must contain
        ``"done"``: (slots,) bool — True once a slot's sequence finished.
        Free slots hold zeros; the step must be NaN-safe on them.

        A FINISHED LANE IS FROZEN (ISSUE 41): a lane whose out-block said
        ``done`` is not changed by any later ``step`` — every leaf of the
        state block keeps that lane's rows bit for bit, and the lane writes
        no page or ring of its own (a paged family sends its write to the
        sentinel, page 0) — until an insert or a prefill launch gives the
        lane to another request. The engine keeps one step queued ahead of
        the one it reads, so it learns of ``done`` one step late: the lane
        rides that step, ``extract`` reads its rows AFTER it, and the slot's
        pages may already be another request's. ``step`` takes the donated
        state block and nothing from the host, so nothing else is asked.
        ``tests/test_genserve.py`` holds every registered family to this."""

    @abc.abstractmethod
    def extract(self, params: Any, state: Any, slot: Any) -> Any:
        """Jittable with a TRACED slot index: the finished slot's final
        device outputs (one compile covers every slot). Runs once per
        retirement — put the heavy tail work here (e.g. the VAE decode)."""

    def state_partition_specs(self, struct: Any, mesh: Any) -> Any:
        """PartitionSpec tree (or None = replicate everything) for the
        engine's device state block on a SHARDED mesh (ISSUE 20). Families
        that can split decode across chips override — textgen puts KV
        heads on "model" beside its QKV column shards — and the engine
        threads the result through ``register_program``'s arg/out specs so
        the state block never materializes unsharded. Returning None keeps
        the replicated layout (correct for every family, the default)."""
        return None

    # -- host contract --------------------------------------------------------
    def gen_max_steps(self) -> int:
        """Upper bound on iterations any single request can take (the
        engine's runaway guard and the staged canary's loop bound)."""
        raise NotImplementedError

    def is_finished(self, step_out: dict, slot: int) -> bool:
        """Read one slot's finished flag from the fetched step out-block."""
        return bool(step_out["done"][slot])

    @abc.abstractmethod
    def finalize(self, extracted: Any, item: Any) -> Any:
        """Fetched extract() outputs (+ the original decoded item) -> the
        JSON-able / bytes response. Host-side, runs on the postproc stage."""

    def result_units(self, result: Any) -> float:
        """Headline output units one finished result carries — tokens for
        text, images for diffusion (default 1). Feeds the engine's
        ``gen_units_total`` counter, which a measurement divides by wall
        time for tokens/s or images per minute (counting requests would
        hide mixed output lengths)."""
        return 1.0

    # -- paged KV contract (ISSUE 18; one description since ISSUE 70) -----------
    # A family with paged programs swaps the dense per-slot state slab for a
    # global pool of fixed-size KV pages plus a per-slot block table, and
    # init_state for an incremental prefill_chunk program. WHAT A SLOT KEEPS it
    # says ONCE, in the ``CachePlan`` that ``kv_plan`` returns; the engine builds
    # its ledger (genserve.pages.PageLedger) from it and reads /stats ``kv`` off
    # it. EVERY page index the compiled programs consume is traced, so one
    # compiled step/prefill serves every page assignment — the same zero-recompile
    # obligation slot indices already carry (runtime.register_program).

    def kv_plan(self, slots: int, page_tokens: int,
                pages: int = 0) -> "CachePlan | None":
        """Host-side: the paged state block for ``slots`` slots and ``pages`` pages
        of ``page_tokens`` rows, the sentinel among them (0: the family's worst
        case, every slot at its longest context). None (the default): no paged
        programs (sd15); the dense slab stays even when [genserve] kv_paging is on."""
        return None

    def share_stats(self) -> "dict | None":
        """Host-side: what of each layer this chip holds, where the model is
        one chip's share of a deployment (/stats ``pipeline.models.<m>.share``);
        None for a model that is whole."""
        return None

    def observe_step(self, step_out: dict) -> None:
        """Host-side, after every fetched step: a family that sums counts on
        the device (experts hit, context read) moves them into its own
        counters here (``bind_metrics`` bound them). Nothing by default."""

    def context_tokens(self, item: Any) -> int:
        """Host-side: positions of context this request may reach, its prompt
        PLUS its full decode budget. The engine reserves the pages for them
        (``CachePlan.pages_for``) at fold-in, so an admitted sequence can never
        hit mid-decode page exhaustion (budgeted admission, Clockwork P3)."""
        raise NotImplementedError

    def prompt_tokens(self, item: Any) -> int:
        """Host-side: real (unpadded) prompt length of one decoded item —
        the engine's chunked-prefill cursor bound."""
        raise NotImplementedError

    def kv_prefill_chunk(self, requested: int) -> int:
        """Host-side: the static chunk width the compiled prefill program
        is built with, given the [genserve] prefill_chunk knob (0 = whole
        prompt in one chunk)."""
        raise NotImplementedError

    # Packed prefill (ISSUE 31). One launch of the prefill program has the
    # static width ``chunk`` and carries the waiting PIECES of up to K
    # prompts: a piece is tokens [start, start + length) of one slot's
    # prompt, and takes whole tiles of ``chunk // K`` rows. The engine
    # builds every launch from pieces, in order of admission, and hands a
    # launch that is full to the device at once; one that is not full may
    # wait a bounded number of iterations for more (engine.PREFILL_HOLD). A
    # family declares K; K = 1 (the default) is one prompt a launch, for
    # which every launch is full and nothing waits.

    def kv_prefill_pieces(self, chunk: int, page_tokens: int) -> int:
        """Host-side: K, how many prompts' pieces one launch of the prefill
        program takes; it divides ``chunk``."""
        return 1

    def pack_prefill(self, pieces: "list[PrefillPiece]", chunk: int,
                     k: int) -> Any:
        """Host-side: the ``launch`` argument of ``prefill_chunk`` for these
        pieces (1..K of them, of distinct slots, together at most K tiles):
        a pytree of fixed-shape np arrays whatever the pieces are. The
        default is the one-prompt launch: (slot, item, start, cache row)."""
        (p,) = pieces
        return (np.int32(p.slot), p.item, np.int32(p.start), p.cache)

    def prefill_chunk(self, params: Any, state: Any, launch: Any, *,
                      chunk: int) -> Any:
        """Jittable with every index of ``launch`` TRACED and a STATIC
        width: fold each piece's tokens into its slot's pages (and ring)
        and return the new state. A piece that ends its prompt (start +
        length >= prompt length) also samples that request's first token
        and arms its lane for decode; an earlier piece leaves the lane
        frozen so interleaved decode steps skip it."""
        raise NotImplementedError

    # -- streaming contract (ISSUE 17) ----------------------------------------
    # The engine calls stream_units after EVERY fetched iteration for each
    # slot with an attached stream, and stream_final_units once at retire;
    # the HTTP layer encodes each unit with encode_stream_unit under
    # stream_content_type. Units are plain dicts with a "type" key; a unit
    # carrying "droppable": True may be discarded under the model's
    # stream_policy = "drop" when the client reads slowly (progress and
    # previews are droppable, tokens and terminals never are).

    def stream_units(self, step_out: dict, slot: int, stream: dict) -> list:
        """Newly produced stream units for one slot after one iteration.
        ``stream`` is a per-request mutable dict the model keeps its
        incremental emission state in (e.g. tokens already sent). The
        default streams nothing per iteration (the terminal burst from
        stream_final_units still makes the stream well-formed)."""
        return []

    def stream_wants_preview(self, step_out: dict, slot: int,
                             stream: dict) -> bool:
        """Side-effect-free: should the engine run the (already compiled)
        extract program for this slot NOW to build a mid-flight preview
        unit? Families that answer True pay one extract per preview but
        never a new compile — the program is the same one retirement uses
        (the zero-recompile obligation the stream drill gates on)."""
        return False

    def stream_preview_unit(self, extracted: Any, stream: dict) -> dict:
        """Fetched extract() outputs -> one droppable preview unit (and the
        model's chance to note in ``stream`` when it last previewed)."""
        return {"type": "preview", "droppable": True}

    def stream_final_units(self, extracted: Any, result: Any) -> list:
        """Terminal burst for one retired slot, ending in the ``done``
        event every complete stream MUST carry (clients distinguish
        complete from torn by the terminal alone)."""
        return [{"type": "done",
                 "finish_reason": self.stream_finish_reason(result),
                 "usage": self.stream_usage(result)}]

    def stream_finish_reason(self, result: Any) -> str:
        """Why generation ended: "stop" (natural EOS) or "length" (cap)."""
        return "stop"

    def stream_usage(self, result: Any) -> dict:
        """The usage block on the terminal ``done`` event."""
        return {"units": self.result_units(result)}

    def stream_content_type(self) -> str:
        """Wire format for streamed responses: SSE by default; binary
        families (sd15 previews) answer ``frame.CONTENT_TYPE`` instead."""
        return "text/event-stream"

    def encode_stream_unit(self, unit: dict) -> bytes:
        """One unit -> wire bytes under stream_content_type. The SSE
        default renders ``event: <type>`` + a JSON data line; every key
        except "type" (and the droppable marker) rides in the data."""
        data = {k: v for k, v in unit.items()
                if k not in ("type", "droppable")}
        return (f"event: {unit['type']}\n"
                f"data: {json.dumps(data)}\n\n").encode("utf-8")

    def stream_heartbeat(self) -> bytes:
        """Idle-gap keepalive bytes (an SSE comment by default); empty
        bytes disable heartbeats for the family."""
        return b": hb\n\n"
