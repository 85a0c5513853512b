"""Operations and bytes of the `hybrid_ffn_moe` family's two device programs,
of their state-space parts, of decode attention and of a step's routed block,
from the configuration's sizes and what a window's counters say the programs
worked on. Every count is the LEAST the work needs (flops/hybrid.py's header
says what that means), so a share of the roofline computed from it cannot pass
100%.

The Mamba-2 mixers, the attention mixers, the head and the embedding rows are
counted by flops/hybrid.py's own `launch`, `update` and `scan` (handed the sizes
with no expert layer of ITS kind), decode attention by flops/hybrid_ffn.py's
`attend_decode`. What this family adds is the routed block of EVERY layer:

- every live token through the router (2 x d x `num_experts`) and the shared
  expert (2 x 3 x d x `shared_width`), both read once a launch;
- a pick on a HELD expert through that expert's three products (2 x 3 x d x
  `expert_width`); of the held experts only those that were HIT are read, once;
  a held pick's row in (the served type) and out (float32, as the products hand
  it on). Picks on the other chip's experts, the sort, the gathers and the way
  back count for nothing.
"""

from __future__ import annotations

from benchmark import spec

_hy = spec.load_module("flops", "hybrid")
state_bytes, update, scan, kv_read_bytes = _hy.state_bytes, _hy.update, _hy.scan, _hy.kv_read_bytes
attend_decode = spec.load_module("flops", "hybrid_ffn").attend_decode


def _matrices(sz: dict) -> dict:
    """Parameters by role, one layer's routed block."""
    d = sz["d_model"]
    return {"router": d * sz["num_experts"], "shared": 3 * d * sz["shared_width"],
            "expert": 3 * d * sz["expert_width"]}


def _picks_bytes(sz: dict, held_picks: float) -> float:
    """The held picks' rows into the products and out of them."""
    return held_picks * sz["d_model"] * (sz["weight_bytes"] + 4.0)


def launch(sz: dict, tokens: float, sampled: float, context_sum: float, held_picks: float,
           experts_hit: float, states: float) -> tuple[float, float]:
    """One launch (a prefill launch or a decode step): flops/hybrid.py's
    `launch` of the mixers, the head and the embedding, and the routed blocks:
    `held_picks` of the tokens' picks landed on held experts and hit
    `experts_hit` of them (both summed over the layers)."""
    ops, nbytes = _hy.launch(dict(sz, n_expert=0), tokens, sampled, context_sum, 0.0, 0.0, states)
    m, wb = _matrices(sz), sz["weight_bytes"]
    always = sz["n_expert"] * (m["router"] + m["shared"])
    return ops + 2.0 * tokens * always + 2.0 * held_picks * m["expert"], \
        nbytes + wb * (always + experts_hit * m["expert"]) + _picks_bytes(sz, held_picks)


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float,
                experts_hit: float) -> tuple[float, float]:
    ops, nbytes = launch(sz, lanes, lanes, context_sum, held_picks, experts_hit, lanes)
    return ops, nbytes + kv_read_bytes(sz, context_sum)


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float,
                  experts_hit: float) -> tuple[float, float]:
    """A launch reads the K and V of earlier launches at least once; its own it
    has at hand. It carries at least one piece: one slot's state."""
    ops, nbytes = launch(sz, tokens, 1.0, context_sum, held_picks, experts_hit,
                         1.0 if tokens else 0.0)
    earlier = max(0.0, context_sum / tokens - (tokens + 1) / 2.0) if tokens else 0.0
    return ops, nbytes + kv_read_bytes(sz, earlier)


def experts_step(sz: dict, lanes: float, held_picks: float,
                 experts_hit: float) -> tuple[float, float]:
    """The routed experts' own work in one decode step, every layer (the
    `moe_experts` scope: the grouped products and the body between them; the
    router, the dispatch and the shared expert are outside): the HIT experts'
    three matrices once, a held pick's row in and out, its three products.
    `lanes` is not read: the picks are."""
    m = _matrices(sz)
    return 2.0 * held_picks * m["expert"], \
        sz["weight_bytes"] * experts_hit * m["expert"] + _picks_bytes(sz, held_picks)


def routed_layer(sz: dict, tokens: float, held_picks: float,
                 experts_hit: float) -> tuple[float, float]:
    """The `moe_layer` scopes of one launch, every layer: the router for every
    live token, `experts_step`'s work, the router read once, the tokens' rows
    read. The shared expert runs under `moe_shared`, outside this scope, so it
    is counted in `launch` and not here: a share that counted work its time
    leaves out would read too high."""
    m, n = _matrices(sz), sz["n_expert"]
    ops, nbytes = experts_step(sz, tokens, held_picks, experts_hit)
    return ops + 2.0 * tokens * n * m["router"], \
        nbytes + sz["weight_bytes"] * n * (m["router"] + tokens * sz["d_model"])


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    picks = batch * sz["top_k"] * sz["experts_held"] / max(1, sz["num_experts"])
    hit = sz["experts_held"] * (1.0 - (1.0 - 1.0 / max(1, sz["experts_held"])) ** picks)
    return decode_step(sz, batch, float(batch * seq), picks * sz["n_expert"],
                       hit * sz["n_expert"])
