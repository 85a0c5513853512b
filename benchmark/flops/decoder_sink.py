"""Operations and bytes of the `decoder_sink` family's two device programs and
of its attention's parts, from the configuration's sizes and what a window's
counters say the programs worked on: flops/decoder.py's functions with the
widths and the KV heads BY LAYER KIND (keys `dk` wide, values `dv`, `kv_heads`
of a global and of a window layer: `sizes["by_kind"]`), and no shared expert.
Every count is the LEAST the work needs, so a share of the roofline computed
from it cannot pass 100%:

- operations are the matrix multiplications alone (2 per multiply-add) over
  LIVE tokens: projections, scores over `dk` and context over `dv` for the
  positions a token may attend to (its own context in a global layer, at most
  the window in a window layer), the dense SwiGLU, the router, the held
  experts' products for the picks that landed on them, the head for the rows
  that are sampled. Norms, rotary, softmax, the sink, top-k, sorting and
  gathers are left out.
- bytes are the least traffic to device memory: every matrix that is used read
  ONCE a launch (of the routed experts only those that were hit), the K and V
  rows a live token attends over read once a launch at `kv_heads x (dk + dv)`
  values a position (a global layer: the lane's own context; a window layer:
  at most the window), the new rows written, the embedding rows gathered.
  Activations, padding and everything re-read count for nothing.

`ops_and_bytes(sizes, batch, seq)` is what the harness's generic readers call
(a decode step of `batch` lanes at context `seq`, every held expert hit).
"""

from __future__ import annotations

GLOBAL, WINDOW = "global", "window"


def _attention(sz: dict, kind: str) -> float:
    """Parameters of one attention of `kind`: W_q, W_k, W_v, W_o."""
    d, g = sz["d_model"], sz["by_kind"][kind]
    return d * g["heads"] * g["dk"] + d * g["kv_heads"] * (g["dk"] + g["dv"]) \
        + g["heads"] * g["dv"] * d


def _row_bytes(sz: dict, kind: str) -> float:
    """K and V of one position in one layer of `kind`."""
    g = sz["by_kind"][kind]
    return float(g["kv_heads"] * (g["dk"] + g["dv"]) * sz["weight_bytes"])


def _seen(sz: dict, kind: str, tokens: float, context_sum: float) -> float:
    """Positions the tokens of a launch attend over in a layer of `kind`: their
    context, at most the window in a window layer."""
    if kind == GLOBAL:
        return context_sum
    return tokens * min(context_sum / tokens if tokens else 0.0, sz["win_tokens"])


def _pair_ops(sz: dict, kind: str) -> float:
    """Operations a (query position, key position) pair: scores and context."""
    g = sz["by_kind"][kind]
    return 2.0 * g["heads"] * (g["dk"] + g["dv"])


def _always(sz: dict) -> float:
    """Parameters every token passes through: attention, dense SwiGLU, routers."""
    d = sz["d_model"]
    return sum(_attention(sz, kind) + (d * sz["num_experts"] if sparse
                                       else 3.0 * d * sz["dense_width"])
               for kind, sparse in zip(sz["kinds"], sz["sparse"]))


def launch(sz: dict, tokens: float, sampled: float, context_sum: float, held_picks: float,
           experts_hit: float) -> tuple[float, float]:
    """One launch (a prefill chunk or a decode step) that took `tokens` live
    tokens through every layer, sampled `sampled` of them through the head,
    whose tokens attend from positions that sum to `context_sum` (a token at
    position p counts p + 1), `held_picks` of whose expert picks landed on
    held experts (summed over the sparse layers), hitting `experts_hit` held
    experts (summed over the sparse layers) -> (operations, bytes)."""
    wb, d = sz["weight_bytes"], sz["d_model"]
    always, expert = _always(sz), 3.0 * d * sz["expert_width"]
    ops = 2.0 * tokens * always + 2.0 * held_picks * expert + 2.0 * sampled * d * sz["vocab"]
    nbytes = wb * (always + experts_hit * expert + d * sz["vocab"]) + wb * tokens * d
    for kind in sz["kinds"]:
        ops += _pair_ops(sz, kind) * _seen(sz, kind, tokens, context_sum)
        nbytes += _row_bytes(sz, kind) * tokens          # the new K and V rows, written
    return ops, nbytes


def kv_read_bytes(sz: dict, lanes: float, context_sum: float) -> float:
    """K and V a decode step reads at least: each lane its own context in a
    global layer and at most the window in a window layer."""
    return sum(_row_bytes(sz, kind) * _seen(sz, kind, lanes, context_sum) for kind in sz["kinds"])


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float,
                experts_hit: float) -> tuple[float, float]:
    ops, nbytes = launch(sz, lanes, lanes, context_sum, held_picks, experts_hit)
    return ops, nbytes + kv_read_bytes(sz, lanes, context_sum)


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float,
                  experts_hit: float) -> tuple[float, float]:
    """A chunk reads the K and V of earlier chunks at least once; its own it
    has at hand. The first token is sampled by at most one chunk a prompt."""
    ops, nbytes = launch(sz, tokens, 1.0, context_sum, held_picks, experts_hit)
    earlier = max(0.0, context_sum / tokens - (tokens + 1) / 2.0) if tokens else 0.0
    return ops, nbytes + kv_read_bytes(sz, 1.0, earlier)


def _mixers(sz: dict, kinds: list, lanes: float, context_sum: float,
            matrices: bool) -> tuple[float, float]:
    ops = nbytes = 0.0
    for kind in kinds:
        seen, row = _seen(sz, kind, lanes, context_sum), _row_bytes(sz, kind)
        ops += _pair_ops(sz, kind) * seen
        nbytes += row * (seen + lanes)                    # rows read once, the new rows written
        if matrices:
            ops += 2.0 * lanes * _attention(sz, kind)
            nbytes += sz["weight_bytes"] * _attention(sz, kind)
    return ops, nbytes


def attend_decode(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """Every attention mixer of one decode step, from the three projections to
    `W_o`'s product: the four matrices once a layer; scores and context over
    each live lane's own positions (at most the window in a window layer); its
    K and V rows read once, the new rows written."""
    return _mixers(sz, sz["kinds"], lanes, context_sum, True)


def full_walk(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """The global layers' walk of one decode step alone (the rows' write into
    the pages and the kernel): each live lane's own K and V rows read ONCE a
    global layer at `dk + dv` values a KV head, the new rows written; the two
    products over the same rows."""
    return _mixers(sz, [k for k in sz["kinds"] if k == GLOBAL], lanes, context_sum, False)


def ring_read(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """The window layers' ring write, read and softmax of one decode step:
    `min(context, window)` ring rows read once a window layer, the new rows
    written; the two products over the same rows."""
    return _mixers(sz, [k for k in sz["kinds"] if k == WINDOW], lanes, context_sum, False)


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    sparse = sum(sz["sparse"])
    share = sz["experts_held"] / max(1, sz["num_experts"])
    return decode_step(sz, batch, float(batch * seq), batch * sz["top_k"] * sparse * share,
                       min(sz["experts_held"], batch * sz["top_k"] * share) * sparse)
