"""The plain reference for the `mla_sel` family: a decoder-only language model
with latent attention OVER THE POSITIONS A LEARNED INDEXER PICKS, group-limited
routed experts and a share of each layer, written down from its published
`config.json` in straightforward float32 (`jax.numpy` under
`jax.default_matmul_precision("highest")`; the routed experts' products in
`numpy` float32), attention in its EXPANDED form only, one causal pass, the
picks by `jax.lax.top_k`, with no cache, no chunking, no absorbed product, no
threshold and no kernel. It imports nothing of the program. The weights'
recipe, the control's rounding, the dense SwiGLU, the routed experts' loop and
the check's statistic are `reference/mla.py`'s own functions and yarn's
frequencies and its rotary turn `reference/mla_hc.py`'s, imported and not edited.

THE LAYER (eps = `rms_norm_eps`, no biases but the index key's, an untied head;
norm gains are ones in the recipe and are left out): `x <- x +
attention(RMSNorm(x))`, `x <- x + ffn(RMSNorm(x))`; logits = `RMSNorm(x) W_head`.

- attention, `u` the normed stream at position t: `c_q = RMSNorm(u W_qa)`; `q =
  c_q W_qb`, a head `[q_nope | q_rope]`; `[c_kv | k_r] = u W_kva`; `c_kv <-
  RMSNorm(c_kv)`; `q_rope <- RoPE(q_rope, t)`, `k_r <- RoPE(k_r, t)`, ONE rotary
  key for every head; `[k_nope_h | v_h] = c_kv W_kvb`. Rotary frequencies by yarn
  (`reference/mla_hc.py` `yarn`), cos and sin times `m(mscale) /
  m(mscale_all_dim)` (1 as published) and every score times `m(mscale_all_dim)^2
  / sqrt(qk_nope + qk_rope)`, `m(a) = 0.1 a ln(factor) + 1`; pairs (i, i + dim /
  2) (the config has no `rope_interleave`).
- THE INDEXER: `qI(t) = c_q WI_qb`, `index_n_heads` heads of `index_head_dim`,
  the first `qk_rope_head_dim` columns of each head turned at t by the same
  frequencies; ONE index key `kI(t) = LayerNorm(u WI_k) + beta` (mean and
  variance, eps 1e-6; the gain is ones), its first rotary columns turned at t;
  `w(t) = (u WI_w) x index_n_heads^-1/2 x index_head_dim^-1/2`; `I(t, s) = sum_j
  w_j(t) ReLU(qI_j(t) . kI(s))`, `s <= t`; `S(t)` = the `min(index_topk, t + 1)`
  positions of largest `I(t, s)` (`jax.lax.top_k`).
- `score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_r(s)) x scale`, a
  softmax over `s in S(t)` ONLY, `o_h = sum_s p v_h(s)`, out = `concat_h(o_h) W_o`.
- feed-forward: layers below `first_k_dense_replace`: `(silu(u G) * (u U)) D` of
  `intermediate_size`; the others: `s = sigmoid(u W_r)` over all `n_routed_experts`
  in float32; `s' = s + b`; `n_group` groups of neighbouring outputs, a group's
  score the sum of its two largest `s'`; the `topk_group` best groups stay; the
  `num_experts_per_tok` largest `s'` among their outputs are the picks; weights
  `s_e / (their sum)` times `routed_scaling_factor`; expert e a SwiGLU of
  `moe_intermediate_size`; plus the shared expert on the same u.

THE SHARE (`share` in the architecture): this chip holds experts `experts_held =
[first, count]` and vocabulary rows `vocab_rows = [first, count]`. The routed sum
is over the held experts a token picked; the shared expert, attention and the
indexer are whole; that partial result is what goes on. Without `share` the
layer is whole.

ASSUMED (the configuration file repeats this under `assumed`): the published
indexer turns `qI` and `kI` by a Hadamard matrix (orthogonal: every `qI . kI` is
what it was) and keeps them in FP8 with block scales (below the served
bfloat16): neither is here; the indexer's rotary pairing is the attention's; the
selection bias `b` is drawn small (a bell within +-0.06) and moves picks, never
weights; the multi-token-prediction module is not part of the main stack's
logits; no end-of-sequence id.

WEIGHTS BY RECIPE (`counter-bell-v1`, `reference/mla.py` `draw`): the program's
names and shapes: `layer<i>/...` as `reference/mla.py` names an attention's
eight, `layer<i>/wi_qb | wi_k | wi_w`, `layer<i>/index_beta` (a float32 vector
inside +-3 x `index_beta`), `layer<i>/router`, `layer<i>/e_bias`, `layer<i>/e_gate |
e_up | e_down/<g>`, an expert a tensor, g its PUBLISHED number.

`forward(..., selected=)`: a test hands each layer's picks in (a boolean (T, P +
T) a sequence) and the attention runs UNDER THEM: the program's own picks, so
that its attention is held to the reference apart from its selection; `picked=`
collects the reference's own.

THE CHECK (`compare`): `reference/mla.py`'s statistic (`logprob_q25` beside
`logprob_rms`), the pass made in TWO calls of `forward` as `reference/mla_sc.py`
makes it: the prompts while the server starts (`in_background`), the served
tokens after, continued from the rows the first cached (a token's `c_kv`, `k_r`
and index key: what a server caches). `check.reference_inputs =
"3-bit-mantissa"` (a control, never a cell) rounds the inputs of the reference's
matrix products (every kernel but the router's and the head weights', the normed
stream, the query's latent, what a server would CACHE: `c_kv`, `k_r` and the
index key, the index queries, the heads' outputs, the hidden rows of every
SwiGLU) to 3 explicit mantissa bits.
"""

from __future__ import annotations

import atexit
import functools
import math
import threading
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import spec

base = spec.load_module("reference", "mla")
hc = spec.load_module("reference", "mla_hc")
LOGPROBS = base.LOGPROBS
DEFAULT_SCALES = {**base.DEFAULT_SCALES, "q_b": 1.5, "k_rope": 1.5, "k_b": 1.5,
                  "index_q": 1.0, "index_k": 1.0, "index_w": 1.0, "index_beta": 0.1}
INDEX_EPS = 1e-6


class Model(base.Model):
    """`mla`'s numbers and tensors, and: yarn, the indexer, the groups, the
    share (an expert a tensor of its own)."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        super().__init__(arch, seed, served_dtype)
        a = arch
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self.inv_freq, self.on_cos_sin, on_score = hc.yarn(self.dr, self.theta,
                                                           a.get("rope_scaling"))
        self.score_scale = on_score / math.sqrt(self.dn + self.dr)
        self.groups = (int(a.get("n_group", 1)), int(a.get("topk_group", 1)))
        self.hi, self.di = int(a["index_n_heads"]), int(a["index_head_dim"])
        self.index_topk = int(a["index_topk"])
        self.vocab_full = self.vocab
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.e])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.vocab_full])

    def embed(self) -> np.ndarray:
        return np.asarray(self.tensor("embed", (self.vocab, self.d), "embed", 1,
                                      (self.vocab_full, self.d), (self.v_first, 0)))

    def head(self):
        return self.tensor("head", (self.d, self.vocab), "head", self.d,
                           (self.d, self.vocab_full), (0, self.v_first))

    def attention(self, i: int) -> dict:
        L, d, t = f"layer{i}", self.d, self.tensor
        b3 = 3.0 * self.scales["index_beta"]
        u = jnp.float32(0.5) + base.draw(self.seed, f"{L}/index_beta", (self.di,),
                                         base.BELL_STD / 1020.0, jnp.float32, (self.di,), (0,))
        return {**super().attention(i),
                "wi_qb": t(f"{L}/wi_qb", (self.q_rank, self.hi, self.di), "index_q", self.q_rank),
                "wi_k": t(f"{L}/wi_k", (d, self.di), "index_k", d),
                "wi_w": t(f"{L}/wi_w", (d, self.hi), "index_w", d),
                "index_beta": jnp.float32(-b3) + jnp.float32(2 * b3) * u}

    def held_experts(self, i: int) -> dict:
        """Layer i's held experts, each a tensor named by its published number."""
        L, d, f = f"layer{i}", self.d, self.f

        def stack(name, shape, role, fan_in):
            return np.stack([np.asarray(self.tensor(f"{L}/{name}/{g}", shape, role, fan_in))
                             for g in range(self.e_first, self.e_first + self.e_count)])

        return {"e_gate": stack("e_gate", (d, f), "ffn_in", d),
                "e_up": stack("e_up", (d, f), "ffn_in", d),
                "e_down": stack("e_down", (f, d), "expert_out", f)}

    def layer(self, i: int) -> dict:
        """Everything layer i holds here: drawn as a pass reaches it."""
        return {"attn": self.attention(i), "ffn": self.ffn(i),
                "experts": self.held_experts(i) if i >= self.first_dense else None}


# -- the forward pass ----------------------------------------------------------------

def _turn(x, pos, inv_freq, factor: float, dr: int):
    """The first `dr` columns of `x` (T, ..., width) turned at `pos`, the rest
    passed."""
    return jnp.concatenate([hc._rope(x[..., :dr], pos, inv_freq, factor, False), x[..., dr:]],
                           axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _attention(dims: tuple, low: bool, given: bool, q_from: int, w: dict, x, pos, inv_freq, past,
               kpos, selected):
    """x (T, d), new tokens at positions `pos`, after tokens whose cached rows
    are `past` = (`c_kv` (P, r), `k_r` (P, rope), index keys (P, Di)) (P = 0:
    none) -> (x + attention(RMSNorm(x)), every token's three cached rows, the
    picks (T, P + T) the attention ran under). `given`: the picks are
    `selected`, not the indexer's. `q_from`: only the rows from there on are
    QUERIES (the stream and the picks that come back are theirs alone); every
    row is a key. A pass's last layer asks so for the rows whose output
    something reads."""
    h, dn, dr, dv, eps, on_cos_sin, scale, hi, di, topk = dims
    rnd = base._round3_traced if low else (lambda z: z)
    beta, wi_w = w["index_beta"], w["wi_w"]
    if low:  # the control: every kernel's values at 3 mantissa bits
        w = {k: base._round3_traced(v) for k, v in w.items()}
    n = past[0].shape[0] + x.shape[0]
    xq, posq = x[q_from:], pos[q_from:]
    t = xq.shape[0]
    with jax.default_matmul_precision("highest"):
        u = rnd(base._rms(x, eps))
        c_q = rnd(base._rms(u[q_from:] @ w["w_qa"], eps))
        q_nope = jnp.einsum("tq,qhn->htn", c_q, w["w_qb_nope"])
        q_rope = hc._rope(jnp.einsum("tq,qhr->thr", c_q, w["w_qb_rope"]), posq, inv_freq,
                          on_cos_sin, False).transpose(1, 0, 2)
        # What a server caches: the normed latent, the rotated shared key, the index key.
        c_kv = jnp.concatenate([past[0], rnd(base._rms(u @ w["w_kva_c"], eps))])
        k_r = jnp.concatenate([past[1], rnd(hc._rope(u @ w["w_kva_r"], pos, inv_freq,
                                                     on_cos_sin, False))])
        k = u @ w["wi_k"]
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + INDEX_EPS) + beta
        k_i = jnp.concatenate([past[2], rnd(_turn(k, pos, inv_freq, on_cos_sin, dr))])
        q_i = rnd(_turn(jnp.einsum("tq,qhk->thk", c_q, w["wi_qb"]), posq, inv_freq, on_cos_sin,
                        dr)).transpose(1, 0, 2)
        # float32 in the program too, as the router's: the control leaves them
        w_i = (base._rms(xq, eps) @ wi_w) * jnp.float32(hi ** -0.5 * di ** -0.5)
        k_nope = jnp.einsum("tr,rhn->htn", c_kv, w["w_kb"])
        v = jnp.einsum("tr,rhv->htv", c_kv, w["w_vb"])
        out, picks = [], []
        for lo in range(0, t, base.QUERY_BLOCK):
            hi_ = min(t, lo + base.QUERY_BLOCK)
            see = kpos[None, :] <= posq[lo:hi_, None]
            if given:
                pick = selected[q_from + lo:q_from + hi_] & see
            else:
                index = jnp.einsum("tj,jtk->tk", w_i[lo:hi_], jax.nn.relu(
                    jnp.einsum("jqd,kd->jqk", q_i[:, lo:hi_], k_i)))
                _, at = jax.lax.top_k(jnp.where(see, index, -jnp.inf), min(topk, n))
                pick = jnp.zeros((hi_ - lo, n), bool).at[jnp.arange(hi_ - lo)[:, None], at] \
                    .set(True) & see
            s = (jnp.einsum("hqn,hkn->hqk", q_nope[:, lo:hi_], k_nope)
                 + jnp.einsum("hqr,kr->hqk", q_rope[:, lo:hi_], k_r)) * scale
            s = jnp.where(pick[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,hkv->hqv", jax.nn.softmax(s, axis=-1), v))
            picks.append(pick)
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(t, h * dv)
        return xq + rnd(o) @ w["wo"].reshape(h * dv, -1), (c_kv, k_r, k_i), jnp.concatenate(picks)


def picks(m: Model, scores: np.ndarray, e_bias: np.ndarray):
    """The experts each token picks and their weights: `s' = s + b`; a group's
    score is the sum of its two largest `s'`; among the outputs of the
    `topk_group` best groups, the `num_experts_per_tok` largest `s'`; weighted by
    `s` alone."""
    n_group, topk_group = m.groups
    by = scores + e_bias[None, :]
    if n_group > 1:
        g = by.reshape(by.shape[0], n_group, -1)
        group = np.sort(g, axis=-1)[:, :, -2:].sum(axis=-1)
        kept = np.argsort(-group, axis=-1, kind="stable")[:, :topk_group]
        stays = np.zeros(group.shape, bool)
        np.put_along_axis(stays, kept, True, axis=-1)
        by = np.where(np.repeat(stays, g.shape[-1], axis=-1), by, -np.inf)
    top = np.argsort(-by, axis=-1, kind="stable")[:, :m.top_k]
    wt = np.take_along_axis(scores, top, axis=-1)
    if m.a.get("norm_topk_prob", True):
        wt = wt / wt.sum(axis=-1, keepdims=True)
    return top, wt * np.float32(m.a.get("routed_scaling_factor", 1.0))


def routed(m: Model, experts: dict, us: list, tops: list, wts: list, low: bool) -> list:
    """The held experts' weighted sums of every sequence, in numpy float32:
    each held expert over the tokens that picked it."""
    rnd = base._round3 if low else (lambda z: z)
    ys = [np.zeros_like(u) for u in us]
    for local in range(m.e_count):
        gate_w, up_w, down_w = (rnd(experts[k][local]) for k in ("e_gate", "e_up", "e_down"))
        for u, top, wt, y in zip(us, tops, wts, ys):
            tok, slot = np.nonzero(top == m.e_first + local)
            if tok.size == 0:
                continue
            ut = u[tok]
            gate = ut @ gate_w
            hid = gate / (1.0 + np.exp(-gate)) * (ut @ up_w)
            y[tok] += wt[tok, slot][:, None] * (rnd(hid) @ down_w)
    return ys


def forward(m: Model, layers, sequences: list[np.ndarray], carry=None, low: bool = False,
            selected=None, picked=None, streams=None, outputs_from=None):
    """The NEW tokens `sequences` of each sequence through `layers` (an
    iterable of `Model.layer` in order: each is drawn as the pass reaches it),
    after the tokens that `carry` (what an earlier call returned; None: none)
    holds the cached rows of -> (the new tokens' final hidden states, before
    the last norm; the carry after them: by layer, a sequence, its tokens'
    `c_kv`, `k_r` and index keys). `selected[i][n]`: layer i's picks of sequence
    n, handed in; `picked` (a list): every layer's picks by sequence are
    appended to it, and to `streams` the stream each layer began from.
    `outputs_from[n]`: the first of sequence n's new rows whose final hidden
    state anything reads (the check's prompts: the last alone): the LAST layer
    takes only those rows as queries and through its feed-forward, every row
    still a key, and they alone come back. `low`: the control (the header)."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) for ids in sequences]
    del embed
    dims = (m.heads, m.dn, m.dr, m.dv, m.eps, m.on_cos_sin, m.score_scale, m.hi, m.di,
            m.index_topk)
    none = (jnp.zeros((0, m.r), jnp.float32), jnp.zeros((0, m.dr), jnp.float32),
            jnp.zeros((0, m.di), jnp.float32))
    after = []
    for i, w in enumerate(layers):
        kept, these, out = [], [], []
        if streams is not None:
            streams.append([np.asarray(x) for x in xs])
        for n, x in enumerate(xs):
            past = carry[i][n] if carry else none
            p, t = past[0].shape[0], x.shape[0]
            given = selected is not None
            q_from = int(outputs_from[n]) if outputs_from is not None and i == m.n_layers - 1 else 0
            y, rows, pick = _attention(
                dims, low, given, q_from, w["attn"], x, p + jnp.arange(t), m.inv_freq, past,
                jnp.arange(p + t), jnp.asarray(selected[i][n]) if given else jnp.zeros((), bool))
            out.append(y.block_until_ready())
            kept.append(rows)
            these.append(pick)
        after.append(kept)
        if picked is not None:
            picked.append([np.asarray(p) for p in these])
        if i < m.first_dense:
            xs = [base._dense(m.eps, low, w["ffn"], x).block_until_ready() for x in out]
            continue
        whole = [base._sparse_whole(m.eps, low, {k: v for k, v in w["ffn"].items()
                                                 if k != "e_bias"}, x) for x in out]
        chosen = [picks(m, np.asarray(scores), w["ffn"]["e_bias"]) for _u, scores, _r in whole]
        ys = routed(m, w["experts"], [np.asarray(u) for u, _s, _r in whole],
                    [t for t, _ in chosen], [wt for _, wt in chosen], low)
        xs = [rest + jnp.asarray(y) for (_u, _s, rest), y in zip(whole, ys)]
    return xs, after


def hidden_states(m: Model, sequences: list[np.ndarray], low: bool = False, selected=None,
                  picked=None, streams=None) -> list:
    """Final hidden states of whole sequences, each layer drawn once and dropped."""
    return forward(m, (m.layer(i) for i in range(m.n_layers)), sequences, None, low, selected,
                   picked, streams)[0]


def _log_softmax(m: Model, head, h):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.nn.log_softmax(base._rms(h, m.eps) @ head, axis=-1))


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int], low: bool = False,
              selected=None, picked=None, streams=None) -> list[np.ndarray]:
    """Per sequence: log-softmax over the held vocabulary rows at positions
    `first_row` onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low, selected, picked, streams)
    head = m.head()
    return [_log_softmax(m, head, h[r:]) for h, r in zip(hs, first_rows)]


def prompt_pass(m: Model, prompts: list[np.ndarray], low: bool = False, stop=None):
    """The prompts' part of the check's pass: every layer drawn once and KEPT
    (float32 copies of every kernel held here, 18.5 GB at the cell's size), the
    prompts taken through them -> (the layers, each prompt's last hidden state,
    the carry). `stop`: an event that ends it at the next layer."""
    layers = []

    def drawn():
        for i in range(m.n_layers):
            if stop is not None and stop.is_set():
                raise RuntimeError("the prompts' pass was stopped: the run is ending")
            layers.append(m.layer(i))
            yield layers[-1]

    hs, carry = forward(m, drawn(), prompts, None, low,
                        outputs_from=[len(p) - 1 for p in prompts])
    return layers, [h[-1:] for h in hs], carry


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (*(k for k in base.ARCH_KEYS if k not in ("head_dim", "qk_head_dim",
                                                      "rope_interleave")),
             "index_head_dim", "index_n_heads", "index_topk")


def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the benchmark:
    the published keys, with the counts that the file states as HELD HERE
    (`reduced`: experts, vocabulary rows) put back to the published counts of
    `published` and the held part said under `share`."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    pub, where, share = cfg.get("published", {}), cfg.get("deployment_share", {}), {}
    if "n_routed_experts" in pub:
        share["experts_held"] = [int(where.get("experts_first", 0)), int(cfg["n_routed_experts"])]
        arch["n_routed_experts"] = int(pub["n_routed_experts"])
    if "vocab_size" in pub:
        share["vocab_rows"] = [int(where.get("vocab_first", 0)), int(cfg["vocab_size"])]
        arch["vocab_size"] = int(pub["vocab_size"])
    if share:
        arch["share"] = share
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """What this file, flops/mla_sel.py and the per-layer readers need:
    `reference/mla.py`'s (`row`: the latent row, 512 + 64), with the share, the
    indexer and `cache_row`, a token's values in all three leaves (`head_dim`,
    which `kv_reserved_pct` reads, is half of that)."""
    a = arch_from_config(cfg)
    share = a.get("share", {})
    sz = base.sizes_from_config(cfg)
    row = int(a["kv_lora_rank"]) + int(a["qk_rope_head_dim"]) + int(a["index_head_dim"])
    vocab_first, vocab = share.get("vocab_rows", [0, int(a["vocab_size"])])
    return {**sz, "arch": a, "cache_row": row, "head_dim": row // 2,
            "index_heads": int(a["index_n_heads"]), "index_dim": int(a["index_head_dim"]),
            "index_topk": int(a["index_topk"]),
            "vocab": int(vocab), "vocab_first": int(vocab_first),
            "num_experts": int(a["n_routed_experts"]),
            "experts_held": int(share.get("experts_held", [0, a["n_routed_experts"]])[1])}


def prepare(seed: int, sizes: dict, cfg: dict, work: str):
    """`reference/mla.py` `prepare` (no checkpoint: the program draws its
    weights by `assumed.weights`; the model's config file in the published
    layout, with its share), and whether this run is the control."""
    weights, options, ref = base.prepare(seed, sizes, cfg, work)
    return weights, options, dict(
        ref, low=cfg["check"].get("reference_inputs") == "3-bit-mantissa")


def reference_answers(ref: dict, inputs: list[dict], sizes: dict) -> dict:
    """The pass is teacher-forced on the served tokens, so their part waits
    for them (`compare`); the PROMPTS' part starts now, beside the server's
    start-up (`reference/mla_sc.py` `in_background`, handed this file's pass)."""
    model = Model(sizes["arch"], ref["seed"], ref["dtype"])
    prompts = [np.asarray(inp["ids"], np.int64) - sizes["vocab_first"] for inp in inputs]
    return {"ref": ref, "inputs": inputs, "sizes": sizes, "model": model,
            "prompts": in_background(model, prompts, ref["low"])}


def in_background(m: Model, prompts: list[np.ndarray], low: bool):
    """`prompt_pass` in a thread of its own (`reference/mla_sc.py`'s, which
    says why the thread is a daemon that the interpreter's exit stops at the
    next layer and waits for)."""
    out, stop = Future(), threading.Event()

    def work():
        try:
            out.set_result(prompt_pass(m, prompts, low, stop))
        except BaseException as e:  # handed to the caller of `result`
            out.set_exception(e)

    thread = threading.Thread(target=work, daemon=True)
    thread.start()
    atexit.register(lambda: (stop.set(), thread.join()))
    return out


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    """`reference/mla.py` `compare`'s statistic over this family's pass, which
    is made in two calls of `forward`: the prompts while the server started,
    the served tokens now, continued from the rows the first cached."""
    sz, low, model = reference["sizes"], reference["ref"]["low"], reference["model"]
    tails = []
    for answer, inp in zip(served, reference["inputs"], strict=True):
        tokens = [int(t) for t in answer.get("tokens", [])]
        lp = answer.get("logprobs") or {}
        if len(tokens) != inp["max_new"] or answer.get("n_tokens") != len(tokens) \
                or np.shape(lp.get("ids")) != (len(tokens), LOGPROBS) \
                or np.shape(lp.get("values")) != (len(tokens), LOGPROBS):
            return float("inf"), (f"logprob_rms=inf: a request of {inp['max_new']} tokens with "
                                  f"logprobs {LOGPROBS} got {len(tokens)} tokens, logprobs of "
                                  f"shape {np.shape(lp.get('ids'))}")
        # Ids travel as the published vocabulary's; the held rows begin at `vocab_first`.
        ids = np.asarray(tokens[:-1], np.int64) - sz["vocab_first"]
        if ids.size and (ids.min() < 0 or ids.max() >= sz["vocab"]):
            return float("inf"), "logprob_rms=inf: a served token lies outside the held rows"
        tails.append(ids)
    t0 = time.monotonic()
    layers, last, carry = reference["prompts"].result()
    waited = time.monotonic() - t0
    some = [n for n, ids in enumerate(tails) if ids.size]   # an answer of one token has no tail
    hs, _ = forward(model, layers, [tails[n] for n in some],
                    [[per[n] for n in some] for per in carry], low)
    del layers, carry
    head, rows = model.head(), dict(zip(some, hs))
    served = [dict(a, logprobs=dict(a["logprobs"], ids=(
        np.asarray(a["logprobs"]["ids"], np.int64) - sz["vocab_first"]))) for a in served]
    gaps = [base.centred_gap(a, _log_softmax(
        model, head, jnp.concatenate([h0, rows[n]]) if n in rows else h0))
        for n, (a, h0) in enumerate(zip(served, last))]
    print(f"[reference] waited {waited:.1f} s for the prompts' pass; {sum(len(t) for t in tails)} "
          f"served tokens of {len(tails)} sequences through {model.n_layers} layers in "
          f"{time.monotonic() - t0 - waited:.1f} s", flush=True)
    # One number a generated position: the RMS of its eight centred differences.
    per = [np.sqrt(np.mean(g ** 2, axis=-1)) for g in gaps]
    quartile = max(float(np.quantile(p, 0.25)) for p in per)
    rms = float(np.sqrt(np.mean(np.concatenate(per) ** 2)))
    limit, rms_limit = float(cfg["check"]["limit"]), float(cfg["check"].get("rms_limit", 0) or 0)
    stat = max(quartile, rms * limit / rms_limit) if rms_limit > 0 else quartile
    by_request = ", ".join(f"{float(np.quantile(p, 0.25)):.4g}/{float(np.sqrt(np.mean(p ** 2))):.4g}"
                           for p in per)
    return stat, (f"logprob_q25={quartile:.6g} (the largest of the requests' lower quartiles of a "
                  f"position's RMS gap; limit {limit:.6g}) logprob_rms={rms:.6g}"
                  + (f" (limit {rms_limit:.6g}, as {rms * limit / rms_limit:.6g} of the first)"
                     if rms_limit > 0 else "")
                  + f" over {sum(len(p) for p in per)} generated positions of {len(served)} requests "
                  f"(quartile/RMS by request: {by_request}; widest position "
                  f"{max(float(p.max()) for p in per):.4g})"
                  + (" [the reference's matrix inputs and what a server caches at 3 mantissa "
                     "bits: a control]" if low else ""))
