"""The plain reference for the `mla_sc` family: a decoder-only language model of
DOUBLE layers (two latent attentions, two dense SwiGLUs) with its routed experts
on a SHORTCUT and zero-compute (identity) outputs in its router, written down
from its published `config.json` in straightforward float32 (`jax.numpy` under
`jax.default_matmul_precision("highest")`; the routed experts' products, whose
groups have every size, in `numpy` float32), attention in its EXPANDED form
only, one causal pass, with no cache, no chunking, no absorbed product, no
kernel and no batching. It imports nothing of the program. The weights' recipe,
the rotary turn, the control's rounding, the dense SwiGLU and the check's
statistic are `reference/mla.py`'s own functions, imported and not edited.

THE LAYER (d = `hidden_size`, eps = `rms_norm_eps`, no biases, an untied head;
norm gains are ones in the recipe and are left out), `num_layers` times:

    a0 = h  + MLA_0(RMSNorm(h))      u0 = RMSNorm(a0)
    s  = Routed(u0)                   b0 = a0 + MLP_0(u0)
    a1 = b0 + MLA_1(RMSNorm(b0))     u1 = RMSNorm(a1)
    h' = a1 + MLP_1(u1) + s

The routed layer reads the FIRST sublayer's normed stream and joins the stream
at the layer's END. `MLP_j(u) = (silu(u W_gate) * (u W_up)) W_down`,
`ffn_hidden_size` wide. logits = `RMSNorm(h_L) W_head`.

- `MLA_j(u)` at position t: `c_q = RMSNorm(u W_qa)`; `q = (c_q W_qb) x sqrt(d /
  q_lora_rank)` (`mla_scale_q_lora`), a head `[q_nope | q_rope]`, `q_rope <-
  RoPE(q_rope, t)`; `[c_kv | k_r] = u W_kva`; `c_kv <- RMSNorm(c_kv) x sqrt(d /
  kv_lora_rank)` (`mla_scale_kv_lora`) before `W_kvb`, so `k_nope` and `v` carry
  the factor and `k_r` does not; `k_r <- RoPE(k_r, t)`, ONE rotary key for every
  head; `[k_nope_h | v_h] = c_kv W_kvb`; `score_h(t, s) = (q_nope_h(t) . k_nope_h(s)
  + q_rope_h(t) . k_r(s)) / sqrt(qk_nope + qk_rope)`, causal softmax, `o_h = sum_s
  p v_h(s)`, out = `concat_h(o_h) W_o`. RoPE: plain, pair i of the rotary columns
  is (2i, 2i + 1) and turns by `t x rope_theta ** (-2i / qk_rope_head_dim)`.
- `Routed(u)`: `p = softmax(u W_r)` over all `n_routed_experts + zero_expert_num`
  outputs (the real experts first); the `moe_topk` largest of `p + b`; `w_e =
  routed_scaling_factor x p_e`, NOT divided by the picks' sum; `Routed(u) =
  sum_{picked e real} w_e SwiGLU_e(u) + (sum_{picked e zero-compute} w_e) u`,
  `SwiGLU_e` of `expert_ffn_hidden_size`.

THE SHARE (`share` in the architecture): this chip holds real experts
`experts_held = [first, count]` and vocabulary rows `vocab_rows = [first, count]`.
`Routed` sums over the held experts a token picked AND the zero-compute term
(it has no weights: every chip of the layer computes it alike, and it counts
once); that partial `s` is what goes on. Without `share` the layer is whole.

ASSUMED (the configuration file repeats this under `assumed`): the two latent
factors as above (the config gives two booleans); weights without
renormalising (no `norm_topk_prob` key); the selection bias covers every output
and is drawn small (a bell within +-0.06): it moves picks, never weights;
interleaved rotary pairs; no `rope_scaling`, so no factor on the softmax scale;
an untied head; softmax and norms in float32; no end-of-sequence id.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`, `reference/mla.py`
`draw`): the tensors' names and shapes are the program's: `layer<i>/attn<j>/...`
as `reference/mla.py` names an attention's eight, `layer<i>/mlp<j>/w_gate | w_up |
w_down`, `layer<i>/router` (d x outputs), `layer<i>/e_bias`, `layer<i>/e_gate |
e_up | e_down/<g>`, an expert a tensor, g its PUBLISHED number. Experts are drawn
`EXPERT_BLOCK` at a time.

THE CHECK (`compare`): `reference/mla.py`'s statistic, computed the same way:
`logprob_q25` (the largest, over the requests, of the lower quartile of a
request's positions' RMS gap of centred top-8 log-probabilities) beside
`logprob_rms` over all positions, teacher-forced on the served tokens in one
full pass. The pass is made in TWO calls of `forward`, the prompts while the
server starts (`prompt_pass`, in a thread) and the served tokens after, the
second continued from the rows the first cached, an attention a sequence (the
scaled `c_kv` and the rotated `k_r`: what a server caches, and all a later
token needs of an earlier one): 5.2 GFLOP a token over 1,450 prompt tokens is a
minute of the host, which a run's budget does not have after the server is
ready. `check.reference_inputs = "3-bit-mantissa"` (a control, never a cell)
rounds the inputs of the reference's matrix products (every kernel but the
router's, the normed stream, the query's latent, what a server would CACHE: the
scaled `c_kv` and the rotated `k_r`, the heads' outputs, the hidden rows of
every SwiGLU) to 3 explicit mantissa bits.
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
LOGPROBS = base.LOGPROBS
DEFAULT_SCALES = {**base.DEFAULT_SCALES, "router": 1.75}
EXPERT_BLOCK = 4   # experts drawn at a time (one is 151 MB in float32 at the published widths)


class Model:
    """The architecture's numbers and its tensors' shapes; draws one
    attention's, one SwiGLU's or the router's matrices, or one block of a
    layer's experts, at a time."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        a = self.a = arch
        self.seed, self.dtype = int(seed), jnp.dtype(served_dtype)
        self.d, self.n_layers = int(a["hidden_size"]), int(a["num_layers"])
        self.eps = float(a.get("rms_norm_eps", 1e-6))
        self.heads = int(a["num_attention_heads"])
        self.q_rank, self.r = int(a["q_lora_rank"]), int(a["kv_lora_rank"])
        self.dn, self.dr, self.dv = (int(a[k]) for k in (
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        self.theta = float(a.get("rope_theta", 10000.0))
        self.interleave = bool(a.get("rope_interleave", True))
        self.q_scale = math.sqrt(self.d / self.q_rank) if a.get("mla_scale_q_lora") else 1.0
        self.kv_scale = math.sqrt(self.d / self.r) if a.get("mla_scale_kv_lora") else 1.0
        self.f = int(a["ffn_hidden_size"])
        self.e, self.zero = int(a["n_routed_experts"]), int(a.get("zero_expert_num", 0))
        self.top_k, self.fe = int(a["moe_topk"]), int(a["expert_ffn_hidden_size"])
        self.route_scale = float(a.get("routed_scaling_factor", 1.0))
        self.norm_topk = bool(a.get("norm_topk_prob", False))
        self.vocab_full = int(a["vocab_size"])
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.e])
        self.v_first, self.vocab = share.get("vocab_rows", [0, self.vocab_full])
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    def tensor(self, name: str, shape, role: str, fan_in: int, full=None, start=None):
        shape = tuple(shape)
        return base.draw(self.seed, name, shape, self.scales[role] / math.sqrt(fan_in),
                         self.dtype, tuple(full or shape), tuple(start or (0,) * len(shape)))

    def embed(self) -> np.ndarray:
        return np.asarray(self.tensor("embed", (self.vocab, self.d), "embed", 1,
                                      (self.vocab_full, self.d), (self.v_first, 0)))

    def head(self):
        return self.tensor("head", (self.d, self.vocab), "head", self.d,
                           (self.d, self.vocab_full), (0, self.v_first))

    def attention(self, i: int, j: int) -> dict:
        L, d, h, t = f"layer{i}/attn{j}", self.d, self.heads, self.tensor
        return {"w_qa": t(f"{L}/w_qa", (d, self.q_rank), "q_a", d),
                "w_qb_nope": t(f"{L}/w_qb_nope", (self.q_rank, h, self.dn), "q_b", self.q_rank),
                "w_qb_rope": t(f"{L}/w_qb_rope", (self.q_rank, h, self.dr), "q_b", self.q_rank),
                "w_kva_c": t(f"{L}/w_kva_c", (d, self.r), "kv_a", d),
                "w_kva_r": t(f"{L}/w_kva_r", (d, self.dr), "k_rope", d),
                "w_kb": t(f"{L}/w_kb", (self.r, h, self.dn), "k_b", self.r),
                "w_vb": t(f"{L}/w_vb", (self.r, h, self.dv), "v", self.r),
                "wo": t(f"{L}/wo", (h, self.dv, d), "o", h * self.dv)}

    def mlp(self, i: int, j: int) -> dict:
        L, d, f, t = f"layer{i}/mlp{j}", self.d, self.f, self.tensor
        return {"w_gate": t(f"{L}/w_gate", (d, f), "ffn_in", d),
                "w_up": t(f"{L}/w_up", (d, f), "ffn_in", d),
                "w_down": t(f"{L}/w_down", (f, d), "ffn_out", f)}

    def router(self, i: int):
        """(the router's matrix over every output, the selection bias: a
        float32 vector inside [-b3, b3], the four summed bytes over their
        range, then the range)."""
        n, b3 = self.e + self.zero, 3.0 * self.scales["router_bias"]
        u = jnp.float32(0.5) + base.draw(self.seed, f"layer{i}/e_bias", (n,),
                                         base.BELL_STD / 1020.0, jnp.float32, (n,), (0,))
        return (self.tensor(f"layer{i}/router", (self.d, n), "router", self.d),
                np.asarray(jnp.float32(-b3) + jnp.float32(2 * b3) * u))

    def held_experts(self, i: int) -> dict:
        """Layer i's held experts, drawn `EXPERT_BLOCK` at a time."""
        blocks = [self.expert_block(i, first, min(EXPERT_BLOCK, self.e_first + self.e_count - first))
                  for first in range(self.e_first, self.e_first + self.e_count, EXPERT_BLOCK)]
        return {k: np.concatenate([b[k] for b in blocks]) for k in ("e_gate", "e_up", "e_down")}

    def layer(self, i: int) -> dict:
        """Everything layer i holds here."""
        router, e_bias = self.router(i)
        return {"attn": [self.attention(i, 0), self.attention(i, 1)],
                "mlp": [self.mlp(i, 0), self.mlp(i, 1)], "router": router, "e_bias": e_bias,
                "experts": self.held_experts(i)}

    def expert_block(self, i: int, first: int, count: int) -> dict:
        """Experts `first` .. `first + count - 1` of layer i, each a tensor of
        its own named by its published number."""
        L, d, f = f"layer{i}", self.d, self.fe

        def stack(name, shape, role, fan_in):
            return np.stack([np.asarray(self.tensor(f"{L}/{name}/{g}", shape, role, fan_in))
                             for g in range(first, first + count)])

        return {"e_gate": stack("e_gate", (d, f), "ffn_in", d),
                "e_up": stack("e_up", (d, f), "ffn_in", d),
                "e_down": stack("e_down", (f, d), "expert_out", f)}


# -- the forward pass ----------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1))
def _attention(dims: tuple, low: bool, w: dict, x, pos, c_past, r_past, kpos):
    """x (T, d), new tokens at positions `pos`, after tokens whose cached rows
    are `c_past` (P, r) and `r_past` (P, rope) (P = 0: none) -> (x +
    MLA(RMSNorm(x)), every token's `c_kv`, every token's `k_r`): the expanded
    form, one causal pass over the keys at `kpos` (all P + T of them),
    `QUERY_BLOCK` queries at a time. What comes back beside the stream is what
    a server caches, which is all that a later call needs of these tokens."""
    h, dn, dr, dv, eps, theta, interleave, q_scale, kv_scale = dims
    rnd = base._round3_traced if low else (lambda z: z)
    if low:  # the control: every kernel's values at 3 mantissa bits
        w = {k: base._round3_traced(v) for k, v in w.items()}
    t = x.shape[0]
    with jax.default_matmul_precision("highest"):
        u = rnd(base._rms(x, eps))
        c_q = rnd(base._rms(u @ w["w_qa"], eps))
        q_nope = jnp.einsum("tq,qhn->htn", c_q, w["w_qb_nope"]) * q_scale
        q_rope = base._rope(jnp.einsum("tq,qhr->thr", c_q, w["w_qb_rope"]) * q_scale, pos, theta,
                            interleave).transpose(1, 0, 2)
        # What a server caches: the normed, scaled latent and the rotated shared key.
        c_kv = jnp.concatenate([c_past, rnd(base._rms(u @ w["w_kva_c"], eps) * kv_scale)])
        k_r = jnp.concatenate([r_past, rnd(base._rope(u @ w["w_kva_r"], pos, theta, interleave))])
        k_nope = jnp.einsum("tr,rhn->htn", c_kv, w["w_kb"])
        v = jnp.einsum("tr,rhv->htv", c_kv, w["w_vb"])
        past, out = c_past.shape[0], []
        for lo in range(0, t, base.QUERY_BLOCK):
            hi = min(t, lo + base.QUERY_BLOCK)
            s = (jnp.einsum("hqn,hkn->hqk", q_nope[:, lo:hi], k_nope[:, :past + hi])
                 + jnp.einsum("hqr,kr->hqk", q_rope[:, lo:hi], k_r[:past + hi])) / math.sqrt(dn + dr)
            s = jnp.where((kpos[None, :past + hi] <= pos[lo:hi, None])[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,hkv->hqv", jax.nn.softmax(s, axis=-1), v[:, :past + hi]))
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(t, h * dv)
        return x + rnd(o) @ w["wo"].reshape(h * dv, -1), c_kv, k_r


@functools.partial(jax.jit, static_argnums=(0,))
def _route(eps: float, router, x):
    """-> (the normed stream, softmax over every output of the router): float32
    in the program too, so the control leaves them."""
    with jax.default_matmul_precision("highest"):
        u = base._rms(x, eps)
        return u, jax.nn.softmax(u @ router, axis=-1)


def picks(m: Model, p: np.ndarray, e_bias: np.ndarray):
    """The outputs each token picks and their weights: the `moe_topk` largest
    of p + bias, weighted by p alone."""
    top = np.argsort(-(p + e_bias[None, :]), axis=-1, kind="stable")[:, :m.top_k]
    wt = np.take_along_axis(p, top, axis=-1)
    if m.norm_topk:
        wt = wt / wt.sum(axis=-1, keepdims=True)
    return top, wt * np.float32(m.route_scale)


def routed(m: Model, experts: dict, us: list, tops: list, wts: list, low: bool,
           parts: bool = False) -> list:
    """`Routed(u)` of every sequence, in numpy float32: the zero-compute picks'
    weight times u, then each HELD expert (`experts`: `Model.held_experts`)
    over the tokens that picked it. `parts`: (the held experts' sum, the zero
    term) apart."""
    rnd = base._round3 if low else (lambda z: z)
    zeros = [np.where(top >= m.e, wt, 0.0).sum(axis=-1, dtype=np.float32)[:, None] * u
             for u, top, wt in zip(us, tops, wts)]
    ys = [np.zeros_like(u) for u in us]
    us = [rnd(u) for u in us]
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
    return list(zip(ys, zeros)) if parts else [y + z for y, z in zip(ys, zeros)]


def forward(m: Model, layers, sequences: list[np.ndarray], carry=None, low: bool = False):
    """The NEW tokens `sequences` of each sequence through `layers` (an
    iterable of `Model.layer` in order: each is drawn as the pass reaches it),
    after the tokens that `carry` (what an earlier call returned; None: none)
    holds the cached rows of -> (the new tokens' final hidden states, before
    the last norm; the carry after them: by layer and attention, a sequence,
    its tokens' `c_kv` and `k_r`). `low`: the control (the header)."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) for ids in sequences]
    del embed
    dims = (m.heads, m.dn, m.dr, m.dv, m.eps, m.theta, m.interleave, m.q_scale, m.kv_scale)
    none = (jnp.zeros((0, m.r), jnp.float32), jnp.zeros((0, m.dr), jnp.float32))
    after = []
    for i, w in enumerate(layers):
        kept = [[], []]

        def attend(j, xs, i=i, w=w, kept=kept):
            out = []
            for n, x in enumerate(xs):
                c_past, r_past = carry[i][j][n] if carry else none
                past, t = c_past.shape[0], x.shape[0]
                y, c_kv, k_r = _attention(dims, low, w["attn"][j], x, past + jnp.arange(t),
                                          c_past, r_past, jnp.arange(past + t))
                out.append(y.block_until_ready())
                kept[j].append((c_kv, k_r))
            return out

        def dense(j, xs, w=w):
            return [base._dense(m.eps, low, w["mlp"][j], x).block_until_ready() for x in xs]

        a0 = attend(0, xs)
        us, chosen = [], []
        for x in a0:
            u, p = _route(m.eps, w["router"], x)
            us.append(np.asarray(u))
            chosen.append(picks(m, np.asarray(p), w["e_bias"]))
        s = routed(m, w["experts"], us, [t for t, _ in chosen], [wt for _, wt in chosen], low)
        a1 = attend(1, dense(0, a0))
        xs = [h + jnp.asarray(y) for h, y in zip(dense(1, a1), s)]
        after.append(kept)
    return xs, after


def hidden_states(m: Model, sequences: list[np.ndarray], low: bool = False) -> list:
    """Final hidden states of whole sequences, each layer drawn once and dropped."""
    return forward(m, (m.layer(i) for i in range(m.n_layers)), sequences, None, low)[0]


def _log_softmax(m: Model, head, h):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.nn.log_softmax(base._rms(h, m.eps) @ head, axis=-1))


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the held vocabulary rows at positions
    `first_row` onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low)
    head = m.head()
    return [_log_softmax(m, head, h[r:]) for h, r in zip(hs, first_rows)]


def prompt_pass(m: Model, prompts: list[np.ndarray], low: bool = False, stop=None):
    """The prompts' part of the check's pass: every layer drawn once and KEPT
    (float32 copies of every kernel held here, 20 GB at the cell's size), the
    prompts taken through them -> (the layers, each prompt's last hidden
    state, the carry). `stop`: an event that ends it at the next layer
    (`in_background`)."""
    layers = []

    def drawn():
        for i in range(m.n_layers):
            if stop is not None and stop.is_set():
                raise RuntimeError("the prompts' pass was stopped: the run is ending")
            layers.append(m.layer(i))
            yield layers[-1]

    hs, carry = forward(m, drawn(), prompts, None, low)
    return layers, [h[-1:] for h in hs], carry


def in_background(m: Model, prompts: list[np.ndarray], low: bool) -> Future:
    """`prompt_pass` in a thread of its own, because the served tokens' part
    waits for the server and this does not (`reference/hybrid_ffn.py` has the
    same, and says why the thread is a daemon that the interpreter's exit
    stops at the next layer and waits for)."""
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


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (
    "attention_bias", "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_layers", "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
    "v_head_dim", "qk_nope_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
    "routed_scaling_factor", "n_routed_experts", "max_position_embeddings", "rms_norm_eps",
    "rope_theta", "attention_method", "zero_expert_num", "zero_expert_type", "moe_topk")


def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the benchmark:
    the published keys, with the counts that the file states as HELD HERE
    (`reduced`: experts, vocabulary rows) put back to the published counts of
    `published` and the held part said under `share`, as the program and this
    reference read it."""
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
    """What this file, flops/mla_sc.py and the per-layer readers need."""
    gen = cfg["serve"]["tables"]["genserve"]
    served = cfg["assumed"]["served"]
    a = arch_from_config(cfg)
    share = a.get("share", {})
    layers = int(a["num_layers"])
    max_ctx = int(served["max_prompt_tokens"]) + int(served["max_new_tokens"])
    page, slots = int(gen["kv_page_tokens"]), int(gen["slots"])
    row = int(a["kv_lora_rank"]) + int(a["qk_rope_head_dim"])
    vocab_first, vocab = share.get("vocab_rows", [0, int(a["vocab_size"])])
    return {
        "arch": a, "d_model": int(a["hidden_size"]), "layers": layers, "n_attn": 2 * layers,
        "n_sparse": layers, "heads": int(a["num_attention_heads"]),
        "q_rank": int(a["q_lora_rank"]), "kv_rank": int(a["kv_lora_rank"]),
        "nope": int(a["qk_nope_head_dim"]), "rope": int(a["qk_rope_head_dim"]),
        "v_dim": int(a["v_head_dim"]), "row": row,
        # What kv_reserved_pct looks up (a ratio of pages: `reference/mla.py` says why
        # half a row stands for a head): one latent row an ATTENTION, two a layer.
        "layer_types": ["full_attention"] * 2 * layers, "window": 0, "kv_heads": 1,
        "head_dim": row // 2,
        "vocab": int(vocab), "vocab_first": int(vocab_first),
        "num_experts": int(a["n_routed_experts"]), "zero_experts": int(a.get("zero_expert_num", 0)),
        "experts_held": int(share.get("experts_held", [0, a["n_routed_experts"]])[1]),
        "top_k": int(a["moe_topk"]), "dense_width": int(a["ffn_hidden_size"]),
        "expert_width": int(a["expert_ffn_hidden_size"]),
        "max_prompt": int(served["max_prompt_tokens"]), "max_new": int(served["max_new_tokens"]),
        "max_ctx": max_ctx, "slots": slots, "page_tokens": page,
        "pages_per_slot": -(-max_ctx // page),
        "kv_pages": int(gen.get("kv_pages") or 0) or slots * -(-max_ctx // page) + 1,
        "prefill_chunk": int(gen.get("prefill_chunk") or 0) or int(served["max_prompt_tokens"]),
        "weight_bytes": 2 if cfg["serve"]["model"]["dtype"] == "bfloat16" else 4,
    }


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
    start-up (`in_background`)."""
    model = Model(sizes["arch"], ref["seed"], ref["dtype"])
    prompts = [np.asarray(inp["ids"], np.int64) - sizes["vocab_first"] for inp in inputs]
    return {"ref": ref, "inputs": inputs, "sizes": sizes, "model": model,
            "prompts": in_background(model, prompts, ref["low"])}


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
                    [[[per[n] for n in some] for per in two] for two in carry], low)
    del layers, carry
    head, rows = model.head(), dict(zip(some, hs))
    served = [dict(a, logprobs=dict(a["logprobs"], ids=(
        np.asarray(a["logprobs"]["ids"], np.int64) - sz["vocab_first"]))) for a in served]
    gaps = [base.centred_gap(a, _log_softmax(
        model, head, jnp.concatenate([h0, rows[n]]) if n in rows else h0))
        for n, (a, h0) in enumerate(zip(served, last))]
    print(f"[reference] waited {waited:.1f} s for the prompts' pass; {sum(len(t) for t in tails)} "
          f"served tokens of {len(tails)} sequences through {model.n_layers} double layers in "
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
