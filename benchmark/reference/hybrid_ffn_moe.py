"""The plain reference for the `hybrid_ffn_moe` family: `hybrid_ffn`'s model (two
sublayers a layer, a mixer chosen by `layer_types`, Mamba-2 or attention without
a position term, under four scalar multipliers, a tied head) whose SECOND
sublayer is a routed block in every layer: softmax-routed SwiGLU experts with no
bias and one shared expert on the same normed rows. Written down from the
published `config.json` in straightforward float32 (`jax.numpy` under
`jax.default_matmul_precision("highest")`; the experts' products, whose groups
have every size, in `numpy` float32), with no cache, no batching, no chunking,
no dispatch and no kernel. It imports nothing of the program. The mixers
(`_mamba`, `_attention`), the gated product and the mixers' weights are
`reference/hybrid_ffn.py`'s own lines, the recipe, the roundings of the control
and the check's statistic `reference/hybrid.py`'s, imported and not edited.

THE MODEL, with e = `embedding_multiplier`, r = `residual_multiplier`, a =
`attention_multiplier`, s = `logits_scaling`, E the embedding
(`reference/hybrid_ffn.py`'s header has the mixers):

- `h_0 = e E[ids]`; layer i: `h <- h + r mixer_i(RMSNorm(h; g1_i))`; then, with
  `v = RMSNorm(h; g2_i)`:
- `l = v W_r`, float32, `num_local_experts` wide, no bias (upstream's
  `GraniteMoeHybridTopKGating`: one linear layer);
- `P = topk(l, num_experts_per_tok)`; `w = softmax(l[P])`: a softmax over the
  picked logits ALONE, literally so here (`picks`);
- `routed = sum_{j in P} w_j (silu(v Wg_j) * (v Wu_j)) Wd_j`, the experts
  `intermediate_size` wide (upstream's `input_linear` of an expert is `[gate |
  up]` in one tensor, chunked in two: here two tensors);
- `shared = (silu(v Sg) * (v Su)) Sd`, `shared_intermediate_size` wide
  (`shared_mlp`), on the same `v`;
- `h <- h + r (routed + shared)` (`GraniteMoeHybridDecoderLayer`: `moe_hidden_states
  + shared_mlp(hidden_states)`, then the residual multiplier).
- `logits = RMSNorm(h; g_f) E^T / s`, tied.

THE SHARE (`share` in the architecture), the same as the program is given:
`experts_held = [first, count]` (a pick on another chip's expert adds nothing
here: what the other chip's experts would add is left out, here as in the
program), `vocab_rows = [first, count]`; mixers, router, shared expert and norms
whole.

ASSUMED (the configuration file repeats this under `assumed`): `hybrid_ffn`'s
list; the width of one expert is `intermediate_size`; the router has no bias and
its logits are float32; a tie among the logits goes to the lower expert number
(`jax.lax.top_k`'s order, a stable sort's here).

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`): the names,
shapes, fan-ins and scales are the program's: the mixers' as `reference/hybrid.py`
draws them for a pattern letter `M` or `*`, `layer<i>/router`, `e_gate`, `e_up`
(role `ffn_in`), `e_down` (role `expert_out`), `s_gate`, `s_up` (`ffn_in`),
`s_down` (`ffn_out`) as `tpuserve/models/hybrid_delta.py` `RoutedExperts` yields
them; of the routed kernels the held experts' block alone is drawn.

THE CHECK (`compare`): `reference/hybrid.py`'s statistic (`logprob_q25` beside
`logprob_rms`, centred top-8 log-probabilities, teacher-forced on the served
tokens). The full pass is made in TWO calls of `forward`, as
`reference/hybrid_ffn.py` makes it: the prompts while the server starts
(`prompt_pass`, in a thread) and the served tokens after, continued from the
state, the convolution's inputs and the keys and values the first left.
`check.reference_inputs = "3-bit-mantissa"` (a control, never a cell) rounds the
inputs of the reference's matrix products (every kernel but the router's, the
normed stream that enters a sublayer, the gated rows before `W_out`, the hidden
rows before a down-projection) to 3 explicit mantissa bits AND keeps the
recurrent state in bfloat16; the router reads the unrounded rows, as the
program's decides in float32.
"""

from __future__ import annotations

import atexit
import threading
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import spec

hf = spec.load_module("reference", "hybrid_ffn")
hy = hf.hy
LOGPROBS = hy.LOGPROBS
DEFAULT_SCALES = {**hf.DEFAULT_SCALES, "router": 1.0, "expert_out": 1.0}
ARCH_KEYS = hf.ARCH_KEYS
# What the control leaves alone: the router decides in float32 in the program
# too, and the float32 vectors are no matrix product's input.
EXACT = ("router", *hf.EXACT)


# -- the architecture ------------------------------------------------------------

def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the benchmark:
    the published keys, with the counts that the file states as HELD HERE
    (`reduced`: experts, vocabulary rows) put back to the published counts of
    `published` and the held part said under `share`, as the program and this
    reference read it. The depth and the pattern stay as the file cuts them."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    assert len(arch["layer_types"]) == int(arch["num_hidden_layers"])
    pub, held = cfg.get("published", {}), cfg.get("deployment_share", {})
    share = {}
    if "num_local_experts" in pub:
        share["experts_held"] = [int(held.get("experts_first", 0)), int(cfg["num_local_experts"])]
        arch["num_local_experts"] = int(pub["num_local_experts"])
    if "vocab_size" in pub:
        share["vocab_rows"] = [int(held.get("vocab_first", 0)), int(cfg["vocab_size"])]
        arch["vocab_size"] = int(pub["vocab_size"])
    if share:
        arch["share"] = share
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """`reference/hybrid_ffn.py`'s sizes (the mixers', the cache's, the served
    context) with the routed block's and the share's beside them, under the
    names the other routed families' sizes have."""
    a = arch_from_config(cfg)
    whole = dict(cfg, vocab_size=a["vocab_size"], num_local_experts=a["num_local_experts"])
    sz = hf.sizes_from_config(whole)
    share = a.get("share", {})
    e_full = int(a["num_local_experts"])
    sz.update({
        "arch": a, "n_expert": len(a["layer_types"]), "num_experts": e_full,
        "experts_held": share.get("experts_held", [0, e_full])[1],
        "top_k": int(a["num_experts_per_tok"]), "expert_width": int(a["intermediate_size"]),
        "shared_width": int(a["shared_intermediate_size"]),
        "vocab": share.get("vocab_rows", [0, int(a["vocab_size"])])[1],
        "vocab_first": share.get("vocab_rows", [0, 0])[0],
    })
    return sz


# -- weights by recipe -------------------------------------------------------------

class Model(hf.Model):
    """`reference/hybrid_ffn.py`'s numbers and mixers, the routed block's
    tensors in place of the dense feed-forward's, and the share."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        super().__init__(dict(arch, weight_scales={**DEFAULT_SCALES,
                                                   **arch.get("weight_scales", {})}),
                         seed, served_dtype)
        a = self.a = arch
        self.e_full, self.top_k = int(a["num_local_experts"]), int(a["num_experts_per_tok"])
        self.fe, self.fs = int(a["intermediate_size"]), self.f
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.e_full])
        mm = self.mixers   # it draws the embedding: the held rows of the whole
        mm.v_first, mm.vocab = share.get("vocab_rows", [0, mm.v_full])
        self.v_first, self.vocab = mm.v_first, mm.vocab

    def layer(self, i: int) -> dict:
        t, L, d = self.mixers.tensor, f"layer{i}", self.d
        e, ec, e0, f, fs = self.e_full, self.e_count, self.e_first, self.fe, self.fs
        w = self.mixers.layer(i)
        w["router"] = t(f"{L}/router", (d, e), (d, e), (0, 0), "router", d)
        for name in ("e_gate", "e_up"):
            w[name] = t(f"{L}/{name}", (ec, d, f), (e, d, f), (e0, 0, 0), "ffn_in", d)
        w["e_down"] = t(f"{L}/e_down", (ec, f, d), (e, f, d), (e0, 0, 0), "expert_out", f)
        for name in ("s_gate", "s_up"):
            w[name] = t(f"{L}/{name}", (d, fs), (d, fs), (0, 0), "ffn_in", d)
        w["s_down"] = t(f"{L}/s_down", (fs, d), (fs, d), (0, 0), "ffn_out", fs)
        return w


# -- the routed block ------------------------------------------------------------------

def picks(m: Model, router, v) -> tuple[np.ndarray, np.ndarray]:
    """The published router on the normed rows `v` (float32, never rounded):
    the logits, `topk`, then a softmax over the picked logits alone -> (the
    picked experts (T, k), their weights)."""
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jnp.asarray(v) @ jnp.asarray(router)).astype(np.float32)
    top = np.argsort(-logits, axis=-1, kind="stable")[:, :m.top_k]
    picked = np.take_along_axis(logits, top, axis=-1)
    z = np.exp(picked - picked.max(axis=-1, keepdims=True))
    return top, (z / z.sum(axis=-1, keepdims=True)).astype(np.float32)


def experts(m: Model, w: dict, u: np.ndarray, routed_on: np.ndarray,
            low: bool = False) -> np.ndarray:
    """The held experts' part of the routed sum, in numpy float32: a loop over
    the held experts, each over the tokens that picked it. `routed_on` (T, d) is
    what the router reads (never rounded), `u` what the experts read."""
    top, wt = picks(m, w["router"], routed_on)
    rnd = hy._round3 if low else (lambda z: z)
    y = np.zeros_like(u)
    for local in range(m.e_count):
        tok, slot = np.nonzero(top == m.e_first + local)
        if tok.size == 0:
            continue
        g = u[tok] @ w["e_gate"][local]
        h = g / (1.0 + np.exp(-g)) * (u[tok] @ w["e_up"][local])
        y[tok] += wt[tok, slot][:, None] * (rnd(h.astype(np.float32)) @ w["e_down"][local])
    return y


# -- the forward pass ----------------------------------------------------------------

ROUTED = ("router", "e_gate", "e_up", "e_down")


def forward(m: Model, layers, tokens: list[np.ndarray], carry: list | None = None,
            low_precision: bool = False) -> tuple[list, list]:
    """`reference/hybrid_ffn.py`'s `forward` with the routed block as the second
    sublayer: the rows of `tokens` (held-row ids, one array a sequence) through
    every layer, continued from `carry` (what the same sequences' EARLIER tokens
    left, a layer and a sequence), or None from position 0 -> (final hidden
    states before the last norm, the carry they leave). `layers`: an iterable of
    `Model.layer(i)`."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) * m.e for ids in tokens]
    del embed
    rnd = hy._round3_whole if low_precision else (lambda z: z)
    kept = "bfloat16" if low_precision else "float32"
    mm, took, left = m.mixers, {"mamba": 0.0, "attention": 0.0, "routed": 0.0}, []
    dims = (mm.mh, mm.mp, mm.mg, mm.mn, mm.conv_k, m.eps)
    zeros = (jnp.zeros((mm.mh, mm.mp, mm.mn), jnp.float32),
             jnp.zeros((mm.conv_k - 1, mm.mh * mm.mp + 2 * mm.mg * mm.mn), jnp.float32))
    none = (jnp.zeros((0, m.kv, m.hd), jnp.float32),) * 2
    with jax.default_matmul_precision("highest"):
        for i, (kind, w) in enumerate(zip(m.kinds, layers, strict=True)):
            if low_precision:  # the control: every kernel but the router's
                w = {k: (v if k in EXACT else np.asarray(hy._round3_whole(v)))
                     for k, v in w.items()}
            dev = {k: jnp.asarray(v) for k, v in w.items() if k not in ROUTED}
            left.append([])
            for n, x in enumerate(xs):
                before = carry[i][n] if carry else (zeros if kind == "mamba" else none)
                if x.shape[0] == 0:   # no row of this sequence in this call
                    left[i].append(before)
                    continue
                t0 = time.monotonic()
                u = rnd(hy._rms(x, m.eps))
                if kind == "mamba":
                    g, *after = hf._mamba(
                        dims, kept, {k: v for k, v in dev.items()
                                     if k != "w_out" and not k.startswith("s_")}, u, *before)
                    y = hy._project(rnd(g).reshape(g.shape[0], -1),
                                    dev["w_out"].reshape(-1, m.d))
                else:
                    y, *after = hf._attention(
                        (m.heads, m.kv, m.att),
                        {k: dev[k] for k in ("wq", "wk", "wv", "wo")}, u, *before)
                left[i].append(tuple(after))
                x = (x + m.r * y).block_until_ready()
                t1 = time.monotonic()
                v = hy._rms(x, m.eps)
                vr = rnd(v)
                routed = experts(m, w, np.asarray(vr), np.asarray(v), low_precision)
                shared = hy._project(rnd(hf._gated(vr, dev["s_gate"], dev["s_up"])),
                                     dev["s_down"])
                xs[n] = (x + m.r * (jnp.asarray(routed) + shared)).block_until_ready()
                took[kind] += t1 - t0
                took["routed"] += time.monotonic() - t1
            del w, dev
    print("[reference] " + str(sum(len(s) for s in tokens)) + " tokens through "
          + ", ".join(f"{m.kinds.count(k)} {k} layers" for k in ("mamba", "attention"))
          + ": " + ", ".join(f"{k} {v:.1f} s" for k, v in took.items()), flush=True)
    return xs, left


def hidden_states(m: Model, sequences: list[np.ndarray], low_precision: bool = False) -> list:
    """Final hidden states of each whole sequence of held-row ids, in ONE pass
    from position 0; layers outermost, each drawn once and dropped."""
    return forward(m, (m.layer(i) for i in range(len(m.kinds))), sequences, None,
                   low_precision)[0]


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low_precision: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the held vocabulary rows at positions
    `first_row` onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low_precision)
    head = jnp.asarray(m.head())
    return [hf._log_softmax(m, head, h[r:]) for h, r in zip(hs, first_rows)]


def prompt_pass(m: Model, prompts: list[np.ndarray], low_precision: bool = False, stop=None):
    """The prompts' part of the check's pass: every layer drawn once and KEPT
    (float32 copies of every held kernel, 18.2 GB at the cell's size), the
    prompts taken through them. -> (the layers, each prompt's last hidden state,
    the carry). `stop`: an event that ends it at the next layer."""
    layers = []

    def drawn():
        for i in range(len(m.kinds)):
            if stop is not None and stop.is_set():
                raise RuntimeError("the prompts' pass was stopped: the run is ending")
            layers.append(m.layer(i))
            yield layers[-1]

    hs, carry = forward(m, drawn(), prompts, None, low_precision)
    return layers, [h[-1:] for h in hs], carry


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

prepare = hy.prepare   # no checkpoint: the published keys as the program's config file


def reference_answers(ref: dict, inputs: list[dict], sizes: dict) -> dict:
    """The pass is teacher-forced on the served tokens, so their part waits
    for them (`compare`); the prompts' part starts now, while the server
    starts (`reference/hybrid_ffn.py`'s thread, this module's pass)."""
    model = Model(sizes["arch"], ref["seed"], ref["dtype"])
    low = sizes.get("reference_inputs") == "3-bit-mantissa"
    prompts = [np.asarray(inp["ids"], np.int64) - sizes["vocab_first"] for inp in inputs]
    return {"inputs": inputs, "sizes": sizes, "model": model, "low": low,
            "prompts": in_background(model, prompts, low)}


def in_background(m: Model, prompts: list[np.ndarray], low_precision: bool) -> Future:
    """`prompt_pass` in a daemon thread of its own that the interpreter's exit
    stops at the next layer and waits for (`reference/hybrid_ffn.py`'s
    `in_background`, over this module's pass)."""
    out, stop = Future(), threading.Event()

    def work():
        try:
            out.set_result(prompt_pass(m, prompts, low_precision, stop))
        except BaseException as e:  # handed to the caller of `result`
            out.set_exception(e)

    thread = threading.Thread(target=work, daemon=True)
    thread.start()
    atexit.register(lambda: (stop.set(), thread.join()))
    return out


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    """`reference/hybrid.py`'s statistic over this family's pass, as
    `reference/hybrid_ffn.py` takes it: a generated position's number is the RMS
    of its eight centred differences; the statistic is the largest, over the
    requests, of the lower quartile of a request's positions, beside the RMS
    over all positions against `check.rms_limit` (the number compared with
    `check.limit` is the larger of the quartile and the RMS scaled by `limit /
    rms_limit`)."""
    sz = reference["sizes"]
    v0, after = sz["vocab_first"], []
    for answer, inp in zip(served, reference["inputs"], strict=True):
        tokens = [int(t) for t in answer.get("tokens", [])]
        lp = answer.get("logprobs") or {}
        if len(tokens) != inp["max_new"] or answer.get("n_tokens") != len(tokens) \
                or np.shape(lp.get("ids")) != (len(tokens), LOGPROBS) \
                or np.shape(lp.get("values")) != (len(tokens), LOGPROBS):
            return float("inf"), (f"logprob_rms=inf: a request of {inp['max_new']} tokens with "
                                  f"logprobs {LOGPROBS} got {len(tokens)} tokens, logprobs of "
                                  f"shape {np.shape(lp.get('ids'))}")
        ids = np.asarray(tokens[:-1], np.int64) - v0   # the last served token predicts nothing served
        if len(ids) and (ids.min() < 0 or ids.max() >= sz["vocab"]):
            return float("inf"), "logprob_rms=inf: a served token lies outside the held rows"
        after.append(ids)
    low, m = reference["low"], reference["model"]
    assert low == (cfg["check"].get("reference_inputs") == "3-bit-mantissa")
    layers, last, carry = reference["prompts"].result()
    # Every request's served tokens as rows of ONE length (ids of 0 behind the
    # shorter ones: the model is causal, so a row never sees a later one).
    longest = max(len(ids) for ids in after)
    hs, _ = forward(m, layers, [np.pad(ids, (0, longest - len(ids))) for ids in after], carry, low)
    hs = [h[:len(ids)] for h, ids in zip(hs, after)]
    del layers, carry
    # A prompt's last row predicts the first served token, a served token's row the next.
    head = jnp.asarray(m.head())
    gaps = [hy.centred_gap(a, hf._log_softmax(m, head, jnp.concatenate([h0, h], axis=0)), v0)
            for a, h0, h in zip(served, last, hs)]
    del head
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
                  + (" [the reference's matrix inputs at 3 mantissa bits, its state in "
                     "bfloat16: a control]" if low else ""))
