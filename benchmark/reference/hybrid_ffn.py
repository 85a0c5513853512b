"""The plain reference for the `hybrid_ffn` family: a language model whose
layers are two sublayers each (a mixer chosen by `layer_types`, Mamba-2 or
attention without a position term, then a dense SwiGLU feed-forward) under four
scalar multipliers, written down from its published `config.json` in
straightforward float32 (`jax.numpy` under
`jax.default_matmul_precision("highest")`), with no cache, no batching, no
chunking and no kernel. It imports nothing of the program. The Mamba-2 layer,
the recipe of the weights and the check's statistic are `reference/hybrid.py`'s
own functions, imported and not edited.

THE MODEL, with e = `embedding_multiplier`, r = `residual_multiplier`, a =
`attention_multiplier`, s = `logits_scaling`, E the embedding:

- `h_0 = e E[ids]`.
- layer i: `h <- h + r mixer_i(RMSNorm(h; g1_i))`; then `v = RMSNorm(h; g2_i)`,
  `h <- h + r (silu(v W_gate) * (v W_up)) W_down`, `shared_intermediate_size`
  wide, no bias; eps = `rms_norm_eps`.
- `layer_types[i] == "mamba"`: Mamba-2 with `mamba_n_heads` H of `mamba_d_head`
  P, `mamba_n_groups` G, `mamba_d_state` N, `mamba_d_conv` k and its bias:
  `reference/hybrid.py`'s header has the equations. THE RECURRENCE IS THE
  RECURRENCE: a `lax.scan` over the tokens, one at a time.
- `"attention"`: `num_attention_heads` query heads over `num_key_value_heads`
  KV heads of `hidden_size / num_attention_heads` (query head h reads KV head
  h // (H / KV)), causal softmax of `a q.k`, NO rotary embedding and no other
  position term, `W_o`, no bias.
- `logits = RMSNorm(h; g_f) E^T / s` (`tie_word_embeddings`; else a head of its
  own).

ASSUMED (the configuration file repeats this under `assumed`): no clamp on
delta beyond softplus; `dt_bias`, `A_log`, `D` drawn inside mamba2's default
ranges (softplus(dt_bias) in [0.001, 0.1], A in [1, 16], D about 1); the gated
norm over a group (ONE group: the whole inner width), gate before norm.

WEIGHTS BY RECIPE (`assumed.weights`, recipe `counter-bell-v1`): the tensors'
names, shapes and scales are the program's; a Mamba-2 or attention layer's are
exactly those `reference/hybrid.py` draws for a pattern letter `M` or `*`
(`Model.mixers`), the feed-forward's are `layer<i>/w_gate`, `w_up`, `w_down`.

THE CHECK (`compare`): `reference/hybrid.py`'s statistic (`logprob_q25` beside
`logprob_rms`, centred top-8 log-probabilities, teacher-forced on the served
tokens, a layer at a time). The full pass is made in TWO calls of `forward`,
the prompts while the server starts (`prompt_pass`) and the served tokens after,
the second continued from the state, the convolution's inputs and the keys and
values that the first left: the same recurrence over the same tokens (40 layers
over 1,550 tokens take the host over a minute, which a run's budget does not
have after the server is ready). `check.reference_inputs =
"3-bit-mantissa"` (a control, never a cell) rounds the inputs of the
reference's matrix products (every kernel, the normed stream that enters a
sublayer, the gated rows before `W_out` and the hidden rows before `W_down`) to
3 explicit mantissa bits AND keeps the recurrent state in bfloat16.
"""

from __future__ import annotations

import atexit
import functools
import threading
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import spec

hy = spec.load_module("reference", "hybrid")
LOGPROBS = hy.LOGPROBS
DEFAULT_SCALES = {"embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
                  "ffn_out": 1.0, "ssm_in": 1.0, "ssm_bc": 2.0, "ssm_dt": 1.0, "ssm_out": 1.0,
                  "conv": 1.0, "conv_bias": 0.1, "ssm_d": 0.1}
# The keys of a configuration file that are the model's own config.json.
ARCH_KEYS = (
    "model_type", "attention_bias", "attention_multiplier", "embedding_multiplier", "hidden_act",
    "hidden_size", "intermediate_size", "layer_types", "logits_scaling", "mamba_chunk_size",
    "mamba_conv_bias", "mamba_d_conv", "mamba_d_head", "mamba_d_state", "mamba_expand",
    "mamba_n_groups", "mamba_n_heads", "mamba_proj_bias", "max_position_embeddings",
    "normalization_function", "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "num_local_experts", "position_embedding_type", "residual_multiplier",
    "rms_norm_eps", "rope_scaling", "rope_theta", "shared_intermediate_size",
    "tie_word_embeddings", "vocab_size")
# The float32 vectors the control leaves alone: no matrix product's input.
EXACT = ("dt_bias", "A_log", "D")


# -- the architecture ------------------------------------------------------------

def arch_from_config(cfg: dict) -> dict:
    """The program's `config_file` from a configuration file of the
    benchmark: the published keys as they are (nothing is cut), and the drawn
    tensors' scales."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    assert len(arch["layer_types"]) == int(arch["num_hidden_layers"])
    weights = cfg.get("assumed", {}).get("weights", {})
    if "scales" in weights:
        arch["weight_scales"] = weights["scales"]
    return arch


def sizes_from_config(cfg: dict) -> dict:
    """What this file, flops/hybrid_ffn.py and the per-layer readers need
    (`flops/hybrid.py`'s names where the quantity is the same)."""
    gen = cfg["serve"]["tables"]["genserve"]
    served = cfg["assumed"]["served"]
    a = arch_from_config(cfg)
    kinds = a["layer_types"]
    n_m, n_a = kinds.count("mamba"), kinds.count("attention")
    max_ctx = int(served["max_prompt_tokens"]) + int(served["max_new_tokens"])
    page, slots = int(gen["kv_page_tokens"]), int(gen["slots"])
    wb = 2 if cfg["serve"]["model"]["dtype"] == "bfloat16" else 4
    mh, mg = int(a["mamba_n_heads"]), int(a["mamba_n_groups"])
    mp, mn, ck = int(a["mamba_d_head"]), int(a["mamba_d_state"]), int(a["mamba_d_conv"])
    heads = int(a["num_attention_heads"])
    return {
        "arch": a, "d_model": int(a["hidden_size"]),
        "head_dim": int(a["hidden_size"]) // heads,
        "layers": len(kinds), "n_mamba": n_m, "n_attn": n_a,
        # no expert layer: what flops/hybrid.py's functions look up of one
        "n_expert": 0, "num_experts": 0, "experts_held": 0, "top_k": 0, "expert_width": 0,
        "latent": 0, "shared_width": 0,
        # what kv_reserved_pct (pages only) and the generic readers look up
        "layer_types": ["full_attention"] * n_a, "window": 0,
        "heads": heads, "kv_heads": int(a["num_key_value_heads"]),
        "mamba_heads": mh, "mamba_groups": mg, "mamba_head_dim": mp, "state_size": mn,
        "conv_kernel": ck, "conv_channels": mh * mp + 2 * mg * mn,
        "state_bytes_per_slot": n_m * (mh * mp * mn * 4 + (ck - 1) * (mh * mp + 2 * mg * mn) * wb),
        "ffn_width": int(a["shared_intermediate_size"]),
        "tied": bool(a.get("tie_word_embeddings", False)),
        "vocab": int(a["vocab_size"]), "vocab_first": 0,
        "max_prompt": int(served["max_prompt_tokens"]), "max_new": int(served["max_new_tokens"]),
        "max_ctx": max_ctx, "slots": slots, "page_tokens": page,
        "pages_per_slot": -(-max_ctx // page),
        "kv_pages": int(gen.get("kv_pages") or 0) or slots * -(-max_ctx // page) + 1,
        "prefill_chunk": int(gen.get("prefill_chunk") or 0) or int(served["max_prompt_tokens"]),
        "weight_bytes": wb,
        # a control's configuration says what its reference rounds (`reference_answers`)
        "reference_inputs": cfg.get("check", {}).get("reference_inputs"),
    }


# -- weights by recipe -------------------------------------------------------------

class Model:
    """The architecture's numbers; draws one tensor or one layer at a time."""

    def __init__(self, arch: dict, seed: int, served_dtype="bfloat16") -> None:
        a = self.a = arch
        self.kinds = list(a["layer_types"])
        self.d, self.f = int(a["hidden_size"]), int(a["shared_intermediate_size"])
        self.eps = float(a.get("rms_norm_eps", 1e-5))
        self.heads, self.kv = int(a["num_attention_heads"]), int(a["num_key_value_heads"])
        self.hd = self.d // self.heads
        self.e = float(a.get("embedding_multiplier", 1.0))
        self.r = float(a.get("residual_multiplier", 1.0))
        self.att = float(a.get("attention_multiplier", self.hd ** -0.5))
        self.s = float(a.get("logits_scaling", 1.0))
        self.tied = bool(a.get("tie_word_embeddings", False))
        # The mixers' tensors are those `reference/hybrid.py` draws for a
        # pattern of M and *: the same names, shapes, fan-ins and ranges.
        scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}
        self.mixers = hy.Model({
            "hidden_size": self.d, "head_dim": self.hd, "layer_norm_epsilon": self.eps,
            "hybrid_override_pattern": "".join("M" if k == "mamba" else "*" for k in self.kinds),
            "mamba_num_heads": a["mamba_n_heads"], "mamba_head_dim": a["mamba_d_head"],
            "n_groups": a["mamba_n_groups"], "ssm_state_size": a["mamba_d_state"],
            "conv_kernel": a.get("mamba_d_conv", 4), "use_conv_bias": a.get("mamba_conv_bias", True),
            "num_attention_heads": self.heads, "num_key_value_heads": self.kv,
            "n_routed_experts": 0, "vocab_size": a["vocab_size"], "weight_scales": scales,
        }, seed, served_dtype)

    def embed(self) -> np.ndarray:
        return self.mixers.embed()

    def head(self) -> np.ndarray:
        """(d, vocab): the embedding transposed where the head is tied."""
        return self.embed().T if self.tied else self.mixers.head()

    def layer(self, i: int) -> dict:
        t, L, d, f = self.mixers.tensor, f"layer{i}", self.d, self.f
        w = self.mixers.layer(i)
        for name in ("w_gate", "w_up"):
            w[name] = t(f"{L}/{name}", (d, f), (d, f), (0, 0), "ffn_in", d)
        w["w_down"] = t(f"{L}/w_down", (f, d), (f, d), (0, 0), "ffn_out", f)
        return w


# -- the forward pass ----------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1))
def _mamba(dims: tuple, state_dtype: str, w: dict, u, s0, tail0):
    """`reference/hybrid.py`'s `_mamba` continued from where the sequence's
    earlier tokens left it: `s0` (H, P, N) the state and `tail0` (k - 1,
    channels) the convolution's last inputs (zeros before position 0). ->
    (the gated, normed rows (T, H, P), the state and the inputs it ends with)."""
    H, P, G, N, k, eps = dims
    t = u.shape[0]
    with jax.default_matmul_precision("highest"):
        z = jnp.einsum("td,dhp->thp", u, w["in_z"])
        pre = jnp.concatenate([jnp.einsum("td,dhp->thp", u, w["in_x"]).reshape(t, -1),
                               jnp.einsum("td,dgn->tgn", u, w["in_B"]).reshape(t, -1),
                               jnp.einsum("td,dgn->tgn", u, w["in_C"]).reshape(t, -1)], axis=1)
        dt = u @ w["in_dt"]
    cw = jnp.concatenate([w[f"conv_{p}"].reshape(k, -1) for p in "xBC"], axis=1)
    cb = jnp.concatenate([w[f"conv_bias_{p}"].reshape(-1) for p in "xBC"])
    padded = jnp.concatenate([tail0, pre], axis=0)
    act = jax.nn.silu(cb + sum(padded[j:j + t] * cw[j] for j in range(k)))
    x = act[:, :H * P].reshape(t, H, P)
    B = jnp.repeat(act[:, H * P:H * P + G * N].reshape(t, G, N), H // G, axis=1)   # by head
    C = jnp.repeat(act[:, H * P + G * N:].reshape(t, G, N), H // G, axis=1)
    delta = jax.nn.softplus(dt + w["dt_bias"])
    decay = jnp.exp(-jnp.exp(w["A_log"]) * delta)
    kept = jnp.dtype(state_dtype)

    def token(S, row):
        a_t, d_t, x_t, b_t, c_t = row
        S = a_t[:, None, None] * S.astype(jnp.float32) \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        S = S.astype(kept)
        return S, jnp.sum(S.astype(jnp.float32) * c_t[:, None, :], axis=-1)

    s_end, y = jax.lax.scan(token, s0.astype(kept), (decay, delta, x, B, C))
    y = y + w["D"][:, None] * x
    g = (y * jax.nn.silu(z)).reshape(t, G, -1)
    return hy._rms(g, eps).reshape(t, H, P), s_end, padded[t:]   # the gated norm's gain is ones


@functools.partial(jax.jit, static_argnums=(0,))
def _attention(dims: tuple, w: dict, u, k0, v0):
    """Causal attention of the rows `u` over themselves and the sequence's
    earlier keys and values `k0`, `v0` (T0, KV, hd). -> (out, all keys, all
    values)."""
    heads, kv, scale = dims
    t, t0 = u.shape[0], k0.shape[0]
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("td,dhk->thk", u, w["wq"])
        k = jnp.concatenate([k0, jnp.einsum("td,dhk->thk", u, w["wk"])], axis=0)
        v = jnp.concatenate([v0, jnp.einsum("td,dhk->thk", u, w["wv"])], axis=0)
        kh, vh = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
        see = t0 + jnp.arange(t)[:, None] >= jnp.arange(t0 + t)[None, :]
        s = jnp.einsum("qhd,khd->hqk", q, kh) * scale     # no position term of any kind
        o = jnp.einsum("hqk,khd->qhd",
                       jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1), vh)
        return jnp.einsum("qhd,hdo->qo", o, w["wo"]), k, v


@jax.jit
def _gated(v, w_gate, w_up):
    with jax.default_matmul_precision("highest"):
        return jax.nn.silu(v @ w_gate) * (v @ w_up)


def forward(m: Model, layers, tokens: list[np.ndarray], carry: list | None = None,
            low_precision: bool = False) -> tuple[list, list]:
    """The rows of `tokens` (ids, one array a sequence) through every layer,
    continued from `carry`: what the same sequences' EARLIER tokens left, a
    layer and a sequence (a Mamba-2 layer's state and its convolution's last
    inputs; an attention layer's keys and values), or None from position 0. ->
    (final hidden states before the last norm, the carry they leave). One call
    over a whole sequence is the plain full pass; the check makes it in two,
    the prompt while the server starts and the served tokens after, because
    the prompt does not wait for them. `layers`: an iterable of `Model.layer(i)`."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) * m.e for ids in tokens]
    del embed
    rnd = hy._round3_whole if low_precision else (lambda z: z)
    kept = "bfloat16" if low_precision else "float32"
    mm, t_kind, left = m.mixers, {}, []
    dims = (mm.mh, mm.mp, mm.mg, mm.mn, mm.conv_k, m.eps)
    zeros = (jnp.zeros((mm.mh, mm.mp, mm.mn), jnp.float32),
             jnp.zeros((mm.conv_k - 1, mm.mh * mm.mp + 2 * mm.mg * mm.mn), jnp.float32))
    none = (jnp.zeros((0, m.kv, m.hd), jnp.float32),) * 2
    with jax.default_matmul_precision("highest"):
        for i, (kind, w) in enumerate(zip(m.kinds, layers, strict=True)):
            t0 = time.monotonic()
            if low_precision:  # the control: every kernel
                w = {k: (v if k in EXACT else np.asarray(hy._round3_whole(v)))
                     for k, v in w.items()}
            w = {k: jnp.asarray(v) for k, v in w.items()}
            left.append([])
            for n, x in enumerate(xs):
                before = carry[i][n] if carry else (zeros if kind == "mamba" else none)
                if x.shape[0] == 0:   # no row of this sequence in this call
                    left[i].append(before)
                    continue
                u = rnd(hy._rms(x, m.eps))
                if kind == "mamba":
                    g, *after = _mamba(dims, kept, {k: v for k, v in w.items() if k != "w_out"},
                                       u, *before)
                    y = hy._project(rnd(g).reshape(g.shape[0], -1), w["w_out"].reshape(-1, m.d))
                else:
                    y, *after = _attention((m.heads, m.kv, m.att), w, u, *before)
                left[i].append(tuple(after))
                x = x + m.r * y
                v = rnd(hy._rms(x, m.eps))
                f = hy._project(rnd(_gated(v, w["w_gate"], w["w_up"])), w["w_down"])
                xs[n] = (x + m.r * f).block_until_ready()
            del w
            t_kind[kind] = t_kind.get(kind, 0.0) + time.monotonic() - t0
    print("[reference] " + str(sum(len(s) for s in tokens)) + " tokens through "
          + ", ".join(f"{m.kinds.count(k)} {k} layers (each with its feed-forward) in "
                      f"{t_kind[k]:.1f} s" for k in t_kind), flush=True)
    return xs, left


def hidden_states(m: Model, sequences: list[np.ndarray], low_precision: bool = False) -> list:
    """Final hidden states of each whole sequence, in ONE pass from position
    0; layers outermost, each drawn once and dropped."""
    return forward(m, (m.layer(i) for i in range(len(m.kinds))), sequences, None,
                   low_precision)[0]


def _log_softmax(m: Model, head, rows) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.nn.log_softmax(hy._rms(rows, m.eps) @ head / m.s, axis=-1))


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low_precision: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the vocabulary at positions
    `first_row` onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low_precision)
    head = jnp.asarray(m.head())
    return [_log_softmax(m, head, h[r:]) for h, r in zip(hs, first_rows)]


def prompt_pass(m: Model, prompts: list[np.ndarray], low_precision: bool = False, stop=None):
    """The prompts' part of the check's pass: every layer drawn once and KEPT
    (float32 copies of every kernel, 12.8 GB at the cell's size), the prompts
    taken through them. -> (the layers, each prompt's last hidden state, the
    carry). `stop`: an event that ends it at the next layer (`in_background`)."""
    layers = []

    def drawn():
        for i in range(len(m.kinds)):
            if stop is not None and stop.is_set():
                raise RuntimeError("the prompts' pass was stopped: the run is ending")
            layers.append(m.layer(i))
            yield layers[-1]

    hs, carry = forward(m, drawn(), prompts, None, low_precision)
    return layers, [h[-1:] for h in hs], carry


def in_background(m: Model, prompts: list[np.ndarray], low_precision: bool) -> Future:
    """`prompt_pass` in a thread of its own, because the served tokens' part
    waits for the server and this does not. A daemon thread that the
    interpreter's exit stops at the next layer and waits for: a run that ends
    early (no accelerator, a server that does not start) exits with its own
    code and not in the middle of a product."""
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


# -- what the harness calls (benchmark/README.md, "a family that generates") --------

prepare = hy.prepare   # no checkpoint: the published keys as the program's config file


def reference_answers(ref: dict, inputs: list[dict], sizes: dict) -> dict:
    """The pass is teacher-forced on the served tokens, so their part waits
    for them (`compare`); the prompts' part starts now, while the server
    starts."""
    model = Model(sizes["arch"], ref["seed"], ref["dtype"])
    low = sizes.get("reference_inputs") == "3-bit-mantissa"
    prompts = [np.asarray(inp["ids"], np.int64) for inp in inputs]
    return {"inputs": inputs, "sizes": sizes, "model": model, "low": low,
            "prompts": in_background(model, prompts, low)}


def compare(served: list[dict], reference: dict, cfg: dict) -> tuple[float, str]:
    """`reference/hybrid.py`'s statistic over this family's pass: a generated
    position's number is the RMS of its eight centred differences; the
    statistic is the largest, over the requests, of the lower quartile of a
    request's positions, beside the RMS over all positions against
    `check.rms_limit` (the number compared with `check.limit` is the larger of
    the quartile and the RMS scaled by `limit / rms_limit`)."""
    sz = reference["sizes"]
    after = []
    for answer, inp in zip(served, reference["inputs"], strict=True):
        tokens = [int(t) for t in answer.get("tokens", [])]
        lp = answer.get("logprobs") or {}
        if len(tokens) != inp["max_new"] or answer.get("n_tokens") != len(tokens) \
                or np.shape(lp.get("ids")) != (len(tokens), LOGPROBS) \
                or np.shape(lp.get("values")) != (len(tokens), LOGPROBS):
            return float("inf"), (f"logprob_rms=inf: a request of {inp['max_new']} tokens with "
                                  f"logprobs {LOGPROBS} got {len(tokens)} tokens, logprobs of "
                                  f"shape {np.shape(lp.get('ids'))}")
        ids = np.asarray(tokens[:-1], np.int64)    # the last served token predicts nothing served
        if len(ids) and (ids.min() < 0 or ids.max() >= sz["vocab"]):
            return float("inf"), "logprob_rms=inf: a served token lies outside the vocabulary"
        after.append(ids)
    low, m = reference["low"], reference["model"]
    assert low == (cfg["check"].get("reference_inputs") == "3-bit-mantissa")
    layers, last, carry = reference["prompts"].result()
    # Every request's served tokens as rows of ONE length (ids of 0 behind the
    # shorter ones: the model is causal, so a row never sees a later one): the
    # host compiles one set of programs for them, not one a request.
    longest = max(len(ids) for ids in after)
    hs, _ = forward(m, layers, [np.pad(ids, (0, longest - len(ids))) for ids in after], carry, low)
    hs = [h[:len(ids)] for h, ids in zip(hs, after)]
    del layers, carry
    # A prompt's last row predicts the first served token, a served token's row the next.
    head = jnp.asarray(m.head())
    gaps = [hy.centred_gap(a, _log_softmax(m, head, jnp.concatenate([h0, h], axis=0)), 0)
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
