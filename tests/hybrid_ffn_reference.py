"""The plain reference of the `hybrid_ffn` family for tier-1 (ISSUE 40): a
language model whose layers are two sublayers each (a mixer chosen by
`layer_types`, Mamba-2 or attention without a position term, then a dense
SwiGLU feed-forward) under four scalar multipliers, in straightforward float32
under `jax.default_matmul_precision("highest")`: the recurrence token by token,
full causal attention, no cache, no chunks, no batching, no kernel. It imports
nothing of `tpuserve`. The Mamba-2 layer and the weights' recipe are
`tests/hybrid_reference.py`'s. `benchmark/reference/hybrid_ffn.py` holds the
benchmark's copy of the same forward pass (its header has the equations and
what is assumed); `tests/test_hybrid_ffn.py` holds the two to the same numbers.

With e = `embedding_multiplier`, r = `residual_multiplier`, a =
`attention_multiplier`, s = `logits_scaling`, E the embedding: `h_0 = e E[ids]`;
layer i: `h <- h + r mixer_i(RMSNorm(h; g1_i))`, then `h <- h + r (silu(v W_gate)
* (v W_up)) W_down` with `v = RMSNorm(h; g2_i)`; attention's scores are `a q.k`
with no position term; `logits = RMSNorm(h; g_f) E^T / s` where the head is tied.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from tests import hybrid_reference as hy

LOGPROBS = hy.LOGPROBS
DEFAULT_SCALES = {"embed": 1.0, "head": 1.0, "qk": 2.0, "v": 1.0, "o": 1.0, "ffn_in": 1.0,
                  "ffn_out": 1.0, "ssm_in": 1.0, "ssm_bc": 2.0, "ssm_dt": 1.0, "ssm_out": 1.0,
                  "conv": 1.0, "conv_bias": 0.1, "ssm_d": 0.1}
# The float32 vectors a lower-precision pass leaves alone: no matrix product's input.
EXACT = ("dt_bias", "A_log", "D")


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
        # The mixers' tensors are those `tests/hybrid_reference.py` draws for a
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

@functools.partial(jax.jit, static_argnums=(0,))
def _attention(dims: tuple, w: dict, u):
    heads, kv, scale = dims
    t = u.shape[0]
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("td,dhk->thk", u, w["wq"])
        k = jnp.einsum("td,dhk->thk", u, w["wk"])
        v = jnp.einsum("td,dhk->thk", u, w["wv"])
        k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
        see = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.einsum("qhd,khd->hqk", q, k) * scale      # no position term of any kind
        o = jnp.einsum("hqk,khd->qhd",
                       jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1), v)
        return jnp.einsum("qhd,hdo->qo", o, w["wo"])


@jax.jit
def _gated(v, w_gate, w_up):
    with jax.default_matmul_precision("highest"):
        return jax.nn.silu(v @ w_gate) * (v @ w_up)


def hidden_states(m: Model, sequences: list[np.ndarray], low_precision: bool = False) -> list:
    """Final hidden states (before the last norm) of each sequence of ids;
    layers outermost, so each layer is drawn once and dropped."""
    embed = m.embed()
    xs = [jnp.asarray(embed[np.asarray(ids)]) * m.e for ids in sequences]
    del embed
    rnd = hy._round3_whole if low_precision else (lambda z: z)
    t_kind: dict[str, float] = {}
    mm = m.mixers
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(m.kinds):
            t0 = time.monotonic()
            w = m.layer(i)
            if low_precision:  # the control: every kernel
                w = {k: (v if k in EXACT else np.asarray(hy._round3_whole(v)))
                     for k, v in w.items()}
            ffn = {k: jnp.asarray(w.pop(k)) for k in ("w_gate", "w_up", "w_down")}
            for n, x in enumerate(xs):
                u = rnd(hy._rms(x, m.eps))
                if kind == "mamba":
                    g, w_out = hy.mamba(mm, w, u, jnp.bfloat16 if low_precision else jnp.float32)
                    y = hy._project(rnd(g).reshape(g.shape[0], -1),
                                    jnp.asarray(w_out).reshape(-1, m.d))
                else:
                    y = _attention((m.heads, m.kv, m.att),
                                   {k: jnp.asarray(v) for k, v in w.items()}, u)
                x = x + m.r * y
                v = rnd(hy._rms(x, m.eps))
                f = hy._project(rnd(_gated(v, ffn["w_gate"], ffn["w_up"])), ffn["w_down"])
                xs[n] = (x + m.r * f).block_until_ready()
            del w, ffn
            t_kind[kind] = t_kind.get(kind, 0.0) + time.monotonic() - t0
    print("[reference] " + str(sum(len(s) for s in sequences)) + " tokens through "
          + ", ".join(f"{m.kinds.count(k)} {k} layers (each with its feed-forward) in "
                      f"{t_kind[k]:.1f} s" for k in t_kind), flush=True)
    return xs


def log_probs(m: Model, sequences: list[np.ndarray], first_rows: list[int],
              low_precision: bool = False) -> list[np.ndarray]:
    """Per sequence: log-softmax over the vocabulary at positions
    `first_row` onwards (row p predicts position p + 1)."""
    hs = hidden_states(m, sequences, low_precision)
    head = jnp.asarray(m.head())
    with jax.default_matmul_precision("highest"):
        return [np.asarray(jax.nn.log_softmax(hy._rms(h[r:], m.eps) @ head / m.s, axis=-1))
                for h, r in zip(hs, first_rows)]
