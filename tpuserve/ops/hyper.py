"""A residual of several streams with per-sublayer maps: manifold-constrained
hyper-connections (Xie et al., "mHC", arXiv:2512.24880, over Zhu et al.,
"Hyper-Connections", arXiv:2409.19606), as ISSUE 46 writes them out. Plain
XLA, the maps in float32 whatever the served type; no kernel.

A token's stream is ``X`` in R^(n x d), held as ONE row of ``n d`` values
(stream ``j`` in columns ``[j d, (j + 1) d)``: whole 128-lane tiles at any
width that is a multiple of 128, where a middle axis of ``n`` = 4 would pad
every tile fourfold). A sublayer ``F`` with its own ``Phi`` (n d, 2 n + n^2),
scalars ``alpha`` (pre, post, res) and biases ``b_pre``, ``b_post`` (n,),
``b_res`` (n, n) computes, a token::

    vt          = vec(X) / sqrt(mean(vec(X)^2) + eps)        RMSNorm, no gain
    [p | q | r] = vt Phi                                     float32, HIGHEST
    H_pre  = sigmoid(alpha_pre p + b_pre)                    (n,)   in (0, 1)
    H_post = 2 sigmoid(alpha_post q + b_post)                (n,)   in (0, 2)
    M      = exp(clip(alpha_res mat(r) + b_res, lo, hi))     (n, n)
    iters times:  M <- M / (column sums + hc_eps);  M <- M / (row sums + hc_eps)
    H_res  = M                      doubly stochastic to the iteration's error
    u      = sum_j H_pre[j] X[j]                             float32, rounded
    y      = F(u)                                            (d,) float32
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y            float32, rounded

Rows of ``H_res`` index the OUTGOING stream. ``maps`` returns the three with
the tokens LAST, (n, T) and (n, n, T): the Sinkhorn iterations are then
elementwise passes over whole lane rows of tokens (sixteen rows of T), where
(T, n, n) would put a 4 x 4 block into every tile of 8 x 128.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sinkhorn(m: jax.Array, iters: int, hc_eps: float) -> jax.Array:
    """``m`` (n, n, ...) positive: ``iters`` times columns then rows, each
    over its sum plus ``hc_eps``."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + hc_eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + hc_eps)
    return m


def maps(x: jax.Array, hp: dict, n: int, eps: float, iters: int, hc_eps: float,
         clamp: tuple[float, float]):
    """The stream ``x`` (T, n d) in the served type and one sublayer's
    tensors ``hp`` (``phi``, ``alpha``, ``b_pre``, ``b_post``, ``b_res``) ->
    ``H_pre`` (n, T), ``H_post`` (n, T), ``H_res`` (n, n, T), float32."""
    f32 = jnp.float32
    v = x.astype(f32)
    vt = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    pqr = jnp.matmul(vt, hp["phi"].astype(f32), precision=jax.lax.Precision.HIGHEST).T
    a = hp["alpha"].astype(f32)
    h_pre = jax.nn.sigmoid(a[0] * pqr[:n] + hp["b_pre"].astype(f32)[:, None])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * pqr[n:2 * n] + hp["b_post"].astype(f32)[:, None])
    logits = a[2] * pqr[2 * n:].reshape(n, n, -1) + hp["b_res"].astype(f32)[:, :, None]
    return h_pre, h_post, sinkhorn(jnp.exp(jnp.clip(logits, *clamp)), iters, hc_eps)


def _weighted(x: jax.Array, w) -> jax.Array:
    """``sum_j w[j] X[j]`` in float32: ``x`` (T, n d), ``w`` n weights (T,) -> (T, d)."""
    d = x.shape[-1] // len(w)
    acc = None
    for j, wj in enumerate(w):
        term = wj[:, None] * x[:, j * d:(j + 1) * d].astype(jnp.float32)
        acc = term if acc is None else acc + term
    return acc


def mix_in(x: jax.Array, h_pre: jax.Array) -> jax.Array:
    """What the sublayer reads: ``u = sum_j H_pre[j] X[j]``, (T, n d) -> (T, d),
    a float32 sum rounded to the stream's type."""
    return _weighted(x, list(h_pre)).astype(x.dtype)


def mix_out(x: jax.Array, h_res: jax.Array, h_post: jax.Array, y: jax.Array) -> jax.Array:
    """What the sublayer leaves: ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i]
    y`` with ``y`` (T, d) float32, a float32 sum rounded to the stream's type
    -> (T, n d)."""
    y = y.astype(jnp.float32)
    return jnp.concatenate([_weighted(x, list(h_res[i])) + h_post[i][:, None] * y
                            for i in range(h_post.shape[0])], axis=-1).astype(x.dtype)
