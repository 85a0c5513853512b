"""A decoder-only language model with latent attention and routed experts
under a HYPER-CONNECTED RESIDUAL of several streams (ISSUE 46): ``mla``'s
layer (its attention, cache leaves, both forms, both kernels, router and
experts, by inheritance) whose two sublayers each read a mix of ``hc_mult``
residual streams and write back into all of them through their own three maps
(manifold-constrained hyper-connections, ``tpuserve/ops/hyper.py``, which has
the equations). Built from a published ``config.json`` and served through the
generation engine as ``mla`` is. Nothing here knows a model's name.

THE STREAM of a token is ``X`` in R^(n x d), ``n = hc_mult``, one row of ``n
d`` values in the served type from the entry to the exit. ENTRY: ``X[j] =
embed[id]`` for every ``j``. A LAYER is two sublayers, ``F_1`` latent attention
and ``F_2`` the feed-forward (dense in the first ``first_k_dense_replace``
layers, routed experts plus the shared expert after), each as ``mla`` computes
it behind its own pre-norm gain, and each with ITS OWN ``Phi`` (n d, 2 n +
n^2), ``alpha`` (pre, post, res) and biases ``b_pre``, ``b_post`` (n,), ``b_res``
(n, n): ``u = sum_j H_pre[j] X[j]``, ``y = F(RMSNorm(u; g))``, ``X'[i] = sum_j
H_res[i, j] X[j] + H_post[i] y`` with ``H_res`` the Sinkhorn projection
(``hc_sinkhorn_iters`` times columns then rows, ``hc_eps`` in the sums) of
``exp`` of logits held in ``[mhc_h_res_clamp_min, mhc_h_res_clamp_max]``; the
maps in float32 whatever the served type, the flattened norm without a gain
at ``rms_norm_eps``. EXIT: ``x = sum_j X[j]``, then the final norm and the head.
With ``n = 1`` and unit maps this is ``mla``'s layer.

ATTENTION is ``mla``'s; this family's configs carry a yarn ``rope_scaling``,
which ``mla._read_attention`` reads in DeepSeek's convention (the magnitude on
the score). The cache is ``mla``'s two leaves a layer: the streams keep
nothing between launches.

THE DRAW (recipe ``counter-bell-v1``, three roles more than ``mla``'s):
``hc_phi`` is ``Phi``'s scale over ``sqrt(n d)``, so ``p``, ``q`` and ``r`` have
that deviation a token; ``hc_alpha`` the centre of ``alpha_pre`` and
``alpha_post``, each a bell within half of it either way (``alpha_res``:
``RES_ALPHA`` of that); ``hc_bias`` a third of the half-width of every bias's
bell, about ``b_pre`` = 0, ``b_post`` = ``POST_BIAS`` (``H_post`` about 2
sigmoid(POST_BIAS): what keeps the streams' RMS from growing as a plain
residual's would) and ``b_res`` = ``RES_DIAGONAL`` x identity (a stream mostly
keeps itself, and really mixes).

WHERE THE MAPS AND THE MIXES RUN is chosen when a program is traced
(``_hc_path``, as ``mla._walk`` chooses a walk): ``kernel`` on the TPU at
shapes ``hyper.fits`` takes (a bfloat16 launch of whole row tiles: two kernel
calls a sublayer, ``hyper.enter`` and ``hyper.leave``, ISSUE 47), ``xla``
everywhere else (the CPU, float32, a step's few rows: ``hyper.maps``,
``mix_in``, ``mix_out``).

WHAT IS COUNTED AND NAMED: ``hc_maps_total{phase=,path=kernel|xla}``, live
tokens times sublayers mapped, by where they were (two a layer: over the
tokens it reads 2 x layers, and anything else means a sublayer ran without its
maps); the scope ``hc_mix`` around the maps and both mixes of every sublayer,
outside ``mla_prefill`` / ``mla_decode``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.config import ModelConfig
from tpuserve.models import mla
from tpuserve.models.paged_lm import Column, counted, read_config_file, rms_norm, series
from tpuserve.ops import hyper

DEFAULT_SCALES = {**mla.DEFAULT_SCALES, "hc_phi": 1.0, "hc_alpha": 3.0, "hc_bias": 0.1}
POST_BIAS = -3.0     # b_post's centre: H_post 0.5 on average under alpha_post q of deviation 3
RES_DIAGONAL = 1.25  # b_res's diagonal: a stream keeps about half of itself and really mixes
# alpha_res's centre over the other two's: the residual map's logits spread by a
# seventh of what p and q do, because twenty Sinkhorn iterations bring the column
# sums within 1e-4 of 1 only where the logits lie within a few units of each
# other (a deviation of 0.5 about a diagonal of 1: 1e-5 at worst over 200,000
# tokens; of 0.5 about 2: 3e-3; of 1 about 2: 2e-2).
RES_ALPHA = 0.15
SUBLAYERS = ("hc1", "hc2")   # a layer's two sets of maps: attention's, the feed-forward's


class HyperLatentServing(mla.LatentServing):
    # ``mla``'s twelve columns and the sublayers mapped, over live tokens, by
    # where the launch's maps and mixes ran (``_hc_path``: the walks' two names).
    COLUMNS = (*mla.LatentServing.COLUMNS,
               *(Column(lambda model, stats, counts, path=path:
                        counts["hc_maps"] if counts["hc_path"] == path else 0,
                        series("hc_maps_total", f",path={path}")) for path in mla.WALKS))
    # A launch of 4,096 rows in eight tiles of 512 (the expanded form and its
    # kernel from 171 rows up): a prompt of 2,100 tokens pads a fifth tile of
    # 512 where it would pad a third of 1,024. 8.27 against 7.93 requests/s on
    # one seed of the family's cell (my chip runs, PR 46; PERF.md section 6).
    TILE_ROWS = 256

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.n_streams = int(a.get("hc_mult", 1))
        self.hc_iters = int(a.get("hc_sinkhorn_iters", 20))
        self.hc_eps = float(a.get("hc_eps", 1e-6))
        self.hc_clamp = (float(a.get("mhc_h_res_clamp_min", -30.0)),
                         float(a.get("mhc_h_res_clamp_max", 30.0)))
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    # -- params ---------------------------------------------------------------
    def _maps(self):
        """The path of every sublayer's maps: two a layer."""
        return [(f"layer{i}", k) for i in range(self.n_layers) for k in SUBLAYERS]

    def _tensors(self):
        yield from super()._tensors()
        n, nd = self.n_streams, self.n_streams * self.d
        shape = (nd, 2 * n + n * n)
        for at in self._maps():
            yield (*at, "phi"), shape, shape, (0, 0), self.scales["hc_phi"], nd

    def _vectors(self):
        yield from super()._vectors()
        n, a, b3 = self.n_streams, self.scales["hc_alpha"], 3.0 * self.scales["hc_bias"]
        for at in self._maps():
            yield (*at, "alpha"), (3,), (3,), (0,), 0.5 * a, 1.5 * a   # ``draw_params``: res x RES_ALPHA
            yield (*at, "b_pre"), (n,), (n,), (0,), -b3, b3
            yield (*at, "b_post"), (n,), (n,), (0,), POST_BIAS - b3, POST_BIAS + b3
            yield (*at, "b_res"), (n, n), (n, n), (0, 0), -b3, b3

    def draw_params(self, seed: int) -> Any:
        p = super().draw_params(seed)
        for layer, k in self._maps():
            hp = p[layer][k]
            hp["alpha"] = hp["alpha"] * jnp.asarray([1.0, 1.0, RES_ALPHA], jnp.float32)
            hp["b_res"] = hp["b_res"] + jnp.float32(RES_DIAGONAL) * jnp.eye(self.n_streams)
        return p

    # -- device math --------------------------------------------------------------
    def _embed(self, params, ids):
        """The entry: every stream begins as the token's embedding."""
        return jnp.tile(super()._embed(params, ids), (1, self.n_streams))

    def _head(self, params, x):
        """The exit, ``x = sum_j X[j]`` (a float32 sum rounded to the served
        type), then the final norm and the head."""
        xs = x.astype(jnp.float32).reshape(x.shape[0], self.n_streams, self.d)
        return super()._head(params, jnp.sum(xs, axis=1).astype(x.dtype))

    def _hc_path(self, x) -> str:
        """Where a launch's maps and mixes run, chosen when the program is
        traced: ``kernel`` (on the TPU at shapes the kernels take) or ``xla``."""
        if jax.default_backend() != "tpu":  # tps-ok[TPS503]: at trace time
            return "xla"
        fits = hyper.fits(x.shape[0], self.n_streams, self.d, x.dtype)
        return "kernel" if fits else "xla"

    def _sublayer(self, hp: dict, x, m: dict, f):
        """One sublayer ``f`` (the mixed stream (T, d) -> ((T, d) float32, its
        counts)) under its maps ``hp``: (T, n d) -> ((T, n d), the counts)."""
        n, path = self.n_streams, m.setdefault("hc_path", self._hc_path(x))
        a = (n, self.eps, self.hc_iters, self.hc_eps, self.hc_clamp)
        with jax.named_scope("hc_mix"):
            if path == "kernel":
                u, h = hyper.enter(x, hp, *a)
            else:
                h_pre, h_post, h_res = hyper.maps(x, hp, *a)
                u = hyper.mix_in(x, h_pre)
        y, st = f(u)
        with jax.named_scope("hc_mix"):
            x = hyper.leave(x, y, h, n) if path == "kernel" else \
                hyper.mix_out(x, h_res, h_post, y)
        m["hc_maps"] = m.get("hc_maps", 0) + 1   # at trace time: sublayers that took their maps
        return x, st

    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        def attend(u):
            with jax.named_scope(m["scope"]):
                return self._attention(lp, rms_norm(u, lp["norm1"], self.eps), i, c, m), None

        x, _ = self._sublayer(lp["hc1"], x, m, attend)
        return self._sublayer(lp["hc2"], x, m, lambda u: self._ffn(
            lp, i, rms_norm(u, lp["norm2"], self.eps), m["live"]))

    def _counts(self, m: dict) -> dict:
        return {**super()._counts(m), "hc_maps": m.get("hc_maps", 0) * jnp.sum(m["live"]),
                "hc_path": m.get("hc_path", "xla")}


def create(cfg: ModelConfig) -> HyperLatentServing:
    return HyperLatentServing(cfg)
