"""``hybrid_ffn``'s sibling for a model whose SECOND sublayer is a routed block
in every layer, behind a Mamba-2 mixer and behind attention alike (ISSUE 64):
softmax-routed SwiGLU experts with no selection bias and one shared expert on
the same normed rows, under the residual multiplier, built from a published
``config.json`` and served through the generation engine with paged KV AND a
recurrent state a slot. The mixers, the four multipliers, the tied head, the
cache and the entry points are ``hybrid_ffn``'s; the experts' tensors, dispatch
and counts are ``hybrid_delta.RoutedExperts``.

Nothing here knows a model's name. With ``r`` = ``residual_multiplier`` and
``v = RMSNorm(h; g2_i)``, layer ``i`` after its mixer (``hybrid_ffn``'s header):

- ``l = v W_r`` in float32 over all ``num_local_experts``, no bias; ``P`` = the
  ``num_experts_per_tok`` largest of ``l``; ``w = softmax(l[P])`` over the picked
  ALONE (``topk_route(scoring="softmax", normalize=True)``: softmax is monotone
  and the picks' sum divides out, so the picks and the weights are the same);
- ``routed = sum_{j in P} w_j (silu(v Wg_j) * (v Wu_j)) Wd_j``, the experts
  ``intermediate_size`` wide (the published ``input_linear`` is ``[gate | up]``:
  here two tensors, as ``hybrid_ffn``'s dense layer has them);
- ``shared = (silu(v Sg) * (v Su)) Sd``, ``shared_intermediate_size`` wide;
- ``h <- h + r (routed + shared)``, the sum in float32 before the multiplier.

THE SHARE, as ``decoder`` reads it: ``share.experts_held = [first, count]`` of the
router's experts (a pick on another chip's expert adds nothing here; the shared
expert, the router, the mixers and every norm are whole) and ``share.vocab_rows =
[first, count]``. No code stands in for the other chips or their exchange.

In a device trace ``jax.named_scope("moe_layer")`` (``RoutedExperts._routed``'s,
as every routed family's block since ISSUE 66) holds the router, the picks, the
dispatch and the experts' products (``moe_route``, ``moe_dispatch``,
``moe_experts`` inside it, ``ops/moe.py``) and ``jax.named_scope("moe_shared")``
the shared expert.
"""

from __future__ import annotations

import jax

from tpuserve.config import ModelConfig
from tpuserve.models import hybrid_ffn
from tpuserve.models.hybrid_delta import RoutedExperts
from tpuserve.models.hybrid_ffn import HybridFfnServing
from tpuserve.models.mixers import SCAN_COLUMNS, SSM_COLUMNS
from tpuserve.models.paged_lm import (COMPACT_COLUMN, CONTEXT_COLUMN, EXPERT_COLUMNS,
                                      SAMPLE_COLUMNS, read_config_file, rms_norm)

# ``hybrid_ffn``'s roles (``ffn_in`` / ``ffn_out``: the shared expert's and the
# routed experts' first kernels), the router's, and ``expert_out``: the routed
# experts' second kernel alone, so that a configuration can draw it apart.
DEFAULT_SCALES = {**hybrid_ffn.DEFAULT_SCALES, "router": 1.0, "expert_out": 1.0}


class HybridFfnMoeServing(RoutedExperts, HybridFfnServing):
    # The expert layer's four and the context, the scan layers' four, the
    # compact dispatches, a launch's scans by where they ran, and the steps by
    # the sampler's branch.
    COLUMNS = (*EXPERT_COLUMNS, CONTEXT_COLUMN, *SSM_COLUMNS, COMPACT_COLUMN, *SCAN_COLUMNS,
               *SAMPLE_COLUMNS)
    ROUTED = True
    SHARE_KEYS = ("experts_held", "vocab_rows")
    route_scoring = "softmax"  # over all logits, the picks' weights over their own sum
    route_bias = False         # the published router has none

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        a = read_config_file(cfg)
        self.e_layers = list(range(self.n_layers))
        self.n_experts = int(a["num_local_experts"])
        self.top_k = int(a["num_experts_per_tok"])
        if not 0 < self.top_k <= self.n_experts:
            raise ValueError(f"{cfg.name}: num_experts_per_tok = {self.top_k} of "
                             f"num_local_experts = {self.n_experts}")
        self.expert_width = int(a["intermediate_size"])
        self.shared_width = self.ffn_width
        self.norm_topk, self.route_scale = True, 1.0
        share = a.get("share", {})
        self.e_first, self.e_count = share.get("experts_held", [0, self.n_experts])
        if not 0 <= self.e_first <= self.e_first + self.e_count <= self.n_experts:
            raise ValueError(f"{cfg.name}: share.experts_held = {share['experts_held']} "
                             f"of {self.n_experts} experts")
        self.scales = {**DEFAULT_SCALES, **a.get("weight_scales", {})}

    # -- params ---------------------------------------------------------------
    def _tensors(self):
        """(path, shape held here, full shape, start, role, fan-in) of every
        matrix, in a fixed order: no dense feed-forward, the routed block's."""
        yield from self._vocab_tensors()
        yield from self._mamba_tensors()
        yield from self._attention_tensors()
        yield from self._expert_tensors()

    def share_stats(self) -> dict:
        """``/stats``: what of each layer is held here."""
        return {"experts_held": [self.e_first, self.e_count], "experts": self.n_experts,
                "vocab_rows": [self.v_first, self.vocab], "vocab": self.vocab_full}

    # -- device math --------------------------------------------------------------
    def _ffn(self, lp, x, live):
        """The stream through the routed block -> (the stream, the expert
        layer's counts)."""
        v = rms_norm(x, lp["norm2"], self.eps)
        y, stats = self._routed(lp, v, live)   # under ``moe_layer``
        with jax.named_scope("moe_shared"):
            y = y + self._shared(lp, v)
        return self._add(x, y), stats

    def _layer(self, i: int, lp: dict, x, c: dict, m: dict):
        y = self._mixer(i, lp, rms_norm(x, lp["norm1"], self.eps), c, m)
        return self._ffn(lp, self._add(x, y), m["live"])


def create(cfg: ModelConfig) -> HybridFfnMoeServing:
    return HybridFfnMoeServing(cfg)
