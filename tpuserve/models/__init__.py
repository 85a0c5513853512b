"""Model zoo (SURVEY.md §2 C4): the families the server can build.

Each family implements the ``ServingModel`` interface in ``base.py``:
a jittable on-device ``forward`` (with fused resize/normalize preproc and
on-device postproc like top-k / NMS), host-side request decode, and
regex partition rules for tensor parallelism.

Families (BASELINE.json ``configs``):
- resnet50       — ResNet-50 ImageNet classify
- mobilenetv3    — MobileNetV3-Large, batch=1 latency mode
- bert           — BERT-base text classification, bucketed seq lens
- efficientdet   — EfficientDet-D0 detection with fixed-shape NMS
- sd15           — Stable Diffusion 1.5 txt2img, fori_loop denoise
- textgen        — autoregressive prefix-LM text generation (KV-cache
                   decode via the iteration-level engine, ISSUE 9)
- decoder        — a decoder-only language model built from a published
                   config.json: window and full attention, routed experts
                   with a share, two cache kinds (ISSUE 28)
- hybrid         — a language model whose layers are single mixers by a
                   pattern string (Mamba-2 state-space, attention, routed
                   experts in a latent), built from a published config.json:
                   a recurrent state a slot beside the paged KV (ISSUE 32)
- hybrid_ffn     — a language model whose layers are two sublayers each (a
                   mixer by a list: Mamba-2 or attention with no position
                   term, then a dense SwiGLU) under the embedding, residual,
                   attention and logit multipliers, with a tied head, built
                   from a published config.json (ISSUE 40)
- mla            — a language model with latent attention (MLA: one
                   compressed row a token a layer in the page pool, an
                   absorbed decode and an expanded prefill form) and routed
                   SwiGLU experts, built from a published config.json
                   (ISSUE 34)
- mla_sc         — ``mla``'s attention in DOUBLE layers: two latent attentions
                   and two dense SwiGLUs a layer, and a routed layer on a
                   shortcut whose router also picks zero-compute (identity)
                   outputs; a share of the experts and the vocabulary
                   (ISSUE 42)
- mla_hc         — ``mla``'s layer under a HYPER-CONNECTED residual of several
                   streams: each sublayer reads a mix of the streams and writes
                   into all of them through its own pre, post and
                   Sinkhorn-projected residual maps; yarn on the latent
                   attention in DeepSeek's convention (ISSUE 46)
- decoder_sink   — ``decoder``'s sibling for window and global attention whose
                   keys are wider than their values and whose KV heads and
                   cache rows go by the layer's kind, a learned sink in the
                   window layers' softmax, sigmoid-routed experts with no
                   shared one, and a grouped decode walk over the global
                   layers' pages (ISSUE 49)
- hybrid_delta   — ``hybrid_ffn``'s sibling for a model whose recurrent layers
                   are gated delta-rule linear attention (a decay a channel, a
                   state that is read before it is written, one kernel call a
                   step) and whose softmax layers have no position term and an
                   elementwise output gate, with sigmoid-routed experts and a
                   shared one in every layer; a share of the experts and the
                   vocabulary (ISSUE 53)
- eva            — ``decoder``'s sibling for EVA attention: every query over
                   the exact keys of its own ALIGNED window and one
                   learned-pooled summary row a chunk of every earlier window
                   in one softmax; a ring a slot beside pages of summary rows
                   in one pool a layer, walked as one virtual block table; a
                   float32 stream, gains ``1 + g``, a head of several
                   prediction blocks of which the first is served (ISSUE 55)
- hybrid_conv    — ``hybrid_delta``'s sibling for a model whose recurrent layers
                   are gated short convolutions (a slot's whole state is the
                   convolution's last rows: one leaf, no float32 state) and
                   whose attention has an RMSNorm a head on queries and keys
                   and a rotary embedding, with dense SwiGLUs in the leading
                   layers and sigmoid-routed experts with no shared one in the
                   rest, all held (ISSUE 59)
- mla_sel        — ``mla``'s sibling whose attention runs over the positions a
                   learned indexer picks (an index key a token in a third page
                   leaf, the exact ``index_topk`` largest scores a query), with
                   group-limited routed experts and a share of each layer
                   (ISSUE 62)
- hybrid_ffn_moe — ``hybrid_ffn``'s sibling whose second sublayer is a routed
                   block in EVERY layer, behind the Mamba-2 mixers and behind
                   attention alike: softmax-routed SwiGLU experts with no
                   selection bias (the picks' weights a softmax over the picked
                   alone) and one shared expert, under the residual multiplier;
                   a share of the experts and the vocabulary (ISSUE 64)
- hybrid_blk     — ``hybrid_ffn``'s sibling for a model whose layers are linear
                   attention with a constant decay a head (a (heads, D, D)
                   float32 state a slot and no convolution rows) or softmax
                   attention over the blocks of keys a query's KV group picks by
                   scores over mean-pooled keys (a third page leaf; the first
                   and the local blocks always kept; dense under ``dense_len``),
                   each followed by a dense SwiGLU, under the embedding, depth
                   and logit scalars (ISSUE 68)
- toy            — a linear classifier for tests and drills

A family with paged programs says what a slot keeps ONCE: ``kv_plan`` ->
``genserve.model.CachePlan`` (each cache leaf with its kind: ``paged_lm``).
"""

from __future__ import annotations

from typing import TYPE_CHECKING

if TYPE_CHECKING:
    from tpuserve.config import ModelConfig
    from tpuserve.models.base import ServingModel

_REGISTRY: dict[str, str] = {
    "resnet50": "tpuserve.models.resnet",
    "mobilenetv3": "tpuserve.models.mobilenet",
    "bert": "tpuserve.models.bert",
    "efficientdet": "tpuserve.models.efficientdet",
    "sd15": "tpuserve.models.sd15",
    "textgen": "tpuserve.models.textgen",
    "decoder": "tpuserve.models.decoder",
    "hybrid": "tpuserve.models.hybrid",
    "hybrid_ffn": "tpuserve.models.hybrid_ffn",
    "mla": "tpuserve.models.mla",
    "mla_sc": "tpuserve.models.mla_sc",
    "mla_hc": "tpuserve.models.mla_hc",
    "decoder_sink": "tpuserve.models.decoder_sink",
    "hybrid_delta": "tpuserve.models.hybrid_delta",
    "eva": "tpuserve.models.eva",
    "hybrid_conv": "tpuserve.models.hybrid_conv",
    "mla_sel": "tpuserve.models.mla_sel",
    "hybrid_ffn_moe": "tpuserve.models.hybrid_ffn_moe",
    "hybrid_blk": "tpuserve.models.hybrid_blk",
    "toy": "tpuserve.models.toy",
}


def build(cfg: "ModelConfig") -> "ServingModel":
    """Instantiate the ServingModel for cfg.family."""
    import importlib

    if cfg.family not in _REGISTRY:
        raise KeyError(f"unknown model family {cfg.family!r}; known: {sorted(_REGISTRY)}")
    try:
        mod = importlib.import_module(_REGISTRY[cfg.family])
    except ModuleNotFoundError as e:
        raise NotImplementedError(
            f"model family {cfg.family!r} is registered but its module "
            f"{_REGISTRY[cfg.family]} is not implemented yet"
        ) from e
    return mod.create(cfg)


def families() -> list[str]:
    return sorted(_REGISTRY)
