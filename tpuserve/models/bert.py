"""BERT-base text classification (SURVEY.md §2 C4, §3d; BASELINE.json config 3).

TPU-first shaping decisions:
- **Static (batch, seq) buckets**: every (batch_bucket, seq_bucket) pair is
  its own AOT-compiled executable; the batcher groups requests by seq bucket
  (``group_key``) so short texts never pay long-sequence FLOPs. This is the
  build's answer to the reference-era "dynamic seq-len" problem — bucketed
  padding, per BASELINE.json.
- Tokenization on the host threadpool (pure Python WordPiece,
  ``tpuserve.text``); only int32 (ids, mask) arrays cross to the device —
  a few hundred bytes per request.
- Attention masking is additive -1e9 bias from the padding mask, so padded
  lanes cannot perturb real lanes (tested:
  tests/test_bert.py::test_seq_bucket_invariance).
- bf16 compute, f32 softmax/logits; post-LN residual blocks (original BERT),
  gelu FFN, tanh pooler on [CLS], linear classifier.
- TP partition rules shard QKV/out and FFN kernels on "model" when cfg.tp>1.

Sizes come from ``cfg.options`` (layers/d_model/heads/d_ff/vocab_size) with
BERT-base defaults; tests use tiny sizes. ``cfg.options["vocab_file"]`` loads
a standard vocab.txt; otherwise the deterministic synthetic dev vocab is used
(no network, no artifacts — SURVEY.md §7 hard part 8).
"""

from __future__ import annotations

import json
import time
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpuserve import quantize as qz
from tpuserve.config import ModelConfig
from tpuserve.models.base import ServingModel
from tpuserve.obs import trace_span
from tpuserve.text import WordPieceTokenizer, synthetic_vocab


class BertBlock(nn.Module):
    heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    # "dense" (XLA einsum) | "fused" (Pallas kernel, a whole short sequence
    # a step): BertServing.forward sets it for each bucket it traces.
    attention_impl: str = "dense"
    ln_eps: float = 1e-12  # original BERT value; keeps imported weights exact
    # > 0: replace the dense FFN with a Switch MoE over this many experts
    # (tpuserve.ops.moe); expert dims shard on "model" for EP serving.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    # True: FFN matmuls via quantize.Int8Dense (int8 MXU path when the
    # runtime leaves their kernels quantized — quantize = "int8c").
    quantize_compute: bool = False

    @nn.compact
    def __call__(self, x, mask_bias, segments=None):
        # Post-LN (original BERT): sublayer -> add -> LayerNorm. Masking is an
        # explicit additive bias inside attention_fn so the semantics stay
        # bucket-invariant (padded keys get -1e9 before the f32 softmax).
        # ``segments`` (B, S) numbers the documents that share a row (0 =
        # padding): mask_bias is then (B, 1, S, S), built from it.
        if segments is not None and self.moe_experts:
            raise ValueError(
                f"moe_experts={self.moe_experts}: the routed feed-forward "
                "counts capacity by row and cannot keep the documents of a "
                "shared row apart")
        if self.attention_impl == "fused":
            from tpuserve.ops.fused_attention import fused_attention

            # The kernel takes segment numbers; a 0 / 1 key mask (a live
            # key's bias is 0.0) is one document a row.
            seg = (mask_bias[:, 0, 0, :] == 0.0 if segments is None
                   else segments)
            fn = lambda q, k, v, **kw: fused_attention(q, k, v, seg)  # noqa: E731
        else:
            fn = lambda q, k, v, **kw: _masked_attention(q, k, v, mask_bias)  # noqa: E731
        if self.quantize_compute:
            # Identical param tree to MHDPA; q/k/v/out projections run
            # int8 on the MXU when the runtime leaves their kernels
            # quantized (quantize = "int8c").
            attn = qz.Int8SelfAttention(
                heads=self.heads, dtype=self.dtype, attention_fn=fn,
                name="attn")
        else:
            attn = nn.MultiHeadDotProductAttention(
                num_heads=self.heads, dtype=self.dtype, deterministic=True,
                attention_fn=fn,
                name="attn")
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=self.ln_eps, dtype=self.dtype, name=name)
        x = ln("ln_attn")(x + attn(x))
        if self.moe_experts:
            from tpuserve.ops.moe import SwitchFFN

            # Recover the (B, S) 0/1 token mask from the additive key bias so
            # padded tokens never claim expert capacity. The serving forward
            # discards the load-balance aux (it only shapes training).
            token_mask = (mask_bias[:, 0, 0, :] == 0.0).astype(jnp.float32)
            h, _aux = SwitchFFN(self.moe_experts, self.d_ff,
                                capacity_factor=self.moe_capacity_factor,
                                dtype=self.dtype, name="moe")(x, token_mask)
        else:
            # Int8Dense == nn.Dense structurally; with quantize="int8c" the
            # runtime leaves these two kernels {"q8","q8_scale"} and the
            # FFN matmuls (2/3 of block FLOPs) run int8 on the MXU.
            dense = (qz.Int8Dense if self.quantize_compute else
                     lambda features, dtype, name: nn.Dense(
                         features, dtype=dtype, name=name))
            h = dense(self.d_ff, dtype=self.dtype, name="mlp_up")(x)
            # Exact (erf) GELU, matching BERT; the tanh approximation drifts
            # ~1e-3 on imported weights.
            h = nn.gelu(h, approximate=False)
            h = dense(x.shape[-1], dtype=self.dtype, name="mlp_down")(h)
        return ln("ln_mlp")(x + h)


def _masked_attention(q, k, v, mask_bias):
    """(B,S,H,D) attention with an additive bias, by key (B,1,1,S) or by pair
    (B,1,S,S: documents sharing a row), f32 softmax."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = s + mask_bias
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _positions_in_segment(segments):
    """(B, S) position of each token within its own run of one segment
    number: a row's documents lie one after another, each from 0."""
    at = jnp.arange(segments.shape[1], dtype=jnp.int32)[None, :]
    starts = segments != jnp.pad(segments[:, :-1], ((0, 0), (1, 0)))
    return at - jax.lax.cummax(jnp.where(starts, at, 0), axis=1)


def _segment_bias(segments):
    """(B, 1, S, S) additive bias: 0.0 between two tokens of one document,
    -1e9 for a key of another document or of padding. A padded query sees
    every document's keys (as with one document a row) and means nothing."""
    keys, queries = segments[:, None, None, :], segments[:, None, :, None]
    seen = ((queries == keys) | (queries == 0)) & (keys != 0)
    return jnp.where(seen, 0.0, -1e9).astype(jnp.float32)


class BertClassifier(nn.Module):
    vocab_size: int
    layers: int
    d_model: int
    heads: int
    d_ff: int
    max_seq: int
    num_classes: int
    dtype: Any = jnp.bfloat16
    attention_impl: str = "dense"
    ln_eps: float = 1e-12
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    quantize_compute: bool = False

    @nn.compact
    def __call__(self, ids, mask, cls_at=None):
        """One document a row: ``mask`` is the 0 / 1 token mask and the
        answer has a row a batch row. Documents sharing rows (``cls_at``
        given): ``mask`` numbers each token's document within its row (0 =
        padding, 1 .. J), ``cls_at`` holds the flat position ``row * S +
        offset`` of every document's [CLS] in arrival order, and the answer
        has a row for each of them. A document takes positions 0 .. n-1 from
        the table wherever it lies in its row, attends over its own keys
        only, and the pooler reads its own [CLS]: what it answers alone."""
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype, name="embed")(ids)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (self.max_seq, self.d_model))
        if cls_at is None:
            segments = None
            x = x + pos[None, : ids.shape[1], :].astype(self.dtype)
            mask_bias = (1.0 - mask.astype(jnp.float32))[:, None, None, :] * -1e9
        else:
            segments = mask
            x = x + pos.astype(self.dtype)[_positions_in_segment(segments)]
            mask_bias = _segment_bias(segments)
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype, name="ln_embed")(x)
        for i in range(self.layers):
            x = BertBlock(self.heads, self.d_ff, dtype=self.dtype,
                          attention_impl=self.attention_impl,
                          ln_eps=self.ln_eps,
                          moe_experts=self.moe_experts,
                          moe_capacity_factor=self.moe_capacity_factor,
                          quantize_compute=self.quantize_compute,
                          name=f"layer{i}")(x, mask_bias, segments)
        cls = (x[:, 0, :] if cls_at is None
               else x.reshape(-1, x.shape[-1])[cls_at])
        pooled = jnp.tanh(nn.Dense(self.d_model, dtype=self.dtype, name="pooler")(cls))
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="classifier")(pooled)


class BertServing(ServingModel):
    # Most documents a row of a launch holds: a constant of the program (the
    # answer has this many rows a batch row). The mix's own draw never put
    # more than five in 512 tokens; the kernel's mask has room for fifteen.
    ROW_ITEMS = 8

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        self._tokenize_obs = None  # bind_metrics
        opt = cfg.options
        # On one device the attention path is chosen for each bucket while
        # its program is traced (forward); a mesh keeps the XLA pair, which
        # GSPMD can partition.
        self._choose_attention = cfg.parallelism in ("single", "replica")
        self._attention_traced: dict[tuple, str] = {}
        moe_experts = int(opt.get("moe_experts", 0))
        if moe_experts and cfg.parallelism == "sharded" and cfg.tp > 1 \
                and moe_experts % cfg.tp:
            raise ValueError(
                f"options.moe_experts={moe_experts} shards the expert dim "
                f"over the model axis (tp={cfg.tp}); it must divide evenly")
        if moe_experts and cfg.weights:
            # import_tf_variables maps dense-FFN checkpoints (mlp_up/down);
            # there is no TF source scheme for the MoE variant's
            # moe/{router, w_up, w_down} params.
            raise ValueError(
                "options.moe_experts cannot be combined with weights=: no "
                "TF import mapping exists for the MoE FFN; serve it with "
                "seeded weights or an orbax checkpoint trained in-framework")
        self.dtype = jnp.dtype(cfg.dtype)
        self.max_seq = max(cfg.seq_buckets)
        vocab_file = opt.get("vocab_file")
        if vocab_file:
            self.tokenizer = WordPieceTokenizer.from_vocab_file(vocab_file)
        else:
            self.tokenizer = WordPieceTokenizer(
                synthetic_vocab(int(opt.get("vocab_size", 8192))))
        self.module = BertClassifier(
            vocab_size=max(self.tokenizer.vocab.values()) + 1,
            layers=int(opt.get("layers", 12)),
            d_model=int(opt.get("d_model", 768)),
            heads=int(opt.get("heads", 12)),
            d_ff=int(opt.get("d_ff", 3072)),
            max_seq=self.max_seq,
            num_classes=cfg.num_classes,
            dtype=self.dtype,
            # options.moe_experts=N serves a Switch-MoE FFN variant with the
            # expert dim sharded on "model" (expert parallelism).
            moe_experts=moe_experts,
            moe_capacity_factor=float(opt.get("moe_capacity_factor", 1.25)),
            # "int8c" computes the FFN matmuls int8 x int8 -> int32 on the
            # MXU (quantize.Int8Dense consumes the still-quantized kernels
            # the runtime leaves in place — int8c_native_kernel_paths).
            quantize_compute=cfg.quantize == "int8c",
        )
        self.top_k = min(5, cfg.num_classes)
        # The rule and its one exception are input_signature's docstring.
        self.packs_rows = cfg.parallelism == "single" and not moe_experts

    def int8c_native_kernel_paths(self) -> list[str]:
        """The kernels the int8c modules consume natively: FFN matmuls
        (Int8Dense, 2/3 of block matmul FLOPs) and the q/k/v/out attention
        projections (Int8SelfAttention, the remaining 1/3). The MoE
        variant has no mlp kernels (SwitchFFN replaces them), so it
        returns [] and the runtime rejects int8c with guidance rather than
        silently degrading to weight-only."""
        if self.module.moe_experts:
            return []
        return [r"mlp_(up|down)/kernel$",
                r"attn/(query|key|value|out)/kernel$"]

    def import_tf_variables(self, flat: dict) -> Any:
        """HF transformers TFBert(ForSequenceClassification) -> this pytree.

        Source scheme (``transformers.TFBertForSequenceClassification``
        SavedModel): ``<root>/bert/embeddings/{word_embeddings/weight,
        position_embeddings/embeddings, token_type_embeddings/embeddings,
        LayerNorm}``, per-layer ``bert/encoder/layer_._{i}/{attention/self/
        query|key|value, attention/output/dense, attention/output/LayerNorm,
        intermediate/dense, output/dense, output/LayerNorm}``, then
        ``bert/pooler/dense`` and ``<root>/classifier``.

        Layout translations: HF fuses heads into (d, d) attention kernels;
        Flax MHA wants (d, heads, head_dim) for Q/K/V and (heads, head_dim,
        d) for the out projection — pure reshapes, head-major on both sides.
        The serving path is single-segment (classify one text), so the
        token-type table collapses to its segment-0 row, folded into the
        position embeddings (both are added before the embedding LayerNorm).
        """
        m = self.module
        head_dim = m.d_model // m.heads
        f: dict[str, np.ndarray] = {}
        for k, v in flat.items():
            k = k.split(":")[0]
            k = k.split("/", 1)[1] if "/" in k else k  # drop the root name
            f[k] = np.asarray(v)

        emb = "bert/embeddings"
        words = f[f"{emb}/word_embeddings/weight"]
        if words.shape[0] != m.vocab_size:
            raise ValueError(
                f"imported embedding table has {words.shape[0]} rows but the "
                f"serving tokenizer implies vocab_size {m.vocab_size}; pair "
                "the checkpoint with its matching vocab_file")
        n_cls = f["classifier/kernel"].shape[1]
        if n_cls != self.cfg.num_classes:
            raise ValueError(
                f"imported classifier has {n_cls} classes but cfg.num_classes "
                f"is {self.cfg.num_classes}")
        pos = f[f"{emb}/position_embeddings/embeddings"]
        if pos.shape[0] < self.max_seq:
            raise ValueError(
                f"imported position table covers {pos.shape[0]} positions "
                f"but max seq bucket is {self.max_seq}")
        pos = pos[: self.max_seq]
        tt = f.get(f"{emb}/token_type_embeddings/embeddings")
        if tt is not None:
            pos = pos + tt[0][None, :]

        params: dict = {
            "embed": {"embedding": words},
            "pos_embed": pos,
            "ln_embed": {"scale": f[f"{emb}/LayerNorm/gamma"],
                         "bias": f[f"{emb}/LayerNorm/beta"]},
            "pooler": {"kernel": f["bert/pooler/dense/kernel"],
                       "bias": f["bert/pooler/dense/bias"]},
            "classifier": {"kernel": f["classifier/kernel"],
                           "bias": f["classifier/bias"]},
        }
        for i in range(m.layers):
            lyr = f"bert/encoder/layer_._{i}"

            def qkv(name: str) -> dict:
                return {
                    "kernel": f[f"{lyr}/attention/self/{name}/kernel"].reshape(
                        m.d_model, m.heads, head_dim),
                    "bias": f[f"{lyr}/attention/self/{name}/bias"].reshape(
                        m.heads, head_dim),
                }

            def ln(name: str) -> dict:
                return {"scale": f[f"{lyr}/{name}/gamma"],
                        "bias": f[f"{lyr}/{name}/beta"]}

            def dense(name: str) -> dict:
                return {"kernel": f[f"{lyr}/{name}/kernel"],
                        "bias": f[f"{lyr}/{name}/bias"]}

            params[f"layer{i}"] = {
                "attn": {
                    "query": qkv("query"),
                    "key": qkv("key"),
                    "value": qkv("value"),
                    "out": {
                        "kernel": f[f"{lyr}/attention/output/dense/kernel"]
                        .reshape(m.heads, head_dim, m.d_model),
                        "bias": f[f"{lyr}/attention/output/dense/bias"],
                    },
                },
                "ln_attn": ln("attention/output/LayerNorm"),
                "mlp_up": dense("intermediate/dense"),
                "mlp_down": dense("output/dense"),
                "ln_mlp": ln("output/LayerNorm"),
            }
        return {"params": params}

    # -- params --------------------------------------------------------------
    def init_params(self, rng: jax.Array) -> Any:
        s = min(self.cfg.seq_buckets)
        ids = jnp.zeros((1, s), jnp.int32)
        mask = jnp.ones((1, s), jnp.int32)
        return self.module.init(rng, ids, mask)

    # -- shapes --------------------------------------------------------------
    def buckets(self) -> list[tuple]:
        return [(b, s) for b in self.cfg.batch_buckets for s in self.cfg.seq_buckets]

    def bucket_for(self, n: int, group=None) -> tuple:
        s = group if group is not None else max(self.cfg.seq_buckets)
        for b in self.cfg.batch_buckets:
            if b >= n:
                return (b, s)
        return (self.cfg.batch_buckets[-1], s)

    def input_signature(self, bucket: tuple) -> Any:
        """(ids, segments, cls_at): each token's document within its row
        (several whole documents share a row, each attending only to
        itself) and every document's [CLS] as a flat position in arrival
        order. The one exception: a mesh (``parallelism = "replica"`` or
        ``"sharded"``) and the routed feed-forward (``options.moe_experts``,
        which counts capacity by row) take (ids, mask) and one document a
        row."""
        b, s = bucket
        sig = (jax.ShapeDtypeStruct((b, s), jnp.int32),
               jax.ShapeDtypeStruct((b, s), jnp.int32))
        if self.packs_rows:
            sig += (jax.ShapeDtypeStruct((b * self.ROW_ITEMS,), jnp.int32),)
        return sig

    def row_shape(self, group=None) -> tuple[int, int]:
        if not self.packs_rows:
            return super().row_shape(group)
        return (group if group is not None else self.max_seq), self.ROW_ITEMS

    def item_units(self, item: np.ndarray, group=None) -> int:
        if not self.packs_rows:
            return 1
        return min(item.shape[0], self.row_shape(group)[0])

    # -- device side ---------------------------------------------------------
    def forward(self, params: Any, batch: Any) -> dict:
        ids, mask, *cls_at = batch   # mask: segment numbers beside cls_at
        module = self.module
        if self._choose_attention:
            from tpuserve.ops.fused_attention import attention_path, platform_here

            module = module.clone(attention_impl=attention_path(
                platform_here(), self.dtype, ids.shape[1],
                module.d_model // module.heads))
        # Runs while the bucket is traced, never per call: the record of
        # what this bucket's program holds (traced_paths).
        self._attention_traced[tuple(ids.shape)] = module.attention_impl
        logits = module.apply(params, ids, mask, *cls_at)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, self.top_k)
        return {"probs": top_p, "indices": top_i}

    def traced_paths(self, bucket: tuple) -> dict:
        path = self._attention_traced.get(tuple(bucket))
        return {"attention": path} if path else {}

    # -- host side -----------------------------------------------------------
    def host_decode(self, payload: bytes, content_type: str) -> np.ndarray:
        """Request body -> unpadded int32 token ids (incl. [CLS]/[SEP])."""
        return self.host_decode_items(payload, content_type)[0][0]

    def host_decode_items(self, payload: bytes, content_type: str) -> tuple[list, bool]:
        """One JSON parse: {"text": str} is single, {"texts": [...]} a batch;
        non-JSON bodies are one plain-text item."""
        if not content_type.startswith("application/json"):
            return self._encode_all([payload.decode("utf-8")]), False
        body = json.loads(payload.decode("utf-8"))
        texts = body.get("texts")
        if texts is not None:
            if not isinstance(texts, list) or not all(isinstance(t, str) for t in texts):
                raise ValueError('"texts" must be a list of strings')
            if len(texts) > self.MAX_ITEMS_PER_REQUEST:
                raise ValueError(
                    f"batch of {len(texts)} exceeds the per-request limit "
                    f"({self.MAX_ITEMS_PER_REQUEST})")
            return self._encode_all(texts), True
        text = body.get("text")
        if not isinstance(text, str):
            raise ValueError('JSON body must contain "text": str')
        return self._encode_all([text]), False

    def bind_metrics(self, metrics) -> None:
        name = self.name
        self._tokenize_obs = (
            metrics.histogram(f"latency_ms{{model={name},phase=tokenize}}"),
            metrics.counter(f"ingest_tokenize_cpu_seconds_total{{model={name}}}"),
            metrics.counter(f"ingest_tokens_total{{model={name}}}"),
            metrics.counter(f"ingest_tokenize_path_total{{model={name},path=ascii}}"),
            metrics.counter(f"ingest_tokenize_path_total{{model={name},path=unicode}}"))

    def _encode_all(self, texts: list[str]) -> list[np.ndarray]:
        """The tokenizer alone (no JSON parse), measured in the thread that
        runs it: wall time, this thread's CPU time (wall less CPU is the
        wait for the GIL and the scheduler), ids produced, [CLS] and [SEP]
        included, and documents by the split text.py chose for them."""
        wall0, cpu0 = time.perf_counter(), time.thread_time()
        with trace_span("tpuserve.tokenize", model=self.name,
                        items=len(texts)) as span:
            items = [self._encode(t) for t in texts]
            tokens = sum(it.shape[0] for it in items)
            span.set_metadata(tokens=tokens)
        if self._tokenize_obs is not None:
            hist, cpu_s, n_tokens, n_ascii, n_unicode = self._tokenize_obs
            hist.observe((time.perf_counter() - wall0) * 1e3)
            cpu_s.inc(time.thread_time() - cpu0)
            n_tokens.inc(tokens)
            ascii_docs = sum(t.isascii() for t in texts)
            n_ascii.inc(ascii_docs)
            n_unicode.inc(len(texts) - ascii_docs)
        return items

    def _encode(self, text: str) -> np.ndarray:
        tok = self.tokenizer
        ids = [tok.cls_id] + tok.ids(text)
        ids = ids[: self.max_seq - 1] + [tok.sep_id]
        return np.asarray(ids, np.int32)  # unpadded; assemble pads per bucket

    def group_key(self, item: np.ndarray):
        """Seq bucket for an unpadded id array -> batching group."""
        for s in self.cfg.seq_buckets:
            if s >= item.shape[0]:
                return s
        return max(self.cfg.seq_buckets)

    def canary_item(self) -> np.ndarray:
        return self.host_decode(b'{"text": "canary"}', "application/json")

    def assemble(self, items: list[np.ndarray], bucket: tuple,
                 rows: "list[int] | None" = None) -> Any:
        return self.assemble_into(items, bucket, tuple(
            np.empty(s.shape, s.dtype) for s in self.input_signature(bucket)),
            rows)

    def assemble_into(self, items: list[np.ndarray], bucket: tuple, out,
                      rows: "list[int] | None" = None) -> Any:
        """``rows`` (the batcher's, where rows are shared): the row of each
        item, items of one row in the order they lie in it; None is one item
        a row in order."""
        s = bucket[1]
        ids, mask = out[0], out[1]
        ids[:] = self.tokenizer.pad_id
        mask[:] = 0
        if not self.packs_rows:
            for i, it in enumerate(items):
                n = min(it.shape[0], s)
                ids[i, :n] = it[:n]
                mask[i, :n] = 1
            return ids, mask
        cls_at = out[2]
        cls_at[:] = 0
        used = [0] * ids.shape[0]      # tokens in each row so far
        held = [0] * ids.shape[0]      # documents in each row so far
        for i, it in enumerate(items):
            r = i if rows is None else rows[i]
            at, n = used[r], min(it.shape[0], s)
            if at + n > s or held[r] >= self.ROW_ITEMS:
                raise ValueError(
                    f"row {r} of a {bucket} launch cannot take item {i}: "
                    f"{at} + {n} tokens, {held[r]} documents")
            ids[r, at:at + n] = it[:n]
            held[r] += 1
            mask[r, at:at + n] = held[r]
            cls_at[i] = r * s + at
            used[r] = at + n
        return ids, mask, cls_at

    def host_postprocess(self, outputs: dict, n_valid: int) -> list[dict]:
        return self.format_top_k(outputs, n_valid)

    # -- parallelism ---------------------------------------------------------
    def partition_rules(self):
        if self.cfg.tp <= 1:
            return [(".*", P())]
        return [
            (r"attn/(query|key|value)/kernel", P(None, "model", None)),
            (r"attn/out/kernel", P("model", None, None)),
            (r"mlp_up/kernel", P(None, "model")),
            (r"mlp_down/kernel", P("model", None)),
            # EP: expert dim of the (E, D, F) MoE weights on "model" (same
            # layout as train.TRAIN_PARTITION_RULES); router replicated.
            (r"moe/w_(up|down)", P("model", None, None)),
            (r".*", P()),
        ]


def create(cfg: ModelConfig) -> BertServing:
    return BertServing(cfg)
