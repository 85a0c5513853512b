"""ServingModel: the contract between the model zoo and the runtime/batcher.

Design (SURVEY.md §3b/§3c): the runtime AOT-compiles ``forward`` once per
(batch-bucket, input-shape) pair at startup; the batcher assembles padded
host batches, and ``forward`` does everything device-side — resize/normalize
preprocessing fused in front of the network, and postprocessing (top-k, NMS,
image decode to uint8) fused behind it — so exactly two host<->device
crossings happen per batch (H2D inputs, D2H small outputs).

``forward`` must be a pure jittable function of (params, batch) with static
shapes. Dynamic request counts are handled by padding: the batcher passes
``n_valid`` alongside the batch, and host_postprocess slices the first
``n_valid`` rows. Padded lanes must not influence real lanes (tested in
tests/test_runtime.py::test_padding_lanes_do_not_affect_real_lanes).
"""

from __future__ import annotations

import abc
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from tpuserve.config import ModelConfig

# A host batch: pytree of np.ndarrays with leading batch dim.
HostBatch = Any
# Device outputs: pytree of jax.Arrays with leading batch dim.
Outputs = Any


def _stack_pad(arrs: list[np.ndarray], b: int) -> np.ndarray:
    out = np.stack(arrs, axis=0)
    if out.shape[0] < b:
        pad = np.zeros((b - out.shape[0],) + out.shape[1:], dtype=out.dtype)
        out = np.concatenate([out, pad], axis=0)
    return out


class ServingModel(abc.ABC):
    """One deployable model family instance."""

    def __init__(self, cfg: ModelConfig) -> None:
        self.cfg = cfg
        self.name = cfg.name
        # Result-cache eligibility (server ModelCache + router wire cache).
        # Config-driven so operators can opt a nondeterministic deployment
        # out; families whose sampling params all ride inside the decoded
        # item (textgen, sd15) are safely cacheable by construction.
        self.cacheable = bool(getattr(cfg, "cacheable", True))
        self.class_labels: list[str] | None = None
        if cfg.labels:
            with open(cfg.labels, encoding="utf-8") as f:
                lines = [line.rstrip("\r\n") for line in f]
            while lines and not lines[-1]:  # trailing blank lines
                lines.pop()
            self.class_labels = lines

    def bind_metrics(self, metrics: Any) -> None:
        """Prebind whatever the family measures inside its own host code
        (the server calls this once at start, with its obs.Metrics).
        Nothing by default; a model nobody bound measures nothing."""

    # -- parameters ---------------------------------------------------------
    @abc.abstractmethod
    def init_params(self, rng: jax.Array) -> Any:
        """Seeded random params (no-network dev mode, SURVEY.md §7 hard pt 8)."""

    def load_params(self) -> Any:
        """Load real weights if cfg.weights is set, else random init."""
        if self.cfg.weights:
            from tpuserve import savedmodel

            return savedmodel.load_params_for(self)
        return self.init_params(jax.random.key(0))

    def import_tf_variables(self, flat: dict[str, np.ndarray]) -> Any:
        """Translate a flat TF {name: array} dict into this model's pytree.

        Family-specific (name schemes and layouts differ per source repo);
        implement when wiring real TF weights for the family.
        """
        raise NotImplementedError(
            f"{type(self).__name__} has no TF variable mapping; convert the "
            "weights to an orbax checkpoint or implement import_tf_variables"
        )

    def import_torch_variables(self, flat: dict[str, np.ndarray]) -> Any:
        """Translate a flat torch {name: array} state_dict into this model's
        pytree. Family-specific; implement for families whose published
        artifacts ship as torch/safetensors (e.g. SD 1.5)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no torch state_dict mapping; convert "
            "the weights to an orbax checkpoint or implement "
            "import_torch_variables"
        )

    # -- shapes -------------------------------------------------------------
    @abc.abstractmethod
    def input_signature(self, bucket: tuple) -> Any:
        """Pytree of jax.ShapeDtypeStruct for a bucket key.

        Bucket keys are model-specific tuples: ``(batch,)`` for vision,
        ``(batch, seq)`` for text.
        """

    def buckets(self) -> list[tuple]:
        """All bucket keys to AOT-compile at startup."""
        return [(b,) for b in self.cfg.batch_buckets]

    def bucket_for(self, n: int, **kw) -> tuple:
        """Smallest bucket that fits n requests (used by the batcher)."""
        for b in self.cfg.batch_buckets:
            if b >= n:
                return (b,)
        return (self.cfg.batch_buckets[-1],)

    def row_shape(self, group: Any = None) -> tuple[int, int]:
        """``(units, items)`` a row of this group's launches holds. The
        batcher counts a batch in ROWS against the batch buckets, and places
        an item of ``item_units`` in an open row with that much left and
        fewer than ``items`` in it. ``(1, 1)``, the default, is one item a
        row: rows are items. A family whose program keeps the items of a
        shared row apart answers more (BERT: the group's tokens); its
        ``assemble`` / ``assemble_into`` then take ``rows=``, the row of each
        item, and its outputs lead with ``bucket[0] * items`` rows in the
        items' order, so ``host_postprocess(outputs, n_valid)`` reads the
        first ``n_valid`` as ever."""
        return 1, 1

    def item_units(self, item: Any, group: Any = None) -> int:
        """What ``item`` takes of a row of ``row_shape(group)[0]`` units
        (at least 1, at most the row)."""
        return 1

    # -- device-side --------------------------------------------------------
    def device_preprocess(self, batch: HostBatch) -> Any:
        """Jittable fused-preprocessing seam: raw wire bytes -> network input.

        The wire contract ships exactly what the host decoded — uint8 RGB or
        YUV420 planes for vision, token ids for text — and EVERY cast,
        /255 scale, normalize, resize, and colorspace conversion happens
        here, inside the compiled program, where XLA fuses it into the
        network's first consumers. ``forward`` implementations must route
        their input through this method (rather than open-coding the math)
        so the fusion is a named, testable, probe-able boundary: the
        roofline attribution compiles ``device_preprocess`` standalone to
        price the fused-preproc share of the executable, and tests assert
        ``forward(params, wire) == net(device_preprocess(wire))``. Identity
        by default for families whose network consumes the wire format
        directly (e.g. token ids)."""
        return batch

    @abc.abstractmethod
    def forward(self, params: Any, batch: HostBatch) -> Outputs:
        """Jittable: on-device preproc (via ``device_preprocess``) + network
        + on-device postproc."""

    def traced_paths(self, bucket: tuple) -> dict:
        """What ``forward`` chose from the platform, the dtype and this
        bucket's shape while its program was traced, by name: a family that
        picks a kernel then says which (BERT: ``{"attention": "fused"}``).
        The runtime shows it beside the variant in ``/stats`` and counts
        launches under it. Default: nothing was chosen."""
        return {}

    def int8c_native_kernel_paths(self) -> list[str]:
        """Regexes of param paths this model computes in int8 NATIVELY
        (``quantize = "int8c"``): those kernels stay ``{"q8", "q8_scale"}``
        in the compiled forward and run int8 x int8 -> int32 on the MXU
        (tpuserve.quantize.Int8Dense). Empty means the family only supports
        weight-only "int8" — the runtime rejects "int8c" with guidance."""
        return []

    # -- host-side ----------------------------------------------------------
    @abc.abstractmethod
    def host_decode(self, payload: bytes, content_type: str) -> Any:
        """Decode one request body into per-item input arrays (threadpool).

        Runs in the decode threadpool; must touch only its own arguments.
        """

    def host_decode_items(self, payload: bytes, content_type: str) -> tuple[list, bool]:
        """Decode one request body into (items, is_batch) with a single parse.

        Batched client requests amortize HTTP and host-decode overhead and
        let one POST fill a whole device bucket. Families opt in by
        overriding: vision accepts a (N, H, W, 3) uint8 npy tensor, text a
        {"texts": [...]} JSON list; ``is_batch`` requests answer in the
        {"results": [...]} shape even for one item. Default: single-item
        ``host_decode``. Runs in the decode threadpool.
        """
        return [self.host_decode(payload, content_type)], False

    # A single POST may not carry more items than one full device batch era;
    # bounds host memory for the decode stage.
    MAX_ITEMS_PER_REQUEST = 1024

    def canary_item(self) -> Any:
        """A trivial decoded item used by health canaries; default zero image."""
        w = self.cfg.wire_size
        return np.zeros((w, w, 3), dtype=np.uint8)

    def group_key(self, item: Any) -> Any:
        """Batching group for a decoded item (e.g. seq bucket); None = one group."""
        return None

    @abc.abstractmethod
    def host_postprocess(self, outputs: Outputs, n_valid: int) -> list[Any]:
        """Convert device outputs (already np) to n_valid JSON-able results."""

    def format_top_k(self, outputs: dict, n_valid: int) -> list[dict]:
        """Shared classifier response shape: {"top_k": [{class, prob}, ...]},
        plus a "label" per entry when cfg.labels names the classes."""
        probs = outputs["probs"][:n_valid]
        idx = outputs["indices"][:n_valid]
        return [
            {"top_k": [self._class_entry(i, p) for i, p in zip(idx[r], probs[r])]}
            for r in range(n_valid)
        ]

    def _class_entry(self, i, p) -> dict:
        entry = {"class": int(i), "prob": float(p)}
        label = self.label_for(int(i))
        if label is not None:
            entry["label"] = label
        return entry

    def label_for(self, i: int) -> str | None:
        if self.class_labels is not None and 0 <= i < len(self.class_labels):
            return self.class_labels[i]
        return None

    def assemble(self, items: list[Any], bucket: tuple) -> HostBatch:
        """Stack decoded items into one padded host batch for `bucket`.

        Default: items are single np arrays or tuples of np arrays (e.g. YUV
        planes); each component is stacked along axis 0 and zero-padded on the
        batch dim up to bucket[0].
        """
        b = bucket[0]
        if isinstance(items[0], tuple):
            return tuple(
                _stack_pad([it[k] for it in items], b) for k in range(len(items[0]))
            )
        return _stack_pad(items, b)

    def assemble_into(self, items: list[Any], bucket: tuple, out: HostBatch) -> HostBatch:
        """Assemble into a preallocated host-batch buffer (arena recycling).

        ``out`` is a pytree of np arrays shaped like
        ``input_signature(bucket)``, a buffer of the batcher's
        AssemblyArena. Must produce exactly what ``assemble`` would, writing
        in place: real rows copied, padded rows zeroed. The batcher only uses this when it can prove equivalence
        (``assemble`` not overridden, or ``assemble_into`` overridden
        alongside it); families that customize ``assemble`` should override
        this too to keep the allocation-free hot path."""
        n = len(items)
        if isinstance(items[0], tuple):
            for k in range(len(items[0])):
                comp = out[k]
                for i, it in enumerate(items):
                    comp[i] = it[k]
                if n < comp.shape[0]:
                    comp[n:] = 0
            return out
        for i, it in enumerate(items):
            out[i] = it
        if n < out.shape[0]:
            out[n:] = 0
        return out

    # -- parallelism --------------------------------------------------------
    def partition_rules(self) -> list[tuple[str, P]]:
        """Ordered (regex, PartitionSpec) rules for params; default replicate."""
        return [(".*", P())]

    def batch_spec(self) -> Any:
        """PartitionSpec pytree for the batch input (leading dim = data axis)."""
        return P("data")

    def out_spec(self) -> Any:
        """PartitionSpec pytree for forward outputs."""
        return P("data")
