"""Runtime: AOT compile & execute model executables on the mesh (SURVEY.md C5).

The reference runs TF SavedModel graphs on TensorFlow-GPU; the TPU-native
equivalent compiles each (model, bucket) pair once, ahead of time, to an XLA
executable resident on the device mesh:

    jax.jit(forward, in_shardings=..., out_shardings=...)
        .lower(params_struct, batch_struct).compile()

Static shapes are the contract: every batch bucket (and seq bucket for text)
is its own executable, compiled at startup — in parallel across buckets — and
cached persistently via the JAX compilation cache so restart != recompile
(SURVEY.md §5 checkpoint/resume).

Execution is asynchronous: ``run`` dispatches and returns device arrays
immediately (XLA async dispatch); ``fetch`` blocks for D2H and is intended to
be called off the event loop (batcher runs it in a threadpool) so batch N+1
dispatches while N computes — the dispatch pipelining from SURVEY.md §7
hard-part 2.

Parallelism modes per model (SURVEY.md §2.1):
- "sharded": one executable over the whole mesh; batch sharded on the data
  axis; params replicated or TP-sharded by the model's partition rules.
- "replica": one single-device executable per device, independent queues —
  lower p50 for batch=1 latency models (MobileNetV3).
- "single": first device only (dev mode).
"""

from __future__ import annotations

import concurrent.futures as cf
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuserve.analysis import witness
from tpuserve.config import ModelConfig, ParallelConfig, ServerConfig
from tpuserve.models.base import ServingModel
from tpuserve.obs import Metrics, trace_span
from tpuserve.utils.retrace import allow_transfers, host_fetch
from tpuserve.parallel import make_mesh, match_partition_rules
from tpuserve.parallel.mesh import MeshPlan, plan_for, select_devices
from tpuserve.parallel.partition import specs_to_shardings, struct_shardings
from tpuserve.utils.locks import new_lock

log = logging.getLogger("tpuserve.runtime")

# Sampling is sharding-invariant because jax's partitionable ThreeFry (the
# default) computes each element's bits independent of device layout: the
# sharded decode's token-identical-to-single-mesh obligation rests on it.

# Where the persistent XLA compile cache lives unless the environment places
# it (configure_compile_cache): fixed to the checkout, so every process of
# one command — server, restart, probe child — reads what the first wrote.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jaxcache")


class NaNDetected(ValueError):
    """A candidate weight tree holds NaN/Inf float leaves; the reload gate
    (tpuserve.lifecycle) rejects it and the old version keeps serving."""


def configure_compile_cache() -> str:
    """Place the persistent XLA compile cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` is the one way to move the cache
    — when it is set jax reads it itself and no code sets another path;
    otherwise the cache is ``<checkout>/.jaxcache``. Every compile is kept
    (no minimum compile time), so a restart's cache reads never depend on
    whether a program happened to compile faster than a threshold."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def check_backend(platform: str, requested: str) -> None:
    """Refuse a CPU backend nobody asked for.

    jax falls back to the CPU with a warning when the accelerator cannot be
    opened; a server that then compiles and serves from the host looks
    healthy and is ~100x slow. ``requested`` is the ``JAX_PLATFORMS`` value
    the process runs under: tests, CI and the smoke scripts name ``cpu``
    there, which is the one way to serve from the host on purpose."""
    if platform == "cpu" and "cpu" not in requested.lower().split(","):
        raise RuntimeError(
            "jax resolved to the 'cpu' platform but JAX_PLATFORMS="
            f"{requested!r} does not name it: the accelerator could not be "
            "opened (another process may hold the chip). Set "
            "JAX_PLATFORMS=cpu to serve from the host on purpose.")


def configure_backend() -> None:
    """The start-up rules of EVERY process that compiles — server, router
    worker, smoke child: place the compile cache, say which backend this
    is, and refuse a CPU nobody asked for."""
    cache = configure_compile_cache()
    devs = jax.devices()
    log.info("backend: platform=%s device_kind=%s devices=%d "
             "compile_cache=%s", devs[0].platform, devs[0].device_kind,
             len(devs), cache)
    check_backend(devs[0].platform, jax.config.jax_platforms or "")


def configure_jax(cfg: ServerConfig) -> None:
    """Process-wide JAX settings (call once, before any compilation)."""
    configure_backend()
    if cfg.debug_nans:
        jax.config.update("jax_debug_nans", True)
        jax.config.update("jax_debug_infs", True)  # NaN alone misses overflow


@dataclass
class Executable:
    """One compiled (bucket, device-set) executable."""

    bucket: tuple
    compiled: Any  # jax.stages.Compiled
    batch_sharding: Any  # pytree of NamedSharding for the batch input
    device_index: int = 0  # replica mode: which replica
    donated: bool = False  # batch input buffers donated to the outputs


def bucket_label(bucket: tuple) -> str:
    """(256, 512) -> "256x512": a bucket in metric labels and span
    arguments."""
    return "x".join(str(d) for d in bucket)


@dataclass(frozen=True)
class VariantKey:
    """Identity of one fully-specialized compiled variant (ISSUE 6).

    Clockwork's premise (PAPERS.md P3) is that predictable serving comes
    from precompiled, fully-specialized executables managed bottom-up; the
    registry keys each one by everything the compilation specialized on —
    the static batch/seq bucket, the compute dtype, the quantization mode,
    and the parallelism layout. TF-Serving's servable discipline (P2) adds
    the second half: variants must be cheaply enumerable artifacts, so
    `/v1/models` and `/stats` can list exactly what is resident, and a
    counter (`runtime_compiles_total`) can prove the steady state compiles
    nothing new. Weight versions are deliberately NOT part of the key:
    publish/rollback swap trees under unchanged shapes, so every version
    reuses the same variant set (zero recompiles across reloads)."""

    bucket: tuple
    dtype: str
    quantize: str | None
    parallelism: str

    @property
    def label(self) -> str:
        """Compact metric-label form: "<bucket>/<dtype>/<quantize>/<mode>"."""
        return (f"{bucket_label(self.bucket)}/{self.dtype}/"
                f"{self.quantize or 'fp'}/{self.parallelism}")


@dataclass
class Variant:
    """Registry entry: one VariantKey's executables across replicas."""

    key: VariantKey
    executables: list[Executable]
    compile_ms: float = 0.0
    # What the model chose while this bucket was traced, by name
    # (ServingModel.traced_paths): BERT's {"attention": "fused" | "dense"}.
    paths: dict = field(default_factory=dict)

    def summary(self) -> dict:
        return {
            **self.paths,
            "bucket": list(self.key.bucket),
            "dtype": self.key.dtype,
            "quantize": self.key.quantize,
            "parallelism": self.key.parallelism,
            "replicas": len(self.executables),
            "donated": any(e.donated for e in self.executables),
            "compile_ms": round(self.compile_ms, 1),
        }


@dataclass
class GenProgram:
    """One registered generative program (tpuserve.genserve): an AOT-compiled
    jittable of ``(params, *args)`` that is NOT a forward bucket — the
    engine's insert/step/extract executables. Registered in the same
    VariantKey registry as forward buckets (bucket = (tag, width)), counted
    by the same ``runtime_compiles_total``, so the zero-steady-state-
    recompile obligation covers slot churn and reloads in one counter."""

    tag: str
    compiled: list  # jax.stages.Compiled, one per replica mesh
    donated: bool = False
    counter: Any = None  # prebound runtime_variant_batches_total{variant=}


def _leaves_with_shardings(struct: Any, shardings: Any) -> list[tuple]:
    """Pair a ShapeDtypeStruct tree's leaves with their shardings;
    ``shardings`` may be one NamedSharding broadcast over the tree."""
    leaves = jax.tree_util.tree_leaves(struct)
    if isinstance(shardings, NamedSharding):
        return [(l, shardings) for l in leaves]
    sh = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    return list(zip(leaves, sh))


def _donation_shapes_ok(batch_struct: Any, batch_shardings: Any,
                        out_struct: Any, out_shardings: Any) -> bool:
    """True when EVERY batch input buffer can alias a distinct output buffer
    (same shape, dtype, and sharding spec). Donation is all-or-nothing on
    purpose: partially-usable donation only produces "donated buffers were
    not usable" warnings on every compile with no memory benefit (ADVICE r1,
    which removed unconditional donation) — so the batch argument is donated
    only when XLA can provably consume all of it."""
    def key(leaf, sharding):
        return (tuple(leaf.shape), str(jnp.dtype(leaf.dtype)),
                str(getattr(sharding, "spec", sharding)))

    outs: dict[tuple, int] = {}
    for leaf, sh in _leaves_with_shardings(out_struct, out_shardings):
        k = key(leaf, sh)
        outs[k] = outs.get(k, 0) + 1
    ins = _leaves_with_shardings(batch_struct, batch_shardings)
    if not ins:
        return False
    for leaf, sh in ins:
        k = key(leaf, sh)
        if not outs.get(k):
            return False
        outs[k] -= 1
    return True


class ModelRuntime:
    """Owns params-on-device and the compiled executable set for one model."""

    def __init__(self, model: ServingModel, mesh: Mesh | None = None,
                 metrics: Metrics | None = None,
                 parallel: ParallelConfig | None = None) -> None:
        self.model = model
        self.cfg: ModelConfig = model.cfg
        # A private registry when the caller has none (direct construction in
        # tests/probes): the counters still work, they just aren't scraped.
        self.metrics = metrics if metrics is not None else Metrics()
        # Server-wide multi-chip plan ([parallel] block): bounds the device
        # set and sizes the sharded data axis. The MODE override happens at
        # the config level (ServerState.build rewrites cfg.parallelism
        # before the model is even built, so family-level mode checks see
        # it); by the time a runtime exists, cfg.parallelism is the truth.
        self.pcfg = parallel if parallel is not None else ParallelConfig()
        self.mode = self.cfg.parallelism
        if self.mode not in ("sharded", "replica", "single"):
            raise ValueError(f"unknown parallelism mode {self.mode!r}")
        if self.cfg.quantize not in (None, "int8", "int8c"):
            raise ValueError(f"unknown quantize mode {self.cfg.quantize!r}")
        if (self.cfg.quantize == "int8c"
                and not model.int8c_native_kernel_paths()):
            raise ValueError(
                f"{model.name}: quantize='int8c' (int8 COMPUTE) is not "
                f"supported by family {self.cfg.family!r} — it names no "
                "int8-native kernel sites; use quantize='int8' "
                "(weight-only) instead")

        # Device set the [parallel] plan serves on: every visible device by
        # default, the first n_chips when bounded. `data` alone sizes a
        # sharded mesh to exactly data*tp*sp chips.
        n_chips = self.pcfg.n_chips
        if not n_chips and self.pcfg.data and self.mode == "sharded":
            n_chips = self.pcfg.data * self.cfg.tp * self.cfg.sp
        devs = select_devices(n_chips)
        if self.mode == "replica":
            # One 1-device mesh per device; params replicated per device.
            # Each replica is an independent failure/serving domain: the
            # batcher keeps a depth-k staging-slot pool per entry here.
            self.meshes = [make_mesh(MeshPlan(), devices=[d]) for d in devs]
        elif self.mode == "single":
            self.meshes = [make_mesh(MeshPlan(), devices=[devs[0]])]
        else:
            self.meshes = [mesh if mesh is not None
                           else make_mesh(plan_for(self.pcfg, tp=self.cfg.tp,
                                                   sp=self.cfg.sp),
                                          devices=devs)]
        if self.mode == "sharded":
            # Sharded-batch executables need batch % data-axis == 0; normalize
            # buckets up to mesh multiples (batch=1 latency work belongs in
            # replica mode, SURVEY.md §2.1).
            from tpuserve.parallel.mesh import pad_batch_to_mesh

            aligned = sorted({pad_batch_to_mesh(b, self.meshes[0]) for b in self.cfg.batch_buckets})
            if aligned != self.cfg.batch_buckets:
                log.info("%s: batch buckets %s -> %s (data axis %d)",
                         model.name, self.cfg.batch_buckets, aligned,
                         self.meshes[0].shape["data"])
                self.cfg.batch_buckets = aligned

        self.params_per_mesh: list[Any] = []
        # Compiled-variant registry (ISSUE 6): every executable set is keyed
        # by the full specialization (bucket x dtype x quantize x
        # parallelism) and cheap to enumerate; ``executables`` remains the
        # hot-path view of the ACTIVE variant per bucket (same Executable
        # objects — the registry adds identity and accounting, not a copy).
        self.variants: dict[VariantKey, Variant] = {}
        self.executables: dict[tuple, list[Executable]] = {}
        # Generative programs (tpuserve.genserve): tag -> GenProgram. Kept
        # off the forward hot-path view but inside the variant registry.
        self.gen_programs: dict[str, GenProgram] = {}
        # The generation engine's compiled-geometry record (slot width,
        # paged-KV pool shape, prefill chunk): a second engine reusing
        # this runtime's programs must match it exactly — the state block
        # is shape-frozen (genserve.engine.GenEngine.compile).
        self.gen_meta: dict = {}
        # False when this runtime backs an iteration-level engine: the
        # engine's programs replace the forward bucket executables, so
        # compile_all/ensure_compiled must not build (or re-demand) them.
        self.compile_forward = True
        # Per-bucket raw-executable time (ms/batch), measured by
        # probe_raw_ms with inputs already resident — the device-time term
        # of the roofline's compute split (docs/PERFORMANCE.md).
        self.raw_ms_per_batch: dict[tuple, float | None] = {}
        # When True, h2d() blocks until the transfer completes so the "h2d"
        # phase owns the wire and "compute" measures dispatch-to-ready only
        # (roofline attribution; [pipeline] h2d_sync, set by the batcher).
        self.h2d_sync = False
        name = model.name
        # Every .compile() increments this; a steady-state delta of 0 is the
        # proof that serving repeat buckets (and publish/rollback churn)
        # recompiles nothing (scripts/roofline_smoke.sh asserts it).
        self._c_compiles = self.metrics.counter(
            f"runtime_compiles_total{{model={name}}}")
        self._g_variants = self.metrics.gauge(
            f"runtime_variants{{model={name}}}")
        # Batches dispatched per specialized variant, prebound at compile
        # time (one locked inc per batch, not per request).
        self._c_variant_batches: dict[tuple, Any] = {}
        self._c_path_batches: dict[tuple, Any] = {}
        # Per-chip dispatch attribution (Clockwork P3: predictability needs
        # per-device accounting shipped WITH the parallel placement, not
        # after it): one prebound counter per replica, ticked in dispatch().
        # In sharded mode there is one entry covering the whole mesh — the
        # per-chip share is the aggregate divided by the data-axis size,
        # which /stats' parallel block reports alongside.
        self._c_replica_batches = [
            self.metrics.replica_batches_counter(name, i)
            for i in range(len(self.meshes))]
        # Versioned lifecycle (tpuserve.lifecycle): the live tree carries a
        # monotonically numbered version; publish() retains the previous tree
        # as last-known-good so rollback() is a pointer swap, not a reload.
        self.version = 1
        self._version_seq = 1  # never reused, even across rollbacks
        self._prev_params: list[Any] | None = None
        self._prev_version: int | None = None
        self._rr = 0  # round-robin cursor for replica mode
        self._rr_lock = new_lock("runtime.replica_rr")
        self._reload_lock = new_lock("runtime.reload")
        # Deterministic chaos (tpuserve.faults.FaultInjector); None in prod.
        # Kinds "device_error"/"slow_compute" fire inside run() — below the
        # batcher — so retry/breaker behavior is proven against failures the
        # batcher did not itself synthesize.
        self.injector = None

    # -- startup ------------------------------------------------------------
    def load_and_shard_params(self) -> None:
        # Init/load on the host CPU backend, cast on host, then device_put
        # exactly once per mesh: a host-side numpy cast (ml_dtypes handles
        # bf16) beats dispatching hundreds of tiny convert ops.
        drawn = self._params_drawn_on_device()
        if drawn is not None:
            self.params_per_mesh = drawn
            return
        self.params_per_mesh = self._shard_onto_meshes(self._load_host_params())

    def _params_drawn_on_device(self) -> "list | None":
        """Weights by recipe (ISSUE 28): a family with ``device_params``
        draws its tensors on the chip that serves them, in the served type,
        so that a model of ten gigabytes never crosses the host. One device a
        mesh only (a drawn tree has no partition specs), and no quantization
        of what was never on the host. None where the family has no such
        hook or declines (a checkpoint is configured, no recipe is set)."""
        hook = getattr(self.model, "device_params", None)
        if hook is None or self.cfg.quantize in ("int8", "int8c") \
                or any(m.devices.size != 1 for m in self.meshes):
            return None
        out = []
        for mesh in self.meshes:
            params = hook(mesh.devices.flat[0])
            if params is None:
                return None
            out.append(params)
        log.info("%s: params drawn on the device by the family's recipe",
                 self.model.name)
        return out

    def _load_host_params(self, verify_integrity: bool = True,
                          require_manifest: bool = False) -> Any:
        try:
            cpu = jax.local_devices(backend="cpu")[0]
        except RuntimeError:  # JAX_PLATFORMS names the accelerator alone
            cpu = None
        if cpu is not None:
            with jax.default_device(cpu):
                params = self.model.load_params()
        else:
            params = self.model.load_params()
        log.info("%s: params initialised on %s", self.model.name,
                 "the host cpu backend" if cpu is not None
                 else "the default device (no cpu backend in JAX_PLATFORMS)")
        with allow_transfers():  # deliberate: weights land host-side first
            params = jax.device_get(params)
        # Integrity gate BEFORE the compute-dtype cast: the sidecar manifest
        # digests the checkpoint's raw bytes, so the comparison must see the
        # tree exactly as restored.
        if verify_integrity and self.cfg.weights:
            from tpuserve import savedmodel

            if savedmodel.detect_format(self.cfg.weights) == "orbax":
                savedmodel.verify_manifest_if_present(
                    self.cfg.weights, params, require=require_manifest)
        dtype = jnp.dtype(self.cfg.dtype)
        # Pre-quantized {"q8", "q8_scale"} subtrees stay as saved: scales are
        # deliberately float32 (dequant casts into the compute dtype itself).
        from tpuserve import quantize as qz

        return jax.tree_util.tree_map(
            lambda x: x if qz.is_quantized(x)
            else (x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x),
            params,
            is_leaf=qz.is_quantized,
        )

    def _shard_onto_meshes(self, params: Any) -> list:
        from tpuserve import quantize as qz

        rules = self.model.partition_rules()
        pre_quantized = qz.has_quantized_leaves(params)
        if pre_quantized and self.cfg.quantize not in ("int8", "int8c"):
            raise ValueError(
                f"{self.model.name}: loaded weights are int8-quantized but "
                "quantize is not set; set quantize = \"int8\"")
        if self.cfg.quantize in ("int8", "int8c"):
            # Quantize first (idempotent over pre-quantized checkpoints),
            # then derive specs from the tree's actual quantization state —
            # rule regexes see the original weight paths, scale specs derive
            # from their weight's, and no save-time min_size agreement is
            # needed for sharding.
            params = qz.quantize_tree(params, self.cfg.quantize_min_size)
            specs = qz.specs_for_tree(rules, params)
        else:
            specs = match_partition_rules(rules, params)
        out = []
        for mesh in self.meshes:
            shardings = specs_to_shardings(specs, mesh)
            out.append(jax.tree_util.tree_map(jax.device_put, params, shardings))
        return out

    def _forward_fn(self):
        """The function each bucket compiles: the model's forward, behind a
        dequantization layer when weights are stored int8."""
        if self.cfg.quantize == "int8":
            from tpuserve import quantize as qz

            dtype = jnp.dtype(self.cfg.dtype)
            return lambda p, batch: self.model.forward(
                qz.dequantize_tree(p, dtype), batch)
        if self.cfg.quantize == "int8c":
            # int8 COMPUTE: kernels the model consumes natively (Int8Dense
            # sites) stay {"q8", "q8_scale"} and hit the MXU's int8 path;
            # everything else dequantizes as in weight-only mode.
            from tpuserve import quantize as qz

            dtype = jnp.dtype(self.cfg.dtype)
            keep = self.model.int8c_native_kernel_paths()
            return lambda p, batch: self.model.forward(
                qz.dequantize_tree_except(p, dtype, keep), batch)
        return self.model.forward

    @property
    def parallel_signature(self) -> str:
        """The parallelism dimension of every VariantKey this runtime
        compiles (ISSUE 7): the mode PLUS the device layout it was
        specialized on, so an 8-chip sharded executable and a 1-chip one
        are distinct registry entries (they are different XLA programs)
        while staying one label on a dashboard. "single" stays bare — it
        is the 1-chip degenerate case every prior test/bench name uses."""
        if self.mode == "sharded":
            return f"sharded@d{self.meshes[0].shape['data']}"
        if self.mode == "replica":
            return f"replica@{len(self.meshes)}"
        return self.mode

    def variant_key(self, bucket: tuple) -> VariantKey:
        """The ACTIVE variant identity for a bucket: what this runtime's
        config specializes its executables on."""
        return VariantKey(bucket=tuple(bucket), dtype=self.cfg.dtype,
                          quantize=self.cfg.quantize,
                          parallelism=self.parallel_signature)

    def compile_all(self, pool: cf.ThreadPoolExecutor | None = None) -> None:
        """AOT-compile every bucket (in parallel when a pool is given)."""
        t0 = time.perf_counter()
        buckets = self.model.buckets()
        if pool is None:
            for b in buckets:
                self._compile_bucket(b)
        else:
            list(pool.map(self._compile_bucket, buckets))
        log.info(
            "%s: compiled %d bucket(s) x %d replica(s) in %.1fs",
            self.model.name, len(buckets), len(self.meshes), time.perf_counter() - t0,
        )

    def ensure_compiled(self, params_per_mesh: "list[Any] | None" = None) -> int:
        """Compile any configured bucket missing from the variant registry;
        returns how many variants were newly compiled.

        The lifecycle calls this at STAGE time (tpuserve.lifecycle), so a
        staged canary — and the first post-publish request — never pays a
        first-compile: by the time a candidate tree runs, every variant it
        can reach is resident. In the common case (shapes unchanged across
        versions, which stage_params enforces) this is a cheap no-op whose
        return value of 0 is itself the steady-state proof.

        ``params_per_mesh`` supplies the tree the compilation derives its
        param shardings/structs from when the LIVE tree is absent — a
        cold-booted model's first warm-up (tpuserve.scheduler) compiles
        against the staged candidate before anything has published. Once
        compiled, warm→cold→warm churn re-uses the variants: the counter
        delta across re-warms of an already-compiled model is 0."""
        new = 0
        if not self.compile_forward:
            # Engine-backed runtime: the generative programs were all
            # registered at engine compile time and shapes never change
            # across versions, so there is nothing to demand here — the
            # 0 return IS the steady-state proof for the gen path.
            return new
        for b in self.model.buckets():
            if self.variant_key(tuple(b)) not in self.variants:
                self._compile_bucket(tuple(b), params_per_mesh)
                new += 1
        return new

    @property
    def compiles_total(self) -> float:
        """Executables compiled over this runtime's lifetime (the
        ``runtime_compiles_total`` counter's value)."""
        return self._c_compiles.value

    def variants_summary(self) -> list[dict]:
        """Cheap enumeration of every resident compiled variant. The sort
        key stringifies bucket elements: forward buckets are int tuples,
        generative programs (tag, width) tuples, and Python refuses to
        order str against int."""
        return [v.summary() for _, v in sorted(
            self.variants.items(),
            key=lambda kv: tuple(str(x) for x in kv[0].bucket))]

    def _compile_bucket(self, bucket: tuple,
                        params_per_mesh: "list[Any] | None" = None) -> None:
        t0 = time.perf_counter()
        exes = []
        ppm = params_per_mesh if params_per_mesh else self.params_per_mesh
        for i, mesh in enumerate(self.meshes):
            params = ppm[i]
            batch_struct = self.model.input_signature(bucket)
            # batch_spec is either one P applied to every leaf, or a pytree of
            # P matching batch_struct's structure.
            spec = self.model.batch_spec()
            if isinstance(spec, P):
                in_batch_sharding = jax.tree_util.tree_map(
                    lambda _s: NamedSharding(mesh, spec), batch_struct
                )
            else:
                in_batch_sharding = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), spec,
                    is_leaf=lambda x: isinstance(x, P),
                )
            out_spec = self.model.out_spec()
            if isinstance(out_spec, P):
                out_shardings = NamedSharding(mesh, out_spec)
            else:
                out_shardings = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), out_spec,
                    is_leaf=lambda x: isinstance(x, P),
                )
            param_shardings = jax.tree_util.tree_map(lambda x: x.sharding, params)
            params_struct = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), params
            )
            # Donate the batch input only when every leaf provably aliases an
            # output (shape+dtype+sharding match; _donation_shapes_ok) —
            # typical classifiers (uint8 in, small float out) never qualify
            # and compile warning-free (ADVICE r1). Never on the CPU backend:
            # device_put there may alias host memory (the assembly arena),
            # and a donated alias would let XLA scribble on a recycled
            # arena buffer.
            fwd = self._forward_fn()
            donate = False
            if jax.default_backend() != "cpu":
                out_struct = jax.eval_shape(fwd, params_struct, batch_struct)
                donate = _donation_shapes_ok(
                    batch_struct, in_batch_sharding, out_struct, out_shardings)
            jitted = jax.jit(
                fwd,
                in_shardings=(param_shardings, in_batch_sharding),
                out_shardings=out_shardings,
                donate_argnums=(1,) if donate else (),
            )
            with trace_span("tpuserve.compile", model=self.model.name,
                            bucket=bucket_label(bucket)):
                compiled = jitted.lower(params_struct, batch_struct).compile()
            exes.append(Executable(bucket, compiled, in_batch_sharding,
                                   device_index=i, donated=donate))
        key = self.variant_key(bucket)
        paths = self.model.traced_paths(bucket)
        self.variants[key] = Variant(
            key, exes, compile_ms=(time.perf_counter() - t0) * 1e3, paths=paths)
        self.executables[bucket] = exes
        # Registered before the counters tick so a scrape can never observe
        # a compile with no variant behind it.
        self._c_compiles.inc(len(exes))
        # Retrace witness: a post-warmup-barrier compile raises here, with
        # the variant already registered and the counter ticked — the
        # ledgers stay consistent while the violation propagates to
        # whoever demanded the compile.
        witness.note_compile(self.model.name, key.label)
        self._g_variants.set(len(self.variants))
        self._c_variant_batches[bucket] = self.metrics.counter(
            f"runtime_variant_batches_total{{model={self.model.name},"
            f"variant={key.label}}}")
        if paths:
            # The same launches under the traced paths' names: over the
            # series above, the share of launches that went through a kernel.
            chosen = ",".join(f"{k}={v}" for k, v in sorted(paths.items()))
            self._c_path_batches[bucket] = self.metrics.counter(
                f"runtime_variant_path_batches_total{{model={self.model.name},"
                f"variant={key.label},{chosen}}}")

    # -- generative programs (tpuserve.genserve) ------------------------------
    def register_program(self, tag: str, fn, arg_structs: tuple,
                         width: int = 0,
                         donate_argnums: tuple = (),
                         arg_specs: "tuple | None" = None,
                         out_specs: Any = None) -> GenProgram:
        """AOT-compile ``fn(params, *args)`` against the live param
        structure and register it in the specialized-variant registry.

        The iteration-level engine's executables (insert / step / extract)
        go through here so they get the same discipline as forward buckets:
        a frozen VariantKey identity (bucket = (tag, width) — enumerable in
        /v1/models and /stats), a ``runtime_compiles_total`` tick per
        compile (the zero-steady-state-recompile proof covers them), and a
        prebound per-variant serving counter ticked by run_program.
        Weight versions stay out of the key exactly as for forward buckets:
        publish/rollback swap trees under unchanged shapes, so every
        version reuses the registered program.

        The zero-recompile obligation covers every index a program
        consumes: slot indices AND — for the paged-KV programs (ISSUE 18)
        — page/block-table indices and the chunk-start cursor are all
        TRACED arguments, never baked into shapes, so slot churn, page
        churn, and chunked-prefill progress all replay the same compiled
        executables (``runtime_compiles_total`` steady-state delta 0).

        Layout composition (ISSUE 20): in "single"/"sharded" modes one
        program is compiled against the one mesh; in "replica" mode the
        SAME program is compiled once per replica mesh (mirroring
        ``_compile_bucket``), so one ``GenEngine`` per replica dispatches
        via ``run_program(..., replica=i)`` with no cross-engine contention
        on compiled state.

        ``arg_structs`` leaves are replicated (P()) onto the mesh unless
        ``arg_specs`` (a tuple parallel to ``arg_structs`` of
        PartitionSpec trees or ``None`` per arg) pins them to mesh axes —
        the sharded decode path puts KV heads on "model" and pages on
        "seq". ``out_specs`` (a PartitionSpec pytree-prefix of the output)
        pins output shardings: REQUIRED whenever a sharded output feeds
        back as an input of the same AOT executable (the engine's state
        block), because ``jax.stages.Compiled`` demands exact input
        shardings and would otherwise see GSPMD-chosen layouts drift.
        Params keep their partition-rule shardings. ``donate_argnums``
        indexes into ``args`` (0 = the first arg after params) and is
        honored off-CPU only — on the CPU backend device_put may alias
        host memory (the assembly-arena rule)."""
        t0 = time.perf_counter()
        donate = ()
        if donate_argnums and jax.default_backend() != "cpu":
            donate = tuple(1 + i for i in donate_argnums)
        if arg_specs is None:
            arg_specs = (None,) * len(arg_structs)
        exes: list[Executable] = []
        compiled_per_mesh: list = []
        arg_shardings: tuple = ()
        for i, mesh in enumerate(self.meshes):
            params = self.params_per_mesh[i]
            param_shardings = jax.tree_util.tree_map(
                lambda x: x.sharding, params)
            params_struct = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), params)
            arg_shardings = tuple(
                struct_shardings(mesh, struct, spec)
                for struct, spec in zip(arg_structs, arg_specs))
            jit_kwargs: dict = {}
            if out_specs is not None:
                jit_kwargs["out_shardings"] = specs_to_shardings(
                    out_specs, mesh)
            jitted = jax.jit(fn,
                             in_shardings=(param_shardings, *arg_shardings),
                             donate_argnums=donate, **jit_kwargs)
            compiled = jitted.lower(params_struct, *arg_structs).compile()
            compiled_per_mesh.append(compiled)
            exes.append(Executable((tag, width), compiled,
                                   batch_sharding=arg_shardings,
                                   device_index=i, donated=bool(donate)))
        prog = GenProgram(tag, compiled_per_mesh, donated=bool(donate))
        self.gen_programs[tag] = prog
        key = self.variant_key((tag, width))
        self.variants[key] = Variant(
            key, exes, compile_ms=(time.perf_counter() - t0) * 1e3)
        self._c_compiles.inc(len(exes))
        witness.note_compile(tag, key.label)  # retrace witness (see above)
        self._g_variants.set(len(self.variants))
        prog.counter = self._c_variant_batches[(tag, width)] = \
            self.metrics.counter(
                f"runtime_variant_batches_total{{model={self.model.name},"
                f"variant={key.label}}}")
        return prog

    def run_program(self, tag: str, *args,
                    params_override: "list[Any] | None" = None,
                    replica: int = 0) -> Any:
        """Async-dispatch a registered generative program against the LIVE
        param tree (or a staged candidate via ``params_override`` — the
        lifecycle's staged canary runs a short generation through the real
        compiled programs without the candidate ever serving). The params
        list is snapshotted per call, so every dispatch is version-
        consistent and a mid-flight publish affects only later iterations.
        ``replica`` selects the per-mesh executable + param copy in replica
        mode (each replica engine passes its own index) and ticks that
        replica's dispatch ledger so /stats' parallel block proves every
        chip actually generates."""
        if self.injector is not None:
            delay = self.injector.delay_s("slow_compute", self.model.name)
            if delay > 0:
                time.sleep(delay)  # runs on a stage executor thread
            self.injector.check("device_error", self.model.name)
        prog = self.gen_programs[tag]
        if prog.counter is not None:
            prog.counter.inc()
        self._c_replica_batches[replica].inc()
        params = (params_override if params_override is not None
                  else self.params_per_mesh)
        return prog.compiled[replica](params[replica], *args)

    # -- hot path -----------------------------------------------------------
    @property
    def n_replicas(self) -> int:
        """Independent executable sets (the batcher keeps a depth-k
        staging-slot pool per replica)."""
        return len(self.meshes)

    @property
    def n_chips(self) -> int:
        """Physical devices the serving path occupies: replica meshes are
        disjoint single-device meshes (sum = chip count), a sharded mesh is
        one mesh spanning them all."""
        return sum(m.size for m in self.meshes)

    def replica_batches(self) -> list[float]:
        """Current per-replica dispatch counts (replica_batches_total),
        in replica order — the /stats parallel block and the multichip
        smoke read these to prove every chip actually serves."""
        return [c.value for c in self._c_replica_batches]

    def pick_replica(self, loads: "list[int] | None" = None) -> int:
        """First-choice replica for the next batch.

        With ``loads`` (the batcher passes each replica's staging-slot
        occupancy) this is least-loaded: the emptiest device section gets
        the work, so a slow batch on one chip never starves the other
        seven of their depth-k pipelines. Ties break on a rotating
        round-robin cursor so equal-load replicas still alternate instead
        of replica 0 absorbing every cold start. Without ``loads`` it is
        plain round-robin (prewarm, canaries, direct run() callers)."""
        n = len(self.meshes)
        if n == 1:
            return 0
        with self._rr_lock:
            self._rr = (self._rr + 1) % n
            start = self._rr
        if not loads:
            return start
        return min(range(n), key=lambda i: (loads[i], (i - start) % n))

    def h2d(self, bucket: tuple, host_batch: Any, replica: int = 0) -> Any:
        """Transfer stage: ONE batched device_put of the whole host pytree
        against the bucket's input shardings (a single transfer call, not a
        tree_map of per-leaf puts). Runs on the pipeline's h2d executor.

        With ``h2d_sync`` (the [pipeline] default) the call blocks until the
        transfer completes, so the "h2d" phase owns the wire wait and the
        "compute" phase measures dispatch-to-ready only — without it a
        buffered/async transfer returns instantly and its wall time silently
        lands in "compute" (exactly the r05 465-ms-vs-24-ms ambiguity the
        roofline split exists to name). Throughput is unaffected: the block
        happens on a dedicated h2d stage thread the link serializes anyway."""
        exe = self.executables[bucket][replica]
        dev = jax.device_put(host_batch, exe.batch_sharding)
        if self.h2d_sync:
            jax.block_until_ready(dev)
        return dev

    def dispatch(self, bucket: tuple, dev_batch: Any, replica: int = 0,
                 params_override: list[Any] | None = None) -> Any:
        """Compute stage: async-dispatch the compiled call against an
        already-transferred device batch; returns device outputs immediately
        (XLA async dispatch). Chaos kinds device_error/slow_compute fire
        here — below the batcher — on both the run() and pipelined paths."""
        if self.injector is not None:
            delay = self.injector.delay_s("slow_compute", self.model.name)
            if delay > 0:
                time.sleep(delay)  # runs on a stage executor thread
            self.injector.check("device_error", self.model.name)
        exe = self.executables[bucket][replica]
        c = self._c_variant_batches.get(bucket)
        if c is not None:
            c.inc()
        c = self._c_path_batches.get(bucket)
        if c is not None:
            c.inc()
        self._c_replica_batches[replica].inc()
        params = (params_override if params_override is not None
                  else self.params_per_mesh)
        # On the profiler's clock, from the thread that launches: the span
        # ends when XLA has the program queued, before the device's own
        # `XLA Modules` event begins. Nested in the batcher's tpuserve.h2d
        # span, which names the batch.
        with trace_span("tpuserve.launch", model=self.model.name,
                        bucket=bucket_label(bucket), replica=replica):
            return exe.compiled(params[replica], dev_batch)

    def run(self, bucket: tuple, host_batch: Any, replica: int | None = None,
            params_override: list[Any] | None = None) -> Any:
        """H2D + async dispatch in one call (h2d -> dispatch). Returns the
        device output pytree immediately.

        ``params_override`` (a per-mesh tree list shaped like
        ``params_per_mesh``) runs this batch against a DIFFERENT weight tree
        than the published one — the lifecycle's staged canary executes the
        candidate version through the real compiled executables without it
        ever serving traffic."""
        i = replica if replica is not None else self.pick_replica()
        return self.dispatch(bucket, self.h2d(bucket, host_batch, i), i,
                             params_override=params_override)

    @staticmethod
    def fetch(outputs: Any) -> Any:
        """Block for D2H; call off the event loop. Routes through the
        retrace witness's blessed readback so an armed transfer guard
        (TPUSERVE_RETRACE_WITNESS=1) never trips on deliberate fetches."""
        return host_fetch(outputs)

    def prewarm(self) -> None:
        """Execute every (bucket, replica) once on zeros and block for it.

        Compiling does not load the program onto the device: the first real
        execution pays PJRT program load. Paying that at startup keeps it
        off the first real request's latency and out of any measurement
        window.
        """
        t0 = time.perf_counter()
        pending = []
        for bucket, exes in sorted(self.executables.items()):
            struct = self.model.input_signature(bucket)
            host = jax.tree_util.tree_map(
                lambda s: np.zeros(s.shape, s.dtype), struct)
            # Dispatch everything async first so loads on distinct devices
            # overlap; then one D2H fetch per executable: the dependent read
            # proves the program load completed and the output is fetchable.
            pending.extend(self.run(bucket, host, replica=i)
                           for i in range(len(exes)))
        for out in pending:
            self.fetch(out)
        log.info("%s: prewarmed %d executable(s) in %.1fs",
                 self.model.name, len(pending), time.perf_counter() - t0)

    # -- roofline probes ------------------------------------------------------
    def probe_raw_ms(self, bucket: tuple, iters: int = 8,
                     replica: int = 0) -> float | None:
        """Raw-executable time for one bucket (ms/batch), inputs resident.

        ``iters`` back-to-back async dispatches against an already-
        transferred device batch, closed by ONE dependent D2H read — the
        wire never appears in the window, so this is the device-time
        ceiling the serving "compute" phase is measured against
        (docs/PERFORMANCE.md "Reading the roofline"). Donated variants are
        skipped (None): re-dispatching a donated buffer is a use-after-
        donate, and re-transferring per iteration would put the wire back
        in the window. Call after prewarm (PJRT program load out of the
        way) and before the injector is armed."""
        exes = self.executables.get(bucket)
        if not exes or exes[replica].donated:
            self.raw_ms_per_batch[bucket] = None
            return None
        struct = self.model.input_signature(bucket)
        host = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype), struct)
        dev = jax.device_put(host, exes[replica].batch_sharding)
        jax.block_until_ready(dev)
        self.fetch(self.dispatch(bucket, dev, replica))  # warm the window
        t0 = time.perf_counter()
        out = None
        for _ in range(max(1, iters)):
            out = self.dispatch(bucket, dev, replica)
        self.fetch(out)  # dependent read: the only honest completion signal
        ms = (time.perf_counter() - t0) / max(1, iters) * 1e3
        self.raw_ms_per_batch[bucket] = round(ms, 3)
        return ms

    def probe_all_raw(self, iters: int = 8) -> dict[tuple, float | None]:
        """probe_raw_ms over every compiled bucket; returns the map (also
        retained on the runtime for /stats roofline attribution)."""
        t0 = time.perf_counter()
        for bucket in sorted(self.executables):
            self.probe_raw_ms(bucket, iters=iters)
        log.info("%s: raw-executable probes %s in %.1fs", self.model.name,
                 {str(b): v for b, v in sorted(self.raw_ms_per_batch.items())},
                 time.perf_counter() - t0)
        return dict(self.raw_ms_per_batch)

    # -- versioned weight lifecycle ------------------------------------------
    #
    # stage_params -> (staged canary, lifecycle.py) -> publish | rollback.
    # Staging builds and validates the candidate tree entirely OFF the
    # serving path; publish is one reference assignment under the reload
    # lock — no window where inference can observe a half-validated tree,
    # and in-flight batches finish on the old params (their dispatch
    # captured the references). The previous tree is retained as
    # last-known-good so rollback is a pointer swap, not a disk load.

    def stage_params(self, verify_integrity: bool = True,
                     nan_scan: bool = True,
                     require_manifest: bool = False) -> list[Any]:
        """Load + validate a candidate weight tree without publishing it.

        Gates, in order (each names the failure precisely so the lifecycle
        can label the rejection): sidecar checksum manifest (IntegrityError),
        NaN/Inf scan of the float leaves (NaNDetected), and shape/dtype/
        structure match against what the executables were compiled for
        (ValueError). Injected ``reload_corrupt`` / ``reload_nan`` faults
        fire at their respective gates so chaos drills prove each rejection
        path keeps the old version serving."""
        name = self.model.name
        if self.injector is not None:
            from tpuserve.faults import FaultInjected
            from tpuserve.savedmodel import IntegrityError

            try:
                self.injector.check("reload_corrupt", name)
            except FaultInjected as e:
                raise IntegrityError(
                    f"checksum mismatch (injected): {e}") from e
        params = self._load_host_params(verify_integrity=verify_integrity,
                                        require_manifest=require_manifest)
        if nan_scan:
            if self.injector is not None:
                from tpuserve.faults import FaultInjected

                try:
                    self.injector.check("reload_nan", name)
                except FaultInjected as e:
                    raise NaNDetected(f"NaN leaves (injected): {e}") from e
            from tpuserve.utils.trees import nonfinite_paths

            bad = nonfinite_paths(params)
            if bad:
                raise NaNDetected(
                    f"candidate weights for {name} hold NaN/Inf in {bad}; "
                    "candidate rejected")
        fresh = self._shard_onto_meshes(params)
        old = self.params_per_mesh
        if old:
            same_struct = (jax.tree_util.tree_structure(old[0])
                           == jax.tree_util.tree_structure(fresh[0]))
            if not same_struct or any(
                a.shape != b.shape or a.dtype != b.dtype
                for a, b in zip(jax.tree_util.tree_leaves(old[0]),
                                jax.tree_util.tree_leaves(fresh[0]))):
                raise ValueError(
                    "reloaded weights do not match the compiled "
                    "shapes/dtypes; old params kept")
        return fresh

    def publish(self, staged: list[Any]) -> dict:
        """Atomically make a staged tree live as version N+1; the previous
        tree is retained as last-known-good for rollback().

        Multi-chip atomicity (ISSUE 7): ``staged`` holds one tree PER MESH
        (stage_params device_puts the candidate to every replica / the
        whole sharded mesh before this is called), and the publication is
        ONE list-reference assignment — so there is no instant at which
        replica 3 serves version N+1 while replica 5 still serves N.
        dispatch() snapshots the list once per batch; in-flight batches
        finish on the version they captured, which is version-consistent
        per batch by construction."""
        with self._reload_lock:
            # A cold-booted/demoted runtime has no live tree: retaining []
            # would make rollback() "restore" an unservable empty state.
            self._prev_params = self.params_per_mesh or None
            self._prev_version = self.version if self.params_per_mesh else None
            self._version_seq += 1
            self.version = self._version_seq
            self.params_per_mesh = staged
            return {"model": self.model.name, "version": self.version,
                    "previous_version": self._prev_version}

    def rollback(self) -> dict:
        """Restore the retained last-known-good tree (version N-1).

        One reference assignment, same publication discipline as publish().
        Version numbers are never reused: a later publish continues the
        monotonic sequence. Raises ValueError when nothing is retained
        (startup state, or already rolled back)."""
        with self._reload_lock:
            if self._prev_params is None:
                raise ValueError(
                    f"no retained previous version for {self.model.name} "
                    "to roll back to")
            rolled_from = self.version
            self.params_per_mesh = self._prev_params
            self.version = self._prev_version
            self._prev_params = None
            self._prev_version = None
            return {"model": self.model.name, "version": self.version,
                    "rolled_back_from": rolled_from}

    def release_params(self) -> None:
        """Demote to cold (tpuserve.scheduler weight paging): drop every
        device-resident param tree — the live one AND the retained
        last-known-good — so the device buffers free once in-flight batches
        (which captured their own references at dispatch) complete. The
        compiled variant registry stays resident: a later re-warm
        (stage_params → publish) serves through the same executables with
        zero recompiles."""
        with self._reload_lock:
            self.params_per_mesh = []
            self._prev_params = None
            self._prev_version = None

    @property
    def params_resident(self) -> bool:
        """True while a live device param tree is resident (False = cold:
        HBM for this model's weights is free)."""
        return bool(self.params_per_mesh)

    def reload_params(self) -> dict:
        """Hot-swap weights from cfg.weights without recompiling.

        Compatibility path (stage + publish in one call, no canary): the
        HTTP reload goes through tpuserve.lifecycle, which canaries the
        staged tree first and owns rollback. A failed stage raises and the
        old params keep serving. Serialized via the reload lock in
        publish(); concurrent stagings are themselves read-only."""
        t0 = time.perf_counter()
        staged = self.stage_params()
        info = self.publish(staged)
        info["reload_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        info["params"] = self.describe()["params"]
        return info

    # -- info ---------------------------------------------------------------
    def describe(self) -> dict:
        from tpuserve.utils.trees import tree_summary

        return {
            "model": self.model.name,
            "family": self.cfg.family,
            "version": self.version,
            "mode": self.mode,
            "dtype": self.cfg.dtype,
            "quantize": self.cfg.quantize,
            # Provenance + behavior knobs operators need to see live: seeded
            # random weights (None) vs a real artifact, and per-family options
            # like BERT's attention impl.
            "weights": self.cfg.weights,
            "labels": self.cfg.labels,
            "options": dict(self.cfg.options),
            "replicas": len(self.meshes),
            "n_chips": self.n_chips,
            "parallel": self.parallel_signature,
            "mesh_shape": dict(self.meshes[0].shape),
            "buckets": [list(b) for b in sorted(self.executables)],
            # Specialized-variant registry: what is compiled-resident, with
            # what it was specialized on (ISSUE 6; enumerable per P2).
            "variants": self.variants_summary(),
            "compiles_total": self.compiles_total,
            "params": tree_summary(self.params_per_mesh[0]) if self.params_per_mesh else {},
        }


def build_runtime(model: ServingModel, mesh: Mesh | None = None,
                  pool: cf.ThreadPoolExecutor | None = None,
                  metrics: Metrics | None = None,
                  parallel: ParallelConfig | None = None,
                  compile_forward: bool = True) -> ModelRuntime:
    """``compile_forward=False`` builds a params-only runtime for an
    iteration-level engine (tpuserve.genserve): the engine registers its
    insert/step/extract programs instead of the forward bucket set, so
    compiling both would double startup compile time for nothing."""
    rt = ModelRuntime(model, mesh, metrics=metrics, parallel=parallel)
    rt.compile_forward = compile_forward
    rt.load_and_shard_params()
    if compile_forward:
        rt.compile_all(pool)
    return rt
