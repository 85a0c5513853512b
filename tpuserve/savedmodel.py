"""Model weight import/export (SURVEY.md §2 C6; §5 checkpoint/resume).

The reference persists models as TF SavedModels executed by TF-GPU. The
TPU-native build separates *weights* from *graphs*: graphs are always our own
Flax modules (tpuserve.models), and this module moves weights between three
formats:

- **orbax checkpoint dir** — the native format. Fast, sharding-aware,
  TF-free startup. Produced by ``python -m tpuserve import-model`` or
  ``save_orbax``.
- **TF SavedModel dir** (``saved_model.pb`` + ``variables/``) — read via
  ``tf.saved_model.load`` on CPU; variables are extracted to a flat
  ``name -> np.ndarray`` dict and handed to the model family's
  ``import_tf_variables`` for name/layout translation (NHWC vs NCHW, fused
  BN, etc.). TF import is lazy: serving from orbax never imports TF.
- **frozen GraphDef ``.pb``** — 2016-era repos ship these; constants are
  extracted from the graph nodes into the same flat dict.
- **torch checkpoints** (``.safetensors`` / ``.ckpt`` / ``.pt`` / ``.pth`` /
  ``.bin``) — how SD 1.5-class artifacts actually ship (VERDICT r3 missing
  1). Read on CPU (safetensors directly; pickle checkpoints via
  ``torch.load(weights_only=True)`` so untrusted files cannot execute code)
  into the same flat ``name -> np.ndarray`` dict, then handed to the
  family's ``import_torch_variables``.

Detection is by directory shape, so ``ModelConfig.weights`` is just a path.
Golden-output parity between the TF graph and our Flax path is asserted in
tests (SURVEY.md §4-4), not here.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
from typing import Any

import jax
import numpy as np

log = logging.getLogger("tpuserve.savedmodel")


class IntegrityError(ValueError):
    """A checkpoint failed its sidecar checksum manifest (tpuserve.lifecycle:
    the reload path rejects the candidate and the old version keeps serving)."""


# -- format detection --------------------------------------------------------

def detect_format(path: str) -> str:
    """'orbax' | 'saved_model' | 'graphdef' | 'torch'."""
    if os.path.isdir(path):
        if os.path.exists(os.path.join(path, "saved_model.pb")):
            return "saved_model"
        return "orbax"
    if path.endswith(".pb"):
        return "graphdef"
    if path.endswith((".safetensors", ".ckpt", ".pt", ".pth", ".bin")):
        return "torch"
    raise ValueError(f"cannot identify weight format of {path!r}")


def load_params_for(model) -> Any:
    """Entry point used by ServingModel.load_params when cfg.weights is set."""
    path = model.cfg.weights
    fmt = detect_format(path)
    log.info("loading %s weights for %s from %s", fmt, model.name, path)
    if fmt == "orbax":
        return load_orbax(path, model)
    if fmt == "torch":
        try:
            state = extract_torch_state_dict(path)
        except Exception as e:
            if path.endswith(".bin"):
                # '.bin' is only *assumed* torch (pytorch_model.bin is the
                # common case); a GGML/raw-blob .bin fails torch parsing —
                # give the unidentified-format guidance instead of a bare
                # unpickling trace (ADVICE r4).
                raise ValueError(
                    f"cannot identify weight format of {path!r}: tried the "
                    "torch loader for the '.bin' suffix but it failed "
                    f"({type(e).__name__}: {e}); supported formats are orbax "
                    "dirs, TF SavedModel dirs, GraphDef .pb, and torch "
                    ".safetensors/.ckpt/.pt/.pth/.bin"
                ) from e
            raise
        return model.import_torch_variables(state)
    flat = (
        extract_saved_model_variables(path)
        if fmt == "saved_model"
        else extract_graphdef_constants(path)
    )
    return model.import_tf_variables(flat)


# -- sidecar checksum manifest (tpuserve.lifecycle integrity gate) -----------
#
# Written NEXT TO the orbax dir (<path>.manifest.json), never inside it, so
# orbax's own directory layout is untouched. Per-leaf sha256 over
# dtype/shape/raw bytes of the saved host tree; a reload recomputes the
# digests over the restored tree and any mismatch (bit rot, truncated copy,
# a writer racing the reload) rejects the candidate before it can serve.

MANIFEST_ALGO = "sha256"


def manifest_path(ckpt_path: str) -> str:
    return os.path.abspath(ckpt_path).rstrip("/") + ".manifest.json"


def tree_digests(params: Any) -> dict[str, str]:
    """{tree path: sha256 hex} over dtype + shape + raw bytes per leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    out: dict[str, str] = {}
    for path, leaf in flat:
        a = np.asarray(jax.device_get(leaf))
        h = hashlib.sha256()
        h.update(str(a.dtype).encode())
        h.update(repr(tuple(a.shape)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
        out[jax.tree_util.keystr(path)] = h.hexdigest()
    return out


def write_manifest(ckpt_path: str, params: Any) -> str:
    mpath = manifest_path(ckpt_path)
    doc = {"algo": MANIFEST_ALGO, "leaves": tree_digests(params)}
    tmp = mpath + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    os.replace(tmp, mpath)  # atomic: a racing reader never sees a torn file
    return mpath


def verify_manifest_if_present(ckpt_path: str, params: Any,
                               require: bool = False) -> bool:
    """Check ``params`` against the sidecar manifest; raises IntegrityError on
    any mismatch. Returns False when no manifest exists (skipped) — unless
    ``require`` is set, which makes a missing manifest itself a rejection."""
    mpath = manifest_path(ckpt_path)
    if not os.path.exists(mpath):
        if require:
            raise IntegrityError(
                f"no checksum manifest at {mpath!r} and lifecycle."
                "require_manifest is set; re-export the checkpoint with "
                "save_orbax / import-model")
        log.debug("no manifest for %s; integrity check skipped", ckpt_path)
        return False
    with open(mpath, encoding="utf-8") as f:
        doc = json.load(f)
    if doc.get("algo") != MANIFEST_ALGO:
        raise IntegrityError(
            f"manifest {mpath!r} uses unknown algo {doc.get('algo')!r}")
    want: dict[str, str] = doc.get("leaves", {})
    got = tree_digests(params)
    if got != want:
        missing = sorted(set(want) - set(got))
        extra = sorted(set(got) - set(want))
        changed = sorted(k for k in set(want) & set(got) if want[k] != got[k])
        detail = "; ".join(
            f"{label} {paths[:3]}" for label, paths in
            (("missing", missing), ("unexpected", extra), ("corrupt", changed))
            if paths)
        raise IntegrityError(
            f"checkpoint at {ckpt_path!r} fails its checksum manifest "
            f"({detail}); candidate rejected")
    return True


# -- orbax native checkpoints ------------------------------------------------

def save_orbax(path: str, params: Any) -> None:
    import orbax.checkpoint as ocp

    host_params = jax.device_get(params)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(path), host_params)
        ckptr.wait_until_finished()
    # Sidecar integrity manifest: the lifecycle reload gate verifies the
    # restored tree against these digests before staging.
    write_manifest(path, host_params)


def load_orbax(path: str, model) -> Any:
    """Restore an orbax checkpoint, raw or int8-quantized.

    The restore target comes from the checkpoint's own metadata (shapes +
    dtypes of the saved tree), so a checkpoint written by ``import-model
    --quantize int8`` — whose eligible leaves are {"q8", "q8_scale"}
    sub-trees — restores exactly as saved with no agreement needed on
    quantization settings. After restore, the tree is validated against the
    model's structure (quantized sub-trees collapse to their weight's
    shape) and a quantized checkpoint without quantize = "int8" set
    produces guidance, not a downstream crash.
    """
    import orbax.checkpoint as ocp

    from tpuserve import quantize as qz

    apath = os.path.abspath(path)
    with ocp.StandardCheckpointer() as ckptr:
        target = jax.tree_util.tree_map(
            lambda m: jax.ShapeDtypeStruct(tuple(m.shape), np.dtype(str(m.dtype))),
            ckptr.metadata(apath).item_metadata)
        # Restore as host numpy; the runtime device_puts with shardings.
        restored = ckptr.restore(apath, target)

    if qz.has_quantized_leaves(restored) \
            and getattr(model.cfg, "quantize", None) not in ("int8", "int8c"):
        raise ValueError(
            f"checkpoint at {path!r} holds int8-quantized weights; set "
            "quantize = \"int8\" (weight-only) or \"int8c\" (int8 compute) "
            "on the model to serve it")

    raw = jax.eval_shape(model.init_params, jax.random.key(0))
    shape_of = lambda x: (tuple(x[qz.QKEY].shape) if qz.is_quantized(x)  # noqa: E731
                          else tuple(x.shape))

    def dtype_ok(g, w) -> bool:
        # Exact dtype equality is too strict (bf16 vs f32 checkpoints are
        # both fine — the runtime casts to compute dtype), but a float-slot
        # leaf restored as int (or vice versa) must fail HERE with guidance,
        # not later as a cast surprise or compile error (ADVICE r3).
        # Quantized sub-trees carry their own {q8:int8, q8_scale:float}
        # dtypes by design.
        if qz.is_quantized(g):
            return True
        # jnp.issubdtype, not np: numpy classifies bfloat16 (kind 'V') as
        # non-floating, which would reject legitimate bf16 checkpoints.
        import jax.numpy as jnp

        return (jnp.issubdtype(np.dtype(g.dtype), jnp.floating)
                == jnp.issubdtype(np.dtype(w.dtype), jnp.floating))

    got, got_def = jax.tree_util.tree_flatten_with_path(
        restored, is_leaf=qz.is_quantized)
    want, want_def = jax.tree_util.tree_flatten_with_path(raw)
    if len(got) != len(want) or any(
            gp != wp or shape_of(g) != tuple(w.shape) or not dtype_ok(g, w)
            for (gp, g), (wp, w) in zip(got, want)):
        raise ValueError(
            f"checkpoint at {path!r} does not match {model.name}'s param "
            "structure (tree paths, shapes, or dtype classes differ); pair "
            "the checkpoint with the family/options it was converted with")
    return restored


# -- torch checkpoint extraction (lazy torch import) -------------------------

def extract_torch_state_dict(path: str) -> dict[str, np.ndarray]:
    """Flat {name: np.ndarray} from a torch-ecosystem checkpoint file.

    - ``.safetensors``: read via safetensors (zero pickle exposure).
    - pickle checkpoints (``.ckpt``/``.pt``/``.pth``/``.bin``): read with
      ``torch.load(weights_only=True)`` — tensor data only, no arbitrary
      code execution from untrusted files. LDM-style wrappers that nest the
      weights under a ``state_dict`` key are unwrapped.

    bf16/f16 tensors are widened to f32 on the host (numpy has no bf16);
    the runtime casts to the serving compute dtype at device_put anyway.
    """
    import torch  # lazy: only on torch-import paths

    if path.endswith(".safetensors"):
        from safetensors.torch import load_file

        sd = load_file(path, device="cpu")
    else:
        obj = torch.load(path, map_location="cpu", weights_only=True)
        sd = obj.get("state_dict", obj) if isinstance(obj, dict) else obj
    out: dict[str, np.ndarray] = {}
    for k, v in sd.items():
        if not isinstance(v, torch.Tensor):
            continue  # e.g. LDM checkpoints carry step counters
        if v.dtype in (torch.bfloat16, torch.float16):
            v = v.float()
        out[k] = v.numpy()
    if not out:
        raise ValueError(f"torch checkpoint at {path!r} holds no tensors")
    return out


# -- TF weight extraction (lazy TF import) -----------------------------------

_CKPT_SUFFIX = "/.ATTRIBUTES/VARIABLE_VALUE"


def extract_saved_model_variables(path: str) -> dict[str, np.ndarray]:
    """Flat {name: np.ndarray} from a TF2 SavedModel.

    Prefers the loaded object's ``variables`` collection, whose names are the
    semantic layer paths (``conv1_conv/kernel``) that family
    ``import_tf_variables`` mappings are written against; the ``:0`` tensor
    suffix is stripped. Falls back to reading the ``variables/`` checkpoint
    shards directly (object-graph paths like ``layer_with_weights-0/kernel``)
    for SavedModels whose root object exposes no variables.
    """
    import tensorflow as tf  # lazy: only on import paths

    out: dict[str, np.ndarray] = {}
    try:
        loaded = tf.saved_model.load(path)
        variables = list(getattr(loaded, "variables", None) or ())
        semantic: dict[str, np.ndarray] = {}
        for v in variables:
            semantic[v.name.split(":")[0]] = np.asarray(v.numpy())
        # Commit only a complete AND collision-free read: a mid-loop failure
        # or duplicate names (legal in TF for subclassed models) must not
        # hand a truncated dict to import_tf_variables when the checkpoint
        # reader below could produce the full set.
        if len(semantic) == len(variables):
            out = semantic
        elif variables:
            log.warning(
                "SavedModel %s has %d variables but only %d unique names; "
                "using checkpoint reader", path, len(variables), len(semantic))
    except Exception:  # noqa: BLE001 — fall through to the checkpoint reader
        log.warning("tf.saved_model.load failed for %s; using checkpoint reader", path)
    if out:
        return out

    reader = tf.train.load_checkpoint(os.path.join(path, "variables", "variables"))
    for key in reader.get_variable_to_shape_map():
        name = key[: -len(_CKPT_SUFFIX)] if key.endswith(_CKPT_SUFFIX) else key
        if name.startswith("_CHECKPOINTABLE_OBJECT_GRAPH") or "OBJECT_CONFIG" in name:
            continue
        out[name] = reader.get_tensor(key)
    if not out:
        raise ValueError(f"SavedModel at {path!r} exposes no variables")
    return out


def extract_graphdef_constants(path: str) -> dict[str, np.ndarray]:
    """Flat {node_name: np.ndarray} of Const nodes from a frozen GraphDef."""
    import tensorflow as tf

    gd = tf.compat.v1.GraphDef()
    with open(path, "rb") as f:
        gd.ParseFromString(f.read())
    out: dict[str, np.ndarray] = {}
    for node in gd.node:
        if node.op == "Const":
            t = node.attr["value"].tensor
            out[node.name] = np.array(tf.make_ndarray(t))
    if not out:
        raise ValueError(f"GraphDef at {path!r} has no Const nodes")
    return out


# -- CLI ---------------------------------------------------------------------

def convert_cli(saved_model_path: str, family: str, out_path: str,
                options: dict | None = None, quantize: str | None = None) -> None:
    """SavedModel/GraphDef -> orbax, so serving startup never needs TF.

    ``options`` configures the family for the import — keys naming
    ModelConfig fields (e.g. num_classes, dtype, seq_buckets) set those
    fields; everything else lands in ModelConfig.options (e.g. BERT's
    vocab_file / layer sizes). The import must match the artifact.

    ``quantize="int8"`` writes the weight-only-quantized tree (half the
    checkpoint bytes and startup upload); serve it with quantize = "int8".
    The loader reads the saved structure from checkpoint metadata, so no
    other settings need to agree."""
    import dataclasses

    from tpuserve.config import ModelConfig
    from tpuserve import models as modelzoo

    opts = dict(options or {})
    reserved = {"name", "family", "weights", "options"}
    bad = reserved & set(opts)
    if bad:
        raise ValueError(f"--opt cannot set {sorted(bad)}; use the dedicated "
                         "CLI flags instead")
    settable = {f.name for f in dataclasses.fields(ModelConfig)} - reserved
    fields = {k: opts.pop(k) for k in list(opts) if k in settable}
    cfg = ModelConfig(name=family, family=family, weights=saved_model_path,
                      options=opts, **fields)
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown --quantize mode {quantize!r}")
    model = modelzoo.build(cfg)
    params = load_params_for(model)
    if quantize == "int8":
        from tpuserve import quantize as qz

        params = qz.quantize_tree(jax.device_get(params), cfg.quantize_min_size)
    save_orbax(out_path, params)
    log.info("wrote orbax checkpoint to %s", out_path)
    print(f"converted {saved_model_path} -> {out_path}"
          + (f" ({quantize}-quantized)" if quantize else ""))
