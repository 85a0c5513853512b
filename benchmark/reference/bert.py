"""The plain reference for the `bert` family: BERT (Devlin et al. 2018) as the
paper and google-research/bert `modeling.py` describe it, in straightforward
`jax.numpy` float32 under `jax.default_matmul_precision("highest")`.

Embeddings (word + position + token type 0, LayerNorm), post-LN encoder blocks
(multi-head self-attention, exact erf GELU feed-forward), tanh pooler on
[CLS], linear classifier, softmax. No kernels, no batching, no buckets: one
sequence at its own length.

Everything here is made from the seed by this file. The program is handed the
same tensors as a checkpoint (`to_program_tree`), through its ordinary
`weights =` path; the reference never sees anything the program made.

Two departures from the published initialisation, on purpose (`assumed` in
the configuration files):

- biases and LayerNorm offsets are drawn like the kernels (std
  `initializer_range`) and LayerNorm gains are 1 + such a draw, as in a
  trained checkpoint. With the paper's zeros and ones a program that dropped
  a bias or a gain would still agree with the reference;
- the query and key kernels are drawn `assumed.qk_scale` times wider (4 for
  BERT-base, 2 for BERT-large). At the published 0.02 every attention score is
  near 0, attention is a plain average over the text, and all texts answer
  nearly alike (0.013 apart in centred logits; chip, PR 24). The rounding
  errors of the sample's texts are then one error seen many times: the check's
  statistic swung by a factor of 2 from seed to seed (the int8 control read
  0.0046 to 0.0090) and came within 2.1x of the sound runs. Drawn wider,
  attention picks tokens as a trained model's does, texts answer 0.03 to 0.10
  apart (a swapped lane reads nine times the limit or more), and the statistic
  is steady: control seeds within 10% of their mean. Too wide and the softmax
  amplifies bfloat16's rounding: BERT-large's 24 layers at 4x read 0.013 to
  0.026 (one seed in seven twice the others) against a control of 0.044; at 2x
  they read 0.0022 to 0.0027 against 0.0095. BERT-base at 8x: 30-fold (CPU).

The tensors are drawn in float32 and rounded once to bfloat16, the type the
configurations serve in, so both sides start from identical numbers and the
check measures the arithmetic, not the rounding of the weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# The check's limit is per configuration (deeper and wider models round more):
# each configuration file states it under `check`, with the two chip readings
# it was set between: the largest that sound runs gave over a dozen seeds or
# more, and the smallest that the control gave (the program's own int8 compute
# path, `quantize = "int8c"`, the nearest precision below bfloat16). PERF.md,
# section 2, has the table. The statistic is benchmark/check.py's.

# Tensors stacked on a leading layer axis (the rest are per model).
LAYER_KEYS = tuple(f"{n}_{s}" for n in "qkvo" for s in "wb") + (
    "up_w", "up_b", "down_w", "down_b",
    "attn_ln_g", "attn_ln_b", "out_ln_g", "out_ln_b")


def sizes_from_config(cfg: dict) -> dict:
    """The sizes this file needs, by the published config's own key names."""
    return {
        "layers": int(cfg["num_hidden_layers"]),
        "d_model": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "d_ff": int(cfg["intermediate_size"]),
        "vocab_size": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "type_vocab": int(cfg.get("type_vocab_size", 2)),
        "num_classes": int(cfg["assumed"]["num_classes"]),
        "std": float(cfg.get("initializer_range", 0.02)),
        "ln_eps": float(cfg.get("layer_norm_eps", 1e-12)),
        "qk_scale": float(cfg["assumed"].get("qk_scale", 1.0)),
    }


def _key(seed: int) -> jax.Array:
    # --seed may pass 2**31; a PRNG key seed may not (int32 without x64).
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def param_shapes(sz: dict) -> dict[str, tuple]:
    L, d, f = sz["layers"], sz["d_model"], sz["d_ff"]
    shapes = {
        "word": (sz["vocab_size"], d), "position": (sz["positions"], d),
        "token_type": (sz["type_vocab"], d),
        "emb_ln_g": (d,), "emb_ln_b": (d,),
        "up_w": (L, d, f), "up_b": (L, f), "down_w": (L, f, d), "down_b": (L, d),
        "attn_ln_g": (L, d), "attn_ln_b": (L, d),
        "out_ln_g": (L, d), "out_ln_b": (L, d),
        "pool_w": (d, d), "pool_b": (d,),
        "cls_w": (d, sz["num_classes"]), "cls_b": (sz["num_classes"],),
    }
    for n in "qkvo":
        shapes[f"{n}_w"] = (L, d, d)
        shapes[f"{n}_b"] = (L, d)
    return shapes


def make_params(seed: int, sz: dict) -> dict[str, jax.Array]:
    """Every tensor of the model from `seed`, in ONE jitted call, bfloat16.
    Layer tensors are stacked on a leading layer axis."""
    shapes = param_shapes(sz)
    names = sorted(shapes)
    std = sz["std"]

    def draw(key):
        out = {}
        for i, n in enumerate(names):
            x = std * jax.random.normal(jax.random.fold_in(key, i), shapes[n],
                                        jnp.float32)
            if n.endswith("ln_g"):
                x = 1.0 + x
            elif n in ("q_w", "k_w"):
                x = sz["qk_scale"] * x
            out[n] = x.astype(jnp.bfloat16)
        return out

    return jax.jit(draw)(_key(seed))


def to_program_tree(params: dict, sz: dict, max_seq: int) -> dict:
    """The same tensors in the layout `tpuserve/models/bert.py` restores
    (flax tree of BertClassifier): heads split out of the fused (d, d)
    attention kernels, token type 0 folded into the position table (the
    program serves single-segment text and keeps no token-type table)."""
    p = {k: np.asarray(v) for k, v in params.items()}
    d, h = sz["d_model"], sz["heads"]
    hd = d // h
    pos = (p["position"][:max_seq].astype(np.float32)
           + p["token_type"][0].astype(np.float32)).astype(p["position"].dtype)
    tree: dict = {
        "embed": {"embedding": p["word"]},
        "pos_embed": pos,
        "ln_embed": {"scale": p["emb_ln_g"], "bias": p["emb_ln_b"]},
        "pooler": {"kernel": p["pool_w"], "bias": p["pool_b"]},
        "classifier": {"kernel": p["cls_w"], "bias": p["cls_b"]},
    }
    for i in range(sz["layers"]):
        attn = {
            name: {"kernel": p[f"{n}_w"][i].reshape(d, h, hd),
                   "bias": p[f"{n}_b"][i].reshape(h, hd)}
            for n, name in (("q", "query"), ("k", "key"), ("v", "value"))
        }
        attn["out"] = {"kernel": p["o_w"][i].reshape(h, hd, d),
                       "bias": p["o_b"][i]}
        tree[f"layer{i}"] = {
            "attn": attn,
            "ln_attn": {"scale": p["attn_ln_g"][i], "bias": p["attn_ln_b"][i]},
            "mlp_up": {"kernel": p["up_w"][i], "bias": p["up_b"][i]},
            "mlp_down": {"kernel": p["down_w"][i], "bias": p["down_b"][i]},
            "ln_mlp": {"scale": p["out_ln_g"][i], "bias": p["out_ln_b"][i]},
        }
    return {"params": tree}


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def logits(params: dict, ids: jax.Array, sz: dict, operand_dtype=None) -> jax.Array:
    """(n,) token ids of ONE sequence -> (num_classes,) logits, in float32.

    `operand_dtype` is for the control only: the operands of every matrix
    product are rounded to it (accumulation stays float32), which is how a
    chip computes in that precision. The reference leaves it None."""
    p = {k: v.astype(jnp.float32) for k, v in params.items()}
    h = sz["heads"]
    n = ids.shape[0]
    eps = sz["ln_eps"]

    def lo(a):
        return a if operand_dtype is None else a.astype(operand_dtype).astype(jnp.float32)

    def mm(a, b):
        return lo(a) @ lo(b)

    x = p["word"][ids] + p["position"][:n] + p["token_type"][0]
    x = _layer_norm(x, p["emb_ln_g"], p["emb_ln_b"], eps)

    def block(x, w):
        q = (mm(x, w["q_w"]) + w["q_b"]).reshape(n, h, -1)
        k = (mm(x, w["k_w"]) + w["k_b"]).reshape(n, h, -1)
        v = (mm(x, w["v_w"]) + w["v_b"]).reshape(n, h, -1)
        s = jnp.einsum("qhd,khd->hqk", lo(q), lo(k)) / np.sqrt(q.shape[-1])
        a = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("hqk,khd->qhd", lo(a), lo(v)).reshape(n, -1)
        x = _layer_norm(x + mm(ctx, w["o_w"]) + w["o_b"],
                        w["attn_ln_g"], w["attn_ln_b"], eps)
        ff = jax.nn.gelu(mm(x, w["up_w"]) + w["up_b"], approximate=False)
        x = _layer_norm(x + mm(ff, w["down_w"]) + w["down_b"],
                        w["out_ln_g"], w["out_ln_b"], eps)
        return x, None

    x, _ = jax.lax.scan(block, x, {k: p[k] for k in LAYER_KEYS})
    pooled = jnp.tanh(mm(x[0], p["pool_w"]) + p["pool_b"])
    return mm(pooled, p["cls_w"]) + p["cls_b"]


def class_log_probs(params: dict, ids_list: list[np.ndarray], sz: dict,
                    operand_dtype=None) -> np.ndarray:
    """Reference answer for each sequence: (len(ids_list), num_classes)
    log-probabilities, float32 at matmul precision "highest"."""
    fn = jax.jit(lambda p, i: jax.nn.log_softmax(logits(p, i, sz, operand_dtype)))
    out = []
    with jax.default_matmul_precision("highest"):
        for ids in ids_list:
            out.append(np.asarray(fn(params, jnp.asarray(ids, jnp.int32))))
    return np.stack(out)


def save_checkpoint(path: str, params: dict, sz: dict, cfg: dict) -> None:
    """Write the tensors where the program's `weights =` finds them: an orbax
    checkpoint of the tree its model restores."""
    import orbax.checkpoint as ocp

    max_seq = max(cfg["serve"]["model"]["seq_buckets"])
    tree = to_program_tree(params, sz, max_seq)
    # One file per tensor, no OCDBT merge step: several times quicker to
    # write, and the program's StandardCheckpointer restores it the same.
    handler = ocp.PyTreeCheckpointHandler(use_ocdbt=False)
    with ocp.Checkpointer(handler) as ckptr:
        ckptr.save(path, args=ocp.args.PyTreeSave(tree))
