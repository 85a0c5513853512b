"""The plain reference agrees with the family's own forward at a tiny size in
float32, and the check's statistic tells a bfloat16 run from an int8c run
(the control kept as a test: the lower precision must come out further from
the reference than the stated one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, spec, vocab
from tpuserve import quantize as qz
from tpuserve.config import ModelConfig
from tpuserve.models import bert as program_bert

ref = spec.load_module("reference", "bert")
CFG = spec.load_config(spec.load_benchmark(), "rehearsal-tiny")
SZ = ref.sizes_from_config(CFG)
LENGTHS = [3, 9, 17, 30, 45, 62]


def _serving(dtype: str, quantize=None):
    m = CFG["serve"]["model"]
    cfg = ModelConfig(
        name="m", family="bert", dtype=dtype, quantize=quantize,
        batch_buckets=[8], seq_buckets=[64], num_classes=SZ["num_classes"],
        parallelism="single", quantize_min_size=64,
        options={"layers": SZ["layers"], "d_model": SZ["d_model"], "heads": SZ["heads"],
                 "d_ff": SZ["d_ff"], "vocab_size": SZ["vocab_size"]})
    assert m["seq_buckets"][-1] == 64
    return program_bert.create(cfg)


def _texts(seed):
    rng = np.random.default_rng(seed)
    return [np.concatenate([[vocab.CLS], rng.integers(vocab.FIRST_WORD, SZ["vocab_size"], n),
                            [vocab.SEP]]).astype(np.int32) for n in LENGTHS]


def _served_probs(model, tree, ids_list, forward=None):
    """What the server would answer: the family's forward on a padded bucket,
    probabilities put back in class order."""
    ids, mask = model.assemble(ids_list, (8, 64))
    out = (forward or model.forward)(tree, (jnp.asarray(ids), jnp.asarray(mask)))
    p, i = np.asarray(out["probs"], np.float64), np.asarray(out["indices"])
    probs = np.zeros_like(p)
    np.put_along_axis(probs, i, p, axis=1)
    return probs[: len(ids_list)]


def _statistic(seed, dtype, quantize=None):
    params = ref.make_params(seed, SZ)
    ids_list = _texts(seed)
    ref_logp = ref.class_log_probs(params, ids_list, SZ)
    model = _serving(dtype, quantize)
    tree = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.dtype(dtype)),
                                  ref.to_program_tree(params, SZ, 64))
    forward = None
    if quantize == "int8c":  # what runtime._forward_fn builds for this mode
        tree = qz.quantize_tree(jax.device_get(tree), 64)
        keep = model.int8c_native_kernel_paths()
        forward = lambda p, b: model.forward(  # noqa: E731
            qz.dequantize_tree_except(p, jnp.dtype(dtype), keep), b)
    return check.rms_centred_logit_error(
        _served_probs(model, tree, ids_list, forward), ref_logp)


def test_reference_agrees_with_the_family_forward_in_float32():
    stat = _statistic(7, "float32")
    # float32 against float32 at "highest": only the order of sums differs.
    assert stat < 1e-4, stat


def test_weights_are_a_function_of_the_seed_alone():
    a, b, c = (ref.make_params(s, SZ) for s in (2**31 + 5, 2**31 + 5, 6))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["word"], c["word"])
    assert a["word"].dtype == jnp.bfloat16
    assert set(a) == set(ref.param_shapes(SZ))


SEEDS = [11, 12, 13]


def _pooled(values):
    return float(np.sqrt(np.mean(np.square(values))))


def test_statistic_separates_bfloat16_from_the_lower_precision():
    """The control, kept at a size a test can hold. Two lower precisions are
    put in the program's place: the reference itself with every matrix
    product's operands rounded to float8 (the contract's plain control), and
    the program's own int8 compute path. At this toy width (64) per-token
    int8 scales span few values, so int8c is barely coarser than bfloat16 and
    only the pooled order is pinned; at the cells' widths the chip reads it
    4x apart (reference/bert.py, CHECK_READINGS)."""
    sound, int8c, fp8 = [], [], []
    for seed in SEEDS:
        sound.append(_statistic(seed, "bfloat16"))
        int8c.append(_statistic(seed, "bfloat16", "int8c"))
        params = ref.make_params(seed, SZ)
        ids_list = _texts(seed)
        ref_logp = ref.class_log_probs(params, ids_list, SZ)
        low = ref.class_log_probs(params, ids_list, SZ, operand_dtype=jnp.float8_e4m3fn)
        fp8.append(check.rms_centred_logit_error(np.exp(low), ref_logp))
    assert np.isfinite(sound + int8c + fp8).all()
    assert min(fp8) > 3 * max(sound), (sound, fp8)
    assert _pooled(int8c) > 1.2 * _pooled(sound), (sound, int8c)


def test_a_swapped_lane_or_a_dropped_bias_is_caught():
    params = ref.make_params(3, SZ)
    ids_list = _texts(3)
    ref_logp = ref.class_log_probs(params, ids_list, SZ)
    model = _serving("float32")
    tree = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                  ref.to_program_tree(params, SZ, 64))
    good = _served_probs(model, tree, ids_list)
    assert check.rms_centred_logit_error(good[::-1], ref_logp) > 5 * check.rms_centred_logit_error(good, ref_logp)
    tree["params"]["pooler"]["bias"] = jnp.zeros_like(tree["params"]["pooler"]["bias"])
    assert check.rms_centred_logit_error(
        _served_probs(model, tree, ids_list), ref_logp) > 1e-4


def test_probs_by_class_needs_every_class_once():
    ans = {"top_k": [{"class": 1, "prob": 0.7}, {"class": 0, "prob": 0.3}]}
    np.testing.assert_allclose(check.probs_by_class(ans, 2), [0.3, 0.7])
    with pytest.raises(ValueError):
        check.probs_by_class(ans, 3)
    assert check.rms_centred_logit_error(np.array([[0.0, 1.0]]), np.zeros((1, 2))) == float("inf")
