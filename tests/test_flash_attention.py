"""Pallas fused blockwise attention (SURVEY.md §7 M8): parity with the dense
reference in interpret mode on CPU, padding-bias semantics, block clamping,
and the BERT "attention=flash" option end-to-end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.ops.flash_attention import flash_attention
from tpuserve.ops.ring_attention import dense_attention


def rand_qkv(rng, b=2, s=256, h=4, d=64):
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, s, h, d)).astype(np.float32))
    return mk(), mk(), mk()


def test_matches_dense_reference(rng):
    q, k, v = rand_qkv(rng)
    out = np.asarray(flash_attention(q, k, v))
    ref = np.asarray(dense_attention(q, k, v))
    np.testing.assert_allclose(out, ref, atol=2e-6)


def test_padding_bias_matches_and_masks(rng):
    q, k, v = rand_qkv(rng)
    mask = np.ones((2, 256), np.float32)
    mask[:, 200:] = 0.0
    bias = jnp.asarray((1.0 - mask) * -1e9)
    out = np.asarray(flash_attention(q, k, v, bias))
    ref = np.asarray(dense_attention(q, k, v, bias[:, None, None, :]))
    np.testing.assert_allclose(out, ref, atol=2e-6)
    # Masked keys must not influence the output at all: perturbing them
    # changes nothing.
    k2 = k.at[:, 200:].set(0.0)
    v2 = v.at[:, 200:].set(0.0)
    out2 = np.asarray(flash_attention(q, k2, v2, bias))
    np.testing.assert_allclose(out, out2, atol=2e-6)


def test_block_clamp_small_sequences(rng):
    """Seq 64 < default block 128: blocks clamp instead of erroring."""
    q, k, v = rand_qkv(rng, s=64)
    out = np.asarray(flash_attention(q, k, v))
    ref = np.asarray(dense_attention(q, k, v))
    np.testing.assert_allclose(out, ref, atol=2e-6)


def test_non_power_of_two_seq_clamps_to_divisor(rng):
    """192 isn't a multiple of 128: blocks clamp to gcd (64) and still match."""
    q, k, v = rand_qkv(rng, s=192)
    out = np.asarray(flash_attention(q, k, v))
    ref = np.asarray(dense_attention(q, k, v))
    np.testing.assert_allclose(out, ref, atol=2e-6)


def test_unalignable_seq_rejected(rng):
    q, k, v = rand_qkv(rng, s=96)  # gcd(64, 96) = 32 ok; gcd(36, 96) = 12 bad
    with pytest.raises(ValueError, match="TPU lowering rejects"):
        flash_attention(q, k, v, block_q=36)


def test_unknown_platform_raises(rng, monkeypatch):
    """interpret=None interprets on cpu and compiles on tpu; any other
    platform raises instead of silently taking the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    q, k, v = rand_qkv(rng, b=1, s=24, h=1, d=8)  # a shape no test compiled
    with pytest.raises(ValueError, match="platform 'gpu'"):
        flash_attention(q, k, v)


def test_bf16_inputs(rng):
    q, k, v = (x.astype(jnp.bfloat16) for x in rand_qkv(rng, s=128))
    raw = flash_attention(q, k, v)
    assert raw.dtype == jnp.bfloat16  # out_shape follows q.dtype
    ref = np.asarray(dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32)))
    np.testing.assert_allclose(np.asarray(raw).astype(np.float32), ref, atol=2e-2)


def test_stats_variant_merges_across_key_blocks(rng):
    """return_stats=True exposes the unnormalized accumulator + online-
    softmax (m, l) so two key-block results merge to the full answer — the
    contract ring attention's per-device step relies on."""
    q, k, v = rand_qkv(rng, s=128)
    a_1, m1, l1 = flash_attention(q, k[:, :64], v[:, :64], return_stats=True)
    a_2, m2, l2 = flash_attention(q, k[:, 64:], v[:, 64:], return_stats=True)
    a_1, m1, l1, a_2, m2, l2 = (np.asarray(x) for x in (a_1, m1, l1, a_2, m2, l2))
    m12 = np.maximum(m1, m2)
    w1, w2 = np.exp(m1 - m12), np.exp(m2 - m12)
    l12 = l1 * w1 + l2 * w2
    merged = (a_1 * w1[..., None] + a_2 * w2[..., None]) / l12[..., None]
    ref = np.asarray(dense_attention(q, k, v))
    np.testing.assert_allclose(merged, ref, atol=2e-6)


def test_ring_flash_fully_masked_block_stays_finite(rng):
    """A device block whose keys are ALL masked (-inf per-key bias over a
    whole shard) must contribute zero, not NaN (review regression: the
    normalized kernel output was 0/0 there)."""
    from tpuserve.ops.ring_attention import ring_attention
    from tpuserve.parallel import make_mesh
    from tpuserve.parallel.mesh import MeshPlan

    mesh = make_mesh(MeshPlan(sp=4))
    q, k, v = rand_qkv(rng, b=2, s=256, h=4, d=64)
    mask = np.ones((2, 256), np.float32)
    mask[:, 192:] = 0.0  # the 4th device's whole 64-key block
    bias = jnp.asarray(np.where(mask > 0, 0.0, -np.inf).astype(np.float32))
    out_f = np.asarray(ring_attention(q, k, v, mesh, key_padding=bias,
                                      local_impl="flash"))
    ref = np.asarray(dense_attention(q, k, v, bias[:, None, None, :]))
    assert np.isfinite(out_f[:, :192]).all()
    np.testing.assert_allclose(out_f[:, :192], ref[:, :192], atol=2e-5)


def test_flash_attention_is_differentiable(rng):
    """jax.grad through the kernel works (dense-recompute VJP): the training
    path reaches ring/ulysses with auto-selected flash locals (review
    regression: the raw pallas_call had no autodiff rule)."""
    from tpuserve.ops.ring_attention import ring_attention
    from tpuserve.parallel import make_mesh
    from tpuserve.parallel.mesh import MeshPlan

    q, k, v = rand_qkv(rng, b=1, s=64, h=2, d=64)

    g = jax.grad(lambda q_: flash_attention(q_, k, v).sum())(q)
    g_ref = jax.grad(lambda q_: dense_attention(q_, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=2e-5)

    # And through the ring with flash locals (the train.py path shape).
    mesh = make_mesh(MeshPlan(sp=4))
    q2, k2, v2 = rand_qkv(rng, b=2, s=256, h=4, d=64)
    gr = jax.grad(lambda q_: ring_attention(
        q_, k2, v2, mesh, local_impl="flash").astype(jnp.float32).sum())(q2)
    gr_ref = jax.grad(lambda q_: dense_attention(
        q_, k2, v2).astype(jnp.float32).sum())(q2)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(gr_ref), atol=2e-4)


def test_ring_local_flash_matches_dense_local(rng):
    """ring_attention's per-device inner step through the Pallas kernel
    (local_impl='flash') == the dense-einsum inner step == full dense."""
    from tpuserve.ops.ring_attention import ring_attention
    from tpuserve.parallel import make_mesh
    from tpuserve.parallel.mesh import MeshPlan

    mesh = make_mesh(MeshPlan(sp=4))
    q, k, v = rand_qkv(rng, b=2, s=256, h=4, d=64)
    mask = np.ones((2, 256), np.float32)
    mask[:, 230:] = 0.0
    bias = jnp.asarray((1.0 - mask) * -1e9)
    out_f = np.asarray(ring_attention(q, k, v, mesh, key_padding=bias,
                                      local_impl="flash"))
    out_d = np.asarray(ring_attention(q, k, v, mesh, key_padding=bias,
                                      local_impl="dense"))
    ref = np.asarray(dense_attention(q, k, v, bias[:, None, None, :]))
    np.testing.assert_allclose(out_f, out_d, atol=2e-5)
    np.testing.assert_allclose(out_f, ref, atol=2e-5)
    # auto at this (tiny) shape picks DENSE — the memory-derived threshold
    # (see test_auto_local_impl_decision) is unreachable on CPU shapes, so
    # this line only proves auto composes; the flash branch of the decision
    # is unit-tested directly below.
    out_a = np.asarray(ring_attention(q, k, v, mesh, key_padding=bias))
    np.testing.assert_allclose(out_a, ref, atol=2e-5)


def test_auto_local_impl_decision():
    """The memory-derived dense/flash choice, unit-tested with hypothetical
    shapes a CPU test cannot materialize (BASELINE.md 'Flash vs dense':
    dense is faster whenever it fits; flash exists for when it doesn't)."""
    from tpuserve.ops.ring_attention import DENSE_SCORE_BYTES_MAX, auto_local_impl

    # Serving shapes (measured table): dense everywhere.
    assert auto_local_impl(32, 12, 128, 64) == "dense"
    assert auto_local_impl(4, 12, 2048, 64) == "dense"
    # 32k local seq, 12 heads: 2*4*1*12*32768^2 ~ 103 GB of dense scores
    # -> only the O(S) kernel can run it.
    assert auto_local_impl(1, 12, 32768, 64) == "flash"
    # Just over the threshold flips exactly at the documented constant.
    s = 16384
    b_over = DENSE_SCORE_BYTES_MAX // (2 * 4 * 1 * s * s) + 1
    assert auto_local_impl(b_over, 1, s, 64) == "flash"
    assert auto_local_impl(max(b_over - 1, 1), 1, s, 64) == "dense"
    # Kernel-hostile shapes never pick flash, regardless of size.
    assert auto_local_impl(64, 32, 32768, 40) == "dense"   # head_dim
    assert auto_local_impl(64, 32, 32771, 64) == "dense"   # row alignment


def test_ulysses_local_flash_matches_dense_local(rng):
    from tpuserve.ops.ulysses import ulysses_attention
    from tpuserve.parallel import make_mesh
    from tpuserve.parallel.mesh import MeshPlan

    mesh = make_mesh(MeshPlan(sp=4))
    q, k, v = rand_qkv(rng, b=2, s=256, h=4, d=64)
    out_f = np.asarray(ulysses_attention(q, k, v, mesh, local_impl="flash"))
    ref = np.asarray(dense_attention(q, k, v))
    np.testing.assert_allclose(out_f, ref, atol=2e-5)


@pytest.mark.slow
def test_bert_sharded_flash_serving_matches_dense():
    """attention='flash' + parallelism='sharded' on the 8-fake-device mesh:
    the kernel runs per device under shard_map (the r3 build-time rejection,
    now supported); logits match dense and the AOT-compiled path serves."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build
    from tpuserve.runtime import build_runtime

    def cfg(attn, par="single"):
        return ModelConfig(
            name="b", family="bert", dtype="float32", num_classes=4,
            batch_buckets=[8], seq_buckets=[64], parallelism=par,
            request_timeout_ms=30_000.0,
            options={"layers": 2, "d_model": 64, "heads": 2, "d_ff": 128,
                     "vocab_size": 512, "attention": attn})

    flash = build(cfg("flash", par="sharded"))
    rt = build_runtime(flash)  # binds the mesh + AOT-compiles the shard_map
    dense = build(cfg("dense"))
    params = dense.init_params(jax.random.key(0))
    items = [dense.host_decode(
        json.dumps({"text": f"sharded flash {i}"}).encode(),
        "application/json") for i in range(5)]  # 5 of 8 lanes real
    # Each its own batch: the one-device model's program takes segments.
    batch = flash.assemble(items, (8, 64))
    o_f = np.asarray(jax.jit(flash.forward)(params, batch)["probs"])
    o_d = np.asarray(jax.jit(dense.forward)(
        params, dense.assemble(items, (8, 64)))["probs"])
    np.testing.assert_allclose(o_f[:5], o_d[:5], atol=1e-5)
    assert np.asarray(rt.run((8, 64), batch)["probs"]).shape == (8, 4)


@pytest.mark.slow
def test_bert_flash_option_matches_dense():
    """cfg.options['attention']='flash' serves identical logits (same params)."""
    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    def cfg(attn):
        return ModelConfig(
            name="b", family="bert", dtype="float32", num_classes=4,
            batch_buckets=[2], seq_buckets=[64],
            options={"layers": 2, "d_model": 64, "heads": 2, "d_ff": 128,
                     "vocab_size": 512, "attention": attn})

    dense = build(cfg("dense"))
    flash = build(cfg("flash"))
    params = dense.init_params(jax.random.key(0))
    item = dense.host_decode(b'{"text": "flash attention parity"}',
                             "application/json")
    batch = dense.assemble([item, item], (2, 64))
    o_d = np.asarray(jax.jit(dense.forward)(params, batch)["probs"])
    o_f = np.asarray(jax.jit(flash.forward)(params, batch)["probs"])
    np.testing.assert_allclose(o_f, o_d, atol=1e-5)


def test_bert_rejects_unknown_attention_option():
    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    with pytest.raises(ValueError, match="dense.*flash"):
        build(ModelConfig(name="b", family="bert",
                          options={"attention": "Flash"}))


def test_check_vma_false_still_required_canary():
    """ring_attention (and bert's flash-under-shard_map) pass
    check_vma=False because the Pallas interpreter cannot propagate vma
    through its internal block slicing (upstream jax workaround). This
    canary re-tries the composition WITH check_vma=True on every run: the
    day a jax upgrade makes it pass, this test fails loudly — the signal to
    delete the check_vma=False escapes in tpuserve/ops/ring_attention.py
    and tpuserve/models/bert.py and regain the stronger collective
    checking (VERDICT r4 weak 7 asked for exactly this tripwire)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from tpuserve.parallel import make_mesh
    from tpuserve.parallel.mesh import MeshPlan

    if len(jax.devices()) < 4:
        pytest.skip("needs the fake multi-device mesh")
    mesh = make_mesh(MeshPlan(sp=1))
    rng = np.random.default_rng(11)
    q, k, v = rand_qkv(rng, b=len(jax.devices()), s=128, h=2, d=64)
    spec = P("data", None, None, None)
    try:
        f = shard_map(
            lambda q_, k_, v_: flash_attention(q_, k_, v_),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=True)
        np.asarray(f(q, k, v))
    except ValueError as e:
        # Only the KNOWN failure keeps the escapes justified; any other
        # error (e.g. a shard_map API change raising TypeError) must fail
        # this test rather than silently reading as "still required".
        assert "check_vma" in str(e) or "varying" in str(e), (
            f"unexpected failure shape from the vma canary: {e}")
        return
    pytest.fail(
        "shard_map(flash_attention, check_vma=True) now WORKS on this jax: "
        "remove the check_vma=False escapes in tpuserve/ops/"
        "ring_attention.py, tpuserve/ops/ulysses.py, and tpuserve/models/"
        "bert.py, then update this canary")
