"""The whole-sequence attention kernel (`ops.fused_attention.fused_attention`)
in the Pallas interpreter, the rule that routes BERT's buckets to it
(`attention_path`), and what the runtime shows of the choice."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.config import ModelConfig
from tpuserve.genserve.model import LeafKind
from tpuserve.models import build
from tpuserve.models.bert import _masked_attention, _segment_bias
from tpuserve.obs import Metrics
from tpuserve.ops import fused_attention as fa

B, H, D = 2, 2, 64
# (bucket, live keys of the first row; the second row is full)
CASES = [(128, 1), (128, 17), (128, 77), (128, 128),
         (512, 1), (512, 130), (512, 300), (512, 512)]


def _inputs(s: int, n_live: int, dtype=jnp.bfloat16, seed: int = 0):
    rng = np.random.default_rng(seed + s + n_live)
    q, k, v = (jnp.asarray(rng.normal(size=(B, s, H, D)), dtype)
               for _ in range(3))
    live = np.arange(s)[None, :] < np.array([n_live, s])[:, None]
    return q, k, v, live, jnp.asarray(np.where(live, 0.0, -1e9), jnp.float32)



def _float64(q, k, v, live):
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    sc = np.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    sc = np.where(live[:, None, None, :], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("s,n_live", CASES)
def test_fused_matches_float64_reference(s, n_live):
    """bf16 in and out: no further from plain float64 attention than the
    XLA pair it replaces (whose scores leave the first product in bf16)."""
    q, k, v, live, bias = _inputs(s, n_live)
    out = fa.fused_attention(q, k, v, live)
    assert out.dtype == jnp.bfloat16 and out.shape == q.shape
    want = _float64(q, k, v, live)
    err = np.abs(np.asarray(out, np.float64) - want)[live]
    dense = np.abs(np.asarray(_masked_attention(
        q, k, v, bias[:, None, None, :]), np.float64) - want)[live]
    assert err.max() < 0.03
    assert np.sqrt((err ** 2).mean()) <= 1.1 * np.sqrt((dense ** 2).mean())


@pytest.mark.parametrize("s,n_live", CASES)
def test_fused_matches_masked_attention(s, n_live):
    q, k, v, live, bias = _inputs(s, n_live, seed=1)
    out = np.asarray(fa.fused_attention(q, k, v, live), np.float32)
    ref = np.asarray(_masked_attention(q, k, v, bias[:, None, None, :]),
                     np.float32)
    np.testing.assert_allclose(out[live], ref[live], atol=0.04)


@pytest.mark.parametrize("s,n_live,d", [(128, 40, 64), (512, 200, 64),
                                        (128, 40, 32)])
def test_fused_float32_is_exact_to_rounding(s, n_live, d):
    """float32 operands take the same kernel: the mathematics alone. Head 32
    has a scale that is no power of two, applied to the scores instead."""
    q, k, v, live, bias = _inputs(s, n_live, dtype=jnp.float32)
    q, k, v = (x[..., :d] for x in (q, k, v))
    out = np.asarray(fa.fused_attention(q, k, v, live), np.float64)
    np.testing.assert_allclose(out[live], _float64(q, k, v, live)[live],
                               atol=2e-5)


@pytest.mark.parametrize("s,n_live", [(128, 1), (128, 77), (512, 300)])
def test_padded_keys_weigh_exactly_nothing(s, n_live):
    """Whatever a padded key and its value hold, live rows' answers are the
    same bits: the weight is 0.0, not small."""
    q, k, v, live, bias = _inputs(s, n_live)
    noise = jnp.asarray(np.random.default_rng(7).normal(size=k.shape) * 50,
                        k.dtype)
    pad = jnp.asarray(~live)[:, :, None, None]
    a = fa.fused_attention(q, k, v, live)
    b = fa.fused_attention(q, jnp.where(pad, noise, k),
                           jnp.where(pad, noise, v), live)
    assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("block_h", [1, 2])
def test_heads_a_step_do_not_change_the_answer(block_h):
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 128, 4, 64)), jnp.bfloat16)
               for _ in range(3))
    live = jnp.asarray(np.arange(128)[None, :] < np.array([[50], [128]]))
    want = fa.fused_attention(q, k, v, live)
    got = fa.fused_attention(q, k, v, live, block_h=block_h)
    assert np.array_equal(np.asarray(want, np.float32),
                          np.asarray(got, np.float32))


@pytest.mark.parametrize("shape", [(2, 100, 2, 64), (2, 128, 2, 40),
                                   (2, 128, 3, 64)])
def test_fused_refuses_what_it_cannot_tile(shape):
    x = jnp.zeros(shape, jnp.bfloat16)
    with pytest.raises(ValueError, match="use dense attention"):
        fa.fused_attention(x, x, x, jnp.ones(shape[:2]), block_h=2)


def test_unknown_platform_raises(monkeypatch):
    """interpret=None interprets on cpu and compiles on tpu; any other
    platform raises instead of silently taking the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    x = jnp.zeros((1, 128, 1, 16), jnp.bfloat16)  # a shape no test compiled
    with pytest.raises(ValueError, match="platform 'gpu'"):
        fa.fused_attention(x, x, x, jnp.ones((1, 128)))


@pytest.mark.parametrize("mask", ["segments", "key-mask"])
def test_a_row_with_no_live_key_stays_finite_and_alone(mask):
    """The padding rows of a part-filled launch (segment numbers all 0; a
    key mask all 0): the kernel's answer there is finite, and every other
    row is bit for bit what it is in a launch of its own."""
    q, k, v, live, _ = _inputs(512, 300)
    live = live.copy()
    live[1] = False
    m = jnp.asarray(live.astype(np.int32) if mask == "segments" else live)
    out = np.asarray(fa.fused_attention(q, k, v, m), np.float32)
    assert np.isfinite(out).all()
    alone = np.asarray(fa.fused_attention(q[:1], k[:1], v[:1], m[:1]),
                       np.float32)
    assert np.array_equal(out[:1], alone)


# -- documents that share a row --------------------------------------------------

# Lengths of the documents of one row of 512, in the order they lie in it:
# one, two and the cap of eight, ending exactly at the row's end or short of it.
ROWS = {"one-full": [512], "one-short": [300], "two-full": [200, 312],
        "two-short": [100, 150], "eight-full": [64] * 8,
        "eight-short": [30, 40, 50, 60, 70, 80, 20, 16]}


def _segments(lengths, s=512):
    seg, at = np.zeros(s, np.int32), 0
    for j, n in enumerate(lengths):
        seg[at:at + n] = j + 1
        at += n
    return seg


def _packed_inputs(lengths, seed=0, s=512):
    """Row 0 holds the documents; row 1 is one document of the whole row."""
    rng = np.random.default_rng(seed + len(lengths) + sum(lengths))
    q, k, v = (jnp.asarray(rng.normal(size=(B, s, H, D)), jnp.bfloat16)
               for _ in range(3))
    seg = np.stack([_segments(lengths, s), np.ones(s, np.int32)])
    return q, k, v, seg


def _attend(path, q, k, v, seg):
    if path == "fused":
        return fa.fused_attention(q, k, v, jnp.asarray(seg))
    return _masked_attention(q, k, v, _segment_bias(jnp.asarray(seg)))


@pytest.mark.parametrize("path", ["fused", "dense"])
@pytest.mark.parametrize("layout", sorted(ROWS))
def test_a_document_answers_in_a_shared_row_as_alone(layout, path):
    """Each document of the row, moved alone to the start of a row of its
    own, gets the answer it got among its neighbours."""
    lengths = ROWS[layout]
    q, k, v, seg = _packed_inputs(lengths)
    together = np.asarray(_attend(path, q, k, v, seg), np.float32)
    at = 0
    for n in lengths:
        alone = [jnp.zeros_like(x).at[0, :n].set(x[0, at:at + n])
                 for x in (q, k, v)]
        seg1 = np.stack([_segments([n]), np.ones(512, np.int32)])
        got = np.asarray(_attend(path, *alone, seg1), np.float32)
        # Other keys weigh exactly 0.0; what is left is the order in which
        # the second product adds the live ones up.
        np.testing.assert_allclose(together[0, at:at + n], got[0, :n],
                                   atol=2e-3)
        at += n


@pytest.mark.parametrize("layout", sorted(ROWS))
def test_fused_matches_the_dense_path_on_shared_rows(layout):
    q, k, v, seg = _packed_inputs(ROWS[layout], seed=1)
    out = np.asarray(_attend("fused", q, k, v, seg), np.float32)
    ref = np.asarray(_attend("dense", q, k, v, seg), np.float32)
    np.testing.assert_allclose(out[seg != 0], ref[seg != 0], atol=0.04)


@pytest.mark.parametrize("path", ["fused", "dense"])
def test_keys_of_a_neighbour_weigh_exactly_nothing(path):
    """Whatever the other documents of the row hold, a document's answer is
    the same bits."""
    lengths = ROWS["eight-short"]
    q, k, v, seg = _packed_inputs(lengths)
    mine = jnp.asarray(seg == 3)[:, :, None, None]
    noise = jnp.asarray(np.random.default_rng(7).normal(size=k.shape) * 50,
                        k.dtype)
    a = _attend(path, q, k, v, seg)
    b = _attend(path, q, jnp.where(mine, k, noise), jnp.where(mine, v, noise),
                seg)
    assert np.array_equal(np.asarray(a, np.float32)[seg == 3],
                          np.asarray(b, np.float32)[seg == 3])


@pytest.mark.parametrize("path", ["fused", "dense"])
def test_a_swapped_segment_number_is_caught(path):
    """Two neighbours under each other's number at one token each: both
    answers move, far past rounding."""
    q, k, v, seg = _packed_inputs(ROWS["two-full"])
    wrong = seg.copy()
    wrong[0, 199], wrong[0, 200] = 2, 1
    right = np.asarray(_attend(path, q, k, v, seg), np.float32)
    got = np.asarray(_attend(path, q, k, v, wrong), np.float32)
    assert np.abs(got[0, :199] - right[0, :199]).max() > 0.05
    assert np.array_equal(got[1], right[1])


def test_one_document_a_row_is_the_key_mask_bit_for_bit():
    """Segment numbers 0 / 1 are the mask the kernel took before rows were
    shared, padded queries included."""
    q, k, v, live, _ = _inputs(512, 300)
    seg = np.where(live, 1, 0).astype(np.int32)
    assert np.array_equal(
        np.asarray(fa.fused_attention(q, k, v, live), np.float32),
        np.asarray(fa.fused_attention(q, k, v, jnp.asarray(seg)), np.float32))


# -- the rule ------------------------------------------------------------------

BF16, F32 = "bfloat16", "float32"


@pytest.mark.parametrize("platform,dtype,seq,head_dim,want", [
    ("tpu", BF16, 512, 64, "fused"),     # both BERT cells' long bucket
    ("tpu", BF16, 384, 64, "fused"),
    ("tpu", BF16, 256, 64, "fused"),     # measured: 6-9% at batch 256
    ("tpu", BF16, 128, 64, "dense"),     # the short bucket: XLA's pair wins
    ("tpu", BF16, 1024, 64, "dense"),    # past what was measured
    ("tpu", BF16, 64, 64, "dense"),      # not whole lanes
    ("tpu", BF16, 500, 64, "dense"),
    ("tpu", F32, 512, 64, "dense"),      # the kernel's case is bf16
    ("tpu", BF16, 512, 40, "dense"),     # SD's head widths
    ("tpu", BF16, 512, 128, "dense"),    # not measured
    ("cpu", BF16, 512, 64, "dense"),     # tier-1 runs here
    ("cpu", F32, 128, 64, "dense"),
    ("gpu", BF16, 512, 64, "dense"),
])
def test_attention_path_rule(platform, dtype, seq, head_dim, want):
    assert fa.attention_path(platform, dtype, seq, head_dim) == want


def _bert_cfg(**options) -> ModelConfig:
    parallelism = options.pop("parallelism", "single")
    return ModelConfig(
        name="b", family="bert", dtype="bfloat16", num_classes=4,
        batch_buckets=[2], seq_buckets=[512], parallelism=parallelism,
        options={"layers": 1, "d_model": 128, "heads": 2, "d_ff": 128,
                 "vocab_size": 512, **options})


def _trace(model, bucket=(2, 512)):
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    jax.eval_shape(model.forward, params, model.input_signature(bucket))
    return model.traced_paths(bucket)


def test_the_cpu_keeps_the_xla_path():
    assert _trace(build(_bert_cfg())) == {"attention": "dense"}


@pytest.mark.parametrize("parallelism,want", [
    ("single", "fused"), ("replica", "fused"), ("sharded", "dense")])
def test_attention_is_chosen_on_one_device_only(parallelism, want,
                                                monkeypatch):
    """Steered to the TPU's answer in the test, never by an option: a mesh
    keeps the path GSPMD can partition."""
    monkeypatch.setattr(fa, "platform_here", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret_here", lambda: True)
    model = build(_bert_cfg(parallelism=parallelism))
    assert model.traced_paths((2, 512)) == {}        # nothing traced yet
    assert _trace(model) == {"attention": want}


# The two cells' widths (google-research/bert, uncased_L-12_H-768_A-12 and
# uncased_L-24_H-1024_A-16) at the cells' four buckets.
CELL_WIDTHS = {"base": dict(layers=12, d_model=768, heads=12, d_ff=3072),
               "large": dict(layers=24, d_model=1024, heads=16, d_ff=4096)}


@pytest.mark.parametrize("bucket", [(32, 128), (32, 512), (256, 128), (256, 512)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("width", sorted(CELL_WIDTHS))
def test_the_cells_buckets_keep_their_path(width, bucket, monkeypatch):
    """What both BERT cells run on the chip, traced where no chip is: the
    kernel at 512, the XLA pair at 128, documents sharing rows (three
    leaves). Who changes `attention_path` or BERT's build changes this."""
    monkeypatch.setattr(fa, "platform_here", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret_here", lambda: False)  # traced, never lowered
    model = build(ModelConfig(
        name=width, family="bert", dtype="bfloat16", num_classes=5,
        parallelism="single", batch_buckets=[32, 256], seq_buckets=[128, 512],
        options={"vocab_size": 512, **CELL_WIDTHS[width]}))
    assert len(model.input_signature(bucket)) == 3
    want = "fused" if bucket[1] == 512 else "dense"
    assert _trace(model, bucket) == {"attention": want}


def test_chosen_kernel_serves_the_dense_answer(monkeypatch):
    dense = build(_bert_cfg())     # traced on the CPU: the XLA pair
    chosen = build(_bert_cfg())
    params = dense.init_params(jax.random.key(0))
    items = [dense.host_decode(b'{"text": "%s"}' % t, "application/json")
             for t in (b"one live text", b"and a longer one " * 40)]
    batch = dense.assemble(items, (2, 512))
    want = jax.jit(dense.forward)(params, batch)
    assert dense.traced_paths((2, 512)) == {"attention": "dense"}
    monkeypatch.setattr(fa, "platform_here", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret_here", lambda: True)
    got = jax.jit(chosen.forward)(params, batch)
    assert chosen.traced_paths((2, 512)) == {"attention": "fused"}
    np.testing.assert_allclose(np.asarray(got["probs"]),
                               np.asarray(want["probs"]), atol=0.02)


# -- what the runtime shows ------------------------------------------------------

def test_stats_and_series_name_the_traced_path():
    from tpuserve.runtime import build_runtime

    metrics = Metrics()
    cfg = _bert_cfg()
    cfg.seq_buckets = [128]
    model = build(cfg)
    rt = build_runtime(model, metrics=metrics)
    (variant,) = rt.describe()["variants"]
    assert variant["bucket"] == [2, 128] and variant["attention"] == "dense"
    item = model.canary_item()
    for _ in range(3):
        rt.fetch(rt.run((2, 128), model.assemble([item], (2, 128))))
    label = "2x128/bfloat16/fp/single"
    both = [metrics.counter(name).value for name in (
        f"runtime_variant_batches_total{{model=b,variant={label}}}",
        f"runtime_variant_path_batches_total{{model=b,variant={label},"
        "attention=dense}")]
    assert both[0] == both[1] >= 3
    assert "runtime_variant_path_batches_total" in metrics.render_prometheus()


def test_a_family_that_chooses_nothing_adds_no_series():
    from tpuserve.runtime import build_runtime

    metrics = Metrics()
    rt = build_runtime(build(ModelConfig(
        name="toy", family="toy", batch_buckets=[1], dtype="float32",
        num_classes=10, parallelism="single")), metrics=metrics)
    assert "attention" not in rt.describe()["variants"][0]
    assert "runtime_variant_path_batches_total" not in metrics.render_prometheus()


# -- the chip's compiler, without the chip ---------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip: the TPU's compiler is installed where no TPU is.
    Made inside the fixture, never at import: one process may hold libtpu."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("heads,mask", [(16, jnp.bool_), (12, jnp.bool_),
                                        (16, jnp.int32), (12, jnp.int32)])
def test_kernel_compiles_for_the_v5e_at_the_cells_widths(one_chip, heads,
                                                         mask):
    """What the interpreter cannot show: Mosaic takes the transposed first
    product, the sublane concatenations and 16 unrolled heads within VMEM,
    and XLA adds no copy around the call when q, k and v arrive
    sequence-minor, as the projections write them."""
    b, s, d = 8, 512, 64
    x = jax.ShapeDtypeStruct((b, heads, d, s), jnp.bfloat16, sharding=one_chip)
    # A key mask, or the segment numbers of documents sharing a row.
    live = jax.ShapeDtypeStruct((b, s), mask, sharding=one_chip)

    def attend(q, k, v, live):
        to = lambda a: a.transpose(0, 3, 1, 2)  # noqa: E731
        o = fa.fused_attention(to(q), to(k), to(v), live, interpret=False)
        return o.transpose(0, 2, 3, 1)

    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        text = jax.jit(attend).lower(x, x, x, live).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in text
    assert " copy(" not in text and " transpose(" not in text


@pytest.mark.parametrize("family,heads,tile,block_pages,pps", [
    ("mla", 32, 1024, 8, 194), ("mla_sc", 64, 256, 2, 22)])
def test_a_prefill_tiles_walk_is_one_kernel_call_on_the_v5e_at_the_cells_widths(
        one_chip, tmp_path, monkeypatch, family, heads, tile, block_pages, pps):
    """`ops/tile_attention.py` under the families' map over a launch's tiles
    (ISSUE 43), at the two cells' sizes: JoyAI's 32 heads in tiles and key
    blocks of 1,024, LongCat's 64 heads in tiles and key blocks of 256, a
    latent row of 512 and two rotary keys of 64 side by side, two tiles of a
    launch. The TPU branch is steered by the backend's name here, in the test.
    Mosaic takes the kernel (the pools passed once a page of a block, the
    block-table row and the tile's position by scalar prefetch, the walk's
    length a traced grid bound); the program holds ONE custom call a tile and
    no `while` at all (none over key blocks, none over tiles); nothing float32
    by head and tile (a partial context, a statistic) and no copy of a pool
    is in it."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    arch = {"vocab_size": 256, "hidden_size": 1024, "num_attention_heads": heads,
            "q_lora_rank": 256, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128}
    arch.update({"num_hidden_layers": 1, "intermediate_size": 256, "first_k_dense_replace": 1}
                if family == "mla" else
                {"num_layers": 1, "ffn_hidden_size": 256, "expert_ffn_hidden_size": 256,
                 "n_routed_experts": 8, "zero_expert_num": 4, "moe_topk": 2,
                 "attention_method": "MLA"})
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(arch))
    model = build(ModelConfig(name="walk", family=family, dtype="bfloat16", batch_buckets=[1],
                              options={"config_file": str(path),
                                       "max_prompt_tokens": pps * 128 - 768,
                                       "max_new_tokens": 768}))
    assert model.TILE_ROWS == tile and model._form(tile) == "expanded" \
        and model.kv_plan(1, 128).pages_per_slot == pps and model._block_pages(128, pps) == block_pages

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    K, pages = 2, 3200
    lp = {"w_kb": shape(512, heads, 128), "w_vb": shape(512, heads, 128)}

    def attend(lp, qn, qr, ckv, kr, rows, qpos, last):
        t = {"K": K, "T": tile, "rows": rows, "qpos": qpos, "last": last}
        return model._attend_tiles(lp, qn, qr, (ckv, kr), t, "expanded")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        text = jax.jit(attend).lower(
            lp, shape(K * tile, heads, 128), shape(K * tile, heads, 64),
            shape(pages, 128, 512), shape(pages, 64, 128), shape(K, pps, dtype=jnp.int32),
            shape(K, tile, dtype=jnp.int32), shape(K, dtype=jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    calls = [ln for ln in text.split("\n") if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == K and all("tile_walk" in ln for ln in calls)
    assert " while(" not in text
    for partial in (f"f32[{heads},{tile},128]", f"f32[{tile},{heads},128]", f"f32[{heads},{tile}]"):
        assert partial not in text
    assert not [ln for ln in text.split("\n")
                if " copy(" in ln and (f"{pages},128,512" in ln or f"{pages},64,128" in ln)]


@pytest.mark.parametrize("family,heads,lanes,block_pages,pps,pages", [
    ("mla", 32, 16, 8, 194, 3200), ("mla_sc", 64, 256, 4, 22, 2048)])
def test_a_steps_walk_is_one_kernel_call_an_attention_on_the_v5e_at_the_cells_widths(
        one_chip, tmp_path, monkeypatch, family, heads, lanes, block_pages, pps, pages):
    """`ops/lane_attention.py` under the families' step (ISSUE 44), at the two
    cells' sizes: JoyAI's 16 lanes of 32 heads over cells of 8 pages,
    LongCat's 256 lanes of 64 heads over cells of 4, a latent row of 512
    and two rotary keys of 64 side by side. The TPU branch is steered by the
    backend's name here, in the test. Mosaic takes the kernel (the pools
    passed once a page of a block, the work list by scalar prefetch, its
    length a traced grid bound, the rotary keys laid out a position a row by
    strided stores); one attention of a step is ONE custom call
    and no `while` at all (none over lanes, groups or key blocks), nothing
    float32 by lane, head and key (a block's scores) is in the program, and
    no copy of a pool."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    arch = {"vocab_size": 256, "hidden_size": 1024, "num_attention_heads": heads,
            "q_lora_rank": 256, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128}
    arch.update({"num_hidden_layers": 1, "intermediate_size": 256, "first_k_dense_replace": 1}
                if family == "mla" else
                {"num_layers": 1, "ffn_hidden_size": 256, "expert_ffn_hidden_size": 256,
                 "n_routed_experts": 8, "zero_expert_num": 4, "moe_topk": 2,
                 "attention_method": "MLA"})
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(arch))
    model = build(ModelConfig(name="walk", family=family, dtype="bfloat16", batch_buckets=[1],
                              options={"config_file": str(path),
                                       "max_prompt_tokens": pps * 128 - 768,
                                       "max_new_tokens": 768}))
    assert model._form(1) == "absorbed" and model.kv_plan(1, 128).pages_per_slot == pps \
        and model.step_keys // 128 == block_pages

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    lp = {"w_kb": shape(512, heads, 128), "w_vb": shape(512, heads, 128)}

    def attend(lp, qn, qr, ckv, kr, bt, last):
        walk, work, _ = model._step_walk((ckv, kr), bt, last)
        assert walk == "kernel"
        return model._walk_lanes(lp, qn, qr, (ckv, kr), work)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        text = jax.jit(attend).lower(
            lp, shape(lanes, heads, 128), shape(lanes, heads, 64), shape(pages, 128, 512),
            shape(pages, 64, 128), shape(lanes, pps, dtype=jnp.int32),
            shape(lanes, dtype=jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    calls = [ln for ln in text.split("\n") if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "lane_walk" in calls[0]
    assert " while(" not in text
    keys = block_pages * 128
    for scores in (f"f32[{lanes},{heads},{keys}]", f"f32[{heads},{keys}]"):
        assert scores not in text
    assert not [ln for ln in text.split("\n")
                if " copy(" in ln and (f"{pages},128,512" in ln or f"{pages},64,128" in ln)]


def test_attention_over_picks_is_kernel_calls_on_the_v5e_at_the_cells_widths(
        one_chip, tmp_path, monkeypatch):
    """The `mla_sel` family's walks (ISSUE 62) at its cell's sizes: 128 heads in
    tiles and key blocks of 1,024, 64 index heads of 128 over a third leaf of
    128 values a token, 16 lanes, block tables of 258 pages. A tile past
    `index_topk`: `tile_scores` (the index keys' pages read in place through the
    block table, the walk's length a traced grid bound; since ISSUE 65 it keeps
    a row sub-tile's order keys over the padded 33,792 key places in 34.6 MB of
    scratch and leaves each row's threshold beside the scores) and `tile_walk`
    under the pair, beside the plain `tile_walk` of a tile under it, a `cond`
    between them: NOTHING of XLA's runs over a tile's scores (no operation
    but the two kernels' calls has a (1,024, 33,792) operand or result); a
    step: `lane_scores` and `lane_walk` under the lanes' picks by ONE work
    list, beside the plain walk. Mosaic takes all four; nothing float32 by
    index head, row and key (a block's products before the ReLU) is in
    either program, and no copy of a pool."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    heads, tile, pps, pages, lanes = 128, 1024, 258, 4224, 16
    arch = {"vocab_size": 256, "hidden_size": 1024, "num_attention_heads": heads,
            "q_lora_rank": 256, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128, "num_hidden_layers": 1,
            "intermediate_size": 256, "first_k_dense_replace": 1, "index_n_heads": 64,
            "index_head_dim": 128, "index_topk": 2048}
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(arch))
    model = build(ModelConfig(name="walk", family="mla_sel", dtype="bfloat16", batch_buckets=[1],
                              options={"config_file": str(path), "max_prompt_tokens": 32768,
                                       "max_new_tokens": 256}))
    assert model.TILE_ROWS == tile and model._form(tile) == "expanded" \
        and model.kv_plan(1, 128).pages_per_slot == pps and model._block_pages(128, pps) == 8

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    K = 2
    lp = {"w_kb": shape(512, heads, 128), "w_vb": shape(512, heads, 128)}
    pools = (shape(pages, 128, 512), shape(pages, 64, 128), shape(pages, 128, 128))

    def tiles(lp, qn, qr, qi, wi, ckv, kr, ik, rows, qpos, last):
        t = {"K": K, "T": tile, "rows": rows, "qpos": qpos, "last": last}
        return model._attend_tiles(lp, qn, qr, (ckv, kr), t, "expanded", (qi, wi, ik))

    def step(lp, qn, qr, qi, wi, ckv, kr, ik, bt, pos):
        walk, work, _ = model._step_walk((ckv, kr), bt, pos)
        assert walk == "kernel"
        m = {"walk": walk, "work": work, "bt": bt, "last": pos, "pos": pos, "form": "absorbed"}
        return model._attend_lanes(lp, qn, qr, qi, wi, (ckv, kr), ik, m)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        launch = jax.jit(tiles).lower(
            lp, shape(K * tile, heads, 128), shape(K * tile, heads, 64),
            shape(K * tile, 64, 128), shape(K * tile, 64, dtype=jnp.float32), *pools,
            shape(K, pps, dtype=jnp.int32), shape(K, tile, dtype=jnp.int32),
            shape(K, dtype=jnp.int32)).compile().as_text()
        a_step = jax.jit(step).lower(
            lp, shape(lanes, heads, 128), shape(lanes, heads, 64), shape(lanes, 64, 128),
            shape(lanes, 64, dtype=jnp.float32), *pools, shape(lanes, pps, dtype=jnp.int32),
            shape(lanes, dtype=jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)

    def calls(text):
        return sorted(name for ln in text.split("\n")
                      if " custom-call(" in ln and "tpu_custom_call" in ln
                      for name in ("tile_scores", "tile_walk", "lane_scores", "lane_walk")
                      if name in ln)

    assert calls(launch) == ["tile_scores"] * K + ["tile_walk"] * 2 * K
    assert calls(a_step) == ["lane_scores", "lane_walk", "lane_walk"]
    wide = [ln for ln in launch.split("\n") if f"[{tile},33792]" in ln]
    assert len(wide) == 3 * K and all(
        " custom-call(" in ln or " get-tuple-element(%tile_scores" in ln for ln in wide), wide[:3]
    for text in (launch, a_step):
        for products in (f"f32[64,{tile},1024]", f"f32[{tile},64,1024]", "f32[16,64,1024]"):
            assert products not in text
        assert not [ln for ln in text.split("\n") if " copy(" in ln and any(
            f"{pages},{rows}" in ln for rows in ("128,512", "64,128", "128,128"))]


def _sink_cell(tmp_path, one_chip):
    """`decoder_sink` at the cell's heads on a hidden size of 1,024 and the
    cell's lanes, pages and rings, as shapes on the described chip."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    arch = {"vocab_size": 256, "hidden_size": 1024, "intermediate_size": 256,
            "num_hidden_layers": 2, "hybrid_layer_pattern": [0, 1], "moe_layer_freq": [0, 0],
            "num_attention_heads": 64, "num_key_value_heads": 4, "head_dim": 192,
            "v_head_dim": 128, "swa_num_attention_heads": 64, "swa_num_key_value_heads": 8,
            "swa_head_dim": 192, "swa_v_head_dim": 128, "partial_rotary_factor": 0.334,
            "rope_theta": 10000000, "swa_rope_theta": 10000, "sliding_window": 128,
            "add_swa_attention_sink_bias": True, "attention_value_scale": 0.707}
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(arch))
    model = build(ModelConfig(name="walk", family="decoder_sink", dtype="bfloat16",
                              batch_buckets=[1], options={
                                  "config_file": str(path), "max_prompt_tokens": 2048,
                                  "max_new_tokens": 1024}))
    lanes, pages, P = 384, 4608, 128

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    sig = model.kv_plan(lanes, P, pages).state
    leaves = {leaf: shape(*sig[leaf][0].shape) for leaf in model._leaves()}
    lane = {"bt": shape(lanes, model.kv_plan(1, P).pages_per_slot, dtype=jnp.int32),
            "pos": shape(lanes, dtype=jnp.int32), "live": shape(lanes, dtype=jnp.bool_),
            "ring": shape(lanes, dtype=jnp.int32)}
    return model, shape, leaves, lane


def _compiled_text(fn, donate: int, *args) -> str:
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        return jax.jit(fn, donate_argnums=(donate,)).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_a_global_layers_decode_is_one_kernel_call_on_the_v5e_at_the_cells_widths(
        one_chip, tmp_path, monkeypatch):
    """`ops/lane_attention.py` `head_walk` under `decoder_sink`'s step (ISSUE 49)
    at the cell's sizes: 384 lanes of 64 query heads on 4 KV heads, keys in a
    passing part of 128 and a turning part of 64 two heads a row, values of
    128 in a pool of their own, 4,608 pages of 128 tokens, a block table of 24
    pages, cells of 4 pages. The TPU branch is steered by the backend's name
    here, in the test. Mosaic takes the kernel (all four KV heads of a page in
    one block, the work list by scalar prefetch, its length a traced grid
    bound); a global layer's walk of a step, with its rows' write into the
    three pools, is ONE custom call and no `while`, nothing float32 by lane,
    head and key is in the program, no gather of the padded table, and no copy
    of a pool."""
    model, shape, leaves, lane = _sink_cell(tmp_path, one_chip)
    lanes, pages, P = 384, 4608, 128
    pps, heads = model.kv_plan(1, P).pages_per_slot, model._heads(0)
    assert pps == 24 and heads == (4, 192, 128) and model.step_keys // P == 4
    pools = tuple(leaves[leaf] for leaf in model._leaves(LeafKind.POOL))
    assert [p.shape for p in pools] == [(4, pages, P, 128), (2, pages, P, 128), (4, pages, P, 128)]

    def attend(q, k, v, pools, bt, pos, live, ring):
        state = {"bt": bt, "kn": [pools[0]], "kr": [pools[1]], "vf": [pools[2]], "ring": ring}
        m = model._step_plan(state, live, pos)
        assert m["walk"] == "kernel"
        return model._attend_global(q, k, v, pools, m, heads)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the pools donated, as the engine donates the state they are part of
    text = _compiled_text(attend, 3, shape(lanes, 64, 192), shape(lanes, 4, 192),
                          shape(lanes, 4, 128), pools, *lane.values())
    calls = [ln for ln in text.split("\n") if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "head_walk" in calls[0]
    assert " while(" not in text
    for scores in (f"f32[{lanes},64,512]", "f32[64,512]", f"[4,{lanes},{pps * P},"):
        assert scores not in text
    assert not [ln for ln in text.split("\n") if " copy(" in ln and f"{pages},128,128" in ln]


def test_a_window_layers_decode_reads_its_ring_in_place_on_the_v5e_at_the_cells_widths(
        one_chip, tmp_path, monkeypatch):
    """The same kernel over a window layer's rings (ISSUE 50) at the cell's
    sizes: 384 lanes of 64 query heads on 8 KV heads (8 rows a head: every row
    over each head's keys), 385 rings of 128 places, a place a row of 1,024,
    512 and 1,024 values (the key's part that passes, its part that turns,
    the values: 8 heads side by side), the learned sink an operand. Mosaic
    takes it (a ring a block, a head's columns cut out of it on whole lane
    tiles); a window layer's step, with its row's write into the three rings,
    is ONE custom call: no gathered ring (nothing by lane, place and 1,536
    columns), no float32 scores by lane, no `while`, and no copy of a ring."""
    model, shape, leaves, lane = _sink_cell(tmp_path, one_chip)
    lanes, heads = 384, model._heads(1)
    assert heads == (8, 192, 128) and model.window == 128
    rings = tuple(leaves[leaf] for leaf in ("kwn", "kwr", "vw"))
    assert [r.shape for r in rings] == [(385, 128, 1024), (385, 128, 512), (385, 128, 1024)]

    def attend(q, k, v, rings, sink, bt, pos, live, ring):
        m = model._step_plan({"bt": bt, "kn": [leaves["kn"]], "ring": ring}, live, pos)
        assert m["ring_walk"] == "kernel" and m["ring_work"]["pages"].shape == (lanes,)
        return model._attend_ring(q, k, v, rings, m, sink, heads)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_text(attend, 3, shape(lanes, 64, 192), shape(lanes, 8, 192),
                          shape(lanes, 8, 128), rings, shape(64, dtype=jnp.float32),
                          *lane.values())
    calls = [ln for ln in text.split("\n") if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "head_walk" in calls[0]
    assert " while(" not in text
    for gathered in (f"[{lanes},128,1536]", f"[{lanes},128,8,192]", f"[{lanes},128,1024]",
                     f"f32[{lanes},8,8,1,128]"):
        assert gathered not in text
    assert not [ln for ln in text.split("\n") if " copy(" in ln and "[385,128," in ln]


def test_decode_over_packed_pages_compiles_for_the_v5e_at_the_cells_widths(one_chip, tmp_path,
                                                                           monkeypatch):
    """`paged_lm._decode_full` over pools whose rows hold two KV heads of 64
    (ISSUE 40), at the cell's sizes: 80 lanes, 32 query heads over 8 KV heads,
    1,280 pages of 128 tokens, a block table of 12 pages. The TPU branch is
    steered by the backend's name here, in the test: jax's paged-attention
    kernel takes the 128-wide packed rows and eight padded query heads a
    pair, in compute blocks of 4 pages, and XLA copies no pool around it."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    arch = {"vocab_size": 256, "hidden_size": 2048, "num_hidden_layers": 1,
            "layer_types": ["attention"], "mamba_n_heads": 64, "mamba_d_head": 64,
            "mamba_n_groups": 1, "mamba_d_state": 128, "num_attention_heads": 32,
            "num_key_value_heads": 8, "shared_intermediate_size": 256,
            "attention_multiplier": 0.015625, "position_embedding_type": "nope"}
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(arch))
    model = build(ModelConfig(name="packed", family="hybrid_ffn", dtype="bfloat16",
                              batch_buckets=[1], options={
                                  "config_file": str(path), "max_prompt_tokens": 1024,
                                  "max_new_tokens": 512}))
    S = jax.ShapeDtypeStruct
    pool = S(model._page_shape(1280, 128), jnp.bfloat16, sharding=one_chip)
    assert pool.shape == (4, 1280, 128, 128)
    q = S((80, 32, 64), jnp.bfloat16, sharding=one_chip)
    bt = S((80, 12), jnp.int32, sharding=one_chip)
    pos = S((80,), jnp.int32, sharding=one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        text = jax.jit(model._decode_full).lower(q, pool, pool, bt, pos).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in text
    assert not [ln for ln in text.split("\n") if " copy(" in ln and "1280,128,128" in ln]


def test_a_sublayers_hyper_connection_is_two_kernel_calls_on_the_v5e_at_the_cells_widths(
        one_chip, tmp_path, monkeypatch):
    """`ops/hyper.py` `enter` and `leave` under the `mla_hc` family's sublayer
    (ISSUE 47), at the cell's sizes: a launch of 4,096 rows of four streams of
    3,584 values, `Phi` 14,336 x 24 in bfloat16. The TPU branch is steered by
    the backend's name here, in the test. Mosaic takes both kernels (the
    product with the stream as the transposed operand, the transposition of a
    tile's maps, sublane sums in the Sinkhorn, dynamic lane offsets in the
    mixes); the sublayer is TWO custom calls, no float32 value of the stream's
    size and no copy of the stream is in the program; a step's 64 rows keep
    XLA and have none."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    n, d, rows = 4, 3584, 4096
    arch = {"vocab_size": 256, "hidden_size": d, "num_attention_heads": 2, "q_lora_rank": 128,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "num_hidden_layers": 1, "intermediate_size": 256,
            "first_k_dense_replace": 1, "hc_mult": n}
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(arch))
    model = build(ModelConfig(name="hc", family="mla_hc", dtype="bfloat16", batch_buckets=[1],
                              options={"config_file": str(path), "max_prompt_tokens": 1024,
                                       "max_new_tokens": 128}))

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    f32 = jnp.float32
    hp = {"phi": shape(n * d, 2 * n + n * n), "alpha": shape(3, dtype=f32),
          "b_pre": shape(n, dtype=f32), "b_post": shape(n, dtype=f32),
          "b_res": shape(n, n, dtype=f32)}

    paths = []

    def sublayer(hp, x, w):
        plan = {}
        out, _ = model._sublayer(hp, x, plan, lambda u: (jnp.dot(
            u, w, preferred_element_type=f32), None))
        paths.append(plan["hc_path"])
        return out

    def compiled(rows):
        lowered = jax.jit(sublayer).lower(hp, shape(rows, n * d), shape(d, d))
        return lowered.compile().as_text().split("\n")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        launch, step = compiled(rows), compiled(64)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert paths == ["kernel", "xla"]
    calls = [ln for ln in launch if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 2 and "hc_enter" in calls[0] and "hc_leave" in calls[1]
    assert not [ln for ln in launch if f"f32[{rows},{n * d}]" in ln]
    assert not [ln for ln in launch if " copy(" in ln and f"[{rows},{n * d}]" in ln]
    assert not [ln for ln in step if "tpu_custom_call" in ln]


def test_a_steps_delta_rule_update_is_one_kernel_call_a_layer_on_the_v5e_at_the_cells_widths(
        one_chip, tmp_path, monkeypatch):
    """`ops/delta_update.py` under the `hybrid_delta` family's step (ISSUE 53),
    at the cell's sizes: 192 lanes of 64 heads of 128 x 128 float32 a layer on a
    hidden size of 1,024. The TPU branch is steered by the backend's name here,
    in the test. Mosaic takes the kernel (the transposition of a cell's packed
    vectors, column slices broadcast along lanes, sublane sums); a delta-rule
    layer of a step is ONE custom call whose state operand is the state leaf
    itself and whose result is written over it (the step's state donated), and
    no other float32 value of a state's size, no copy of one, is in the
    program."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    lanes, heads, hd = 192, 64, 128
    arch = {"vocab_size": 256, "hidden_size": 1024, "num_hidden_layers": 3, "gqa_layers": [0],
            "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": hd, "num_heads": heads,
                                   "num_kv_heads": None},
            "num_attention_heads": 8, "num_key_value_heads": 1, "head_dim": 128,
            "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
            "kda_allow_neg_eigval": True, "first_k_dense_replace": 0, "n_routed_experts": 8,
            "n_shared_experts": 1, "num_experts_per_tok": 2, "moe_intermediate_size": 128}
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(arch))
    model = build(ModelConfig(name="hd", family="hybrid_delta", dtype="bfloat16",
                              batch_buckets=[1],
                              options={"config_file": str(path), "max_prompt_tokens": 256,
                                       "max_new_tokens": 128}))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(place, jax.eval_shape(lambda: model.draw_params(0)))
    state = jax.tree_util.tree_map(place, model.kv_plan(lanes, 128, 64).state)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        text = jax.jit(model.step, donate_argnums=1).lower(params, state).compile() \
            .as_text().split("\n")
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    big = f"f32[{lanes},{heads},{hd},{hd}]"
    calls = [ln for ln in text if " custom-call(" in ln and "delta_update" in ln.split("=")[0]]
    assert len(calls) == 2 and all(big in ln for ln in calls)
    # Nothing else MAKES a value of a state's size: no decayed copy, no `S'^T k` of it.
    assert not [ln for ln in text if f"= {big}" in ln and " parameter(" not in ln
                and " get-tuple-element(" not in ln and " bitcast(" not in ln]
    assert not [ln for ln in text if " copy(" in ln and big in ln]


def test_a_launchs_chunked_delta_rule_is_one_kernel_call_a_layer_on_the_v5e_at_the_cells_widths(
        one_chip, tmp_path, monkeypatch):
    """`ops/delta_scan.py` under the `hybrid_delta` family's launch (ISSUE 54),
    at the cell's sizes: 8 tiles of 128 rows, 64 heads of 128 channels, on a
    hidden size of 1,024. The TPU branch is steered by the backend's name here,
    in the test. Mosaic takes the kernel (lane offsets and sublane strides that
    follow the loop over a cell's heads, rolls along lanes and sublanes, the
    transposed products, the slices of half a table); a delta-rule layer of a
    launch is ONE custom call that reads what the convolution gives and g where
    XLA keeps them, so the scope has no `lax.scan` over tiles (the scatter of the pieces' states
    is a `while` of its own) and
    makes no copy of what the convolution gives (no q, k, v by head in device
    memory)."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    K, T, heads, hd, slots = 8, 128, 64, 128, 16
    arch = {"vocab_size": 256, "hidden_size": 1024, "num_hidden_layers": 2, "gqa_layers": [0],
            "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": hd, "num_heads": heads,
                                   "num_kv_heads": None},
            "num_attention_heads": 8, "num_key_value_heads": 1, "head_dim": 128,
            "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
            "kda_allow_neg_eigval": True, "first_k_dense_replace": 0, "n_routed_experts": 8,
            "n_shared_experts": 1, "num_experts_per_tok": 2, "moe_intermediate_size": 128}
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(arch))
    model = build(ModelConfig(name="hd", family="hybrid_delta", dtype="bfloat16",
                              batch_buckets=[1],
                              options={"config_file": str(path), "max_prompt_tokens": 2048,
                                       "max_new_tokens": 128}))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    lp = jax.tree_util.tree_map(place, jax.eval_shape(lambda: model.draw_params(0)))["layer1"]
    sig = model.kv_plan(slots, 128, 64).state

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def layer(lp, u, ssm, conv, slot, start, length):
        launch = {"slot": slot, "start": start, "length": length,
                  "pages": jnp.zeros((K, 1), jnp.int32)}
        t = model._tiles(launch, K * T)
        return model._delta_prefill(lp, u, t, ssm, conv, slot, start, length, model._scan_path(t))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        text = jax.jit(layer).lower(
            lp, shape(K * T, 1024, dtype=jnp.bfloat16), place(sig["ssm"][0]),
            place(sig["conv"][0]), shape(K), shape(K), shape(K)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    lines = text.split("\n")
    calls = [ln for ln in lines if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "delta_scan" in calls[0].split("=")[0]
    assert f"f32[{K},{T},{3 * heads * hd}]" in calls[0] and f"f32[{K},{T},{heads},{hd}]" in calls[0]
    assert not [ln for ln in lines if " while(" in ln and "scan/while" in ln]   # no lax.scan
    # q, k and v are never made by head outside the call: nothing of their size but the
    # convolution's own result, and no copy or transpose of that
    rows = (f"f32[{K},{T},{3 * heads * hd}]", f"f32[{K * T},{3 * heads * hd}]",
            f"f32[{K},{T},3,{heads},{hd}]", f"f32[{K * T},3,{heads},{hd}]")
    assert not [ln for ln in lines if (" copy(" in ln or " transpose(" in ln)
                and any(s in ln.split("=")[1][:60] for s in rows)]


def test_a_picked_tiles_block_scores_are_one_kernel_call_on_the_v5e_at_the_cells_widths(
        one_chip, monkeypatch):
    """`ops/block_scores.py` under `BlockSelectAttention._tile_keep` (ISSUE 69), at
    the MiniCPM-SALA cell's sizes: a tile of 512 rows, 32 heads of 128 on 2 KV
    groups, bfloat16, a table of 1,029 pages of 64 (4,116 windows in 9 window
    blocks of 512, 1,040 spans). The TPU branch is steered by the backend's name
    here, in the test. Mosaic takes the kernel (a sub-tile's scores held in
    scratch, the window blocks a traced grid bound, a block's maximum by a roll
    of one lane); a tile's picks are ONE custom call and the threshold's search:
    nothing of (KV, g T, windows) float32 is in the program, whole or padded."""
    from tpuserve.models import mixers

    T, H, KV, hd, P, pps, spans = 512, 32, 2, 128, 64, 1029, 1040

    class Layer(mixers.BlockSelectAttention):
        name, heads, kv, dtype = "layer", H, KV, jnp.dtype("bfloat16")

        def _scale(self):
            return hd ** -0.5

    model = Layer()
    model.hd = hd
    model._blk_setup("layer", {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                               "topk": 64, "init_blocks": 1, "window_size": 2048,
                               "dense_len": 8192})

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def keep(q, kc, row, qpos, last):
        path = model._select_path(T, pps, P)
        assert path == "kernel"
        return model._tile_keep(q, kc, row, qpos, spans, P, last, path)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        text = jax.jit(keep).lower(
            shape(T, H, hd, dtype=jnp.bfloat16), shape(16465 * 4, KV * hd, dtype=jnp.bfloat16),
            shape(pps), shape(T), shape()).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    lines = text.split("\n")
    calls = [ln for ln in lines if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "block_scores" in calls[0].split("=")[0]
    assert f"f32[{KV},9,{T},128]" in calls[0].split("=")[1][:40]     # 128 block scores a window block
    g = H // KV
    for windows in (4116, 4224, 4608):       # the table's, the plain form's padding, the kernel's
        assert f"f32[{KV},{g * T},{windows}]" not in text
        assert f"f32[{KV},{g},{T},{windows}]" not in text


@pytest.mark.parametrize("heads,groups", [(64, 1), (128, 1), (32, 2)])
def test_a_launchs_mamba2_scan_is_one_kernel_call_a_layer_on_the_v5e_at_the_cells_widths(
        one_chip, monkeypatch, heads, groups):
    """`ops/ssm_scan.py` under `Mamba2Mixer`'s launch (ISSUE 67), at the three
    Mamba-2 cells' sizes: 8 tiles of 128 rows, heads of 64 channels, a state of
    128, bfloat16. The TPU branch is steered by the backend's name here, in the
    test. Mosaic takes the kernel (the heads picked by products with a 0/1 matrix,
    two heads a register selected by lane, the transposed product into the state,
    y written rows on lanes); a Mamba-2 layer of a launch is ONE custom call that
    reads x, B and C out of the activated rows where XLA keeps them and the
    pieces' states in the slots' own block, in place, so the scope has no
    `lax.scan` over tiles, no (tiles, heads, T, T) table of decays, no copy of
    the activated rows' x part and no (tiles, heads, P, N) copy of the states."""
    from tpuserve.models import mixers
    from tpuserve.models.paged_lm import PagedLM

    K, T, P, N, slots = 8, 128, 64, 128, 80   # a block too large to be staged whole

    class Layer(mixers.Mamba2Mixer):
        name, conv_k, eps, dtype = "layer", 4, 1e-5, jnp.dtype("bfloat16")
        mh, mp, mg, mn, conv_ch = heads, P, groups, N, heads * P + 2 * groups * N

    model, ch = Layer(), heads * P + 2 * groups * N

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    lp = {"conv_w": shape(4, ch, dtype=jnp.bfloat16), "conv_b": shape(ch, dtype=jnp.bfloat16),
          "dt_bias": shape(heads), "A_log": shape(heads), "D": shape(heads),
          "gate_norm": shape(heads, P, dtype=jnp.bfloat16)}

    def layer(lp, z, xbc, dt, ssm, conv, slot, start, length):
        t = PagedLM._tiles({"slot": slot, "start": start, "length": length,
                            "pages": jnp.zeros((K, 1), jnp.int32)}, K * T)
        assert model._scan_path(t, ssm) == "kernel"
        with jax.named_scope("ssm_scan"):
            y, ssm, conv = model._scan_slots(lp, xbc, dt, t, ssm, conv, slot, start, length)
            return model._gated_norm(lp, y, z), ssm, conv

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        ints = [shape(K, dtype=jnp.int32)] * 3
        text = jax.jit(layer, donate_argnums=(4, 5)).lower(
            lp, shape(K * T, heads, P), shape(K * T, ch, dtype=jnp.bfloat16), shape(K * T, heads),
            shape(slots, heads, P, N), shape(slots, 3, ch, dtype=jnp.bfloat16),
            *ints).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    lines = text.split("\n")
    calls = [ln for ln in lines if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "ssm_scan" in calls[0].split("=")[0]
    assert f"bf16[{K},{T},{ch}]" in calls[0] and f"f32[{heads * P},{K * T}]" in calls[0]
    assert not [ln for ln in lines if " while(" in ln and "scan/while" in ln]   # no lax.scan
    assert f"[{K},{heads},{T},{T}]" not in text                                # no table of decays
    # nothing of x's size is cut out of the activated rows, and the pieces' states are neither
    # gathered nor scattered: no (K, H, P, N) array, no copy of the slots' block
    made = (f"bf16[{K},{T},{heads * P}]", f"bf16[{K},{T},{heads},{P}]",
            f"bf16[{K * T},{heads},{P}]", f"f32[{slots},{heads},{P},{N}]",
            f"f32[{slots},{heads * P},{N}]")
    assert not [ln for ln in lines if (" copy(" in ln or " slice(" in ln or " slice-start(" in ln)
                and any(s in ln.split(" = ")[1][:60] for s in made)]
    assert f"f32[{K},{heads},{P},{N}]" not in text and f"f32[{K},{heads * P},{N}]" not in text


def test_evas_step_walks_rings_and_pages_in_place_on_the_v5e_at_the_cells_widths(
        one_chip, tmp_path, monkeypatch):
    """The `eva` family's two programs (ISSUE 55) at the cell's widths, two layers
    of them: 32 heads of 128, a window of 2,048 in chunks of 16, 24 slots' rings
    and 160 pages of 128 summary rows in one pool a layer. The TPU branch is
    steered by the backend's name here, in the test. A step is ONE call of the
    repo's own `head_walk` a layer (ISSUE 56: a key in one part, the pools as
    they lie; Mosaic takes it at 32 query rows on 32 KV heads) over the virtual
    block table's work list, under `eva_decode`; jax's `paged_attention` is in
    neither program; a LAUNCH is ONE call of `launch_walk` a layer (ISSUE 58)
    with no loop; and NO copy, transpose or gather of a whole pool exists in
    a step (a pool crossed to another layout once a layer a step when the
    chunk's rows were gathered from the pool seen flat: 5 ms each by the
    compiler's own estimate), nor in a launch. The pools hold a position as ONE
    row, its heads side by side (ISSUE 63), and a program scatters into each
    pool twice a layer."""
    import json

    from tpuserve.config import ModelConfig
    from tpuserve.models import build

    slots, pages, P, chunk = 24, 160, 128, 1024
    arch = {"model_type": "evabyte", "attention_class": "eva", "attention_bias": False,
            "chunk_size": 16, "window_size": 2048, "num_chunks": None, "fp32_ln": False,
            "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu",
            "hidden_size": 4096, "intermediate_size": 11008, "norm_add_unit_offset": True,
            "num_attention_heads": 32, "num_key_value_heads": 32, "num_hidden_layers": 2,
            "num_pred_heads": 8, "rms_norm_eps": 1e-5, "rope_scaling": None,
            "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320}
    path = tmp_path / "arch.json"
    path.write_text(json.dumps(arch))
    model = build(ModelConfig(name="eva", family="eva", dtype="bfloat16", batch_buckets=[1],
                              options={"config_file": str(path), "max_prompt_tokens": 24576,
                                       "max_new_tokens": 512}))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(place, jax.eval_shape(lambda: model.draw_params(0)))
    state = jax.tree_util.tree_map(place, model.kv_plan(slots, P, pages).state)
    k = model.kv_prefill_pieces(chunk, P)
    assert (k, model.kv_plan(1, P).pages_per_slot, state["kf"][0].shape) == (8, 13, (560, 128, 4096))
    launch = {"ids": (chunk,), "pages": (k, 13), "temp": (k,),
              **{f: (k,) for f in ("slot", "start", "length", "n", "seed", "max_new", "ring")}}
    launch = {f: jax.ShapeDtypeStruct(dims, jnp.float32 if f == "temp" else jnp.int32,
                                      sharding=one_chip) for f, dims in launch.items()}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable here
    try:
        step = jax.jit(model.step, donate_argnums=(1,)).lower(params, state).compile().as_text()
        fill = jax.jit(lambda p, s, la: model.prefill_chunk(p, s, la, chunk=chunk),
                       donate_argnums=(1,)).lower(params, state, launch).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    calls = [ln for ln in step.split("\n") if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 2 and all("eva_decode" in ln and "head_walk" in ln for ln in calls)
    assert "paged_attention" not in step and "paged_attention" not in fill
    # a launch's attention is ONE call of `launch_walk` a layer (ISSUE 58) under
    # `eva_prefill`: no loop over tiles or pages, no page taken from a pool
    calls = [ln for ln in fill.split("\n") if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 2 and all("eva_prefill" in ln and "launch_walk" in ln for ln in calls)
    scoped = "".join(ln for ln in fill.split("\n") if "eva_prefill" in ln)
    assert " while(" not in scoped and "dynamic-slice(" not in scoped and " gather(" not in scoped
    whole = ("[560,128,4096]", "[71680,4096]", "[4480,16,4096]")
    for text in (step, fill):
        moved = [ln.split("=")[0] for ln in text.split("\n")
                 if any(f" {op}(" in ln for op in ("copy", "transpose", "gather"))
                 and any(f"bf16{dims}" in ln.split("=")[1].split("(")[0] for dims in whole)]
        assert not moved, moved
    # a token is ONE row of a pool (ISSUE 63): a layer writes each pool twice, a step its
    # ring's rows and its summaries', a launch its summaries and then its rings' pages as slabs
    wrote = [[ln.split("=")[1].split("{")[0].strip() for ln in text.split("\n") if " scatter(" in ln
              and any(f"bf16{dims}" in ln.split("=")[1].split("(")[0] for dims in whole)]
             for text in (step, fill)]
    assert wrote[0] == ["bf16[71680,4096]"] * 8, wrote[0]
    assert sorted(wrote[1]) == ["bf16[560,128,4096]"] * 4 + ["bf16[71680,4096]"] * 4, wrote[1]
