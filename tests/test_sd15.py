"""Stable Diffusion 1.5 (config 5): tiny-variant txt2img end-to-end,
determinism, padded-lane invariance, DDIM schedule math, full-size parameter
parity with the published model. VERDICT.md r2 item 8; SURVEY.md §3e."""

import asyncio
import io

import jax
import numpy as np
import pytest

from tpuserve.config import ModelConfig, ServerConfig
from tpuserve.models import build
from tpuserve.models.sd15 import MAX_TOKENS, ddim_schedule

pytestmark = pytest.mark.slow

TINY = dict(steps=3, guidance=5.0, vocab_size=512,
            text_layers=1, text_d_model=32, text_heads=2,
            unet_ch=16, unet_mults=[1, 2], unet_res=1, unet_attn_levels=[0],
            unet_heads=2, vae_ch=16, vae_mults=[1, 2])


def sd_cfg(**over) -> ModelConfig:
    base = dict(
        name="sd", family="sd15", batch_buckets=[1, 2], deadline_ms=2.0,
        dtype="float32", parallelism="single", request_timeout_ms=120_000.0,
        image_size=32, options=dict(TINY),
    )
    base.update(over)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def sd_model():
    m = build(sd_cfg())
    return m, m.init_params(jax.random.key(0)), jax.jit(m.forward)


def test_ddim_schedule_math():
    ts, a_t, a_prev = ddim_schedule(10)
    assert ts.shape == a_t.shape == a_prev.shape == (10,)
    assert ts[0] == 999 and ts[-1] == 0
    assert (np.diff(ts) < 0).all()            # high noise -> low noise
    assert a_prev[-1] == 1.0                  # final step lands on x0
    assert (a_prev[:-1] > a_t[:-1]).all()     # denoising increases alpha
    assert (np.diff(a_t) > 0).all()


def test_txt2img_roundtrip_png(sd_model):
    from PIL import Image

    m, params, fwd = sd_model
    item = m.host_decode(b'{"prompt": "a red square", "seed": 7}',
                         "application/json")
    out = jax.tree_util.tree_map(np.asarray, fwd(params, m.assemble([item], (1,))))
    assert out["image"].shape == (1, 32, 32, 3)     # PNG edge == image_size
    png = m.host_postprocess(out, 1)[0]
    assert png[:4] == b"\x89PNG"
    assert Image.open(io.BytesIO(png)).size == (32, 32)


def test_same_prompt_seed_is_deterministic_different_seed_is_not(sd_model):
    m, params, fwd = sd_model
    a = m.host_decode(b'{"prompt": "x", "seed": 1}', "application/json")
    b = m.host_decode(b'{"prompt": "x", "seed": 2}', "application/json")
    o1 = np.asarray(fwd(params, m.assemble([a], (1,)))["image"])
    o2 = np.asarray(fwd(params, m.assemble([a], (1,)))["image"])
    o3 = np.asarray(fwd(params, m.assemble([b], (1,)))["image"])
    np.testing.assert_array_equal(o1, o2)
    assert (o1 != o3).any()


def test_padded_lanes_do_not_affect_real_lanes(sd_model):
    m, params, fwd = sd_model
    a = m.host_decode(b'{"prompt": "hello world", "seed": 3}', "application/json")
    b = m.host_decode(b'{"prompt": "other", "seed": 9}', "application/json")
    lane0_padded = np.asarray(fwd(params, m.assemble([a], (2,)))["image"])[0]
    lane0_full = np.asarray(fwd(params, m.assemble([a, b], (2,)))["image"])[0]
    np.testing.assert_array_equal(lane0_padded, lane0_full)


def test_tokenize_fixed_77(sd_model):
    m, _, _ = sd_model
    ids, neg, seed = m.host_decode(b'{"prompt": "a b c", "seed": 5}', "application/json")
    assert ids.shape == (MAX_TOKENS,) and ids.dtype == np.int32
    assert neg.shape == (MAX_TOKENS,)  # empty negative, still fixed-shape
    assert int(seed) == 5
    long = b'{"prompt": "' + b"word " * 200 + b'"}'
    ids2, _, _ = m.host_decode(long, "application/json")
    assert ids2.shape == (MAX_TOKENS,)
    with pytest.raises(ValueError):
        m.host_decode(b'{"seed": 1}', "application/json")


def test_full_size_matches_published_figures():
    """SD 1.5 published sizes: UNet 859.5M, CLIP text 123.1M, VAE decoder
    ~49.5M. Shape-only trace (eval_shape), no allocation — but the UNet
    trace alone is ~2 minutes of Python, the slowest test in the suite."""
    m = build(ModelConfig(name="sd", family="sd15", dtype="bfloat16",
                          image_size=512, options=dict(vocab_size=49408)))
    p = jax.eval_shape(m.init_params, jax.random.key(0))
    cnt = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                        for x in jax.tree_util.tree_leaves(t))
    assert 855e6 < cnt(p["unet"]) < 865e6, cnt(p["unet"])
    assert 120e6 < cnt(p["text"]) < 126e6, cnt(p["text"])
    assert 45e6 < cnt(p["vae"]) < 55e6, cnt(p["vae"])
    assert m.latent == 64


def test_orbax_roundtrip_preserves_images(sd_model, tmp_path):
    """SD params survive an orbax save/load (the production startup path)
    and regenerate the identical image."""
    from tpuserve import savedmodel

    m, params, fwd = sd_model
    path = str(tmp_path / "ckpt")
    savedmodel.save_orbax(path, params)
    m2 = build(sd_cfg(weights=path))
    restored = m2.load_params()
    item = m.host_decode(b'{"prompt": "same", "seed": 11}', "application/json")
    a = np.asarray(fwd(params, m.assemble([item], (1,)))["image"])
    b = np.asarray(jax.jit(m2.forward)(restored, m2.assemble([item], (1,)))["image"])
    np.testing.assert_array_equal(a, b)


def test_http_generate_end_to_end():
    from aiohttp.test_utils import TestClient, TestServer

    from tpuserve.server import ServerState, make_app

    cfg = ServerConfig(models=[sd_cfg()], decode_threads=2, startup_canary=False)
    state = ServerState(cfg)
    state.build()
    app = make_app(state)
    loop = asyncio.new_event_loop()
    try:
        async def run():
            client = TestClient(TestServer(app))
            await client.start_server()
            r = await client.post(
                "/v1/models/sd:generate",
                data=b'{"prompt": "a tpu rendering images", "seed": 42}',
                headers={"Content-Type": "application/json"})
            body = await r.read()
            ctype = r.content_type
            bad = await client.post(
                "/v1/models/sd:generate", data=b'{"seed": 1}',
                headers={"Content-Type": "application/json"})
            await client.close()
            return r.status, ctype, body, bad.status

        status, ctype, body, bad_status = loop.run_until_complete(run())
        assert status == 200
        assert ctype == "image/png"
        assert body[:4] == b"\x89PNG"
        assert bad_status == 400
    finally:
        loop.close()


def test_negative_prompt_steers_and_defaults_to_empty(sd_model):
    """negative_prompt rides the CFG uncond lane: setting one changes the
    image; leaving it unset equals an explicit empty negative."""
    m, params, fwd = sd_model
    base = m.host_decode(b'{"prompt": "a cat", "seed": 4}', "application/json")
    explicit_empty = m.host_decode(
        b'{"prompt": "a cat", "negative_prompt": "", "seed": 4}',
        "application/json")
    steered = m.host_decode(
        b'{"prompt": "a cat", "negative_prompt": "a dog", "seed": 4}',
        "application/json")
    o_base = np.asarray(fwd(params, m.assemble([base], (1,)))["image"])
    o_empty = np.asarray(fwd(params, m.assemble([explicit_empty], (1,)))["image"])
    o_steer = np.asarray(fwd(params, m.assemble([steered], (1,)))["image"])
    np.testing.assert_array_equal(o_base, o_empty)
    assert not np.array_equal(o_base, o_steer)

    with pytest.raises(ValueError, match="negative_prompt"):
        m.host_decode(b'{"prompt": "x", "negative_prompt": 5}',
                      "application/json")


def _write_tiny_bpe(tmp_path):
    import json as _json

    vocab = {"<|startoftext|>": 0, "<|endoftext|>": 1, "a</w>": 2,
             "cat</w>": 3, "c": 4, "at</w>": 5, "a": 6, "t</w>": 7}
    (tmp_path / "vocab.json").write_text(_json.dumps(vocab))
    (tmp_path / "merges.txt").write_text("#version: 0.2\na t</w>\nc at</w>\n")
    return str(tmp_path / "vocab.json"), str(tmp_path / "merges.txt")


def test_clip_bpe_tokenizer_contract(tmp_path):
    """CLIP-style byte-level BPE behind the WordPiece encode() contract:
    BOS + merged pieces + EOS, EOS-padded fixed length."""
    from tpuserve.text import CLIPBPETokenizer

    vocab_file, merges_file = _write_tiny_bpe(tmp_path)
    tok = CLIPBPETokenizer(vocab_file, merges_file)
    ids, mask = tok.encode("a cat", 8)
    assert ids.shape == (8,) and mask.shape == (8,)
    assert list(ids[:4]) == [0, 2, 3, 1]  # BOS, a</w>, merged cat</w>, EOS
    assert list(mask) == [1, 1, 1, 1, 0, 0, 0, 0]
    assert ids[4:].tolist() == [tok.pad_id] * 4  # EOS-padded


def test_sd15_serves_with_bpe_tokenizer(tmp_path):
    """options.bpe_vocab/bpe_merges swap the prompt tokenizer by config."""
    vocab_file, merges_file = _write_tiny_bpe(tmp_path)
    m = build(sd_cfg(options={**TINY, "bpe_vocab": vocab_file,
                              "bpe_merges": merges_file}))
    ids, neg, seed = m.host_decode(b'{"prompt": "a cat", "seed": 2}',
                                   "application/json")
    assert ids.shape == (MAX_TOKENS,) and list(ids[:4]) == [0, 2, 3, 1]
    assert m.text_encoder.vocab_size == 8  # sized from the BPE vocab
