"""ctypes binding for the native JPEG->YUV420 decode shim (SURVEY.md §2 C12).

The shim (native/decode/jpegyuv.c) entropy-decodes baseline 4:2:0 JPEGs into
raw Y/Cb/Cr planes — no chroma upsample, no RGB conversion — so the host
ships 1.5 B/px over the wire instead of 3 B/px and the device does the color
math (tpuserve.preproc.device_prepare_images_yuv420). ctypes releases the
GIL for the call, so decode threads scale on multi-core hosts.

``load()`` runs ``make`` once per process (~1 s to build, a no-op when the
.so is newer than jpegyuv.c), so a stale or foreign .so left on disk is never
loaded as-is. When the build fails (no toolchain, no libjpeg) it returns None
and callers fall back to the PIL path at about twice the cost per JPEG —
counted per request in ``native_decode_fallback_total``, never silent.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess

import numpy as np

from tpuserve.utils.locks import new_lock

log = logging.getLogger("tpuserve.native")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "native", "decode")
_SO_PATH = os.path.join(_NATIVE_DIR, "libjpegyuv.so")

_lock = new_lock("native.decoder")
_lib = None
_load_failed = False


def _build() -> bool:
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, timeout=120)
        return True
    except Exception as e:
        log.warning("jpegyuv shim build failed (falling back to PIL): %s", e)
        return False


def load():
    """Return the loaded shim library, or None if unavailable."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if not _build():
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            log.warning("jpegyuv shim load failed: %s", e)
            _load_failed = True
            return None
        lib.jpegyuv_decode.restype = ctypes.c_int
        lib.jpegyuv_decode.argtypes = [
            ctypes.c_char_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
        ]
        lib.jpegyuv_probe.restype = ctypes.c_int
        lib.jpegyuv_probe.argtypes = [
            ctypes.c_char_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


def decode_yuv420(payload: bytes, edge: int):
    """Decode an edge x edge 4:2:0 JPEG to (y, u, v) uint8 planes.

    Returns None when the shim is unavailable or the file isn't an exact-size
    4:2:0 baseline JPEG — the caller falls back to PIL (decode + re-subsample
    or RGB wire).
    """
    lib = load()
    if lib is None:
        return None
    half = edge // 2
    y = np.empty((edge, edge), dtype=np.uint8)
    u = np.empty((half, half), dtype=np.uint8)
    v = np.empty((half, half), dtype=np.uint8)
    rc = lib.jpegyuv_decode(
        payload, len(payload),
        y.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        u.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        v.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        edge,
    )
    if rc != 0:
        return None
    return y, u, v
