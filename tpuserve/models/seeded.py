"""Weights by recipe: every tensor drawn where it will live, from a seed and
the tensor's name (ISSUE 28).

A model of ten gigabytes cannot pass through the host as a checkpoint inside
a start-up budget, and a benchmark of serving speed needs no trained weights.
``draw`` makes one tensor from integer words by exact steps, so that two
backends (and a reference that imports nothing of this package, which writes
the same few lines down again) hold the same values bit for bit:

1. the tensor's key: the first four bytes of blake2s(f"{seed}/{name}");
2. element ``i`` of the WHOLE tensor (row-major over ``full_shape``) gets the
   word ``fmix32(i * 0x9E3779B1 + key)`` (murmur3's finaliser; uint32
   arithmetic wraps the same everywhere);
3. the word's four bytes are summed (0..1020, mean 510, standard deviation
   147.8016...: an Irwin-Hall bell), centred, converted to float32 exactly,
   multiplied ONCE by float32(std / 147.8016...) and rounded to the served
   type, both IEEE operations with one correctly rounded result.

Because the word depends on the element's index in the whole tensor, a
share of it (some experts, some heads, some rows of the vocabulary) is drawn
alone by passing the share's ``start`` within ``full_shape``: the shares of
two chips are slices of one model, and a reference draws one layer at a time.
Nothing here is a good random number generator; it is a reproducible one.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

BELL_STD = float(np.sqrt(4 * (256 ** 2 - 1) / 12.0))  # of four summed bytes


def tensor_key(seed: int, name: str) -> int:
    digest = hashlib.blake2s(f"{int(seed)}/{name}".encode()).digest()
    return int.from_bytes(digest[:4], "little")


def _fmix32(h: jax.Array) -> jax.Array:
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def draw(seed: int, name: str, shape: tuple, std: float, dtype,
         full_shape: "tuple | None" = None,
         start: "tuple | None" = None) -> jax.Array:
    """Jittable: the block of tensor ``name`` that begins at ``start`` of
    ``full_shape`` and has ``shape`` (the whole tensor by default)."""
    full_shape = tuple(full_shape or shape)
    start = tuple(start or (0,) * len(shape))
    if int(np.prod(full_shape)) >= 2 ** 32:
        raise ValueError(f"{name}: {full_shape} has more elements than a "
                         "32-bit counter can index")
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for axis in range(len(shape) - 1, -1, -1):
        coord = jax.lax.broadcasted_iota(jnp.uint32, shape, axis) \
            + jnp.uint32(start[axis])
        idx = idx + coord * jnp.uint32(stride)
        stride *= full_shape[axis]
    h = _fmix32(idx * jnp.uint32(0x9E3779B1) + jnp.uint32(tensor_key(seed, name)))
    s = (h & 255) + ((h >> 8) & 255) + ((h >> 16) & 255) + (h >> 24)
    centred = (s.astype(jnp.int32) - 510).astype(jnp.float32)
    return (centred * jnp.float32(std / BELL_STD)).astype(dtype)
