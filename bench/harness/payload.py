"""The payload: float32 values made from the seed, one per element.

Element ``i`` holds ``value(seed, i)``, a 32-bit integer hash of the
seed and the index turned into a float in [1, 2).  The device builds
the whole archive in one jitted call; the reference recomputes any
element with numpy, so it needs nothing the program has made.
"""

from __future__ import annotations

import numpy as np

GOLDEN = 0x9E3779B1
M1, M2 = 0x85EBCA6B, 0xC2B2AE35
ONE_BITS = 0x3F800000


def seed_words(seed: int) -> np.ndarray:
    """The seed's low and high 32-bit words (seeds may pass 2**32)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seeds are whole numbers >= 0")
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def _mix(xp, idx, words):
    """murmur3's finaliser over (index, seed), in uint32 arithmetic that
    wraps alike in numpy and in XLA."""
    u = xp.uint32
    x = idx * u(GOLDEN) ^ words[0]
    for salt in (words[1], u(M2)):
        x = x ^ (x >> u(16))
        x = x * u(M1)
        x = x ^ (x >> u(13))
        x = x * u(M2)
        x = x ^ (x >> u(16))
        x = x ^ salt
    return (x >> u(9)) | u(ONE_BITS)


def reference_bits(seed: int, offsets: np.ndarray) -> np.ndarray:
    """uint32 bit patterns of the float32 values at ``offsets``."""
    idx = np.asarray(offsets).astype(np.uint32)
    with np.errstate(over="ignore"):
        return _mix(np, idx, seed_words(seed))


def reference_values(seed: int, offsets: np.ndarray) -> np.ndarray:
    return reference_bits(seed, offsets).view(np.float32)


def _build(words, n_elements: int):
    import jax
    import jax.numpy as jnp

    idx = jax.lax.iota(jnp.uint32, n_elements)
    return jax.lax.bitcast_convert_type(_mix(jnp, idx, words), jnp.float32)


def make_device_payload(seed: int, n_elements: int):
    """The whole archive as one float32 vector on JAX's default device,
    built there in one jitted call; the seed is an argument, so every
    seed runs the same compiled program."""
    import jax
    import jax.numpy as jnp

    if n_elements > 2 ** 31 - 1:
        raise ValueError(f"{n_elements} elements do not fit one int32 axis")
    build = jax.jit(_build, static_argnums=1)
    return build(jnp.asarray(seed_words(seed)),
                 n_elements).block_until_ready()
