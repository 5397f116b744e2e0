"""Inputs made on the device from the seed, integer-valued so that sums,
products of powers of two and bitwise results are exact in any order of
reduction: the comparison with numpy can then ask for every bit."""
from __future__ import annotations

import zlib


def stable_hash(text: str) -> int:
    """A 31-bit hash that does not change between processes (``hash``
    does), to fold a point's name into the seed."""
    return zlib.crc32(text.encode()) & 0x7FFFFFFF


def values(key, shape, dtype: str, op: str):
    """An array of ``shape`` and ``dtype`` drawn from ``key``.

    * op ``PROD``: plus or minus 1/2, 1 or 2, so a product of a few
      stays a power of two;
    * an integer dtype: any bit pattern (bitwise ops see every bit);
    * else: whole numbers from -8 to 8, exact in float32 and bfloat16.
    """
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    if op == "PROD":
        k_exp, k_sign = jax.random.split(key)
        exp = jax.random.randint(k_exp, shape, -1, 2)
        sign = 1 - 2 * jax.random.randint(k_sign, shape, 0, 2)
        return (sign * jnp.exp2(exp.astype(jnp.float32))).astype(dt)
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        return jax.random.randint(key, shape, info.min, info.max, dt)
    return jax.random.randint(key, shape, -8, 9).astype(dt)
