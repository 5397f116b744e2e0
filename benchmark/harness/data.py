"""Inputs made on the device from the seed, integer-valued so that sums,
products of powers of two and bitwise results are exact in any order of
reduction: the comparison with numpy can then ask for every bit."""
from __future__ import annotations

import zlib


def stable_hash(text: str) -> int:
    """A 31-bit hash that does not change between processes (``hash``
    does), to fold a point's name into the seed."""
    return zlib.crc32(text.encode()) & 0x7FFFFFFF


FINE16 = "fine16"           # a point's ``values``: see ``values`` below


def values(key, shape, dtype: str, op: str, named: str | None = None):
    """An array of ``shape`` and ``dtype`` drawn from ``key``.

    * a point that says ``"values": "fine16"`` (float32, SUM): whole
      multiples of 2**-12 from -8 to 8, 16 significant bits: exact in
      float32 and **not** in bfloat16, and a sum over up to 256 ranks
      needs 24 bits, so it is still exact in any order.  A path that
      rounds a gradient to bfloat16 on its way changes nearly every
      element, which whole numbers from -8 to 8 would let through;
    * op ``PROD``: plus or minus 1/2, 1 or 2, so a product of a few
      stays a power of two;
    * an integer dtype: any bit pattern (bitwise ops see every bit);
    * else: whole numbers from -8 to 8, exact in float32 and bfloat16.
    """
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    if named is not None:
        if named != FINE16 or dt != jnp.float32 or op != "SUM":
            raise ValueError(f"values {named!r} are {FINE16!r}, float32 "
                             f"and SUM, or absent; not {dtype} {op}")
        return jax.random.randint(key, shape, -(1 << 15), 1 << 15).astype(
            dt) * jnp.float32(2.0 ** -12)
    if op == "PROD":
        k_exp, k_sign = jax.random.split(key)
        exp = jax.random.randint(k_exp, shape, -1, 2)
        sign = 1 - 2 * jax.random.randint(k_sign, shape, 0, 2)
        return (sign * jnp.exp2(exp.astype(jnp.float32))).astype(dt)
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        return jax.random.randint(key, shape, info.min, info.max, dt)
    return jax.random.randint(key, shape, -8, 9).astype(dt)
