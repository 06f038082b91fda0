"""Weights from the seed: what every family module's ``make_weights``
shares, whatever the family.

A family draws its tree in one jitted call of ``(key, hi)``, the two
halves of the seed that ``seed_key`` gives: a seed may exceed 32 bits.

Packed FFN weights are ternary codes {0, 1, 2} meaning {-1, 0, +1},
four to a byte along the reduction axis, the lowest bits first:
``carrier[k // 4, n] = sum_j code[4 * (k // 4) + j, n] << 2 * j``.
That is the program's carrier layout; a family's reference decodes it
with its own code, so nothing the program computes reaches the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> tuple[jax.Array, jax.Array]:
    """(key, hi): the key of the seed's low 32 bits and its high bits, to
    be folded in inside the jitted draw."""
    return jax.random.key(seed % 2**32), jnp.uint32(seed // 2**32)


def pack_codes(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(..., K, N) integer codes -> (..., K * bits / 8, N) uint8 carrier."""
    per = 8 // bits
    *lead, k, n = codes.shape
    c = codes.astype(jnp.uint8).reshape(*lead, k // per, per, n)
    shifts = (jnp.arange(per, dtype=jnp.uint8) * bits)[:, None]
    return jnp.sum(c << shifts, axis=-2, dtype=jnp.uint8)


def ternary(key, shape, bits: int, scale: float) -> dict:
    """A packed matrix of ``shape``: ternary codes in a uint8 carrier and
    float32 per-column scales about ``scale``."""
    if bits != 2:
        raise ValueError(f"only 2-bit ternary FFN weights are made, not {bits}")
    kc, ks = jax.random.split(key)
    # P(-1) = P(+1) = 0.3, P(0) = 0.4
    codes = jax.random.choice(
        kc, jnp.arange(3, dtype=jnp.uint8), shape, p=jnp.array([0.3, 0.4, 0.3])
    )
    *lead, _, n = shape
    s = scale * (1.0 + 0.25 * jax.random.uniform(ks, (*lead, n)))
    return {"packed": pack_codes(codes, bits), "scale": s.astype(jnp.float32)}
