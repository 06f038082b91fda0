"""Random weights from the seed, made by the benchmark in one jitted call.

The tree has the layout the serving program consumes (stacked per-layer
leaves, matrices in the served dtype, norm gains in float32, and for
packed FFNs a uint8 carrier plus float32 per-column scales). The same
function feeds the plain reference, which decodes the carriers with its
own code: nothing the program computes reaches the reference.

Packed FFN weights are ternary codes {0, 1, 2} meaning {-1, 0, +1},
four to a byte along the reduction axis, the lowest bits first:
``carrier[k // 4, n] = sum_j code[4 * (k // 4) + j, n] << 2 * j``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.core.config import ModelSizes


def pack_codes(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(..., K, N) integer codes -> (..., K * bits / 8, N) uint8 carrier."""
    per = 8 // bits
    *lead, k, n = codes.shape
    c = codes.astype(jnp.uint8).reshape(*lead, k // per, per, n)
    shifts = (jnp.arange(per, dtype=jnp.uint8) * bits)[:, None]
    return jnp.sum(c << shifts, axis=-2, dtype=jnp.uint8)


def _packed(key, shape, bits: int, scale: float) -> dict:
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


def make_weights(m: ModelSizes, seed: int, padded_vocab: int) -> dict:
    """The model's weights on the device, from ``seed``."""
    return _make(m, padded_vocab)(
        jax.random.key(seed % 2**32), jnp.uint32(seed // 2**32)
    )


@functools.lru_cache(maxsize=None)
def _make(m: ModelSizes, padded_vocab: int):
    dt = jnp.dtype(m.dtype)
    d, ff, n_l = m.hidden, m.intermediate, m.layers
    q_w, kv_w = m.heads * m.head_dim, m.kv_heads * m.head_dim

    def gain(key, shape):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    def mat(key, shape, fan_in, mult=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                * (mult * fan_in**-0.5)).astype(dt)

    @jax.jit
    def make(key, hi):
        key = jax.random.fold_in(key, hi)
        k = iter(jax.random.split(key, 12))
        layers = {
            "ln1": gain(next(k), (n_l, d)),
            "ln2": gain(next(k), (n_l, d)),
            "wq": mat(next(k), (n_l, d, q_w), d),
            "wk": mat(next(k), (n_l, d, kv_w), d),
            "wv": mat(next(k), (n_l, d, kv_w), d),
            "wo": mat(next(k), (n_l, q_w, d), q_w),
        }
        shapes = {"w1": ((n_l, d, ff), d, 1.0), "w3": ((n_l, d, ff), d, 1.0),
                  "w2": ((n_l, ff, d), ff, 0.5)}
        for name, (shape, fan_in, mult) in shapes.items():
            kk = next(k)
            if m.ffn_bits:
                # ternary values: 60% nonzero, so the scale that keeps the
                # output variance of a dense weight of the same fan-in
                layers[name] = _packed(
                    kk, shape, m.ffn_bits, mult * (0.6 * fan_in) ** -0.5
                )
            else:
                layers[name] = mat(kk, shape, fan_in, mult)
        params = {
            # small enough that the tied unembedding does not simply echo
            # the input token: the next token depends on the context
            "embed": (jax.random.normal(next(k), (padded_vocab, d))
                      * 0.02).astype(dt),
            "final_norm": gain(next(k), (d,)),
            "layers": layers,
        }
        if not m.tied:
            params["unembed"] = mat(next(k), (padded_vocab, d), d)
        return params

    return make
