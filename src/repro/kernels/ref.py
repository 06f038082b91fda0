"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package has a reference implementation here; the test
suite sweeps shapes/dtypes and asserts ``assert_allclose(kernel, ref)``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.quant.quantizers import unpack_bits


def decode_weights(packed: jnp.ndarray, bits: int, k: int) -> jnp.ndarray:
    """uint8 carrier -> float weight values.

    bits=1: codes {0,1} -> {-1,+1};  bits=2: codes {0,1,2} -> {-1,0,+1};
    bits=4/8: signed two's-complement-style codes centred at 2^(bits-1).
    """
    codes = unpack_bits(packed, bits, k).astype(jnp.float32)
    if bits == 1:
        return codes * 2.0 - 1.0
    if bits == 2:
        return codes - 1.0
    return codes - float(2 ** (bits - 1))


def packed_matmul_ref(
    x: jnp.ndarray, packed_w: jnp.ndarray, scale: jnp.ndarray, bits: int, k: int
) -> jnp.ndarray:
    """Oracle for ``packed_matmul``: unpack then dense f32 matmul.

    x: (M, K); packed_w: (K*bits/8, N) uint8; scale: (N,) per-channel.
    """
    w = decode_weights(packed_w, bits, k)
    out = jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)
    return out * scale[None, :]


def stream_matmul_ref(
    x: jnp.ndarray,
    w: jnp.ndarray,
    scale: jnp.ndarray,
    bits: int,
    k: int,
) -> jnp.ndarray:
    """Oracle for ``weight_stream.stream_matmul``.

    The streaming kernel's math is chunked accumulation of the same
    product; the oracle materialises the decoded weight once and does a
    single f32 matmul — identical math to the resident (non-streamed)
    ``lm.packed_dense`` / ``layers.dense`` paths, which is what makes the
    budgeted and unbudgeted serve paths token-identical on CPU.

    x: (M, K); w: (K*bits/8, N) uint8 carrier, or (K, N) dense if bits=0;
    scale: (N,).
    """
    if bits == 0:
        vals = w.astype(jnp.float32)
    else:
        vals = decode_weights(w, bits, k)
    out = jnp.dot(
        x.astype(jnp.float32), vals, preferred_element_type=jnp.float32
    )
    return out * scale[None, :]


def mvau_ref(
    x: jnp.ndarray,
    packed_w: jnp.ndarray,
    thresholds: jnp.ndarray,
    signs: jnp.ndarray,
    offset: int,
    bits: int,
    k: int,
) -> jnp.ndarray:
    """Oracle for the fused MVAU: packed matmul -> integer thresholding.

    thresholds: (N, L) ascending per output channel; signs: (N,) in {-1,+1}.
    Returns int32 activation levels (paper §III-B streamlined datapath).
    """
    w = decode_weights(packed_w, bits, k)
    acc = jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)
    acc = acc * signs[None, :]
    levels = jnp.sum(
        (acc[..., None] >= thresholds[None, :, :]).astype(jnp.int32), axis=-1
    )
    return levels + offset


def flash_attention_ref(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Dense-softmax oracle for the flash-attention kernels.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D). Returns (B, Sq, Hq, D).
    """
    import jax
    import numpy as np

    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
        k.astype(jnp.float32),
    ) / np.sqrt(d)
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        m &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(m[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, d).astype(q.dtype)


def paged_decode_ref(
    q, k_new, v_new, pool_k, pool_v, layer, block_table, lengths, *,
    n_kv: int, window: int = 0,
):
    """Oracle for ``paged_decode``: the new rows written through
    ``attention.write_tokens``, then every lane's whole block table
    gathered (``attention.paged_decode_attention_ref``)."""
    from repro.models.attention import paged_decode_attention_ref, write_tokens

    pool_k = write_tokens(pool_k, layer, block_table, lengths, k_new[:, None])
    pool_v = write_tokens(pool_v, layer, block_table, lengths, v_new[:, None])
    o = paged_decode_attention_ref(
        q, pool_k, pool_v, layer, block_table, lengths + 1, n_kv=n_kv,
        window=window,
    )
    return o, pool_k, pool_v
