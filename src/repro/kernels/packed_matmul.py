"""Pallas TPU kernel: matmul with bit-packed weights, unpacked in VMEM.

This is the TPU adaptation of the paper's packed weight memories (DESIGN.md
§3): weights live in HBM as a dense uint8 carrier holding 8/``bits`` weights
per byte (the "optimally filled BRAM"), are staged into VMEM by the Pallas
grid pipeline (the GALS weight streamer), and are unpacked with VPU shift/
mask ops just before hitting the MXU. The HBM roofline term for weights
drops by 16x (bf16 -> 1 bit) / 8x (2 bit); the compensation cost is VPU
unpack work, not MXU cycles — the same surplus-resource trade the paper
makes with the memory-clock surplus (R_F).

Layout: ``x`` (M, K) activations; ``packed_w`` (K*bits/8, N) uint8 carrier
packed along the reduction dim (see ``quant.quantizers.pack_bits``);
``scale`` (N,) per-output-channel dequant scale. Out: (M, N) f32.

Grid: (M/bm, N/bn, K/bk), k innermost ("arbitrary"), accumulating into the
output block, which Pallas keeps VMEM-resident across the k sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_block(w_packed, bits: int, bk: int, bn: int):
    """uint8 (bk*bits/8, bn) -> f32 (bk, bn) weight values, in-register.

    Weight k = i*per + j sits in carrier row i at bit-offset j*bits
    (matches ``pack_bits``). The unpack is per*2 VPU ops per carrier
    element — cheap relative to the 2*bk*bn MXU flops it feeds.
    """
    per = 8 // bits
    # Mosaic has no uint8 -> f32 cast; widen to int32 first (uint8 -> int32
    # and int32 -> f32 both lower), then shift and mask in 32-bit lanes.
    wide = w_packed.astype(jnp.int32)
    mask = 2**bits - 1
    planes = [
        ((wide >> (j * bits)) & mask).astype(jnp.float32) for j in range(per)
    ]
    # (bk/per, per, bn) -> (bk, bn): row-major interleave of the planes.
    codes = jnp.stack(planes, axis=1).reshape(bk, bn)
    if bits == 1:
        return codes * 2.0 - 1.0  # {0,1} -> {-1,+1}
    if bits == 2:
        return codes - 1.0  # {0,1,2} -> {-1,0,+1}
    return codes - float(2 ** (bits - 1))


def _packed_matmul_kernel(x_ref, w_ref, s_ref, o_ref, *, bits, bk, bn, nk):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = _decode_block(w_ref[...], bits, bk, bn)
    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )

    @pl.when(k_idx == nk - 1)
    def _scale():
        o_ref[...] *= s_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bits", "k", "bm", "bn", "bk", "interpret")
)
def packed_matmul(
    x: jnp.ndarray,
    packed_w: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    bits: int,
    k: int,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """out[m, n] = sum_k x[m, k] * decode(packed_w)[k, n] * scale[n].

    Shapes must be pre-padded: M % bm == 0, N % bn == 0, K % bk == 0,
    and bk % (8/bits) == 0 (use ``ops.packed_matmul`` for auto-padding).
    """
    m, kk = x.shape
    assert kk == k, (kk, k)
    per = 8 // bits
    n = packed_w.shape[1]
    assert packed_w.shape[0] == k // per, (packed_w.shape, k, per)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % per == 0
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    kernel = functools.partial(
        _packed_matmul_kernel, bits=bits, bk=bk, bn=bn, nk=nk
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bk // per, bn), lambda i, j, kb: (kb, j)),
            pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, packed_w, scale.reshape(1, n))
