"""Pallas TPU kernel: fused MVAU — packed matmul + integer thresholding.

The FINN Matrix-Vector-Activation Unit is the paper's unit of dataflow
compute: matrix-vector product on packed low-bit weights followed by the
streamlined BN+activation as multi-threshold comparison (paper §III-B,
Fig. 6). Fusing the thresholding into the matmul epilogue means the f32
accumulator never leaves VMEM — only the A-bit activation levels are
written back, shrinking the activation-write roofline term by 8-16x
exactly as the streamlined FPGA datapath carries A-bit streams.

Thresholds (N, L) and channel signs (N,) arrive as a second packed memory,
mirroring the paper's threshold memories co-packed with weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_matmul import _decode_block


def _mvau_kernel(
    x_ref, w_ref, t_ref, sg_ref, o_ref, acc_ref, *, bits, bk, bn, nk
):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode_block(w_ref[...], bits, bk, bn)
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )

    @pl.when(k_idx == nk - 1)
    def _threshold():
        acc = acc_ref[...] * sg_ref[...]  # (bm, bn) sign-canonicalised
        t = t_ref[...]  # (bn, L) ascending thresholds
        levels = jnp.sum(
            (acc[:, :, None] >= t[None, :, :]).astype(jnp.int32), axis=-1
        )
        o_ref[...] = levels


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "offset", "bm", "bn", "bk", "interpret"),
)
def mvau(
    x: jnp.ndarray,
    packed_w: jnp.ndarray,
    thresholds: jnp.ndarray,
    signs: jnp.ndarray,
    *,
    bits: int,
    k: int,
    offset: int = 0,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Streamlined MVAU: int32 levels = offset + #{l : sign*acc >= T_l}."""
    m, kk = x.shape
    assert kk == k
    per = 8 // bits
    n = packed_w.shape[1]
    n_lvl = thresholds.shape[1]
    assert thresholds.shape[0] == n and signs.shape == (n,)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % per == 0
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    kernel = functools.partial(_mvau_kernel, bits=bits, bk=bk, bn=bn, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bk // per, bn), lambda i, j, kb: (kb, j)),
            pl.BlockSpec((bn, n_lvl), lambda i, j, kb: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, packed_w, thresholds, signs.reshape(1, n))
    return out + offset
