"""Pallas TPU kernel: matmul streaming weights HBM -> VMEM through a ring.

This is the executor half of the FCMP port story (paper §IV-§V): a weight
block that the residency plan does *not* pin in VMEM stays in HBM and is
pulled through a ``stream_depth``-slot VMEM ring by manual async DMA, one
K-chunk ahead of the MXU per slot — the GALS weight streamer, with the
stream-ahead depth playing the role of the memory-clock ratio ``R_F``:
bit-packing leaves an HBM-bandwidth surplus (1/2-bit weights move 8-16x
fewer bytes than bf16), and that surplus is what lets the ring run deep
enough to hide HBM latency, exactly as the paper's frequency surplus lets
one BRAM port serve ``H_B`` logical buffers.

Unlike ``packed_matmul`` (whose weights ride the automatic grid pipeline,
i.e. are assumed VMEM-schedulable), the weight operand here is declared in
``pl.ANY``/HBM memory space and never materialises in VMEM beyond
``stream_depth`` chunks — the kernel's VMEM footprint is the *budget* the
residency plan reserved for streaming, independent of the weight size.

Layout: ``x`` (M, K) activations (VMEM-resident — decode batches are
small); ``w`` (Kc, N) weights in HBM, either a packed uint8 carrier
(``bits`` in {1, 2}, Kc = K*bits/8, see ``quant.quantizers.pack_bits``)
or dense float rows (``bits=0``, Kc = K); ``scale`` (N,) per-channel
dequant scale (ones for dense). Out: (M, N) f32.

Grid: (N/bn,) — one output column block per program; the K sweep is the
in-kernel DMA ring. The interpret path (tier-1 CPU) emulates the DMAs;
``ref.stream_matmul_ref`` is the numerical oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_matmul import _decode_block


def _decode_chunk(w_chunk, bits: int, ck: int, bn: int):
    """Carrier chunk -> f32 (ck, bn) weight values, in-register.

    bits=0: dense rows, cast only. bits in {1,2}: the ``pack_bits``
    row-major interleave, decoded by ``packed_matmul._decode_block``.
    """
    if bits == 0:
        return w_chunk.astype(jnp.float32)
    return _decode_block(w_chunk, bits, ck, bn)


def _stream_kernel(
    x_ref, w_ref, s_ref, o_ref, *, bits, m, ck, bn, nk, depth
):
    j = pl.program_id(0)
    per = 8 // bits if bits else 1
    ckc = ck // per  # carrier rows per K chunk

    def body(scratch, sem):
        def chunk_dma(slot, i):
            return pltpu.make_async_copy(
                w_ref.at[pl.ds(i * ckc, ckc), pl.ds(j * bn, bn)],
                scratch.at[slot],
                sem.at[slot],
            )

        # warm-up: fill the ring stream_depth chunks ahead
        for i in range(min(depth, nk)):
            chunk_dma(i, i).start()

        def k_step(i, acc):
            slot = i % depth
            chunk_dma(slot, i).wait()
            w = _decode_chunk(scratch[slot], bits, ck, bn)
            acc = acc + jnp.dot(
                x_ref[:, pl.ds(i * ck, ck)].astype(jnp.float32),
                w,
                preferred_element_type=jnp.float32,
            )

            # the consumed slot immediately prefetches chunk i + depth
            @pl.when(i + depth < nk)
            def _():
                chunk_dma(slot, i + depth).start()

            return acc

        acc = jax.lax.fori_loop(
            0, nk, k_step, jnp.zeros((m, bn), jnp.float32)
        )
        o_ref[...] = acc * s_ref[...]

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((depth, ckc, bn), w_ref.dtype),
        sem=pltpu.SemaphoreType.DMA((depth,)),
    )


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "bn", "ck", "stream_depth", "interpret"),
)
def stream_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    bits: int,
    k: int,
    bn: int = 128,
    ck: int = 256,
    stream_depth: int = 2,
    interpret: bool = False,
) -> jnp.ndarray:
    """out[m, n] = sum_k x[m, k] * decode(w)[k, n] * scale[n], w streamed.

    Shapes must be pre-padded: N % bn == 0, K % ck == 0, and
    ck % (8/bits) == 0 for packed weights (``ops.stream_matmul`` pads).
    ``stream_depth`` >= 2 is the DMA ring depth (R_F analogue).
    """
    m, kk = x.shape
    assert kk == k, (kk, k)
    per = 8 // bits if bits else 1
    n = w.shape[1]
    assert w.shape[0] == (k // per if bits else k), (w.shape, k, per)
    assert n % bn == 0 and k % ck == 0 and ck % per == 0
    assert stream_depth >= 2, "need a ring of >= 2 slots to overlap DMA"
    nk = k // ck
    kernel = functools.partial(
        _stream_kernel,
        bits=bits, m=m, ck=ck, bn=bn, nk=nk, depth=stream_depth,
    )
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),  # x fully VMEM-resident
            pl.BlockSpec(memory_space=pl.ANY),  # w stays in HBM
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, w, scale.reshape(1, n))
