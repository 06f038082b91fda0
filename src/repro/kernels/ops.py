"""Public jit'd entry points for the Pallas kernels.

Handles padding to hardware-aligned block shapes (MXU multiples of 128 in
the lane dim, 8 in the sublane dim — the TPU "fixed memory geometry" whose
mismatch with logical shapes is the paper's Eq. 1 inefficiency, paid here
once in padding rather than per-BRAM), backend selection (interpret mode on
CPU, compiled Mosaic on TPU), and batch-dim flattening.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import mvau as _mvau
from repro.kernels import packed_matmul as _pm
from repro.quant.quantizers import pack_bits


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pick_blocks(m: int, n: int, k: int, bits: int) -> tuple[int, int, int]:
    """Block shapes: MXU-aligned, working set bounded to ~2 MiB of VMEM."""
    per = 8 // bits
    bm = min(128, _round_up(m, 8))
    bn = min(128, _round_up(n, 128))
    bk = min(512, _round_up(k, max(256, per * 8)))
    return bm, bn, bk


def packed_matmul(
    x: jnp.ndarray,
    packed_w: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    bits: int,
    k: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched packed matmul; pads all dims to block multiples.

    x: (..., K); packed_w: (K*bits/8, N); scale: (N,). Returns (..., N) f32.
    """
    if interpret is None:
        interpret = _on_cpu()
    per = 8 // bits
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    n = packed_w.shape[1]
    x2 = x.reshape(m, k)
    bm, bn, bk = _pick_blocks(m, n, k, bits)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    # pad the carrier with the code for weight value 0 so padded K rows are
    # exact no-ops (binary has no 0 code; its pad contributes sign(0-pad of x)
    # * 0-activation = 0 because x is zero-padded along K as well).
    wp = jnp.pad(packed_w, ((0, (kp - k) // per), (0, np_ - n)))
    sp = jnp.pad(scale, (0, np_ - n))
    out = _pm.packed_matmul(
        x2, wp, sp, bits=bits, k=kp, bm=bm, bn=bn, bk=bk, interpret=interpret
    )
    return out[:m, :n].reshape(*lead, n)


def stream_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    scale: jnp.ndarray | None = None,
    *,
    bits: int = 0,
    k: int,
    stream_depth: int = 2,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched HBM-streaming matmul (``kernels.weight_stream``); pads to
    block multiples.

    x: (..., K); w: (K*bits/8, N) packed carrier or (K, N) dense (bits=0);
    scale: (N,) or None. Returns (..., N) f32. On the CPU backend the jnp
    reference runs instead of the kernel (interpret-mode DMA emulation is
    exercised by the kernel equivalence tests). Both multiply in f32, so
    they match the resident weight path exactly only for f32 models.
    """
    from repro.kernels import weight_stream as _ws
    from repro.kernels.ref import stream_matmul_ref

    if interpret is None:
        interpret = _on_cpu()
    per = 8 // bits if bits else 1
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    n = w.shape[1]
    x2 = x.reshape(m, k)
    if scale is None:
        scale = jnp.ones((n,), jnp.float32)
    if interpret:
        out = stream_matmul_ref(x2, w, scale, bits, k)
        return out[:m].reshape(*lead, n)
    bn = min(128, _round_up(n, 128))
    ck = min(512, _round_up(k, max(256, per * 8)))
    mp, np_, kp = _round_up(m, 8), _round_up(n, bn), _round_up(k, ck)
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    # K padding: packed carriers pad with code 0; x is zero-padded along K
    # so binary's missing 0 code is still an exact no-op (see packed_matmul)
    wp = jnp.pad(w, ((0, (kp - k) // per), (0, np_ - n)))
    sp = jnp.pad(scale, (0, np_ - n))
    out = _ws.stream_matmul(
        x2, wp, sp,
        bits=bits, k=kp, bn=bn, ck=ck, stream_depth=stream_depth,
        interpret=False,
    )
    return out[:m, :n].reshape(*lead, n)


def paged_decode_runs_kernel(hd: int, pool: jnp.ndarray) -> bool:
    """Whether the decode step's KV sub-layer runs ``paged_decode``'s
    kernel: on the TPU backend, for a block tile the kernel covers (else
    the reference formulation runs, gathering every lane's whole table;
    interpret-mode DMA emulation is exercised by the kernel equivalence
    tests)."""
    if _on_cpu():
        return False
    from repro.kernels import paged_attention as _pa

    return _pa.supports(hd, pool.shape[-2:])


def paged_decode(q, k_new, v_new, pool_k, pool_v, layer, block_table,
                 lengths, *, n_kv: int, window: int = 0):
    """The Pallas paged decode kernel (``kernels.paged_attention``): each
    lane's new K/V row written in place and its live blocks attended
    over. Returns (attention (B, 1, Hq, D), pool_k, pool_v)."""
    from repro.kernels import paged_attention as _pa

    return _pa.paged_decode(
        q, k_new, v_new, pool_k, pool_v, layer, block_table, lengths,
        n_kv=n_kv, window=window,
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
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused packed-matmul + thresholding; pads to block multiples."""
    if interpret is None:
        interpret = _on_cpu()
    per = 8 // bits
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    n = packed_w.shape[1]
    x2 = x.reshape(m, k)
    bm, bn, bk = _pick_blocks(m, n, k, bits)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(packed_w, ((0, (kp - k) // per), (0, np_ - n)))
    # padded channels get +inf thresholds (never crossed) and sign +1
    tp = jnp.pad(
        thresholds, ((0, np_ - n), (0, 0)), constant_values=jnp.inf
    )
    sg = jnp.pad(signs, (0, np_ - n), constant_values=1.0)
    out = _mvau.mvau(
        x2, wp, tp, sg,
        bits=bits, k=kp, offset=offset, bm=bm, bn=bn, bk=bk,
        interpret=interpret,
    )
    return out[:m, :n].reshape(*lead, n)


def pack_weights(w_values: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Float weight values (K, N) -> uint8 carrier (K*bits/8, N), padding K
    to a byte boundary. Inverse-decode convention matches ``ref.decode``."""
    per = 8 // bits
    k = w_values.shape[0]
    kp = _round_up(k, per)
    w = jnp.pad(w_values, ((0, kp - k),) + ((0, 0),) * (w_values.ndim - 1))
    if bits == 1:
        codes = (w > 0).astype(jnp.uint8)
    elif bits == 2:
        codes = (jnp.sign(w) + 1).astype(jnp.uint8)
    else:
        codes = (jnp.round(w) + 2 ** (bits - 1)).astype(jnp.uint8)
    return pack_bits(codes, bits)


# --------------------------------------------------------------------------
# Fused flash attention (kernels/flash_attention.py) with a custom VJP
# --------------------------------------------------------------------------


def _fa_pick(s: int, target: int) -> int:
    for d in range(min(target, s), 0, -1):
        if s % d == 0:
            return d
    return 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fa(q, k, v, causal, window, qb, kb, q_offset, interpret):
    out, _ = _fa_fwd(q, k, v, causal, window, qb, kb, q_offset, interpret)
    return out


def _fa_fwd(q, k, v, causal, window, qb, kb, q_offset, interpret):
    from repro.kernels import flash_attention as FK

    out, lse = FK.flash_fwd(
        q, k, v, causal=causal, window=window, qb=qb, kb=kb,
        q_offset=q_offset, interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, qb, kb, q_offset, interpret, res, do):
    from repro.kernels import flash_attention as FK

    q, k, v, out, lse = res
    dq, dk_g, dv_g = FK.flash_bwd(
        q, k, v, out, lse, do, causal=causal, window=window, qb=qb, kb=kb,
        q_offset=q_offset, interpret=interpret,
    )
    bh, sk, d = dk_g.shape
    bkv = k.shape[0]
    g = bh // bkv
    # sum per-q-head partials over each GQA group
    dk = jnp.sum(dk_g.reshape(bkv, g, sk, d), axis=1).astype(k.dtype)
    dv = jnp.sum(dv_g.reshape(bkv, g, sk, d), axis=1).astype(v.dtype)
    return dq, dk, dv


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 256,
    kv_block: int = 512,
    q_offset: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused Pallas flash attention. q: (B, Sq, Hq, D); k/v: (B, Sk,
    Hkv, D). Differentiable (FA2 backward kernels)."""
    if interpret is None:
        interpret = _on_cpu()
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    qb = _fa_pick(sq, q_block)
    kb = _fa_pick(sk, kv_block)
    # (B, S, H, D) -> (B*H, S, D); BH row order b*H + h matches the
    # kernel's GQA index map (bh // g).
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    of = _fa(qf, kf, vf, causal, window, qb, kb, q_offset, interpret)
    return of.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
