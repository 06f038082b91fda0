"""Pallas TPU kernels: fused flash attention (forward + backward).

The TPU execution path for the attention hot-spot (EXPERIMENTS.md §Perf
iteration 4). The jnp FA2 path (``models.flash``) is what the 512-device
dry-run lowers — XLA materialises every (g*qb, kb) score/probability tile
at fusion boundaries, ~81% of the smollm train-cell HBM traffic. In this
kernel those tiles live in VMEM scratch and never touch HBM: per-step HBM
traffic is q/k/v reads + out writes only.

Layouts: heads are flattened into the leading grid dim. q: (BH, Sq, D)
with BH = B*Hq; k/v: (BKV, Sk, D) with BKV = B*Hkv; GQA maps q-head
bh -> kv row (bh // Hq) * Hkv + (bh % Hq) // G in the BlockSpec index
maps — no materialised KV replication.

Grid: (BH, nq, nk), nk innermost ("arbitrary") so the online-softmax
scratch (m, l, acc) persists across the KV sweep. Causal / sliding-window
blocks that are fully masked are skipped with ``pl.when`` (they still pay
a grid step, but no MXU work or VMEM writes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, qb, kb, nk, causal, window, q_offset,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = q_offset + qi * qb
    k_lo = kj * kb
    # visibility of this (qi, kj) block pair
    visible = True
    if causal:
        visible = jnp.asarray(k_lo <= q_lo + qb - 1)
    if window > 0:
        visible = jnp.logical_and(
            visible, jnp.asarray(k_lo + kb - 1 > q_lo - window)
        )

    @pl.when(visible)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (qb, kb)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        ok = jnp.ones((qb, kb), jnp.bool_)
        if causal:
            ok &= q_pos >= k_pos
        if window > 0:
            ok &= q_pos - k_pos < window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv

    @pl.when(kj == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[...] + jnp.log(l))[:, 0]


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, qb, kb, nk, causal, window, q_offset,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_lo = q_offset + qi * qb
    k_lo = kj * kb
    visible = True
    if causal:
        visible = jnp.asarray(k_lo <= q_lo + qb - 1)
    if window > 0:
        visible = jnp.logical_and(
            visible, jnp.asarray(k_lo + kb - 1 > q_lo - window)
        )

    @pl.when(visible)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        ok = jnp.ones((qb, kb), jnp.bool_)
        if causal:
            ok &= q_pos >= k_pos
        if window > 0:
            ok &= q_pos - k_pos < window
        p = jnp.where(ok, jnp.exp(s - lse_ref[0][:, None]), 0.0)
        dov = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dov - delta_ref[0][:, None]) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, qb, kb, nq, causal, window, q_offset,
):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_lo = q_offset + qi * qb
    k_lo = kj * kb
    visible = True
    if causal:
        visible = jnp.asarray(k_lo <= q_lo + qb - 1)
    if window > 0:
        visible = jnp.logical_and(
            visible, jnp.asarray(k_lo + kb - 1 > q_lo - window)
        )

    @pl.when(visible)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        ok = jnp.ones((qb, kb), jnp.bool_)
        if causal:
            ok &= q_pos >= k_pos
        if window > 0:
            ok &= q_pos - k_pos < window
        p = jnp.where(ok, jnp.exp(s - lse_ref[0][:, None]), 0.0)
        dov = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dov - delta_ref[0][:, None]) * scale
        # dv += p^T do ; dk += ds^T q
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dims(q, k, qb, kb):
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    g = bh // bkv
    return bh, bkv, g, sq, sk, d


def flash_fwd(
    q, k, v, *, causal=True, window=0, qb=256, kb=512, q_offset=0,
    interpret=False,
):
    """q: (BH, Sq, D); k/v: (BKV, Sk, D); BH % BKV == 0 (GQA).

    Returns (out (BH, Sq, D), lse (BH, Sq) f32).
    """
    bh, bkv, g, sq, sk, d = _dims(q, k, qb, kb)
    nq, nk = sq // qb, sk // kb
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, qb=qb, kb=kb, nk=nk,
        causal=causal, window=window, q_offset=q_offset,
    )
    kv_row = lambda bhi: (bhi // g, )  # BKV row for a BH row
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qb, d), lambda bhi, qi, kj: (bhi, qi, 0)),
            pl.BlockSpec((1, kb, d), lambda bhi, qi, kj: (bhi // g, kj, 0)),
            pl.BlockSpec((1, kb, d), lambda bhi, qi, kj: (bhi // g, kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, qb, d), lambda bhi, qi, kj: (bhi, qi, 0)),
            pl.BlockSpec((1, qb), lambda bhi, qi, kj: (bhi, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)


def flash_bwd(
    q, k, v, out, lse, do, *, causal=True, window=0, qb=256, kb=512,
    q_offset=0, interpret=False,
):
    """Returns (dq (BH,Sq,D), dk_g (BH,Sk,D), dv_g (BH,Sk,D)).

    dk_g/dv_g are per-q-head partials; sum groups of G rows to get the
    kv-head gradients (done in ``ops.flash_attention``'s VJP).
    """
    bh, bkv, g, sq, sk, d = _dims(q, k, qb, kb)
    nq, nk = sq // qb, sk // kb
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (BH, Sq)

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, qb=qb, kb=kb, nk=nk,
        causal=causal, window=window, q_offset=q_offset,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qb, d), lambda bhi, qi, kj: (bhi, qi, 0)),
            pl.BlockSpec((1, kb, d), lambda bhi, qi, kj: (bhi // g, kj, 0)),
            pl.BlockSpec((1, kb, d), lambda bhi, qi, kj: (bhi // g, kj, 0)),
            pl.BlockSpec((1, qb, d), lambda bhi, qi, kj: (bhi, qi, 0)),
            pl.BlockSpec((1, qb), lambda bhi, qi, kj: (bhi, qi)),
            pl.BlockSpec((1, qb), lambda bhi, qi, kj: (bhi, qi)),
        ],
        out_specs=pl.BlockSpec((1, qb, d), lambda bhi, qi, kj: (bhi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((qb, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, qb=qb, kb=kb, nq=nq,
        causal=causal, window=window, q_offset=q_offset,
    )
    dk_g, dv_g = pl.pallas_call(
        dkv_kernel,
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, qb, d), lambda bhi, kj, qi: (bhi, qi, 0)),
            pl.BlockSpec((1, kb, d), lambda bhi, kj, qi: (bhi // g, kj, 0)),
            pl.BlockSpec((1, kb, d), lambda bhi, kj, qi: (bhi // g, kj, 0)),
            pl.BlockSpec((1, qb, d), lambda bhi, kj, qi: (bhi, qi, 0)),
            pl.BlockSpec((1, qb), lambda bhi, kj, qi: (bhi, qi)),
            pl.BlockSpec((1, qb), lambda bhi, kj, qi: (bhi, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, kb, d), lambda bhi, kj, qi: (bhi, kj, 0)),
            pl.BlockSpec((1, kb, d), lambda bhi, kj, qi: (bhi, kj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kb, d), jnp.float32),
            pltpu.VMEM((kb, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk_g, dv_g
