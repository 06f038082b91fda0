"""Attention: blockwise online-softmax ("flash") scan + decode paths.

``flash_attention`` is the lowering-friendly pure-jnp path used everywhere
(training, prefill, dry-run): a nested ``lax.scan`` over query blocks
(outer) and KV blocks (inner) keeps the live score tile at
(q_block x kv_block) regardless of sequence length — this is what makes the
32k-prefill and 4k-train cells compile within HBM. GQA is handled by
grouping query heads over each KV head. Sliding-window masking supports the
h2o-danube cells.

Decode paths attend one query token against a (possibly sequence-sharded)
KV cache with a dense masked softmax — at decode the score tensor is
(B, H, S) which is small and shards over ('data', 'model', ...).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


NEG_INF = -1e30


def _pick_block(s: int, target: int) -> int:
    """Largest divisor of ``s`` that is <= target (handles non-power-of-2
    sequence lengths like whisper's 1500 frames or vlm's 32768+256)."""
    for d in range(min(target, s), 0, -1):
        if s % d == 0:
            return d
    return 1


def _mask(
    q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool, window: int
) -> jnp.ndarray:
    """(Q, K) boolean validity mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    kv_block: int = 1024,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Flash attention; implementation selected by ``set_attn_impl``.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D); Hq % Hkv == 0. Returns
    (B, Sq, Hq, D).
      * 'fa2' (default): custom-VJP FlashAttention-2 with static causal
        block skipping (``models.flash``, EXPERIMENTS.md §Perf iter. 1+).
      * 'scan': the original scan-of-scans online softmax below — the
        paper-faithful §Perf BASELINE and the numerical reference.
    """
    if _ATTN_IMPL["name"] == "scan":
        return flash_attention_scan(
            q, k, v, causal=causal, window=window, q_block=q_block,
            kv_block=kv_block, q_offset=q_offset,
        )
    from repro.models.flash import flash_attention as _fa2

    return _fa2(
        q, k, v, causal=causal, window=window, q_block=q_block,
        kv_block=kv_block, q_offset=q_offset,
    )


_ATTN_IMPL = {"name": "fa2"}


def set_attn_impl(name: str) -> None:
    """'fa2' | 'scan' — switch the attention path (A/B in the dry-run)."""
    assert name in ("fa2", "scan"), name
    _ATTN_IMPL["name"] = name


def flash_attention_scan(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    kv_block: int = 1024,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Naive scan-of-scans online softmax (reference; §Perf baseline)."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qb = _pick_block(sq, q_block)
    kb = _pick_block(sk, kv_block)
    nq, nk = sq // qb, sk // kb

    # (B, Sq, Hkv, G, D) -> blocks (nq, B, qb, Hkv, G, D)
    qg = q.reshape(b, nq, qb, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    kg = k.reshape(b, nk, kb, hkv, d).transpose(1, 0, 2, 3, 4)
    vg = v.reshape(b, nk, kb, hkv, d).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_blk):
        qi, q_blk = qi_blk
        q_pos = q_offset + qi * qb + jnp.arange(qb)

        def kv_step(carry, kj_blk):
            m_run, l_run, acc = carry
            kj, k_blk, v_blk = kj_blk
            k_pos = kj * kb + jnp.arange(kb)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", q_blk, k_blk, preferred_element_type=jnp.float32
            ) * scale
            valid = _mask(q_pos, k_pos, causal, window)
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            # fully-masked blocks (possible with sliding windows) would give
            # exp(NEG_INF - NEG_INF) = 1: zero them explicitly.
            p = jnp.where(valid[None, None, None], p, 0.0)
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, hkv, g, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qb), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, qb, d), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kg, vg)
        )
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        # (B, Hkv, G, qb, D) -> (B, qb, Hkv, G, D)
        return None, out.transpose(0, 3, 1, 2, 4)

    _, blocks = jax.lax.scan(q_step, None, (jnp.arange(nq), qg))
    # (nq, B, qb, Hkv, G, D) -> (B, Sq, Hq, D)
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hq, d)
    return out.astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,
    *,
    window: int = 0,
) -> jnp.ndarray:
    """One-token attention against a cache.

    q: (B, 1, Hq, D); caches: (B, S, Hkv, D); cache_len: () current length
    (the new token's position is cache_len - 1 after insertion).
    """
    b, _, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, g, d)
    scores = jnp.einsum(
        "bhgd,bshd->bhgs", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    pos = jnp.arange(s)
    valid = pos[None] < cache_len
    if window > 0:
        valid &= pos[None] > cache_len - 1 - window
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def chunk_attention(
    q: jnp.ndarray,
    k_rows: jnp.ndarray,
    v_rows: jnp.ndarray,
    q_pos: jnp.ndarray,
    *,
    window: int = 0,
) -> jnp.ndarray:
    """Multi-token causal attention against gathered pool rows.

    ``decode_attention`` generalised to a C-token query chunk (the chunked
    prefill path): q: (B, C, Hq, D); k_rows/v_rows: (B, S, Hkv, D) rows
    gathered from the KV pool in logical order (row i holds position i);
    q_pos: (B, C) absolute positions of the chunk tokens. Rows beyond the
    chunk (scratch padding included) are masked by causality; ``q_pos``
    may be traced, so one trace serves every chunk offset.
    """
    b, c, hq, d = q.shape
    _, s, hkv, _ = k_rows.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, c, hkv, g, d)
    scores = jnp.einsum(
        "bqhgd,bshd->bhgqs", qg, k_rows, preferred_element_type=jnp.float32
    ) * scale
    k_pos = jnp.arange(s)
    valid = q_pos[:, :, None] >= k_pos[None, None, :]  # (B, C, S)
    if window > 0:
        valid &= q_pos[:, :, None] - k_pos[None, None, :] < window
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgqs,bshd->bqhgd", p.astype(v_rows.dtype), v_rows,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, c, hq, d).astype(q.dtype)


# --------------------------------------------------------------------------
# The paged KV pool's block-contiguous layout
# --------------------------------------------------------------------------

SCRATCH_BLOCK = 0  # pool block 0 is never allocated: unused table entries


def pool_tile(n_kv: int, block_tokens: int, hd: int) -> tuple[int, int]:
    """(rows, width) of one pool block. A block's ``block_tokens`` tokens x
    ``n_kv`` heads x ``hd`` values are stored head-major (head, token,
    dim); each head's values fill rows of 128 lanes, the TPU's vector
    width, when they divide into them (16 x 64 -> 8 rows), else one row
    of their own."""
    per_head = block_tokens * hd
    width = 128 if per_head % 128 == 0 else per_head
    return n_kv * per_head // width, width


def tiles_to_tokens(tiles: jnp.ndarray, n_kv: int, hd: int) -> jnp.ndarray:
    """(..., nb, rows, width) blocks -> (..., nb * T, n_kv, hd) rows in
    position order."""
    *lead, nb, r, w = tiles.shape
    t = r * w // (n_kv * hd)
    x = tiles.reshape(*lead, nb, n_kv, t, hd)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, nb * t, n_kv, hd)


def tokens_to_tiles(
    rows: jnp.ndarray, block_tokens: int, tile: tuple[int, int]
) -> jnp.ndarray:
    """Inverse of ``tiles_to_tokens``: (..., S, n_kv, hd) with S a multiple
    of ``block_tokens`` -> (..., S / block_tokens, *tile)."""
    *lead, s, n_kv, hd = rows.shape
    x = rows.reshape(*lead, s // block_tokens, block_tokens, n_kv, hd)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, s // block_tokens, *tile)


def live_block_span(kv_len, block_tokens: int, window: int = 0):
    """(first, end): the block-table entries ``[first, end)`` holding a
    position that a query at ``kv_len - 1`` attends to, for numpy or
    traced ``kv_len`` (the decode kernel visits these and no others)."""
    end = (kv_len + block_tokens - 1) // block_tokens
    if not window:
        return end * 0, end
    return (kv_len - window).clip(0) // block_tokens, end


def decode_window(cfg) -> int:
    """The window a paged decode query attends within: the config's
    sliding window, but none in the hybrid family, whose shared attention
    block attends over every position."""
    return 0 if cfg.family == "hybrid" else cfg.sliding_window


def gather_tokens(
    pool: jnp.ndarray, layer, block_table: jnp.ndarray, n_kv: int, hd: int
) -> jnp.ndarray:
    """Every lane's whole block table of one layer's pool, as rows:
    pool (L, n_blocks, rows, width), block_table (B, nb) ->
    (B, nb * T, n_kv, hd), row i holding position i."""
    return tiles_to_tokens(pool[layer, block_table], n_kv, hd)


def write_tokens(
    pool: jnp.ndarray,
    layer,
    block_table: jnp.ndarray,
    starts: jnp.ndarray,
    vals: jnp.ndarray,
) -> jnp.ndarray:
    """Write lane b's ``vals[b]`` (C, n_kv, hd) at its positions
    ``starts[b] ..`` of one layer's pool, in place: the table blocks the
    chunk touches are read, patched and written back whole. Positions
    past the table land in the scratch block. A lane writes only at and
    past its length, which lie in blocks of its own."""
    b, c, n_kv, hd = vals.shape
    tile = pool.shape[-2:]
    t = tile[0] * tile[1] // (n_kv * hd)  # tokens per block
    nb = block_table.shape[1]
    j = starts[:, None] // t + jnp.arange((c + t - 2) // t + 1)[None]
    ids = jnp.where(
        j < nb,
        jnp.take_along_axis(block_table, jnp.minimum(j, nb - 1), axis=1),
        SCRATCH_BLOCK,
    )
    cur = tiles_to_tokens(pool[layer, ids], n_kv, hd)
    new = jax.vmap(
        lambda x, v, o: jax.lax.dynamic_update_slice_in_dim(x, v, o, 0)
    )(cur, vals.astype(pool.dtype), starts % t)
    return pool.at[layer, ids].set(tokens_to_tiles(new, t, tile))


def paged_decode_attention_ref(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    layer,
    block_table: jnp.ndarray,
    kv_lens: jnp.ndarray,
    *,
    n_kv: int,
    window: int = 0,
) -> jnp.ndarray:
    """One-token attention over a paged pool, by gathering every lane's
    whole block table into rows and taking the masked softmax of
    ``decode_attention``: the reference of ``kernels.paged_attention``.
    q: (B, 1, Hq, D); kv_lens: (B,) positions each lane attends to."""
    d = q.shape[-1]
    kg = gather_tokens(pool_k, layer, block_table, n_kv, d)
    vg = gather_tokens(pool_v, layer, block_table, n_kv, d)
    o = decode_attention(q, kg, vg, kv_lens[:, None], window=window)
    # a lane with no position to attend to reads nothing: zeros
    return jnp.where(kv_lens[:, None, None, None] > 0, o, 0).astype(q.dtype)


def cache_insert(
    cache: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray
) -> jnp.ndarray:
    """Insert (B, 1, Hkv, D) at ring position ``pos`` (static cache size)."""
    return jax.lax.dynamic_update_slice_in_dim(cache, new, pos, axis=1)


def flash_attention_seq_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    mesh=None,
    axis: str = "model",
    batch_axes=("pod", "data"),
):
    """Sequence-sharded prefill attention (EXPERIMENTS.md §Perf iter. 8).

    For prefill cells whose head count doesn't divide TP and whose batch
    doesn't divide the mesh (smollm/phi3 prefill_32k), GSPMD replicates
    the attention math 16x over the model axis. Here each model shard
    computes its own q-sequence slice against the replicated K/V
    (shard_map), with the causal mask offset by the shard's position —
    attention compute and block traffic drop by the TP degree. Forward
    only (prefill has no backward; the scan path accepts a traced
    q_offset).
    """
    from jax.sharding import PartitionSpec as P

    sq = q.shape[1]
    tp = mesh.shape[axis]
    local_s = sq // tp

    def local(q_l, k_l, v_l):
        off = jax.lax.axis_index(axis) * local_s
        return flash_attention_scan(
            q_l, k_l, v_l, causal=causal, window=window, q_offset=off,
        )

    ba = tuple(a for a in batch_axes if a in mesh.axis_names)
    bspec = ba if ba else None
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(bspec, axis, None, None),
            P(bspec, None, None, None),
            P(bspec, None, None, None),
        ),
        out_specs=P(bspec, axis, None, None),
        # the scan carries start from unvarying constants; outputs vary
        # with the shard via axis_index — skip the vma consistency check
        check_vma=False,
    )(q, k, v)


def decode_attention_split_d(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,
    *,
    window: int = 0,
    mesh=None,
    axis: str = "model",
    batch_axes=("data",),
):
    """Decode attention with the KV cache head_dim-sharded over ``axis``.

    For archs whose KV-head count doesn't divide TP (phi3's 10 on a 16-way
    axis) GSPMD re-shards the whole cache every decode step ("involuntary
    full rematerialization", ~350 ms/step of HBM on phi3/decode_32k). This
    shard_map keeps the cache resident in its d-sharded layout: each shard
    computes partial scores over its d-slice, one (B, H, G, S) f32 psum
    reconstructs the logits, softmax runs replicated, and the PV product
    returns d-sharded — exactly what the row-sharded output projection
    wants (EXPERIMENTS.md §Perf iteration 7).
    """
    from jax.sharding import PartitionSpec as P

    d_model_axis = axis

    def local(q_l, k_l, v_l, cl):
        b, _, hq, dl = q_l.shape
        _, s, hkv, _ = k_l.shape
        g = hq // hkv
        # per-shard partial scores over the local d slice
        qg = q_l.reshape(b, hkv, g, dl)
        part = jnp.einsum(
            "bhgd,bshd->bhgs", qg, k_l, preferred_element_type=jnp.float32
        )
        scores = jax.lax.psum(part, d_model_axis) / math.sqrt(
            dl * jax.lax.psum(1, d_model_axis)
        )
        pos = jnp.arange(s)
        valid = pos[None] < cl
        if window > 0:
            valid &= pos[None] > cl - 1 - window
        scores = jnp.where(valid[:, None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bhgs,bshd->bhgd", p.astype(v_l.dtype), v_l,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, 1, hq, dl).astype(q_l.dtype)

    ba = tuple(a for a in batch_axes if a in mesh.axis_names)
    spec = P(ba if ba else None, None, None, axis)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec, P()),
        out_specs=spec,
        # partial scores vary per d-shard and are psum-reconstructed inside
        check_vma=False,
    )(q, k_cache, v_cache, cache_len)
