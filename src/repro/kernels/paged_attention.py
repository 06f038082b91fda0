"""Pallas TPU kernel: the decode step's KV sub-layer over the paged pool.
For each lane it writes the new token's K/V row into the lane's block and
attends over the lane's live blocks, reading no others.

Pool layout (``models.attention.pool_tile``): (L, n_blocks, n_kv * R, 128).
A block's T tokens x n_kv heads x hd values are stored head-major, each
head's T * hd values in R rows of 128 lanes; with hd dividing 128 a row
holds p = 128 / hd tokens side by side, so token t of head h sits in row
h * R + t // p at lanes (t % p) * hd onwards.

One matmul scores a group of blocks. The kernel expands q into p phases
per query head: phase j holds the head's q at lanes j * hd onwards and
zeros elsewhere, so its product with row r is the score of token
r * p + j. Each (phase, head) row keeps an online softmax of its own, in
f32, and the p phases of each head are merged at the end. Query rows of
every kv head meet the block rows of every kv head in that matmul; a
constant table of token offsets (-1 across heads) masks the products
across heads. The expansion, the new row's tile and the merge run in the
kernel, so that the step has no small XLA operations around it.

Grid: one program per lane, in order. The layer, the block table and the
lane lengths are scalar-prefetched. A lane's live table entries
(``attention.live_block_span``: blocks past the new token and blocks
wholly outside a sliding window are never read) but the last are DMAed
from HBM GROUP blocks at a time into a two-slot VMEM ring, one group
ahead of the math, under a ``fori_loop``. The last block, which takes the
new row, comes into a buffer of its own: the row is patched in, the
block is written back to the pool (aliased in place) and attended over.
Each lane starts the next lane's last block and first group before its
own math, into buffers of the other parity (last blocks rotate through
three, so that the write-back issued from one has landed before the
buffer is filled again); the last lane waits for the write-backs still
in flight.
``kernels.ref.paged_decode_ref`` is the numerical oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.attention import NEG_INF, SCRATCH_BLOCK

LANES = 128
GROUP = 8  # blocks per DMA group and per matmul


def supports(hd: int, tile: tuple[int, int]) -> bool:
    """Whether the kernel covers a pool of (rows, width) block tiles."""
    return tile[1] == LANES and LANES % hd == 0


@functools.lru_cache(maxsize=None)
def _tables(n_kv: int, hq: int, hd: int, r: int):
    """The kernel's constants: ``place`` (p, hd, 128), placing hd values at
    lanes j * hd onwards; ``heads`` (n_kv * r, n_kv), the kv head of each
    block row; ``off`` (p * hq, GROUP * n_kv * r), the position within a
    group of blocks that query row (phase j, query head i) scores against
    group row (block bi, kv head h, row rr), -1 where h is not i's kv
    head; and ``tok`` (n_kv * r, 128), the token within its block of each
    value of a block tile."""
    p, g, nk = LANES // hd, hq // n_kv, n_kv * r
    place = np.zeros((p, hd, LANES), np.float32)
    for j in range(p):
        place[j, np.arange(hd), j * hd + np.arange(hd)] = 1
    heads = (np.arange(nk)[:, None] // r == np.arange(n_kv)).astype(np.float32)
    off = np.full((p * hq, GROUP * nk), -1, np.int32)
    for j in range(p):
        for i in range(hq):
            for bi in range(GROUP):
                h = i // g
                cols = slice(bi * nk + h * r, bi * nk + (h + 1) * r)
                off[j * hq + i, cols] = bi * r * p + np.arange(r) * p + j
    tok = np.arange(r)[:, None] * p + np.arange(LANES)[None] // hd
    return place, heads, off, np.tile(tok, (n_kv, 1)).astype(np.int32)


def _kernel(layer_ref, table_ref, len_ref, q_ref, knew_ref, vnew_ref,
            place_ref, heads_ref, off_ref, tok_ref, k_hbm, v_hbm, o_ref,
            k_out, v_out, kbuf, vbuf, lbuf, sem, lsem, wsem, *, nb, t,
            window, scale):
    # written with lax primitives, not jnp wrappers: each jnp function is
    # a jitted call of its own, traced and lowered separately, which a
    # cold start pays for
    f32 = jnp.float32
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    layer = layer_ref[0]
    nk = kbuf.shape[3]
    p, hd, w = place_ref.shape

    def span(lane):
        """(positions attended, first live entry, last entry) of a lane,
        ``attention.live_block_span``'s; its new row goes at position
        ``positions - 1``. A row at or past the table's end (a drafter
        lane rolled past max_len) goes to entry ``nb``, the scratch
        block, as ``attention.write_tokens`` sends it."""
        n = len_ref[lane] + 1
        last = lax.min(lax.div(n + t - 1, t) - 1, nb)
        first = lax.div(lax.max(n - window, 0), t) if window else 0
        return n, lax.min(first, last), last

    def block(src, lane, j):
        entry = table_ref[lane * nb + lax.min(j, nb - 1)]
        return src.at[layer, lax.select(j < nb, entry, SCRATCH_BLOCK)]

    def last_copies(lane, last):
        s = lax.rem(lane, 3)
        return [
            pltpu.make_async_copy(
                block(src, lane, last), lbuf.at[c, s], lsem.at[c, s]
            )
            for c, src in ((0, k_hbm), (1, v_hbm))
        ]

    def write_back(lane, last):
        s = lax.rem(lane, 3)
        return [
            pltpu.make_async_copy(
                lbuf.at[c, s], block(out, lane, last), wsem.at[c, s]
            )
            for c, out in ((0, k_out), (1, v_out))
        ]

    def group_copies(lane, slot, j0, count, start):
        par = lax.rem(lane, 2)

        def one(i, carry):
            for c, src, buf in ((0, k_hbm, kbuf), (1, v_hbm, vbuf)):
                cp = pltpu.make_async_copy(
                    block(src, lane, j0 + i), buf.at[par, slot, i],
                    sem.at[par, c, slot],
                )
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        lax.fori_loop(0, count, one, 0)

    def prefetch(lane, carry):
        """Start a lane's last block and its first group of blocks."""
        _, first, last = span(lane)
        for c in last_copies(lane, last):
            c.start()
        group_copies(lane, 0, first, lax.min(GROUP, last - first), True)
        return carry

    # the next lane's last-block slot was the one three lanes before it
    # wrote back from: let that write land, then start the next lane's
    # copies (the first lane's too, on the first step), so that they run
    # under this lane's math
    @pl.when(b >= 2)
    def _():
        for c in write_back(b - 2, span(b - 2)[2]):
            c.wait()

    lax.fori_loop(lax.select(b == 0, 0, b + 1), lax.min(b + 2, n_lanes),
                  prefetch, 0)

    n, first, last = span(b)
    held = n - 1
    ls = lax.rem(b, 3)
    par = lax.rem(b, 2)

    def dot(x, y):
        return lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                               preferred_element_type=f32)

    q = lax.convert_element_type(q_ref[0], f32)
    q = lax.concatenate([dot(q, place_ref[j]) for j in range(p)], 0)
    nq = q.shape[0]
    off = off_ref[...]

    def attend(carry, k, v, offsets, base, cols):
        m, l, acc = carry
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32) * scale
        pos = base + offsets
        # positions past the table are not held: the reference's gather
        # of the whole table ends there
        valid = (offsets >= 0) & (pos < lax.min(n, nb * t))
        if cols is not None:
            valid &= lax.broadcasted_iota(jnp.int32, s.shape, 1) < cols
        if window:
            valid &= pos >= n - window
        s = lax.select(valid, s, lax.full(s.shape, NEG_INF, f32))
        row = lambda x: lax.broadcast_in_dim(x, (nq, 1), (0,))
        m_new = lax.max(m, row(lax.reduce_max(s, (1,))))
        e = lax.select(valid, lax.exp(s - m_new), lax.full(s.shape, 0.0, f32))
        alpha = lax.exp(m - m_new)
        l = alpha * l + row(lax.reduce_sum(e, (1,)))
        return m_new, l, alpha * acc + dot(e, v)

    n_groups = lax.div(last - first + GROUP - 1, GROUP)
    size = lambda g: lax.min(GROUP, last - first - g * GROUP)

    def group(g, carry):
        slot = lax.rem(g, 2)

        @pl.when(g + 1 < n_groups)
        def _():
            group_copies(b, 1 - slot, first + (g + 1) * GROUP, size(g + 1),
                         True)

        count = size(g)
        group_copies(b, slot, first + g * GROUP, count, False)
        k, v = (
            lax.convert_element_type(buf[par, slot], f32).reshape(
                GROUP * nk, w
            )
            for buf in (kbuf, vbuf)
        )
        # rows past the group's blocks hold stale values: zero them, so
        # that no 0 * nan reaches the sum
        rows = lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = lax.select(rows < count * nk, v, lax.full(v.shape, 0.0, f32))
        return attend(carry, k, v, off, (first + g * GROUP) * t,
                      count * nk)

    carry = lax.fori_loop(
        0, n_groups, group,
        (
            lax.full((nq, 1), NEG_INF, f32),
            lax.full((nq, 1), 0.0, f32),
            lax.full((nq, w), 0.0, f32),
        ),
    )
    # the new row of each head, repeated into every token slot of a tile,
    # goes into the slot of position ``held``
    new_slot = tok_ref[...] == lax.rem(held, t)
    spread = place_ref[0]
    for j in range(1, p):
        spread = spread + place_ref[j]
    for c in last_copies(b, last):
        c.wait()
    for c, new in ((0, knew_ref), (1, vnew_ref)):
        tile = dot(heads_ref[...],
                   dot(lax.convert_element_type(new[0], f32), spread))
        lbuf[c, ls] = lax.select(
            new_slot, lax.convert_element_type(tile, lbuf.dtype), lbuf[c, ls]
        )
    for c in write_back(b, last):
        c.start()
    m, l, acc = attend(
        carry, lax.convert_element_type(lbuf[0, ls], f32),
        lax.convert_element_type(lbuf[1, ls], f32), off[:, :nk], last * t,
        None,
    )
    # merge each head's p phases: phase j's sums are exact on lanes j * hd
    # onwards, which a lane rotation brings to lanes 0..hd
    hq = nq // p
    phase = lambda x, j: x[j * hq:(j + 1) * hq]
    top = phase(m, 0)
    for j in range(1, p):
        top = lax.max(top, phase(m, j))
    out = lax.full((hq, w), 0.0, f32)
    total = lax.full((hq, 1), 0.0, f32)
    for j in range(p):
        a = lax.exp(phase(m, j) - top)
        total = total + a * phase(l, j)
        part = a * phase(acc, j)
        out = out + (part if j == 0 else pltpu.roll(part, w - j * hd, 1))
    o_ref[0] = lax.convert_element_type(out[:, :hd] / total, o_ref.dtype)

    # the last lane lets its own write-back and the one before land
    @pl.when(b == n_lanes - 1)
    def _():
        for c in write_back(b, last):
            c.wait()

        @pl.when(b >= 1)
        def _():
            for c in write_back(b - 1, span(b - 1)[2]):
                c.wait()


def paged_decode(
    q: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    layer,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    n_kv: int,
    window: int = 0,
    interpret: bool = False,
):
    """One decode step's KV sub-layer of pool layer ``layer``: lane b's new
    row ``k_new[b]``/``v_new[b]`` (n_kv, hd) is written at position
    ``lengths[b]`` through its block table, and its query q[b] attends
    over positions 0..lengths[b] (the window's, for a sliding window).
    q: (B, 1, Hq, D); pools: (L, n_blocks, n_kv * R, 128), updated in
    place; block_table: (B, nb) int32; lengths: (B,) int32. Returns
    (attention (B, 1, Hq, D) in q's dtype, pool_k, pool_v)."""
    b, _, hq, d = q.shape
    rows, w = pool_k.shape[-2:]
    assert supports(d, (rows, w)), (d, rows, w)
    r = rows // n_kv
    t = r * w // d
    tables = [jnp.asarray(a) for a in _tables(n_kv, hq, d, r)]
    kernel = functools.partial(
        _kernel, nb=block_table.shape[1], t=t, window=window,
        scale=1.0 / math.sqrt(d),
    )
    lane = lambda *shape: pl.BlockSpec((1, *shape), lambda i, *_: (i, 0, 0))
    whole = lambda a: pl.BlockSpec(
        a.shape, lambda i, *_: (0,) * a.ndim
    )
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    o, pool_k, pool_v = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[lane(hq, d), lane(n_kv, d), lane(n_kv, d),
                      *map(whole, tables), hbm, hbm],
            out_specs=[lane(hq, d), hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, 2, GROUP, rows, w), pool_k.dtype),
                pltpu.VMEM((2, 2, GROUP, rows, w), pool_v.dtype),
                pltpu.VMEM((2, 3, rows, w), pool_k.dtype),
                pltpu.SemaphoreType.DMA((2, 2, 2)),
                pltpu.SemaphoreType.DMA((2, 3)),
                pltpu.SemaphoreType.DMA((2, 3)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, d), q.dtype),
            jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
            jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
        ],
        input_output_aliases={10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_decode",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        block_table.reshape(-1).astype(jnp.int32),
        lengths.astype(jnp.int32),
        q.reshape(b, hq, d),
        k_new,
        v_new,
        *tables,
        pool_k,
        pool_v,
    )
    return o.reshape(b, 1, hq, d), pool_k, pool_v
