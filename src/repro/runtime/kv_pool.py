"""Shared physical KV pool for continuous-batching decode (serving FCMP).

The paper packs many logical weight buffers into one physical BRAM and
compensates with a faster memory clock (``core.gals``); the serving analog
packs many per-request KV caches into one contiguous physical pool and
compensates with the scheduler's decode/admission interleave. The mapping:

    logical buffer      -> one request's KV cache
    physical BRAM block -> a fixed ``block_tokens``-row pool block
    bin height H_B      -> co-resident requests per pool
    paper Eq. 1         -> ``utilization()`` (held tokens / held rows)

Blocks are **refcounted**: the FCMP move of sharing one physical memory
between several logical consumers applies to KV too, because identical
prompt prefixes produce identical KV rows. A request's block table may
alias blocks held by other requests and/or pinned by the radix prefix
cache (``runtime.prefix_cache``); a block returns to the free list only
when its last holder lets go. Shared blocks are read-only for everyone
but the original writer; a request that must write into a *partially*
matched block first takes a private copy (``adopt_prefix``'s
copy-on-write of the tail block). Cached blocks with no live request
holder are reclaimable: under admission pressure the pool asks its
attached cache (the ``evictor`` hook) to evict LRU entries.

Block geometry and fragmentation accounting reuse ``core.packing`` /
``core.resource_model`` directly: a request's footprint is a
``WeightBuffer`` (width 1 "lane", depth = tokens), a pool block is a
``RamPrimitive`` with a single legal aspect ratio ``(1, block_tokens)``,
and ``pack_ffd`` provides the first-fit-decreasing machinery for the
block-size sweep and the tail-sharing lower bound.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import Counter
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.buffers import WeightBuffer
from repro.core.packing import PackItem, baseline_packing, pack_ffd
from repro.core.resource_model import RamPrimitive
from repro.models.attention import (
    SCRATCH_BLOCK,  # never allocated; idle lanes' tables point at it
    pool_tile,
    tiles_to_tokens,
    tokens_to_tiles,
)
from repro.models.config import PAGED_FAMILIES, ModelConfig


@functools.partial(jax.jit, donate_argnums=(0,))
def write_blocks(pool, ids, rows):
    """Write (L, n, n_kv, hd) rows, n a whole number of blocks, into
    blocks ``ids`` in place on the donated pool (one trace per row count;
    the .at[].set outside jit would copy the pool)."""
    t = rows.shape[1] // ids.shape[0]
    return pool.at[:, ids].set(
        tokens_to_tiles(rows.astype(pool.dtype), t, pool.shape[-2:])
    )


# copy-on-write block duplication: block ``src``'s tile copied into block
# ``dst`` in every layer, in place on the donated pool
_block_copy = jax.jit(
    lambda pool, dst, src: pool.at[:, dst].set(pool[:, src]),
    donate_argnums=(0,),
)


def kv_block_ram(block_tokens: int) -> RamPrimitive:
    """A pool block as a RAM primitive: one legal shape, 1 x block_tokens."""
    return RamPrimitive(
        name="KVBLOCK",
        capacity_bits=block_tokens,
        n_ports=2,
        configs=((1, block_tokens),),
    )


def request_buffer(rid: int, n_tokens: int) -> WeightBuffer:
    """A request's KV footprint as a logical buffer (1 lane x tokens)."""
    return WeightBuffer(f"req{rid}", width_bits=1, depth_words=n_tokens, w_bits=1)


def choose_block_tokens(
    lengths: list[int],
    candidates: tuple[int, ...] = (4, 8, 16, 32, 64),
    overhead_rows: float = 0.5,
) -> int:
    """Pick the block size minimising lifetime pool waste for a length mix.

    A decode cache *grows* 1 -> L tokens, so the cost of a block size is
    the request-lifetime average of (allocated rows - held tokens) plus a
    per-block bookkeeping overhead (block-table entries, gather indices).
    This is the same blocks_for() geometry sweep ``core.packing.bin_cost``
    runs over BRAM aspect ratios: small blocks waste little tail but pay
    per-block overhead, large blocks the reverse — ``overhead_rows`` is
    what stops "always pick the smallest shape".
    """
    if not lengths:
        return candidates[0]
    counts = Counter(lengths)
    best_t, best_cost = candidates[0], None
    for t in candidates:
        ram = kv_block_ram(t)
        cost = 0.0
        for length, n in counts.items():
            blocks = [
                request_buffer(0, l).blocks(ram)
                for l in range(1, max(2, length + 1))
            ]
            waste = sum(b * t - l for l, b in enumerate(blocks, start=1))
            cost += n * (waste + overhead_rows * sum(blocks)) / len(blocks)
        if best_cost is None or cost < best_cost:
            best_t, best_cost = t, cost
    return best_t


@dataclasses.dataclass
class PoolStats:
    n_blocks: int
    block_tokens: int
    held_blocks: int  # unique physical blocks held by live requests
    held_tokens: int  # useful rows in them, each physical row counted once
    free_blocks: int
    committed_blocks: int
    shared_blocks: int = 0  # request-held blocks with > 1 request holder
    cached_blocks: int = 0  # blocks pinned by the prefix cache
    evictable_blocks: int = 0  # cached blocks no live request holds

    @property
    def utilization(self) -> float:
        """Serving Eq. 1: useful KV rows / physical rows held.

        Both terms are per *physical* block — a block shared by N
        requests contributes its rows once, not N times, so sharing
        raises effective utilization instead of double-counting it.
        """
        if self.held_blocks == 0:
            return 1.0
        return self.held_tokens / (self.held_blocks * self.block_tokens)

    @property
    def occupancy(self) -> float:
        return self.held_blocks / max(1, self.n_blocks)


class KVPool:
    """One contiguous physical KV cache with refcounted block sharing.

    Device side: ``k``/``v`` are (L, n_blocks, rows, width) arrays, one
    block-contiguous tile per block (``attention.pool_tile``: the block's
    tokens x heads x head dim, head-major, in lane-dense rows), so a
    step reads a lane's blocks whole and in place. Host side: a
    free-block inventory, per-request block tables that may *alias* each
    other on shared prefixes, a per-block refcount, and the set of blocks
    pinned by the attached prefix cache.

    Admission reserves a *commitment* (the request's full block need from
    ``blocks_for``) but hands out blocks lazily as tokens arrive, so
    utilization stays high while on-demand growth can never fail:

        invariant:  sum(committed - held) over live requests
                    <= free blocks + evictable cached blocks

    (Shared blocks adopted from the cache count as held without touching
    the free list, so a prefix hit only *shrinks* a request's residual
    claim on the free list — the invariant stays conservative.)
    """

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        n_blocks: int,
        block_tokens: int,
        dtype=None,
    ):
        if cfg.family not in PAGED_FAMILIES:
            raise ValueError(
                f"KVPool serves the paged families {PAGED_FAMILIES}; got "
                f"{cfg.family!r} (pure-ssm decode state is fixed-size per "
                "slot and holds no KV rows)"
            )
        if n_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the scratch block)")
        self.cfg = cfg
        self.n_blocks = n_blocks
        self.block_tokens = block_tokens
        self.ram = kv_block_ram(block_tokens)
        dt = jnp.dtype(dtype or cfg.dtype)
        # hybrid holds one growing KV cache per *shared* attention block
        # (n_super of them), not per layer
        shape = (cfg.n_kv_cache_layers, n_blocks) + pool_tile(
            cfg.n_kv, block_tokens, cfg.hd
        )
        self.k = jnp.zeros(shape, dt)
        self.v = jnp.zeros(shape, dt)
        # block 0 reserved as scratch for idle decode lanes
        self._free: list[int] = list(range(n_blocks - 1, SCRATCH_BLOCK, -1))
        self._held: dict[int, list[int]] = {}
        self._tokens: dict[int, int] = {}
        self._committed: dict[int, int] = {}
        # open speculative brackets: rid -> blocks grown by begin_draft
        # and not yet settled by end_draft (owner="draft" ledger class)
        self._draft: dict[int, int] = {}
        self._refs: dict[int, int] = {}  # block -> live holders (+1 cached)
        self._cached: set[int] = set()  # blocks pinned by the prefix cache
        # incremental aggregates so the per-decode-step stats() read is
        # O(1) instead of rescanning every block table (validate()
        # cross-checks them against a full recount)
        self._users: Counter = Counter()  # block -> live *request* holders
        self._used: dict[int, int] = {}  # block -> deepest row any holder uses
        self._used_total = 0
        self._shared = 0  # blocks with > 1 request holder
        self._evictable = 0  # cached blocks with no request holder
        # the attached prefix cache's eviction hook: (blocks needed) ->
        # blocks actually returned to the free list
        self.evictor: Callable[[int], int] | None = None
        # lifetime counters (runtime.tracker records + soak conservation:
        # alloc - freed always equals the referenced-block count)
        self.alloc_blocks = 0
        self.freed_blocks = 0
        self.cow_copies = 0
        # the attached memory ledger (runtime.memledger.MemLedger.attach);
        # every mutation below notifies it so integrated deltas reproduce
        # stats() exactly at any point between mutations
        self.ledger = None

    @classmethod
    def for_slots(
        cls,
        cfg: ModelConfig,
        *,
        slots: int,
        max_len: int,
        block_tokens: int,
        dtype=None,
    ) -> "KVPool":
        """A pool sized so ``slots`` concurrent max_len requests always fit
        (their full block commitments, plus the scratch block)."""
        per_slot = -(-max_len // block_tokens)
        return cls(
            cfg,
            n_blocks=1 + slots * per_slot,
            block_tokens=block_tokens,
            dtype=dtype,
        )

    # ---------------- geometry ----------------

    def blocks_for(self, n_tokens: int) -> int:
        return request_buffer(0, n_tokens).blocks(self.ram)

    @property
    def usable_blocks(self) -> int:
        return self.n_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def cached_blocks(self) -> int:
        return len(self._cached)

    @property
    def evictable_blocks(self) -> int:
        """Cached blocks no live request holds — reclaimable on demand.

        A cached block with refcount 1 is pinned only by the cache; the
        radix tree's prefix-chain structure guarantees its whole subtree
        is equally unheld, so every such block is evictable bottom-up.
        """
        return self._evictable

    # ---------------- incremental accounting ----------------

    def _add_user(self, block: int) -> None:
        self._users[block] += 1
        if self._users[block] == 2:
            self._shared += 1
        if self._users[block] == 1 and block in self._cached:
            self._evictable -= 1

    def _drop_user(self, block: int) -> None:
        c = self._users[block] - 1
        if c == 0:
            del self._users[block]
            self._used_total -= self._used.pop(block, 0)
            if block in self._cached:
                self._evictable += 1
        else:
            self._users[block] = c
            if c == 1:
                self._shared -= 1

    def _count_use(self, block: int, rows: int) -> None:
        old = self._used.get(block, 0)
        if rows > old:
            self._used[block] = rows
            self._used_total += rows - old

    @property
    def outstanding_commitment(self) -> int:
        return sum(
            max(0, self._committed[r] - len(self._held[r])) for r in self._held
        )

    def ref_count(self, block: int) -> int:
        return self._refs.get(block, 0)

    # ---------------- lifecycle ----------------

    def can_admit(self, total_tokens: int) -> bool:
        need = self.blocks_for(total_tokens)
        avail = self.free_blocks + self.evictable_blocks
        return avail - self.outstanding_commitment >= need

    def admit(self, rid: int, total_tokens: int) -> None:
        if rid in self._held:
            raise ValueError(f"request {rid} already admitted")
        if not self.can_admit(total_tokens):
            raise RuntimeError(
                f"pool cannot admit request {rid} "
                f"({self.blocks_for(total_tokens)} blocks needed, "
                f"{self.free_blocks + self.evictable_blocks - self.outstanding_commitment}"
                " uncommitted)"
            )
        self._committed[rid] = self.blocks_for(total_tokens)
        self._held[rid] = []
        self._tokens[rid] = 0
        if self.ledger is not None:
            self.ledger.record(
                "admit", owner="request", rid=rid, committed=self._committed[rid]
            )

    def _pop_free(self) -> int:
        """Take a block off the free list, evicting cached blocks first
        when it is empty. Commitment accounting guarantees this succeeds
        for any in-commitment growth."""
        if not self._free and self.evictor is not None:
            self.evictor(1)
        if not self._free:
            raise RuntimeError("pool free list empty and nothing evictable")
        b = self._free.pop()
        self._refs[b] = 1
        self.alloc_blocks += 1
        return b

    def ensure_rows(self, rid: int, n_tokens: int) -> None:
        """Grow the request's block list to hold ``n_tokens`` rows."""
        held = self._held[rid]
        before = len(held)
        while len(held) * self.block_tokens < n_tokens:
            if len(held) >= self._committed[rid]:
                raise RuntimeError(
                    f"request {rid} exceeds its {self._committed[rid]}-block "
                    "commitment"
                )
            b = self._pop_free()
            self._add_user(b)
            held.append(b)
        # note_tokens-driven row-coverage drift deliberately does not
        # emit (it would flood one record per decode token); the ledger's
        # round sync() folds it in. Block growth is an event.
        if self.ledger is not None and len(held) > before:
            self.ledger.record(
                "grow", owner="request", rid=rid, grown=len(held) - before
            )

    def note_tokens(self, rid: int, n_tokens: int) -> None:
        """Record the request's token count (monotone while held: a
        smaller count than already noted keeps the deeper coverage)."""
        self.ensure_rows(rid, n_tokens)
        old = self._tokens[rid]
        if n_tokens <= old:
            return
        self._tokens[rid] = n_tokens
        held, t = self._held[rid], self.block_tokens
        for idx in range(0 if old == 0 else (old - 1) // t,
                         (n_tokens - 1) // t + 1):
            self._count_use(held[idx], min(t, n_tokens - idx * t))

    def begin_draft(self, rid: int, n_tokens: int) -> None:
        """Grow the request's block list to cover a speculative draft
        chain ending at row ``n_tokens``, without advancing the token
        count. Draft rows land in the request's own (private) blocks, so
        a rejected suffix needs no data movement to undo: ``end_draft``
        returns the surplus blocks and the stale rows are overwritten by
        the next chain. Blocks grown here are charged to the ``draft``
        owner class in the ledger, distinct from committed request growth.
        """
        held = self._held[rid]
        before = len(held)
        while len(held) * self.block_tokens < n_tokens:
            if len(held) >= self._committed[rid]:
                raise RuntimeError(
                    f"draft for request {rid} exceeds its "
                    f"{self._committed[rid]}-block commitment"
                )
            b = self._pop_free()
            self._add_user(b)
            held.append(b)
        grown = len(held) - before
        if grown:
            self._draft[rid] = self._draft.get(rid, 0) + grown
            if self.ledger is not None:
                self.ledger.record(
                    "draft_grow", owner="draft", rid=rid, grown=grown
                )

    def end_draft(self, rid: int, n_tokens: int) -> None:
        """Settle a draft chain at its accepted length: rows through
        ``n_tokens`` become committed coverage (``note_tokens``); draft
        blocks past the accepted prefix are released back to the free
        list. Exactly inverts ``begin_draft`` when nothing is accepted
        into the drafted blocks, so the ledger integrates to zero across
        a fully-rejected chain."""
        draft = self._draft.pop(rid, 0)
        held = self._held[rid]
        keep = max(self.blocks_for(n_tokens), len(held) - draft)
        freed = 0
        while len(held) > keep:
            b = held.pop()
            self._drop_user(b)
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)
                self.freed_blocks += 1
            freed += 1
        self.note_tokens(rid, n_tokens)
        if self.ledger is not None and (draft or freed):
            self.ledger.record(
                "draft_end", owner="draft", rid=rid,
                kept=draft - freed, freed=freed,
            )

    def draft_rids(self) -> tuple[int, ...]:
        """Requests currently holding draft-class blocks (empty outside a
        begin_draft/end_draft bracket — the soak leak probe)."""
        return tuple(self._draft)

    def adopt_prefix(
        self,
        rid: int,
        shared: tuple[int, ...],
        tail_block: int | None,
        n_tokens: int,
    ) -> None:
        """Alias a matched prefix's blocks into a fresh request's table.

        ``shared`` are the cache's full blocks covering rows
        ``[0, len(shared) * block_tokens)`` — adopted read-only, refcount
        bumped. ``tail_block`` (required iff ``n_tokens`` is not
        block-aligned) holds the partially-matched block: the request
        will *write* rows ``n_tokens..`` of that block span, so it gets a
        private **copy-on-write** duplicate instead of an alias — the
        partial-block-divergence rule that keeps shared rows immutable.
        Must run right after ``admit``, before any rows are held.
        """
        held = self._held[rid]
        if held or self._tokens[rid]:
            raise RuntimeError(
                f"request {rid} must adopt a prefix before holding rows"
            )
        t = self.block_tokens
        if len(shared) != n_tokens // t:
            raise ValueError(
                f"{len(shared)} shared blocks cannot cover "
                f"{n_tokens // t} full blocks of {n_tokens} tokens"
            )
        if (tail_block is None) != (n_tokens % t == 0):
            raise ValueError(
                f"tail block required iff the matched prefix ({n_tokens} "
                f"tokens) ends mid-block (block_tokens={t})"
            )
        if len(shared) + (tail_block is not None) > self._committed[rid]:
            raise RuntimeError(
                f"adopted prefix exceeds request {rid}'s commitment"
            )
        for b in shared:
            if b == SCRATCH_BLOCK or b not in self._refs:
                raise ValueError(f"cannot adopt unallocated block {b}")
            self._refs[b] += 1
            self._add_user(b)
            held.append(b)
        if tail_block is not None:
            if tail_block == SCRATCH_BLOCK or tail_block not in self._refs:
                raise ValueError(f"cannot adopt unallocated block {tail_block}")
            new = self._pop_free()
            dst, src = jnp.int32(new), jnp.int32(tail_block)
            self.k = _block_copy(self.k, dst, src)
            self.v = _block_copy(self.v, dst, src)
            self._add_user(new)
            held.append(new)
            self.cow_copies += 1
        self.note_tokens(rid, n_tokens)
        if self.ledger is not None:
            self.ledger.record(
                "adopt_prefix",
                owner="request",
                rid=rid,
                shared=len(shared),
                cow=int(tail_block is not None),
            )

    def release(self, rid: int) -> None:
        if rid not in self._held:
            raise ValueError(
                f"release of unknown request {rid}: it was never admitted "
                "or was already released (double free) — its blocks are "
                "not on the free list twice"
            )
        for b in self._held.pop(rid):
            self._drop_user(b)
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)
                self.freed_blocks += 1
        del self._tokens[rid], self._committed[rid]
        self._draft.pop(rid, None)
        if self.ledger is not None:
            self.ledger.record("release", owner="request", rid=rid)

    # ---------------- prefix-cache pinning ----------------

    def retain_cached(self, block: int) -> None:
        """Pin a block on behalf of the prefix cache (one pin per block)."""
        if block == SCRATCH_BLOCK or block not in self._refs:
            raise ValueError(f"cannot cache unallocated block {block}")
        if block in self._cached:
            raise ValueError(f"block {block} already cached")
        self._cached.add(block)
        self._refs[block] += 1
        if self.ledger is not None:
            self.ledger.record("retain_cached", owner="prefix-cache", block=block)

    def uncache(self, block: int) -> int:
        """Drop the cache's pin; returns 1 if the block went free, else 0.

        Eviction can never reclaim a block a live request holds: the
        refcount only reaches zero when no block table references it.
        """
        if block not in self._cached:
            raise ValueError(f"block {block} is not cached")
        self._cached.remove(block)
        self._refs[block] -= 1
        freed = 0
        if self._refs[block] == 0:
            del self._refs[block]
            self._free.append(block)
            self._evictable -= 1  # it was cache-only; now it is free
            self.freed_blocks += 1
            freed = 1
        if self.ledger is not None:
            self.ledger.record("uncache", owner="prefix-cache", block=block)
        return freed

    # ---------------- introspection ----------------

    def live_requests(self) -> list[int]:
        return list(self._held)

    def blocks_of(self, rid: int) -> tuple[int, ...]:
        return tuple(self._held[rid])

    def blocks_held(self, rid: int) -> int:
        return len(self._held[rid])

    def tokens_held(self, rid: int) -> int:
        return self._tokens[rid]

    # ---------------- device-side addressing ----------------

    def table_of(self, rid: int, n_entries: int) -> np.ndarray:
        """The request's block table: its blocks in position order, padded
        to ``n_entries`` with the scratch block."""
        table = np.full((n_entries,), SCRATCH_BLOCK, np.int32)
        held = self._held[rid][:n_entries]
        table[: len(held)] = held
        return table

    def write_prefill(
        self,
        rid: int,
        ks: jnp.ndarray,
        vs: jnp.ndarray,
        n_tokens: int | None = None,
    ) -> None:
        """Write a prefilled (L, P, n_kv, hd) KV prefix into the pool.

        Cold-path only: the request's blocks must be private (a warm
        prefix-cache admission writes its suffix through the chunked
        prefill steps instead, which never touch adopted shared rows).
        ``ks``/``vs`` may be right-padded past ``n_tokens`` (the prefill
        bucket); the pad fills the tail of the last block, where nothing
        valid lies yet, and whole blocks past the request's land in the
        scratch block, so the jitted write traces once per bucket size,
        and the donated pool buffer updates in place instead of copying
        the whole pool per admission.
        """
        p = n_tokens if n_tokens is not None else ks.shape[1]
        self.note_tokens(rid, p)
        t = self.block_tokens
        pad = -ks.shape[1] % t
        if pad:
            widths = ((0, 0), (0, pad), (0, 0), (0, 0))
            ks, vs = jnp.pad(ks, widths), jnp.pad(vs, widths)
        ids = jnp.asarray(self.table_of(rid, ks.shape[1] // t))
        self.k = write_blocks(self.k, ids, ks)
        self.v = write_blocks(self.v, ids, vs)

    def export_blocks(
        self, rid: int, n_tokens: int | None = None
    ) -> tuple[tuple[int, ...], np.ndarray, np.ndarray]:
        """Snapshot a request's KV for handoff, serialized in block-id
        order: returns (block ids, K rows, V rows) with the row payloads
        shaped (L, n_tokens, n_kv, hd) — the blocks are read in table
        order, so the ids fully describe the payload layout and a
        block-granular transport could ship the physical blocks as-is.
        Shared (prefix-cache) blocks export by value like any other: the
        importing pool allocates its own blocks, so refcounts stay
        engine-local and intact."""
        ids = tuple(self._held[rid])
        n = n_tokens if n_tokens is not None else self._tokens[rid]
        idx = jnp.asarray(ids, jnp.int32)
        cfg = self.cfg
        return ids, *(
            np.asarray(tiles_to_tokens(pool[:, idx], cfg.n_kv, cfg.hd)[:, :n])
            for pool in (self.k, self.v)
        )

    # ---------------- accounting / reporting ----------------

    def stats(self) -> PoolStats:
        # the per-block aggregates (deepest row any holder uses, holder
        # counts, shared/evictable tallies) are maintained incrementally
        # on admit/grow/adopt/release, so this read — which the
        # scheduler takes every decode step — never rescans block tables
        return PoolStats(
            n_blocks=self.usable_blocks,
            block_tokens=self.block_tokens,
            held_blocks=len(self._users),
            held_tokens=self._used_total,
            free_blocks=self.free_blocks,
            committed_blocks=self.outstanding_commitment,
            shared_blocks=self._shared,
            cached_blocks=len(self._cached),
            evictable_blocks=self._evictable,
        )

    def validate(self) -> None:
        """Allocator invariants: refcounts exact, no free+referenced
        overlap, free-list uniqueness, full accounting."""
        if len(self._free) != len(set(self._free)):
            raise AssertionError("free list holds duplicate blocks")
        holders: Counter = Counter()
        for bs in self._held.values():
            holders.update(bs)
        referenced = set(holders) | self._cached
        if SCRATCH_BLOCK in referenced or SCRATCH_BLOCK in self._free:
            raise AssertionError("scratch block entered circulation")
        if referenced != set(self._refs):
            raise AssertionError("refcount keys out of sync with holders")
        for b in referenced:
            want = holders[b] + (1 if b in self._cached else 0)
            if self._refs[b] != want:
                raise AssertionError(
                    f"block {b} refcount {self._refs[b]} != {want} holders"
                )
        if referenced & set(self._free):
            raise AssertionError("block simultaneously referenced and free")
        if len(referenced) + len(self._free) != self.usable_blocks:
            raise AssertionError("blocks leaked")
        for rid, bs in self._held.items():
            if len(bs) != len(set(bs)):
                raise AssertionError(f"request {rid} holds a block twice")
            if self._tokens[rid] > len(bs) * self.block_tokens:
                raise AssertionError(f"request {rid} overflows its blocks")
        for rid, n in self._draft.items():
            if rid not in self._held or n > len(self._held[rid]):
                raise AssertionError(
                    f"draft bracket for request {rid} out of sync"
                )
        # incremental aggregates must equal a full recount
        used: dict[int, int] = {}
        t = self.block_tokens
        for rid, bs in self._held.items():
            for i, b in enumerate(bs):
                r = min(t, max(0, self._tokens[rid] - i * t))
                if r:  # draft-grown blocks carry no committed rows yet
                    used[b] = max(used.get(b, 0), r)
        if holders != self._users:
            raise AssertionError("per-block holder counts drifted")
        if used != {b: r for b, r in self._used.items()} or (
            sum(used.values()) != self._used_total
        ):
            raise AssertionError("per-block row-coverage drifted")
        if self._shared != sum(1 for n in holders.values() if n > 1):
            raise AssertionError("shared-block tally drifted")
        if self._evictable != sum(
            1 for b in self._cached if self._refs[b] == 1
        ):
            raise AssertionError("evictable-block tally drifted")
        # lifetime conservation: every allocation is either still
        # referenced or was returned to the free list exactly once
        if self.alloc_blocks - self.freed_blocks != len(self._refs):
            raise AssertionError(
                f"block conservation violated: {self.alloc_blocks} allocated"
                f" - {self.freed_blocks} freed != {len(self._refs)} live"
            )

    def fragmentation_report(self) -> dict:
        """Baseline (private blocks) vs the ``pack_ffd`` tail-sharing bound.

        The physical placement treats each request's logical footprint as
        its own buffer (prefix sharing aside), i.e. ``baseline_packing``;
        FFD with height H_B=4 quotes what packing request tails into
        shared blocks would save — the serving analog of the paper's
        baseline-vs-FCMP BRAM comparison.
        """
        items = [
            PackItem(request_buffer(rid, self._tokens[rid]))
            for rid in sorted(self._held)
            if self._tokens[rid] > 0
        ]
        base = baseline_packing(items, self.ram)
        packed = pack_ffd(items, max_height=4, ram=self.ram)
        return {
            "baseline_blocks": base.total_blocks,
            "ffd_blocks": packed.total_blocks,
            "baseline_efficiency": base.efficiency,
            "ffd_efficiency": packed.efficiency,
        }
