"""Continuous-batching request scheduler over a shared KV pool.

Request lifecycle: QUEUED -> PREFILL -> DECODE -> DONE. Admission is
token-budget bound (the sum of committed prompt+generation tokens across
in-flight requests never exceeds ``token_budget``) and pool-bound (the
``KVPool`` must hold the request's full block commitment). Prefill is one
batched full-sequence step per request (time-to-first-token is a single
step, not prompt_len serve steps); decode lanes run the pool-indexed
paged step, each lane at its own depth — no lockstep shared cache length.

The frequency-compensation knob: ``decode_per_round`` (R_F) is how many
decode steps run per admission/prefill round. It is the serving Eq. 2 of
``core.gals``: a pool serving H_B co-resident requests through one
physical memory sustains decode throughput iff the decode domain gets
R_F >= H_B / N_ports rounds for every round the admission/prefill domain
steals — so the default is ``ceil(required_rf(slots))``. R_F = 1 is a
prefill-heavy schedule (fast admission, decode throughput dips); large
R_F starves admission (TTFT grows) the way an under-clocked memory
domain starves the paper's compute pipeline.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import math
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gals import required_rf
from repro.runtime.tracker import DELTA_KEYS
from repro.models.config import (
    CHUNKABLE_FAMILIES,
    PREFIX_CACHE_FAMILIES,
    ModelConfig,
)
from repro.models.attention import (
    SCRATCH_BLOCK,
    decode_window,
    live_block_span,
)
from repro.models.lm import (
    STEP_SCOPES,
    SamplingParams,
    init_ssm_lane_state,
    sample_logits,
)
from repro.perf.hlo_analysis import scope_table
from repro.runtime.kv_pool import KVPool
from repro.runtime.spans import phase_annotation
from repro.runtime.speculative import SPEC_FAMILIES, LaneDraft
from repro.runtime.steps import (
    make_chunk_prefill_step,
    make_hybrid_suffix_prefill_step,
    make_paged_serve_step,
    make_pool_prefill_step,
    make_verify_step,
)


# jit wrappers cached per config so schedulers (and benchmark A/B runs)
# share compilations instead of retracing per instance
@functools.lru_cache(maxsize=None)
def _jitted_prefill(cfg: ModelConfig):
    return jax.jit(make_pool_prefill_step(cfg))


@functools.lru_cache(maxsize=None)
def _jitted_decode(cfg: ModelConfig):
    if cfg.family == "hybrid":
        # hybrid signature carries the per-lane SSM state (argnum 6) in
        # addition to the two pool halves
        return jax.jit(make_paged_serve_step(cfg), donate_argnums=(2, 3, 6))
    return jax.jit(make_paged_serve_step(cfg), donate_argnums=(2, 3))


@functools.lru_cache(maxsize=None)
def _jitted_chunk_prefill(cfg: ModelConfig):
    return jax.jit(make_chunk_prefill_step(cfg), donate_argnums=(2, 3))


@functools.lru_cache(maxsize=None)
def _jitted_verify(cfg: ModelConfig):
    return jax.jit(make_verify_step(cfg), donate_argnums=(2, 3))


@functools.lru_cache(maxsize=None)
def _jitted_hybrid_suffix(cfg: ModelConfig):
    return jax.jit(
        make_hybrid_suffix_prefill_step(cfg), donate_argnums=(2, 3, 7)
    )


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODE = "decode"
    HANDOFF = "handoff"  # prefilled here, decoded on another engine
    DONE = "done"


@dataclasses.dataclass
class PrefillHandoff:
    """A prefilled request leaving a prefill-role engine.

    The KV payload is serialized through the source pool's block ids:
    ``k``/``v`` hold the request's rows gathered in block order (shape
    (L, n_tokens, n_kv, hd)), and ``block_ids`` records which physical
    blocks produced them — the wire format is block-granular, mirroring
    the allocator, so a zero-copy transport could ship whole blocks.
    Hybrid requests additionally ship ``lane_state`` — the per-request
    SSM decode state (leaves (L, 1, ...) as in ``init_ssm_lane_state``)
    at the prompt end — so zamba2 disaggregates prefill/decode too.
    """

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    first_token: int
    n_tokens: int
    block_ids: tuple[int, ...]
    block_tokens: int
    k: np.ndarray
    v: np.ndarray
    lane_state: dict | None = None

    @property
    def kv_bytes(self) -> int:
        lane = (
            sum(leaf.nbytes for leaf in jax.tree.leaves(self.lane_state))
            if self.lane_state is not None
            else 0
        )
        return self.k.nbytes + self.v.nbytes + lane

    @property
    def total_tokens(self) -> int:
        return self.n_tokens + self.max_new_tokens


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (P,) int32
    max_new_tokens: int
    state: RequestState = RequestState.QUEUED
    output: list[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_first_token: float = 0.0
    states_seen: list[RequestState] = dataclasses.field(default_factory=list)

    @property
    def total_tokens(self) -> int:
        return len(self.prompt) + self.max_new_tokens

    @property
    def ttft(self) -> float:
        return self.t_first_token - self.t_submit

    def _enter(self, state: RequestState) -> None:
        self.state = state
        self.states_seen.append(state)


@dataclasses.dataclass
class SchedulerStats:
    completed: int = 0
    generated_tokens: int = 0
    prefill_steps: int = 0
    prefill_tokens: int = 0  # charged for the *unmatched* suffix only
    prefix_hits: int = 0
    prefix_hit_tokens: int = 0  # prompt tokens served from cached blocks
    decode_steps: int = 0
    handoffs: int = 0
    expert_tokens: int = 0  # moe: routed (token, expert) slots, all layers
    # speculative decode: tokens emitted by verify steps (1..k each),
    # drafter proposals offered, and batched verify calls run
    accepted_tokens: int = 0
    draft_tokens: int = 0
    verify_steps: int = 0
    rounds: int = 0
    ttfts: list[float] = dataclasses.field(default_factory=list)
    util_samples: list[float] = dataclasses.field(default_factory=list)
    util_samples_any: list[float] = dataclasses.field(default_factory=list)
    shared_blocks_peak: int = 0
    decode_time: float = 0.0
    # K/V blocks per layer the paged decode attention visits, over all
    # decode steps: each lane's live blocks (``live_block_span``) where
    # the kernel runs, every table entry where the reference does
    kv_blocks_read: int = 0

    @property
    def mean_ttft(self) -> float:
        return sum(self.ttfts) / len(self.ttfts) if self.ttfts else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of submitted prompt tokens served from the cache
        (hit tokens / (hit tokens + prefilled tokens))."""
        total = self.prefix_hit_tokens + self.prefill_tokens
        return self.prefix_hit_tokens / total if total else 0.0

    @property
    def accepted_per_step(self) -> float:
        """Mean tokens emitted per verify step (1.0 = no draft ever
        accepted — speculative decode's whole win is this number)."""
        if not self.verify_steps:
            return 0.0
        return self.accepted_tokens / self.verify_steps

    @property
    def steady_state_utilization(self) -> float:
        """Mean pool utilization over decode steps with all lanes busy;
        if the trace never fills every lane (requests < slots), fall back
        to steps with any lane busy rather than reporting 0."""
        samples = self.util_samples or self.util_samples_any
        if not samples:
            return 0.0
        return sum(samples) / len(samples)

class Scheduler:
    """Drives requests through a fixed set of decode lanes over a KVPool."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        pool: KVPool,
        *,
        slots: int,
        max_len: int,
        token_budget: int | None = None,
        decode_per_round: int | None = None,
        sample: Callable[[np.ndarray], np.ndarray] | None = None,
        sampling: SamplingParams | None = None,
        prefill_chunk: int | None = None,
        residency=None,
        handoff: Callable[[PrefillHandoff], None] | None = None,
        prefix_cache=None,
        speculative=None,
        tracker=None,
        spans=None,
        ledger=None,
        mem_monitor=None,
    ):
        self.cfg = cfg
        self.params = params
        self.pool = pool
        self.slots = slots
        self.max_len = max_len
        self.n_table = pool.blocks_for(max_len)  # block-table width
        # whether the decode step's kernel reads only each lane's live
        # blocks; the reference formulation reads every lane's whole table
        from repro.kernels import ops

        self._reads_live = ops.paged_decode_runs_kernel(cfg.hd, pool.k)
        usable_tokens = pool.usable_blocks * pool.block_tokens
        self.token_budget = min(token_budget or usable_tokens, usable_tokens)
        # serving Eq. 2: R_F rounds of decode per admission round
        self.decode_per_round = decode_per_round or max(
            1, math.ceil(required_rf(slots))
        )
        # ``sample`` (a batched (B, V) -> (B,) callable) overrides the
        # seed-deterministic per-request sampler; default greedy either way
        self.sample = sample
        self.sampling = sampling or SamplingParams()
        # admission compute budget per prefill chunk: prompts longer than
        # this are split across rounds instead of monopolizing one round
        self.prefill_chunk = min(
            prefill_chunk or self.token_budget, self.token_budget
        )
        self.residency = residency
        # prefill-role engines export prefilled KV instead of decoding;
        # hybrid payloads additionally carry the SSM lane-state snapshot
        self.handoff = handoff
        # radix prefix cache (runtime.prefix_cache) over this pool: new
        # requests adopt their longest cached prefix's blocks and prefill
        # only the unmatched suffix
        if prefix_cache is not None:
            if cfg.family not in PREFIX_CACHE_FAMILIES:
                raise ValueError(
                    f"prefix caching covers {PREFIX_CACHE_FAMILIES}; "
                    f"family {cfg.family!r} cannot prefill a bare suffix"
                )
            if prefix_cache.pool is not pool:
                raise ValueError("prefix cache must index this pool")
        self.prefix_cache = prefix_cache
        # speculative decode (runtime.speculative.Speculator): a drafter
        # proposes depth-k chains per decode lane; one batched verify
        # call scores them against the pool and the longest accepted
        # prefix lands — token-identical to plain decode because the
        # verifier samples with the same (seed, rid, position) rng
        if speculative is not None and cfg.family not in SPEC_FAMILIES:
            raise ValueError(
                f"speculative decoding covers {SPEC_FAMILIES}; family "
                f"{cfg.family!r} has no draft-chain rollback path"
            )
        self.speculative = speculative
        self._verify = _jitted_verify(cfg) if speculative is not None else None
        self._prefill = _jitted_prefill(cfg)
        # hybrid chunks through the carried-state suffix step below, not
        # the stateless attention chunk step
        self._chunk_prefill = (
            _jitted_chunk_prefill(cfg)
            if cfg.family in CHUNKABLE_FAMILIES and cfg.family != "hybrid"
            else None
        )
        self._hybrid_suffix = (
            _jitted_hybrid_suffix(cfg) if cfg.family == "hybrid" else None
        )
        if residency is not None:
            from repro.runtime.residency.executor import cached_budgeted_step

            self._decode = cached_budgeted_step(cfg, residency)
        else:
            self._decode = _jitted_decode(cfg)
        self._chunk_cursor: dict[int, int] = {}
        # hybrid chunked prefill: the carried SSD/conv state between a
        # long prompt's chunks (leaves (L, 1, ...)), keyed like the
        # cursor; installed into the lane slot on the final chunk
        self._chunk_lane: dict[int, dict] = {}
        # hybrid: fixed-size per-lane SSM decode state, resident next to
        # the pool (the pool pages only the shared attention blocks' KV)
        self._lane_state = (
            init_ssm_lane_state(cfg, slots) if cfg.family == "hybrid" else None
        )
        self.queue: deque[Request] = deque()
        self.requests: dict[int, Request] = {}
        self.active: list[int | None] = [None] * slots
        self._token = np.zeros((slots, 1), np.int32)
        self._lengths = np.zeros((slots,), np.int32)
        # per-lane block tables, updated on admission / block growth /
        # completion only (not rebuilt every decode step); the device copy
        # is re-uploaded only when an event dirties the table
        self._block_table = np.full(
            (slots, self.n_table), SCRATCH_BLOCK, np.int32
        )
        self._block_table_dev = jnp.asarray(self._block_table)
        self._table_dirty = False
        self._next_rid = 0
        self.stats = SchedulerStats()
        # moe expert-load observability: cumulative per-(layer, expert)
        # routed-token tally fed by every serve step's counts output;
        # ``_emit_round`` derives the load-entropy / hot-expert gauges
        # from it. ``_expert_resident`` is the residency plan's pinned
        # (L, E) set — with no plan every expert is resident.
        self._expert_counts = (
            np.zeros((cfg.n_layers, cfg.n_experts), np.float64)
            if cfg.family == "moe"
            else None
        )
        self._expert_resident = None
        if cfg.family == "moe" and residency is not None:
            self._expert_resident = ~np.asarray(
                residency.expert_stream_mask(cfg), bool
            )
        # unified observability (runtime.tracker): one record per round,
        # emitted either straight to ``tracker`` or through ``on_round``
        # (a fleet Engine installs the hook so the record also carries
        # the post-round virtual clock). Counters are emitted as deltas
        # against ``_emit_base`` so replaying a stream reproduces the
        # totals exactly, wherever the counters were advanced.
        self.tracker = tracker
        self.on_round: Callable[[dict], None] | None = None
        self._emit_base: dict[str, int] = {}
        self._emit_ttft_base = 0
        # request-lifecycle spans (runtime.spans.SpanRecorder): queue /
        # prefix_lookup / prefill chunk / decode slice per request, with
        # exact-decomposition tiling, and the round's own phases. A fleet
        # Engine passes a recorder on its virtual clock; bare schedulers
        # may pass a wall-clock one.
        self.spans = spans
        # virtual-time charge hook: a fleet Engine installs this so each
        # unit of work advances the virtual clock at the instant it
        # happens (charge("prefill", tokens=, steps=) / ("decode",
        # steps=)) — per-request spans then carry true phase boundaries
        # instead of round-granular ones.
        self.charge: Callable[..., None] | None = None
        # logits hook: on_logits(rid, n, row) sees the (V,) row each
        # request's n-th output token is sampled from (correctness checks
        # compare these rows against a reference forward)
        self.on_logits: Callable[[int, int, np.ndarray], None] | None = None
        # open decode slices: rid -> [t_slice_start, steps] for the
        # contiguous decode steps a lane ran this round (one span each)
        self._decode_open: dict[int, list] = {}
        # event-sourced memory ledger (runtime.memledger.MemLedger):
        # every pool mutation emits a kind="mem" delta record; the round
        # emission syncs + flushes it *before* the gauge record so
        # integrated deltas equal the gauges at every round boundary
        self.ledger = ledger
        if ledger is not None and ledger.pool is None:
            ledger.attach(pool)
        # streaming pressure signal (runtime.memledger.MemPressureMonitor)
        # fed once per round — the elastic-fleet admission/scale input
        self.mem_monitor = mem_monitor
        if ledger is not None and residency is not None:
            # static owners: the weight-resident VMEM set and the expert
            # stream ring buffer, so byte attribution covers the whole
            # accelerator budget rather than just the KV pool
            ledger.reserve(
                "weight-resident",
                residency.resident_bytes,
                blocks=residency.resident_block_count,
            )
            ledger.reserve(
                "ring-slot", residency.ring_bytes, depth=residency.stream_ahead
            )
        if tracker is not None:
            hp = {
                "surface": "scheduler",
                "arch": cfg.name,
                "family": cfg.family,
                "slots": slots,
                "max_len": max_len,
                "token_budget": self.token_budget,
                "decode_per_round": self.decode_per_round,
                "prefill_chunk": self.prefill_chunk,
                "block_tokens": pool.block_tokens,
                "pool_blocks": pool.usable_blocks,
                "prefix_cache": prefix_cache is not None,
            }
            if speculative is not None:
                hp["speculate"] = speculative.name
                hp["spec_depth"] = speculative.depth
            if residency is not None:
                hp["residency"] = residency.summary()
            tracker.log_hyperparameters(hp)

    # ---------------- tracing ----------------

    @property
    def spans(self):
        return self._spans

    @spans.setter
    def spans(self, recorder) -> None:
        """Attach a span recorder. One with a tracker also records the
        decode program's scope table, here: a traced serving run attaches
        its recorder after warm-up, so the lowering this takes is served
        from JAX's caches and nothing compiles while requests run (a cold
        scheduler compiles its decode step here, not at its first step)."""
        self._spans = recorder
        if (
            recorder is not None
            and recorder.tracker is not None
            and self.handoff is None
            and self.speculative is None
        ):
            compiled = self._decode.lower(*self._decode_args()).compile()
            recorder.step_scopes(
                "decode", scope_table(compiled.as_text(), STEP_SCOPES)
            )

    def _phase(self, name: str, **attrs):
        """Round phase ``name`` (listed in ``runtime.spans``): the
        profiler annotation, and a record carrying ``attrs`` when the
        recorder is tracked."""
        if self._spans is None:
            return phase_annotation(name)
        return self._spans.phase(name, round=self.stats.rounds, **attrs)

    # ---------------- submission ----------------

    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        *,
        rid: int | None = None,
        t_submit: float | None = None,
    ) -> int:
        """Queue a request. ``rid`` lets a fleet router assign globally
        unique ids across engines — the sampler is keyed on (seed, rid,
        position), so a request keeps its exact token stream wherever it
        lands (and across a drain/requeue). ``t_submit`` anchors the
        request's queue span on the caller's clock (a router passes the
        client arrival time, so queue wait is measured from submission,
        not admission)."""
        total = len(prompt) + max_new_tokens
        if len(prompt) < 1 or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new_tokens >= 1")
        if total > self.max_len:
            raise ValueError(
                f"request needs {total} tokens > max_len {self.max_len}"
            )
        usable = self.pool.usable_blocks * self.pool.block_tokens
        if total > usable:
            raise ValueError(
                f"request needs {total} tokens > pool capacity {usable}"
            )
        # prompts over the admission token budget are legal for chunkable
        # families: they admit solo and prefill in budget-sized chunks
        # across rounds (hybrid carries the SSD/conv state between
        # chunks; moe routes dropless, so a chunk boundary is invisible
        # to the expert dispatch).
        if (
            total > self.token_budget
            and self.cfg.family not in CHUNKABLE_FAMILIES
        ):
            raise ValueError(
                f"request needs {total} tokens > token budget "
                f"{self.token_budget} ({self.cfg.family} prompts cannot "
                "chunk)"
            )
        if rid is None:
            rid = self._next_rid
        elif rid in self.requests:
            raise ValueError(f"request id {rid} already known")
        self._next_rid = max(self._next_rid, rid + 1)
        req = Request(rid, np.asarray(prompt, np.int32), max_new_tokens)
        req.t_submit = time.monotonic()
        req._enter(RequestState.QUEUED)
        self.queue.append(req)
        self.requests[rid] = req
        if self.spans is not None:
            self.spans.open(rid, "queue", t0=t_submit)
        return rid

    def drain(self) -> list[Request]:
        """Stop intake: pop and return every request this engine can
        still give up, so a router can requeue it elsewhere (sampling is
        rid-keyed, so the token stream survives the move).

        That covers the queue *and* any mid-flight chunked prefill: a
        request whose ``_chunk_cursor`` is live has a lane reserved and
        pool blocks partially written, but no token sampled yet — its
        blocks are released (refcounts make adopted prefix blocks safe),
        its cursor and carried hybrid chunk state dropped, and its lane
        returned, so the requeued request restarts cold with nothing
        leaked here. Decoding requests finish here normally (their
        sampled tokens exist only on this engine)."""
        out: list[Request] = []
        # aborted chunked prefills first: they are older than anything
        # still queued, and requeue order preserves FIFO fairness
        for slot, rid in enumerate(self.active):
            if rid is None or rid not in self._chunk_cursor:
                continue
            req = self.requests.pop(rid)
            del self._chunk_cursor[rid]
            self._chunk_lane.pop(rid, None)
            self.pool.release(rid)
            self.active[slot] = None
            self._token[slot, 0] = 0
            self._lengths[slot] = 0
            self._block_table[slot] = SCRATCH_BLOCK
            self._table_dirty = True
            req.output.clear()
            req._enter(RequestState.QUEUED)
            if self.spans is not None:
                self.spans.abort(rid, reason="drain")
            out.append(req)
        while self.queue:
            req = self.queue.popleft()
            del self.requests[req.rid]
            if self.spans is not None:
                self.spans.abort(req.rid, reason="drain")
            out.append(req)
        return out

    # ---------------- internals ----------------

    @property
    def committed_tokens(self) -> int:
        return sum(
            self.requests[r].total_tokens
            for r in self.active
            if r is not None
        )

    def _free_slot(self) -> int | None:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return None

    # ---------------- sampling ----------------

    def _sample_one(self, req: Request, row: np.ndarray) -> int:
        """Next token for one request from its (V,) logits row.

        Seed-deterministic: the rng is keyed on (seed, rid, position), so
        a request's output never depends on lane placement or co-resident
        requests (the staggered-lane invariant extends to sampling).
        """
        if self.on_logits is not None:
            self.on_logits(req.rid, len(req.output), row)
        if self.sample is not None:  # legacy batched override
            return int(self.sample(row[None, :])[0])
        sp = self.sampling
        rng = np.random.default_rng(
            np.random.SeedSequence([sp.seed, req.rid, len(req.output)])
        )
        return sample_logits(row, sp, rng)

    def _note_expert_counts(self, counts) -> None:
        """Fold one serve step's (L, E) routed-token tally into the run
        totals. Padded prompt rows and idle decode lanes route too (the
        dropless dispatch is per-token, so their routing is inert for
        outputs but still visible here) — the gauges are a load signal,
        not an exact busy-token count."""
        c = np.asarray(counts, np.float64)
        self._expert_counts += c
        self.stats.expert_tokens += int(c.sum())

    # ---------------- admission / prefill ----------------

    def _lane_snapshot(self, slot: int) -> dict:
        """Host copy of one lane's SSM state (leaves (L, 1, ...))."""
        return jax.tree.map(
            lambda v: np.asarray(v[:, slot : slot + 1]), self._lane_state
        )

    def _restore_lane(self, slot: int, snapshot: dict) -> None:
        self._lane_state = jax.tree.map(
            lambda dst, src: dst.at[:, slot].set(jnp.asarray(src)[:, 0]),
            self._lane_state,
            snapshot,
        )

    def _commit_prefix(self, slot: int, req: Request) -> None:
        """Index the freshly-prefilled prompt in the radix cache: full
        blocks become shared nodes; hybrids also anchor the SSM state at
        the exact prompt end (snapshot taken *before* decode advances
        it)."""
        if self.prefix_cache is None:
            return
        lane = (
            self._lane_snapshot(slot) if self.cfg.family == "hybrid" else None
        )
        self.prefix_cache.commit(
            req.prompt, self.pool.blocks_of(req.rid), lane_state=lane
        )

    def _start_decode(self, slot: int, req: Request, first: int) -> None:
        """Move a fully-prefilled request onto its decode lane — or, on a
        prefill-role engine, export it through the handoff hook instead."""
        req.t_first_token = time.monotonic()
        self.stats.ttfts.append(req.ttft)
        req.output.append(first)
        self._commit_prefix(slot, req)
        if self.handoff is not None:
            self._export_handoff(slot, req)
            return
        if self.spans is not None:
            # the first token exists the instant its prefill step ends
            self.spans.event("first", req.rid)
        req._enter(RequestState.DECODE)
        p = len(req.prompt)
        self._token[slot, 0] = first
        self._lengths[slot] = p
        self._block_table[slot] = self.pool.table_of(req.rid, self.n_table)
        self._table_dirty = True
        if self.speculative is not None:
            self._start_drafter(slot, req)
        if len(req.output) >= req.max_new_tokens:
            self._complete(slot)

    def _start_drafter(self, slot: int, req: Request) -> None:
        """Warm the drafter's lane for a request entering decode. A model
        drafter prefills the prompt through its own weights (the target's
        prefix-cache hits don't transfer), charged at the drafter's
        roofline and attributed to a ``draft`` span."""
        t0 = self.spans.now() if self.spans is not None else 0.0
        tokens, steps = self.speculative.start_lane(slot, req.prompt)
        if tokens or steps:
            if self.charge is not None:
                self.charge("draft", tokens=tokens, steps=steps)
            if self.spans is not None:
                self.spans.mark(
                    req.rid, "draft", t0, self.spans.now(), tokens=tokens
                )

    def _export_handoff(self, slot: int, req: Request) -> None:
        """Ship a prefilled request's KV (in block-id order) off-engine
        and reclaim its lane and blocks immediately."""
        rid = req.rid
        p = len(req.prompt)
        block_ids, ks, vs = self.pool.export_blocks(rid, n_tokens=p)
        payload = PrefillHandoff(
            rid=rid,
            prompt=req.prompt,
            max_new_tokens=req.max_new_tokens,
            first_token=req.output[0],
            n_tokens=p,
            block_ids=block_ids,
            block_tokens=self.pool.block_tokens,
            k=ks,
            v=vs,
            lane_state=(
                self._lane_snapshot(slot)
                if self.cfg.family == "hybrid"
                else None
            ),
        )
        req._enter(RequestState.HANDOFF)
        self.pool.release(rid)
        self.active[slot] = None
        self.stats.handoffs += 1
        self.handoff(payload)

    def import_prefilled(
        self, payload: PrefillHandoff, *, ready_at: float | None = None
    ) -> bool:
        """Adopt a request prefilled on another engine: admit its full
        token commitment, scatter the handed-off KV rows into the pool,
        and start its decode lane at the next position. Returns False
        (without side effects) when no lane / budget / pool room is free.
        ``ready_at`` is the payload's interconnect-ready time — the span
        timeline resumes there, so any import backlog shows as ``wait``.
        """
        if payload.rid in self.requests:
            raise ValueError(f"request {payload.rid} already on this engine")
        slot = self._free_slot()
        if slot is None:
            return False
        total = payload.total_tokens
        if self.committed_tokens + total > self.token_budget:
            return False
        if not self.pool.can_admit(total):
            return False
        if self.cfg.family == "hybrid" and payload.lane_state is None:
            raise ValueError(
                f"hybrid handoff of request {payload.rid} lacks the SSM "
                "lane state; decode cannot resume from KV rows alone"
            )
        req = Request(
            payload.rid,
            np.asarray(payload.prompt, np.int32),
            payload.max_new_tokens,
        )
        req.t_submit = time.monotonic()
        req.t_first_token = req.t_submit  # first token arrived with the KV
        req.output.append(payload.first_token)
        req._enter(RequestState.DECODE)
        self.requests[payload.rid] = req
        self.pool.admit(payload.rid, total)
        self.pool.write_prefill(
            payload.rid, payload.k, payload.v, n_tokens=payload.n_tokens
        )
        if self.cfg.family == "hybrid":
            self._restore_lane(slot, payload.lane_state)
        if self.prefix_cache is not None:
            # the imported KV warms this engine's cache too
            self.prefix_cache.commit(
                req.prompt,
                self.pool.blocks_of(payload.rid),
                lane_state=payload.lane_state,
            )
        self._next_rid = max(self._next_rid, payload.rid + 1)
        self.active[slot] = payload.rid
        self._token[slot, 0] = payload.first_token
        self._lengths[slot] = payload.n_tokens
        self._block_table[slot] = self.pool.table_of(
            payload.rid, self.n_table
        )
        self._table_dirty = True
        if self.spans is not None:
            now = self.spans.now()
            t_ready = now if ready_at is None else min(ready_at, now)
            self.spans.seed(payload.rid, t_ready)
            if now > t_ready:
                self.spans.mark(
                    payload.rid, "wait", t_ready, now, reason="import"
                )
            # the first token arrived with the payload: it becomes
            # client-visible the instant this engine adopts it
            self.spans.event("first", payload.rid, now)
        if self.speculative is not None:
            self._start_drafter(slot, req)
        if len(req.output) >= req.max_new_tokens:
            self._complete(slot)
        return True

    def _admit_one(self) -> bool:
        """Admit the head-of-queue request if resources allow.

        Prompts within the admission budget prefill in one batched step;
        longer (chunkable-family) prompts are admitted only when no other
        request holds budget, then stream through ``prefill_chunk``-sized
        rounds so admission never stalls decode for a whole long prompt.
        """
        if not self.queue:
            return False
        slot = self._free_slot()
        if slot is None:
            return False
        req = self.queue[0]
        over_budget = (
            self.committed_tokens + req.total_tokens > self.token_budget
        )
        if over_budget and self.committed_tokens > 0:
            return False
        if not self.pool.can_admit(req.total_tokens):
            return False
        self.queue.popleft()
        req._enter(RequestState.PREFILL)
        t_admit = 0.0
        if self.spans is not None:
            t_admit = self.spans.close(req.rid)  # ends the queue span
            self.spans.event("admit", req.rid, t_admit)
        self.pool.admit(req.rid, req.total_tokens)
        p = len(req.prompt)

        # radix-cache lookup: adopt the longest cached prefix's blocks
        # (refcount bump; COW for a partially-matched block) and charge
        # prefill only for the unmatched suffix
        match = None
        if self.prefix_cache is not None:
            match = self.prefix_cache.lookup(
                req.prompt, anchor=(self.cfg.family == "hybrid")
            )
        if match is not None:
            self.pool.adopt_prefix(
                req.rid, match.shared, match.tail_block, match.matched
            )
            self.stats.prefix_hits += 1
            self.stats.prefix_hit_tokens += match.matched
        if self.spans is not None and self.prefix_cache is not None:
            # zero-width on the virtual clock: the lookup is bookkeeping,
            # but its matched-prefix length is the tuning signal
            self.spans.mark(
                req.rid,
                "prefix_lookup",
                t_admit,
                t_admit,
                matched=match.matched if match is not None else 0,
                hit=match is not None,
            )

        if self.cfg.family in CHUNKABLE_FAMILIES and (
            match is not None or p > self.prefill_chunk
        ):
            # chunked prefill: reserve the lane now, feed chunks per
            # round, starting past the matched prefix (0 on a miss).
            # Hybrid chunks resume the SSD/conv recurrence from the
            # carried state: the anchor's snapshot on a warm hit, the
            # zero state cold — a warm suffix within one chunk is
            # exactly the old single-shot suffix prefill.
            self.active[slot] = req.rid
            self._chunk_cursor[req.rid] = match.matched if match else 0
            if self.cfg.family == "hybrid":
                self._chunk_lane[req.rid] = (
                    jax.tree.map(jnp.asarray, match.lane_state)
                    if match is not None
                    else init_ssm_lane_state(self.cfg, 1)
                )
            self._prefill_one_chunk(slot)
            return True

        if self.cfg.family == "hybrid":
            # the hybrid SSD state integrates every position (a padded
            # tail would pollute the handed-over state), so hybrid
            # prefills unpadded — one trace per length. MoE buckets like
            # dense: dropless routing makes padded rows inert.
            bucket = p
        else:
            bucket = max(
                self.pool.block_tokens,
                -(-p // self.pool.block_tokens) * self.pool.block_tokens,
            )
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :p] = req.prompt
        t0 = self.spans.now() if self.spans is not None else 0.0
        if self.cfg.family == "hybrid":
            logits, ks, vs, lane = self._prefill(
                self.params, jnp.asarray(padded), p - 1
            )
            # the request's post-prompt SSM state moves into its lane slot
            self._lane_state = jax.tree.map(
                lambda dst, src: dst.at[:, slot].set(src[:, 0]),
                self._lane_state,
                lane,
            )
        elif self.cfg.family == "moe":
            logits, ks, vs, counts = self._prefill(
                self.params, jnp.asarray(padded), p - 1
            )
            self._note_expert_counts(counts)
        else:
            logits, ks, vs = self._prefill(
                self.params, jnp.asarray(padded), p - 1
            )
        self.pool.write_prefill(req.rid, ks[:, 0], vs[:, 0], n_tokens=p)
        self.stats.prefill_steps += 1
        self.stats.prefill_tokens += p
        if self.charge is not None:
            self.charge("prefill", tokens=p, steps=1)
        if self.spans is not None:
            self.spans.mark(
                req.rid, "prefill", t0, self.spans.now(), tokens=p
            )

        first = self._sample_one(req, np.asarray(logits[0, 0, :]))
        self.active[slot] = req.rid
        self._start_decode(slot, req, first)
        return True

    def _prefill_one_chunk(self, slot: int) -> None:
        """Run one ``prefill_chunk``-sized piece of a long prompt.

        Attention families pad the chunk to the fixed chunk width (one
        trace total); the padding's rows land past the prompt, where
        nothing valid lies yet. Hybrid chunks run *unpadded* —
        the SSD state integrates every fed position, so a padded tail
        would pollute the carried state — and thread ``_chunk_lane``
        through ``lm.prefill_suffix_paged_hybrid``: each chunk resumes
        the recurrence exactly where the previous one stopped, which is
        why chunked hybrid prefill is token-identical to single-shot.
        """
        with self._phase("prefill_chunk"):
            rid = self.active[slot]
            req = self.requests[rid]
            c0 = self._chunk_cursor[rid]
            p = len(req.prompt)
            c = self.prefill_chunk
            n = min(c, p - c0)
            t0 = self.spans.now() if self.spans is not None else 0.0
            self.pool.note_tokens(rid, c0 + n)
            table = jnp.asarray(self.pool.table_of(rid, self.n_table)[None])
            if self.cfg.family == "hybrid":
                logits, self.pool.k, self.pool.v, self._chunk_lane[rid] = (
                    self._hybrid_suffix(
                        self.params,
                        jnp.asarray(req.prompt[c0 : c0 + n][None]),
                        self.pool.k,
                        self.pool.v,
                        table,
                        jnp.asarray(c0, jnp.int32),
                        jnp.asarray(n - 1, jnp.int32),
                        self._chunk_lane[rid],
                    )
                )
            else:
                tokens = np.zeros((1, c), np.int32)
                tokens[0, :n] = req.prompt[c0 : c0 + n]
                out = self._chunk_prefill(
                    self.params,
                    jnp.asarray(tokens),
                    self.pool.k,
                    self.pool.v,
                    table,
                    jnp.asarray(c0, jnp.int32),
                    jnp.asarray(n - 1, jnp.int32),
                )
                if self.cfg.family == "moe":
                    logits, self.pool.k, self.pool.v, counts = out
                    self._note_expert_counts(counts)
                else:
                    logits, self.pool.k, self.pool.v = out
            self.stats.prefill_steps += 1
            self.stats.prefill_tokens += n
            if self.charge is not None:
                self.charge("prefill", tokens=n, steps=1)
            if self.spans is not None:
                self.spans.mark(
                    rid, "prefill", t0, self.spans.now(), tokens=n,
                    chunk_start=c0,
                )
            self._chunk_cursor[rid] = c0 + n
            if c0 + n >= p:
                del self._chunk_cursor[rid]
                if self.cfg.family == "hybrid":
                    # the post-prompt state moves into the decode lane slot
                    lane = self._chunk_lane.pop(rid)
                    self._lane_state = jax.tree.map(
                        lambda dst, src: dst.at[:, slot].set(src[:, 0]),
                        self._lane_state,
                        lane,
                    )
                first = self._sample_one(req, np.asarray(logits[0, 0, :]))
                self._start_decode(slot, req, first)

    def _commit_generated(self, slot: int, req: Request) -> None:
        """Re-index the finished conversation — prompt *plus* generated
        tokens — so a multi-turn follow-up (prompt = this prompt + this
        response + new text) adopts the whole transcript's blocks, not
        just the original prompt's. The last sampled token was never fed
        back through the model and has no KV row, so the committed
        sequence stops one short of the full output. Must run before
        ``pool.release``: the cache pins blocks of a live request."""
        if self.prefix_cache is None:
            return
        seq = np.concatenate(
            [req.prompt, np.asarray(req.output[:-1], np.int32)]
        )
        if len(seq) == len(req.prompt):
            return  # 1-token request: the prompt commit already covers it
        lane = (
            self._lane_snapshot(slot) if self.cfg.family == "hybrid" else None
        )
        self.prefix_cache.commit(
            seq, self.pool.blocks_of(req.rid), lane_state=lane
        )

    def _complete(self, slot: int) -> None:
        rid = self.active[slot]
        req = self.requests[rid]
        req._enter(RequestState.DONE)
        self._commit_generated(slot, req)
        if self.speculative is not None:
            self.speculative.release_lane(slot)
        self.pool.release(rid)
        self.active[slot] = None
        self._token[slot, 0] = 0
        self._lengths[slot] = 0
        self._block_table[slot] = SCRATCH_BLOCK
        self._table_dirty = True
        self.stats.completed += 1
        self.stats.generated_tokens += len(req.output)
        if self.spans is not None:
            t = self.spans.now()
            sl = self._decode_open.pop(rid, None)
            if sl is not None:
                # completion lands exactly on this decode slice's end
                self.spans.mark(rid, "decode", sl[0], t, steps=sl[1])
            self.spans.event("done", rid, t)
            self.spans.forget(rid)

    def _decoding(self, rid: int | None) -> bool:
        return (
            rid is not None
            and self.requests[rid].state is RequestState.DECODE
        )

    def _decode_args(self) -> tuple:
        """The decode program's arguments for the current lanes."""
        args = (
            self.params,
            jnp.asarray(self._token),
            self.pool.k,
            self.pool.v,
            self._block_table_dev,
            jnp.asarray(self._lengths),
        )
        if self.cfg.family == "hybrid":
            args += (self._lane_state,)
        return args

    def _sync_table(self, i: int, rid: int, before: int) -> None:
        """Refresh lane ``i``'s block table if its request's block count
        moved from ``before``."""
        if self.pool.blocks_held(rid) != before:
            self._block_table[i] = self.pool.table_of(rid, self.n_table)
            self._table_dirty = True

    def _upload_table(self) -> None:
        if self._table_dirty:
            self._block_table_dev = jnp.asarray(self._block_table)
            self._table_dirty = False

    def _decode_step(self) -> None:
        # the kernel reads every lane's live blocks, the scratch block of
        # an idle one too; the reference reads every table entry
        table = self.slots * self.n_table
        read = table
        if self._reads_live:
            first, end = live_block_span(
                self._lengths + 1, self.pool.block_tokens,
                decode_window(self.cfg),
            )
            read = int((end - first).sum())
        self.stats.kv_blocks_read += read
        with self._phase(
            "decode_dispatch", kv_blocks=read, kv_table_blocks=table
        ):
            t0_step = self.spans.now() if self.spans is not None else 0.0
            for i, rid in enumerate(self.active):
                if not self._decoding(rid):
                    continue  # empty lane, or a mid-chunked-prefill lane
                # room for the incoming token's KV row
                before = self.pool.blocks_held(rid)
                self.pool.note_tokens(rid, int(self._lengths[i]) + 1)
                self._sync_table(i, rid, before)
            self._upload_table()
            out = self._decode(*self._decode_args())
        if self.cfg.family == "hybrid":
            logits, self.pool.k, self.pool.v, self._lane_state = out
        elif self.cfg.family == "moe":
            logits, self.pool.k, self.pool.v, counts = out
            self._note_expert_counts(counts)
        else:
            logits, self.pool.k, self.pool.v = out
        self.stats.decode_steps += 1
        if self.charge is not None:
            self.charge("decode", steps=1)
        if self.spans is not None:
            # extend (or open) each participating lane's decode slice;
            # a lane's contiguous steps this round become one span
            for rid in self.active:
                if self._decoding(rid):
                    sl = self._decode_open.get(rid)
                    if sl is None:
                        self._decode_open[rid] = [t0_step, 1]
                    else:
                        sl[1] += 1
        with self._phase("logits_fetch"):
            rows = np.asarray(logits[:, 0, :])
        with self._phase("sample"):
            pool_st = self.pool.stats()
            util = pool_st.utilization
            self.stats.shared_blocks_peak = max(
                self.stats.shared_blocks_peak, pool_st.shared_blocks
            )
            self.stats.util_samples_any.append(util)
            if all(r is not None for r in self.active):
                self.stats.util_samples.append(util)
            for i, rid in enumerate(self.active):
                if not self._decoding(rid):
                    continue
                req = self.requests[rid]
                nxt = self._sample_one(req, rows[i])
                req.output.append(nxt)
                self._token[i, 0] = nxt
                self._lengths[i] += 1
                if len(req.output) >= req.max_new_tokens:
                    self._complete(i)

    def _spec_step(self) -> None:
        """One speculate-and-verify cycle over every decoding lane.

        The drafter proposes up to ``depth - 1`` tokens per lane; ONE
        batched ``verify_chunk_paged`` call then feeds each lane's
        pending token plus its proposals at the lane's own offsets,
        writing their KV rows and returning the target's logits at every
        chain position. Sampling position ``m`` with the non-speculative
        rng key (seed, rid, m) makes longest-accepted-prefix selection
        deterministic — and the output token-identical to plain decode,
        since each position's logits depend only on accepted tokens.
        Rejected rows cost nothing: ``end_draft`` pops the surplus
        blocks (owner="draft" in the ledger) and the stale rows are
        overwritten by the next chain before any unmasked gather.
        """
        lanes = [
            (i, rid)
            for i, rid in enumerate(self.active)
            if self._decoding(rid)
        ]
        if not lanes:
            return
        t0 = self.spans.now() if self.spans is not None else 0.0
        views: list[LaneDraft] = []
        k_eff: dict[int, int] = {}
        for i, rid in lanes:
            req = self.requests[rid]
            # never draft past the request's commitment: the chain ends
            # at row p + max_new - 1 at most, so begin_draft stays
            # within the admitted block budget
            k_eff[rid] = min(
                self.speculative.depth,
                req.max_new_tokens - len(req.output),
            )
            views.append(
                LaneDraft(
                    slot=i,
                    rid=rid,
                    pending=int(self._token[i, 0]),
                    out_len=len(req.output),
                    n_rows=int(self._lengths[i]),
                    history=np.concatenate(
                        [req.prompt, np.asarray(req.output, np.int32)]
                    ),
                )
            )
        kmax = max(k_eff.values())
        props: dict[int, np.ndarray] = {}
        if kmax > 1:
            proposed, draft_steps = self.speculative.propose(
                views, kmax, self.sampling
            )
            for v, row in zip(views, proposed):
                props[v.rid] = row
            self.stats.draft_tokens += sum(
                k_eff[rid] - 1 for _, rid in lanes
            )
            if self.charge is not None and draft_steps:
                self.charge("draft", steps=draft_steps)
        t1 = self.spans.now() if self.spans is not None else t0
        with self._phase("decode_dispatch"):
            # room for every lane's chain rows: draft-class blocks,
            # settled (or fully returned) by end_draft after acceptance
            for i, rid in lanes:
                before = self.pool.blocks_held(rid)
                self.pool.begin_draft(
                    rid, int(self._lengths[i]) + k_eff[rid]
                )
                self._sync_table(i, rid, before)
            self._upload_table()
            tokens = np.zeros((self.slots, kmax), np.int32)
            starts = np.zeros((self.slots,), np.int32)
            for i, rid in lanes:
                ke = k_eff[rid]
                tokens[i, 0] = self._token[i, 0]
                if ke > 1:
                    tokens[i, 1:ke] = props[rid][: ke - 1]
                starts[i] = self._lengths[i]
            out = self._verify(
                self.params,
                jnp.asarray(tokens),
                self.pool.k,
                self.pool.v,
                self._block_table_dev,
                jnp.asarray(starts),
            )
        if self.cfg.family == "moe":
            logits, self.pool.k, self.pool.v, counts = out
            self._note_expert_counts(counts)
        else:
            logits, self.pool.k, self.pool.v = out
        self.stats.verify_steps += 1
        if self.charge is not None:
            # one weight sweep plus the chain's extra compute tokens
            self.charge(
                "verify",
                steps=1,
                tokens=sum(k_eff.values()) - len(lanes),
            )
        t2 = self.spans.now() if self.spans is not None else t0
        if self.spans is not None:
            for i, rid in lanes:
                if kmax > 1:
                    self.spans.mark(
                        rid, "draft", t0, t1, tokens=k_eff[rid] - 1
                    )
                self.spans.mark(rid, "verify", t1, t2, depth=k_eff[rid])
        with self._phase("logits_fetch"):
            rows = np.asarray(logits)
        with self._phase("sample"):
            done_slots: list[int] = []
            for i, rid in lanes:
                req = self.requests[rid]
                ke = k_eff[rid]
                n0 = int(self._lengths[i])
                accepted = 0
                for j in range(ke):
                    nxt = self._sample_one(req, rows[i, j])
                    req.output.append(nxt)
                    accepted += 1
                    self._token[i, 0] = nxt
                    if j < ke - 1 and nxt != int(props[rid][j]):
                        break  # correction token accepted, chain tail rejected
                self.stats.accepted_tokens += accepted
                self._lengths[i] = n0 + accepted
                before = self.pool.blocks_held(rid)
                self.pool.end_draft(rid, n0 + accepted)
                self._sync_table(i, rid, before)
                self.speculative.accept(i, n0 + accepted)
                if len(req.output) >= req.max_new_tokens:
                    done_slots.append(i)
            # sample pool pressure with every accept settled but finished
            # requests still resident (the decode-step analog)
            pool_st = self.pool.stats()
            self.stats.shared_blocks_peak = max(
                self.stats.shared_blocks_peak, pool_st.shared_blocks
            )
            self.stats.util_samples_any.append(pool_st.utilization)
            if all(r is not None for r in self.active):
                self.stats.util_samples.append(pool_st.utilization)
            for i in done_slots:
                self._complete(i)

    # ---------------- main loop ----------------

    def round(self) -> None:
        """One scheduler round: drain admissions, advance one chunk of any
        mid-prefill long prompt, then R_F decode steps (speculate-and-
        verify cycles when a drafter is installed)."""
        with self._phase("admit"):
            while self._admit_one():
                pass
        for i, rid in enumerate(self.active):
            if rid is not None and rid in self._chunk_cursor:
                self._prefill_one_chunk(i)
        step = (
            self._spec_step if self.speculative is not None
            else self._decode_step
        )
        t0 = time.monotonic()
        for _ in range(self.decode_per_round):
            if not any(self._decoding(r) for r in self.active):
                break
            step()
        self.stats.decode_time += time.monotonic() - t0
        with self._phase("round_tail"):
            if self.spans is not None and self._decode_open:
                # close still-running lanes' slices at the round's decode end
                t = self.spans.now()
                for rid, (ts, steps) in self._decode_open.items():
                    self.spans.mark(rid, "decode", ts, t, steps=steps)
                self._decode_open.clear()
            self.stats.rounds += 1
            if self.mem_monitor is not None:
                self.mem_monitor.observe(
                    t=(
                        self.spans.now()
                        if self.spans is not None
                        else float(self.stats.rounds)
                    ),
                    pool=self.pool,
                    evicted_blocks=(
                        self.prefix_cache.evicted_blocks
                        if self.prefix_cache is not None
                        else 0
                    ),
                )
            if self.tracker is not None or self.on_round is not None:
                self._emit_round()
        if self.spans is not None:
            self.spans.flush()

    # ---------------- observability ----------------

    def _emit_round(self) -> None:
        """One structured record per round (see ``runtime.tracker``).

        Counters are deltas against the previous emission — not against
        the round's start — so work done outside ``round()`` (a decode
        engine's ``import_prefilled``, a drain) is still accounted to
        the next record and replaying the stream reproduces the totals
        exactly."""
        s = self.stats
        # mem-ledger barrier: fold un-evented note_tokens drift into one
        # sync record and flush the buffer *now*, before the gauge record
        # below is built (and possibly deferred through on_round) — every
        # mem record therefore precedes, on the stream, the metrics
        # record its deltas must integrate to (validate_ledger's exactness
        # contract at round granularity).
        if self.ledger is not None:
            self.ledger.sync()
            self.ledger.flush()
        rec: dict = {"round": s.rounds}
        # the delta set is the tracker's replay contract (DELTA_KEYS):
        # one source of truth, drift-guarded by delta_coverage_gaps
        for k in DELTA_KEYS:
            cur = getattr(s, k)
            rec[k] = cur - self._emit_base.get(k, 0)
            self._emit_base[k] = cur
        rec["ttfts"] = [
            round(t, 6) for t in s.ttfts[self._emit_ttft_base :]
        ]
        self._emit_ttft_base = len(s.ttfts)
        rec["queued"] = len(self.queue)
        rec["queued_tokens"] = sum(r.total_tokens for r in self.queue)
        rec["active"] = sum(r is not None for r in self.active)
        rec["committed_tokens"] = self.committed_tokens
        rec["chunked_prefills"] = len(self._chunk_cursor)
        p = self.pool.stats()
        rec.update(
            pool_utilization=round(p.utilization, 4),
            pool_occupancy=round(p.occupancy, 4),
            pool_free_blocks=p.free_blocks,
            pool_held_blocks=p.held_blocks,
            pool_held_tokens=p.held_tokens,
            pool_committed_blocks=p.committed_blocks,
            pool_shared_blocks=p.shared_blocks,
            pool_cached_blocks=p.cached_blocks,
            pool_evictable_blocks=p.evictable_blocks,
            pool_alloc_blocks=self.pool.alloc_blocks,
            pool_freed_blocks=self.pool.freed_blocks,
            pool_cow_copies=self.pool.cow_copies,
        )
        if self.residency is not None:
            # live residency gauges (satellite of ISSUE 9): what the
            # startup print used to say once, per round — plus the
            # cumulative streamed-traffic integral the Perfetto export
            # differentiates into an HBM MiB/s counter track
            rp = self.residency
            rec.update(
                residency_resident_bytes=int(rp.resident_bytes),
                residency_streamed_bytes_per_step=round(
                    rp.streamed_bytes_per_step, 3
                ),
                residency_hbm_traffic_reduction=round(
                    rp.hbm_traffic_reduction, 4
                ),
                residency_streamed_mib=round(
                    s.decode_steps * rp.streamed_bytes_per_step / 2**20, 6
                ),
            )
        if self.prefix_cache is not None:
            c = self.prefix_cache.stats()
            rec.update(
                cache_nodes=c["nodes"],
                cache_anchors=c["anchors"],
                cache_evicted_blocks=c["evicted_blocks"],
            )
        if self._expert_counts is not None:
            tot = float(self._expert_counts.sum())
            if tot > 0:
                # gauges over the cumulative (L, E) tally: normalized
                # load entropy (1.0 = perfectly balanced) and the
                # fraction of routed tokens that hit a resident expert
                # (1.0 with no residency plan — everything is pinned)
                pe = self._expert_counts.sum(axis=0) / tot
                ent = float(-(pe * np.log(np.maximum(pe, 1e-12))).sum())
                rec["moe_expert_entropy"] = round(
                    ent / math.log(max(2, self.cfg.n_experts)), 4
                )
                hot = (
                    self._expert_resident
                    if self._expert_resident is not None
                    else np.ones(self._expert_counts.shape, bool)
                )
                rec["moe_hot_expert_fraction"] = round(
                    float(self._expert_counts[hot].sum()) / tot, 4
                )
            if self._expert_resident is not None:
                # live (L, E) stream-mask occupancy: which streamed slots
                # the routing actually touched so far — a dead streamed
                # expert is a candidate to swap into the resident set
                streamed = ~self._expert_resident
                n_streamed = int(streamed.sum())
                rec["moe_streamed_experts"] = n_streamed
                rec["moe_stream_mask_occupancy"] = round(
                    float((self._expert_counts[streamed] > 0).sum())
                    / max(1, n_streamed),
                    4,
                )
        if self.on_round is not None:
            self.on_round(rec)
        else:
            self.tracker.log_metrics(rec, step=s.rounds)

    def run(self, max_rounds: int | None = None) -> SchedulerStats:
        """Drain the queue to empty and finish every in-flight request."""
        limit = max_rounds or 64 + sum(
            r.total_tokens for r in self.requests.values()
        )
        while self.queue or any(r is not None for r in self.active):
            if self.stats.rounds >= limit:
                raise RuntimeError(
                    f"scheduler failed to drain: {len(self.queue)} queued, "
                    f"{sum(r is not None for r in self.active)} active after "
                    f"{self.stats.rounds} rounds"
                )
            self.round()
        self.pool.validate()
        if self.ledger is not None:
            # releases after the last emitted round would otherwise sit
            # in the buffer; a trailing sync keeps the stream complete
            self.ledger.sync()
            self.ledger.flush()
        return self.stats

    def outputs(self) -> dict[int, list[int]]:
        return {rid: req.output for rid, req in self.requests.items()}
