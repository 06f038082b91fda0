"""Step builders: one jittable train / prefill / serve step per config.

These close over the ``ModelConfig`` and optimizer so the same callable
serves the smoke tests (1 CPU device), the end-to-end examples, and the
512-device dry-run (where it is lowered with sharded ShapeDtypeStructs).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import encdec as encdec_lib
from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamW


def _split_batch(cfg: ModelConfig, batch: dict):
    kwargs = {}
    if cfg.family == "vlm":
        kwargs["prefix_embeds"] = batch["prefix_embeds"]
    return batch["tokens"], batch["labels"], kwargs


def make_loss_fn(
    cfg: ModelConfig, *, remat: str = "full", ce_chunk: int = 0
) -> Callable:
    def loss(params, batch):
        if cfg.family == "encdec":
            l, _ = encdec_lib.loss_fn(
                params, cfg, batch["tokens"], batch["labels"], batch["frames"]
            )
            return l
        tokens, labels, kw = _split_batch(cfg, batch)
        l, _ = lm.loss_fn(
            params, cfg, tokens, labels,
            remat=remat, ce_chunk=ce_chunk, **kw,
        )
        return l

    return loss


def make_train_step(
    cfg: ModelConfig,
    opt: AdamW | None = None,
    *,
    remat: str = "full",
    ce_chunk: int = 0,
) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""
    opt = opt or AdamW()
    loss = make_loss_fn(cfg, remat=remat, ce_chunk=ce_chunk)

    def step(params, opt_state, batch):
        # allow_int: FCMP-packed uint8 carriers are inference-only leaves;
        # they get float0 tangents here and AdamW skips them entirely.
        l, grads = jax.value_and_grad(loss, allow_int=True)(params, batch)
        new_params, new_state = opt.update(grads, opt_state, params)
        return new_params, new_state, {"loss": l}

    return step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """(params, batch) -> next-token logits (B, 1, V).

    Slices the hidden states *before* the unembedding so the full (B, S, V)
    logits tensor is never built — at 32k x 128k-vocab that tensor is the
    whole HBM budget (EXPERIMENTS.md §Perf).
    """

    from repro.models.layers import logits as unembed_logits

    def step(params, batch):
        if cfg.family == "encdec":
            x, _ = encdec_lib.trunk(
                params, cfg, batch["tokens"], batch["frames"]
            )
        else:
            tokens, _, kw = _split_batch(cfg, batch)
            x, _ = lm.trunk(params, cfg, tokens, **kw)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        return unembed_logits(x[:, -1:, :], table, cfg.vocab)

    return step


def make_serve_step(cfg: ModelConfig) -> Callable:
    """(params, token, cache) -> (logits (B, 1, V), new cache)."""

    def step(params, token, cache):
        if cfg.family == "encdec":
            return encdec_lib.decode_step(params, cfg, token, cache)
        return lm.decode_step(params, cfg, token, cache)

    return step


def make_paged_serve_step(cfg: ModelConfig) -> Callable:
    """Pool-indexed serve step for the continuous-batching scheduler.

    (params, token (B,1), pool_k, pool_v, block_table (B, nb), lengths
    (B,)) -> (logits (B,1,V), new pool_k, new pool_v). Each decode lane
    writes the new token's row into its block of the shared physical pool
    and reads its live blocks through ``block_table`` — the analog of the
    paper's round-robin port schedule over a packed BRAM. The moe family
    appends a per-layer expert-load tally (L, E) to the return. Jit with
    ``donate_argnums=(2, 3)`` so the pool updates in place.
    """

    if cfg.family == "hybrid":
        # extended signature: the per-lane SSM state travels with the step
        # (params, token, pool_k, pool_v, block_table, lengths, lane_state)
        # -> (logits, pool_k, pool_v, lane_state)
        def hybrid_step(
            params, token, pool_k, pool_v, block_table, lengths, lane_state
        ):
            return lm.decode_step_paged_hybrid(
                params, cfg, token, pool_k, pool_v, block_table, lengths,
                lane_state,
            )

        return hybrid_step

    def step(params, token, pool_k, pool_v, block_table, lengths):
        return lm.decode_step_paged(
            params, cfg, token, pool_k, pool_v, block_table, lengths
        )

    return step


def make_pool_prefill_step(cfg: ModelConfig) -> Callable:
    """Batched prefill that returns the KV rows for pool insertion.

    (params, tokens (B, S), last_idx ()) -> (next-token logits (B, 1, V),
    ks, vs stacked (L, B, S, n_kv, hd)). One call fills a request's whole
    prompt — time-to-first-token is one step, not S serve steps. The
    hybrid step additionally returns the per-lane SSM state dict
    (``lm.prefill_with_cache_hybrid``); the moe step appends a per-layer
    expert-load tally (L, E).
    """

    if cfg.family == "hybrid":
        def hybrid_step(params, tokens, last_idx):
            return lm.prefill_with_cache_hybrid(params, cfg, tokens, last_idx)

        return hybrid_step

    def step(params, tokens, last_idx):
        return lm.prefill_with_cache(params, cfg, tokens, last_idx)

    return step


def make_chunk_prefill_step(cfg: ModelConfig) -> Callable:
    """One prompt-chunk prefill against the pool (chunked admission).

    (params, tokens (B, C), pool_k, pool_v, block_table (B, nb), start
    (), last_idx ()) -> (logits at last_idx (B, 1, V), new pool_k, new
    pool_v). ``start`` is traced, so one trace serves every chunk offset
    of every request. Jit with ``donate_argnums=(2, 3)`` so the pool
    updates in place.
    """

    def step(params, tokens, pool_k, pool_v, block_table, start, last_idx):
        return lm.prefill_chunk_paged(
            params, cfg, tokens, pool_k, pool_v, block_table, start,
            last_idx,
        )

    return step


def make_verify_step(cfg: ModelConfig) -> Callable:
    """Batched draft-chain verification against the pool (speculative).

    (params, tokens (B, C), pool_k, pool_v, block_table (B, nb), starts
    (B,)) -> (full logits (B, C, V), new pool_k, new pool_v). One call
    scores every lane's pending token plus its drafter proposals at
    per-lane offsets; ``runtime.speculative`` turns the returned
    distributions into a longest-accepted prefix. Jit with
    ``donate_argnums=(2, 3)`` so the pool updates in place.
    """

    def step(params, tokens, pool_k, pool_v, block_table, starts):
        return lm.verify_chunk_paged(
            params, cfg, tokens, pool_k, pool_v, block_table, starts
        )

    return step


def make_hybrid_suffix_prefill_step(cfg: ModelConfig) -> Callable:
    """Hybrid prompt-suffix prefill resuming from carried SSM state.

    (params, tokens (B, C) unpadded suffix, pool_k, pool_v, block_table
    (B, nb), start (), last_idx (), lane_state) -> (logits at last_idx
    (B, 1, V), new pool_k, new pool_v, new lane_state). The prefix-cache
    warm path for zamba2: the matched prefix's shared-attention KV is
    gathered from the pool and the SSD recurrence seeds from the anchor's
    lane-state snapshot. Jit with ``donate_argnums=(2, 3, 7)``.
    """

    def step(params, tokens, pool_k, pool_v, block_table, start, last_idx,
             lane_state):
        return lm.prefill_suffix_paged_hybrid(
            params, cfg, tokens, pool_k, pool_v, block_table, start,
            last_idx, lane_state,
        )

    return step


def make_budgeted_paged_serve_step(
    cfg: ModelConfig, stream_mask: tuple, stream_depth: int
) -> Callable:
    """The paged serve step under a ``runtime.residency`` plan: weight
    regions the plan left in HBM stream through the
    ``kernels.weight_stream`` ring (depth = the plan's R_F analogue);
    resident regions run the standard in-VMEM path. ``stream_mask`` is
    (L,) per-layer flags for the dense-FFN families, (L, E) per-expert
    flags for moe (consumed by the dropless dispatch). Same signature as
    ``make_paged_serve_step``.
    """
    mask = jnp.asarray(stream_mask, bool)

    def step(params, token, pool_k, pool_v, block_table, lengths):
        return lm.decode_step_paged(
            params, cfg, token, pool_k, pool_v, block_table, lengths,
            stream_mask=mask, stream_depth=stream_depth,
        )

    return step
