"""Execute a residency plan: budgeted paged decode over a split weight set.

The plan's ``layer_stream_mask`` partitions layers into *resident* (FFN
weights pinned — the standard in-VMEM matmul path) and *streamed* (FFN
weights pulled HBM->VMEM per step by ``kernels.weight_stream``, ring depth
= the plan's ``stream_ahead``, i.e. the GALS R_F). The mask is scanned
alongside the stacked layer leaves so the whole model still compiles as
one ``lax.scan`` — HLO size stays flat in depth, and a ``lax.cond``
selects the path per layer at run time.

Numerics: on the CPU backend the streamed branch resolves to the
``kernels.ref`` oracle; on TPU the Pallas streaming kernel runs. Both
multiply and scale in f32, the resident branch in the model dtype, so
``--vmem-budget`` output is token-identical to the unbudgeted path for
f32 models and within bf16 rounding of it for bf16 ones (``chip_smoke.py``
states and checks the tolerance on a v5e).
"""

from __future__ import annotations

import functools
from typing import Callable

from repro.models.config import ModelConfig
from repro.runtime.residency.plan import RuntimeResidencyPlan


def supports_budgeted_decode(cfg: ModelConfig) -> bool:
    """Budgeted decode = paged decode + a streamable FFN weight set:
    the dense-FFN attention families (per-layer stream mask) and moe
    (per-(layer, expert) mask over the dropless dispatch)."""
    return cfg.family in ("dense", "vlm", "moe")


def make_budgeted_paged_serve_step(
    cfg: ModelConfig, plan: RuntimeResidencyPlan
) -> Callable:
    """Pool-indexed serve step running against the plan's budgeted set.

    Same signature as ``steps.make_paged_serve_step``: (params, token,
    pool_k, pool_v, block_table, lengths) -> (logits, pool_k, pool_v)
    (+ a per-layer expert-load tally for moe). The mask granularity
    follows the family: (L,) layers for dense/vlm, (L, E) experts for
    moe — cold experts stream their w1/w3/w2 through the DMA ring while
    the knapsack-pinned hot experts stay resident.
    """
    if not supports_budgeted_decode(cfg):
        raise ValueError(
            f"budgeted decode needs a streamable-FFN attention family; "
            f"got {cfg.family!r} (ssm/hybrid state is out of the "
            "residency executor's scope)"
        )
    if cfg.family == "moe":
        mask = plan.expert_stream_mask(cfg)
        assert len(mask) == cfg.n_layers and all(
            len(row) == cfg.n_experts for row in mask
        ), (len(mask), cfg.n_layers, cfg.n_experts)
    else:
        mask = plan.layer_stream_mask(cfg)
        assert len(mask) == cfg.n_layers, (len(mask), cfg.n_layers)
    from repro.runtime.steps import make_budgeted_paged_serve_step as _mk

    return _mk(cfg, mask, plan.stream_ahead)


@functools.lru_cache(maxsize=None)
def cached_budgeted_step(cfg: ModelConfig, plan: RuntimeResidencyPlan):
    """jit-compiled budgeted step, cached per (config, plan) so schedulers
    and benchmark A/B runs share compilations (mirrors
    ``scheduler._jitted_decode``)."""
    import jax

    return jax.jit(
        make_budgeted_paged_serve_step(cfg, plan), donate_argnums=(2, 3)
    )
