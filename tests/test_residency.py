"""Budgeted weight-residency subsystem: planner invariants, the
weight-streaming kernel vs its oracle, budgeted-vs-full serve
token-identity (the acceptance gate), and the launch.port §V ordering."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.resource_model import TPU_TIERS, TPU_V5E
from repro.core.vmem_plan import WeightBlock, pack_blocks, vmem_tile_ram
from repro.models import lm
from repro.runtime.kv_pool import KVPool
from repro.runtime.residency import (
    TrafficProfile,
    compile_residency_plan,
    stream_ahead_depth,
    weight_blocks,
)
from repro.runtime.scheduler import Scheduler

BLOCK, MAX_LEN, SLOTS, P, GEN = 4, 16, 2, 4, 4


def _cfg(w_bits=0):
    cfg = get_smoke_config("smollm_360m")
    return dataclasses.replace(cfg, w_bits=w_bits) if w_bits else cfg


def _total_block_bytes(cfg):
    return sum(b.padded_bytes() for b in weight_blocks(cfg))


# ---------------- vmem_plan packing bridge ----------------


def test_vmem_tile_ram_matches_chip_geometry():
    """blocks_for on the tile primitive == chip.tile_blocks_for exactly."""
    ram = vmem_tile_ram(TPU_V5E)
    for rows, cols, bits in [(128, 256, 1), (96, 130, 2), (7, 7, 16)]:
        blk = WeightBlock("b", rows, cols, bits)
        carrier_rows = -(-rows * bits // 8)
        assert (
            ram.blocks_for(cols * 8, carrier_rows)
            == TPU_V5E.tile_blocks_for(carrier_rows, cols)
        )
        # padded_bytes is the tile count times the tile byte size
        assert blk.padded_bytes() == TPU_V5E.tile_blocks_for(
            carrier_rows, cols
        ) * TPU_V5E.sublane * TPU_V5E.lane


@pytest.mark.parametrize("solver", ["ffd", "anneal"])
def test_pack_blocks_is_valid_packing(solver):
    blocks = weight_blocks(_cfg(w_bits=1))
    packing = pack_blocks(blocks, solver=solver, max_height=4)
    packing.validate(max_height=4)
    # packing can only improve on one-block-per-bin tile counts
    solo = sum(
        vmem_tile_ram().blocks_for(it.width, it.depth)
        for it in packing.items
    )
    assert packing.total_blocks <= solo


# ---------------- planner ----------------


def test_plan_budget_monotonicity_and_accounting():
    cfg = _cfg()
    total = _total_block_bytes(cfg)
    fracs = [0.0, 0.4, 1.1]
    plans = [
        compile_residency_plan(
            cfg, vmem_budget_bytes=int(total * f),
            traffic=TrafficProfile(lanes=2),
        )
        for f in fracs
    ]
    res = [p.resident_fraction for p in plans]
    assert res == sorted(res), "resident set must grow with the budget"
    assert plans[0].resident_fraction == 0.0
    assert plans[-1].resident_fraction == 1.0
    assert plans[-1].streamed_bytes_per_step == 0
    assert plans[-1].hbm_traffic_reduction == 1.0
    for p in plans:
        assert p.resident_bytes <= p.vmem_budget_bytes
        mask = p.layer_stream_mask(cfg)
        assert len(mask) == cfg.n_layers


def test_plan_rejects_unknown_chip():
    """A plan names its chip; one the tier table lacks is an error, not
    a silent v5e."""
    cfg = _cfg()
    plan = compile_residency_plan(cfg, vmem_budget_bytes=2**20)
    assert plan.resident_bytes >= 0
    with pytest.raises(ValueError, match="unknown chip"):
        dataclasses.replace(plan, chip="tpu_v9x").resident_bytes


def test_plan_packed_blocks_shrink_with_bits():
    """1-bit carriers need ~1/32 the tiles of f32 — the FCMP packing win
    that makes the whole model resident where dense was not."""
    dense, packed = _total_block_bytes(_cfg()), _total_block_bytes(
        _cfg(w_bits=1)
    )
    assert packed * 8 <= dense


def test_stream_ahead_depth_maps_rf():
    """R_F mapping: the packing bandwidth surplus funds the ring depth."""
    assert stream_ahead_depth(_cfg()) == 2  # no surplus -> minimum ring
    assert stream_ahead_depth(_cfg(w_bits=1)) == 8  # 32x surplus, clamped
    assert stream_ahead_depth(_cfg(w_bits=2)) == 8
    bf16 = dataclasses.replace(_cfg(w_bits=2), dtype="bfloat16")
    assert stream_ahead_depth(bf16) == 4  # 2 ports * 8x surplus / H_B=4


def test_plan_residency_is_layer_granular():
    """No stranded VMEM: residency is all-or-nothing per layer, so the
    plan's reported streamed bytes equal exactly what the layer-granular
    executor streams."""
    cfg = _cfg(w_bits=1)
    total = _total_block_bytes(cfg)
    for frac in (0.2, 0.5, 0.8):
        plan = compile_residency_plan(
            cfg, vmem_budget_bytes=int(total * frac),
            traffic=TrafficProfile(lanes=2),
        )
        res = plan.block_resident()
        mask = plan.layer_stream_mask(cfg)
        for l in range(cfg.n_layers):
            states = {
                r for n, r in res.items() if n.startswith(f"L{l:03d}.")
            }
            assert len(states) == 1, f"layer {l} partially resident"
            assert mask[l] == (not states.pop())
        executor_streams = sum(
            b.padded_bytes()
            for b in plan.blocks
            if mask[int(b.name[1:4])]
        )
        assert plan.streamed_bytes_per_step == executor_streams


def test_moe_read_weights_scale_expert_value():
    from repro.runtime.residency.plan import read_weight

    moe = get_smoke_config("olmoe_1b_7b")
    w = read_weight("L000.e0.w1", moe)
    assert w == moe.experts_per_token / moe.n_experts
    assert read_weight("L000.w1", _cfg()) == 1.0


# ---------------- weight-streaming kernel vs oracle ----------------


@pytest.mark.parametrize("bits,depth", [(0, 2), (1, 2), (2, 4), (0, 3)])
def test_weight_stream_kernel_matches_ref(bits, depth):
    from repro.kernels import weight_stream as ws
    from repro.kernels.ops import pack_weights
    from repro.kernels.ref import stream_matmul_ref

    rng = np.random.default_rng(bits * 10 + depth)
    m, k, n = 8, 512, 256
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    scale = jnp.asarray(rng.uniform(0.5, 2.0, size=(n,)).astype(np.float32))
    if bits == 0:
        w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    else:
        vals = rng.integers(-1, 2, size=(k, n)).astype(np.float32)
        if bits == 1:
            vals = np.sign(vals + 0.5)
        w = pack_weights(jnp.asarray(vals), bits)
    out = ws.stream_matmul(
        x, w, scale, bits=bits, k=k, bn=128, ck=128, stream_depth=depth,
        interpret=True,
    )
    ref = stream_matmul_ref(x, w, scale, bits, k)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


def test_ops_stream_matmul_pads_uneven_shapes():
    from repro.kernels.ops import stream_matmul

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 3, 100)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(100, 70)).astype(np.float32))
    out = stream_matmul(x, w, None, bits=0, k=100)
    assert out.shape == (2, 3, 70)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(jnp.einsum("...k,kn->...n", x, w)),
        rtol=1e-4, atol=1e-4,
    )


# ---------------- budgeted serve equivalence (acceptance gate) ----------------


def _serve_outputs(cfg, params, prompts, plan):
    pool = KVPool.for_slots(
        cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
    )
    sched = Scheduler(
        cfg, params, pool, slots=SLOTS, max_len=MAX_LEN, residency=plan
    )
    for p in prompts:
        sched.submit(p, GEN)
    sched.run()
    return sched.outputs()


@pytest.mark.parametrize("w_bits", [0, 1])
def test_budgeted_serve_token_identical(w_bits):
    """`--vmem-budget` decode == unbudgeted decode, token for token, on
    the dense LM family (w_bits=0) and the FCMP-packed 1-bit variant
    (the paper's CNN precision), with the plan forced to stream."""
    cfg = _cfg(w_bits)
    params = lm.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(0, cfg.vocab, size=(P,)).astype(np.int32)
        for _ in range(3)
    ]
    plan = compile_residency_plan(
        cfg,
        vmem_budget_bytes=_total_block_bytes(cfg) // 2,
        traffic=TrafficProfile(lanes=SLOTS, prompt_len=P, gen_len=GEN),
    )
    mask = plan.layer_stream_mask(cfg)
    assert any(mask), "plan must stream at least one layer"
    assert not all(mask), "half budget should pin at least one layer"
    full = _serve_outputs(cfg, params, prompts, None)
    budgeted = _serve_outputs(cfg, params, prompts, plan)
    assert full == budgeted


def test_moe_budgeted_serve_token_identical():
    """Expert streaming is the moe analog of the dense layer stream:
    a half-budget plan pins some (layer, expert) regions and streams the
    rest through the weight ring, and because the dropless dispatch scans
    experts in the same order either way, the budgeted token stream is
    identical to the unbudgeted one."""
    cfg = get_smoke_config("olmoe_1b_7b")
    params = lm.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(6)
    prompts = [
        rng.integers(0, cfg.vocab, size=(P,)).astype(np.int32)
        for _ in range(3)
    ]
    plan = compile_residency_plan(
        cfg,
        vmem_budget_bytes=_total_block_bytes(cfg) // 2,
        traffic=TrafficProfile(lanes=SLOTS, prompt_len=P, gen_len=GEN),
    )
    mask = np.asarray(plan.expert_stream_mask(cfg), bool)
    assert mask.shape == (cfg.n_layers, cfg.n_experts)
    assert mask.any(), "plan must stream at least one expert"
    assert not mask.all(), "half budget should pin at least one expert"
    full = _serve_outputs(cfg, params, prompts, None)
    budgeted = _serve_outputs(cfg, params, prompts, plan)
    assert full == budgeted


def test_budgeted_serve_still_rejects_stateful_families():
    """The residency executor streams FFN weights; ssm/hybrid recurrent
    state is out of its scope and must fail loudly, not silently."""
    hyb = get_smoke_config("zamba2_2p7b")
    plan = compile_residency_plan(
        hyb, vmem_budget_bytes=0, traffic=TrafficProfile(lanes=2)
    )
    from repro.runtime.residency import make_budgeted_paged_serve_step

    with pytest.raises(ValueError, match="streamable-FFN"):
        make_budgeted_paged_serve_step(hyb, plan)


# ---------------- launch.port (§V ordering) ----------------


@pytest.mark.parametrize(
    "arch,target", [("cnv_w1a1", "zynq7012s"), ("rn50_w2a2", "u280")]
)
def test_port_reproduces_section_v_ordering(arch, target):
    from repro.launch.port import accel_port_rows

    rows = {r["device"]: r for r in accel_port_rows(arch)}
    r = rows[target]
    assert not r["baseline_fits"], "port target must be the smaller part"
    assert r["packed_fits"], "FCMP packing must make the design fit"
    assert r["fcmp_delta_fps_pct"] < r["fold2_delta_fps_pct"]
    assert r["recommended"] == "fcmp"


def test_port_lm_ladder_prefers_packing():
    from repro.launch.port import lm_port_rows

    rows = lm_port_rows("smollm_360m", quant=1, lanes=8)
    tiers = {r["device"] for r in rows}
    assert tiers == set(TPU_TIERS)
    by = {(r["device"], r["variant"]): r for r in rows}
    for tier in TPU_TIERS:
        packed = by[(tier, "fcmp_packed")]
        dense = by[(tier, "dense")]
        assert packed["tokens_per_s"] >= dense["tokens_per_s"]
        assert (
            packed["streamed_mib_per_step"] <= dense["streamed_mib_per_step"]
        )
