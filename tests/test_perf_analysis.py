"""Unit tests for the loop-aware HLO cost analysis (perf/hlo_analysis).

The roofline numbers in EXPERIMENTS.md are only as good as this parser:
validate trip-count multiplication, dot-flop math, collective accounting
and the in-place dynamic-update-slice special cases on hand-written HLO,
then cross-check against a real compiled module where XLA's own cost
analysis is exact (loop-free graph).
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.perf.hlo_analysis import (
    analyze,
    computation_multipliers,
    parse_module,
    shape_bytes,
    xla_cost_analysis,
)


SYNTHETIC = """
HloModule test

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16] get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %w = f32[16,16] constant({...})
  %y = f32[8,16] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16] all-reduce(%y), replica_groups={}, to_apply=%sum
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]) tuple(%ni, %ar)
}

%cond (p2: (s32[], f32[8,16])) -> pred[] {
  %p2 = (s32[], f32[8,16]) parameter(0)
  %i2 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i2, %n), direction=LT
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (arg: f32[8,16]) -> (s32[], f32[8,16]) {
  %arg = f32[8,16] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,16]) tuple(%zero, %arg)
  ROOT %w2 = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
}
"""


def test_shape_bytes():
    assert shape_bytes("f32[8,16]{1,0}") == 8 * 16 * 4
    assert shape_bytes("bf16[4]") == 8
    assert shape_bytes("(f32[2], s32[3])") == 8 + 12
    assert shape_bytes("pred[10,10]") == 100


def test_synthetic_trip_count_multiplies():
    comps = parse_module(SYNTHETIC)
    assert set(comps) == {"body", "cond", "sum", "main"}
    mult, kind = computation_multipliers(comps)
    assert mult["main"] == 1.0
    assert mult["body"] == 5.0
    assert mult["cond"] == 5.0
    cost = analyze(SYNTHETIC)
    # dot: 2 * 8*16 * 16 flops, executed 5 times
    assert cost.dot_flops == 5 * 2 * 8 * 16 * 16
    # all-reduce operand: 8*16*4 bytes, 5 times
    assert cost.collective_bytes["all-reduce"] == 5 * 8 * 16 * 4


DUS_HLO = """
HloModule dus

%fused_dus (a: f32[64,16], u: f32[1,16], i: s32[]) -> f32[64,16] {
  %a = f32[64,16] parameter(0)
  %u = f32[1,16] parameter(1)
  %i = s32[] parameter(2)
  %z = s32[] constant(0)
  ROOT %d = f32[64,16] dynamic-update-slice(%a, %u, %i, %z)
}

ENTRY %main (buf: f32[64,16], upd: f32[1,16], idx: s32[]) -> f32[64,16] {
  %buf = f32[64,16] parameter(0)
  %upd = f32[1,16] parameter(1)
  %idx = s32[] parameter(2)
  ROOT %f = f32[64,16] fusion(%buf, %upd, %idx), kind=kLoop, calls=%fused_dus
}
"""


def test_dus_fusion_counts_update_not_buffer():
    cost = analyze(DUS_HLO)
    # 3 x update bytes (1*16*4), NOT the 64*16*4 buffer
    assert cost.traffic_bytes == 3 * 1 * 16 * 4


def test_against_xla_cost_analysis_loop_free():
    """On a loop-free jit, our dot flops match XLA's cost analysis."""

    @jax.jit
    def f(a, b):
        return a @ b

    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    compiled = f.lower(a, b).compile()
    ours = analyze(compiled.as_text()).dot_flops
    theirs = xla_cost_analysis(compiled).get("flops", 0.0)
    assert ours == 2 * 64 * 128 * 32
    # XLA counts the same matmul (modulo fusion bookkeeping)
    assert abs(ours - theirs) / ours < 0.05


def test_scan_undercount_demonstrated():
    """The reason this module exists: XLA's cost analysis does NOT
    multiply scan bodies by trip count; ours does."""
    n = 10

    @jax.jit
    def f(x, w):
        def body(c, _):
            return c @ w, None

        out, _ = jax.lax.scan(body, x, None, length=n)
        return out

    x = jnp.ones((32, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    compiled = f.lower(x, w).compile()
    per_iter = 2 * 32 * 64 * 64
    ours = analyze(compiled.as_text()).dot_flops
    theirs = float(xla_cost_analysis(compiled).get("flops", 0.0))
    assert ours == n * per_iter, (ours, n * per_iter)
    assert theirs <= per_iter * 2  # XLA counts the body ~once


SCOPED = """
HloModule jit_step

%fused_computation (param_0: f32[16,8], param_1: s32[4]) -> f32[4,8] {
  %param_0 = f32[16,8]{1,0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  ROOT %gather.2 = f32[4,8]{1,0} gather(%param_0, %param_1), metadata={op_name="jit(step)/while/body/attention/kv_gather/gather" stack_frame_id=4}
}

ENTRY %main (p: f32[16,8], x: s32[4]) -> f32[4,8] {
  %p = f32[16,8]{1,0} parameter(0), metadata={op_name="p"}
  %x = s32[4]{0} parameter(1), metadata={op_name="x"}
  %copy.7 = f32[16,8]{0,1} copy(%p)
  %gather_fusion.1 = f32[4,8]{1,0} fusion(%copy.7, %x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/attention/kv_gather/gather" stack_frame_id=4}
  ROOT %dot.3 = f32[4,8]{1,0} dot(%gather_fusion.1, %p), metadata={op_name="jit(step)/while/body/ffn/dot_general"}
}
"""


def test_scope_table_maps_instructions_to_named_scopes():
    from repro.perf.hlo_analysis import scope_table

    table = scope_table(SCOPED, ("attention", "kv_gather", "ffn"))
    assert table == {
        "gather.2": "attention/kv_gather",
        "gather_fusion.1": "attention/kv_gather",
        "dot.3": "ffn",
    }
    # operations of no named scope (parameters, layout copies) are left out
    assert scope_table(SCOPED, ("logits",)) == {}
