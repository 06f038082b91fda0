"""Compile-only guards for the TPU v5e: the serving path's kernels and
steps at smollm_360m's published widths, compiled for a described
``v5e:2x2`` topology with no chip attached.

These catch what interpret mode cannot: casts, tilings and VMEM use the
chip's compiler refuses. Nothing runs, so they say nothing about results
or times. The topology is described inside a fixture (never at import:
only one process may hold the TPU library, and every xdist worker imports
this file); the persistent compile cache is off around these compiles,
because a cache entry written for a described chip cannot be read back.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import lm
from repro.runtime.residency import TrafficProfile, compile_residency_plan
from repro.runtime.residency.executor import make_budgeted_paged_serve_step
from repro.runtime.steps import make_chunk_prefill_step, make_paged_serve_step

LANES = 8
MAX_LEN = 2048
BLOCK_TOKENS = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_kernels(monkeypatch):
    """Trace ``kernels.ops`` as on the chip: this process's backend is the
    CPU, which would otherwise pick the jnp reference."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_cpu", lambda: False)


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _paged_args(cfg, sharding):
    """Shapes of (params, token, pool_k, pool_v, block_table, lengths)
    for LANES lanes of MAX_LEN positions each."""
    from repro.models.attention import pool_tile

    params = jax.tree.map(
        lambda x: _spec(x, sharding), lm.abstract_params(cfg)
    )
    nb = MAX_LEN // BLOCK_TOKENS
    pool = jax.ShapeDtypeStruct(
        (cfg.n_kv_cache_layers, 1 + LANES * nb)
        + pool_tile(cfg.n_kv, BLOCK_TOKENS, cfg.hd),
        jnp.dtype(cfg.dtype),
        sharding=sharding,
    )
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
    return params, i32(LANES, 1), pool, pool, i32(LANES, nb), i32(LANES)


@pytest.mark.parametrize("bits", [0, 1, 2])
@pytest.mark.parametrize(
    "k,n", [(960, 2560), (2560, 960)], ids=["w1_w3", "w2"]
)
def test_weight_stream_compiles(one_chip, tpu_kernels, bits, k, n):
    from repro.kernels import ops

    per = 8 // bits if bits else 1
    x = jax.ShapeDtypeStruct((LANES, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct(
        (k // per, n), jnp.uint8 if bits else jnp.bfloat16, sharding=one_chip
    )
    s = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = (
        jax.jit(
            lambda x, w, s: ops.stream_matmul(x, w, s, bits=bits, k=k)
        )
        .lower(x, w, s)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_scopes(text):
    """Scope paths of the compiled program's Pallas kernel calls."""
    from repro.perf.hlo_analysis import scope_table

    table = scope_table(text, lm.STEP_SCOPES)
    calls = [
        line.split(" = ")[0].strip().lstrip("%")
        for line in text.splitlines()
        if "custom_call_target=\"tpu_custom_call\"" in line
    ]
    return {name: table.get(name) for name in calls}


def test_paged_decode_step_compiles(one_chip, tpu_kernels):
    """The decode step walks the block tables with the Pallas kernel, keeps
    its program name, and updates the pool in place: no whole-pool copy."""
    cfg = get_config("smollm_360m")
    args = _paged_args(cfg, one_chip)
    compiled = (
        jax.jit(make_paged_serve_step(cfg), donate_argnums=(2, 3))
        .lower(*args)
        .compile()
    )
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step,")
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    # the step must fit one v5e's 16 GB of HBM
    used = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert used < 16e9, used
    # both pools come back in place, and no scratch could hold a copy
    pool_bytes = args[2].size * args[2].dtype.itemsize
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes, mem.temp_size_in_bytes


def test_paged_decode_kernel_is_in_the_kv_gather_scope(one_chip, tpu_kernels):
    """The decode step's scope table (what ``step.decode_kv_ms`` reads) puts
    the kernel call under ``attention/kv_gather``."""
    cfg = get_config("smollm_360m")
    compiled = (
        jax.jit(make_paged_serve_step(cfg), donate_argnums=(2, 3))
        .lower(*_paged_args(cfg, one_chip))
        .compile()
    )
    scopes = _kernel_scopes(compiled.as_text())
    assert scopes and all(
        path is not None and "attention/kv_gather" in path
        for path in scopes.values()
    ), scopes


def test_chunk_prefill_step_compiles(one_chip):
    """One 256-token prompt chunk against the pool (chunked admission)."""
    cfg = get_config("smollm_360m")
    params, _, pool_k, pool_v, _, _ = _paged_args(cfg, one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    jax.jit(make_chunk_prefill_step(cfg), donate_argnums=(2, 3)).lower(
        params, i32(1, 256), pool_k, pool_v, i32(1, MAX_LEN // BLOCK_TOKENS),
        i32(), i32(),
    ).compile()


def test_budgeted_paged_decode_step_compiles(one_chip, tpu_kernels):
    cfg = dataclasses.replace(get_config("smollm_360m"), w_bits=2)
    plan = compile_residency_plan(
        cfg,
        vmem_budget_bytes=28 * 2**20,
        traffic=TrafficProfile(lanes=LANES, prompt_len=512, gen_len=32),
    )
    mask = plan.layer_stream_mask(cfg)
    assert 0 < sum(mask) < cfg.n_layers, mask
    compiled = (
        jax.jit(
            make_budgeted_paged_serve_step(cfg, plan), donate_argnums=(2, 3)
        )
        .lower(*_paged_args(cfg, one_chip))
        .compile()
    )
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step,")
    # the streamed FFNs' weight_stream calls and the paged decode kernel
    scopes = _kernel_scopes(text)
    assert any("kv_gather" in (p or "") for p in scopes.values()), scopes
    assert any("ffn" in (p or "") for p in scopes.values()), scopes
