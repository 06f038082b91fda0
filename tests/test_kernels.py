"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel body on CPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.kernels import ops, ref
from repro.quant.quantizers import pack_bits, unpack_bits


def _random_case(rng, m, k, n, bits):
    x = rng.normal(size=(m, k)).astype(np.float32)
    per = 8 // bits
    codes = rng.integers(0, 2**bits if bits < 4 else 3, size=(k, n))
    if bits == 2:
        codes = rng.integers(0, 3, size=(k, n))  # ternary codes {0,1,2}
    kp = (k + per - 1) // per * per
    codes_p = np.zeros((kp, n), np.uint8)
    codes_p[:k] = codes
    packed = np.asarray(pack_bits(jnp.asarray(codes_p, jnp.uint8), bits))
    scale = rng.uniform(0.5, 2.0, size=(n,)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(packed), jnp.asarray(scale)


SHAPES = [
    (8, 32, 16),
    (16, 64, 128),
    (128, 256, 128),
    (33, 72, 50),  # deliberately unaligned
    (1, 8, 1),
    (256, 512, 384),
]


@pytest.mark.parametrize("bits", [1, 2])
@pytest.mark.parametrize("m,k,n", SHAPES)
def test_packed_matmul_matches_oracle(bits, m, k, n):
    rng = np.random.default_rng(42 + m + k + n + bits)
    x, packed, scale = _random_case(rng, m, k, n, bits)
    out = ops.packed_matmul(x, packed, scale, bits=bits, k=k, interpret=True)
    want = ref.packed_matmul_ref(x, packed, scale, bits, k)
    assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_matmul_dtypes(dtype):
    rng = np.random.default_rng(0)
    x, packed, scale = _random_case(rng, 16, 64, 32, 1)
    x = x.astype(dtype)
    out = ops.packed_matmul(x, packed, scale, bits=1, k=64, interpret=True)
    want = ref.packed_matmul_ref(
        x.astype(jnp.float32), packed, scale, 1, 64
    )
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert_allclose(np.asarray(out), np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("bits", [1, 2])
@pytest.mark.parametrize("m,k,n", SHAPES[:4])
@pytest.mark.parametrize("n_levels", [1, 3, 7])
def test_mvau_matches_oracle(bits, m, k, n, n_levels):
    rng = np.random.default_rng(7 + m + k + n + bits + n_levels)
    x, packed, _ = _random_case(rng, m, k, n, bits)
    thresholds = np.sort(
        rng.normal(scale=np.sqrt(k), size=(n, n_levels)), axis=1
    ).astype(np.float32)
    signs = rng.choice([-1.0, 1.0], size=(n,)).astype(np.float32)
    offset = -(n_levels + 1) // 2
    out = ops.mvau(
        x, packed, jnp.asarray(thresholds), jnp.asarray(signs),
        bits=bits, k=k, offset=offset, interpret=True,
    )
    want = ref.mvau_ref(
        x, packed, jnp.asarray(thresholds), jnp.asarray(signs),
        offset, bits, k,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_mvau_batched_leading_dims():
    rng = np.random.default_rng(3)
    x, packed, _ = _random_case(rng, 24, 32, 16, 1)
    x3 = x.reshape(2, 12, 32)
    thr = np.zeros((16, 1), np.float32)
    sg = np.ones((16,), np.float32)
    out = ops.mvau(
        x3, packed, jnp.asarray(thr), jnp.asarray(sg),
        bits=1, k=32, interpret=True,
    )
    assert out.shape == (2, 12, 16)


@settings(max_examples=30, deadline=None)
@given(
    bits=st.sampled_from([1, 2, 4]),
    k=st.integers(1, 9),
    n=st.integers(1, 5),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_unpack_roundtrip(bits, k, n, seed):
    """Property: unpack(pack(codes)) == codes for any code tensor."""
    per = 8 // bits
    rng = np.random.default_rng(seed)
    kk = k * per  # multiple of per
    codes = rng.integers(0, 2**bits, size=(kk, n)).astype(np.uint8)
    packed = pack_bits(jnp.asarray(codes), bits)
    assert packed.shape == (k, n)
    out = unpack_bits(packed, bits, kk)
    np.testing.assert_array_equal(np.asarray(out), codes)


@settings(max_examples=15, deadline=None)
@given(
    m=st.integers(1, 17),
    kw=st.integers(1, 8),
    n=st.integers(1, 9),
    bits=st.sampled_from([1, 2]),
    seed=st.integers(0, 2**31 - 1),
)
def test_packed_matmul_property(m, kw, n, bits, seed):
    """Property: kernel == oracle on arbitrary shapes (auto-padding)."""
    per = 8 // bits
    k = kw * per
    rng = np.random.default_rng(seed)
    x, packed, scale = _random_case(rng, m, k, n, bits)
    out = ops.packed_matmul(x, packed, scale, bits=bits, k=k, interpret=True)
    want = ref.packed_matmul_ref(x, packed, scale, bits, k)
    assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_pack_weights_decode_inverse():
    rng = np.random.default_rng(5)
    w = rng.normal(size=(24, 8)).astype(np.float32)
    for bits in (1, 2):
        q = np.sign(w) if bits == 1 else np.sign(w) * (np.abs(w) > 0.5)
        packed = ops.pack_weights(jnp.asarray(q), bits)
        dec = ref.decode_weights(packed, bits, 24)
        if bits == 1:
            np.testing.assert_array_equal(
                np.asarray(dec), np.where(q > 0, 1.0, -1.0)
            )
        else:
            np.testing.assert_array_equal(np.asarray(dec), q)


# --------------------------------------------------------------------------
# fused flash-attention kernel vs dense oracle
# --------------------------------------------------------------------------


@pytest.mark.parametrize("sq,sk,hq,hkv,window,causal,qoff", [
    (64, 64, 4, 4, 0, True, 0),
    (64, 64, 4, 2, 0, True, 0),
    (128, 128, 6, 2, 32, True, 0),
    (64, 64, 4, 4, 0, False, 0),
    (32, 96, 4, 2, 0, True, 64),
    (64, 64, 8, 1, 0, True, 0),
])
def test_flash_kernel_matches_oracle(sq, sk, hq, hkv, window, causal, qoff):
    rng = np.random.default_rng(sq + sk + hq + hkv + window)
    d = 32
    q = jnp.asarray(rng.normal(size=(2, sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, sk, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, sk, hkv, d)), jnp.float32)
    got = ops.flash_attention(
        q, k, v, causal=causal, window=window, q_block=16, kv_block=32,
        q_offset=qoff, interpret=True,
    )
    want = ref.flash_attention_ref(q, k, v, causal, window, qoff)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_kernel_gradients():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.float32)

    def loss_k(q, k, v):
        return jnp.sum(jnp.sin(ops.flash_attention(
            q, k, v, q_block=16, kv_block=32, interpret=True)))

    def loss_r(q, k, v):
        return jnp.sum(jnp.sin(ref.flash_attention_ref(q, k, v)))

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_dtypes(dtype):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), dtype)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), dtype)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), dtype)
    got = ops.flash_attention(q, k, v, q_block=16, kv_block=16,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


# --------------------------------------------------------------------------
# Paged decode attention over the block pool (kernels/paged_attention.py)
# --------------------------------------------------------------------------


def _paged_case(case, dtype):
    """(q, k_new, v_new, pool_k, pool_v, block_table, lengths, n_kv,
    window, live lanes) of one case, at rehearsal widths (2 kv heads of
    32, 2 query heads each); ``lengths`` are the tokens each lane holds
    before the step's new one."""
    from repro.configs import get_smoke_config
    from repro.models.attention import pool_tile
    from repro.runtime.kv_pool import KVPool

    n_kv, g, hd, t = 2, 2, 32, 16
    window, nb = 0, 128
    rng = np.random.default_rng(7)
    pools = tables = None
    if case == "ragged":  # nothing, one, both sides of a block edge, full,
        # and a last lane whose new row falls past its table (nb * t)
        lengths = [0, 1, 15, 16, 2047, 2048]
    elif case == "past_end":
        # drafter lanes rolled past max_len: their rows go to the scratch
        # block, never into the next lane's first block
        lengths = [2048, 2050, 30, 2048]
    elif case == "parked":  # three lanes parked on the scratch block
        lengths = [0, 0, 40, 0]
        tables = np.zeros((4, nb), np.int32)
        tables[2, :3] = [5, 9, 2]
    elif case == "shared":
        # request 1 adopts request 0's two full blocks and takes a private
        # copy of its partly matched third (copy-on-write)
        cfg = dataclasses.replace(get_smoke_config("smollm_360m"), n_kv=n_kv,
                                  head_dim=hd, dtype=dtype)
        pool = KVPool(cfg, n_blocks=12, block_tokens=t)
        pool.k = jnp.asarray(rng.normal(size=pool.k.shape), dtype)
        pool.v = jnp.asarray(rng.normal(size=pool.v.shape), dtype)
        pool.admit(0, 64)
        pool.note_tokens(0, 46)
        pool.admit(1, 64)
        held = pool.blocks_of(0)
        pool.adopt_prefix(1, held[:2], held[2], 40)
        pool.note_tokens(1, 51)
        assert pool.blocks_of(1)[:2] == held[:2] and pool.cow_copies == 1
        tables = np.stack([pool.table_of(r, nb) for r in (0, 1)])
        lengths = [45, 50]
        pools = pool.k, pool.v
    else:  # a sliding window of 20 positions
        window = 20
        lengths = [4, 19, 40, 699]
    n_lanes = len(lengths)
    if tables is None:  # scattered, non-contiguous physical blocks
        perm = 1 + rng.permutation(n_lanes * nb)
        tables = perm.reshape(n_lanes, nb).astype(np.int32)
    if pools is None:
        shape = (2, 1 + n_lanes * nb) + pool_tile(n_kv, t, hd)
        pools = tuple(jnp.asarray(rng.normal(size=shape), dtype)
                      for _ in range(2))
    q, k_new, v_new = (
        jnp.asarray(rng.normal(size=(n_lanes, 1, n_kv * g, hd)), dtype)
        for _ in range(3)
    )
    live = [i for i in range(n_lanes) if tables[i].any()]
    return (q, k_new[:, 0, :n_kv], v_new[:, 0, :n_kv], *pools,
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), n_kv,
            window, live)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "case", ["ragged", "past_end", "parked", "shared", "window"]
)
def test_paged_decode_kernel_matches_reference(case, dtype):
    """The block-table kernel (interpret mode) writes each lane's new row
    and gives the reference's attention: ragged lane lengths up to the
    full 2048 positions and past them, lanes parked on the scratch block,
    blocks two lanes share after prefix adoption and copy-on-write, and a
    sliding window."""
    from repro.kernels.paged_attention import paged_decode
    from repro.models.attention import SCRATCH_BLOCK

    (q, k_new, v_new, pk, pv, table, lengths, n_kv, window,
     live) = _paged_case(case, dtype)
    layer = jnp.int32(pk.shape[0] - 1)
    args = (q, k_new, v_new, pk, pv, layer, table, lengths)
    got = paged_decode(*args, n_kv=n_kv, window=window, interpret=True)
    want = ref.paged_decode_ref(*args, n_kv=n_kv, window=window)
    assert got[0].shape == want[0].shape and got[0].dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    f32 = lambda x: np.asarray(x, np.float32)
    # a parked lane's output is never read, and its rows land in the
    # scratch block, which every parked lane writes
    assert_allclose(f32(got[0])[live], f32(want[0])[live], rtol=tol,
                    atol=tol)
    for g, w in zip(got[1:], want[1:]):
        keep = np.arange(g.shape[1]) != SCRATCH_BLOCK
        assert_allclose(f32(g)[:, keep], f32(w)[:, keep], rtol=0, atol=0)


def test_live_block_span_counts_the_blocks_a_lane_reads():
    from repro.models.attention import live_block_span

    lens = np.array([0, 1, 16, 17, 2048])
    first, end = live_block_span(lens, 16)
    assert first.tolist() == [0] * 5 and end.tolist() == [0, 1, 1, 2, 128]
    first, end = live_block_span(lens, 16, window=20)
    # positions kv_len - 20 .. kv_len - 1
    assert first.tolist() == [0, 0, 0, 0, 126] and end.tolist()[-1] == 128


@pytest.mark.parametrize("arch, want", [
    ("h2o_danube_1p8b", 64),  # a sliding window
    ("smollm_360m", 0),
    ("zamba2_2p7b", 0),  # the hybrid's shared block attends over all
])
def test_decode_window_is_the_window_the_decode_step_reads(arch, want):
    from repro.configs import get_smoke_config
    from repro.models.attention import decode_window

    cfg = get_smoke_config(arch)
    assert decode_window(cfg) == want
    if cfg.family == "hybrid":  # even where a window is configured
        assert decode_window(dataclasses.replace(cfg, sliding_window=8)) == 0
