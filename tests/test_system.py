"""End-to-end system tests: train->checkpoint->kill->resume on a real
(reduced) model, packed-weight serving, and the streamlined CNN datapath."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.configs import get_smoke_config
from repro.data.pipeline import TokenPipeline
from repro.models import lm
from repro.optim.adamw import AdamW
from repro.runtime.steps import make_serve_step, make_train_step
from repro.runtime.train import TrainLoop, TrainLoopConfig


def _setup(arch="smollm_360m"):
    cfg = get_smoke_config(arch)
    opt = AdamW(lr=1e-3, warmup_steps=2)
    step = jax.jit(make_train_step(cfg, opt, remat="none", ce_chunk=16))
    params = lm.init_params(cfg, jax.random.key(0))
    pipe = TokenPipeline(vocab=cfg.vocab, batch=2, seq_len=32, seed=1)
    return cfg, opt, step, params, pipe


def test_train_ckpt_kill_resume_equals_uninterrupted(tmp_path):
    loop_cfg = TrainLoopConfig(n_steps=12, ckpt_every=4, ckpt_async=False)

    # reference: uninterrupted
    cfg, opt, step, params, pipe = _setup()
    ref, _, _ = TrainLoop(step, pipe, None, loop_cfg).run(
        params, opt.init(params)
    )

    # interrupted at step 7 -> restart from the step-4 checkpoint
    cfg, opt, step, params, pipe = _setup()
    ckpt = CheckpointManager(str(tmp_path))

    class Boom(RuntimeError):
        pass

    def bomb(s):
        if s == 7:
            raise Boom()

    with pytest.raises(Boom):
        TrainLoop(step, pipe, ckpt, loop_cfg, pre_step_hook=bomb).run(
            params, opt.init(params)
        )

    cfg, opt, step, params, pipe = _setup()
    loop = TrainLoop(step, pipe, ckpt, loop_cfg)
    p, s, start = loop.restore_or_init(params, opt.init(params))
    assert start == 4
    out, _, _ = loop.run(p, s, start)

    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-5, atol=1e-6,
        )


def test_loss_descends_on_learnable_data():
    cfg, opt, step, params, pipe = _setup()
    state = opt.init(params)
    losses = []
    for i in range(25):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.1, losses[::6]


def test_packed_weights_serve_loop():
    """FCMP-packed (1-bit) model generates greedily without NaNs and the
    packed leaves are genuinely uint8 carriers (16x smaller)."""
    cfg = dataclasses.replace(get_smoke_config("llama3p2_1b"), w_bits=1)
    params = lm.init_params(cfg, jax.random.key(0))
    w1 = params["layers"]["w1"]
    dense_bytes = cfg.n_layers * cfg.d_model * cfg.d_ff * 2
    packed_bytes = w1["packed"].size + w1["scale"].size * 4
    assert packed_bytes < dense_bytes / 8
    serve = jax.jit(make_serve_step(cfg))
    cache = lm.init_cache(cfg, 2, 12)
    tok = jnp.zeros((2, 1), jnp.int32)
    for _ in range(8):
        logits, cache = serve(params, tok, cache)
        assert bool(jnp.isfinite(logits).all())
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)


def test_cnn_streamlined_matches_float_path():
    """Paper §III-B: BN+act folded to thresholds is bit-exact vs the QAT
    graph in eval mode — on the full CNV topology."""
    from repro.models.cnn import (
        cnn_forward,
        cnn_forward_streamlined,
        cnv_topology,
        init_cnn_params,
        streamline_params,
    )

    specs = cnv_topology(w_bits=1, a_bits=2)
    params = init_cnn_params(specs, jax.random.key(0))
    # randomise BN stats so the fold is non-trivial
    k = jax.random.key(1)
    for sp in specs:
        k, k1, k2 = jax.random.split(k, 3)
        params[sp.name]["bn_mu"] = (
            jax.random.normal(k1, (sp.c_out,)) * 0.2
        )
        params[sp.name]["bn_var"] = (
            jax.random.uniform(k2, (sp.c_out,)) * 2.0 + 0.1
        )
    x = jax.random.normal(jax.random.key(2), (2, 32, 32, 3))
    ref = cnn_forward(params, specs, x, train=False)
    sparams = streamline_params(params, specs)
    got = cnn_forward_streamlined(sparams, specs, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


def test_conv_as_mvau_kernel_path():
    """The im2col + fused Pallas MVAU path equals the conv+threshold path."""
    from repro.models.cnn import (
        cnn_forward,
        conv_as_mvau,
        cnv_topology,
        init_cnn_params,
        streamline_params,
    )

    specs = cnv_topology(w_bits=1, a_bits=2)[1:2]  # conv1 template
    sp = dataclasses.replace(specs[0], c_in=8, c_out=16, pool=False)
    params = init_cnn_params([sp], jax.random.key(0))
    sparams = streamline_params(params, [sp])
    x = jax.random.normal(jax.random.key(1), (1, 8, 8, 8))
    want = cnn_forward(params, [sp], x, train=False)
    got = conv_as_mvau(
        x, np.asarray(sparams[sp.name]["w"]),
        sparams[sp.name]["thresholds"], sp.w_bits,
    )
    np.testing.assert_allclose(
        np.asarray(got).reshape(want.shape), np.asarray(want),
        rtol=1e-4, atol=1e-4,
    )


def test_packed_arch_train_step_excludes_carriers():
    """ROADMAP bugfix: jax.grad over a packed (w_bits=1) arch must not
    crash — uint8 carriers get float0 tangents (allow_int) and AdamW
    passes them through untouched while float leaves keep training."""
    cfg = dataclasses.replace(get_smoke_config("llama3p2_1b"), w_bits=1)
    opt = AdamW(lr=1e-3, warmup_steps=2)
    step = jax.jit(make_train_step(cfg, opt, remat="none", ce_chunk=16))
    params = lm.init_params(cfg, jax.random.key(0))
    state = opt.init(params)
    pipe = TokenPipeline(vocab=cfg.vocab, batch=2, seq_len=32, seed=1)
    carriers_before = np.asarray(params["layers"]["w1"]["packed"])
    embed_before = np.asarray(params["embed"])
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    new_params, state, m = step(params, state, batch)
    assert np.isfinite(float(m["loss"]))
    np.testing.assert_array_equal(
        np.asarray(new_params["layers"]["w1"]["packed"]), carriers_before
    )
    assert new_params["layers"]["w1"]["packed"].dtype == jnp.uint8
    assert not np.array_equal(np.asarray(new_params["embed"]), embed_before)


def test_train_driver_rejects_quant_on_packed_arch(capsys):
    """`train.py --quant 1` on a packing arch exits with an actionable
    message instead of a jax.grad traceback; unknown --arch likewise."""
    from repro.launch import train as train_launch

    rc = train_launch.main(
        ["--arch", "llama3p2_1b", "--smoke", "--quant", "1", "--steps", "1"]
    )
    out = capsys.readouterr().out
    assert rc == 2
    assert "inference-only" in out and "quantize" in out

    rc = train_launch.main(["--arch", "not_a_real_arch", "--smoke"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "valid archs" in out


def test_serve_rejects_encdec(capsys):
    """An encoder-decoder arch has no serving path: exit 2, not a 0 that
    served nothing."""
    from repro.launch import serve as serve_launch

    rc = serve_launch.main(["--arch", "whisper_tiny", "--smoke"])
    assert rc == 2
    assert "encoder-decoder" in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "default"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR is left to JAX; without it the cache goes
    to the fixed .jax_cache at the repo root."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.use_compile_cache()
        if env_dir:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(compile_cache.DEFAULT_DIR)
            assert compile_cache.DEFAULT_DIR.parent.joinpath(
                "chip_smoke.py"
            ).is_file()
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
