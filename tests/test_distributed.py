"""Distribution-layer tests on forced multi-device CPU (subprocess-based:
the parent pytest process has already locked jax to 1 device, so every
multi-device check runs in a child with XLA_FLAGS set before jax init)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_forced(script: str, n_dev: int = 8, timeout: int = 500) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_int8_allreduce_multidevice():
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import int8_allreduce
        mesh = jax.make_mesh((8,), ("data",))
        g = jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 64.0
        f = jax.shard_map(
            lambda x: int8_allreduce(x[0], "data"),
            mesh=mesh, in_specs=P("data"), out_specs=P(),
        )
        got = f(g)
        want = jnp.mean(g, axis=0)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 1e-2, err
        print("OK", err)
    """)
    assert "OK" in out


def test_pipeline_parallel_forward():
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.runtime.pipeline import pipeline_forward
        mesh = jax.make_mesh((4,), ("stage",))
        # 4 stages, each multiplies by its own matrix
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.normal(size=(4, 1, 8, 8)) * 0.3, jnp.float32)
        xs = jnp.asarray(rng.normal(size=(6, 2, 8)), jnp.float32)  # 6 ubatches
        def stage(w, x):
            return x @ w[0]
        out = pipeline_forward(stage, ws, xs, mesh=mesh, axis="stage")
        want = xs
        for s in range(4):
            want = jnp.einsum("mbi,ij->mbj", want, ws[s, 0])
        err = float(jnp.max(jnp.abs(out - want)))
        assert err < 1e-4, err
        print("OK", err)
    """)
    assert "OK" in out


def test_small_mesh_train_step_shards():
    """A reduced config train step lowers + runs on a real 2x4 mesh, with
    the policy shardings, and matches the single-device result."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs import get_smoke_config
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.optim.adamw import AdamW
        from repro.runtime.steps import make_train_step
        cfg = get_smoke_config("llama3p2_1b")
        mesh = make_mesh((2, 4), ("data", "model"))
        params = lm.init_params(cfg, jax.random.key(0))
        opt = AdamW(warmup_steps=1)
        step = make_train_step(cfg, opt, remat="none", ce_chunk=16)
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32) + 3,
                 "labels": jnp.ones((4, 32), jnp.int32)}
        # sharded
        p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            shd.param_specs(cfg, mesh))
        with mesh:
            p = jax.device_put(params, p_sh)
            st = opt.init(p)
            p2, st2, m = jax.jit(step)(p, st, batch)
            sharded_loss = float(m["loss"])
        # single-device reference
        p2r, st2r, mr = jax.jit(step)(params, opt.init(params), batch)
        ref_loss = float(mr["loss"])
        assert abs(sharded_loss - ref_loss) < 1e-4, (sharded_loss, ref_loss)
        print("OK", sharded_loss, ref_loss)
    """)
    assert "OK" in out


def test_moe_expert_parallel_consistency():
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs import get_smoke_config
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        cfg = get_smoke_config("olmoe_1b_7b")
        mesh = make_mesh((2, 4), ("data", "model"))
        params = lm.init_params(cfg, jax.random.key(1))
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab, (2, 16)), jnp.int32)
        ref, _ = jax.jit(lambda p, t: lm.forward(p, cfg, t))(params, toks)
        p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            shd.param_specs(cfg, mesh))
        with mesh:
            p = jax.device_put(params, p_sh)
            got, _ = jax.jit(lambda p, t: lm.forward(p, cfg, t))(p, toks)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 1e-3, err
        print("OK", err)
    """)
    assert "OK" in out


def test_dryrun_cell_on_small_mesh():
    """The dry-run path itself (lower+compile+roofline) on an 8-device
    toy mesh with a reduced config — exercises the exact production code."""
    out = run_forced("""
        import jax, dataclasses
        from repro.configs import get_smoke_config
        from repro.launch.dryrun import lower_cell
        from repro.models.config import SHAPES, ShapeConfig
        from repro.perf.roofline import roofline
        cfg = get_smoke_config("h2o_danube_1p8b")
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        SHAPES["toy"] = ShapeConfig("toy", 64, 8, "train")
        lowered, _ = lower_cell(cfg, "toy", mesh, remat="none", ce_chunk=16)
        compiled = lowered.compile()
        rl = roofline("toy", compiled, cfg, SHAPES["toy"], mesh.size)
        assert rl.flops > 0 and rl.hbm_bytes > 0
        assert rl.coll_bytes > 0  # TP all-reduces must be present
        print("OK", rl.bottleneck, rl.flops)
    """)
    assert "OK" in out


def test_elastic_restore_across_mesh_shapes(tmp_path):
    """Checkpoint written under a (2,4) mesh restores onto (4,2) and (1,1)."""
    out = run_forced(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.ckpt import CheckpointManager
        from repro.ckpt.manager import restore_resharded
        from repro.configs import get_smoke_config
        from repro.dist import sharding as shd
        from repro.models import lm
        cfg = get_smoke_config("llama3p2_1b")
        params = lm.init_params(cfg, jax.random.key(0))
        mesh1 = jax.make_mesh((2, 4), ("data", "model"))
        p_sh1 = jax.tree.map(lambda s: NamedSharding(mesh1, s),
                             shd.param_specs(cfg, mesh1))
        p1 = jax.device_put(params, p_sh1)
        mgr = CheckpointManager({str(tmp_path)!r}, keep=2)
        mgr.save(5, p1)
        mesh2 = jax.make_mesh((4, 2), ("data", "model"))
        p_sh2 = jax.tree.map(lambda s: NamedSharding(mesh2, s),
                             shd.param_specs(cfg, mesh2))
        restored, _ = restore_resharded(mgr, params, p_sh2)
        a = np.asarray(jax.device_get(restored["embed"]))
        b = np.asarray(jax.device_get(params["embed"]))
        np.testing.assert_array_equal(a, b)
        print("OK")
    """)
    assert "OK" in out


def test_split_d_decode_attention_matches_dense():
    """The shard_map split-d decode path (Perf iter. 7) is numerically
    identical to the dense decode attention on a real multi-device mesh."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import attention as A
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        b, s, hq, hkv, d = 4, 32, 6, 3, 8   # hkv=3 doesn't divide 4
        q = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
        cl = jnp.asarray(s, jnp.int32)
        want = A.decode_attention(q, k, v, cl)
        with mesh:
            got = jax.jit(lambda q, k, v: A.decode_attention_split_d(
                q, k, v, cl, mesh=mesh, batch_axes=("data",)))(q, k, v)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 2e-5, err
        print("OK", err)
    """)
    assert "OK" in out


def test_seq_sharded_prefill_attention_matches_dense():
    """The shard_map sequence-sharded prefill path (Perf iter. 8) matches
    the reference flash attention on a real mesh, incl. the causal mask
    across shard boundaries."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import attention as A
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(1)
        b, s, hq, hkv, d = 4, 64, 6, 2, 8
        q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
        want = A.flash_attention_scan(q, k, v, causal=True, q_block=16,
                                      kv_block=16)
        with mesh:
            got = jax.jit(lambda q, k, v: A.flash_attention_seq_sharded(
                q, k, v, causal=True, mesh=mesh,
                batch_axes=("data",)))(q, k, v)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 2e-5, err
        # windowed variant too
        want_w = A.flash_attention_scan(q, k, v, causal=True, window=24,
                                        q_block=16, kv_block=16)
        with mesh:
            got_w = jax.jit(lambda q, k, v: A.flash_attention_seq_sharded(
                q, k, v, causal=True, window=24, mesh=mesh,
                batch_axes=("data",)))(q, k, v)
        err_w = float(jnp.max(jnp.abs(got_w - want_w)))
        assert err_w < 2e-5, err_w
        print("OK", err, err_w)
    """)
    assert "OK" in out
