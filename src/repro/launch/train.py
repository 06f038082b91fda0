"""End-to-end training driver.

Runs on whatever devices exist: a (1, 1) mesh on this CPU container (the
examples train a ~100M-param model for a few hundred steps), the 16x16 /
2x16x16 production meshes on real pods. Fault tolerance comes from
``runtime.train.TrainLoop`` (atomic async checkpoints, deterministic
resume, straggler monitor).

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch smollm_360m \
        --smoke --steps 100 --batch 8 --seq 256 --ckpt /tmp/ckpt
"""

import argparse

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import TokenPipeline
from repro.dist import sharding as shd
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import lm
from repro.optim.adamw import AdamW
from repro.runtime.steps import make_train_step
from repro.runtime.train import TrainLoop, TrainLoopConfig


def fit_mesh():
    """Largest (data, model) mesh the available devices support."""
    n = len(jax.devices())
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--ce-chunk", type=int, default=0)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--quant", type=int, default=0, choices=[0, 1, 2])
    ap.add_argument("--seed", type=int, default=0)
    return ap


def run_training(cfg, mesh, args):
    """Initialise params sharded over ``mesh`` by the ``repro.dist``
    policy and run ``args.steps`` steps (resuming from ``args.ckpt``).
    Returns (params, opt_state, per-step log, start step)."""
    opt = AdamW(lr=args.lr)
    step_fn = make_train_step(
        cfg, opt, remat=args.remat, ce_chunk=args.ce_chunk
    )
    p_sh = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        shd.param_specs(cfg, mesh),
    )
    with mesh:
        params = jax.jit(
            lambda k: lm.init_params(cfg, k), out_shardings=p_sh
        )(jax.random.key(args.seed))
        opt_state = opt.init(params)
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))

        pipeline = TokenPipeline(
            vocab=cfg.vocab, batch=args.batch, seq_len=args.seq,
            seed=args.seed,
        )
        ckpt = CheckpointManager(args.ckpt) if args.ckpt else None
        loop = TrainLoop(
            step_fn=jitted,
            pipeline=pipeline,
            ckpt=ckpt,
            config=TrainLoopConfig(
                n_steps=args.steps, ckpt_every=args.ckpt_every,
                log_every=10,
            ),
        )
        params, opt_state, start = loop.restore_or_init(params, opt_state)
        params, opt_state, log = loop.run(params, opt_state, start)
    return params, opt_state, log, start


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    use_compile_cache()
    try:
        cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    except ValueError as e:
        print(f"[train] {e}")
        return 2
    if args.quant:
        # Families with a dense FFN store 1/2-bit weights as packed uint8
        # carriers (repro.models.lm), which are inference-only: no
        # gradients, no optimizer moments (optim.adamw._is_frozen).
        from repro.models.config import PACKING_FAMILIES

        if cfg.family in PACKING_FAMILIES:
            print(
                f"[train] --quant {args.quant} is not trainable: "
                f"{cfg.family!r} archs pack FFN weights into inference-only "
                "uint8 carriers. Train dense (no --quant), then quantize the "
                "checkpoint for serving (examples/pack_and_port.py, "
                "launch/serve.py)."
            )
            return 2
        # non-packing families: leave cfg untouched so the message stays
        # true downstream (ckpt metadata, traffic modeling keyed on w_bits)
        print(f"[train] note: --quant has no effect on family "
              f"{cfg.family!r} (no dense FFN to pack); ignoring")
    mesh = (
        make_production_mesh() if args.production_mesh else fit_mesh()
    )
    print(f"[train] {cfg.name}: {cfg.n_params()/1e6:.1f}M params, "
          f"mesh {dict(mesh.shape)}")
    params, opt_state, log, start = run_training(cfg, mesh, args)
    if start:
        print(f"[train] resumed from step {start}")

    first, last = log[0]["loss"], log[-1]["loss"]
    print(f"[train] steps {start}..{len(log)+start}: "
          f"loss {first:.4f} -> {last:.4f}")
    for e in log[:: max(1, len(log) // 10)]:
        print(f"  step {e['step']:5d} loss {e['loss']:.4f} "
              f"{e['time_s']*1e3:7.1f} ms")
    if not np.isfinite(last):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
