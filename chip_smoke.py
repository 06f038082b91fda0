#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip.

    python chip_smoke.py               # phases A and B, one chip
    python chip_smoke.py --four-chips  # only the sharded-training check, four chips

Phase A serves full-width smollm_360m (bf16, prefix cache on, prompts
chunked into 256-token prefill steps) through ``repro.launch.serve`` and
compares the logits two requests sampled from, after prefill and after
their first decode step, with ``lm.forward`` over the same tokens.

Phase B serves the same model with 2-bit FFN weights twice: unbudgeted,
and under a VMEM budget that leaves about half of the layers' FFN weights
in HBM, streamed through the Pallas ``weight_stream`` kernel. It checks
that the budgeted decode step lowers to that kernel and compares the two
runs' logits.

``--four-chips`` trains smollm_360m at full width for a few steps on the
mesh ``launch.train.fit_mesh`` builds over four chips, then on a
one-device mesh in the same process, and compares the losses.

Every number printed before the last line is a smoke reading, not a
benchmark number. The last line is one JSON object naming the device.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke.py: no src/repro next to {ROOT}; run it from a "
             "checkout of the repository")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import jaxlib  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch import serve as serve_lib  # noqa: E402
from repro.launch import train as train_lib  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.runtime.residency.executor import cached_budgeted_step  # noqa: E402

ARCH = "smollm_360m"
# 16 requests over 8 lanes: two waves, so the second reuses lanes and
# pool blocks the first released. 512-token prompts in 256-token chunks.
SERVE_ARGV = (
    "--arch", ARCH, "--requests", "16", "--batch", "8",
    "--prompt-len", "512", "--gen-len", "32", "--max-len", "1024",
    "--prefill-chunk", "256", "--seed", "0",
)
# request 0 runs in the first wave; request 15 in the second, on a
# recycled lane and recycled blocks
PROBES = (0, 15)
# 17 of the 32 layers' FFN weights stream at this budget (2-bit carriers)
VMEM_BUDGET_MIB = 28.0
# Phase A tolerance on max |logit difference|, as a fraction of the
# reference row's spread (max - min). Serving and lm.forward run the same
# bf16 math in different orders (paged gather, chunked prefill, one-token
# decode against a full-sequence pass), so they differ by bf16 rounding
# carried through 32 layers: 0.007-0.008 on a v5e. The controls printed
# beside it (attending another request's rows: about 0.7; dropping the
# newest row: 0.05-0.1) must exceed it, or the check could not see a
# wrong block table or length.
A_TOL = 0.025
# Phase B tolerance, same form: streamed layers multiply exact {-1,0,1}
# weights and scale in f32, resident layers in bf16, so the two runs
# differ by one bf16 rounding per FFN output of each streamed layer
# (0.0055 on a v5e). Not token-identical: greedy picks among near-tied
# random-weight logits flip.
B_TOL = 0.025
TRAIN_ARGV = ("--arch", ARCH, "--steps", "3", "--batch", "8", "--seq", "256")
# Four chips against one: same params (the partitionable threefry draws
# the same values under any sharding), same batches; the losses differ
# only by the order of bf16 partial sums across the model axis.
LOSS_TOL = 0.02


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def max_diff(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.max(np.abs(a.astype(np.float64) - b.astype(np.float64))))


def spread(row: np.ndarray) -> float:
    return float(np.max(row) - np.min(row))


def serve_once(cfg, params, argv, probes):
    """One run of ``launch.serve``'s pool engine. Returns its summary, the
    (V,) logits rows each probed request sampled its first two tokens
    from, and the engine."""
    args = serve_lib.build_parser().parse_args(list(argv))
    sched = serve_lib.build_pool_engine(cfg, params, args)
    rows: dict[tuple[int, int], np.ndarray] = {}

    def keep(rid, n, row):
        if rid in probes and n < 2:
            rows[rid, n] = np.asarray(row[: cfg.vocab], np.float32)

    sched.on_logits = keep
    m = serve_lib.run_pool_engine(cfg, params, args, sched)
    return m, rows, sched


def serve_twice(label, cfg, params, argv, probes):
    """Cold run (compiles) then warm run (compiled programs reused)."""
    cold, _, _ = serve_once(cfg, params, argv, probes)
    m, rows, sched = serve_once(cfg, params, argv, probes)
    say(
        f"{label}: {m['requests']} requests, {m['generated_tokens']} "
        f"generated tokens, {m['prefill_steps']} prefill + "
        f"{m['decode_steps']} decode steps; wall {cold['wall_s']:.3f} s "
        f"cold (compiles), {m['wall_s']:.3f} s warm, compile about "
        f"{cold['wall_s'] - m['wall_s']:.3f} s; warm outputs equal cold: "
        f"{cold['outputs'] == m['outputs']}"
    )
    return m, rows, sched


class SmokeFailure(RuntimeError):
    """A smoke check failed; the script exits non-zero."""


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def check(label: str, diff: float, tol: float) -> None:
    require(diff <= tol, f"{label}: {diff} > {tol}")


def phase_a(cfg, argv=SERVE_ARGV, probes=PROBES) -> None:
    """Unbudgeted serving against ``lm.forward`` over the same tokens."""
    tol = A_TOL
    args = serve_lib.build_parser().parse_args(list(argv))
    params = lm.init_params(cfg, jax.random.key(args.seed))
    m, rows, _ = serve_twice("phase A (bf16)", cfg, params, argv, probes)
    prompts = serve_lib.make_requests(args, cfg.vocab)
    forward = jax.jit(lambda p, t: lm.forward(p, cfg, t)[0][0])

    def last_logits(tokens):
        out = forward(params, jnp.asarray(np.asarray(tokens, np.int32)[None]))
        return np.asarray(out[-1, : cfg.vocab], np.float32)

    for i, rid in enumerate(probes):
        prompt, first = prompts[rid], m["outputs"][rid][0]
        other = prompts[probes[(i + 1) % len(probes)]]
        seq = np.concatenate([prompt, [first]])
        ref = forward(params, jnp.asarray(seq[None], jnp.int32))
        ref = np.asarray(ref[:, : cfg.vocab], np.float32)
        p = len(prompt)
        for n, pos in ((0, p - 1), (1, p)):
            want = ref[pos]
            scale = spread(want)
            got = max_diff(rows[rid, n], want) / scale
            # what a wrong block table gives: the same last token over
            # another request's rows
            ctx = np.concatenate([other[:pos], seq[pos : pos + 1]])
            wrong_rows = max_diff(last_logits(ctx), want) / scale
            # what a length off by one gives: the newest row missing
            short = np.concatenate([seq[: pos - 1], seq[pos : pos + 1]])
            wrong_len = max_diff(last_logits(short), want) / scale
            step = "prefill" if n == 0 else "decode 1"
            say(
                f"phase A request {rid} {step}: max |serve - forward| / "
                f"spread = {got:.6f} (tolerance {tol}); controls: other "
                f"request's rows {wrong_rows:.6f}, newest row dropped "
                f"{wrong_len:.6f}; spread {scale:.4f}"
            )
            check(f"phase A request {rid} {step}", got, tol)
            require(
                min(wrong_rows, wrong_len) > tol,
                f"phase A controls {wrong_rows}, {wrong_len} do not exceed "
                f"the tolerance {tol}: the check cannot see a wrong row "
                "table or length",
            )


def phase_b(
    cfg, argv=SERVE_ARGV, budget_mib=VMEM_BUDGET_MIB, probes=PROBES,
    expect_kernel=True,
) -> None:
    """2-bit serving, unbudgeted against budgeted (FFNs streamed)."""
    tol = B_TOL
    args = serve_lib.build_parser().parse_args(list(argv))
    cfg = dataclasses.replace(cfg, w_bits=2)
    params = lm.init_params(cfg, jax.random.key(args.seed))
    q_argv = tuple(argv) + ("--quant", "2")
    base, base_rows, _ = serve_twice(
        "phase B (2-bit, unbudgeted)", cfg, params, q_argv, probes
    )
    b_argv = q_argv + ("--vmem-budget", str(budget_mib))
    m, rows, sched = serve_twice(
        f"phase B (2-bit, {budget_mib} MiB VMEM budget)",
        cfg, params, b_argv, probes,
    )
    r = m["residency"]
    streamed_layers = sum(sched.residency.layer_stream_mask(cfg))
    streamed_blocks = r["n_blocks"] - r["resident_blocks"]
    say(
        f"phase B plan: {streamed_blocks} of {r['n_blocks']} FFN weight "
        f"blocks streamed ({streamed_layers} of {cfg.n_layers} layers), "
        f"{r['resident_mib']} MiB pinned, ring depth {r['stream_ahead']}"
    )
    require(streamed_blocks > 0, "the budget left no block streamed")
    require(streamed_layers < cfg.n_layers, "the budget pinned no layer")
    if expect_kernel:
        step = cached_budgeted_step(cfg, sched.residency)
        b = sched.slots
        hlo = step.lower(
            sched.params,
            jnp.zeros((b, 1), jnp.int32),
            sched.pool.k,
            sched.pool.v,
            jnp.zeros((b, sched.n_table), jnp.int32),
            jnp.zeros((b,), jnp.int32),
        ).as_text()
        require("tpu_custom_call" in hlo, "budgeted step runs no kernel")
        say("phase B budgeted decode step lowers to a tpu_custom_call")
    same = sum(
        base["outputs"][rid] == m["outputs"][rid] for rid in m["outputs"]
    )
    say(
        f"phase B token identity: {same} of {len(m['outputs'])} requests "
        "generate the same tokens budgeted and unbudgeted"
    )
    for rid in probes:
        for n in (0, 1):
            want = base_rows[rid, n]
            got = max_diff(rows[rid, n], want) / spread(want)
            step = "prefill" if n == 0 else "decode 1"
            say(
                f"phase B request {rid} {step}: max |budgeted - "
                f"unbudgeted| / spread = {got:.6f} (tolerance {tol})"
            )
            check(f"phase B request {rid} {step}", got, tol)


def param_bytes_per_device(params) -> dict[str, int]:
    out: dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return out


def four_chip_training(cfg) -> None:
    """``launch.train`` on ``fit_mesh`` over four chips against the same
    steps on one device."""
    tol = LOSS_TOL
    args = train_lib.build_parser().parse_args(list(TRAIN_ARGV))
    mesh = train_lib.fit_mesh()
    require(mesh.devices.size == 4, f"fit_mesh gave {dict(mesh.shape)}")
    params, _, log4, _ = train_lib.run_training(cfg, mesh, args)
    per_dev = param_bytes_per_device(params)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    split = sum(
        not leaf.sharding.is_fully_replicated
        for leaf in jax.tree.leaves(params)
    )
    del params
    say(
        f"four chips, mesh {dict(mesh.shape)}: {split} parameter arrays "
        f"split; parameter bytes per device {per_dev}, {total} in all"
    )
    require(len(per_dev) == 4, f"parameters on devices {sorted(per_dev)}")
    require(split > 0, "every parameter is replicated")
    require(max(per_dev.values()) < total, "one device holds every byte")
    one = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    _, _, log1, _ = train_lib.run_training(cfg, one, args)
    for e4, e1 in zip(log4, log1, strict=True):
        d = abs(e4["loss"] - e1["loss"])
        say(
            f"train step {e4['step']}: loss four chips {e4['loss']:.6f}, "
            f"one device {e1['loss']:.6f}, |diff| {d:.6f} (tolerance "
            f"{tol}); wall {e4['time_s']:.3f} s / {e1['time_s']:.3f} s"
        )
        require(bool(np.isfinite(e4["loss"])), f"loss not finite: {e4}")
        check(f"train step {e4['step']} loss", d, tol)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded-training check, on four chips",
    )
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    cache = pathlib.Path(use_compile_cache())
    say("every time below is a smoke reading, not a benchmark number")
    say(
        f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{importlib.metadata.version('libtpu')}; {len(jax.devices())} x "
        f"{dev.device_kind}"
    )
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    say(f"compile cache {cache}: {entries} entries at start")
    if args.four_chips:
        if len(jax.devices()) != 4:
            print(f"chip_smoke.py: --four-chips needs 4 chips, found "
                  f"{len(jax.devices())}", file=sys.stderr)
            return 1
        four_chip_training(get_config(ARCH))
    else:
        t0 = time.monotonic()
        phase_a(get_config(ARCH))
        say(f"phase A done in {time.monotonic() - t0:.1f} s")
        t0 = time.monotonic()
        phase_b(get_config(ARCH))
        say(f"phase B done in {time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
