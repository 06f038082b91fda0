"""Build the system under test, the engine ``repro.launch.serve`` builds,
from a benchmark configuration, and warm up the shapes a workload uses.

Besides the traced run's span recorder (``bench/run.py``), this is where
the harness reaches into the program.
"""

from __future__ import annotations

import numpy as np

from bench.core.config import ModelConfig
from bench.core.traffic import Workload, bucket, prompt_shape_classes


def program_config(cfg: ModelConfig):
    """The program's ModelConfig at the benchmark configuration's sizes,
    as the configuration's family maps them onto the program's own
    configuration of ``arch``."""
    from repro.configs import get_config

    return cfg.family.program_config(cfg.sizes, get_config(cfg.arch))


def serve_args(cfg: ModelConfig, seed: int):
    s = cfg.serving
    w_bits = program_config(cfg).w_bits
    argv = [
        "--batch", str(s.lanes),
        "--max-len", str(s.max_len),
        "--prefill-chunk", str(s.prefill_chunk),
        "--block-tokens", str(s.block_tokens),
        "--seed", str(seed),
        "--prefix-cache" if s.prefix_cache else "--no-prefix-cache",
    ]
    if w_bits:
        argv += ["--quant", str(w_bits)]
    if s.vmem_budget_mib:
        argv += [
            "--vmem-budget", repr(s.vmem_budget_mib),
            "--prompt-len", str(s.plan_prompt_len),
            "--gen-len", str(s.plan_gen_len),
        ]
    from repro.launch import serve

    return serve.build_parser().parse_args(argv)


def build_engine(cfg: ModelConfig, params, seed: int):
    """A fresh scheduler over a fresh KV pool, as ``launch.serve`` builds
    it. A budgeted configuration must get the plan its file records."""
    from repro.launch import serve

    pcfg = program_config(cfg)
    sched = serve.build_pool_engine(pcfg, params, serve_args(cfg, seed))
    if sched.residency is not None:
        mask = sched.residency.layer_stream_mask(pcfg)
        streamed = tuple(i for i, s in enumerate(mask) if s)
        if streamed != cfg.serving.streamed_layers:
            raise ValueError(
                f"{cfg.name}: the residency plan streams layers {streamed}; "
                f"the configuration records {cfg.serving.streamed_layers}"
            )
    return sched


def warmup_requests(
    w: Workload, chunk: int, block_tokens: int, vocab: int, seed: int,
    prefix_cache: bool,
) -> list[list[tuple[np.ndarray, int]]]:
    """Waves of (prompt, max_new) that send every shape ``w`` uses through
    the engine once: one prompt per single-step prefill bucket the
    traffic reaches, one chunked prompt, and with the prefix cache on a
    pair whose second adopts the first's blocks with a partial last block
    (copy-on-write, then suffix prefill). Unshared traffic reaches that
    path too, whenever a prompt happens to begin with a cached prompt's
    first tokens. Each wave runs to completion before the next, so the
    second of a pair finds the first cached. The cache keeps only full
    blocks, so the first of the pair fills two: the second then matches
    one and a half of them."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x3A3]))
    cls = prompt_shape_classes(w, chunk)

    def tokens(n):
        return rng.integers(0, vocab, size=n).astype(np.int32)

    first: list[tuple[np.ndarray, int]] = []
    for b in sorted({bucket(n, block_tokens) for n in cls["short_lengths"]}):
        first.append((tokens(b), 2))
    if cls["chunked"]:
        first.append((tokens(min(cls["longest_prompt"], chunk + 1)), 2))
    waves = [first]
    if prefix_cache:
        shared = tokens(block_tokens + block_tokens // 2)
        waves[0].append(
            (np.concatenate([shared, tokens(block_tokens // 2)]), 2)
        )
        waves.append([(np.concatenate([shared, tokens(5)]), 2)])
    return [wave for wave in waves if wave]


def warm_up(sched, waves) -> None:
    for wave in waves:
        for prompt, max_new in wave:
            sched.submit(prompt, max_new)
        sched.run()
