"""Serving driver: continuous batching over a shared KV pool.

The default engine is the ``runtime.scheduler`` subsystem: one physical
KV pool (``runtime.kv_pool``, block-granular, allocated/freed per
request), token-budget admission, single-step batched prefill, and
paged decode lanes that each run at their own depth. The legacy
fixed-batch loop (per-slot ring caches, lockstep positions, prompt
replayed token-by-token through the decode path) is kept as
``--engine fixed`` — it is the A/B baseline for ``benchmarks/serve_bench``
and the fallback for the SSM/hybrid families, whose decode state is
fixed-size per slot and needs no pool.

Usage::

    PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --smoke \
        --requests 12 --batch 4 --gen-len 16
"""

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import lm
from repro.models.config import (
    PACKING_FAMILIES,
    PAGED_FAMILIES,
    PREFIX_CACHE_FAMILIES,
)
from repro.runtime.kv_pool import KVPool, choose_block_tokens
from repro.runtime.prefix_cache import PrefixCache
from repro.runtime.scheduler import Scheduler
from repro.runtime.steps import make_serve_step


def make_requests(args, vocab: int) -> list[np.ndarray]:
    rng = np.random.default_rng(args.seed)
    return [
        rng.integers(0, vocab, size=(args.prompt_len,)).astype(np.int32)
        for _ in range(args.requests)
    ]


def build_residency_plan(cfg, args):
    """Compile the ``--vmem-budget`` residency plan (None when unbudgeted)."""
    if not args.vmem_budget:
        return None
    from repro.runtime.residency import TrafficProfile, compile_residency_plan
    from repro.runtime.residency.executor import supports_budgeted_decode

    if not supports_budgeted_decode(cfg):
        raise ValueError(
            f"--vmem-budget needs a streamable-FFN attention family; "
            f"{cfg.name} is {cfg.family!r}"
        )
    traffic = TrafficProfile(
        lanes=args.batch, prompt_len=args.prompt_len, gen_len=args.gen_len
    )
    return compile_residency_plan(
        cfg,
        vmem_budget_bytes=int(args.vmem_budget * 2**20),
        traffic=traffic,
    )


def build_pool_engine(cfg, params, args) -> Scheduler:
    total = args.prompt_len + args.gen_len
    block_tokens = args.block_tokens or choose_block_tokens(
        [total] * args.requests
    )
    pool = KVPool.for_slots(
        cfg, slots=args.batch, max_len=args.max_len, block_tokens=block_tokens
    )
    prefix_cache = None
    if args.prefix_cache and cfg.family in PREFIX_CACHE_FAMILIES:
        prefix_cache = PrefixCache(pool)
    tracker = None
    spans = None
    if getattr(args, "trace_out", None):
        from repro.runtime.tracker import JsonlTracker

        tracker = JsonlTracker(args.trace_out)
        if getattr(args, "trace_spans", True):
            # standalone serving has no virtual clock: spans are stamped
            # on the host monotonic clock instead (same record schema,
            # same Perfetto export; decomposition exactness is a
            # virtual-clock property and not asserted here)
            from repro.runtime.spans import SpanRecorder

            spans = SpanRecorder(time.monotonic, tracker=tracker)
    from repro.runtime.memledger import MemLedger, MemPressureMonitor

    # no engine stamp: standalone round records carry none either, and
    # the ledger/metrics engine keys must agree for validate_ledger. With
    # no tracker nothing would read its records: build none, so the pool
    # snapshots nothing on each mutation
    ledger = (
        MemLedger(time.monotonic, tracker=tracker)
        if tracker is not None
        else None
    )
    mem_monitor = MemPressureMonitor()
    speculator = None
    if getattr(args, "speculate", ""):
        from repro.runtime.speculative import SpecConfig, build_speculator

        speculator = build_speculator(
            cfg,
            params,
            SpecConfig(
                drafter=args.speculate,
                depth=args.spec_depth,
                quant=args.spec_quant,
            ),
            slots=args.batch,
            max_len=args.max_len,
            smoke=args.smoke,
        )
    return Scheduler(
        cfg,
        params,
        pool,
        slots=args.batch,
        max_len=args.max_len,
        token_budget=args.token_budget or None,
        decode_per_round=args.rf or None,
        sampling=lm.SamplingParams(
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            seed=args.seed,
        ),
        prefill_chunk=args.prefill_chunk or None,
        residency=build_residency_plan(cfg, args),
        prefix_cache=prefix_cache,
        speculative=speculator,
        tracker=tracker,
        spans=spans,
        ledger=ledger,
        mem_monitor=mem_monitor,
    )


def run_pool_engine(cfg, params, args, sched: Scheduler | None = None) -> dict:
    """Serve ``make_requests(args)`` to completion on ``sched`` (built from
    ``args`` when not given) and summarize the run."""
    if sched is None:
        sched = build_pool_engine(cfg, params, args)
    for prompt in make_requests(args, cfg.vocab):
        sched.submit(prompt, args.gen_len)
    t0 = time.monotonic()
    stats = sched.run()
    dt = time.monotonic() - t0
    if sched.tracker is not None:
        sched.tracker.finish()
    outputs = sched.outputs()
    assert stats.completed == args.requests, (stats.completed, args.requests)
    assert all(len(v) == args.gen_len for v in outputs.values())
    return {
        "engine": "pool",
        "requests": args.requests,
        "generated_tokens": stats.generated_tokens,
        "steps": stats.prefill_steps + stats.decode_steps,
        "prefill_steps": stats.prefill_steps,
        "decode_steps": stats.decode_steps,
        "wall_s": dt,
        "tokens_per_s": stats.generated_tokens / dt if dt > 0 else 0.0,
        "decode_step_ms": (
            stats.decode_time / stats.decode_steps * 1e3
            if stats.decode_steps
            else 0.0
        ),
        "mean_ttft_s": stats.mean_ttft,
        "pool_utilization": stats.steady_state_utilization,
        "block_tokens": sched.pool.block_tokens,
        "prefix_cache": sched.prefix_cache is not None,
        "prefix_hits": stats.prefix_hits,
        "prefix_hit_tokens": stats.prefix_hit_tokens,
        "prefix_hit_rate": stats.prefix_hit_rate,
        "shared_blocks_peak": stats.shared_blocks_peak,
        "cached_blocks": sched.pool.cached_blocks,
        "speculate": (
            sched.speculative.name if sched.speculative is not None else ""
        ),
        "spec_depth": (
            sched.speculative.depth if sched.speculative is not None else 0
        ),
        "accepted_tokens": stats.accepted_tokens,
        "draft_tokens": stats.draft_tokens,
        "verify_steps": stats.verify_steps,
        "accepted_per_step": stats.accepted_per_step,
        "residency": (
            sched.residency.summary() if sched.residency is not None else None
        ),
        "span_records": sched.spans.n_spans if sched.spans else 0,
        "mem": sched.mem_monitor.summary(now=time.monotonic()),
        "mem_records": sched.ledger.n_records if sched.ledger else 0,
        "fragmentation": sched.pool.fragmentation_report(),
        "outputs": outputs,
    }


@functools.lru_cache(maxsize=None)
def _jitted_fixed_step(cfg):
    return jax.jit(make_serve_step(cfg), donate_argnums=(2,))


def run_fixed_engine(cfg, params, args) -> dict:
    """The legacy fixed-batch loop: per-slot ring caches, lockstep
    positions, prompts replayed through the decode path. Drains the queue
    to empty (requests % batch != 0 included)."""
    if args.prompt_len + args.gen_len > args.max_len:
        # the ring cache holds max_len rows; past that, rows clobber
        # (caught in main -> exit 2, matching the pool engine's check)
        raise ValueError(
            f"request needs {args.prompt_len + args.gen_len} tokens "
            f"> max_len {args.max_len}"
        )
    serve = _jitted_fixed_step(cfg)
    queue = make_requests(args, cfg.vocab)
    b = args.batch
    cache = None  # allocated at each wave boundary below
    active = [None] * b
    to_go = np.zeros(b, np.int32)
    fed = np.zeros((b,), np.int32)
    prompts: list[np.ndarray | None] = [None] * b
    outputs: dict[int, list[int]] = {}
    ttft: dict[int, float] = {}
    next_req = 0
    done = 0
    steps = 0
    t0 = time.monotonic()
    token = np.zeros((b, 1), np.int32)
    decode_time = 0.0
    gen_steps = 0
    while done < args.requests:
        if next_req < len(queue) and all(a is None for a in active):
            # wave boundary (lockstep lengths drain all slots at once):
            # fresh ring + len=0 so a long trace can't overflow max_len
            # rows and clobber the new wave's KV history
            cache = lm.init_cache(cfg, b, args.max_len)
            token[:] = 0
        for i in range(b):
            if active[i] is None and next_req < len(queue):
                active[i] = next_req
                prompts[i] = queue[next_req]
                fed[i] = 0
                to_go[i] = args.gen_len
                outputs[next_req] = []
                next_req += 1
        ts = time.monotonic()
        logits, cache = serve(params, jnp.asarray(token), cache)
        steps += 1
        nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1), np.int32)
        generated_this_step = 0
        for i in range(b):
            if active[i] is None:
                continue
            if fed[i] < len(prompts[i]):  # still feeding the prompt
                token[i, 0] = prompts[i][fed[i]]
                fed[i] += 1
            else:
                if not outputs[active[i]]:
                    ttft[active[i]] = time.monotonic() - t0
                generated_this_step += 1
                outputs[active[i]].append(int(nxt[i]))
                token[i, 0] = nxt[i]
                to_go[i] -= 1
                if to_go[i] <= 0:
                    done += 1
                    active[i] = None
        if generated_this_step:
            # a decoding step, counted once per step, host bookkeeping
            # included (the pool engine's decode_time is measured the same
            # way around its round loop)
            decode_time += time.monotonic() - ts
            gen_steps += 1
        if steps > args.requests * (args.prompt_len + args.gen_len) + 64:
            raise RuntimeError("serving loop failed to drain the queue")
    dt = time.monotonic() - t0
    total_tokens = sum(len(v) for v in outputs.values())
    return {
        "engine": "fixed",
        "requests": args.requests,
        "generated_tokens": total_tokens,
        "steps": steps,
        "prefill_steps": 0,
        "decode_steps": steps,
        "wall_s": dt,
        "tokens_per_s": total_tokens / dt if dt > 0 else 0.0,
        "decode_step_ms": decode_time / gen_steps * 1e3 if gen_steps else 0.0,
        "mean_ttft_s": sum(ttft.values()) / len(ttft) if ttft else 0.0,
        "pool_utilization": 0.0,
        "block_tokens": 0,
        "prefix_cache": False,
        "prefix_hits": 0,
        "prefix_hit_tokens": 0,
        "prefix_hit_rate": 0.0,
        "shared_blocks_peak": 0,
        "cached_blocks": 0,
        "outputs": outputs,
    }


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", choices=["pool", "fixed"], default="pool")
    ap.add_argument("--block-tokens", type=int, default=0,
                    help="KV-pool block size; 0 = bin-cost sweep")
    ap.add_argument("--rf", type=int, default=0,
                    help="decode steps per admission round; 0 = Eq. 2 default")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="admission token budget; 0 = pool capacity")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill chunk size for long prompts; "
                         "0 = the admission token budget")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="radix prefix cache over the KV pool: requests "
                         "adopt their longest cached prefix's blocks and "
                         "prefill only the unmatched suffix "
                         "(--no-prefix-cache disables)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the top-k logits; 0 = off")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass; 1.0 = off")
    ap.add_argument("--speculate", default="",
                    help="speculative decoding drafter: 'ngram' (self-"
                         "drafting suffix match) or a canonical arch id "
                         "whose packed twin drafts for the target "
                         "(pool engine, dense/vlm/moe families)")
    ap.add_argument("--spec-depth", type=int, default=4,
                    help="draft chain depth k: each verify step scores "
                         "the pending token plus k-1 proposals")
    ap.add_argument("--spec-quant", type=int, default=2, choices=[1, 2],
                    help="packed-carrier width of a model drafter's FFN "
                         "(the twin's w_bits)")
    ap.add_argument("--quant", type=int, default=0, choices=[0, 1, 2],
                    help="serve with FCMP-packed 1/2-bit FFN weights "
                         "(inference-only carriers)")
    ap.add_argument("--vmem-budget", type=float, default=0.0,
                    help="MiB of VMEM for pinned weight blocks; decode "
                         "runs against the budgeted set, cold blocks "
                         "stream HBM->VMEM (0 = unbudgeted)")
    ap.add_argument("--trace-out", default="",
                    help="append one JSONL record per scheduler round "
                         "(runtime.tracker stream; pool engine only)")
    ap.add_argument("--trace-spans", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="emit per-request lifecycle span records into "
                         "--trace-out (wall-clock stamps; export with "
                         "perf.trace_export; --no-trace-spans for "
                         "rounds-only streams)")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    use_compile_cache()
    try:
        cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    except ValueError as e:
        print(f"[serve] {e}")
        return 2
    if cfg.family == "encdec":
        print(f"[serve] {cfg.name} is encoder-decoder, which has no serving "
              "path; use a decoder-only arch")
        return 2
    if args.quant:
        if cfg.family not in PACKING_FAMILIES:
            print(f"[serve] note: --quant has no effect on family "
                  f"{cfg.family!r} (no dense FFN to pack)")
        else:
            cfg = dataclasses.replace(cfg, w_bits=args.quant)
    engine = args.engine
    if engine == "pool" and cfg.family not in PAGED_FAMILIES:
        print(f"[serve] family {cfg.family!r} keeps fixed-size per-slot "
              "decode state and holds no KV rows; using the fixed-batch "
              "engine")
        engine = "fixed"
    if args.vmem_budget and engine == "fixed":
        # the fixed loop has no budgeted decode path; failing loudly beats
        # reporting numbers the user would read as budgeted
        print(f"[serve] --vmem-budget needs the pool engine's paged decode; "
              f"family {cfg.family!r} / --engine fixed cannot run budgeted")
        return 2
    if args.speculate and engine == "fixed":
        print(f"[serve] --speculate needs the pool engine's paged verify; "
              f"family {cfg.family!r} / --engine fixed cannot speculate")
        return 2

    params = lm.init_params(cfg, jax.random.key(args.seed))
    run = run_pool_engine if engine == "pool" else run_fixed_engine
    try:
        m = run(cfg, params, args)
    except ValueError as e:
        # bad request/budget geometry (e.g. prompt+gen > --max-len)
        print(f"[serve] {e}")
        return 2
    line = (
        f"[serve/{m['engine']}] {m['requests']} requests, "
        f"{m['generated_tokens']} generated tokens in {m['steps']} steps "
        f"({m['prefill_steps']} prefill + {m['decode_steps']} decode), "
        f"{m['wall_s']:.1f}s ({m['tokens_per_s']:.1f} tok/s, "
        f"TTFT {m['mean_ttft_s']*1e3:.0f} ms)"
    )
    if m["engine"] == "pool":
        line += f", pool utilization {m['pool_utilization']*100:.1f}%"
    print(line)
    if m.get("speculate"):
        print(
            f"[serve/spec] drafter {m['speculate']} depth {m['spec_depth']}: "
            f"{m['accepted_tokens']} tokens from {m['verify_steps']} verify "
            f"steps ({m['accepted_per_step']:.2f} accepted/step, "
            f"{m['draft_tokens']} drafted)"
        )
    if m.get("prefix_cache"):
        print(
            f"[serve/prefix] {m['prefix_hits']} prefix hits, "
            f"{m['prefix_hit_tokens']} prompt tokens served from cache "
            f"(hit rate {m['prefix_hit_rate']*100:.1f}%), "
            f"{m['shared_blocks_peak']} shared blocks at peak, "
            f"{m['cached_blocks']} blocks cached at drain"
        )
    if m.get("residency"):
        r = m["residency"]
        print(
            f"[serve/residency] {r['resident_blocks']}/{r['n_blocks']} "
            f"weight blocks pinned ({r['resident_mib']:.2f} MiB of "
            f"{r['vmem_budget_mib']:.2f} MiB budget), HBM re-stream "
            f"traffic cut {r['hbm_traffic_reduction']*100:.0f}%, "
            f"stream-ahead depth {r['stream_ahead']} (R_F)"
        )
    if m.get("mem"):
        mm = m["mem"]
        frag = mm.get("frag_at_peak") or {}  # drain-time report is empty
        line = (
            f"[serve/mem] signal {mm['signal']}, peak occupancy "
            f"{mm['peak_occupancy']*100:.1f}% "
            f"({mm['peak_held_blocks']} blocks, headroom "
            f"{mm['headroom_blocks']}), {mm['evicted_blocks']} blocks "
            f"evicted, {m['mem_records']} ledger records"
        )
        if frag:
            line += (
                f", packing at peak "
                f"{frag.get('baseline_efficiency', 1.0)*100:.1f}% "
                f"(FFD bound {frag.get('ffd_efficiency', 1.0)*100:.1f}%)"
            )
        print(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
