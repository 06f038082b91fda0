"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic
mix, limits and metrics are files found by name (``bench.core.registry``).
The run makes the weights on the device from the seed, builds the engine
``repro.launch.serve`` builds, warms up every shape the traffic uses,
drives one open- or closed-loop window through ``Scheduler.round``,
drains, reads peak device memory, frees the program's state, and then
compares a sample of the served tokens with the plain reference.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, with the profiler on from the window's last seconds
through the drain.

It needs the accelerator: without one, or with fewer chips than the cell
asks for, it exits non-zero and prints no result. ``--rehearsal`` runs the
same path on the CPU at the configuration's rehearsal sizes, for tests;
it prints a ``[rehearsal]`` line and never the result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the persistent compile cache: a fixed path inside the checkout, so that
# only a checkout's first run of a cell compiles
CACHE_DIR = ROOT / ".jax_cache"
# a --trace 1 run traces the last TRACE_S seconds of the window and the
# drain; the profiler stops only after the drain, because writing the
# trace out stalls the host for seconds and every waiting request with it
TRACE_S = 12.0


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU path at rehearsal sizes; prints no result")
    return ap.parse_args(argv)


def device_info(jax, rehearsal: bool, chips: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearsal and (info["platform"] != "tpu" or info["count"] < chips):
        raise SystemExit(
            f"bench/run.py: the cell needs {chips} TPU chip(s); JAX found "
            f"{info['count']} {info['platform']} device(s)"
        )
    return info


def memory_peak(jax) -> int | None:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(
    root: pathlib.Path, workload: str, seed: int, seconds: float,
    trace: bool, rehearsal: bool = False, rate_per_s: float | None = None,
    control: bool = False, strict: bool = True,
) -> dict:
    """One run of one cell; returns the result object.

    ``rate_per_s`` replaces an open-loop mix's rate (the knee sweep) and
    ``control`` adds the lower-precision control's reading of the same
    served positions under ``"control"`` (setting the limits); the
    benchmark's own runs use neither. With ``strict`` off, a metric the
    sample cannot support is left out instead of failing the run."""
    import jax

    from bench.core import check, registry
    from bench.core.config import load_config
    from bench.core.engine import (
        build_engine, program_config, warm_up, warmup_requests,
    )
    from bench.core.peaks import peaks_for
    from bench.core.record import Run
    from bench.core.stats import TooFewSamples
    from bench.core.trace import summarize
    from bench.core.traffic import generate, load_mix
    from bench.core.window import CompileCounter, counters, drive

    # set-up phases, to tell which part of set-up moves between runs
    phases = [("imports", time.monotonic())]
    cell = registry.find_cell(root, workload)
    dev = device_info(jax, rehearsal, cell.chips)
    phases.append(("devices", time.monotonic()))
    peaks = None if rehearsal else peaks_for(dev["kind"])
    cfg = load_config(cell.config_file, cell.config_name, rehearsal, root)
    family = cfg.family
    mix = load_mix(cell.traffic_file, rehearsal)
    if rate_per_s is not None:
        mix["rate_per_s"] = rate_per_s
    work = generate(mix, seed, seconds, cfg.sizes.vocab, cfg.serving.max_len)
    limits = check.load_limits(cell.limits_file)
    compiles = CompileCounter()

    padded_vocab = program_config(cfg).padded_vocab
    weights = family.make_weights(cfg.sizes, seed, padded_vocab)
    jax.block_until_ready(weights)
    phases.append(("weights", time.monotonic()))
    sched = build_engine(cfg, weights, seed)
    phases.append(("engine", time.monotonic()))
    warm_up(sched, warmup_requests(
        work, cfg.serving.prefill_chunk, cfg.serving.block_tokens,
        cfg.sizes.vocab, seed, cfg.serving.prefix_cache,
    ))
    phases.append(("warm-up", time.monotonic()))
    del sched
    gc.collect()
    sched = build_engine(cfg, weights, seed)
    phases.append(("engine again", time.monotonic()))

    hooks = ()
    trace_dir = None
    traced: list[float] = []
    traced_counts: list[dict] = []
    spans = None
    if trace:
        from repro.runtime.spans import SpanRecorder
        from repro.runtime.tracker import MemoryTracker

        spans = MemoryTracker()
        sched.spans = SpanRecorder(time.monotonic, tracker=spans)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1

        def start():
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced.append(time.monotonic())
            traced_counts.append(counters(sched))

        hooks = ((max(0.0, seconds - TRACE_S), start),)

    setup_s = time.monotonic() - T_START
    window = drive(sched, work, seconds, compiles, annotate=trace, at=hooks)
    if trace:
        traced.append(time.monotonic())
        traced_counts.append(counters(sched))
        jax.profiler.stop_trace()
    peak = memory_peak(jax)

    finished = [
        rid for rid in window.attempted
        if window.finished(rid, window.specs[rid].max_new)
    ]
    served = [
        check.Served(rid, window.specs[rid].prompt,
                     tuple(sched.requests[rid].output))
        for rid in finished
    ]
    del sched, weights
    gc.collect()

    summary = None
    if trace:
        files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
        summary = summarize(files[0]) if files else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(
        cell=workload, cfg=cfg, workload=work, window=window,
        setup_s=setup_s, peaks=peaks,
        spans=spans.spans if spans is not None else None,
        trace=summary,
        traced=tuple(traced) if len(traced) == 2 else None,
        traced_counts=(
            tuple(traced_counts) if len(traced_counts) == 2 else None
        ),
    )

    chosen = check.sample(served, seed)
    ref_weights = family.make_weights(cfg.sizes, seed, padded_vocab)
    gaps = check.gaps(
        family, cfg.sizes, ref_weights, chosen,
        cfg.serving.max_len, work.max_new,
    )
    if control:
        low = check.gaps(
            family, cfg.sizes, ref_weights, chosen,
            cfg.serving.max_len, work.max_new, control=True,
        )
    del ref_weights
    numbers = {"gap_max": float(gaps.max()) if len(gaps) else float("inf")}
    # the rehearsal's float32 toy model has limits of its own
    key = "rehearsal_limit" if rehearsal else "limit"
    checks = {
        k: {"value": v, "limit": limits[k][key]} for k, v in numbers.items()
    }
    failed = len(window.attempted) - len(finished)
    correct = (
        bool(chosen)
        and all(c["value"] <= c["limit"] for c in checks.values())
    )

    say(f"compiles inside the window: {window.compiles_in_window}, in the "
        f"drain: {window.compiles_in_drain}; compiled {window.compiled}")
    late = sorted(window.lateness) or [0.0]
    late_p99 = late[int(0.99 * (len(late) - 1))]
    say(f"generator lateness p99: {late_p99 * 1e3:.3f} ms, max "
        f"{late[-1] * 1e3:.3f} ms, over {len(window.lateness)} submissions")
    say("set-up phases (s): " + ", ".join(
        f"{name} {t - t0:.3f}"
        for (name, t), t0 in zip(phases, [T_START] + [t for _, t in phases])
    ))
    say(f"peak_bytes_in_use: {peak}")
    say(f"requests attempted {len(window.attempted)}, finished "
        f"{len(finished)}, withdrawn unsent {len(window.withdrawn)}; "
        f"checked {len(chosen)} requests, {len(gaps)} served tokens")

    kind, entries = (
        ("metrics", cell.per_layer) if trace else ("end_to_end", cell.end_to_end)
    )
    metrics = {}
    for entry in entries:
        try:
            value = registry.reader(root, kind, entry["name"]).read(run)
        except TooFewSamples:
            if strict:
                raise
            value = None
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = dict(dev, memory_peak_bytes=peak)
    result = {
        "correct": correct,
        "attempted": len(window.attempted),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace and summary is not None and run.traced is not None:
        from bench.core.breakdown import breakdown, busy_window

        busy_s, window_s = busy_window(run)
        device.update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = breakdown(run)
    result["window"] = {
        "drain_s": window.t_end - window.t_close,
        "late_p99_ms": late_p99 * 1e3,
        "compiles": window.compiles_in_window,
        "queued_at_close": window.queued_at_close,
        "longest_round_s": max(
            (b - a for a, b in window.rounds if a <= window.t_close),
            default=0.0,
        ),
        "finished_in_window": sum(
            1 for r in finished
            if window.tokens[r][-1] <= window.t_close
        ),
    }
    if control:
        result["control"] = {"gap_max": float(low.max()) if len(low) else None}
    result["checks"] = checks
    return result


def configure_jax(rehearsal: bool):
    """Put the program and the harness on the path and point JAX's
    persistent compile cache into the checkout; returns ``jax``."""
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"bench/run.py: no program (src/repro) in {ROOT}")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        # the TPU runtime pins a host buffer for transfers when it starts;
        # pinning the default size took 6-10 s of set-up on a v5e host,
        # varying from run to run, where 256 MiB takes about 2 s and
        # still holds the largest transfer of a step (the logits, 6.3 MB)
        os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 * 2**20))
    import jax

    if not rehearsal:
        CACHE_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    args = parse(argv)
    configure_jax(args.rehearsal)
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), args.rehearsal)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    if args.rehearsal:
        print("[rehearsal] " + json.dumps(result))
    else:
        print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
