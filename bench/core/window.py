"""Drive the engine through one measured window and record host times.

One thread does everything: it submits each request when it is due (or,
in a closed loop, whenever the queue is short), runs ``Scheduler.round``
while there is work, and sleeps otherwise. The scheduler admits only at
the start of a round, so submitting between rounds hands it every request
it could have admitted; the wait counts, because every latency is taken
from the request's due time. The ``on_logits`` hook stamps each token
when its logits reach the host.

After the window closes no request arrives. Requests due in it are
followed to completion for as long again; one still unfinished then has
failed. A closed loop's queued backlog is withdrawn at the close: it was
never sent.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from collections import deque
from typing import Callable

import jax

from bench.core.traffic import Workload

COUNTERS = (
    "prefill_steps",
    "prefill_tokens",
    "prefix_hits",
    "prefix_hit_tokens",
    "decode_steps",
    "generated_tokens",
    "completed",
    "rounds",
)

# where JAX logs each compile under ``jax_log_compiles``
COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")
# compile events JAX reports through jax.monitoring
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileCounter:
    """Counts traces and backend compiles in the process from install on,
    and keeps the names JAX logs for the ones it compiles."""

    def __init__(self):
        self.n = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.config.update("jax_log_compiles", True)
        handler = logging.Handler(level=logging.WARNING)
        handler.emit = self._log
        for name in COMPILE_LOGGERS:
            log = logging.getLogger(name)
            log.addHandler(handler)
            log.propagate = False

    def _on(self, key: str, _secs: float, **_kw) -> None:
        if key in COMPILE_EVENTS:
            self.n += 1

    def _log(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" with ")[0][len("Compiling "):])


@dataclasses.dataclass
class Window:
    seconds: float
    t_open: float = 0.0
    t_close: float = 0.0
    t_end: float = 0.0  # end of the drain
    due: dict = dataclasses.field(default_factory=dict)  # rid -> due time
    specs: dict = dataclasses.field(default_factory=dict)  # rid -> spec
    lateness: list = dataclasses.field(default_factory=list)
    tokens: dict = dataclasses.field(default_factory=dict)  # rid -> times
    attempted: list = dataclasses.field(default_factory=list)
    withdrawn: list = dataclasses.field(default_factory=list)
    rounds: list = dataclasses.field(default_factory=list)  # (t0, t1)
    at_open: dict = dataclasses.field(default_factory=dict)
    at_close: dict = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    compiled: list = dataclasses.field(default_factory=list)  # names
    queued_at_close: int = 0
    compiles_in_drain: int = 0

    def finished(self, rid: int, max_new: int) -> bool:
        return len(self.tokens.get(rid, ())) >= max_new


def counters(sched) -> dict:
    return {k: getattr(sched.stats, k) for k in COUNTERS}


def _has_work(sched) -> bool:
    return bool(sched.queue) or any(r is not None for r in sched.active)


def drive(
    sched,
    workload: Workload,
    seconds: float,
    compiles: CompileCounter,
    annotate: bool = False,
    at: tuple[tuple[float, Callable[[], None]], ...] = (),
) -> Window:
    """Run ``workload`` on ``sched`` for ``seconds`` and drain.

    ``annotate`` wraps submit, round and the logits hook in profiler
    annotations. ``at`` holds (seconds after the open, callback) pairs run
    between rounds once their time has come (the traced run starts the
    profiler with one)."""
    ann = jax.profiler.TraceAnnotation if annotate else (
        lambda _name: contextlib.nullcontext()
    )
    w = Window(seconds=seconds)

    def on_logits(rid, _n, _row):
        with ann("bench.on_logits"):
            w.tokens.setdefault(rid, []).append(time.monotonic())

    sched.on_logits = on_logits
    specs = w.specs
    pending = deque(sorted(workload.requests, key=lambda r: r.due))
    hooks = deque(sorted(at, key=lambda h: h[0]))

    def submit(spec, due_abs):
        with ann("bench.submit"):
            rid = sched.submit(spec.prompt, spec.max_new, t_submit=due_abs)
        w.lateness.append(time.monotonic() - due_abs)
        w.due[rid] = due_abs
        specs[rid] = spec
        w.attempted.append(rid)

    def run_round():
        t0 = time.monotonic()
        with ann("bench.round"):
            sched.round()
        w.rounds.append((t0, time.monotonic()))

    w.at_open = counters(sched)
    n0, k0 = compiles.n, len(compiles.names)
    w.t_open = time.monotonic()
    w.t_close = w.t_open + seconds
    closed = False
    while True:
        now = time.monotonic()
        while hooks and now >= w.t_open + hooks[0][0]:
            hooks.popleft()[1]()
        if not closed and workload.loop == "open":
            # every arrival due by now, the window's last ones included
            while pending and w.t_open + pending[0].due <= now:
                spec = pending.popleft()
                submit(spec, w.t_open + spec.due)
        if not closed and now >= w.t_close:
            closed = True
            w.at_close = counters(sched)
            w.queued_at_close = len(sched.queue)
            w.compiles_in_window = compiles.n - n0
            if workload.loop == "closed":
                gone = {r.rid for r in sched.drain()}
                w.withdrawn = [r for r in w.attempted if r in gone]
                w.attempted = [r for r in w.attempted if r not in gone]
        if closed and now >= w.t_close + seconds:
            break
        if not closed and workload.loop == "closed":
            while len(sched.queue) < workload.backlog:
                if not pending:
                    raise RuntimeError(
                        "the closed loop ran out of requests; give the mix "
                        "more"
                    )
                submit(pending.popleft(), time.monotonic())
        if _has_work(sched):
            run_round()
        elif closed:
            break
        else:
            nxt = w.t_close
            if pending and workload.loop == "open":
                nxt = min(nxt, w.t_open + pending[0].due)
            if hooks:
                nxt = min(nxt, w.t_open + hooks[0][0])
            time.sleep(max(0.0, nxt - time.monotonic()))
    while hooks:  # a hook timed past the drain still runs
        hooks.popleft()[1]()
    w.t_end = time.monotonic()
    w.compiles_in_drain = compiles.n - n0 - w.compiles_in_window
    w.compiled = compiles.names[k0:]
    return w
