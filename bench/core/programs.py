"""Which of the program's compiled serving steps a device event belongs to.

The serving program jits every step under one name (``step``), so its
runs all appear as ``jit_step`` programs that differ by program id. The
program's counters say how many times each kind ran while the profiler
was on: ``decode_steps`` decode calls and ``prefill_steps`` prefill calls
(256-token chunks and single-step buckets). The decode program is the
one that ran exactly ``decode_steps`` times while the other ``jit_step``
programs ran ``prefill_steps`` times between them. Where no program, or
more than one, fits those counts, the split is unknown and the readers
that need it stay silent rather than guess.
"""

from __future__ import annotations

import re

STEP = re.compile(r"^jit_step\b")


def step_programs(modules, lo: float, hi: float) -> dict[str, list[float]]:
    """{program key: [runs, device ns]} of the serving steps that started
    within [lo, hi]."""
    out: dict[str, list[float]] = {}
    for s, e, key in modules:
        if lo <= s <= hi and STEP.match(key):
            g = out.setdefault(key, [0, 0.0])
            g[0] += 1
            g[1] += e - s
    return out


def split(groups: dict[str, list[float]], decode_steps: int,
          prefill_steps: int):
    """(decode key, prefill keys), or (None, []) where the counts do not
    single out one decode program."""
    fits = [
        k for k, (runs, _) in groups.items()
        if runs == decode_steps
        and sum(g[0] for j, g in groups.items() if j != k) == prefill_steps
    ]
    if decode_steps <= 0 or len(fits) != 1:
        return None, []
    dec = fits[0]
    return dec, [k for k in groups if k != dec]
