"""Decide ``correct``: the served tokens against the plain reference.

After the window closes and the program's state is freed, a sample of the
finished requests (drawn from the seed, always holding the longest) is
run through the reference once each: prompt plus served tokens, one
forward pass. For every served token the reference gives the logits the
token should have been drawn from; the number compared is the widest gap
by which a served token's reference logit lies below the reference's
best. Under greedy decoding a correct system serves the reference's best
token up to rounding, where two candidates are all but tied; a wrong row,
a stale cache or an altered token serves one far below it.

The control puts the reference in the program's place at a lower
precision: at the same positions it takes the token the lower precision
ranks first, and reads the same gap.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

MIN_TOKENS = 512
MIN_REQUESTS = 8
MAX_REQUESTS = 32


@dataclasses.dataclass(frozen=True)
class Served:
    rid: int
    prompt: np.ndarray
    output: tuple[int, ...]


def sample(served: list[Served], seed: int) -> list[Served]:
    """The longest finished request, then others in an order the seed
    draws, until the sample holds ``MIN_TOKENS`` served tokens and
    ``MIN_REQUESTS`` requests (at most ``MAX_REQUESTS``)."""
    if not served:
        return []
    longest = max(served, key=lambda s: (len(s.prompt) + len(s.output), s.rid))
    rest = [s for s in served if s.rid != longest.rid]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    order = rng.permutation(len(rest))
    chosen = [longest]
    n_tok = len(longest.output)
    for i in order:
        if len(chosen) >= MAX_REQUESTS or (
            n_tok >= MIN_TOKENS and len(chosen) >= MIN_REQUESTS
        ):
            break
        chosen.append(rest[i])
        n_tok += len(rest[i].output)
    return chosen


def _inputs(s: Served, seq_len: int, rows_len: int):
    """Tokens (prompt + all served tokens but the last, right-padded) and
    the positions whose next-token logits produced each served token."""
    seq = np.concatenate([s.prompt, np.asarray(s.output[:-1], np.int32)])
    tokens = np.zeros(seq_len, np.int32)
    tokens[: len(seq)] = seq
    p = len(s.prompt)
    rows = np.zeros(rows_len, np.int32)
    rows[: len(s.output)] = np.arange(p - 1, p - 1 + len(s.output))
    return tokens, rows


def gaps(
    family, sizes, weights, chosen: list[Served], seq_len: int,
    rows_len: int, control: bool = False,
) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit of
    the token served (or, for the control, of the token the lower
    precision ranks first at that position). ``family`` is the
    configuration's family module, whose ``logits_at`` is the reference."""
    exact = family.logits_at(sizes, "f32")
    low = family.logits_at(sizes, "fp8") if control else None
    out = []
    for s in chosen:
        tokens, rows = _inputs(s, seq_len, rows_len)
        n = len(s.output)
        want = np.asarray(exact(weights, tokens, rows), np.float64)[:n]
        if control:
            picked = np.asarray(
                low(weights, tokens, rows), np.float64
            )[:n].argmax(-1)
        else:
            picked = np.asarray(s.output, np.int64)
        out.append(want.max(-1) - want[np.arange(n), picked])
    return np.concatenate(out) if out else np.zeros(0)


def load_limits(path: pathlib.Path) -> dict:
    """{number: {"limit": x, ...}} for one cell."""
    return json.loads(pathlib.Path(path).read_text())
