"""The one traffic generator: reads a mix's parameters from its JSON file.

Every seed gets the same amount of work: the same multiset of prompt
lengths, output lengths, inter-arrival gaps and prefix sharing, drawn as
evenly spaced quantiles of each distribution, in an order and with token
ids that the seed chooses. So two seeds differ in the order and content
of the work and not in its size, and a run's spread measures the system
rather than the draw.

A mix is either ``"loop": "open"`` (arrivals at ``rate_per_s`` whatever
the system does, Poisson-shaped gaps) or ``"loop": "closed"`` (the
generator keeps ``backlog`` requests queued at all times, drawing from a
list of ``requests``).
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    due: float  # seconds after the window opens; 0 for a closed loop
    prompt: np.ndarray  # int32 token ids
    max_new: int
    prefix_id: int  # which shared prefix it starts with; -1 for none


@dataclasses.dataclass(frozen=True)
class Workload:
    loop: str  # "open" or "closed"
    rate_per_s: float  # open loop only
    backlog: int  # closed loop only
    requests: tuple[RequestSpec, ...]
    prefix_lengths: tuple[int, ...]

    @property
    def max_new(self) -> int:
        return max(r.max_new for r in self.requests)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at evenly spaced quantiles of ``dist``, clipped
    to [min, max], in ascending order."""
    u = _quantiles(n)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int, seconds: float) -> np.ndarray:
    """``n`` gaps at the quantiles of Exp(rate), scaled to sum to
    ``seconds`` so that exactly ``n`` arrivals fall in the window."""
    g = -np.log1p(-_quantiles(n)) / rate
    return g * (seconds / g.sum())


def zipf_counts(n_items: int, s: float, total: int) -> np.ndarray:
    """How many of ``total`` draws each rank gets under Zipf(s), by
    largest remainders so the counts sum to ``total``."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -s
    exact = total * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = total - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def load_mix(path: pathlib.Path, rehearsal: bool = False) -> dict:
    mix = json.loads(pathlib.Path(path).read_text())
    if rehearsal:
        over = mix.pop("rehearsal")
        for key, val in over.items():
            if isinstance(val, dict) and isinstance(mix.get(key), dict):
                mix[key] = {**mix[key], **val}
            else:
                mix[key] = val
    else:
        mix.pop("rehearsal", None)
    return mix


def generate(
    mix: dict, seed: int, seconds: float, vocab: int, max_len: int
) -> Workload:
    """The requests of one run of ``mix`` under ``seed``."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7AFF1C]))
    loop = mix["loop"]
    if loop == "open":
        rate = float(mix["rate_per_s"])
        n = max(1, round(rate * seconds))
        gaps = rng.permutation(exponential_gaps(rate, n, seconds))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        backlog = 0
    elif loop == "closed":
        rate = 0.0
        n = int(mix["requests"])
        due = np.zeros(n)
        backlog = int(mix["backlog"])
    else:
        raise ValueError(f"unknown loop {loop!r}")
    prompt_lens = rng.permutation(sizes(mix["prompt"], n))
    output_lens = rng.permutation(sizes(mix["output"], n))

    prefixes: list[np.ndarray] = []
    owner = np.full(n, -1)
    pre = mix.get("prefix")
    if pre:
        k = int(pre["count"])
        # which length each popularity rank gets is fixed, not drawn from
        # the seed: the most popular prefix sets most of the cache's work
        lengths = np.random.default_rng(0).permutation(sizes(pre["length"], k))
        prefixes = [
            rng.integers(0, vocab, size=int(m)).astype(np.int32)
            for m in lengths
        ]
        pop = pre["popularity"]
        if pop["dist"] != "zipf":
            raise ValueError(f"unknown popularity {pop['dist']!r}")
        counts = zipf_counts(k, float(pop["s"]), n)
        owner = rng.permutation(np.repeat(np.arange(k), counts))

    reqs = []
    for i in range(n):
        body = rng.integers(0, vocab, size=int(prompt_lens[i])).astype(
            np.int32
        )
        if owner[i] >= 0:
            body = np.concatenate([prefixes[owner[i]], body])
        total = len(body) + int(output_lens[i])
        if total > max_len:
            raise ValueError(
                f"request of {total} tokens exceeds max_len {max_len}"
            )
        reqs.append(
            RequestSpec(
                due=float(due[i]),
                prompt=body,
                max_new=int(output_lens[i]),
                prefix_id=int(owner[i]),
            )
        )
    return Workload(
        loop=loop,
        rate_per_s=rate,
        backlog=backlog,
        requests=tuple(reqs),
        prefix_lengths=tuple(len(p) for p in prefixes),
    )


def prompt_shape_classes(w: Workload, chunk: int) -> dict:
    """What shapes the traffic sends through the engine: the prompt
    lengths that fit one prefill step (each takes a bucket of its own),
    whether any prompt goes through the chunked path (longer than a chunk,
    or a suffix after a shared prefix), and the longest prompt."""
    short = sorted({len(r.prompt) for r in w.requests if r.prefix_id < 0
                    and len(r.prompt) <= chunk})
    return {
        "short_lengths": short,
        "chunked": any(
            len(r.prompt) > chunk or r.prefix_id >= 0 for r in w.requests
        ),
        "longest_prompt": max(len(r.prompt) for r in w.requests),
    }


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def bucket(length: int, block_tokens: int) -> int:
    """The single-step prefill bucket a prompt of ``length`` takes."""
    return max(block_tokens, round_up(length, block_tokens))

