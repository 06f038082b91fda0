"""A model configuration as the benchmark runs it, read from its JSON file.

The file keeps the published config's keys at its top level (Hugging Face
names), the FFN weight width under ``quantization``, how the system
serves it under ``serving``, and the sizes of the CPU rehearsal under
``rehearsal``. ``reference`` names the configuration's family module,
``bench/reference/<reference>.py``, which reads the published keys and
knows everything else of the family (its interface is in
``bench/reference/__init__.py``); this module reads only what every
family shares.

``reduced`` maps each key cut for one chip to its published value, and
``assumed`` each size the source does not give to the reason for it.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from types import ModuleType
from typing import Any

from bench.core import registry

# the harness's own checkout: where a family module is found by default
ROOT = pathlib.Path(__file__).resolve().parents[2]
# the keys read here, whatever the family
COMMON_KEYS = ("arch", "reference", "serving", "rehearsal", "quantization")
# keys that describe the configuration and shape nothing
DESCRIPTIVE_KEYS = (
    "source", "model_type", "deployment", "derived", "reduced", "assumed",
)


@dataclasses.dataclass(frozen=True)
class Serving:
    lanes: int
    max_len: int
    prefill_chunk: int
    block_tokens: int
    prefix_cache: bool
    vmem_budget_mib: float
    # the traffic the residency plan is compiled for, and the layers that
    # plan streams; only read when vmem_budget_mib > 0
    plan_prompt_len: int
    plan_gen_len: int
    streamed_layers: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch: str  # the program's configuration id
    family: ModuleType  # bench/reference/<reference>.py
    sizes: Any  # the family module's ``Sizes``
    serving: Serving


def _check_keys(raw: dict, top: dict, family: ModuleType) -> None:
    """Every top-level key is read by this module or by the family, and
    every key ``reduced`` names is in the file, cut from its published
    value."""
    known = set(COMMON_KEYS) | set(DESCRIPTIVE_KEYS) | set(family.KEYS)
    unread = sorted(set(top) - known)
    if unread:
        raise ValueError(
            f"no part of the harness reads {unread}: the family module "
            f"{raw['reference']!r} reads {sorted(family.KEYS)}"
        )
    for key, published in raw.get("reduced", {}).items():
        if key not in raw:
            raise ValueError(f"reduced key {key!r} is not in the file")
        if raw[key] == published:
            raise ValueError(
                f"reduced key {key!r} keeps its published value {published!r}"
            )


def _serving(s: dict) -> Serving:
    plan = s.get("residency_plan", {})
    return Serving(
        lanes=int(s["lanes"]),
        max_len=int(s["max_len"]),
        prefill_chunk=int(s["prefill_chunk"]),
        block_tokens=int(s["block_tokens"]),
        prefix_cache=bool(s["prefix_cache"]),
        vmem_budget_mib=float(s.get("vmem_budget_mib", 0)),
        plan_prompt_len=int(plan.get("prompt_len", 0)),
        plan_gen_len=int(plan.get("gen_len", 0)),
        streamed_layers=tuple(plan.get("streamed_layers", ())),
    )


def load_config(
    path: pathlib.Path, name: str, rehearsal: bool = False,
    root: pathlib.Path = ROOT,
) -> ModelConfig:
    """Read one configuration file; ``rehearsal`` applies the file's
    ``rehearsal`` group (CPU sizes) over its published keys and serving
    settings. The family module is ``root``'s."""
    raw = json.loads(pathlib.Path(path).read_text())
    top = dict(raw)
    serving = dict(raw["serving"])
    if rehearsal:
        over = dict(raw["rehearsal"])
        serving.update(over.pop("serving", {}))
        top.update(over)
    family = registry.family(root, str(raw["reference"]))
    _check_keys(raw, top, family)
    cfg = ModelConfig(
        name=name,
        arch=str(raw["arch"]),
        family=family,
        sizes=family.sizes(top, dict(raw.get("quantization", {}))),
        serving=_serving(serving),
    )
    if cfg.serving.max_len > cfg.sizes.context:
        raise ValueError(
            f"{name}: max_len {cfg.serving.max_len} exceeds the context "
            f"{cfg.sizes.context}"
        )
    return cfg
