"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

Nothing here lists a configuration, a traffic mix or a metric: each is a
file of its own, found by name, so a later cell, mix or metric is added
by adding files and entries, not by editing a file that exists.

- configuration: the file its ``configs`` entry names (JSON)
- traffic mix: ``bench/traffic/<traffic>.json``
- limits of the correctness check: ``bench/limits/<workload>.json``
- end-to-end metric: ``bench/end_to_end/<name>.py``
- per-layer metric: ``bench/metrics/<name>.py``
- model family: ``bench/reference/<reference>.py``, named by the
  configuration file's ``reference`` key

A metric file defines ``read(run) -> float | None``; None means it found
nothing to read in this run, and the metric is left out of the line.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import importlib.util
import json
import pathlib
import sys
from types import ModuleType


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    config_file: pathlib.Path
    traffic: str
    traffic_file: pathlib.Path
    limits_file: pathlib.Path
    chips: int
    end_to_end: tuple[dict, ...]  # the BENCHMARK.json entries it reports
    per_layer: tuple[dict, ...]


def load_benchmark(root: pathlib.Path) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: pathlib.Path, name: str) -> Cell:
    root = pathlib.Path(root)
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = configs[w["config"]]
    return Cell(
        name=name,
        config_name=cfg["name"],
        config_file=root / cfg["file"],
        traffic=w["traffic"],
        traffic_file=root / "bench" / "traffic" / f"{w['traffic']}.json",
        limits_file=root / "bench" / "limits" / f"{name}.json",
        chips=int(w["chips"]),
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)),
    )


def reader(root: pathlib.Path, kind: str, name: str) -> ModuleType:
    """The reader module of metric ``name``; ``kind`` is ``end_to_end`` or
    ``metrics``."""
    path = pathlib.Path(root) / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path
    )
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def family(root: pathlib.Path, name: str) -> ModuleType:
    """The family module ``bench/reference/<name>.py`` of ``root``, loaded
    from its file once per process (its jitted functions and weights
    cache with it)."""
    path = (pathlib.Path(root) / "bench" / "reference" / f"{name}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no family module {name!r} at {path}")
    key = hashlib.sha256(str(path).encode()).hexdigest()[:12]
    mod_name = f"bench_family_{name}_{key}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses resolve their module by name while the class is made
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
