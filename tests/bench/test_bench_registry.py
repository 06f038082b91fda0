"""A cell, configuration, traffic mix and metric are found by name: one is
added with new files and new BENCHMARK.json entries, and no file that
already exists changes."""

import hashlib
import json
import pathlib
import shutil

from bench.core import registry
from bench.core.config import load_config
from bench.core.traffic import load_mix

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _digest(root):
    return {
        p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted((root / "bench").rglob("*")) if p.is_file()
        and "__pycache__" not in p.parts
    }


def test_added_files_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path)

    b = tmp_path / "bench"
    cfg = json.loads((b / "configs/smollm_360m.json").read_text())
    cfg["serving"]["lanes"] = 16
    (b / "configs/smollm_360m_16.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic/chat.json").read_text())
    mix["rate_per_s"] = 1.5
    (b / "traffic/chat_slow.json").write_text(json.dumps(mix))
    (b / "limits/smollm_360m_16.chat_slow.json").write_text(
        json.dumps({"gap_max": {"limit": 0.5}}))
    (b / "metrics/sched.rounds.py").write_text(
        "def read(run):\n    return float(run.delta('rounds'))\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({**bench["configs"][0], "name": "smollm_360m_16",
                             "file": "bench/configs/smollm_360m_16.json"})
    bench["workloads"].append({"name": "smollm_360m_16.chat_slow",
                               "config": "smollm_360m_16",
                               "traffic": "chat_slow", "chips": 1,
                               "why": "added"})
    bench["per_layer"].append({"name": "sched.rounds", "unit": "rounds",
                               "better": "lower", "source": "program_counter",
                               "layer": "scheduler", "moves": "itl_p50_ms",
                               "workloads": ["smollm_360m_16.chat_slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = registry.find_cell(tmp_path, "smollm_360m_16.chat_slow")
    assert load_config(cell.config_file, cell.config_name).serving.lanes == 16
    assert load_mix(cell.traffic_file)["rate_per_s"] == 1.5
    assert cell.limits_file.is_file()
    names = [m["name"] for m in cell.per_layer]
    assert "sched.rounds" in names and "prefix.hit_share" not in names
    assert [m["name"] for m in cell.end_to_end] == ["itl_p50_ms", "setup_s"]
    for m in cell.per_layer + cell.end_to_end:
        kind = "metrics" if m in cell.per_layer else "end_to_end"
        assert callable(registry.reader(tmp_path, kind, m["name"]).read)
    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_cell_of_the_benchmark_resolves():
    bench = registry.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = registry.find_cell(ROOT, w["name"])
        assert cell.config_file.is_file() and cell.traffic_file.is_file()
        assert cell.limits_file.is_file()
        for m in cell.per_layer:
            registry.reader(ROOT, "metrics", m["name"])
        for m in cell.end_to_end:
            registry.reader(ROOT, "end_to_end", m["name"])
