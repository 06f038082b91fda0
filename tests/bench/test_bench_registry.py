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


# A family added by one file: Llama equations with one uniform sliding
# window, which the program serves through ModelConfig.sliding_window.
WINDOW_FAMILY = '''"""Llama equations with one sliding window: in every layer a query
attends to the ``sliding_window`` positions up to and including its own."""

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.reference import llama

KEYS = llama.KEYS + ("sliding_window",)


@dataclasses.dataclass(frozen=True)
class Sizes(llama.Sizes):
    window: int = 0


def sizes(top, quantization):
    dense = llama.sizes(top, quantization)
    return Sizes(**dataclasses.asdict(dense), window=int(top["sliding_window"]))


make_weights = llama.make_weights
streamed_matmuls = llama.streamed_matmuls


def program_config(m, base):
    return dataclasses.replace(llama.program_config(m, base),
                               sliding_window=m.window)


def span_flops(m, start, n, logits_rows):
    if n <= 0:
        return 0
    keys = sum(min(p + 1, m.window) for p in range(start, start + n))
    return (n * m.layers * llama.layer_matmul_flops(m)
            + llama.attention_flops(m, keys)
            + logits_rows * llama.unembed_flops(m))


def decode_token_flops(m, position):
    return span_flops(m, position, 1, 1)


@functools.lru_cache(maxsize=None)
def logits_at(m, mm="f32"):
    ein = llama._einsum(mm)
    g = m.heads // m.kv_heads

    def layer(x, lp):
        s = x.shape[0]
        pos = jnp.arange(s)
        h = llama.rms_norm(x, lp["ln1"], m.norm_eps)
        q = ein("sd,dk->sk", h, lp["wq"]).reshape(s, m.heads, m.head_dim)
        k = ein("sd,dk->sk", h, lp["wk"]).reshape(s, m.kv_heads, m.head_dim)
        v = ein("sd,dk->sk", h, lp["wv"]).reshape(s, m.kv_heads, m.head_dim)
        q, k = llama.rope(q, pos, m.rope_theta), llama.rope(k, pos, m.rope_theta)
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = ein("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(m.head_dim))
        back = pos[:, None] - pos[None, :]
        seen = (back >= 0) & (back < m.window)
        p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        o = ein("hqk,khd->qhd", p, v).reshape(s, m.heads * m.head_dim)
        x = x + ein("sk,kd->sd", o, lp["wo"])
        h = llama.rms_norm(x, lp["ln2"], m.norm_eps)
        a = ein("sd,df->sf", h, llama.ffn_matrix(lp["w1"], m.ffn_bits))
        u = jax.nn.silu(a) * ein("sd,df->sf", h,
                                 llama.ffn_matrix(lp["w3"], m.ffn_bits))
        return x + ein("sf,fd->sd", u, llama.ffn_matrix(lp["w2"], m.ffn_bits)), None

    @jax.jit
    def f(params, tokens, rows):
        table = params["embed"][: m.vocab].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, table[tokens], params["layers"])
        x = llama.rms_norm(x[rows], params["final_norm"], m.norm_eps)
        out = table if m.tied else params["unembed"][: m.vocab]
        return ein("rd,vd->rv", x, out)

    return f
'''


def test_added_family_is_found_by_name(tmp_path):
    """A family module, a configuration that names it, a limits file and a
    cell: new files and entries only. The program gets the file's window,
    and the CPU rehearsal of the cell reads correct against the new
    family's reference."""
    from bench.core.engine import program_config
    from bench.run import run_cell

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path)

    b = tmp_path / "bench"
    (b / "reference/llama_window.py").write_text(WINDOW_FAMILY)
    cfg = json.loads((b / "configs/smollm_360m.json").read_text())
    # a window below the rehearsal's max_len (160) and its longest chat
    # sequence (96 positions), so that it changes what a query sees
    cfg.update(reference="llama_window", sliding_window=512,
               reduced={}, assumed={"sliding_window": "a test family"})
    cfg["rehearsal"]["sliding_window"] = 24
    (b / "configs/smollm_360m_window.json").write_text(json.dumps(cfg))
    (b / "limits/smollm_360m_window.chat.json").write_text(
        json.dumps({"gap_max": {"limit": 0.07, "rehearsal_limit": 0.05}}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({**bench["configs"][0],
                             "name": "smollm_360m_window",
                             "file": "bench/configs/smollm_360m_window.json"})
    bench["workloads"].append({"name": "smollm_360m_window.chat",
                               "config": "smollm_360m_window",
                               "traffic": "chat", "chips": 1, "why": "added"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = registry.find_cell(tmp_path, "smollm_360m_window.chat")
    small = load_config(cell.config_file, cell.config_name, rehearsal=True,
                        root=tmp_path)
    assert program_config(small).sliding_window == 24
    full = load_config(cell.config_file, cell.config_name, root=tmp_path)
    assert program_config(full).sliding_window == 512
    assert full.family.decode_token_flops(full.sizes, 2047) < (
        _config_flops("smollm_360m", 2047))

    res = run_cell(tmp_path, "smollm_360m_window.chat", 11, 0.5, False,
                   rehearsal=True, strict=False)
    assert res["correct"], res["checks"]
    assert res["window"]["compiles"] == 0
    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before


def _config_flops(name, position):
    cfg = load_config(ROOT / f"bench/configs/{name}.json", name)
    return cfg.family.decode_token_flops(cfg.sizes, position)
