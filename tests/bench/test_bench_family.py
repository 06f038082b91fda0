"""The configuration's family module is the one place that knows a model
family: through it the SmolLM configurations give the weights, program
configuration, FLOP counts and reference logits recorded while
``bench/core`` still held the dense block, and the loader refuses a file
whose keys no part of the harness reads."""

import dataclasses
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from bench.core import registry
from bench.core.config import load_config
from bench.core.engine import program_config

ROOT = pathlib.Path(__file__).resolve().parents[2]
RECORDED = json.loads((ROOT / "tests/bench/data/smollm_recorded.json").read_text())
CONFIGS = ("smollm_360m", "smollm_360m_w2")


def _config(name, rehearsal=False):
    return load_config(ROOT / f"bench/configs/{name}.json", name, rehearsal)


def digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes, in tree order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}\n".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_the_recorded_ones(name):
    cfg = _config(name, rehearsal=True)
    w = cfg.family.make_weights(cfg.sizes, 3, program_config(cfg).padded_vocab)
    assert digest(w) == RECORDED[name]["weights_rehearsal_seed3"]


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_is_the_recorded_one(name):
    pcfg = dataclasses.asdict(program_config(_config(name)))
    assert json.loads(json.dumps(pcfg)) == RECORDED[name]["program_config"]


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_are_the_recorded_ones(name):
    cfg = _config(name)
    rec = RECORDED[name]
    for start, n, rows, want in rec["span_flops"]:
        assert cfg.family.span_flops(cfg.sizes, start, n, rows) == want
    for pos, want in rec["decode_token_flops"]:
        assert cfg.family.decode_token_flops(cfg.sizes, pos) == want


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_logits_are_the_recorded_ones(name):
    cfg = _config(name, rehearsal=True)
    w = cfg.family.make_weights(cfg.sizes, 3, program_config(cfg).padded_vocab)
    rec = RECORDED[name]["reference_logits"]
    tokens = (np.arange(48, dtype=np.int32) * 37) % cfg.sizes.vocab
    rows = np.asarray(rec["rows"], np.int32)
    lg = np.asarray(cfg.family.logits_at(cfg.sizes, "f32")(w, tokens, rows),
                    np.float64)
    assert lg.argmax(-1).tolist() == rec["argmax"]
    # float32 sums of the same operations: equal up to the order of a
    # threaded reduction
    np.testing.assert_allclose(lg[:, :16], rec["first16"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(lg.sum(-1), rec["sum"], rtol=1e-5, atol=1e-4)


def test_reduced_keys_are_those_the_benchmark_lists():
    for c in registry.load_benchmark(ROOT)["configs"]:
        raw = json.loads((ROOT / c["file"]).read_text())
        assert sorted(raw.get("reduced", {})) == sorted(c["reduced"]), c["name"]


def _write(tmp_path, **changes):
    raw = json.loads((ROOT / "bench/configs/smollm_360m.json").read_text())
    raw.update(changes)
    path = tmp_path / "c.json"
    path.write_text(json.dumps(raw))
    return path


@pytest.mark.parametrize("key, value", [
    ("num_experts", 8),  # an expert count the llama family does not read
    ("sliding_window", 1024),
])
def test_unread_shape_key_fails_to_load(tmp_path, key, value):
    with pytest.raises(ValueError, match=key):
        load_config(_write(tmp_path, **{key: value}), "c")


@pytest.mark.parametrize("reduced, why", [
    ({"num_experts": 64}, "not in the file"),
    ({"num_hidden_layers": 32}, "keeps its published value"),
])
def test_reduced_keys_are_checked(tmp_path, reduced, why):
    with pytest.raises(ValueError, match=why):
        load_config(_write(tmp_path, reduced=reduced), "c")


def test_a_cut_and_an_assumption_load(tmp_path):
    path = _write(tmp_path, num_hidden_layers=8,
                  reduced={"num_hidden_layers": 32},
                  assumed={"head_dim": "hidden_size / num_attention_heads"})
    assert load_config(path, "c").sizes.layers == 8


def test_an_activation_the_family_does_not_compute_fails(tmp_path):
    with pytest.raises(ValueError, match="gelu"):
        load_config(_write(tmp_path, hidden_act="gelu"), "c")


def _run(cfg, spans, tokens, specs):
    from bench.core.peaks import PEAKS
    from bench.core.record import Run
    from bench.core.window import Window

    window = Window(seconds=10.0, t_open=0.0, t_close=10.0, specs=specs,
                    tokens=tokens, rounds=[(0.0, 2.0), (5.0, 12.0)])
    return Run(cell="c", cfg=cfg, workload=None, window=window, setup_s=0.0,
               peaks=PEAKS["TPU v5 lite"], spans=spans)


def test_model_mfu_counts_the_family_flops():
    """Two chunks of a 300-token prompt prefilled in the window, the last
    with its logits row, then three decoded tokens, one after the close;
    the rounds were busy 7 s of the window (2 + 5)."""
    from bench.core.traffic import RequestSpec

    cfg = _config("smollm_360m")
    spec = RequestSpec(due=0.0, prompt=np.zeros(300, np.int32), max_new=4,
                       prefix_id=-1)
    spans = [
        {"phase": "prefill", "rid": 0, "t1": 1.0, "chunk_start": 0, "tokens": 256},
        {"phase": "prefill", "rid": 0, "t1": 1.5, "chunk_start": 256, "tokens": 44},
        {"phase": "queue", "rid": 0, "t1": 0.5},
    ]
    run = _run(cfg, spans, {0: [1.5, 6.0, 7.0, 11.0]}, {0: spec})
    f = cfg.family
    m = cfg.sizes
    want = (f.span_flops(m, 0, 256, 0) + f.span_flops(m, 256, 44, 1)
            + f.decode_token_flops(m, 300) + f.decode_token_flops(m, 301))
    got = registry.reader(ROOT, "metrics", "model.mfu").read(run)
    assert got == pytest.approx(100.0 * want / (7.0 * 197e12), rel=1e-12)


@pytest.mark.parametrize("name, matrices", [
    ("smollm_360m", ()),
    ("smollm_360m_w2", ((960, 2560, 2), (960, 2560, 2), (2560, 960, 2))),
])
def test_streamed_matrices_come_from_the_family(name, matrices):
    """The weight-stream roofline's matrices: none in bf16, where its
    reader stays silent, and a streamed layer's three FFN matrices in the
    2-bit configuration."""
    cfg = _config(name)
    assert cfg.family.streamed_matmuls(cfg.sizes) == matrices
    if not matrices:
        run = _run(cfg, [], {}, {})
        run.trace = object()  # a trace is there; the family streams nothing
        reader = registry.reader(ROOT, "metrics", "weight_stream_roofline")
        assert reader.read(run) is None
