"""FLOP and byte functions against a hand count at SmolLM-360M widths."""

import json
import pathlib

import pytest

from bench.core.config import load_config
from bench.core.flops import packed_matmul_cost, roofline_seconds
from bench.reference.llama import (
    decode_token_flops, layer_matmul_flops, span_flops,
)

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def sizes():
    return load_config(ROOT / "bench/configs/smollm_360m.json", "m").sizes


def test_layer_matmuls_by_hand(sizes):
    d, ff, hd = 960, 2560, 64
    q, kv, o = 2 * d * 15 * hd, 2 * 2 * d * 5 * hd, 2 * 15 * hd * d
    ffn = 3 * 2 * d * ff
    assert layer_matmul_flops(sizes) == q + kv + o + ffn == 19_660_800


def test_decode_token_by_hand(sizes):
    # position 99 attends over 100 keys in each of 32 layers
    attn = 32 * 4 * 15 * 64 * 100
    want = 32 * 19_660_800 + attn + 2 * 960 * 49152
    assert decode_token_flops(sizes, 99) == want


def test_span_is_the_sum_of_its_tokens(sizes):
    per_token = sum(span_flops(sizes, p, 1, 0) for p in range(256, 512))
    assert span_flops(sizes, 256, 256, 0) == per_token
    assert span_flops(sizes, 256, 256, 1) == per_token + 2 * 960 * 49152
    assert span_flops(sizes, 0, 0, 1) == 0


def test_packed_matmul_bytes_by_hand():
    # 32 lanes through a 2-bit 960 x 2560 FFN matrix
    flops, moved = packed_matmul_cost(32, 960, 2560, bits=2)
    assert flops == 2 * 32 * 960 * 2560
    carrier = 960 * 2560 // 4  # four weights a byte
    assert moved == carrier + 4 * 2560 + 32 * 960 * 2 + 32 * 2560 * 4
    t, bound = roofline_seconds(flops, moved, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(moved / 819e9)


def test_configs_state_the_published_widths():
    for name in ("smollm_360m", "smollm_360m_w2"):
        raw = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
        assert (raw["hidden_size"], raw["intermediate_size"],
                raw["num_attention_heads"], raw["num_key_value_heads"],
                raw["num_hidden_layers"], raw["vocab_size"]) == (
                    960, 2560, 15, 5, 32, 49152)
