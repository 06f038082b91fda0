"""A model configuration as the benchmark runs it, read from its JSON file.

The file keeps the published config's keys at its top level (Hugging Face
names), the FFN weight width under ``quantization``, how the system
serves it under ``serving``, and the sizes of the CPU rehearsal under
``rehearsal``. ``reference`` names the plain reference implementation in
``bench/reference/``.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

# the published keys the harness reads; every one must be in the file
MODEL_KEYS = (
    "num_hidden_layers",
    "hidden_size",
    "intermediate_size",
    "num_attention_heads",
    "num_key_value_heads",
    "head_dim",
    "vocab_size",
    "max_position_embeddings",
    "rope_theta",
    "rms_norm_eps",
    "tie_word_embeddings",
    "torch_dtype",
)


@dataclasses.dataclass(frozen=True)
class ModelSizes:
    layers: int
    hidden: int
    intermediate: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    context: int
    rope_theta: float
    norm_eps: float
    tied: bool
    dtype: str
    ffn_bits: int  # 0: FFN weights in ``dtype``; 1 or 2: packed carriers


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
    reference: str  # module name under bench/reference/
    sizes: ModelSizes
    serving: Serving


def _sizes(top: dict, ffn_bits: int) -> ModelSizes:
    missing = [k for k in MODEL_KEYS if k not in top]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    return ModelSizes(
        layers=int(top["num_hidden_layers"]),
        hidden=int(top["hidden_size"]),
        intermediate=int(top["intermediate_size"]),
        heads=int(top["num_attention_heads"]),
        kv_heads=int(top["num_key_value_heads"]),
        head_dim=int(top["head_dim"]),
        vocab=int(top["vocab_size"]),
        context=int(top["max_position_embeddings"]),
        rope_theta=float(top["rope_theta"]),
        norm_eps=float(top["rms_norm_eps"]),
        tied=bool(top["tie_word_embeddings"]),
        dtype=str(top["torch_dtype"]),
        ffn_bits=ffn_bits,
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
    path: pathlib.Path, name: str, rehearsal: bool = False
) -> ModelConfig:
    """Read one configuration file; ``rehearsal`` applies the file's
    ``rehearsal`` group (CPU sizes) over its published keys and serving
    settings."""
    raw = json.loads(pathlib.Path(path).read_text())
    top = dict(raw)
    serving = dict(raw["serving"])
    if rehearsal:
        over = dict(raw["rehearsal"])
        serving.update(over.pop("serving", {}))
        top.update(over)
    bits = int(raw.get("quantization", {}).get("ffn_weight_bits", 0))
    cfg = ModelConfig(
        name=name,
        arch=str(raw["arch"]),
        reference=str(raw["reference"]),
        sizes=_sizes(top, bits),
        serving=_serving(serving),
    )
    if cfg.serving.max_len > cfg.sizes.context:
        raise ValueError(
            f"{name}: max_len {cfg.serving.max_len} exceeds the context "
            f"{cfg.sizes.context}"
        )
    return cfg
