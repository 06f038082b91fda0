"""The Llama family: a decoder with the Llama layer equations.

Pre-norm RMSNorm, rotary position embedding on the two halves of each
head (the Hugging Face ``rotate_half`` form), grouped-query causal
attention with a 1/sqrt(head_dim) scale, a SwiGLU FFN
(``w2(silu(x w1) * x w3)``), a final RMSNorm and the unembedding (the
tied embedding table when ``tie_word_embeddings``). SmolLM is this
architecture.

This module is the family module of ``bench/reference/__init__.py``:
the keys it reads, its sizes, its weights, the program's configuration
of it, its FLOP count, and its plain reference.

The reference is computed in float32 at the highest matmul precision,
one sequence at a time, layer by layer in a scan so that it fits beside
nothing else. Packed FFN weights are decoded here from their carrier
layout (``bench.core.weights``). ``mm="fp8"`` rounds every matmul operand
to float8 e4m3 with a per-tensor scale, accumulating in float32: the
control that a lower precision must fail.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.core.weights import seed_key, ternary

# the published keys this family reads; every one must be in the file
KEYS = (
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
    "hidden_act",
)


@dataclasses.dataclass(frozen=True)
class Sizes:
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


def sizes(top: dict, quantization: dict) -> Sizes:
    missing = [k for k in KEYS if k not in top]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    if top["hidden_act"] != "silu":
        raise ValueError(
            f"the llama family computes a SwiGLU FFN (silu), not "
            f"{top['hidden_act']!r}"
        )
    return Sizes(
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
        ffn_bits=int(quantization.get("ffn_weight_bits", 0)),
    )


# ---------------- weights ----------------


def make_weights(m: Sizes, seed: int, padded_vocab: int) -> dict:
    """The model's weights on the device, from ``seed``, in the layout the
    serving program consumes: stacked per-layer leaves, matrices in the
    served dtype, norm gains in float32, and for packed FFNs a uint8
    carrier plus float32 per-column scales."""
    return _make(m, padded_vocab)(*seed_key(seed))


@functools.lru_cache(maxsize=None)
def _make(m: Sizes, padded_vocab: int):
    dt = jnp.dtype(m.dtype)
    d, ff, n_l = m.hidden, m.intermediate, m.layers
    q_w, kv_w = m.heads * m.head_dim, m.kv_heads * m.head_dim

    def gain(key, shape):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    def mat(key, shape, fan_in, mult=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                * (mult * fan_in**-0.5)).astype(dt)

    @jax.jit
    def make(key, hi):
        key = jax.random.fold_in(key, hi)
        k = iter(jax.random.split(key, 12))
        layers = {
            "ln1": gain(next(k), (n_l, d)),
            "ln2": gain(next(k), (n_l, d)),
            "wq": mat(next(k), (n_l, d, q_w), d),
            "wk": mat(next(k), (n_l, d, kv_w), d),
            "wv": mat(next(k), (n_l, d, kv_w), d),
            "wo": mat(next(k), (n_l, q_w, d), q_w),
        }
        shapes = {"w1": ((n_l, d, ff), d, 1.0), "w3": ((n_l, d, ff), d, 1.0),
                  "w2": ((n_l, ff, d), ff, 0.5)}
        for name, (shape, fan_in, mult) in shapes.items():
            kk = next(k)
            if m.ffn_bits:
                # ternary values: 60% nonzero, so the scale that keeps the
                # output variance of a dense weight of the same fan-in
                layers[name] = ternary(
                    kk, shape, m.ffn_bits, mult * (0.6 * fan_in) ** -0.5
                )
            else:
                layers[name] = mat(kk, shape, fan_in, mult)
        params = {
            # small enough that the tied unembedding does not simply echo
            # the input token: the next token depends on the context
            "embed": (jax.random.normal(next(k), (padded_vocab, d))
                      * 0.02).astype(dt),
            "final_norm": gain(next(k), (d,)),
            "layers": layers,
        }
        if not m.tied:
            params["unembed"] = mat(next(k), (padded_vocab, d), d)
        return params

    return make


# ---------------- the program's configuration ----------------


def program_config(m: Sizes, base):
    """The program's ModelConfig at these sizes: a dense decoder with
    full attention, whatever ``base`` says of windows or experts."""
    return dataclasses.replace(
        base,
        family="dense",
        sliding_window=0,
        n_layers=m.layers,
        d_model=m.hidden,
        n_heads=m.heads,
        n_kv=m.kv_heads,
        head_dim=m.head_dim,
        d_ff=m.intermediate,
        vocab=m.vocab,
        rope_theta=m.rope_theta,
        norm_eps=m.norm_eps,
        tie_embeddings=m.tied,
        dtype=m.dtype,
        w_bits=m.ffn_bits,
    )


# ---------------- FLOPs ----------------


def layer_matmul_flops(m: Sizes) -> int:
    """FLOPs of one token through one layer's projections and FFN."""
    q = 2 * m.hidden * m.heads * m.head_dim
    kv = 2 * 2 * m.hidden * m.kv_heads * m.head_dim
    o = 2 * m.heads * m.head_dim * m.hidden
    ffn = 3 * 2 * m.hidden * m.intermediate
    return q + kv + o + ffn


def attention_flops(m: Sizes, keys: int) -> int:
    """FLOPs of one query token attending over ``keys`` positions, all
    layers: scores (QK) and the weighted sum (PV), 2 FLOPs per MAC."""
    return m.layers * 4 * m.heads * m.head_dim * keys


def unembed_flops(m: Sizes) -> int:
    return 2 * m.hidden * m.vocab


def span_flops(m: Sizes, start: int, n: int, logits_rows: int) -> int:
    """FLOPs of ``n`` consecutive tokens at positions start..start+n-1,
    each attending causally over every position up to its own, with
    ``logits_rows`` of them unembedded."""
    if n <= 0:
        return 0
    # sum over p of (p + 1) keys for p in [start, start + n)
    keys = n * start + n * (n + 1) // 2
    return (
        n * m.layers * layer_matmul_flops(m)
        + attention_flops(m, keys)
        + logits_rows * unembed_flops(m)
    )


def decode_token_flops(m: Sizes, position: int) -> int:
    """One decoded token at ``position``: its row is unembedded."""
    return span_flops(m, position, 1, 1)


def streamed_matmuls(m: Sizes) -> tuple[tuple[int, int, int], ...]:
    """(k, n, bits) of the packed matrices a streamed layer multiplies by:
    w1 and w3 (hidden -> intermediate), then w2 (intermediate -> hidden)."""
    if not m.ffn_bits:
        return ()
    up = (m.hidden, m.intermediate, m.ffn_bits)
    return (up, up, (m.intermediate, m.hidden, m.ffn_bits))


# ---------------- the plain reference ----------------


HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(a: jnp.ndarray) -> jnp.ndarray:
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(mm: str):
    def f(spec, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if mm == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    return f


def unpack(carrier: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(K * bits / 8, N) uint8 carrier -> (K, N) integer codes."""
    per = 8 // bits
    planes = [(carrier >> (bits * j)) & (2**bits - 1) for j in range(per)]
    kc, n = carrier.shape
    return jnp.stack(planes, axis=1).reshape(kc * per, n)


def ffn_matrix(w, bits: int) -> jnp.ndarray:
    """A dense FFN weight in float32; a packed one decoded: ternary codes
    {0, 1, 2} are {-1, 0, +1} times the column's scale."""
    if not isinstance(w, dict):
        return w.astype(jnp.float32)
    if bits != 2:
        raise ValueError(f"no decoding for {bits}-bit carriers")
    vals = unpack(w["packed"], bits).astype(jnp.float32) - 1.0
    return vals * w["scale"].astype(jnp.float32)[None, :]


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x (S, H, D), pos (S,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def logits_at(sizes, mm: str = "f32"):
    """A jitted ``f(params, tokens (S,), rows (R,)) -> (R, vocab)``: the
    next-token logits after each position in ``rows`` of ``tokens``.
    Positions past the real sequence are right padding: causality keeps
    them out of every earlier row."""
    ein = _einsum(mm)
    m = sizes
    g = m.heads // m.kv_heads
    eps = m.norm_eps

    def layer(x, lp):
        s = x.shape[0]
        pos = jnp.arange(s)
        h = rms_norm(x, lp["ln1"], eps)
        q = ein("sd,dk->sk", h, lp["wq"]).reshape(s, m.heads, m.head_dim)
        k = ein("sd,dk->sk", h, lp["wk"]).reshape(s, m.kv_heads, m.head_dim)
        v = ein("sd,dk->sk", h, lp["wv"]).reshape(s, m.kv_heads, m.head_dim)
        q, k = rope(q, pos, m.rope_theta), rope(k, pos, m.rope_theta)
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = ein("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(m.head_dim))
        causal = pos[:, None] >= pos[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = ein("hqk,khd->qhd", p, v).reshape(s, m.heads * m.head_dim)
        x = x + ein("sk,kd->sd", o, lp["wo"])
        h = rms_norm(x, lp["ln2"], eps)
        w1 = ffn_matrix(lp["w1"], m.ffn_bits)
        w3 = ffn_matrix(lp["w3"], m.ffn_bits)
        w2 = ffn_matrix(lp["w2"], m.ffn_bits)
        a = ein("sd,df->sf", h, w1)
        u = jax.nn.silu(a) * ein("sd,df->sf", h, w3)
        return x + ein("sf,fd->sd", u, w2), None

    @jax.jit
    def f(params, tokens, rows):
        table = params["embed"][: m.vocab].astype(jnp.float32)
        x = table[tokens]
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = rms_norm(x[rows], params["final_norm"], eps)
        out = table if m.tied else params["unembed"][: m.vocab]
        return ein("rd,vd->rv", x, out)

    return f
