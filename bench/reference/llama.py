"""Plain reference of a decoder with the Llama layer equations.

Pre-norm RMSNorm, rotary position embedding on the two halves of each
head (the Hugging Face ``rotate_half`` form), grouped-query causal
attention with a 1/sqrt(head_dim) scale, a SwiGLU FFN
(``w2(silu(x w1) * x w3)``), a final RMSNorm and the unembedding (the
tied embedding table when ``tie_word_embeddings``). SmolLM is this
architecture.

Computed in float32 at the highest matmul precision, one sequence at a
time, layer by layer in a scan so that it fits beside nothing else.
Packed FFN weights are decoded here from their carrier layout
(``bench.core.weights``). ``mm="fp8"`` rounds every matmul operand to
float8 e4m3 with a per-tensor scale, accumulating in float32: the
control that a lower precision must fail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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
