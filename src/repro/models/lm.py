"""Unified LM-family model: dense / MoE / SSM / hybrid / VLM, one codebase.

Design (DESIGN.md §2): one parameter pytree with *stacked* per-layer leaves
(leading axis = layer) consumed by ``lax.scan`` — this keeps HLO size and
compile time flat in depth (80-layer internvl2 compiles as fast as 16-layer
olmoe), and it is what makes the 512-device dry-run tractable on a CPU
host.

Entry points:
  * ``init_params(cfg, key)``      — real arrays (smoke tests / training)
  * ``abstract_params(cfg)``       — ShapeDtypeStructs (dry-run, no alloc)
  * ``forward(params, cfg, tokens, ...)``      — train/prefill logits
  * ``init_cache(cfg, batch, max_len)``        — decode state
  * ``prefill(params, cfg, tokens, cache)``    — fill cache, return logits
  * ``decode_step(params, cfg, token, cache)`` — one-token serve step

The FCMP packed-weight path: with ``cfg.w_bits`` in {1, 2} the FFN weight
leaves are stored as uint8 carriers + per-channel scales (8x/4x fewer HBM
bytes — the paper's OCM packing, DESIGN.md §3) and are decoded next to the
matmul. The decode is pure-jnp here so it lowers through GSPMD for the
dry-run; the Pallas ``packed_matmul`` kernel is the TPU execution path.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ATTN_KV_FAMILIES, ModelConfig
from repro.models.layers import (
    apply_rope,
    cross_entropy,
    dense,
    embed,
    logits as unembed_logits,
    rms_norm,
    swiglu,
)


# --------------------------------------------------------------------------
# Packed (FCMP) weight leaves
# --------------------------------------------------------------------------


def _pack_leaf_shapes(shape: tuple[int, ...], bits: int):
    """(..., K, N) weight -> carrier (..., K*bits/8, N) uint8 + scale (...,N)."""
    *lead, k, n = shape
    per = 8 // bits
    assert k % per == 0, (shape, bits)
    return tuple(lead) + (k // per, n), tuple(lead) + (n,)


def make_packed(w: jnp.ndarray, bits: int) -> dict[str, jnp.ndarray]:
    """Quantize + pack a float weight (..., K, N) into the carrier format."""
    from repro.quant.quantizers import pack_bits

    axes = tuple(range(w.ndim - 1))
    if bits == 1:
        scale = jnp.mean(jnp.abs(w), axis=-2)  # (..., N)
        codes = (w > 0).astype(jnp.uint8)
    else:
        mean_abs = jnp.mean(jnp.abs(w), axis=-2, keepdims=True)
        delta = 0.7 * mean_abs
        mask = jnp.abs(w) > delta
        scale = jnp.sum(jnp.abs(w) * mask, axis=-2) / jnp.maximum(
            jnp.sum(mask, axis=-2), 1.0
        )
        codes = (jnp.sign(w) * mask + 1).astype(jnp.uint8)
    per = 8 // bits
    k = w.shape[-2]
    # pack along axis -2
    moved = jnp.moveaxis(codes, -2, 0)
    packed = pack_bits(moved, bits)
    packed = jnp.moveaxis(packed, 0, -2)
    return {"packed": packed, "scale": scale.astype(jnp.float32)}


def _unpack_codes(packed: jnp.ndarray, bits: int) -> jnp.ndarray:
    """uint8 carrier (..., Kc, N) -> codes (..., Kc*per, N) along axis -2."""
    per = 8 // bits
    mask = jnp.uint8(2**bits - 1)
    shifts = jnp.arange(per, dtype=jnp.uint8) * bits
    planes = (packed[..., None, :] >> shifts[:, None]) & mask  # (...,Kc,per,N)
    new_shape = packed.shape[:-2] + (packed.shape[-2] * per, packed.shape[-1])
    return planes.reshape(new_shape)


def packed_dense(x: jnp.ndarray, w: Any, bits: int) -> jnp.ndarray:
    """Matmul against a dense or packed weight leaf."""
    if not isinstance(w, dict):
        return dense(x, w)
    codes = _unpack_codes(w["packed"], bits).astype(x.dtype)
    vals = codes * 2.0 - 1.0 if bits == 1 else codes - 1.0
    out = jnp.einsum("...k,kn->...n", x, vals)
    return out * w["scale"].astype(x.dtype)


def packed_swiglu(x, w1, w3, w2, bits: int):
    h = jax.nn.silu(packed_dense(x, w1, bits)) * packed_dense(x, w3, bits)
    return packed_dense(h, w2, bits)


def _streamed_matmul(x: jnp.ndarray, w: Any, bits: int, depth: int):
    """Matmul with the weight left in HBM and streamed through a VMEM ring
    (``kernels.weight_stream``; its jnp reference on the CPU backend).
    Accumulates and scales in f32: token-identical to the resident path
    for f32 models, within rounding of it for bf16 ones."""
    from repro.kernels.ops import stream_matmul

    kdim = x.shape[-1]
    if isinstance(w, dict):
        out = stream_matmul(
            x, w["packed"], w["scale"], bits=bits, k=kdim, stream_depth=depth
        )
    else:
        out = stream_matmul(x, w, None, bits=0, k=kdim, stream_depth=depth)
    return out.astype(x.dtype)


def streamed_swiglu(x, w1, w3, w2, bits: int, depth: int):
    """The FFN of a non-resident layer: every mat streamed HBM->VMEM."""
    h = jax.nn.silu(_streamed_matmul(x, w1, bits, depth)) * _streamed_matmul(
        x, w3, bits, depth
    )
    return _streamed_matmul(h, w2, bits, depth)


# --------------------------------------------------------------------------
# Parameter initialisation
# --------------------------------------------------------------------------


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _maybe_pack(w: jnp.ndarray, cfg: ModelConfig):
    if cfg.w_bits in (1, 2):
        return make_packed(w, cfg.w_bits)
    return w


def _init_attn(key, cfg: ModelConfig, n: int, d: int):
    """Stacked attention projections for ``n`` layers over width ``d``."""
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    dt = _dt(cfg)
    return {
        "wq": (jax.random.normal(ks[0], (n, d, hq * hd), dt) * s),
        "wk": (jax.random.normal(ks[1], (n, d, hkv * hd), dt) * s),
        "wv": (jax.random.normal(ks[2], (n, d, hkv * hd), dt) * s),
        "wo": (jax.random.normal(ks[3], (n, hq * hd, d), dt) * s),
    }


def _init_ffn(key, cfg: ModelConfig, n: int, d: int, ff: int, lead=()):
    ks = jax.random.split(key, 3)
    s = d ** -0.5
    dt = _dt(cfg)
    shp1 = (n,) + lead + (d, ff)
    shp2 = (n,) + lead + (ff, d)
    # FCMP packing applies to the dense-FFN families; the MoE expert
    # einsums consume dense stacked weights (lead = (E,)), so packed
    # carriers are not produced for them.
    pack = _maybe_pack if not lead else (lambda w, _cfg: w)
    return {
        "w1": pack(jax.random.normal(ks[0], shp1, dt) * s, cfg),
        "w3": pack(jax.random.normal(ks[1], shp1, dt) * s, cfg),
        "w2": pack(jax.random.normal(ks[2], shp2, dt) * s * 0.5, cfg),
    }


def _init_ssm(key, cfg: ModelConfig, n: int):
    d, di, st = cfg.d_model, cfg.d_inner, cfg.ssm_state
    h, k = cfg.ssm_heads, cfg.conv_kernel
    ks = jax.random.split(key, 8)
    s = d ** -0.5
    dt = _dt(cfg)
    return {
        "in_z": jax.random.normal(ks[0], (n, d, di), dt) * s,
        "in_x": jax.random.normal(ks[1], (n, d, di), dt) * s,
        "in_b": jax.random.normal(ks[2], (n, d, st), dt) * s,
        "in_c": jax.random.normal(ks[3], (n, d, st), dt) * s,
        "in_dt": jax.random.normal(ks[4], (n, d, h), dt) * s,
        "dt_bias": jnp.zeros((n, h), jnp.float32),
        "conv_x": jax.random.normal(ks[5], (n, k, di), dt) * 0.3,
        "conv_b": jax.random.normal(ks[6], (n, k, st), dt) * 0.3,
        "conv_c": jax.random.normal(ks[7], (n, k, st), dt) * 0.3,
        "a_log": jnp.zeros((n, h), jnp.float32),  # A = -1
        "d_skip": jnp.ones((n, h), jnp.float32),
        "gate_norm": jnp.ones((n, di), jnp.float32),
        "out": jax.random.normal(ks[5], (n, di, d), dt) * di**-0.5,
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    d, ff, l = cfg.d_model, cfg.d_ff, cfg.n_layers
    pv = cfg.padded_vocab
    keys = jax.random.split(key, 8)
    dt = _dt(cfg)
    params: dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (pv, d), dt) * 0.02,
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = jax.random.normal(keys[1], (pv, d), dt) * 0.02

    if cfg.family in ("dense", "vlm"):
        params["layers"] = {
            "ln1": jnp.ones((l, d), jnp.float32),
            "ln2": jnp.ones((l, d), jnp.float32),
            **_init_attn(keys[2], cfg, l, d),
            **_init_ffn(keys[3], cfg, l, d, ff),
        }
    elif cfg.family == "moe":
        params["layers"] = {
            "ln1": jnp.ones((l, d), jnp.float32),
            "ln2": jnp.ones((l, d), jnp.float32),
            **_init_attn(keys[2], cfg, l, d),
            "router": jax.random.normal(
                keys[4], (l, d, cfg.n_experts), jnp.float32
            )
            * 0.02,
            **_init_ffn(keys[3], cfg, l, d, ff, lead=(cfg.n_experts,)),
        }
    elif cfg.family == "ssm":
        params["layers"] = {
            "ln1": jnp.ones((l, d), jnp.float32),
            **_init_ssm(keys[2], cfg, l),
        }
    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        assert l % every == 0, (l, every)
        params["layers"] = {
            "ln1": jnp.ones((l, d), jnp.float32),
            **_init_ssm(keys[2], cfg, l),
        }
        shared_attn = _init_attn(keys[3], cfg, 1, d)
        params["shared"] = {
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            **{k: v[0] for k, v in shared_attn.items()},
            **jax.tree.map(lambda v: v[0], _init_ffn(keys[5], cfg, 1, d, ff)),
        }
    elif cfg.family == "encdec":
        params["layers"] = {  # decoder
            "ln1": jnp.ones((l, d), jnp.float32),
            "ln_x": jnp.ones((l, d), jnp.float32),
            "ln2": jnp.ones((l, d), jnp.float32),
            **_init_attn(keys[2], cfg, l, d),
            **{
                f"x_{k}": v
                for k, v in _init_attn(keys[4], cfg, l, d).items()
            },
            **_init_ffn(keys[3], cfg, l, d, ff),
        }
        le = cfg.n_enc_layers
        params["enc_layers"] = {
            "ln1": jnp.ones((le, d), jnp.float32),
            "ln2": jnp.ones((le, d), jnp.float32),
            **_init_attn(keys[5], cfg, le, d),
            **_init_ffn(keys[6], cfg, le, d, ff),
        }
        params["enc_final_norm"] = jnp.ones((d,), jnp.float32)
    else:
        raise ValueError(cfg.family)
    return params


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(0)
    )


# --------------------------------------------------------------------------
# Layer bodies
# --------------------------------------------------------------------------


# Optional batch-resharding constraint for the attention region. When the
# head count doesn't divide the TP degree, GSPMD falls back to running
# attention REPLICATED across the model axis (16x redundant compute and
# HBM traffic — measured on smollm, EXPERIMENTS.md §Perf iteration 5).
# Setting a spec like P(('data','model')) reshards q/k/v batch-wise over
# the whole mesh for the attention math instead.
_ATTN_BATCH_SHARD = {"spec": None}
# Sequence-sharded prefill attention (§Perf iteration 8): used when the
# batch can't be resharded (prefill batch 32 on 256+ devices).
_ATTN_SEQ_SHARD = {"mesh": None, "axis": "model", "batch_axes": ("pod", "data")}


def set_attn_batch_sharding(spec) -> None:
    """PartitionSpec for the attention batch dim, or None to disable."""
    _ATTN_BATCH_SHARD["spec"] = spec


def set_attn_seq_sharding(mesh, axis: str = "model",
                          batch_axes=("pod", "data")) -> None:
    """Enable (mesh != None) / disable sequence-sharded prefill attention."""
    _ATTN_SEQ_SHARD.update(mesh=mesh, axis=axis, batch_axes=batch_axes)


def _attn_shard(t):
    spec = _ATTN_BATCH_SHARD["spec"]
    if spec is None:
        return t
    return jax.lax.with_sharding_constraint(t, spec)


def _qkv(lp, cfg: ModelConfig, x, positions):
    """Pre-norm q/k/v projection + RoPE shared by EVERY attention path
    (full-sequence, chunked prefill, and via ``_decode_qkv`` the one-token
    decode paths); x: (B, S, d), positions: (B|1, S). Keeping this single
    is what keeps all paths numerically equal."""
    b, s, _ = x.shape
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = dense(h, lp["wq"]).reshape(b, s, cfg.n_heads, cfg.hd)
    k = dense(h, lp["wk"]).reshape(b, s, cfg.n_kv, cfg.hd)
    v = dense(h, lp["wv"]).reshape(b, s, cfg.n_kv, cfg.hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_block(lp, cfg: ModelConfig, x, positions, *, causal=True, window=0):
    """Full-sequence attention sub-block (pre-norm residual)."""
    b, s, d = x.shape
    q, k, v = _qkv(lp, cfg, x, positions)
    seq_mesh = _ATTN_SEQ_SHARD["mesh"]
    if (
        seq_mesh is not None
        and s % seq_mesh.shape[_ATTN_SEQ_SHARD["axis"]] == 0
    ):
        o = attn.flash_attention_seq_sharded(
            q, k, v, causal=causal, window=window,
            mesh=seq_mesh, axis=_ATTN_SEQ_SHARD["axis"],
            batch_axes=_ATTN_SEQ_SHARD["batch_axes"],
        )
    else:
        q, k, v = _attn_shard(q), _attn_shard(k), _attn_shard(v)
        o = attn.flash_attention(q, k, v, causal=causal, window=window)
    return x + dense(o.reshape(b, s, -1), lp["wo"]), (k, v)


def _ffn_block(lp, cfg: ModelConfig, x, ln_name="ln2", *, dropless=False,
               expert_mask=None, stream_depth=2):
    """Pre-norm FFN residual. ``dropless`` switches the moe family onto
    the per-token serving dispatch (``moe_ffn_dropless``), whose second
    return is the (E,) expert-load tally instead of the train-path aux
    loss; ``expert_mask`` ((E,) bool) marks experts whose weights stream
    HBM->VMEM under a residency budget."""
    h = rms_norm(x, lp[ln_name], cfg.norm_eps)
    if cfg.family == "moe":
        if dropless:
            y, counts = moe_lib.moe_ffn_dropless(
                h, lp["router"], lp["w1"], lp["w3"], lp["w2"], cfg,
                stream_mask=expert_mask, stream_depth=stream_depth,
            )
            return x + y, counts
        y, aux = moe_lib.moe_ffn(
            h, lp["router"], lp["w1"], lp["w3"], lp["w2"], cfg
        )
        return x + y, aux
    if cfg.w_bits in (1, 2):
        y = packed_swiglu(h, lp["w1"], lp["w3"], lp["w2"], cfg.w_bits)
    else:
        y = swiglu(h, lp["w1"], lp["w3"], lp["w2"])
    return x + y, jnp.zeros((), jnp.float32)


def _ffn_block_streamed(lp, cfg: ModelConfig, x, depth: int):
    """`_ffn_block` for a layer the residency plan left in HBM: same
    pre-norm residual shape, weights streamed (dense-FFN families only)."""
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    y = streamed_swiglu(h, lp["w1"], lp["w3"], lp["w2"], cfg.w_bits, depth)
    return x + y, jnp.zeros((), jnp.float32)


def _conv_tail(u: jnp.ndarray, k: int, prev: jnp.ndarray | None = None) -> jnp.ndarray:
    """Last ``k-1`` pre-conv inputs of a (B, S, C) sequence, left-padded
    with zeros when the sequence is shorter — exactly the decode-time
    ``conv_decode_step`` buffer after the sequence has been consumed.
    ``prev`` (B, K-1, C) is the buffer carried in from an earlier chunk
    of the same sequence (suffix prefill)."""
    if prev is not None:
        u = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
    b, s, c = u.shape
    tail = u[:, max(0, s - (k - 1)):]
    pad = (k - 1) - tail.shape[1]
    if pad > 0:
        tail = jnp.concatenate(
            [jnp.zeros((b, pad, c), u.dtype), tail], axis=1
        )
    return tail


def _ssm_block(lp, cfg: ModelConfig, x, state=None, conv_bufs=None):
    """Mamba2 block: train path (state None), one-token decode path
    (state given, S == 1), or sequence-with-state path (state given,
    S > 1 — a suffix resumed from a carried SSD state + conv buffers,
    the prefix-cache / chunked-hybrid prefill case).

    All paths return ``(x_out, new_state, new_bufs)``: the sequence
    paths' state/bufs are the *post-sequence* decode state (final SSD
    state + trailing pre-conv inputs), which is what lets a prefill hand
    a request straight to the per-token decode recurrence."""
    b = x.shape[0]
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    z = dense(h, lp["in_z"])
    xi = dense(h, lp["in_x"])
    bi = dense(h, lp["in_b"])
    ci = dense(h, lp["in_c"])
    dt = jax.nn.softplus(
        dense(h, lp["in_dt"]).astype(jnp.float32) + lp["dt_bias"]
    )
    if state is None or x.shape[1] > 1:
        k = cfg.conv_kernel
        cx, cb, cc = conv_bufs if conv_bufs is not None else (None,) * 3
        new_bufs = (
            _conv_tail(xi, k, cx), _conv_tail(bi, k, cb),
            _conv_tail(ci, k, cc),
        )
        xi = ssm_lib.causal_conv(xi, lp["conv_x"], state=cx)
        bi = ssm_lib.causal_conv(bi, lp["conv_b"], state=cb)
        ci = ssm_lib.causal_conv(ci, lp["conv_c"], state=cc)
        s = x.shape[1]
        xh = xi.reshape(b, s, cfg.ssm_heads, cfg.ssm_head_dim)
        y, new_state = ssm_lib.ssd_chunked(
            xh, dt, lp["a_log"], bi, ci, lp["d_skip"], cfg.ssm_chunk,
            h0=state,
        )
        y = y.reshape(b, s, cfg.d_inner)
    else:
        cx, cb, cc = conv_bufs
        xi1, cx = ssm_lib.conv_decode_step(cx, xi[:, 0], lp["conv_x"])
        bi1, cb = ssm_lib.conv_decode_step(cb, bi[:, 0], lp["conv_b"])
        ci1, cc = ssm_lib.conv_decode_step(cc, ci[:, 0], lp["conv_c"])
        xh = xi1.reshape(b, cfg.ssm_heads, cfg.ssm_head_dim)
        y1, new_state = ssm_lib.ssd_decode_step(
            state, xh, dt[:, 0], lp["a_log"], bi1, ci1, lp["d_skip"]
        )
        y = y1.reshape(b, 1, cfg.d_inner)
        new_bufs = (cx, cb, cc)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 lp["gate_norm"], cfg.norm_eps)
    return x + dense(y, lp["out"]), new_state, new_bufs


# --------------------------------------------------------------------------
# Forward (train / prefill, full sequence)
# --------------------------------------------------------------------------


def trunk(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    prefix_embeds: jnp.ndarray | None = None,
    remat: str = "none",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """All layers + final norm, *without* the unembedding.

    Returns (hidden states over the token positions (B, S, d), aux loss).
    ``prefix_embeds`` (B, P, d) are pre-computed modality embeddings (vlm
    patches) prepended to the token embeddings.
    """
    x = embed(tokens, params["embed"], _dt(cfg))
    n_prefix = 0
    if prefix_embeds is not None:
        n_prefix = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, d = x.shape
    positions = jnp.arange(s)[None, :]

    layer_fn = _make_layer_fn(cfg, positions)
    if remat == "full":
        layer_fn = jax.checkpoint(layer_fn)
    elif remat == "dots":
        layer_fn = jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )

    if cfg.family == "hybrid":
        x, aux = _hybrid_stack(params, cfg, x, positions, layer_fn)
    else:
        (x, aux), _ = jax.lax.scan(
            lambda carry, lp: (layer_fn(carry, lp), None),
            (x, jnp.zeros((), jnp.float32)),
            params["layers"],
        )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x[:, n_prefix:], aux


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    prefix_embeds: jnp.ndarray | None = None,
    remat: str = "none",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. tokens: (B, S) int32. Returns (logits, aux)."""
    x, aux = trunk(
        params, cfg, tokens, prefix_embeds=prefix_embeds, remat=remat
    )
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return unembed_logits(x, table, cfg.vocab), aux


def _make_layer_fn(cfg: ModelConfig, positions):
    def layer_fn(carry, lp):
        x, aux = carry
        if cfg.family in ("dense", "vlm", "moe"):
            x, _ = _attn_block(
                lp, cfg, x, positions, causal=True, window=cfg.sliding_window
            )
            x, a = _ffn_block(lp, cfg, x)
            return (x, aux + a)
        if cfg.family in ("ssm", "hybrid"):
            x, _, _ = _ssm_block(lp, cfg, x)
            return (x, aux)
        raise ValueError(cfg.family)

    return layer_fn


def _hybrid_stack(params, cfg: ModelConfig, x, positions, layer_fn):
    """Zamba2: scan over super-blocks of ``every`` ssm layers + one
    application of the single shared attention/FFN block."""
    every = cfg.hybrid_attn_every
    n_super = cfg.n_layers // every
    shaped = jax.tree.map(
        lambda v: v.reshape((n_super, every) + v.shape[1:]), params["layers"]
    )
    shared = params["shared"]

    def super_block(carry, lps):
        def inner(c, lp):
            return layer_fn(c, lp), None

        carry, _ = jax.lax.scan(inner, carry, lps)
        x, aux = carry
        x, _ = _attn_block(shared, cfg, x, positions, causal=True)
        x, a = _ffn_block(shared, cfg, x)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(
        super_block, (x, jnp.zeros((), jnp.float32)), shaped
    )
    return x, aux


def loss_fn(
    params, cfg: ModelConfig, tokens, labels, *, prefix_embeds=None,
    remat: str = "none", aux_weight: float = 0.01, ce_chunk: int = 0,
):
    """Training loss. ``ce_chunk > 0`` switches to the fused chunked
    unembed+CE (never materialises (B, S, V) logits — required for the
    128k-vocab train cells, EXPERIMENTS.md §Perf)."""
    from repro.models.layers import chunked_softmax_xent

    table_of = lambda: (
        params["embed"] if cfg.tie_embeddings else params["unembed"]
    )
    if ce_chunk:
        x, aux = trunk(
            params, cfg, tokens, prefix_embeds=prefix_embeds, remat=remat
        )
        ce = chunked_softmax_xent(
            x, table_of(), labels, cfg.vocab, chunk=ce_chunk
        )
    else:
        lg, aux = forward(
            params, cfg, tokens, prefix_embeds=prefix_embeds, remat=remat
        )
        ce = cross_entropy(lg, labels, cfg.vocab)
    return ce + aux_weight * aux, (ce, aux)


# --------------------------------------------------------------------------
# Decode: cache init, prefill, single-token step
# --------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Decode-state pytree. Attention caches are (L, B, W, Hkv, D) with W =
    min(max_len, sliding_window); ssm state is (L, B, H, P, N)."""
    dt = _dt(cfg)
    cache: dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
    w = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    kv_shape = (cfg.n_layers, batch, w, cfg.n_kv, cfg.hd)
    if cfg.family in ("dense", "vlm", "moe", "encdec"):
        cache["k"] = jnp.zeros(kv_shape, dt)
        cache["v"] = jnp.zeros(kv_shape, dt)
    if cfg.family in ("ssm", "hybrid"):
        l = cfg.n_layers
        cache["ssm"] = jnp.zeros(
            (l, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32,
        )
        k = cfg.conv_kernel
        cache["conv_x"] = jnp.zeros((l, batch, k - 1, cfg.d_inner), dt)
        cache["conv_b"] = jnp.zeros((l, batch, k - 1, cfg.ssm_state), dt)
        cache["conv_c"] = jnp.zeros((l, batch, k - 1, cfg.ssm_state), dt)
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.hybrid_attn_every
        cache["k"] = jnp.zeros(
            (n_super, batch, max_len, cfg.n_kv, cfg.hd), dt
        )
        cache["v"] = jnp.zeros(
            (n_super, batch, max_len, cfg.n_kv, cfg.hd), dt
        )
    return cache


# Decode-path split-d attention (EXPERIMENTS.md §Perf iteration 7): when
# KV heads don't divide TP, GSPMD re-shards the whole cache every step;
# the shard_map path in ``attention.decode_attention_split_d`` keeps the
# cache resident in its head_dim-sharded layout instead.
_DECODE_SPLIT_D = {"mesh": None, "axis": "model", "batch_axes": ("data",)}


def set_decode_split_d(mesh, axis: str = "model",
                       batch_axes=("pod", "data")) -> None:
    """Enable (mesh != None) / disable the split-d decode attention."""
    _DECODE_SPLIT_D.update(mesh=mesh, axis=axis, batch_axes=batch_axes)


def _decode_qkv(lp, cfg, x, pos_b):
    """One-token q/k/v for the decode paths (per-slot ring and
    pool-indexed paged); ``pos_b`` is (B, 1) positions. Delegates to the
    shared ``_qkv`` so every path stays numerically equal."""
    return _qkv(lp, cfg, x, pos_b)


def _decode_attn_block(lp, cfg, x, k_cache, v_cache, pos, *, window=0):
    """One-token attention against one layer's cache; returns new k/v row."""
    b = x.shape[0]
    pos_b = jnp.broadcast_to(pos[None, None], (b, 1))
    q, k, v = _decode_qkv(lp, cfg, x, pos_b)
    w = k_cache.shape[1]
    slot = pos % w if window else jnp.minimum(pos, w - 1)
    k_cache = attn.cache_insert(k_cache, k, slot)
    v_cache = attn.cache_insert(v_cache, v, slot)
    if _DECODE_SPLIT_D["mesh"] is not None:
        o = attn.decode_attention_split_d(
            q, k_cache, v_cache, jnp.minimum(pos + 1, w), window=window,
            mesh=_DECODE_SPLIT_D["mesh"], axis=_DECODE_SPLIT_D["axis"],
            batch_axes=_DECODE_SPLIT_D["batch_axes"],
        )
    else:
        o = attn.decode_attention(
            q, k_cache, v_cache, jnp.minimum(pos + 1, w), window=window
        )
    return x + dense(o.reshape(b, 1, -1), lp["wo"]), k_cache, v_cache


def decode_step(
    params: dict, cfg: ModelConfig, token: jnp.ndarray, cache: dict
) -> tuple[jnp.ndarray, dict]:
    """One serving step: token (B, 1) -> (logits (B, 1, V), new cache)."""
    x = embed(token, params["embed"], _dt(cfg))
    pos = cache["len"]
    new_cache = dict(cache)

    if cfg.family in ("dense", "vlm", "moe"):
        def layer_fn(carry, lp_kv):
            x, aux = carry
            lp, kc, vc = lp_kv
            x, kc, vc = _decode_attn_block(
                lp, cfg, x, kc, vc, pos, window=cfg.sliding_window
            )
            x, a = _ffn_block(lp, cfg, x)
            return (x, aux + a), (kc, vc)

        (x, _), (ks, vs) = jax.lax.scan(
            layer_fn,
            (x, jnp.zeros((), jnp.float32)),
            (params["layers"], cache["k"], cache["v"]),
        )
        new_cache["k"], new_cache["v"] = ks, vs

    elif cfg.family == "ssm":
        def layer_fn(x, lp_state):
            lp, st, cx, cb, cc = lp_state
            x, st, bufs = _ssm_block(lp, cfg, x, state=st, conv_bufs=(cx, cb, cc))
            return x, (st, *bufs)

        x, (sts, cxs, cbs, ccs) = jax.lax.scan(
            layer_fn,
            x,
            (
                params["layers"],
                cache["ssm"],
                cache["conv_x"],
                cache["conv_b"],
                cache["conv_c"],
            ),
        )
        new_cache.update(ssm=sts, conv_x=cxs, conv_b=cbs, conv_c=ccs)

    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_super = cfg.n_layers // every
        shaped = jax.tree.map(
            lambda v: v.reshape((n_super, every) + v.shape[1:]),
            params["layers"],
        )
        ssm_states = jax.tree.map(
            lambda v: v.reshape((n_super, every) + v.shape[1:]),
            (cache["ssm"], cache["conv_x"], cache["conv_b"], cache["conv_c"]),
        )
        shared = params["shared"]

        def super_block(x, inp):
            lps, (sts, cxs, cbs, ccs), kc, vc = inp

            def inner(x, lp_state):
                lp, st, cx, cb, cc = lp_state
                x, st, bufs = _ssm_block(
                    lp, cfg, x, state=st, conv_bufs=(cx, cb, cc)
                )
                return x, (st, *bufs)

            x, new_states = jax.lax.scan(inner, x, (lps, sts, cxs, cbs, ccs))
            x, kc, vc = _decode_attn_block(shared, cfg, x, kc, vc, pos)
            x, _ = _ffn_block(shared, cfg, x)
            return x, (new_states, kc, vc)

        x, (new_states, ks, vs) = jax.lax.scan(
            super_block, x, (shaped, ssm_states, cache["k"], cache["v"])
        )
        sts, cxs, cbs, ccs = new_states
        merge = lambda v: v.reshape((cfg.n_layers,) + v.shape[2:])
        new_cache.update(
            ssm=merge(sts), conv_x=merge(cxs), conv_b=merge(cbs),
            conv_c=merge(ccs), k=ks, v=vs,
        )
    else:
        raise ValueError(f"decode not supported for family {cfg.family}")

    new_cache["len"] = pos + 1
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return unembed_logits(x, table, cfg.vocab), new_cache


def prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    prefix_embeds=None,
) -> jnp.ndarray:
    """Prefill = the full-sequence forward (cache materialisation is the
    serving engine's job; the dry-run lowers the compute graph)."""
    lg, _ = forward(params, cfg, tokens, prefix_embeds=prefix_embeds)
    return lg


def prefill_with_cache(
    params: dict, cfg: ModelConfig, tokens: jnp.ndarray, last_idx: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-sequence prefill that *keeps* the per-layer K/V rows.

    tokens: (B, S) right-padded prompts; ``last_idx`` the index of the last
    real token. Causality makes the padded tail inert for positions
    <= last_idx in every attention-KV family — dense/vlm trivially, and
    moe because serving routes through the dropless per-token dispatch
    (``moe_ffn_dropless``: a padded row's routing never touches a real
    row's output). Returns (next-token logits (B, 1, V), ks, vs) with
    ks/vs stacked (L, B, S, n_kv, hd) — already RoPE'd, i.e. exactly the
    rows the decode cache stores; the moe family appends a per-layer
    expert-load tally (L, E). Attention-KV families only.
    """
    if cfg.family not in ATTN_KV_FAMILIES:
        raise ValueError(f"prefill_with_cache: unsupported family {cfg.family}")
    moe = cfg.family == "moe"
    x = embed(tokens, params["embed"], _dt(cfg))
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]

    def layer_fn(carry, lp):
        x, aux = carry
        x, (k, v) = _attn_block(
            lp, cfg, x, positions, causal=True, window=cfg.sliding_window
        )
        if moe:
            x, counts = _ffn_block(lp, cfg, x, dropless=True)
            return (x, aux), (k, v, counts)
        x, a = _ffn_block(lp, cfg, x)
        return (x, aux + a), (k, v)

    (x, _), outs = jax.lax.scan(
        layer_fn, (x, jnp.zeros((), jnp.float32)), params["layers"]
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    lg = unembed_logits(x_last, table, cfg.vocab)
    if moe:
        ks, vs, counts = outs
        return lg, ks, vs, counts
    ks, vs = outs
    return lg, ks, vs


# the named scopes of the paged serving steps: they reach the compiled
# step's op metadata (``op_name``), so a device profile's operations can
# be put down to the attention sub-block, its KV pool write and gather,
# the FFN and the unembedding
STEP_SCOPES = ("attention", "kv_write", "kv_gather", "ffn", "logits")


def _paged_kv(pk, pv, layer, block_table, starts, k, v):
    """Write each lane's new K/V rows ((B, C, n_kv, hd), lane b's at its
    positions ``starts[b]..``) into layer ``layer`` of the block pools,
    then gather every lane's whole block table as rows: (new pk, new pv,
    gathered k, gathered v), the gathered rows (B, S_max, n_kv, hd)."""
    n_kv, hd = k.shape[-2:]
    with jax.named_scope("kv_write"):
        pk = attn.write_tokens(pk, layer, block_table, starts, k)
        pv = attn.write_tokens(pv, layer, block_table, starts, v)
    with jax.named_scope("kv_gather"):
        return (
            pk, pv,
            attn.gather_tokens(pk, layer, block_table, n_kv, hd),
            attn.gather_tokens(pv, layer, block_table, n_kv, hd),
        )


def _paged_decode_kv(pk, pv, layer, block_table, lengths, q, k, v, window):
    """The decode step's KV sub-layer: each lane's new K/V row written at
    position ``lengths[b]`` of layer ``layer``, and attention over the
    lane's positions 0..lengths[b]: (new pk, new pv, attention output
    (B, 1, Hq, hd)). On the chip one kernel does both and reads only the
    live blocks (``kernels.paged_attention``); elsewhere the row is
    written block-wise and every lane's whole table gathered."""
    from repro.kernels import ops

    n_kv = k.shape[-2]
    if ops.paged_decode_runs_kernel(q.shape[-1], pk):
        with jax.named_scope("kv_gather"):
            o, pk, pv = ops.paged_decode(
                q, k[:, 0], v[:, 0], pk, pv, layer, block_table, lengths,
                n_kv=n_kv, window=window,
            )
        return pk, pv, o
    with jax.named_scope("kv_write"):
        pk = attn.write_tokens(pk, layer, block_table, lengths, k)
        pv = attn.write_tokens(pv, layer, block_table, lengths, v)
    with jax.named_scope("kv_gather"):
        o = attn.paged_decode_attention_ref(
            q, pk, pv, layer, block_table, lengths + 1, n_kv=n_kv,
            window=window,
        )
    return pk, pv, o


def _scoped_logits(params, cfg: ModelConfig, x, last_idx=None):
    """Final norm and unembedding (of position ``last_idx`` alone when
    given), under the ``logits`` scope."""
    with jax.named_scope("logits"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if last_idx is not None:
            x = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        return unembed_logits(x, table, cfg.vocab)


def decode_step_paged(
    params: dict,
    cfg: ModelConfig,
    token: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    stream_mask: jnp.ndarray | None = None,
    stream_depth: int = 2,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One serving step against a shared block-addressed KV pool.

    token: (B, 1) next token per decode lane; pool_k/pool_v:
    (L, n_blocks, rows, width) physical pools of block tiles
    (``attention.pool_tile``); block_table: (B, nb) physical block of each
    lane's logical block (scratch-block padded); lengths: (B,) tokens
    already held per lane. The new token's K/V row is written at position
    ``lengths[b]`` through the lane's table, then each lane attends over
    its own live blocks with per-lane positions (no lockstep shared
    length — lanes at different depths coexist in one batched step). The
    pools ride the layer scan as its carry and are updated in place.

    ``stream_mask`` turns on the budgeted weight-residency path
    (``runtime.residency``). For the dense-FFN families it is (L,) bool:
    layers flagged True run their FFN through the HBM->VMEM weight
    streamer with ring depth ``stream_depth`` instead of the resident
    in-VMEM matmul. For moe it is (L, E) bool: per-(layer, expert) cold
    flags consumed by the dropless dispatch, which streams the flagged
    experts' w1/w3/w2 and keeps the pinned (hot) experts resident.
    Either way the mask is scanned with the layer leaves so the model
    still compiles as one scan.

    Returns (logits (B, 1, V), new pool_k, new pool_v); the moe family
    appends a per-layer expert-load tally (L, E).
    """
    if cfg.family not in ATTN_KV_FAMILIES:
        raise ValueError(f"decode_step_paged: unsupported family {cfg.family}")
    moe = cfg.family == "moe"
    x = embed(token, params["embed"], _dt(cfg))
    b = x.shape[0]
    pos_b = lengths[:, None]  # (B, 1) position of the incoming token

    def layer_fn(carry, xs):
        x, aux, pk, pv = carry
        if stream_mask is None:
            lp, layer = xs
            streamed = None
        else:
            lp, layer, streamed = xs
        with jax.named_scope("attention"):
            q, k, v = _decode_qkv(lp, cfg, x, pos_b)
            pk, pv, o = _paged_decode_kv(
                pk, pv, layer, block_table, lengths, q, k, v,
                attn.decode_window(cfg),
            )
            x = x + dense(o.reshape(b, 1, -1), lp["wo"])
        with jax.named_scope("ffn"):
            if moe:
                x, counts = _ffn_block(
                    lp, cfg, x, dropless=True, expert_mask=streamed,
                    stream_depth=stream_depth,
                )
            elif stream_mask is None:
                x, a = _ffn_block(lp, cfg, x)
            else:
                x, a = jax.lax.cond(
                    streamed,
                    lambda h: _ffn_block_streamed(lp, cfg, h, stream_depth),
                    lambda h: _ffn_block(lp, cfg, h),
                    x,
                )
        if moe:
            return (x, aux, pk, pv), counts
        return (x, aux + a, pk, pv), None

    xs = (params["layers"], jnp.arange(cfg.n_layers))
    if stream_mask is not None:
        xs = xs + (stream_mask,)
    (x, _, pks, pvs), counts = jax.lax.scan(
        layer_fn, (x, jnp.zeros((), jnp.float32), pool_k, pool_v), xs
    )
    lg = _scoped_logits(params, cfg, x)
    if moe:
        return lg, pks, pvs, counts
    return lg, pks, pvs


def _chunk_layers(params, cfg: ModelConfig, x, pool_k, pool_v, block_table,
                  starts, positions):
    """The layer scan shared by chunk prefill and draft verification:
    each lane's C tokens at ``positions`` (its rows written from
    ``starts[b]``) attend causally over its gathered block table. Returns
    (x, new pool_k, new pool_v, moe expert-load tally or None)."""
    moe = cfg.family == "moe"
    b, c, _ = x.shape

    def layer_fn(carry, xs):
        x, aux, pk, pv = carry
        lp, layer = xs
        with jax.named_scope("attention"):
            q, k, v = _qkv(lp, cfg, x, positions)
            pk, pv, kg, vg = _paged_kv(
                pk, pv, layer, block_table, starts, k, v
            )
            # gathered rows sit at logical positions 0..S_max-1; rows past
            # each query (scratch padding included) are masked by causality
            o = attn.chunk_attention(
                q, kg, vg, positions, window=cfg.sliding_window
            )
            x = x + dense(o.reshape(b, c, -1), lp["wo"])
        with jax.named_scope("ffn"):
            if moe:
                x, counts = _ffn_block(lp, cfg, x, dropless=True)
                return (x, aux, pk, pv), counts
            x, a = _ffn_block(lp, cfg, x)
        return (x, aux + a, pk, pv), None

    (x, _, pks, pvs), counts = jax.lax.scan(
        layer_fn,
        (x, jnp.zeros((), jnp.float32), pool_k, pool_v),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return x, pks, pvs, counts


def prefill_chunk_paged(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,
    start: jnp.ndarray,
    last_idx: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill one chunk of a prompt against the shared KV pool.

    Chunked prefill (ROADMAP): a prompt longer than the scheduler's
    admission token budget is split across rounds instead of monopolizing
    one round with a single huge prefill step. Each chunk attends over the
    request's *already-pooled* prefix (gathered through ``block_table``)
    plus itself, causally — flash attention with ``q_offset = start`` —
    and writes its own K/V rows into the pool. ``start`` doubles as the
    matched-prefix offset of a prefix-cache hit: the warm path prefills
    only the unmatched suffix, attending over the adopted shared blocks
    exactly as it would over its own earlier chunks.

    tokens: (B, C) chunk tokens, right-padded; block_table: (B, nb) the
    request's block table; start: () position of the chunk's first token
    (the chunk's rows are written at positions start.., the padding's
    past the prompt, where nothing valid lies yet); last_idx: () in-chunk
    index of the prompt's last token (only meaningful on the final
    chunk). Attention-KV families only — moe included: the dropless
    per-token dispatch makes a chunk boundary invisible to routing, so
    chunked == single-shot exactly.

    Returns (logits at last_idx (B, 1, V), new pool_k, new pool_v); the
    moe family appends a per-layer expert-load tally (L, E).
    """
    if cfg.family not in ATTN_KV_FAMILIES:
        raise ValueError(
            f"prefill_chunk_paged: unsupported family {cfg.family}"
        )
    x = embed(tokens, params["embed"], _dt(cfg))
    b, c, _ = x.shape
    positions = start + jnp.arange(c)[None, :]  # (1, C) broadcast over B
    x, pks, pvs, counts = _chunk_layers(
        params, cfg, x, pool_k, pool_v, block_table,
        jnp.full((b,), start, jnp.int32), positions,
    )
    lg = _scoped_logits(params, cfg, x, last_idx)
    if cfg.family == "moe":
        return lg, pks, pvs, counts
    return lg, pks, pvs


def verify_chunk_paged(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,
    starts: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Score a depth-C draft chain per lane against the shared KV pool.

    The speculative-decoding verifier (``runtime.speculative``): each lane
    feeds its pending token plus the drafter's proposals as one chunk, so
    the target scores every draft position in ONE batched step instead of
    C sequential ``decode_step_paged`` calls. ``prefill_chunk_paged``
    generalised two ways: ``starts`` is per-lane (B,) — decode lanes sit
    at different depths — and the full (B, C, V) logits come back, because
    longest-accepted-prefix selection needs the distribution at every
    draft position, not just the last. K/V rows for the fed chain land in
    the lanes' own (private, refcounted) blocks at positions starts[b]..;
    rows past a lane's accepted prefix are dead weight the next chain
    overwrites, which is what makes rejection rollback free.

    tokens: (B, C) draft chains, right-padded (a padded token's row lands
    past the lane's chain, where nothing valid lies); starts: (B,)
    position of each lane's first fed token. Attention-KV families only —
    moe included (dropless dispatch is chunk-invariant); the moe family
    appends a per-layer expert-load tally (L, E).
    """
    if cfg.family not in ATTN_KV_FAMILIES:
        raise ValueError(
            f"verify_chunk_paged: unsupported family {cfg.family}"
        )
    x = embed(tokens, params["embed"], _dt(cfg))
    c = x.shape[1]
    positions = starts[:, None] + jnp.arange(c)[None, :]  # (B, C)
    x, pks, pvs, counts = _chunk_layers(
        params, cfg, x, pool_k, pool_v, block_table, starts, positions
    )
    lg = _scoped_logits(params, cfg, x)
    if cfg.family == "moe":
        return lg, pks, pvs, counts
    return lg, pks, pvs


# --------------------------------------------------------------------------
# Hybrid (Zamba2) paged serving: shared-attention KV pages through the
# pool, SSM conv/state stays resident per decode lane
# --------------------------------------------------------------------------


def init_ssm_lane_state(cfg: ModelConfig, slots: int) -> dict:
    """Per-lane resident SSM decode state for the hybrid paged scheduler.

    Unlike the attention KV cache, this state is fixed-size per lane (the
    SSD recurrence is O(1) in sequence length), so it never pages: leaves
    are (L, slots, ...) and a lane's slice is overwritten on admission.
    """
    dt = _dt(cfg)
    l, k = cfg.n_layers, cfg.conv_kernel
    return {
        "ssm": jnp.zeros(
            (l, slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32,
        ),
        "conv_x": jnp.zeros((l, slots, k - 1, cfg.d_inner), dt),
        "conv_b": jnp.zeros((l, slots, k - 1, cfg.ssm_state), dt),
        "conv_c": jnp.zeros((l, slots, k - 1, cfg.ssm_state), dt),
    }


def prefill_with_cache_hybrid(
    params: dict, cfg: ModelConfig, tokens: jnp.ndarray, last_idx: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, dict]:
    """Hybrid full-sequence prefill keeping *both* kinds of decode state.

    tokens: (B, S) prompts — hybrid prompts must be **unpadded** (the
    final SSD state integrates every position, so padded tails would
    pollute it; the scheduler prefills hybrids one-trace-per-length like
    MoE). Returns (next-token logits (B, 1, V), ks, vs stacked
    (n_super, B, S, n_kv, hd) — the shared attention blocks' KV rows for
    pool insertion — and the lane-state dict of ``init_ssm_lane_state``
    leaves shaped (L, B, ...)).
    """
    if cfg.family != "hybrid":
        raise ValueError(
            f"prefill_with_cache_hybrid: family {cfg.family!r} is not hybrid"
        )
    x = embed(tokens, params["embed"], _dt(cfg))
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    every = cfg.hybrid_attn_every
    n_super = cfg.n_layers // every
    shaped = jax.tree.map(
        lambda v: v.reshape((n_super, every) + v.shape[1:]), params["layers"]
    )
    shared = params["shared"]

    def super_block(carry, lps):
        x, aux = carry

        def inner(c, lp):
            y, st, bufs = _ssm_block(lp, cfg, c)
            return y, (st, *bufs)

        x, states = jax.lax.scan(inner, x, lps)
        x, (k, v) = _attn_block(shared, cfg, x, positions, causal=True)
        x, a = _ffn_block(shared, cfg, x)
        return (x, aux + a), (states, k, v)

    (x, _), (states, ks, vs) = jax.lax.scan(
        super_block, (x, jnp.zeros((), jnp.float32)), shaped
    )
    sts, cxs, cbs, ccs = states  # leaves (n_super, every, B, ...)
    merge = lambda v: v.reshape((cfg.n_layers,) + v.shape[2:])
    lane_state = {
        "ssm": merge(sts), "conv_x": merge(cxs),
        "conv_b": merge(cbs), "conv_c": merge(ccs),
    }
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return unembed_logits(x_last, table, cfg.vocab), ks, vs, lane_state


def decode_step_paged_hybrid(
    params: dict,
    cfg: ModelConfig,
    token: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    lane_state: dict,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, dict]:
    """``decode_step_paged`` for the hybrid family.

    The shared attention block of each super-block writes its KV row and
    attends over the lane's live blocks of the pool (pool_k/pool_v are
    (n_super, n_blocks, rows, width), addressed by the same per-lane
    ``block_table``/``lengths`` as the attention families), while the SSM
    recurrence advances the resident per-lane ``lane_state`` (leaves
    (L, B, ...)). Returns (logits (B, 1, V), new pool_k, new pool_v, new
    lane_state).
    """
    if cfg.family != "hybrid":
        raise ValueError(
            f"decode_step_paged_hybrid: family {cfg.family!r} is not hybrid"
        )
    x = embed(token, params["embed"], _dt(cfg))
    b = x.shape[0]
    pos_b = lengths[:, None]
    every = cfg.hybrid_attn_every
    n_super = cfg.n_layers // every
    shaped = jax.tree.map(
        lambda v: v.reshape((n_super, every) + v.shape[1:]), params["layers"]
    )
    states = jax.tree.map(
        lambda v: v.reshape((n_super, every) + v.shape[1:]),
        (
            lane_state["ssm"], lane_state["conv_x"],
            lane_state["conv_b"], lane_state["conv_c"],
        ),
    )
    shared = params["shared"]

    def super_block(carry, inp):
        x, pk, pv = carry
        lps, (sts, cxs, cbs, ccs), layer = inp

        def inner(x, lp_state):
            lp, st, cx, cb, cc = lp_state
            x, st, bufs = _ssm_block(
                lp, cfg, x, state=st, conv_bufs=(cx, cb, cc)
            )
            return x, (st, *bufs)

        x, new_states = jax.lax.scan(inner, x, (lps, sts, cxs, cbs, ccs))
        with jax.named_scope("attention"):
            q, k, v = _decode_qkv(shared, cfg, x, pos_b)
            pk, pv, o = _paged_decode_kv(
                pk, pv, layer, block_table, lengths, q, k, v,
                attn.decode_window(cfg),
            )
            x = x + dense(o.reshape(b, 1, -1), shared["wo"])
        with jax.named_scope("ffn"):
            x, _ = _ffn_block(shared, cfg, x)
        return (x, pk, pv), new_states

    (x, pks, pvs), new_states = jax.lax.scan(
        super_block, (x, pool_k, pool_v),
        (shaped, states, jnp.arange(n_super)),
    )
    sts, cxs, cbs, ccs = new_states
    merge = lambda v: v.reshape((cfg.n_layers,) + v.shape[2:])
    new_lane = {
        "ssm": merge(sts), "conv_x": merge(cxs),
        "conv_b": merge(cbs), "conv_c": merge(ccs),
    }
    return _scoped_logits(params, cfg, x), pks, pvs, new_lane


def prefill_suffix_paged_hybrid(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,
    start: jnp.ndarray,
    last_idx: jnp.ndarray,
    lane_state: dict,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, dict]:
    """Hybrid prefill of a prompt *suffix*, resuming from carried state.

    The prefix-cache warm path for zamba2: positions ``0..start-1`` were
    served by a cached prefix — their shared-attention KV rows sit in the
    pool (gathered through ``block_table``) and the SSM recurrence resumes
    from ``lane_state``, the anchor snapshot taken when the prefix was
    committed (leaves shaped (L, B, ...) as in ``init_ssm_lane_state``).
    The suffix's SSD scan seeds ``ssd_chunked`` with the carried state
    and the causal convs take their left context from the carried conv
    buffers, so the result is the cold full-prompt prefill's — this is
    also the machinery chunked hybrid prefill needs (SSD state carried
    across chunks).

    tokens: (B, C) **unpadded** suffix (hybrid prompts never pad), its
    rows written at positions ``start..``; start: () position of the
    suffix's first token; last_idx: () in-suffix index of the prompt's
    last token. Returns (logits at last_idx (B, 1, V), new pool_k, new
    pool_v, new lane_state).
    """
    if cfg.family != "hybrid":
        raise ValueError(
            f"prefill_suffix_paged_hybrid: family {cfg.family!r} is not hybrid"
        )
    x = embed(tokens, params["embed"], _dt(cfg))
    b, c, _ = x.shape
    positions = start + jnp.arange(c)[None, :]
    starts = jnp.full((b,), start, jnp.int32)
    every = cfg.hybrid_attn_every
    n_super = cfg.n_layers // every
    shaped = jax.tree.map(
        lambda v: v.reshape((n_super, every) + v.shape[1:]), params["layers"]
    )
    states = jax.tree.map(
        lambda v: v.reshape((n_super, every) + v.shape[1:]),
        (
            lane_state["ssm"], lane_state["conv_x"],
            lane_state["conv_b"], lane_state["conv_c"],
        ),
    )
    shared = params["shared"]

    def super_block(carry, inp):
        x, pk, pv = carry
        lps, (sts, cxs, cbs, ccs), layer = inp

        def inner(x, lp_state):
            lp, st, cx, cb, cc = lp_state
            x, st, bufs = _ssm_block(
                lp, cfg, x, state=st, conv_bufs=(cx, cb, cc)
            )
            return x, (st, *bufs)

        x, new_states = jax.lax.scan(inner, x, (lps, sts, cxs, cbs, ccs))
        with jax.named_scope("attention"):
            q, k, v = _qkv(shared, cfg, x, positions)
            pk, pv, kg, vg = _paged_kv(
                pk, pv, layer, block_table, starts, k, v
            )
            o = attn.chunk_attention(q, kg, vg, positions)
            x = x + dense(o.reshape(b, c, -1), shared["wo"])
        with jax.named_scope("ffn"):
            x, _ = _ffn_block(shared, cfg, x)
        return (x, pk, pv), new_states

    (x, pks, pvs), new_states = jax.lax.scan(
        super_block, (x, pool_k, pool_v),
        (shaped, states, jnp.arange(n_super)),
    )
    sts, cxs, cbs, ccs = new_states
    merge = lambda v: v.reshape((cfg.n_layers,) + v.shape[2:])
    new_lane = {
        "ssm": merge(sts), "conv_x": merge(cxs),
        "conv_b": merge(cbs), "conv_c": merge(ccs),
    }
    return _scoped_logits(params, cfg, x, last_idx), pks, pvs, new_lane


# --------------------------------------------------------------------------
# Sampling (host-side: the scheduler samples from materialised logits)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Decode sampling policy. ``temperature == 0`` is exact greedy (the
    default and the special case every equivalence test pins); top-k and
    top-p restrict the support *before* renormalising. Seed-determinism
    is the scheduler's contract: it draws from an rng keyed on
    (seed, request id, position), so a request's output is independent of
    lane placement and co-resident requests."""

    temperature: float = 0.0
    top_k: int = 0  # 0 = unrestricted
    top_p: float = 1.0  # 1.0 = unrestricted
    seed: int = 0

    @property
    def is_greedy(self) -> bool:
        return self.temperature <= 0.0


def sample_logits(
    row,
    sp: SamplingParams,
    rng=None,
) -> int:
    """Draw one token from a (V,) numpy logits row under ``sp``.

    Greedy (temperature 0) never touches ``rng`` (it may be None); top_k=1
    collapses to greedy regardless of temperature; top_k >= V is
    unrestricted.
    """
    import numpy as np

    row = np.asarray(row, np.float64)
    if sp.is_greedy or sp.top_k == 1:
        return int(np.argmax(row))
    logits = row / sp.temperature
    top_k = min(sp.top_k, len(row))
    if top_k > 0:
        kth = np.partition(logits, -top_k)[-top_k]
        logits = np.where(logits >= kth, logits, -np.inf)
    logits = logits - np.max(logits)
    probs = np.exp(logits)
    probs /= probs.sum()
    if sp.top_p < 1.0:
        order = np.argsort(-probs)
        csum = np.cumsum(probs[order])
        # smallest prefix whose mass reaches top_p (>= 1 token)
        cut = int(np.searchsorted(csum, sp.top_p)) + 1
        mask = np.zeros_like(probs, bool)
        mask[order[:cut]] = True
        probs = np.where(mask, probs, 0.0)
        probs /= probs.sum()
    return int(rng.choice(len(probs), p=probs))
