"""Speculative decoding over the paged KV pool: drafters + resolution.

Decode is HBM-bound: every step re-reads the full weight set to emit one
token per lane. Speculate-and-verify buys back that sweep — a cheap
drafter proposes a depth-``k`` chain per decode lane, and the target
model scores *all* chain positions in ONE batched paged-attention call
(``lm.verify_chunk_paged``), accepting the longest prefix whose sampled
tokens match the proposals. Each verify step therefore yields between 1
and ``k`` tokens for roughly one decode step's weight traffic.

The paper's artifact supplies the drafter for free: an FCMP-packed
1-bit/2-bit arch (arXiv:2011.07317) is a cheap low-precision twin of its
dense counterpart — same attention weights, FFN mats swapped for packed
carriers at 1/16th (w1) or 1/8th (w2) the bytes — so its decode roofline
is a fraction of the target's (``StepCostModel.for_config`` already
discounts packed FFN HBM traffic). Families without packable FFNs (moe)
fall back to the self-drafting n-gram drafter: a deterministic
suffix-match lookup over the request's own prompt+output history, free
of model cost entirely.

Token identity is structural, not probabilistic: the verifier samples
position ``m`` from the target's own logits with the same
(seed, rid, m)-keyed rng that non-speculative decode would use, and a
position's logits only depend on accepted (= identical) earlier tokens.
Drafter quality moves the acceptance rate, never the output.

Drafter eligibility:

    target family   model drafter (packed twin)   ngram drafter
    dense           yes                           yes
    vlm             yes                           yes
    moe             no (expert FFNs not packed)   yes
    hybrid          no — rejected with an actionable error: SSM lane
                    state has no per-position rollback, so draft-chain
                    rejection cannot restore the lane recurrence
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.attention import pool_tile
from repro.models.config import ModelConfig
from repro.runtime.kv_pool import write_blocks
from repro.runtime.steps import make_paged_serve_step, make_pool_prefill_step

# families verify_chunk_paged serves (hybrid's SSM lanes cannot roll back)
SPEC_FAMILIES = ("dense", "vlm", "moe")
# families whose FFN leaves pack into FCMP carriers -> model drafters
MODEL_DRAFT_FAMILIES = ("dense", "vlm")

NGRAM = "ngram"


@functools.lru_cache(maxsize=None)
def _jitted_draft_decode(cfg: ModelConfig):
    return jax.jit(make_paged_serve_step(cfg), donate_argnums=(2, 3))


@functools.lru_cache(maxsize=None)
def _jitted_draft_prefill(cfg: ModelConfig):
    return jax.jit(make_pool_prefill_step(cfg))


# tokens per block of a model drafter's private pool
DRAFT_BLOCK_TOKENS = 16


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """CLI-level speculative knobs (``--speculate`` / ``--spec-depth``).

    ``drafter`` is ``"ngram"`` or a canonical arch id from
    ``configs.ARCH_IDS``; ``quant`` is the packed-carrier width for model
    drafters (the w_bits of the twin)."""

    drafter: str
    depth: int = 4
    quant: int = 2


@dataclasses.dataclass(frozen=True)
class LaneDraft:
    """One decode lane's view, handed to the drafter each verify cycle."""

    slot: int
    rid: int
    pending: int  # last sampled token, not yet fed to the target
    out_len: int  # len(request.output) — the next sample's rng position
    n_rows: int  # KV rows the target pool holds for this request
    history: np.ndarray  # prompt + output so far (pending included)


def _sample_keyed(row, sp: lm.SamplingParams, rid: int, pos: int) -> int:
    """The scheduler's (seed, rid, position)-keyed sampler, shared so a
    model drafter's proposals use the exact rng the verifier will."""
    rng = np.random.default_rng(np.random.SeedSequence([sp.seed, rid, pos]))
    return int(lm.sample_logits(row, sp, rng))


# --------------------------------------------------------------------------
# Drafter twins: FFN packing / dequantization
# --------------------------------------------------------------------------


def pack_ffn_params(params: dict, bits: int) -> dict:
    """The packed twin of a dense/vlm param set: FFN leaves (w1/w3/w2)
    swapped for FCMP carriers, everything else shared by reference.
    Already-packed leaves (a quantized target) pass through."""
    lay = dict(params["layers"])
    for k in ("w1", "w3", "w2"):
        if not isinstance(lay[k], dict):
            lay[k] = lm.make_packed(lay[k], bits)
    return {**params, "layers": lay}


def dequantize_ffn_params(params: dict, bits: int) -> dict:
    """The dense counterpart of a packed twin: FFN leaves replaced by
    their decoded carrier values, so ``pack_ffn_params`` of the result
    round-trips losslessly (quantization is idempotent on its own
    codebook). This is the spec_bench pairing: random smoke weights have
    no trained drafter/target correlation, so the bench serves the
    packed arch's dense execution as the target — with real checkpoints
    the natural pair is a trained dense target and its packed twin."""

    def dequant(w):
        if isinstance(w, dict):
            p = w
        else:
            p = lm.make_packed(w, bits)
        codes = lm._unpack_codes(p["packed"], bits).astype(jnp.float32)
        vals = codes * 2.0 - 1.0 if bits == 1 else codes - 1.0
        out = vals * p["scale"][..., None, :]
        return out.astype(w.dtype if not isinstance(w, dict) else out.dtype)

    lay = dict(params["layers"])
    for k in ("w1", "w3", "w2"):
        lay[k] = dequant(lay[k])
    return {**params, "layers": lay}


# --------------------------------------------------------------------------
# Resolution: --speculate <drafter> against a target config
# --------------------------------------------------------------------------


def compatible_drafters(cfg: ModelConfig, *, smoke: bool = False) -> list[str]:
    """Drafter names servable against ``cfg``: ``ngram`` plus every
    canonical arch of a packable family whose vocab matches the target
    (logit rows must index the same token space)."""
    from repro import configs

    out = [NGRAM]
    for arch in configs.ARCH_IDS:
        try:
            dcfg = (
                configs.get_smoke_config(arch)
                if smoke
                else configs.get_config(arch)
            )
        except ValueError:
            continue
        if dcfg.family in MODEL_DRAFT_FAMILIES and dcfg.vocab == cfg.vocab:
            out.append(arch)
    return out


@dataclasses.dataclass(frozen=True)
class ResolvedSpec:
    """A validated drafter choice for one target config.

    ``draft_cfg`` is the serving-size drafter config (None for ngram);
    ``draft_full_cfg`` is the full-size one the fleet's virtual clock
    charges (``StepCostModel.for_config`` — the packed twin's FFN bytes
    are discounted there, which is where the TPOT win comes from);
    ``twin`` marks a drafter of the target's own arch, built by packing
    the served params rather than initialising fresh ones."""

    spec: SpecConfig
    draft_cfg: ModelConfig | None
    draft_full_cfg: ModelConfig | None
    twin: bool

    def build(self, cfg: ModelConfig, params, *, slots: int, max_len: int):
        """Per-engine drafter state (each engine drafts its own lanes)."""
        if self.draft_cfg is None:
            return Speculator(NgramDrafter(), depth=self.spec.depth)
        if self.twin:
            dparams = pack_ffn_params(params, self.draft_cfg.w_bits)
        else:
            # no distilled checkpoint in the smoke harness: a foreign
            # drafter arch serves freshly-initialised weights (acceptance
            # will be poor; the twin pairing is the supported fast path)
            dparams = lm.init_params(self.draft_cfg, jax.random.key(0))
        drafter = ModelDrafter(
            self.draft_cfg, dparams, slots=slots, max_len=max_len
        )
        return Speculator(drafter, depth=self.spec.depth)


def resolve(
    cfg: ModelConfig, spec: SpecConfig, *, smoke: bool = False
) -> ResolvedSpec:
    """Validate ``--speculate``/``--spec-depth`` against the target.

    Raises ``ValueError`` (the CLIs' exit-2 path) with an actionable
    message listing the compatible drafters when the arch is unknown,
    un-packable, vocab-mismatched, or the target family cannot verify."""
    if cfg.family not in SPEC_FAMILIES:
        raise ValueError(
            f"speculative decoding: family {cfg.family!r} has no draft-tree "
            f"verification path (SSM lane state cannot roll back a rejected "
            f"chain); serve one of {SPEC_FAMILIES} or drop --speculate"
        )
    if spec.depth < 2:
        raise ValueError(
            f"--spec-depth {spec.depth} proposes no draft tokens; "
            "use a depth >= 2 (or drop --speculate)"
        )
    if spec.quant not in (1, 2):
        raise ValueError(
            f"--spec-quant {spec.quant} is not a packed carrier width; "
            "FCMP packs 1- or 2-bit codes"
        )
    if spec.drafter == NGRAM:
        return ResolvedSpec(spec, None, None, twin=False)

    from repro import configs

    options = ", ".join(compatible_drafters(cfg, smoke=smoke))
    try:
        arch = configs.canonical(spec.drafter)
        dcfg = (
            configs.get_smoke_config(arch) if smoke else configs.get_config(arch)
        )
        dfull = configs.get_config(arch)
    except ValueError:
        raise ValueError(
            f"unknown drafter arch {spec.drafter!r}; compatible drafters "
            f"for {cfg.name}: {options}"
        ) from None
    if dcfg.family not in MODEL_DRAFT_FAMILIES:
        raise ValueError(
            f"drafter arch {spec.drafter!r} (family {dcfg.family!r}) has no "
            f"packed twin — only {MODEL_DRAFT_FAMILIES} FFNs pack into FCMP "
            f"carriers; compatible drafters for {cfg.name}: {options}"
        )
    if dcfg.vocab != cfg.vocab:
        raise ValueError(
            f"drafter arch {spec.drafter!r} vocab {dcfg.vocab} != target "
            f"{cfg.name} vocab {cfg.vocab} — proposals would index a "
            f"different token space; compatible drafters: {options}"
        )
    twin = dcfg.name == cfg.name
    dcfg = dataclasses.replace(dcfg, w_bits=spec.quant)
    dfull = dataclasses.replace(dfull, w_bits=spec.quant)
    return ResolvedSpec(spec, dcfg, dfull, twin=twin)


# --------------------------------------------------------------------------
# Drafters
# --------------------------------------------------------------------------


class NgramDrafter:
    """Self-drafting suffix-match lookup over the request's own history.

    Proposes the continuation that followed the most recent earlier
    occurrence of the current suffix (longest suffix first, down to one
    token; last-token repetition when nothing matches). Deterministic and
    model-free — zero charge on the virtual clock — so any accepted token
    is pure profit. Works for every SPEC_FAMILIES target, including moe.
    """

    is_model = False
    max_suffix = 8
    window = 512

    def start_lane(self, slot: int, prompt: np.ndarray) -> tuple[int, int]:
        return 0, 0

    def release_lane(self, slot: int) -> None:
        pass

    def accept(self, slot: int, n_rows: int) -> None:
        pass

    def _continuation(self, ctx: np.ndarray, n: int) -> np.ndarray:
        out = np.full((n,), int(ctx[-1]), np.int32)  # repeat-last fallback
        ln = len(ctx)
        for m in range(min(self.max_suffix, ln - 1), 0, -1):
            suffix = ctx[ln - m:]
            # most recent earlier occurrence of the suffix
            for s in range(ln - m - 1, -1, -1):
                if np.array_equal(ctx[s : s + m], suffix):
                    cont = ctx[s + m : s + m + n]
                    out[: len(cont)] = cont
                    if len(cont) < n and len(cont) > 0:
                        out[len(cont):] = int(cont[-1])
                    return out
        return out

    def propose(
        self, lanes: list[LaneDraft], k: int, sampling: lm.SamplingParams
    ) -> tuple[np.ndarray, int]:
        props = np.zeros((len(lanes), k - 1), np.int32)
        for j, ln in enumerate(lanes):
            ctx = ln.history[-self.window:]
            props[j] = self._continuation(np.asarray(ctx, np.int32), k - 1)
        return props, 0


class ModelDrafter:
    """A packed-twin (or foreign-arch) model drafter with private KV.

    The drafter runs the standard paged decode step over its own
    fixed-geometry pool: lane ``i`` owns the contiguous blocks
    ``[1 + i*nb, 1 + (i+1)*nb)`` (block 0 is scratch), so its block table
    is static and rollback is just clamping the lane length — the rollout
    feeds exactly the tokens the verifier feeds, so rows under the
    accepted prefix are already correct and rows past it are overwritten
    by the next chain.
    """

    is_model = True

    def __init__(
        self, cfg: ModelConfig, params, *, slots: int, max_len: int
    ):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.s = max_len
        t = DRAFT_BLOCK_TOKENS
        self._nb = -(-max_len // t)
        shape = (cfg.n_kv_cache_layers, 1 + slots * self._nb) + pool_tile(
            cfg.n_kv, t, cfg.hd
        )
        dt = jnp.dtype(cfg.dtype)
        self.k = jnp.zeros(shape, dt)
        self.v = jnp.zeros(shape, dt)
        table = 1 + np.arange(slots * self._nb).reshape(slots, self._nb)
        self._block_table = table.astype(np.int32)
        self._block_table_dev = jnp.asarray(self._block_table)
        self.lengths = np.zeros((slots,), np.int32)
        self._decode = _jitted_draft_decode(cfg)
        self._prefill = _jitted_draft_prefill(cfg)

    @property
    def cache_bytes(self) -> int:
        return self.k.nbytes + self.v.nbytes

    def start_lane(self, slot: int, prompt: np.ndarray) -> tuple[int, int]:
        """Prefill the drafter's own KV for the prompt (one padded step;
        the target's prefix-cache hits don't transfer — the drafter's
        rows are its own model's). Returns (tokens, steps) to charge."""
        p = len(prompt)
        padded = np.zeros((1, self.s), np.int32)
        padded[0, :p] = prompt
        _, ks, vs = self._prefill(self.params, jnp.asarray(padded), p - 1)
        # the padded tail lands past the prompt in the lane's own blocks
        pad = ((0, 0), (0, self._nb * DRAFT_BLOCK_TOKENS - self.s), (0, 0),
               (0, 0))
        ids = jnp.asarray(self._block_table[slot])
        self.k = write_blocks(self.k, ids, jnp.pad(ks[:, 0], pad))
        self.v = write_blocks(self.v, ids, jnp.pad(vs[:, 0], pad))
        self.lengths[slot] = p
        return p, 1

    def release_lane(self, slot: int) -> None:
        self.lengths[slot] = 0

    def accept(self, slot: int, n_rows: int) -> None:
        """Settle a verified chain: the accepted prefix's rows were fed
        identically here and in the target, so rollback = length clamp."""
        self.lengths[slot] = n_rows

    def propose(
        self, lanes: list[LaneDraft], k: int, sampling: lm.SamplingParams
    ) -> tuple[np.ndarray, int]:
        """Roll the drafter ``k`` steps: feed each lane's pending token
        then its own proposals, sampling with the verifier's own
        (seed, rid, position) rng keys so greedy *and* seeded chains
        match whenever the logits agree. The k-th step emits no proposal
        — it writes the KV row of the last proposal, so a fully-accepted
        chain leaves the drafter cache complete."""
        token = np.zeros((self.slots, 1), np.int32)
        lengths = self.lengths.copy()
        for ln in lanes:
            if lengths[ln.slot] != ln.n_rows:
                raise RuntimeError(
                    f"drafter lane {ln.slot} holds {lengths[ln.slot]} rows; "
                    f"target holds {ln.n_rows} — mirror out of sync"
                )
            token[ln.slot, 0] = ln.pending
        props = np.zeros((len(lanes), k - 1), np.int32)
        steps = 0
        for step in range(k):
            logits, self.k, self.v = self._decode(
                self.params,
                jnp.asarray(token),
                self.k,
                self.v,
                self._block_table_dev,
                jnp.asarray(lengths),
            )
            steps += 1
            rows = np.asarray(logits[:, 0, :])
            for j, ln in enumerate(lanes):
                lengths[ln.slot] += 1
                if step < k - 1:
                    d = _sample_keyed(
                        rows[ln.slot], sampling, ln.rid, ln.out_len + step
                    )
                    props[j, step] = d
                    token[ln.slot, 0] = d
        return props, steps


class Speculator:
    """The scheduler-facing bundle: one drafter + the draft depth."""

    def __init__(self, drafter, *, depth: int):
        self.drafter = drafter
        self.depth = depth

    @property
    def is_model(self) -> bool:
        return self.drafter.is_model

    @property
    def name(self) -> str:
        if self.is_model:
            return f"{self.drafter.cfg.name}@w{self.drafter.cfg.w_bits}"
        return NGRAM

    def start_lane(self, slot: int, prompt: np.ndarray) -> tuple[int, int]:
        return self.drafter.start_lane(slot, prompt)

    def release_lane(self, slot: int) -> None:
        self.drafter.release_lane(slot)

    def accept(self, slot: int, n_rows: int) -> None:
        self.drafter.accept(slot, n_rows)

    def propose(self, lanes, k, sampling) -> tuple[np.ndarray, int]:
        return self.drafter.propose(lanes, k, sampling)


def build_speculator(
    cfg: ModelConfig,
    params,
    spec: SpecConfig,
    *,
    slots: int,
    max_len: int,
    smoke: bool = False,
) -> Speculator:
    """One-shot resolve + build for single-engine callers (serve.py)."""
    return resolve(cfg, spec, smoke=smoke).build(
        cfg, params, slots=slots, max_len=max_len
    )
