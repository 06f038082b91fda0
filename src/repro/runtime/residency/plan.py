"""Compile a weight-residency plan: which blocks live in VMEM, which stream.

The paper's §V porting result is that FCMP packing lets a fixed on-chip
memory hold more of the model, so the design ports to a smaller device
with less throughput loss than re-folding. The TPU analogue planned here:

  * the *streamable set* is the dense-FFN weight blocks — exactly the
    weight memories FCMP packs on FPGA (conv/FC MVAU buffers <-> FFN
    matmuls); attention projections, norms and the embedding are the
    "datapath" side and are accounted as fixed HBM traffic,
  * ``core.vmem_plan.pack_blocks`` runs the paper's bin-packing solvers
    over the blocks' int8 carriers so oddly-shaped blocks co-locate into
    shared (8, 128) VMEM tile bins (Eq. 1 one level down the hierarchy),
  * a greedy knapsack pins the highest-traffic-per-tile *regions* (one
    layer / one expert — the executor's stream granularity) until the
    VMEM budget is spent; everything else re-streams from HBM each
    decode step through ``kernels.weight_stream``,
  * the GALS ``R_F`` knob maps to the streamer's ring depth
    (``stream_ahead_depth``): bit-packing leaves an HBM bandwidth surplus
    (bf16 -> 1/2-bit moves 8-16x fewer bytes) and that surplus is what
    funds deep prefetch, the way the paper's memory-clock surplus funds
    bin heights > N_ports.

Traffic enters the plan the way it enters the paper's Eq. 2: a block's
pin value is the HBM bytes it would otherwise move *per decode step*
(MoE expert blocks are read with probability top_k/E, the hybrid shared
block once per super-block), so the same model packs differently under
different serving mixes.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp

from repro.core.gals import N_PORTS
from repro.core.packing import Packing, bin_cost
from repro.core.resource_model import TPU_V5E, TPU_TIERS, TpuChip
from repro.core.vmem_plan import WeightBlock, pack_blocks, vmem_tile_ram
from repro.models.config import ModelConfig

MAX_STREAM_DEPTH = 8


@dataclasses.dataclass(frozen=True)
class TrafficProfile:
    """What the serve tier is asked to do (the §V 'at what traffic?')."""

    lanes: int = 8  # concurrent decode lanes (batch)
    prompt_len: int = 512
    gen_len: int = 128

    @property
    def mean_context(self) -> int:
        """Average KV rows held per lane over a request's decode phase."""
        return self.prompt_len + self.gen_len // 2


def _dtype_bits(cfg: ModelConfig) -> int:
    return jnp.dtype(cfg.dtype).itemsize * 8


def _block_bits(cfg: ModelConfig) -> int:
    return cfg.w_bits if cfg.w_bits in (1, 2) else _dtype_bits(cfg)


def weight_blocks(cfg: ModelConfig) -> tuple[WeightBlock, ...]:
    """The streamable weight-block set of one model replica.

    One block per FFN matmul per layer, named ``L{l}.{mat}`` (MoE experts
    ``L{l}.e{e}.{mat}``, the hybrid shared block ``shared.{mat}``), with
    ``bits_per_weight`` the packed precision (or the dense dtype width).
    """
    bits = _block_bits(cfg)
    d, ff = cfg.d_model, cfg.d_ff
    mats = {"w1": (d, ff), "w3": (d, ff), "w2": (ff, d)}
    blocks: list[WeightBlock] = []
    if cfg.family in ("dense", "vlm", "encdec"):
        for l in range(cfg.n_layers):
            for mat, (r, c) in mats.items():
                blocks.append(WeightBlock(f"L{l:03d}.{mat}", r, c, bits))
    elif cfg.family == "moe":
        # expert einsums consume dense stacked weights (lm._init_ffn):
        # expert blocks carry the dense dtype width, not cfg.w_bits
        ebits = _dtype_bits(cfg)
        for l in range(cfg.n_layers):
            for e in range(cfg.n_experts):
                for mat, (r, c) in mats.items():
                    blocks.append(
                        WeightBlock(f"L{l:03d}.e{e}.{mat}", r, c, ebits)
                    )
    elif cfg.family == "hybrid":
        for mat, (r, c) in mats.items():
            blocks.append(WeightBlock(f"shared.{mat}", r, c, bits))
    else:  # ssm: no dense FFN to pack or stream
        pass
    return tuple(blocks)


def _region_of(name: str) -> str:
    """The executor granularity a block belongs to: its layer for dense
    FFN mats (``L000``), its expert for MoE (``L000.e3``), the shared
    block for hybrid. Bins never mix regions and the knapsack pins whole
    regions, so the plan's resident set is exactly what the executor can
    keep resident — pinning 2 of a layer's 3 mats would spend VMEM the
    layer-granular stream mask could not exploit."""
    return name.rsplit(".", 1)[0]


def read_weight(name: str, cfg: ModelConfig) -> float:
    """Expected reads of a block per decode step (the Eq. 2 traffic term)."""
    if cfg.family == "moe" and ".e" in name:
        return cfg.experts_per_token / max(1, cfg.n_experts)
    if cfg.family == "hybrid" and name.startswith("shared."):
        return cfg.n_layers / max(1, cfg.hybrid_attn_every)
    return 1.0


def fixed_hbm_bytes(cfg: ModelConfig, traffic: TrafficProfile) -> int:
    """Per-decode-step HBM bytes outside the plan: attention projections,
    the unembedding row product, and the lanes' KV-row reads."""
    d, hd = cfg.d_model, cfg.hd
    dt = jnp.dtype(cfg.dtype).itemsize
    attn = cfg.n_layers * (
        d * cfg.n_heads * hd + 2 * d * cfg.n_kv * hd + cfg.n_heads * hd * d
    )
    unembed = cfg.padded_vocab * d
    kv = (
        traffic.lanes
        * cfg.n_layers
        * 2
        * cfg.n_kv
        * hd
        * traffic.mean_context
    )
    return (attn + unembed + kv) * dt


def stream_ahead_depth(cfg: ModelConfig, max_height: int = 4) -> int:
    """GALS Eq. 2 mapped to the DMA ring: R_F is the HBM-bandwidth surplus
    of bit-packing (dense-dtype bits / packed bits), and the ring depth is
    the virtual ports that surplus funds per bin height,
    ``N_ports * R_F / H_B`` — clamped to [2, 8] (a ring needs 2 slots to
    overlap at all; deeper than 8 buys nothing at TPU DMA latency)."""
    bits = _block_bits(cfg)
    r_f = _dtype_bits(cfg) / bits
    depth = math.floor(N_PORTS * r_f / max_height)
    return max(2, min(MAX_STREAM_DEPTH, depth))


@dataclasses.dataclass(frozen=True)
class RuntimeResidencyPlan:
    """A compiled residency schedule, hashable so jitted steps key on it."""

    model: str
    chip: str
    blocks: tuple[WeightBlock, ...]
    bins: tuple[tuple[int, ...], ...]  # tile-bin membership (block indices)
    bin_tiles: tuple[int, ...]  # physical VMEM tiles per bin
    resident: tuple[bool, ...]  # per *bin*
    vmem_budget_bytes: int
    stream_ahead: int
    read_weights: tuple[float, ...]  # per block

    # ---------------- derived ----------------

    def _tile_bytes(self, chip: TpuChip) -> int:
        return chip.sublane * chip.lane

    @property
    def _chip(self) -> TpuChip:
        tier = self.chip.removeprefix("tpu_")
        if tier not in TPU_TIERS:
            raise ValueError(
                f"unknown chip {self.chip!r}; known: {sorted(TPU_TIERS)}"
            )
        return TPU_TIERS[tier]

    @property
    def resident_bytes(self) -> int:
        tb = self._tile_bytes(self._chip)
        return sum(
            t * tb for t, r in zip(self.bin_tiles, self.resident) if r
        )

    def block_resident(self) -> dict[str, bool]:
        out = {}
        for b, r in zip(self.bins, self.resident):
            for i in b:
                out[self.blocks[i].name] = r
        return out

    @property
    def resident_block_count(self) -> int:
        return sum(
            len(b) for b, r in zip(self.bins, self.resident) if r
        )

    @property
    def resident_fraction(self) -> float:
        return self.resident_block_count / max(1, len(self.blocks))

    @property
    def streamable_bytes_per_step(self) -> float:
        """Expected HBM bytes per decode step of the *whole* streamable
        set (every FFN weight block, resident or not) — the baseline the
        budgeted roofline subtracts pinned blocks from."""
        return sum(
            w * b.padded_bytes(self._chip)
            for b, w in zip(self.blocks, self.read_weights)
        )

    @property
    def streamed_bytes_per_step(self) -> float:
        """Expected HBM bytes re-read per decode step for cold blocks."""
        res = self.block_resident()
        return sum(
            w * b.padded_bytes(self._chip)
            for b, w in zip(self.blocks, self.read_weights)
            if not res[b.name]
        )

    @property
    def hbm_traffic_reduction(self) -> float:
        return 1.0 - self.streamed_bytes_per_step / max(
            1.0, self.streamable_bytes_per_step
        )

    @property
    def ring_bytes(self) -> int:
        """VMEM held by the prefetch ring: ``stream_ahead`` slots, each
        sized for the largest streamed block (the ring is a fixed-shape
        double-plus buffer, so every slot pays the worst case). The
        memory ledger reports this as the ``ring-slot`` owner."""
        res = self.block_resident()
        slot = max(
            (
                b.padded_bytes(self._chip)
                for b in self.blocks
                if not res[b.name]
            ),
            default=0,
        )
        return int(self.stream_ahead * slot)

    def layer_stream_mask(self, cfg: ModelConfig) -> tuple[bool, ...]:
        """Per-layer 'FFN is streamed' flags for the executor: a layer
        only runs resident if *all* of its FFN mats are pinned (the
        region-granular knapsack guarantees all-or-nothing per layer, so
        no pinned byte is stranded in a streamed layer)."""
        res = self.block_resident()
        mask = []
        for l in range(cfg.n_layers):
            prefix = f"L{l:03d}."
            mine = [r for n, r in res.items() if n.startswith(prefix)]
            mask.append(not (mine and all(mine)))
        return tuple(mask)

    def expert_stream_mask(
        self, cfg: ModelConfig
    ) -> tuple[tuple[bool, ...], ...]:
        """Per-(layer, expert) 'FFN is streamed' flags for the moe
        executor: an expert runs resident only if *all three* of its mats
        are pinned (the knapsack pins whole ``L{l}.e{e}`` regions, so this
        is all-or-nothing per expert — the expert-granular analogue of
        ``layer_stream_mask``). Shape (n_layers, n_experts), scanned with
        the stacked layer leaves so each layer sees its (E,) row."""
        res = self.block_resident()
        mask = []
        for l in range(cfg.n_layers):
            row = []
            for e in range(cfg.n_experts):
                prefix = f"L{l:03d}.e{e}."
                mine = [r for n, r in res.items() if n.startswith(prefix)]
                row.append(not (mine and all(mine)))
            mask.append(tuple(row))
        return tuple(mask)

    def summary(self) -> dict:
        return {
            "model": self.model,
            "chip": self.chip,
            "n_blocks": len(self.blocks),
            "n_bins": len(self.bins),
            "vmem_budget_mib": round(self.vmem_budget_bytes / 2**20, 3),
            "resident_blocks": self.resident_block_count,
            "resident_fraction": round(self.resident_fraction, 4),
            "resident_mib": round(self.resident_bytes / 2**20, 3),
            "streamed_mib_per_step": round(
                self.streamed_bytes_per_step / 2**20, 3
            ),
            "hbm_traffic_reduction": round(self.hbm_traffic_reduction, 4),
            "stream_ahead": self.stream_ahead,
        }


def compile_residency_plan(
    cfg: ModelConfig,
    *,
    vmem_budget_bytes: int,
    traffic: TrafficProfile = TrafficProfile(),
    chip: TpuChip = TPU_V5E,
    solver: str = "ffd",
    max_height: int = 4,
) -> RuntimeResidencyPlan:
    """Plan = pack carriers into tile bins, then knapsack *regions* into
    VMEM.

    Bins are region-constrained (one layer / one MoE expert / the hybrid
    shared block — ``_region_of``) and the knapsack pins whole regions,
    ranked by traffic value density: expected HBM bytes avoided per step
    per VMEM byte pinned. Under a tight budget the plan keeps the regions
    the traffic profile actually re-reads (every step for dense layers,
    top_k/E of steps for MoE experts), and every pinned byte is one the
    layer-granular executor can exploit.
    """
    blocks = weight_blocks(cfg)
    weights = tuple(read_weight(b.name, cfg) for b in blocks)
    regions = tuple(_region_of(b.name) for b in blocks)
    packing: Packing = pack_blocks(
        blocks, chip=chip, max_height=max_height, solver=solver,
        regions=regions,
    )
    ram = vmem_tile_ram(chip)
    tile_bytes = chip.sublane * chip.lane
    bins = tuple(tuple(b) for b in packing.bins)
    bin_tiles = tuple(
        bin_cost([packing.items[i] for i in b], ram)[0] for b in bins
    )
    groups: dict[str, list[int]] = {}
    for j, b in enumerate(bins):
        groups.setdefault(regions[b[0]], []).append(j)

    def group_cost(js: list[int]) -> int:
        return sum(bin_tiles[j] for j in js) * tile_bytes

    def density(js: list[int]) -> float:
        avoided = sum(
            weights[i] * blocks[i].padded_bytes(chip)
            for j in js
            for i in bins[j]
        )
        return avoided / max(1, group_cost(js))

    order = sorted(groups.values(), key=density, reverse=True)
    resident = [False] * len(bins)
    used = 0
    for js in order:
        cost = group_cost(js)
        if used + cost <= vmem_budget_bytes:
            for j in js:
                resident[j] = True
            used += cost
    return RuntimeResidencyPlan(
        model=cfg.name,
        chip=chip.name,
        blocks=blocks,
        bins=bins,
        bin_tiles=bin_tiles,
        resident=tuple(resident),
        vmem_budget_bytes=vmem_budget_bytes,
        stream_ahead=stream_ahead_depth(cfg, max_height),
        read_weights=weights,
    )
