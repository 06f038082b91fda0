"""Loop-aware cost analysis of post-optimization HLO text.

XLA's built-in ``HloCostAnalysis`` (what ``compiled.cost_analysis()``
reports) visits every instruction ONCE — a ``lax.scan`` over 32 layers
contributes its body a single time, so FLOPs/bytes/collectives are under-
counted by the trip count (13x on smollm train_4k). This module re-derives
the counts from ``compiled.as_text()`` with multipliers propagated through
the call graph:

  * ``while`` bodies/conditions x known_trip_count (XLA stamps
    ``backend_config={"known_trip_count":{"n":...}}`` on counted loops),
  * ``fusion`` / ``call`` / ``conditional`` / ``to_apply`` edges x 1,
  * a computation reachable from several sites accumulates the sum.

Counted metrics (all per-device — the module is the SPMD partition):
  * ``dot_flops``: 2 * prod(result dims) * prod(lhs contracting dims) for
    every dot; this is the MXU-relevant compute term.
  * ``traffic_bytes``: operand + result bytes of every materialising
    instruction outside fusion bodies (the HloCostAnalysis convention),
    i.e. an HBM-traffic proxy.
  * ``collective_bytes``: operand bytes of all-reduce / all-gather /
    reduce-scatter / all-to-all / collective-permute, by kind. These are
    the bytes *entering* the collective on one device (ring all-reduce
    moves ~2x this on the wire; the roofline term uses the operand-bytes
    convention from the assignment).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3b11fnuz": 1, "token": 0,
    "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def xla_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` normalised across jax versions.

    Older jax returns a list with one properties-dict per partition; newer
    jax returns the dict directly. Callers always get a plain dict (empty
    when XLA reports nothing).
    """
    props = compiled.cost_analysis()
    if isinstance(props, (list, tuple)):
        props = props[0] if props else {}
    return dict(props)


def shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO shape string (tuples summed, layouts ignored)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(shape_str: str) -> list[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclasses.dataclass
class Instruction:
    name: str
    shape: str  # result shape string
    opcode: str
    operands: list[str]
    attrs: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    instructions: list[Instruction]
    params: dict[str, str]  # param name -> shape string


_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\((.*)\)\s*->\s*(.+)\s*\{\s*$")
_INSTR_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*")


def _parse_instruction(line: str) -> Instruction | None:
    """Parse '  %name = <shape> opcode(<operands>), attrs' with balanced
    parens (operand lists contain nested parens; attrs follow the match)."""
    m = _INSTR_HEAD.match(line)
    if not m:
        return None
    name = m.group(1)
    rest = line[m.end():]
    # result shape: a tuple '(...)' or a run of shape tokens up to the
    # opcode word that precedes the operand '('.
    if rest.startswith("("):
        depth, i = 0, 0
        while i < len(rest):
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
                if depth == 0:
                    i += 1
                    break
            i += 1
        shape, rest = rest[:i], rest[i:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        shape, rest = rest[:sp], rest[sp + 1:].lstrip()
    op_m = re.match(r"([\w\-]+)\(", rest)
    if not op_m:
        return None
    opcode = op_m.group(1)
    i, depth = op_m.end() - 1, 0
    start = i + 1
    while i < len(rest):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                break
        i += 1
    operands = rest[start:i]
    attrs = rest[i + 1:]
    return Instruction(
        name, shape.strip(), opcode, _split_top_level(operands), attrs, line
    )
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_ATTR = re.compile(
    r"(?:calls|to_apply|condition|body)=%?([\w\.\-]+)"
)
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND_NAME = re.compile(r"%([\w\.\-]+)")


def _split_top_level(s: str) -> list[str]:
    """Split an operand list on top-level commas."""
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HEADER.match(line.strip())
            if m:
                params = {}
                # "a: f32[2], b: (f32[2], s32[])" — split top-level commas
                for p in _split_top_level(m.group(3)):
                    if ":" in p:
                        pname, pshape = p.split(":", 1)
                        params[pname.strip().lstrip("%")] = pshape.strip()
                cur = Computation(m.group(2), [], params)
            continue
        if line.strip() == "}" or line.strip().startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        ins = _parse_instruction(line)
        if ins is not None:
            cur.instructions.append(ins)
    return comps


def _call_edges(comp: Computation) -> list[tuple[str, float, str]]:
    """(callee, multiplier, kind) edges out of one computation."""
    edges = []
    for ins in comp.instructions:
        trip = 1.0
        if ins.opcode == "while":
            m = _TRIP.search(ins.attrs)
            trip = float(m.group(1)) if m else 1.0
        for cm in _CALL_ATTR.finditer(ins.attrs):
            kind = "fusion" if ins.opcode == "fusion" else ins.opcode
            edges.append((cm.group(1), trip, kind))
        bm = _BRANCHES.search(ins.attrs)
        if bm:
            for b in bm.group(1).split(","):
                edges.append((b.strip().lstrip("%"), 1.0, "conditional"))
    return edges


def computation_multipliers(
    comps: dict[str, Computation],
) -> tuple[dict[str, float], dict[str, str]]:
    """Execution-count multiplier for every computation + its call kind."""
    entry = None
    for name, c in comps.items():
        if name.startswith("main") or entry is None:
            pass
    # the ENTRY computation is the one never called by anyone
    called = set()
    edges_by_comp = {n: _call_edges(c) for n, c in comps.items()}
    for edges in edges_by_comp.values():
        for callee, _, _ in edges:
            called.add(callee)
    roots = [n for n in comps if n not in called]
    mult: dict[str, float] = defaultdict(float)
    kind: dict[str, str] = {}
    for r in roots:
        mult[r] = 1.0
        kind[r] = "entry"
    # propagate in topological order (HLO call graphs are acyclic);
    # iterate to fixpoint (small graphs, few dozen computations)
    for _ in range(len(comps) + 2):
        changed = False
        new = defaultdict(float)
        for r in roots:
            new[r] = 1.0
        for name, edges in edges_by_comp.items():
            m = mult.get(name, 0.0)
            if m == 0.0:
                continue
            for callee, trip, k in edges:
                new[callee] += m * trip
                kind.setdefault(callee, k)
        for n, v in new.items():
            if abs(mult.get(n, 0.0) - v) > 1e-9:
                changed = True
        mult = new
        if not changed:
            break
    return dict(mult), kind


_SKIP_TRAFFIC = {
    "tuple", "get-tuple-element", "parameter", "bitcast", "constant",
    "after-all", "partition-id", "replica-id", "iota",
}


def _fusion_dus_bytes(comps: dict, ins: "Instruction"):
    """In-place dynamic-update-slice fusions: traffic is the update slice
    (read + written region + inputs), not the whole buffer.

    Matches fusions whose computation contains a DUS acting on a
    buffer-sized operand, with the fusion result the same (buffer) shape —
    XLA updates these in place inside while loops (possibly with trailing
    converts/bitcasts fused after the DUS). Returns bytes or None.
    """
    cm = re.search(r"calls=%?([\w\.\-]+)", ins.attrs)
    if not cm or cm.group(1) not in comps:
        return None
    callee = comps[cm.group(1)]
    if not callee.instructions:
        return None
    fusion_dims = _shape_dims(ins.shape)
    defs = {i.name: i.shape for i in callee.instructions}

    def shape_of(operand):
        if "[" in operand and "%" in operand:
            return operand
        mm = _OPERAND_NAME.search(operand)
        if mm:
            nm = mm.group(1)
            return defs.get(nm, callee.params.get(nm, ""))
        return ""

    for inner in callee.instructions:
        if inner.opcode != "dynamic-update-slice" or len(inner.operands) < 2:
            continue
        if _shape_dims(inner.shape) != fusion_dims:
            continue  # the DUS doesn't produce the fusion-sized buffer
        return 3 * shape_bytes(shape_of(inner.operands[1]))
    return None


@dataclasses.dataclass
class HloCost:
    dot_flops: float
    traffic_bytes: float
    collective_bytes: dict[str, float]
    transcendentals: float
    n_unknown_trip: int

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


def analyze(text: str) -> HloCost:
    comps = parse_module(text)
    mult, kind = computation_multipliers(comps)

    dot_flops = 0.0
    traffic = 0.0
    transcendental = 0.0
    coll: dict[str, float] = defaultdict(float)
    unknown_trip = 0

    def op_shape(comp: Computation, defs: dict[str, str], operand: str) -> str:
        # operand may carry an inline shape ("f32[8,16] %x.3") or be a bare
        # reference; fall back to defs / params.
        if "[" in operand and "%" in operand:
            return operand
        m = _OPERAND_NAME.search(operand)
        if m:
            nm = m.group(1)
            if nm in defs:
                return defs[nm]
            if nm in comp.params:
                return comp.params[nm]
        return ""

    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        in_fusion = kind.get(cname, "") in ("fusion",)
        is_applied = kind.get(cname, "") in (
            "reduce", "all-reduce", "reduce-scatter", "scatter", "sort",
            "reduce-window", "select-and-scatter", "map",
        )
        defs = {i.name: i.shape for i in comp.instructions}
        for ins in comp.instructions:
            if ins.opcode == "while" and not _TRIP.search(ins.attrs):
                unknown_trip += 1
            # ---- dot flops (count inside fusions too) ----
            if ins.opcode == "dot" and not is_applied:
                res = 1
                for d in _shape_dims(ins.shape):
                    res *= d
                lhs_shape = op_shape(comp, defs, ins.operands[0]) if ins.operands else ""
                lhs_dims = _shape_dims(lhs_shape)
                cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
                contract = 1
                if cm and lhs_dims:
                    for idx in cm.group(1).split(","):
                        if idx:
                            contract *= lhs_dims[int(idx)]
                dot_flops += m * 2.0 * res * contract
            if ins.opcode in ("exponential", "log", "tanh", "rsqrt", "sqrt",
                              "power", "logistic"):
                res = 1
                for d in _shape_dims(ins.shape):
                    res *= d
                transcendental += m * res
            # ---- collectives ----
            base = None
            for c in COLLECTIVE_OPS:
                if ins.opcode in (c, f"{c}-start"):
                    base = c
                    break
            if base is not None:
                b = sum(
                    shape_bytes(op_shape(comp, defs, o)) for o in ins.operands
                )
                coll[base] += m * b
            # ---- traffic ----
            if in_fusion or is_applied:
                continue
            if ins.opcode in _SKIP_TRAFFIC or base is not None:
                continue
            if ins.opcode == "dynamic-update-slice":
                # XLA updates loop-carried buffers in place: traffic is the
                # update slice (read) + the written region, NOT the whole
                # buffer (HloCostAnalysis makes the same special case).
                upd = (
                    shape_bytes(op_shape(comp, defs, ins.operands[1]))
                    if len(ins.operands) > 1
                    else 0
                )
                traffic += m * 2 * upd
                continue
            if ins.opcode == "dynamic-slice":
                traffic += m * 2 * shape_bytes(ins.shape)
                continue
            if ins.opcode == "fusion":
                dus = _fusion_dus_bytes(comps, ins)
                if dus is not None:
                    traffic += m * dus
                    continue
            b = shape_bytes(ins.shape)
            for o in ins.operands:
                b += shape_bytes(op_shape(comp, defs, o))
            traffic += m * b
    return HloCost(
        dot_flops=dot_flops,
        traffic_bytes=traffic,
        collective_bytes=dict(coll),
        transcendentals=transcendental,
        n_unknown_trip=unknown_trip,
    )


def top_contributors(text: str, metric: str = "traffic", n: int = 20):
    """Debug/profiling: the n largest per-instruction contributors.

    metric: 'traffic' (operand+result bytes x multiplier), 'dot_flops',
    or 'collective'. Returns [(value, comp_name, instr_name, opcode,
    shape, op_name_metadata)].
    """
    comps = parse_module(text)
    mult, kind = computation_multipliers(comps)
    rows = []
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        in_fusion = kind.get(cname, "") in ("fusion",)
        is_applied = kind.get(cname, "") in (
            "reduce", "all-reduce", "reduce-scatter", "scatter", "sort",
            "reduce-window", "select-and-scatter", "map",
        )
        defs = {i.name: i.shape for i in comp.instructions}

        def shape_of(operand):
            if "[" in operand and "%" in operand:
                return operand
            mm = _OPERAND_NAME.search(operand)
            if mm:
                nm = mm.group(1)
                return defs.get(nm, comp.params.get(nm, ""))
            return ""

        for ins in comp.instructions:
            meta = ""
            mm = re.search(r'op_name="([^"]*)"', ins.attrs)
            if mm:
                meta = mm.group(1)
            if metric == "dot_flops":
                if ins.opcode != "dot" or is_applied:
                    continue
                res = 1
                for d in _shape_dims(ins.shape):
                    res *= d
                ld = _shape_dims(shape_of(ins.operands[0]) if ins.operands else "")
                cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
                contract = 1
                if cm and ld:
                    for idx in cm.group(1).split(","):
                        if idx:
                            contract *= ld[int(idx)]
                val = m * 2.0 * res * contract
            elif metric == "collective":
                if not any(
                    ins.opcode in (c, f"{c}-start") for c in COLLECTIVE_OPS
                ):
                    continue
                val = m * sum(shape_bytes(shape_of(o)) for o in ins.operands)
            else:  # traffic
                if in_fusion or is_applied or ins.opcode in _SKIP_TRAFFIC:
                    continue
                if any(ins.opcode in (c, f"{c}-start") for c in COLLECTIVE_OPS):
                    continue
                if ins.opcode == "dynamic-update-slice":
                    val = m * 2 * (
                        shape_bytes(shape_of(ins.operands[1]))
                        if len(ins.operands) > 1 else 0
                    )
                elif ins.opcode == "dynamic-slice":
                    val = m * 2 * shape_bytes(ins.shape)
                elif (
                    ins.opcode == "fusion"
                    and _fusion_dus_bytes(comps, ins) is not None
                ):
                    val = m * _fusion_dus_bytes(comps, ins)
                else:
                    b = shape_bytes(ins.shape)
                    for o in ins.operands:
                        b += shape_bytes(shape_of(o))
                    val = m * b
            rows.append((val, cname[:36], ins.name, ins.opcode, ins.shape[:44], meta[:70]))
    rows.sort(reverse=True)
    return rows[:n]


_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope_table(text: str, scopes) -> dict[str, str]:
    """{instruction name: scope path} of the instructions whose
    ``op_name`` metadata passes through any of ``scopes`` (names given
    with ``jax.named_scope``); the path keeps those names alone,
    outermost first (``"attention/kv_gather"``). A fusion carries the
    metadata of its root, so the table names what a device profile's
    operations (one per instruction run) were computing. Line by line,
    so it reads the compiled module's text and a lowered module printed
    with ``debug_info`` alike."""
    wanted = set(scopes)
    out: dict[str, str] = {}
    for line in text.splitlines():
        head = _INSTR_HEAD.match(line)
        meta = head and _OP_NAME.search(line, head.end())
        if not meta:
            continue
        path = [p for p in meta.group(1).split("/") if p in wanted]
        if path:
            out[head.group(1)] = "/".join(path)
    return out
