"""Compiled-HLO analysis: collective-byte accounting for the roofline.

``cost_analysis()`` gives per-chip FLOPs and HBM bytes but NOT collective
traffic; we parse the (post-SPMD, per-chip) HLO text and sum the wire bytes
of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, using ring-algorithm wire-byte conventions:

    all-reduce      result_bytes × 2(g−1)/g     (reduce-scatter + all-gather)
    all-gather      result_bytes × (g−1)/g
    reduce-scatter  result_bytes × (g−1)         (input = result × g)
    all-to-all      result_bytes × (g−1)/g
    collective-permute  result_bytes

where g is the replica-group size parsed from ``replica_groups``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g. ``%all-reduce.1 = f32[64,256]{1,0} all-reduce(%dot.1), ...``
#      ``... = (f32[8]{0}, f32[8]{0}) all-reduce(...)`` (tuple results)
# The shapes group must also admit layout/annotation-bearing types emitted
# by newer XLA — tiled layouts ``{1,0:T(8,128)}``, memory-space suffixes
# ``S(1)``, and sharding annotations such as ``maximal device=0`` — which
# contain ``:``, ``(``, ``)``, ``=`` and uppercase letters.  The op-name
# alternation anchors the match, so the broader class cannot overrun it.
_OP_RE = re.compile(
    r"=\s*(?P<shapes>\(?[a-zA-Z0-9\[\],{}():=\s]*\)?)\s*"
    r"(?P<op>all-reduce-start|all-gather-start|collective-permute-start|"
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shapes_text: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(shapes_text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return max(1, len(ids))
    return default


@dataclasses.dataclass
class CollectiveStats:
    #: wire bytes per chip, per collective kind
    wire_bytes: Dict[str, float]
    #: op invocation counts per kind
    counts: Dict[str, int]
    #: raw result-shape bytes per kind (pre wire-convention)
    result_bytes: Dict[str, float]

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def parse_collectives(hlo_text: str, n_devices: int = 1) -> CollectiveStats:
    wire: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    raw: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    # while-loop bodies appear once in the module; trip counts are already
    # reflected in cost_analysis but NOT in text — scan for known trip-count
    # markers so scanned layers are multiplied (see loop_trip_counts).
    trips = loop_trip_counts(hlo_text)
    current_comp = ""
    for line in hlo_text.splitlines():
        comp_m = re.match(r"\s*%?(\S+)\s*\(.*\)\s*->", line) or \
                 re.match(r"\s*ENTRY\s+%?(\S+)", line)
        if comp_m:
            current_comp = comp_m.group(1)
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group("op").replace("-start", "")
        nbytes = _shape_bytes(m.group("shapes"))
        g = _group_size(line, n_devices)
        if g <= 1:
            continue
        if op == "all-reduce":
            w = nbytes * 2.0 * (g - 1) / g
        elif op == "all-gather":
            w = nbytes * (g - 1) / g
        elif op == "reduce-scatter":
            w = nbytes * (g - 1)
        elif op == "all-to-all":
            w = nbytes * (g - 1) / g
        else:  # collective-permute
            w = nbytes
        mult = trips.get(current_comp, 1)
        wire[op] += w * mult
        counts[op] += mult
        raw[op] += nbytes * mult
    return CollectiveStats(wire_bytes=wire, counts=counts, result_bytes=raw)


def loop_trip_counts(hlo_text: str) -> Dict[str, int]:
    """Map while-body computation name -> trip count (scan-over-layers).

    XLA annotates compiled while loops with known trip counts via backend
    config or induction-variable comparisons; we use the conservative
    pattern of `trip_count=N` markers when present.
    """
    trips: Dict[str, int] = {}
    for m in re.finditer(
            r"body=%?(\S+?),.*?\"known_trip_count\":\{\"n\":\"?(\d+)",
            hlo_text):
        trips[m.group(1)] = int(m.group(2))
    return trips


def count_ops(hlo_text: str, names: List[str]) -> Dict[str, int]:
    """Occurrences of given HLO op names (e.g. to spot remat recompute)."""
    out = {}
    for n in names:
        out[n] = len(re.findall(rf"\b{re.escape(n)}\(", hlo_text))
    return out


# ===========================================================================
# Full-module cost analyzer with while-loop trip folding
# ===========================================================================
#
# ``compiled.cost_analysis()`` counts each while-loop body ONCE — a
# scan-over-layers model reports 1/L of its FLOPs.  This analyzer parses the
# compiled HLO module, builds the computation call graph, multiplies every
# computation's cost by its execution multiplicity (product of enclosing
# known trip counts), and accumulates:
#   * flops  — dot ops: 2·numel(result)·prod(contracting dims); elementwise
#              and fusion outputs at 1 flop/element (dot-dominated workloads
#              make this exact to within a few percent — validated in tests
#              against cost_analysis on loop-free modules)
#   * bytes  — post-fusion boundary traffic: operands + results of
#              memory-touching ops in executed computations (fusion bodies
#              excluded: internal values live in registers/VMEM)
#   * wire   — collective wire bytes (same conventions as parse_collectives)

#: layout/annotation suffixes inside brace groups — tiled layouts
#: ``{1,0:T(8,128)}`` and memory-space tags ``{1,0:T(8,128)S(1)}`` from
#: newer XLA.  The embedded ``T(`` / ``S(`` would otherwise satisfy
#: ``_INSTR_RE``'s op-name-followed-by-paren group and shadow the real
#: opcode, silently dropping the instruction (collectives included) from
#: the analysis — normalize to the bare dims ``{1,0}`` before parsing.
_LAYOUT_ANNOT_RE = re.compile(r"\{([\d,\s]*):[^{}]*\}")

_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.*\{")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*?)\s*"
    r"([a-zA-Z][a-zA-Z0-9\-]*)\((.*)$")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count"\s*:\s*\{\s*"n"\s*:\s*"?(\d+)')
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_APPLY_RE = re.compile(r"to_apply=%?([\w\.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

_ELEMENTWISE_FLOP_OPS = {
    "add", "multiply", "subtract", "divide", "maximum", "minimum",
    "exponential", "tanh", "logistic", "rsqrt", "sqrt", "power", "negate",
    "compare", "select", "convert", "and", "or", "xor", "log", "floor",
    "clamp", "abs", "sign", "cosine", "sine", "reduce", "fusion",
}
_BYTE_OPS = _ELEMENTWISE_FLOP_OPS | {
    "dot", "copy", "broadcast", "iota", "transpose", "reshape", "concatenate",
    "slice", "pad", "reverse", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all", "collective-permute", "all-reduce-start", "all-gather-start",
    "reduce-window", "sort", "rng", "rng-bit-generator", "cholesky", "map",
    "convolution",
}
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "after-all", "partition-id", "replica-id", "opt-barrier",
             "all-reduce-done", "all-gather-done", "copy-done", "copy-start"}


def _shape_list(type_text: str):
    """[(bytes_per_el, numel), ...] for a (possibly tuple) HLO type."""
    out = []
    for dt, dims in _SHAPE_RE.findall(type_text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        out.append((_DTYPE_BYTES[dt], n))
    return out


def _type_bytes(type_text: str) -> float:
    return sum(b * n for b, n in _shape_list(type_text))


def _operand_bytes(type_text: str) -> float:
    """Bytes charged for one operand *use*.

    When the referenced instruction produces a tuple (e.g. a fused tuple
    all-reduce), a consumer touches one element, not the whole tuple —
    charge the largest element as the per-use upper bound."""
    shapes = _shape_list(type_text)
    if len(shapes) > 1 and type_text.lstrip().startswith("("):
        return max(b * n for b, n in shapes)
    return sum(b * n for b, n in shapes)


def _type_numel(type_text: str) -> float:
    return sum(n for _, n in _shape_list(type_text))


def _dims_of(type_text: str):
    m = _SHAPE_RE.search(type_text)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d.strip()]


@dataclasses.dataclass
class ModuleCost:
    flops: float
    bytes: float
    wire_bytes: float
    collective_wire: Dict[str, float]
    collective_counts: Dict[str, int]
    unknown_trip_loops: int          # loops lacking known_trip_count
    #: trip-folded FLOPs per HLO op family (``dot``, ``add``, ``fusion``…) —
    #: what the static auditor reconciles op-class-by-op-class against the
    #: analytical records (dot ↔ gemm/bmm being the load-bearing pair)
    flops_by_op: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: trip-folded boundary bytes per HLO op family
    bytes_by_op: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: ring-convention wire ELEMENTS per collective kind — the
    #: dtype-independent twin of ``collective_wire``.  Backends may widen
    #: on-wire dtypes relative to the serving deployment (XLA:CPU
    #: legalizes bf16 compute to f32), so reconciling wire traffic against
    #: an analytical model priced at serving dtype must compare elements
    #: (or elements × serving bytes/el), not raw module bytes.
    collective_wire_elements: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    @property
    def wire_elements(self) -> float:
        return sum(self.collective_wire_elements.values())

    @property
    def dot_flops(self) -> float:
        """FLOPs of matmul-family ops (dot + convolution) — the exact,
        dtype-independent quantity both XLA and the analytical model count
        the same way (2·m·k·n up to the −mn accumulator term)."""
        return (self.flops_by_op.get("dot", 0.0)
                + self.flops_by_op.get("convolution", 0.0))

    def as_dict(self):
        return {"flops": self.flops, "bytes": self.bytes,
                "wire_bytes": self.wire_bytes,
                "collective_wire": self.collective_wire,
                "collective_counts": self.collective_counts,
                "unknown_trip_loops": self.unknown_trip_loops,
                "flops_by_op": self.flops_by_op,
                "bytes_by_op": self.bytes_by_op,
                "collective_wire_elements": self.collective_wire_elements}


def analyze(hlo_text: str, n_devices: int = 1,
            default_trip: int = 1) -> ModuleCost:
    # ---- pass 1: split into computations -------------------------------
    comps: Dict[str, list] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        h = _HEADER_RE.match(line.strip())
        if h and ("->" in line):
            cur = h.group(2)
            comps[cur] = []
            if h.group(1):
                entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)

    # ---- pass 2: per-computation parse ---------------------------------
    parsed: Dict[str, list] = {}
    dus_root_update_bytes: Dict[str, float] = {}
    #: fusion body -> {param_index: charged bytes} for params that are only
    #: windowed into (dynamic-slice reads / dynamic-update-slice buffers):
    #: the caller charges the touched window, not the whole (loop-carried
    #: KV-cache / layer-stack) operand.
    param_charges: Dict[str, Dict[int, float]] = {}
    for name, lines in comps.items():
        instrs = []
        symtab: Dict[str, str] = {}
        for line in lines:
            m = _INSTR_RE.match(_LAYOUT_ANNOT_RE.sub(r"{\1}", line))
            if not m:
                continue
            iname, rtype, op, rest = m.groups()
            symtab[iname] = rtype
            instrs.append((iname, rtype, op, rest, line))
        parsed[name] = (instrs, symtab)
        # fusions containing dynamic-update-slice(s) update their buffer
        # IN PLACE (loop-carried KV caches, scan-ys stacking, donated
        # weights) — charge the update-slice sizes, not the whole buffer.
        upd_total = 0.0
        for iname, rtype, op, rest, line in instrs:
            if op == "dynamic-update-slice":
                refs = _OPERAND_RE.findall(rest)
                upd = symtab.get(refs[1], "") if len(refs) > 1 else ""
                upd_total += _type_bytes(upd)
        if upd_total:
            dus_root_update_bytes[name] = upd_total
        # parameter-use analysis: params touched only through windowed ops
        params_idx: Dict[str, int] = {}
        for iname, rtype, op, rest, line in instrs:
            if op == "parameter":
                mm = re.match(r"\s*(\d+)", rest)
                if mm:
                    params_idx[iname] = int(mm.group(1))
        windowed: Dict[str, float] = {}
        full_use: set = set()
        for iname, rtype, op, rest, line in instrs:
            if op == "parameter":
                continue
            refs = _OPERAND_RE.findall(rest)
            for pos_i, ref in enumerate(refs):
                if ref not in params_idx:
                    continue
                if op == "dynamic-slice" and pos_i == 0:
                    windowed[ref] = windowed.get(ref, 0.0) + _type_bytes(rtype)
                elif op == "dynamic-update-slice" and pos_i == 0:
                    # aliased in-place buffer: written window charged via
                    # dus_root_update_bytes; the buffer itself is not read
                    windowed.setdefault(ref, 0.0)
                elif op in ("dynamic-update-slice", "dynamic-slice"):
                    pass  # update operand / indices: charged elsewhere
                else:
                    full_use.add(ref)
        charges = {params_idx[r]: b for r, b in windowed.items()
                   if r not in full_use}
        if charges:
            param_charges[name] = charges

    # ---- pass 3: multiplicities via call graph -------------------------
    mult: Dict[str, float] = {entry: 1.0} if entry else {}
    fusion_bodies = set()
    reducer_bodies = set()
    unknown_loops = 0
    # BFS from entry
    frontier = [entry] if entry else list(parsed)
    seen = set()
    while frontier:
        cname = frontier.pop()
        if cname in seen or cname not in parsed:
            continue
        seen.add(cname)
        m_here = mult.get(cname, 1.0)
        for iname, rtype, op, rest, line in parsed[cname][0]:
            if op == "while":
                t = _TRIP_RE.search(line)
                trips = int(t.group(1)) if t else default_trip
                if not t:
                    unknown_loops += 1
                for rx, extra in ((_BODY_RE, trips), (_COND_RE, trips + 1)):
                    mm = rx.search(line)
                    if mm:
                        child = mm.group(1)
                        mult[child] = mult.get(child, 0.0) + m_here * extra
                        frontier.append(child)
            elif op == "fusion":
                mm = _CALLS_RE.search(line)
                if mm:
                    fusion_bodies.add(mm.group(1))
                    mult[mm.group(1)] = mult.get(mm.group(1), 0.0) + m_here
                    frontier.append(mm.group(1))
            elif op in ("call", "conditional"):
                for mm in _CALLS_RE.finditer(line):
                    mult[mm.group(1)] = mult.get(mm.group(1), 0.0) + m_here
                    frontier.append(mm.group(1))
            else:
                mm = _APPLY_RE.search(line)
                if mm:
                    reducer_bodies.add(mm.group(1))

    # ---- pass 4: accumulate costs ---------------------------------------
    flops = 0.0
    bytes_ = 0.0
    fby: Dict[str, float] = {}
    bby: Dict[str, float] = {}
    wire: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    welems: Dict[str, float] = {}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}

    def _acc(d: Dict[str, float], key: str, v: float) -> None:
        d[key] = d.get(key, 0.0) + v

    for cname, (instrs, symtab) in parsed.items():
        m_here = mult.get(cname, 0.0)
        if m_here == 0.0 or cname in reducer_bodies:
            continue
        in_fusion = cname in fusion_bodies
        for iname, rtype, op, rest, line in instrs:
            if op in _FREE_OPS or op == "while":
                continue
            # ---- flops -------------------------------------------------
            if op == "dot":
                ops_n = _type_numel(rtype)
                contract = 1
                mc = _LHS_CONTRACT_RE.search(line)
                lhs_ref = _OPERAND_RE.search(rest)
                if mc and lhs_ref and lhs_ref.group(1) in symtab:
                    lhs_dims = _dims_of(symtab[lhs_ref.group(1)])
                    for ci in mc.group(1).split(","):
                        if ci.strip() and int(ci) < len(lhs_dims):
                            contract *= lhs_dims[int(ci)]
                f = m_here * 2.0 * ops_n * contract
                flops += f
                _acc(fby, "dot", f)
            elif op in _ELEMENTWISE_FLOP_OPS and not in_fusion:
                f = m_here * _type_numel(rtype)
                flops += f
                _acc(fby, op, f)
            elif op in _ELEMENTWISE_FLOP_OPS and in_fusion and op != "fusion":
                # fusion internals: count arithmetic, not memory
                if op in ("add", "multiply", "subtract", "divide",
                          "exponential", "tanh", "logistic", "rsqrt",
                          "power", "maximum", "minimum", "log"):
                    f = m_here * _type_numel(rtype)
                    flops += f
                    _acc(fby, op, f)
                continue
            if in_fusion:
                continue
            # ---- bytes ---------------------------------------------------
            if op in ("dynamic-update-slice",):
                # in-place: update operand read + written (+ indices)
                refs = _OPERAND_RE.findall(rest)
                upd = symtab.get(refs[1], "") if len(refs) > 1 else ""
                b = m_here * 2.0 * _type_bytes(upd)
                bytes_ += b
                _acc(bby, op, b)
            elif op in ("dynamic-slice", "gather"):
                b = m_here * 2.0 * _type_bytes(rtype)
                bytes_ += b
                _acc(bby, op, b)
            elif op == "scatter":
                refs = _OPERAND_RE.findall(rest)
                upd = symtab.get(refs[-1], "") if refs else ""
                b = m_here * 2.0 * _type_bytes(upd)
                bytes_ += b
                _acc(bby, op, b)
            elif op == "fusion":
                callee = _CALLS_RE.search(line)
                cal = callee.group(1) if callee else ""
                charges = param_charges.get(cal, {})
                opbytes = 0.0
                for pos_i, ref in enumerate(
                        _OPERAND_RE.findall(rest.split(" calls=")[0])):
                    if pos_i in charges:
                        opbytes += charges[pos_i]     # windowed access
                    elif ref in symtab:
                        opbytes += _operand_bytes(symtab[ref])
                if cal in dus_root_update_bytes:
                    # in-place buffer update: result aliases the buffer —
                    # charge the written window, not the whole result
                    b = m_here * (opbytes + dus_root_update_bytes[cal])
                else:
                    b = m_here * (opbytes + _type_bytes(rtype))
                bytes_ += b
                _acc(bby, op, b)
            elif op in _BYTE_OPS:
                opbytes = 0.0
                for ref in _OPERAND_RE.findall(rest.split(" calls=")[0]):
                    if ref in symtab:
                        opbytes += _operand_bytes(symtab[ref])
                b = m_here * (opbytes + _type_bytes(rtype))
                bytes_ += b
                _acc(bby, op, b)
            # ---- collectives --------------------------------------------
            base_op = op.replace("-start", "")
            if base_op in _COLLECTIVES:
                nb = _type_bytes(rtype)
                ne = _type_numel(rtype)
                if op.endswith("-start"):
                    nb /= 2.0          # (operand, result) tuple type
                    ne /= 2.0
                g = _group_size(line, n_devices)
                if g > 1:
                    if base_op == "all-reduce":
                        ring = 2.0 * (g - 1) / g
                    elif base_op == "all-gather":
                        ring = (g - 1) / g
                    elif base_op == "reduce-scatter":
                        ring = float(g - 1)
                    elif base_op == "all-to-all":
                        ring = (g - 1) / g
                    else:
                        ring = 1.0
                    wire[base_op] += m_here * nb * ring
                    _acc(welems, base_op, m_here * ne * ring)
                    counts[base_op] += int(m_here)
    return ModuleCost(flops=flops, bytes=bytes_,
                      wire_bytes=sum(wire.values()),
                      collective_wire=wire, collective_counts=counts,
                      unknown_trip_loops=unknown_loops,
                      flops_by_op=fby, bytes_by_op=bby,
                      collective_wire_elements=welems)


# ===========================================================================
# Donation / buffer-aliasing introspection (compile-hygiene audits)
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class AliasEntry:
    """One ``input_output_alias`` record of a compiled module header:
    output tuple index ← (parameter number, parameter tuple index)."""
    output_index: tuple
    param_number: int
    param_index: tuple
    kind: str                    # "may-alias" | "must-alias"


_ALIAS_HDR_RE = re.compile(r"input_output_alias=\{(.*?)\}(?:,\s*\w+=|\s*$)")
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{([\d,\s]*)\},\s*(may-alias|must-alias)\)")
_ENTRY_LAYOUT_RE = re.compile(r"entry_computation_layout=\{\((.*?)\)->")


def _idx_tuple(text: str) -> tuple:
    return tuple(int(x) for x in text.split(",") if x.strip())


def parse_input_output_aliases(hlo_text: str) -> List[AliasEntry]:
    """Donated-buffer aliases declared in the module header.

    ``jax.jit(..., donate_argnums=...)`` surfaces as
    ``input_output_alias={ {out}: (param, {idx}, kind), ... }`` on the
    ``HloModule`` line; an input buffer that XLA could NOT reuse in place
    simply has no entry — which is exactly what the donation auditor
    looks for (a silently copied KV pool)."""
    for line in hlo_text.splitlines():
        if "input_output_alias=" not in line:
            continue
        m = _ALIAS_HDR_RE.search(line)
        body = m.group(1) if m else line.split("input_output_alias=", 1)[1]
        return [AliasEntry(output_index=_idx_tuple(o), param_number=int(p),
                           param_index=_idx_tuple(i), kind=k)
                for o, p, i, k in _ALIAS_ENTRY_RE.findall(body)]
    return []


def entry_parameter_shapes(hlo_text: str) -> List[str]:
    """Normalized ``dtype[dims]`` of each entry parameter, in parameter
    order, read from the header's ``entry_computation_layout`` (layout
    and memory-space annotations stripped)."""
    m = _ENTRY_LAYOUT_RE.search(hlo_text)
    if not m:
        return []
    return [f"{dt}[{dims}]" for dt, dims in _SHAPE_RE.findall(m.group(1))]
