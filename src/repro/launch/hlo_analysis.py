"""Loop-aware cost analysis over optimized (post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` counts ``while`` bodies ONCE (verified
empirically: a scan of 10 matmuls reports the flops of 1), which silently
underestimates every scanned block sweep and every ring-step loop by
the trip count. This analyzer re-derives per-device costs from the HLO text
with loop multipliers:

- builds a per-computation instruction table (result shapes from definition
  lines, including tuple types),
- extracts trip counts from each ``while``'s condition computation (max
  integer constant; +1 for ``direction=LE``),
- propagates multipliers entry→callees through ``body=/condition=/calls=/
  to_apply=`` edges (nested loops multiply),
- FLOPs: ``dot`` ops as 2 · result_elems · contracted_extent (the MXU work;
  elementwise flops are noise at these scales),
- HBM bytes: Σ (operands + result) over memory-moving op kinds (fusion
  call-site model: fused interiors stay in registers/VMEM),
- collectives: payload/link bytes per op kind with ring algorithm factors
  (× loop multipliers).

All numbers are per device (the HLO module is one SPMD program).
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# Op kinds whose operands+results approximate HBM traffic under a
# TPU-fusion model: matmuls, data movement, reductions/sorts, collectives
# and explicit fusion call sites. Plain elementwise ops (add/multiply/
# convert/broadcast/...) are EXCLUDED — the CPU pipeline leaves them
# unfused, but on the TPU target they fuse into neighbors, so counting
# them would overstate HBM traffic by ~10×. Known biases are documented
# in EXPERIMENTS.md §Roofline (method).
_HBM_OPS = {
    "dot", "fusion", "copy", "transpose", "reduce", "scatter",
    "gather", "dynamic-slice", "dynamic-update-slice", "pad", "concatenate",
    "slice", "sort", "select-and-scatter", "reduce-window",
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
}

_SHAPE_TOKEN = re.compile(r"(\w+)\[([\d,]*)\]")
# Result types may be tuples containing layout braces and /*index=N*/
# comments; they never contain parentheses, so `\([^)]*\)` is safe.
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*(?P<type>\([^)]*\)|[\w\[\],{} ]+?)\s+"
    r"(?P<op>[\w\-]+)\((?P<args>[^)]*)\)(?P<rest>.*)$"
)
_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
_CALLEE = re.compile(r"(?:condition|body|calls|to_apply)=%([\w.\-]+)")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CONSTANT_INT = re.compile(r"=\s*[su]\d+\[\]\s+constant\((\d+)\)")


def _parse_shapes(type_str: str) -> list:
    """[(dtype, [dims...]), ...] from a (possibly tuple) HLO type string."""
    out = []
    for m in _SHAPE_TOKEN.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _shape_bytes(shapes: list) -> int:
    return sum(
        _DTYPE_BYTES[dt] * math.prod(dims or [1]) for dt, dims in shapes
    )


@dataclasses.dataclass
class Instr:
    name: str
    op: str
    shapes: list          # result shapes [(dtype, dims)]
    args: list            # operand names
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    instrs: list
    table: dict           # name -> Instr


def parse_module(text: str) -> dict:
    comps: dict = {}
    current: Computation | None = None
    for line in text.splitlines():
        h = _COMP_HEADER.match(line.strip())
        if h and ("->" in line):
            current = Computation(h.group(1), [], {})
            comps[current.name] = current
            continue
        if current is None:
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        args = re.findall(r"%([\w.\-]+)", m.group("args"))
        ins = Instr(
            name=m.group("name"),
            op=m.group("op"),
            shapes=_parse_shapes(m.group("type")),
            args=args,
            line=line,
        )
        current.instrs.append(ins)
        current.table[ins.name] = ins
    return comps


def _trip_count(cond: Computation) -> int:
    best = 1
    for ins in cond.instrs:
        m = _CONSTANT_INT.search(ins.line)
        if m:
            best = max(best, int(m.group(1)))
    if any("direction=LE" in i.line for i in cond.instrs):
        best += 1
    return best


def _multipliers(comps: dict) -> dict:
    """Execution-count multiplier per computation.

    Roots (the ENTRY, i.e. computations referenced by no one) start at 1;
    ``while`` edges multiply by the trip count, plain call edges by 1.
    The call graph is a DAG, so a bounded fixpoint converges exactly.
    """
    referenced = set()
    fused_interior = set()      # reached via calls=/to_apply= (fusion bodies)
    edges = defaultdict(list)   # caller -> [(callee, factor)]
    for cname, comp in comps.items():
        for ins in comp.instrs:
            if ins.op == "while":
                body = re.search(r"body=%([\w.\-]+)", ins.line)
                cond = re.search(r"condition=%([\w.\-]+)", ins.line)
                trip = 1
                if cond and cond.group(1) in comps:
                    trip = _trip_count(comps[cond.group(1)])
                if body:
                    edges[cname].append((body.group(1), trip))
                    referenced.add(body.group(1))
                if cond:
                    edges[cname].append((cond.group(1), trip + 1))
                    referenced.add(cond.group(1))
            else:
                for c in _CALLEE.findall(ins.line):
                    edges[cname].append((c, 1))
                    referenced.add(c)
                    fused_interior.add(c)
    roots = [n for n in comps if n not in referenced]
    mult = defaultdict(float)
    for r in roots:
        mult[r] = 1.0
    for _ in range(len(comps) + 1):
        nxt = defaultdict(float)
        for r in roots:
            nxt[r] = 1.0
        for caller, outs in edges.items():
            for callee, f in outs:
                nxt[callee] += mult[caller] * f
        if dict(nxt) == dict(mult):
            break
        mult = nxt
    return mult, fused_interior


def _dot_flops(ins: Instr, table: dict) -> float:
    if not ins.shapes:
        return 0.0
    result_elems = math.prod(ins.shapes[0][1] or [1])
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.line)
    if not m or not ins.args:
        return 2.0 * result_elems  # degenerate
    lhs = table.get(ins.args[0])
    if lhs is None or not lhs.shapes:
        return 2.0 * result_elems
    lhs_dims = lhs.shapes[0][1]
    contracted = 1
    for d in m.group(1).split(","):
        if d and int(d) < len(lhs_dims):
            contracted *= lhs_dims[int(d)]
    return 2.0 * result_elems * contracted


def _hbm_bytes(ins: Instr, comp: Computation, comps: dict) -> float:
    """HBM traffic of one top-level instruction.

    Slicing ops touch only the slice, not the whole operand — critical for
    scan-stacked loop carries (a [L, ...] activation stack read layer-wise
    must be billed per-slice, not L × full-stack). Fusions whose interior
    slices/updates a parameter get the same treatment via the callee's
    parameter table.
    """
    result = _shape_bytes(ins.shapes)
    if ins.op == "dynamic-slice" or ins.op == "slice" or ins.op == "gather":
        return 2.0 * result
    if ins.op == "dynamic-update-slice":
        # args: (operand, update, indices...): read update + write region
        upd = comp.table.get(ins.args[1]) if len(ins.args) > 1 else None
        ub = _shape_bytes(upd.shapes) if upd else result
        return 2.0 * ub
    if ins.op == "fusion":
        callee = _CALLEE.search(ins.line)
        inner = comps.get(callee.group(1)) if callee else None
        total = result
        sliced_params, dus_root = _fusion_slice_info(inner) if inner else ({}, None)
        if dus_root is not None:
            total = dus_root  # in-place stack update: bill the slice
        for idx, a in enumerate(ins.args):
            if a not in comp.table:
                continue
            if idx in sliced_params:
                total += sliced_params[idx]
            else:
                total += _shape_bytes(comp.table[a].shapes)
        return float(total)
    operand_bytes = sum(
        _shape_bytes(comp.table[a].shapes)
        for a in ins.args
        if a in comp.table
    )
    return float(operand_bytes + result)


def _fusion_slice_info(inner: Computation):
    """(param_index → slice bytes) for params only consumed via slicing,
    plus the update size if the fusion root is a dynamic-update-slice."""
    param_index = {}
    uses = defaultdict(list)    # param name -> [instr]
    alias = {}
    # NB: `convert` counts as pass-through here: a TPU fusion performs the
    # dtype cast slice-wise inside the DUS/slice kernel, whereas CPU HLO
    # materializes a full-buffer convert (which would distort the billing).
    for i in inner.instrs:
        if i.op == "parameter":
            m = re.search(r"parameter\((\d+)\)", i.line)
            if m:
                param_index[i.name] = int(m.group(1))
        for a in i.args:
            uses[a].append(i)
        if i.op in ("bitcast", "reshape", "convert") and i.args:
            alias[i.name] = i.args[0]
    def canon(n):
        seen = set()
        while n in alias and n not in seen:
            seen.add(n)
            n = alias[n]
        return n
    sliced = {}
    for pname, idx in param_index.items():
        consumers = []
        for i in inner.instrs:
            if any(canon(a) == pname for a in i.args):
                consumers.append(i)
        slicers = [
            i for i in consumers
            if i.op in ("dynamic-slice", "slice", "gather")
            or (i.op == "dynamic-update-slice" and canon(i.args[0]) == pname)
        ]
        passthrough = [
            i for i in consumers if i.op in ("bitcast", "reshape", "convert")
        ]
        if slicers and len(slicers) + len(passthrough) == len(consumers):
            b = 0
            for s in slicers:
                if s.op == "dynamic-update-slice" and len(s.args) > 1:
                    upd = inner.table.get(s.args[1])
                    b += _shape_bytes(upd.shapes) if upd else 0
                else:
                    b += _shape_bytes(s.shapes)
            sliced[idx] = b
    dus_root = None
    root = None
    for i in inner.instrs:
        if "ROOT" in i.line.split("=")[0] or i.line.lstrip().startswith("ROOT"):
            root = i
            break
    if root is None and inner.instrs:
        root = inner.instrs[-1]
    # Walk back through convert/bitcast/reshape wrappers around the root.
    seen = set()
    while (
        root is not None
        and root.op in ("convert", "bitcast", "reshape")
        and root.args
        and root.name not in seen
    ):
        seen.add(root.name)
        root = inner.table.get(root.args[0])
    if root is not None and root.op == "dynamic-update-slice" and len(root.args) > 1:
        upd = inner.table.get(root.args[1])
        if upd:
            dus_root = _shape_bytes(upd.shapes)
    return sliced, dus_root


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 1


def _collective_link_bytes(ins: Instr) -> tuple:
    """(kind, payload_bytes, link_bytes) for a collective instruction."""
    kind = ins.op.replace("-start", "")
    if kind not in _COLLECTIVES:
        return None
    shapes = ins.shapes
    if ins.op.endswith("-start") and len(shapes) > 1:
        shapes = shapes[len(shapes) // 2 :]
    payload = _shape_bytes(shapes)
    g = max(_group_size(ins.line), 1)
    if kind == "all-gather":
        link = payload * (g - 1) / g
    elif kind == "all-reduce":
        link = 2 * payload * (g - 1) / g
    elif kind == "reduce-scatter":
        link = payload * (g - 1)
    elif kind == "all-to-all":
        link = payload * (g - 1) / g
    else:
        link = payload
    return kind, payload, link


def analyze(text: str) -> dict:
    comps = parse_module(text)
    mult, fused_interior = _multipliers(comps)

    flops = 0.0
    hbm = 0.0
    coll = {
        k: {"count": 0.0, "link_bytes": 0.0, "payload_bytes": 0.0}
        for k in _COLLECTIVES
    }
    for cname, comp in comps.items():
        w = mult.get(cname, 0.0)
        if w <= 0:
            continue
        # fusion interiors: dots still burn MXU flops, but their memory
        # traffic is covered by the fusion call site (operands+result).
        interior = cname in fused_interior
        for ins in comp.instrs:
            if ins.op == "dot":
                flops += w * _dot_flops(ins, comp.table)
            if ins.op.replace("-start", "") in _COLLECTIVES and not ins.op.endswith(
                "-done"
            ):
                kind, payload, link = _collective_link_bytes(ins)
                coll[kind]["count"] += w
                coll[kind]["payload_bytes"] += w * payload
                coll[kind]["link_bytes"] += w * link
            base = ins.op.replace("-start", "").replace("-done", "")
            if base in _HBM_OPS and not interior:
                hbm += w * _hbm_bytes(ins, comp, comps)
    return {
        "flops": flops,
        "hbm_bytes": hbm,
        "collectives": coll,
        "link_bytes": sum(v["link_bytes"] for v in coll.values()),
        "n_computations": len(comps),
        "max_multiplier": max(mult.values()) if mult else 1.0,
    }
