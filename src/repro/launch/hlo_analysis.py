"""Trip-count-aware cost analysis over optimized HLO text.

``compiled.cost_analysis()`` counts a ``while`` body **once**, so every
scan (layer stack, CE-loss chunks, attention q-blocks, XFER gathers inside
the layer scan) is undercounted by its trip count. This module re-derives
the three roofline terms from the optimized HLO with loop multipliers:

  * FLOPs        — from dot/convolution ops (2 · out_elems · contraction)
  * HBM bytes    — per top-level op: operands + outputs (post-fusion HLO,
                   so fusion internals are free — XLA's own traffic model)
  * collectives  — wire bytes per type with ring factor (g-1)/g

Computations are resolved bottom-up: ``fusion`` contributes its callee's
FLOPs but only its own boundary bytes; ``while`` multiplies its body by the
trip count recovered from the loop condition's comparison constant.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(\(?[^=]*?\)?)\s+([a-z][a-z0-9\-]*)\((.*)$")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*n[^0-9]*(\d+)')
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_CALL_RE = re.compile(r"(?:calls|to_apply|condition|body)=%?([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")

_ZERO_COST_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "add-dependency", "partition-id", "replica-id", "iota",
    "opt-barrier", "domain", "convert",
}
_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute"}


def _shape_elems_bytes(type_str: str) -> Tuple[int, int]:
    elems = bytes_ = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        bytes_ += n * _DTYPE_BYTES[dt]
    return elems, bytes_


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    # traffic of ops inside a "flashattn" named scope: the Pallas flash
    # kernels keep these tensors in VMEM on the TPU target, so they are
    # reported separately and excluded from the HBM roofline term.
    vmem_resident_bytes: float = 0.0
    coll: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=lambda: defaultdict(lambda: {"count": 0.0, "wire_bytes": 0.0}))

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        self.vmem_resident_bytes += other.vmem_resident_bytes * mult
        for k, v in other.coll.items():
            self.coll[k]["count"] += v["count"] * mult
            self.coll[k]["wire_bytes"] += v["wire_bytes"] * mult

    @property
    def collective_wire_bytes(self) -> float:
        return sum(v["wire_bytes"] for v in self.coll.values())

    def as_dict(self) -> dict:
        return {"flops": self.flops, "hbm_bytes": self.hbm_bytes,
                "vmem_resident_bytes": self.vmem_resident_bytes,
                "collective_wire_bytes": self.collective_wire_bytes,
                "collectives": {k: dict(v) for k, v in self.coll.items()}}


_VMEM_SCOPE = "flashattn"


def _in_vmem_scope(ins: "_Instr") -> bool:
    return _VMEM_SCOPE in ins.rest


@dataclasses.dataclass
class _Instr:
    name: str
    opcode: str
    type_str: str
    rest: str  # operand list + attributes
    operands: Tuple[str, ...] = ()


def _head_operands(rest: str) -> Tuple[str, Tuple[str, ...]]:
    """Split rest into (operand-list-string, operand names)."""
    depth = 0
    head = rest
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth < 0:
                head = rest[:i]
                break
    names = tuple(tok.strip().lstrip("%") for tok in re.findall(r"%[\w.\-]+", head))
    return head, names


@dataclasses.dataclass
class _Comp:
    instrs: List[_Instr]
    types: Dict[str, str]  # instr name -> type string

    def by_name(self, name: str) -> Optional[_Instr]:
        if not hasattr(self, "_idx"):
            self._idx = {i.name: i for i in self.instrs}
        return self._idx.get(name)


_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _parse_computations(hlo: str) -> Dict[str, _Comp]:
    comps: Dict[str, _Comp] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if "/*" in line:
            line = _COMMENT_RE.sub("", line)
        if cur is None:
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = _Comp([], {})
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            name = m.group(1).lstrip("%")
            _, opnds = _head_operands(m.group(4))
            ins = _Instr(name, m.group(3), m.group(2), m.group(4), opnds)
            comps[cur].instrs.append(ins)
            comps[cur].types[name] = m.group(2)
    return comps


def _dot_flops(instr: _Instr, types: Dict[str, str]) -> float:
    out_elems, _ = _shape_elems_bytes(instr.type_str)
    lhs_type = types.get(instr.operands[0], "") if instr.operands else ""
    mshape = _SHAPE_RE.search(lhs_type)
    if not mshape:
        return 2.0 * out_elems  # unknown contraction: lower bound
    lhs_dims = [int(d) for d in mshape.group(2).split(",") if d]
    m = _CONTRACT_RE.search(instr.rest)
    contract = 1
    if m and m.group(1):
        for i in m.group(1).split(","):
            idx = int(i)
            if idx < len(lhs_dims):
                contract *= lhs_dims[idx]
    return 2.0 * out_elems * contract


def _conv_flops(instr: _Instr, types: Dict[str, str]) -> float:
    out_elems, _ = _shape_elems_bytes(instr.type_str)
    ktype = types.get(instr.operands[1], "") if len(instr.operands) > 1 else ""
    mshape = _SHAPE_RE.search(ktype)
    if not mshape:
        return 2.0 * out_elems
    kdims = [int(d) for d in mshape.group(2).split(",") if d]
    out_dims_m = _SHAPE_RE.search(instr.type_str)
    if not out_dims_m:
        return 0.0
    k = 1
    for d in kdims:
        k *= d
    cout = max([int(d) for d in out_dims_m.group(2).split(",") if d] or [1])
    return 2.0 * out_elems * max(k // max(cout, 1), 1)


def _operand_bytes(instr: _Instr, types: Dict[str, str],
                   comp: Optional["_Comp"] = None,
                   comps: Optional[Dict[str, "_Comp"]] = None) -> float:
    total = 0.0
    for name in instr.operands:
        if comp is not None and comps is not None:
            total += _storage_bytes(name, comp, comps)
        else:
            t = types.get(name)
            if t:
                _, b = _shape_elems_bytes(t)
                total += b
    if total == 0.0:  # inline-shape dump style fallback
        head, _ = _head_operands(instr.rest)
        _, total = _shape_elems_bytes(head)
    return total


# --- effective-read modelling -------------------------------------------------
# dynamic-slice / gather read only their output; dynamic-update-slice /
# scatter write only the update (XLA updates in place). Without these rules
# an embedding lookup would "read" the whole 2 GB table and a scanned layer
# stack would re-read all L layers' params every iteration.

_SLICE_OPS = {"dynamic-slice", "gather"}
_INPLACE_OPS = {"dynamic-update-slice", "scatter"}


_PASSTHRU_OPS = {"bitcast", "reshape", "copy", "transpose", "convert"}

# dtype-narrowing chain: ops that preserve the logical tensor while the CPU
# backend may have widened it (bf16->f32 `convert` legalisation around dots).
# On the TPU target the tensor's storage dtype is the narrow one.
_NARROW_CHAIN = {"convert", "bitcast", "copy", "transpose", "reshape"}


def _storage_bytes(name: str, comp: "_Comp", comps: Dict[str, "_Comp"],
                   depth: int = 0) -> float:
    """Effective storage bytes of a value: min along its producer chain of
    layout/dtype-preserving ops (TPU keeps the narrow dtype end-to-end)."""
    _, b = _shape_elems_bytes(comp.types.get(name, ""))
    if depth > 6 or b == 0:
        return b
    prod = comp.by_name(name)
    if prod is None or not prod.operands:
        return b
    if prod.opcode in _NARROW_CHAIN:
        return min(b, _storage_bytes(prod.operands[0], comp, comps, depth + 1))
    if prod.opcode == "fusion":
        m = _CALL_RE.search(prod.rest)
        callee = comps.get(m.group(1)) if m else None
        if callee and callee.instrs:
            # follow the callee root through layout/dtype ops to a parameter;
            # the true storage is the matching outer operand's
            node = callee.instrs[-1]
            for _ in range(6):
                if node is None:
                    break
                if node.opcode == "parameter":
                    mi = re.match(r"\s*(\d+)", node.rest)
                    if mi and int(mi.group(1)) < len(prod.operands):
                        return min(b, _storage_bytes(
                            prod.operands[int(mi.group(1))], comp, comps, depth + 1))
                    break
                if node.opcode in _NARROW_CHAIN and node.operands:
                    nxt = callee.by_name(node.operands[0])
                    if nxt is None:  # operand is a callee parameter by name
                        break
                    node = nxt
                    continue
                # root computes something real: its narrowest side still
                # bounds the storage (e.g. convert deep inside)
                _, rb = _shape_elems_bytes(node.type_str)
                if node.opcode == "convert" and node.operands:
                    _, src = _shape_elems_bytes(callee.types.get(node.operands[0], ""))
                    if src:
                        return min(b, src)
                break
    return b


def _fusion_param_reads(comp: "_Comp") -> Dict[int, float]:
    """Per-parameter effective read bytes inside a fused computation."""
    # consumers per instr name
    consumers: Dict[str, List[_Instr]] = defaultdict(list)
    params: Dict[str, int] = {}
    for ins in comp.instrs:
        if ins.opcode == "parameter":
            m = re.match(r"\s*(\d+)", ins.rest)
            if m:
                params[ins.name] = int(m.group(1))
        for opnd in ins.operands:
            consumers[opnd].append(ins)

    def effective_uses(name: str, depth: int = 0) -> List[_Instr]:
        out: List[_Instr] = []
        for u in consumers.get(name, []):
            if u.opcode in _PASSTHRU_OPS and depth < 4:
                out += effective_uses(u.name, depth + 1)
            else:
                out.append(u)
        return out

    reads: Dict[int, float] = {}
    for pname, pidx in params.items():
        _, full = _shape_elems_bytes(comp.types.get(pname, ""))
        uses = effective_uses(pname)
        if uses and all(u.opcode in _SLICE_OPS for u in uses):
            eff = sum(_shape_elems_bytes(u.type_str)[1] for u in uses)
            reads[pidx] = min(eff, full)
        elif uses and all(u.opcode in _INPLACE_OPS and u.operands
                          and u.operands[0] == pname for u in uses):
            reads[pidx] = 0.0  # in-place destination alias
        else:
            reads[pidx] = full
    return reads


def _fusion_bytes(instr: _Instr, types: Dict[str, str],
                  callee: Optional["_Comp"],
                  comp: Optional["_Comp"] = None,
                  comps: Optional[Dict[str, "_Comp"]] = None) -> float:
    _, out_b = _shape_elems_bytes(instr.type_str)
    if callee is None:
        return out_b + _operand_bytes(instr, types, comp, comps)
    reads = _fusion_param_reads(callee)
    total = out_b
    for i, name in enumerate(instr.operands):
        _, full = _shape_elems_bytes(types.get(name, ""))
        if comp is not None and comps is not None:
            full = min(full, _storage_bytes(name, comp, comps)) if full else full
        total += min(reads.get(i, full), full) if full else reads.get(i, 0.0)
    # in-place root: output traffic is the update, not the buffer. Handles
    # both a bare DUS root and a tuple of DUS results (k+v cache updates
    # stacked by one scan fusion).
    root = callee.instrs[-1] if callee.instrs else None
    # walk the root through dtype/layout ops (CPU wraps the DUS in converts)
    for _ in range(4):
        if root is not None and root.opcode in _NARROW_CHAIN and root.operands:
            root = callee.by_name(root.operands[0])
        else:
            break
    if root is not None:
        dus_nodes = []
        if root.opcode in _INPLACE_OPS:
            dus_nodes = [root]
        elif root.opcode == "tuple" and root.operands:
            nodes = [callee.by_name(n) for n in root.operands]
            if nodes and all(n is not None and n.opcode in _INPLACE_OPS
                             for n in nodes):
                dus_nodes = nodes
        if dus_nodes:
            upd = 0.0
            for n in dus_nodes:
                if len(n.operands) > 1:
                    _, u = _shape_elems_bytes(callee.types.get(n.operands[1], ""))
                    upd += u
            total = total - out_b + upd
    return total


def _collective_wire(instr: _Instr) -> Tuple[str, float]:
    kind = instr.opcode.replace("-start", "").replace("-done", "")
    _, out_bytes = _shape_elems_bytes(instr.type_str)
    m = _GROUPS_IOTA_RE.search(instr.rest)
    if m:
        g = int(m.group(2))
    else:
        m = _GROUPS_RE.search(instr.rest)
        g = len(m.group(1).split(",")) if m else 2
    ring = (g - 1) / g if g > 1 else 0.0
    if kind == "all-reduce":
        factor = 2.0 * ring
    elif kind == "collective-permute":
        factor = 1.0
    else:
        factor = ring
    return kind, out_bytes * factor


def _trip_count(while_instr: _Instr, cond: Optional["_Comp"]) -> int:
    m = _TRIP_RE.search(while_instr.rest)
    if m:
        return int(m.group(1))
    consts = []
    for ins in (cond.instrs if cond else []):
        if ins.opcode == "constant":
            mm = re.match(r"\s*(\d+)", ins.rest)
            if mm:
                consts.append(int(mm.group(1)))
        for mm in _CONST_RE.finditer(ins.rest):
            consts.append(int(mm.group(1)))
    return max(consts) if consts else 1


def analyze(hlo: str) -> Cost:
    comps = _parse_computations(hlo)
    memo: Dict[str, Cost] = {}
    entry = None
    # the last computation in the module is the entry in XLA dumps; prefer
    # one whose name starts with main
    for name in comps:
        if name.split(".")[0].endswith("main") or name.startswith("main"):
            entry = name
    if entry is None and comps:
        entry = list(comps)[-1]

    def comp_cost(name: str) -> Cost:
        if name in memo:
            return memo[name]
        memo[name] = Cost()  # cycle guard
        c = Cost()
        comp = comps.get(name)
        if comp is not None:
            for ins in comp.instrs:
                ic = instr_cost(ins, comp.types, comp)
                if ins.opcode not in ("while", "call", "conditional") and _in_vmem_scope(ins):
                    ic.vmem_resident_bytes += ic.hbm_bytes
                    ic.hbm_bytes = 0.0
                c.add(ic)
        memo[name] = c
        return c

    def instr_cost(ins: _Instr, types: Dict[str, str],
                   comp: Optional["_Comp"] = None) -> Cost:
        c = Cost()
        op = ins.opcode
        base = op.replace("-start", "").replace("-done", "")
        if op in _ZERO_COST_OPS:
            return c
        if base in _COLLECTIVES:
            if op.endswith("-done"):
                return c
            kind, wire = _collective_wire(ins)
            if comp is not None and ins.operands:
                _, ob_full = _shape_elems_bytes(ins.type_str)
                src = sum(_storage_bytes(n, comp, comps) for n in ins.operands)
                full = _operand_bytes(ins, types)
                if full > 0 and src > 0:
                    wire *= min(src / full, 1.0)  # TPU moves the storage dtype
            c.coll[kind]["count"] += 1
            c.coll[kind]["wire_bytes"] += wire
            _, ob = _shape_elems_bytes(ins.type_str)
            c.hbm_bytes += min(ob, ob) + _operand_bytes(ins, types, comp, comps)
            return c
        if op == "fusion":
            m = _CALL_RE.search(ins.rest)
            callee = None
            if m:
                callee_name = m.group(1).strip().strip("%")
                callee = comps.get(callee_name)
                inner = comp_cost(callee_name)
                c.flops += inner.flops  # flops inside count; bytes don't
                for k, v in inner.coll.items():
                    c.coll[k]["count"] += v["count"]
                    c.coll[k]["wire_bytes"] += v["wire_bytes"]
            c.hbm_bytes += _fusion_bytes(ins, types, callee, comp, comps)
            return c
        if op == "while":
            mc = re.search(r"condition=%?([\w.\-]+)", ins.rest)
            mb = re.search(r"body=%?([\w.\-]+)", ins.rest)
            cond = comps.get(mc.group(1)) if mc else None
            trips = max(_trip_count(ins, cond), 1)
            if mb:
                c.add(comp_cost(mb.group(1)), mult=trips)
            if mc:
                c.add(comp_cost(mc.group(1)), mult=trips)
            return c
        if op in ("call", "conditional", "async-start", "custom-call"):
            has_body = False
            for m in re.finditer(r"(?:calls|branch_computations|to_apply)=\{?%?([\w.\-]+)",
                                 ins.rest):
                if m.group(1) in comps:
                    has_body = True
                    c.add(comp_cost(m.group(1)))
            # call/conditional with a resolvable body are inlined scheduling,
            # not data movement: the callee already accounts for its own
            # traffic (charging boundary bytes here would re-read e.g. a
            # whole embedding table the callee only gathers 32 rows of).
            # custom-call/async-start bodies are helper lambdas (comparator,
            # reducer) that do NOT model the op's operand traffic — their
            # boundary bytes stay.
            if not has_body or op in ("custom-call", "async-start"):
                _, ob = _shape_elems_bytes(ins.type_str)
                c.hbm_bytes += ob + _operand_bytes(ins, types)
            return c
        if op == "dot":
            c.flops += _dot_flops(ins, types)
            _, ob = _shape_elems_bytes(ins.type_str)
            if comp is not None:
                # CPU legalizes bf16 dots to f32 + convert-back; on TPU the
                # dot writes the requested (narrow) dtype directly.
                for other in comp.instrs:
                    if other.opcode == "convert" and ins.name in other.operands:
                        _, cb = _shape_elems_bytes(other.type_str)
                        if cb:
                            ob = min(ob, cb)
            c.hbm_bytes += ob + _operand_bytes(ins, types, comp, comps)
            return c
        if op == "convolution":
            c.flops += _conv_flops(ins, types)
            _, ob = _shape_elems_bytes(ins.type_str)
            c.hbm_bytes += ob + _operand_bytes(ins, types, comp, comps)
            return c
        if op in _SLICE_OPS:
            _, ob = _shape_elems_bytes(ins.type_str)
            c.hbm_bytes += 2.0 * ob  # read slice + write slice
            return c
        if op in _INPLACE_OPS and len(ins.operands) > 1:
            _, upd = _shape_elems_bytes(types.get(ins.operands[1], ""))
            c.hbm_bytes += 2.0 * upd
            return c
        if op in _PASSTHRU_OPS and ins.operands and comp is not None:
            # pure layout ops: TPU traffic is the narrow storage, both sides
            nb = _storage_bytes(ins.operands[0], comp, comps)
            c.hbm_bytes += 2.0 * nb
            return c
        # generic op: traffic only
        _, ob = _shape_elems_bytes(ins.type_str)
        c.hbm_bytes += ob + _operand_bytes(ins, types, comp, comps)
        return c

    return comp_cost(entry) if entry else Cost()


def collective_stats(compiled) -> Dict[str, Dict[str, float]]:
    """Collectives of one execution of a compiled program, by kind:
    ``{"all-reduce": {"count": n, "wire_bytes": b, "max_bytes": m}, ...}``.

    ``compiled`` is a ``jax.stages.Compiled``. Every loop body counts its
    trip count times (``analyze``), so a layer scan's exchanges count once
    per layer; ``wire_bytes`` is ring-counted per device; ``max_bytes`` is
    the largest operand or result of one collective of that kind. A
    program with no collective gives ``{}``."""
    text = compiled.as_text()
    largest: Dict[str, float] = defaultdict(float)
    for comp in _parse_computations(text).values():
        for ins in comp.instrs:
            kind = ins.opcode.replace("-start", "").replace("-done", "")
            if kind in _COLLECTIVES and not ins.opcode.endswith("-done"):
                sizes = [_shape_elems_bytes(t)[1] for t in
                         [ins.type_str] + [comp.types.get(n, "")
                                           for n in ins.operands]]
                largest[kind] = max(largest[kind], *sizes)
    return {kind: {"count": int(round(v["count"])),
                   "wire_bytes": float(v["wire_bytes"]),
                   "max_bytes": float(largest[kind])}
            for kind, v in sorted(analyze(text).coll.items()) if v["count"]}
