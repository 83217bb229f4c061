"""Static analyzer for compiled (partitioned) HLO text.

``compiled.cost_analysis()`` counts every while-loop body ONCE -- useless
for scan-over-layers models where the loop carries 98% of the work.  This
analyzer walks the computation graph, multiplies while bodies by their
``known_trip_count`` (emitted by XLA in backend_config; falls back to the
loop-condition constant), and produces:

  * flops           -- dot/custom-call matmuls (2*M*N*K) + elementwise
  * bytes           -- HBM-traffic model: every non-fused op's operands +
                       result (fusion internals excluded: they live in
                       registers/VMEM, fusion boundaries are materialized)
  * collectives     -- per-kind count + operand/result bytes, trip-scaled

All numbers are per-device (the HLO is the per-device SPMD program).
Validated against cost_analysis on unrolled graphs in
tests/test_hlo_analysis.py.
"""

from __future__ import annotations

import dataclasses
import math
import re
from functools import lru_cache

__all__ = ["CostReport", "analyze_hlo", "xla_cost_analysis"]


def xla_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict of XLA's counters."""
    return dict(compiled.cost_analysis())

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "tanh", "log", "log-plus-one", "rsqrt", "sqrt",
    "power", "sine", "cosine", "expm1", "logistic", "floor", "ceil",
    "round-nearest-afz", "sign", "atan2", "remainder", "select", "clamp",
    "compare", "and", "or", "not", "xor", "convert", "erf",
}

# HBM-traffic model: only ops that genuinely stream buffers count.  On TPU
# the elementwise/convert/broadcast/transpose ops that XLA:CPU leaves at top
# level would be fused or handled by layout assignment, and the conservative
# full-carry `copy` ops XLA:CPU inserts around while loops are elided by
# buffer donation -- counting any of them inflates the memory term 10-100x.
# Slicing ops get special-cased in analyze(): in-place updates touch only
# the slice, not the whole buffer.
_TRAFFIC_OPS = {
    "dot", "custom-call", "fusion", "dynamic-slice",
    "dynamic-update-slice", "gather", "scatter", "reduce", "reduce-window",
    "sort", "select-and-scatter",
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
}

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_TYPE_RE = re.compile(r"[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?")
_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w\.\-]+|[\w\.\-]+)\s*\(.*\{\s*$")
_TRIP_RE = re.compile(r'known_trip_count[^}]*?"n"\s*:\s*"(\d+)"')
_NAME_RE = re.compile(r"%([\w\.\-]+)")


def _operand_names(rest: str) -> list[str]:
    """Operand names from the '(...)' group that follows an opcode.

    Recent XLA prints typed operands -- ``dot(f32[64,64]{1,0} %a, ...)`` --
    which the old ``split(',')`` + ``lstrip('%')`` parsing returned with the
    type prefix attached, so symbol-table lookups silently missed and every
    contraction dim fell back to 1 (under-counting loop-nest FLOPs ~64x in
    the nested-scan test).  Scanning the balanced paren group for ``%names``
    handles both the typed and the bare (``dot(%a, %b)``) forms, as well as
    tuple-typed operands with nested parens.
    """
    s = rest.strip()
    if not s.startswith("("):
        return []
    depth, end = 0, -1
    for i, ch in enumerate(s):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    if end < 0:
        return []
    return _NAME_RE.findall(s[: end + 1])


def _parse_op_line(line: str):
    """'  ROOT %x = (s32[], /*index=1*/f32[2]{0}) while(%t), ...' -> _Op.

    Hand-rolled because tuple types embed /*index=N*/ comments and layout
    braces that defeat any simple regex.
    """
    s = line.strip()
    is_root = s.startswith("ROOT ")
    if is_root:
        s = s[5:]
    if not s.startswith("%"):
        return None
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[:eq].strip()
    rest = s[eq + 3:]
    if rest.startswith("("):  # tuple type: scan balanced parens
        depth = 0
        end = -1
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        type_str, tail = rest[: end + 1], rest[end + 1:]
    else:
        m = _TYPE_RE.match(rest)
        if not m:
            return None
        type_str, tail = m.group(0), rest[m.end():]
    tail = tail.strip()
    m = re.match(r"([\w\-]+)", tail)
    if not m:
        return None
    return _Op(name.lstrip("%"), type_str, m.group(1), tail[m.end():],
               is_root)


def _type_info(type_str: str):
    """-> (bytes_total, elems_total, dims of first array)."""
    total_b, total_e, first_dims = 0, 0, None
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        d = [int(x) for x in dims.split(",") if x] if dims else []
        n = math.prod(d) if d else 1
        total_e += n
        total_b += n * _DTYPE_BYTES[dt]
        if first_dims is None:
            first_dims = d
    return total_b, total_e, first_dims if first_dims is not None else []


@dataclasses.dataclass
class CostReport:
    flops: float = 0.0
    bytes: float = 0.0
    collectives: dict = dataclasses.field(default_factory=dict)

    def add(self, other: "CostReport", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        for k, v in other.collectives.items():
            slot = self.collectives.setdefault(
                k, {"count": 0, "operand_bytes": 0, "result_bytes": 0})
            for f in slot:
                slot[f] += v[f] * mult

    @property
    def collective_bytes(self) -> float:
        """Data-moved model: max(operand, result) per collective kind."""
        return sum(max(v["operand_bytes"], v["result_bytes"])
                   for v in self.collectives.values())


@dataclasses.dataclass
class _Op:
    name: str
    type_str: str
    opcode: str
    rest: str
    is_root: bool = False


def _parse_computations(text: str) -> dict[str, list[_Op]]:
    comps: dict[str, list[_Op]] = {}
    cur: list[_Op] | None = None
    for line in text.splitlines():
        if not line.startswith((" ", "\t")) and line.rstrip().endswith("{"):
            m = _HEADER_RE.match(line.strip())
            if m:
                name = m.group(1).lstrip("%")
                cur = []
                comps[name] = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        op = _parse_op_line(line)
        if op is not None:
            cur.append(op)
    return comps


def _dot_flops(op: _Op, symtab: dict[str, str]) -> float:
    _, out_elems, _ = _type_info(op.type_str)
    operands = _operand_names(op.rest)
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
    k = 1
    if cm and operands:
        lhs_t = symtab.get(operands[0], "")
        _, _, lhs_dims = _type_info(lhs_t)
        for idx in (int(i) for i in cm.group(1).split(",") if i):
            if idx < len(lhs_dims):
                k *= lhs_dims[idx]
    return 2.0 * out_elems * k


def _cc_flops(op: _Op, symtab: dict[str, str]) -> float:
    """Custom-call matmuls (oneDNN etc.): assume lhs (.., M, K) x rhs (.., K, N)."""
    if not re.search(r'custom_call_target="[^"]*(matmul|gemm|dot)[^"]*"',
                     op.rest, re.I):
        return 0.0
    _, out_elems, _ = _type_info(op.type_str)
    operands = _operand_names(op.rest)
    if operands:
        _, _, lhs_dims = _type_info(symtab.get(operands[0], ""))
        if lhs_dims:
            return 2.0 * out_elems * lhs_dims[-1]
    return 0.0


_TRANSPARENT = {"convert", "copy", "bitcast", "reshape", "transpose"}


def _first_operands(op: "_Op") -> list[str]:
    return _operand_names(op.rest)


def _build_alias_ctx(comps):
    """Per-computation: name->op map + convert-only-fusion alias set.

    XLA:CPU's float normalization wraps every bf16 value in f32 convert
    round-trips (bf16 dots are unsupported on CPU); on TPU none of those
    converts exist.  ``charge`` therefore resolves an operand through
    transparent ops (convert/copy/bitcast/...) and convert-only fusions and
    charges the MINIMUM bytes along the chain -- the true (bf16) tensor.
    """
    by_name = {c: {o.name: o for o in ops} for c, ops in comps.items()}
    convert_only_fusion: set[str] = set()
    for c, ops in comps.items():
        if all(o.opcode in _TRANSPARENT or o.opcode == "parameter"
               for o in ops):
            convert_only_fusion.add(c)
    return by_name, convert_only_fusion


def _charge(comp: str, name: str, by_name, convert_only, depth=12) -> float:
    """Bytes to charge for reading operand ``name`` in ``comp``."""
    best = None
    cur = name
    for _ in range(depth):
        op = by_name.get(comp, {}).get(cur)
        if op is None:
            break
        b = _type_info(op.type_str)[0]
        best = b if best is None else min(best, b)
        if op.opcode in _TRANSPARENT:
            ops_ = _first_operands(op)
            if len(ops_) == 1:
                cur = ops_[0]
                continue
        if op.opcode == "fusion":
            m = re.search(r"calls=(%[\w\.\-]+)", op.rest)
            if m and m.group(1).lstrip("%") in convert_only:
                ops_ = _first_operands(op)
                if len(ops_) >= 1:
                    cur = ops_[0]
                    continue
        break
    return best if best is not None else 0.0


def _fusion_traffic(op, operands, res_bytes, symtab, comps, called,
                    comp, by_name, convert_only) -> float:
    """Traffic of one fusion call.

    A fusion reads each input once and writes its output once -- except
    inputs that are only *sliced* inside (the TPU DMA fetches the slice,
    not the buffer) and in-place dynamic-update-slice roots (the big
    operand aliases the output; only the update slice is written).
    Convert chains inside the body are transparent (CPU float
    normalization artifacts).
    """
    fname = called(op, "calls")
    body = comps.get(fname)
    if body is None:
        return res_bytes + sum(
            _charge(comp, o, by_name, convert_only) for o in operands)
    if fname in convert_only:
        return 0.0  # pure dtype round-trip: does not exist on TPU
    bsym = {o.name: o for o in body}
    # intra-body alias map through transparent single-operand ops
    def resolve(nm, depth=12):
        for _ in range(depth):
            o = bsym.get(nm)
            if o is None or o.opcode not in _TRANSPARENT:
                return nm
            ops_ = _first_operands(o)
            if len(ops_) != 1:
                return nm
            nm = ops_[0]
        return nm

    pname = {}
    for o in body:
        if o.opcode == "parameter":
            m = re.match(r"\((\d+)\)", o.rest.strip())
            if m:
                pname[int(m.group(1))] = o.name
    param_names = set(pname.values())

    sliced_bytes: dict[str, float] = {}
    dus_target: set[str] = set()
    root_update = None
    for o in body:
        onames = [resolve(x) for x in _first_operands(o)]
        if o.opcode in ("dynamic-slice", "slice", "gather") and onames:
            tgt = onames[0]
            rb = min(_type_info(o.type_str)[0],
                     _charge(comp, op.name, by_name, convert_only) or 1 << 62)
            sliced_bytes[tgt] = sliced_bytes.get(tgt, 0.0) +                 _type_info(o.type_str)[0]
            del rb
        elif o.opcode not in _TRANSPARENT and o.opcode != "parameter":
            for x in onames:
                if x in param_names:
                    sliced_bytes[x] = float("inf")
        if o.opcode == "dynamic-update-slice" and onames:
            root_of = resolve(next((r.name for r in body if r.is_root), ""))
            if o.name == root_of or o.is_root:
                dus_target.add(onames[0])
                raw = _first_operands(o)
                if len(raw) >= 2:
                    upd = bsym.get(resolve(raw[1]))
                    if upd is not None:
                        root_update = _type_info(upd.type_str)[0]
    total = 0.0
    for i, oname in enumerate(operands):
        full = _charge(comp, oname, by_name, convert_only)
        internal = pname.get(i)
        if internal in dus_target:
            continue  # aliased in-place output target
        sb = sliced_bytes.get(internal)
        if sb is not None and sb != float("inf"):
            total += min(full, sb)
        else:
            total += full
    if root_update is not None:
        total += root_update  # only the update slice is written
    else:
        # output: charge the smaller of declared result vs its bf16 source
        total += res_bytes
    return total


def analyze_hlo(text: str) -> CostReport:
    comps = _parse_computations(text)
    # symbol table per computation: op name -> type string
    symtabs = {c: {o.name: o.type_str for o in ops} for c, ops in comps.items()}
    by_name, convert_only = _build_alias_ctx(comps)

    # which computations are fusion bodies (register-resident, no traffic)
    fusion_bodies: set[str] = set()
    for ops in comps.values():
        for op in ops:
            if op.opcode == "fusion":
                fm = re.search(r"calls=(%[\w\.\-]+)", op.rest)
                if fm:
                    fusion_bodies.add(fm.group(1).lstrip("%"))

    entry = None
    for name in comps:
        if name.startswith("main") or entry is None:
            if name.startswith("main"):
                entry = name
    if entry is None:
        raise ValueError("no entry computation found")

    def called(op: _Op, key: str) -> str | None:
        m = re.search(key + r"=(%[\w\.\-]+)", op.rest)
        return m.group(1).lstrip("%") if m else None

    def trip_count(op: _Op) -> float:
        m = _TRIP_RE.search(op.rest)
        if m:
            return float(m.group(1))
        cond = called(op, "condition")
        if cond and cond in comps:
            consts = [float(c) for o in comps[cond]
                      for c in re.findall(r"constant\((\d+)\)", o.rest)]
            if consts:
                return max(consts)
        return 1.0

    memo: dict[tuple[str, bool], CostReport] = {}

    def analyze(comp: str, in_fusion: bool) -> CostReport:
        key = (comp, in_fusion)
        if key in memo:
            return memo[key]
        rep = CostReport()
        memo[key] = rep
        symtab = symtabs.get(comp, {})
        for op in comps.get(comp, []):
            res_bytes, res_elems, _ = _type_info(op.type_str)
            oc = op.opcode
            # ---- flops -------------------------------------------------------
            if oc == "dot":
                rep.flops += _dot_flops(op, symtab)
            elif oc == "custom-call":
                rep.flops += _cc_flops(op, symtab)
            elif oc in _ELEMENTWISE:
                rep.flops += res_elems
            elif oc in ("reduce", "reduce-window", "scatter"):
                # approx: one op per input element of the reduced operand
                ops_ = _operand_names(op.rest)
                in_elems = sum(_type_info(symtab.get(o, ""))[1] for o in ops_[:1])
                rep.flops += max(in_elems, res_elems)
            # ---- collectives ---------------------------------------------------
            for cop in _COLLECTIVES:
                if oc == cop or oc == cop + "-start":
                    operands = _operand_names(op.rest)
                    ob = sum(_type_info(symtab.get(o, ""))[0] for o in operands)
                    slot = rep.collectives.setdefault(
                        cop, {"count": 0, "operand_bytes": 0, "result_bytes": 0})
                    slot["count"] += 1
                    slot["operand_bytes"] += ob
                    slot["result_bytes"] += res_bytes
            # ---- bytes (traffic at fusion boundaries) ---------------------------
            if not in_fusion and oc in _TRAFFIC_OPS:
                operands = _operand_names(op.rest)
                if oc == "dynamic-update-slice" and len(operands) >= 2:
                    # in-place: read + write only the updated slice
                    upd = _charge(comp, operands[1], by_name, convert_only)
                    rep.bytes += 2 * upd
                elif oc in ("dynamic-slice", "gather"):
                    rep.bytes += 2 * res_bytes  # read slice + write out
                elif oc == "scatter" and len(operands) >= 3:
                    upd = _charge(comp, operands[2], by_name, convert_only)
                    rep.bytes += 2 * upd
                elif oc == "fusion":
                    rep.bytes += _fusion_traffic(op, operands, res_bytes,
                                                 symtab, comps, called,
                                                 comp, by_name, convert_only)
                else:
                    opb = sum(_charge(comp, o, by_name, convert_only)
                              for o in operands)
                    rep.bytes += res_bytes + opb
            # ---- control flow ----------------------------------------------------
            if oc == "while":
                body = called(op, "body")
                cond = called(op, "condition")
                n = trip_count(op)
                if body in comps:
                    rep.add(analyze(body, in_fusion), n)
                if cond in comps:
                    rep.add(analyze(cond, in_fusion), n + 1)
            elif oc == "fusion":
                f = called(op, "calls")
                if f in comps:
                    rep.add(analyze(f, True), 1.0)
            elif oc == "conditional":
                branches = re.findall(r"branch_computations=\{([^}]*)\}", op.rest)
                names = []
                if branches:
                    names = [b.strip().lstrip("%") for b in branches[0].split(",")]
                else:
                    for key in ("true_computation", "false_computation"):
                        c = called(op, key)
                        if c:
                            names.append(c)
                subs = [analyze(b, in_fusion) for b in names if b in comps]
                if subs:
                    worst = max(subs, key=lambda r: r.flops)
                    rep.add(worst, 1.0)
            elif oc == "call":
                c = called(op, "to_apply")
                if c in comps:
                    rep.add(analyze(c, in_fusion), 1.0)
        return rep

    return analyze(entry, False)
