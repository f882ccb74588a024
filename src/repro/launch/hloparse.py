"""Post-SPMD HLO text analysis with while-loop trip-count awareness.

XLA's `compiled.cost_analysis()` counts while-loop bodies ONCE, which makes
it useless for scan-over-layers programs (a 88-layer model reports the cost
of one layer).  This module parses `compiled.as_text()` instead:

  * splits the module into computations,
  * per computation, sums dot/conv FLOPs and collective operand bytes,
  * finds `while` ops, infers each loop's trip count from the constant in
    its condition computation (lax.scan lowers to a canonical `i < N` loop),
  * walks the call graph from ENTRY multiplying nested bodies' costs by
    their trip counts.

All numbers are per-device (the text is the per-device SPMD program).
"""
from __future__ import annotations

import collections
import dataclasses
import re

_DT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
             "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
             "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
# header params may contain tuple-typed (nested-paren) args — match prefix only
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_CALL_TARGET_RE = re.compile(r"(?:body|to_apply|branch_computations|called_computations)=\{?%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_WHILE_RE = re.compile(r"\bwhile\(")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_elems_bytes(shape_text: str):
    total_elems, total_bytes = 0, 0
    for m in _SHAPE_RE.finditer(shape_text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total_elems += n
        total_bytes += n * _DT_BYTES[dt]
    return total_elems, total_bytes


@dataclasses.dataclass
class CompStats:
    dot_flops: float = 0.0
    coll_bytes: float = 0.0
    hbm_bytes: float = 0.0  # sum of top-level op output bytes (write side)
    topk_wire_bytes: float = 0.0  # measured mask-encoded top-k payload bytes
    coll_by_op: dict = dataclasses.field(default_factory=lambda: collections.Counter())
    # (child computation, trip count, structural?) edges
    children: list = dataclasses.field(default_factory=list)


# XLA's TopK custom call: a (values[rows, k], indices[rows, k]) tuple
# from an operand [rows, D].  Operands print as bare names ("%abs.40").
_TOPK_RE = re.compile(r'custom_call_target="TopK"')
_OPERAND_RE = re.compile(r"\s*%?([\w.\-]+)")


def _is_magnitude_topk(opname: str, defs: dict, comps: dict | None) -> bool:
    """True when the top-k's operand is |x| — the wire-stage signature.

    ``repro.codecs.wire.TopKSparsify`` always ranks MAGNITUDES (top_k of
    ``abs``); other top-ks in the program (the MoE router ranks raw logits)
    are not sparsified payloads and must not count as wire bytes.  The abs
    may be a standalone op or swallowed into a fusion, so resolve one level
    of ``calls=`` indirection."""
    d = defs.get(opname, "")
    if " abs(" in d or "= abs(" in d:
        return True
    if "fusion(" in d and comps is not None:
        cm = re.search(r"calls=%?([\w.\-]+)", d)
        if cm:
            return any("abs(" in body_ln for body_ln in comps.get(cm.group(1), []))
    return False


def _topk_wire_bytes_for_line(ln: str, defs: dict,
                              comps: dict | None = None) -> float:
    """MEASURED wire bytes of one top-k op's mask-encoded payload.

    ``repro.codecs.wire.TopKSparsify`` ships a D-bit mask + k f32 survivors
    per row; the analytic formula trusts the codec's payload_shape.  Here
    the SAME quantity is derived from the compiled program instead: the
    top-k op's VALUES output [rows..., k] gives the true row count and k,
    its operand [rows..., D] gives the mask width — so sparsified payload
    bytes can be audited post-SPMD (loop trips are applied by the caller's
    walk, like every other per-computation stat).  Only MAGNITUDE top-ks
    (operand resolving to ``abs``, see :func:`_is_magnitude_topk`) count
    when ``defs`` is given — a router's top-k over raw logits is program
    control flow, not payload.  The operand's shape comes from ``defs``.
    """
    if not _TOPK_RE.search(ln):
        return 0.0
    left, _, right = ln.partition("custom-call(")
    outs = _SHAPE_RE.findall(left)
    nm = _OPERAND_RE.match(right)
    if nm is None or not _is_magnitude_topk(nm.group(1), defs, comps):
        return 0.0
    opnd = _SHAPE_RE.search(defs.get(nm.group(1), ""))
    if not outs or not opnd:
        return 0.0
    val_dims = [int(d) for d in outs[0][1].split(",") if d.strip()]
    op_dims = [int(d) for d in opnd.group(2).split(",") if d.strip()]
    if len(val_dims) < 2 or len(op_dims) < 2:
        return 0.0
    k = val_dims[-1]
    rows = 1
    for d in val_dims[:-1]:
        rows *= d
    D = op_dims[-1]
    return rows * ((D + 7) // 8 + 4 * k)


_HBM_SKIP_OPS = ("parameter(", "get-tuple-element(", "tuple(", "constant(",
                 "bitcast(", "after-all(", "partition-id(", "replica-id(")


def _hbm_bytes_for_line(ln: str, out_shape_head: str, shapes: dict) -> float:
    """HBM write bytes for one op.  dynamic-update-slice writes only the
    update operand (in-place), not the whole buffer — scan stacking would
    otherwise be overcounted by the stack length."""
    if "dynamic-update-slice(" in ln:
        m = re.search(r"dynamic-update-slice\(\s*%?[\w.\-]+\s*,\s*%?([\w.\-]+)", ln)
        if m and m.group(1) in shapes:
            _, b = _shape_elems_bytes(shapes[m.group(1)].split(" ")[0])
            return b
    _, b = _shape_elems_bytes(out_shape_head)
    return b


def split_computations(hlo: str, headers: dict | None = None) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if cur is None:
            m = _COMP_HDR_RE.match(stripped)
            if m and stripped.endswith("{"):
                cur = m.group(1)
                comps[cur] = []
                if headers is not None:
                    headers[cur] = stripped
            continue
        if stripped == "}":
            cur = None
            continue
        comps[cur].append(stripped)
    return comps


def _header_param_order(header: str) -> list[str]:
    """Param names in declaration order from a computation header."""
    m = re.search(r"\((.*)\)\s*->", header)
    if not m:
        return []
    names = []
    # params look like "name: type[...]"; tuple types add nested commas, but
    # names always precede ':' at depth 1
    depth = 0
    token = ""
    for ch in m.group(1) + ",":
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        if ch == "," and depth == 0:
            if ":" in token:
                names.append(token.split(":")[0].strip().lstrip("%"))
            token = ""
        else:
            token += ch
    return names


def _trace_trip_constant(while_line: str, comps, headers, defs) -> int | None:
    """lax.scan while: cond does compare(counter, limit); the limit is a
    carried tuple element initialized with constant(N).  Trace it."""
    cm = _COND_RE.search(while_line)
    om = re.search(r"while\(\s*%?([\w.\-]+)\s*\)", while_line)
    if not cm or not om:
        return None
    cond = cm.group(1)
    params = _header_param_order(headers.get(cond, ""))
    cmp_line = next((l for l in comps.get(cond, []) if "compare(" in l), None)
    if cmp_line is None:
        return None
    ops = re.search(r"compare\(\s*%?([\w.\-]+)\s*,\s*%?([\w.\-]+)\s*\)", cmp_line)
    if not ops:
        return None
    init_def = defs.get(om.group(1), "")
    tm = re.search(r"tuple\((.*)\)", init_def)
    init_elems = []
    if tm:
        init_elems = [t.strip().lstrip("%") for t in tm.group(1).split(",")]
    for opname in (ops.group(2), ops.group(1)):
        # direct constant in cond?
        d = defs.get(opname, "")
        km = re.search(r"constant\((\d+)\)", d)
        if km:
            return int(km.group(1))
        # tuple-element param -> init operand
        if opname in params:
            idx = params.index(opname)
            if idx < len(init_elems):
                km = re.search(r"constant\((\d+)\)", defs.get(init_elems[idx], ""))
                if km:
                    return int(km.group(1))
    return None


def _build_shape_map(comps) -> dict[str, str]:
    shapes = {}
    for lines in comps.values():
        for ln in lines:
            m = _DEF_RE.match(ln)
            if m:
                shapes[m.group(1)] = m.group(2)
    return shapes


def _dot_flops(line: str, out_shape_text: str, shapes: dict[str, str]) -> float:
    # operands print as bare names ("dot(%a, %b)"): look the lhs shape up
    _, _, right = line.partition("dot(")
    m = _OPERAND_RE.match(right)
    if not right or not m:
        return 0.0
    lhs = shapes.get(m.group(1), "")
    lhs_m = _SHAPE_RE.search(lhs)
    out_m = _SHAPE_RE.search(out_shape_text)
    if not lhs_m or not out_m:
        return 0.0
    lhs_dims = [int(d) for d in lhs_m.group(2).split(",") if d.strip()]
    out_dims = [int(d) for d in out_m.group(2).split(",") if d.strip()]
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
    contract = 1
    if cm:
        for idx in cm.group(1).split(","):
            if idx.strip():
                contract *= lhs_dims[int(idx)]
    out_elems = 1
    for d in out_dims:
        out_elems *= d
    return 2.0 * out_elems * contract


def _trip_count(cond_lines: list[str]) -> int:
    """lax.scan condition: compare(counter, constant(N)), direction=LT."""
    best = 1
    for ln in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", ln):
            best = max(best, int(m.group(1)))
    return best


def _trip_from_carry(while_line: str) -> int:
    """jax lowers scan by carrying stacked (N, ...) xs/ys in the while tuple
    and dynamic-slicing per step, so the loop length is the modal leading dim
    of the carried arrays (stacked params/ys dominate the tuple)."""
    counts = collections.Counter()
    m = re.search(r"=\s*\((.*?)\)\s*while\(", while_line)
    if not m:
        return 1
    for sm in _SHAPE_RE.finditer(m.group(1)):
        dims = [int(d) for d in sm.group(2).split(",") if d.strip()]
        if len(dims) >= 2 and dims[0] > 1:
            counts[dims[0]] += 1
    if not counts:
        return 1
    return counts.most_common(1)[0][0]


def _bf16_upcast_factor(ln: str, defs: dict, comps: dict) -> float:
    """XLA:CPU lowers bf16 dots as convert-to-f32 + f32 dot, and the SPMD
    partitioner then moves FSDP/TP all-gathers AFTER the convert — so f32
    collectives that originate from bf16 tensors are a CPU artifact; the
    TPU target gathers bf16.  Returns 0.5 for such collectives."""
    if "f32[" not in ln:
        return 1.0
    om = re.search(r"(?:all-gather|all-reduce|reduce-scatter|all-to-all|"
                   r"collective-permute)(?:-start)?\(\s*%?([\w.\-]+)", ln)
    if not om:
        return 1.0
    src_def = defs.get(om.group(1), "")
    if "convert" in src_def and "f32[" in src_def:
        cm = re.search(r"calls=%?([\w.\-]+)", src_def)
        body = "\n".join(comps.get(cm.group(1), [])) if cm else src_def
        if "bf16[" in body or "convert" in src_def:
            return 0.5
    return 1.0


def analyze(hlo: str):
    headers: dict[str, str] = {}
    comps = split_computations(hlo, headers)
    shapes = _build_shape_map(comps)
    # full def line per op name (for constant/tuple tracing)
    defs: dict[str, str] = {}
    for lines in comps.values():
        for ln in lines:
            m = _DEF_RE.match(ln)
            if m:
                defs[m.group(1)] = ln
    stats: dict[str, CompStats] = {}

    for name, lines in comps.items():
        cs = CompStats()
        for ln in lines:
            dm = _DEF_RE.match(ln)
            out_shape = dm.group(2) if dm else ln
            if " dot(" in ln or re.search(r"=\s*\S+\s+dot\(", ln):
                cs.dot_flops += _dot_flops(ln, out_shape, shapes)
            cs.topk_wire_bytes += _topk_wire_bytes_for_line(ln, defs, comps)
            if not any(skip in ln for skip in _HBM_SKIP_OPS):
                head = out_shape.split(" ")[0]
                cs.hbm_bytes += _hbm_bytes_for_line(ln, head, shapes)
            for op in _COLLECTIVES:
                if re.search(rf"\b{op}(?:-start)?\(", ln):
                    # operand bytes = output shape bytes (same size)
                    _, b = _shape_elems_bytes(out_shape.split(" ")[0])
                    b *= _bf16_upcast_factor(ln, defs, comps)
                    cs.coll_bytes += b
                    cs.coll_by_op[op] += b
                    break
            if _WHILE_RE.search(ln):
                bm = re.search(r"body=%?([\w.\-]+)", ln)
                cm = _COND_RE.search(ln)
                trip = _trip_count(comps.get(cm.group(1), [])) if cm else 1
                if trip <= 1:
                    trip = _trace_trip_constant(ln, comps, headers, defs) or \
                        _trip_from_carry(ln)
                if bm:
                    cs.children.append((bm.group(1), trip, True))
            else:
                for m in re.finditer(r"(?:to_apply|calls)=\{?%?([\w.\-]+)", ln):
                    cs.children.append((m.group(1), 1, False))
                m = re.search(r"branch_computations=\{([^}]*)\}", ln)
                if m:
                    for b in m.group(1).split(","):
                        cs.children.append((b.strip().lstrip("%"), 1, True))
        stats[name] = cs

    entry = None
    for line in hlo.splitlines():
        m = re.match(r"ENTRY\s+%?([\w.\-]+)", line.strip())
        if m:
            entry = m.group(1)
            break
    if entry is None:
        entry = next(iter(comps))

    totals = {"dot_flops": 0.0, "coll_bytes": 0.0, "hbm_bytes": 0.0,
              "topk_wire_bytes": 0.0, "coll_by_op": collections.Counter()}
    seen_stack = []

    def walk(name: str, mult: float, structural: bool):
        if name not in stats or name in seen_stack:
            return
        seen_stack.append(name)
        cs = stats[name]
        totals["dot_flops"] += mult * cs.dot_flops
        totals["coll_bytes"] += mult * cs.coll_bytes
        totals["topk_wire_bytes"] += mult * cs.topk_wire_bytes
        if structural:
            # fusion internals never touch HBM; only structural computations
            # (entry / while bodies / branches) write buffers.  x2 = read+write.
            totals["hbm_bytes"] += 2.0 * mult * cs.hbm_bytes
        for op, b in cs.coll_by_op.items():
            totals["coll_by_op"][op] += mult * b
        for child, trip, child_structural in cs.children:
            walk(child, mult * trip, child_structural)
        seen_stack.pop()

    walk(entry, 1.0, True)
    totals["coll_by_op"] = dict(totals["coll_by_op"])
    return totals


def top_hbm_ops(hlo: str, k: int = 20):
    """The k largest HBM writers (op output bytes x loop trips) — the
    profile view the §Perf hillclimbs read."""
    headers: dict[str, str] = {}
    comps = split_computations(hlo, headers)
    defs: dict[str, str] = {}
    shapes: dict[str, str] = {}
    for lines in comps.values():
        for ln in lines:
            m = _DEF_RE.match(ln)
            if m:
                defs[m.group(1)] = ln
                shapes[m.group(1)] = m.group(2)

    # computation -> multiplier (structural only), via the same walk
    mult: dict[str, float] = {}
    children: dict[str, list] = {}
    for name, lines in comps.items():
        ch = []
        for ln in lines:
            if _WHILE_RE.search(ln):
                bm = re.search(r"body=%?([\w.\-]+)", ln)
                cm = _COND_RE.search(ln)
                trip = _trip_count(comps.get(cm.group(1), [])) if cm else 1
                if trip <= 1:
                    trip = _trace_trip_constant(ln, comps, headers, defs) or \
                        _trip_from_carry(ln)
                if bm:
                    ch.append((bm.group(1), trip))
        children[name] = ch
    entry = None
    for line in hlo.splitlines():
        m = re.match(r"ENTRY\s+%?([\w.\-]+)", line.strip())
        if m:
            entry = m.group(1)
            break

    stack = [(entry, 1.0)]
    seen = set()
    while stack:
        name, m0 = stack.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        mult[name] = m0
        for child, trip in children.get(name, []):
            stack.append((child, m0 * trip))

    rows = []
    for name, m0 in mult.items():
        for ln in comps[name]:
            if any(skip in ln for skip in _HBM_SKIP_OPS):
                continue
            dm = _DEF_RE.match(ln)
            if not dm:
                continue
            head = dm.group(2).split(" ")[0]
            b = _hbm_bytes_for_line(ln, head, shapes)
            if b:
                meta = re.search(r'op_name="([^"]*)"', ln)
                rows.append((b * m0, head, meta.group(1)[:90] if meta else "",
                             name))
    rows.sort(reverse=True)
    return rows[:k]
