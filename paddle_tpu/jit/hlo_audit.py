"""Per-op HLO cost audit — where a compiled step's bytes and FLOPs go.

XLA's ``cost_analysis()`` reports one aggregate number per executable;
that is enough for MFU accounting (``FusedTrainStep.lowered_flops``) but
useless for *finding* the op that eats the bandwidth. PERF.md's lesson is
that the only fusions worth writing are cross-op HBM-traffic removals XLA
cannot see — so the campaign needs a per-op ledger of the OPTIMIZED HLO
(post-fusion, the program that actually runs), not guesses.

This module parses ``compiled.as_text()`` — the scheduled HLO module —
and assigns each entry-computation instruction:

- **bytes**: estimated memory traffic. Elementwise/reduce ops read their
  operands and write their result in full; ``dynamic-slice``/``gather``
  read only the addressed region (a 1M-row table behind a gather costs
  row traffic, not a table stream); ``dynamic-update-slice`` aliases its
  buffer and touches only the update region. A ``fusion`` charges its
  result plus each external operand at the granularity the fused body
  actually touches it (an operand consumed solely through slices/gathers
  counts region reads). ``while``/``call`` are costed per iteration of
  their body × a trip count recovered from the loop condition's bound
  constant — loop-carried buffers are updated in place, not streamed.
- **flops**: ``dot``/``convolution`` from their contraction shapes
  (2*MNK-style), elementwise/reduce ops one per output element, data
  movement zero; fusions/loops sum (×trip) their bodies.

These are first-order estimates for *ranking*, not for MFU — the
aggregate backend number stays authoritative and is reported alongside.
The audit is how ISSUE 6's acceptance is checked mechanically: on the
lazy-Adam path, deepfm's top-bytes table must no longer contain
vocab-sized dense scatter/update ops (``vocab_sized_ops``)."""

from __future__ import annotations

import re

__all__ = ["parse_hlo_costs", "audit", "format_table", "vocab_sized_ops"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"          # instruction name
    r"((?:\([^=]*?\))|(?:[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?))\s+"
    r"([\w\-]+)\(")                                  # opcode
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\([^)]*\)\s*->.*\{\s*$")
_CALLS_RE = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_DIM_LABELS_RE = re.compile(r"dim_labels=([\w]+)_([\w]+)->([\w]+)")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
    "abs", "negate", "sign", "exponential", "exponential-minus-one", "log",
    "log-plus-one", "sqrt", "rsqrt", "cbrt", "tanh", "logistic", "sine",
    "cosine", "tan", "atan2", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "remainder", "and", "or", "xor", "not", "compare",
    "select", "clamp", "convert", "is-finite", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "popcnt", "clz",
    "stochastic-convert", "erf",
}
# no traffic of their own inside a costed scope (reads are charged to the
# consuming op; metadata/layout ops are free)
_FREE = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "broadcast", "reshape", "transpose", "iota",
    "after-all", "partition-id", "replica-id", "optimization-barrier",
    "copy-start", "copy-done",
}
_CONTROL = {"while", "call", "conditional"}


def _shape_tokens(text):
    """All (dtype, dims tuple) shape tokens in an HLO text fragment."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        out.append((dt, dims))
    return out


def _nbytes(tok):
    dt, dims = tok
    n = _DTYPE_BYTES[dt]
    for d in dims:
        n *= d
    return n


def _numel(dims):
    n = 1
    for d in dims:
        n *= d
    return n


def _strip_tail(line):
    """Drop metadata=/backend_config= tails whose strings can hold
    anything shape-regex-like."""
    return re.split(r",\s*(?:metadata|backend_config|sharding)=", line)[0]


def _operand_names(operand_txt):
    """The ``%name`` operands inside an instruction's own parentheses
    (``operand_txt`` starts right after the opening one)."""
    depth, end = 1, len(operand_txt)
    for i, ch in enumerate(operand_txt):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            end = i
            break
    return re.findall(r"%([\w.\-]+)", operand_txt[:end])


class _Instr:
    __slots__ = ("name", "opcode", "result_txt", "results", "operands",
                 "operand_names", "line", "is_root")

    def __init__(self, name, opcode, result_txt, line, operand_txt):
        self.name = name
        self.opcode = opcode
        self.result_txt = result_txt
        self.results = _shape_tokens(result_txt)
        # operand_txt starts right after the opcode's opening paren, so
        # operands[0] is the first REAL operand (never the result token)
        self.operands = _shape_tokens(_strip_tail(operand_txt))
        self.operand_names = _operand_names(operand_txt)
        self.line = line
        self.is_root = line.startswith("ROOT ")


def _resolve_operands(instrs):
    """jax 0.9 prints operands by name alone (``dot(%a.1, %b.1)``): an
    instruction that carries no operand shape inline takes each operand's
    from the instruction of that name in its own computation (the first
    element of a tuple), so that a ``dot``'s contracted size, a
    ``convolution``'s kernel and every operand read are known again."""
    shapes = {ins.name: ins.results for ins in instrs}
    for ins in instrs:
        if not ins.operands:
            ins.operands = [shapes[n][0] for n in ins.operand_names
                            if shapes.get(n)]


def _parse_computations(hlo_text):
    """{computation name: (is_entry, [_Instr])}."""
    comps = {}
    cur = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        m = _COMP_RE.match(line)
        if m:
            cur = []
            comps[m.group(2)] = (bool(m.group(1)), cur)
            continue
        if cur is None:
            continue
        if line == "}":
            _resolve_operands(cur)
            cur = None
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            cur.append(_Instr(mi.group(1), mi.group(3), mi.group(2), line,
                              line[mi.end():]))
    return comps


def _instr_flops(ins):
    """First-order FLOP estimate for one non-control instruction."""
    res_elems = sum(_numel(d) for _, d in ins.results)
    op = ins.opcode
    if op == "dot":
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.line)
        if ins.operands and m:
            lhs = ins.operands[0][1]
            k = 1
            for i in (int(x) for x in m.group(1).split(",") if x):
                if i < len(lhs):
                    k *= lhs[i]
            return 2.0 * res_elems * k
        return 2.0 * res_elems
    if op == "convolution":
        # contraction extent per output element = input-feature size x the
        # WINDOW footprint (the window attribute stays truthful for
        # gradient convs, where the kernel operand is a big activation and
        # prod(rhs)/out_channels would overcount by orders of magnitude)
        m = _DIM_LABELS_RE.search(ins.line)
        wm = re.search(r"window=\{size=([0-9x]+)", ins.line)
        if len(ins.operands) >= 2 and m:
            rhs_labels = m.group(2)
            rhs = ins.operands[1][1]
            i_idx = rhs_labels.find("i")
            if wm and 0 <= i_idx < len(rhs):
                k = rhs[i_idx]
                for w in wm.group(1).split("x"):
                    k *= int(w)
            else:
                k = _numel(rhs)
                o_idx = rhs_labels.find("o")
                if 0 <= o_idx < len(rhs) and rhs[o_idx]:
                    k //= rhs[o_idx]
            return 2.0 * res_elems * k
        return 2.0 * res_elems
    if op in ("reduce", "reduce-window", "all-reduce"):
        return float(_numel(ins.operands[0][1]) if ins.operands
                     else res_elems)
    if op == "scatter":
        upd = ins.operands[2][1] if len(ins.operands) >= 3 else ()
        return float(_numel(upd)) if upd else float(res_elems)
    if op in ("map", "sort"):
        return float(res_elems)
    if op in _ELEMENTWISE:
        return float(res_elems)
    return 0.0


def _instr_bytes(ins):
    """Region-granular traffic estimate for one non-control, non-fusion
    instruction inside a costed scope."""
    op = ins.opcode
    res = sum(_nbytes(t) for t in ins.results)
    if op in _FREE:
        return 0.0
    if op in ("dynamic-slice", "gather"):
        # reads only the addressed region (== result), never the full
        # operand — THE distinction that keeps an embedding gather from
        # being billed a full table stream
        idx = sum(_nbytes(t) for t in ins.operands[1:])
        return float(2 * res + idx)
    if op == "dynamic-update-slice":
        # aliases operand 0; touches the update region (read+write) only
        upd = _nbytes(ins.operands[1]) if len(ins.operands) > 1 else res
        idx = sum(_nbytes(t) for t in ins.operands[2:])
        return float(2 * upd + idx)
    if op == "scatter":
        upd = _nbytes(ins.operands[2]) if len(ins.operands) >= 3 else res
        idx = _nbytes(ins.operands[1]) if len(ins.operands) >= 2 else 0
        # updates read + target regions read-modify-write
        return float(3 * upd + idx)
    if op in ("slice", "pad", "reverse", "concatenate", "copy"):
        return float(res + sum(_nbytes(t) for t in ins.operands))
    # default: full operand reads + result write
    return float(res + sum(_nbytes(t) for t in ins.operands))


def _body_cost(comp_name, comps, seen=frozenset()):
    """(bytes, flops) of one execution of a computation's body, with
    nested control flow expanded."""
    if comp_name in seen or comp_name not in comps:
        return 0.0, 0.0
    seen = seen | {comp_name}
    b = f = 0.0
    for ins in comps[comp_name][1]:
        ib, fl = _cost_one(ins, comps, seen)
        b += ib
        f += fl
    return b, f


def _trip_count(ins, comps):
    """Heuristic while-loop trip count: the largest integer bound constant
    in the loop's condition computation (the scatter/map loops this audit
    cares about compare an induction variable against a fixed bound)."""
    m = _COND_RE.search(ins.line)
    if not m or m.group(1) not in comps:
        return 1
    best = 1
    for cond_ins in comps[m.group(1)][1]:
        for c in re.finditer(r"constant\((\d+)\)", cond_ins.line):
            best = max(best, int(c.group(1)))
    return best


def _updates_in_place(ins, comps):
    """Whether a fusion's root updates one of the fused body's parameters
    in place: a ``scatter`` or ``dynamic-update-slice`` whose operand 0 is
    a parameter, so that the fusion's result is that operand's buffer and
    only the updated region moves. XLA:CPU wraps a bare scatter so
    (``wrapped_scatter``, a fusion of one instruction)."""
    for cname in _CALLS_RE.findall(ins.line):
        instrs = comps.get(cname, (False, []))[1]
        params = {i.name for i in instrs if i.opcode == "parameter"}
        for i2 in instrs:
            if i2.is_root:
                return (i2.opcode in ("scatter", "dynamic-update-slice")
                        and bool(i2.operand_names)
                        and i2.operand_names[0] in params)
    return False


def _fusion_cost(ins, comps):
    """A fusion's traffic: result write + each external operand read at
    the granularity the fused body touches it (an operand consumed only
    through gathers/slices counts those regions, not its full size).
    FLOPs: the fused body's."""
    called = _CALLS_RE.findall(ins.line)
    body_b = body_f = 0.0
    touched = {}
    for cname in called:
        if cname not in comps:
            continue
        _, instrs = comps[cname]
        params = {}  # %param name -> (index, shape token)
        for i2 in instrs:
            if i2.opcode == "parameter":
                pm = re.search(r"parameter\((\d+)\)", i2.line)
                if pm and i2.results:
                    params[i2.name] = (int(pm.group(1)), i2.results[0])
        body_f += _body_cost(cname, comps)[1]
        for pname, (pidx, ptok) in params.items():
            full = _nbytes(ptok)
            region = 0
            sliced_only = True
            for i2 in instrs:
                if i2.opcode == "parameter" or \
                        not re.search(rf"%{re.escape(pname)}\b", i2.line):
                    continue
                if re.search(rf"%{re.escape(pname)}\b",
                             _strip_tail(i2.line)
                             .split("(", 1)[-1]) is None:
                    continue
                if i2.opcode in ("dynamic-slice", "gather"):
                    region += sum(_nbytes(t) for t in i2.results)
                elif i2.opcode == "dynamic-update-slice":
                    # param updated in place: update-region traffic
                    region += 2 * (_nbytes(i2.operands[1])
                                   if len(i2.operands) > 1 else full)
                elif (i2.opcode == "scatter"
                      and i2.operand_names[:1] == [pname]):
                    # scattered into in place: the rows the updates name
                    region += 2 * (_nbytes(i2.operands[2])
                                   if len(i2.operands) > 2 else full)
                else:
                    sliced_only = False
                    break
            touched[pidx] = (min(full, region) if sliced_only and region
                             else full)
    # a result that is an operand's own buffer is written with its region
    res = (0 if _updates_in_place(ins, comps)
           else sum(_nbytes(t) for t in ins.results))
    if touched:
        nb = float(res + sum(touched.values()))
    else:
        nb = float(res + sum(_nbytes(t) for t in ins.operands))
    return nb, body_f


def _cost_one(ins, comps, seen=frozenset()):
    """(bytes, flops) for one instruction, expanding control flow."""
    if ins.opcode == "fusion":
        return _fusion_cost(ins, comps)
    if ins.opcode == "while":
        trip = _trip_count(ins, comps)
        b = f = 0.0
        for cname in _CALLS_RE.findall(ins.line):
            bb, bf = _body_cost(cname, comps, seen)
            b += bb
            f += bf
        return trip * b, trip * f
    if ins.opcode in ("call", "conditional"):
        b = f = 0.0
        for cname in _CALLS_RE.findall(ins.line):
            bb, bf = _body_cost(cname, comps, seen)
            b += bb
            f += bf
        return b, f
    if ins.opcode in ("reduce", "scatter", "sort", "map"):
        # their combine computations run per element; the element cost is
        # already in _instr_flops — don't double count the called comp
        return _instr_bytes(ins), _instr_flops(ins)
    return _instr_bytes(ins), _instr_flops(ins)


def _dense_shapes(ins, comps, seen=frozenset()):
    """Shape tokens this instruction STREAMS (not merely carries): its
    results for data ops; for control flow, recursively the non-aliasing
    body results (loop state updated via dynamic-update-slice is carried
    in place, never streamed)."""
    if ins.opcode in _CONTROL:
        out = []
        for cname in _CALLS_RE.findall(ins.line):
            if cname in seen or cname not in comps:
                continue
            for i2 in comps[cname][1]:
                out.extend(_dense_shapes(i2, comps, seen | {cname}))
        return out
    if ins.opcode in _FREE - {"broadcast"} or ins.opcode in (
            "dynamic-slice", "dynamic-update-slice", "gather", "slice"):
        return []
    if ins.opcode == "fusion" and _updates_in_place(ins, comps):
        return []
    return list(ins.results)


def parse_hlo_costs(hlo_text):
    """Per-instruction costs of the ENTRY computation of an (optimized)
    HLO module text. Returns a list of dicts:
    ``{"name", "opcode", "shape", "bytes", "flops", "op_name"}``."""
    comps = _parse_computations(hlo_text)
    entry_instrs = None
    for name, (is_entry, instrs) in comps.items():
        if is_entry:
            entry_instrs = instrs
            break
    if entry_instrs is None:
        return []
    ops = []
    for ins in entry_instrs:
        if ins.opcode in ("parameter", "constant", "tuple",
                          "get-tuple-element"):
            continue
        nb, fl = _cost_one(ins, comps)
        md = re.search(r'op_name="([^"]*)"', ins.line)
        ops.append({
            "name": ins.name,
            "opcode": ins.opcode,
            "shape": ins.result_txt.split("{")[0],
            "bytes": float(nb),
            "flops": float(fl),
            "op_name": md.group(1) if md else "",
            "_ins": ins,
        })
    return ops


def audit(compiled, top_n=None):
    """Cost report for a compiled executable (anything with ``as_text()``
    — a jax Compiled object — or a raw HLO string). Returns
    ``{"ops", "n_ops", "total_bytes", "total_flops", "backend_flops",
    "backend_bytes", "hlo_text"}`` with ``ops`` sorted by bytes
    descending (truncated to ``top_n`` when given). backend_* come from
    XLA's own aggregate ``cost_analysis`` when available — the
    authoritative totals this ranking is sanity-checked against."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    ops = parse_hlo_costs(text)
    ops.sort(key=lambda o: (-o["bytes"], -o["flops"], o["name"]))
    report = {
        "ops": ops[:top_n] if top_n else ops,
        "n_ops": len(ops),
        "total_bytes": float(sum(o["bytes"] for o in ops)),
        "total_flops": float(sum(o["flops"] for o in ops)),
        "backend_flops": None,
        "backend_bytes": None,
        "hlo_text": text,
    }
    if not isinstance(compiled, str):
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            if hasattr(ca, "get"):
                report["backend_flops"] = ca.get("flops")
                report["backend_bytes"] = ca.get("bytes accessed")
        except Exception:
            pass
    return report


def vocab_sized_ops(report, vocab, top_n=10):
    """The acceptance probe: ops among the top-``top_n`` by bytes that
    STREAM a tensor with a dimension >= ``vocab`` (covers shard-padded
    row counts). Aliased loop state and region reads (gathers/slices into
    the table) don't count — only ops that actually produce or sweep a
    vocab-sized buffer, which is exactly what the lazy path removes."""
    comps = _parse_computations(report.get("hlo_text", ""))
    hits = []
    for o in report["ops"][:top_n]:
        ins = o.get("_ins")
        toks = (_dense_shapes(ins, comps) if ins is not None
                else _shape_tokens(o["shape"]))
        if any(any(d >= vocab for d in dims) for _, dims in toks):
            hits.append(o)
    return hits


def format_table(report, top_n=15, title=None):
    """Human-readable per-op table (bytes-ranked) with totals."""
    lines = []
    if title:
        lines.append(title)
    lines.append(f"{'op':<28} {'opcode':<18} {'shape':<26} "
                 f"{'MBytes':>10} {'MFLOPs':>10}")
    lines.append("-" * 96)
    for o in report["ops"][:top_n]:
        lines.append(
            f"{o['name'][:28]:<28} {o['opcode'][:18]:<18} "
            f"{o['shape'][:26]:<26} {o['bytes'] / 1e6:>10.3f} "
            f"{o['flops'] / 1e6:>10.3f}")
    lines.append("-" * 96)
    bf = report["backend_flops"]
    bft = f"{bf / 1e6:.3f} M" if bf else "n/a"
    lines.append(
        f"{report['n_ops']} entry ops; total "
        f"{report['total_bytes'] / 1e6:.3f} MB, "
        f"{report['total_flops'] / 1e6:.3f} MFLOPs (parsed estimate); "
        f"backend cost_analysis flops: {bft}")
    return "\n".join(lines)
