"""Trip-count-aware FLOPs/bytes analysis of optimized HLO text.

``compiled.cost_analysis()`` visits a ``while`` body exactly ONCE, so any
model using ``lax.scan`` (scan-over-layers, chunked attention, recurrent
SSMs) is undercounted by the loop trip count — verified empirically in this
repo (scan of 10 matmuls reports 1/10 the FLOPs of the unrolled version).

This module parses the post-SPMD optimized HLO (``compiled.as_text()``),
recursively multiplying called-computation costs by while-loop trip counts
(extracted from the loop condition's compare-against-constant), giving the
numbers the §Roofline table actually needs:

  * FLOPs: dot (2·result·contracted), convolution, elementwise arith,
    reduce / reduce-window ops;
  * bytes: per top-level op, operands + results (fusions count as one op —
    matching HloCostAnalysis semantics), while-loops trip-multiplied.

Both are per-device (the module is the partitioned one).
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "sign", "floor", "ceil", "round-nearest-afz", "not",
    "and", "or", "xor", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "atan2", "remainder",
}
TRANSCENDENTAL = {
    "exponential", "exponential-minus-one", "log", "log-plus-one", "rsqrt",
    "sqrt", "cbrt", "tanh", "sine", "cosine", "power", "logistic", "erf",
    "expm1", "log1p",
}
FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "copy", "copy-start", "copy-done", "after-all", "partition-id",
    "replica-id", "iota", "broadcast", "reshape", "transpose", "slice",
    "concatenate", "dynamic-slice", "dynamic-update-slice", "pad", "reverse",
    "convert", "select", "compare", "clamp", "gather", "scatter", "rng",
    "custom-call", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all", "collective-permute", "optimization-barrier", "domain",
    "send", "recv", "send-done", "recv-done", "infeed", "outfeed", "map",
    "reduce-precision", "real", "imag", "is-finite", "stochastic-convert",
}


def _shape_elems(sig: str) -> int:
    total = 0
    for _, dims in _SHAPE_RE.findall(sig):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n
    return total


def _shape_bytes(sig: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(sig):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES.get(dtype, 4)
    return total


def _first_shape_dims(sig: str) -> list[int]:
    m = _SHAPE_RE.search(sig)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclass
class Instr:
    name: str
    sig: str
    op: str
    args: list[str]
    attrs: str


@dataclass
class Computation:
    name: str
    instrs: list[Instr] = field(default_factory=list)
    symbols: dict[str, str] = field(default_factory=dict)  # %name -> sig


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\((.*)\)\s*->")


def _balanced(s: str, start: int) -> int:
    """Index just past the paren group opening at s[start] == '('."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def _parse_rhs(rhs: str):
    """'<sig> <op>(<args>)<attrs>' with possibly-tuple sig (spaces inside)."""
    rhs = rhs.strip()
    if rhs.startswith("("):  # tuple shape
        end = _balanced(rhs, 0)
        sig, rest = rhs[:end], rhs[end:].strip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        sig, rest = rhs[:sp], rhs[sp + 1 :].strip()
    par = rest.find("(")
    if par < 0:
        return None
    op = rest[:par].strip()
    if not re.fullmatch(r"[\w\-]+", op):
        return None
    aend = _balanced(rest, par)
    args = rest[par + 1 : aend - 1]
    attrs = rest[aend:]
    return sig, op, args, attrs


def parse_hlo(text: str) -> tuple[dict[str, Computation], str]:
    comps: dict[str, Computation] = {}
    entry = ""
    cur: Computation | None = None
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("//") or line.startswith("HloModule"):
            continue
        if line.endswith("{") and ("->" in line or line.startswith("ENTRY")):
            m = _COMP_HDR.match(line)
            if m:
                cur = Computation(m.group(1))
                comps[cur.name] = cur
                if line.startswith("ENTRY"):
                    entry = cur.name
                # parameters: "%p.1: f32[2,3]" pairs
                for pname, psig in re.findall(
                    r"%?([\w\.\-]+):\s*(\(?[\w\[\],\s]*\)?)", m.group(2)
                ):
                    cur.symbols[pname] = psig
            continue
        if line == "}" or line.startswith("}"):
            cur = None
            continue
        if cur is None or " = " not in line:
            continue
        lhs, _, rhs = line.partition(" = ")
        name = lhs.replace("ROOT", "").strip().lstrip("%")
        parsed = _parse_rhs(rhs)
        if parsed is None or not re.fullmatch(r"[\w\.\-]+", name):
            continue
        sig, op, args, attrs = parsed
        arg_names = [
            a.strip().lstrip("%").split(" ")[0] for a in _split_args(args)
        ]
        cur.symbols[name] = sig.strip()
        cur.instrs.append(Instr(name, sig.strip(), op, arg_names, attrs))
    return comps, entry


def _split_args(s: str) -> list[str]:
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return [a for a in (x.strip() for x in out) if a]


_CALLS = re.compile(r"calls=%?([\w\.\-]+)")
_COND = re.compile(r"condition=%?([\w\.\-]+)")
_BODY = re.compile(r"body=%?([\w\.\-]+)")
_LHS_C = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_LHS_B = re.compile(r"lhs_batch_dims=\{([0-9,]*)\}")
_TO_APPLY = re.compile(r"to_apply=%?([\w\.\-]+)")
_WINDOW_SIZE = re.compile(r"size=([0-9x]+)")


class HloCost:
    def __init__(self, text: str):
        self.comps, self.entry = parse_hlo(text)
        self._flops_memo: dict[str, float] = {}
        self._bytes_memo: dict[str, float] = {}

    # ---- trip counts -------------------------------------------------------
    def trip_count(self, cond_name: str) -> int:
        comp = self.comps.get(cond_name)
        if comp is None:
            return 1
        consts: dict[str, int] = {}
        # constants parse as op 'constant' with the value in the args slot
        for ins in comp.instrs:
            if ins.op == "constant" and ins.args:
                try:
                    consts[ins.name] = int(ins.args[0])
                except ValueError:
                    pass
        for ins in comp.instrs:
            if ins.op == "compare":
                for a in ins.args:
                    if a in consts:
                        return max(int(consts[a]), 1)
        if consts:
            return max(max(consts.values()), 1)
        return 1

    # ---- flops ---------------------------------------------------------------
    def comp_flops(self, name: str) -> float:
        if name in self._flops_memo:
            return self._flops_memo[name]
        comp = self.comps.get(name)
        if comp is None:
            return 0.0
        total = 0.0
        self._flops_memo[name] = 0.0  # cycle guard
        for ins in comp.instrs:
            total += self.instr_flops(comp, ins)
        self._flops_memo[name] = total
        return total

    def instr_flops(self, comp: Computation, ins: Instr) -> float:
        op = ins.op
        if op == "dot":
            lhs_sig = comp.symbols.get(ins.args[0], "")
            lhs_dims = _first_shape_dims(lhs_sig)
            m = _LHS_C.search(ins.attrs)
            contracted = 1
            if m and m.group(1):
                for d in m.group(1).split(","):
                    if int(d) < len(lhs_dims):
                        contracted *= lhs_dims[int(d)]
            return 2.0 * _shape_elems(ins.sig) * contracted
        if op == "convolution":
            m = _WINDOW_SIZE.search(ins.attrs)
            ksize = 1
            if m:
                for d in m.group(1).split("x"):
                    ksize *= int(d)
            lhs_dims = _first_shape_dims(comp.symbols.get(ins.args[0], ""))
            cin = lhs_dims[-1] if lhs_dims else 1
            return 2.0 * _shape_elems(ins.sig) * ksize * cin
        if op == "fusion" or op == "call":
            m = _CALLS.search(ins.attrs) or _TO_APPLY.search(ins.attrs)
            return self.comp_flops(m.group(1)) if m else 0.0
        if op == "while":
            c = _COND.search(ins.attrs)
            b = _BODY.search(ins.attrs)
            trips = self.trip_count(c.group(1)) if c else 1
            body = self.comp_flops(b.group(1)) if b else 0.0
            cond = self.comp_flops(c.group(1)) if c else 0.0
            return trips * (body + cond)
        if op == "conditional":
            subs = re.findall(r"(?:branch_computations=\{([^}]*)\}|"
                              r"true_computation=%?([\w\.\-]+)|"
                              r"false_computation=%?([\w\.\-]+))", ins.attrs)
            tot = 0.0
            for g in subs:
                for s in g:
                    if s:
                        for nm in s.split(","):
                            tot = max(tot, self.comp_flops(nm.strip().lstrip("%")))
            return tot
        if op in ("reduce", "reduce-window"):
            in_elems = sum(
                _shape_elems(comp.symbols.get(a, "")) for a in ins.args[:1]
            )
            return float(in_elems)
        if op in ELEMENTWISE:
            return float(_shape_elems(ins.sig))
        if op in TRANSCENDENTAL:
            return float(_shape_elems(ins.sig))
        if op in ("all-reduce", "reduce-scatter"):
            return float(_shape_elems(ins.sig))
        return 0.0

    # ---- bytes -----------------------------------------------------------------
    def comp_bytes(self, name: str) -> float:
        if name in self._bytes_memo:
            return self._bytes_memo[name]
        comp = self.comps.get(name)
        if comp is None:
            return 0.0
        self._bytes_memo[name] = 0.0
        total = 0.0
        for ins in comp.instrs:
            op = ins.op
            if op == "while":
                c = _COND.search(ins.attrs)
                b = _BODY.search(ins.attrs)
                trips = self.trip_count(c.group(1)) if c else 1
                total += trips * (self.comp_bytes(b.group(1)) if b else 0.0)
                continue
            if op in ("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast", "after-all"):
                continue
            # op (incl. fusion as one unit): operands + result
            total += _shape_bytes(ins.sig)
            for a in ins.args:
                total += _shape_bytes(comp.symbols.get(a, ""))
        self._bytes_memo[name] = total
        return total

    def entry_flops(self) -> float:
        return self.comp_flops(self.entry)

    def entry_bytes(self) -> float:
        return self.comp_bytes(self.entry)


def analyze(text: str) -> dict[str, float]:
    h = HloCost(text)
    return {"flops": h.entry_flops(), "bytes": h.entry_bytes()}


def _array_leaves(items):
    """Flatten nested containers to array-likes: the int8 dispatch sites
    hold structured operands — KV-cache dicts whose ``<name>_scale``
    siblings ride next to the code leaves, ``QuantizedWeight`` (a
    NamedTuple bundling ``(q, scale, …)``) — and a counter that skips
    structure undercounts exactly the f32 scale arrays the fused int8
    kernels read."""
    for a in items:
        if a is None:
            continue
        if isinstance(a, dict):
            yield from _array_leaves(a.values())
        elif isinstance(a, (list, tuple)):
            yield from _array_leaves(a)
        else:
            yield a


def est_hbm_bytes(*arrays) -> int:
    """Estimated HBM traffic for one kernel call: operands + results,
    each counted once — the same per-op convention :meth:`HloCost.comp_bytes`
    uses, applied to the abstract values a dispatch site holds (jax
    arrays, tracers, anything with ``.shape``/``.dtype``). Nested
    containers (dicts, tuples, NamedTuples like ``QuantizedWeight``) are
    flattened so the f32 scale siblings of int8 operands count — the
    fused int8-KV decode kernel reads one per-(pos, head) scale row per
    code row, and the quantized conv kernels read their weight/act scale
    arrays; skipping them undercounts int8 paths. Leaves without a
    shape/dtype (None biases, plain Python scalars) are skipped."""
    total = 0
    for a in _array_leaves(arrays):
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is None or dtype is None:
            continue
        itemsize = getattr(dtype, "itemsize", None)
        if itemsize is None:
            itemsize = DTYPE_BYTES.get(str(dtype), 4)
        total += math.prod(shape) * itemsize
    return total
