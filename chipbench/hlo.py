"""Class and matmul FLOPs of each instruction of a compiled program.

Reads the optimized HLO text of the step (``compiled.as_text()``) and
gives, for every instruction the device trace can name, its class and
its matmul FLOPs:

  matmul      a ``dot`` or ``convolution``, or a fusion that holds one;
              FLOPs from the shapes: 2 x the multiply-adds on real
              elements (a convolution's taps on padding or on the holes
              of lhs dilation, which the TPU compiler uses to express
              batched dots, are not counted)
  collective  an all-reduce, all-gather, reduce-scatter, all-to-all or
              collective-permute (their async start/done halves too), or
              a fusion or async wrapper that holds one
  control     while, conditional, call: they enclose other ops
  other       the rest

No byte count is kept: a fusion's operands are whole arrays of which it
may read one slice, so their sizes overstate what it moves.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

import numpy as np

DTYPES = ("pred", "s4", "u4", "s8", "u8", "s16", "u16", "s32", "u32", "s64",
          "u64", "f16", "bf16", "f32", "f64", "c64", "c128", "f8e4m3fn",
          "f8e5m2", "f8e4m3b11fnuz", "f8e4m3fnuz", "f8e5m2fnuz")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
CONTROL = ("while", "conditional", "call")

_SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")


@dataclasses.dataclass
class Instr:
    cls: str = "other"
    flops: float = 0.0


def _shapes(text: str) -> List[tuple]:
    out = []
    for dt, dims in _SHAPE.findall(text):
        if dt not in DTYPES:
            continue
        out.append((dt, [int(x) for x in dims.split(",") if x]))
    return out


def _split_call(rest: str):
    """'<type> <opcode>(<operands>), <attrs>' -> (type, opcode, operands,
    attrs); the type may be a tuple in parentheses."""
    rest = rest.strip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        typ, rest = rest[:i + 1], rest[i + 1:].strip()
    else:
        typ, _, rest = rest.partition(" ")
    m = re.match(r"([\w\-]+)\((.*)$", rest)
    if not m:
        return typ, "", "", ""
    opcode, tail = m.group(1), m.group(2)
    depth = 1
    for i, ch in enumerate(tail):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            break
    return typ, opcode, tail[:i], tail[i + 1:]


def _prod(xs) -> float:
    p = 1.0
    for x in xs:
        p *= x
    return p


def _window(attrs: str) -> Dict[str, List[int]]:
    m = re.search(r"window=\{([^}]*)\}", attrs)
    out: Dict[str, List[int]] = {}
    for item in (m.group(1).split() if m else []):
        key, _, val = item.partition("=")
        if key == "pad":
            out["pad_lo"] = [int(p.split("_")[0]) for p in val.split("x")]
        elif key != "rhs_reversal":
            out[key] = [int(v) for v in val.split("x")]
    return out


def _real_taps(n: int, m: int, k: int, stride: int, lo: int, dl: int,
               dr: int) -> int:
    """(output position, window tap) pairs of one spatial dim that land on
    a real input element: not padding, not a hole of lhs dilation."""
    o = np.arange(m)[:, None] * stride + np.arange(k)[None, :] * dr - lo
    return int(np.sum((o >= 0) & (o <= (n - 1) * dl) & (o % dl == 0)))


def _matmul_flops(opcode: str, typ: str, ops: List[tuple],
                  attrs: str) -> float:
    out = _shapes(typ)
    if not out or len(ops) < 2:
        return 0.0
    out = out[0][1]
    if opcode == "dot":
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", attrs)
        dims = [int(x) for x in m.group(1).split(",") if x] if m else []
        return 2.0 * _prod(out) * _prod(ops[0][1][d] for d in dims)
    m = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", attrs)
    if not m:
        return 0.0
    lhs_l, rhs_l, out_l = m.groups()
    lhs, rhs = ops[0][1], ops[1][1]
    if len(lhs_l) != len(lhs) or len(rhs_l) != len(rhs) or \
            len(out_l) != len(out):
        return 0.0
    w = _window(attrs)
    macs = out[out_l.index("b")] * out[out_l.index("f")] * \
        rhs[rhs_l.index("i")]
    for d in range(len(out_l) - 2):
        c = str(d)

        def get(key, default):
            return w[key][d] if key in w else default
        macs *= _real_taps(lhs[lhs_l.index(c)], out[out_l.index(c)],
                           get("size", rhs[rhs_l.index(c)]),
                           get("stride", 1), get("pad_lo", 0),
                           get("lhs_dilate", 1), get("rhs_dilate", 1))
    return 2.0 * macs


def parse(text: str) -> Dict[str, Instr]:
    """Every instruction of the module by name, with fusions and async
    wrappers carrying what their called computations hold."""
    comps: Dict[str, List[tuple]] = {}
    current: Optional[str] = None
    for line in text.splitlines():
        if current is None:
            m = _COMP.match(line)
            if m:
                current = m.group(1)
                comps[current] = []
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR.match(line)
        if m:
            typ, opcode, operands, attrs = _split_call(m.group(2))
            comps[current].append((m.group(1), typ, opcode, operands, attrs))

    types = {name: typ for instrs in comps.values()
             for name, typ, *_ in instrs}

    def operand_shapes(operands: str) -> List[tuple]:
        """Shapes printed inline, else those of the named operands."""
        inline = _shapes(operands)
        if inline:
            return inline
        out = []
        for ref in re.findall(r"%([\w.\-]+)", operands):
            out.extend(_shapes(types.get(ref, "")))
        return out

    memo: Dict[str, tuple] = {}

    def inner(comp: str):
        """(matmul flops, holds a collective) of a called computation."""
        if comp in memo:
            return memo[comp]
        memo[comp] = (0.0, False)
        flops, coll = 0.0, False
        for _, typ, opcode, operands, attrs in comps.get(comp, []):
            f, c = own(typ, opcode, operands, attrs)
            flops += f
            coll = coll or c
        memo[comp] = (flops, coll)
        return memo[comp]

    def own(typ, opcode, operands, attrs):
        if opcode in ("dot", "convolution"):
            return _matmul_flops(opcode, typ, operand_shapes(operands),
                                 attrs), False
        if re.sub(r"-(start|done|update)$", "", opcode) in COLLECTIVES:
            return 0.0, True
        if opcode in ("fusion", "async-start", "async-done", "async-update"):
            m = re.search(r"calls=%?([\w.\-]+)", attrs)
            if m:
                return inner(m.group(1))
        return 0.0, False

    out: Dict[str, Instr] = {}
    for comp, instrs in comps.items():
        for name, typ, opcode, operands, attrs in instrs:
            flops, coll = own(typ, opcode, operands, attrs)
            cls = "control" if opcode in CONTROL else "collective" if coll \
                else "matmul" if flops > 0 else "other"
            out[name] = Instr(cls=cls, flops=flops)
    return out
