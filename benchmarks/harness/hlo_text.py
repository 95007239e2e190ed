"""Static facts of a compiled step, read from its optimized HLO text.

Two uses. The count and bytes of collectives (the regex arithmetic is a
copy of ``bluefog_tpu/scaling.py``'s ``hlo_collective_stats``, which the
repo's own tests hold: a count that must repeat exactly). And the *kind*
of every instruction by name, which is what lets a device trace — whose
events carry an instruction's name and nothing else — be split into
convolution / dot work, collectives, Mosaic kernels and the rest: a
fusion is matrix work when the computation it calls holds a
``convolution`` or a ``dot``.
"""

import re

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "s4": 1, "u4": 1,
}

COLLECTIVES = (
    "collective-permute", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all",
)

# `dtype[dims]{layout} collective-permute(`: the result shape is the wire
# payload. TPU compilation lowers collectives to async -start / -done
# pairs; the -start carries the payload and is counted, the -done is not.
_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\()?\w+\[[\d,]*\][^=\n]*?)\s"
    r"(collective-permute|all-reduce|all-gather|reduce-scatter|"
    r"all-to-all)(-start)?\("
)
_SHAPE_ELEM_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# async starts whose result tuple is (operands..., results..., contexts...)
_ALIASING_STARTS = ("collective-permute", "all-gather")

# `%name = <shape> opcode(operands...), attr=..., calls=%computation`: the
# opcode is the first lower-case word after a space and before a `(`; a shape
# holds none (but may hold `/*index=5*/`, so `=` does not end it)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?\s([a-z][\w\-]*)\((.*)$"
)
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_NUMBER_RE = re.compile(r"\d+")
_MOSAIC = 'custom_call_target="tpu_custom_call"'

MATMUL_CONV, COLLECTIVE, MOSAIC, OTHER = "matmul_conv", "collective", "mosaic", "other"


def _shape_bytes(dtype, dims):
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def _instruction_bytes(shape_str, kind, is_start):
    elems = _SHAPE_ELEM_RE.findall(shape_str)
    if not shape_str.lstrip().startswith("("):
        return _shape_bytes(*elems[0]) if elems else 0
    if is_start and kind in _ALIASING_STARTS:
        data = [e for e in elems if e[1]]  # drop the scalar context lanes
        if data and len(data) % 2 == 0:
            data = data[len(data) // 2:]   # operands alias results: the results half
        return sum(_shape_bytes(*e) for e in data)
    return sum(_shape_bytes(*e) for e in elems)


def collective_stats(hlo):
    """{op kind: {"count", "bytes"}} over the collectives of ``hlo``."""
    stats = {}
    for m in _COLLECTIVE_RE.finditer(hlo):
        shape_str, kind, start = m.group(1), m.group(2), m.group(3)
        entry = stats.setdefault(kind, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += _instruction_bytes(shape_str, kind, start is not None)
    return stats


def op_kind_of_opcode(opcode):
    base = opcode[:-6] if opcode.endswith("-start") else (
        opcode[:-5] if opcode.endswith("-done") else opcode
    )
    if base in COLLECTIVES:
        return COLLECTIVE
    if opcode in ("convolution", "dot"):
        return MATMUL_CONV
    return OTHER


class HloIndex:
    """Instruction name -> kind, for every computation of the module, and
    -> the jax operation it came from (the ``op_name`` of its metadata)."""

    def __init__(self, hlo):
        self.text = hlo
        module = _MODULE_RE.search(hlo)
        self.module = module.group(1) if module else None
        self.kinds = {}
        self.op_names = {}
        holds_matmul = {}   # computation -> it holds a convolution or a dot
        fusions = []        # (instruction, called computation)
        computation = None
        for line in hlo.splitlines():
            header = _COMPUTATION_RE.match(line)
            if header and "=" not in line.split("(", 1)[0]:
                computation = header.group(1)
                holds_matmul.setdefault(computation, False)
                continue
            m = _INSTR_RE.match(line)
            if not m:
                continue
            name, opcode, rest = m.groups()
            kind = op_kind_of_opcode(opcode)
            if opcode == "custom-call" and _MOSAIC in rest:
                kind = MOSAIC
            if kind == MATMUL_CONV and computation is not None:
                holds_matmul[computation] = True
            if opcode == "fusion":
                called = _CALLS_RE.search(rest)
                if called:
                    fusions.append((name, called.group(1)))
            self.kinds[name] = kind
            op_name = _OP_NAME_RE.search(rest)
            if op_name:
                self.op_names[name] = op_name.group(1)
        for name, called in fusions:
            if holds_matmul.get(called):
                self.kinds[name] = MATMUL_CONV

    def kind(self, name):
        """Kind of the instruction a trace event names; ``None`` for a
        name this module does not hold."""
        return self.kinds.get(name)

    def family(self, name, limit=120):
        """``[kind] jax operation`` with every number starred, so that the
        48 bottleneck blocks' ``Conv_0`` backward convolutions are one
        entry of a breakdown and not 48: what a person reads. An
        instruction without metadata keeps its own (starred) name."""
        source = self.op_names.get(name) or name
        text = f"[{self.kinds.get(name, '?')}] {_NUMBER_RE.sub('*', source)}"
        return text[:limit]

    def summary(self):
        counts = {}
        for kind in self.kinds.values():
            counts[kind] = counts.get(kind, 0) + 1
        return {
            "module": self.module, "instructions": len(self.kinds),
            "by_kind": counts,
            "tpu_custom_call": self.text.count(_MOSAIC),
            "collectives": collective_stats(self.text),
        }
