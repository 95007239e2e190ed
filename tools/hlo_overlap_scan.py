#!/usr/bin/env python
# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Static communication-overlap verification from compiled HLO text.

The overlap claim of the fused train step (`opt.make_train_step`) is that
XLA schedules the gossip's collective-permutes concurrently with
backward/update compute. This module verifies that claim from the
compiled module itself rather than from wall-clock timing:

- On TPU the compiler lowers collectives to async
  ``collective-permute-start`` / ``collective-permute-done`` pairs and the
  post-scheduling HLO text is in schedule order, so counting the compute
  instructions BETWEEN a start and its done is a direct proof the
  transfer is latency-hidden.
- The CPU backend keeps collectives as synchronous ``collective-permute``
  instructions (its async-ness lives below HLO, in the thunk runtime), so
  the same proof is run structurally instead: a def-use reachability
  analysis marks every compute instruction that is neither an ancestor
  nor a descendant of the permute — compute the scheduler is FREE to
  overlap with the transfer. A delayed (one-step-stale) program shows
  near-total independence: its permutes consume only a carried buffer.

Used by ``tests/test_overlap.py`` and ``tests/test_wire_kernels.py``.
No JAX import: pure text analysis, cheap enough to run in-process
anywhere.
"""

import json
import re
import sys

__all__ = ["scan_overlap", "COMPUTE_OPS"]

# Pallas kernels (the fused quantized wire, the flash-attention blocks)
# survive to optimized HLO as opaque `custom-call`s — Mosaic's
# ``tpu_custom_call`` on TPU, Triton's on GPU — rather than any op kind
# above. They are real compute a transfer can hide behind, so the
# parser rewrites recognized targets to this dedicated kind and counts
# it as compute; the overlap verdicts themselves are unchanged.
# (CPU ``interpret=True`` kernels discharge to plain fusions and need
# no special case.)
PALLAS_OP = "custom-call.pallas"
_PALLAS_TARGET_RE = re.compile(
    r'custom_call_target="(?:tpu_custom_call|mosaic[^"]*'
    r'|__gpu\$xla\.gpu\.triton)"'
)

# Instruction kinds that represent real compute an overlapped transfer
# could hide behind (elementwise chains are fused into `fusion` on every
# backend that matters).
COMPUTE_OPS = (
    "fusion",
    "dot",
    "convolution",
    "reduce",
    "reduce-window",
    "scatter",
    "select-and-scatter",
    PALLAS_OP,
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
}

# `%name = <shape(s)> op-name(<operands>)`, tolerant of tuple shapes and
# layout annotations.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^=]*?)\s([\w\-]+)\((.*)$"
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
# computation headers: `%name (params...) -> result {`; the parameter
# list may contain nested parens (tuple-typed params), so don't try to
# match it precisely — the `-> ... {` tail plus the no-`=` guard below
# is what distinguishes a header from an instruction
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _parse_computations(hlo_text: str):
    """-> {computation_name: [instr, ...]} with instr =
    (name, op, shape_text, operand_names, line_index)."""
    comps = {}
    current = None
    for line in hlo_text.splitlines():
        # the printer annotates long tuple types with /*index=N*/
        # comments whose `=` would trip the header-vs-instruction guard
        line = re.sub(r"/\*.*?\*/", "", line)
        mc = _COMP_RE.match(line)
        if mc and "=" not in line.split("{")[0]:
            current = mc.group(1)
            comps[current] = []
            continue
        if current is None:
            continue
        if line.strip() == "}":
            current = None
            continue
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        name, shape_text, op, rest = mi.groups()
        if op == "custom-call" and _PALLAS_TARGET_RE.search(rest):
            op = PALLAS_OP
        # operands live before the first `), attr=` break; good enough to
        # take every %ref on the line minus the instruction's own name
        operands = [o for o in _OPERAND_RE.findall(rest)]
        comps[current].append(
            (name, op, shape_text, operands, len(comps[current]))
        )
    return comps


def _reach(adj, start):
    seen, stack = set(), [start]
    while stack:
        node = stack.pop()
        for nxt in adj.get(node, ()):
            if nxt not in seen:
                seen.add(nxt)
                stack.append(nxt)
    return seen


def scan_overlap(hlo_text: str) -> dict:
    """Scan compiled HLO for collective-permute overlap evidence.

    Returns a dict with the module-level counts plus one record per
    permute: ``compute_between`` (async pairs only — compute scheduled
    between start and done, in text order, which is schedule order in
    post-scheduling TPU HLO) and ``independent_compute_ops`` (def-use
    reachability — compute ops with no dependency path to or from the
    permute, i.e. statically free to overlap with the transfer on any
    backend).
    """
    comps = _parse_computations(hlo_text)
    permutes = []
    total_compute = 0
    for comp_name, instrs in comps.items():
        by_name = {i[0]: i for i in instrs}
        users = {}
        for name, _op, _sh, operands, _pos in instrs:
            for o in operands:
                if o in by_name and o != name:
                    users.setdefault(o, []).append(name)
        producers = {
            name: [o for o in operands if o in by_name and o != name]
            for name, _op, _sh, operands, _pos in instrs
        }
        compute_idx = [
            (name, pos) for name, op, _sh, _ops, pos in instrs
            if op in COMPUTE_OPS
        ]
        total_compute += len(compute_idx)
        starts = {}
        for name, op, shape_text, operands, pos in instrs:
            if op == "collective-permute-start":
                starts[name] = (shape_text, pos)
        for name, op, shape_text, operands, pos in instrs:
            if op == "collective-permute-done":
                src = next((o for o in operands if o in starts), None)
                if src is None:
                    continue
                s_shape, s_pos = starts.pop(src)
                between = sum(
                    1 for _cn, cp in compute_idx if s_pos < cp < pos
                )
                ancestors = _reach(producers, src)
                descendants = _reach(users, name)
                independent = sum(
                    1 for cn, _cp in compute_idx
                    if cn not in ancestors and cn not in descendants
                    and cn not in (src, name)
                )
                permutes.append({
                    "kind": "async",
                    "computation": comp_name,
                    "name": src,
                    "payload_bytes": _shape_bytes(s_shape),
                    "start_pos": s_pos,
                    "done_pos": pos,
                    "compute_between": between,
                    "independent_compute_ops": independent,
                })
            elif op == "collective-permute":
                ancestors = _reach(producers, name)
                descendants = _reach(users, name)
                independent = sum(
                    1 for cn, _cp in compute_idx
                    if cn not in ancestors and cn not in descendants
                    and cn != name
                )
                permutes.append({
                    "kind": "sync",
                    "computation": comp_name,
                    "name": name,
                    "payload_bytes": _shape_bytes(shape_text),
                    "start_pos": pos,
                    "done_pos": pos,
                    "compute_between": 0,
                    "independent_compute_ops": independent,
                })
    pallas_calls = sum(
        1 for instrs in comps.values()
        for _n, op, _sh, _ops, _p in instrs if op == PALLAS_OP
    )
    async_pairs = [p for p in permutes if p["kind"] == "async"]
    return {
        "async_pairs": len(async_pairs),
        "pallas_custom_calls": pallas_calls,
        "overlapped_async_pairs": sum(
            1 for p in async_pairs if p["compute_between"] > 0
        ),
        "sync_collective_permutes": sum(
            1 for p in permutes if p["kind"] == "sync"
        ),
        "overlappable_permutes": sum(
            1 for p in permutes if p["independent_compute_ops"] > 0
        ),
        "total_compute_ops": total_compute,
        "permutes": permutes,
    }


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("hlo", help="optimized HLO text file, or - for stdin")
    args = ap.parse_args()
    text = (
        sys.stdin.read() if args.hlo == "-"
        else open(args.hlo).read()
    )
    result = scan_overlap(text)
    # the per-permute list can be large; summarize on the CLI
    summary = {k: v for k, v in result.items() if k != "permutes"}
    summary["permutes_head"] = result["permutes"][:8]
    print(json.dumps(summary, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
