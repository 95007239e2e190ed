"""From a profiler trace (``.xplane.pb``) to intervals, and the arithmetic
on intervals every per-layer reader is built from.

Read with ``jax.profiler.ProfileData`` and nothing else. What the v5e's
trace looks like (looked at by hand, PR 22, before this was written): one
plane per chip, ``/device:TPU:<i>``, with these lines. ``XLA Modules``: one
event per program execution, named ``jit_body(<fingerprint>)``.
``XLA Ops``: one event per executed HLO instruction, named by the
instruction's *whole text* (``%fusion.1 = bf16[..] fusion(..), kind=kOutput,
calls=%fused_computation.2``): the name before `` = `` is the name the
compiled module gives it. The core runs one instruction at a time, so these
events nest (a ``while`` or ``conditional`` spans its body's) but do not
otherwise overlap: an instruction's own time is its event less the events
inside it. ``Async XLA Ops``: one event from each ``-start`` to its
``-done`` (copies, collectives): what is in flight beside the core's work.
The host's plane ``/host:CPU`` holds the ``TraceAnnotation`` spans the
harness opens; its clock and the chip's differ by about a millisecond (in
the recorded fixture the chip runs a program 1 ms before the host's span
that dispatched it opens), so a gap is attributed to a host span only by
overlap, and only gaps of several milliseconds mean anything.

All times are integer nanoseconds until a method ends in ``_s`` or
``_ms``.
"""

import collections
import glob
import os
import re

from benchmarks.harness import hlo_text

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"

# the harness's host spans, opened as jax.profiler.TraceAnnotation
DISPATCH = "bench.dispatch"
SYNC = "bench.sync"
ANNOTATIONS = (DISPATCH, SYNC)

Op = collections.namedtuple("Op", "name opcode start end")

# `%name = <shape> opcode(operands...)...`: the opcode is the first lower-case
# word that follows a space and is followed by `(`; a shape holds neither
# (its elements are `, dtype[dims]{layout}`, with `/*index=5*/` comments)
_EVENT_RE = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


# -- interval arithmetic --------------------------------------------------------


def union(intervals):
    """Sorted, disjoint (start, end) covering the same points."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(i) for i in out]


def length(intervals):
    return sum(end - start for start, end in union(intervals))


def subtract(intervals, others):
    """The part of ``intervals`` no interval of ``others`` covers."""
    out, others = [], union(others)
    for start, end in union(intervals):
        at = start
        for o_start, o_end in others:
            if o_end <= at:
                continue
            if o_start >= end:
                break
            if o_start > at:
                out.append((at, o_start))
            at = max(at, o_end)
        if at < end:
            out.append((at, end))
    return out


def gaps(intervals, window):
    """The parts of ``window`` = (start, end) no interval covers."""
    return subtract([window], intervals)


def self_times(ops):
    """Each event's own time: its length less the events nested in it.
    -> list of (op, ns), in start order. Events are taken as nested when
    one starts inside another (a ``while`` and its body)."""
    ordered = sorted(ops, key=lambda o: (o.start, -(o.end - o.start)))
    own = [o.end - o.start for o in ordered]
    stack = []  # indices of the events open at this point
    for i, op in enumerate(ordered):
        while stack and ordered[stack[-1]].end <= op.start:
            stack.pop()
        if stack:
            parent = stack[-1]
            own[parent] -= min(op.end, ordered[parent].end) - op.start
        stack.append(i)
    return [(op, max(ns, 0)) for op, ns in zip(ordered, own)]


def is_collective(opcode):
    """A collective's opcode, synchronous or its ``-start`` / ``-done``."""
    return hlo_text.op_kind_of_opcode(opcode) == hlo_text.COLLECTIVE


# -- the trace ------------------------------------------------------------------


class DeviceTrace:
    """One chip's lines. ``ops``, ``in_flight`` and ``modules`` are lists of
    ``Op`` in start order."""

    def __init__(self, name, ops, in_flight, modules):
        by_start = lambda o: o.start  # noqa: E731
        self.name = name
        self.ops = sorted(ops, key=by_start)
        self.in_flight = sorted(in_flight, key=by_start)
        self.modules = sorted(modules, key=by_start)
        self._busy = None

    def window(self):
        """From the first instruction's start to the last one's end."""
        return (self.busy()[0][0], self.busy()[-1][1])

    def busy(self):
        """The union of the instructions' intervals (made once: a traced
        window holds a quarter of a million of them)."""
        if self._busy is None:
            self._busy = union((o.start, o.end) for o in self.ops)
        return self._busy

    def runs_of(self, module):
        """The executions of the program named ``module`` (``jit_body``)."""
        return [m for m in self.modules if m.name.split("(")[0] == module]

    def ops_of(self, module):
        """The instructions that ran inside executions of ``module``: the
        trace names an instruction, not its program, and two programs may
        both hold a ``fusion.1``."""
        runs, out, i = self.runs_of(module), [], 0
        for op in self.ops:
            while i < len(runs) and runs[i].end <= op.start:
                i += 1
            if i < len(runs) and runs[i].start <= op.start:
                out.append(op)
        return out


class Trace:
    def __init__(self, devices, annotations):
        self.devices = devices
        self.annotations = sorted(annotations, key=lambda o: o.start)

    def busy_s(self):
        return _mean(length(d.busy()) for d in self.devices) / 1e9

    def window_s(self):
        return _mean(d.window()[1] - d.window()[0] for d in self.devices) / 1e9

    def idle_share(self):
        return 1.0 - self.busy_s() / self.window_s()

    def per_device_mean(self, fn):
        """Mean over the chips of ``fn(device_trace)``."""
        return _mean(fn(d) for d in self.devices)

    def top_ops(self, k, per_step=1, label=lambda op: op.name):
        """[[label, seconds]] of the ``k`` instructions with the most own
        time, summed over the window and meaned over the chips; seconds
        per step when ``per_step`` is the number of steps traced."""
        totals = collections.Counter()
        for d in self.devices:
            for op, ns in self_times(d.ops):
                totals[label(op)] += ns
        scale = 1e9 * len(self.devices) * max(per_step, 1)
        return [[name, ns / scale] for name, ns in totals.most_common(k)]

    def idle_by_host_span(self, k, min_gap_ns=10_000):
        """[[what the host was doing (number of gaps), seconds]]: every
        idle gap of at least ``min_gap_ns`` on any chip is given to the
        harness span that overlaps it most (``no harness span`` when none
        does); the shorter ones, which no host work explains, are one entry.
        Seconds are summed per entry and meaned over the chips; the ``k``
        largest entries."""
        totals, counts = collections.Counter(), collections.Counter()
        for d in self.devices:
            for start, end in gaps(d.busy(), d.window()):
                if end - start < min_gap_ns:
                    span = f"between instructions, each under {min_gap_ns // 1000} us"
                else:
                    span = self._host_span(start, end)
                totals[span] += end - start
                counts[span] += 1
        scale = 1e9 * len(self.devices)
        return [
            [f"{span} ({counts[span]} gaps)", ns / scale]
            for span, ns in totals.most_common(k)
        ]

    def _host_span(self, start, end):
        best, best_ns = "no harness span", 0
        for a in self.annotations:
            overlap = min(end, a.end) - max(start, a.start)
            if overlap > best_ns:
                best, best_ns = a.name, overlap
        return best


def _mean(values):
    values = list(values)
    return sum(values) / len(values)


def parse_event(text):
    """(name, opcode) of an ``XLA Ops`` event's text; an event that is not
    an instruction's text keeps its text as name and has no opcode."""
    m = _EVENT_RE.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


def _ops(line):
    return [
        Op(*parse_event(e.name), e.start_ns, e.start_ns + e.duration_ns)
        for e in line.events
    ]


def _spans(line, keep=lambda name: True):
    return [
        Op(e.name, "", e.start_ns, e.start_ns + e.duration_ns)
        for e in line.events if keep(e.name)
    ]


def load(path):
    """The ``Trace`` of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, annotations = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            ops = _ops(lines[OPS_LINE]) if OPS_LINE in lines else []
            if not ops:
                continue
            devices.append(DeviceTrace(
                plane.name, ops,
                _ops(lines[ASYNC_LINE]) if ASYNC_LINE in lines else [],
                _spans(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
            ))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                annotations.extend(_spans(line, ANNOTATIONS.__contains__))
    if not devices:
        raise ValueError(
            f"{path}: no {DEVICE_PLANE_PREFIX}* plane with a non-empty "
            f"{OPS_LINE!r} line; planes: {[p.name for p in data.planes]}"
        )
    devices.sort(key=lambda d: d.name)
    return Trace(devices, annotations)


def load_dir(trace_dir):
    """The trace ``jax.profiler.start_trace(trace_dir)`` left."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return load(found[0])
