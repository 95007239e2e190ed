"""What the program says about itself, read back: the host phases of
``train_step`` from its flight ring, and the device step split by the
``bf.*`` scopes in the compiled step's ``op_name``s.

The program (``bluefog_tpu/flight.py``, ``optimizers.py``) cuts every fused
``train_step`` call into five phases — ``resolve``, ``key``, ``stage``,
``enqueue``, ``epilogue`` — by one ring event per boundary, and puts
``jax.named_scope``s round the loss, the packing, the gossip, the unpacking
and the inner update of the compiled step, and a ``name=`` on each Pallas
kernel. Two functions read them for the per-layer readers, each thin over
functions of plain inputs (which is what benchmarks/tests call):

- ``host_phases(run)``: the ring's whole calls inside the harness's
  ``window`` span. The ring's clock (``time.monotonic_ns``) and the spans'
  (``time.perf_counter``) are both ``CLOCK_MONOTONIC`` on Linux; that is
  checked, not assumed.
- ``device_ms_by_scope(run)``: the twin of ``Run.device_ms_by_kind``, own
  device time per step and chip by scope instead of by instruction kind.
  A fusion is one kernel and its time goes whole to one part: the scope of
  the metadata XLA kept for it or, where that names none, the scope most of
  the instructions inside it carry (``instruction_parts``). So work XLA
  fused into a kernel of another scope is read there: the optax arithmetic
  that ends a weight gradient's matmul fusion is in ``backward``.

Both return ``None`` rather than a wrong number: a host time is not reported
off the chip (``run.peaks is None`` is the harness's mark of "not a TPU"),
nor from a ring that no longer holds the window's start, nor from a program
that writes no phases (the parent of PR 24); a device split is not reported
for a step whose compiled text holds no ``bf.`` scope at all.
"""

import collections
import re
import statistics
import time

from benchmarks.harness import flops, hlo_text, trace_reduce
# the harness has one parser of an HLO line: these are its expressions
from benchmarks.harness.hlo_text import _CALLS_RE, _COMPUTATION_RE, _INSTR_RE

PHASES = ("resolve", "key", "stage", "enqueue", "epilogue")
CLOCK_SLACK_S = 1e-3  # the two clocks read 1-3 us apart; a gap is another clock

FORWARD, BACKWARD = "forward", "backward"
PACK_UNPACK, INNER_UPDATE = "pack_unpack", "inner_update"
COMBINE, UNSCOPED = "combine", "unscoped"
PARTS = (FORWARD, BACKWARD, PACK_UNPACK, INNER_UPDATE, COMBINE, UNSCOPED)

_SCOPE_RE = re.compile(r"\bbf\.(loss_grad|pack|unpack|gossip|inner_update)\b")
_PART_OF_SCOPE = {
    "pack": PACK_UNPACK, "unpack": PACK_UNPACK, "gossip": COMBINE,
    "inner_update": INNER_UPDATE,
}
_KERNEL_RE = re.compile(r"([^/]+)/pallas_call$")


def _cached(run, name, make):
    cache = run.__dict__.setdefault("_scopes", {})
    if name not in cache:
        cache[name] = make()
    return cache[name]


# -- host: the phases of train_step ---------------------------------------------


def calls_between(events, t0_us, t1_us, step_phases):
    """The whole ``train_step`` calls of ``events`` (a flight ring's, oldest
    first) inside ``[t0_us, t1_us]``, as ``step_phases`` (the program's
    ``flight.step_phases``) cuts them; ``None`` when the ring has wrapped
    past ``t0_us`` — its oldest event is not the first ever written and is
    younger than the interval's start — or holds no call there."""
    if not events:
        return None
    if events[0]["seq"] > 0 and events[0]["t_us"] > t0_us:
        return None
    return step_phases(t0_us, t1_us, events) or None


def phase_stats(calls):
    """ms: the mean of each phase and of the whole call (sum / calls, so the
    five phase means add up to the call's), and the call's median and 90th
    percentile."""
    n = len(calls)
    totals = [c["total"] / 1e3 for c in calls]
    out = {name: sum(c[name] for c in calls) / 1e3 / n for name in PHASES}
    out.update(
        calls=n, mean=sum(totals) / n, p50=statistics.median(totals),
        p90=statistics.quantiles(totals, n=10)[-1] if n > 1 else totals[0],
    )
    return out


def host_phases(run, span="window"):
    """``phase_stats`` of the calls inside the harness span ``span`` (the
    last one of that name), or ``None`` (see the module's header)."""
    return _cached(run, ("host", span), lambda: _host_phases(run, span))


def _host_phases(run, span):
    if run.peaks is None:
        return None
    from bluefog_tpu import flight

    step_phases = getattr(flight, "step_phases", None)
    if step_phases is None:
        return None
    if abs(time.perf_counter() - time.monotonic()) > CLOCK_SLACK_S:
        return None
    found = [(t0, t1) for name, t0, t1 in run.spans.items if name == span]
    if not found:
        return None
    t0, t1 = found[-1]
    calls = calls_between(
        flight.events(), int(t0 * 1e6), int(t1 * 1e6) + 1, step_phases
    )
    if calls is None:
        return None
    if span == "window" and len(calls) != len(run.dispatch_s):
        return None  # the ring and the harness disagree on what ran
    return phase_stats(calls)


# -- device: the step by scope ---------------------------------------------------


def part_of(op_name):
    """Which of ``PARTS`` an instruction with this ``op_name`` belongs to:
    the innermost ``bf.*`` scope decides; under ``bf.loss_grad`` the
    transposed half of the differentiation is the backward pass. No
    ``op_name`` (XLA made the instruction itself) or no scope: unscoped."""
    found = _SCOPE_RE.findall(op_name or "")
    if not found:
        return UNSCOPED
    if found[-1] == "loss_grad":
        return BACKWARD if "transpose(jvp(" in op_name else FORWARD
    return _PART_OF_SCOPE[found[-1]]


def has_scopes(op_names):
    return any(_SCOPE_RE.search(name) for name in op_names.values())


def fusion_bodies(hlo):
    """{fusion: the instructions of the computation it calls}, every
    computation of the module's text ``hlo`` (a fusion may hold fusions)."""
    members, called, computation = {}, {}, None
    for line in hlo.splitlines():
        header = _COMPUTATION_RE.match(line)
        if header and "=" not in line.split("(", 1)[0]:
            computation = members.setdefault(header.group(1), [])
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, opcode, rest = m.groups()
        if computation is not None:
            computation.append(name)
        calls = opcode == "fusion" and _CALLS_RE.search(rest)
        if calls:
            called[name] = calls.group(1)
    return {name: members.get(c, ()) for name, c in called.items()}


def instruction_labels(op_names, bodies, label_of, order):
    """{instruction: label} for every instruction that has an ``op_name`` or
    is a fusion. ``label_of(op_name)`` is a label or ``None``; an
    instruction's own ``op_name`` decides. XLA keeps one instruction's
    metadata for a whole fusion, and it may be bare glue's or none:
    ``_tree_restack``'s ``broadcast_in_dim`` is the root of fusions that
    hold the inner update's arithmetic. So a fusion whose own ``op_name``
    gives no label takes the one most of the labelled instructions in its
    body (``fusion_bodies``) carry, the first of ``order`` on a tie; one
    whose ``op_name`` gives a label keeps it, whatever else was fused in."""
    labels = {}

    def label(name):
        if name not in labels:
            labels[name] = label_of(op_names.get(name))
            if labels[name] is None and name in bodies:
                votes = collections.Counter(map(label, bodies[name]))
                del votes[None]
                if votes:
                    labels[name] = max(order, key=votes.__getitem__)
        return labels[name]

    for name in (*op_names, *bodies):
        label(name)
    return labels


def instruction_parts(op_names, bodies):
    """``instruction_labels`` over ``PARTS`` (``part_of``): an instruction
    that takes no part of the five scopes' is unscoped."""
    def scoped_part(op_name):
        part = part_of(op_name)
        return None if part == UNSCOPED else part

    labels = instruction_labels(op_names, bodies, scoped_part, PARTS)
    return {name: part or UNSCOPED for name, part in labels.items()}


def scope_of(names):
    """-> ``find(op_name)``: the innermost of the scope names ``names`` in an
    ``op_name`` (the last to appear in it, as a whole word: ``bf.pack`` is
    not in ``bf.packed``), or ``None``."""
    found = re.compile(r"\b(%s)\b" % "|".join(map(re.escape, names))).findall

    def find(op_name):
        return (found(op_name or "") or (None,))[-1]

    return find


def _sum_by(own_times, key):
    totals = {}
    for op, ns in own_times:
        k = key(op)
        if k is not None:
            totals[k] = totals.get(k, 0) + ns
    return totals


def ns_by_part(parts, own_times):
    """{part: own ns} over ``PARTS`` (every part present): each of
    ``own_times`` (``trace_reduce.self_times``' ``(op, ns)``) goes to its
    instruction's part in ``parts`` (``instruction_parts``); one that is
    not there is unscoped."""
    return {
        **dict.fromkeys(PARTS, 0),
        **_sum_by(own_times, lambda op: parts.get(op.name, UNSCOPED)),
    }


def ns_by_kernel(hlo, own_times):
    """{kernel: own ns} of the Mosaic calls among ``own_times``, by the
    ``name=`` their ``pallas_call`` was given (the last but one segment of
    the ``op_name``); a Mosaic call without one is left out."""
    def kernel(op):
        if hlo.kind(op.name) != hlo_text.MOSAIC:
            return None
        m = _KERNEL_RE.search(hlo.op_names.get(op.name, ""))
        return m.group(1) if m else None

    return _sum_by(own_times, kernel)


def _per_step_ms(run, split):
    """``split(own_times) -> {key: ns}`` over every chip's step
    instructions, as ms per step and chip."""
    own_times = _cached(run, "own", lambda: [
        trace_reduce.self_times(d.ops_of(run.hlo.module))
        for d in run.trace.devices
    ])
    totals = {}
    scale = 1e6 * len(own_times) * run.traced_steps
    for own in own_times:
        for k, ns in split(own).items():
            totals[k] = totals.get(k, 0.0) + ns / scale
    return totals


def device_ms_by_scope(run):
    """{part: ms per step and chip} over ``PARTS``: they partition the
    step's device time as ``device_ms_by_kind`` does. ``None`` without a
    trace or the step's HLO, or when no instruction carries a scope."""
    if run.trace is None or run.hlo is None:
        return None
    op_names = run.hlo.op_names

    def split():
        if not has_scopes(op_names):
            return None
        parts = instruction_parts(op_names, fusion_bodies(run.hlo.text))
        return _per_step_ms(run, lambda own: ns_by_part(parts, own))

    return _cached(run, "device", split)


def device_ms_by_scopes(run, names, halves=False):
    """{name: ms per step and chip} of the step's own device time under each
    of the scope names ``names`` (any ``jax.named_scope``s: the program's
    ``bf.*``, a model's own round its router or its expert products), and
    under ``None`` the rest: together the whole step, as
    ``device_ms_by_kind`` sums it. The innermost of ``names`` in an
    instruction's ``op_name`` decides and a fusion without one takes the
    vote of its body (``instruction_labels``, the one implementation
    ``device_ms_by_scope`` splits ``PARTS`` by). With ``halves`` a scope's
    key is ``(name, "forward")`` or ``(name, "backward")``, by
    ``transpose(jvp(`` as in ``part_of``. ``None`` without a trace or the
    step's HLO, or when no instruction names any of ``names``."""
    if run.trace is None or run.hlo is None:
        return None
    names = tuple(names)
    find = scope_of(names)
    label_of, order = find, names
    if halves:
        order = tuple((n, h) for n in names for h in (FORWARD, BACKWARD))

        def label_of(op_name):
            name = find(op_name)
            return name and (
                name, BACKWARD if "transpose(jvp(" in op_name else FORWARD
            )

    def split():
        op_names = run.hlo.op_names
        if not any(map(find, op_names.values())):
            return None
        labels = instruction_labels(
            op_names, fusion_bodies(run.hlo.text), label_of, order
        )
        rest = object()  # `_sum_by` leaves out a key of None
        totals = _per_step_ms(run, lambda own: _sum_by(
            own, lambda op: labels.get(op.name) or rest
        ))
        totals[None] = totals.pop(rest, 0.0)
        return {**dict.fromkeys(order, 0.0), **totals}

    return _cached(run, ("scopes", names, halves), split)


def kernel_roofline(run, cost):
    """``flops.roofline_share`` of one entry of a job's ``kernel_costs()``
    over the device time per step and chip of the kernels it is measured
    against: the Mosaic calls whose ``pallas_call`` name is in
    ``cost["kernels"]`` or, for an entry that names none, all Mosaic time of
    the step (right only while every Mosaic call of the step is that
    kernel's). ``None`` off the chip, without a trace or where none of them
    ran."""
    if not run.peaks:
        return None
    if "kernels" in cost:
        by_kernel = mosaic_ms_by_kernel(run)
        ms = by_kernel and sum(by_kernel.get(k, 0.0) for k in cost["kernels"])
    else:
        by_kind = run.device_ms_by_kind()
        ms = by_kind and by_kind[hlo_text.MOSAIC]
    return flops.roofline_share(cost, (ms or 0) / 1e3, run.peaks)


def mosaic_ms_by_kernel(run):
    """{kernel name: ms per step and chip} of the named Mosaic calls;
    ``None`` without a trace or the step's HLO."""
    if run.trace is None or run.hlo is None:
        return None
    return _cached(run, "kernels", lambda: _per_step_ms(
        run, lambda own: ns_by_kernel(run.hlo, own)
    ))
