"""Set-up, read from inside the program: what its flight ring says happened
before the window.

Since PR 38 the program (``bluefog_tpu/flight.py``) writes one ``build``
event at the end of every trace, lowering and compile-or-load that jax
reports (``phase``, jax's ``fun``, ``dur_us``; a ``backend`` event also what
the persistent cache answered), and says on ``session_start`` how the process
reached ``bf.init()`` (``import_s``, ``process_age_s``). Seven readers in
``layer_metrics/`` split ``setup_s`` by them. ``setup_split(run)`` is thin
over functions of plain inputs (ring events, an interval, the program's
``step_phases`` / ``build_phases``), which is what benchmarks/tests call.

``scopes.host_phases``' rules hold: ``None`` rather than a wrong number —
off the chip (``run.peaks is None``), from a program without the events
(the parent of PR 38), when the ring's clock and the spans' disagree, or
from a ring that no longer holds the harness's ``warm_steps`` span.
"""

import time

from benchmarks.harness import scopes

PHASES = ("trace", "lower", "backend")
WARM_SPAN = "warm_steps"
NAMES = (
    "import_s", "reach_init_s", "step_first_call_s", "step_trace_s",
    "step_lower_s", "step_backend_s", "warm_rebuild_s",
)


def session_start(events):
    """The payload of the ring's ``session_start`` event, or ``None`` once
    the ring has wrapped past it."""
    for e in events:
        if e["kind"] == "session_start":
            return e.get("data", {})
    return None


def outer_seconds(records):
    """{phase: seconds} over ``PHASES``: the durations of the outer
    ``build`` records (``flight.build_phases``' ``outer``), so that no
    second of an inner ``jit``'s trace is counted twice."""
    out = dict.fromkeys(PHASES, 0.0)
    for r in records:
        if r["outer"] and r.get("phase") in out:
            out[r["phase"]] += r["dur_us"] / 1e6
    return out


def builds_in_call(events, call, build_phases):
    """The build records of ``events`` inside one whole ``train_step`` call
    (a record of ``flight.step_phases``)."""
    return build_phases(call["t_us"], call["t_us"] + call["total"], events)


def split_first_calls(events, calls, build_phases):
    """What the warm calls' build events say, in seconds:
    ``step_first_call_s`` (the first call, ``step_resolve`` to
    ``step_end``), ``step_trace_s`` / ``step_lower_s`` / ``step_backend_s``
    (its outer build events by phase) and ``warm_rebuild_s`` (every outer
    build event inside the second and the third call; ``None`` with fewer
    than three calls)."""
    first = builds_in_call(events, calls[0], build_phases)
    seconds = outer_seconds(first)
    out = {
        "step_first_call_s": calls[0]["total"] / 1e6,
        "warm_rebuild_s": None,
    }
    out.update({f"step_{phase}_s": seconds[phase] for phase in PHASES})
    if len(calls) >= 3:
        out["warm_rebuild_s"] = sum(
            sum(outer_seconds(builds_in_call(events, c, build_phases)).values())
            for c in calls[1:3]
        )
    return out


def setup_split(run):
    """{name: seconds or None} over ``NAMES``, or ``None`` (see the module's
    header)."""
    return scopes._cached(run, "setup", lambda: _setup_split(run))


def _setup_split(run):
    if run.peaks is None:
        return None
    from bluefog_tpu import flight

    build_phases = getattr(flight, "build_phases", None)
    step_phases = getattr(flight, "step_phases", None)
    if build_phases is None or step_phases is None:
        return None
    if abs(time.perf_counter() - time.monotonic()) > scopes.CLOCK_SLACK_S:
        return None
    found = [(t0, t1) for name, t0, t1 in run.spans.items if name == WARM_SPAN]
    if not found:
        return None
    t0, t1 = found[-1]
    events = flight.events()
    calls = scopes.calls_between(
        events, int(t0 * 1e6), int(t1 * 1e6) + 1, step_phases
    )
    if calls is None:  # the ring has wrapped past the span, or no call there
        return None
    out = dict.fromkeys(NAMES)
    out.update(split_first_calls(events, calls, build_phases))
    session = session_start(events) or {}
    out["import_s"] = session.get("import_s")
    out["reach_init_s"] = session.get("process_age_s")
    return out
