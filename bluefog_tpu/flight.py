# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Flight recorder: an always-on, bounded-memory black box for gossip runs.

The reference's rank-0 coordinator could at least *name* the stuck
tensors when a run hung (its 60-s message-table scan,
``common/operations.cc:388-433``). The single-controller SPMD port has no
negotiation table to scan — when a rank dies mid-combine the elastic
layer repairs the graph, but the evidence of what happened in the
seconds *before* is gone, and the per-rank Chrome traces are disjoint
files with unaligned clocks. This module is the black box: a fixed-size
ring of structured events fed by the runtime itself (the
PyTorch-NCCL-flight-recorder shape, adapted to gossip), dumped to JSON
when something goes wrong and fused across ranks by
``tools/trace_merge.py``.

Design constraints, in order:

1. **~Zero hot-path cost.** One :func:`record` call is a monotonic-clock
   read plus one slot assignment into a preallocated list. There is no
   lock on the write path: each call takes a unique sequence number from
   an ``itertools.count`` (atomic under the GIL) and writes its own slot
   ``seq % capacity`` — concurrent writers (the training loop, the
   watchdog thread) never share a slot, and readers sort the snapshot by
   sequence. tests/test_flight.py::test_recorder_on_off_trajectory_bitwise
   pins the trajectory bitwise recorder on vs off.
2. **Bounded memory.** ``BLUEFOG_FLIGHT_CAPACITY`` slots (default 8192);
   old events are overwritten, never accumulated. Side tables that the
   postmortem needs regardless of ring age (the compiled CommPlan
   structures, the session clock handshake) are kept separately, bounded.
3. **Always on.** Enabled by default (``BLUEFOG_FLIGHT=0`` disables);
   recording never touches device values, so the training trajectory is
   bitwise-identical with the recorder on or off.

What gets recorded (event ``kind`` -> payload):

- ``session_start`` / ``session_end`` — clock handshake (unix ns,
  monotonic us, timeline us) + mesh shape + process index; the
  cross-rank alignment anchor ``tools/trace_merge.py`` uses.
  ``session_start`` also says how the process got here: ``import_s``
  (the package's own import), ``jax_preloaded`` and ``process_age_s``.
- ``plan_compile`` — every CommPlan the compiler lowers (topology
  version, round count, live token); full round/edge structure is
  retained in a bounded side table for the postmortem.
- ``compile`` — XLA program (re)builds, by cache-key family.
- ``build`` — what jax itself reports while a program is being built:
  one event at the end of each trace, lowering and compile-or-load
  (``phase``, jax's ``fun``, ``dur_us``; a ``backend`` event also says
  what the persistent cache answered). :func:`build_phases` reads them
  back; the outer ones are kept in a bounded side table as well.
- ``step_begin`` / ``step_dispatched`` — optimizer step boundaries with
  the communicating flag; the merge tool turns these into per-rank step
  spans and computes per-step critical paths over the plan's rounds.
- ``step_resolve`` / ``step_key`` / ``step_enqueue`` / ``step_end`` — the
  other boundaries of a fused ``train_step`` call (:class:`StepPhases`):
  with the two above they cut the call into ``resolve``, ``key``,
  ``stage``, ``enqueue`` and ``epilogue``; :func:`step_phases` reads
  them back as microseconds per phase.
- ``sync_begin`` / ``sync_ready`` — host blocking points (the moments a
  hang becomes observable).
- ``window_op`` — one-sided window traffic (put/get/accumulate/update).
- ``membership`` / ``fault`` / ``repair`` — elastic verdicts with
  epoch, reason, and the topology version the verdict was filed under.
- ``stall`` — watchdog deadline hits.
- ``advisory`` — observability diagnoses (:mod:`bluefog_tpu.
  attribution` degraded_link / straggler / recompile_storm /
  consensus_stall / ambient_drift, :mod:`bluefog_tpu.health`
  mixing_degraded, :mod:`bluefog_tpu.staleness` staleness_breach),
  with their evidence, kept eviction-proof in a side table like
  faults.
- ``staleness`` — per-sample delivered-age summaries from the
  staleness observatory's lineage lane (surface, mean/max age, lane
  self-check), so a postmortem can see whether data was going stale
  in the steps before a hang.
- ``memory`` — per-sample live-buffer totals and headroom from the
  memory observatory (:mod:`bluefog_tpu.memory`), so a postmortem can
  see the footprint trending toward the budget in the steps before an
  OOM.
- ``oom`` — a device allocation failure (real ``RESOURCE_EXHAUSTED``
  caught by the memory observatory's crash hooks, or the injected
  ``oom`` chaos fault); the ranked buffer census rides the advisory
  side table so it survives ring eviction.
- ``crash`` / ``sigterm`` — the run's last words.

Dump triggers: a watchdog stall, an elastic SUSPECT/DEAD verdict, an
unhandled exception, SIGTERM, or an explicit ``bf.flight_dump()``. The
automatic triggers write only when ``BLUEFOG_FLIGHT_DIR`` is configured
(set it, or launch with ``bfrun-tpu --flight-dir``); the dump file
``flight_<process_index>.json`` is rewritten in place, so the latest
dump always carries the fullest event window. See docs/flight.md.
"""

import itertools
import json
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax import profiler as _profiler

from bluefog_tpu import metrics as metrics_mod
from bluefog_tpu import timeline as tl
from bluefog_tpu import watchdog
from bluefog_tpu.logging_util import logger

__all__ = [
    "FlightRecorder",
    "enabled",
    "record",
    "events",
    "StepPhases",
    "step_phases",
    "build_phases",
    "note_import",
    "note_plan",
    "note_fault",
    "note_advisory",
    "note_decision",
    "dump",
    "maybe_dump",
    "dump_dir",
    "reconfigure",
    "on_init",
    "on_shutdown",
    "DUMP_VERSION",
]

ENABLE_ENV = "BLUEFOG_FLIGHT"
CAPACITY_ENV = "BLUEFOG_FLIGHT_CAPACITY"
DIR_ENV = "BLUEFOG_FLIGHT_DIR"

DUMP_VERSION = 1

# How many compiled CommPlan structures the side table retains (newest
# kept). The postmortem needs the plan that was ACTIVE at the fault, and
# an elastic run compiles one plan per membership epoch; dynamic
# schedules add one entry per period step. 32 covers any plausible
# window between failure and dump.
_MAX_PLANS = 32


def _now_us() -> int:
    return time.monotonic_ns() // 1000


class FlightRecorder:
    """Fixed-capacity event ring. See the module docstring for the
    lock-free-ish write protocol."""

    def __init__(self, capacity: int):
        assert capacity > 0
        self.capacity = int(capacity)
        self._buf: List[Optional[Tuple]] = [None] * self.capacity
        self._seq = itertools.count()

    def record(self, kind: str, data: Optional[dict] = None) -> int:
        seq = next(self._seq)  # GIL-atomic: unique slot per writer
        self._buf[seq % self.capacity] = (seq, _now_us(), kind, data)
        return seq

    def events(self) -> List[dict]:
        """Snapshot of the ring as dicts, oldest first. Taken without a
        lock: a slot overwritten mid-snapshot just reflects the newer
        event (the ring's contract is "the last N events", not a
        consistent cut)."""
        snap = [e for e in list(self._buf) if e is not None]
        snap.sort(key=lambda e: e[0])
        return [
            {"seq": s, "t_us": t, "kind": k, **({"data": d} if d else {})}
            for s, t, k, d in snap
        ]

    def __len__(self) -> int:
        return sum(1 for e in self._buf if e is not None)


# -- module state -------------------------------------------------------------

_enabled_cache: Optional[bool] = None
_recorder: Optional[FlightRecorder] = None
_plans: List[dict] = []  # bounded side table of compiled plan structures
_faults: List[dict] = []  # bounded side table of fault verdicts: the
# postmortem's fault -> plan linkage must survive ring eviction on long
# runs, exactly like the plan structures themselves
_advisories: List[dict] = []  # bounded side table of doctor advisories
# (bluefog_tpu.attribution): a postmortem that cannot see "degraded_link
# fired 40 minutes ago" mis-tells the story, so advisory history gets
# the same eviction-proof treatment as faults
_decisions: List[dict] = []  # bounded side table of autotune decisions
# (bluefog_tpu.autotune): a postmortem of a run whose topology the
# controller changed mid-flight must carry WHY — the swap/rollback
# history survives ring eviction exactly like the advisories that
# triggered it
_slo: List[dict] = []  # bounded side table of SLO budget snapshots
# (bluefog_tpu.slo): a crash dump must carry the burn-rate and
# error-budget state that preceded it — "we died while paging on a
# burned budget" vs "we died green" is the first postmortem question
# — so the sampled snapshots survive ring eviction like the rest
_builds: List[dict] = []  # bounded side table of the OUTER ``build``
# events, as the ring holds them: the first and the newest _BUILDS_KEPT
# of the session. The ring forgets a job's start after ~1 400 steps, and
# the postmortem of a recompile storm wants both ends: what the first
# programs cost, and what is being built now
_builds_dropped = 0  # outer builds that fell out of the middle
_plans_lock = threading.Lock()
_hooks_installed = False
_prev_excepthook = None
_prev_sigterm = None
_dump_lock = threading.Lock()
# every dump reason this session, oldest first: the canonical dump file
# is rewritten in place, so a later explicit dump must not erase the
# fact that a verdict/stall trigger fired earlier (bounded)
_dump_history: List[str] = []


def enabled() -> bool:
    """Recorder switch, default ON (``BLUEFOG_FLIGHT=0`` disables). The
    value is cached for the hot path; :func:`reconfigure` (called by
    ``bf.init()``) re-reads the environment."""
    global _enabled_cache
    if _enabled_cache is None:
        _enabled_cache = os.environ.get(ENABLE_ENV, "1").lower() not in (
            "0", "false", "off", "no",
        )
    return _enabled_cache


def capacity() -> int:
    from bluefog_tpu.logging_util import env_int

    return max(256, env_int(CAPACITY_ENV, 8192))


def dump_dir() -> Optional[str]:
    """Directory the automatic triggers dump into (``BLUEFOG_FLIGHT_DIR``
    / ``bfrun-tpu --flight-dir``), or None when unset (automatic dumps
    disabled; explicit :func:`dump` still works)."""
    return os.environ.get(DIR_ENV) or None


def _rec() -> FlightRecorder:
    global _recorder
    if _recorder is None:
        _recorder = FlightRecorder(capacity())
    return _recorder


def reconfigure() -> None:
    """Re-read the env knobs and start a fresh ring (one flight per
    session: ``bf.init()`` calls this so a dump never mixes events from
    a torn-down mesh with the new one)."""
    global _enabled_cache, _recorder, _builds_dropped
    _enabled_cache = None
    _recorder = None
    with _plans_lock:
        _plans.clear()
        _faults.clear()
        _advisories.clear()
        _decisions.clear()
        _slo.clear()
        del _builds[:]
        _builds_dropped = 0
    del _dump_history[:]


def record(kind: str, **data) -> int:
    """Append one structured event to the ring; returns its sequence
    number (-1 when the recorder is disabled). ``data`` values must be
    JSON-serializable — they go into the dump verbatim."""
    if not enabled():
        return -1
    return _rec().record(kind, data or None)


def events() -> List[dict]:
    if _recorder is None:
        return []
    return _recorder.events()


# -- the phases of a fused train_step call -------------------------------------

STEP_ROOT = "bf.train_step"
# phase -> the ring event that opens it; ``step_end`` closes the last one.
# ``step_begin`` / ``step_dispatched`` are the boundaries tools/trace_merge.py
# has always read: they open ``stage`` and ``epilogue``.
STEP_PHASES = {
    "resolve": "step_resolve",
    "key": "step_key",
    "stage": "step_begin",
    "enqueue": "step_enqueue",
    "epilogue": "step_dispatched",
}
STEP_END = "step_end"


class StepPhases:
    """One ``train_step`` call cut into :data:`STEP_PHASES`, written to two
    sinks at once. Each boundary is one ring event (so the phases are read
    back in-process with :func:`step_phases`, profiler or not) and the
    close of one ``jax.profiler.TraceAnnotation`` and the opening of the
    next, ``bf.train_step/<phase>``, under a ``StepTraceAnnotation`` root
    (so an open profiler session shows them on the device trace's clock,
    grouped by step; an annotation is inert while no session is open).
    Every event and annotation of a call carries the same ``step``.

    A context manager: entering opens the root and ``resolve``; leaving
    closes whatever is open, and writes ``step_end`` only when the call
    returned — a call that raised leaves its last boundary as the ring's
    word on where it died, and :func:`step_phases` skips it."""

    __slots__ = ("step", "_root", "_phase")

    def __init__(self, step: int):
        self.step = step
        self._root = _profiler.StepTraceAnnotation(STEP_ROOT, step_num=step)
        self._phase = None

    def __enter__(self):
        self._root.__enter__()
        self.enter("resolve")
        return self

    def enter(self, phase: str, **data) -> None:
        """Close the open phase and open ``phase``; ``data`` joins the
        ``step`` in the ring event's payload."""
        if self._phase is not None:
            self._phase.__exit__(None, None, None)
        record(STEP_PHASES[phase], step=self.step, **data)
        self._phase = _profiler.TraceAnnotation(
            f"{STEP_ROOT}/{phase}", step=self.step
        )
        self._phase.__enter__()

    def __exit__(self, exc_type, exc, tb):
        self._phase.__exit__(exc_type, exc, tb)
        if exc_type is None:
            record(STEP_END, step=self.step)
        self._root.__exit__(exc_type, exc, tb)
        return False


def step_phases(t0_us: Optional[int] = None, t1_us: Optional[int] = None,
                evs: Optional[List[dict]] = None) -> List[dict]:
    """The whole fused ``train_step`` calls in the ring (or in ``evs``, a
    list of ring events as :func:`events` gives them) that lie inside
    ``[t0_us, t1_us]`` on the ring's clock (``time.monotonic_ns() //
    1000``): one ``{"step", "t_us", "total", <phase>: us ...}`` per call,
    oldest first. A phase runs from its opening event to the next
    boundary, so the phases of a call sum to its ``total`` exactly. A
    call is whole when its six boundaries follow each other under one
    ``step``; anything else (a call that raised, one the ring has half
    overwritten, the two-program ``opt.step``'s lone ``step_begin`` /
    ``step_dispatched`` pair) is left out."""
    order = list(STEP_PHASES.values()) + [STEP_END]
    names = list(STEP_PHASES)
    out, stamps, step = [], [], None
    for e in (events() if evs is None else evs):
        if e["kind"] not in order:
            continue
        s = e.get("data", {}).get("step")
        if e["kind"] == order[0]:
            stamps, step = [e["t_us"]], s
        elif stamps and s == step and e["kind"] == order[len(stamps)]:
            stamps.append(e["t_us"])
            if len(stamps) == len(order):
                if (t0_us is None or stamps[0] >= t0_us) and (
                    t1_us is None or stamps[-1] <= t1_us
                ):
                    call = {
                        "step": step, "t_us": stamps[0],
                        "total": stamps[-1] - stamps[0],
                    }
                    for i, name in enumerate(names):
                        call[name] = stamps[i + 1] - stamps[i]
                    out.append(call)
                stamps = []
        else:
            stamps = []
    return out


# -- what jax says while a program is built -----------------------------------

# jax's own duration events (jax 0.9: ``dispatch.log_elapsed_time``), each
# with the built function's ``fun_name``: the trace to a jaxpr (one for
# every inner ``jit`` the outer trace meets, inside the outer's, and one for
# every index map and helper a Pallas kernel's lowering traces, inside the
# lowering's), the lowering to an MLIR module, and the backend's compile or
# its load from the persistent cache.
BUILD_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_BUILD_COUNTERS = {
    phase: f"bluefog.build.{phase}_s" for phase in BUILD_PHASES.values()
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_BUILDS_KEPT = 32  # of each end of a session, in the side table
# A child shorter than this is counted on its outer event (``inner``,
# ``inner_us``) and not written: a model's step meets an inner ``jit`` for
# every jax.numpy call of every distinct shape (12 463 phases a run of
# ``gpt2m_1chip_b1``, 3 159 of them inside the step's trace), most of some
# tens of microseconds, and the ring is there for the last 1 400 steps, not
# for them. The ones that say where a trace went are long.
_CHILD_MIN_US = 1000
_listening = False


class _BuildThread(threading.local):
    """One thread's open build phases and what the persistent cache has
    said since its last ``backend`` event. jax says when a phase begins
    (a scalar event of the same name) as well as when it ends, so whether
    an ending phase is an outer one is known on arrival. ``inner`` counts
    the children of the open outer event, at any depth, and ``inner_us``
    adds up its direct children's durations."""

    def __init__(self):
        self.depth = self.inner = self.inner_us = 0
        self.asked = self.hit = False
        self.retrieval_us = None


_build_thread = _BuildThread()

# The three callbacks run inside jax's tracing and compiling machinery: they
# return at once while the recorder is off, call nothing of jax's, and make
# no more than the event they write.


def _on_jax_scalar(event, value=None, **kwargs):
    if not enabled():
        return
    if event in BUILD_PHASES:  # the phase begins
        _build_thread.depth += 1


def _on_jax_event(event, **kwargs):
    if not enabled():
        return
    if event == _CACHE_ASKED:
        _build_thread.asked = True
    elif event == _CACHE_HIT:
        _build_thread.hit = True


def _on_jax_duration(event, duration_secs=0.0, **kwargs):
    if not enabled():
        return
    try:
        _record_build(event, duration_secs, kwargs.get("fun_name"))
    except Exception:  # whatever jax passes, its build goes on
        logger.debug("flight: a build report was dropped", exc_info=True)


def _record_build(event, duration_secs, fun):
    global _builds_dropped
    mine = _build_thread
    phase = BUILD_PHASES.get(event)
    if phase is None:
        if event == _CACHE_RETRIEVAL:
            mine.retrieval_us = int(duration_secs * 1e6)
        return
    dur_us = int(duration_secs * 1e6)
    data = {"phase": phase, "fun": fun, "dur_us": dur_us}
    if phase == "backend":
        # asked and not found is a miss, whether or not the compile was
        # long enough to be written (jax's own ``cache_misses`` counts the
        # entries written)
        data["cache"] = "hit" if mine.hit else "miss" if mine.asked else None
        if mine.hit:
            data["retrieval_us"] = mine.retrieval_us
            metrics_mod.counter("bluefog.build.cache_hits").inc()
        elif mine.asked:
            metrics_mod.counter("bluefog.build.cache_misses").inc()
        mine.asked = mine.hit = False
        mine.retrieval_us = None
    depth = mine.depth = max(0, mine.depth - 1)
    if depth:  # a child: its time is inside its outer event's
        mine.inner += 1
        if depth == 1:
            mine.inner_us += dur_us
        if dur_us >= _CHILD_MIN_US:
            _rec().record("build", data)
        return
    if mine.inner:
        data["inner"], data["inner_us"] = mine.inner, mine.inner_us
        mine.inner = mine.inner_us = 0
    rec = _rec()
    seq = rec.record("build", data)
    metrics_mod.counter(_BUILD_COUNTERS[phase]).inc(duration_secs)
    _builds.append({
        "seq": seq, "t_us": rec._buf[seq % rec.capacity][1],
        "kind": "build", "data": data,
    })
    if len(_builds) > 2 * _BUILDS_KEPT:
        del _builds[_BUILDS_KEPT]  # the oldest of the newest
        _builds_dropped += 1


def _listen_to_jax() -> None:
    """Register the three callbacks, once a process: jax keeps a listener
    for the life of the process and ``bf.init()`` may run again."""
    global _listening
    if _listening:
        return
    from jax import monitoring

    monitoring.register_scalar_listener(_on_jax_scalar)
    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _listening = True


def build_phases(t0_us: Optional[int] = None, t1_us: Optional[int] = None,
                 evs: Optional[List[dict]] = None) -> List[dict]:
    """The ``build`` events in the ring (or in ``evs``: ring events, or a
    dump's ``builds``) that lie whole inside ``[t0_us, t1_us]`` on the
    ring's clock, oldest first: one ``{"seq", "t_us", "start_us", "phase",
    "fun", "dur_us", "outer"}`` per event; a ``backend`` one with its
    ``cache`` (and ``retrieval_us``) too, one that had children with their
    number ``inner`` and its direct children's total ``inner_us`` (so
    ``dur_us - inner_us`` was spent in none of them). An event is written
    when its phase ends, so ``t_us`` is the end and ``start_us = t_us -
    dur_us`` the start (jax times the phase on the wall clock: only the
    duration is taken from it).

    jax reports a trace for every inner ``jit`` the outer trace meets,
    inside the outer's duration, and the traces a lowering makes (a Pallas
    kernel's index maps) inside the lowering's. An event whose interval
    lies inside another ``build`` event's, of whatever phase, is that one's
    child and has ``outer`` false: a sum over the outer events counts no
    second twice, and the children say where the outer event spent its
    time (the ring holds those of a millisecond or more,
    ``_CHILD_MIN_US``; the shorter ones are in ``inner`` / ``inner_us``
    alone). Nesting is read before the interval is cut, so a child stays a
    child when its outer event ends past ``t1_us``."""
    out = []
    for e in (events() if evs is None else evs):
        if e["kind"] == "build":
            d = e.get("data", {})
            out.append({
                "seq": e["seq"], "t_us": e["t_us"],
                "start_us": e["t_us"] - d.get("dur_us", 0), **d, "outer": True,
            })
    # earliest start first and of those the one that ended last (a parent is
    # written after its children): an event lies inside an earlier one of
    # this order iff it ends no later than the latest end seen
    latest_end = None
    for r in sorted(out, key=lambda r: (r["start_us"], -r["t_us"], -r["seq"])):
        if latest_end is not None and r["t_us"] <= latest_end:
            r["outer"] = False
        else:
            latest_end = r["t_us"]
    return [
        r for r in out
        if (t0_us is None or r["start_us"] >= t0_us)
        and (t1_us is None or r["t_us"] <= t1_us)
    ]


def note_plan(plan, topo_version: int, live_token=None,
              kind: str = "worker") -> None:
    """Retain a compiled CommPlan's structure in the bounded side table
    (and drop a ``plan_compile`` ring event). The postmortem resolves
    "which edge/round was rank j waiting on" from exactly this record,
    so it must survive ring eviction. ``kind`` distinguishes worker-rank
    plans from hierarchical *machine*-graph plans — their version
    counters are independent and their node ids mean different things,
    so the postmortem must never match a fault against the wrong kind."""
    if not enabled():
        return
    entry = {
        "kind": kind,
        "topo_version": int(topo_version),
        "n_rounds": len(plan.rounds),
        "rounds": [
            [[int(s), int(d)] for s, d in rnd.perm] for rnd in plan.rounds
        ],
        "live": (
            None if live_token is None
            else {"epoch": live_token[0], "ranks": list(live_token[1])}
        ),
    }
    with _plans_lock:
        if entry in _plans:
            # dynamic-weight plans are rebuilt per dispatch (no cache in
            # front of them): retain the structure once, and don't spam
            # the ring with a plan_compile event per step
            return
        _plans.append(entry)
        del _plans[:-_MAX_PLANS]
    record(
        "plan_compile", topo_version=entry["topo_version"],
        n_rounds=entry["n_rounds"],
        live_epoch=None if live_token is None else live_token[0],
    )


def note_fault(**data) -> None:
    """Record a fault verdict in BOTH the ring and a bounded side table:
    the postmortem resolves the fault's topology version against the
    plan side table, and that linkage must not depend on the fault event
    still being in the (evicted-on-overflow) ring when the dump fires."""
    if not enabled():
        return
    with _plans_lock:
        _faults.append(dict(data))
        del _faults[:-64]
    record("fault", **data)


def note_advisory(**data) -> None:
    """Record a doctor advisory (:mod:`bluefog_tpu.attribution`) in BOTH
    the ring and a bounded side table, mirroring :func:`note_fault`: the
    triage report (``tools/doctor.py``) joins advisories against dump
    reasons and fault verdicts, and that history must survive ring
    eviction."""
    if not enabled():
        return
    with _plans_lock:
        _advisories.append(dict(data))
        del _advisories[:-64]
    # the ring event's own kind is "advisory"; the diagnosis kind rides
    # as advisory_kind (same convention as note_fault's fault_kind)
    record("advisory", **{
        ("advisory_kind" if k == "kind" else k): v
        for k, v in data.items()
    })


def note_decision(**data) -> None:
    """Record an autotune controller decision
    (:mod:`bluefog_tpu.autotune`) in BOTH the ring and a bounded side
    table, mirroring :func:`note_advisory`: the postmortem of a run
    whose topology was swapped mid-flight must name the decision that
    swapped it — and that record must survive ring eviction on a long
    run."""
    if not enabled():
        return
    with _plans_lock:
        _decisions.append(dict(data))
        del _decisions[:-64]
    record("autotune", **data)


def note_slo(**data) -> None:
    """Record an SLO budget snapshot (:mod:`bluefog_tpu.slo`) in BOTH
    the ring and a bounded side table, mirroring
    :func:`note_decision`: the postmortem must read the worst burn
    rate and exhausted-objective set leading into a crash even after
    the ring evicts the samples."""
    if not enabled():
        return
    with _plans_lock:
        _slo.append(dict(data))
        del _slo[:-64]
    record("slo", **data)


def _clock_triple() -> dict:
    """The cross-rank alignment anchor: the same instant on all three
    clocks this process emits timestamps in — wall (shared across
    hosts), monotonic (flight events), timeline (Chrome-trace ts)."""
    return {
        "unix_ns": time.time_ns(),
        "mono_us": _now_us(),
        "timeline_us": (
            tl.timeline_now_us() if tl.timeline_enabled() else None
        ),
    }


def _owned_ranks(ctx) -> List[int]:
    """Mesh slots this controller process is responsible for (all of
    them on a single controller; the local devices' positions on a
    multi-host pod)."""
    try:
        import jax

        proc = jax.process_index()
        if jax.process_count() > 1:
            return [
                i for i, d in enumerate(ctx.devices)
                if getattr(d, "process_index", proc) == proc
            ]
    except Exception:
        pass
    return list(range(ctx.size))


def _build_dump(reason: str) -> dict:
    from bluefog_tpu import context as ctx_mod

    out: Dict[str, Any] = {
        "version": DUMP_VERSION,
        "reason": reason,
        "process_index": tl.process_file_index(),
        "clock": _clock_triple(),
    }
    ctx = ctx_mod._context  # do not raise if uninitialized: a crash dump
    # must succeed even before/after init
    if ctx is not None:
        out["world"] = {
            "size": ctx.size,
            "machine_size": ctx.machine_size,
            "local_size": ctx.local_size,
            "topo_version": ctx.topo_version,
            "ranks": _owned_ranks(ctx),
        }
        m = ctx.elastic_membership
        if m is not None:
            out["membership"] = {
                "epoch": m.epoch,
                "live": list(m.live_ranks()),
                "dead": list(m.dead_ranks()),
                "history": [
                    list(h) for h in m.history[-64:]
                ],
            }
    try:
        from bluefog_tpu import elastic as elastic_mod

        session = elastic_mod.active_session()
        if session is not None:
            out["faults"] = [
                {
                    "kind": f.kind, "rank": f.rank, "step": f.step,
                    "seconds": f.seconds, "factor": f.factor,
                }
                for f in session.plan.faults
            ]
    except Exception:  # a broken elastic import must not lose the dump
        pass
    with _plans_lock:
        out["comm_plans"] = list(_plans)
        out["fault_events"] = list(_faults)
        out["advisories"] = list(_advisories)
        out["autotune_decisions"] = list(_decisions)
        out["slo_snapshots"] = list(_slo)
        out["builds"] = list(_builds)
        out["builds_dropped"] = _builds_dropped
    try:
        out["metrics"] = metrics_mod.snapshot()
    except Exception:
        out["metrics"] = {}
    out["events"] = events()
    return out


def dump(path: Optional[str] = None, reason: str = "explicit") -> str:
    """Write the flight dump as JSON and return the path written.

    ``path`` defaults to ``<BLUEFOG_FLIGHT_DIR or .>/flight_<process
    index>.json``. The write is atomic (tmp + rename): a dump raced by a
    crashing process must never leave a half-written JSON — the file
    exists precisely to be read after something went wrong."""
    if path is None:
        base = dump_dir() or "."
        os.makedirs(base, exist_ok=True)
        path = os.path.join(
            base, f"flight_{tl.process_file_index()}.json"
        )
    with _dump_lock:
        _dump_history.append(reason)
        del _dump_history[:-32]
        payload = _build_dump(reason)
        payload["dump_history"] = list(_dump_history)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    return path


def maybe_dump(reason: str) -> Optional[str]:
    """Automatic-trigger dump: writes ``flight_<proc>.json`` into
    ``BLUEFOG_FLIGHT_DIR`` when that is configured, else does nothing
    (an unconfigured training run must not litter its cwd). Never
    raises — a failing dump must not take down the run it is trying to
    explain."""
    if not enabled() or dump_dir() is None:
        return None
    try:
        return dump(reason=reason)
    except Exception:
        logger.exception("flight dump (%s) failed", reason)
        return None


# -- automatic triggers -------------------------------------------------------


def _on_stall(name: str, waited: float) -> None:
    """Watchdog subscriber: a blocking wait outlived its deadline — the
    exact moment a hang becomes observable, so the black box goes to
    disk now, while the evidence is fresh."""
    record("stall", name=name, waited_s=round(float(waited), 3))
    maybe_dump(f"stall:{name}")


def _excepthook(exc_type, exc, tb):
    try:
        record(
            "crash", type=exc_type.__name__, message=str(exc)[:300]
        )
        maybe_dump(f"exception:{exc_type.__name__}")
    except Exception:
        pass
    hook = _prev_excepthook or sys.__excepthook__
    hook(exc_type, exc, tb)


def _sigterm_handler(signum, frame):
    try:
        record("sigterm")
        maybe_dump("sigterm")
    except Exception:
        pass
    prev = _prev_sigterm
    if callable(prev):
        prev(signum, frame)
        return
    # default/ignored disposition: restore it and re-deliver so the
    # process still dies with the expected SIGTERM status
    signal.signal(signal.SIGTERM, prev if prev is not None
                  else signal.SIG_DFL)
    os.kill(os.getpid(), signum)


def _install_crash_hooks() -> None:
    global _hooks_installed, _prev_excepthook, _prev_sigterm
    if _hooks_installed:
        return
    _prev_excepthook = sys.excepthook
    sys.excepthook = _excepthook
    try:
        _prev_sigterm = signal.signal(signal.SIGTERM, _sigterm_handler)
    except (ValueError, OSError):  # not the main thread / exotic platform
        _prev_sigterm = None
    _hooks_installed = True


def _uninstall_crash_hooks() -> None:
    global _hooks_installed, _prev_excepthook, _prev_sigterm
    if not _hooks_installed:
        return
    if sys.excepthook is _excepthook:
        sys.excepthook = _prev_excepthook or sys.__excepthook__
    try:
        if signal.getsignal(signal.SIGTERM) is _sigterm_handler:
            signal.signal(
                signal.SIGTERM,
                _prev_sigterm if _prev_sigterm is not None
                else signal.SIG_DFL,
            )
    except (ValueError, OSError):
        pass
    _hooks_installed = False
    _prev_excepthook = None
    _prev_sigterm = None


# -- session lifecycle (called by bluefog_tpu.context) ------------------------

# what ``bluefog_tpu/__init__.py`` measured of its own import: the seconds
# from its first statement to its last import, with what that pulls in, and
# whether ``jax`` was in ``sys.modules`` before (then its import is not in
# ``import_s``, as under a caller that imports jax first)
_import: Dict[str, Any] = {"import_s": None, "jax_preloaded": None}


def note_import(import_s: float, jax_preloaded: bool) -> None:
    """Called once, by the package's ``__init__``, after its last import."""
    _import.update(import_s=import_s, jax_preloaded=jax_preloaded)


def _process_age_s() -> Optional[float]:
    """Seconds since this process was started, or None where that cannot
    be read (Linux only: ``starttime``, field 22 of ``/proc/self/stat``,
    in clock ticks since boot, against ``CLOCK_BOOTTIME``). Interpreter
    start, every import so far, the backend's start and the caller's own
    work before ``bf.init()`` are all inside it."""
    try:
        with open("/proc/self/stat") as f:
            # the command (field 2) may hold spaces: count from its ")"
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (
            time.clock_gettime(time.CLOCK_BOOTTIME)
            - started / os.sysconf("SC_CLK_TCK")
        )
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return age if age >= 0 else None


def on_init(ctx) -> None:
    """Open the black box for a fresh session: new ring, clock
    handshake event, watchdog subscription, and (when a dump directory
    is configured) the crash hooks."""
    reconfigure()
    if not enabled():
        return
    _listen_to_jax()
    record(
        "session_start",
        **_clock_triple(),
        process_index=tl.process_file_index(),
        size=ctx.size,
        machine_size=ctx.machine_size,
        pid=os.getpid(),
        # the way here rides the event: the ring is new at every init
        **_import,
        process_age_s=_process_age_s(),
    )
    watchdog.add_stall_handler(_on_stall)  # idempotent (same fn object)
    if dump_dir() is not None:
        _install_crash_hooks()


def on_shutdown() -> None:
    record("session_end", **_clock_triple())
    watchdog.remove_stall_handler(_on_stall)
    _uninstall_crash_hooks()
