# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Step-time attribution observatory (``bf.doctor``).

The repo can record what happened (:mod:`bluefog_tpu.flight`) and count
what moved (:mod:`bluefog_tpu.metrics`); this module attributes *where a
step's time goes* and turns the residual against the compiler's cost
model into live diagnosis. It exists because a headline number that
moves between rounds is uninterpretable without decomposition: was it
compute (ambient host drift), the wire (a degraded link), the host
(a recompile storm), or the algorithm (consensus stalling)?

**Sampling discipline.** The doctor reuses the PR-3 metrics cadence: one
communicating step in every ``BLUEFOG_DOCTOR_INTERVAL`` (default 100) is
a *sample*; every other step pays one integer compare. Crucially the
doctor NEVER changes the training program — it is purely host-side
wall-clock plus separate probe dispatches on throwaway buffers — so
unsampled steps dispatch the bitwise-identical program under the same
cache key as doctor-off (there is no ``doctor`` component in any
compiled-step cache key to diverge on), and the training trajectory is
pinned bitwise doctor-on vs doctor-off
(tests/test_doctor.py::test_doctor_off_is_bitwise_and_structurally_invisible).

**What one sample measures.**

- ``step_s`` — mean wall time per step since the previous sample (the
  all-in number: compute + exposed comm + host work + gaps).
- ``dispatch_s`` — host enqueue time of the sampled dispatch.
- ``sync_lag_s`` — time from dispatch return to output readiness (the
  depth of the async pipeline at the sample point).
- **per-round probes** — for each ppermute round of the active
  :class:`~bluefog_tpu.collective.plan.CommPlan`, a tiny dedicated
  program (``lax.ppermute`` over that round's perm on a cached probe
  buffer — never a training value) is timed and compared against the
  calibrated cost model (:func:`bluefog_tpu.collective.compiler.
  round_cost_s` /``pipelined_cost_s``, per *Synthesizing Optimal
  Collective Algorithms*, arxiv 2008.08708). A round whose residual
  ratio exceeds the threshold triggers a per-edge drill-down: each edge
  of the suspect round is probed alone (a one-pair ppermute), which
  localizes the slow link *within* the round — timing a collective
  round can only blame the round, timing single edges names the edge.
- ``comm_wire_s`` — the measured wire cost of one full gossip step if
  fully exposed (per-round probe times scaled to the actual wire
  payload by the calibrated beta), the ceiling on what overlap can
  hide; ``compute_s`` is the residual ``step_s - comm_wire_s -
  dispatch_s`` clamped at 0 (overlap savings show up as comm_wire_s
  exceeding the exposed share — the decomposition is an attribution
  bound, not a scheduler trace).
- ``anchor_tflops`` — a fixed small bf16 matmul timed every sample: the
  ambient-compute anchor that separates "the host got slower" from
  "the program got slower" (see docs/doctor.md).

**Online baselines and advisories.** Every series above (plus the
consensus-distance gauge, wire-byte and recompile counters read from
:mod:`bluefog_tpu.metrics`) feeds an EWMA + MAD tracker
(:class:`BaselineTracker`). Rule hits raise structured
:class:`Advisory` records:

- ``degraded_link(edge, measured/predicted)`` — a per-edge probe far
  above both the model prediction and its peers;
- ``straggler(rank)`` — two or more blamed edges sharing an endpoint;
- ``recompile_storm`` — XLA recompiles between samples at a rate no
  steady-state loop produces;
- ``consensus_stall`` — the gossip disagreement gauge rising against
  its own baseline for consecutive samples;
- ``ambient_drift`` — the anchor matmul losing throughput while the
  program is unchanged.

Each advisory is emitted simultaneously as a ``bluefog.doctor.*``
metric, a flight-recorder event + bounded side table
(:func:`bluefog_tpu.flight.note_advisory` — postmortems carry the
advisory history), and a ``ph:"i"`` timeline instant
(:func:`bluefog_tpu.timeline.timeline_record_advisory`), and appended to
``BLUEFOG_DOCTOR_FILE`` when set. ``tools/doctor.py`` fuses a doctor
dump + metrics JSONL + flight dumps into one triage report.

**Chaos parity.** Tier-1 meshes have no physically slow link, so the
PR-4 chaos layer simulates one: an active elastic session's ``degrade``
faults (now with an optional ``peer=`` edge target) add a deterministic
delay to probe dispatches whose perm crosses the degraded edge
(:meth:`bluefog_tpu.elastic.recovery.ElasticSession.
simulated_wire_factors`), so "the advisory names the injected edge" is
a reproducible unit test
(tests/test_doctor.py::test_degraded_link_advisory_names_injected_edge).

Env knobs: ``BLUEFOG_DOCTOR=1`` enables (default off),
``BLUEFOG_DOCTOR_INTERVAL`` (default 100 communicating steps),
``BLUEFOG_DOCTOR_FILE`` (JSONL samples + advisories),
``BLUEFOG_DOCTOR_PROBE_ELEMS`` (probe payload cap, default 32 Ki
elements). See docs/doctor.md.
"""

import collections
import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "BaselineTracker",
    "Advisory",
    "StepDoctor",
    "enabled",
    "doctor_interval",
    "probe_elems_cap",
    "start",
    "stop",
    "active",
    "dispatch_timer",
    "observe_step",
    "dump",
    "blame_edges",
    "on_init",
    "on_shutdown",
]

ENABLE_ENV = "BLUEFOG_DOCTOR"
INTERVAL_ENV = "BLUEFOG_DOCTOR_INTERVAL"
FILE_ENV = "BLUEFOG_DOCTOR_FILE"
PROBE_ELEMS_ENV = "BLUEFOG_DOCTOR_PROBE_ELEMS"

# A round (or drilled-down edge) is anomalous when its measured time
# exceeds this multiple of BOTH the model prediction and the median of
# its peers — the double gate keeps a garbage calibration (or a
# uniformly slow host) from flagging every round.
DEGRADE_RATIO = 3.0
# Recompiles between samples above max(this, steps/2) = a storm.
RECOMPILE_STORM_MIN = 3
# Anchor throughput this fraction below its EWMA = ambient drift.
AMBIENT_DRIFT_FRAC = 0.10
# Consecutive drifted samples before ambient_drift fires: one dipped
# anchor measurement on a shared host is load noise, not drift.
AMBIENT_STREAK = 2
# Consecutive rising-disagreement samples before consensus_stall fires.
CONSENSUS_STREAK = 2

_ADVISORY_KINDS = (
    "degraded_link", "straggler", "recompile_storm", "consensus_stall",
    "ambient_drift",
)


def enabled() -> bool:
    """Doctor switch: ``BLUEFOG_DOCTOR=1`` (default off). Like the
    metrics device tier, attribution is opt-in — it is a diagnosis
    surface, not an always-on recorder (that is the flight ring's
    job)."""
    return os.environ.get(ENABLE_ENV, "0").lower() in (
        "1", "true", "on", "yes",
    )


def doctor_interval() -> int:
    """Sampling period in communicating steps
    (``BLUEFOG_DOCTOR_INTERVAL``, default 100). A sample costs roughly
    one settled step plus a handful of tiny probe dispatches, so at the
    default it amortizes to about one step in a hundred; shrink it when
    actively chasing a regression."""
    from bluefog_tpu.logging_util import env_int

    return max(1, env_int(INTERVAL_ENV, 100))


def probe_elems_cap() -> int:
    """Per-probe payload budget in f32 elements
    (``BLUEFOG_DOCTOR_PROBE_ELEMS``, default 32 Ki = 128 KiB): large
    enough that the beta term is visible against dispatch latency,
    small enough that a sample stays cheap. Probe times are scaled to
    the actual wire payload through the calibrated alpha-beta model."""
    from bluefog_tpu.logging_util import env_int

    return max(512, env_int(PROBE_ELEMS_ENV, 1 << 15))


# -- online baseline ----------------------------------------------------------


class BaselineTracker:
    """EWMA mean + EWMA median-absolute-deviation over one scalar
    series. ``update(x)`` returns the *signed z-score of x against the
    baseline as it stood before absorbing x* — the first observation
    scores 0 and seeds the baseline. MAD is floored at 1 % of the mean
    so a perfectly quiet warmup cannot make every later jitter an
    outlier."""

    def __init__(self, alpha: float = 0.2):
        self.alpha = float(alpha)
        self.mean: Optional[float] = None
        self.mad: float = 0.0
        self.n = 0

    def update(self, x: float) -> float:
        x = float(x)
        self.n += 1
        if self.mean is None:
            self.mean = x
            return 0.0
        dev = x - self.mean
        floor = max(self.mad, abs(self.mean) * 0.01, 1e-12)
        z = dev / floor
        a = self.alpha
        self.mean += a * dev
        self.mad += a * (abs(dev) - self.mad)
        return z

    def describe(self) -> dict:
        return {"mean": self.mean, "mad": self.mad, "n": self.n}


@dataclasses.dataclass(frozen=True)
class Advisory:
    """One structured diagnosis. ``detail`` is JSON-serializable — it
    rides verbatim into the flight dump, the doctor JSONL, and the
    timeline instant name."""

    kind: str
    step: int
    detail: Dict[str, Any]

    def to_json(self) -> dict:
        return {"kind": self.kind, "step": self.step, **self.detail}


def blame_edges(
    round_times_s: Sequence[float],
    predicted_s: Sequence[float],
    perms: Sequence[Sequence[Tuple[int, int]]],
    ratio: float = DEGRADE_RATIO,
) -> List[int]:
    """Indices of anomalous rounds: measured time above ``ratio`` times
    BOTH the model prediction and the median of the other rounds. Pure
    (unit-testable) core of the degraded-link detector; the per-edge
    drill-down then separates edges *within* a flagged round, which
    timing the collective round alone cannot."""
    if not round_times_s:
        return []
    srt = sorted(round_times_s)
    # LOWER median: with an even round count and one slow round, the
    # upper median would be the outlier itself and mask it
    median = srt[(len(srt) - 1) // 2]
    out = []
    for i, t in enumerate(round_times_s):
        pred = predicted_s[i] if i < len(predicted_s) else median
        if t > ratio * max(pred, 1e-12) and t > ratio * max(median, 1e-12):
            out.append(i)
    return out


def suspect_join(include_stall_holds: bool = False) -> List[Any]:
    """Edges/ranks that corroborate a fabric-health advisory: the
    chaos layer's active degrade faults (and, with
    ``include_stall_holds``, its active stall payload holds) plus this
    doctor's recent ``degraded_link`` edges. One implementation for
    the health plane's ``mixing_degraded`` suspects and the staleness
    observatory's ``staleness_breach`` suspects — the detectors prove
    a contract is broken; this join names who plausibly broke it.
    Edges render as ``[src, dst]``, rank-wide faults as
    ``{"rank": n}``."""
    out: List[Any] = []

    def add(key):
        item = (
            [int(key[0]), int(key[1])] if isinstance(key, tuple)
            else {"rank": int(key)}
        )
        if item not in out:
            out.append(item)

    try:
        from bluefog_tpu import elastic as elastic_mod

        session = elastic_mod.active_session()
    except Exception:
        session = None
    if session is not None:
        if include_stall_holds:
            holds = getattr(session, "simulated_stale_steps", None)
            for key in sorted(holds() if holds else {}, key=str):
                add(key)
        for key in sorted(session.simulated_wire_factors(), key=str):
            add(key)
    doc = active()
    if doc is not None:
        for adv in doc.advisories[-8:]:
            if adv.kind == "degraded_link":
                edge = adv.detail.get("edge")
                if edge is not None and edge not in out:
                    out.append(edge)
    return out


# -- the doctor ---------------------------------------------------------------


class StepDoctor:
    """One attribution session. Built by :func:`start` (or implicitly by
    ``bf.init()`` under ``BLUEFOG_DOCTOR=1``); fed by the optimizer
    layer through :func:`observe_step` on every communicating step."""

    def __init__(self, interval: Optional[int] = None,
                 probe_reps: int = 2, history: int = 512):
        self.interval = int(interval) if interval else doctor_interval()
        self.probe_reps = max(1, int(probe_reps))
        self._count = 0  # communicating steps observed
        self._last_sample_wall: Optional[float] = None
        self._last_sample_count = 0
        self._last_counters: Dict[str, float] = {}
        self.samples: collections.deque = collections.deque(maxlen=history)
        self.advisories: List[Advisory] = []
        # comm-step count at each emit, parallel to ``advisories`` —
        # recency consumers (the health plane's /healthz verdict)
        # compare this clock, not Advisory.step, which under K>1
        # gradient accumulation counts non-communicating steps too
        self.advisory_marks: List[int] = []
        self.trackers: Dict[str, BaselineTracker] = {}
        self._consensus_streak = 0
        self._ambient_streak = 0
        self._probe_bufs: Dict[int, Any] = {}  # elems -> device array
        self._warm_probes: set = set()  # (perm, elems) compiled+warmed
        self._anchor_ready = False
        self._calibrated = False

    # -- sampling gate --------------------------------------------------------

    def will_sample(self) -> bool:
        """True when the NEXT :meth:`observe` call is a sample — lets
        the dispatcher time the enqueue only when it will be consumed."""
        return self._count % self.interval == 0

    # -- probe plumbing -------------------------------------------------------

    def _tracker(self, name: str) -> BaselineTracker:
        t = self.trackers.get(name)
        if t is None:
            t = self.trackers[name] = BaselineTracker()
        return t

    def _probe_buffer(self, ctx, elems: int):
        buf = self._probe_bufs.get(elems)
        if buf is None:
            import numpy as np
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            from bluefog_tpu import context as ctx_mod

            buf = jax.device_put(
                np.random.RandomState(0)
                .randn(ctx.size, elems).astype(np.float32),
                NamedSharding(ctx.mesh, P(ctx_mod.WORKER_AXIS)),
            )
            self._probe_bufs[elems] = buf
        return buf

    def _probe_fn(self, ctx, perm: Tuple[Tuple[int, int], ...], elems: int):
        """Compiled one-round probe: ``lax.ppermute`` over exactly this
        perm on a [size, elems] throwaway buffer. Cached in the context
        op cache under its own ``doctor_probe`` family — training-step
        cache keys are untouched (the bitwise on/off pin rests on
        that)."""
        key = ("doctor_probe", perm, elems)
        fn = ctx.op_cache.get(key)
        if fn is None:
            import jax
            from jax import lax
            from jax.sharding import PartitionSpec as P

            from bluefog_tpu import context as ctx_mod

            axis = ctx_mod.WORKER_AXIS
            fn = jax.jit(
                jax.shard_map(
                    lambda t: lax.ppermute(t, axis, perm),
                    mesh=ctx.mesh, in_specs=P(axis), out_specs=P(axis),
                )
            )
            ctx.op_cache[key] = fn
        return fn

    def _chaos_delay_s(self, perm, payload_bytes: float) -> float:
        """Deterministic wire-slowness simulation: an active elastic
        session's degrade faults scale the modeled round cost of every
        probe whose perm crosses a degraded edge (rank-wide, or a
        single ``peer=`` edge). Tier-1 meshes have no physically slow
        link — without this, "detect the degraded link" would be
        untestable; with it, the doctor still has to LOCALIZE the edge
        from timings alone."""
        try:
            from bluefog_tpu import elastic as elastic_mod

            session = elastic_mod.active_session()
        except Exception:
            return 0.0
        if session is None:
            return 0.0
        factors = session.simulated_wire_factors()
        if not factors:
            return 0.0
        from bluefog_tpu.collective import compiler

        delay = 0.0
        for s, d in perm:
            # a rank-wide degrade slows every edge TOUCHING the rank
            # (source or destination), matching the documented "the
            # rank's gossip edges"; an edge-narrowed fault matches only
            # its exact (src, dst) pair
            f = factors.get(
                (s, d),
                min(factors.get(s, 1.0), factors.get(d, 1.0)),
            )
            # shared pricing with the autotune candidate scorer: the
            # penalty a probe measures here is exactly what a candidate
            # still carrying this edge is charged there
            delay += compiler.degraded_round_penalty_s(payload_bytes, f)
        return delay

    def _readback_s(self, ctx, elems: int) -> float:
        """Settle latency on an already-materialized array — the fixed
        per-probe cost every timed rep subtracts. Measured once per
        sample (not per rep: a sample's budget is milliseconds, and the
        correction only needs ~30 % accuracy against the 3x advisory
        thresholds)."""
        from bluefog_tpu.timing import settle

        buf = self._probe_buffer(ctx, elems)
        settle(buf)
        t0 = time.perf_counter()
        settle(buf)
        return time.perf_counter() - t0

    def _time_probe(self, ctx, perm, elems: int, rb_s: float) -> float:
        """Wall time of one probe round (best of ``probe_reps``), with
        the pre-measured readback latency ``rb_s`` subtracted — the
        :mod:`bluefog_tpu.timing` correction discipline collapsed to a
        per-sample form. The first visit of a (perm, elems) shape pays
        one warm dispatch (compile); later samples reuse it."""
        from bluefog_tpu.timing import settle

        fn = self._probe_fn(ctx, perm, elems)
        buf = self._probe_buffer(ctx, elems)
        payload_bytes = elems * 4.0
        if (perm, elems) not in self._warm_probes:
            settle(fn(buf))  # compile + warm outside the timed reps
            self._warm_probes.add((perm, elems))
        best = None
        for _ in range(self.probe_reps):
            t0 = time.perf_counter()
            out = fn(buf)
            delay = self._chaos_delay_s(perm, payload_bytes)
            if delay > 0:
                time.sleep(delay)
            settle(out)
            t1 = time.perf_counter()
            dt = (t1 - t0) - rb_s
            if dt <= 0:
                # an ambient stall distorted the correction: keep the
                # raw (upper-bound) time, never publish a fake ~0
                dt = max(t1 - t0, 1e-9)
            best = dt if best is None else min(best, dt)
        return best

    def _probe_rounds(self, ctx, plan, wire_bytes_per_round: float):
        """Measure every round of ``plan`` at the probe payload, price
        it with the calibrated model, and drill into anomalous rounds
        edge by edge. Returns (rounds report, advisories found)."""
        from bluefog_tpu.collective import compiler

        perms = plan.perms
        info = plan.compile_info
        elems = min(
            probe_elems_cap(),
            max(512, int(wire_bytes_per_round // 4) or 512),
        )
        elems -= elems % 512
        elems = max(512, elems)
        probe_bytes = elems * 4.0
        preds = compiler.predicted_round_costs_s(info, probe_bytes,
                                                 n_rounds=len(perms))
        rb_s = self._readback_s(ctx, elems)
        times = [self._time_probe(ctx, p, elems, rb_s) for p in perms]
        suspect = blame_edges(times, preds, perms)
        rounds = []
        for i, p in enumerate(perms):
            rounds.append({
                "round": i,
                "edges": [[int(s), int(d)] for s, d in p],
                "probe_ms": round(times[i] * 1e3, 4),
                "predicted_ms": round(preds[i] * 1e3, 4),
                "residual_ratio": round(times[i] / max(preds[i], 1e-12), 2),
            })
        found: List[Advisory] = []
        blamed_edges: List[Tuple[Tuple[int, int], float, float]] = []
        for i in suspect:
            # drill-down: a collective round can only be blamed as a
            # whole; probing each edge alone separates the slow link
            edge_ts = {
                e: self._time_probe(ctx, (e,), elems, rb_s)
                for e in perms[i]
            }
            pred_edge = compiler.round_cost_s(probe_bytes)
            srt_e = sorted(edge_ts.values())
            med = srt_e[(len(srt_e) - 1) // 2]  # lower median, as above
            for e, t in edge_ts.items():
                if t > DEGRADE_RATIO * max(pred_edge, 1e-12) and (
                    len(edge_ts) == 1 or t > DEGRADE_RATIO * max(med, 1e-12)
                ):
                    blamed_edges.append((e, t, pred_edge))
            rounds[i]["edge_probe_ms"] = {
                f"{s}->{d}": round(t * 1e3, 4)
                for (s, d), t in edge_ts.items()
            }
        for (s, d), t, pred in blamed_edges:
            found.append(Advisory(
                kind="degraded_link", step=self._count,
                detail={
                    "edge": [int(s), int(d)],
                    "measured_ms": round(t * 1e3, 4),
                    "predicted_ms": round(pred * 1e3, 4),
                    "ratio": round(t / max(pred, 1e-12), 2),
                },
            ))
        # >= 2 blamed edges sharing an endpoint: the common factor is
        # the rank, not a link
        by_rank: Dict[int, List] = {}
        for (s, d), t, _pred in blamed_edges:
            by_rank.setdefault(int(s), []).append([int(s), int(d)])
            by_rank.setdefault(int(d), []).append([int(s), int(d)])
        for rank, edges in sorted(by_rank.items()):
            if len(edges) >= 2:
                found.append(Advisory(
                    kind="straggler", step=self._count,
                    detail={"rank": rank, "edges": edges},
                ))
        return rounds, found, probe_bytes, sum(times)

    def _anchor_tflops(self) -> Optional[float]:
        """Fixed small bf16 matmul throughput — the per-sample ambient
        anchor. ~one millisecond per sample; n is fixed for the life of
        the process so the series is self-comparable."""
        try:
            import jax
            import jax.numpy as jnp

            from bluefog_tpu.timing import settle

            n = 256
            if not self._anchor_ready:
                self._anchor_fn = jax.jit(lambda a: (a @ a).sum())
                self._anchor_x = jnp.ones((n, n), jnp.bfloat16)
                settle(self._anchor_fn(self._anchor_x))
                self._anchor_ready = True
            reps = 4
            t0 = time.perf_counter()
            for _ in range(reps):
                out = self._anchor_fn(self._anchor_x)
            settle(out)
            t1 = time.perf_counter()
            settle(out)
            dt = max((t1 - t0) - (time.perf_counter() - t1), 1e-9) / reps
            return 2.0 * n ** 3 / dt / 1e12
        except Exception:
            return None

    # -- the observation entry point ------------------------------------------

    def observe(self, ctx, *, step: int, outputs=None, plan=None,
                params=None, wire: Optional[str] = None,
                dispatch_s: Optional[float] = None) -> Optional[dict]:
        """Called once per communicating step. Unsampled steps cost one
        compare + one increment; the sampled step runs the full
        attribution pass and returns its sample record."""
        sampled = self._count % self.interval == 0
        self._count += 1
        if not sampled:
            return None
        return self._sample(
            ctx, step=step, outputs=outputs, plan=plan, params=params,
            wire=wire, dispatch_s=dispatch_s,
        )

    def _wire_bytes_per_round(self, params, wire) -> float:
        """Total bytes one rank ships per ppermute round for this
        dispatch (all dtype groups, at the compressed wire width)."""
        if params is None:
            return float(probe_elems_cap() * 4)
        import numpy as np
        import jax

        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu.collective import ops as col_ops

        by_item: Dict[int, int] = {}
        for leaf in jax.tree_util.tree_leaves(params):
            n = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
            item = np.dtype(leaf.dtype).itemsize
            by_item[item] = by_item.get(item, 0) + n
        if wire in col_ops._COMPRESSED_WIRES:
            # collapse the dtype groups: a compressed wire reprices every
            # element identically, and wire_bytes_per_step ignores the
            # storage itemsize for quantized tiers (the key is arbitrary)
            by_item = {1: sum(by_item.values())}
        return float(metrics_mod.wire_bytes_per_step(by_item, 1, wire))

    def _sample(self, ctx, *, step, outputs, plan, params, wire,
                dispatch_s) -> dict:
        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu.collective import compiler
        from bluefog_tpu.timing import settle

        if not self._calibrated:
            # residuals only mean something against measured constants:
            # the class-sheet alpha (1 us) is orders off a CPU host's
            # real dispatch latency. One-shot; honors an existing pin
            # (calibrate() never clobbers set_calibration()).
            self._calibrated = True
            try:
                compiler.calibrate()
            except Exception:
                pass

        t_now = time.perf_counter()
        steps_elapsed = self._count - self._last_sample_count
        step_s = None
        if self._last_sample_wall is not None and steps_elapsed > 0:
            step_s = (t_now - self._last_sample_wall) / steps_elapsed
        self._last_sample_wall = t_now
        self._last_sample_count = self._count

        sync_lag_s = None
        if outputs is not None:
            t0 = time.perf_counter()
            try:
                settle(outputs)
            except Exception:
                pass
            sync_lag_s = time.perf_counter() - t0

        sample: Dict[str, Any] = {
            "kind": "sample",
            "step": int(step),
            "comm_steps": self._count,
            "steps_since_last": steps_elapsed,
        }
        if step_s is not None:
            sample["step_ms"] = round(step_s * 1e3, 4)
        if dispatch_s is not None:
            sample["dispatch_ms"] = round(dispatch_s * 1e3, 4)
        if sync_lag_s is not None:
            sample["sync_lag_ms"] = round(sync_lag_s * 1e3, 4)

        # -- per-round comm profile ------------------------------------------
        found: List[Advisory] = []
        comm_wire_s = None
        if plan is not None and getattr(plan, "perms", None):
            wire_bytes = self._wire_bytes_per_round(params, wire)
            rounds, found, probe_bytes, probe_sum_s = self._probe_rounds(
                ctx, plan, wire_bytes
            )
            sample["rounds"] = rounds
            sample["probe_payload_bytes"] = int(probe_bytes)
            sample["wire_bytes_per_round"] = int(wire_bytes)
            # scale each measured probe round to the actual payload via
            # the calibrated beta: t_full = t_probe + (B - b) * c / beta
            cal = compiler.calibration()
            beta = float(cal["beta_bytes_per_s"])
            info = plan.compile_info
            cong = (
                list(info.congestion)
                if info is not None and info.congestion else []
            )
            comm_wire_s = 0.0
            for i, r in enumerate(rounds):
                c = cong[i] if i < len(cong) else 1.0
                extra = max(wire_bytes - probe_bytes, 0.0) * c / beta
                comm_wire_s += r["probe_ms"] / 1e3 + extra
            sample["comm_wire_ms"] = round(comm_wire_s * 1e3, 4)
            if step_s is not None:
                host = dispatch_s or 0.0
                sample["compute_ms"] = round(
                    max(step_s - comm_wire_s - host, 0.0) * 1e3, 4
                )
                sample["exposed_comm_frac"] = round(
                    min(comm_wire_s / max(step_s, 1e-12), 1.0), 4
                )

        # -- registry-fed series ---------------------------------------------
        deltas = {}
        for name in ("bluefog.recompiles", "bluefog.wire_bytes"):
            series = metrics_mod.peek(name)
            cur = float(series.value) if series is not None else 0.0
            prev = self._last_counters.get(name)
            self._last_counters[name] = cur
            deltas[name] = None if prev is None else cur - prev
        if deltas["bluefog.recompiles"] is not None:
            sample["recompiles_since_last"] = deltas["bluefog.recompiles"]
        if deltas["bluefog.wire_bytes"] is not None and steps_elapsed:
            sample["wire_bytes_per_step"] = (
                deltas["bluefog.wire_bytes"] / steps_elapsed
            )
        dis = metrics_mod.peek("bluefog.gossip.disagreement")
        consensus = float(dis.value) if dis is not None else None
        if consensus is not None:
            sample["consensus_distance"] = consensus

        anchor = self._anchor_tflops()
        if anchor is not None:
            sample["anchor_tflops"] = round(anchor, 4)

        # -- baselines + rule-based advisories -------------------------------
        z_step = (
            self._tracker("step_s").update(step_s)
            if step_s is not None else 0.0
        )
        if comm_wire_s is not None:
            self._tracker("comm_wire_s").update(comm_wire_s)
        if sample.get("wire_bytes_per_step") is not None:
            self._tracker("wire_bytes").update(
                sample["wire_bytes_per_step"]
            )

        rec = deltas["bluefog.recompiles"]
        if rec is not None and rec >= max(
            RECOMPILE_STORM_MIN, steps_elapsed / 2.0
        ):
            found.append(Advisory(
                kind="recompile_storm", step=int(step),
                detail={
                    "recompiles": rec, "steps": steps_elapsed,
                },
            ))

        if consensus is not None:
            tr = self._tracker("consensus")
            z = tr.update(consensus)
            rising = z > 3.0 and consensus > (tr.mean or 0.0)
            self._consensus_streak = (
                self._consensus_streak + 1 if rising else 0
            )
            if self._consensus_streak >= CONSENSUS_STREAK:
                found.append(Advisory(
                    kind="consensus_stall", step=int(step),
                    detail={
                        "consensus_distance": consensus,
                        "baseline": tr.mean,
                        "streak": self._consensus_streak,
                    },
                ))
                self._consensus_streak = 0

        if anchor is not None:
            tr = self._tracker("anchor_tflops")
            z = tr.update(anchor)
            base = tr.mean or anchor
            drifted = tr.n > 2 and z < -3.0 and anchor < base * (
                1.0 - AMBIENT_DRIFT_FRAC
            )
            self._ambient_streak = (
                self._ambient_streak + 1 if drifted else 0
            )
            if self._ambient_streak >= AMBIENT_STREAK:
                detail = {
                    "anchor_tflops": round(anchor, 4),
                    "baseline_tflops": round(base, 4),
                    "streak": self._ambient_streak,
                }
                if step_s is not None and z_step > 3.0:
                    detail["step_ms"] = sample.get("step_ms")
                found.append(Advisory(
                    kind="ambient_drift", step=int(step), detail=detail,
                ))
                self._ambient_streak = 0

        if found:
            sample["advisories"] = [a.to_json() for a in found]
        for adv in found:
            self._emit(adv)
        self.samples.append(sample)
        self._export_line(sample)

        from bluefog_tpu import metrics as m

        if step_s is not None:
            m.gauge("bluefog.doctor.step_ms").set(step_s * 1e3)
        if comm_wire_s is not None:
            m.gauge("bluefog.doctor.comm_wire_ms").set(comm_wire_s * 1e3)
        if anchor is not None:
            m.gauge("bluefog.doctor.anchor_tflops").set(anchor)
        m.counter("bluefog.doctor.samples").inc()
        return sample

    # -- emission -------------------------------------------------------------

    def _emit(self, adv: Advisory) -> None:
        """One advisory, three surfaces + the doctor's own JSONL: the
        operator's dashboard (metrics), the postmortem (flight side
        table), and the trace (timeline instant)."""
        from bluefog_tpu import flight as flight_mod
        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu import timeline as tl

        self.advisories.append(adv)
        self.advisory_marks.append(self._count)
        metrics_mod.counter(
            f"bluefog.doctor.advisory.{adv.kind}"
        ).inc()
        metrics_mod.gauge("bluefog.doctor.last_advisory_step").set(
            adv.step
        )
        flight_mod.note_advisory(kind=adv.kind, step=adv.step,
                                 **adv.detail)
        tl.timeline_record_advisory(adv.kind, adv.detail)
        self._export_line({
            "kind": "advisory", "advisory_kind": adv.kind,
            "step": adv.step, **adv.detail,
        })

    def _export_line(self, obj: dict) -> None:
        path = os.environ.get(FILE_ENV)
        if path:
            from bluefog_tpu.logging_util import append_jsonl

            append_jsonl(FILE_ENV, path, obj)

    # -- dump ------------------------------------------------------------------

    def report(self) -> dict:
        """The attribution dump ``tools/doctor.py`` fuses: sample
        history, advisory history, baseline state, the active
        calibration."""
        from bluefog_tpu.collective import compiler

        return {
            "kind": "doctor_dump",
            "interval": self.interval,
            "comm_steps": self._count,
            "samples": list(self.samples),
            "advisories": [a.to_json() for a in self.advisories],
            "baselines": {
                k: t.describe() for k, t in sorted(self.trackers.items())
            },
            "calibration": {
                k: v for k, v in compiler.calibration().items()
                if isinstance(v, (int, float, str))
            },
        }

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.report(), f)
        return path


# -- module-level session -----------------------------------------------------

_doctor: Optional[StepDoctor] = None


def start(interval: Optional[int] = None, **kwargs) -> StepDoctor:
    """Open an attribution session (replacing any active one)."""
    global _doctor
    _doctor = StepDoctor(interval=interval, **kwargs)
    return _doctor


def stop() -> None:
    global _doctor
    _doctor = None


def active() -> Optional[StepDoctor]:
    return _doctor


def dispatch_timer(comm_now: bool) -> Optional[float]:
    """perf_counter() when the imminent dispatch will be consumed by a
    doctor sample, else None — the optimizer times its enqueue only
    when the doctor will look at it."""
    doc = _doctor
    if doc is None or not comm_now or not doc.will_sample():
        return None
    return time.perf_counter()


def observe_step(ctx, *, step: int, outputs=None, plan=None, params=None,
                 wire: Optional[str] = None,
                 dispatch_s: Optional[float] = None) -> None:
    """Optimizer-layer hook, called after every communicating dispatch.
    No-op (one attribute read) when no doctor session is active."""
    doc = _doctor
    if doc is None:
        return
    doc.observe(
        ctx, step=step, outputs=outputs, plan=plan, params=params,
        wire=wire, dispatch_s=dispatch_s,
    )


def dump(path: str) -> Optional[str]:
    """Write the active session's attribution dump (None when no
    session is active)."""
    doc = _doctor
    if doc is None:
        return None
    return doc.dump(path)


def on_init(ctx) -> None:
    """``bf.init()`` hook: auto-start a session when ``BLUEFOG_DOCTOR``
    asks for one (a fresh mesh gets a fresh baseline — stale EWMAs from
    a torn-down mesh would mis-advise the new one)."""
    if enabled():
        start()
    else:
        stop()


def on_shutdown() -> None:
    """``bf.shutdown()`` hook: flush the doctor JSONL tail and drop the
    session."""
    doc = _doctor
    if doc is not None and doc.samples:
        doc._export_line({"kind": "session_end",
                          "comm_steps": doc._count})
    stop()
