# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Memory observatory (``bf.memory``): live HBM/host accounting,
analytic-vs-measured reconciliation, and OOM forensics — the eighth
observability tier.

Seven tiers measure time, wire bytes, mixing, staleness, health and
topology decisions; none measures the one resource that actually kills
large runs: device memory. The weight-update sharding PR shipped an
*analytic* memory model (:func:`bluefog_tpu.scaling.
optimizer_state_bytes`, arxiv 2004.13336) with no measured counterpart
to reconcile it against, the kernel-fusion roadmap item (EQuARX, arxiv
2506.17615) needs a measured baseline of the full-width temporaries the
quantize→pack→ppermute→unpack chain materializes today, and an OOM
produces a bare XLA ``RESOURCE_EXHAUSTED`` with no flight dump, no
buffer census, and no advisory — the only failure mode the black box
does not record. This module closes all three gaps.

**Sampling discipline (PR-3).** 1-in-``BLUEFOG_MEMORY_INTERVAL``
communicating steps take a sample; the observatory is purely host-side
(``jax.live_arrays()`` census + device memory stats + RSS reads), so
unsampled steps — and sampled ones — dispatch the bitwise-identical
observatory-off training program under the SAME cache key
(structural + bitwise pinned by
tests/test_memory.py::test_observatory_compiles_nothing_and_stays_bitwise).

**Per sample:**

- **live-buffer census** — every live jax array classified by owner
  (``params``, ``opt_state`` incl. sharded slots, ``residuals`` =
  CHOCO/EF copies, ``delay`` buffers, ``windows``, ``other``) from the
  trees the optimizer layer hands the hook plus the window registry.
  The census total and per-category bytes land as
  ``bluefog.memory.*`` gauges and in the ``BLUEFOG_MEMORY_FILE``
  JSONL.
- **analytic-vs-measured reconciliation** — the measured per-rank
  optimizer-state bytes (census) against the analytic
  :func:`~bluefog_tpu.scaling.optimizer_state_bytes` model for the
  active shard configuration. A residual past
  ``BLUEFOG_MEMORY_DRIFT_TOL`` (default 10 %) for
  :data:`DRIFT_STREAK` consecutive samples — a leak, a stale buffer
  generation, an unaccounted slot — fires a ``memory_drift`` advisory
  through the PR-7 plumbing (doctor counter, flight side table,
  timeline instant, JSONL).
- **watermark + headroom** — a peak-bytes watermark (census total,
  plus per-phase peaks from :func:`phase_scope` around compile /
  dispatch / checkpoint-save), tracked EWMA+MAD
  (:class:`~bluefog_tpu.attribution.BaselineTracker`). With a
  per-chip budget (``BLUEFOG_MEMORY_BUDGET`` bytes), measured
  headroom below the predicted next-step watermark fires a
  ``memory_pressure`` advisory whose detail carries a
  **shard-recommendation hint**: when the optimizer state dominates
  and ``BLUEFOG_SHARD`` is off, the advisory says so (the 1/N shard
  is the one knob that buys back that category). Autotune
  :class:`~bluefog_tpu.autotune.DecisionRecord` entries carry a
  ``memory_pressure`` flag so the audit trail shows which topology
  decisions were taken under memory pressure.

**OOM forensics.** Crash hooks installed beside the PR-5 SIGTERM hooks:
an uncaught ``MemoryError`` or an XLA error whose message carries
``RESOURCE_EXHAUSTED`` records an ``oom`` flight event, files an
eviction-proof ``oom`` advisory whose detail is the **ranked buffer
census** (largest owner category first, from the last sample — the
allocation that failed is precisely the moment a fresh census cannot
run), and dumps the flight ring. A new ``oom`` chaos fault kind
(:mod:`bluefog_tpu.elastic.faults`) simulates the failure
deterministically so the postmortem is a tier-1 unit test:
``inject("oom", rank=r, step=s)`` runs the same forensics path and
raises :class:`SimulatedResourceExhausted`. ``tools/memory_report.py``
reconstructs the postmortem — who was the biggest owner when the chip
ran out — from the committed dump/JSONL artifacts alone.

**Fleet.** Each rank's census total and headroom ride the health
plane's push-sum lane (two ``FLEET_FIELDS`` slots), ``/fleet`` carries
a ``memory`` block, and ``tools/fleet_report.py`` renders the
columns.

Env knobs: ``BLUEFOG_MEMORY=1`` (default off),
``BLUEFOG_MEMORY_INTERVAL`` (default 20 communicating steps),
``BLUEFOG_MEMORY_BUDGET`` (per-chip bytes; 0/unset = no budget, no
pressure gate), ``BLUEFOG_MEMORY_DRIFT_TOL`` (relative reconciliation
tolerance, default 0.10), ``BLUEFOG_MEMORY_FILE`` (JSONL samples +
advisories). See docs/memory.md.
"""

import collections
import contextlib
import json
import os
import sys
from typing import Any, Dict, List, Optional

__all__ = [
    "MemoryObservatory",
    "SimulatedResourceExhausted",
    "CATEGORIES",
    "enabled",
    "memory_interval",
    "memory_budget",
    "drift_tolerance",
    "device_bytes_in_use",
    "host_peak_rss_bytes",
    "census",
    "ranked_census",
    "phase_scope",
    "start",
    "stop",
    "active",
    "observe_step",
    "on_oom",
    "dump",
    "on_init",
    "on_shutdown",
]

ENABLE_ENV = "BLUEFOG_MEMORY"
INTERVAL_ENV = "BLUEFOG_MEMORY_INTERVAL"
BUDGET_ENV = "BLUEFOG_MEMORY_BUDGET"
DRIFT_TOL_ENV = "BLUEFOG_MEMORY_DRIFT_TOL"
FILE_ENV = "BLUEFOG_MEMORY_FILE"

# Owner categories of the live-buffer census, in ranking-tiebreak
# order. "grads" covers the gradient buffers the optimizer layer holds
# across a dispatch (the input gradient tree plus the K>1 accumulator
# — full-width replicated, or the 1/N scattered slots under
# BLUEFOG_SHARD_GRADS=1, so the ZeRO-2 memory claim is visible in the
# census), "residuals" the CHOCO error-feedback copies (gossip pairs
# and the per-slot scatter residuals), "delay" the delayed-combine
# double buffers, "windows" every win_create buffer (value + neighbor
# slots + p lanes), "wire_temp" is reserved for the XLA temporary
# accounting (read from the compiled program's memory analysis, not
# from live arrays), "other" is everything unattributed — batches,
# user state, framework internals.
CATEGORIES = (
    "params", "opt_state", "grads", "residuals", "delay", "windows",
    "wire_temp", "other",
)

# memory_drift gate: the relative analytic-vs-measured residual must
# exceed the tolerance for this many CONSECUTIVE samples before the
# advisory fires — one sample mid-rebuild (old and new buffer
# generations briefly coexist) is churn, not a leak.
DRIFT_STREAK = 2
# memory_pressure / memory_drift re-fire mute, in samples (the
# staleness-breach cooldown discipline): a persistently tight chip
# keeps its counter raised without flooding the flight ring.
ADVISORY_COOLDOWN = 8
# predicted next-step watermark = EWMA mean + this many MADs (the
# advisory-gate z the doctor's trackers use throughout).
WATERMARK_MADS = 3.0


class SimulatedResourceExhausted(MemoryError):
    """The chaos layer's deterministic stand-in for an XLA
    ``RESOURCE_EXHAUSTED`` allocation failure (the ``oom`` fault
    kind). A ``MemoryError`` subclass whose message carries the XLA
    casing, so every detection path — the crash hooks' type check and
    their message scan — sees exactly what a real OOM produces."""

    def __init__(self, detail: str = ""):
        super().__init__(
            "RESOURCE_EXHAUSTED: simulated allocation failure"
            + (f" ({detail})" if detail else "")
        )


def enabled() -> bool:
    """Observatory switch: ``BLUEFOG_MEMORY=1`` (default off) — opt-in
    like the metrics device tier, the doctor, and the staleness
    observatory. The OOM crash hooks are independent of this knob:
    they install whenever the flight recorder has a dump directory
    configured (``BLUEFOG_FLIGHT_DIR``), the same condition as the
    PR-5 crash hooks they stand beside — forensics follow the black
    box's configuration, not the sampling tier's."""
    return os.environ.get(ENABLE_ENV, "0").lower() in (
        "1", "true", "on", "yes",
    )


def memory_interval() -> int:
    """Sampling period in communicating steps
    (``BLUEFOG_MEMORY_INTERVAL``, default 20). A sample is one
    ``jax.live_arrays()`` walk plus O(leaves) id lookups — host-only —
    paid one step in twenty at the default."""
    from bluefog_tpu.logging_util import env_int

    return max(1, env_int(INTERVAL_ENV, 20))


def memory_budget() -> int:
    """Per-chip memory budget in bytes (``BLUEFOG_MEMORY_BUDGET``; 0 /
    unset disables the headroom gate). On a real TPU this is the HBM
    capacity minus the reserve the serving stack needs; on the CPU CI
    mesh it is whatever the test simulates."""
    from bluefog_tpu.logging_util import env_int

    return max(0, env_int(BUDGET_ENV, 0))


def drift_tolerance() -> float:
    """Relative analytic-vs-measured reconciliation tolerance
    (``BLUEFOG_MEMORY_DRIFT_TOL``, default 0.10). The analytic model
    prices the slot layout exactly, so a persistent residual past this
    is a real unaccounted buffer, not rounding."""
    from bluefog_tpu.logging_util import env_float

    tol = env_float(DRIFT_TOL_ENV, 0.10)
    return tol if tol > 0 else 0.10


# -- measurement primitives ---------------------------------------------------


def device_bytes_in_use(ctx=None) -> Optional[int]:
    """``bytes_in_use`` summed over the context's devices via the
    runtime's ``memory_stats()`` (real HBM numbers on TPU). None where
    the backend exposes no stats — the CPU CI mesh — in which case the
    census total is the measured stand-in and the artifact says so."""
    try:
        import jax

        devices = ctx.devices if ctx is not None else jax.devices()
        total = 0
        seen = False
        for d in devices:
            stats = getattr(d, "memory_stats", lambda: None)()
            if stats and "bytes_in_use" in stats:
                total += int(stats["bytes_in_use"])
                seen = True
        return total if seen else None
    except Exception:
        return None


def host_peak_rss_bytes() -> int:
    """Peak resident set size of this controller process in bytes
    (Linux ``ru_maxrss`` is KiB; 0 where unavailable)."""
    try:
        import resource

        return int(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        ) * 1024
    except Exception:
        return 0


def census(owners: Dict[str, Any]) -> Dict[str, Dict[str, int]]:
    """Classify every live jax array by owner category.

    ``owners`` maps category name -> pytree of arrays (the optimizer
    layer passes the CURRENT params / optax state / EF copies / delay
    buffers; the window registry is folded in by the observatory).
    Identity is ``id()`` membership — jax arrays are replaced
    functionally every step, so the map is built fresh per sample from
    the trees that are live *now*, never registered and left to go
    stale. Everything unmatched is ``other``. Returns
    ``{category: {"bytes": B, "arrays": N}}`` with every category
    present (zeros included) so artifact rows are schema-stable."""
    import jax

    id2cat: Dict[int, str] = {}
    for cat, tree in owners.items():
        for leaf in jax.tree_util.tree_leaves(tree):
            id2cat[id(leaf)] = cat
    out = {c: {"bytes": 0, "arrays": 0} for c in CATEGORIES}
    for arr in jax.live_arrays():
        cat = id2cat.get(id(arr), "other")
        rec = out.setdefault(cat, {"bytes": 0, "arrays": 0})
        try:
            nbytes = int(arr.nbytes)
        except Exception:
            continue
        rec["bytes"] += nbytes
        rec["arrays"] += 1
    return out


def ranked_census(c: Optional[Dict[str, Dict[str, int]]] = None
                  ) -> List[dict]:
    """The census as a ranked list, largest owner first — the form the
    OOM postmortem names its suspect in. With no argument, uses the
    active observatory's last census (the fresh-census fallback exists
    because the crash hook may fire before the first sample)."""
    if c is None:
        obs = _observatory
        c = obs.last_census if obs is not None else None
        if c is None:
            try:
                c = census({})
            except Exception:
                c = {}
    rows = [
        {"category": cat, "bytes": rec["bytes"],
         "arrays": rec["arrays"]}
        for cat, rec in c.items() if rec["arrays"] or rec["bytes"]
    ]
    rows.sort(key=lambda r: (-r["bytes"], r["category"]))
    return rows


# -- phase watermarks ---------------------------------------------------------


@contextlib.contextmanager
def phase_scope(name: str):
    """Bracket one step phase (``compile`` / ``dispatch`` /
    ``checkpoint_save``) so the observatory can decompose the peak
    watermark by phase. No-op — one global read — when no observatory
    session is active; never touches device values, so the bitwise
    pin holds trivially."""
    obs = _observatory
    if obs is None:
        yield
        return
    rss0 = host_peak_rss_bytes()
    try:
        yield
    finally:
        obs._note_phase(name, rss0)


# -- the observatory session --------------------------------------------------


class MemoryObservatory:
    """One memory session. Built by :func:`start` (or implicitly by
    ``bf.init()`` under ``BLUEFOG_MEMORY=1``); fed by the optimizer
    layer through :func:`observe_step` after every communicating
    dispatch."""

    def __init__(self, interval: Optional[int] = None,
                 budget: Optional[int] = None,
                 drift_tol: Optional[float] = None,
                 history: int = 512):
        from bluefog_tpu.attribution import BaselineTracker

        self.interval = int(interval) if interval else memory_interval()
        self.budget = int(budget) if budget is not None else memory_budget()
        self.drift_tol = (
            float(drift_tol) if drift_tol else drift_tolerance()
        )
        self._count = 0  # communicating steps observed
        self.samples: collections.deque = collections.deque(
            maxlen=history
        )
        self.advisories: List[Any] = []
        self.last_census: Optional[Dict[str, Dict[str, int]]] = None
        self._peak_tracker = BaselineTracker()
        self._drift_streak = 0
        self._mutes: Dict[str, int] = {}  # advisory kind -> cooldown
        self.phase_peaks: Dict[str, Dict[str, float]] = {}
        self._peak_bytes = 0.0
        self._last_total = 0.0
        self._last_per_rank = 0.0
        self._last_headroom: Optional[float] = None
        self._analytic_cache: Optional[tuple] = None
        self._last_step = 0
        self.oom_events = 0

    # -- fleet-facing state ---------------------------------------------------

    def last_bytes_per_rank(self) -> float:
        """Census total divided by the mesh size at the latest sample
        (0.0 before the first) — the per-chip usage estimate the fleet
        lane aggregates. On a single-controller virtual mesh the
        worker-stacked arrays hold every rank's slice in one host
        process, so total/size is exactly the per-chip share."""
        return self._last_per_rank

    def last_headroom(self) -> float:
        """Budget minus per-rank usage at the latest sample (0.0 when
        no budget is configured — the lane aggregates a number, and an
        unbudgeted rank must not read as infinitely roomy)."""
        h = self._last_headroom
        return float(h) if h is not None else 0.0

    # -- phase watermarks -----------------------------------------------------

    def _note_phase(self, name: str, rss0: int) -> None:
        from bluefog_tpu import metrics as metrics_mod

        rss1 = host_peak_rss_bytes()
        rec = self.phase_peaks.setdefault(
            name, {"peak_rss_bytes": 0.0, "rss_growth_bytes": 0.0,
                   "count": 0}
        )
        rec["peak_rss_bytes"] = max(rec["peak_rss_bytes"], float(rss1))
        rec["rss_growth_bytes"] += float(max(rss1 - rss0, 0))
        rec["count"] += 1
        metrics_mod.gauge(
            f"bluefog.memory.phase_peak_bytes.{name}"
        ).set(rec["peak_rss_bytes"])

    # -- advisory gating ------------------------------------------------------

    def _tick_mutes(self) -> None:
        """Advance the re-fire cooldowns by one SAMPLE — called once
        per sample, not per gate check, so a mute expires after
        :data:`ADVISORY_COOLDOWN` samples of wall progress regardless
        of whether anything fired in between (a stale mute must never
        swallow a new episode hours later), and one kind's gate never
        drains another kind's cooldown."""
        for k in list(self._mutes):
            self._mutes[k] -= 1
            if self._mutes[k] <= 0:
                del self._mutes[k]

    def _unmuted(self, kind: str) -> bool:
        if kind in self._mutes:
            return False
        self._mutes[kind] = ADVISORY_COOLDOWN
        return True

    def pressure_active(self) -> bool:
        """True while a ``memory_pressure`` advisory is inside its
        re-fire cooldown — the precise form of "an un-cooled-down
        pressure advisory on record" the autotune decision flag
        documents."""
        return "memory_pressure" in self._mutes

    # -- observation ----------------------------------------------------------

    def observe(self, ctx, *, step: int, optimizer=None, params=None,
                opt_state=None, grads=None) -> Optional[dict]:
        """Called once per communicating step. Unsampled steps cost one
        compare + one increment; the sampled step walks the live-array
        census and reconciles it against the analytic models."""
        sampled = self._count % self.interval == 0
        self._count += 1
        # TRAINING-step clock for the OOM record: every other advisory
        # carries the training step, and a postmortem join that mixes
        # clocks mis-orders the OOM against the pressure warnings that
        # preceded it
        self._last_step = int(step)
        if not sampled:
            return None
        return self._sample(
            ctx, step=step, optimizer=optimizer, params=params,
            opt_state=opt_state, grads=grads,
        )

    def _owner_trees(self, ctx, optimizer, params, opt_state,
                     grads=None) -> Dict:
        owners: Dict[str, Any] = {}
        if params is not None:
            owners["params"] = params
        if opt_state is not None:
            owners["opt_state"] = opt_state
        grad_trees = []
        if grads is not None:
            grad_trees.append(grads)
        if optimizer is not None:
            ef = getattr(optimizer, "_ef", None)
            scatter_ef = getattr(optimizer, "_scatter_ef", None)
            if ef or scatter_ef:
                owners["residuals"] = (ef or (), scatter_ef or ())
            buf = getattr(optimizer, "_delay_buf", None)
            if buf:
                owners["delay"] = buf
            accum = getattr(optimizer, "_grad_accum", None)
            if accum is not None:
                grad_trees.append(accum)
        if grad_trees:
            owners["grads"] = grad_trees
        wins = getattr(ctx, "windows", None)
        if wins:
            owners["windows"] = [
                (w.value, w.buffers, w.versions, w.p, w.p_buffers)
                for w in wins.values()
            ]
        return owners

    def _analytic_state_bytes(self, ctx, optimizer, params,
                              opt_state) -> Optional[int]:
        """The analytic per-rank optimizer-state model for the ACTIVE
        shard configuration (None when there is nothing to price).
        Cached on (param avals, shard signature, tx version): the
        model only moves when one of those does, and re-running
        ``jax.eval_shape`` per sample would spend the overhead budget
        on re-deriving a constant."""
        if optimizer is None or params is None:
            return None
        try:
            import jax

            from bluefog_tpu import scaling

            shard_l = getattr(optimizer, "_shard_layout", None)
            key = (
                tuple(
                    (tuple(l.shape), str(l.dtype))
                    for l in jax.tree_util.tree_leaves(params)
                ),
                shard_l.sig() if shard_l is not None else None,
                getattr(optimizer, "_tx_version", None),
            )
            cached = self._analytic_cache
            if cached is not None and cached[0] == key:
                return cached[1]
            val = scaling.optimizer_state_bytes(
                params, optimizer, shard=shard_l is not None,
            )
            self._analytic_cache = (key, val)
            return val
        except Exception:
            return None

    def _sample(self, ctx, *, step, optimizer, params,
                opt_state, grads=None) -> dict:
        from bluefog_tpu import flight as flight_mod
        from bluefog_tpu import metrics as metrics_mod

        self._tick_mutes()
        owners = self._owner_trees(
            ctx, optimizer, params, opt_state, grads=grads
        )
        c = census(owners)
        self.last_census = c
        total = float(sum(rec["bytes"] for rec in c.values()))
        size = max(int(getattr(ctx, "size", 1)), 1)
        per_rank = total / size
        dev_bytes = device_bytes_in_use(ctx)
        measured_per_rank = (
            dev_bytes / size if dev_bytes is not None else per_rank
        )
        self._last_total = total
        self._last_per_rank = measured_per_rank
        self._peak_bytes = max(self._peak_bytes, measured_per_rank)

        # registry gauges
        metrics_mod.counter("bluefog.memory.samples").inc()
        metrics_mod.gauge("bluefog.memory.live_bytes").set(total)
        for cat in CATEGORIES:
            if cat == "wire_temp":
                # reserved for the compiled-program scratch accounting
                # (read from the compiled program's memory_analysis());
                # the live-array census can never populate it, and a
                # permanently-zero gauge is registry noise
                continue
            metrics_mod.gauge(
                f"bluefog.memory.live_bytes.{cat}"
            ).set(c.get(cat, {}).get("bytes", 0))
        metrics_mod.gauge("bluefog.memory.peak_bytes").set(
            self._peak_bytes
        )
        metrics_mod.gauge("bluefog.memory.host_rss_bytes").set(
            host_peak_rss_bytes()
        )

        # analytic-vs-measured optimizer-state reconciliation
        measured_state = c.get("opt_state", {}).get("bytes", 0) / size
        analytic_state = self._analytic_state_bytes(
            ctx, optimizer, params, opt_state
        )
        rel_err = None
        if analytic_state:
            rel_err = abs(measured_state - analytic_state) / analytic_state
            metrics_mod.gauge("bluefog.memory.drift_bytes").set(
                measured_state - analytic_state
            )

        sample: Dict[str, Any] = {
            "kind": "sample",
            "step": int(step),
            "comm_steps": self._count,
            "live_bytes_total": int(total),
            "live_bytes_per_rank": int(per_rank),
            "device_bytes_in_use": dev_bytes,
            "measured_source": (
                "device_memory_stats" if dev_bytes is not None
                else "live_array_census"
            ),
            "host_peak_rss_bytes": host_peak_rss_bytes(),
            "census": {
                cat: dict(rec) for cat, rec in c.items()
                if rec["arrays"] or rec["bytes"]
            },
            "peak_bytes_per_rank": int(self._peak_bytes),
        }
        if analytic_state is not None:
            sample["measured_state_bytes"] = int(measured_state)
            sample["analytic_state_bytes"] = int(analytic_state)
            sample["reconcile_rel_err"] = (
                round(rel_err, 6) if rel_err is not None else None
            )

        # drift gate: persistent residual -> memory_drift
        if rel_err is not None and rel_err > self.drift_tol:
            self._drift_streak += 1
        else:
            self._drift_streak = 0
        if self._drift_streak >= DRIFT_STREAK and self._unmuted(
            "memory_drift"
        ):
            self._advise(
                "memory_drift", step,
                {
                    "measured_state_bytes": int(measured_state),
                    "analytic_state_bytes": int(analytic_state),
                    "rel_err": round(rel_err, 6),
                    "tolerance": self.drift_tol,
                    "streak": self._drift_streak,
                    "census": ranked_census(c)[:4],
                },
                sample,
            )

        # headroom gate: budget-aware pressure tracking
        z = self._peak_tracker.update(measured_per_rank)
        if self.budget:
            headroom = float(self.budget) - measured_per_rank
            self._last_headroom = headroom
            metrics_mod.gauge("bluefog.memory.headroom_bytes").set(
                headroom
            )
            tr = self._peak_tracker
            predicted_next = float(tr.mean or measured_per_rank) + \
                WATERMARK_MADS * float(tr.mad)
            predicted_next = max(predicted_next, 0.0)
            sample["headroom_bytes"] = int(headroom)
            sample["predicted_next_watermark"] = int(predicted_next)
            # the gate: no headroom left, or the predicted next-step
            # watermark (EWMA + 3 MAD of the measured per-rank peak)
            # already exceeds the budget — measured headroom below the
            # next step's watermark, in the ISSUE's phrasing
            pressed = headroom <= 0 or (
                float(self.budget) - predicted_next
            ) <= 0
            if pressed and self._unmuted("memory_pressure"):
                from bluefog_tpu import sharding

                shard_on = sharding.enabled()
                state_frac = (
                    measured_state / measured_per_rank
                    if measured_per_rank else 0.0
                )
                self._advise(
                    "memory_pressure", step,
                    {
                        "budget_bytes": self.budget,
                        "bytes_per_rank": int(measured_per_rank),
                        "headroom_bytes": int(headroom),
                        "predicted_next_watermark": int(predicted_next),
                        "z": round(float(z), 3),
                        "census": ranked_census(c)[:4],
                        # the shard-recommendation hint: the optimizer
                        # state is the one category BLUEFOG_SHARD=1
                        # shrinks to 1/N, so the advisory names the
                        # knob exactly when it would help
                        "shard_hint": bool(
                            not shard_on and state_frac >= 0.25
                        ),
                        "opt_state_fraction": round(state_frac, 4),
                        "shard_enabled": bool(shard_on),
                    },
                    sample,
                )
        if self.phase_peaks:
            sample["phase_peaks"] = {
                k: dict(v) for k, v in sorted(self.phase_peaks.items())
            }

        flight_mod.record(
            "memory", live_bytes=int(total),
            per_rank=int(measured_per_rank),
            headroom=sample.get("headroom_bytes"),
        )
        self.samples.append(sample)
        self._export_line(sample)
        return sample

    # -- OOM forensics --------------------------------------------------------

    def note_oom(self, reason: str, message: str = "") -> List[dict]:
        """The forensics core: ranked census + flight event +
        eviction-proof advisory + dump. Returns the ranked census (the
        postmortem's suspect list). Never raises — forensics must not
        take down the process it is explaining (any further than the
        OOM already has)."""
        from bluefog_tpu import flight as flight_mod
        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu import timeline as tl

        self.oom_events += 1
        ranked = ranked_census(self.last_census)
        try:
            metrics_mod.counter("bluefog.memory.oom_events").inc()
            detail = {
                "reason": reason,
                "message": message[:300],
                "ranked_census": ranked,
                "top_category": (
                    ranked[0]["category"] if ranked else None
                ),
                "bytes_per_rank": int(self._last_per_rank),
                "budget_bytes": self.budget or None,
                "host_peak_rss_bytes": host_peak_rss_bytes(),
            }
            flight_mod.record("oom", reason=reason,
                              top_category=detail["top_category"])
            # the TRAINING-step clock, like every other advisory: the
            # postmortem joins the oom against the pressure warnings
            # by step, and mixed clocks would mis-order them
            flight_mod.note_advisory(kind="oom", step=self._last_step,
                                     **detail)
            tl.timeline_record_advisory("oom", {"reason": reason})
            self._export_line({
                "kind": "advisory", "advisory_kind": "oom",
                "step": self._last_step, **detail,
            })
            flight_mod.maybe_dump(f"oom:{reason}")
        except Exception:
            pass
        return ranked

    # -- emission -------------------------------------------------------------

    def _advise(self, kind: str, step: int, detail: dict,
                sample: dict) -> None:
        """One advisory, the PR-7 surfaces: ``bluefog.doctor.*``
        metrics, flight side table, timeline instant, memory JSONL."""
        from bluefog_tpu import flight as flight_mod
        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu import timeline as tl
        from bluefog_tpu.attribution import Advisory

        adv = Advisory(kind=kind, step=int(step), detail=detail)
        self.advisories.append(adv)
        metrics_mod.counter(f"bluefog.doctor.advisory.{kind}").inc()
        metrics_mod.gauge("bluefog.doctor.last_advisory_step").set(
            adv.step
        )
        flight_mod.note_advisory(kind=kind, step=adv.step, **detail)
        tl.timeline_record_advisory(kind, detail)
        sample.setdefault("advisories", []).append(adv.to_json())
        self._export_line({
            "kind": "advisory", "advisory_kind": kind,
            "step": adv.step, **detail,
        })

    def _export_line(self, obj: dict) -> None:
        path = os.environ.get(FILE_ENV)
        if path:
            from bluefog_tpu.logging_util import append_jsonl

            append_jsonl(FILE_ENV, path, obj)

    # -- artifact -------------------------------------------------------------

    def report(self) -> dict:
        """The memory artifact ``tools/memory_report.py`` consumes."""
        return {
            "kind": "memory_dump",
            "interval": self.interval,
            "budget_bytes": self.budget or None,
            "drift_tol": self.drift_tol,
            "comm_steps": self._count,
            "samples": list(self.samples),
            "advisories": [a.to_json() for a in self.advisories],
            "phase_peaks": {
                k: dict(v) for k, v in sorted(self.phase_peaks.items())
            },
            "peak_bytes_per_rank": int(self._peak_bytes),
            "last_census_ranked": ranked_census(self.last_census),
            "oom_events": self.oom_events,
        }

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.report(), f)
        return path


# -- module-level session -----------------------------------------------------

_observatory: Optional[MemoryObservatory] = None


def start(interval: Optional[int] = None, **kwargs) -> MemoryObservatory:
    """Open a memory session (replacing any active one)."""
    global _observatory
    _observatory = MemoryObservatory(interval=interval, **kwargs)
    return _observatory


def stop() -> None:
    global _observatory
    _observatory = None


def active() -> Optional[MemoryObservatory]:
    return _observatory


def observe_step(ctx, *, step: int, optimizer=None, params=None,
                 opt_state=None, grads=None) -> None:
    """Optimizer-layer hook, called after every communicating dispatch
    (next to the doctor / health / staleness hooks). No-op (one
    attribute read) when no session is active."""
    obs = _observatory
    if obs is None:
        return
    obs.observe(ctx, step=step, optimizer=optimizer, params=params,
                opt_state=opt_state, grads=grads)


def on_oom(reason: str, message: str = "") -> List[dict]:
    """Run the OOM forensics path (ranked census + flight dump) —
    callable with or without an active session: a crash hook firing
    before ``BLUEFOG_MEMORY=1`` was ever read must still produce the
    dump with whatever census it can take."""
    obs = _observatory
    if obs is None:
        obs = MemoryObservatory()
    return obs.note_oom(reason, message)


def dump(path: str) -> Optional[str]:
    """Write the active session's memory artifact (None when no
    session is active)."""
    obs = _observatory
    if obs is None:
        return None
    return obs.dump(path)


# -- crash hooks --------------------------------------------------------------

_hook_installed = False
_prev_excepthook = None


def _is_oom(exc_type, exc) -> bool:
    """A real host ``MemoryError`` or an XLA allocation failure (the
    runtime raises ``XlaRuntimeError`` with ``RESOURCE_EXHAUSTED`` in
    the message — matching the message instead of importing the exact
    exception class keeps the hook alive across jaxlib renames)."""
    if isinstance(exc, MemoryError) or (
        exc_type is not None and issubclass(exc_type, MemoryError)
    ):
        return True
    return "RESOURCE_EXHAUSTED" in str(exc)


def _excepthook(exc_type, exc, tb):
    try:
        # an exception whose forensics already ran (the oom chaos
        # fault marks its raise) must not be counted twice
        if _is_oom(exc_type, exc) and not getattr(
            exc, "_bf_oom_forensics_done", False
        ):
            on_oom(f"exception:{exc_type.__name__}", str(exc))
    except Exception:
        pass
    hook = _prev_excepthook or sys.__excepthook__
    hook(exc_type, exc, tb)


def _install_oom_hooks() -> None:
    global _hook_installed, _prev_excepthook
    if _hook_installed:
        return
    _prev_excepthook = sys.excepthook
    sys.excepthook = _excepthook
    _hook_installed = True


def _uninstall_oom_hooks() -> None:
    global _hook_installed, _prev_excepthook
    if not _hook_installed:
        return
    if sys.excepthook is _excepthook:
        sys.excepthook = _prev_excepthook or sys.__excepthook__
    _hook_installed = False
    _prev_excepthook = None


# -- session lifecycle (called by bluefog_tpu.context) ------------------------


def on_init(ctx) -> None:
    """``bf.init()`` hook: fresh session under ``BLUEFOG_MEMORY=1`` (a
    new mesh must not inherit a torn-down mesh's census or watermark),
    and the OOM crash hooks — which install beside the flight
    recorder's (AFTER it, so this hook runs FIRST on an uncaught
    error: the ranked census lands in the advisory side table before
    the flight hook writes its own crash dump)."""
    if enabled():
        start()
    else:
        stop()
    from bluefog_tpu import flight as flight_mod

    if flight_mod.enabled() and flight_mod.dump_dir() is not None:
        _install_oom_hooks()


def on_shutdown() -> None:
    """``bf.shutdown()`` hook: flush the JSONL tail, drop the session,
    detach the crash hooks."""
    obs = _observatory
    if obs is not None and obs.samples:
        obs._export_line({"kind": "session_end",
                          "comm_steps": obs._count})
    _uninstall_oom_hooks()
    stop()
