# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Staleness & provenance observatory (``bf.staleness``): parameter-age
tracing across gossip, windows, and delayed combines — the sixth
observability tier.

The five existing tiers measure wall-clock health (metrics, flight,
doctor), spectral mixing (health), and fleet state — but none of them
measures parameter *age*: how stale is the data that actually enters
each rank's combine, per edge, per step. That number is the missing
input for two telemetry-driven directions: a fully asynchronous
push-sum mode needs a bounded-staleness gate (which cannot exist
without delivered-age measurement), and closed-loop topology tuning
needs age-weighted mixing as an objective — the PR-9 spectral
prediction assumes zero staleness and silently overstates mixing under
``delayed=True`` and window-op exchanges.

**The provenance lane.** Every sampled outbound payload is stamped with
an int32 lineage tag ``(birth_step, topo_version, membership_epoch)``
that rides the same ppermute fabric as the data — one
:data:`LINEAGE_TAG_BYTES` sidecar per edge per round, priced into
:func:`bluefog_tpu.scaling.wire_payload_bytes` exactly like the
quant-scale sidecars. On receipt the per-edge *delivered age*
(``receiver_comm_step - delivered_birth_step``) is folded host-side.
Sampling is the PR-3 discipline: 1-in-``BLUEFOG_STALENESS_INTERVAL``
communicating steps dispatch the lane as a SEPARATE tiny program
(cached under its own ``staleness_lane`` op-cache family); unsampled
steps dispatch the bitwise-identical observatory-off training program
under the same cache key (tests/test_staleness.py,
tests/test_step_core.py).

**Three exchange surfaces:**

- the synchronous gossip combine — age ≡ 0, asserted per sample (the
  cheap self-check that the lane itself is correct: a nonzero age on a
  synchronous edge is lane corruption, counted in
  ``bluefog.staleness.selfcheck_failures``, never a training error);
- the ``delayed=True`` one-step-stale combine — age ≡ 1 in steady
  state, with the transitions observable: a topology swap or elastic
  repair reseeds the delay buffer from fresh params, so the next
  sample reads age 0 before settling back to 1;
- window ops — the windows subsystem tracks a host-side age lane per
  buffer slot (local steps since the slot was last written, plus the
  age of the oldest uncollected push-sum mass), surfaced through
  :func:`bluefog_tpu.windows.get_win_age` and folded here by
  :func:`observe_window`;
- the asynchronous gossip engine (:mod:`bluefog_tpu.async_gossip`) —
  the same window age lane folded under ``surface="async"``: the
  bounded-staleness gate reads exactly the ages the observatory
  reports.

**Chaos parity.** An injected ``stall`` fault with ``steps=``/``peer=``
(:mod:`bluefog_tpu.elastic.faults`) deterministically holds the
stamped birth step of the affected sender/edge
(:meth:`~bluefog_tpu.elastic.recovery.ElasticSession.
simulated_stale_steps`), so a per-edge stall produces the correct
measured age spike — and a ``staleness_breach`` advisory naming the
edge — as a reproducible unit test, the same pattern the attribution
doctor uses for ``degraded_link`` localization.

**Downstream.** Per-edge age histograms land in the metrics registry
(``bluefog.staleness.*``, log-bucket tail quantiles); the fleet health
plane aggregates each rank's max delivered age fleet-wide over its
push-sum lane and publishes an **age-discounted effective-mixing
estimate** (:func:`age_adjusted_rate`: the stale-mixing companion
polynomial ``t^(A+1) - s t^A - (λ - s)`` generalizes the PR-2 delayed
stability analysis to measured age ``A``, shrinking the
predicted-vs-measured residual on delayed runs); ``staleness_breach``
rides the PR-7 advisory plumbing (``bluefog.doctor.*`` counter, flight
side table, timeline instant, ``BLUEFOG_STALENESS_FILE`` JSONL); and
``tools/staleness_report.py`` triages the committed artifact.

Env knobs: ``BLUEFOG_STALENESS=1`` (default off),
``BLUEFOG_STALENESS_INTERVAL`` (sampling period in communicating
steps, default 20), ``BLUEFOG_STALENESS_BOUND`` (delivered-age breach
bound, default 4), ``BLUEFOG_STALENESS_FILE`` (JSONL samples +
advisories). See docs/staleness.md.
"""

import collections
import json
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "StalenessObservatory",
    "LINEAGE_FIELDS",
    "LINEAGE_TAG_BYTES",
    "enabled",
    "staleness_interval",
    "staleness_bound",
    "age_adjusted_rate",
    "start",
    "stop",
    "active",
    "observe_step",
    "observe_window",
    "dump",
    "on_init",
    "on_shutdown",
]

ENABLE_ENV = "BLUEFOG_STALENESS"
INTERVAL_ENV = "BLUEFOG_STALENESS_INTERVAL"
BOUND_ENV = "BLUEFOG_STALENESS_BOUND"
FILE_ENV = "BLUEFOG_STALENESS_FILE"

# The lineage tag: one int32 per field, shipped per edge per round on
# sampled steps. 12 bytes — priced by scaling.wire_payload_bytes
# (lineage=True) so the chooser/evidence/accounting can never disagree
# about what the observatory puts on the wire.
LINEAGE_FIELDS = ("birth_step", "topo_version", "epoch")
LINEAGE_TAG_BYTES = 4 * len(LINEAGE_FIELDS)

# staleness_breach re-fire mute per (surface, edge), in that surface's
# samples: a persistently stale edge keeps its counter and /healthz
# raised without filling the flight ring (the mixing_degraded
# rate-limit discipline), while a different edge's first breach is
# never swallowed by someone else's cooldown.
BREACH_COOLDOWN = 8
# Per-edge histogram families are bounded: past this many distinct
# edges the per-edge series stop being created (the aggregate
# histogram still sees every sample) — a 1024-rank fleet must not grow
# the registry without bound.
MAX_EDGE_SERIES = 128


def enabled() -> bool:
    """Observatory switch: ``BLUEFOG_STALENESS=1`` (default off) —
    opt-in like the metrics device tier, the doctor, and the health
    plane."""
    return os.environ.get(ENABLE_ENV, "0").lower() in (
        "1", "true", "on", "yes",
    )


def staleness_interval() -> int:
    """Sampling period in communicating steps
    (``BLUEFOG_STALENESS_INTERVAL``, default 20). A sample is one tiny
    int32 lane dispatch plus O(edges) host folding, paid one step in
    twenty at the default."""
    from bluefog_tpu.logging_util import env_int

    return max(1, env_int(INTERVAL_ENV, 20))


def staleness_bound() -> int:
    """Delivered-age bound (``BLUEFOG_STALENESS_BOUND``, default 4)
    above which a ``staleness_breach`` advisory fires. The synchronous
    combine delivers age 0 and ``delayed=True`` age 1, so the default
    flags only genuinely anomalous delivery — and doubles as the gate
    a bounded-staleness asynchronous mode would enforce."""
    from bluefog_tpu.logging_util import env_int

    return max(1, env_int(BOUND_ENV, 4))


def age_adjusted_rate(rate: Optional[float], age: Optional[float],
                      self_weight: float = 0.5) -> Optional[float]:
    """Predicted per-step consensus decay corrected for measured
    delivered age: the largest root magnitude of the stale-mixing
    companion polynomial ``t^(A+1) - s t^A - (rate - s)`` with
    ``A = round(age)``.

    This generalizes the PR-2 delayed-combine stability analysis
    (optimizers._self_weight_fn: each eigenmode of the age-A recursion
    ``x_{k+1} = s x_k + (λ - s) x_{k-A}`` obeys exactly this
    polynomial; Gershgorin keeps every root inside the unit disk for
    row-stochastic nonnegative weights). ``A = 0`` returns ``rate``
    unchanged; with the true measured age the corrected prediction is
    what a delayed or window-op run can actually deliver — the health
    plane uses it to shrink the predicted-vs-measured mixing residual
    instead of flagging honest staleness as degradation."""
    if rate is None or not 0.0 < rate < 1.0:
        return rate
    if age is None or age <= 0:
        return rate
    a = int(round(float(age)))
    if a <= 0:
        return rate
    s = min(max(float(self_weight), 0.0), 1.0 - 1e-9)
    coeffs = np.zeros(a + 2)
    coeffs[0] = 1.0
    coeffs[1] = -s
    coeffs[-1] = -(rate - s)
    roots = np.roots(coeffs)
    adj = float(np.max(np.abs(roots))) if roots.size else rate
    # numerical guard: the corrected rate is a *weaker* promise than
    # the zero-staleness one, never a stronger one, and stays < 1
    return float(min(max(adj, rate), 1.0 - 1e-12))


# -- the lineage lane ---------------------------------------------------------


def _lane_program(ctx, perms):
    """Compiled lineage exchange: each round's int32 tag shipped along
    that round's ppermute (:func:`bluefog_tpu.collective.inner.
    lineage_exchange`). Cached in the context op cache under its own
    ``staleness_lane`` family — training cache keys are untouched,
    which is what keeps the observatory's bitwise no-op trivially
    true."""
    key = ("staleness_lane", perms)
    fn = ctx.op_cache.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from bluefog_tpu import context as ctx_mod
        from bluefog_tpu.collective import inner

        axis = ctx_mod.WORKER_AXIS

        def body(tags):
            return jnp.expand_dims(
                inner.lineage_exchange(tags[0], perms, axis), 0
            )

        fn = jax.jit(
            jax.shard_map(
                body, mesh=ctx.mesh,
                in_specs=P(ctx_mod.WORKER_AXIS),
                out_specs=P(ctx_mod.WORKER_AXIS),
            )
        )
        ctx.op_cache[key] = fn
    return fn


def _chaos_holds() -> Dict:
    """Active simulated staleness holds from the chaos layer:
    ``{(src, dst) | rank: extra_steps}`` (empty without an elastic
    session). The lane *stamps* held birth steps and *measures* from
    the delivered tags alone — detection from the wire, the doctor's
    degraded-link pattern applied to age."""
    try:
        from bluefog_tpu import elastic as elastic_mod

        session = elastic_mod.active_session()
    except Exception:
        session = None
    if session is None:
        return {}
    fn = getattr(session, "simulated_stale_steps", None)
    return fn() if fn is not None else {}


def _suspect_faults() -> List[Any]:
    """Corroborating suspects for a breach: the shared fabric-health
    join (:func:`bluefog_tpu.attribution.suspect_join` — the health
    plane's ``mixing_degraded`` join), extended with the chaos layer's
    active stall payload holds."""
    from bluefog_tpu.attribution import suspect_join

    return suspect_join(include_stall_holds=True)


# -- the observatory session --------------------------------------------------


class StalenessObservatory:
    """One staleness session. Built by :func:`start` (or implicitly by
    ``bf.init()`` under ``BLUEFOG_STALENESS=1``); fed by the optimizer
    layer through :func:`observe_step` after every communicating
    dispatch and by the window layer through :func:`observe_window`."""

    def __init__(self, interval: Optional[int] = None,
                 bound: Optional[int] = None, history: int = 512):
        self.interval = (
            int(interval) if interval else staleness_interval()
        )
        self.bound = int(bound) if bound else staleness_bound()
        self._count = 0       # communicating steps observed (gossip)
        # per-WINDOW observation clocks: one shared counter would alias
        # the modulo across windows (two windows updated alternately at
        # interval 2 would sample only one of them, forever)
        self._wcounts: Dict[str, int] = {}
        self.samples: collections.deque = collections.deque(
            maxlen=history
        )
        self.advisories: List[Any] = []
        self.advisory_marks: List[int] = []
        # per-(surface, edge) re-fire mutes: a persistently stale edge
        # fires once per BREACH_COOLDOWN of ITS surface's samples, but
        # a DIFFERENT edge's (or surface's) first breach is never
        # suppressed by someone else's cooldown
        self._breach_mutes: Dict[Tuple[str, Tuple[int, int]], int] = {}
        # per-edge age table of the CURRENT (topo_version, live_token):
        # a repair or topology swap renames the edges — carrying the
        # old graph's ages would misattribute them to the new one
        self._age_key: Optional[tuple] = None
        self.edge_ages: Dict[Tuple[int, int], Dict[str, float]] = {}
        self._edge_series: set = set()
        self._last_gossip_mean: Optional[float] = None
        self._last_gossip_max: Optional[float] = None
        self._last_window_max: Optional[float] = None

    # -- fleet-facing state ---------------------------------------------------

    def last_age_mean(self) -> Optional[float]:
        """Mean delivered age of the most recent gossip sample (None
        before the first) — the health plane's age-correction input."""
        return self._last_gossip_mean

    def last_age_max(self) -> float:
        """Worst delivered age on record across surfaces (0.0 before
        the first sample) — the scalar the fleet lane aggregates."""
        vals = [
            v for v in (self._last_gossip_max, self._last_window_max)
            if v is not None
        ]
        return float(max(vals)) if vals else 0.0

    # -- breach gating --------------------------------------------------------

    def _unmuted_breaches(self, surface_kind: str,
                          ages: Dict[Tuple[int, int], int]
                          ) -> List[Tuple[int, int]]:
        """Edges past the bound that are not re-fire-muted, worst
        first; the returned edges are muted for :data:`BREACH_COOLDOWN`
        of THIS surface's samples. Mutes are per (surface, edge): a
        persistently stale edge fires once per cooldown window, while
        a different edge's (or the other surface's) first breach is
        never swallowed by someone else's cooldown."""
        for k in list(self._breach_mutes):
            if k[0] == surface_kind:
                self._breach_mutes[k] -= 1
                if self._breach_mutes[k] <= 0:
                    del self._breach_mutes[k]
        breached = sorted(
            (e for e, a in ages.items() if a > self.bound),
            key=lambda e: (-ages[e], e),
        )
        out = [
            e for e in breached
            if (surface_kind, e) not in self._breach_mutes
        ]
        for e in out:
            self._breach_mutes[(surface_kind, e)] = BREACH_COOLDOWN
        return out

    # -- observation ----------------------------------------------------------

    def observe(self, ctx, *, step: int, plan=None, payload_age: int = 0,
                surface: str = "sync") -> Optional[dict]:
        """Called once per communicating step. Unsampled steps cost one
        compare + one increment; the sampled step dispatches the
        lineage lane over the active plan's rounds and folds the
        delivered ages."""
        if plan is None or not getattr(plan, "perms", None):
            # allreduce / empty / machine-mesh communication has no
            # worker-axis edge set to stamp — and must not consume a
            # sample slot either: with two optimizers interleaved in
            # one process, a perms-less surface landing on every
            # sampled slot would starve the gossip surface forever
            return None
        sampled = self._count % self.interval == 0
        self._count += 1
        if not sampled:
            return None
        return self._sample(
            ctx, step=step, plan=plan, payload_age=int(payload_age),
            surface=surface,
        )

    def _reset_if_remapped(self, ctx) -> None:
        key = (ctx.topo_version, ctx.live_token())
        if self._age_key != key:
            # elastic repair / topology swap: fresh edge table under
            # the new live_token — age state never crosses the seam
            self._age_key = key
            self.edge_ages = {}
            self._breach_mutes = {}

    def _sample(self, ctx, *, step, plan, payload_age, surface) -> dict:
        import jax
        import jax.numpy as jnp

        from bluefog_tpu import flight as flight_mod
        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu import scaling

        self._reset_if_remapped(ctx)
        t_now = self._count  # this sample's comm-step clock value
        perms = tuple(plan.perms)
        n_rounds = len(perms)
        holds = _chaos_holds()
        tok = ctx.live_token()
        epoch = int(tok[0]) if tok else 0

        # stamp: [size, rounds, 3] int32 — birth is held back by the
        # payload's real age (the delayed double buffer) plus any
        # chaos-simulated hold on the sending edge
        size = ctx.size
        tags = np.zeros((size, max(n_rounds, 1), 3), np.int32)
        tags[:, :, 0] = t_now - payload_age
        tags[:, :, 1] = ctx.topo_version
        tags[:, :, 2] = epoch
        if holds:
            for r, perm in enumerate(perms):
                for s, d in perm:
                    h = holds.get((s, d), holds.get(s, 0))
                    if h:
                        tags[s, r, 0] = t_now - payload_age - int(h)

        fn = _lane_program(ctx, perms)
        out = np.asarray(jax.device_get(fn(jnp.asarray(tags))))

        # fold: delivered age + provenance check per directed edge
        ages: Dict[Tuple[int, int], int] = {}
        mismatches = 0
        for r, perm in enumerate(perms):
            for s, d in perm:
                got = out[d, r]
                ages[(s, d)] = t_now - int(got[0])
                if int(got[1]) != ctx.topo_version or int(got[2]) != epoch:
                    mismatches += 1
        expected = {
            (s, d): payload_age + int(
                holds.get((s, d), holds.get(s, 0)) if holds else 0
            )
            for r, perm in enumerate(perms) for s, d in perm
        }
        lane_ok = all(ages[e] == expected[e] for e in ages) and not mismatches
        if not lane_ok:
            metrics_mod.counter(
                "bluefog.staleness.selfcheck_failures"
            ).inc()

        age_vals = list(ages.values())
        age_mean = float(np.mean(age_vals)) if age_vals else 0.0
        age_max = float(max(age_vals)) if age_vals else 0.0
        max_edge = (
            max(ages, key=lambda e: (ages[e], e)) if ages else None
        )
        self._last_gossip_mean = age_mean
        self._last_gossip_max = age_max

        # registry: aggregate + bounded per-edge log-bucket histograms
        hist = metrics_mod.histogram("bluefog.staleness.age")
        for (s, d), a in sorted(ages.items()):
            hist.observe(a)
            name = f"bluefog.staleness.edge_age.{s}_{d}"
            if name in self._edge_series \
                    or len(self._edge_series) < MAX_EDGE_SERIES:
                self._edge_series.add(name)
                metrics_mod.histogram(name).observe(a)
            rec = self.edge_ages.setdefault(
                (s, d), {"last": 0.0, "max": 0.0, "n": 0}
            )
            rec["last"] = float(a)
            rec["max"] = max(rec["max"], float(a))
            rec["n"] += 1
        metrics_mod.gauge("bluefog.staleness.age_mean").set(age_mean)
        metrics_mod.gauge("bluefog.staleness.age_max").set(age_max)
        metrics_mod.counter("bluefog.staleness.samples").inc()
        # the sidecar is ON the wire this sample: price it with the
        # canonical accounting (one tag per edge per round)
        sidecar = scaling.wire_payload_bytes(0, 0, lineage=True)
        metrics_mod.counter("bluefog.staleness.wire_bytes").inc(
            sidecar * n_rounds
        )

        sample: Dict[str, Any] = {
            "kind": "sample",
            "surface": surface,
            "step": int(step),
            "comm_steps": t_now,
            "topo_version": int(ctx.topo_version),
            "live_epoch": epoch,
            "payload_age": payload_age,
            "rounds": n_rounds,
            "edges": len(ages),
            "age_mean": round(age_mean, 4),
            "age_max": age_max,
            "lane_ok": lane_ok,
            "lineage_bytes_per_round": sidecar,
        }
        if max_edge is not None:
            sample["max_edge"] = [int(max_edge[0]), int(max_edge[1])]
        if holds:
            sample["chaos_holds"] = {
                str(k): int(v) for k, v in sorted(holds.items(), key=str)
            }
        if mismatches:
            sample["provenance_mismatches"] = mismatches

        # breach gate: edges past the bound, per-edge re-fire muted
        breached = self._unmuted_breaches("gossip", ages)
        if breached:
            from bluefog_tpu.attribution import Advisory

            adv = Advisory(
                kind="staleness_breach", step=int(step),
                detail={
                    "edges": [
                        [int(s), int(d)] for s, d in breached[:8]
                    ],
                    "ages": {
                        f"{s}->{d}": int(ages[(s, d)])
                        for s, d in breached[:8]
                    },
                    "age_max": age_max,
                    "bound": self.bound,
                    "surface": surface,
                    "payload_age": payload_age,
                    "topo_version": int(ctx.topo_version),
                    "suspect_faults": _suspect_faults(),
                },
            )
            sample["advisories"] = [adv.to_json()]
            self._emit(adv)

        flight_mod.record(
            "staleness", surface=surface, age_max=age_max,
            age_mean=round(age_mean, 4), edges=len(ages),
            lane_ok=lane_ok,
        )
        self.samples.append(sample)
        self._export_line(sample)
        return sample

    def observe_window(self, ctx, win, step: Optional[int] = None,
                       surface: str = "window") -> Optional[dict]:
        """Fold one window's host-tracked buffer/mass ages (the
        :mod:`bluefog_tpu.windows` age lane) on the window's own
        sampling clock (per-window — a shared counter would alias the
        modulo across windows and starve some of them forever). Called
        by ``win_update``, the fused window-optimizer step, and the
        asynchronous gossip engine (``surface="async"``,
        :mod:`bluefog_tpu.async_gossip`) — the async lane's delivered
        ages land in the same registry/fleet plumbing; a breach here
        names the stale *source* edge exactly like the gossip
        surface."""
        wname = getattr(win, "name", "?")
        count = self._wcounts.get(wname, 0)
        self._wcounts[wname] = count + 1
        if count % self.interval != 0:
            return None
        from bluefog_tpu import metrics as metrics_mod

        self._reset_if_remapped(ctx)
        clock = int(getattr(win, "clock", 0))
        slot_written = getattr(win, "slot_written", None)
        if slot_written is None:
            return None
        ages: Dict[Tuple[int, int], int] = {}
        mass_ages: Dict[Tuple[int, int], int] = {}
        mass_birth = getattr(win, "mass_birth", None)
        for r, srcs in enumerate(win.in_neighbors):
            for k, s in enumerate(srcs):
                ages[(int(s), int(r))] = clock - int(slot_written[r, k])
                if mass_birth is not None and mass_birth[r, k] >= 0:
                    mass_ages[(int(s), int(r))] = (
                        clock - int(mass_birth[r, k])
                    )
        if not ages:
            return None
        vals = list(ages.values())
        age_mean = float(np.mean(vals))
        age_max = float(max(vals))
        self._last_window_max = age_max
        hist = metrics_mod.histogram("bluefog.staleness.window_age")
        for a in vals:
            hist.observe(a)
        metrics_mod.gauge("bluefog.staleness.window_age_max").set(
            age_max
        )
        if mass_ages:
            metrics_mod.gauge(
                "bluefog.staleness.window_mass_age_max"
            ).set(float(max(mass_ages.values())))
        sample: Dict[str, Any] = {
            "kind": "sample",
            "surface": surface,
            "window": win.name,
            "step": int(step) if step is not None else clock,
            "window_clock": clock,
            "edges": len(ages),
            "age_mean": round(age_mean, 4),
            "age_max": age_max,
        }
        if mass_ages:
            sample["mass_age_max"] = float(max(mass_ages.values()))
        breached = self._unmuted_breaches(surface, ages)
        if breached:
            from bluefog_tpu.attribution import Advisory

            adv = Advisory(
                kind="staleness_breach",
                step=int(step) if step is not None else clock,
                detail={
                    "edges": [
                        [int(s), int(d)] for s, d in breached[:8]
                    ],
                    "ages": {
                        f"{s}->{d}": int(ages[(s, d)])
                        for s, d in breached[:8]
                    },
                    "age_max": age_max,
                    "bound": self.bound,
                    "surface": surface,
                    "window": win.name,
                    "suspect_faults": _suspect_faults(),
                },
            )
            sample["advisories"] = [adv.to_json()]
            self._emit(adv)
        self.samples.append(sample)
        self._export_line(sample)
        return sample

    # -- emission -------------------------------------------------------------

    def _emit(self, adv) -> None:
        """One advisory, the PR-7 surfaces: ``bluefog.doctor.*``
        metrics, flight side table, timeline instant, staleness
        JSONL."""
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

    # -- artifact -------------------------------------------------------------

    def report(self) -> dict:
        """The staleness artifact ``tools/staleness_report.py``
        consumes."""
        return {
            "kind": "staleness_dump",
            "interval": self.interval,
            "bound": self.bound,
            "comm_steps": self._count,
            "window_observations": sum(self._wcounts.values()),
            "samples": list(self.samples),
            "advisories": [a.to_json() for a in self.advisories],
            "edge_ages": {
                f"{s}->{d}": dict(rec)
                for (s, d), rec in sorted(self.edge_ages.items())
            },
            "age_mean": self._last_gossip_mean,
            "age_max": self.last_age_max(),
        }

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.report(), f)
        return path


# -- module-level session -----------------------------------------------------

_observatory: Optional[StalenessObservatory] = None


def start(interval: Optional[int] = None, **kwargs
          ) -> StalenessObservatory:
    """Open a staleness session (replacing any active one)."""
    global _observatory
    _observatory = StalenessObservatory(interval=interval, **kwargs)
    return _observatory


def stop() -> None:
    global _observatory
    _observatory = None


def active() -> Optional[StalenessObservatory]:
    return _observatory


def observe_step(ctx, *, step: int, plan=None, payload_age: int = 0,
                 surface: str = "sync") -> None:
    """Optimizer-layer hook, called after every communicating dispatch
    (next to the doctor and health hooks). No-op (one attribute read)
    when no session is active."""
    obs = _observatory
    if obs is None:
        return
    obs.observe(ctx, step=step, plan=plan, payload_age=payload_age,
                surface=surface)


def observe_window(ctx, win, step: Optional[int] = None,
                   surface: str = "window") -> None:
    """Window-layer hook (``win_update`` / the fused window-optimizer
    step / the async gossip engine with ``surface="async"``). No-op
    when no session is active."""
    obs = _observatory
    if obs is None:
        return
    obs.observe_window(ctx, win, step=step, surface=surface)


def dump(path: str) -> Optional[str]:
    """Write the active session's staleness artifact (None when no
    session is active)."""
    obs = _observatory
    if obs is None:
        return None
    return obs.dump(path)


def on_init(ctx) -> None:
    """``bf.init()`` hook: fresh session under ``BLUEFOG_STALENESS=1``
    (a new mesh must not inherit a torn-down mesh's edge table)."""
    if enabled():
        start()
    else:
        stop()


def on_shutdown() -> None:
    """``bf.shutdown()`` hook: flush the JSONL tail, drop the
    session."""
    obs = _observatory
    if obs is not None and obs.samples:
        obs._export_line({"kind": "session_end",
                          "comm_steps": obs._count})
    stop()
