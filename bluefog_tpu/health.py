# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Fleet health plane (``bf.health``): online mixing-rate observatory,
in-band push-sum fleet aggregation, and live ``/healthz`` serving.

The repo measures *wall-clock* health (:mod:`bluefog_tpu.metrics` counts
what moved, :mod:`bluefog_tpu.attribution` attributes where time went)
— but never checks the *algorithmic* contract the paper rests on: that
neighbor averaging over the active graph contracts the consensus error
at the rate the graph's spectral gap promises. This module closes that
gap and gives every rank a live fleet-wide view without a central
collector. Three parts:

**(a) Mixing observatory.** Host-side spectral analysis of the active
combine matrix, cached per ``(topo_version, live_token)``: SLEM of a
static :class:`~bluefog_tpu.collective.plan.CommPlan`'s weight matrix,
the period-product rate of a dynamic
:class:`~bluefog_tpu.collective.plan.SchedulePlan`, the post-repair
matrix after an elastic membership change (the repaired plan simply
arrives under a new topo_version) — all through
:func:`bluefog_tpu.topology.consensus_decay_rate`. The *predicted*
per-round decay is compared online against the *measured* decay fitted
over the sampled consensus-distance series (the PR-3 sub-gossip
``bluefog.gossip.disagreement`` gauge, or a directly fed series for the
eager path), yielding a **mixing-efficiency ratio**
(``ln(measured)/ln(predicted)``: 1.0 = the fabric delivers what the
spectrum promises, < 1 = it lags), a **time-to-consensus-ε projection**,
and a ``mixing_degraded`` advisory — routed through the PR-7 advisory
plumbing (``bluefog.doctor.*`` metrics, flight side table, timeline
instants) — when measured decay falls beyond the EWMA+MAD baseline of
its own efficiency history. Localization joins the detection with the
chaos layer's active degrade faults and the attribution doctor's
``degraded_link`` edges: the observatory proves the contract is broken,
the wire probes name the link.

**(b) In-band aggregation.** Each rank's scalar health summary
(step-time EWMA, consensus distance, wire bytes/step, advisory count,
live-set digest) is aggregated fleet-wide min/mean/max over the gossip
fabric itself: a tiny push-sum side lane (:func:`fleet_aggregate`) —
sum and weight lanes under a sender-mass-conserving row-normalized
push matrix derived from the active combine, min/max lanes via masked
neighbor-min gossip — compiled over the SAME ppermute fabric the
training gossip uses (no coordinator; a dead rank's mass simply drops
out of the repaired plan, so the estimate converges to the live-set
aggregate). The lane is a *separate* tiny dispatch on sampled steps
only: the training program is untouched, so unsampled steps dispatch
the bitwise-identical health-off program under the same cache key —
the sampling discipline (tests/test_step_core.py).

**(c) Serving surface.** ``BLUEFOG_HEALTH_PORT`` starts a per-rank
stdlib HTTP endpoint: ``/healthz`` (RAG verdict from advisory recency +
elastic liveness; 200 on ok/warn, 503 on critical — load-balancer
ready), ``/metrics`` (live Prometheus scrape via
:func:`bluefog_tpu.metrics.prom_lines`, complementing the textfile
exporter), ``/fleet`` (the in-band aggregate as JSON). A port conflict
logs a warning and serves nothing — never kills training.
``tools/fleet_report.py`` renders one fleet table from N ranks'
artifacts or live endpoints.

Env knobs: ``BLUEFOG_HEALTH=1`` enables the observatory (default off),
``BLUEFOG_HEALTH_INTERVAL`` (sampling period in communicating steps,
default 20), ``BLUEFOG_HEALTH_PORT`` (serve; 0/unset = off),
``BLUEFOG_HEALTH_ROUNDS`` (push-sum applications per sample; 0
disables the lane, unset = auto from the predicted rate),
``BLUEFOG_HEALTH_EPS`` (consensus target for the time-to-ε projection,
default 1e-6), ``BLUEFOG_HEALTH_FILE`` (JSONL samples + advisories).
See docs/health.md.
"""

import collections
import json
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "HealthPlane",
    "HealthServer",
    "enabled",
    "health_interval",
    "health_port",
    "health_eps",
    "fit_decay_rate",
    "mixing_efficiency",
    "time_to_consensus_steps",
    "push_matrix",
    "fleet_aggregate_np",
    "fleet_aggregate",
    "healthz_verdict",
    "FLEET_FIELDS",
    "start",
    "stop",
    "active",
    "observe_step",
    "serve",
    "server",
    "dump",
    "on_init",
    "on_shutdown",
]

ENABLE_ENV = "BLUEFOG_HEALTH"
INTERVAL_ENV = "BLUEFOG_HEALTH_INTERVAL"
PORT_ENV = "BLUEFOG_HEALTH_PORT"
ROUNDS_ENV = "BLUEFOG_HEALTH_ROUNDS"
EPS_ENV = "BLUEFOG_HEALTH_EPS"
FILE_ENV = "BLUEFOG_HEALTH_FILE"

# mixing_degraded gate: efficiency this fraction below its EWMA
# baseline AND a -3 MAD z-score, for MIXING_STREAK consecutive samples
# (one bad fit on a noisy series is jitter, not degradation — the
# ambient_drift discipline applied to the algorithmic contract).
# Calibration note: fully dropping ONE directed edge of an 8-ring only
# costs ~24 % of the promised contraction (SLEM 0.805 -> 0.844), so a
# deeper gate would be blind to exactly the single-flaky-link failure
# this advisory exists for; the z-score + streak carry the
# false-positive burden.
MIXING_DEGRADED_FRAC = 0.10
MIXING_STREAK = 2
# decay-rate fit: least-squares over the last FIT_WINDOW sampled
# (step, distance) points of the CURRENT topology version; fewer than
# MIN_FIT_POINTS points (or distances at the fp noise floor) = no fit.
FIT_WINDOW = 8
MIN_FIT_POINTS = 4
DIST_FLOOR = 1e-12
# advisory recency window for the /healthz verdict, in samples — MUST
# exceed the mixing_degraded re-fire cooldown (FIT_WINDOW samples), or
# a persistently degraded fabric would flap ok/warn between re-fires
VERDICT_RECENT_SAMPLES = 20

# The per-rank health summary vector the push-sum lane aggregates.
FLEET_FIELDS = (
    "step_ms",             # step-time EWMA at this rank
    "consensus",           # per-worker consensus distance (PR-3 drain)
    "wire_bytes_per_step", # wire bytes per communicating step
    "advisories",          # advisories on record (health + doctor)
    "live_digest",         # digest of the believed live set
    "stale_age_max",       # worst delivered parameter age on record
    #                        (bluefog_tpu.staleness; 0 when the
    #                        observatory is off) — fleet-wide
    #                        min/mean/max age rides the same lane
    "mem_bytes_per_rank",  # measured per-chip memory footprint
    #                        (bluefog_tpu.memory census; 0 when the
    #                        observatory is off)
    "mem_headroom",        # budget minus footprint (0 when no
    #                        BLUEFOG_MEMORY_BUDGET is configured) —
    #                        the fleet min is the chip closest to OOM
    "slo_burn",            # worst fast-window SLO burn rate at this
    #                        rank (bluefog_tpu.slo; 0 when the engine
    #                        is off) — the fleet MAX is the rank
    #                        burning its error budget fastest
)


# Non-finite sanitizer for the HTTP endpoints (the JSONL exporters get
# it through logging_util.append_jsonl): a NaN step EWMA before warmup
# must reach the scraper as null, never as a bare NaN token.
from bluefog_tpu.logging_util import json_safe as _json_safe  # noqa: E402


def enabled() -> bool:
    """Observatory switch: ``BLUEFOG_HEALTH=1`` (default off). Like the
    metrics device tier and the doctor, the health plane is opt-in;
    the serving surface additionally needs ``BLUEFOG_HEALTH_PORT``."""
    return os.environ.get(ENABLE_ENV, "0").lower() in (
        "1", "true", "on", "yes",
    )


def health_interval() -> int:
    """Sampling period in communicating steps
    (``BLUEFOG_HEALTH_INTERVAL``, default 20 — twice the metrics drain
    period, so the consensus gauge has refreshed between health
    samples). A sample is host arithmetic plus one tiny push-sum lane
    dispatch, paid one step in twenty at the default."""
    from bluefog_tpu.logging_util import env_int

    return max(1, env_int(INTERVAL_ENV, 20))


def health_port() -> int:
    """``BLUEFOG_HEALTH_PORT`` (0/unset = no serving)."""
    from bluefog_tpu.logging_util import env_int

    return env_int(PORT_ENV, 0)


def health_eps() -> float:
    """Consensus target for the time-to-ε projection
    (``BLUEFOG_HEALTH_EPS``, default 1e-6)."""
    from bluefog_tpu.logging_util import env_float

    return env_float(EPS_ENV, 1e-6)


# -- measured-decay estimation ------------------------------------------------


def fit_decay_rate(
    points: Sequence[Tuple[float, float]]
) -> Optional[float]:
    """Per-step consensus decay rate fitted over sampled ``(comm_step,
    distance)`` points: ``exp`` of the least-squares slope of ``ln d``
    against the step index. Returns None with fewer than
    :data:`MIN_FIT_POINTS` usable points (distances at or under the fp
    noise floor are dropped — a series that has *reached* consensus
    carries no rate information). A returned rate >= 1 means the series
    is not decaying; callers map that to efficiency 0, not an error."""
    usable = [
        (float(s), math.log(float(d)))
        for s, d in points if d is not None and d > DIST_FLOOR
    ]
    if len(usable) < MIN_FIT_POINTS:
        return None
    xs = np.array([s for s, _ in usable])
    ys = np.array([y for _, y in usable])
    if float(xs.max() - xs.min()) <= 0:
        return None
    slope = float(np.polyfit(xs, ys, 1)[0])
    # guard against overflow on a wildly diverging series
    return float(math.exp(min(slope, 50.0)))


def mixing_efficiency(
    measured: Optional[float], predicted: Optional[float]
) -> Optional[float]:
    """``ln(measured) / ln(predicted)``: the fraction of the spectrally
    promised per-step contraction the fabric actually delivers. 1.0 =
    on contract, < 1 = lagging, 0 = not decaying at all; None when
    either rate is unavailable or the matrix promises nothing
    (predicted SLEM ~ 1: a disconnected or non-mixing graph)."""
    if measured is None or predicted is None:
        return None
    if predicted >= 1.0 - 1e-9 or predicted <= 0.0:
        return None
    if measured >= 1.0:
        return 0.0
    eff = math.log(max(measured, 1e-300)) / math.log(predicted)
    return float(eff)


def time_to_consensus_steps(
    distance: Optional[float], rate: Optional[float],
    eps: Optional[float] = None,
) -> Optional[float]:
    """Projected communicating steps until the consensus distance
    reaches ``eps`` at the given per-step decay rate (None when the
    series is not decaying or the distance is unknown; 0 when already
    there)."""
    eps = health_eps() if eps is None else float(eps)
    if distance is None or rate is None or not 0.0 < rate < 1.0:
        return None
    if distance <= eps:
        return 0.0
    return float(math.log(eps / distance) / math.log(rate))


# -- in-band push-sum aggregation ---------------------------------------------


def push_matrix(
    w: np.ndarray, dead: Sequence[int] = ()
) -> np.ndarray:
    """Sender-mass-conserving push matrix from a combine matrix ``W``:
    dead ranks' rows and columns are zeroed, then every live sender's
    row (self weight + out-edge weights) is normalized to sum 1 —
    column-stochastic in the (sender -> receiver) sense, so
    ``sum_j x'_j == sum_i x_i`` exactly and the push-sum ratio
    estimates the *live-set* mean. A live sender left with no mass
    (isolated by the pruning) keeps everything: ``P[i, i] = 1``."""
    w = np.asarray(w, np.float64).copy()
    dead = set(int(r) for r in dead)
    for r in dead:
        w[r, :] = 0.0
        w[:, r] = 0.0
    p = np.zeros_like(w)
    n = w.shape[0]
    for i in range(n):
        if i in dead:
            continue
        row = w[i]
        s = float(row.sum())
        if s <= 0.0:
            p[i, i] = 1.0
        else:
            p[i] = row / s
    return p


def _fleet_estimates(x, p, mn, mx, live) -> dict:
    """Fold lane outputs into the per-rank report: each live rank's
    mean estimate is ``x/p``; the published aggregate is the average of
    the live estimates with the worst-rank deviation disclosed as
    ``residual`` (push-sum converges geometrically — the residual IS
    the honesty metric for a finite-round lane)."""
    live = list(live)
    est = np.array([x[j] / max(p[j], 1e-12) for j in live])
    mean = est.mean(axis=0)
    denom = np.maximum(np.abs(mean), 1e-12)
    residual = float(
        np.max(np.abs(est - mean[None, :]) / denom[None, :])
    ) if len(live) else 0.0
    mn_f = np.min(np.stack([mn[j] for j in live]), axis=0)
    mx_f = np.max(np.stack([mx[j] for j in live]), axis=0)
    return {
        "mean": [float(v) for v in mean],
        "min": [float(v) for v in mn_f],
        "max": [float(v) for v in mx_f],
        "per_rank_mean": {int(j): [float(v) for v in est[k]]
                          for k, j in enumerate(live)},
        "residual": residual,
        "live": [int(j) for j in live],
    }


def fleet_aggregate_np(
    w: np.ndarray,
    values: np.ndarray,
    rounds: int,
    dead: Sequence[int] = (),
) -> dict:
    """Numpy reference of the device lane, same per-application
    semantics: ``rounds`` synchronous applications of (sum lanes
    ``x <- P^T x``, ``p <- P^T p``; min/max lanes one neighbor-min/max
    over the application-start snapshot). The oracle
    ``tests/test_health.py`` pins :func:`fleet_aggregate` against."""
    values = np.asarray(values, np.float64)
    n, k = values.shape
    dead = set(int(r) for r in dead)
    live = [j for j in range(n) if j not in dead]
    p_mat = push_matrix(w, dead)
    in_nbrs = [
        [i for i in range(n) if i != j and p_mat[i, j] > 0.0]
        for j in range(n)
    ]
    x = values.copy()
    p = np.ones(n)
    mn = values.copy()
    mx = values.copy()
    for r in dead:
        x[r] = 0.0
        p[r] = 0.0
        mn[r] = np.inf
        mx[r] = -np.inf
    for _ in range(rounds):
        x = p_mat.T @ x
        p = p_mat.T @ p
        mn0, mx0 = mn.copy(), mx.copy()
        for j in range(n):
            for i in in_nbrs[j]:
                mn[j] = np.minimum(mn[j], mn0[i])
                mx[j] = np.maximum(mx[j], mx0[i])
    return _fleet_estimates(x, p, mn, mx, live)


def _lane_operands(w: np.ndarray, dead: Sequence[int]):
    """Push plan + operands for the lane program — the ONE wire format
    both the one-shot (oracle-pinned) and streaming paths compile
    against: ``(perms, self_w, recv_w, destination mask)``."""
    from bluefog_tpu.collective import plan as plan_mod

    p_mat = push_matrix(w, dead)
    lane_plan = plan_mod.plan_from_matrix(p_mat)
    self_w, recv_w = lane_plan.weight_operands()
    dmask = (recv_w > 0.0).astype(np.float32)
    return lane_plan.perms, self_w, recv_w, dmask


def _seed_state(values32: np.ndarray, dead: Sequence[int],
                k: int) -> np.ndarray:
    """The lane buffer ``[x (k) | p (1) | min (k) | max (k)]`` seeded
    from per-rank values, dead ranks masked (zero mass, ±inf extrema)
    — shared by both lane paths so the oracle pin covers the streaming
    seed layout too."""
    size = values32.shape[0]
    st = np.zeros((size, 3 * k + 1), np.float32)
    st[:, :k] = values32
    st[:, k] = 1.0
    _reseed_minmax(st, values32, dead, k)
    for r in dead:
        st[r, : k + 1] = 0.0
    return st


def _reseed_minmax(st: np.ndarray, values32: np.ndarray,
                   dead: Sequence[int], k: int) -> None:
    """Reset the min/max lanes to current values (generation start)."""
    st[:, k + 1: 2 * k + 1] = values32
    st[:, 2 * k + 1:] = values32
    for r in dead:
        st[r, k + 1: 2 * k + 1] = np.inf
        st[r, 2 * k + 1:] = -np.inf


def _auto_rounds(size: int, predicted_rate: Optional[float]) -> int:
    """Push-sum applications per sample: enough that the mean estimate
    lands within ~1 % (``rho^R <= 0.01``) and the min/max gossip covers
    any strongly-connected diameter, clamped to a fixed budget."""
    env = os.environ.get(ROUNDS_ENV)
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            pass
    rho = predicted_rate if predicted_rate and 0 < predicted_rate < 1 \
        else 0.5
    need = math.log(0.01) / math.log(rho)
    return int(max(4, min(32, max(need, size))))


def _lane_program(ctx, perms, n_apps: int, k: int):
    """Compiled push-sum lane: ``n_apps`` applications of the plan's
    ppermute rounds on a ``[size, 3k+1]`` buffer (sum lanes x|p via the
    weighted combine with weights as operands, min/max lanes via masked
    neighbor gossip over the application-start snapshot). Cached in the
    context op cache under its own ``health_pushsum`` family — training
    cache keys are untouched, which is what keeps the health plane's
    bitwise no-op trivially true."""
    key = ("health_pushsum", perms, n_apps, k)
    fn = ctx.op_cache.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from bluefog_tpu import context as ctx_mod
        from bluefog_tpu.collective import inner

        axis = ctx_mod.WORKER_AXIS
        n_rounds = len(perms)

        def body(v, self_w, recv_w, dmask):
            x = v[:, : k + 1]           # sum lanes: k fields + mass p
            mn = v[:, k + 1: 2 * k + 1]
            mx = v[:, 2 * k + 1:]
            idx = lax.axis_index(axis)
            for _ in range(n_apps):
                x = inner.weighted_combine_operands(
                    x, perms, self_w[0], recv_w[0], axis
                )
                mn0, mx0 = mn, mx
                for r in range(n_rounds):
                    m = dmask[0][r, idx] > 0
                    rmn = lax.ppermute(mn0, axis, perms[r])
                    rmx = lax.ppermute(mx0, axis, perms[r])
                    mn = jnp.minimum(
                        mn, jnp.where(m, rmn, jnp.inf)
                    )
                    mx = jnp.maximum(
                        mx, jnp.where(m, rmx, -jnp.inf)
                    )
            return jnp.concatenate([x, mn, mx], axis=1)

        fn = jax.jit(
            jax.shard_map(
                body,
                mesh=ctx.mesh,
                in_specs=(P(ctx_mod.WORKER_AXIS), P(), P(), P()),
                out_specs=P(ctx_mod.WORKER_AXIS),
            )
        )
        ctx.op_cache[key] = fn
    return fn


def fleet_aggregate(
    ctx,
    values: np.ndarray,
    rounds: Optional[int] = None,
    w: Optional[np.ndarray] = None,
    dead: Sequence[int] = (),
    predicted_rate: Optional[float] = None,
) -> dict:
    """Aggregate a ``[size, K]`` per-rank summary fleet-wide min / mean
    / max over the gossip fabric itself — the in-band lane.

    ``w`` defaults to the active topology's combine matrix; ``dead``
    defaults to the elastic membership's dead set when a session is
    live. Oracle-pinned against :func:`fleet_aggregate_np`."""
    import jax

    values = np.asarray(values, np.float64)
    size, k = values.shape
    if w is None:
        from bluefog_tpu import topology as topo_mod

        w = topo_mod.mixing_matrix(ctx.load_topology())
    if not dead:
        membership = getattr(ctx, "elastic_membership", None)
        if membership is not None:
            dead = list(membership.dead_ranks())
    dead = [int(r) for r in dead]
    live = [j for j in range(size) if j not in dead]
    if rounds is None:
        rounds = _auto_rounds(len(live), predicted_rate)
    if rounds <= 0 or not live:
        return _fleet_estimates(
            values.copy(), np.ones(size),
            values.copy(), values.copy(), live or range(size),
        )
    perms, self_w, recv_w, dmask = _lane_operands(w, dead)
    seed = _seed_state(values.astype(np.float32), dead, k)
    fn = _lane_program(ctx, perms, int(rounds), k)
    out = np.asarray(jax.device_get(fn(
        seed,
        self_w[None, :],
        recv_w[None, :, :],
        dmask[None, :, :],
    )), np.float64)
    x = out[:, :k]
    p = out[:, k]
    mn = out[:, k + 1: 2 * k + 1]
    mx = out[:, 2 * k + 1:]
    rep = _fleet_estimates(x, p, mn, mx, live)
    rep["rounds"] = int(rounds)
    return rep


# -- the health plane session -------------------------------------------------


class HealthPlane:
    """One observatory session. Built by :func:`start` (or implicitly
    by ``bf.init()`` under ``BLUEFOG_HEALTH=1``); fed by the optimizer
    layer through :func:`observe_step` on every communicating step, or
    directly (``plane.observe(ctx, step=..., consensus=...)``) by the
    eager path."""

    def __init__(self, interval: Optional[int] = None,
                 eps: Optional[float] = None, history: int = 512):
        from bluefog_tpu import attribution

        self.interval = int(interval) if interval else health_interval()
        self.eps = float(eps) if eps is not None else health_eps()
        self._count = 0
        # guards the sample history against the serving thread:
        # list(deque) while the training thread appends (and maxlen
        # evicts) raises "deque mutated during iteration", turning
        # /fleet scrapes into spurious 500s exactly on sampled steps
        self._report_lock = threading.Lock()
        self.samples: collections.deque = collections.deque(
            maxlen=history
        )
        self.advisories: List[Any] = []
        # comm-step count at each emit, parallel to ``advisories``: the
        # /healthz recency window compares THIS clock, not the caller's
        # ``step`` (which counts non-communicating accumulation steps
        # too under K>1 gradient accumulation)
        self.advisory_marks: List[int] = []
        self._eff_tracker = attribution.BaselineTracker()
        self._mix_streak = 0
        self._mix_cooldown = 0
        self._oob_streak = 0
        # decay points of the CURRENT topology version only: a repair /
        # topology swap changes the predicted rate, and a fit across
        # the seam would blame the new graph for the old one's series
        self._decay_points: collections.deque = collections.deque(
            maxlen=FIT_WINDOW
        )
        self._decay_topo_v: Optional[int] = None
        self._spectral_cache: Dict[Any, Tuple[Optional[float], dict]] = {}
        self._last_sample_wall: Optional[float] = None
        self._last_sample_count = 0
        self._step_ewma_ms: Optional[float] = None
        self._last_wire_bytes: Optional[float] = None
        self._last_wire_steps = 0
        self._wire_per_step: float = 0.0
        self.fleet: Optional[dict] = None
        # streaming push-sum lane state (one application per sample;
        # the dispatched application is retrieved at the NEXT sample —
        # the metrics deferred-drain discipline, so the sampled step
        # never blocks on the device)
        self._lane_cache: Optional[tuple] = None
        self._lane_state = None
        self._lane_pending = None
        self._lane_prev = None
        self._lane_age = 0
        self._published_mm: Optional[tuple] = None

    # -- spectral side --------------------------------------------------------

    def predicted_rate(self, ctx, plan=None) -> Tuple[Optional[float], dict]:
        """Predicted per-round consensus decay of the ACTIVE combine,
        cached per ``(topo_version, live_token)``. Source of truth is
        the optimizer's dispatched plan when given (static CommPlan,
        dynamic SchedulePlan period product, post-repair plans — all
        carry their effective weight matrix); the declared topology
        otherwise."""
        from bluefog_tpu import topology as topo_mod
        from bluefog_tpu.collective.plan import CommPlan, SchedulePlan

        # the plan SOURCE is part of the key: a direct-fed observation
        # (plan=None, declared-topology SLEM) and an optimizer sample
        # (dynamic period product) under the same topo_version are
        # different predictions — the first caller must not freeze the
        # wrong one for the whole version
        source = (
            "schedule" if isinstance(plan, SchedulePlan)
            else "plan" if isinstance(plan, CommPlan)
            else "topology"
        )
        key = (ctx.topo_version, ctx.live_token(), source)
        hit = self._spectral_cache.get(key)
        if hit is not None:
            return hit
        kind = "topology"
        if isinstance(plan, SchedulePlan):
            mats = [p.weight_matrix() for p in plan.plans]
            rate, spec = topo_mod.consensus_decay_rate_info(mats)
            kind = f"schedule(period={len(mats)})"
            self_w = float(np.mean([np.mean(np.diag(m)) for m in mats]))
        elif isinstance(plan, CommPlan):
            w = plan.weight_matrix()
            rate, spec = topo_mod.consensus_decay_rate_info(w)
            kind = "plan"
            self_w = float(np.mean(np.diag(w)))
        else:
            w = topo_mod.mixing_matrix(ctx.load_topology())
            rate, spec = topo_mod.consensus_decay_rate_info(w)
            self_w = float(np.mean(np.diag(w)))
        # mean self weight of the active combine: the `s` of the
        # stale-mixing companion polynomial the age-discounted
        # prediction solves (bluefog_tpu.staleness.age_adjusted_rate).
        # `spectral` discloses how the number was obtained (dense oracle
        # vs deflated Arnoldi over edge lists) with its convergence
        # residual — the honesty field for fleet-scale predictions.
        meta = {"kind": kind, "slem": float(rate),
                "self_weight": self_w,
                "spectral": {
                    "engine": spec.get("engine"),
                    "matvecs": spec.get("matvecs", 0),
                    "residual": spec.get("residual", 0.0),
                    "converged": spec.get("converged", True),
                }}
        if rate >= 1.0 - 1e-9:
            # no contraction promised (disconnected / periodic):
            # publish "no prediction" rather than a vacuous 1.0
            out = (None, meta)
        else:
            out = (float(rate), meta)
        self._spectral_cache[key] = out
        return out

    # -- suspects join --------------------------------------------------------

    @staticmethod
    def _suspect_edges() -> List[Any]:
        """Edges/ranks to name in a ``mixing_degraded`` advisory: the
        shared fabric-health join (:func:`bluefog_tpu.attribution.
        suspect_join` — chaos degrade faults + recent
        ``degraded_link`` edges). The observatory detects the broken
        contract; the wire layers localize it."""
        from bluefog_tpu.attribution import suspect_join

        return suspect_join()

    # -- observation ----------------------------------------------------------

    def observe(self, ctx, *, step: int, plan=None,
                consensus: Optional[float] = None) -> Optional[dict]:
        """Called once per communicating step. Unsampled steps cost one
        compare + one increment; the sampled step runs the observatory
        pass, the push-sum lane, and the serving-state refresh."""
        sampled = self._count % self.interval == 0
        self._count += 1
        if not sampled:
            return None
        return self._sample(ctx, step=step, plan=plan,
                            consensus=consensus)

    def _read_consensus(self) -> Optional[float]:
        from bluefog_tpu import metrics as metrics_mod

        g = metrics_mod.peek("bluefog.gossip.disagreement")
        return float(g.value) if g is not None else None

    def _read_wire_rate(self, steps_elapsed: int) -> float:
        from bluefog_tpu import metrics as metrics_mod

        c = metrics_mod.peek("bluefog.wire_bytes")
        cur = float(c.value) if c is not None else None
        if cur is not None and self._last_wire_bytes is not None \
                and steps_elapsed > 0:
            self._wire_per_step = (
                (cur - self._last_wire_bytes) / steps_elapsed
            )
        self._last_wire_bytes = cur
        return self._wire_per_step

    def _doctor_advisory_count(self) -> int:
        try:
            from bluefog_tpu import attribution

            doc = attribution.active()
            return len(doc.advisories) if doc is not None else 0
        except Exception:
            return 0

    def _live_set(self, ctx) -> Tuple[List[int], List[int]]:
        membership = getattr(ctx, "elastic_membership", None)
        if membership is None:
            return list(range(ctx.size)), []
        return (list(membership.live_ranks()),
                list(membership.dead_ranks()))

    def _local_vector(self, ctx, consensus, live) -> np.ndarray:
        """[size, K] per-rank summary the lane aggregates. Per-worker
        consensus comes from the PR-3 drain's worker rows when the
        device tier is on; host-wide scalars (step EWMA, wire rate,
        advisory count, live digest) replicate across the ranks this
        controller owns — on a multi-controller fleet each process
        contributes its own."""
        from bluefog_tpu import metrics as metrics_mod

        size = ctx.size
        vec = np.zeros((size, len(FLEET_FIELDS)))
        vec[:, 0] = self._step_ewma_ms or 0.0
        rows = metrics_mod.last_worker_rows()
        per_worker = rows.get("bluefog.gossip.disagreement")
        if per_worker is not None and len(per_worker) == size:
            vec[:, 1] = np.asarray(per_worker)
        elif consensus is not None:
            vec[:, 1] = consensus
        vec[:, 2] = self._wire_per_step
        vec[:, 3] = len(self.advisories) + self._doctor_advisory_count()
        digest = float(
            sum((j + 1) * 31 ** i for i, j in enumerate(sorted(live)))
            % 1_000_003
        )
        vec[:, 4] = digest
        vec[:, 5] = self._staleness_age_max()
        mem_bytes, mem_headroom = self._memory_fields()
        vec[:, 6] = mem_bytes
        vec[:, 7] = mem_headroom
        vec[:, 8] = self._slo_burn()
        return vec

    @staticmethod
    def _memory_fields() -> Tuple[float, float]:
        """This controller's measured per-chip footprint and headroom
        ((0.0, 0.0) when the memory observatory is off) — aggregated
        fleet-wide min/mean/max over the push-sum lane: the fleet MIN
        headroom is the chip closest to OOM."""
        try:
            from bluefog_tpu import memory as mem_mod

            obs = mem_mod.active()
            if obs is None:
                return 0.0, 0.0
            return (float(obs.last_bytes_per_rank()),
                    float(obs.last_headroom()))
        except Exception:
            return 0.0, 0.0

    @staticmethod
    def _slo_burn() -> float:
        """Worst fast-window burn rate this controller's SLO engine
        reports (0.0 when the engine is off) — aggregated fleet-wide
        min/mean/max over the push-sum lane: the fleet MAX names the
        rank burning its error budget fastest."""
        try:
            from bluefog_tpu import slo as slo_mod

            return float(slo_mod.worst_burn())
        except Exception:
            return 0.0

    @staticmethod
    def _staleness_age_max() -> float:
        """Worst delivered parameter age this controller has measured
        (0.0 when the staleness observatory is off) — aggregated
        fleet-wide min/mean/max over the push-sum lane."""
        try:
            from bluefog_tpu import staleness as stal_mod

            obs = stal_mod.active()
            return float(obs.last_age_max()) if obs is not None else 0.0
        except Exception:
            return 0.0

    def _fleet_step(self, ctx, values: np.ndarray,
                    dead: Sequence[int],
                    predicted: Optional[float]) -> dict:
        """One STREAMING push-sum application — the sampled-step form
        of :func:`fleet_aggregate` whose cost fits the 1 % budget.

        The lane state persists on the host between samples; each
        sample injects the summary *delta* into the sum lanes
        (``sum(x)`` stays equal to the current fleet total, so ``x/p``
        tracks the live mean with geometric forgetting) and dispatches
        ONE application of the push plan — ~3 ppermutes instead of a
        full fresh convergence per sample — retrieved at the NEXT
        sample (deferred-drain discipline: a synchronous device_get
        here was measured riding the CPU collective rendezvous for
        whole milliseconds under load). Min/max gossip cannot
        forget, so those lanes run in *generations*: reseeded from
        current values every ``generation_len`` samples, with the last
        COMPLETED generation published (staleness <= 2 generations,
        ``warming`` flagged until the first completes). A topology or
        membership change rebuilds the plan and reseeds everything —
        a dead rank's mass drops out with its edges."""
        import jax

        from bluefog_tpu import topology as topo_mod

        size, k = values.shape
        dead = [int(r) for r in dead]
        live = [j for j in range(size) if j not in set(dead)]
        key = (ctx.topo_version, ctx.live_token(), k)
        if self._lane_cache is None or self._lane_cache[0] != key:
            w = topo_mod.mixing_matrix(ctx.load_topology())
            perms, self_w, recv_w, dmask = _lane_operands(w, dead)
            fn = _lane_program(ctx, perms, 1, k)
            self._lane_cache = (
                key, fn, self_w[None, :], recv_w[None, :, :],
                dmask[None, :, :],
            )
            self._lane_state = None
            self._lane_pending = None  # old plan's in-flight result
        _key, fn, self_w, recv_w, dmask = self._lane_cache
        if self._lane_pending is not None:
            # the PREVIOUS sample's application: dispatched a whole
            # sample interval ago, so this read is a completed-copy
            # pickup, not a sync barrier (np.array, not asarray — the
            # delta injection below writes in place)
            self._lane_state = np.array(
                jax.device_get(self._lane_pending), np.float32
            )
            self._lane_pending = None
        gen_len = _auto_rounds(len(live), predicted)
        st = self._lane_state
        values32 = values.astype(np.float32)
        if st is None:
            st = _seed_state(values32, dead, k)
            self._lane_prev = values.copy()
            self._lane_age = 0
            self._published_mm = None
        else:
            delta = (values - self._lane_prev).astype(np.float32)
            if dead:
                delta[dead] = 0.0
            st[:, :k] += delta
            self._lane_prev = values.copy()
            if self._lane_age >= gen_len:
                self._published_mm = (
                    st[:, k + 1: 2 * k + 1].copy(),
                    st[:, 2 * k + 1:].copy(),
                )
                _reseed_minmax(st, values32, dead, k)
                self._lane_age = 0
        # dispatch this sample's application WITHOUT waiting: the
        # result is picked up at the next sample (estimates below come
        # from the retrieved previous state + this sample's injection,
        # one application behind — a health view, not a barrier)
        self._lane_state = st
        pending = fn(st, self_w, recv_w, dmask)
        try:
            pending.copy_to_host_async()
        except AttributeError:
            pass
        self._lane_pending = pending
        self._lane_age += 1
        mm = (
            self._published_mm if self._published_mm is not None
            else (st[:, k + 1: 2 * k + 1], st[:, 2 * k + 1:])
        )
        rep = _fleet_estimates(
            st[:, :k].astype(np.float64),
            st[:, k].astype(np.float64),
            np.asarray(mm[0], np.float64),
            np.asarray(mm[1], np.float64),
            live,
        )
        rep["rounds"] = 1
        rep["generation_len"] = int(gen_len)
        rep["warming"] = self._published_mm is None
        return rep

    def _sample(self, ctx, *, step, plan, consensus) -> dict:
        from bluefog_tpu import metrics as metrics_mod

        t_now = time.perf_counter()
        steps_elapsed = self._count - self._last_sample_count
        step_s = None
        if self._last_sample_wall is not None and steps_elapsed > 0:
            step_s = (t_now - self._last_sample_wall) / steps_elapsed
        self._last_sample_wall = t_now
        self._last_sample_count = self._count
        if step_s is not None:
            ms = step_s * 1e3
            self._step_ewma_ms = ms if self._step_ewma_ms is None \
                else 0.8 * self._step_ewma_ms + 0.2 * ms

        if consensus is None:
            consensus = self._read_consensus()
        wire_rate = self._read_wire_rate(steps_elapsed)
        live, dead = self._live_set(ctx)

        sample: Dict[str, Any] = {
            "kind": "sample",
            "step": int(step),
            "comm_steps": self._count,
            "topo_version": int(ctx.topo_version),
        }
        if self._step_ewma_ms is not None:
            sample["step_ms_ewma"] = round(self._step_ewma_ms, 4)
        if consensus is not None:
            sample["consensus"] = float(consensus)
        if wire_rate:
            sample["wire_bytes_per_step"] = wire_rate
        if dead:
            sample["dead_ranks"] = dead

        # -- mixing observatory ----------------------------------------------
        predicted, spec_meta = self.predicted_rate(ctx, plan)
        sample["predicted_rate"] = predicted
        sample["spectral"] = spec_meta
        if ctx.topo_version != self._decay_topo_v:
            from bluefog_tpu import attribution

            self._decay_points.clear()
            self._decay_topo_v = ctx.topo_version
            # a new graph promises a new rate: the efficiency baseline
            # of the old one must not advise (or silence) this one
            self._eff_tracker = attribution.BaselineTracker()
            self._mix_streak = 0
            self._mix_cooldown = 0
            self._oob_streak = 0
        if consensus is not None:
            self._decay_points.append((self._count, consensus))
        measured = fit_decay_rate(self._decay_points)
        eff = mixing_efficiency(measured, predicted)
        if measured is not None:
            sample["measured_rate"] = round(measured, 6)
        if eff is not None:
            sample["mixing_efficiency"] = round(eff, 4)
        tte = time_to_consensus_steps(
            consensus,
            measured if measured is not None and measured < 1.0
            else predicted,
            self.eps,
        )
        if tte is not None:
            sample["time_to_eps_steps"] = round(tte, 1)
            sample["eps"] = self.eps

        # -- age-discounted effective mixing (bluefog_tpu.staleness) ---------
        # The spectral prediction assumes zero staleness; under
        # delayed=True or window-op exchanges it silently overstates
        # the promised contraction. When the staleness observatory is
        # measuring delivered age, correct the promise through the
        # stale-mixing companion polynomial — the corrected efficiency
        # is what the fabric can honestly be held to.
        eff_adj = None
        try:
            from bluefog_tpu import staleness as stal_mod

            obs = stal_mod.active()
            age = obs.last_age_mean() if obs is not None else None
        except Exception:
            age = None
        if age and predicted is not None:
            adj = stal_mod.age_adjusted_rate(
                predicted, age, spec_meta.get("self_weight", 0.5)
            )
            sample["age_mean"] = round(float(age), 4)
            if adj is not None and adj != predicted:
                sample["age_adjusted_rate"] = round(adj, 6)
                eff_adj = mixing_efficiency(measured, adj)
                if eff_adj is not None:
                    sample["mixing_efficiency_age_adjusted"] = round(
                        eff_adj, 4
                    )
                metrics_mod.gauge(
                    "bluefog.health.age_adjusted_rate"
                ).set(adj)

        found = []
        if eff is not None:
            tr = self._eff_tracker
            if tr.n < MIN_FIT_POINTS:
                # warmup: the first fits ride a transient (a short
                # window over the initial decay knee) — absorb them
                # unconditionally so a garbage first value can never
                # freeze the baseline
                tr.update(eff)
                base, degraded = tr.mean, False
                self._oob_streak = 0
            else:
                base = tr.mean
                floor = max(tr.mad, abs(base) * 0.01, 1e-12)
                z = (eff - base) / floor
                degraded = z < -3.0 and eff < base * (
                    1.0 - MIXING_DEGRADED_FRAC
                )
                if abs(z) <= 3.0:
                    # only IN-BAND samples teach the baseline: a slow
                    # efficiency ramp absorbed while "not yet degraded"
                    # inflates the MAD exactly as fast as the ramp
                    # diverges, so the z-gate would never trip — the
                    # baseline must stay the healthy reference until
                    # the series returns to band
                    tr.update(eff)
                    self._oob_streak = 0
                elif degraded:
                    self._oob_streak = 0
                else:
                    # out of band but NOT degraded (e.g. efficiency
                    # jumped ABOVE the band): a persistent shift is a
                    # new regime, not an anomaly — re-baseline after a
                    # full fit window of it
                    self._oob_streak += 1
                    if self._oob_streak >= FIT_WINDOW:
                        tr.update(eff)
                        self._oob_streak = 0
            self._mix_streak = self._mix_streak + 1 if degraded else 0
            if self._mix_cooldown > 0:
                self._mix_cooldown -= 1
            if self._mix_streak >= MIXING_STREAK and \
                    self._mix_cooldown == 0:
                from bluefog_tpu.attribution import Advisory

                found.append(Advisory(
                    kind="mixing_degraded", step=int(step),
                    detail={
                        "mixing_efficiency": round(eff, 4),
                        "baseline_efficiency": round(base, 4),
                        "predicted_rate": predicted,
                        "measured_rate": (
                            round(measured, 6)
                            if measured is not None else None
                        ),
                        "topo_version": int(ctx.topo_version),
                        "suspect_edges": self._suspect_edges(),
                    },
                ))
                self._mix_streak = 0
                # rate-limit a PERSISTENT condition: the counter and
                # /healthz stay raised; the flight ring need not fill
                self._mix_cooldown = FIT_WINDOW

        # -- in-band fleet aggregation ---------------------------------------
        try:
            vec = self._local_vector(ctx, consensus, live)
            fleet = self._fleet_step(ctx, vec, dead, predicted)
            fleet["fields"] = list(FLEET_FIELDS)
            self.fleet = fleet
            sample["fleet"] = {
                "mean": fleet["mean"], "min": fleet["min"],
                "max": fleet["max"], "residual": fleet["residual"],
                "rounds": fleet.get("rounds", 0),
                "live": fleet["live"],
            }
            if fleet.get("warming"):
                # min/max lanes publish their first completed
                # generation; until then the extrema cover only the
                # warmup snapshot and must say so
                sample["fleet"]["warming"] = True
            metrics_mod.gauge("bluefog.health.fleet_residual").set(
                fleet["residual"]
            )
        except Exception as e:  # the lane must never kill training
            sample["fleet_error"] = str(e)[:200]

        # -- emission ---------------------------------------------------------
        if eff is not None:
            metrics_mod.gauge("bluefog.health.mixing_efficiency").set(
                eff
            )
        if predicted is not None:
            metrics_mod.gauge("bluefog.health.predicted_rate").set(
                predicted
            )
        if measured is not None:
            metrics_mod.gauge("bluefog.health.measured_rate").set(
                measured
            )
        if eff_adj is not None:
            metrics_mod.gauge(
                "bluefog.health.mixing_efficiency_age_adjusted"
            ).set(eff_adj)
        if tte is not None:
            metrics_mod.gauge("bluefog.health.time_to_eps_steps").set(
                tte
            )
        metrics_mod.counter("bluefog.health.samples").inc()

        if found:
            sample["advisories"] = [a.to_json() for a in found]
        for adv in found:
            self._emit(adv)
        with self._report_lock:
            self.samples.append(sample)
        self._export_line(sample)
        return sample

    def _emit(self, adv) -> None:
        """One advisory, the PR-7 surfaces: ``bluefog.doctor.*``
        metrics, flight side table, timeline instant, health JSONL."""
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

    # -- serving state / artifact ---------------------------------------------

    def _build_report(self) -> dict:
        with self._report_lock:
            samples = list(self.samples)
        return {
            "kind": "health_dump",
            "interval": self.interval,
            "comm_steps": self._count,
            "eps": self.eps,
            "last_sample": samples[-1] if samples else {},
            "samples": samples,
            "advisories": [a.to_json() for a in self.advisories],
            "fleet": self.fleet,
            "fields": list(FLEET_FIELDS),
        }

    def report(self) -> dict:
        """The health artifact ``tools/fleet_report.py`` and
        ``tools/doctor.py --health`` consume. Built on demand (the
        serving thread's clock, not the training loop's — copying the
        sample history every sample was measurable against the 1 %
        budget)."""
        rep = self._build_report()
        rep["healthz"] = healthz_verdict(self)
        # the autotune controller's decision summary rides the /fleet
        # surface: an operator reading the fleet table must see that a
        # rank's topology is being actively re-tuned (and how often it
        # rolled back) next to the health numbers that drove it
        try:
            from bluefog_tpu import autotune as autotune_mod

            tuner = autotune_mod.active()
            if tuner is not None:
                rep["autotune"] = tuner.summary()
        except Exception:
            pass
        # the asynchronous gossip engine's summary rides the same
        # surface: ticks vs local steps, staleness-gate activity, and
        # the cadence map an operator needs to read the age-adjusted
        # mixing score next to it (docs/async.md)
        try:
            from bluefog_tpu import async_gossip as async_mod

            engine = async_mod.active()
            if engine is not None:
                rep["async"] = engine.summary()
        except Exception:
            pass
        # the weight-update shard layout rides here too: an operator
        # sizing a fleet reads per-rank optimizer-state bytes (measured
        # + analytic 1/N model) next to the health numbers
        # (BLUEFOG_SHARD, docs/sharding.md)
        try:
            from bluefog_tpu import sharding as sharding_mod

            shard = sharding_mod.summary()
            if shard is not None:
                rep["shard"] = shard
        except Exception:
            pass
        # the federated fabric rides here too: an operator reading the
        # fleet table must see WHICH pod layout, gateway set, DCN
        # period/wire, and predicted composed consensus rate the gossip
        # they are looking at is actually running (BLUEFOG_PODS,
        # docs/federation.md)
        try:
            from bluefog_tpu import context as ctx_mod
            from bluefog_tpu import federation as fed_mod

            if fed_mod.enabled() and ctx_mod.is_initialized():
                fab = fed_mod.get_fabric(ctx_mod.get_context().size)
                if fab is not None:
                    rep["federation"] = fab.to_json()
        except Exception:
            pass
        # the memory observatory's summary rides the same surface: an
        # operator sizing a fleet reads per-chip footprint, headroom
        # against the budget, and the last ranked census next to the
        # health numbers (BLUEFOG_MEMORY, docs/memory.md)
        try:
            from bluefog_tpu import memory as mem_mod

            obs = mem_mod.active()
            if obs is not None:
                rep["memory"] = {
                    "bytes_per_rank": int(obs.last_bytes_per_rank()),
                    "headroom_bytes": (
                        int(obs.last_headroom()) if obs.budget else None
                    ),
                    "budget_bytes": obs.budget or None,
                    "peak_bytes_per_rank": int(obs._peak_bytes),
                    "oom_events": obs.oom_events,
                    "ranked_census": mem_mod.ranked_census(
                        obs.last_census
                    )[:4],
                }
        except Exception:
            pass
        # the SLO engine's budget summary rides the same surface: the
        # operator reading the fleet table needs "how much failure
        # budget is left and how fast is it burning" next to the raw
        # numbers that spend it (BLUEFOG_SLO, docs/slo.md); the full
        # artifact is served at /slo
        try:
            from bluefog_tpu import slo as slo_mod

            eng = slo_mod.active()
            if eng is not None:
                rep["slo"] = {
                    "worst_burn": eng.worst_burn(),
                    "exhausted": eng.exhausted_objectives(),
                    "alerts": len(eng.alerts),
                    "canary": (
                        eng.canary.last
                        if eng.canary is not None else None
                    ),
                }
        except Exception:
            pass
        return rep

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.report(), f)
        return path


# -- RAG verdict --------------------------------------------------------------


def healthz_verdict(plane: Optional["HealthPlane"] = None) -> dict:
    """The ``/healthz`` RAG verdict, computable without a live mesh:

    - **critical** — the elastic membership holds dead or suspect
      ranks (the run is mid-failure or down a worker), or an SLO
      error budget is exhausted (:mod:`bluefog_tpu.slo` — a spent
      budget is the contract-level outage even while every rank
      answers its heartbeat);
    - **warn** — any advisory (health or doctor) fired within the last
      :data:`VERDICT_RECENT_SAMPLES` health samples;
    - **ok** — otherwise.

    HTTP mapping: 200 for ok/warn (serving but flagged), 503 for
    critical — what a load balancer or k8s liveness probe expects."""
    plane = plane if plane is not None else _plane
    status = "ok"
    reasons: List[str] = []
    dead: List[int] = []
    suspects: List[int] = []
    try:
        from bluefog_tpu import context as ctx_mod

        ctx = ctx_mod.get_context() if ctx_mod.is_initialized() else None
    except Exception:
        ctx = None
    membership = getattr(ctx, "elastic_membership", None) if ctx else None
    if membership is not None:
        dead = [int(r) for r in membership.dead_ranks()]
        from bluefog_tpu.elastic.membership import RankState

        suspects = [
            int(r) for r in range(membership.world_size)
            if membership.state(r) == RankState.SUSPECT
        ] if hasattr(membership, "world_size") else []
        if dead:
            status = "critical"
            reasons.append(f"dead ranks: {dead}")
        if suspects:
            status = "critical"
            reasons.append(f"suspect ranks: {suspects}")
    exhausted: List[str] = []
    try:
        from bluefog_tpu import slo as slo_mod

        exhausted = slo_mod.exhausted_objectives()
    except Exception:
        pass
    if exhausted:
        status = "critical"
        reasons.append(f"slo budget exhausted: {exhausted}")
    recent: List[dict] = []
    if plane is not None:
        floor = plane._count - VERDICT_RECENT_SAMPLES * plane.interval
        recent = [
            a.to_json()
            for a, mark in zip(plane.advisories, plane.advisory_marks)
            if mark >= max(floor, 0)
        ]
    try:
        from bluefog_tpu import attribution

        doc = attribution.active()
        if doc is not None:
            # same window, the DOCTOR's own comm-step clock (its
            # advisory marks; advisory.step counts non-communicating
            # accumulation steps too and would stretch the window K×)
            floor = doc._count - VERDICT_RECENT_SAMPLES * doc.interval
            marks = getattr(doc, "advisory_marks", None)
            if marks is not None:
                recent += [
                    a.to_json()
                    for a, mark in zip(doc.advisories, marks)
                    if mark >= max(floor, 0)
                ]
            else:
                recent += [a.to_json() for a in doc.advisories[-3:]]
    except Exception:
        pass
    if recent and status == "ok":
        status = "warn"
        kinds = sorted({a.get("kind", "?") for a in recent})
        reasons.append(f"recent advisories: {kinds}")
    return {
        "status": status,
        "reasons": reasons,
        "dead_ranks": dead,
        "suspect_ranks": suspects,
        "slo_exhausted": exhausted,
        "recent_advisories": recent[-8:],
        "ts": time.time(),
    }


# -- serving surface ----------------------------------------------------------


class HealthServer:
    """Per-rank stdlib HTTP endpoint: ``/healthz`` (RAG verdict, 503 on
    critical), ``/metrics`` (live Prometheus scrape), ``/fleet`` (the
    in-band aggregate + local summary as JSON). Daemon-threaded; a bind
    failure is a logged no-op (:meth:`maybe_start`), never a training
    crash."""

    def __init__(self, httpd, thread):
        self._httpd = httpd
        self._thread = thread
        self.port = int(httpd.server_address[1])

    @classmethod
    def maybe_start(cls, port: Optional[int] = None,
                    host: str = "0.0.0.0") -> Optional["HealthServer"]:
        """Start serving on ``port`` (default ``BLUEFOG_HEALTH_PORT``;
        0 with an explicit call = OS-assigned). Returns None — with a
        warning, without raising — when the port is taken or the env
        asks for nothing."""
        from http.server import BaseHTTPRequestHandler, HTTPServer
        from socketserver import ThreadingMixIn

        from bluefog_tpu.logging_util import logger

        env_port = port is None
        if port is None:
            port = health_port()
            if port <= 0:
                return None

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # no stderr chatter per scrape
                pass

            def _send(self, code, body, ctype="application/json"):
                data = body.encode() if isinstance(body, str) else body
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                from bluefog_tpu import metrics as metrics_mod

                path = self.path.split("?")[0].rstrip("/") or "/healthz"
                try:
                    if path == "/healthz":
                        v = healthz_verdict()
                        code = 503 if v["status"] == "critical" else 200
                        # strict JSON: a NaN gauge must never reach the
                        # scraper as a bare NaN token (allow_nan=False
                        # is the regression tripwire — _json_safe
                        # already replaced every non-finite value)
                        self._send(code, json.dumps(
                            _json_safe(v), allow_nan=False
                        ))
                    elif path == "/metrics":
                        self._send(
                            200,
                            "\n".join(metrics_mod.prom_lines()) + "\n",
                            ctype="text/plain; version=0.0.4",
                        )
                    elif path == "/fleet":
                        plane = active()
                        body = (
                            plane.report() if plane is not None
                            else {"kind": "health_dump",
                                  "healthz": healthz_verdict(None),
                                  "fleet": None, "samples": []}
                        )
                        self._send(200, json.dumps(
                            _json_safe(body), allow_nan=False
                        ))
                    elif path == "/slo":
                        from bluefog_tpu import slo as slo_mod

                        eng = slo_mod.active()
                        body = (
                            eng.report() if eng is not None
                            else {"kind": "slo_dump",
                                  "objectives": [], "alerts": [],
                                  "canary": None}
                        )
                        self._send(200, json.dumps(
                            _json_safe(body), allow_nan=False
                        ))
                    else:
                        self._send(404, json.dumps(
                            {"error": f"unknown path {path!r}",
                             "paths": ["/healthz", "/metrics",
                                       "/fleet", "/slo"]}
                        ))
                except Exception as e:  # a scrape bug must not hang curl
                    try:
                        self._send(500, json.dumps(
                            {"error": str(e)[:200]}
                        ))
                    except Exception:
                        pass

        class _Server(ThreadingMixIn, HTTPServer):
            daemon_threads = True
            # fast rebinds between tests/restarts; a REAL port conflict
            # (another process listening) still raises EADDRINUSE
            allow_reuse_address = True

        try:
            httpd = _Server((host, int(port)), _Handler)
        except OSError as e:
            logger.warning(
                "health endpoint disabled: cannot bind %s:%s (%s)%s",
                host, port, e,
                " — set BLUEFOG_HEALTH_PORT to a free port" if env_port
                else "",
            )
            return None
        thread = threading.Thread(
            target=httpd.serve_forever, name="bf-healthz", daemon=True
        )
        thread.start()
        return cls(httpd, thread)

    def close(self) -> None:
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:
            pass


# -- module-level session -----------------------------------------------------

_plane: Optional[HealthPlane] = None
_server: Optional[HealthServer] = None


def start(interval: Optional[int] = None, **kwargs) -> HealthPlane:
    """Open a health-plane session (replacing any active one)."""
    global _plane
    _plane = HealthPlane(interval=interval, **kwargs)
    return _plane


def stop() -> None:
    global _plane
    _plane = None


def active() -> Optional[HealthPlane]:
    return _plane


def serve(port: Optional[int] = None) -> Optional[HealthServer]:
    """Start (or restart) the HTTP endpoint; None on bind failure."""
    global _server
    if _server is not None:
        _server.close()
    _server = HealthServer.maybe_start(port)
    return _server


def server() -> Optional[HealthServer]:
    return _server


def observe_step(ctx, *, step: int, plan=None,
                 consensus: Optional[float] = None) -> None:
    """Optimizer-layer hook, called after every communicating dispatch
    (next to :func:`bluefog_tpu.attribution.observe_step`). No-op (one
    attribute read) when no session is active."""
    plane = _plane
    if plane is None:
        return
    plane.observe(ctx, step=step, plan=plan, consensus=consensus)


def dump(path: str) -> Optional[str]:
    """Write the active session's health artifact (None when no
    session is active)."""
    plane = _plane
    if plane is None:
        return None
    return plane.dump(path)


def on_init(ctx) -> None:
    """``bf.init()`` hook: fresh session under ``BLUEFOG_HEALTH=1`` (a
    new mesh must not inherit a torn-down mesh's efficiency baseline),
    endpoint under ``BLUEFOG_HEALTH_PORT``."""
    if enabled():
        start()
    else:
        stop()
    global _server
    if _server is not None:
        _server.close()
        _server = None
    if health_port() > 0:
        serve()


def on_shutdown() -> None:
    """``bf.shutdown()`` hook: flush the JSONL tail, stop serving,
    drop the session."""
    global _server
    plane = _plane
    if plane is not None and plane.samples:
        plane._export_line({"kind": "session_end",
                            "comm_steps": plane._count})
    if _server is not None:
        _server.close()
        _server = None
    stop()
