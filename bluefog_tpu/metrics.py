# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Unified telemetry: in-graph gossip health metrics + host-side registry.

A decentralized trainer's failure modes are *statistical*, not just
temporal: consensus drift between neighbors, quantization/error-feedback
residual growth, and staleness effects are invisible in a Chrome-trace
timeline (:mod:`bluefog_tpu.timeline`, the only observability surface the
reference ships — ``common/timeline.cc``). This module adds the numbers.

Two tiers:

**Device tier** — gossip-health scalars computed *inside* the existing
compiled shard_map programs (zero extra dispatches): the neighbor
disagreement norm ``||x_i - sum_r w_r x_r||`` (equal to the gossip delta
``||y - x||`` for normalized combines — see :func:`build_probe_payload`),
the gossip-input parameter norm, the local gradient norm, the
quantization error of the int8/bf16 wires, and the error-feedback
residual of the ``int8_ef`` wire. Sampling is 1-in-
``BLUEFOG_METRICS_INTERVAL`` communicating steps, two-program style:
the un-sampled steps dispatch the EXACT metrics-off program (same cache
key — zero overhead by construction), and the sampled step's program
additionally outputs tiny pre-scaled subsample slices
(:func:`build_probe_payload`) whose norms the HOST computes at the next
sample from the asynchronously copied-back payload
(:func:`fold_device_payload`). In-graph reductions over the live
training trees were measured to derail the XLA CPU schedule by far more
than their arithmetic; O(cap) slice outputs are free.
Enabling metrics adds *outputs* but identical parameter/optimizer
math — the training state is pinned bitwise-identical metrics-on vs
metrics-off (tests/test_metrics.py).

**Host tier** — a process-wide registry of counters / gauges /
histograms fed by the runtime itself: comm-plan compile cache hits and
misses, XLA program (re)compiles, ppermute rounds and wire bytes per
gossip step, window-op counts, and watchdog stall events. The
attribution doctor (:mod:`bluefog_tpu.attribution`) both reads this
tier (counter deltas via :func:`peek`) and feeds it back
(``bluefog.doctor.*`` gauges and advisory counters).

Exporters (all three can run at once):

- **JSONL** (``BLUEFOG_METRICS_FILE`` or :func:`export_jsonl`): one
  snapshot object per line, appended at every device-buffer drain —
  summarize with ``tools/metrics_report.py``;
- **Prometheus textfile** (``BLUEFOG_METRICS_PROM`` or
  :func:`export_prom`): node-exporter textfile-collector format,
  rewritten atomically at each drain/export;
- **Chrome-trace counter events** (automatic while the timeline is
  active): ``ph:"C"`` records appended to the live timeline JSON, so the
  consensus-drift curve renders directly under the op spans in
  chrome://tracing / Perfetto.

Env knobs: ``BLUEFOG_METRICS=1`` enables the device tier (default off),
``BLUEFOG_METRICS_INTERVAL`` sets the drain period in communicating
steps (default 10). See docs/metrics.md.
"""

import json
import os
import threading
import time
from typing import Dict, Optional

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "peek",
    "reset",
    "enabled",
    "metrics_interval",
    "flush",
    "register_flush_hook",
    "export_jsonl",
    "export_prom",
    "prom_lines",
    "export_timeline_counters",
    "last_worker_rows",
    "metrics_export",
    "N_SLOTS",
    "SLOT_COUNT",
    "SLOT_DISAGREEMENT",
    "SLOT_PARAM_NORM",
    "SLOT_GRAD_NORM",
    "SLOT_QUANT_ERR",
    "SLOT_EF_RESIDUAL",
    "sample_elems_cap",
    "build_probe_payload",
    "fold_device_payload",
    "drain_device_buffer",
    "wire_bytes_per_step",
    "record_allgather_wire",
]


# -- host-tier registry -------------------------------------------------------

_lock = threading.Lock()
_registry: Dict[str, object] = {}


class Counter:
    """Monotonic event count (plan-cache hits, recompiles, stalls...)."""

    kind = "counter"

    def __init__(self):
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        with _lock:
            self.value += n

    def describe(self) -> dict:
        return {"type": self.kind, "value": self.value}


class Gauge:
    """Last-written value (rounds per step, drained RMS norms...)."""

    kind = "gauge"

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        with _lock:
            self.value = float(v)

    def describe(self) -> dict:
        return {"type": self.kind, "value": self.value}


class Histogram:
    """Running summary (count / sum / min / max / last) plus bounded
    log-bucket tail quantiles (p50 / p90 / p99).

    The five-number summary feeds dashboards and JSONL diffs; the
    quantiles answer the question a five-number summary cannot — "what
    is tail latency" — without unbounded storage: observations land in
    logarithmic buckets (:data:`_LOG_RES` per octave, clamped to a
    fixed index range), so memory is O(1) in the observation count and
    a reported quantile is within one bucket (≈ ``2**(1/(2*_LOG_RES))``,
    ~9 % relative) of the true order statistic. Exact distributions
    belong in the profiler tier."""

    kind = "histogram"

    # log-bucket resolution: buckets per octave. Reported quantiles are
    # within 2**(1/(2*_LOG_RES)) (~9%) of the true value.
    _LOG_RES = 4
    # clamp indices to [2**-40, 2**40] (~1e-12 .. ~1e12): 321 buckets
    # max, so a hostile series cannot grow the dict without bound
    _IDX_MIN = -40 * _LOG_RES
    _IDX_MAX = 40 * _LOG_RES
    QUANTILES = (0.5, 0.9, 0.99)

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.last = 0.0
        self._buckets: Dict[int, int] = {}

    def _bucket(self, v: float) -> int:
        import math

        if v <= 0.0:
            # zero / negative observations share the underflow bucket:
            # the quantile walk reports them as "at or below 2^-40"
            return self._IDX_MIN
        idx = round(self._LOG_RES * math.log2(v))
        return max(self._IDX_MIN, min(self._IDX_MAX, idx))

    def observe(self, v: float) -> None:
        v = float(v)
        b = self._bucket(v)
        with _lock:
            self.count += 1
            self.sum += v
            self.min = v if self.min is None else min(self.min, v)
            self.max = v if self.max is None else max(self.max, v)
            self.last = v
            self._buckets[b] = self._buckets.get(b, 0) + 1

    def quantile(self, q: float) -> Optional[float]:
        """The ``q``-quantile from the log buckets (None when empty).
        Representative value is the bucket's log-space center, clamped
        into the exact observed [min, max] envelope so a one-bucket
        histogram reports its own numbers."""
        with _lock:
            if self.count == 0:
                return None
            need = q * self.count
            seen = 0
            idx = self._IDX_MAX
            for idx in sorted(self._buckets):
                seen += self._buckets[idx]
                if seen >= need:
                    break
            v = 2.0 ** (idx / self._LOG_RES)
            lo = self.min if self.min is not None else v
            hi = self.max if self.max is not None else v
            return float(min(max(v, lo), hi))

    def describe(self) -> dict:
        out = {
            "type": self.kind,
            "count": self.count,
            "sum": self.sum,
            "min": self.min,
            "max": self.max,
            "last": self.last,
        }
        if self.count:
            for q in self.QUANTILES:
                out[f"p{int(q * 100)}"] = self.quantile(q)
        return out


def _series(name: str, cls):
    with _lock:
        cur = _registry.get(name)
        if cur is None:
            cur = cls()
            _registry[name] = cur
            return cur
    if not isinstance(cur, cls):
        raise TypeError(
            f"metric {name!r} is a {cur.kind}, requested {cls.kind}"
        )
    return cur


def counter(name: str) -> Counter:
    return _series(name, Counter)


def gauge(name: str) -> Gauge:
    return _series(name, Gauge)


def histogram(name: str) -> Histogram:
    return _series(name, Histogram)


def snapshot() -> dict:
    """All series as ``{name: {"type": ..., "value"/"count"/...}}``."""
    with _lock:
        items = sorted(_registry.items())
    return {name: s.describe() for name, s in items}


def peek(name: str):
    """The registered series object, or None when nothing has written
    it yet. Read-only consumers (the attribution doctor's counter-delta
    and gauge reads, :mod:`bluefog_tpu.attribution`) use this instead of
    :func:`counter`/:func:`gauge`, which would CREATE an empty series —
    a snapshot polluted with never-written zeros is indistinguishable
    from measured zeros."""
    with _lock:
        return _registry.get(name)


def reset() -> None:
    """Drop every registered series (test isolation)."""
    global _allgather_calls
    with _lock:
        _registry.clear()
        _last_worker_rows.clear()
        _allgather_calls = 0


# -- env knobs ----------------------------------------------------------------


def enabled() -> bool:
    """Device-tier switch: ``BLUEFOG_METRICS=1`` (default off). The host
    registry records unconditionally (its cost is a dict update on
    already-host-side events); this gates the in-graph computation and
    the per-dispatch accounting on the training hot path."""
    return os.environ.get("BLUEFOG_METRICS", "0").lower() in (
        "1", "true", "on", "yes",
    )


def metrics_interval() -> int:
    """Sampling/drain period in communicating steps
    (``BLUEFOG_METRICS_INTERVAL``, default 10): one step in every
    ``interval`` dispatches the program with metric outputs (the other
    steps run the metrics-off program unchanged) and its buffer is
    drained with an async device->host copy. Larger interval = coarser
    health sampling, proportionally lower overhead."""
    from bluefog_tpu.logging_util import env_int

    return max(1, env_int("BLUEFOG_METRICS_INTERVAL", 10))


# -- device tier: buffer layout and traced helpers ----------------------------

# One f32 row per worker per drained sample; every slot except COUNT
# holds a SUM OF SQUARES so the drain reports an RMS. Rows are built
# host-side by fold_device_payload from the sampled step's subsample
# payload.
SLOT_COUNT = 0         # communicating steps accumulated since last drain
SLOT_DISAGREEMENT = 1  # sum ||y - x||^2  (weighted neighbor disagreement)
SLOT_PARAM_NORM = 2    # sum ||x||^2 of the gossip input
SLOT_GRAD_NORM = 3     # sum ||g||^2 of the local gradient
SLOT_QUANT_ERR = 4     # sum ||payload - dequant(payload)||^2 (quantized wires)
SLOT_EF_RESIDUAL = 5   # sum ||x - x_hat_self||^2 (int8_ef CHOCO residual)
N_SLOTS = 6

_SLOT_NAMES = {
    SLOT_DISAGREEMENT: "disagreement",
    SLOT_PARAM_NORM: "param_norm",
    SLOT_GRAD_NORM: "grad_norm",
    SLOT_QUANT_ERR: "quant_err",
    SLOT_EF_RESIDUAL: "ef_residual",
}


def sample_elems_cap() -> int:
    """Per-metric element budget for the probe subsamples
    (``BLUEFOG_METRICS_SAMPLE_ELEMS``, default 64 Ki). Payloads at or
    under the cap are covered exactly; larger payloads are estimated
    from a CONTIGUOUS 512-aligned prefix of each packed dtype group,
    scaled by the coverage ratio — O(cap) cost however large the model,
    at the price of a bias toward the group's leading leaves (the
    packing order). Health telemetry needs drift *trends*, not the
    tenth significant digit; set the knob huge to force exact
    coverage."""
    from bluefog_tpu.logging_util import env_int

    return max(512, env_int("BLUEFOG_METRICS_SAMPLE_ELEMS", 1 << 16))


# Subsample granularity: whole contiguous 512-element chunks, matching
# the quantization chunk (so the quant_err path's re-quantized chunk
# scales stay bit-identical to the wire's for the covered region).
_ROW = 512




def build_probe_payload(pairs, g_subs, wire=None):
    """Package the metrics SUB-GOSSIP's results (traced, inside
    shard_map) into the payload dict the HOST folds at drain time
    (:func:`fold_device_payload`).

    ``pairs`` is ``[(sub_x, sub_y, scale, ef_self_new | None)]`` per
    dtype group, where ``sub_x`` is a 512-aligned prefix of the packed
    combine input and ``sub_y`` the output of running the SAME wire on
    just that subsample — the combine is elementwise (chunk-local for
    the quantized wires, and the prefix preserves chunk boundaries), so
    ``sub_y`` is bitwise the restriction of the full combine. This is
    the design that survived measurement: any metric computation that
    consumes the BIG combine's outputs (norms, slices, packed or
    unpacked) derails the CPU backend's schedule by a third of a step,
    while a sub-gossip touches only input values plus tiny extra
    ppermutes.

    The host derives *disagreement* ``||y - x||^2``: for a normalized
    combine ``y = s x + sum_r w_r x_r`` with ``s + sum_r w_r = 1`` this
    equals ``||sum_r w_r (x - x_r)||^2`` — the weighted disagreement
    with the in-neighborhood (consensus distance / gossip delta).
    ``scale`` (group elems / covered elems) is folded in as
    ``sqrt(scale)`` so plain host squared sums estimate the full
    payload — exact when it fits :func:`sample_elems_cap`.

    ``g_subs`` is ``[(sub, scale)]`` for the local gradient tree
    (sliced the same way, no combine). ``wire`` additionally ships the
    UNSCALED input slice per group for the host's quantization-error
    replay; for ``int8_ef`` the probe's updated ``x_hat_self`` slice
    rides along (the CHOCO identity makes quantization error == new
    residual).

    Everything here is *observational*: no value feeding the parameter /
    optimizer-state outputs is touched, which is what keeps metrics
    on/off bitwise-identical for the training state.
    """
    import math

    import jax.numpy as jnp

    def scaled(sub, scale):
        sub = sub.astype(jnp.float32)
        if scale != 1.0:
            sub = sub * jnp.float32(math.sqrt(scale))
        return sub

    payload = {
        "x": tuple(scaled(sx, sc) for sx, _sy, sc, _e in pairs),
        "y": tuple(scaled(sy, sc) for _sx, sy, sc, _e in pairs),
        "g": tuple(scaled(sg, sc) for sg, sc in g_subs),
        "pack": (),
        "ef": (),
    }
    if wire in _QUANT_WIRES:
        # unscaled slice + its ratio: the host quantizes the slice
        # itself, so the scale cannot be folded into the values
        payload["pack"] = tuple(
            (sx.astype(jnp.float32), jnp.full((1,), sc, jnp.float32))
            for sx, _sy, sc, _e in pairs
        )
    if wire in _EF_WIRES:
        payload["ef"] = tuple(e for _sx, _sy, _sc, e in pairs)
    return payload


def _np_chunk_quantize(xf):
    """Host-side replica of
    :func:`bluefog_tpu.collective.inner._chunk_quantize` (same chunking,
    same zero-guard) for the drain-time quantization-error fold —
    delegates to the shared packed-wire reference
    (:mod:`bluefog_tpu.collective.wire_ref`), the single numpy source of
    truth the device paths are pinned against."""
    from bluefog_tpu.collective import wire_ref

    return wire_ref.np_chunk_quantize(xf)


def _np_pack_nibbles(q):
    """Host replica of ``inner._pack_nibbles`` (shared reference —
    see :mod:`bluefog_tpu.collective.wire_ref`)."""
    from bluefog_tpu.collective import wire_ref

    return wire_ref.np_pack_nibbles(q)


def _np_unpack_nibbles(p):
    """Host replica of ``inner._unpack_nibbles`` (shared reference)."""
    from bluefog_tpu.collective import wire_ref

    return wire_ref.np_unpack_nibbles(p)


def _np_chunk_quantize4(xf):
    """Host-side replica of ``inner._chunk_quantize4`` — int4 nibbles
    against the bf16-snapped block scale, through the pack/unpack pair
    so the replay exercises the exact wire format (shared reference)."""
    from bluefog_tpu.collective import wire_ref

    return wire_ref.np_chunk_quantize4(xf)


# Every wire tier with a quant-error replay; the _ef members additionally
# publish the CHOCO residual slot.
_QUANT_WIRES = ("int8", "bf16", "int8_ef", "int4", "int4_ef")
_EF_WIRES = ("int8_ef", "int4_ef")


def fold_device_payload(payload, wire=None,
                        prefix: str = "bluefog.gossip",
                        export: bool = True) -> dict:
    """Fold a drained (host-side, worker-stacked) subsample payload into
    the metric row per worker, then into the registry via
    :func:`drain_device_buffer`. ``payload`` leaves are numpy-convertible
    ``[size, ...]`` arrays as produced by the sampled program."""
    import numpy as np

    def stacked(ts):
        return [np.asarray(t, np.float64) for t in ts]

    xs, ys_, gs = stacked(payload["x"]), stacked(payload["y"]), stacked(
        payload["g"]
    )
    size = xs[0].shape[0] if xs else 1
    buf = np.zeros((size, N_SLOTS), np.float64)
    buf[:, SLOT_COUNT] = 1.0
    for x, y in zip(xs, ys_):
        buf[:, SLOT_DISAGREEMENT] += ((y - x) ** 2).reshape(size, -1).sum(1)
        buf[:, SLOT_PARAM_NORM] += (x ** 2).reshape(size, -1).sum(1)
    for g in gs:
        buf[:, SLOT_GRAD_NORM] += (g ** 2).reshape(size, -1).sum(1)
    if wire in _QUANT_WIRES:
        import ml_dtypes

        for pi, (sub, scale) in enumerate(payload["pack"]):
            sub = np.asarray(sub, np.float32)
            scale = float(np.asarray(scale).reshape(size, -1)[0, 0])
            for w in range(size):
                v = sub[w].reshape(-1)
                if wire == "bf16":
                    err = ((v - v.astype(ml_dtypes.bfloat16)
                            .astype(np.float32)) ** 2).sum()
                elif wire == "int8":
                    err = ((v - _np_chunk_quantize(v)) ** 2).sum()
                elif wire == "int4":
                    err = ((v - _np_chunk_quantize4(v)) ** 2).sum()
                else:  # int8_ef / int4_ef: residual vs the hat-self copy
                    hat = np.asarray(
                        payload["ef"][pi], np.float32
                    )[w].reshape(-1)
                    err = ((v - hat) ** 2).sum()
                buf[w, SLOT_QUANT_ERR] += err * scale
        if wire in _EF_WIRES:
            buf[:, SLOT_EF_RESIDUAL] = buf[:, SLOT_QUANT_ERR]
    return drain_device_buffer(
        buf, prefix=prefix, export=export, wire=wire
    )


def drain_device_buffer(buf, prefix: str = "bluefog.gossip",
                        export: bool = True, wire=None) -> dict:
    """Fold a drained ``[size, N_SLOTS]`` host array into the registry.

    Per metric: the per-worker RMS over the interval
    (``sqrt(sum_sq / count)``), published as ``<prefix>.<name>`` (mean
    over workers) and ``<prefix>.<name>.max`` (worst worker — the one a
    fleet operator pages on). The wire-specific slots are published
    ONLY when ``wire`` measures them — a 0.0 gauge that means "not
    measured" is indistinguishable from "no quantization error" and
    would overwrite real values. Returns the computed dict;
    ``export=True`` also triggers the env-configured exporters (each
    drain appends one JSONL time-series point)."""
    import numpy as np

    buf = np.asarray(buf, np.float64)
    counts = buf[:, SLOT_COUNT]
    out = {"steps": float(counts.max(initial=0.0))}
    denom = np.maximum(counts, 1.0)
    for slot, name in sorted(_SLOT_NAMES.items()):
        if slot == SLOT_QUANT_ERR and wire not in _QUANT_WIRES:
            continue
        if slot == SLOT_EF_RESIDUAL and wire not in _EF_WIRES:
            continue
        rms = np.sqrt(buf[:, slot] / denom)
        mean_v, max_v = float(rms.mean()), float(rms.max())
        gauge(f"{prefix}.{name}").set(mean_v)
        gauge(f"{prefix}.{name}.max").set(max_v)
        with _lock:
            # the PER-WORKER vector behind the mean/.max gauges: the
            # fleet health plane seeds its push-sum lane from this
            _last_worker_rows[f"{prefix}.{name}"] = rms.copy()
        out[name] = mean_v
        out[f"{name}.max"] = max_v
    if export:
        auto_export()
    return out


# Per-worker RMS vectors of the most recent device drain, keyed by the
# published gauge name. The registry only keeps mean/.max scalars; the
# health plane's per-rank summary vector needs the worker axis back.
_last_worker_rows: Dict[str, object] = {}


def last_worker_rows() -> Dict[str, object]:
    """``{series: per-worker numpy vector}`` from the most recent device
    drain (empty before the first drain / when the device tier is off).
    Read-only view for :mod:`bluefog_tpu.health`."""
    with _lock:
        return dict(_last_worker_rows)


# -- deferred-drain flush hooks ----------------------------------------------

# The optimizers defer each interval's registry fold until the async
# device->host copy is surely done (see _GossipOptimizer._maybe_drain
# _metrics); export paths call flush() so a snapshot written to disk
# never misses the tail of the run. Weakrefs: a registered optimizer
# must stay collectable.
_flush_hooks: list = []


def register_flush_hook(obj) -> None:
    """Register an object exposing ``_flush_metrics()`` to be folded at
    every :func:`flush` (held by weakref)."""
    import weakref

    _flush_hooks.append(weakref.ref(obj))


def flush() -> None:
    """Fold every registered holder's pending device metrics into the
    registry (dead refs are dropped). Called by the facade exporters and
    ``bf.shutdown()``."""
    alive = []
    for ref in _flush_hooks:
        obj = ref()
        if obj is not None:
            obj._flush_metrics()
            alive.append(ref)
    _flush_hooks[:] = alive


def wire_bytes_per_step(n_elems_by_itemsize, n_rounds: int,
                        wire: Optional[str] = None) -> int:
    """Per-worker wire bytes one gossip step puts on the interconnect —
    delegates to the canonical scale-sidecar-inclusive accounting in
    :func:`bluefog_tpu.scaling.wire_bytes_per_step` (kept here as a
    re-export: the optimizer/window counters and ``CommPlan.wire_bytes``
    call through this name)."""
    from bluefog_tpu import scaling

    return scaling.wire_bytes_per_step(n_elems_by_itemsize, n_rounds, wire)


# Compressed-allgather dispatch count, for the 1-in-metrics_interval
# quant-error sampling below (the eager gather has no optimizer comm
# clock to ride).
_allgather_calls = 0


def record_allgather_wire(x, wire: str, wire_bytes: int) -> None:
    """Quant-error + wire-byte telemetry for one compressed
    ``neighbor_allgather`` dispatch.

    Wire bytes are counted on every dispatch (a dict update). The
    quant-error replay follows the gossip tier's sampling discipline —
    1-in-:func:`metrics_interval` dispatches — and transfers only a
    512-aligned PREFIX of the input (sliced on device before the host
    copy, :func:`sample_elems_cap` elements per worker), replayed with
    the same quantizer replicas the drain-time fold uses: the
    reconstruction is the restriction of what the wire ships. Publishes
    ``bluefog.allgather.quant_err[.max]`` (per-worker RMS over the
    covered prefix)."""
    import ml_dtypes
    import numpy as np

    global _allgather_calls
    counter("bluefog.allgather.wire_bytes").inc(wire_bytes)
    with _lock:  # check-and-increment atomically, like the registry
        sampled = _allgather_calls % metrics_interval() == 0
        _allgather_calls += 1
    if not sampled:
        return
    size = int(x.shape[0])
    n = 1
    for d in x.shape[1:]:
        n *= int(d)
    cap = sample_elems_cap()
    keep = min(n, max(_ROW, cap - cap % _ROW))
    # slice BEFORE the host copy: only O(cap) elements per worker cross
    # the device boundary, however large the gather payload
    sub = np.asarray(
        x.reshape(size, -1)[:, :keep], np.float32
    )
    errs = np.zeros(size)
    for w in range(size):
        v = sub[w]
        if wire == "bf16":
            hat = v.astype(ml_dtypes.bfloat16).astype(np.float32)
        elif wire == "int8":
            hat = _np_chunk_quantize(v)
        else:  # int4
            hat = _np_chunk_quantize4(v)
        errs[w] = np.sqrt(((v - hat) ** 2).sum() / max(keep, 1))
    gauge("bluefog.allgather.quant_err").set(float(errs.mean()))
    gauge("bluefog.allgather.quant_err.max").set(float(errs.max()))


# -- exporters ----------------------------------------------------------------


def export_jsonl(path: Optional[str] = None) -> Optional[str]:
    """Append one snapshot line to ``path`` (default
    ``BLUEFOG_METRICS_FILE``). Each line is a standalone JSON object
    ``{"ts": <unix seconds>, "metrics": {...}}`` — the format
    ``tools/metrics_report.py`` summarizes. Returns the path written, or
    None when no path is configured."""
    path = path or os.environ.get("BLUEFOG_METRICS_FILE")
    if not path:
        return None
    line = json.dumps({"ts": time.time(), "metrics": snapshot()})
    with open(path, "a") as f:
        f.write(line + "\n")
    return path


def _prom_name(name: str) -> str:
    out = "".join(c if c.isalnum() or c == "_" else "_" for c in name)
    return out if not out[:1].isdigit() else "_" + out


def _prom_val(v: float) -> str:
    """Render one sample value in Prometheus exposition format. Python's
    ``%g`` spells non-finite floats ``nan``/``inf``, which strict
    exposition parsers reject — the format's own casings are ``NaN`` /
    ``+Inf`` / ``-Inf`` (a NaN gauge, e.g. a step EWMA before warmup,
    must degrade to an explicitly-unparseable-as-number token, not an
    invalid line)."""
    if v != v:
        return "NaN"
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    return f"{v:g}"


def prom_lines() -> list:
    """The registry rendered as Prometheus exposition lines, in
    DETERMINISTIC order (series sorted by raw name; fixed sub-line
    order per series) with the conventional ``# HELP`` / ``# TYPE``
    preamble per family — successive scrapes/textfiles of an unchanged
    registry are byte-identical, so they diff cleanly. Counter names
    get ``_total``; histograms export as a summary:
    ``_count`` / ``_sum`` / ``_min`` / ``_max`` plus the log-bucket
    ``{quantile="..."}`` series. Shared by the textfile exporter and
    the live ``/metrics`` endpoint (:mod:`bluefog_tpu.health`)."""
    lines = []
    for name, desc in sorted(snapshot().items()):
        pname = _prom_name(name)
        if desc["type"] == "counter":
            lines.append(f"# HELP {pname}_total bluefog_tpu series "
                         f"{name}")
            lines.append(f"# TYPE {pname}_total counter")
            lines.append(f"{pname}_total {_prom_val(desc['value'])}")
        elif desc["type"] == "gauge":
            lines.append(f"# HELP {pname} bluefog_tpu series {name}")
            lines.append(f"# TYPE {pname} gauge")
            lines.append(f"{pname} {_prom_val(desc['value'])}")
        else:
            lines.append(f"# HELP {pname} bluefog_tpu series {name}")
            lines.append(f"# TYPE {pname} summary")
            for q in Histogram.QUANTILES:
                v = desc.get(f"p{int(q * 100)}")
                if v is not None:
                    lines.append(
                        f'{pname}{{quantile="{q:g}"}} {_prom_val(v)}'
                    )
            lines.append(f"{pname}_count {_prom_val(desc['count'])}")
            lines.append(f"{pname}_sum {_prom_val(desc['sum'])}")
            for k in ("min", "max"):
                if desc[k] is not None:
                    lines.append(f"{pname}_{k} {_prom_val(desc[k])}")
    return lines


def export_prom(path: Optional[str] = None) -> Optional[str]:
    """Write :func:`prom_lines` in Prometheus textfile-collector format
    to ``path`` (default ``BLUEFOG_METRICS_PROM``), atomically (write to
    ``<path>.tmp`` then rename — node_exporter may scrape mid-write)."""
    path = path or os.environ.get("BLUEFOG_METRICS_PROM")
    if not path:
        return None
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write("\n".join(prom_lines()) + "\n")
    os.replace(tmp, path)
    return path


def export_timeline_counters() -> int:
    """Emit every scalar series as a Chrome-trace counter event
    (``ph:"C"``) on the active timeline; counters render as stacked area
    tracks under the op spans in chrome://tracing / Perfetto. No-op (0)
    when no timeline is active; returns the number of events emitted."""
    from bluefog_tpu import timeline as tl

    if not tl.timeline_enabled():
        return 0
    n = 0
    for name, desc in snapshot().items():
        value = desc.get("value", desc.get("last"))
        if value is None:
            continue
        tl.timeline_record_counter(name, float(value))
        n += 1
    return n


def auto_export() -> None:
    """Run every env-configured exporter: JSONL append, Prometheus
    textfile rewrite, timeline counter events. Called at each device
    drain and from ``bf.shutdown()``."""
    export_jsonl()
    export_prom()
    export_timeline_counters()


def metrics_export(jsonl_path: Optional[str] = None,
                   prom_path: Optional[str] = None) -> dict:
    """Facade export (``bf.metrics_export()``): flush any deferred
    device-tier drains, write the JSONL and/or Prometheus files
    (explicit paths win over the env defaults), emit timeline counters
    if a timeline is active, and return the snapshot."""
    flush()
    export_jsonl(jsonl_path)
    export_prom(prom_path)
    export_timeline_counters()
    return snapshot()
