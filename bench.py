#!/usr/bin/env python
# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Benchmark driver: the full performance evidence set in one run.

Default (no BENCH_MODE): emits EVERY metric family — scaling accounting,
gossip overhead (with its regression assertion on TPU), flash-vs-dense
attention timings, transformer throughput — each in an isolated
subprocess, then the ResNet50 headline line LAST (so a tail-reading
driver still lands on the headline). Every line is standalone JSON.

Individual families via ``BENCH_MODE``:

- ``headline``: ResNet50 decentralized train step, mirroring the
  reference benchmark driver (``examples/pytorch_benchmark.py``: bs=64
  per worker, neighbor_allreduce optimizer). Baseline: BlueFog-NCCL
  ResNet50 at 4310.6 img/s total on 16 V100s (docs/performance.rst:16-24)
  = 269.4 img/s per accelerator; ``vs_baseline`` is imgs/sec-per-chip
  against that. ``mfu`` uses the 2*MAC FLOP convention. Best-of-N timed
  windows with the min/median spread disclosed.
- ``transformer``: TransformerLM (bf16, dim 1024 / 16 heads / 12 layers,
  T=4096) train-step tokens/sec + MFU over the Pallas flash kernels.
- ``flash``: flash-vs-dense attention fwd / fwd+bwd timings at
  T in {1k, 4k, 8k} (the measured basis for flash-by-default).
- ``gossip``: gossip-overhead bound with communication REALLY in the
  program; asserts the per-worker combine stays < 10 % of a bs=64 step
  on TPU (regression check).
- ``scaling``: static HLO comm accounting + weak-scaling harness
  (reference docs/performance.rst:26-53, README.rst:51-60).
- ``plan``: comm-plan compiler evidence — naive (offset-grouped) vs
  optimized (minimum-round edge coloring) round counts, verified from
  compiled HLO, plus measured gossip-step times for irregular
  topologies (star, mesh2d, sparse random digraph). See
  ``docs/plan_compiler.md``.
- ``overlap``: exposed-communication comparison for the fused train
  step (two-program baseline vs fused vs fused+buckets vs delayed),
  per-bucket schedule timeline, and the static HLO overlap scan
  (``tools/hlo_overlap_scan.py``). See docs/performance.md
  "Overlapping communication with compute".
- ``metrics``: telemetry-overhead evidence — the fused gossip step
  timed with the device metric tier off vs on (interval 10), the
  bitwise on/off state pin, and a drained-registry sample; asserts the
  <2 % overhead acceptance bound. See ``docs/metrics.md``.
- ``flight``: flight-recorder evidence — per-event ring-write cost x
  exact events/step over the differenced step time (<=1 % bound,
  asserted), bitwise on/off trajectory pin, and a fault-plan kill whose
  dumps are fused by ``tools/trace_merge.py`` (merged-trace round count
  vs the compiled CommPlan, hang postmortem naming the killed rank and
  the stalled edges/rounds). See ``docs/flight.md``.
- ``attribution``: step-time attribution doctor evidence
  (``bf.doctor``, docs/doctor.md) — measured overhead at the default
  sampling interval (<=1 % bound, asserted, A/A control disclosed),
  the structural pin that unsampled steps dispatch the doctor-off
  program under the same cache key, the bitwise on/off trajectory pin,
  a sample's compute/comm/host decomposition, and a fault-plan
  degraded-link scenario where the emitted advisory must name the
  injected edge. Committed as ATTRIBUTION_EVIDENCE.json.
- ``health``: fleet-health-plane evidence (``bf.health``,
  docs/health.md) — measured consensus decay vs the spectral (SLEM)
  prediction on ring and Exp2 through the real eager combine (with the
  Exp2-faster ordering asserted), the push-sum in-band aggregation
  lane vs its numpy oracle under a dead rank, the <=1 % overhead bound
  at the default sampling interval (A/A control, structural +
  bitwise pins), and a deterministic lossy-link chaos scenario whose
  ``mixing_degraded`` advisory must name the injected edge. Committed
  as HEALTH_EVIDENCE.json.
- ``slo``: fleet-SLO-engine evidence (``bf.slo``, docs/slo.md) — a
  hard fault paging within the documented ``page_sample_bound`` with
  a 600-sample clean A/A raising nothing, a slow error ramp caught by
  the slow burn window while the fast window AND the doctor's
  EWMA+MAD streak rule stay correctly silent, the 512-element
  known-signal canary bit-clean through the real quantized wire on a
  healthy fabric and naming exactly the chaos-degraded edge on a
  lossy one, the <=1 % overhead bound at the default sampling
  interval (A/A control, structural + bitwise pins), and the burn /
  error-budget arithmetic pinned exactly to a numpy oracle through an
  N=1024 fleetsim churn storm. Committed as SLO_EVIDENCE.json.
- ``staleness``: staleness-observatory evidence (``bf.staleness``,
  docs/staleness.md) — the lineage lane's synchronous-path age ≡ 0
  self-check with the sidecar priced by
  ``scaling.wire_payload_bytes``, the ``delayed=True`` steady-state
  age ≡ 1 invariant with the topology-swap age-0 transition, the
  age-discounted mixing correction measurably shrinking the health
  plane's predicted-vs-measured residual on a delayed run, the <=1 %
  overhead bound at the default sampling interval (A/A control,
  structural + bitwise pins), and a deterministic per-edge stall chaos
  scenario whose measured age spike and ``staleness_breach`` advisory
  must name the injected edge. Committed as STALENESS_EVIDENCE.json.
- ``autotune``: closed-loop topology-controller evidence
  (``bf.autotune``, docs/autotune.md) — an injected degraded link is
  detected through the real doctor advisory stream, routed around by a
  live migration through the elastic repair path (decision record
  naming the edge, measured wire cost + mixing efficiency recovering
  past gated thresholds), with the ≤1 % overhead bound at the default
  interval (A/A control, structural + bitwise pins), a dry-run pass
  recording full decision history with zero migrations, and the audit
  trail round-tripped through every surface (metrics, flight side
  table, JSONL, ``tools/autotune_report.py``). Committed as
  AUTOTUNE_EVIDENCE.json.
- ``async``: asynchronous-gossip evidence (``bf.make_async_train_step``,
  docs/async.md) — the straggler-immunity chaos scenario (one rank
  compute-dilated 10x via the ``slow`` fault: synchronous fleet
  throughput collapses to ~1/10 while the async lane's measured
  participation stays within ~1/N of nominal), convergence within
  tolerance of the synchronous baseline on the same problem, exact
  push-sum mass conservation under random per-rank cadences for the
  fp32/int8_ef/int4_ef wire tiers, the bounded-staleness gate engaging
  (age histogram + ``async_staleness`` advisory naming the slow rank),
  and the async-off dispatch pinned bitwise to the current synchronous
  optimizer path. Committed as ASYNC_EVIDENCE.json.
- ``quant``: quantized-wire evidence — every wire tier
  (fp32/bf16/int8/int8_ef/int4/int4_ef) on one pure-consensus problem,
  per-tier wire bytes with the block-scale sidecar priced in,
  consensus-distance curves, quant-error telemetry, and the push-sum
  mass-conservation check under ``BLUEFOG_WINDOW_WIRE=int4``; asserts
  the >=2x wire-reduction-vs-int8 claim at int8-or-better consensus
  quality. Committed as QUANT_EVIDENCE.json.
- ``fleetscale``: fleet-scale control-plane evidence (``bf.fleetsim``,
  docs/fleetsim.md) — the thousand-rank fleet simulator driving the
  real membership/repair/plan-cache machinery with no device dispatch:
  per-membership-event repair cost sublinear in N (growth exponent
  asserted < 1 over N in {128..1024}, dense baseline timed at small N
  and power-law-extrapolated with the model disclosed), a 10 %
  simultaneous rank-loss storm at N=1024 repaired with ZERO stale
  dispatches under full edge auditing, bounded controller decision
  latency at N=1024 through the sparse spectral engine, and the
  sparse-vs-dense SLEM agreement spot check at the routing boundary.
  Committed as FLEETSCALE_EVIDENCE.json.
- ``federate``: hierarchical multi-pod federation evidence
  (``bf.federation``, docs/federation.md) — the two-level ICI/DCN
  gossip fabric: the spectrally-chosen DCN period matching the
  measured composed consensus rate within a disclosed tolerance, the
  >= 8x cross-pod (DCN) wire-byte cut vs the strongest flat opponent
  at the matched measured rate, whole-pod loss repaired as ONE event
  with zero stale dispatches (gateway re-election included), and a
  live 2-pod dispatch whose per-leg
  ``bluefog.federation.{ici,dcn}_wire_bytes`` counters reconcile.
  Committed as FEDERATE_EVIDENCE.json.

Every run additionally emits an **ambient-drift anchor** line
(``{"metric": "ambient_anchor"}``: the fixed dense bf16 matmul TFLOP/s
of ``tools/perf_probe.py``, 8192^3 on TPU) and the ResNet50/transformer
headlines carry ``vs_anchor`` (throughput per ambient TFLOP/s), so a
cross-round headline delta is classifiable as ambient host drift vs a
real change — ``tools/bench_diff.py`` consumes the anchor to make that
call mechanically.

Timing windows that come out degenerate (a clamped ``diff <= 0`` in
``timed_differenced`` — an ambient stall ate the differenced half) are
retried and excluded; a cell whose every window stayed degenerate is
published with ``"degenerate": true`` instead of a silent 0.0, and is
excluded from the flash regression assertion.
"""

import json
import os
import sys
import time

# Peak dense bf16 FLOP/s by TPU generation (public spec sheets); used only
# to report MFU. Unknown kinds fall back to 0 => mfu omitted.
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# 2*MAC FLOPs: ResNet50 forward at 224x224 is ~4.1 GMACs = 8.2 GFLOP/img;
# backward ~= 2x forward.
_FLOPS_PER_IMG_FWD_BWD = 3 * 8.2e9


def _provenance() -> dict:
    """Round-over-round bench deltas are only attributable when every
    evidence artifact records WHAT produced it: jax/jaxlib versions,
    platform, CPU model, timing method, and the git SHA. Emitted as a
    standalone ``{"metric": "provenance"}`` line by every BENCH_MODE, so
    committed ``BENCH_*``/``*_EVIDENCE`` files carry it."""
    import platform as _platform
    import subprocess

    import jax
    import jaxlib

    cpu_model = ""
    try:
        fields = {}
        with open("/proc/cpuinfo") as f:
            for line in f:
                if ":" in line:
                    k, v = line.split(":", 1)
                    fields.setdefault(k.strip(), v.strip())
                if line.strip() == "":
                    break  # first processor block is enough
        cpu_model = fields.get("model name", "")
        if cpu_model in ("", "unknown"):
            # virtualized hosts often blank the model name; the numeric
            # family/model ids still identify the microarchitecture
            cpu_model = " ".join(
                filter(None, (
                    fields.get("vendor_id", ""),
                    f"family={fields.get('cpu family', '?')}",
                    f"model={fields.get('model', '?')}",
                ))
            )
    except OSError:
        cpu_model = _platform.processor() or _platform.machine()
    try:
        sha = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        # TimeoutExpired included: a hung git (stale lock, slow NFS)
        # must degrade to sha="unknown", not kill the whole bench
        sha = "unknown"
    return {
        "metric": "provenance",
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "python": sys.version.split()[0],
        # requested platform only — resolving the actual backend here
        # would initialize it before the mode's own device setup
        "jax_platforms_env": os.environ.get("JAX_PLATFORMS", ""),
        "platform_node": _platform.platform(),
        "cpu_model": cpu_model,
        "timing_method": (
            "time.perf_counter, timed_differenced windows "
            "(bluefog_tpu/timing.py); best-of-N with spread disclosed"
        ),
        "git_sha": sha,
        "bench_mode": os.environ.get("BENCH_MODE", "all"),
        # host-memory context for every evidence artifact: the
        # process's peak RSS at emission time (Linux ru_maxrss is KiB).
        # Harness metadata like anchor_tflops — tools/bench_diff.py
        # must never treat its movement as a comparability break.
        "peak_rss_bytes": _peak_rss_bytes(),
        # per-link-class cost-model constants in force when this
        # artifact was produced (ici = intra-pod torus, dcn = the
        # cross-pod gateway leg): a plan-cost delta between rounds is
        # only attributable when the calibration that priced it is on
        # the record
        "calibration_link_classes": _calibration_classes(),
    }


def _calibration_classes() -> dict:
    try:
        from bluefog_tpu.collective import compiler as compiler_mod

        return {
            cls: compiler_mod.calibration(cls)
            for cls in compiler_mod.LINK_CLASSES
        }
    except Exception:  # provenance must never fail the bench
        return {}


def _peak_rss_bytes() -> int:
    """Peak resident set size of this process in bytes — the memory
    observatory's reader (one KiB→bytes conversion to keep correct;
    bluefog_tpu.memory is stdlib-only at import, and bench already
    imports the package for timing helpers)."""
    from bluefog_tpu.memory import host_peak_rss_bytes

    return host_peak_rss_bytes()


def _peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "")
    for key, val in _PEAK_BF16.items():
        if kind.startswith(key):
            return val
    return 0.0


_ANCHOR_LINE = None


def _ambient_anchor() -> dict:
    """The ambient-drift anchor: a fixed dense bf16 matmul
    (``tools/perf_probe.py`` roofline probe — 8192^3 on TPU, a small
    CPU-sized square otherwise) timed in THIS process right where the
    evidence was measured. Same code, same shape, every round: when the
    anchor moves between rounds the host moved, and a headline delta of
    the same magnitude is ambient, not a regression (VERDICT Weak #1's
    unattributable 2798.8 -> 2510.5 drop is the wound this closes).
    Memoized so the headline's ``vs_anchor`` and the emitted anchor
    line are the same measurement."""
    global _ANCHOR_LINE
    if _ANCHOR_LINE is None:
        import jax

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from tools.perf_probe import matmul_tflops

        on_tpu = jax.devices()[0].platform not in ("cpu",)
        n = int(
            os.environ.get("BENCH_ANCHOR_N", "8192" if on_tpu else "512")
        )
        _ANCHOR_LINE = {
            "metric": "ambient_anchor",
            "n": n,
            "dtype": "bfloat16",
            "tflops": round(
                matmul_tflops(n, iters=10 if on_tpu else 3, warmup=2), 4
            ),
            "device": jax.devices()[0].device_kind,
        }
    return _ANCHOR_LINE


def bench_row_problems(row: dict) -> list:
    """Physical-plausibility validator for one bench row: a published
    measurement must not claim a non-positive time, and a fwd+bwd cell
    can never undercut its own fwd. Returns the violations (empty =
    plausible). Rows already flagged ``degenerate`` are exempt — their
    values are disclosed as artifacts, not measurements. Wired into
    ``run_flash`` (reject + remeasure) and unit-tested so impossible
    rows cannot ship again (the r05 artifact committed a
    ``dense_fwdbwd_ms`` below ``dense_fwd_ms``)."""
    if row.get("degenerate"):
        return []
    problems = []
    times = {
        k: v for k, v in row.items()
        if k.endswith("_ms") and isinstance(v, (int, float))
        and not isinstance(v, bool)
    }
    for k, v in sorted(times.items()):
        if v <= 0:
            problems.append(f"{k}={v} is not a positive time")
    for k, v in sorted(times.items()):
        if "fwdbwd" not in k:
            continue
        fwd_key = k.replace("fwdbwd", "fwd")
        f = times.get(fwd_key)
        if f is not None and v < f:
            problems.append(
                f"{k}={v} < {fwd_key}={f}: fwd+bwd cannot be faster "
                "than its own forward"
            )
    return problems


# Readback sync point (a plain np.asarray readback would cache on the
# array object and break the readback-latency correction) + the shared
# differenced-window timing harness.
from bluefog_tpu.timing import (  # noqa: E402
    settle as _settle,
    timed_differenced as _timed_differenced,
)


def run_headline() -> int:
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bluefog_tpu.models import ResNet50
    import bluefog_tpu.topology as topo
    from bluefog_tpu.collective import inner, plan as planlib

    devices = jax.devices()
    on_tpu = devices[0].platform not in ("cpu",)
    n = len(devices)

    # Per-worker batch: the BASELINE config is 64; CPU fallback stays tiny
    # so the driver always gets a line.
    batch = int(os.environ.get("BENCH_BATCH", "64" if on_tpu else "4"))
    image = int(os.environ.get("BENCH_IMAGE", "224" if on_tpu else "32"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "20" if on_tpu else "3")))
    # >=1: the timing loop settles on the warmup's last loss
    warmup = max(
        1, int(os.environ.get("BENCH_WARMUP", "5" if on_tpu else "1"))
    )

    mesh = Mesh(np.array(devices), ("workers",))
    plan = planlib.plan_from_topology(
        topo.ExponentialTwoGraph(n) if n > 1 else topo.FullyConnectedGraph(1),
        weighted=True,
    )

    model = ResNet50(num_classes=1000)
    rng = jax.random.PRNGKey(0)
    sample = jnp.ones((batch, image, image, 3), jnp.bfloat16)
    variables = model.init(rng, sample, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), tree
        )

    spec = P("workers")
    sharding = NamedSharding(mesh, spec)
    state = jax.device_put(
        (stack(params), stack(batch_stats), stack(opt_state)), sharding
    )

    def train_step(state, images, labels):
        params, batch_stats, opt_state = jax.tree_util.tree_map(
            lambda t: t[0], state
        )
        x, y = images[0], labels[0]

        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats},
                x,
                train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            return loss, mutated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # Adapt-then-combine gossip of the updated parameters (the
        # neighbor_allreduce optimizer's hot path).
        params = jax.tree_util.tree_map(
            lambda t: inner.neighbor_allreduce(t, plan, "workers"), params
        )
        expand = lambda tr: jax.tree_util.tree_map(
            lambda t: jnp.expand_dims(t, 0), tr
        )
        return expand((params, new_stats, opt_state)), loss.reshape(1)

    fn = jax.jit(
        jax.shard_map(
            train_step,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec, spec),
        ),
        donate_argnums=(0,),
    )

    rng_np = np.random.RandomState(0)
    images = jax.device_put(
        rng_np.randn(n, batch, image, image, 3).astype(np.float32), sharding
    ).astype(jnp.bfloat16)
    labels = jax.device_put(
        rng_np.randint(0, 1000, size=(n, batch)).astype(np.int32), sharding
    )

    for _ in range(warmup):
        state, loss = fn(state, images, labels)
    _settle(loss)
    _settle(loss)  # warm any readback-path compile cache

    # Best-of-N timed windows (default 8 on TPU; each is cheap once
    # compiled): a single window can absorb unrelated host stalls; the
    # best window is the reproducible hardware number (each window is
    # still steps>=20 long).
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "8" if on_tpu else "1")))
    carry = [state]

    def _step():
        carry[0], loss = fn(carry[0], images, labels)
        return loss

    # per-call, sorted; degenerate (stall-clamped) windows are excluded,
    # so the disclosed count is the CLEAN sample size, not the request
    dts, degen = _timed_differenced(
        _step, steps, windows, with_degenerate=True
    )
    per_chip = batch / dts[0]
    baseline_per_accel = 4310.6 / 16.0  # docs/performance.rst:16-24
    anchor = _ambient_anchor()
    result = {
        "metric": "resnet50_bs%d_imgs_per_sec_per_chip" % batch,
        "value": round(per_chip, 2),
        "unit": "imgs/sec/chip",
        "vs_baseline": round(per_chip / baseline_per_accel, 4),
        # throughput per ambient TFLOP/s: stable vs_anchor + moving
        # value across rounds = the host moved, not the code
        "vs_anchor": round(per_chip / max(anchor["tflops"], 1e-9), 3),
        "anchor_tflops": anchor["tflops"],
        # window spread: best-of-N filters host stalls; the
        # median and worst window are disclosed so the headline is not
        # mistaken for a guaranteed-reproducible number
        "windows": len(dts),
        "median": round(batch / dts[len(dts) // 2], 2),
        "min": round(batch / dts[-1], 2),
    }
    if degen:
        result["degenerate"] = True
    peak = _peak_flops(devices[0])
    if peak:
        # FLOPs/img scale ~quadratically with resolution (BENCH_IMAGE knob).
        flops_img = _FLOPS_PER_IMG_FWD_BWD * (image / 224.0) ** 2
        result["mfu"] = round(per_chip * flops_img / peak, 4)
        result["device"] = devices[0].device_kind
    print(json.dumps(result))
    return 0


def run_scaling() -> int:
    """Scaling-efficiency evidence: HLO comm accounting + weak scaling.

    Defaults to an 8-device virtual CPU mesh, selected through
    ``jax.config`` before backend init. Set BENCH_SCALING_PLATFORM=native
    to run on the real devices of a multi-chip slice.
    """
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(int(os.environ.get("BENCH_SCALING_DEVICES", "8")))
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bluefog_tpu.topology as topo
    from bluefog_tpu import scaling
    from bluefog_tpu.collective import plan as planlib

    n_dev = len(jax.devices())
    # Model size in ELEMENTS (ResNet50 has ~25.56M parameters); the f32 wire
    # payload is 4 bytes each.
    payload_elems = int(os.environ.get("BENCH_PAYLOAD_ELEMS", str(25_557_032)))
    payload_bytes = payload_elems * 4
    lines = []

    # Static comm accounting across mesh sizes (bounded by device count).
    ns = [n for n in (2, 4, 8, 16) if n <= n_dev]
    for n in ns:
        sched = planlib.schedule_from_dynamic(
            n,
            lambda r: topo.GetDynamicOnePeerSendRecvRanks(
                topo.ExponentialGraph(n), r
            ),
        )
        stats = scaling.gossip_comm_stats(
            sched.plans[0], payload_elems, jnp.float32
        )
        cp = stats.get("collective-permute", {"count": 0, "bytes": 0})
        ring = scaling.ring_allreduce_cost(n, payload_bytes)
        lines.append(
            {
                "metric": "one_peer_gossip_comm",
                "n_workers": n,
                "collective_permutes": cp["count"],
                "wire_bytes_per_worker": cp["bytes"],
                "ring_allreduce_wire_bytes": round(ring["wire_bytes"]),
                "ring_allreduce_hops": ring["latency_hops"],
            }
        )

    # Weak scaling: constant per-worker compute + one-peer gossip.
    def make_step(mesh):
        n = mesh.devices.size
        plan = (
            planlib.schedule_from_dynamic(
                n,
                lambda r: topo.GetDynamicOnePeerSendRecvRanks(
                    topo.ExponentialGraph(n), r
                ),
            ).plans[0]
            if n > 1
            else planlib.plan_from_topology(topo.FullyConnectedGraph(1))
        )
        spec = P("workers")

        def body(x, w):
            y = jnp.tanh(x @ w)
            return scaling.inner.neighbor_allreduce(y, plan, "workers")

        fn = jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=(spec, P()), out_specs=spec
            )
        )
        x = jax.device_put(
            np.ones((n, 64, 1024), np.float32), NamedSharding(mesh, spec)
        )
        w = jnp.ones((1024, 1024), jnp.float32)
        return fn, (x, w)

    ns_weak = [n for n in (1, 2, 4, 8) if n <= n_dev]
    virtual = os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native"
    for row in scaling.weak_scaling_times(make_step, ns_weak):
        lines.append(
            {
                "metric": "weak_scaling_gossip_step",
                "n_workers": row["n"],
                "ms_per_step": round(row["ms_per_step"], 3),
                "efficiency": round(row["efficiency"], 4),
                # virtual workers share one host's cores: these rows
                # validate the HARNESS (the step runs, efficiency is
                # computable), they are not a hardware scaling claim
                "harness_validation": virtual,
            }
        )

    for line in lines:
        print(json.dumps(line))
    return 0


def run_plan() -> int:
    """Plan-compiler evidence: for each topology, the naive
    (offset-grouped) vs optimized (cost-modeled minimum-round) lowering —
    round counts cross-checked against the compiled HLO's
    collective-permute count — plus measured gossip-step time for both
    plans. Circulant topologies (exp2, ring) must show identical rounds
    (the fast path is kept); the sparse random digraph is where the
    edge-coloring pass wins (König bound = max degree, vs O(N) offsets).

    Then the bandwidth-family evidence (ROADMAP item 2): a one-shot
    measured calibration of the alpha-beta constants
    (``{"metric": "plan_calibration"}``) followed by a payload-size
    sweep (``BENCH_PLAN_SWEEP_BYTES``, default 64 KiB -> 100 MiB) over
    the degree-3 random digraph, measuring the min-round coloring
    against chunked/pipelined and short-cut lowerings per payload —
    with an A/A re-measurement of the baseline as the noise floor —
    and reporting whether the calibrated ``auto`` chooser tracks the
    measured-fastest family (``{"metric": "plan_sweep"}`` lines;
    committed as PLAN_SWEEP_EVIDENCE.json). Degenerate timing windows
    are flagged per cell and excluded from the chooser comparison.
    ``BENCH_ASSERT=1`` additionally asserts the chooser tracks the
    measured winner (within the A/A floor) at both sweep extremes.

    Runs on a virtual CPU mesh by default (same contract as
    BENCH_MODE=scaling: backend init must be owned here); set
    BENCH_SCALING_PLATFORM=native for the real devices of a multi-chip
    slice.
    """
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_PLAN_DEVICES", "16"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import bluefog_tpu.topology as topo
    from bluefog_tpu import scaling
    from bluefog_tpu.collective import compiler, inner, plan as planlib

    n = min(
        len(jax.devices()), int(os.environ.get("BENCH_PLAN_WORKERS", "16"))
    )
    payload_elems = int(
        os.environ.get("BENCH_PLAN_PAYLOAD_ELEMS", str(1 << 16))
    )
    steps = max(1, int(os.environ.get("BENCH_STEPS", "5")))
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))

    topologies = {
        "exp2": topo.ExponentialTwoGraph(n),
        "ring": topo.RingGraph(n),
        "star": topo.StarGraph(n),
        "mesh2d": topo.MeshGrid2DGraph(n),
        "random_d3": topo.RandomRegularDigraph(n, min(3, n - 1), seed=1),
    }
    mesh = Mesh(np.array(jax.devices()[:n]), ("workers",))
    sharding = NamedSharding(mesh, P("workers"))
    x0 = jax.device_put(
        np.random.RandomState(0)
        .randn(n, payload_elems)
        .astype(np.float32),
        sharding,
    )

    def measure(plan, x=None, chunks=1, n_steps=None, n_windows=None):
        fn = jax.jit(
            jax.shard_map(
                lambda t: inner.neighbor_allreduce(
                    t, plan, "workers", chunks=chunks
                ),
                mesh=mesh, in_specs=P("workers"), out_specs=P("workers"),
            )
        )
        carry = [x0 if x is None else x]

        def _step():
            carry[0] = fn(carry[0])
            return carry[0][0, 0]  # scalar settle target

        dts, degen = _timed_differenced(
            _step, n_steps or steps, n_windows or windows,
            with_degenerate=True,
        )
        return dts[0], degen

    for name, g in topologies.items():
        optimized = planlib.plan_from_topology(g, weighted=True)
        naive = planlib.plan_from_topology(g, weighted=True, method="offset")
        stats = scaling.gossip_comm_stats(
            optimized, payload_elems, jnp.float32, include_plan=True
        )
        hlo_cp = stats.get("collective-permute", {"count": 0})["count"]
        summary = stats["plan"]
        t_opt, degen_opt = measure(optimized)
        if optimized.perms == naive.perms:
            # circulant fast path kept: the plans are byte-identical, so a
            # second measurement would only publish ambient noise as a
            # fake naive-vs-optimized delta
            t_naive, degen_naive = t_opt, degen_opt
        else:
            t_naive, degen_naive = measure(naive)
        line = {
            "metric": "plan_compiler",
            "topology": name,
            "n_workers": n,
            "payload_elems": payload_elems,
            "naive_rounds": len(naive.rounds),
            "optimized_rounds": len(optimized.rounds),
            "lower_bound": summary["lower_bound"],
            "decomposition": summary["decomposition"],
            "hlo_collective_permutes": hlo_cp,
            "predicted_cost_us": round(summary["predicted_cost_us"], 2),
            "naive_cost_us": round(summary["naive_cost_us"], 2),
            "naive_ms_per_step": round(t_naive * 1e3, 3),
            "optimized_ms_per_step": round(t_opt * 1e3, 3),
        }
        if degen_opt or degen_naive:
            line["degenerate"] = True
        assert len(optimized.rounds) <= len(naive.rounds), line
        assert hlo_cp == len(optimized.rounds), line
        print(json.dumps(line))

    # -- bandwidth family: measured calibration + payload-size sweep --------
    cal = compiler.calibrate(force=True)
    print(json.dumps({
        "metric": "plan_calibration",
        "alpha_us": round(cal["alpha_s"] * 1e6, 2),
        "beta_gbytes_per_s": round(cal["beta_bytes_per_s"] / 1e9, 4),
        "pipeline_eff": round(cal.get("pipeline_eff", 1.0), 4),
        "source": cal["source"],
        "probe_gain_2round_4chunk": round(
            cal.get("probe_gain_2round_4chunk", 0.0), 4
        ),
        "class_alpha_us": compiler.ROUND_ALPHA_S * 1e6,
        "class_beta_gbytes_per_s": compiler.ICI_LINK_BYTES_PER_S / 1e9,
    }))

    sweep_bytes = [
        int(v) for v in os.environ.get(
            "BENCH_PLAN_SWEEP_BYTES",
            "65536,1048576,16777216,104857600",
        ).split(",") if v.strip()
    ]
    sweep_steps = max(1, int(os.environ.get("BENCH_PLAN_SWEEP_STEPS", "3")))
    sweep_windows = max(
        1, int(os.environ.get("BENCH_PLAN_SWEEP_WINDOWS", "2"))
    )
    g = topologies["random_d3"]
    plan_color = planlib.plan_from_topology(g, weighted=True, method="coloring")
    plan_short = planlib.plan_from_topology(g, weighted=True, method="shortcut")
    rng = np.random.RandomState(1)
    sweep_results = []
    for payload_bytes in sweep_bytes:
        elems = max(512, payload_bytes // 4)
        x = jax.device_put(
            rng.randn(n, elems).astype(np.float32), sharding
        )
        auto_k = compiler.choose_chunks(
            plan_color.compile_info, payload_bytes, n_elems=elems,
        )
        # family grid: the latency-optimal point, the chunked/pipelined
        # point (the chooser's k, or a fixed k=8 so the family is still
        # measured when auto stays at 1), and the short-cut relay family
        chunk_k = auto_k if auto_k > 1 else 8
        cells = {}
        degen_cells = []
        for fam, plan, k in (
            ("coloring_k1", plan_color, 1),
            (f"chunked_k{chunk_k}", plan_color, chunk_k),
            (f"shortcut_k{chunk_k}", plan_short, chunk_k),
        ):
            t, degen = measure(
                plan, x=x, chunks=k, n_steps=sweep_steps,
                n_windows=sweep_windows,
            )
            cells[fam] = round(t * 1e3, 3)
            if degen:
                degen_cells.append(fam)
        # A/A floor: re-measure the baseline cell; the disclosed noise
        # any family-vs-family delta must clear to mean anything
        t_aa, degen_aa = measure(
            plan_color, x=x, chunks=1, n_steps=sweep_steps,
            n_windows=sweep_windows,
        )
        if degen_aa:
            degen_cells.append("aa_baseline")
        base = cells["coloring_k1"]
        aa_ms = round(t_aa * 1e3, 3)
        noise_pct = round(
            abs(aa_ms - base) / max(min(aa_ms, base), 1e-9) * 100.0, 2
        )
        auto_family = f"chunked_k{auto_k}" if auto_k > 1 else "coloring_k1"
        clean = {
            f: v for f, v in cells.items() if f not in degen_cells
        }
        measured_best = min(clean, key=clean.get) if clean else None
        # the verdict only means something when the auto family's own
        # cell survived the degenerate-window retries: a flagged cell is
        # EXCLUDED (tracks=None, "unknown"), never trusted either way
        auto_ms = clean.get(auto_family)
        tracks = (
            None
            if auto_ms is None or measured_best is None
            else auto_ms <= clean[measured_best] * (1.0 + noise_pct / 100.0)
        )
        line = {
            "metric": "plan_sweep",
            "topology": "random_d3",
            "n_workers": n,
            "payload_bytes": payload_bytes,
            "rounds": len(plan_color.rounds),
            "shortcut_rounds": len(plan_short.rounds),
            "cells_ms_per_step": cells,
            "aa_baseline_ms": aa_ms,
            "aa_noise_pct": noise_pct,
            "auto_choice": auto_family,
            "auto_chunks": auto_k,
            "predicted_auto_cost_us": round(
                scaling.pipelined_cost_s(
                    payload_bytes, auto_k,
                    plan_color.compile_info.congestion,
                ) * 1e6, 1,
            ),
            "measured_best": measured_best,
            "auto_tracks_best_within_noise": (
                None if tracks is None else bool(tracks)
            ),
        }
        if degen_cells:
            line["degenerate_cells"] = sorted(set(degen_cells))
        sweep_results.append(line)
        print(json.dumps(line))

    if os.environ.get("BENCH_ASSERT", "0") == "1" and len(sweep_results) >= 2:
        # acceptance: the calibrated chooser must track the measured
        # winner at both ends of the sweep (cells that stayed degenerate
        # after retries are excluded above rather than trusted: an end
        # whose verdict is None is unassertable, not a pass or a fail)
        for end in (sweep_results[0], sweep_results[-1]):
            assert end["auto_tracks_best_within_noise"] is not False, end
    return 0


def run_gossip_overhead() -> int:
    """Bound the gossip step's on-chip cost with communication REALLY in
    the program: 8 virtual workers share the one chip (vmapped replicas,
    bs/8 each), and the neighbor combine is the algebraically-identical
    einsum with the Exp2 weight matrix over the replica axis. The delta
    vs the combine-free step bounds the per-step gossip arithmetic +
    memory cost; the model-size HBM roundtrip gives the per-round wire
    floor a real ppermute pays on top (ICI transfer not measurable with
    one chip). Emits one JSON line per measurement."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    import networkx as nx

    from bluefog_tpu.models import ResNet50
    import bluefog_tpu.topology as topo

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    n_virt = int(os.environ.get("BENCH_GOSSIP_WORKERS", "8"))
    batch = int(os.environ.get("BENCH_BATCH", "8" if on_tpu else "2"))
    image = int(os.environ.get("BENCH_IMAGE", "224" if on_tpu else "32"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "10" if on_tpu else "2")))
    # >=1: the timing loop settles on the warmup's last loss
    warmup = max(
        1, int(os.environ.get("BENCH_WARMUP", "3" if on_tpu else "1"))
    )

    w = jnp.asarray(
        nx.to_numpy_array(topo.ExponentialTwoGraph(n_virt)), jnp.float32
    )
    model = ResNet50(num_classes=1000)
    rng = jax.random.PRNGKey(0)
    sample = jnp.ones((batch, image, image, 3), jnp.bfloat16)
    variables = model.init(rng, sample, train=True)
    tx = optax.sgd(0.1, momentum=0.9)
    stack = lambda tree: jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(t[None], (n_virt,) + t.shape) + 0.0, tree
    )
    params = stack(variables["params"])
    batch_stats = stack(variables["batch_stats"])
    opt_state = jax.tree_util.tree_map(
        lambda t: t + 0.0, stack(tx.init(variables["params"]))
    )
    rng_np = np.random.RandomState(0)
    images = jnp.asarray(
        rng_np.randn(n_virt, batch, image, image, 3), jnp.bfloat16
    )
    labels = jnp.asarray(
        rng_np.randint(0, 1000, (n_virt, batch)), jnp.int32
    )

    def one_step(p, bs, s, x, y):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": bs}, x, train=True,
                mutable=["batch_stats"],
            )
            return (
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, y
                ).mean(),
                mutated["batch_stats"],
            )

        (loss, nbs), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), nbs, s, loss

    def make(gossip):
        def step(params, batch_stats, opt_state, images, labels):
            p, nbs, s, loss = jax.vmap(one_step)(
                params, batch_stats, opt_state, images, labels
            )
            if gossip:
                # y_j = sum_i W[i, j] x_i over the replica axis — the
                # exact neighbor_allreduce combine, on-chip
                p = jax.tree_util.tree_map(
                    lambda t: jnp.einsum(
                        "ij,i...->j...", w.astype(t.dtype), t
                    ),
                    p,
                )
            return p, nbs, s, loss

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def stepper(fn, carry):
        def _step():
            p, bs, s = carry[0]
            p, bs, s, loss = fn(p, bs, s, images, labels)
            carry[0] = (p, bs, s)
            return loss

        return _step

    copy = lambda tr: jax.tree_util.tree_map(lambda t: t + 0.0, tr)
    step_plain = stepper(
        make(False), [(copy(params), copy(batch_stats), copy(opt_state))]
    )
    step_gossip = stepper(make(True), [(params, batch_stats, opt_state)])
    for _ in range(warmup - 1):
        step_plain()
        step_gossip()
    # INTERLEAVED rounds: the overhead is a ratio of two measurements,
    # and ambient host drift between two sequential measurement
    # phases (observed up to ~30% across minutes) would read as fake
    # overhead; alternating windows expose both variants to the same
    # ambient conditions
    dts_plain, dts_gossip = [], []
    for _ in range(3):
        dts_plain += _timed_differenced(step_plain, steps, windows=1)
        dts_gossip += _timed_differenced(step_gossip, steps, windows=1)
    dt_plain, dt_gossip = min(dts_plain), min(dts_gossip)

    # wire floor: one model-size HBM roundtrip (a ppermute's on-chip
    # cost). Sub-ms per iteration, so run many to dominate the readback
    # correction.
    flat = jnp.zeros((25_557_032,), jnp.float32)
    bump = jax.jit(lambda t: t + 1.0)
    copy_iters = 20 * steps
    for _ in range(warmup):
        flat = bump(flat)
    _settle(flat[:1])
    t0 = time.perf_counter()
    for _ in range(copy_iters):
        flat = bump(flat)
    _settle(flat[:1])
    t1 = time.perf_counter()
    _settle(flat[:1])
    dt_copy = max(t1 - t0 - (time.perf_counter() - t1), 1e-9) / copy_iters

    total = n_virt * batch
    overhead_pct = 100.0 * (dt_gossip - dt_plain) / dt_plain
    # The per-WORKER combine cost against the BASELINE-config (bs=64)
    # step is the deployment-relevant number: the raw ratio above divides
    # by this mode's deliberately small per-replica compute (bs=8 so 8
    # replicas fit one chip), which inflates it ~8x vs a real worker and
    # leaves it noise-dominated.
    combine_ms_per_worker = max(dt_gossip - dt_plain, 0.0) / n_virt * 1e3
    step_bs64_ms = dt_plain / n_virt * (64.0 / batch) * 1e3
    overhead_pct_bs64 = 100.0 * combine_ms_per_worker / step_bs64_ms
    for line in (
        {"metric": "gossip_step_no_comm", "workers_on_chip": n_virt,
         "imgs_per_sec": round(total / dt_plain, 1),
         "ms_per_step": round(dt_plain * 1e3, 2)},
        {"metric": "gossip_step_with_combine", "workers_on_chip": n_virt,
         "imgs_per_sec": round(total / dt_gossip, 1),
         "ms_per_step": round(dt_gossip * 1e3, 2),
         "gossip_overhead_pct": round(overhead_pct, 2),
         "combine_ms_per_worker": round(combine_ms_per_worker, 3),
         "overhead_pct_vs_bs64_step": round(overhead_pct_bs64, 2)},
        {"metric": "model_hbm_roundtrip", "ms": round(dt_copy * 1e3, 3)},
    ):
        print(json.dumps(line))
    if on_tpu and os.environ.get("BENCH_ASSERT", "1") != "0":
        # regression assertion (reference analogue:
        # scripts/pytorch_opt_linear_speedup_test.py asserts, not
        # narrates): the full-model combine must stay under 10% of a
        # baseline-config worker's step — loose enough to ride timing
        # noise, tight enough to catch a structural blowup (e.g. the
        # per-leaf combine regression _packed_gossip exists to prevent)
        assert overhead_pct_bs64 < 10.0, (
            f"per-worker gossip combine regressed to "
            f"{combine_ms_per_worker:.2f} ms = {overhead_pct_bs64:.2f}% "
            "of a bs=64 step (must stay < 10%)"
        )
    return 0


def run_overlap() -> int:
    """Exposed-communication comparison for the overlap layer
    (``opt.make_train_step``): two-program baseline vs fused vs
    fused+buckets vs delayed, plus the static HLO overlap scan.

    Each variant trains the same MLP regression step over an Exp2 gossip
    topology; ``exposed_comm_ms`` is the variant's step time minus the
    communication-free fused step (the compute floor), so it measures
    exactly the communication left on the critical path. The HLO scan
    (tools/hlo_overlap_scan.py) verifies the overlap claim statically:
    on TPU it counts async ``collective-permute-start``/``-done`` pairs
    with compute scheduled between them; on CPU (whose backend keeps
    collectives synchronous at the HLO level) it proves overlap
    *capability* by def-use independence instead. Runs on the ambient
    platform when it exposes >1 device (a real slice); otherwise on a
    virtual CPU mesh.
    """
    native = os.environ.get("BENCH_SCALING_PLATFORM", "")
    ambient = os.environ.get("JAX_PLATFORMS", "")
    use_native = native == "native" or (
        native == "" and ambient not in ("", "cpu")
    )
    if not use_native:
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_OVERLAP_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu.collective import inner as col_inner

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.hlo_overlap_scan import scan_overlap

    devices = jax.devices()
    on_tpu = devices[0].platform not in ("cpu",)
    n = min(len(devices), int(os.environ.get("BENCH_OVERLAP_WORKERS", "8")))
    if n < 2:
        # a 1-device native platform has no wire: nothing to overlap,
        # and every variant would time identically up to noise
        print(json.dumps({
            "metric": "overlap_skipped", "reason": "single device",
            "platform": devices[0].platform,
        }))
        return 0
    dim = int(os.environ.get("BENCH_OVERLAP_DIM", "2048" if on_tpu else "512"))
    layers = int(os.environ.get("BENCH_OVERLAP_LAYERS", "8" if on_tpu else "6"))
    batch = int(os.environ.get("BENCH_OVERLAP_BATCH", "128" if on_tpu else "32"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "10" if on_tpu else "5")))
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "5" if on_tpu else "3")))
    bucket_bytes = int(
        os.environ.get("BENCH_OVERLAP_BUCKET_BYTES", str(1 << 20))
    )

    bf.init(devices=devices[:n])
    bf.set_topology(topo.ExponentialTwoGraph(n))

    rng = np.random.RandomState(0)
    w0 = [
        (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        for _ in range(layers)
    ]
    x_np = rng.randn(n, batch, dim).astype(np.float32)
    y_np = rng.randn(n, batch, dim).astype(np.float32)

    def make_params():
        return {
            f"w{i}": bf.worker_values(lambda r, i=i: w0[i])
            for i in range(layers)
        }

    xs = bf.worker_values(lambda r: x_np[r])
    ys = bf.worker_values(lambda r: y_np[r])

    def loss_fn(p, x, y):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    n_elems = layers * dim * dim
    ctx = bf.get_context()

    def new_opt():
        return bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.01, momentum=0.9)
        )

    def fused_stepper(opt, **kwargs):
        train_step = bf.make_train_step(opt, loss_fn, **kwargs)
        params = make_params()
        state = opt.init(params)
        carry = [(params, state)]

        def _step():
            p, s = carry[0]
            p, s, loss = train_step(p, s, xs, ys)
            carry[0] = (p, s)
            return loss

        return _step, train_step, carry

    def fused_hlo(opt, carry):
        """Optimized HLO of this variant's fused program."""
        p, s = carry[0]
        return opt.lower_last_fused_hlo(p, s, xs, ys)

    variants = ("no_comm", "two_program", "fused", "fused_buckets",
                "delayed")
    env_caps = {
        "two_program": "0",  # cap irrelevant: one payload, legacy path
        "fused": "0",
        "fused_buckets": str(bucket_bytes),
        "delayed": str(bucket_bytes),
        "no_comm": "0",
    }
    old_cap = os.environ.get("BLUEFOG_BUCKET_BYTES")
    # an ambient BLUEFOG_OVERLAP=0 would short-circuit bucket_bytes_cap()
    # and silently compile the bucketed variants monolithic — the
    # published evidence would describe programs that were never built
    old_overlap = os.environ.get("BLUEFOG_OVERLAP")
    os.environ["BLUEFOG_OVERLAP"] = "1"
    steppers = {}
    hlo_texts = {}

    # restore belongs in finally: bucket_bytes_cap() reads the env on
    # every optimizer dispatch, so an exception mid-bench (XLA OOM, a
    # degenerate-platform abort) must not leak the last variant's cap
    # into the caller's process
    try:
        for variant in variants:
            os.environ["BLUEFOG_BUCKET_BYTES"] = env_caps[variant]
            if variant == "two_program":
                # the pre-overlap reality: the caller's grad program and
                # the optimizer's gossip+update program are separate
                # dispatches — every ppermute round fully exposed
                # between them
                opt = new_opt()
                params = make_params()
                state = opt.init(params)
                spec = P("workers")

                def grad_body(p_b, x_b, y_b):
                    p = jax.tree_util.tree_map(lambda t: t[0], p_b)
                    g = jax.grad(loss_fn)(p, x_b[0], y_b[0])
                    return jax.tree_util.tree_map(
                        lambda t: jnp.expand_dims(t, 0), g
                    )

                grad_fn = jax.jit(
                    jax.shard_map(
                        grad_body, mesh=ctx.mesh,
                        in_specs=(spec, spec, spec), out_specs=spec,
                    )
                )
                carry = [(params, state)]

                def _step(carry=carry, grad_fn=grad_fn, opt=opt):
                    p, s = carry[0]
                    g = grad_fn(p, xs, ys)
                    p, s = opt.step(p, s, g)
                    carry[0] = (p, s)
                    return p["w0"][0, 0, 0]  # scalar settle target

                steppers[variant] = _step
            else:
                opt = new_opt()
                if variant == "no_comm":
                    opt.communication_type = bf.CommunicationType.empty
                kwargs = {"delayed": True} if variant == "delayed" else {}
                _step, train_step, carry = fused_stepper(opt, **kwargs)
                steppers[variant] = _step
                _step()  # compile now, under this variant's bucket cap
                if variant in ("fused", "fused_buckets", "delayed"):
                    hlo_texts[variant] = fused_hlo(opt, carry)

        # INTERLEAVED windows (same rationale as BENCH_MODE=gossip): the
        # comparison is a ratio of separately-timed variants, and
        # ambient drift between sequential phases would read as fake
        # overlap gains; round-robin windows expose every variant to the
        # same conditions.
        dts = {v: [] for v in variants}
        degens = {v: 0 for v in variants}  # stall-clamped window count
        for _ in range(windows):
            for variant in variants:
                os.environ["BLUEFOG_BUCKET_BYTES"] = env_caps[variant]
                ts_w, degen = _timed_differenced(
                    steppers[variant], steps, 1, with_degenerate=True
                )
                if degen:
                    degens[variant] += 1
                else:
                    dts[variant] += ts_w
    finally:
        if old_cap is None:
            os.environ.pop("BLUEFOG_BUCKET_BYTES", None)
        else:
            os.environ["BLUEFOG_BUCKET_BYTES"] = old_cap
        if old_overlap is None:
            os.environ.pop("BLUEFOG_OVERLAP", None)
        else:
            os.environ["BLUEFOG_OVERLAP"] = old_overlap
    results = {
        v: (min(dts[v]) if dts[v] else 0.0, not dts[v]) for v in variants
    }

    floor, floor_degen = results["no_comm"]
    for variant in ("two_program", "fused", "fused_buckets", "delayed"):
        dt, degen = results[variant]
        exposed = max(dt - floor, 0.0)
        line = {
            "metric": "overlap_step",
            "variant": variant,
            "n_workers": n,
            "payload_mb": round(n_elems * 4 / 1e6, 2),
            "ms_per_step": round(dt * 1e3, 3),
            "compute_floor_ms": round(floor * 1e3, 3),
            "exposed_comm_ms": round(exposed * 1e3, 3),
        }
        if floor > 0:
            line["gossip_overhead_pct"] = round(100.0 * exposed / floor, 2)
        if degens[variant]:
            # partial stalls: the published best-of excludes them, but
            # the sample size shrank — disclose, don't hide
            line["degenerate_windows"] = degens[variant]
            line["clean_windows"] = len(dts[variant])
        if degen or floor_degen:
            # every window clamped: the value is a floor artifact
            line["degenerate"] = True
        print(json.dumps(line))

    # the fused_buckets variant's messages a round (exact wire): a leaf at
    # or over the cap alone, the smaller ones packed and cut into buckets
    from bluefog_tpu import optimizers as opt_mod

    elems = [
        n for _itemsize, n in
        opt_mod._gossip_messages(make_params(), bucket_bytes, True)
    ]
    print(json.dumps({
        "metric": "overlap_buckets",
        "bucket_bytes_cap": bucket_bytes,
        "n_buckets": len(elems),
        "bucket_elems": elems[:16],
    }))

    for variant, txt in hlo_texts.items():
        scan = scan_overlap(txt)
        print(json.dumps({
            "metric": "overlap_hlo",
            "variant": variant,
            "platform": devices[0].platform,
            **{k: v for k, v in scan.items() if k != "permutes"},
        }))
        if variant in ("fused_buckets", "delayed"):
            # schedule-order timeline: one event per bucket-round permute
            print(json.dumps({
                "metric": "overlap_bucket_timeline",
                "variant": variant,
                "events": [
                    {
                        "name": p["name"],
                        "kind": p["kind"],
                        "payload_bytes": p["payload_bytes"],
                        "start_pos": p["start_pos"],
                        "done_pos": p["done_pos"],
                        "overlapped_compute": p["compute_between"],
                        "independent_compute_ops":
                            p["independent_compute_ops"],
                    }
                    for p in scan["permutes"][:32]
                ],
            }))

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        degenerate = any(d for _t, d in results.values())
        if not degenerate:
            # the acceptance pair: fused+buckets must leave LESS
            # communication exposed than the two-program baseline
            two = results["two_program"][0] - floor
            fb = results["fused_buckets"][0] - floor
            assert fb < two, (
                f"fused+buckets exposed comm {fb*1e3:.3f} ms is not below "
                f"the two-program baseline {two*1e3:.3f} ms"
            )
        if on_tpu:
            scan = scan_overlap(hlo_texts["fused_buckets"])
            assert scan["overlapped_async_pairs"] >= 1, (
                "TPU fused program shows no async collective-permute "
                "pair overlapping compute: "
                f"{ {k: v for k, v in scan.items() if k != 'permutes'} }"
            )
    return 0


def run_metrics() -> int:
    """Metrics-overhead evidence: the same fused gossip train step timed
    with the telemetry device tier off vs on (``BLUEFOG_METRICS=1``,
    interval 10) on the 8-worker CPU mesh, plus the bitwise pin that
    enabling metrics does not move the training state, and a sample of
    the drained registry. The acceptance bound — <2 % step-time
    overhead — is asserted here so the committed METRICS_EVIDENCE.json
    is re-checked by every bench run.

    Measurement protocol — per-sample delta, analytically amortized.
    Direct wall-clock A/B at interval 10 cannot resolve <2 % on a
    shared host: the A/A (off vs off) control of both window-level and
    step-level paired protocols was measured swinging +-5 % run to run
    (ambient load states are autocorrelated at the seconds scale). The
    <2 % claim decomposes into two facts that ARE resolvable:

    1. Unsampled steps (interval-1 of every interval) dispatch the SAME
       compiled program as metrics-off — verified structurally here by
       toggling BLUEFOG_METRICS on the same optimizer and asserting no
       new op-cache entry appears. Zero overhead by construction.
    2. The sampled step's incremental cost (metric-instrumented program
       + drain swap) is measured directly by running the on-stepper at
       interval=1 — every step pays it — against the off-stepper in a
       step-level rotation (all orderings, position bias cancels).
       Resolving the PER-SAMPLE delta needs only ~20 % resolution for a
       2 % amortized bound, well above the noise floor; the published
       ``overhead_pct`` is that delta divided by the interval. An
       off/off A/A control runs the identical protocol and is published
       amortized the same way as the method's noise floor."""
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_METRICS_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu import metrics as bf_metrics

    devices = jax.devices()
    n = min(len(devices), int(os.environ.get("BENCH_METRICS_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_METRICS_DIM", "512"))
    layers = int(os.environ.get("BENCH_METRICS_LAYERS", "12"))
    batch = int(os.environ.get("BENCH_METRICS_BATCH", "32"))
    interval = int(os.environ.get("BLUEFOG_METRICS_INTERVAL", "10"))
    samples = max(
        30, int(os.environ.get("BENCH_METRICS_SAMPLES", "150"))
    )

    bf.init(devices=devices[:n])
    bf.set_topology(topo.ExponentialTwoGraph(n))

    rng = np.random.RandomState(0)
    w0 = [
        (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        for _ in range(layers)
    ]
    xs = bf.worker_values(lambda r: rng.randn(batch, dim).astype(np.float32))
    ys = bf.worker_values(lambda r: rng.randn(batch, dim).astype(np.float32))

    def loss_fn(p, x, y):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    def make_stepper():
        opt = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.01, momentum=0.9)
        )
        train_step = bf.make_train_step(opt, loss_fn)
        params = {
            f"w{i}": bf.worker_values(lambda r, i=i: w0[i])
            for i in range(layers)
        }
        carry = [(params, opt.init(params))]

        def _step():
            p, s = carry[0]
            p, s, loss = train_step(p, s, xs, ys)
            carry[0] = (p, s)
            return loss

        return _step, carry

    old_env = {
        k: os.environ.get(k)
        for k in ("BLUEFOG_METRICS", "BLUEFOG_METRICS_INTERVAL",
                  "BLUEFOG_METRICS_FILE", "BLUEFOG_METRICS_PROM")
    }
    # no exporter I/O inside the timed loop: the evidence bounds the
    # in-graph computation + the interval-amortized drain readback
    os.environ.pop("BLUEFOG_METRICS_FILE", None)
    os.environ.pop("BLUEFOG_METRICS_PROM", None)
    os.environ["BLUEFOG_METRICS_INTERVAL"] = str(interval)
    # "on" runs at interval=1 so EVERY timed step pays the sampled
    # program + drain; "off2" is the A/A control: a second metrics-off
    # stepper measured with the same protocol, so the published number
    # comes with the methodology's own noise floor next to it.
    env_cfg = {"off": ("0", None), "on": ("1", "1"), "off2": ("0", None)}

    def set_env(variant):
        met, iv = env_cfg[variant]
        os.environ["BLUEFOG_METRICS"] = met
        os.environ["BLUEFOG_METRICS_INTERVAL"] = iv or str(interval)

    try:
        import itertools
        import time as time_mod

        steppers = {}
        carries = {}
        for variant in ("off", "on", "off2"):
            set_env(variant)
            steppers[variant], carries[variant] = make_stepper()
            steppers[variant]()  # compile under this variant's config
            steppers[variant]()  # and the on-variant's drain path
            _settle(steppers[variant]())

        # structural fact 1: with metrics enabled, an off-boundary
        # (unsampled) dispatch reuses the metrics-off compiled program —
        # toggling the flag on the SAME stepper adds no op-cache entry
        ctx = bf.get_context()
        os.environ["BLUEFOG_METRICS"] = "0"
        steppers["off"]()
        n_cache = len(ctx.op_cache)
        # the off-stepper's comm count is already past 0, so with a huge
        # interval this enabled dispatch is off-boundary == unsampled
        os.environ["BLUEFOG_METRICS"] = "1"
        os.environ["BLUEFOG_METRICS_INTERVAL"] = "1000000000"
        steppers["off"]()
        unsampled_shared = len(ctx.op_cache) == n_cache
        set_env("off")

        orders = list(itertools.permutations(("off", "on", "off2")))
        times = {v: [] for v in steppers}
        for i in range(samples):
            for variant in orders[i % len(orders)]:
                set_env(variant)
                t0 = time_mod.perf_counter()
                _settle(steppers[variant]())
                times[variant].append(time_mod.perf_counter() - t0)

        pairs = list(zip(times["off"], times["on"]))
        control_pairs = list(zip(times["off"], times["off2"]))

        # bitwise pin, fresh state both ways, same step count, at the
        # published interval (so both sampled and unsampled dispatches
        # are exercised on the metrics-on side)
        state_bits = {}
        os.environ["BLUEFOG_METRICS_INTERVAL"] = str(interval)
        for variant in ("off", "on"):
            os.environ["BLUEFOG_METRICS"] = env_cfg[variant][0]
            _step, carry = make_stepper()
            for _ in range(12):
                _step()
            state_bits[variant] = jax.tree_util.tree_leaves(carry[0])
        bitwise = all(
            bool(np.array_equal(np.asarray(a), np.asarray(b)))
            for a, b in zip(state_bits["off"], state_bits["on"])
        )
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def median(v):
        v = sorted(v)
        return v[len(v) // 2] if v else 0.0

    degenerate = not pairs
    base_s = median(times["off"])
    # per-SAMPLE incremental cost (ms): paired per-step deltas, median
    sample_extra_s = median([on - off for off, on in pairs])
    control_extra_s = median([o2 - off for off, o2 in control_pairs])
    # amortized: one sampled step per interval, the rest are the shared
    # metrics-off program (unsampled_shared above)
    overhead_pct = (
        100.0 * sample_extra_s / interval / base_s if base_s > 0 else 0.0
    )
    control_pct = (
        100.0 * control_extra_s / interval / base_s if base_s > 0 else 0.0
    )
    line = {
        "metric": "metrics_overhead",
        "n_workers": n,
        "payload_mb": round(layers * dim * dim * 4 / 1e6, 2),
        "interval": interval,
        "ms_per_step_off": round(base_s * 1e3, 3),
        "ms_sampled_step_extra": round(sample_extra_s * 1e3, 3),
        "unsampled_program_shared": unsampled_shared,
        "overhead_pct": round(overhead_pct, 3),
        # A/A control: what the same protocol+amortization reports for
        # two IDENTICAL metrics-off steppers — the honest noise floor
        "control_aa_pct": round(control_pct, 3),
        "bitwise_identical": bitwise,
        "samples": len(pairs),
    }
    if degenerate:
        line["degenerate"] = True
    print(json.dumps(line))

    bf_metrics.flush()  # fold any deferred drains before sampling
    snap = bf_metrics.snapshot()
    sample = {
        k: v.get("value")
        for k, v in snap.items()
        if k.startswith("bluefog.gossip.") or k in (
            "bluefog.wire_bytes", "bluefog.comm_steps",
            "bluefog.recompiles",
        )
    }
    print(json.dumps({"metric": "metrics_snapshot_sample", **sample}))

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert bitwise, (
            "enabling metrics changed the training state bitwise"
        )
        assert unsampled_shared, (
            "unsampled metrics-on dispatch did not reuse the "
            "metrics-off compiled program"
        )
        if not degenerate:
            assert overhead_pct < 2.0, (
                f"metrics overhead {overhead_pct:.2f}% exceeds the 2% "
                "acceptance bound at interval "
                f"{interval}"
            )
    return 0


def run_elastic() -> int:
    """Elastic-gossip evidence (``BENCH_MODE=elastic``): an 8-worker CPU
    mesh with a rank killed mid-training through the deterministic chaos
    layer. Emits steps-to-detect, steps-to-repair, the post-repair
    consensus distance against the numpy survivor-oracle, and the
    plan-cache live-set accounting proving no stale CommPlan dispatched
    after the membership change. ``BENCH_ASSERT=1`` (default) enforces
    the acceptance bounds. See docs/elastic.md."""
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_ELASTIC_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax
    import optax

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo

    devices = jax.devices()
    n = min(len(devices), int(os.environ.get("BENCH_ELASTIC_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_ELASTIC_DIM", "4096"))
    kill_step = int(os.environ.get("BENCH_ELASTIC_KILL_STEP", "5"))
    grad_steps = int(os.environ.get("BENCH_ELASTIC_GRAD_STEPS", "12"))
    steps = int(os.environ.get("BENCH_ELASTIC_STEPS", "48"))
    kill_rank = n // 2
    lr = np.float32(0.05)

    bf.init(devices=devices[:n])
    bf.set_topology(topo.ExponentialTwoGraph(n))
    ctx = bf.get_context()

    session = bf.elastic.start(policy="average")
    session.inject("kill", rank=kill_rank, step=kill_step)
    opt = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(float(lr)))
    guard = bf.elastic.guard(opt)

    rng = np.random.RandomState(0)
    x0 = rng.randn(n, dim).astype(np.float32)
    grads = [
        rng.randn(n, dim).astype(np.float32) * 0.1 for _ in range(grad_steps)
    ]
    zeros = np.zeros((n, dim), np.float32)
    params = {"w": bf.worker_values(lambda r: x0[r])}
    state = opt.init(params)
    at_repair = None
    t0 = time.perf_counter()
    for t in range(steps):
        g = grads[t] if t < grad_steps else zeros
        if t == kill_step:
            at_repair = np.asarray(params["w"])
        params, state = guard.step(
            params, state, {"w": bf.worker_values(lambda r: g[r])}
        )
    wall_s = time.perf_counter() - t0

    rec = session.repairs[0]
    live = list(session.membership.live_ranks())
    final = np.asarray(params["w"])

    # survivor-consensus oracle: mean of survivors at repair plus the
    # post-repair gradient drift (the doubly stochastic repaired mix
    # preserves the survivor mean exactly)
    target = at_repair[live].mean(axis=0)
    for t in range(kill_step, grad_steps):
        target = target - lr * grads[t][live].mean(axis=0)
    consensus_dist = float(np.abs(final[live] - target).max())
    spread = float(np.abs(final[live] - final[live].mean(axis=0)).max())

    # live-set-aware plan cache: every static plan compiled after the
    # session opened carries a live token; repair added a new entry
    plan_keys = [
        k for k in ctx.op_cache if isinstance(k, tuple)
        and k and k[0] == "static_plan"
    ]
    tokened = [k for k in plan_keys if k[-1] is not None]

    detect = max(rec.steps_to_detect.values())
    lines = [
        {
            "metric": "elastic_repair",
            "workers": n,
            "kill_rank": kill_rank,
            "kill_step": kill_step,
            "repair_step": rec.step,
            "steps_to_detect": detect,
            "steps_to_repair": rec.steps_to_repair,
            "policy": rec.policy,
            "dead": list(rec.dead),
            "live_count": len(live),
            "topo_version_after": rec.topo_version,
            "wall_s_total": round(wall_s, 3),
        },
        {
            "metric": "elastic_consensus",
            "steps_after_repair": steps - kill_step,
            "post_repair_consensus_distance": consensus_dist,
            "survivor_spread": spread,
            "oracle": "numpy survivor mean + gradient drift",
        },
        {
            "metric": "elastic_plan_cache",
            "static_plan_cache_entries": len(plan_keys),
            "entries_with_live_token": len(tokened),
            "stale_commplan_dispatches": session.stale_dispatches,
        },
    ]
    for line in lines:
        print(json.dumps(line))
    bf.elastic.stop()

    if os.environ.get("BENCH_ASSERT", "1") == "1":
        assert detect <= 1, f"detection took {detect} steps"
        assert rec.steps_to_repair == 0, rec
        assert session.stale_dispatches == 0
        assert consensus_dist < 1e-3, consensus_dist
        assert tokened, "static-plan cache keys carry no live token"
    return 0


def run_flight() -> int:
    """Flight-recorder evidence (``BENCH_MODE=flight``): the black box
    must cost ~nothing and the postmortem must be right. Three claims,
    each measured the way it is resolvable (the direct-A/B noise-floor
    lesson of BENCH_MODE=metrics applies here too):

    1. **Overhead <= 1 % per step** (recorder is on by default). Primary
       measurement is analytic decomposition: the per-event ring-write
       cost (tight microbenchmark, best-of-windows) times the exact
       events-per-step count (read off the ring's sequence numbers)
       over the differenced-harness step time. A direct interleaved
       on/off A/B with an off/off A/A control is published next to it
       as the honest end-to-end cross-check (NOT asserted: its noise
       floor on a shared host exceeds the bound being claimed).
    2. **Bitwise-identical trajectory** recorder on vs off (recording
       never touches device values; pinned here every round).
    3. **Postmortem correctness**: a BLUEFOG_FAULT_PLAN-killed rank on
       the 8-worker mesh, dumps + timeline fused by
       ``tools/trace_merge.py`` — the merged Perfetto JSON must be
       valid, its per-step round count must match the independently
       compiled CommPlan, and the hang postmortem must name the killed
       rank and the exact edge/round each neighbor stalled on.
    """
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_FLIGHT_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import itertools
    import tempfile
    import time as time_mod

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu import flight as bf_flight
    from bluefog_tpu.collective.plan import plan_from_topology

    devices = jax.devices()
    n = min(len(devices), int(os.environ.get("BENCH_FLIGHT_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_FLIGHT_DIM", "512"))
    layers = int(os.environ.get("BENCH_FLIGHT_LAYERS", "12"))
    batch = int(os.environ.get("BENCH_FLIGHT_BATCH", "32"))
    samples = max(24, int(os.environ.get("BENCH_FLIGHT_SAMPLES", "90")))
    kill_step = int(os.environ.get("BENCH_FLIGHT_KILL_STEP", "5"))
    pm_steps = int(os.environ.get("BENCH_FLIGHT_STEPS", "12"))

    old_env = {
        k: os.environ.get(k)
        for k in ("BLUEFOG_FLIGHT", "BLUEFOG_FLIGHT_DIR",
                  "BLUEFOG_TIMELINE")
    }
    os.environ.pop("BLUEFOG_FLIGHT_DIR", None)
    os.environ.pop("BLUEFOG_TIMELINE", None)

    bf.init(devices=devices[:n])
    bf.set_topology(topo.ExponentialTwoGraph(n))

    rng = np.random.RandomState(0)
    w0 = [
        (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        for _ in range(layers)
    ]
    xs = bf.worker_values(lambda r: rng.randn(batch, dim).astype(np.float32))
    ys = bf.worker_values(lambda r: rng.randn(batch, dim).astype(np.float32))

    def loss_fn(p, x, y):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    def make_stepper():
        opt = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.01, momentum=0.9)
        )
        train_step = bf.make_train_step(opt, loss_fn)
        params = {
            f"w{i}": bf.worker_values(lambda r, i=i: w0[i])
            for i in range(layers)
        }
        carry = [(params, opt.init(params))]

        def _step():
            p, s = carry[0]
            p, s, loss = train_step(p, s, xs, ys)
            carry[0] = (p, s)
            return loss

        return _step, carry

    def set_flight(on: bool):
        os.environ["BLUEFOG_FLIGHT"] = "1" if on else "0"
        bf_flight.reconfigure()

    try:
        # -- claim 1a: per-event ring-write cost (microbenchmark) ------------
        set_flight(True)
        n_calls = 200_000
        per_event = []
        for _ in range(5):
            t0 = time_mod.perf_counter()
            for _i in range(n_calls):
                bf_flight.record("bench", step=1, comm=True)
            per_event.append((time_mod.perf_counter() - t0) / n_calls)
        per_event_s = min(per_event)

        # -- claim 1b: exact events-per-step, from ring sequence numbers -----
        set_flight(True)
        stepper, _carry = make_stepper()
        stepper()  # compile outside the counted window
        _settle(stepper())
        before = max(
            (e["seq"] for e in bf_flight.events()), default=0
        )
        count_steps = 10
        for _ in range(count_steps):
            stepper()
        _settle(stepper())
        after = max((e["seq"] for e in bf_flight.events()), default=0)
        events_per_step = (after - before) / (count_steps + 1)

        # -- claim 1c: step time (differenced harness), recorder ON ----------
        step_times = _timed_differenced(stepper, 10, 4)
        step_s = step_times[0]
        overhead_pct = (
            100.0 * events_per_step * per_event_s / step_s
            if step_s > 0 else 0.0
        )

        # -- cross-check: direct interleaved A/B + A/A control (disclosed) ---
        steppers = {}
        for variant in ("off", "on", "off2"):
            set_flight(variant == "on")
            steppers[variant], _ = make_stepper()
            steppers[variant]()
            _settle(steppers[variant]())
        orders = list(itertools.permutations(("off", "on", "off2")))
        times = {v: [] for v in steppers}
        for i in range(samples):
            for variant in orders[i % len(orders)]:
                set_flight(variant == "on")
                t0 = time_mod.perf_counter()
                _settle(steppers[variant]())
                times[variant].append(time_mod.perf_counter() - t0)

        def median(v):
            v = sorted(v)
            return v[len(v) // 2] if v else 0.0

        base_s = median(times["off"])
        direct_pct = (
            100.0 * median([b - a for a, b in zip(times["off"],
                                                  times["on"])]) / base_s
            if base_s > 0 else 0.0
        )
        control_pct = (
            100.0 * median([b - a for a, b in zip(times["off"],
                                                  times["off2"])]) / base_s
            if base_s > 0 else 0.0
        )

        # -- claim 2: bitwise trajectory pin, on vs off ----------------------
        state_bits = {}
        for variant in ("off", "on"):
            set_flight(variant == "on")
            _step, carry = make_stepper()
            for _ in range(12):
                _step()
            state_bits[variant] = jax.tree_util.tree_leaves(carry[0])
        bitwise = all(
            bool(np.array_equal(np.asarray(a), np.asarray(b)))
            for a, b in zip(state_bits["off"], state_bits["on"])
        )

        print(json.dumps({
            "metric": "flight_recorder_overhead",
            "n_workers": n,
            "payload_mb": round(layers * dim * dim * 4 / 1e6, 2),
            "per_event_us": round(per_event_s * 1e6, 3),
            "events_per_step": round(events_per_step, 2),
            "ms_per_step": round(step_s * 1e3, 3),
            "overhead_pct": round(overhead_pct, 4),
            "method": (
                "analytic: per-event ring-write cost x exact "
                "events/step over the differenced step time"
            ),
            "direct_ab_pct": round(direct_pct, 3),
            "control_aa_pct": round(control_pct, 3),
            "direct_ab_note": (
                "interleaved per-step median delta; disclosed as the "
                "end-to-end cross-check, not asserted (shared-host "
                "noise floor exceeds the 1% bound)"
            ),
            "bitwise_identical": bitwise,
            "samples": samples,
        }))

        # -- claim 3: kill -> dump -> merge -> postmortem --------------------
        bf.shutdown()
        dump_dir = tempfile.mkdtemp(prefix="bf_flight_")
        os.environ["BLUEFOG_FLIGHT_DIR"] = dump_dir
        os.environ["BLUEFOG_TIMELINE"] = os.path.join(dump_dir, "trace_")
        os.environ["BLUEFOG_FLIGHT"] = "1"
        bf.init(devices=devices[:n])
        bf.set_topology(topo.ExponentialTwoGraph(n))
        kill_rank = n // 2
        session = bf.elastic.start(policy="average")
        session.inject("kill", rank=kill_rank, step=kill_step)
        opt = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(0.05))
        guard = bf.elastic.guard(opt)
        params = {"w": bf.worker_values(
            lambda r: rng.randn(dim).astype(np.float32)
        )}
        state = opt.init(params)
        for _t in range(pm_steps):
            params, state = guard.step(
                params, state,
                {"w": bf.worker_values(np.zeros(dim, np.float32))},
            )
        bf.flight_dump()
        bf.elastic.stop()
        bf.shutdown()  # closes the env-owned timeline -> valid JSON

        from tools.trace_merge import merge_and_analyze

        merged, report = merge_and_analyze(dump_dir)
        merged_valid = isinstance(
            json.loads(json.dumps(merged))["traceEvents"], list
        )
        # independent ground truth: compile the same topology again
        base_plan = plan_from_topology(topo.ExponentialTwoGraph(n))
        pre_kill = [
            s for s in report["per_step_rounds"] if s["step"] < kill_step
        ]
        rounds_match = bool(pre_kill) and all(
            s["rounds"] == len(base_plan.rounds) for s in pre_kill
        )
        pm = report["hang_postmortem"] or {}
        waiters = pm.get("waiters", [])
        rounds_by_edge = {}
        for ri, rnd in enumerate(base_plan.rounds):
            for s, d in rnd.perm:
                rounds_by_edge.setdefault((s, d), ri)
        expected_waiters = sorted(
            d for (s, d) in rounds_by_edge if s == kill_rank
        )
        postmortem_ok = (
            pm.get("dead_ranks") == [kill_rank]
            and sorted(w["rank"] for w in waiters) == expected_waiters
            and all(
                w["waiting_on"] == kill_rank
                and rounds_by_edge.get((kill_rank, w["rank"]))
                == w["round"]
                for w in waiters
            )
            # the DEAD verdict itself must have gone to disk (the
            # automatic trigger, not just the explicit end-of-run dump)
            and any(
                str(r).startswith("verdict:dead")
                for r in pm.get("dump_reasons", [])
            )
        )
        print(json.dumps({
            "metric": "flight_trace_merge",
            "n_workers": n,
            "merged_events": len(merged["traceEvents"]),
            "merged_valid_json": merged_valid,
            "plan_rounds_compiled": len(base_plan.rounds),
            "plan_rounds_reported": report["plan_rounds"],
            "per_step_rounds_match_plan": rounds_match,
            "steps_analyzed": len(report["steps"]),
        }))
        print(json.dumps({
            "metric": "flight_postmortem",
            "kill_rank": kill_rank,
            "kill_step": kill_step,
            "dead_ranks_reported": pm.get("dead_ranks"),
            "waiters": waiters,
            "expected_waiters": expected_waiters,
            "last_completed_step": pm.get("last_completed_step"),
            "dump_reasons": pm.get("dump_reasons"),
            "named_correctly": postmortem_ok,
        }))
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        bf_flight.reconfigure()

    if os.environ.get("BENCH_ASSERT", "1") == "1":
        assert bitwise, (
            "enabling the flight recorder changed the training state"
        )
        assert overhead_pct <= 1.0, (
            f"flight recorder overhead {overhead_pct:.3f}% exceeds the "
            "1% acceptance bound"
        )
        assert merged_valid and rounds_match, (
            "merged trace invalid or round counts diverge from the "
            "compiled CommPlan"
        )
        assert postmortem_ok, (
            f"postmortem failed to name the killed rank/edges: {pm}"
        )
    return 0


def run_attribution() -> int:
    """Attribution-doctor evidence (``BENCH_MODE=attribution``,
    committed as ATTRIBUTION_EVIDENCE.json). Four claims, measured the
    way each is resolvable (the BENCH_MODE=metrics noise-floor lessons
    apply unchanged):

    1. **Structural pin**: the doctor never changes the training
       program — enabling it adds no compiled-train-step cache entry
       (its probe programs live under their own ``doctor_probe`` keys),
       so every unsampled step dispatches the doctor-off program under
       the doctor-off cache key by construction.
    2. **Bitwise trajectory pin**: doctor on vs off, fresh state both
       ways, identical training state to the bit.
    3. **Overhead <= 1 % at the default interval**: the doctor's
       per-sample cost (settle + per-round probes + anchor) is measured
       directly by sampling EVERY step (interval 1) against a
       doctor-off stepper in a step-level rotation (all orderings), and
       amortized over the default interval; an off/off A/A control runs
       the identical protocol as the disclosed noise floor.
    4. **Degraded-link localization**: a fault-plan ``degrade`` on one
       directed edge (the PR-4 chaos layer's deterministic wire
       simulation); the doctor's per-round probes + per-edge drill-down
       must emit a ``degraded_link`` advisory naming exactly the
       injected edge — from timings alone.
    """
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_ATTR_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import itertools
    import time as time_mod

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu import attribution
    from bluefog_tpu.collective import compiler

    devices = jax.devices()
    n = min(len(devices), int(os.environ.get("BENCH_ATTR_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_ATTR_DIM", "256"))
    layers = int(os.environ.get("BENCH_ATTR_LAYERS", "6"))
    batch = int(os.environ.get("BENCH_ATTR_BATCH", "16"))
    samples = max(18, int(os.environ.get("BENCH_ATTR_SAMPLES", "60")))

    old_env = {
        k: os.environ.get(k)
        for k in ("BLUEFOG_DOCTOR", "BLUEFOG_DOCTOR_INTERVAL",
                  "BLUEFOG_DOCTOR_FILE", "BLUEFOG_METRICS")
    }
    os.environ.pop("BLUEFOG_DOCTOR", None)
    # the evidence claims the DEFAULT interval: an ambient override
    # would silently re-scope the committed overhead amortization
    os.environ.pop("BLUEFOG_DOCTOR_INTERVAL", None)
    os.environ.pop("BLUEFOG_DOCTOR_FILE", None)
    os.environ.pop("BLUEFOG_METRICS", None)
    default_interval = attribution.doctor_interval()

    bf.init(devices=devices[:n])
    bf.set_topology(topo.ExponentialTwoGraph(n))
    # calibrate ONCE up front: the doctor's lazy first-sample probe
    # must not land inside a timed window
    compiler.calibrate()

    rng = np.random.RandomState(0)
    w0 = [
        (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        for _ in range(layers)
    ]
    xs = bf.worker_values(lambda r: rng.randn(batch, dim).astype(np.float32))
    ys = bf.worker_values(lambda r: rng.randn(batch, dim).astype(np.float32))

    def loss_fn(p, x, y):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    def make_stepper():
        opt = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.01, momentum=0.9)
        )
        train_step = bf.make_train_step(opt, loss_fn)
        params = {
            f"w{i}": bf.worker_values(lambda r, i=i: w0[i])
            for i in range(layers)
        }
        carry = [(params, opt.init(params))]

        def _step():
            p, s = carry[0]
            p, s, loss = train_step(p, s, xs, ys)
            carry[0] = (p, s)
            return loss

        return _step, carry

    try:
        ctx = bf.get_context()

        # -- claim 1: structural — no train-step cache entry changes ---------
        attribution.stop()
        stepper, _carry = make_stepper()
        stepper()
        stepper()
        def train_keys():
            return {
                k for k in ctx.op_cache
                if isinstance(k, tuple) and k
                and k[0] in ("opt_step", "opt_fused_step")
            }
        keys_off = train_keys()
        doc = attribution.start(interval=1)
        stepper()
        stepper()
        keys_on = train_keys()
        probe_keys = [
            k for k in ctx.op_cache
            if isinstance(k, tuple) and k and k[0] == "doctor_probe"
        ]
        unsampled_shared = keys_on == keys_off
        attribution.stop()

        # -- claim 2: bitwise trajectory pin ---------------------------------
        state_bits = {}
        for variant in ("off", "on"):
            if variant == "on":
                attribution.start(interval=3)
            else:
                attribution.stop()
            _step, carry = make_stepper()
            for _ in range(12):
                _step()
            state_bits[variant] = jax.tree_util.tree_leaves(carry[0])
        attribution.stop()
        bitwise = all(
            bool(np.array_equal(np.asarray(a), np.asarray(b)))
            for a, b in zip(state_bits["off"], state_bits["on"])
        )

        # -- claim 3: overhead at the default interval -----------------------
        steppers = {}
        doc_on = attribution.StepDoctor(interval=1)
        for variant in ("off", "on", "off2"):
            attribution.activate(doc_on if variant == "on" else None)
            steppers[variant], _ = make_stepper()
            steppers[variant]()  # compile (+ probe compile for "on")
            _settle(steppers[variant]())
        orders = list(itertools.permutations(("off", "on", "off2")))
        times = {v: [] for v in steppers}
        for i in range(samples):
            for variant in orders[i % len(orders)]:
                attribution.activate(
                    doc_on if variant == "on" else None
                )
                t0 = time_mod.perf_counter()
                _settle(steppers[variant]())
                times[variant].append(time_mod.perf_counter() - t0)
        attribution.activate(None)

        def median(v):
            v = sorted(v)
            return v[len(v) // 2] if v else 0.0

        base_s = median(times["off"])
        sample_extra_s = median(
            [on - off for off, on in zip(times["off"], times["on"])]
        )
        control_extra_s = median(
            [o2 - off for off, o2 in zip(times["off"], times["off2"])]
        )
        overhead_pct = (
            100.0 * sample_extra_s / default_interval / base_s
            if base_s > 0 else 0.0
        )
        control_pct = (
            100.0 * control_extra_s / default_interval / base_s
            if base_s > 0 else 0.0
        )

        # one representative decomposition sample from the on-doctor
        decomp = {}
        for s in reversed(doc_on.samples):
            if "step_ms" in s and "comm_wire_ms" in s:
                decomp = {
                    "step_ms": s["step_ms"],
                    "comm_wire_ms": s["comm_wire_ms"],
                    "compute_ms": s.get("compute_ms"),
                    "dispatch_ms": s.get("dispatch_ms"),
                    "exposed_comm_frac": s.get("exposed_comm_frac"),
                    "rounds": len(s.get("rounds", [])),
                }
                break

        print(json.dumps({
            "metric": "attribution_overhead",
            "n_workers": n,
            "payload_mb": round(layers * dim * dim * 4 / 1e6, 2),
            "interval": default_interval,
            "ms_per_step_off": round(base_s * 1e3, 3),
            "ms_sampled_step_extra": round(sample_extra_s * 1e3, 3),
            "overhead_pct": round(overhead_pct, 3),
            "control_aa_pct": round(control_pct, 3),
            "unsampled_program_shared": unsampled_shared,
            "doctor_probe_programs": len(probe_keys),
            "bitwise_identical": bitwise,
            "samples": samples,
        }))
        print(json.dumps({
            "metric": "attribution_sample", **decomp,
        }))

        # -- claim 4: degraded-link localization -----------------------------
        bf.shutdown()
        bf.init(devices=devices[:n])
        bf.set_topology(topo.ExponentialTwoGraph(n))
        compiler.calibrate()
        # Exp2 edges are rank -> rank+2^k: degrade the single directed
        # edge (kill_src, kill_dst) and make the doctor find it
        kill_src = int(os.environ.get("BENCH_ATTR_DEGRADE_RANK", "2"))
        kill_dst = (kill_src + 4) % n
        session = bf.elastic.start(policy="average")
        session.inject(
            "degrade", rank=kill_src, step=0, factor=0.05, peer=kill_dst
        )
        doc = attribution.start(interval=2)
        opt = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(0.05))
        guard = bf.elastic.guard(opt)
        params = {"w": bf.worker_values(
            lambda r: rng.randn(4096).astype(np.float32)
        )}
        state = opt.init(params)
        zeros = {"w": bf.worker_values(np.zeros(4096, np.float32))}
        for _t in range(6):
            params, state = guard.step(params, state, zeros)
        linked = [
            a.to_json() for a in doc.advisories
            if a.kind == "degraded_link"
        ]
        named = sorted({tuple(a["edge"]) for a in linked})
        named_correctly = (kill_src, kill_dst) in named
        print(json.dumps({
            "metric": "attribution_degraded_link",
            "injected_edge": [kill_src, kill_dst],
            "degrade_factor": 0.05,
            "advisories": linked[:4],
            "edges_named": [list(e) for e in named],
            "named_correctly": named_correctly,
        }))
        attribution.stop()
        bf.elastic.stop()
    finally:
        attribution.activate(None)
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert unsampled_shared, (
            "enabling the doctor changed the compiled train-step "
            "cache entries"
        )
        assert bitwise, (
            "enabling the doctor changed the training state bitwise"
        )
        assert overhead_pct <= 1.0, (
            f"doctor overhead {overhead_pct:.3f}% exceeds the 1% "
            f"acceptance bound at interval {default_interval}"
        )
        assert named_correctly, (
            f"degraded_link advisory failed to name the injected edge "
            f"({kill_src}, {kill_dst}): named {named}"
        )
    return 0


def run_health() -> int:
    """Fleet-health-plane evidence (``BENCH_MODE=health``, committed as
    HEALTH_EVIDENCE.json). Four claims, each measured the way it is
    resolvable (the metrics/attribution noise-floor lessons apply):

    1. **Decay tracks the spectrum**: a pure consensus problem is
       gossiped through the REAL eager combine on ring and Exp2; the
       observatory's fitted per-step decay must land within the
       disclosed tolerance of the SLEM prediction on both, and the
       Exp2-mixes-faster-than-ring ordering must hold (the paper's
       whole premise, now a machine-checked artifact).
    2. **Overhead <= 1 % at the default interval**: the health plane's
       per-sample cost (host fits + the push-sum lane dispatch) is
       measured by sampling EVERY step against a health-off stepper in
       a step-level rotation (all orderings) and amortized over the
       default interval; an off/off A/A control discloses the noise
       floor. Structural pin: enabling health adds no train-step cache
       entry (lane programs live under ``health_pushsum`` keys);
       bitwise pin: health on/off training state identical to the bit.
    3. **In-band aggregation is correct**: the device push-sum lane on
       a weighted digraph with one dead rank vs the numpy oracle.
    4. **Degraded-link chaos**: a lossy link (5 % delivery on one
       directed ring edge, replayed deterministically) measurably slows
       mixing below the spectral promise; ``mixing_degraded`` must fire
       and its suspect join must name the injected edge.
    """
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_HEALTH_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import itertools
    import time as time_mod

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu import health
    from bluefog_tpu import metrics as bf_metrics

    devices = jax.devices()
    n = min(len(devices), int(os.environ.get("BENCH_HEALTH_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_HEALTH_DIM", "256"))
    layers = int(os.environ.get("BENCH_HEALTH_LAYERS", "6"))
    batch = int(os.environ.get("BENCH_HEALTH_BATCH", "16"))
    samples = max(18, int(os.environ.get("BENCH_HEALTH_SAMPLES", "60")))
    decay_steps = int(os.environ.get("BENCH_HEALTH_DECAY_STEPS", "40"))
    tolerance = 0.15  # |ln(measured)/ln(predicted) - 1| bound, disclosed

    old_env = {
        k: os.environ.get(k)
        for k in ("BLUEFOG_HEALTH", "BLUEFOG_HEALTH_INTERVAL",
                  "BLUEFOG_HEALTH_PORT", "BLUEFOG_HEALTH_FILE",
                  "BLUEFOG_HEALTH_ROUNDS", "BLUEFOG_METRICS",
                  "BLUEFOG_DOCTOR")
    }
    for k in old_env:
        os.environ.pop(k, None)
    default_interval = health.health_interval()

    bf.init(devices=devices[:n])
    ctx = bf.get_context()
    rng = np.random.RandomState(0)

    # -- claim 1: measured decay vs the spectral prediction ------------------
    decay_lines = {}
    for name, graph in (
        ("ring", topo.RingGraph(n)),
        ("exp2", topo.ExponentialTwoGraph(n)),
    ):
        bf.set_topology(graph)
        w = topo.mixing_matrix(graph)
        predicted = topo.consensus_decay_rate(w)
        plane = health.start(interval=1)
        x = bf.worker_values(
            lambda r: rng.randn(4096).astype(np.float32)
        )
        last = None
        d0 = None
        for t in range(decay_steps):
            x = bf.neighbor_allreduce(x)  # the real eager combine
            xs = np.asarray(x, np.float64)
            d = float(
                np.sqrt(((xs - xs.mean(0)) ** 2).sum(1)).mean()
            )
            d0 = d if d0 is None else d0
            if d < d0 * 1e-4:
                # the f32 combine's rounding floor is ~1e-6 of the
                # payload scale: feeding the plateau to the fit would
                # measure the noise floor, not the mixing rate
                break
            last = plane.observe(ctx, step=t, consensus=d)
        eff = last.get("mixing_efficiency")
        line = {
            "metric": "health_decay",
            "topology": name,
            "n_workers": n,
            "predicted_rate": round(predicted, 6),
            "measured_rate": last.get("measured_rate"),
            "mixing_efficiency": eff,
            "rate_ratio": eff,
            "tolerance": tolerance,
            "within_tolerance": (
                eff is not None and abs(eff - 1.0) <= tolerance
            ),
            "time_to_eps_steps": last.get("time_to_eps_steps"),
            "eps": last.get("eps"),
            "steps": decay_steps,
        }
        decay_lines[name] = line
        print(json.dumps(line))
        health.stop()
    exp2_faster = (
        decay_lines["exp2"]["measured_rate"] is not None
        and decay_lines["ring"]["measured_rate"] is not None
        and decay_lines["exp2"]["measured_rate"]
        < decay_lines["ring"]["measured_rate"]
    )
    print(json.dumps({
        "metric": "health_decay_ordering",
        "exp2_mixes_faster_than_ring": exp2_faster,
        "ring_measured": decay_lines["ring"]["measured_rate"],
        "exp2_measured": decay_lines["exp2"]["measured_rate"],
    }))

    # -- claim 3: in-band push-sum lane vs the numpy oracle ------------------
    bf.set_topology(topo.ExponentialTwoGraph(n))
    w = topo.mixing_matrix(bf.load_topology())
    vals = rng.rand(n, len(health.FLEET_FIELDS)) * 10.0
    dead = [n - 2] if n > 2 else []
    dev = health.fleet_aggregate(ctx, vals, rounds=12, w=w, dead=dead)
    ora = health.fleet_aggregate_np(w, vals, rounds=12, dead=dead)
    live = [j for j in range(n) if j not in dead]
    true_mean = vals[live].mean(axis=0)
    lane_err = float(np.max(np.abs(
        np.array(dev["mean"]) - np.array(ora["mean"])
    )))
    minmax_exact = bool(
        np.allclose(dev["min"], vals[live].min(axis=0))
        and np.allclose(dev["max"], vals[live].max(axis=0))
    )
    mean_err = float(np.max(np.abs(
        (np.array(dev["mean"]) - true_mean)
        / np.maximum(np.abs(true_mean), 1e-12)
    )))
    print(json.dumps({
        "metric": "health_fleet",
        "n_workers": n,
        "dead_ranks": dead,
        "rounds": 12,
        "lane_vs_oracle_max_err": lane_err,
        "minmax_exact_over_live": minmax_exact,
        "mean_rel_err_vs_true": round(mean_err, 6),
        "fleet_residual": dev["residual"],
    }))
    lane_ok = lane_err < 1e-3 and minmax_exact and mean_err < 0.05

    # -- claim 2: overhead / structural / bitwise pins -----------------------
    w0 = [
        (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        for _ in range(layers)
    ]
    xs_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )
    ys_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )

    def loss_fn(p, x, y):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    def make_stepper():
        opt = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.01, momentum=0.9)
        )
        train_step = bf.make_train_step(opt, loss_fn)
        params = {
            f"w{i}": bf.worker_values(lambda r, i=i: w0[i])
            for i in range(layers)
        }
        carry = [(params, opt.init(params))]

        def _step():
            p, s = carry[0]
            p, s, loss = train_step(p, s, xs_b, ys_b)
            carry[0] = (p, s)
            return loss

        return _step, carry

    # structural pin: enabling health adds no train-step cache entry
    health.stop()
    stepper, _carry = make_stepper()
    stepper()
    stepper()

    def train_keys():
        return {
            k for k in ctx.op_cache
            if isinstance(k, tuple) and k
            and k[0] in ("opt_step", "opt_fused_step")
        }

    keys_off = train_keys()
    health.start(interval=1)
    stepper()
    stepper()
    keys_on = train_keys()
    lane_keys = [
        k for k in ctx.op_cache
        if isinstance(k, tuple) and k and k[0] == "health_pushsum"
    ]
    unsampled_shared = keys_on == keys_off
    health.stop()

    # bitwise trajectory pin
    state_bits = {}
    for variant in ("off", "on"):
        if variant == "on":
            health.start(interval=3)
        else:
            health.stop()
        _step, carry = make_stepper()
        for _ in range(12):
            _step()
        state_bits[variant] = jax.tree_util.tree_leaves(carry[0])
    health.stop()
    bitwise = all(
        bool(np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(state_bits["off"], state_bits["on"])
    )

    # overhead at the default interval, all-orderings rotation + A/A
    steppers = {}
    plane_on = health.HealthPlane(interval=1)
    for variant in ("off", "on", "off2"):
        health.activate(plane_on if variant == "on" else None)
        steppers[variant], _ = make_stepper()
        steppers[variant]()  # compile (+ lane compile for "on")
        _settle(steppers[variant]())
    orders = list(itertools.permutations(("off", "on", "off2")))
    times = {v: [] for v in steppers}
    for i in range(samples):
        for variant in orders[i % len(orders)]:
            health.activate(plane_on if variant == "on" else None)
            t0 = time_mod.perf_counter()
            _settle(steppers[variant]())
            times[variant].append(time_mod.perf_counter() - t0)
    health.activate(None)

    def median(v):
        v = sorted(v)
        return v[len(v) // 2] if v else 0.0

    base_s = median(times["off"])
    sample_extra_s = median(
        [on - off for off, on in zip(times["off"], times["on"])]
    )
    control_extra_s = median(
        [o2 - off for off, o2 in zip(times["off"], times["off2"])]
    )
    overhead_pct = (
        100.0 * sample_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    control_pct = (
        100.0 * control_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    print(json.dumps({
        "metric": "health_overhead",
        "n_workers": n,
        "payload_mb": round(layers * dim * dim * 4 / 1e6, 2),
        "interval": default_interval,
        "ms_per_step_off": round(base_s * 1e3, 3),
        "ms_sampled_step_extra": round(sample_extra_s * 1e3, 3),
        "overhead_pct": round(overhead_pct, 3),
        "control_aa_pct": round(control_pct, 3),
        "unsampled_program_shared": unsampled_shared,
        "health_lane_programs": len(lane_keys),
        "bitwise_identical": bitwise,
        "samples": samples,
    }))

    # -- claim 4: lossy link slows mixing; mixing_degraded names it ----------
    bf.shutdown()
    bf.init(devices=devices[:n])
    ctx = bf.get_context()
    ring = topo.RingGraph(n)
    bf.set_topology(ring)
    w = topo.mixing_matrix(ring)
    kill_src = int(os.environ.get("BENCH_HEALTH_DEGRADE_RANK", "2"))
    kill_dst = (kill_src + 1) % n
    factor = 0.05
    session = bf.elastic.start(policy="average")
    session.inject(
        "degrade", rank=kill_src, step=0, factor=factor, peer=kill_dst
    )
    plane = health.start(interval=1)
    x = rng.randn(n, 64)
    healthy_steps = 30
    for t in range(healthy_steps + 60):
        y = w.T @ x
        if t >= healthy_steps:
            # deterministic lossy-link replay: only `factor` of the
            # transfer on the injected edge arrives; the receiver keeps
            # its own value for the dropped fraction (the chaos-layer
            # model a real flaky ICI link reduces to)
            y[kill_dst] += (1.0 - factor) * w[kill_src, kill_dst] * (
                x[kill_dst] - x[kill_src]
            )
        x = y
        d = float(np.sqrt(((x - x.mean(0)) ** 2).sum(1)).mean())
        plane.observe(ctx, step=t, consensus=d)
    mix_advs = [
        a.to_json() for a in plane.advisories
        if a.kind == "mixing_degraded"
    ]
    named = sorted({
        tuple(e) for a in mix_advs
        for e in a.get("suspect_edges", []) if isinstance(e, list)
    })
    named_correctly = (kill_src, kill_dst) in named
    healthy_eff = None
    degraded_eff = None
    for s in plane.samples:
        if s.get("mixing_efficiency") is None:
            continue
        if s["step"] < healthy_steps:
            healthy_eff = s["mixing_efficiency"]
        else:
            degraded_eff = s["mixing_efficiency"]
    print(json.dumps({
        "metric": "health_mixing_degraded",
        "injected_edge": [kill_src, kill_dst],
        "degrade_factor": factor,
        "healthy_efficiency": healthy_eff,
        "degraded_efficiency": degraded_eff,
        "advisories": mix_advs[:3],
        "edges_named": [list(e) for e in named],
        "named_correctly": named_correctly,
    }))
    health.stop()
    bf.elastic.stop()

    bf_metrics.flush()
    for k, v in old_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        for name, line in decay_lines.items():
            assert line["within_tolerance"], (
                f"{name}: measured decay "
                f"{line['measured_rate']} outside the {tolerance} "
                f"tolerance of the spectral prediction "
                f"{line['predicted_rate']}"
            )
        assert exp2_faster, (
            "Exp2 did not measure faster mixing than ring: "
            f"{decay_lines}"
        )
        assert lane_ok, "push-sum lane diverged from the numpy oracle"
        assert unsampled_shared, (
            "enabling the health plane changed the compiled "
            "train-step cache entries"
        )
        assert bitwise, (
            "enabling the health plane changed the training state "
            "bitwise"
        )
        assert overhead_pct <= 1.0, (
            f"health overhead {overhead_pct:.3f}% exceeds the 1% "
            f"acceptance bound at interval {default_interval}"
        )
        assert named_correctly, (
            f"mixing_degraded failed to name the injected edge "
            f"({kill_src}, {kill_dst}): named {named}"
        )
    return 0


def run_slo() -> int:
    """Fleet-SLO-engine evidence (``BENCH_MODE=slo``, committed as
    SLO_EVIDENCE.json). Five claims, each measured the way it is
    resolvable (the metrics/health noise-floor lessons apply):

    1. **Pages within the documented bound, zero false alarms**: a
       hard fault (availability to zero) must raise ``slo_fast_burn``
       within ``page_sample_bound`` sampled evaluations of onset, and
       a 600-sample clean A/A series must raise nothing.
    2. **The slow window catches ramps the hygiene never trips on**: a
       slowly densifying error pattern (spacing 40 -> 8 samples over
       600) keeps the fast window silent AND never arms the doctor's
       EWMA+MAD two-streak rule on the rolling success fraction — the
       baseline adapts, by design — yet ``slo_slow_burn`` fires
       against the fixed target.
    3. **The canary flips on a lossy link and names the edge**: the
       512-element known-signal probe through the REAL quantized wire
       is bit-clean (vs the wire-exact numpy replay) on a healthy
       fabric and flags exactly the chaos-degraded edge when one is
       injected.
    4. **Overhead <= 1 % at the default interval**: sampled-step cost
       (resolver reads + canary dispatch) measured by an all-orderings
       step-level rotation with an off/off A/A noise-floor control.
       Structural pin: enabling SLO adds no train-step cache entry
       (canary programs live under ``slo_canary`` keys); bitwise pin:
       slo on/off training state identical to the bit.
    5. **Burn math matches the numpy oracle at fleet scale**: a 10 %
       churn storm on an N=1024 ``bf.fleetsim`` fleet drives a
       participation objective; the engine's fast/slow burn and budget
       accounting must match a from-scratch numpy recomputation
       exactly at EVERY step, and the storm must page within the
       documented bound.
    """
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_SLO_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import itertools
    import time as time_mod

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import fleetsim
    from bluefog_tpu import slo
    from bluefog_tpu import metrics as bf_metrics
    from bluefog_tpu.attribution import BaselineTracker
    from bluefog_tpu.collective.plan import plan_from_topology

    devices = jax.devices()
    n = min(len(devices), int(os.environ.get("BENCH_SLO_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_SLO_DIM", "256"))
    layers = int(os.environ.get("BENCH_SLO_LAYERS", "6"))
    batch = int(os.environ.get("BENCH_SLO_BATCH", "16"))
    samples = max(18, int(os.environ.get("BENCH_SLO_SAMPLES", "60")))

    old_env = {
        k: os.environ.get(k)
        for k in ("BLUEFOG_SLO", "BLUEFOG_SLO_INTERVAL",
                  "BLUEFOG_SLO_FILE", "BLUEFOG_SLO_CANARY",
                  "BLUEFOG_METRICS", "BLUEFOG_HEALTH",
                  "BLUEFOG_DOCTOR")
    }
    for k in old_env:
        os.environ.pop(k, None)
    default_interval = slo.slo_interval()

    def probe_objective(**kw):
        base = dict(
            name="probe_avail", series="bench.synthetic", target=0.99,
            comparison="ge", window=240, budget_frac=0.05,
            fast_window=5, fast_burn=8.0, slow_window=60,
            slow_burn=2.0,
        )
        base.update(kw)
        return slo.Objective(**base)

    # -- claim 1: fault pages within the bound; A/A zero false alarms --------
    obj = probe_objective()
    bound = slo.page_sample_bound(
        obj.fast_window, obj.fast_burn, obj.budget_frac
    )
    eng = slo.SLOEngine(interval=1, objectives=[obj], canary=False)
    for t in range(obj.window):
        eng.observe(None, step=t, values={"probe_avail": 1.0})
    warmup_alerts = len(eng.alerts)
    onset = obj.window
    fired_at = None
    for t in range(onset, onset + 20):
        eng.observe(None, step=t, values={"probe_avail": 0.0})
        if any(a.kind == "slo_fast_burn" for a in eng.alerts):
            fired_at = t
            break
    samples_to_page = (
        fired_at - onset + 1 if fired_at is not None else None
    )
    eng_aa = slo.SLOEngine(
        interval=1, objectives=[probe_objective()], canary=False
    )
    aa_steps = 600
    for t in range(aa_steps):
        eng_aa.observe(None, step=t, values={"probe_avail": 1.0})
    print(json.dumps({
        "metric": "slo_page_bound",
        "fast_window": obj.fast_window,
        "fast_burn_threshold": obj.fast_burn,
        "budget_frac": obj.budget_frac,
        "page_sample_bound": bound,
        "samples_to_page": samples_to_page,
        "paged_within_bound": (
            samples_to_page is not None and samples_to_page <= bound
        ),
        "warmup_false_alarms": warmup_alerts,
        "aa_steps": aa_steps,
        "aa_false_alarms": len(eng_aa.alerts),
    }))
    page_ok = (
        samples_to_page is not None and samples_to_page <= bound
        and warmup_alerts == 0 and not eng_aa.alerts
    )

    # -- claim 2: slow ramp caught; EWMA+MAD hygiene correctly silent --------
    obj_b = probe_objective(name="ramp_avail")
    eng_b = slo.SLOEngine(interval=1, objectives=[obj_b], canary=False)
    tracker = BaselineTracker()
    rolling: list = []
    last_bad = None
    max_z = 0.0
    streak = 0
    hygiene_armed = False
    warmup_steps = 60  # clean preamble: the baseline the ramp erodes
    ramp_steps = 600
    bad_count = 0
    for t in range(warmup_steps + ramp_steps):
        # error spacing densifies 40 -> 8 samples: a ramp, not a step
        r = max(0, t - warmup_steps)
        spacing = max(8, int(round(40 - 32 * r / (ramp_steps - 1))))
        bad = t >= warmup_steps and (
            last_bad is None or (t - last_bad) >= spacing
        )
        if bad:
            last_bad = t
            bad_count += 1
        eng_b.observe(
            None, step=t, values={"ramp_avail": 0.0 if bad else 1.0}
        )
        # the doctor's view: rolling success fraction through the
        # EWMA+MAD baseline with the two-consecutive-outlier streak
        # rule every PR-9 detector uses — it adapts to the ramp
        rolling.append(0.0 if bad else 1.0)
        del rolling[:-60]
        z = tracker.update(sum(rolling) / len(rolling))
        max_z = max(max_z, abs(z))
        streak = streak + 1 if abs(z) >= 3.0 else 0
        hygiene_armed = hygiene_armed or streak >= 2
    ramp_kinds = sorted({a.kind for a in eng_b.alerts})
    slow_caught = (
        "slo_slow_burn" in ramp_kinds
        and "slo_fast_burn" not in ramp_kinds
        and not hygiene_armed
    )
    print(json.dumps({
        "metric": "slo_slow_ramp",
        "ramp_steps": ramp_steps,
        "bad_samples": bad_count,
        "alert_kinds": ramp_kinds,
        "fast_window_silent": "slo_fast_burn" not in ramp_kinds,
        "slow_window_fired": "slo_slow_burn" in ramp_kinds,
        "hygiene_max_abs_z": round(max_z, 3),
        "hygiene_streak_armed": hygiene_armed,
    }))

    # -- claim 3: canary flips on a lossy link and names the edge ------------
    bf.init(devices=devices[:n])
    ctx = bf.get_context()
    wire = os.environ.get("BENCH_SLO_WIRE", "int8")
    plan = plan_from_topology(ctx.load_topology())
    eng_c = slo.SLOEngine(interval=1, objectives=[], canary=True)
    clean = eng_c.canary.probe(ctx, plan, wire)
    kill_src = int(os.environ.get("BENCH_SLO_DEGRADE_RANK", "2"))
    kill_dst = int(os.environ.get("BENCH_SLO_DEGRADE_PEER", "3"))
    session = bf.elastic.start(policy="average")
    session.inject(
        "degrade", rank=kill_src, step=0, factor=0.05, peer=kill_dst
    )
    eng_c._canary_probe(ctx, plan, wire, step=0)
    lossy = eng_c.canary.last
    named = sorted({(e[0], e[1]) for e in lossy["edges"]})
    canary_advs = [
        a.to_json() for a in eng_c.alerts
        if a.kind == "slo_canary_failed"
    ]
    bf.elastic.stop()
    canary_ok = (
        clean["ok"] and not lossy["ok"]
        and named == [(kill_src, kill_dst)] and bool(canary_advs)
    )
    print(json.dumps({
        "metric": "slo_canary",
        "wire": wire,
        "probe_elems": slo.CANARY_ELEMS,
        "rounds": clean["rounds"],
        "tolerance": slo.CANARY_TOL,
        "clean_ok": clean["ok"],
        "clean_max_dev": clean["max_dev"],
        "injected_edge": [kill_src, kill_dst],
        "lossy_ok": lossy["ok"],
        "lossy_max_dev": lossy["max_dev"],
        "edges_named": [list(e) for e in named],
        "named_correctly": named == [(kill_src, kill_dst)],
        "advisory_fired": bool(canary_advs),
    }))

    # -- claim 4: overhead / structural / bitwise pins -----------------------
    rng = np.random.RandomState(0)
    w0 = [
        (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        for _ in range(layers)
    ]
    xs_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )
    ys_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )

    def loss_fn(p, x, y):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    def make_stepper():
        opt = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.01, momentum=0.9)
        )
        train_step = bf.make_train_step(opt, loss_fn)
        params = {
            f"w{i}": bf.worker_values(lambda r, i=i: w0[i])
            for i in range(layers)
        }
        carry = [(params, opt.init(params))]

        def _step():
            p, s = carry[0]
            p, s, loss = train_step(p, s, xs_b, ys_b)
            carry[0] = (p, s)
            return loss

        return _step, carry

    # structural pin: enabling slo adds no train-step cache entry
    slo.activate(None)
    stepper, _carry = make_stepper()
    stepper()
    stepper()

    def train_keys():
        return {
            k for k in ctx.op_cache
            if isinstance(k, tuple) and k
            and k[0] in ("opt_step", "opt_fused_step")
        }

    keys_off = train_keys()
    slo.activate(slo.SLOEngine(interval=1, canary=True))
    stepper()
    stepper()
    keys_on = train_keys()
    canary_keys = [
        k for k in ctx.op_cache
        if isinstance(k, tuple) and k and k[0] == "slo_canary"
    ]
    unsampled_shared = keys_on == keys_off
    slo.activate(None)

    # bitwise trajectory pin
    state_bits = {}
    for variant in ("off", "on"):
        slo.activate(
            slo.SLOEngine(interval=3, canary=True)
            if variant == "on" else None
        )
        _step, carry = make_stepper()
        for _ in range(12):
            _step()
        state_bits[variant] = jax.tree_util.tree_leaves(carry[0])
    slo.activate(None)
    bitwise = all(
        bool(np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(state_bits["off"], state_bits["on"])
    )

    # overhead at the default interval, all-orderings rotation + A/A
    steppers = {}
    eng_on = slo.SLOEngine(interval=1, canary=True)
    for variant in ("off", "on", "off2"):
        slo.activate(eng_on if variant == "on" else None)
        steppers[variant], _ = make_stepper()
        steppers[variant]()  # compile (+ canary compile for "on")
        _settle(steppers[variant]())
    orders = list(itertools.permutations(("off", "on", "off2")))
    times = {v: [] for v in steppers}
    for i in range(samples):
        for variant in orders[i % len(orders)]:
            slo.activate(eng_on if variant == "on" else None)
            t0 = time_mod.perf_counter()
            _settle(steppers[variant]())
            times[variant].append(time_mod.perf_counter() - t0)
    slo.activate(None)

    def median(v):
        v = sorted(v)
        return v[len(v) // 2] if v else 0.0

    base_s = median(times["off"])
    sample_extra_s = median(
        [on - off for off, on in zip(times["off"], times["on"])]
    )
    control_extra_s = median(
        [o2 - off for off, o2 in zip(times["off"], times["off2"])]
    )
    overhead_pct = (
        100.0 * sample_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    control_pct = (
        100.0 * control_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    print(json.dumps({
        "metric": "slo_overhead",
        "n_workers": n,
        "payload_mb": round(layers * dim * dim * 4 / 1e6, 2),
        "interval": default_interval,
        "ms_per_step_off": round(base_s * 1e3, 3),
        "ms_sampled_step_extra": round(sample_extra_s * 1e3, 3),
        "overhead_pct": round(overhead_pct, 3),
        "control_aa_pct": round(control_pct, 3),
        "unsampled_program_shared": unsampled_shared,
        "canary_programs": len(canary_keys),
        "bitwise_identical": bitwise,
        "samples": samples,
    }))
    bf.shutdown()

    # -- claim 5: N=1024 churn storm burn math vs the numpy oracle -----------
    nfleet = int(os.environ.get("BENCH_SLO_FLEET", "1024"))
    storm_step = 10
    storm = fleetsim.storm_plan(nfleet, 0.10, step=storm_step, seed=7)
    vf = fleetsim.VirtualFleet(
        nfleet, topology="exp2", policy="receiver", plan=storm,
        audit_edges=False, seed=0,
    )
    obj_e = probe_objective(
        name="participation", target=0.95, window=60, slow_window=30,
    )
    eng_e = slo.SLOEngine(interval=1, objectives=[obj_e], canary=False)
    flags_hist: list = []
    max_burn_err = 0.0
    max_budget_err = 0.0
    ticks = 40
    for t in range(ticks):
        vf.tick()
        frac = vf._live_count / nfleet
        eng_e.observe(None, step=t, values={"participation": frac})
        flags_hist.append(0 if frac >= obj_e.target else 1)
        snap = eng_e._state["participation"].snapshot()
        # from-scratch numpy oracle of the engine's burn/budget math
        for w, key in ((obj_e.fast_window, "burn_fast"),
                       (obj_e.slow_window, "burn_slow")):
            if len(flags_hist) < w:
                assert snap[key] is None
                continue
            bad = float(np.sum(np.asarray(flags_hist[-w:])))
            oracle = (bad / w) / obj_e.budget_frac
            max_burn_err = max(max_burn_err, abs(snap[key] - oracle))
        wnd = np.asarray(flags_hist[-obj_e.window:], dtype=np.float64)
        total = obj_e.budget_frac * obj_e.window
        spent = float(wnd.sum())
        oracle_remaining = max(0.0, total - spent)
        max_budget_err = max(
            max_budget_err,
            abs(snap["budget"]["remaining"] - oracle_remaining),
        )
    storm_page = next(
        (a for a in eng_e.alerts if a.kind == "slo_fast_burn"), None
    )
    storm_bound = slo.page_sample_bound(
        obj_e.fast_window, obj_e.fast_burn, obj_e.budget_frac
    )
    storm_paged_within = (
        storm_page is not None
        and storm_page.step - storm_step + 1 <= storm_bound
    )
    print(json.dumps({
        "metric": "slo_fleet_storm",
        "fleet_n": nfleet,
        "storm_step": storm_step,
        "storm_fraction": 0.10,
        "live_after": vf._live_count,
        "ticks": ticks,
        "max_burn_err_vs_oracle": max_burn_err,
        "max_budget_err_vs_oracle": max_budget_err,
        "page_step": (
            storm_page.step if storm_page is not None else None
        ),
        "page_sample_bound": storm_bound,
        "paged_within_bound": storm_paged_within,
        "exhausted": eng_e.exhausted_objectives(),
    }))

    # the shipped catalog, for the record next to the claims
    print(json.dumps({
        "metric": "slo_catalog",
        "default_interval": default_interval,
        "objectives": [
            o.to_json() for o in slo.default_objectives()
        ],
    }))

    bf_metrics.flush()
    for k, v in old_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert page_ok, (
            f"fault did not page within {bound} samples clean of "
            f"false alarms: paged in {samples_to_page}, warmup "
            f"{warmup_alerts}, A/A {len(eng_aa.alerts)}"
        )
        assert slow_caught, (
            "slow ramp separation failed: kinds "
            f"{ramp_kinds}, hygiene_armed {hygiene_armed}"
        )
        assert canary_ok, (
            f"canary failed: clean {clean}, lossy edges {named} vs "
            f"({kill_src}, {kill_dst})"
        )
        assert unsampled_shared, (
            "enabling the SLO engine changed the compiled train-step "
            "cache entries"
        )
        assert canary_keys, (
            "canary probe compiled no slo_canary program"
        )
        assert bitwise, (
            "enabling the SLO engine changed the training state "
            "bitwise"
        )
        assert overhead_pct <= 1.0, (
            f"slo overhead {overhead_pct:.3f}% exceeds the 1% "
            f"acceptance bound at interval {default_interval}"
        )
        assert max_burn_err == 0.0 and max_budget_err == 0.0, (
            "engine burn/budget math diverged from the numpy oracle: "
            f"burn {max_burn_err}, budget {max_budget_err}"
        )
        assert storm_paged_within, (
            f"N={nfleet} storm did not page within {storm_bound} "
            f"samples: {storm_page}"
        )
    return 0


def run_staleness() -> int:
    """Staleness-observatory evidence (``BENCH_MODE=staleness``,
    committed as STALENESS_EVIDENCE.json). Five claims, each measured
    the way it is resolvable (the metrics/health noise-floor lessons
    apply):

    1. **Sync age ≡ 0 (lane self-check)**: the two-program optimizer
       gossips the fresh iterate; every sampled per-edge delivered age
       must be exactly 0 with the lane's own provenance check green —
       plus the sidecar-accounting pin (``scaling.wire_payload_bytes``
       with ``lineage=True`` prices exactly LINEAGE_TAG_BYTES more).
    2. **Delayed age ≡ 1 + transition**: the fused ``delayed=True``
       path measures age 0 on the reseed step, 1 in steady state, and
       an observable age-0 transition at a mid-run topology swap.
    3. **Age-discounted mixing shrinks the health residual**: on a
       pure-consensus ``delayed=True`` run the raw efficiency reads
       ~0.6-0.7 (the zero-staleness SLEM overstates the promise); the
       stale-mixing companion-polynomial correction must land the
       adjusted efficiency strictly closer to 1.0.
    4. **Overhead <= 1 % at the default interval**: sampled-step extra
       cost measured by an all-orderings off/on/off rotation,
       amortized over the default interval, A/A control disclosed;
       structural pin (no new train-step cache entries; the lane lives
       under ``staleness_lane`` keys) and bitwise on/off trajectory
       pin.
    5. **Per-edge stall chaos**: an injected ``stall`` with
       ``steps=``/``peer=`` must produce exactly the expected measured
       age ramp on the injected edge (and ONLY that edge), and the
       ``staleness_breach`` advisory must name it.
    """
    from bluefog_tpu.platforms import ensure_cpu_device_count

    ensure_cpu_device_count(
        int(os.environ.get("BENCH_STALENESS_DEVICES", "8"))
    )
    import itertools
    import time as time_mod

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    jax.config.update("jax_platforms", "cpu")

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu import health, scaling, staleness
    from bluefog_tpu import metrics as bf_metrics

    devices = jax.devices()
    n = min(len(devices),
            int(os.environ.get("BENCH_STALENESS_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_STALENESS_DIM", "256"))
    layers = int(os.environ.get("BENCH_STALENESS_LAYERS", "6"))
    batch = int(os.environ.get("BENCH_STALENESS_BATCH", "16"))
    samples = max(18, int(os.environ.get("BENCH_STALENESS_SAMPLES",
                                         "60")))

    old_env = {
        k: os.environ.get(k)
        for k in ("BLUEFOG_STALENESS", "BLUEFOG_STALENESS_INTERVAL",
                  "BLUEFOG_STALENESS_BOUND", "BLUEFOG_STALENESS_FILE",
                  "BLUEFOG_METRICS", "BLUEFOG_HEALTH", "BLUEFOG_DOCTOR")
    }
    for k in old_env:
        os.environ.pop(k, None)
    default_interval = staleness.staleness_interval()

    bf.init(devices=devices[:n])
    ctx = bf.get_context()
    rng = np.random.RandomState(0)

    # -- claim 1: synchronous path age ≡ 0, sidecar priced --------------------
    bf.set_topology(topo.RingGraph(n))
    obs = staleness.start(interval=1)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.01))
    params = {"w": bf.worker_values(
        lambda r: rng.randn(4096).astype(np.float32)
    )}
    state = opt.init(params)
    grads = {"w": bf.worker_values(
        lambda r: np.zeros(4096, np.float32)
    )}
    sync_steps = 12
    for _ in range(sync_steps):
        params, state = opt.step(params, state, grads)
    sync_samples = list(obs.samples)
    ages_all_zero = all(
        s["age_max"] == 0.0 and s["lane_ok"] for s in sync_samples
    )
    sidecar_delta = (
        scaling.wire_payload_bytes(4096, 4, None, lineage=True)
        - scaling.wire_payload_bytes(4096, 4, None)
    )
    lane_bytes = bf_metrics.peek("bluefog.staleness.wire_bytes")
    print(json.dumps({
        "metric": "staleness_sync",
        "n_workers": n,
        "steps": sync_steps,
        "edges_per_sample": sync_samples[0]["edges"],
        "ages_all_zero": ages_all_zero,
        "lane_selfcheck_ok": all(s["lane_ok"] for s in sync_samples),
        "lineage_tag_bytes": scaling.LINEAGE_TAG_BYTES,
        "sidecar_delta_bytes": sidecar_delta,
        "sidecar_priced_in_wire_payload_bytes": (
            sidecar_delta == scaling.LINEAGE_TAG_BYTES
        ),
        "lane_wire_bytes_total": (
            lane_bytes.value if lane_bytes is not None else 0
        ),
    }))
    staleness.stop()

    # -- claim 2: delayed ≡ 1 steady state + swap transition ------------------
    def consensus_loss(p, x):
        return ((p["w"] - x) ** 2).mean()

    opt_d = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(0.0))
    ts = opt_d.make_train_step(consensus_loss, delayed=True)
    p_d = {"w": bf.worker_values(
        lambda r: np.random.RandomState(r).randn(2048)
        .astype(np.float32)
    )}
    s_d = opt_d.init(p_d)
    x_d = bf.worker_values(lambda r: np.zeros(2048, np.float32))
    obs = staleness.start(interval=1)
    pre_swap = 8
    for _ in range(pre_swap):
        p_d, s_d, _loss = ts(p_d, s_d, x_d)
    bf.set_topology(topo.ExponentialTwoGraph(n))
    for _ in range(6):
        p_d, s_d, _loss = ts(p_d, s_d, x_d)
    age_seq = [s["age_mean"] for s in obs.samples]
    steady_pre = age_seq[1:pre_swap]
    post = age_seq[pre_swap:]
    delayed_line = {
        "metric": "staleness_delayed",
        "n_workers": n,
        "age_sequence": age_seq,
        "seed_age_zero": age_seq[0] == 0.0,
        "steady_state_age_one": (
            bool(steady_pre) and all(a == 1.0 for a in steady_pre)
        ),
        "swap_transition_age_zero": bool(post) and post[0] == 0.0,
        "post_swap_steady_one": all(a == 1.0 for a in post[1:]),
    }
    print(json.dumps(delayed_line))
    staleness.stop()

    # -- claim 3: age-discounted mixing shrinks the health residual ----------
    bf.set_topology(topo.RingGraph(n))
    opt_r = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(0.0))
    ts_r = opt_r.make_train_step(consensus_loss, delayed=True)
    p_r = {"w": bf.worker_values(
        lambda r: np.random.RandomState(100 + r).randn(2048)
        .astype(np.float32)
    )}
    s_r = opt_r.init(p_r)
    obs = staleness.start(interval=1)
    plane = health.HealthPlane(interval=1)  # driven directly, not installed
    last = None
    for t in range(40):
        p_r, s_r, _loss = ts_r(p_r, s_r, x_d)
        w = np.asarray(p_r["w"], np.float64)
        d = float(np.sqrt(((w - w.mean(0)) ** 2).sum(1)).mean())
        last = plane.observe(ctx, step=t, consensus=d)
    eff = last.get("mixing_efficiency")
    eff_adj = last.get("mixing_efficiency_age_adjusted")
    residual_raw = abs(eff - 1.0) if eff is not None else None
    residual_adj = abs(eff_adj - 1.0) if eff_adj is not None else None
    print(json.dumps({
        "metric": "staleness_residual",
        "n_workers": n,
        "predicted_rate": last.get("predicted_rate"),
        "age_adjusted_rate": last.get("age_adjusted_rate"),
        "measured_rate": last.get("measured_rate"),
        "age_mean": last.get("age_mean"),
        "mixing_efficiency": eff,
        "mixing_efficiency_age_adjusted": eff_adj,
        "residual_raw": residual_raw,
        "residual_age_adjusted": residual_adj,
        "residual_shrinks": (
            residual_raw is not None and residual_adj is not None
            and residual_adj < residual_raw
        ),
    }))
    staleness.stop()

    # -- claim 4: overhead / structural / bitwise pins -----------------------
    w0 = [
        (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        for _ in range(layers)
    ]
    xs_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )
    ys_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )

    def loss_fn(p, x, y):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    def make_stepper():
        opt_s = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.01, momentum=0.9)
        )
        train_step = bf.make_train_step(opt_s, loss_fn)
        params_s = {
            f"w{i}": bf.worker_values(lambda r, i=i: w0[i])
            for i in range(layers)
        }
        carry = [(params_s, opt_s.init(params_s))]

        def _step():
            p, s = carry[0]
            p, s, loss = train_step(p, s, xs_b, ys_b)
            carry[0] = (p, s)
            return loss

        return _step, carry

    # structural pin: enabling staleness adds no train-step cache entry
    staleness.stop()
    stepper, _carry = make_stepper()
    stepper()
    stepper()

    def train_keys():
        return {
            k for k in ctx.op_cache
            if isinstance(k, tuple) and k
            and k[0] in ("opt_step", "opt_fused_step")
        }

    keys_off = train_keys()
    staleness.start(interval=1)
    stepper()
    stepper()
    keys_on = train_keys()
    lane_keys = [
        k for k in ctx.op_cache
        if isinstance(k, tuple) and k and k[0] == "staleness_lane"
    ]
    unsampled_shared = keys_on == keys_off
    staleness.stop()

    # bitwise trajectory pin
    state_bits = {}
    for variant in ("off", "on"):
        if variant == "on":
            staleness.start(interval=3)
        else:
            staleness.stop()
        _step, carry = make_stepper()
        for _ in range(12):
            _step()
        state_bits[variant] = jax.tree_util.tree_leaves(carry[0])
    staleness.stop()
    bitwise = all(
        bool(np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(state_bits["off"], state_bits["on"])
    )

    # overhead at the default interval, all-orderings rotation + A/A
    steppers = {}
    obs_on = staleness.StalenessObservatory(interval=1)
    for variant in ("off", "on", "off2"):
        staleness.activate(obs_on if variant == "on" else None)
        steppers[variant], _ = make_stepper()
        steppers[variant]()  # compile (+ lane compile for "on")
        _settle(steppers[variant]())
    orders = list(itertools.permutations(("off", "on", "off2")))
    times = {v: [] for v in steppers}
    for i in range(samples):
        for variant in orders[i % len(orders)]:
            staleness.activate(obs_on if variant == "on" else None)
            t0 = time_mod.perf_counter()
            _settle(steppers[variant]())
            times[variant].append(time_mod.perf_counter() - t0)
    staleness.activate(None)

    def median(v):
        v = sorted(v)
        return v[len(v) // 2] if v else 0.0

    base_s = median(times["off"])
    sample_extra_s = median(
        [on - off for off, on in zip(times["off"], times["on"])]
    )
    control_extra_s = median(
        [o2 - off for off, o2 in zip(times["off"], times["off2"])]
    )
    overhead_pct = (
        100.0 * sample_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    control_pct = (
        100.0 * control_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    print(json.dumps({
        "metric": "staleness_overhead",
        "n_workers": n,
        "payload_mb": round(layers * dim * dim * 4 / 1e6, 2),
        "interval": default_interval,
        "ms_per_step_off": round(base_s * 1e3, 3),
        "ms_sampled_step_extra": round(sample_extra_s * 1e3, 3),
        "overhead_pct": round(overhead_pct, 3),
        "control_aa_pct": round(control_pct, 3),
        "unsampled_program_shared": unsampled_shared,
        "staleness_lane_programs": len(lane_keys),
        "bitwise_identical": bitwise,
        "samples": samples,
    }))

    # -- claim 5: per-edge stall chaos → age spike + breach naming -----------
    bf.shutdown()
    bf.init(devices=devices[:n])
    ctx = bf.get_context()
    bf.set_topology(topo.RingGraph(n))
    stall_src = int(os.environ.get("BENCH_STALENESS_STALL_RANK", "2"))
    stall_dst = (stall_src + 1) % n
    hold_steps = 6
    stall_at = 4
    session = bf.elastic.start(policy="average")
    session.inject("stall", rank=stall_src, step=stall_at,
                   steps=hold_steps, peer=stall_dst)
    obs = staleness.start(interval=1)  # default bound 4 < spike of 6
    opt_c = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.01))
    guard = bf.elastic.guard(opt_c)
    p_c = {"w": bf.worker_values(
        lambda r: rng.randn(2048).astype(np.float32)
    )}
    s_c = opt_c.init(p_c)
    g_c = {"w": bf.worker_values(
        lambda r: np.zeros(2048, np.float32)
    )}
    for _ in range(stall_at + hold_steps + 4):
        p_c, s_c = guard.step(p_c, s_c, g_c)
    spike = [
        s["age_max"] for s in obs.samples
        if s.get("max_edge") == [stall_src, stall_dst]
    ]
    other_edges_clean = all(
        rec["max"] == 0.0
        for edge, rec in obs.report()["edge_ages"].items()
        if edge != f"{stall_src}->{stall_dst}"
    )
    breaches = [
        a.to_json() for a in obs.advisories
        if a.kind == "staleness_breach"
    ]
    named = sorted({
        tuple(e) for a in breaches for e in a.get("edges", [])
    })
    named_correctly = (
        named == [(stall_src, stall_dst)]
    )
    lane_ok_throughout = all(s["lane_ok"] for s in obs.samples)
    print(json.dumps({
        "metric": "staleness_chaos",
        "injected_edge": [stall_src, stall_dst],
        "hold_steps": hold_steps,
        "measured_spike_max": max(spike, default=0.0),
        "spike_matches_hold": max(spike, default=0.0) == hold_steps,
        "other_edges_age_zero": other_edges_clean,
        "bound": obs.bound,
        "breaches": breaches[:3],
        "edges_named": [list(e) for e in named],
        "named_correctly": named_correctly,
        "lane_selfcheck_ok": lane_ok_throughout,
    }))
    staleness.stop()
    bf.elastic.stop()

    bf_metrics.flush()
    for k, v in old_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert ages_all_zero, (
            "synchronous-path delivered age was not identically 0: "
            f"{sync_samples}"
        )
        assert sidecar_delta == scaling.LINEAGE_TAG_BYTES, (
            f"lineage sidecar mispriced: {sidecar_delta} != "
            f"{scaling.LINEAGE_TAG_BYTES}"
        )
        assert delayed_line["steady_state_age_one"], (
            f"delayed path steady-state age != 1: {age_seq}"
        )
        assert delayed_line["swap_transition_age_zero"], (
            f"topology-swap reseed transition not observed: {age_seq}"
        )
        assert residual_raw is not None and residual_adj is not None, (
            "health residual comparison incomplete: "
            f"raw={residual_raw} adj={residual_adj}"
        )
        assert residual_adj < residual_raw, (
            "age-discounted mixing did not shrink the residual: "
            f"raw={residual_raw} adj={residual_adj}"
        )
        assert unsampled_shared, (
            "enabling the staleness observatory changed the compiled "
            "train-step cache entries"
        )
        assert bitwise, (
            "enabling the staleness observatory changed the training "
            "state bitwise"
        )
        assert overhead_pct <= 1.0, (
            f"staleness overhead {overhead_pct:.3f}% exceeds the 1% "
            f"acceptance bound at interval {default_interval}"
        )
        assert max(spike, default=0.0) == hold_steps, (
            f"measured age spike {max(spike, default=0.0)} != injected "
            f"hold {hold_steps}"
        )
        assert other_edges_clean, "uninjected edges measured stale"
        assert named_correctly, (
            f"staleness_breach failed to name the injected edge "
            f"({stall_src}, {stall_dst}): named {named}"
        )
        assert lane_ok_throughout, "lane self-check failed under chaos"
    return 0


def run_autotune() -> int:
    """Closed-loop controller evidence (``BENCH_MODE=autotune``,
    committed as AUTOTUNE_EVIDENCE.json). Four claims, each measured
    the way it is resolvable (the metrics/health noise-floor lessons
    apply):

    1. **The loop closes on real telemetry** (``autotune_chaos``): a
       per-edge degrade fault slows the attribution doctor's probe
       dispatches deterministically; the ``degraded_link`` advisory
       names the edge from timings alone; the controller harvests it,
       searches, and migrates the LIVE guarded optimizer through the
       elastic repair path — the decision record names the edge in its
       trigger set, the installed matrix excludes (or down-weights)
       it, zero stale dispatches, and the doctor's own measured wire
       cost collapses back to the healthy level after the swap.
    2. **Mixing efficiency recovers** (``autotune_mixing_recovery``):
       the deterministic lossy-link consensus replay (the
       ``BENCH_MODE=health`` chaos model) degrades measured mixing
       below the spectral promise; ``mixing_degraded`` fires naming
       the edge; the controller routes around it and the measured
       efficiency (and the chaos-priced simulated step time, pinned
       calibration disclosed) recover past the gated thresholds. The
       same scenario re-run under ``dry_run`` records the full
       decision history with ZERO migrations (``autotune_dry_run``),
       and its audit trail round-trips through every surface —
       metrics, flight side table, JSONL,
       ``tools/autotune_report.py`` reconstruction, the health /fleet
       block (``autotune_audit``).
    3. **Overhead <= 1 % at the default interval**
       (``autotune_overhead``): controller-on (sampling every step,
       quiescent fabric) vs controller-off in a step-level all-
       orderings rotation, amortized over the default interval, with
       an off/off A/A control. Structural pin: enabling the
       controller adds no train-step cache entry; bitwise pin:
       controller-on/off training state identical to the bit (the
       controller never touches the dispatched program; only a
       migration bumps the topology version, and a quiescent fabric
       never migrates).
    """
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_AUTOTUNE_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import itertools
    import tempfile
    import time as time_mod

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu import attribution
    from bluefog_tpu import autotune
    from bluefog_tpu import flight as flight_mod
    from bluefog_tpu import health
    from bluefog_tpu import metrics as bf_metrics
    from bluefog_tpu.collective import compiler

    devices = jax.devices()
    n = min(len(devices),
            int(os.environ.get("BENCH_AUTOTUNE_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_AUTOTUNE_DIM", "256"))
    layers = int(os.environ.get("BENCH_AUTOTUNE_LAYERS", "6"))
    batch = int(os.environ.get("BENCH_AUTOTUNE_BATCH", "16"))
    samples = max(18, int(os.environ.get("BENCH_AUTOTUNE_SAMPLES",
                                         "60")))

    old_env = {
        k: os.environ.get(k)
        for k in ("BLUEFOG_AUTOTUNE", "BLUEFOG_AUTOTUNE_INTERVAL",
                  "BLUEFOG_AUTOTUNE_FILE", "BLUEFOG_AUTOTUNE_DRY_RUN",
                  "BLUEFOG_AUTOTUNE_COOLDOWN", "BLUEFOG_AUTOTUNE_WIRE",
                  "BLUEFOG_DOCTOR", "BLUEFOG_HEALTH",
                  "BLUEFOG_METRICS")
    }
    for k in old_env:
        os.environ.pop(k, None)
    default_interval = autotune.autotune_interval()
    rng = np.random.RandomState(0)

    # -- claim 1: the loop closes on real doctor telemetry -------------------
    bf.init(devices=devices[:n])
    ctx = bf.get_context()
    bf.set_topology(topo.RingGraph(n))
    compiler.calibrate()
    kill_src = int(os.environ.get("BENCH_AUTOTUNE_DEGRADE_RANK", "2"))
    kill_dst = (kill_src + 1) % n
    factor = 0.05
    session = bf.elastic.start(policy="average")
    session.inject("degrade", rank=kill_src, step=0, factor=factor,
                   peer=kill_dst)
    # doctor at interval 1: every step probes, so an occasional
    # blame-free sample under ambient load cannot open a quiet gap
    # long enough to reset the controller's trigger streak
    doc = attribution.start(interval=1)
    # the controller is driven explicitly with a PINNED step clock for
    # its verification channel (an ambient-load spike on the shared
    # host would otherwise roll a good migration back — guardrail
    # working as designed, noise this evidence must not depend on);
    # the measured step-time recovery channel below is the doctor's
    # probe-measured wire cost, which IS wall clock
    tuner = autotune.TopologyAutotuner(interval=1, cooldown=8)
    opt = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(0.05))
    guard = bf.elastic.guard(opt)
    params = {"w": bf.worker_values(
        lambda r: rng.randn(4096).astype(np.float32)
    )}
    state = opt.init(params)
    zeros = {"w": bf.worker_values(np.zeros(4096, np.float32))}
    w_before = topo.mixing_matrix(bf.load_topology()).copy()
    for _t in range(14):
        params, state = guard.step(params, state, zeros)
        tuner.observe(ctx, step=_t, optimizer=opt, step_s=0.01)
    named = sorted({
        tuple(a.detail["edge"]) for a in doc.advisories
        if a.kind == "degraded_link" and a.detail.get("edge")
    })
    detected = (kill_src, kill_dst) in named
    swap = next(
        (d for d in tuner.decisions if d.action == "swap"), None
    )
    trigger_names_edge = bool(swap) and any(
        t.get("edge") == [kill_src, kill_dst] for t in swap.triggers
    )
    w_after = topo.mixing_matrix(bf.load_topology())
    migrated_excludes = bool(
        w_after[kill_src, kill_dst] < w_before[kill_src, kill_dst]
    )
    wire_series = [
        s["comm_wire_ms"] for s in doc.samples
        if s.get("comm_wire_ms") is not None
    ]
    wire_degraded = max(wire_series[:2], default=0.0)
    wire_recovered = min(wire_series[-2:], default=0.0)
    wire_ratio = (
        wire_degraded / wire_recovered if wire_recovered > 0 else None
    )
    finite = bool(np.all(np.isfinite(np.asarray(params["w"]))))
    chaos_line = {
        "metric": "autotune_chaos",
        "n_workers": n,
        "injected_edge": [kill_src, kill_dst],
        "degrade_factor": factor,
        "detected_by_doctor": detected,
        "edges_named": [list(e) for e in named],
        "decision_action": swap.action if swap else None,
        "chosen": swap.chosen if swap else None,
        "trigger_names_edge": trigger_names_edge,
        "predicted_gain_frac": (
            swap.predicted.get("gain_frac") if swap else None
        ),
        "migrated_excludes_edge": migrated_excludes,
        "edge_weight_before": round(
            float(w_before[kill_src, kill_dst]), 6
        ),
        "edge_weight_after": round(
            float(w_after[kill_src, kill_dst]), 6
        ),
        "comm_wire_degraded_ms": round(wire_degraded, 4),
        "comm_wire_recovered_ms": round(wire_recovered, 4),
        "comm_wire_recovery_ratio": (
            round(wire_ratio, 2) if wire_ratio else None
        ),
        "stale_dispatches": session.stale_dispatches,
        "training_state_finite": finite,
    }
    print(json.dumps(chaos_line))
    autotune.stop()
    attribution.stop()
    bf.elastic.stop()
    bf.shutdown()

    # -- claim 2: mixing recovery + dry run + audit trail --------------------
    # Deterministic host replay of the lossy link (the BENCH_MODE=health
    # chaos model) with a PINNED calibration so the chaos-priced
    # simulated step times are identical run to run (disclosed: the
    # step-time channel here is the chaos pricing, not a wall clock —
    # claim 1 carries the measured-wall-clock recovery).
    compiler.set_calibration(1e-4, 1e9, source="pinned-sim")
    tmp_dir = tempfile.mkdtemp(prefix="bf_autotune_bench_")
    jsonl_path = os.path.join(tmp_dir, "autotune.jsonl")

    def run_sim(dry_run):
        bf.init(devices=devices[:n])
        ctx = bf.get_context()
        bf.set_topology(topo.RingGraph(n))
        session = bf.elastic.start(policy="average")
        healthy_steps = 30
        session.inject("degrade", rank=kill_src, step=healthy_steps,
                       factor=factor, peer=kill_dst)
        plane = health.start(interval=1)
        tuner = autotune.start(interval=1, cooldown=8,
                               dry_run=dry_run)
        v0 = ctx.topo_version
        x = rng.randn(n, 64)
        B = compiler.DEFAULT_PAYLOAD_BYTES
        last_v = ctx.topo_version
        sim_ms = []
        for t in range(130):
            session.before_dispatch(None)
            if ctx.topo_version != last_v:
                last_v = ctx.topo_version
                x = rng.randn(n, 64)  # fresh signal for the new
                # graph's decay fit (the old series hit the fp floor)
            w = topo.mixing_matrix(bf.load_topology())
            y = w.T @ x
            for key, f in session.simulated_wire_factors().items():
                if isinstance(key, tuple):
                    s, d = key
                    if w[s, d] != 0.0:
                        y[d] += (1.0 - f) * w[s, d] * (x[d] - x[s])
            x = y
            dist = float(np.sqrt(((x - x.mean(0)) ** 2).sum(1)).mean())
            plane.observe(ctx, step=t, consensus=dist)
            pen = sum(
                compiler.degraded_round_penalty_s(B, f)
                for key, f in
                session.simulated_wire_factors().items()
                if isinstance(key, tuple)
                and w[key[0], key[1]] != 0.0
            )
            sim_ms.append((0.010 + pen) * 1e3)
            tuner.observe(ctx, step=t, step_s=0.010 + pen)
        return ctx, plane, tuner, sim_ms, v0

    os.environ["BLUEFOG_AUTOTUNE_FILE"] = jsonl_path
    ctx, plane, tuner, sim_ms, _v0 = run_sim(dry_run=False)
    mix_advs = [
        a for a in plane.advisories if a.kind == "mixing_degraded"
    ]
    adv_named = sorted({
        tuple(e) for a in mix_advs
        for e in a.detail.get("suspect_edges", [])
        if isinstance(e, list)
    })
    swap2 = next(
        (d for d in tuner.decisions if d.action == "swap"), None
    )
    eff_degraded = (
        mix_advs[0].detail.get("mixing_efficiency") if mix_advs
        else None
    )
    eff_baseline = (
        mix_advs[0].detail.get("baseline_efficiency") if mix_advs
        else None
    )
    rec_effs = [
        s["mixing_efficiency"] for s in plane.samples
        if s.get("mixing_efficiency") is not None
        and swap2 is not None and s["step"] > swap2.step + 5
    ]
    eff_recovered = rec_effs[-1] if rec_effs else None
    w_final = topo.mixing_matrix(bf.load_topology())
    step_degraded_ms = max(sim_ms)
    step_recovered_ms = sim_ms[-1]
    recovery_line = {
        "metric": "autotune_mixing_recovery",
        "n_workers": n,
        "injected_edge": [kill_src, kill_dst],
        "degrade_factor": factor,
        "advisory_fired": bool(mix_advs),
        "advisory_names_edge": (kill_src, kill_dst) in adv_named,
        "decision_action": swap2.action if swap2 else None,
        "chosen": swap2.chosen if swap2 else None,
        "efficiency_baseline": eff_baseline,
        "efficiency_degraded": eff_degraded,
        "efficiency_recovered": eff_recovered,
        "sim_step_degraded_ms": round(step_degraded_ms, 3),
        "sim_step_recovered_ms": round(step_recovered_ms, 3),
        "recovered_step_ratio": round(
            step_degraded_ms / max(step_recovered_ms, 1e-9), 2
        ),
        "migrated_excludes_edge": bool(
            w_final[kill_src, kill_dst] == 0.0
        ),
        "calibration": "pinned (alpha=1e-4s, beta=1e9B/s) — the "
                       "simulated step-time channel is the chaos "
                       "pricing, disclosed",
    }
    print(json.dumps(recovery_line))

    # audit trail: every surface carries the decision
    snap = bf_metrics.snapshot()
    dump = flight_mod._build_dump("bench")
    from tools.autotune_report import build_report

    dump_path = os.path.join(tmp_dir, "autotune_dump.json")
    tuner.dump(dump_path)
    recon_dump = build_report([dump_path])
    recon_jsonl = build_report([jsonl_path])
    fleet_block = plane.report().get("autotune") or {}
    audit_line = {
        "metric": "autotune_audit",
        "decisions": len(tuner.decisions),
        "metrics_decisions": snap.get(
            "bluefog.autotune.decisions", {}
        ).get("value"),
        "flight_side_table_has_swap": any(
            d.get("action") == "swap"
            for d in dump.get("autotune_decisions", [])
        ),
        "jsonl_reconstruction_matches": (
            recon_jsonl["decisions"] == len(tuner.decisions)
        ),
        "dump_reconstruction_matches": (
            recon_dump["decisions"] == len(tuner.decisions)
        ),
        "report_joins_verification": any(
            h.get("verification") is not None
            for h in recon_dump["history"]
            if h.get("action") == "swap"
        ),
        "fleet_block": fleet_block,
    }
    print(json.dumps(audit_line))
    os.environ.pop("BLUEFOG_AUTOTUNE_FILE", None)
    autotune.stop()
    health.stop()
    bf.elastic.stop()
    bf.shutdown()

    # dry run: same condition, full history, zero migrations
    ctx, plane, tuner_dry, _sim, v0 = run_sim(dry_run=True)
    v_end = ctx.topo_version
    dry_line = {
        "metric": "autotune_dry_run",
        "decisions": len(tuner_dry.decisions),
        "actions": sorted({
            d.action for d in tuner_dry.decisions
        }),
        "swaps": tuner_dry.swaps,
        "migrations_zero": bool(
            tuner_dry.swaps == 0 and v_end == v0
        ),
        "topo_version_end": v_end,
        "candidates_recorded": bool(
            tuner_dry.decisions
            and tuner_dry.decisions[0].candidates
        ),
    }
    print(json.dumps(dry_line))
    autotune.stop()
    health.stop()
    bf.elastic.stop()
    bf.shutdown()
    compiler.clear_calibration()

    # -- claim 3: overhead / structural / bitwise pins -----------------------
    bf.init(devices=devices[:n])
    ctx = bf.get_context()
    w0 = [
        (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        for _ in range(layers)
    ]
    xs_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )
    ys_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )

    def loss_fn(p, x, y):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    def make_stepper():
        opt = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.01, momentum=0.9)
        )
        train_step = bf.make_train_step(opt, loss_fn)
        params = {
            f"w{i}": bf.worker_values(lambda r, i=i: w0[i])
            for i in range(layers)
        }
        carry = [(params, opt.init(params))]

        def _step():
            p, s = carry[0]
            p, s, loss = train_step(p, s, xs_b, ys_b)
            carry[0] = (p, s)
            return loss

        return _step, carry

    # structural pin: enabling the controller adds no cache entry at all
    autotune.stop()
    stepper, _carry = make_stepper()
    stepper()
    stepper()
    keys_off = set(ctx.op_cache)
    autotune.start(interval=1)
    stepper()
    stepper()
    keys_on = set(ctx.op_cache)
    unsampled_shared = keys_on == keys_off
    autotune.stop()

    # bitwise trajectory pin
    state_bits = {}
    for variant in ("off", "on"):
        if variant == "on":
            autotune.start(interval=3)
        else:
            autotune.stop()
        _step, carry = make_stepper()
        for _ in range(12):
            _step()
        state_bits[variant] = jax.tree_util.tree_leaves(carry[0])
    autotune.stop()
    bitwise = all(
        bool(np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(state_bits["off"], state_bits["on"])
    )

    # overhead at the default interval, all-orderings rotation + A/A
    steppers = {}
    tuner_on = autotune.TopologyAutotuner(interval=1)
    for variant in ("off", "on", "off2"):
        autotune.activate(tuner_on if variant == "on" else None)
        steppers[variant], _ = make_stepper()
        steppers[variant]()
        _settle(steppers[variant]())
    orders = list(itertools.permutations(("off", "on", "off2")))
    times = {v: [] for v in steppers}
    for i in range(samples):
        for variant in orders[i % len(orders)]:
            autotune.activate(tuner_on if variant == "on" else None)
            t0 = time_mod.perf_counter()
            _settle(steppers[variant]())
            times[variant].append(time_mod.perf_counter() - t0)
    autotune.activate(None)

    def median(v):
        v = sorted(v)
        return v[len(v) // 2] if v else 0.0

    base_s = median(times["off"])
    sample_extra_s = median(
        [on - off for off, on in zip(times["off"], times["on"])]
    )
    control_extra_s = median(
        [o2 - off for off, o2 in zip(times["off"], times["off2"])]
    )
    overhead_pct = (
        100.0 * sample_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    control_pct = (
        100.0 * control_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    print(json.dumps({
        "metric": "autotune_overhead",
        "n_workers": n,
        "payload_mb": round(layers * dim * dim * 4 / 1e6, 2),
        "interval": default_interval,
        "ms_per_step_off": round(base_s * 1e3, 3),
        "ms_sampled_step_extra": round(sample_extra_s * 1e3, 3),
        "overhead_pct": round(overhead_pct, 3),
        "control_aa_pct": round(control_pct, 3),
        "unsampled_program_shared": unsampled_shared,
        "bitwise_identical": bitwise,
        "samples": samples,
    }))
    bf.shutdown()

    bf_metrics.flush()
    for k, v in old_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert detected, (
            f"doctor failed to name the injected edge "
            f"({kill_src}, {kill_dst}): named {named}"
        )
        assert swap is not None and trigger_names_edge, (
            f"no swap decision naming the injected edge: {chaos_line}"
        )
        assert migrated_excludes, (
            "migrated topology kept the blamed edge at full weight"
        )
        assert wire_ratio is not None and wire_ratio >= 2.0, (
            f"measured wire cost did not recover: {chaos_line}"
        )
        assert chaos_line["stale_dispatches"] == 0
        assert finite, "training state went non-finite across the swap"
        assert recovery_line["advisory_fired"] and \
            recovery_line["advisory_names_edge"], recovery_line
        assert recovery_line["migrated_excludes_edge"], recovery_line
        assert eff_recovered is not None and eff_recovered >= 0.9, (
            f"mixing efficiency did not recover: {recovery_line}"
        )
        assert recovery_line["recovered_step_ratio"] >= 2.0, (
            recovery_line
        )
        assert dry_line["migrations_zero"] and \
            dry_line["decisions"] >= 1, dry_line
        assert dry_line["actions"] == ["dry_run_swap"], dry_line
        assert audit_line["flight_side_table_has_swap"], audit_line
        assert audit_line["jsonl_reconstruction_matches"], audit_line
        assert audit_line["dump_reconstruction_matches"], audit_line
        assert audit_line["report_joins_verification"], audit_line
        assert unsampled_shared, (
            "enabling the controller changed the compiled cache entries"
        )
        assert bitwise, (
            "enabling the controller changed the training state bitwise"
        )
        assert overhead_pct <= 1.0, (
            f"autotune overhead {overhead_pct:.3f}% exceeds the 1% "
            f"acceptance bound at interval {default_interval}"
        )
    return 0


def run_async() -> int:
    """Asynchronous-gossip evidence (``BENCH_MODE=async``, committed as
    ASYNC_EVIDENCE.json): the straggler-immunity scenario synchronous
    gossip cannot reach, plus the correctness pins that make the async
    lane trustworthy. Five claims:

    1. **Straggler immunity** — one rank compute-dilated 10x (the
       ``slow`` chaos fault). Synchronous gossip's fleet throughput is
       gated by the slowest rank: every step costs
       ``max_r(dilation_r)`` local-step times, so the fleet runs at
       ~1/10 nominal. The async engine's measured participation ratio
       (real engine counters over the replayed cadence) stays within
       ~1/N of nominal: the slow rank costs only its own share. The
       tick clock is the virtual time base (a virtual CPU mesh has no
       physically slow chip — the dilation is the deterministic chaos
       replay, disclosed), while per-dispatch wall costs of both modes
       are measured for comparability.
    2. **Convergence** — the same quadratic consensus problem driven
       to convergence by both modes under the straggler; the async
       distance-to-optimum must land within tolerance of sync's.
    3. **Mass conservation** — random per-rank cadences x
       {fp32, int8_ef, int4_ef} wire tiers at lr=0: total push-sum x
       mass (window + pending buffers) and p mass pinned to f32
       rounding per tier (the sender absorbs its shipped quantization
       residual — exact by construction, not to quantization
       precision).
    4. **Bounded-staleness gate** — the 10x rank trips the
       ``BLUEFOG_ASYNC_MAX_AGE`` gate: delivered-age histogram, the
       ``async_staleness`` advisory naming the slow rank, and fresh
       edges staying at age <= cadence spread.
    5. **Async-off dispatch** — ``BLUEFOG_ASYNC=0`` returns the
       synchronous optimizer path, pinned bitwise over a multi-step
       trajectory.

    ``BENCH_ASSERT=1`` (default) enforces all bounds. See
    docs/async.md."""
    from bluefog_tpu.platforms import ensure_cpu_device_count

    ensure_cpu_device_count(
        int(os.environ.get("BENCH_ASYNC_DEVICES", "8"))
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    import collections

    import numpy as np
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu import windows as win_mod

    devices = jax.devices()
    n = min(len(devices), int(os.environ.get("BENCH_ASYNC_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_ASYNC_DIM", "4096"))
    dilation = float(os.environ.get("BENCH_ASYNC_DILATION", "10"))
    slow_rank = n - 2
    lr = 0.05
    rng = np.random.RandomState(0)
    z0 = rng.randn(n, dim).astype(np.float32)
    targets = z0 + rng.randn(n, dim).astype(np.float32)
    opt_point = targets.mean(axis=0)

    def loss_fn(p, target):
        return 0.5 * jnp.mean((p["w"] - target) ** 2)

    def median_ms(fn, reps=20):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(ts))

    lines = []

    # -- 1 + 2: straggler immunity + convergence ------------------------------
    # synchronous baseline (no chaos needed for the math: the collapse
    # is structural — each step is gated by the slowest participant)
    bf.init(devices=devices[:n])
    bf.set_topology(topo.RingGraph(n, connect_style=1))
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(lr))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    # timed below on the same inputs over and over: keep them
    sync_step = opt.make_train_step(loss_fn, donate=False)
    batch = jnp.asarray(targets)
    params, state, _ = sync_step(params, state, batch)  # compile
    sync_steps = int(os.environ.get("BENCH_ASYNC_STEPS", "120"))
    t_sync_ms = median_ms(
        lambda: jax.block_until_ready(
            sync_step(params, state, batch)[0]["w"]
        )
    )
    for _ in range(sync_steps):
        params, state, _ = sync_step(params, state, batch)
    dist_sync = float(
        np.abs(np.asarray(params["w"]) - opt_point).max()
    )
    bf.shutdown()

    # asynchronous run under the 10x straggler
    bf.init(devices=devices[:n])
    bf.set_topology(topo.RingGraph(n, connect_style=1))
    session = bf.elastic.start(policy="push_sum")
    session.inject("slow", rank=slow_rank, step=0, factor=dilation)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(lr))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    async_step = bf.make_async_train_step(opt, loss_fn, max_age=4)
    eng = async_step.engine
    params, state, _ = async_step(params, state, batch)  # compile
    t_tick_ms = median_ms(
        lambda: jax.block_until_ready(
            async_step(params, state, batch)[0]["w"]
        )
    )
    ages_hist: collections.Counter = collections.Counter()
    # ages of edges NOT sourced at the dilated rank, tracked separately:
    # the "fresh edges stay within the bound" claim must be a real
    # measurement over the healthy edges, not a tautology over ages
    # already filtered to <= max_age
    healthy_hist: collections.Counter = collections.Counter()
    ticks = int(os.environ.get("BENCH_ASYNC_TICKS", "240"))
    while eng._tick < ticks:
        params, state, _ = async_step(params, state, batch)
        win = win_mod._get_win(bf.get_context(), eng._name)
        for r, srcs in enumerate(win.in_neighbors):
            for k, s in enumerate(srcs):
                a = int(win.clock - win.slot_written[r, k])
                ages_hist[a] += 1
                if s != slow_rank:
                    healthy_hist[a] += 1
    dist_async = float(
        np.abs(np.asarray(params["w"]) - opt_point).max()
    )
    # fleet throughput on the shared virtual time base (the tick = one
    # undilated local-step time): sync's per-step cost is gated by the
    # slowest rank; async's measured participation is the engine's own
    # counter over the deterministic cadence replay
    participation = eng._local_steps / (eng._tick * n)
    fleet_ratio_async = participation
    fleet_ratio_sync = 1.0 / max(dilation, 1.0)
    gate_advisory = eng.advisories[0] if eng.advisories else None
    lines.append({
        "metric": "async_straggler",
        "workers": n,
        "dim": dim,
        "slow_rank": slow_rank,
        "dilation": dilation,
        "ticks": eng._tick,
        "local_steps": eng._local_steps,
        "fleet_ratio_async": round(fleet_ratio_async, 4),
        "fleet_ratio_sync": round(fleet_ratio_sync, 4),
        "within_1_over_n": bool(
            fleet_ratio_async >= 1.0 - 1.5 / n
        ),
        "sync_collapse": bool(
            fleet_ratio_sync <= 1.5 / dilation
        ),
        "measured_sync_step_ms": round(t_sync_ms, 3),
        "measured_async_tick_ms": round(t_tick_ms, 3),
        "dilation_model": (
            "simulated: deterministic slow-fault cadence replay on the "
            "tick clock (virtual CPU mesh has no physically slow "
            "chip); per-dispatch wall costs measured above"
        ),
    })
    lines.append({
        "metric": "async_convergence",
        "steps_sync": sync_steps,
        "ticks_async": eng._tick,
        "dist_to_opt_sync": dist_sync,
        "dist_to_opt_async": dist_async,
        "tolerance_factor": 3.0,
        "within_tolerance": bool(
            dist_async <= 3.0 * dist_sync + 1e-3
        ),
    })
    # -- 4: the bounded-staleness gate ---------------------------------------
    # worst age over ALL edges not sourced at the slow rank — a real
    # measurement of "healthy edges never trip the gate"
    fresh_max = max(healthy_hist, default=0)
    lines.append({
        "metric": "async_staleness_gate",
        "max_age": eng.max_age,
        "policy": eng.policy,
        "age_hist": {
            str(a): int(c) for a, c in sorted(ages_hist.items())
        },
        "age_max": int(max(ages_hist)),
        "stale_drops": eng._stale_drops,
        "gate_engaged": bool(eng._stale_drops > 0),
        "advisory_present": gate_advisory is not None,
        "advisory_names_slow_rank": bool(
            gate_advisory is not None
            and slow_rank in gate_advisory.detail["slow_ranks"]
        ),
        "advisory_edges": (
            gate_advisory.detail["edges"] if gate_advisory else []
        ),
        "fresh_edges_within_bound": int(fresh_max),
    })
    gate = lines[-1]
    straggler = lines[0]
    conv = lines[1]
    bf.elastic.stop()
    bf.shutdown()

    # -- 3: mass conservation per wire tier ----------------------------------
    tiers = {}
    for tier in ("fp32", "int8_ef", "int4_ef"):
        bf.init(devices=devices[:n])
        bf.set_topology(topo.RingGraph(n, connect_style=1))
        trng = np.random.RandomState(5)
        cadence = {
            r: int(p) for r, p in enumerate(trng.randint(1, 5, n))
        }
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
        params = {"w": jnp.asarray(z0)}
        state = opt.init(params)
        step = bf.make_async_train_step(
            opt, loss_fn, cadence=cadence, wire=tier, max_age=10 ** 6
        )
        mass0 = float(np.sum(z0, dtype=np.float64))
        scale = float(np.abs(z0).sum())
        drift = p_drift = 0.0
        for _ in range(15):
            params, state, _ = step(params, state, batch)
            win = win_mod._get_win(bf.get_context(), step.engine._name)
            total = float(
                np.sum(np.asarray(win.value), dtype=np.float64)
            ) + float(np.sum(np.asarray(win.buffers), dtype=np.float64))
            ptotal = float(
                np.sum(np.asarray(win.p), dtype=np.float64)
            ) + float(
                np.sum(np.asarray(win.p_buffers), dtype=np.float64)
            )
            drift = max(drift, abs(total - mass0))
            p_drift = max(p_drift, abs(ptotal - n))
        tiers[tier] = {
            "mass_drift": drift,
            "p_drift": p_drift,
            "bound": 1e-5 * scale,
            "conserved": bool(
                drift < 1e-5 * scale and p_drift < 1e-5
            ),
        }
        bf.shutdown()
    lines.append({
        "metric": "async_mass",
        "dim": dim,
        "ticks": 15,
        "cadences": "random in [1, 4]",
        "tiers": tiers,
        "mass_drift_max": max(t["mass_drift"] for t in tiers.values()),
        "conserved_all_tiers": all(
            t["conserved"] for t in tiers.values()
        ),
    })
    mass = lines[-1]

    # -- 5: async-off dispatch is the synchronous path, bitwise --------------
    bf.init(devices=devices[:n])
    bf.set_topology(topo.RingGraph(n, connect_style=1))
    opt_a = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(lr))
    pa = {"w": jnp.asarray(z0)}
    sa = opt_a.init(pa)
    off_step = bf.make_async_train_step(opt_a, loss_fn, enabled=False)
    opt_b = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(lr))
    pb = {"w": jnp.asarray(z0)}
    sb = opt_b.init(pb)
    ref_step = opt_b.make_train_step(loss_fn)
    bitwise = True
    for _ in range(10):
        pa, sa, la = off_step(pa, sa, batch)
        pb, sb, lb = ref_step(pb, sb, batch)
        bitwise = bitwise and np.array_equal(
            np.asarray(pa["w"]), np.asarray(pb["w"])
        ) and np.array_equal(np.asarray(la), np.asarray(lb))
    lines.append({
        "metric": "async_off_bitwise",
        "steps": 10,
        "bitwise_identical": bool(bitwise),
        "dispatch_path_shared": not hasattr(off_step, "engine"),
    })
    off = lines[-1]
    bf.shutdown()

    for line in lines:
        print(json.dumps(line), flush=True)

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert straggler["within_1_over_n"], straggler
        assert straggler["sync_collapse"], straggler
        assert conv["within_tolerance"], conv
        assert mass["conserved_all_tiers"], mass
        assert gate["gate_engaged"], gate
        assert gate["advisory_names_slow_rank"], gate
        assert gate["age_max"] > gate["max_age"], gate
        assert off["bitwise_identical"], off
        assert off["dispatch_path_shared"], off
    return 0


def run_transformer() -> int:
    """TransformerLM train-step throughput: tokens/sec + MFU at long
    sequence over the Pallas flash kernels (fwd + custom-VJP bwd).

    The reference has no transformer or long-context tier (SURVEY §5);
    this number backs the beyond-reference attention stack with the same
    measured-claims discipline as the headline
    (reference docs/performance.rst:16-24)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from bluefog_tpu.models.transformer import TransformerLM

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    seq = int(os.environ.get("BENCH_SEQ", "4096" if on_tpu else "128"))
    batch = int(os.environ.get("BENCH_TLM_BATCH", "2" if on_tpu else "1"))
    dim = int(os.environ.get("BENCH_TLM_DIM", "1024" if on_tpu else "64"))
    heads = int(os.environ.get("BENCH_TLM_HEADS", "16" if on_tpu else "4"))
    layers = int(os.environ.get("BENCH_TLM_LAYERS", "12" if on_tpu else "2"))
    vocab = int(os.environ.get("BENCH_TLM_VOCAB", "16384" if on_tpu else "256"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "10" if on_tpu else "2")))
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "8" if on_tpu else "1")))

    remat = os.environ.get("BENCH_TLM_REMAT", "0") == "1"
    model = TransformerLM(
        vocab=vocab, dim=dim, heads=heads, layers=layers, max_len=seq,
        dtype=jnp.bfloat16, remat=remat,
    )
    rng_np = np.random.RandomState(0)
    tokens = jnp.asarray(
        rng_np.randint(0, vocab, (batch, seq)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params)
    )

    @jax.jit
    def train_step(params, opt_state, tokens):
        def loss_fn(p):
            logits = model.apply({"params": p}, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, new_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_state, loss

    carry = (params, opt_state)

    def step(tokens):
        nonlocal carry
        p, s, loss = train_step(carry[0], carry[1], tokens)
        carry = (p, s)
        return loss  # scalar: a fixed cheap settle readback

    dt = _timed_differenced(lambda: step(tokens), steps, windows)[0]
    tok_per_sec = batch * seq / dt
    # fwd FLOPs/token = 2*P (params matmuls) + 2*T*dim*L (causal QK^T+PV
    # at average context T/2, both 2*MAC); fwd+bwd = 3x fwd
    flops_token = 3 * (2 * n_params + 2 * seq * dim * layers)
    anchor = _ambient_anchor()
    result = {
        "metric": "transformer_lm_tokens_per_sec",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_anchor": round(tok_per_sec / max(anchor["tflops"], 1e-9), 2),
        "anchor_tflops": anchor["tflops"],
        "seq_len": seq,
        "params_m": round(n_params / 1e6, 1),
        "dim": dim, "heads": heads, "layers": layers, "batch": batch,
        "attention": "pallas_flash", "remat": remat,
    }
    peak = _peak_flops(jax.devices()[0])
    if peak:
        result["mfu"] = round(tok_per_sec * flops_token / peak, 4)
        result["device"] = jax.devices()[0].device_kind
    print(json.dumps(result))
    return 0


def run_flash() -> int:
    """Flash-vs-dense attention timings: the measured basis for the
    flash-by-default decision (VERDICT r04 item 1). Emits one line per
    (shape, direction) with the speedup; on TPU asserts flash wins at
    long sequence so a kernel regression fails the bench."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.ops.attention import reference_attention
    from bluefog_tpu.ops.flash import flash_attention

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3" if on_tpu else "1")))
    seqs = [
        int(s) for s in os.environ.get(
            "BENCH_FLASH_SEQS", "1024,4096,8192" if on_tpu else "256"
        ).split(",")
    ]
    speedups = {}
    for h, d in ((16, 64), (8, 128)):
        for t in seqs:
            rng = np.random.RandomState(0)
            q, k, v = (
                jnp.asarray(rng.randn(1, t, h, d), jnp.bfloat16)
                for _ in range(3)
            )

            def mk(fn):
                # both timed programs return a SCALAR so the settle point
                # is a fixed cheap readback (settling a [T,H,D] output
                # would swamp the measurement)
                fwd = jax.jit(
                    lambda q, k, v: fn(q, k, v, causal=True)
                    .astype(jnp.float32).mean()
                )

                def loss(q, k, v):
                    return fn(q, k, v, causal=True).astype(
                        jnp.float32
                    ).mean()

                bwd = jax.jit(
                    lambda q, k, v: sum(
                        g.astype(jnp.float32).sum()
                        for g in jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
                    )
                )
                return fwd, bwd

            f_fwd, f_bwd = mk(flash_attention)
            r_fwd, r_bwd = mk(reference_attention)

            def measure(fn, cost_mult):
                # steps sized from the analytic FLOP count to ~1 s of
                # compute per window half (sub-second windows are pure
                # settle-cost noise)
                flops = 2.0 * t * t * h * d * 1 * cost_mult  # causal ~half
                # floored: a sub-ms shape's per-call time is dominated
                # by dispatch (~50 us), not FLOPs — an unfloored
                # estimate requests absurd step counts and the window
                # measures dispatch noise, the r05 impossible-row root
                est = max(flops / 2.0e13, 5e-5)
                steps = max(8, min(4096, int(1.0 / est)))
                dts, degen = _timed_differenced(
                    lambda: fn(q, k, v), steps, windows,
                    with_degenerate=True,
                )
                return dts[0], degen

            def one_cell():
                (tf, d1), (tr, d2) = measure(f_fwd, 1), measure(r_fwd, 2)
                (tfb, d3), (trb, d4) = measure(f_bwd, 3), measure(r_bwd, 6)
                degenerate = d1 or d2 or d3 or d4
                cell = {
                    "metric": "flash_attention_vs_dense",
                    "seq_len": t, "heads": h, "head_dim": d,
                    "causal": True,
                    "flash_fwd_ms": round(tf * 1e3, 3),
                    "dense_fwd_ms": round(tr * 1e3, 3),
                    "fwd_speedup": round(tr / tf, 2),
                    "flash_fwdbwd_ms": round(tfb * 1e3, 3),
                    "dense_fwdbwd_ms": round(trb * 1e3, 3),
                    "fwdbwd_speedup": round(trb / tfb, 2),
                }
                if degenerate:
                    # every timing window stayed clamped even after
                    # retries: disclose instead of publishing a fake
                    # ~0 ms cell (and keep the cell out of the
                    # regression assertion below)
                    cell["degenerate"] = True
                return cell, degenerate, (tr / tf, trb / tfb)

            cell, degenerate, sp = one_cell()
            problems = bench_row_problems(cell)
            if problems:
                # an impossible row never ships as a measurement: one
                # full remeasure (transient stalls are the usual cause),
                # then reject the cell with its violations disclosed
                cell, degenerate, sp = one_cell()
                problems = bench_row_problems(cell)
                if problems:
                    cell["degenerate"] = True
                    cell["rejected"] = problems
                    degenerate = True
            if not degenerate:
                speedups[(h, d, t)] = sp
            print(json.dumps(cell))
    if on_tpu and os.environ.get("BENCH_ASSERT", "1") != "0":
        # stall-robust regression check: a single host stall can distort
        # one cell, so require every long config to win in at least one
        # direction and at least one to win decisively in both (degenerate
        # cells never reach `speedups`)
        long_wins = [
            s for (h, d, t), s in speedups.items() if t >= 4096
        ]
        if long_wins:  # no long configs measured != a kernel regression
            assert all(
                max(fwd, bwd) > 1.0 for fwd, bwd in long_wins
            ) and any(
                fwd > 1.5 and bwd > 1.5 for fwd, bwd in long_wins
            ), f"flash lost to dense at long sequence: {speedups}"
    return 0


def run_quant() -> int:
    """Quantized-wire evidence (``BENCH_MODE=quant``, committed as
    QUANT_EVIDENCE.json): the full wire-tier family —
    fp32/bf16/int8/int8_ef/int4/int4_ef — run on the same pure-consensus
    problem (zero gradients isolate the wire's noise from optimizer
    bias), with per-tier wire bytes (scale sidecar priced in), the
    consensus-distance curve, and the metrics tier's quant-error
    telemetry. The headline claim this artifact gates (``BENCH_ASSERT``,
    default on): the int4 tiers ship >= 2x fewer wire bytes than int8,
    and ``int4_ef`` reaches consensus quality no worse than int8's
    (within the disclosed multi-seed A/A spread — error feedback erases
    the coarser quantizer's floor, so it typically lands ORDERS below).
    ``quant_kernel`` rows compare the fused wire kernels
    (``BLUEFOG_WIRE_KERNELS``) against the composite path — measured
    XLA scratch, step time, bitwise output equality — and gate the
    fused scratch BELOW the fp32 row for int8 AND int4 (the full-width
    temporary never materializes; docs/performance.md).
    A push-sum window run under ``BLUEFOG_WINDOW_WIRE=int4`` closes the
    artifact with the sender-mass-conservation check (drift bounded by
    f32 rounding, not quantization: the sender absorbs the residual of
    the mass it ships — docs/windows.md)."""
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_QUANT_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu import metrics as bf_metrics
    from bluefog_tpu import scaling
    from bluefog_tpu import windows as win_mod
    from bluefog_tpu.collective.plan import plan_from_topology

    n = min(len(jax.devices()),
            int(os.environ.get("BENCH_QUANT_WORKERS", "8")))
    dim = int(os.environ.get("BENCH_QUANT_DIM", "4096"))
    steps = int(os.environ.get("BENCH_QUANT_STEPS", "200"))
    seeds = max(2, int(os.environ.get("BENCH_QUANT_SEEDS", "3")))
    curve_every = max(1, steps // 20)

    plan = plan_from_topology(topo.ExponentialTwoGraph(n), weighted=True)
    tiers = (None, "bf16", "int8", "int8_ef", "int4", "int4_ef")

    old_env = {
        k: os.environ.get(k)
        for k in ("BLUEFOG_METRICS", "BLUEFOG_METRICS_INTERVAL",
                  "BLUEFOG_METRICS_FILE", "BLUEFOG_METRICS_PROM",
                  "BLUEFOG_WINDOW_WIRE")
    }
    os.environ.pop("BLUEFOG_METRICS_FILE", None)
    os.environ.pop("BLUEFOG_METRICS_PROM", None)
    os.environ["BLUEFOG_METRICS"] = "1"
    os.environ["BLUEFOG_METRICS_INTERVAL"] = "1"

    def consensus_dist(w):
        return float(
            np.sqrt(((w - w.mean(0)) ** 2).sum(1)).mean()
        )

    finals = {}
    try:
        bf.init(devices=jax.devices()[:n])
        bf.set_topology(topo.ExponentialTwoGraph(n))
        for wire in tiers:
            name = wire or "fp32"
            curves = []
            quant_err = None
            for seed in range(seeds):
                bf_metrics.reset()
                c = (
                    np.random.RandomState(100 + seed)
                    .randn(n, dim).astype(np.float32) * 5.0
                )
                opt = bf.DistributedNeighborAllreduceOptimizer(
                    optax.sgd(0.0)
                )
                opt.compression = wire
                params = {"w": bf.worker_values(lambda r: c[r])}
                state = opt.init(params)
                zero = {"w": jnp.zeros((n, dim), jnp.float32)}
                curve = []
                for step in range(steps):
                    params, state = opt.step(params, state, zero)
                    if step == 0 and seed == 0 and wire not in (
                        None, "bf16",
                    ):
                        # first-step quant error: the EF tiers drive
                        # theirs to exactly 0 at consensus, so the
                        # meaningful sample is the full-magnitude one
                        bf_metrics.flush()
                        g = bf_metrics.snapshot().get(
                            "bluefog.gossip.quant_err"
                        )
                        quant_err = g["value"] if g else None
                    if step % curve_every == 0 or step == steps - 1:
                        curve.append(
                            round(consensus_dist(
                                np.asarray(params["w"])
                            ), 8)
                        )
                curves.append(curve)
            finals[name] = [cv[-1] for cv in curves]
            summary = scaling.plan_comm_summary(
                plan, dim * 4, wire=wire
            )
            line = {
                "metric": "quant_tier",
                "wire": name,
                "n_workers": n,
                "dim": dim,
                "steps": steps,
                "rounds": summary["rounds"],
                "wire_bytes_per_step": plan.wire_bytes(dim, 4, wire=wire),
                "effective_compression_ratio": summary[
                    "effective_compression_ratio"
                ],
                "final_consensus_median": float(
                    np.median(finals[name])
                ),
                "final_consensus_seeds": finals[name],
                "consensus_curve": curves[0],
            }
            if quant_err is not None:
                line["quant_err_rms"] = round(float(quant_err), 8)
            print(json.dumps(line), flush=True)
        bf.shutdown()

        # the disclosed A/A floor: the reference tier's own multi-seed
        # spread of final consensus distance (different random problems,
        # same config) — the resolution limit of "equal quality"
        int8_f = np.asarray(finals["int8"], np.float64)
        aa_noise_pct = float(
            100.0 * (int8_f.max() - int8_f.min())
            / max(int8_f.min(), 1e-30)
        )
        b_int8 = plan.wire_bytes(dim, 4, wire="int8")
        b_int4 = plan.wire_bytes(dim, 4, wire="int4")
        b_int4ef = plan.wire_bytes(dim, 4, wire="int4_ef")
        ratio = b_int8 / b_int4
        int8_med = float(np.median(finals["int8"]))
        int4ef_med = float(np.median(finals["int4_ef"]))
        equal_quality = int4ef_med <= int8_med * (
            1.0 + aa_noise_pct / 100.0
        )
        print(json.dumps({
            "metric": "quant_summary",
            "n_workers": n,
            "dim": dim,
            "wire_bytes_int8": b_int8,
            "wire_bytes_int4": b_int4,
            "wire_bytes_int4_ef": b_int4ef,
            "wire_reduction_int4_vs_int8": round(ratio, 4),
            "aa_noise_pct": round(aa_noise_pct, 3),
            "final_consensus_int8": int8_med,
            "final_consensus_int4_ef": int4ef_med,
            "int4_ef_no_worse_than_int8": bool(equal_quality),
        }), flush=True)

        # -- fused wire kernels: kernel-vs-composite ----------------------
        # (BLUEFOG_WIRE_KERNELS, collective/kernels.py): same combine,
        # compiled twice — composite (kernels pinned off, the
        # MEMORY_EVIDENCE before-baseline) vs fused — comparing the
        # measured XLA scratch, the step time, and bitwise equality of
        # the outputs. The headline gate: the fused path's scratch
        # lands BELOW the fp32 row (no full-width temporary exists),
        # for int8 AND int4.
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from bluefog_tpu.collective import inner
        from bluefog_tpu.collective import kernels as wire_kernels

        k_plan = plan_from_topology(topo.RingGraph(n))
        mesh = Mesh(np.array(jax.devices()[:n]), ("workers",))
        xk = jax.device_put(
            jnp.asarray(
                np.random.RandomState(7)
                .randn(n, dim).astype(np.float32) * 5.0
            ),
            NamedSharding(mesh, P("workers")),
        )

        def kernel_build(wire):
            if wire is None:
                body = lambda t: inner.neighbor_allreduce(
                    t, k_plan, "workers"
                )
            else:
                body = lambda t, w=wire: inner.weighted_combine_quantized(
                    t, k_plan, "workers", wire=w
                )
            fn = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=P("workers"),
                out_specs=P("workers"),
            ))
            c = fn.lower(xk).compile()
            return fn, int(c.memory_analysis().temp_size_in_bytes)

        def kernel_time_us(fn, reps=30):
            jax.block_until_ready(fn(xk))  # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn(xk))
            return 1e6 * (time.perf_counter() - t0) / reps

        old_wk = os.environ.get("BLUEFOG_WIRE_KERNELS")
        kernel_rows = []
        try:
            os.environ["BLUEFOG_WIRE_KERNELS"] = "0"
            _, fp32_temp = kernel_build(None)
            for wire in ("int8", "int4"):
                os.environ["BLUEFOG_WIRE_KERNELS"] = "0"
                fn_c, temp_c = kernel_build(wire)
                out_c = np.asarray(fn_c(xk))
                t_c = kernel_time_us(fn_c)
                os.environ["BLUEFOG_WIRE_KERNELS"] = "1"
                fn_f, temp_f = kernel_build(wire)
                out_f = np.asarray(fn_f(xk))
                t_f = kernel_time_us(fn_f)
                kernel_rows.append({
                    "metric": "quant_kernel",
                    "wire": wire,
                    "payload_elems": dim,
                    "kernels_native": jax.default_backend() == "tpu",
                    "temp_bytes_composite": temp_c,
                    "temp_bytes_fused": temp_f,
                    "temp_bytes_fp32": fp32_temp,
                    "temp_bytes_analytic_fused": (
                        scaling.quantized_temporaries_bytes(
                            dim, wire, fused=True
                        )
                    ),
                    "temp_bytes_analytic_composite": (
                        scaling.quantized_temporaries_bytes(dim, wire)
                    ),
                    "fused_below_fp32_row": temp_f < fp32_temp,
                    "step_time_composite_us": round(t_c, 2),
                    "step_time_fused_us": round(t_f, 2),
                    "bitwise_equal": bool(
                        (out_c.view(np.uint32)
                         == out_f.view(np.uint32)).all()
                    ),
                })
                print(json.dumps(kernel_rows[-1]), flush=True)
        finally:
            if old_wk is None:
                os.environ.pop("BLUEFOG_WIRE_KERNELS", None)
            else:
                os.environ["BLUEFOG_WIRE_KERNELS"] = old_wk

        # push-sum mass conservation under the quantized window wire
        os.environ["BLUEFOG_WINDOW_WIRE"] = "int4"
        os.environ["BLUEFOG_METRICS"] = "0"
        bf.init(devices=jax.devices()[:n])
        bf.set_topology(topo.ExponentialTwoGraph(n))
        bf.turn_on_win_ops_with_associated_p()
        x0 = (
            np.random.RandomState(0).randn(n, dim).astype(np.float32) * 3
        )
        bf.win_create(
            bf.worker_values(lambda r: x0[r]), "quant_ps", zero_init=True
        )
        outs = bf.get_context().out_neighbor_ranks()
        dst = [
            {d: 1.0 / (len(outs[r]) + 1) for d in outs[r]}
            for r in range(n)
        ]
        sw = [1.0 / (len(outs[r]) + 1) for r in range(n)]
        total0 = x0.sum(0, dtype=np.float64)
        max_drift = 0.0
        ps_steps = int(os.environ.get("BENCH_QUANT_PS_STEPS", "25"))
        for _ in range(ps_steps):
            bf.win_accumulate(
                name="quant_ps", self_weight=sw, dst_weights=dst
            )
            bf.win_update_then_collect("quant_ps")
            v = np.asarray(bf.win_read("quant_ps"), np.float64)
            max_drift = max(
                max_drift, float(np.abs(v.sum(0) - total0).max())
            )
        p = win_mod.win_associated_p("quant_ps")
        est = np.asarray(bf.win_read("quant_ps")) / np.asarray(
            p
        )[:, None]
        # bound: f32 rounding of the running sums, NOT quantization
        # magnitude — per-element mass error accumulates as ~n_workers *
        # steps * ulp(sum) with the quantization residual absorbed
        mass_bound = float(
            ps_steps * n * float(np.abs(x0).max())
            * np.finfo(np.float32).eps * 64
        )
        mass_ok = max_drift < mass_bound
        print(json.dumps({
            "metric": "quant_window_mass",
            "wire": "int4",
            "wire_kernels_on": wire_kernels.wire_kernels_on(),
            "n_workers": n,
            "dim": dim,
            "ps_steps": ps_steps,
            "max_mass_drift": round(max_drift, 9),
            "mass_bound": round(mass_bound, 9),
            "mass_conserved": bool(mass_ok),
            "consensus_err": round(
                float(np.abs(est - x0.mean(0)).max()), 6
            ),
        }), flush=True)
        bf.shutdown()
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert ratio >= 2.0, (
            f"int4 wire reduction vs int8 is {ratio:.3f}x, below the "
            "2x acceptance bound"
        )
        assert equal_quality, (
            f"int4_ef final consensus {int4ef_med:.3e} exceeds int8's "
            f"{int8_med:.3e} beyond the {aa_noise_pct:.2f}% A/A floor"
        )
        assert mass_ok, (
            f"push-sum mass drift {max_drift:.3e} exceeds the f32 "
            f"rounding bound {mass_bound:.3e} under the int4 window wire"
        )
        for row in kernel_rows:
            assert row["bitwise_equal"], (
                f"fused wire kernels changed the {row['wire']} combine "
                "bitwise — the same-bits contract is broken"
            )
            assert row["fused_below_fp32_row"], (
                f"fused {row['wire']} scratch "
                f"{row['temp_bytes_fused']} B is not below the fp32 "
                f"row's {row['temp_bytes_fp32']} B — the full-width "
                "temporary still materializes"
            )
    return 0


def run_shard() -> int:
    """Weight-update-sharding evidence (``BENCH_MODE=shard``, committed
    as SHARD_EVIDENCE.json). Five facts, BENCH_ASSERT-gated:

    1. *Memory*: on an 8-worker mesh, Adam state for a model whose
       REPLICATED per-rank footprint exceeds a simulated per-chip
       budget trains under ``BLUEFOG_SHARD=1`` with measured (real
       allocated arrays, not a model) per-rank state bytes at
       1/N + the disclosed 512-alignment slack.
    2. *Trajectory*: the sharded run matches the replicated run AND the
       numpy Adam oracle coordinate-for-coordinate (ulp envelope) —
       sharding is a memory layout, not an algorithm change. The ZeRO-2
       run (``BLUEFOG_SHARD_GRADS=1``, gradient leg lowered to
       reduce-scatter) sits inside the SAME envelope.
    3. *Step time*: sharded vs unsharded at the same model size stays
       within the disclosed A/A noise floor (the 1/N update saving and
       the all-gather cost trade against each other on CPU).
    4. *Off pin*: ``BLUEFOG_SHARD=0`` dispatches bitwise-identically
       with zero shard-tagged cache keys.
    5. *Gradient wire* (``shard_grad_wire``): the dispatched
       reduce-scatter delivers a measured per-rank reduced-gradient
       buffer at ~1/N of the allreduce's (pad slack disclosed);
       reduce-scatter + all-gather wire <= allreduce + all-gather; and
       the quantized scatter tiers price at the exact block-scale
       ratios (int8 = 516/2048, int4 = 258/2048 — slots are 512-grid
       multiples so the ratios are exact, not approximate).

    See docs/sharding.md."""
    if os.environ.get("BENCH_SCALING_PLATFORM", "cpu") != "native":
        from bluefog_tpu.platforms import ensure_cpu_device_count

        ensure_cpu_device_count(
            int(os.environ.get("BENCH_SHARD_DEVICES", "8"))
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import scaling, sharding

    devices = jax.devices()
    n = min(len(devices), int(os.environ.get("BENCH_SHARD_WORKERS", "8")))
    # odd on purpose: the 512-grid padding slack must be real, not zero
    dim = int(os.environ.get("BENCH_SHARD_DIM", "262145"))
    budget = int(os.environ.get("BENCH_SHARD_BUDGET", str(1 << 20)))
    steps = int(os.environ.get("BENCH_SHARD_STEPS", "24"))
    t_steps = int(os.environ.get("BENCH_SHARD_TIME_STEPS", "60"))
    lr = 0.02
    rng = np.random.RandomState(0)
    c = rng.randn(n, dim).astype(np.float32)
    c_mean = c.mean(axis=0)

    def session(shard, body, grads=False):
        os.environ["BLUEFOG_SHARD"] = "1" if shard else "0"
        if grads:
            os.environ["BLUEFOG_SHARD_GRADS"] = "1"
        bf.init(devices=devices[:n])
        try:
            return body()
        finally:
            bf.shutdown()
            os.environ.pop("BLUEFOG_SHARD", None)
            os.environ.pop("BLUEFOG_SHARD_GRADS", None)

    def make(shard_unused=None):
        opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(lr))
        params = {"w": bf.worker_values(
            lambda r: np.zeros(dim, np.float32)
        )}
        state = opt.init(params)
        return opt, params, state

    def grads_of(params):
        return {"w": params["w"] - jnp.asarray(c)}

    import jax.numpy as jnp

    def loss_of(params):
        w = np.asarray(params["w"])
        return float(np.mean(0.5 * np.sum((w - c_mean) ** 2, -1)))

    lines = []

    # -- 1. memory + train-past-the-budget ------------------------------
    def mem_shard():
        opt, params, state = make()
        layout = opt._shard_layout
        measured = scaling.optimizer_state_bytes(state=state, world=n)
        analytic = scaling.optimizer_state_bytes(params, opt, shard=True)
        loss0 = loss_of(params)
        for _ in range(steps):
            params, state = opt.step(params, state, grads_of(params))
            # one multi-device program in flight at a time: overlapped
            # 8-participant rendezvous can starve each other on a
            # small host
            jax.block_until_ready(params)
        w = np.asarray(params["w"])
        return {
            "measured": measured, "analytic": analytic,
            "slot_elems": layout.groups[0].slot,
            "pad_ratio": round(
                layout.groups[0].padded / layout.groups[0].elems - 1.0, 6
            ),
            "gather_bytes": sharding.gather_wire_bytes(layout),
            "loss0": loss0, "loss1": loss_of({"w": w}),
            "replica_spread": float(np.abs(w - w[0]).max()),
        }

    def mem_repl():
        opt, params, state = make()
        return {
            "measured": scaling.optimizer_state_bytes(state=state,
                                                      world=n),
            "analytic": scaling.optimizer_state_bytes(params, opt,
                                                      shard=False),
        }

    sh = session(True, mem_shard)
    rp = session(False, mem_repl)
    shard_ratio = sh["measured"] / rp["measured"]
    # the 1/N claim with the alignment slack disclosed: the sharded
    # footprint is bounded by slot/dim of replicated (slot IS
    # ceil(dim/N) rounded to the 512 grid) plus scalar state overhead
    mem_bound = rp["measured"] * (sh["slot_elems"] / dim) * 1.02 + 4096
    lines.append({
        "metric": "shard_memory",
        "workers": n,
        "dim": dim,
        "optimizer": "adam",
        "budget_bytes": budget,
        "state_bytes_replicated": rp["measured"],
        "state_bytes_sharded": sh["measured"],
        "state_bytes_replicated_analytic": rp["analytic"],
        "state_bytes_sharded_analytic": sh["analytic"],
        "shard_ratio": round(shard_ratio, 6),
        "slot_elems": sh["slot_elems"],
        "pad_ratio": sh["pad_ratio"],
        "gather_bytes_per_step": sh["gather_bytes"],
        "replicated_exceeds_budget": rp["measured"] > budget,
        "sharded_fits_budget": sh["measured"] <= budget,
        "trained_steps": steps,
        "loss_start": sh["loss0"],
        "loss_end": sh["loss1"],
        "replica_spread": sh["replica_spread"],
    })

    # -- 2. trajectory: sharded == replicated == numpy Adam oracle ------
    traj_dim = int(os.environ.get("BENCH_SHARD_TRAJ_DIM", "4099"))
    ct = rng.randn(n, traj_dim).astype(np.float32)
    ct_mean = ct.mean(axis=0)

    def traj(shard):
        del shard
        opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(lr))
        params = {"w": bf.worker_values(
            lambda r: np.zeros(traj_dim, np.float32)
        )}
        state = opt.init(params)
        for _ in range(8):
            params, state = opt.step(
                params, state, {"w": params["w"] - jnp.asarray(ct)}
            )
            jax.block_until_ready(params)
        return np.asarray(params["w"])[0]

    w_sh = session(True, lambda: traj(True))
    w_rp = session(False, lambda: traj(False))
    # ZeRO-2: the same trajectory with the gradient leg lowered to
    # reduce-scatter (BLUEFOG_SHARD_GRADS=1) — the scatter's fixed
    # reduction order must keep it inside the SAME pin envelope
    w_z2 = session(True, lambda: traj(True), grads=True)

    # numpy oracle: replicated gradient-allreduce Adam on the quadratic
    # (grad of 0.5||x - c_r||^2 allreduce-means to x - mean(c))
    b1, b2, eps = 0.9, 0.999, 1e-8
    x = np.zeros(traj_dim, np.float32)
    m = np.zeros(traj_dim, np.float32)
    v = np.zeros(traj_dim, np.float32)
    for t in range(1, 9):
        g = x - ct_mean
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        x = x - lr * (m / (1 - b1 ** t)) / (
            np.sqrt(v / (1 - b2 ** t)) + eps
        )
    traj_tol = 1e-5
    traj_max_dev = float(np.abs(w_sh - w_rp).max())
    oracle_dev = float(np.abs(w_sh - x).max())
    z2_max_dev = float(np.abs(w_z2 - w_rp).max())
    z2_oracle_dev = float(np.abs(w_z2 - x).max())
    lines.append({
        "metric": "shard_trajectory",
        "dim": traj_dim,
        "steps": 8,
        "traj_max_dev": traj_max_dev,
        "oracle_max_dev": oracle_dev,
        "zero2_max_dev": z2_max_dev,
        "zero2_oracle_max_dev": z2_oracle_dev,
        "tol": traj_tol,
        "sharded_matches_replicated": traj_max_dev <= traj_tol,
        "sharded_matches_numpy_oracle": oracle_dev <= 1e-4,
        "zero2_matches_replicated": z2_max_dev <= traj_tol,
        "zero2_matches_numpy_oracle": z2_oracle_dev <= 1e-4,
        "oracle": "numpy replicated-Adam replay",
    })

    # -- 3. step time within the A/A noise floor ------------------------
    def timed(shard):
        def body():
            opt, params, state = make()
            holder = {"p": params, "s": state}

            def one():
                holder["p"], holder["s"] = opt.step(
                    holder["p"], holder["s"], grads_of(holder["p"])
                )
                # synchronous per-step timing on both arms: identical
                # A/B treatment, and no overlapped rendezvous
                return jax.block_until_ready(holder["p"]["w"])

            one()  # compile
            return _timed_differenced(one, t_steps, windows=2)[0]

        return session(shard, body)

    # INTERLEAVED A/B/A/B... windows (the BENCH_MODE=gossip
    # discipline): ambient drift on a shared host lands on both
    # configs instead of biasing one; best-of-R per config, A/A floor
    # from the spread of the A windows
    reps = int(os.environ.get("BENCH_SHARD_TIME_REPS", "3"))
    t_off, t_on = [], []
    for _rep in range(reps):
        t_off.append(timed(False))
        t_on.append(timed(True))
    t_a = min(t_off)
    t_b = min(t_on)
    aa_pct = (max(t_off) - min(t_off)) / t_a * 100
    delta_pct = (t_b - t_a) / t_a * 100
    noise_bound_pct = max(3 * aa_pct, 15.0)
    lines.append({
        "metric": "shard_step_time",
        "dim": dim,
        "steps_timed": t_steps,
        "windows": reps,
        "ms_unsharded": round(t_a * 1e3, 4),
        "ms_unsharded_aa": round(max(t_off) * 1e3, 4),
        "ms_sharded": round(t_b * 1e3, 4),
        "aa_noise_pct": round(aa_pct, 3),
        "delta_pct": round(delta_pct, 3),
        "noise_bound_pct": round(noise_bound_pct, 3),
        "within_noise": abs(delta_pct) <= noise_bound_pct,
    })

    # -- 4. shard-off bitwise pin + cache-key hygiene --------------------
    def off_run():
        opt, params, state = make()
        for _ in range(4):
            params, state = opt.step(params, state, grads_of(params))
            jax.block_until_ready(params)
        keys = [
            k for k in bf.get_context().op_cache
            if isinstance(k, tuple) and "shard" in map(str, k)
        ]
        return np.asarray(params["w"]), len(keys)

    w_off1, k_off1 = session(False, off_run)
    w_off2, k_off2 = session(False, off_run)
    lines.append({
        "metric": "shard_off_pin",
        "bitwise_identical": bool(np.array_equal(w_off1, w_off2)),
        "shard_tagged_cache_keys": int(k_off1 + k_off2),
        "steps": 4,
    })

    # -- 5. ZeRO-2 gradient memory + scatter wire ------------------------
    def grad_mem():
        """MEASURED (real allocated arrays) reduced-gradient bytes:
        dispatch the actual reduce-scatter collective on the bench
        payload and read the delivered buffer's nbytes — the [slot]
        owned row is the ONLY reduced-gradient buffer the ZeRO-2
        program materializes, vs the allreduce's full [dim] output."""
        from jax.sharding import NamedSharding, PartitionSpec

        from bluefog_tpu.collective import inner as inner_mod

        opt, params, state = make()
        layout = opt._shard_layout
        assert layout is not None and layout.grads
        for _ in range(2):
            params, state = opt.step(params, state, grads_of(params))
            jax.block_until_ready(params)
        g = layout.groups[0]
        ctx = bf.get_context()
        spec = PartitionSpec("workers")
        nd = NamedSharding(ctx.mesh, spec)
        live_index = tuple(
            int(v) for v in np.asarray(layout.live_index())
        )
        xs = np.zeros((n, g.padded), np.float32)
        xs[:, :dim] = c
        rs = jax.jit(jax.shard_map(
            lambda t: inner_mod.reduce_scatter(
                t[0], "workers", live_index, g.slot
            )[None],
            mesh=ctx.mesh, in_specs=spec, out_specs=spec,
        ))
        ar = jax.jit(jax.shard_map(
            lambda t: inner_mod.allreduce(t, "workers", average=True),
            mesh=ctx.mesh, in_specs=spec, out_specs=spec,
        ))
        # one multi-device program in flight at a time: on a small host
        # two concurrent 8-participant rendezvous can starve each other
        y_scat = rs(jax.device_put(jnp.asarray(xs), nd))
        y_scat.block_until_ready()
        y_full = ar(jax.device_put(jnp.asarray(c), nd))
        y_full.block_until_ready()
        # value cross-check: the concatenated delivered slots ARE the
        # allreduce mean (the two programs compute the same reduction)
        got = np.asarray(y_scat)[layout.live, :].reshape(-1)[:dim]
        np.testing.assert_allclose(
            got, np.asarray(y_full)[0], rtol=0, atol=1e-5
        )
        return {
            "layout": layout,
            "slot": g.slot,
            "scat_bytes": int(y_scat.nbytes) // n,
            "full_bytes": int(y_full.nbytes) // n,
        }

    gm = session(True, grad_mem, grads=True)
    layout = gm["layout"]
    slot = gm["slot"]
    grad_ratio = gm["scat_bytes"] / gm["full_bytes"]
    scatter_fp32 = scaling.reduce_scatter_bytes(((slot, 4),), n)
    allreduce_fp32 = sharding.allreduce_wire_bytes(layout)
    gather_fp32 = sharding.gather_wire_bytes(layout)
    tiers = {
        "fp32": {
            "scatter_bytes_per_step": scatter_fp32,
            "ratio_vs_fp32": 1.0,
        },
    }
    for tier in ("bf16", "int8", "int4", "int8_ef", "int4_ef"):
        b = scaling.reduce_scatter_bytes(((slot, 4),), n, wire=tier)
        tiers[tier] = {
            "scatter_bytes_per_step": b,
            "ratio_vs_fp32": round(b / scatter_fp32, 6),
        }
    lines.append({
        "metric": "shard_grad_wire",
        "workers": n,
        "dim": dim,
        "slot_elems": slot,
        "grad_bytes_replicated_measured": gm["full_bytes"],
        "grad_bytes_sharded_measured": gm["scat_bytes"],
        "grad_ratio_measured": round(grad_ratio, 6),
        "grad_pad_ratio": round(slot * n / dim - 1.0, 6),
        "scatter_bytes_per_step": scatter_fp32,
        "allreduce_bytes_per_step": allreduce_fp32,
        "gather_bytes_per_step": gather_fp32,
        "scatter_plus_gather": scatter_fp32 + gather_fp32,
        "allreduce_plus_gather": allreduce_fp32 + gather_fp32,
        "wire_le_baseline": (
            scatter_fp32 + gather_fp32 <= allreduce_fp32 + gather_fp32
        ),
        "tiers": tiers,
    })

    for line in lines:
        print(json.dumps(line), flush=True)

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        memline = lines[0]
        assert memline["replicated_exceeds_budget"], (
            f"replicated state {memline['state_bytes_replicated']} does "
            f"not exceed the simulated budget {budget} — the scenario "
            "proves nothing; raise BENCH_SHARD_DIM"
        )
        assert memline["sharded_fits_budget"], memline
        assert memline["state_bytes_sharded"] <= mem_bound, (
            memline["state_bytes_sharded"], mem_bound,
        )
        assert memline["loss_end"] < 0.5 * memline["loss_start"], memline
        assert memline["replica_spread"] == 0.0, memline
        trajline = lines[1]
        assert trajline["sharded_matches_replicated"], trajline
        assert trajline["sharded_matches_numpy_oracle"], trajline
        assert trajline["zero2_matches_replicated"], trajline
        assert trajline["zero2_matches_numpy_oracle"], trajline
        timeline = lines[2]
        assert timeline["within_noise"], timeline
        offline = lines[3]
        assert offline["bitwise_identical"], offline
        assert offline["shard_tagged_cache_keys"] == 0, offline
        gw = lines[4]
        # measured reduced-gradient footprint: exactly slot/dim of the
        # replicated buffer (both are real f32 arrays, so the ratio is
        # the geometry itself — no tolerance needed beyond the slack)
        assert gw["grad_bytes_sharded_measured"] * dim == (
            gw["grad_bytes_replicated_measured"] * gw["slot_elems"]
        ), gw
        assert gw["grad_ratio_measured"] <= 1.0 / n + gw["grad_pad_ratio"] + 1e-6, gw
        assert gw["wire_le_baseline"], gw
        assert gw["scatter_bytes_per_step"] < gw["allreduce_bytes_per_step"], gw
        # block-scale tier ratios are EXACT on the 512 grid
        assert gw["tiers"]["int8"]["ratio_vs_fp32"] == round(516 / 2048, 6), gw
        assert gw["tiers"]["int4"]["ratio_vs_fp32"] == round(258 / 2048, 6), gw
        assert gw["tiers"]["int8_ef"]["ratio_vs_fp32"] == (
            gw["tiers"]["int8"]["ratio_vs_fp32"]
        ), gw
        assert gw["tiers"]["bf16"]["ratio_vs_fp32"] == 0.5, gw
    return 0


def run_memory() -> int:
    """Memory-observatory evidence (``BENCH_MODE=memory``, committed as
    MEMORY_EVIDENCE.json). Four claims, each measured the way it is
    resolvable (the metrics/health noise-floor lessons apply):

    1. **Analytic-vs-measured reconciliation** (``memory_reconcile``):
       on an 8-worker mesh the observatory's live-array census of the
       Adam state must match the analytic
       ``scaling.optimizer_state_bytes`` model within the disclosed
       tolerance for BOTH ``BLUEFOG_SHARD=0`` and ``=1``, and the
       measured sharded/replicated ratio must be consistent with
       SHARD_EVIDENCE's x0.127 at N=8 — the reconciliation loop PR 14
       shipped only half of.
    2. **Quantized-wire temporaries** (``memory_wire_temps``): at the
       PR-8 payload width, the compiled int8/int4 combines' measured
       XLA scratch (``memory_analysis().temp_size_in_bytes``) must
       contain the full-width f32 temporary (>= 4 bytes/elem) and
       EXCEED the uncompressed combine's scratch — the committed
       before-baseline the ROADMAP-2 kernel-fusion PR must beat
       (EQuARX, arxiv 2506.17615). The analytic staging model
       (``scaling.quantized_temporaries_bytes``) is disclosed next to
       the measurement.
    3. **Overhead <= 1 % at the default interval**
       (``memory_overhead``): sampled-census extra cost in an
       all-orderings off/on/off rotation, amortized over the default
       interval, A/A control disclosed; structural pin (the
       observatory compiles NOTHING — zero new cache entries of any
       kind) and bitwise on/off trajectory pin.
    4. **Pressure gate** (``memory_pressure``): under a simulated
       per-chip budget the ``memory_pressure`` advisory fires with the
       shard-recommendation hint when the optimizer state dominates
       and ``BLUEFOG_SHARD`` is off.
    """
    from bluefog_tpu.platforms import ensure_cpu_device_count

    ensure_cpu_device_count(
        int(os.environ.get("BENCH_MEMORY_DEVICES", "8"))
    )
    import itertools
    import time as time_mod

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    jax.config.update("jax_platforms", "cpu")

    import bluefog_tpu as bf
    import bluefog_tpu.topology as topo
    from bluefog_tpu import memory as bf_memory
    from bluefog_tpu import metrics as bf_metrics
    from bluefog_tpu import scaling
    from bluefog_tpu.collective import inner, plan as planlib

    devices = jax.devices()
    n = min(len(devices),
            int(os.environ.get("BENCH_MEMORY_WORKERS", "8")))
    # the SHARD_EVIDENCE model size: ratio x0.127 at N=8 reproduces
    dim_rec = int(os.environ.get("BENCH_MEMORY_RECONCILE_DIM",
                                 "262145"))
    # the PR-8 payload width (QUANT_EVIDENCE dim)
    dim_wire = int(os.environ.get("BENCH_MEMORY_WIRE_DIM", "4096"))
    dim = int(os.environ.get("BENCH_MEMORY_DIM", "256"))
    layers = int(os.environ.get("BENCH_MEMORY_LAYERS", "6"))
    batch = int(os.environ.get("BENCH_MEMORY_BATCH", "16"))
    samples = max(18, int(os.environ.get("BENCH_MEMORY_SAMPLES", "60")))
    tol = float(os.environ.get("BENCH_MEMORY_TOL", "0.02"))

    old_env = {
        k: os.environ.get(k)
        for k in ("BLUEFOG_MEMORY", "BLUEFOG_MEMORY_INTERVAL",
                  "BLUEFOG_MEMORY_BUDGET", "BLUEFOG_MEMORY_FILE",
                  "BLUEFOG_SHARD", "BLUEFOG_METRICS", "BLUEFOG_HEALTH",
                  "BLUEFOG_DOCTOR", "BLUEFOG_STALENESS")
    }
    for k in old_env:
        os.environ.pop(k, None)
    default_interval = bf_memory.memory_interval()
    rng = np.random.RandomState(0)

    # -- claim 1: analytic-vs-measured reconciliation, SHARD=0/1 --------------
    def reconcile(shard):
        os.environ["BLUEFOG_SHARD"] = "1" if shard else "0"
        bf.init(devices=devices[:n])
        try:
            obs = bf_memory.start(interval=1)
            opt = bf.DistributedGradientAllreduceOptimizer(
                optax.adam(0.02)
            )
            params = {"w": bf.worker_values(
                lambda r: np.zeros(dim_rec, np.float32)
            )}
            state = opt.init(params)
            grads = {"w": bf.worker_values(
                lambda r: rng.randn(dim_rec).astype(np.float32)
            )}
            for _ in range(3):
                params, state = opt.step(params, state, grads)
            s = obs.samples[-1]
            return {
                "measured": s["measured_state_bytes"],
                "analytic": s["analytic_state_bytes"],
                "rel_err": s["reconcile_rel_err"],
            }
        finally:
            bf_memory.stop()
            bf.shutdown()
            os.environ.pop("BLUEFOG_SHARD", None)

    rec_repl = reconcile(False)
    rec_shard = reconcile(True)
    ratio = rec_shard["measured"] / rec_repl["measured"]
    shard_ref = 0.127  # SHARD_EVIDENCE's measured ratio at N=8
    reconcile_line = {
        "metric": "memory_reconcile",
        "workers": n,
        "dim": dim_rec,
        "optimizer": "adam",
        "tolerance": tol,
        "replicated_measured_bytes": rec_repl["measured"],
        "replicated_analytic_bytes": rec_repl["analytic"],
        "replicated_rel_err": rec_repl["rel_err"],
        "sharded_measured_bytes": rec_shard["measured"],
        "sharded_analytic_bytes": rec_shard["analytic"],
        "sharded_rel_err": rec_shard["rel_err"],
        "measured_shard_ratio": round(ratio, 6),
        "shard_evidence_ratio": shard_ref,
        "ratio_consistent_with_shard_evidence": (
            abs(ratio - shard_ref) <= 0.02
        ),
        "both_within_tolerance": (
            rec_repl["rel_err"] <= tol and rec_shard["rel_err"] <= tol
        ),
    }
    print(json.dumps(reconcile_line))

    # -- claim 2: quantized-wire temporaries (the fusion baseline) ------------
    mesh = Mesh(np.array(devices[:n]), ("workers",))
    wire_plan = planlib.plan_from_topology(topo.RingGraph(n))
    x_wire = jax.device_put(
        jnp.zeros((n, dim_wire), jnp.float32),
        NamedSharding(mesh, P("workers")),
    )

    def temp_bytes(wire):
        if wire is None:
            body = lambda t: inner.neighbor_allreduce(
                t, wire_plan, "workers"
            )
        else:
            body = lambda t, w=wire: inner.weighted_combine_quantized(
                t, wire_plan, "workers", wire=w
            )
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("workers"),
            out_specs=P("workers"),
        ))
        ma = fn.lower(x_wire).compile().memory_analysis()
        return int(ma.temp_size_in_bytes)

    full_width = 4 * dim_wire  # the f32 temporary fusion eliminates
    temps = {}
    wire_rows = []
    # pin the fused kernels OFF: these rows are the committed COMPOSITE
    # before-baseline (the fused numbers live in QUANT_EVIDENCE's
    # quant_kernel rows); wire_kernels_on() reads the env per trace, so
    # the fresh lambdas above retrace under the pin
    old_wk = os.environ.get("BLUEFOG_WIRE_KERNELS")
    os.environ["BLUEFOG_WIRE_KERNELS"] = "0"
    try:
        for wire in (None, "int8", "int4"):
            name = wire or "fp32"
            t = temp_bytes(wire)
            temps[name] = t
            wire_rows.append({
                "metric": "memory_wire_temps",
                "wire": name,
                "payload_elems": dim_wire,
                "temp_bytes_measured": t,
                "temp_bytes_analytic": (
                    scaling.quantized_temporaries_bytes(dim_wire, wire)
                ),
                "full_width_bytes": full_width,
                "wire_bytes_per_round": scaling.wire_payload_bytes(
                    dim_wire, 4, wire
                ),
                "extra_vs_exact_bytes": t - temps["fp32"],
                "full_width_temporary_materializes": t >= full_width,
            })
            print(json.dumps(wire_rows[-1]))
    finally:
        if old_wk is None:
            os.environ.pop("BLUEFOG_WIRE_KERNELS", None)
        else:
            os.environ["BLUEFOG_WIRE_KERNELS"] = old_wk
    wire_summary = {
        "metric": "memory_wire_summary",
        "payload_elems": dim_wire,
        "quantized_scratch_exceeds_exact": (
            temps["int8"] > temps["fp32"]
            and temps["int4"] > temps["fp32"]
        ),
        "all_full_width": all(
            r["full_width_temporary_materializes"] for r in wire_rows
            if r["wire"] != "fp32"
        ),
        "note": (
            "composite quantize->pack->ppermute->unpack scratch, "
            "measured with BLUEFOG_WIRE_KERNELS=0 — the retained "
            "before-baseline for the fused wire kernels; the fused "
            "path's measurement (temp_bytes below the fp32 row) lives "
            "in QUANT_EVIDENCE's quant_kernel rows"
        ),
    }
    print(json.dumps(wire_summary))

    # -- claim 4: pressure gate + shard hint ----------------------------------
    # (measured BEFORE the overhead claim: its small model must not be
    # drowned in the overhead steppers' still-live buffers)
    bf.init(devices=devices[:n])
    ctx = bf.get_context()
    obs_p = bf_memory.start(interval=1)
    opt_p = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.02))
    p_p = {"w": bf.worker_values(
        lambda r: np.zeros(1 << 16, np.float32)
    )}
    s_p = opt_p.init(p_p)
    g_p = {"w": bf.worker_values(
        lambda r: rng.randn(1 << 16).astype(np.float32)
    )}
    p_p, s_p = opt_p.step(p_p, s_p, g_p)
    # budget just under the measured footprint: the very next sample
    # must read zero headroom and fire the pressure advisory
    obs_p.budget = int(obs_p.last_bytes_per_rank() * 0.9) or 1
    for _ in range(3):
        p_p, s_p = opt_p.step(p_p, s_p, g_p)
    pressures = [
        a for a in obs_p.advisories if a.kind == "memory_pressure"
    ]
    pressure_line = {
        "metric": "memory_pressure",
        "budget_bytes": obs_p.budget,
        "bytes_per_rank": int(obs_p.last_bytes_per_rank()),
        "headroom_bytes": int(obs_p.last_headroom()),
        "advisory_fired": bool(pressures),
        "shard_hint": (
            pressures[0].detail.get("shard_hint") if pressures
            else None
        ),
        "opt_state_fraction": (
            pressures[0].detail.get("opt_state_fraction")
            if pressures else None
        ),
    }
    print(json.dumps(pressure_line))
    bf_memory.stop()
    del opt_p, p_p, s_p, g_p
    import gc

    gc.collect()

    # -- claim 3: overhead / structural / bitwise pins ------------------------
    bf.set_topology(topo.RingGraph(n))
    w0 = [
        (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        for _ in range(layers)
    ]
    xs_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )
    ys_b = bf.worker_values(
        lambda r: rng.randn(batch, dim).astype(np.float32)
    )

    def loss_fn(p, x, y):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    def make_stepper():
        opt_s = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.01, momentum=0.9)
        )
        train_step = bf.make_train_step(opt_s, loss_fn)
        params_s = {
            f"w{i}": bf.worker_values(lambda r, i=i: w0[i])
            for i in range(layers)
        }
        carry = [(params_s, opt_s.init(params_s))]

        def _step():
            p, s = carry[0]
            p, s, loss = train_step(p, s, xs_b, ys_b)
            carry[0] = (p, s)
            return loss

        return _step, carry

    # structural pin: the observatory compiles NOTHING — enabling it
    # adds zero cache entries of any kind
    bf_memory.stop()
    stepper, _carry = make_stepper()
    stepper()
    stepper()
    keys_off = set(ctx.op_cache)
    bf_memory.start(interval=1)
    stepper()
    stepper()
    keys_on = set(ctx.op_cache)
    unsampled_shared = keys_on == keys_off
    bf_memory.stop()

    # bitwise trajectory pin
    state_bits = {}
    for variant in ("off", "on"):
        if variant == "on":
            bf_memory.start(interval=3)
        else:
            bf_memory.stop()
        _step, carry = make_stepper()
        for _ in range(12):
            _step()
        state_bits[variant] = jax.tree_util.tree_leaves(carry[0])
    bf_memory.stop()
    bitwise = all(
        bool(np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(state_bits["off"], state_bits["on"])
    )

    # overhead at the default interval, all-orderings rotation + A/A
    steppers = {}
    obs_on = bf_memory.MemoryObservatory(interval=1)
    for variant in ("off", "on", "off2"):
        bf_memory.activate(obs_on if variant == "on" else None)
        steppers[variant], _ = make_stepper()
        steppers[variant]()  # compile
        _settle(steppers[variant]())
    orders = list(itertools.permutations(("off", "on", "off2")))
    times = {v: [] for v in steppers}
    for i in range(samples):
        for variant in orders[i % len(orders)]:
            bf_memory.activate(obs_on if variant == "on" else None)
            t0 = time_mod.perf_counter()
            _settle(steppers[variant]())
            times[variant].append(time_mod.perf_counter() - t0)
    bf_memory.activate(None)

    def median(v):
        v = sorted(v)
        return v[len(v) // 2] if v else 0.0

    base_s = median(times["off"])
    sample_extra_s = median(
        [on - off for off, on in zip(times["off"], times["on"])]
    )
    control_extra_s = median(
        [o2 - off for off, o2 in zip(times["off"], times["off2"])]
    )
    overhead_pct = (
        100.0 * sample_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    control_pct = (
        100.0 * control_extra_s / default_interval / base_s
        if base_s > 0 else 0.0
    )
    overhead_line = {
        "metric": "memory_overhead",
        "n_workers": n,
        "payload_mb": round(layers * dim * dim * 4 / 1e6, 2),
        "interval": default_interval,
        "ms_per_step_off": round(base_s * 1e3, 3),
        "ms_sampled_step_extra": round(sample_extra_s * 1e3, 3),
        "overhead_pct": round(overhead_pct, 3),
        "control_aa_pct": round(control_pct, 3),
        "unsampled_program_shared": unsampled_shared,
        # MEASURED: cache entries that appeared while the observatory
        # was on (the structural claim is that this is zero — it
        # compiles nothing)
        "observatory_cache_entries": len(keys_on - keys_off),
        "bitwise_identical": bitwise,
        "samples": samples,
    }
    print(json.dumps(overhead_line))
    bf.shutdown()

    bf_metrics.flush()
    for k, v in old_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert reconcile_line["both_within_tolerance"], (
            "analytic-vs-measured optimizer-state reconciliation "
            f"exceeded the {tol} tolerance: {reconcile_line}"
        )
        assert reconcile_line[
            "ratio_consistent_with_shard_evidence"
        ], (
            f"measured shard ratio {ratio:.4f} inconsistent with "
            f"SHARD_EVIDENCE's {shard_ref} at N={n}"
        )
        assert wire_summary["all_full_width"], (
            "a quantized combine's measured scratch lost the "
            f"full-width temporary: {wire_rows}"
        )
        assert wire_summary["quantized_scratch_exceeds_exact"], (
            "quantized scratch no longer exceeds the exact path's — "
            "either fusion landed (update this baseline) or the "
            f"accounting broke: {temps}"
        )
        assert unsampled_shared, (
            "enabling the memory observatory changed the compiled "
            "cache entries (it must compile nothing)"
        )
        assert bitwise, (
            "enabling the memory observatory changed the training "
            "state bitwise"
        )
        assert overhead_pct <= 1.0, (
            f"memory-observatory overhead {overhead_pct:.3f}% exceeds "
            f"the 1% acceptance bound at interval {default_interval}"
        )
        assert pressure_line["advisory_fired"], pressure_line
        assert pressure_line["shard_hint"] is True, (
            "memory_pressure fired without the shard hint although "
            f"the Adam state dominates and BLUEFOG_SHARD is off: "
            f"{pressure_line}"
        )
    return 0


def run_fleetscale() -> int:
    """Fleet-scale control-plane evidence (``BENCH_MODE=fleetscale``,
    committed as FLEETSCALE_EVIDENCE.json). The fleet simulator
    (``bf.fleetsim``, docs/fleetsim.md) drives the real membership
    state machine, repair-weight algebra, and plan-cache key
    discipline for hundreds-to-thousands of virtual ranks — no device
    dispatch, so every number here is pure control-plane cost. Four
    claims:

    1. **Per-membership-event cost is sublinear in N**
       (``fleetscale_event_scaling``): a 32-kill cascade at N in
       {128..1024} under the structure-preserving ``receiver`` policy
       (lazy neighborhood renormalization, O(degree^2) per kill;
       ``average`` rebuilds O(edges) per event and is excluded from
       the sublinearity claim — disclosed). The growth exponent of
       the per-event repair cost (log-log least squares over the N
       sweep, best-of-3 runs) must stay < 1. The dense baseline
       (full ``repaired_matrix`` + dense-eig verdict per event) is
       timed at small N only and extrapolated by its own fitted
       power law — the extrapolation model is disclosed in the row,
       not silently assumed.
    2. **A 10 % simultaneous rank-loss storm at N=1024 repairs with
       zero stale dispatches** (``fleetscale_storm``): audit mode ON
       — every dispatch replays its plan's compile-time edge snapshot
       against the current dead set, so one surviving stale plan
       would trip the counter. Asserts zero, plus the churn advisory
       and the exact post-storm live count.
    3. **Controller decision latency at N=1024 is bounded**
       (``fleetscale_decision``): one decision over the candidate set
       (incumbent / live ring / live Exp2) through the sparse
       spectral engine, every candidate's convergence disclosure
       carried; asserts the sparse engine actually ran and the
       decision landed under the bound.
    4. **The sparse engine agrees with the dense oracle at the
       routing boundary** (``fleetscale_agreement``): |sparse-SLEM -
       dense-SLEM| <= 1e-9 at N around ``BLUEFOG_SPECTRAL_DENSE_MAX``
       (the tier-1 property sweep pins this exhaustively; the
       evidence row keeps the claim visible next to the numbers that
       depend on it).
    """
    import numpy as np

    from bluefog_tpu import fleetsim
    from bluefog_tpu.topology import spectral as spectral_mod

    topology = "exp2"
    policy = "receiver"
    kills = 32
    best_of = 3

    # -- claim 1: per-event cost scaling ----------------------------------
    sweep_ns = (128, 256, 512, 1024)
    cells = []
    for n in sweep_ns:
        means, maxes = [], []
        for rep in range(best_of):
            plan = fleetsim.cascade_plan(n, kills, start_step=1,
                                         stride=1, seed=rep)
            vf = fleetsim.VirtualFleet(n, topology=topology,
                                       policy=policy, plan=plan,
                                       audit_edges=False, seed=rep)
            vf.run(kills + 4)
            evs = [e["event_ms"] for e in vf.events
                   if e["metric"] == "fleetsim_repair"]
            means.append(float(np.mean(evs)))
            maxes.append(float(np.max(evs)))
        cells.append({
            "n": n,
            "repairs": kills,
            # best-of-N: ambient stalls only ever inflate a window
            "event_ms_mean": round(min(means), 6),
            "event_ms_max": round(min(maxes), 6),
            "spread_ms": round(max(means) - min(means), 6),
        })
    xs = np.log([c["n"] for c in cells])
    ys = np.log([max(c["event_ms_mean"], 1e-9) for c in cells])
    exponent = float(np.polyfit(xs, ys, 1)[0])

    # dense baseline: full-matrix repair + dense-eig verdict per event,
    # timed at small N, extrapolated by its own fitted power law
    from bluefog_tpu.elastic.repair import repaired_matrix

    dense_ns = (64, 128, 256)
    dense_cells = []
    for n in dense_ns:
        edges = fleetsim.base_edges(n, topology)
        w = np.zeros((n, n))
        for (i, j), v in edges.items():
            w[i, j] = v
        rng = np.random.RandomState(0)
        dead = sorted(rng.choice(n, size=max(1, n // 32),
                                 replace=False).tolist())
        live = [r for r in range(n) if r not in dead]
        reps = []
        for _ in range(best_of):
            t0 = time.perf_counter()
            fixed = repaired_matrix(w, live, policy=policy)
            sub = fixed[np.ix_(live, live)]
            spectral_mod.dense_slem(sub)
            reps.append((time.perf_counter() - t0) * 1e3)
        dense_cells.append({"n": n, "event_ms": round(min(reps), 6)})
    dxs = np.log([c["n"] for c in dense_cells])
    dys = np.log([c["event_ms"] for c in dense_cells])
    dfit = np.polyfit(dxs, dys, 1)
    dense_exponent = float(dfit[0])
    dense_at_1024_ms = float(np.exp(dfit[1]) * 1024 ** dense_exponent)
    sparse_at_1024 = cells[-1]["event_ms_mean"]
    scaling_line = {
        "metric": "fleetscale_event_scaling",
        "topology": topology,
        "policy": policy,
        "cells": cells,
        "growth_exponent": round(exponent, 4),
        "sublinear": exponent < 1.0,
        "dense_baseline_cells": dense_cells,
        "dense_growth_exponent": round(dense_exponent, 4),
        "dense_extrapolation_model": (
            "power-law fit of the measured dense per-event cost "
            f"(log-log least squares over N={list(dense_ns)}), "
            "evaluated at N=1024 — the dense path (full repaired_matrix "
            "+ O(N^3) eig verdict) is never actually run at 1024"
        ),
        "dense_at_1024_ms_extrapolated": round(dense_at_1024_ms, 3),
        "sparse_at_1024_ms": sparse_at_1024,
        "speedup_at_1024_extrapolated": round(
            dense_at_1024_ms / max(sparse_at_1024, 1e-9), 1),
        "note": (
            "per-event cost = lazy neighborhood renormalization of the "
            "killed ranks (receiver policy); the 'average' policy "
            "rebuilds O(edges) per event and is excluded from the "
            "sublinearity claim"
        ),
    }
    print(json.dumps(scaling_line), flush=True)

    # -- claim 2: 10% storm at N=1024, zero stale dispatches ---------------
    n = 1024
    frac = 0.10
    plan = fleetsim.storm_plan(n, frac, step=5, seed=1)
    killed = len(plan.faults)
    vf = fleetsim.VirtualFleet(n, topology=topology, policy=policy,
                               plan=plan, audit_edges=True, seed=1)
    vf.run(12)
    summary = vf.summary()
    storm_line = {
        "metric": "fleetscale_storm",
        "n": n,
        "fraction": frac,
        "killed": killed,
        "steps": summary["steps"],
        "live_after": summary["live"],
        "repair_events": summary["repairs"],
        "stale_dispatches": summary["stale_dispatches"],
        "worst_event_ms": summary["worst_event_ms"],
        "cache_hits": summary["cache_hits"],
        "cache_misses": summary["cache_misses"],
        "advisories": [a["kind"] for a in summary["advisories"]],
        "audit": "every dispatch replays the plan's compile-time edge "
                 "snapshot against the current dead set",
    }
    print(json.dumps(storm_line), flush=True)

    # -- claim 3: decision latency at N=1024 -------------------------------
    decision_bound_ms = 30_000.0
    probe = vf.decision_probe()
    decision_line = {
        "metric": "fleetscale_decision",
        "n_live": probe["n_live"],
        "chosen": probe["chosen"],
        "decision_ms": probe["decision_ms"],
        "bound_ms": decision_bound_ms,
        "candidates": probe["candidates"],
    }
    print(json.dumps(decision_line), flush=True)

    # -- claim 4: sparse/dense agreement at the routing boundary -----------
    agree_rows = []
    worst = 0.0
    for kind in ("ring", "exp2"):
        for an in (48, 64):
            edges = fleetsim.base_edges(an, kind)
            em = spectral_mod.EdgeMatrix(an, edges)
            sparse_rho, _ = spectral_mod.slem_info((an, edges))
            dense_rho = spectral_mod.dense_slem(em.to_dense())
            diff = abs(sparse_rho - dense_rho)
            worst = max(worst, diff)
            agree_rows.append({
                "topology": kind, "n": an,
                "sparse": sparse_rho, "dense": dense_rho,
                "abs_diff": diff,
            })
    agreement_line = {
        "metric": "fleetscale_agreement",
        "tolerance": 1e-9,
        "worst_abs_diff": worst,
        "rows": agree_rows,
        "note": "tests/test_spectral.py sweeps every generator x N x "
                "live subset x period product at this tolerance",
    }
    print(json.dumps(agreement_line), flush=True)

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert scaling_line["sublinear"], (
            f"per-event control-plane cost grew with exponent "
            f"{exponent:.3f} >= 1 over N={list(sweep_ns)}: {cells}"
        )
        assert scaling_line["speedup_at_1024_extrapolated"] > 10.0, (
            "sparse per-event repair no longer clearly beats the "
            f"extrapolated dense baseline at N=1024: {scaling_line}"
        )
        assert storm_line["stale_dispatches"] == 0, (
            f"storm repair leaked stale dispatches: {storm_line}"
        )
        assert storm_line["live_after"] == n - killed, storm_line
        assert storm_line["repair_events"] >= 1, storm_line
        assert "fleet_churn" in storm_line["advisories"], storm_line
        assert decision_line["decision_ms"] <= decision_bound_ms, (
            f"N=1024 decision latency {decision_line['decision_ms']}ms "
            f"exceeded the {decision_bound_ms}ms bound"
        )
        for name, cand in decision_line["candidates"].items():
            assert cand["spectral"]["engine"] == "sparse", (
                f"candidate {name} was not scored by the sparse "
                f"engine at fleet scale: {cand}"
            )
        assert agreement_line["worst_abs_diff"] <= 1e-9, agreement_line
    return 0


def run_federate() -> int:
    """Hierarchical-federation evidence (``BENCH_MODE=federate``,
    committed as FEDERATE_EVIDENCE.json). A two-pod fabric
    (``bf.federation``, docs/federation.md): intra-pod gossip on ICI at
    full rate, a designated-gateway inter-pod leg on DCN every
    ``BLUEFOG_DCN_PERIOD``-th communicating step at the aggressive DCN
    wire tier. Four claims:

    1. **The chosen DCN period matches the spectral prediction**
       (``federate_period``): ``choose_dcn_period`` picks the largest
       period whose composed two-level window (scored end-to-end by the
       PR-18 sparse engine) still meets the target per-step consensus
       rate; the MEASURED rate (host gossip of a random mean-zero
       vector through the real period-T matrix window) must agree with
       the prediction within a disclosed absolute tolerance.
    2. **DCN wire bytes cut >= 8x vs flat gossip at matched measured
       consensus rate** (``federate_wire``): the flat baseline is the
       same base topology spanning both pods, gossiping every k-th
       step with k chosen so its measured per-step rate is at least as
       good as the federated fabric's — the strongest flat opponent at
       the matched rate. Cross-pod bytes per communicating step, both
       sides per-edge totals. The flat side is priced at the exact
       fp32 wire (a flat fabric has ONE tier for all edges — per-leg
       tiers are the point of federation); the all-int4 flat variant
       is disclosed unasserted, since its consensus-error cost is not
       modeled here.
    3. **Whole-pod loss is ONE repair event with zero stale
       dispatches** (``federate_podloss``): a 4x16 fleetsim fleet
       loses pod 1 entirely at one step — the batched repair
       re-elects gateways and renormalizes the inter-pod ring in the
       same event, audit mode on.
    4. **The live dispatch accounts per-leg wire bytes**
       (``federate_dispatch``): a real 8-device 2-pod optimizer run
       under ``BLUEFOG_METRICS=1`` — the
       ``bluefog.federation.{ici,dcn}_wire_bytes`` counters must
       reconcile with the DCN event count and the global mean must be
       preserved through the two-level combine.
    """
    import numpy as np

    from bluefog_tpu import federation, fleetsim

    kind = "exp2"
    n = 16
    layout = federation.parse_pods("2x8", n)

    # -- claim 1: chosen period vs measured rate ---------------------------
    target_rate = float(os.environ.get("BENCH_FED_TARGET_RATE", "0.985"))
    rate_tol = 0.02
    chosen = federation.choose_dcn_period(layout, target_rate, kind=kind)
    period = chosen["period"]
    w_ici = (n, federation.intra_edges(layout, kind))
    w_dcn = (n, federation.inter_edges(layout))
    measured_fed = federation.simulate_consensus(
        [w_ici] * period + [w_dcn], steps=max(4, 256 // period),
        comm_steps_per_cycle=period,
    )
    period_line = {
        "metric": "federate_period",
        "n": n,
        "pods": layout.n_pods,
        "kind": kind,
        "target_rate": target_rate,
        "chosen_period": period,
        "predicted_rate": round(chosen["predicted_rate"], 6),
        "measured_rate": round(measured_fed, 6),
        "abs_err": round(abs(chosen["predicted_rate"] - measured_fed), 6),
        "tolerance": rate_tol,
        "met": chosen["met"],
        "table": chosen["table"],
    }
    print(json.dumps(period_line), flush=True)

    # -- claim 2: matched-rate DCN byte cut --------------------------------
    flat_edges = (n, fleetsim.base_edges(n, kind))
    measured_flat = federation.simulate_consensus([flat_edges], steps=64)
    # flat gossiping every k-th step contracts rate_flat^(1/k) per step;
    # the largest k keeping that at least as strong as the federated
    # measured rate is the cheapest flat opponent at the matched rate
    k = max(1, int(np.floor(
        np.log(max(measured_flat, 1e-12))
        / np.log(max(measured_fed, 1e-12))
    )))
    n_elems = int(os.environ.get("BENCH_FED_ELEMS", str(1 << 20)))
    ws = federation.wire_summary(
        layout, n_elems, itemsize=4, ici_wire=None,
        dcn_wire_tier="int4", period=period, kind=kind,
    )
    flat_dcn_per_step = ws["flat_dcn_bytes_per_step"] / k
    ratio = flat_dcn_per_step / max(ws["dcn_wire_bytes_per_step"], 1e-9)
    ws_int4 = federation.wire_summary(
        layout, n_elems, itemsize=4, ici_wire="int4",
        dcn_wire_tier="int4", period=period, kind=kind,
    )
    ratio_flat_int4 = (
        ws_int4["flat_dcn_bytes_per_step"] / k
        / max(ws["dcn_wire_bytes_per_step"], 1e-9)
    )
    wire_line = {
        "metric": "federate_wire",
        "n": n,
        "n_elems": n_elems,
        "dcn_wire": ws["dcn_wire"],
        "dcn_period": period,
        "measured_rate_fed": round(measured_fed, 6),
        "measured_rate_flat_dense": round(measured_flat, 6),
        "flat_gossip_every": k,
        "measured_rate_flat_matched": round(
            measured_flat ** (1.0 / k), 6
        ),
        "fed_dcn_bytes_per_step": round(
            ws["dcn_wire_bytes_per_step"], 1
        ),
        "flat_dcn_bytes_per_step_matched": round(flat_dcn_per_step, 1),
        "flat_cross_pod_edges": ws["flat_cross_pod_edges"],
        "dcn_cut_ratio_matched": round(ratio, 2),
        "dcn_cut_ratio_flat_int4_unasserted": round(ratio_flat_int4, 2),
        "ici_wire_bytes_per_step": ws["ici_wire_bytes_per_step"],
        "note": (
            "both sides per-edge cross-pod totals per communicating "
            "step; flat opponent gossips every k-th step so its "
            "measured per-step rate is at least as strong as the "
            "federated fabric's"
        ),
    }
    print(json.dumps(wire_line), flush=True)

    # -- claim 3: whole-pod loss = one repair event ------------------------
    n2 = 64
    layout2 = federation.parse_pods("4x16", n2)
    lost = layout2.ranks(1)
    plan = fleetsim.region_plan(n2, lost.start, lost.stop, step=3)
    os.environ["BLUEFOG_PODS"] = "4x16"
    try:
        ff = federation.FederatedFleet(
            layout2, kind=kind, policy="receiver", plan=plan,
            audit_edges=True, seed=0,
        )
        ff.run(8)
        summary = ff.summary()
    finally:
        os.environ.pop("BLUEFOG_PODS", None)
    repair_events = [
        e for e in ff.fleet.events if e["metric"] == "fleetsim_repair"
    ]
    podloss_line = {
        "metric": "federate_podloss",
        "n": n2,
        "pods": layout2.n_pods,
        "pod_lost": 1,
        "ranks_lost": len(lost),
        "repair_events": summary["repairs"],
        "stale_dispatches": summary["stale_dispatches"],
        "loss_class": (
            repair_events[0].get("loss_class") if repair_events else None
        ),
        "pods_lost": (
            repair_events[0].get("pods_lost") if repair_events else None
        ),
        "gateways_after": summary["federation"]["gateways"],
        "gateway_change": (
            repair_events[0].get("gateway_change")
            if repair_events else None
        ),
        "event_ms": (
            repair_events[0].get("event_ms") if repair_events else None
        ),
        "live_after": summary["live"],
    }
    print(json.dumps(podloss_line), flush=True)

    # -- claim 4: live dispatch, per-leg counters --------------------------
    from bluefog_tpu.platforms import ensure_cpu_device_count

    ensure_cpu_device_count(8)
    os.environ["BLUEFOG_PODS"] = "2"
    os.environ["BLUEFOG_DCN_PERIOD"] = "4"
    os.environ["BLUEFOG_METRICS"] = "1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import metrics as metrics_mod

    federation.clear_fabric_cache()
    bf.init(devices=jax.devices()[:8])
    steps = 8
    dcn_events = (steps + 3) // 4
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.05))
    params = {"w": bf.worker_values(lambda r: jnp.full((256,), float(r)))}
    state = opt.init(params)
    train = bf.make_train_step(
        opt, lambda p, b: jnp.sum(p["w"] ** 2) * 0.0
    )
    for _ in range(steps):
        params, state, _loss = train(params, state, None)
    snap = metrics_mod.snapshot()
    w = np.asarray(params["w"])
    fab = federation.get_fabric(8)
    dispatch_line = {
        "metric": "federate_dispatch",
        "devices": 8,
        "pods": 2,
        "dcn_period": 4,
        "dcn_wire": fab.wire if fab else None,
        "steps": steps,
        "dcn_events": dcn_events,
        "ici_wire_bytes": snap.get(
            "bluefog.federation.ici_wire_bytes", {}
        ).get("value"),
        "dcn_wire_bytes": snap.get(
            "bluefog.federation.dcn_wire_bytes", {}
        ).get("value"),
        "total_wire_bytes": snap.get(
            "bluefog.wire_bytes", {}
        ).get("value"),
        "mean_preserved": bool(
            np.isclose(float(w.mean()), (8 - 1) / 2.0, atol=1e-4)
        ),
        "consensus_spread": round(
            float(w.mean(axis=1).max() - w.mean(axis=1).min()), 6
        ),
    }
    print(json.dumps(dispatch_line), flush=True)

    if os.environ.get("BENCH_ASSERT", "1") != "0":
        assert period_line["met"], (
            f"no DCN period meets the {target_rate} target: {period_line}"
        )
        assert period_line["abs_err"] <= rate_tol, (
            "measured federated consensus rate drifted from the "
            f"spectral prediction: {period_line}"
        )
        assert wire_line["dcn_cut_ratio_matched"] >= 8.0, (
            f"DCN byte cut fell below 8x at matched rate: {wire_line}"
        )
        assert podloss_line["repair_events"] == 1, (
            f"whole-pod loss was not ONE repair event: {podloss_line}"
        )
        assert podloss_line["stale_dispatches"] == 0, podloss_line
        assert podloss_line["loss_class"] == "pod_loss", podloss_line
        assert podloss_line["pods_lost"] == [1], podloss_line
        assert podloss_line["live_after"] == n2 - len(lost), podloss_line
        assert dispatch_line["ici_wire_bytes"], dispatch_line
        assert dispatch_line["dcn_wire_bytes"], dispatch_line
        assert dispatch_line["mean_preserved"], dispatch_line
        expected_total = (
            dispatch_line["ici_wire_bytes"]
            + dispatch_line["dcn_wire_bytes"]
        )
        assert dispatch_line["total_wire_bytes"] == expected_total, (
            "per-leg counters do not reconcile with the total: "
            f"{dispatch_line}"
        )
    return 0


def run_all() -> int:
    """The full evidence set: each family in an isolated subprocess (the
    scaling family must own backend init; a family crash must not take
    out the headline), headline last for tail-reading drivers."""
    import subprocess

    for mode in ("scaling", "plan", "overlap", "metrics", "elastic",
                 "flight", "attribution", "health", "slo",
                 "staleness", "autotune", "async", "quant", "shard",
                 "memory", "fleetscale", "federate", "gossip",
                 "flash", "transformer"):
        env = dict(os.environ, BENCH_MODE=mode)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)], env=env,
                capture_output=True, text=True, timeout=2400,
            )
        except subprocess.TimeoutExpired as e:
            # isolation contract: a hung family must not take out the
            # remaining families or the headline
            print(json.dumps({
                "metric": f"bench_{mode}_failed",
                "timeout_s": 2400,
                "stdout_tail": (e.stdout or b"").decode(
                    "utf-8", "replace"
                )[-200:] if isinstance(e.stdout, bytes)
                else (e.stdout or "")[-200:],
            }), flush=True)
            continue
        for line in proc.stdout.splitlines():
            if line.startswith("{"):
                print(line, flush=True)
        if proc.returncode != 0:
            print(json.dumps({
                "metric": f"bench_{mode}_failed",
                "returncode": proc.returncode,
                "stderr_tail": proc.stderr[-400:],
            }), flush=True)
    return run_headline()


def main() -> int:
    mode = os.environ.get("BENCH_MODE", "")
    print(json.dumps(_provenance()), flush=True)
    runners = {
        "scaling": run_scaling,
        "elastic": run_elastic,
        "plan": run_plan,
        "overlap": run_overlap,
        "metrics": run_metrics,
        "flight": run_flight,
        "attribution": run_attribution,
        "health": run_health,
        "slo": run_slo,
        "staleness": run_staleness,
        "autotune": run_autotune,
        "async": run_async,
        "quant": run_quant,
        "shard": run_shard,
        "memory": run_memory,
        "fleetscale": run_fleetscale,
        "federate": run_federate,
        "gossip": run_gossip_overhead,
        "transformer": run_transformer,
        "flash": run_flash,
        "headline": run_headline,
    }
    rc = runners.get(mode, run_all)()
    # the ambient-drift anchor closes EVERY evidence artifact: measured
    # after the mode ran (the mode owns backend/platform init), memoized
    # so a headline's embedded vs_anchor is this same measurement
    try:
        print(json.dumps(_ambient_anchor()), flush=True)
    except Exception as e:  # an anchor failure must not fail the bench
        print(json.dumps({
            "metric": "ambient_anchor", "error": str(e)[:200],
        }), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
