# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Metrics subsystem tests: registry semantics, exporters, the in-graph
gossip-health device tier (numpy-oracled), and the load-bearing pin that
enabling metrics never perturbs the training state.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
import bluefog_tpu.topology as tu
from bluefog_tpu import metrics
from bluefog_tpu.collective import ops as col_ops

SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    monkeypatch.delenv("BLUEFOG_METRICS", raising=False)
    monkeypatch.delenv("BLUEFOG_METRICS_FILE", raising=False)
    monkeypatch.delenv("BLUEFOG_METRICS_PROM", raising=False)
    metrics.reset()
    bf.init(devices=cpu_devices[:SIZE])
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    yield
    bf.shutdown()
    metrics.reset()


# -- host-tier registry -------------------------------------------------------


def test_registry_counter_gauge_histogram():
    metrics.counter("c").inc()
    metrics.counter("c").inc(2.5)
    metrics.gauge("g").set(7)
    h = metrics.histogram("h")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    snap = metrics.snapshot()
    assert snap["c"] == {"type": "counter", "value": 3.5}
    assert snap["g"] == {"type": "gauge", "value": 7.0}
    assert snap["h"]["count"] == 3 and snap["h"]["min"] == 1.0
    assert snap["h"]["max"] == 3.0 and snap["h"]["last"] == 2.0


def test_registry_rejects_type_conflict():
    metrics.counter("series")
    with pytest.raises(TypeError):
        metrics.gauge("series")


def test_facade_snapshot_and_export(tmp_path):
    metrics.counter("bluefog.test").inc(4)
    jsonl = str(tmp_path / "m.jsonl")
    prom = str(tmp_path / "m.prom")
    snap = bf.metrics_export(jsonl_path=jsonl, prom_path=prom)
    assert snap["bluefog.test"]["value"] == 4.0
    assert bf.metrics_snapshot()["bluefog.test"]["value"] == 4.0
    (line,) = open(jsonl).read().splitlines()
    obj = json.loads(line)
    assert obj["metrics"]["bluefog.test"]["value"] == 4.0
    text = open(prom).read()
    assert "# TYPE bluefog_test_total counter" in text
    assert "bluefog_test_total 4" in text


def test_prom_export_sanitizes_and_types(tmp_path):
    metrics.gauge("bluefog.gossip.rounds").set(3)
    metrics.histogram("bluefog.lat").observe(0.5)
    path = metrics.export_prom(str(tmp_path / "x.prom"))
    text = open(path).read()
    assert "bluefog_gossip_rounds 3" in text
    assert "bluefog_lat_count 1" in text and "bluefog_lat_sum 0.5" in text
    # no stray characters survive sanitization
    for line in text.splitlines():
        assert " " in line and not line.startswith("."), line


def test_histogram_log_bucket_quantiles():
    """Bounded log-bucket tail quantiles: p50/p90/p99 within the
    documented ~9% relative error on a known distribution, bounded
    bucket count on a hostile range, zero handling, describe() and
    exporter surfacing."""
    h = metrics.Histogram()
    for v in range(1, 1001):  # uniform 1..1000: p50=500, p90=900
        h.observe(float(v))
    assert h.quantile(0.5) == pytest.approx(500, rel=0.1)
    assert h.quantile(0.9) == pytest.approx(900, rel=0.1)
    assert h.quantile(0.99) == pytest.approx(990, rel=0.1)
    desc = h.describe()
    assert desc["p50"] == h.quantile(0.5)
    assert desc["p99"] <= desc["max"]
    # quantiles never escape the observed envelope
    one = metrics.Histogram()
    one.observe(7.3)
    assert one.quantile(0.5) == 7.3 and one.quantile(0.99) == 7.3
    # bounded storage on a hostile range; zeros share the underflow
    # bucket and report at the floor, not a crash
    wild = metrics.Histogram()
    for v in (0.0, -5.0, 1e-30, 1e30, 3.0):
        wild.observe(v)
    assert len(wild._buckets) <= 321
    assert wild.quantile(0.5) is not None
    empty = metrics.Histogram()
    assert empty.quantile(0.5) is None
    assert "p50" not in empty.describe()


def test_histogram_quantile_clamp_boundary_read_only():
    """Satellite: ``Histogram.quantile`` at the 321-bucket clamp
    boundary — observations beyond the 2**±40 index range share the
    edge buckets yet every reported quantile stays inside the exact
    observed [min, max] envelope — and the read is PURE: quantile()
    mutates no exporter state (the SLO engine's p99 reads must never
    perturb a scrape)."""
    h = metrics.Histogram()
    # both sides of the clamp: overflow bucket (2**40 and far beyond
    # alias to _IDX_MAX) and underflow (<= 2**-40 and zero/negative)
    for v in (2.0 ** 41, 1e13, 3e13, 2.0 ** -41, 1e-13, 0.0):
        h.observe(v)
    assert len(h._buckets) <= 2  # everything clamped to the two edges
    lo, hi = h.min, h.max
    for q in (0.01, 0.5, 0.99, 1.0):
        v = h.quantile(q)
        assert lo <= v <= hi, (q, v)
    # the overflow bucket's representative (2**40) is BELOW the true
    # max — the envelope clamp is what keeps p99 honest out there
    assert h.quantile(0.99) <= hi
    before = (h.count, h.sum, h.min, h.max, h.last, dict(h._buckets))
    desc_before = h.describe()
    for q in (0.5, 0.99):
        h.quantile(q)
    assert (h.count, h.sum, h.min, h.max, h.last,
            dict(h._buckets)) == before
    assert h.describe() == desc_before
    # and a registry-level read through peek() creates nothing
    metrics.reset()
    assert metrics.peek("bluefog.slo.never_written") is None
    assert metrics.snapshot() == {}


def test_prom_export_deterministic_with_help_and_quantiles(tmp_path):
    """Satellite: successive scrapes of an unchanged registry are
    byte-identical (deterministic series ordering) and every family
    carries # HELP/# TYPE; histogram quantiles export as
    {quantile=...} series."""
    metrics.gauge("bluefog.z_last").set(1)
    metrics.counter("bluefog.a_first").inc()
    for v in (1.0, 2.0, 4.0):
        metrics.histogram("bluefog.lat").observe(v)
    p1 = str(tmp_path / "a.prom")
    p2 = str(tmp_path / "b.prom")
    metrics.export_prom(p1)
    metrics.export_prom(p2)
    t1, t2 = open(p1).read(), open(p2).read()
    assert t1 == t2  # diffs cleanly scrape to scrape
    lines = t1.splitlines()
    # sorted by raw name: a_first family renders before lat before z_last
    first_of = {
        name: next(
            i for i, l in enumerate(lines) if name in l
        )
        for name in ("bluefog_a_first", "bluefog_lat", "bluefog_z_last")
    }
    assert first_of["bluefog_a_first"] < first_of["bluefog_lat"] < (
        first_of["bluefog_z_last"]
    )
    for pname, ptype in (
        ("bluefog_a_first_total", "counter"),
        ("bluefog_z_last", "gauge"),
        ("bluefog_lat", "summary"),
    ):
        assert f"# HELP {pname} " in t1
        assert f"# TYPE {pname} {ptype}" in t1
    assert 'bluefog_lat{quantile="0.5"}' in t1
    assert 'bluefog_lat{quantile="0.99"}' in t1


def test_metrics_report_surfaces_histogram_quantiles(tmp_path):
    """tools/metrics_report.py renders p50/p90/p99 as synthetic series
    rows, so a JSONL digest can state tail latency."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    row = {"ts": 1.0, "metrics": {
        "bluefog.lat": {"type": "histogram", "count": 3, "sum": 7.0,
                        "min": 1.0, "max": 4.0, "last": 4.0,
                        "p50": 2.0, "p90": 4.0, "p99": 4.0},
    }}
    path = tmp_path / "run.jsonl"
    path.write_text(json.dumps(row) + "\n")
    env = dict(os.environ, PYTHONPATH=repo + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools",
                                      "metrics_report.py"),
         str(path), "--json"],
        capture_output=True, text=True, timeout=60, env=env, cwd=repo,
    )
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert report["series"]["bluefog.lat.p50"]["last"] == 2.0
    assert report["series"]["bluefog.lat.p99"]["last"] == 4.0


# -- satellite: unknown log level warns once ----------------------------------


def test_unknown_log_level_warns_once(monkeypatch, caplog):
    from bluefog_tpu import logging_util

    monkeypatch.setenv("BLUEFOG_LOG_LEVEL", "chatty-nonsense")
    bf.logger.propagate = True
    try:
        with caplog.at_level("WARNING", logger="bluefog_tpu"):
            logging_util._configure_from_env()
            logging_util._configure_from_env()  # second call: silent
    finally:
        bf.logger.propagate = False
        monkeypatch.delenv("BLUEFOG_LOG_LEVEL")
        logging_util._configure_from_env()
    warns = [
        r for r in caplog.records if "BLUEFOG_LOG_LEVEL" in r.message
    ]
    assert len(warns) == 1, [r.message for r in caplog.records]
    assert "chatty-nonsense" in warns[0].getMessage()
    assert "trace" in warns[0].getMessage()  # names the accepted set


# -- device tier: numpy oracle ------------------------------------------------


def test_disagreement_matches_numpy_oracle(cpu_devices, monkeypatch):
    """Consensus-distance oracle on a hand-built 4-node weighted digraph:
    after one communicating step, the drained disagreement gauge equals
    ``rms_i ||x_i - sum_j W[j, i] x_j||`` computed in numpy."""
    import networkx as nx

    n = 4
    # weighted digraph: 0->1->2->3->0 plus 0->2, receiver-normalized
    w = np.zeros((n, n))
    np.fill_diagonal(w, [0.5, 0.6, 0.4, 0.7])
    w[0, 1] = 0.4
    w[1, 2] = 0.35
    w[2, 3] = 0.3
    w[3, 0] = 0.5
    w[0, 2] = 0.25
    assert np.allclose(w.sum(axis=0), 1.0)
    g = nx.from_numpy_array(w, create_using=nx.DiGraph)
    bf.init(devices=cpu_devices[:n])
    bf.set_topology(g, is_weighted=True)

    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    monkeypatch.setenv("BLUEFOG_METRICS_INTERVAL", "1")
    rng = np.random.RandomState(7)
    x = rng.randn(n, 5).astype(np.float32)
    # lr=0 inner update: the step is pure gossip, so the oracle needs no
    # optimizer modeling
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    params = {"w": bf.worker_values(lambda r: x[r])}
    s = opt.init(params)
    p, s = opt.step(params, s, {"w": jnp.zeros_like(params["w"])})
    metrics.flush()  # fold the deferred drain now

    y = w.T @ x  # combine: y_j = sum_i W[i, j] x_i
    per_worker = np.linalg.norm(x - y, axis=1)
    snap = metrics.snapshot()
    got_mean = snap["bluefog.gossip.disagreement"]["value"]
    got_max = snap["bluefog.gossip.disagreement.max"]["value"]
    np.testing.assert_allclose(got_mean, per_worker.mean(), rtol=1e-5)
    np.testing.assert_allclose(got_max, per_worker.max(), rtol=1e-5)
    # the gossip output itself matches the oracle combine
    np.testing.assert_allclose(np.asarray(p["w"]), y, rtol=1e-5, atol=1e-6)
    # param-norm slot: rms over workers of ||x_i||
    np.testing.assert_allclose(
        snap["bluefog.gossip.param_norm"]["value"],
        np.linalg.norm(x, axis=1).mean(), rtol=1e-5,
    )


def test_quant_err_and_ef_residual_populate(monkeypatch):
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    monkeypatch.setenv("BLUEFOG_METRICS_INTERVAL", "1")
    c = np.random.RandomState(0).randn(SIZE, 600).astype(np.float32)
    for wire, slot in (("int8", "quant_err"), ("int8_ef", "ef_residual"),
                       ("int4", "quant_err"), ("int4_ef", "ef_residual")):
        metrics.reset()
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
        opt.compression = wire
        params = {"w": bf.worker_values(lambda r: c[r])}
        s = opt.init(params)
        opt.step(params, s, {"w": jnp.zeros_like(params["w"])})
        metrics.flush()
        val = metrics.snapshot()[f"bluefog.gossip.{slot}"]["value"]
        assert val > 0.0, (wire, slot)
        if wire.endswith("_ef"):
            # CHOCO identity — this step's quantization error IS the
            # new residual
            snap = metrics.snapshot()
            assert (
                snap["bluefog.gossip.quant_err"]["value"]
                == snap["bluefog.gossip.ef_residual"]["value"]
            ), wire


def test_int4_probe_matches_host_replay(monkeypatch):
    """The int4 quant-err fold replays the exact wire format: the gauge
    equals the numpy-oracle RMS of ``x - dequant(pack(Q(x)))`` over the
    covered prefix (the sub-gossip probe ships raw input slices, so the
    host replica must be bit-faithful for the number to mean
    anything)."""
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    monkeypatch.setenv("BLUEFOG_METRICS_INTERVAL", "1")
    c = np.random.RandomState(5).randn(SIZE, 600).astype(np.float32)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    opt.compression = "int4"
    params = {"w": bf.worker_values(lambda r: c[r])}
    s = opt.init(params)
    opt.step(params, s, {"w": jnp.zeros_like(params["w"])})
    metrics.flush()
    got = metrics.snapshot()["bluefog.gossip.quant_err"]["value"]
    per_worker = np.asarray([
        np.sqrt(((c[w] - metrics._np_chunk_quantize4(c[w])) ** 2).sum())
        for w in range(SIZE)
    ])
    np.testing.assert_allclose(got, per_worker.mean(), rtol=1e-5)


def test_allgather_wire_telemetry(monkeypatch):
    """The compressed neighbor_allgather populates its own quant-error
    gauges and wire-byte counter (scale sidecar included); the exact
    gather does not touch them."""
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    x = np.random.RandomState(6).randn(SIZE, 600).astype(np.float32)
    bf.neighbor_allgather(x)
    assert "bluefog.allgather.quant_err" not in metrics.snapshot()
    bf.neighbor_allgather(x, compression="int4")
    snap = metrics.snapshot()
    got = snap["bluefog.allgather.quant_err"]["value"]
    per_worker = np.asarray([
        np.sqrt(
            ((x[w] - metrics._np_chunk_quantize4(x[w])) ** 2).sum() / 600
        )
        for w in range(SIZE)
    ])
    np.testing.assert_allclose(got, per_worker.mean(), rtol=1e-5)
    from bluefog_tpu import scaling
    from bluefog_tpu.collective.plan import plan_from_topology

    plan = plan_from_topology(tu.ExponentialTwoGraph(SIZE), weighted=True)
    assert snap["bluefog.allgather.wire_bytes"]["value"] == (
        len(plan.rounds) * scaling.wire_payload_bytes(600, 4, "int4")
    )


def test_wire_bytes_and_rounds_accounting(monkeypatch):
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    monkeypatch.setenv("BLUEFOG_METRICS_INTERVAL", "5")
    c = np.random.RandomState(0).randn(SIZE, 256).astype(np.float32)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    params = {"w": bf.worker_values(lambda r: c[r])}
    s = opt.init(params)
    p = params
    for _ in range(3):
        p, s = opt.step(p, s, {"w": jnp.zeros_like(p["w"])})
    snap = metrics.snapshot()
    # Exp2(8) lowers to 3 rounds; f32 payload of 256 elems re-shipped
    # per round
    assert snap["bluefog.gossip.rounds"]["value"] == 3.0
    assert snap["bluefog.wire_bytes"]["value"] == 3 * (3 * 256 * 4)
    assert snap["bluefog.comm_steps"]["value"] == 3.0


def test_plan_wire_bytes_helper():
    from bluefog_tpu.collective.plan import plan_from_topology

    plan = plan_from_topology(tu.ExponentialTwoGraph(SIZE), weighted=True)
    assert plan.wire_bytes(1024, 4) == len(plan.rounds) * 1024 * 4
    # int8: 1 byte/elem + one f32 scale per 512-element chunk
    assert plan.wire_bytes(1024, 4, wire="int8") == len(plan.rounds) * (
        1024 + 4 * 2
    )
    assert plan.wire_bytes(1024, 4, wire="bf16") == len(plan.rounds) * 2048


def test_plan_cache_and_recompile_counters():
    from bluefog_tpu.collective import compiler

    compiler.clear_compile_cache()
    before = metrics.counter("bluefog.plan_cache.misses").value
    edges = tuple((i, (i + 1) % SIZE) for i in range(SIZE))
    compiler.compile_edges(edges, SIZE)
    compiler.compile_edges(edges, SIZE)
    assert metrics.counter("bluefog.plan_cache.misses").value == before + 1
    assert metrics.counter("bluefog.plan_cache.hits").value >= 1
    # eager dispatch: first build counts as a recompile, repeats do not
    x = bf.worker_values(np.float32(1))
    bf.neighbor_allreduce(x)
    r0 = metrics.counter("bluefog.recompiles").value
    bf.neighbor_allreduce(x)
    assert metrics.counter("bluefog.recompiles").value == r0


# -- the bitwise on/off pin ---------------------------------------------------


FACTORIES = {
    "cta": bf.DistributedNeighborAllreduceOptimizer,
    "atc": lambda tx: bf.DistributedAdaptThenCombineOptimizer(
        tx, bf.CommunicationType.neighbor_allreduce
    ),
}


def _run_steps(order, wire, enabled, c, monkeypatch, fused):
    monkeypatch.setenv("BLUEFOG_METRICS", "1" if enabled else "0")
    monkeypatch.setenv("BLUEFOG_METRICS_INTERVAL", "2")
    opt = FACTORIES[order](optax.sgd(0.1, momentum=0.9))
    opt.compression = wire
    params = {"w": bf.worker_values(lambda r: c[r])}
    s = opt.init(params)
    p = params
    if fused:
        cvals = bf.worker_values(lambda r: c[r])

        def loss_fn(pp, cv):
            return 0.5 * jnp.sum((pp["w"] - cv) ** 2)

        train_step = opt.make_train_step(loss_fn)
        for _ in range(3):
            p, s, _loss = train_step(p, s, cvals)
    else:
        for _ in range(3):
            p, s = opt.step(p, s, {"w": p["w"] - jnp.asarray(c)})
    return p, s


@pytest.mark.parametrize("order", ["cta", "atc"])
@pytest.mark.parametrize("wire", [None, "int8", "int8_ef", "int4",
                                  "int4_ef"])
def test_metrics_on_off_bitwise_identical(order, wire, monkeypatch):
    """THE pin: enabling metrics recompiles the step with extra outputs
    but must not perturb params or optimizer state by a single bit, for
    ATC/CTA x fp32/int8/int8_ef/int4/int4_ef."""
    c = np.random.RandomState(1).randn(SIZE, 700).astype(np.float32)
    p_off, s_off = _run_steps(order, wire, False, c, monkeypatch, False)
    p_on, s_on = _run_steps(order, wire, True, c, monkeypatch, False)
    for a, b in zip(
        jax.tree_util.tree_leaves((p_off, s_off)),
        jax.tree_util.tree_leaves((p_on, s_on)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_metrics_on_off_bitwise_identical_fused(monkeypatch):
    c = np.random.RandomState(2).randn(SIZE, 300).astype(np.float32)
    p_off, s_off = _run_steps("cta", None, False, c, monkeypatch, True)
    p_on, s_on = _run_steps("cta", None, True, c, monkeypatch, True)
    for a, b in zip(
        jax.tree_util.tree_leaves((p_off, s_off)),
        jax.tree_util.tree_leaves((p_on, s_on)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unsampled_metric_steps_run_the_metrics_off_program(monkeypatch):
    """Two-program sampling: with metrics on at interval 2, the sampled
    steps build ONE extra program and every other step dispatches the
    metrics-off program under its own cache key."""
    monkeypatch.setenv("BLUEFOG_METRICS", "0")
    ctx = bf.get_context()
    c = np.random.RandomState(3).randn(SIZE, 300).astype(np.float32)
    opt = FACTORIES["cta"](optax.sgd(0.1, momentum=0.9))
    p = {"w": bf.worker_values(lambda r: c[r])}
    s = opt.init(p)

    def step_keys():
        return {
            k for k in ctx.op_cache
            if isinstance(k, tuple) and k and k[0] == "opt_step"
        }

    p, s = opt.step(p, s, {"w": p["w"] - jnp.asarray(c)})
    off = step_keys()
    assert len(off) == 1
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    monkeypatch.setenv("BLUEFOG_METRICS_INTERVAL", "2")
    for _ in range(4):
        p, s = opt.step(p, s, {"w": p["w"] - jnp.asarray(c)})
    on = step_keys()
    assert off < on and len(on - off) == 1


def test_metrics_drain_interval(monkeypatch):
    """No registry update before the interval elapses; the periodic
    path (swap at one boundary, fold at the next — no explicit flush)
    populates it after two intervals."""
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    monkeypatch.setenv("BLUEFOG_METRICS_INTERVAL", "3")
    c = np.random.RandomState(3).randn(SIZE, 8).astype(np.float32)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    params = {"w": bf.worker_values(lambda r: c[r])}
    s = opt.init(params)
    p = params
    for i in range(2):
        p, s = opt.step(p, s, {"w": jnp.zeros_like(p["w"])})
    assert "bluefog.gossip.disagreement" not in metrics.snapshot()
    for i in range(4):  # steps 3..6: swap at 3, deferred fold at 6
        p, s = opt.step(p, s, {"w": jnp.zeros_like(p["w"])})
    snap = metrics.snapshot()
    assert snap["bluefog.gossip.disagreement"]["value"] > 0
    # the drained window really covered `interval` communicating steps
    assert snap["bluefog.comm_steps"]["value"] == 6.0


def test_jsonl_auto_export_on_drain(tmp_path, monkeypatch):
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    monkeypatch.setenv("BLUEFOG_METRICS_INTERVAL", "1")
    path = str(tmp_path / "auto.jsonl")
    monkeypatch.setenv("BLUEFOG_METRICS_FILE", path)
    c = np.random.RandomState(4).randn(SIZE, 8).astype(np.float32)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    params = {"w": bf.worker_values(lambda r: c[r])}
    s = opt.init(params)
    p = params
    for _ in range(3):
        p, s = opt.step(p, s, {"w": jnp.zeros_like(p["w"])})
    # drains fold one interval late (async copy): 3 steps at interval 1
    # = 2 folded time-series points so far
    lines = [json.loads(l) for l in open(path).read().splitlines()]
    assert len(lines) == 2, lines
    assert all(
        "bluefog.gossip.disagreement" in l["metrics"] for l in lines
    )
    bf.metrics_export()  # flushes the tail and appends a final line
    lines = [json.loads(l) for l in open(path).read().splitlines()]
    assert len(lines) == 3


def test_watchdog_stall_counts_and_marks_timeline(tmp_path):
    import time

    from bluefog_tpu import watchdog

    path = str(tmp_path / "stall_trace.json")
    assert bf.timeline_init(path)
    watchdog.set_stall_timeout(0.1)
    before = metrics.counter("bluefog.stalls").value
    try:
        with watchdog.watch("metrics-test-op"):
            time.sleep(0.5)
    finally:
        watchdog.set_stall_timeout(60)
        assert bf.timeline_shutdown()
    assert metrics.counter("bluefog.stalls").value == before + 1
    events = json.load(open(path))
    stalls = [
        e for e in events
        if e.get("ph") == "i" and e.get("cat") == "STALL"
    ]
    assert stalls and "metrics-test-op" in stalls[0]["name"]
