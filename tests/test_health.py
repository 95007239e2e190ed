# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Fleet-health-plane tests: spectral helpers, decay-rate fitting and
mixing efficiency, the in-band push-sum lane vs its numpy oracle
(including a dead rank on a weighted digraph), the ``mixing_degraded``
advisory across all emission surfaces, the ``/healthz`` / ``/metrics``
/ ``/fleet`` endpoints (including port-conflict graceful no-op), and
``tools/fleet_report.py``.
"""

import json
import os
import socket
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import bluefog_tpu as bf
import bluefog_tpu.topology as tu
from bluefog_tpu import flight, health, metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    for k in ("BLUEFOG_HEALTH", "BLUEFOG_HEALTH_INTERVAL",
              "BLUEFOG_HEALTH_PORT", "BLUEFOG_HEALTH_FILE",
              "BLUEFOG_HEALTH_ROUNDS", "BLUEFOG_HEALTH_EPS"):
        monkeypatch.delenv(k, raising=False)
    metrics.reset()
    bf.init(devices=cpu_devices[:SIZE])
    yield
    health.stop()
    bf.elastic.stop()
    bf.shutdown()
    metrics.reset()


# -- spectral helpers ---------------------------------------------------------


def test_slem_known_values():
    """Ring/Exp2/fully-connected SLEMs land on their analytic values:
    the ring's 1/3 + 2/3·cos(2π/8), Exp2's 1/2, fully-connected 0."""
    ring = tu.mixing_matrix(tu.RingGraph(SIZE))
    exp2 = tu.mixing_matrix(tu.ExponentialTwoGraph(SIZE))
    full = tu.mixing_matrix(tu.FullyConnectedGraph(SIZE))
    assert tu.second_largest_eigenvalue_modulus(ring) == pytest.approx(
        1.0 / 3.0 + 2.0 / 3.0 * np.cos(2 * np.pi / SIZE), abs=1e-9
    )
    assert tu.second_largest_eigenvalue_modulus(exp2) == pytest.approx(
        0.5, abs=1e-9
    )
    assert tu.second_largest_eigenvalue_modulus(full) < 1e-9
    assert tu.spectral_gap(full) == pytest.approx(1.0, abs=1e-9)
    # Exp2 promises faster mixing than ring — the paper's premise
    assert tu.consensus_decay_rate(exp2) < tu.consensus_decay_rate(ring)


def test_slem_disconnected_graph_promises_nothing():
    """Two disconnected cliques have a repeated eigenvalue 1: SLEM 1.0
    (no contraction), and the observatory maps that to 'no
    prediction'."""
    w = np.zeros((4, 4))
    w[:2, :2] = 0.5
    w[2:, 2:] = 0.5
    assert tu.second_largest_eigenvalue_modulus(w) == pytest.approx(1.0)
    assert health.mixing_efficiency(0.5, 1.0) is None


def test_one_peer_period_product_beats_single_step():
    """The dynamic one-peer schedule's period-product rate: each single
    iteration barely mixes (one peer per rank), but the period product
    contracts — and the helper's matrices are doubly stochastic."""
    topo = tu.ExponentialTwoGraph(SIZE)
    mats = tu.one_peer_period_matrices(topo)
    assert len(mats) == 3  # out-degree log2(8) = 3 neighbor choices
    for m in mats:
        assert m.sum(axis=0) == pytest.approx(np.ones(SIZE))
        assert m.sum(axis=1) == pytest.approx(np.ones(SIZE))
    rate = tu.consensus_decay_rate(mats)
    assert 0.0 < rate < 1.0
    # the period product mixes strictly better per step than any single
    # iteration's matrix promises alone
    single = tu.consensus_decay_rate(mats[0])
    assert rate < single


# -- decay fit / efficiency / projection --------------------------------------


def test_fit_decay_rate_recovers_geometric_series():
    pts = [(i, 3.0 * 0.85 ** i) for i in range(0, 24, 3)]
    rate = health.fit_decay_rate(pts)
    assert rate == pytest.approx(0.85, abs=1e-9)
    assert health.mixing_efficiency(rate, 0.85) == pytest.approx(
        1.0, abs=1e-6
    )


def test_fit_decay_rate_refuses_thin_or_flat_input():
    assert health.fit_decay_rate([(0, 1.0), (1, 0.9)]) is None
    # noise-floor points are dropped, starving the fit
    pts = [(i, 1e-15) for i in range(10)]
    assert health.fit_decay_rate(pts) is None
    # a non-decaying series reports rate >= 1 -> efficiency 0
    pts = [(i, 1.0 + 0.01 * i) for i in range(8)]
    rate = health.fit_decay_rate(pts)
    assert rate >= 1.0
    assert health.mixing_efficiency(rate, 0.8) == 0.0


def test_time_to_consensus_projection():
    # 1.0 -> 1e-6 at rate 0.5: log(1e-6)/log(0.5) ~ 19.9 steps
    steps = health.time_to_consensus_steps(1.0, 0.5, eps=1e-6)
    assert steps == pytest.approx(19.93, abs=0.01)
    assert health.time_to_consensus_steps(1e-9, 0.5, eps=1e-6) == 0.0
    assert health.time_to_consensus_steps(1.0, 1.1, eps=1e-6) is None
    assert health.time_to_consensus_steps(None, 0.5) is None


# -- push-sum lane ------------------------------------------------------------


def test_push_matrix_conserves_sender_mass():
    w = tu.mixing_matrix(tu.ExponentialTwoGraph(SIZE))
    p = health.push_matrix(w, dead=[5])
    # every live row sums to 1 (mass conservation); dead row/col zeroed
    for i in range(SIZE):
        if i == 5:
            assert p[i].sum() == 0.0
            assert p[:, i].sum() == 0.0
        else:
            assert p[i].sum() == pytest.approx(1.0)


def test_fleet_aggregate_device_matches_numpy_oracle():
    """The acceptance oracle: the compiled lane on a WEIGHTED digraph
    with one dead rank must match the numpy replay, and both must
    deliver the live-set mean/min/max."""
    # a genuinely weighted, non-symmetric digraph: exp2 weights skewed
    g = tu.ExponentialTwoGraph(SIZE)
    w = tu.mixing_matrix(g)
    w[0, 1] *= 2.0  # break symmetry; lane normalizes per sender
    ctx = bf.get_context()
    bf.set_topology(g)
    rng = np.random.RandomState(3)
    vals = rng.randn(SIZE, len(health.FLEET_FIELDS)) * 5.0
    dead = [4]
    dev = health.fleet_aggregate(ctx, vals, rounds=12, w=w, dead=dead)
    ora = health.fleet_aggregate_np(w, vals, rounds=12, dead=dead)
    assert np.allclose(dev["mean"], ora["mean"], rtol=1e-4, atol=1e-5)
    assert np.allclose(dev["min"], ora["min"])
    assert np.allclose(dev["max"], ora["max"])
    live = [j for j in range(SIZE) if j not in dead]
    assert np.allclose(dev["min"], vals[live].min(axis=0))
    assert np.allclose(dev["max"], vals[live].max(axis=0))
    true_mean = vals[live].mean(axis=0)
    assert np.allclose(dev["mean"], true_mean, rtol=0.02, atol=0.02)
    assert dev["live"] == live
    assert dev["residual"] < 0.02


def test_streaming_lane_tracks_changing_values():
    """The sampled-step streaming form: delta injection keeps the
    push-sum mean tracking a CHANGING per-rank summary, and the min/max
    generations publish exact extrema once warmed."""
    ctx = bf.get_context()
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    plane = health.HealthPlane(interval=1)
    rng = np.random.RandomState(0)
    vals = rng.rand(SIZE, len(health.FLEET_FIELDS))
    rep = None
    for t in range(40):
        if t == 20:
            vals = vals + 10.0  # the fleet state moves mid-run
        rep = plane._fleet_step(ctx, vals, dead=[], predicted=0.5)
    assert not rep["warming"]
    assert np.allclose(rep["mean"], vals.mean(axis=0), rtol=0.02,
                       atol=0.02)
    assert np.allclose(rep["min"], vals.min(axis=0), atol=1e-5)
    assert np.allclose(rep["max"], vals.max(axis=0), atol=1e-5)


# -- observatory + advisory ---------------------------------------------------


def _drive_consensus(plane, ctx, w, steps, start_step=0, x=None,
                     lossy=None, factor=0.05):
    """Drive the plane with an eager numpy consensus iteration;
    ``lossy=(s, d)`` replays a deterministic packet-droppy link."""
    if x is None:
        x = np.random.RandomState(1).randn(w.shape[0], 64)
    for t in range(start_step, start_step + steps):
        y = w.T @ x
        if lossy is not None:
            s, d = lossy
            y[d] += (1.0 - factor) * w[s, d] * (x[d] - x[s])
        x = y
        dist = float(np.sqrt(((x - x.mean(0)) ** 2).sum(1)).mean())
        plane.observe(ctx, step=t, consensus=dist)
    return x


def test_observatory_measures_on_contract_efficiency():
    ctx = bf.get_context()
    ring = tu.RingGraph(SIZE)
    bf.set_topology(ring)
    w = tu.mixing_matrix(ring)
    plane = health.start(interval=1)
    _drive_consensus(plane, ctx, w, steps=25)
    s = plane.samples[-1]
    pred = tu.consensus_decay_rate(w)
    assert s["predicted_rate"] == pytest.approx(pred, abs=1e-9)
    assert s["measured_rate"] == pytest.approx(pred, rel=0.05)
    assert s["mixing_efficiency"] == pytest.approx(1.0, abs=0.1)
    assert s["time_to_eps_steps"] > 0
    # gauges landed
    assert metrics.peek("bluefog.health.mixing_efficiency") is not None
    assert metrics.peek("bluefog.health.samples").value >= 25


def _eager_decay(graph, steps=40):
    """Drive the real eager combine (``bf.neighbor_allreduce``) on the
    mesh from random iterates and feed each step's consensus distance
    to a fresh plane; stop before the f32 rounding floor, where the fit
    would measure the noise rather than the mixing rate."""
    ctx = bf.get_context()
    bf.set_topology(graph)
    plane = health.start(interval=1)
    rng = np.random.RandomState(0)
    x = bf.worker_values(lambda r: rng.randn(4096).astype(np.float32))
    d0, last = None, None
    for t in range(steps):
        x = bf.neighbor_allreduce(x)
        xs = np.asarray(x, np.float64)
        d = float(np.sqrt(((xs - xs.mean(0)) ** 2).sum(1)).mean())
        d0 = d if d0 is None else d0
        if d < d0 * 1e-4:
            break
        last = plane.observe(ctx, step=t, consensus=d)
    health.stop()
    return last


@pytest.mark.parametrize("make", [tu.RingGraph, tu.ExponentialTwoGraph],
                         ids=["ring", "exp2"])
def test_real_combine_decays_at_the_spectral_rate(make):
    """On the device combine, the measured consensus decay lands within
    15 % (in log rate) of the spectral prediction, and the plane
    projects a time to consensus."""
    g = make(SIZE)
    s = _eager_decay(g)
    pred = tu.consensus_decay_rate(tu.mixing_matrix(g))
    assert 0 < pred < 1
    assert s["predicted_rate"] == pytest.approx(pred, abs=1e-9)
    assert 0 < s["measured_rate"] < 1
    assert abs(s["mixing_efficiency"] - 1.0) <= 0.15, s
    assert s["time_to_eps_steps"] > 0


def test_exp2_mixes_faster_than_ring_on_the_real_combine():
    ring = _eager_decay(tu.RingGraph(SIZE))
    exp2 = _eager_decay(tu.ExponentialTwoGraph(SIZE))
    assert exp2["measured_rate"] < ring["measured_rate"]
    assert exp2["time_to_eps_steps"] < ring["time_to_eps_steps"]


def test_mixing_degraded_fires_and_names_injected_edge(tmp_path):
    """The chaos acceptance path: a lossy link measurably slows mixing
    below the spectral promise; the advisory fires on every surface
    (metrics counter, flight side table, health JSONL) and its suspect
    join names the injected edge."""
    os.environ["BLUEFOG_HEALTH_FILE"] = str(tmp_path / "health.jsonl")
    ctx = bf.get_context()
    ring = tu.RingGraph(SIZE)
    bf.set_topology(ring)
    w = tu.mixing_matrix(ring)
    session = bf.elastic.start(policy="average")
    session.inject("degrade", rank=2, step=0, factor=0.05, peer=3)
    plane = health.start(interval=1)
    x = _drive_consensus(plane, ctx, w, steps=30)
    assert not [a for a in plane.advisories
                if a.kind == "mixing_degraded"]
    _drive_consensus(plane, ctx, w, steps=50, start_step=30, x=x,
                     lossy=(2, 3))
    advs = [a for a in plane.advisories if a.kind == "mixing_degraded"]
    assert advs, "mixing_degraded never fired"
    assert [2, 3] in advs[0].detail["suspect_edges"]
    assert advs[0].detail["mixing_efficiency"] < (
        advs[0].detail["baseline_efficiency"]
    )
    # surfaces: metrics counter, flight advisory side table, JSONL
    c = metrics.peek("bluefog.doctor.advisory.mixing_degraded")
    assert c is not None and c.value >= 1
    flight_advs = [
        a for a in flight.events()
        if a.get("kind") == "advisory"
    ]
    lines = [
        json.loads(l) for l in
        open(tmp_path / "health.jsonl").read().splitlines()
    ]
    assert any(l.get("advisory_kind") == "mixing_degraded"
               for l in lines)
    assert any(l.get("kind") == "sample" and "mixing_efficiency" in l
               for l in lines)
    # /healthz degrades to warn while the advisory is recent
    assert health.healthz_verdict(plane)["status"] == "warn"
    del flight_advs


def test_advisory_survives_healthy_restart_of_baseline():
    """A topology swap mid-session resets the efficiency baseline: the
    new graph's different (healthy) efficiency must NOT fire the
    advisory that a stale baseline would have."""
    ctx = bf.get_context()
    ring = tu.RingGraph(SIZE)
    bf.set_topology(ring)
    plane = health.start(interval=1)
    _drive_consensus(plane, ctx, tu.mixing_matrix(ring), steps=25)
    exp2 = tu.ExponentialTwoGraph(SIZE)
    bf.set_topology(exp2)  # topo_version bumps
    _drive_consensus(plane, ctx, tu.mixing_matrix(exp2), steps=20)
    assert not [a for a in plane.advisories
                if a.kind == "mixing_degraded"]
    s = plane.samples[-1]
    assert s["predicted_rate"] == pytest.approx(0.5, abs=1e-9)


def test_healthz_recency_uses_comm_step_marks():
    """Regression: under K>1 gradient accumulation an advisory's
    ``step`` (optimizer step clock) runs K× faster than the plane's
    comm-step count; the /healthz recency window must compare the
    comm-step emit marks, or a cleared condition stays 'warn' K×
    longer than the window intends."""
    from bluefog_tpu.attribution import Advisory

    plane = health.start(interval=1)
    adv = Advisory(kind="mixing_degraded", step=400, detail={})
    plane.advisories.append(adv)
    plane.advisory_marks.append(100)  # emitted at comm step 100
    plane._count = 100 + health.VERDICT_RECENT_SAMPLES + 1
    v = health.healthz_verdict(plane)
    assert v["status"] == "ok", v  # stale despite step=400 >> floor
    plane._count = 100 + health.VERDICT_RECENT_SAMPLES - 1
    assert health.healthz_verdict(plane)["status"] == "warn"


# -- serving surface ----------------------------------------------------------


def test_healthz_fleet_metrics_endpoints():
    ctx = bf.get_context()
    bf.set_topology(tu.RingGraph(SIZE))
    plane = health.start(interval=1)
    _drive_consensus(plane, ctx, tu.mixing_matrix(bf.load_topology()),
                     steps=12)
    srv = health.serve(0)  # OS-assigned port
    assert srv is not None
    base = f"http://127.0.0.1:{srv.port}"
    v = json.loads(urllib.request.urlopen(base + "/healthz").read())
    assert v["status"] == "ok" and v["dead_ranks"] == []
    prom = urllib.request.urlopen(base + "/metrics").read().decode()
    assert "# HELP" in prom and "# TYPE" in prom
    assert "bluefog_health_samples_total" in prom
    fleet = json.loads(urllib.request.urlopen(base + "/fleet").read())
    assert fleet["kind"] == "health_dump"
    assert fleet["fleet"]["fields"] == list(health.FLEET_FIELDS)
    assert fleet["healthz"]["status"] == "ok"
    # unknown path -> 404 with the path list
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(base + "/nope")
    assert err.value.code == 404
    srv.close()


def test_healthz_critical_on_dead_rank_returns_503():
    ctx = bf.get_context()
    bf.set_topology(tu.RingGraph(SIZE))
    session = bf.elastic.start(policy="average")
    session.membership.mark_dead(5, "killed", 0)
    plane = health.start(interval=1)
    v = health.healthz_verdict(plane)
    assert v["status"] == "critical" and 5 in v["dead_ranks"]
    srv = health.serve(0)
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz"
        )
    assert err.value.code == 503
    srv.close()
    del ctx


def test_port_conflict_is_graceful_noop():
    blocker = socket.socket()
    blocker.bind(("0.0.0.0", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    try:
        srv = health.HealthServer.maybe_start(port)
        assert srv is None  # warned, did not raise, did not serve
    finally:
        blocker.close()


def test_env_port_wires_serving_through_init(cpu_devices, monkeypatch):
    free = socket.socket()
    free.bind(("", 0))
    port = free.getsockname()[1]
    free.close()
    monkeypatch.setenv("BLUEFOG_HEALTH_PORT", str(port))
    monkeypatch.setenv("BLUEFOG_HEALTH", "1")
    bf.shutdown()
    bf.init(devices=cpu_devices[:SIZE])
    try:
        assert health.server() is not None
        assert health.active() is not None  # BLUEFOG_HEALTH=1 observatory
        v = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz"
        ).read())
        assert v["status"] in ("ok", "warn")
    finally:
        bf.shutdown()
        assert health.server() is None  # shutdown closed it


# -- optimizer integration ----------------------------------------------------


def test_optimizer_hook_feeds_plane_without_touching_programs():
    """The hook path: a real fused train step drives the plane; the
    train-step cache is untouched (lane programs live under their own
    family), and the sampled plane sees the topology's predicted
    rate."""
    import optax

    ctx = bf.get_context()
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    rng = np.random.RandomState(0)
    w0 = (rng.randn(32, 32) / 6.0).astype(np.float32)
    xs = bf.worker_values(lambda r: rng.randn(8, 32).astype(np.float32))
    ys = bf.worker_values(lambda r: rng.randn(8, 32).astype(np.float32))

    def loss_fn(p, x, y):
        import jax.numpy as jnp

        return jnp.mean((jnp.tanh(x @ p["w"]) - y) ** 2)

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.01))
    step = bf.make_train_step(opt, loss_fn)
    params = {"w": bf.worker_values(lambda r: w0)}
    state = opt.init(params)
    for _ in range(2):
        params, state, _ = step(params, state, xs, ys)
    train_keys = {
        k for k in ctx.op_cache
        if isinstance(k, tuple) and k and k[0] in (
            "opt_step", "opt_fused_step",
        )
    }
    plane = health.start(interval=2)
    for _ in range(6):
        params, state, _ = step(params, state, xs, ys)
    assert plane.samples, "optimizer hook never sampled"
    s = plane.samples[-1]
    assert s["predicted_rate"] == pytest.approx(0.5, abs=1e-6)
    assert s["fleet"]["live"] == list(range(SIZE))
    after = {
        k for k in ctx.op_cache
        if isinstance(k, tuple) and k and k[0] in (
            "opt_step", "opt_fused_step",
        )
    }
    assert after == train_keys  # structural pin
    assert any(
        isinstance(k, tuple) and k and k[0] == "health_pushsum"
        for k in ctx.op_cache
    )


# -- fleet_report CLI ---------------------------------------------------------


def test_fleet_report_renders_artifacts(tmp_path):
    ctx = bf.get_context()
    bf.set_topology(tu.RingGraph(SIZE))
    plane = health.start(interval=1)
    _drive_consensus(plane, ctx, tu.mixing_matrix(bf.load_topology()),
                     steps=15)
    art = tmp_path / "health_0.json"
    health.dump(str(art))
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleet_report.py"),
         str(art), "--json"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["kind"] == "fleet_report"
    assert rep["overall"] == "ok"
    assert rep["processes"][0]["mixing_efficiency"] is not None
    assert rep["worst_rank"] is not None
    assert 0 <= rep["worst_rank"]["rank"] < SIZE
    # human table mode renders without crashing
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleet_report.py"),
         str(art)],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out2.returncode == 0, out2.stderr
    assert "worst rank" in out2.stdout
    assert "fleet aggregate" in out2.stdout


def test_fleet_report_unreadable_inputs_exit_2(tmp_path):
    bad = tmp_path / "nope.json"
    bad.write_text("{}")
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleet_report.py"),
         str(bad), "--json"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out.returncode == 2


def test_doctor_triage_ingests_health_artifact(tmp_path):
    """tools/doctor.py --health: the triage report names the worst rank
    and its dominant advisory in the human-sentence section."""
    ctx = bf.get_context()
    ring = tu.RingGraph(SIZE)
    bf.set_topology(ring)
    w = tu.mixing_matrix(ring)
    session = bf.elastic.start(policy="average")
    session.inject("degrade", rank=2, step=0, factor=0.05, peer=3)
    plane = health.start(interval=1)
    x = _drive_consensus(plane, ctx, w, steps=30)
    _drive_consensus(plane, ctx, w, steps=50, start_step=30, x=x,
                     lossy=(2, 3))
    art = tmp_path / "health.json"
    health.dump(str(art))
    attr = tmp_path / "doctor.json"
    attr.write_text(json.dumps({
        "kind": "doctor_dump", "interval": 100, "samples": [],
        "advisories": [], "baselines": {}, "calibration": {},
    }))
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "doctor.py"),
         "--attribution", str(attr), "--health", str(art), "--json"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["health"]["worst_rank"] is not None
    assert rep["health"]["dominant_advisory"] == "mixing_degraded"
    joined = " ".join(rep["summary"])
    assert "worst in the fleet" in joined
    assert "mixing_degraded" in joined


# -- non-finite rendering + concurrent scrapes (staleness-PR satellites) ------


def test_endpoints_survive_non_finite_gauges():
    """Regression: a NaN gauge (e.g. a step EWMA before warmup) must
    never reach a scraper as a bare ``NaN`` token — strict JSON
    parsers reject it and a /fleet scrape turns into a parse error
    exactly while the plane warms up. JSON surfaces degrade the value
    to null; the Prometheus text surface uses the exposition format's
    own ``NaN``/``+Inf`` casings."""
    plane = health.start(interval=1)
    plane._step_ewma_ms = float("nan")
    with plane._report_lock:
        plane.samples.append({
            "kind": "sample", "step": 0,
            "step_ms_ewma": float("nan"),
            "consensus": float("inf"),
            "nested": {"v": float("-inf"), "list": [float("nan")]},
        })
    metrics.gauge("bluefog.test.nan_gauge").set(float("nan"))
    metrics.gauge("bluefog.test.inf_gauge").set(float("inf"))
    srv = health.serve(0)
    assert srv is not None
    base = f"http://127.0.0.1:{srv.port}"

    def strict_loads(raw):
        def reject(tok):
            raise ValueError(f"non-finite token {tok!r} in JSON")

        return json.loads(raw, parse_constant=reject)

    fleet = strict_loads(urllib.request.urlopen(base + "/fleet").read())
    last = fleet["samples"][-1]
    assert last["step_ms_ewma"] is None
    assert last["consensus"] is None
    assert last["nested"]["v"] is None
    assert last["nested"]["list"] == [None]
    strict_loads(urllib.request.urlopen(base + "/healthz").read())
    prom = urllib.request.urlopen(base + "/metrics").read().decode()
    for line in prom.splitlines():
        assert " nan" not in line and " inf" not in line, line
    assert "bluefog_test_nan_gauge NaN" in prom
    assert "bluefog_test_inf_gauge +Inf" in prom
    srv.close()


def test_concurrent_scrapes_while_plane_publishes():
    """Two clients hammering /metrics and /fleet while the training
    thread publishes sampled steps: every response must be a parseable
    200 (the report-lock regression surface — deque mutation during
    iteration turned scrapes into 500s exactly on sampled steps)."""
    import threading

    ctx = bf.get_context()
    bf.set_topology(tu.RingGraph(SIZE))
    plane = health.start(interval=1)
    srv = health.serve(0)
    assert srv is not None
    base = f"http://127.0.0.1:{srv.port}"
    errors = []
    stop = threading.Event()

    def scrape(path):
        while not stop.is_set():
            try:
                raw = urllib.request.urlopen(base + path, timeout=5).read()
                if path != "/metrics":
                    json.loads(raw)
            except Exception as e:  # any non-200 / parse failure
                errors.append((path, repr(e)))
                return

    threads = [
        threading.Thread(target=scrape, args=("/metrics",), daemon=True),
        threading.Thread(target=scrape, args=("/fleet",), daemon=True),
    ]
    for t in threads:
        t.start()
    w = tu.mixing_matrix(bf.load_topology())
    x = np.random.RandomState(0).randn(SIZE, 64)
    for t_step in range(30):
        x = w.T @ x
        d = float(np.sqrt(((x - x.mean(0)) ** 2).sum(1)).mean())
        metrics.gauge("bluefog.gossip.disagreement").set(d)
        plane.observe(ctx, step=t_step, consensus=d)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    srv.close()
    assert not errors, errors
