# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""SLO-engine tests: burn-rate/budget arithmetic vs an independent
numpy oracle, the documented page bound, fast-vs-slow window
separation (including the slow ramp the health EWMA+MAD gate
correctly never trips on), error-budget exhaustion escalating the
``/healthz`` RAG verdict, the ``/slo`` endpoint (non-finite guard +
concurrent scrapes), the synthetic canary lane against the wire
replay (clean fabric passes, a ``degrade`` chaos fault flips the
verdict naming the edge, own op-cache family + structural pin), the
PR-7 emission surfaces, the fleet ``slo_burn`` field, autotune
``DecisionRecord.slo_burn``, the N=1024 fleetsim churn-storm burn
rehearsal, and ``tools/slo_report.py``.
"""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import bluefog_tpu as bf
import bluefog_tpu.topology as tu
from bluefog_tpu import flight, health, metrics, slo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    for k in ("BLUEFOG_SLO", "BLUEFOG_SLO_INTERVAL",
              "BLUEFOG_SLO_FILE", "BLUEFOG_SLO_CANARY",
              "BLUEFOG_HEALTH", "BLUEFOG_HEALTH_PORT"):
        monkeypatch.delenv(k, raising=False)
    metrics.reset()
    bf.init(devices=cpu_devices[:SIZE])
    yield
    slo.stop()
    health.stop()
    bf.elastic.stop()
    bf.shutdown()
    metrics.reset()


def _objective(**kw):
    base = dict(name="avail", series="test", target=0.99,
                comparison="ge", window=20, budget_frac=0.1,
                fast_window=3, fast_burn=5.0, slow_window=10,
                slow_burn=1.5)
    base.update(kw)
    return slo.Objective(**base)


def _engine(**kw):
    kw.setdefault("interval", 1)
    kw.setdefault("objectives", [_objective()])
    kw.setdefault("canary", False)
    return slo.SLOEngine(**kw)


# -- burn / budget arithmetic vs numpy oracle ---------------------------------


def _oracle(flags, window, budget_frac):
    """Independent recomputation of burn + budget over a flag series
    (numpy, no shared code path with the engine's deque walk)."""
    a = np.asarray(flags, dtype=np.int64)
    burn = None
    if len(a) >= window:
        burn = (a[-window:].sum() / window) / budget_frac
    recent = a[-window:]
    total = budget_frac * window
    spent = int(recent.sum())
    return burn, {
        "total": total, "spent": spent,
        "remaining": max(0.0, total - spent),
        "exhausted": spent >= total and total > 0,
        "compliance": 1.0 - spent / len(recent) if len(recent) else 1.0,
    }


def test_burn_and_budget_match_numpy_oracle():
    """Engine arithmetic == oracle on a deterministic mixed series,
    at every prefix (the streaming invariant: the deque walk can
    never drift from the batch recomputation)."""
    rng = np.random.RandomState(7)
    series = (rng.rand(300) < 0.12).astype(int)  # ~12% bad
    eng = _engine()
    flags = []
    st = eng._state["avail"]
    for t, bad in enumerate(series):
        eng.observe(None, step=t,
                    values={"avail": 0.0 if bad else 1.0})
        flags.append(int(bad))
        o = st.obj
        window_flags = flags[-o.window:]
        for w in (o.fast_window, o.slow_window, o.window):
            got = slo.burn_rate(list(st.flags), w, o.budget_frac)
            want, _ = _oracle(window_flags, w, o.budget_frac)
            assert got == (pytest.approx(want) if want is not None
                           else None), (t, w)
        want_budget = _oracle(window_flags, o.window, o.budget_frac)[1]
        got_budget = slo.budget_state(list(st.flags), o.window,
                                      o.budget_frac)
        assert got_budget == pytest.approx(want_budget), t


def test_page_fires_within_documented_bound_and_aa_control_is_silent():
    """Total degradation pages within page_sample_bound() samples of
    onset; the A/A control (all-good series of the same length)
    fires nothing."""
    obj = _objective()
    bound = slo.page_sample_bound(obj.fast_window, obj.fast_burn,
                                  obj.budget_frac)
    assert 1 <= bound <= obj.fast_window
    eng = _engine(objectives=[obj])
    warm = 30
    for t in range(warm):
        eng.observe(None, step=t, values={"avail": 1.0})
    assert not eng.alerts  # A/A within the same engine: green warmup
    fired_at = None
    for k in range(obj.fast_window + 1):
        eng.observe(None, step=warm + k, values={"avail": 0.0})
        if any(a.kind == "slo_fast_burn" for a in eng.alerts):
            fired_at = k + 1  # bad samples consumed
            break
    assert fired_at is not None and fired_at <= bound
    page = [a for a in eng.alerts if a.kind == "slo_fast_burn"][0]
    assert page.detail["severity"] == "page"
    assert page.detail["objective"] == "avail"
    # A/A control: an independent engine fed only good samples
    ctrl = _engine()
    for t in range(warm + obj.fast_window + 1):
        ctrl.observe(None, step=t, values={"avail": 1.0})
    assert not ctrl.alerts
    assert ctrl.worst_burn() == 0.0


def test_slow_ramp_caught_by_slow_window_not_fast():
    """A ramp bad at ~20% of samples: burns 2× budget (slow window
    fires the ticket) but never concentrates enough for the page —
    the scenario the health plane's EWMA+MAD hygiene deliberately
    never trips on (out-of-band samples don't absorb; a slow drift
    walks the baseline up), which is the slow burn window's reason to
    exist."""
    eng = _engine()
    for t in range(200):
        bad = (t % 5) == 4  # exactly 20% bad, evenly spread
        eng.observe(None, step=t,
                    values={"avail": 0.0 if bad else 1.0})
    kinds = {a.kind for a in eng.alerts}
    assert "slo_slow_burn" in kinds
    assert "slo_fast_burn" not in kinds
    ticket = [a for a in eng.alerts if a.kind == "slo_slow_burn"][0]
    assert ticket.detail["severity"] == "ticket"
    assert ticket.detail["burn"] == pytest.approx(2.0)


def test_default_catalog_covers_every_tier_and_costs_nothing_idle():
    """The shipped catalog holds one objective per tier (eight, names
    unique, each serializable); on a session with every tier off each
    resolver finds no signal, so sampling skips them all without
    charging a budget or raising an alert."""
    eng = slo.SLOEngine(interval=1, canary=False)
    names = [o.name for o in eng.objectives]
    assert len(names) >= 8 and len(set(names)) == len(names)
    assert {"step_time", "mixing_efficiency", "param_age",
            "mass_residual", "memory_headroom", "async_participation",
            "ici_consensus", "dcn_consensus"} <= set(names)
    for o in eng.objectives:
        row = json.loads(json.dumps(o.to_json()))
        assert row["name"] == o.name and "resolver" not in row
        assert o.comparison in ("le", "ge")
        assert o.fast_window < o.slow_window <= o.window
    for t in range(5):
        eng.observe(bf.get_context(), step=t)
    rep = eng.report()
    assert rep["samples_run"] == 5 and not rep["alerts"]
    for snap in rep["objectives"]:
        assert snap["last_value"] is None
        assert snap["budget"]["spent"] == 0


def test_none_and_non_finite_values_skip_without_budget_charge():
    eng = _engine()
    for t in range(40):
        eng.observe(None, step=t, values={"avail": 1.0})
    st = eng._state["avail"]
    before = list(st.flags)
    eng.observe(None, step=40, values={"avail": None})
    eng.observe(None, step=41, values={"avail": float("nan")})
    eng.observe(None, step=42, values={})  # resolver-less: no data
    assert list(st.flags) == before
    assert st.skips == 3  # None, NaN, and missing each count a skip
    assert eng.worst_burn() == 0.0


def test_register_replaces_and_resets_history():
    eng = _engine()
    for t in range(10):
        eng.observe(None, step=t, values={"avail": 0.0})
    assert eng._state["avail"].samples == 10
    eng.register(_objective(target=0.5))
    assert eng._state["avail"].samples == 0  # re-targeted: fresh flags
    assert len(eng.objectives) == 1


def test_sampling_interval_and_env_knobs(monkeypatch):
    eng = slo.SLOEngine(interval=4, objectives=[_objective()],
                        canary=False)
    for t in range(12):
        eng.observe(None, step=t, values={"avail": 1.0})
    assert eng._samples == 3  # 1-in-4 communicating steps
    monkeypatch.setenv("BLUEFOG_SLO_INTERVAL", "nonsense")
    assert slo.slo_interval() == slo.DEFAULT_INTERVAL  # warn + default
    monkeypatch.setenv("BLUEFOG_SLO_INTERVAL", "3")
    assert slo.slo_interval() == 3
    assert not slo.enabled()
    monkeypatch.setenv("BLUEFOG_SLO", "1")
    assert slo.enabled()
    monkeypatch.setenv("BLUEFOG_SLO_CANARY", "0")
    assert not slo.canary_enabled()


def test_on_init_gates_session_on_env(cpu_devices, monkeypatch):
    assert slo.active() is None  # fixture init ran without the knob
    monkeypatch.setenv("BLUEFOG_SLO", "1")
    bf.shutdown()
    bf.init(devices=cpu_devices[:SIZE])
    assert slo.active() is not None
    bf.shutdown()
    assert slo.active() is None  # on_shutdown dropped it
    bf.init(devices=cpu_devices[:SIZE])  # fixture teardown expects one


# -- PR-7 surfaces ------------------------------------------------------------


def test_alert_emission_reaches_all_surfaces(tmp_path, monkeypatch):
    """One page alert: doctor counter, flight side table + ring,
    timeline-safe, JSONL file — and the sampled budget snapshot lands
    in the eviction-proof slo side table."""
    path = tmp_path / "slo.jsonl"
    monkeypatch.setenv("BLUEFOG_SLO_FILE", str(path))
    flight.reconfigure()
    eng = _engine()
    for t in range(30):
        eng.observe(None, step=t, values={"avail": 1.0})
    for t in range(30, 34):
        eng.observe(None, step=t, values={"avail": 0.0})
    assert any(a.kind == "slo_fast_burn" for a in eng.alerts)
    c = metrics.peek("bluefog.doctor.advisory.slo_fast_burn")
    assert c is not None and c.value >= 1
    assert metrics.peek("bluefog.slo.alerts").value >= 1
    dump = json.loads(open(bf.flight_dump(
        str(tmp_path / "flight.json")
    )).read())
    kinds = [a.get("kind") for a in dump["advisories"]]
    assert "slo_fast_burn" in kinds
    assert dump["slo_snapshots"], "budget snapshot side table empty"
    snap = dump["slo_snapshots"][-1]
    assert snap["worst_burn"] > 0
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert any(l.get("advisory_kind") == "slo_fast_burn"
               for l in lines)
    assert any(l.get("kind") == "sample" for l in lines)
    # burn gauges published under the documented series names
    assert metrics.peek("bluefog.slo.burn_fast.avail").value >= 5.0
    assert metrics.peek(
        "bluefog.slo.budget_remaining.avail"
    ) is not None


def test_flight_reconfigure_clears_slo_side_table():
    flight.reconfigure()
    flight.note_slo(step=1, worst_burn=2.0, exhausted=[],
                    canary_ok=True)
    assert flight._build_dump("test")["slo_snapshots"] == [
        {"step": 1, "worst_burn": 2.0, "exhausted": [],
         "canary_ok": True}
    ]
    flight.reconfigure()
    assert flight._build_dump("test")["slo_snapshots"] == []


# -- /healthz escalation + /slo endpoint --------------------------------------


def _exhaust(eng):
    for t in range(40):
        eng.observe(None, step=t, values={"avail": 0.0})


def test_budget_exhaustion_escalates_healthz_to_critical():
    eng = slo.start(interval=1, objectives=[_objective()],
                    canary=False)
    plane = health.start(interval=1)
    v = health.healthz_verdict(plane)
    assert v["status"] == "ok" and v["slo_exhausted"] == []
    _exhaust(eng)
    assert eng.exhausted_objectives() == ["avail"]
    v = health.healthz_verdict(plane)
    assert v["status"] == "critical"
    assert v["slo_exhausted"] == ["avail"]
    assert any("slo budget exhausted" in r for r in v["reasons"])
    # and the HTTP mapping returns 503, the load-balancer contract
    srv = health.serve(0)
    assert srv is not None
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz"
        )
    assert err.value.code == 503
    body = json.loads(err.value.read())
    assert body["slo_exhausted"] == ["avail"]
    srv.close()


def test_slo_endpoint_serves_report_and_404_lists_it():
    eng = slo.start(interval=1, objectives=[_objective()],
                    canary=False)
    for t in range(25):
        eng.observe(None, step=t,
                    values={"avail": 1.0 if t % 7 else 0.0})
    srv = health.serve(0)
    assert srv is not None
    base = f"http://127.0.0.1:{srv.port}"
    rep = json.loads(urllib.request.urlopen(base + "/slo").read())
    assert rep["kind"] == "slo_dump"
    names = [o["name"] for o in rep["objectives"]]
    assert names == ["avail"]
    assert rep["objectives"][0]["budget"]["spent"] >= 1
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(base + "/nope")
    assert err.value.code == 404
    assert "/slo" in json.loads(err.value.read())["paths"]
    # no active engine -> empty-but-valid block, never a 500
    slo.stop()
    rep = json.loads(urllib.request.urlopen(base + "/slo").read())
    assert rep["objectives"] == []
    srv.close()


def test_slo_endpoint_non_finite_guard():
    """A non-finite objective reading must reach the scraper as null,
    never a bare NaN token (strict-JSON regression tripwire on the
    NEW block)."""
    eng = slo.start(interval=1, objectives=[_objective()],
                    canary=False)
    for t in range(5):
        eng.observe(None, step=t, values={"avail": 1.0})
    # forge non-finite state the sanitizer must degrade to null
    eng._state["avail"].last_value = float("nan")
    eng.samples.append({"kind": "sample", "step": 99,
                        "worst_burn": float("inf"),
                        "objectives": {}})
    srv = health.serve(0)
    assert srv is not None

    def reject(tok):
        raise ValueError(f"non-finite token {tok!r}")

    raw = urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/slo"
    ).read()
    rep = json.loads(raw, parse_constant=reject)
    assert rep["objectives"][0]["last_value"] is None
    assert rep["samples"][-1]["worst_burn"] is None
    srv.close()


def test_concurrent_scrapes_during_sampled_publishes():
    """Two clients hammering /slo and /healthz while the engine
    publishes sampled evaluations: every response parses as strict
    JSON (the PR-10 concurrent-scrape discipline applied to the new
    block)."""
    eng = slo.start(interval=1, objectives=[_objective()],
                    canary=False)
    srv = health.serve(0)
    assert srv is not None
    base = f"http://127.0.0.1:{srv.port}"
    errors = []
    stop = threading.Event()

    def scrape(path):
        while not stop.is_set():
            try:
                raw = urllib.request.urlopen(
                    base + path, timeout=5
                ).read()
                json.loads(raw)
            except urllib.error.HTTPError as e:
                if e.code != 503:  # critical is a VALID verdict here
                    errors.append((path, repr(e)))
                    return
            except Exception as e:
                errors.append((path, repr(e)))
                return

    threads = [
        threading.Thread(target=scrape, args=("/slo",), daemon=True),
        threading.Thread(target=scrape, args=("/healthz",),
                         daemon=True),
    ]
    for t in threads:
        t.start()
    rng = np.random.RandomState(3)
    for t_step in range(120):
        eng.observe(None, step=t_step,
                    values={"avail": float(rng.rand() > 0.2)})
    stop.set()
    for t in threads:
        t.join(timeout=10)
    srv.close()
    assert not errors, errors


# -- fleet field + autotune record --------------------------------------------


def test_fleet_field_and_health_report_carry_slo_burn():
    assert health.FLEET_FIELDS[-1] == "slo_burn"
    eng = slo.start(interval=1, objectives=[_objective()],
                    canary=False)
    _exhaust(eng)
    assert slo.worst_burn() == pytest.approx(10.0)  # 1/budget_frac
    ctx = bf.get_context()
    plane = health.start(interval=1)
    vec = plane._local_vector(ctx, None, list(range(SIZE)))
    i = list(health.FLEET_FIELDS).index("slo_burn")
    assert np.allclose(vec[:, i], 10.0)
    rep = plane.report()
    assert rep["slo"]["worst_burn"] == pytest.approx(10.0)
    assert rep["slo"]["exhausted"] == ["avail"]
    # engine off -> field reads 0.0, block absent
    slo.stop()
    vec = plane._local_vector(ctx, None, list(range(SIZE)))
    assert np.allclose(vec[:, i], 0.0)
    assert "slo" not in plane.report()


def test_autotune_decision_record_carries_slo_burn():
    from bluefog_tpu import autotune

    assert autotune._slo_burn() == 0.0  # engine off
    eng = slo.start(interval=1, objectives=[_objective()],
                    canary=False)
    _exhaust(eng)
    assert autotune._slo_burn() == pytest.approx(10.0)
    rec = autotune.DecisionRecord(
        seq=0, step=1, comm_steps=1, action="hold", triggers=[],
        blamed=[], candidates=[], chosen=None, predicted={},
        hysteresis={}, topo_version_before=0, topo_version_after=0,
        dry_run=False, slo_burn=autotune._slo_burn(),
    )
    assert rec.to_json()["slo_burn"] == pytest.approx(10.0)


# -- canary lane --------------------------------------------------------------


@pytest.mark.parametrize("wire", [None, "bf16", "int8", "int4",
                                  "int8_ef"])
def test_canary_clean_fabric_passes_against_wire_replay(wire):
    """A healthy mesh: every delivered edge matches the wire_ref
    replay within tolerance, for every wire tier (the EF tier ships
    its base tier — the probe is memoryless)."""
    ctx = bf.get_context()
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    plan = _train_plan(ctx, wire)
    lane = slo.CanaryLane()
    verdict = lane.probe(ctx, plan, wire)
    assert verdict["ok"], verdict
    assert verdict["rounds"] == 3
    assert verdict["wire"] == (wire or "fp32").replace("_ef", "")
    assert lane.probes == 1 and lane.failures == 0


def _train_plan(ctx, wire):
    """A real compiled plan for the active topology (what the
    optimizer hook passes as ``self._last_plan``)."""
    from bluefog_tpu.collective.plan import plan_from_topology

    return plan_from_topology(ctx.load_topology())


def test_canary_flips_on_degrade_fault_naming_edge():
    """Chaos parity: an injected lossy link corrupts the delivered
    canary host-side; the verdict flips and the worst edge row names
    exactly the injected (src, dst)."""
    ctx = bf.get_context()
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start(policy="average")
    session.inject("degrade", rank=2, step=0, factor=0.05, peer=3)
    plan = _train_plan(ctx, "int8")
    lane = slo.CanaryLane()
    verdict = lane.probe(ctx, plan, "int8")
    assert not verdict["ok"]
    assert verdict["edges"][0][:2] == [2, 3]
    assert verdict["max_dev"] > 100 * slo.CANARY_TOL
    # only the injected edge fails
    assert {tuple(e[:2]) for e in verdict["edges"]} == {(2, 3)}


def test_canary_advisory_and_gauges_on_failure():
    ctx = bf.get_context()
    bf.set_topology(tu.RingGraph(SIZE))
    session = bf.elastic.start(policy="average")
    session.inject("degrade", rank=1, step=0, factor=0.1, peer=2)
    eng = slo.start(interval=1, objectives=[], canary=True)
    plan = _train_plan(ctx, None)
    eng.observe(ctx, step=0, plan=plan, wire=None)
    assert metrics.peek("bluefog.slo.canary_ok").value == 0.0
    assert metrics.peek("bluefog.slo.canary_probes").value == 1
    advs = [a for a in eng.alerts if a.kind == "slo_canary_failed"]
    assert advs and advs[0].detail["edges"][0][:2] == [1, 2]


def test_optimizer_hook_runs_canary_without_touching_programs():
    """The full hook path under BLUEFOG_SLO: a real train step drives
    the engine, the canary compiles into its own op-cache family, and
    the training cache keys are untouched (structural pin)."""
    import optax

    ctx = bf.get_context()
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    rng = np.random.RandomState(0)
    w0 = (rng.randn(16, 16) / 4.0).astype(np.float32)
    xs = bf.worker_values(
        lambda r: rng.randn(4, 16).astype(np.float32))
    ys = bf.worker_values(
        lambda r: rng.randn(4, 16).astype(np.float32))

    def loss_fn(p, x, y):
        import jax.numpy as jnp

        return jnp.mean((x @ p["w"] - y) ** 2)

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
    eng = slo.start(interval=2, canary=True)
    for _ in range(6):
        params, state, _ = step(params, state, xs, ys)
    assert eng._samples >= 3
    assert eng.canary.probes >= 3
    assert eng.canary.last["ok"]
    after = {
        k for k in ctx.op_cache
        if isinstance(k, tuple) and k and k[0] in (
            "opt_step", "opt_fused_step",
        )
    }
    assert after == train_keys  # structural pin
    assert any(
        isinstance(k, tuple) and k and k[0] == "slo_canary"
        for k in ctx.op_cache
    )


# -- fleetsim rehearsal -------------------------------------------------------


def test_fleetsim_churn_storm_burn_rehearsal_n1024():
    """N=1024 virtual ranks, 10% churn storm: the availability
    objective's burn/budget series matches the numpy oracle tick for
    tick, the storm pages the fast window, and the pre-storm prefix
    stays silent."""
    from bluefog_tpu import fleetsim

    n = 1024
    plan = fleetsim.storm_plan(n, 0.10, step=10, seed=3)
    vf = fleetsim.VirtualFleet(n, topology="exp2",
                               policy="receiver", plan=plan, seed=3)
    obj = slo.Objective("availability", "fleetsim live fraction",
                        target=0.95, comparison="ge", window=30,
                        budget_frac=0.1, fast_window=4,
                        fast_burn=5.0, slow_window=15,
                        slow_burn=1.5)
    eng = slo.SLOEngine(interval=1, objectives=[obj], canary=False)
    fracs = []
    for t in range(30):
        vf.tick()
        frac = vf._live_count / n
        fracs.append(frac)
        eng.observe(None, step=t, values={"availability": frac})
    # oracle: the same arithmetic rebuilt from the recorded series
    flags = [0 if f >= 0.95 else 1 for f in fracs]
    want_burn, want_budget = _oracle(flags[-obj.window:],
                                     obj.fast_window, obj.budget_frac)
    st = eng._state["availability"]
    assert slo.burn_rate(list(st.flags), obj.fast_window,
                         obj.budget_frac) == pytest.approx(want_burn)
    got_budget = slo.budget_state(list(st.flags), obj.window,
                                  obj.budget_frac)
    _w, want_full = _oracle(flags[-obj.window:], obj.window,
                            obj.budget_frac)
    assert got_budget == pytest.approx(want_full)
    # the storm kills 10% at tick 10 -> every later sample is bad
    assert sum(flags[:10]) == 0
    assert all(flags[11:])
    page = [a for a in eng.alerts if a.kind == "slo_fast_burn"]
    assert page and page[0].step <= 10 + obj.fast_window


# -- tools --------------------------------------------------------------------


def test_slo_report_tool(tmp_path):
    eng = slo.start(interval=1, objectives=[_objective()],
                    canary=False)
    for t in range(40):
        eng.observe(None, step=t,
                    values={"avail": 1.0 if t < 30 else 0.0})
    art = tmp_path / "slo.json"
    slo.dump(str(art))
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "slo_report.py"),
         str(art), "--json"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["objectives"][0]["name"] == "avail"
    assert rep["objectives"][0]["budget"]["exhausted"] is True
    assert rep["worst_alert"] == "slo_budget_exhausted" or \
        rep["alerts"] >= 1
    # human rendering names the objective and the budget
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "slo_report.py"),
         str(art)],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    assert "avail" in out.stdout and "budget" in out.stdout.lower()
