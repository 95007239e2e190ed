# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Memory-observatory tests: the live-buffer census ownership
classification, analytic-vs-measured reconciliation with the
``memory_drift`` gate, the budgeted ``memory_pressure`` advisory with
its shard-recommendation hint, phase watermarks, the shared
``env_int``/``env_float`` knob parsing, OOM forensics (crash-hook
detection, the ``oom`` chaos fault producing a flight dump whose
ranked census names the planted owner category), the health-plane
fleet fields + ``/fleet`` block, the autotune decision flag, and
``tools/memory_report.py`` postmortem reconstruction from committed
artifacts alone.
"""

import json
import os
import subprocess
import sys

import numpy as np
import optax
import pytest

import bluefog_tpu as bf
import bluefog_tpu.topology as tu
from bluefog_tpu import autotune, flight, health
from bluefog_tpu import memory as bf_memory
from bluefog_tpu import metrics, scaling
from bluefog_tpu.logging_util import env_float, env_int

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    for k in ("BLUEFOG_MEMORY", "BLUEFOG_MEMORY_INTERVAL",
              "BLUEFOG_MEMORY_BUDGET", "BLUEFOG_MEMORY_FILE",
              "BLUEFOG_MEMORY_DRIFT_TOL", "BLUEFOG_SHARD",
              "BLUEFOG_METRICS", "BLUEFOG_HEALTH", "BLUEFOG_FLIGHT_DIR"):
        monkeypatch.delenv(k, raising=False)
    metrics.reset()
    bf.init(devices=cpu_devices[:SIZE])
    yield
    bf_memory.stop()
    health.stop()
    bf.elastic.stop()
    bf.shutdown()
    metrics.reset()


def _adam_problem(dim=4096, order="grad"):
    cls = (
        bf.DistributedGradientAllreduceOptimizer if order == "grad"
        else bf.DistributedNeighborAllreduceOptimizer
    )
    opt = cls(optax.adam(0.01))
    rng = np.random.RandomState(0)
    params = {"w": bf.worker_values(
        lambda r: rng.randn(dim).astype(np.float32)
    )}
    state = opt.init(params)
    grads = {"w": bf.worker_values(
        lambda r: np.zeros(dim, np.float32)
    )}
    return opt, params, state, grads


# -- env knob parsing (logging_util.env_int/env_float) ------------------------


def test_env_int_malformed_falls_back_with_one_warning(monkeypatch):
    from bluefog_tpu import logging_util

    monkeypatch.setenv("BLUEFOG_MEMORY_INTERVAL", "twenty")
    key = "env_int:BLUEFOG_MEMORY_INTERVAL:twenty"
    logging_util._warned_once.discard(key)
    assert bf_memory.memory_interval() == 20
    assert key in logging_util._warned_once
    n = len(logging_util._warned_once)
    assert bf_memory.memory_interval() == 20  # second read: silent
    assert len(logging_util._warned_once) == n


def test_env_int_and_float_parse_valid_values(monkeypatch):
    monkeypatch.setenv("X_INT", "42")
    monkeypatch.setenv("X_FLOAT", "2.5")
    assert env_int("X_INT", 7) == 42
    assert env_float("X_FLOAT", 1.0) == 2.5
    assert env_int("X_ABSENT", 7) == 7
    assert env_float("X_ABSENT", 1.5) == 1.5


def test_malformed_knobs_do_not_raise_across_modules(monkeypatch):
    """The audit's point: a typo'd interval/capacity/byte knob must
    never raise ValueError out of a dispatch path."""
    from bluefog_tpu import async_gossip, attribution, staleness
    from bluefog_tpu.collective import inner

    for k in ("BLUEFOG_METRICS_INTERVAL", "BLUEFOG_HEALTH_INTERVAL",
              "BLUEFOG_HEALTH_PORT", "BLUEFOG_DOCTOR_INTERVAL",
              "BLUEFOG_STALENESS_INTERVAL", "BLUEFOG_STALENESS_BOUND",
              "BLUEFOG_AUTOTUNE_INTERVAL", "BLUEFOG_FLIGHT_CAPACITY",
              "BLUEFOG_BUCKET_BYTES", "BLUEFOG_ASYNC_MAX_AGE",
              "BLUEFOG_MEMORY_BUDGET"):
        monkeypatch.setenv(k, "not-a-number")
    assert metrics.metrics_interval() == 10
    assert health.health_interval() == 20
    assert health.health_port() == 0
    assert attribution.doctor_interval() == 100
    assert staleness.staleness_interval() == 20
    assert staleness.staleness_bound() == 4
    assert autotune.autotune_interval() == 50
    assert flight.capacity() == 8192
    assert inner.bucket_bytes_cap() == 4 << 20
    assert async_gossip.async_max_age() == 8
    assert bf_memory.memory_budget() == 0


def test_quantized_temporaries_bytes_model():
    """The ROADMAP-2 fusion baseline's analytic staging model: f32
    dequant (4 B/elem) + int8 quantize staging (1 B/elem) + the packed
    nibble copy for the int4 tiers (0.5 B/elem), all over the payload
    padded UP to the 512-element scale grid; fp32 ships verbatim."""
    f = scaling.quantized_temporaries_bytes
    assert f(4096, None) == 0
    assert f(0, "int8") == 0
    assert f(4096, "bf16") == 4 * 4096
    assert f(4096, "int8") == 4 * 4096 + 4096
    assert f(4096, "int8_ef") == f(4096, "int8")
    assert f(4096, "int4") == 4 * 4096 + 4096 + 4096 // 2
    assert f(4096, "int4_ef") == f(4096, "int4")
    # padding: 100 elems stage a whole 512-block
    assert f(100, "int8") == 4 * 512 + 512
    assert f(100, "int4") == 4 * 512 + 512 + 256
    # int4 stages MORE than int8 (the extra packed copy) even though
    # it ships fewer wire bytes — exactly the fusion motivation
    assert f(4096, "int4") > f(4096, "int8")
    assert scaling.wire_payload_bytes(4096, 4, "int4") < \
        scaling.wire_payload_bytes(4096, 4, "int8")


def test_quantized_temporaries_bytes_fused_arm():
    """``fused=True`` prices the kernel wire: 2x (packed buffer +
    scale sidecar) — the encode output plus the one in-flight received
    copy — and never a full-width reconstruction. It must land below
    both the composite model AND the raw fp32 payload (4 B/elem), the
    bound tests/test_wire_kernels.py::test_fused_scratch_below_fp32_row
    holds the measured bytes to."""
    f = scaling.quantized_temporaries_bytes
    # int8: packed = padded int8 lanes, sidecar = f32 scale per block
    assert f(4096, "int8", fused=True) == 2 * (4096 + (4096 // 512) * 4)
    # int4: half-width lanes, bf16 scale per block
    assert f(4096, "int4", fused=True) == 2 * (2048 + (4096 // 512) * 2)
    for wire in ("int8", "int4"):
        assert f(4096, wire + "_ef", fused=True) == f(4096, wire, fused=True)
        assert f(4096, wire, fused=True) < f(4096, wire)
        assert f(4096, wire, fused=True) < 4 * 4096  # under the fp32 payload
    # padding still rounds up to the 512-element scale grid
    assert f(100, "int8", fused=True) == 2 * (512 + 4)
    assert f(100, "int4", fused=True) == 2 * (256 + 2)
    # no fused path for bf16/fp32 — priced identically
    assert f(4096, "bf16", fused=True) == f(4096, "bf16")
    assert f(4096, None, fused=True) == 0
    assert f(0, "int4", fused=True) == 0


# -- census + reconciliation --------------------------------------------------


def test_census_classifies_owner_categories():
    opt, params, state, grads = _adam_problem()
    params, state = opt.step(params, state, grads)
    c = bf_memory.census({"params": params, "opt_state": state})
    assert set(bf_memory.CATEGORIES) <= set(c)
    assert c["params"]["bytes"] == SIZE * 4096 * 4
    # Adam: mu + nu (+ scalar count) — at least 2x the param bytes
    assert c["opt_state"]["bytes"] >= 2 * c["params"]["bytes"]
    assert c["other"]["bytes"] > 0  # grads etc. are unattributed
    ranked = bf_memory.ranked_census(c)
    assert ranked[0]["bytes"] >= ranked[-1]["bytes"]


def test_census_grads_owner_category():
    """The ``grads`` owner class (the ZeRO-2 memory axis): a gradient
    tree handed to the census is attributed to ``grads``, not
    ``other`` — what the ×1/N reduced-gradient claim is measured
    against (docs/sharding.md)."""
    opt, params, state, grads = _adam_problem()
    params, state = opt.step(params, state, grads)
    assert "grads" in bf_memory.CATEGORIES
    c0 = bf_memory.census({"params": params, "opt_state": state})
    c1 = bf_memory.census(
        {"params": params, "opt_state": state, "grads": grads}
    )
    assert c0["grads"]["bytes"] == 0
    assert c1["grads"]["bytes"] == SIZE * 4096 * 4
    assert c1["other"]["bytes"] <= c0["other"]["bytes"]


def test_reconciliation_is_exact_for_replicated_adam():
    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem()
    for _ in range(3):
        params, state = opt.step(params, state, grads)
    s = obs.samples[-1]
    assert s["measured_state_bytes"] == s["analytic_state_bytes"]
    assert s["reconcile_rel_err"] == 0.0
    assert not [a for a in obs.advisories if a.kind == "memory_drift"]


def test_reconciliation_is_exact_for_sharded_adam(monkeypatch):
    monkeypatch.setenv("BLUEFOG_SHARD", "1")
    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem(dim=1 << 15)
    for _ in range(3):
        params, state = opt.step(params, state, grads)
    s = obs.samples[-1]
    assert s["analytic_state_bytes"] < scaling.optimizer_state_bytes(
        params, opt, shard=False
    ), "sharded analytic model must price the 1/N slot"
    assert s["reconcile_rel_err"] == 0.0


def test_memory_drift_fires_on_planted_leak():
    """A state tree carrying an unaccounted buffer (a leak, a stale
    generation) must trip the persistent-residual gate and name the
    advisory across the emission surfaces."""
    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem()
    leak = bf.worker_values(
        lambda r: np.zeros(4096, np.float32)
    )
    ctx = bf.get_context()
    for step in range(bf_memory.DRIFT_STREAK + 1):
        # feed the observatory directly: same params/opt, but the
        # opt_state tree is padded with the planted leak
        obs.observe(ctx, step=step, optimizer=opt,
                    params=params, opt_state=(state, leak, leak))
    drifts = [a for a in obs.advisories if a.kind == "memory_drift"]
    assert drifts, "planted leak did not fire memory_drift"
    d = drifts[0].detail
    assert d["measured_state_bytes"] > d["analytic_state_bytes"]
    assert d["rel_err"] > obs.drift_tol
    # the advisory reached the doctor counter and the flight side table
    ctr = metrics.peek("bluefog.doctor.advisory.memory_drift")
    assert ctr is not None and ctr.value >= 1
    assert any(
        a.get("kind") == "memory_drift" for a in flight._advisories
    )


def test_clean_run_never_fires_drift_or_pressure():
    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem()
    for _ in range(6):
        params, state = opt.step(params, state, grads)
    assert obs.advisories == []
    assert obs.samples, "sampling must have happened"


# -- pressure gate + shard hint -----------------------------------------------


def test_memory_pressure_fires_under_budget_with_shard_hint():
    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem(dim=1 << 15)
    params, state = opt.step(params, state, grads)
    obs.budget = max(int(obs.last_bytes_per_rank() * 0.9), 1)
    for _ in range(3):
        params, state = opt.step(params, state, grads)
    pressures = [
        a for a in obs.advisories if a.kind == "memory_pressure"
    ]
    assert pressures, "budget breach did not fire memory_pressure"
    d = pressures[0].detail
    assert d["headroom_bytes"] < 0
    assert d["shard_enabled"] is False
    assert d["shard_hint"] is True, d
    assert d["census"], "advisory must carry the ranked census"
    assert obs.last_headroom() < 0


def test_memory_pressure_respects_cooldown():
    obs = bf_memory.start(interval=1)
    obs.budget = 1  # everything breaches
    opt, params, state, grads = _adam_problem()
    for _ in range(bf_memory.ADVISORY_COOLDOWN):
        params, state = opt.step(params, state, grads)
    pressures = [
        a for a in obs.advisories if a.kind == "memory_pressure"
    ]
    assert len(pressures) == 1, (
        "persistent pressure must re-fire once per cooldown, got "
        f"{len(pressures)}"
    )


def test_cooldown_expires_on_the_sample_clock():
    """The mute ticks per SAMPLE, not per gate check: a pressure
    episode that ends, followed by a quiet stretch longer than the
    cooldown, must not swallow the NEXT episode's first advisory."""
    obs = bf_memory.start(interval=1)
    obs.budget = 1
    opt, params, state, grads = _adam_problem()
    params, state = opt.step(params, state, grads)  # episode 1 fires
    assert len(obs.advisories) == 1
    obs.budget = 1 << 40  # pressure relieved
    for _ in range(bf_memory.ADVISORY_COOLDOWN + 1):
        params, state = opt.step(params, state, grads)
    assert not obs.pressure_active(), "mute must expire while quiet"
    obs.budget = 1  # episode 2
    params, state = opt.step(params, state, grads)
    pressures = [
        a for a in obs.advisories if a.kind == "memory_pressure"
    ]
    assert len(pressures) == 2, (
        "a new episode after an expired cooldown must fire immediately"
    )


def test_autotune_decision_records_carry_memory_pressure():
    """The decision flag is 'un-cooled-down advisory RIGHT NOW': true
    inside the re-fire window, false again once it expires."""
    obs = bf_memory.start(interval=1)
    assert autotune._memory_pressure() is False
    obs.budget = 1
    opt, params, state, grads = _adam_problem()
    params, state = opt.step(params, state, grads)
    assert autotune._memory_pressure() is True
    obs.budget = 1 << 40  # relieved; let the cooldown run out
    for _ in range(bf_memory.ADVISORY_COOLDOWN + 1):
        params, state = opt.step(params, state, grads)
    assert autotune._memory_pressure() is False


# -- phase watermarks ---------------------------------------------------------


def test_phase_scopes_record_watermarks():
    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem()
    params, state = opt.step(params, state, grads)
    assert "dispatch" in obs.phase_peaks
    assert obs.phase_peaks["dispatch"]["count"] >= 1
    assert obs.phase_peaks["dispatch"]["peak_rss_bytes"] > 0
    assert "compile" in obs.phase_peaks  # first step built the program
    g = metrics.peek("bluefog.memory.phase_peak_bytes.dispatch")
    assert g is not None and g.value > 0


def test_phase_scope_noop_without_session():
    bf_memory.stop()
    with bf_memory.phase_scope("dispatch"):
        pass  # must not raise, must not create state
    assert bf_memory.active() is None


def test_checkpoint_save_records_phase(tmp_path):
    from bluefog_tpu import checkpoint

    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem(dim=512)
    params, state = opt.step(params, state, grads)
    checkpoint.save(str(tmp_path / "ckpt"), 1, params, state, opt)
    assert "checkpoint_save" in obs.phase_peaks


# -- structural / bitwise neutrality ------------------------------------------


def test_observatory_compiles_nothing_and_stays_bitwise():
    ctx = bf.get_context()
    opt, params, state, grads = _adam_problem(order="na")
    params, state = opt.step(params, state, grads)
    keys_off = set(ctx.op_cache)
    bf_memory.start(interval=1)
    params_on, state_on = opt.step(params, state, grads)
    assert set(ctx.op_cache) == keys_off, (
        "the memory observatory must not add cache entries"
    )
    bf_memory.stop()
    params_off, state_off = opt.step(params, state, grads)
    # same inputs, observatory on vs off: identical bits
    assert np.array_equal(
        np.asarray(params_on["w"]), np.asarray(params_off["w"])
    )


# -- OOM forensics ------------------------------------------------------------


def test_oom_fault_grammar_validation():
    from bluefog_tpu.elastic.faults import Fault, parse_fault_plan

    plan = parse_fault_plan("oom:rank=3,step=12")
    assert plan.faults[0].kind == "oom"
    with pytest.raises(ValueError, match="peer="):
        Fault(kind="oom", rank=1, step=0, peer=2)
    with pytest.raises(ValueError, match="seconds=/factor="):
        Fault(kind="oom", rank=1, step=0, seconds=5.0)
    with pytest.raises(ValueError, match="seconds=/factor="):
        Fault(kind="oom", rank=1, step=0, factor=0.5)
    with pytest.raises(ValueError, match="steps="):
        Fault(kind="oom", rank=1, step=0, hold_steps=3)


def test_is_oom_detects_both_shapes():
    assert bf_memory._is_oom(MemoryError, MemoryError("boom"))
    assert bf_memory._is_oom(
        RuntimeError,
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating"),
    )
    assert not bf_memory._is_oom(ValueError, ValueError("nope"))
    assert bf_memory._is_oom(
        bf_memory.SimulatedResourceExhausted,
        bf_memory.SimulatedResourceExhausted("x"),
    )


def test_oom_chaos_dump_names_planted_owner_category(
    tmp_path, monkeypatch
):
    """The acceptance criterion: a simulated RESOURCE_EXHAUSTED (the
    ``oom`` fault kind) produces a flight dump whose RANKED buffer
    census names the planted owner category — and
    ``tools/memory_report.py`` reconstructs the postmortem from the
    committed artifact alone."""
    monkeypatch.setenv("BLUEFOG_FLIGHT_DIR", str(tmp_path))
    flight.reconfigure()
    obs = bf_memory.start(interval=1)
    # plant the owner: a window buffer far bigger than everything else
    big = bf.worker_values(
        lambda r: np.zeros((1 << 20,), np.float32)  # 4 MiB per rank
    )
    bf.win_create(big, "planted")
    opt, params, state, grads = _adam_problem(dim=1024)
    session = bf.elastic.start(policy="average")
    session.inject("oom", rank=2, step=2)
    guard = bf.elastic.guard(opt)
    with pytest.raises(MemoryError, match="RESOURCE_EXHAUSTED"):
        for _ in range(4):
            params, state = guard.step(params, state, grads)
    # the forensics path ran: counter, ring event, side table, dump
    ctr = metrics.peek("bluefog.memory.oom_events")
    assert ctr is not None and ctr.value >= 1
    dump_path = tmp_path / "flight_0.json"
    assert dump_path.exists(), "oom must trigger an automatic dump"
    d = json.loads(dump_path.read_text())
    assert any(h.startswith("oom:chaos") for h in d["dump_history"])
    ooms = [a for a in d["advisories"] if a.get("kind") == "oom"]
    assert ooms, "ranked census must ride the advisory side table"
    assert ooms[-1]["top_category"] == "windows", ooms[-1]
    assert ooms[-1]["ranked_census"][0]["category"] == "windows"
    assert obs.oom_events >= 1

    # postmortem reconstruction from the committed artifact ALONE
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "memory_report.py"),
         "--flight", str(dump_path), "--json"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert report["postmortems"], report
    pm = report["postmortems"][0]
    assert pm["top_category"] == "windows"
    assert pm["ranked_census"][0]["category"] == "windows"
    # human mode names the category in a sentence
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "memory_report.py"),
         "--flight", str(dump_path)],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out2.returncode == 0, out2.stderr
    assert "windows" in out2.stdout
    assert "OOM postmortem" in out2.stdout


def test_real_memoryerror_excepthook_path(tmp_path, monkeypatch):
    """An uncaught MemoryError through the installed excepthook must
    run the forensics path (hook chain preserved and restored)."""
    monkeypatch.setenv("BLUEFOG_FLIGHT_DIR", str(tmp_path))
    flight.reconfigure()
    bf_memory.start(interval=1)
    orig_hook = sys.excepthook
    bf_memory._install_oom_hooks()
    try:
        prev_calls = []
        bf_memory._prev_excepthook = (
            lambda *a: prev_calls.append(a)
        )
        exc = MemoryError("RESOURCE_EXHAUSTED: oom")
        sys.excepthook(MemoryError, exc, None)
        assert prev_calls, "previous hook must still be chained"
        assert (tmp_path / "flight_0.json").exists()
        d = json.loads((tmp_path / "flight_0.json").read_text())
        assert any(
            a.get("kind") == "oom" for a in d["advisories"]
        )
    finally:
        bf_memory._uninstall_oom_hooks()
        sys.excepthook = orig_hook
    assert sys.excepthook is not bf_memory._excepthook


def test_injected_oom_counts_once_through_excepthook(
    tmp_path, monkeypatch
):
    """The chaos fault runs forensics at the raise site and marks the
    exception; an UNCAUGHT propagation through the installed
    excepthook must not run them a second time (one injected failure
    = one oom event, like a real single-hook OOM)."""
    monkeypatch.setenv("BLUEFOG_FLIGHT_DIR", str(tmp_path))
    flight.reconfigure()
    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem(dim=1024)
    session = bf.elastic.start(policy="average")
    session.inject("oom", rank=1, step=0)
    guard = bf.elastic.guard(opt)
    caught = None
    try:
        guard.step(params, state, grads)
    except MemoryError as e:
        caught = e
    assert caught is not None
    assert obs.oom_events == 1
    # replay the uncaught path: the hook must skip marked exceptions
    orig_hook = sys.excepthook
    bf_memory._install_oom_hooks()
    try:
        bf_memory._prev_excepthook = lambda *a: None
        sys.excepthook(type(caught), caught, None)
        assert obs.oom_events == 1, "forensics must not run twice"
        # an UNmarked oom still runs them (the real-OOM path)
        sys.excepthook(MemoryError, MemoryError("RESOURCE_EXHAUSTED"),
                       None)
        assert obs.oom_events == 2
    finally:
        bf_memory._uninstall_oom_hooks()
        sys.excepthook = orig_hook


# -- fleet plumbing -----------------------------------------------------------


def test_fleet_fields_carry_memory_slots():
    assert "mem_bytes_per_rank" in health.FLEET_FIELDS
    assert "mem_headroom" in health.FLEET_FIELDS
    obs = bf_memory.start(interval=1)
    obs.budget = 1 << 30
    opt, params, state, grads = _adam_problem()
    params, state = opt.step(params, state, grads)
    plane = health.HealthPlane(interval=1)
    vec = plane._local_vector(bf.get_context(), None, list(range(SIZE)))
    i_bytes = health.FLEET_FIELDS.index("mem_bytes_per_rank")
    i_head = health.FLEET_FIELDS.index("mem_headroom")
    assert vec[0, i_bytes] > 0
    assert vec[0, i_head] > 0
    assert vec[0, i_head] == pytest.approx(
        (1 << 30) - vec[0, i_bytes]
    )


def test_serving_report_carries_memory_block():
    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem()
    params, state = opt.step(params, state, grads)
    plane = health.HealthPlane(interval=1)
    rep = plane.report()
    assert "memory" in rep
    blk = rep["memory"]
    assert blk["bytes_per_rank"] > 0
    assert blk["ranked_census"], blk
    assert blk["oom_events"] == 0


def test_fleet_report_renders_memory_columns(tmp_path):
    """tools/fleet_report.py: memory columns render when the block is
    present and degrade to absent when it is not (pre-memory
    artifacts)."""
    with_mem = {
        "kind": "health_dump", "comm_steps": 10,
        "last_sample": {"step_ms_ewma": 1.0},
        "healthz": {"status": "ok"},
        "memory": {"bytes_per_rank": 123456, "headroom_bytes": 1000,
                   "budget_bytes": 124456, "peak_bytes_per_rank": 130000,
                   "oom_events": 0, "ranked_census": []},
    }
    without = {
        "kind": "health_dump", "comm_steps": 10,
        "last_sample": {"step_ms_ewma": 1.0},
        "healthz": {"status": "ok"},
    }
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(with_mem))
    b.write_text(json.dumps(without))
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleet_report.py"),
         str(a), str(b), "--json"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    rows = rep["processes"]
    assert rows[0]["memory"] == "active"
    assert rows[0]["mem_bytes_per_rank"] == 123456
    assert rows[0]["mem_headroom_bytes"] == 1000
    assert rows[1]["memory"] == "absent"
    assert rows[1]["mem_bytes_per_rank"] is None


# -- artifacts + CLI ----------------------------------------------------------


def test_dump_and_memory_report_cli(tmp_path, monkeypatch):
    jsonl = tmp_path / "memory.jsonl"
    monkeypatch.setenv("BLUEFOG_MEMORY_FILE", str(jsonl))
    obs = bf_memory.start(interval=1)
    opt, params, state, grads = _adam_problem()
    for _ in range(3):
        params, state = opt.step(params, state, grads)
    dump = tmp_path / "memory_dump.json"
    assert bf_memory.dump(str(dump)) == str(dump)
    d = json.loads(dump.read_text())
    assert d["kind"] == "memory_dump"
    assert d["samples"] and d["last_census_ranked"]
    assert jsonl.exists()

    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "memory_report.py"),
         str(dump), "--jsonl", str(jsonl), "--json"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["kind"] == "memory_report"
    assert rep["samples"] >= 3
    assert rep["last_census"]
    # human mode renders without crashing
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "memory_report.py"),
         str(dump)],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO,
    )
    assert out2.returncode == 0, out2.stderr
    assert "last census" in out2.stdout


def test_init_respects_enable_env(monkeypatch, cpu_devices):
    monkeypatch.setenv("BLUEFOG_MEMORY", "1")
    bf.init(devices=cpu_devices[:SIZE])
    assert bf_memory.active() is not None
    monkeypatch.delenv("BLUEFOG_MEMORY")
    bf.init(devices=cpu_devices[:SIZE])
    assert bf_memory.active() is None
