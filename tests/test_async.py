# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Asynchronous gossip engine (``bf.make_async_train_step``): numpy
oracle equivalence under decoupled cadences, the async-off bitwise pin,
the bounded-staleness gate (drop and throttle policies, advisory
naming), elastic repair re-windowing, the watchdog SUSPECT path for a
hung fold, and the observability integrations (staleness surface,
health report block, autotune record flag)."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import async_gossip
from bluefog_tpu import metrics
from bluefog_tpu import staleness as staleness_mod
from bluefog_tpu import topology as tu
from bluefog_tpu import watchdog
from bluefog_tpu import windows as win_mod
from bluefog_tpu.elastic.membership import RankState

SIZE = 8
DIM = 3


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices):
    bf.init(devices=cpu_devices[:SIZE])
    yield
    bf.elastic.stop()
    bf.win_free()
    bf.shutdown()
    metrics.reset()


def quad_loss(p, target):
    return 0.5 * jnp.sum((p["w"] - target) ** 2)


def problem(seed=0, dim=DIM):
    rng = np.random.RandomState(seed)
    z0 = rng.randn(SIZE, dim).astype(np.float32)
    return z0


def build(lr=0.2, seed=0, dim=DIM, **kwargs):
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    z0 = problem(seed, dim)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(lr))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    step = bf.make_async_train_step(opt, quad_loss, **kwargs)
    return z0, params, state, step


# -- numpy oracle -------------------------------------------------------------


def sender_stochastic_matrix(graph, size):
    w = np.zeros((size, size))
    for i in range(size):
        outs = [j for j in graph.successors(i) if j != i]
        share = 1.0 / (len(outs) + 1)
        w[i, i] = share
        for j in outs:
            w[i, j] = share
    return w


def async_oracle(z0, c, lr, ticks, w, periods):
    """Numpy model of the engine tick: ranks due on the tick clock take
    a local sgd step at the estimate z = x/p applied to the raw mass x,
    push their column-stochastic shares into per-edge buffers, and fold
    every pending buffer; everyone else is the identity. Returns the
    per-tick estimate sequence."""
    n = len(z0)
    x = z0.astype(np.float64).copy()
    p = np.ones(n)
    edges = [(i, j) for i in range(n) for j in range(n)
             if i != j and w[i, j] != 0.0]
    buf = {e: np.zeros(z0.shape[1]) for e in edges}
    pbuf = {e: 0.0 for e in edges}
    seq = []
    for t in range(ticks):
        part = [t % periods[r] == 0 for r in range(n)]
        z = x / p[:, None]
        u = x.copy()
        for i in range(n):
            if part[i]:
                u[i] = x[i] - lr * (z[i] - c[i])
        newx, newp = u.copy(), p.copy()
        for i in range(n):
            if part[i]:
                newx[i] = w[i, i] * u[i]
                newp[i] = w[i, i] * p[i]
                for j in range(n):
                    if j != i and w[i, j] != 0.0:
                        buf[(i, j)] += w[i, j] * u[i]
                        pbuf[(i, j)] += w[i, j] * p[i]
        x, p = newx, newp
        for r in range(n):
            if part[r]:
                for (s, d) in edges:
                    if d == r:
                        x[r] += buf[(s, d)]
                        p[r] += pbuf[(s, d)]
                        buf[(s, d)] = np.zeros(z0.shape[1])
                        pbuf[(s, d)] = 0.0
        seq.append((x / p[:, None]).copy())
    return np.asarray(seq)


def test_uniform_cadence_matches_oracle():
    """Every rank at cadence 1: the engine IS the accumulated-p
    push-sum recursion, tick for tick."""
    z0, params, state, step = build(lr=0.2)
    graph = bf.load_topology()
    w = sender_stochastic_matrix(graph, SIZE)
    oracle = async_oracle(z0, z0, 0.2, 10, w, [1] * SIZE)
    batch = jnp.asarray(z0)
    for t in range(10):
        params, state, _ = step(params, state, batch)
        np.testing.assert_allclose(
            np.asarray(params["w"]), oracle[t], rtol=1e-4, atol=1e-5,
            err_msg=f"diverged from the async oracle at tick {t}",
        )


def test_decoupled_cadences_match_oracle():
    """Random per-rank cadences: participation masking, pending-mass
    buffering, and the per-slot fold all match the numpy model."""
    rng = np.random.RandomState(3)
    periods = [int(p) for p in rng.randint(1, 5, SIZE)]
    cadence = {r: p for r, p in enumerate(periods) if p > 1}
    z0, params, state, step = build(lr=0.1, seed=1, cadence=cadence)
    graph = bf.load_topology()
    w = sender_stochastic_matrix(graph, SIZE)
    oracle = async_oracle(z0, z0, 0.1, 16, w, periods)
    batch = jnp.asarray(z0)
    for t in range(16):
        params, state, _ = step(params, state, batch)
        np.testing.assert_allclose(
            np.asarray(params["w"]), oracle[t], rtol=1e-4, atol=1e-5,
            err_msg=f"diverged at tick {t} (periods {periods})",
        )


def test_async_consensus_reaches_exact_mean():
    """lr=0: only communication moves state; the estimates converge to
    the exact initial mean even with decoupled cadences (push-sum mass
    conservation under asynchrony)."""
    z0, params, state, step = build(lr=0.0, cadence={0: 3, 5: 2})
    batch = jnp.asarray(z0)
    for _ in range(250):
        params, state, _ = step(params, state, batch)
    np.testing.assert_allclose(
        np.asarray(params["w"]), np.tile(z0.mean(0), (SIZE, 1)),
        atol=1e-3,
    )


# -- async off: the synchronous path, bitwise ---------------------------------


def test_async_off_is_bitwise_synchronous_path():
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    z0 = problem(2)
    batch = jnp.asarray(z0)

    opt_a = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.05))
    pa = {"w": jnp.asarray(z0)}
    sa = opt_a.init(pa)
    off = bf.make_async_train_step(opt_a, quad_loss, enabled=False)
    assert not hasattr(off, "engine")  # the passthrough, not a lane

    opt_b = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.05))
    pb = {"w": jnp.asarray(z0)}
    sb = opt_b.init(pb)
    ref = opt_b.make_train_step(quad_loss)

    for _ in range(6):
        pa, sa, la = off(pa, sa, batch)
        pb, sb, lb = ref(pb, sb, batch)
    assert np.array_equal(np.asarray(pa["w"]), np.asarray(pb["w"]))
    assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_env_kill_switch(monkeypatch):
    monkeypatch.setenv("BLUEFOG_ASYNC", "0")
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    step = bf.make_async_train_step(opt, quad_loss)
    assert not hasattr(step, "engine")
    monkeypatch.setenv("BLUEFOG_ASYNC", "1")
    step = bf.make_async_train_step(opt, quad_loss)
    assert hasattr(step, "engine")


def test_optimizer_method_facade():
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    step = opt.make_async_train_step(quad_loss, cadence={1: 2})
    assert step.engine.cadence == {1: 2}


# -- knob validation ----------------------------------------------------------


def test_bad_knobs_rejected():
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    with pytest.raises(ValueError, match="cadence"):
        bf.make_async_train_step(opt, quad_loss, cadence={0: 0})
    with pytest.raises(ValueError, match="policy"):
        bf.make_async_train_step(opt, quad_loss, policy="panic")
    with pytest.raises(ValueError, match="max_age"):
        bf.make_async_train_step(opt, quad_loss, max_age=0)
    with pytest.raises(ValueError, match="wire"):
        bf.make_async_train_step(opt, quad_loss, wire="int2")


def test_wire_resolution():
    assert async_gossip.async_wire("fp32") is None
    assert async_gossip.async_wire("int8_ef") == "int8"
    assert async_gossip.async_wire("int4_ef") == "int4"
    assert async_gossip.async_wire("bf16") == "bf16"


def test_wire_defaults_to_optimizer_compression():
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = "int4_ef"
    step = bf.make_async_train_step(opt, quad_loss)
    assert step.engine.wire == "int4"
    assert step.engine.wire_name == "int4_ef"


# -- the bounded-staleness gate -----------------------------------------------


def test_drop_gate_files_advisory_naming_slow_rank():
    """A 10x compute-dilated rank (the new ``slow`` fault) trips the
    gate: its out-edges' buffer ages pass the bound, the fold drops
    them (mass stays pending), and the ``async_staleness`` advisory
    names the slow rank."""
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    z0 = problem(4)
    session = bf.elastic.start(policy="push_sum")
    session.inject("slow", rank=2, step=0, factor=10)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    step = bf.make_async_train_step(
        opt, quad_loss, max_age=4, policy="drop"
    )
    eng = step.engine
    batch = jnp.asarray(z0)
    for _ in range(12):
        params, state, _ = step(params, state, batch)
    assert eng._stale_drops > 0
    assert eng.advisories, "gate never filed an advisory"
    adv = eng.advisories[0]
    assert adv.kind == "async_staleness"
    assert 2 in adv.detail["slow_ranks"]
    assert adv.detail["surface"] == "async"
    assert adv.detail["action"] == "dropped_from_fold"
    assert all(s == 2 for s, _d in map(tuple, adv.detail["edges"]))
    snap = metrics.snapshot()
    assert snap["bluefog.doctor.advisory.async_staleness"]["value"] >= 1
    assert snap["bluefog.async.stale_drops"]["value"] == eng._stale_drops
    # mass conservation survives the drops: pending mass is buffered,
    # never discarded
    win = win_mod._get_win(bf.get_context(), eng._name)
    total = float(np.sum(np.asarray(win.value), dtype=np.float64)) \
        + float(np.sum(np.asarray(win.buffers), dtype=np.float64))
    assert abs(total - float(np.sum(z0, dtype=np.float64))) < 1e-4


def test_throttle_gate_skips_receivers():
    """policy='throttle': ranks whose in-edges fell behind skip their
    own local step instead of dropping the edge."""
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    z0 = problem(5)
    session = bf.elastic.start(policy="push_sum")
    session.inject("slow", rank=3, step=0, factor=8)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    step = bf.make_async_train_step(
        opt, quad_loss, max_age=3, policy="throttle"
    )
    batch = jnp.asarray(z0)
    for _ in range(14):
        params, state, _ = step(params, state, batch)
    eng = step.engine
    assert eng._throttled > 0
    assert eng._stale_drops == 0
    assert metrics.snapshot()["bluefog.async.throttled"]["value"] \
        == eng._throttled
    assert eng.advisories and eng.advisories[0].detail["action"] \
        == "throttled_receivers"


def test_slow_fault_dilates_cadence():
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    z0 = problem(6)
    session = bf.elastic.start(policy="push_sum")
    session.inject("slow", rank=1, step=0, factor=4)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    step = bf.make_async_train_step(opt, quad_loss, max_age=100)
    batch = jnp.asarray(z0)
    for _ in range(8):
        params, state, _ = step(params, state, batch)
    # rank 1 participated only on ticks 0 and 4: 8 ticks x 8 ranks
    # minus 6 skipped = 58 local steps
    assert step.engine._local_steps == 8 * SIZE - 6


def test_straggler_costs_only_its_own_share():
    """A 10x compute-dilated rank (the ``slow`` chaos fault) gates every
    synchronous step, so the synchronous fleet runs at 1/10 of nominal;
    the async engine's own counters show the fleet within ~1/N of
    nominal: the slow rank skips only its own local steps."""
    dilation, slow_rank = 10, SIZE - 2
    z0 = problem(8)
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    session = bf.elastic.start(policy="push_sum")
    session.inject("slow", rank=slow_rank, step=0, factor=dilation)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.05))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    step = bf.make_async_train_step(opt, quad_loss, max_age=4)
    batch = jnp.asarray(z0 + 1.0)
    eng = step.engine
    while eng._tick < 40:
        params, state, _ = step(params, state, batch)
        jax.block_until_ready(params)
    participation = eng._local_steps / (eng._tick * SIZE)
    assert participation >= 1.0 - 1.5 / SIZE, participation
    # exactly the slow rank's skipped ticks are missing
    ran = -(-eng._tick // dilation)
    assert eng._local_steps == eng._tick * (SIZE - 1) + ran
    assert participation > 1.0 / dilation
    assert np.isfinite(np.asarray(params["w"])).all()


@pytest.mark.parametrize("wire", ["fp32", "int8_ef", "int4_ef"])
def test_push_sum_mass_conserved_per_wire_tier(wire):
    """Random per-rank cadences at lr=0: the total x mass (window plus
    pending buffers) and the p mass stay at their initial values to f32
    rounding on every wire tier — the sender absorbs the quantization
    residual of the mass it ships."""
    dim = 512
    z0 = problem(5, dim)
    rng = np.random.RandomState(5)
    cadence = {r: int(p) for r, p in enumerate(rng.randint(1, 5, SIZE))}
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    step = bf.make_async_train_step(
        opt, quad_loss, cadence=cadence, wire=wire, max_age=10 ** 6
    )
    assert step.engine.wire == async_gossip.async_wire(wire)
    batch = jnp.asarray(z0)
    mass0 = float(np.sum(z0, dtype=np.float64))
    bound = 1e-5 * float(np.abs(z0).sum())
    for _ in range(15):
        params, state, _ = step(params, state, batch)
        win = win_mod._get_win(bf.get_context(), step.engine._name)
        total = float(np.sum(np.asarray(win.value), dtype=np.float64)) \
            + float(np.sum(np.asarray(win.buffers), dtype=np.float64))
        ptotal = float(np.sum(np.asarray(win.p), dtype=np.float64)) \
            + float(np.sum(np.asarray(win.p_buffers), dtype=np.float64))
        assert abs(total - mass0) < bound, (total, mass0)
        assert abs(ptotal - SIZE) < 1e-5, ptotal


# -- elastic repair / re-window -----------------------------------------------


def test_kill_repairs_and_rewindows_preserving_estimate():
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    z0 = problem(7)
    session = bf.elastic.start(policy="push_sum")
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    step = bf.make_async_train_step(opt, quad_loss)
    eng = step.engine
    batch = jnp.asarray(z0)
    for _ in range(6):
        params, state, _ = step(params, state, batch)
    before = np.asarray(params["w"]).copy()
    session.inject("kill", rank=5, step=session.step)
    params, state, _ = step(params, state, batch)
    assert len(session.repairs) == 1
    assert session.stale_dispatches == 0
    assert eng._rewindows == 1
    # the re-window preserved the estimate: survivors' post-repair
    # estimates stay in the convex hull the pre-kill estimates spanned
    after = np.asarray(params["w"])
    live = [r for r in range(SIZE) if r != 5]
    assert np.all(after[live].max(0) <= before.max(0) + 1e-4)
    assert np.all(after[live].min(0) >= before.min(0) - 1e-4)
    # and the lane keeps running on the repaired topology
    for _ in range(4):
        params, state, _ = step(params, state, batch)
    assert session.stale_dispatches == 0
    assert metrics.snapshot()["bluefog.async.rewindows"]["value"] == 1


# -- watchdog: a hung fold files SUSPECT verdicts -----------------------------


def test_hung_async_fold_files_suspects(monkeypatch):
    """The tick dispatch is a registered watchdog blocking point: a
    wait outliving the liveness deadline files SUSPECT verdicts
    through the existing add_stall_handler -> elastic recovery hook."""
    from bluefog_tpu import optimizers as opt_mod

    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    z0 = problem(8)
    session = bf.elastic.start(
        policy="push_sum", liveness_timeout_s=0.2
    )
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    params = {"w": jnp.asarray(z0)}
    state = opt.init(params)
    step = bf.make_async_train_step(opt, quad_loss)
    batch = jnp.asarray(z0)
    params, state, _ = step(params, state, batch)  # warm compile

    orig = opt_mod._timed_dispatch

    def hung_dispatch(name, fn, *args):
        if name == "async_tick":
            time.sleep(0.9)  # monitor polls every ~50 ms at this limit
        return orig(name, fn, *args)

    monkeypatch.setattr(opt_mod, "_timed_dispatch", hung_dispatch)
    old = watchdog.stall_timeout()
    watchdog.set_stall_timeout(0.2)
    try:
        params, state, _ = step(params, state, batch)
    finally:
        watchdog.set_stall_timeout(old)
    suspects = [
        r for r in range(SIZE)
        if session.membership.state(r) is RankState.SUSPECT
    ]
    assert suspects, "hung async fold filed no SUSPECT verdicts"
    assert metrics.snapshot()["bluefog.elastic.suspects"]["value"] \
        == len(suspects)


# -- observability integrations -----------------------------------------------


def test_staleness_observatory_samples_async_surface():
    obs = staleness_mod.start(interval=1)
    try:
        z0, params, state, step = build(lr=0.0, cadence={0: 4})
        batch = jnp.asarray(z0)
        for _ in range(6):
            params, state, _ = step(params, state, batch)
        surfaces = {s.get("surface") for s in obs.samples}
        assert "async" in surfaces
        async_samples = [
            s for s in obs.samples if s.get("surface") == "async"
        ]
        # the slow-cadence rank's out-edge age is visible to the tier
        assert any(s["age_max"] >= 2 for s in async_samples)
        # the fleet-facing scalar reflects the latest window sample
        assert obs.last_age_max() >= 1
    finally:
        staleness_mod.stop()


def test_health_report_carries_async_block():
    from bluefog_tpu import health as health_mod

    plane = health_mod.start()
    try:
        z0, params, state, step = build(lr=0.0)
        batch = jnp.asarray(z0)
        for _ in range(3):
            params, state, _ = step(params, state, batch)
        rep = plane.report()
        assert "async" in rep
        assert rep["async"]["ticks"] == 3
        assert rep["async"]["policy"] in ("drop", "throttle")
    finally:
        health_mod.stop()


def test_active_engine_registry_and_shutdown():
    z0, params, state, step = build(lr=0.0)
    assert async_gossip.active() is step.engine
    bf.shutdown()
    assert async_gossip.active() is None
    bf.init()  # fixture teardown shuts down again harmlessly


def test_autotune_decision_records_carry_async_mode():
    from bluefog_tpu.autotune import _async_mode

    assert _async_mode() is False
    z0, params, state, step = build(lr=0.0)
    assert _async_mode() is True


def test_tick_program_is_cached_across_participation_patterns():
    """Masks/weights ride as operands: a cadence pattern change must
    never recompile the tick program."""
    z0, params, state, step = build(lr=0.1, cadence={0: 2, 3: 3})
    batch = jnp.asarray(z0)
    params, state, _ = step(params, state, batch)
    compiles = metrics.snapshot()["bluefog.recompiles"]["value"]
    for _ in range(7):  # walks many distinct participation patterns
        params, state, _ = step(params, state, batch)
    assert metrics.snapshot()["bluefog.recompiles"]["value"] == compiles
