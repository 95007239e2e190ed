# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Elastic gossip: fault injection, liveness, repair, recovery.

The chaos suite runs entirely on the 8-device virtual CPU mesh — every
failure mode is a deterministic replay (:mod:`bluefog_tpu.elastic.faults`),
so rank death is a tier-1 unit test, not a multi-host fire drill.

Oracle notes. The fp32 end-to-end tests pin the device trajectory
BITWISE against a numpy replay: the combine accumulates left-to-right in
round order (verified), and the only backend latitude observed is whether
the SGD apply ``p + (-lr)*g`` is contracted to a single-rounding FMA —
both are legal IEEE evaluations, so the oracle computes both (FMA
emulated exactly via float64) and asserts the device matches one of them
for the WHOLE trajectory. The int8 wire's accumulation is vectorized
with mixed FMA lanes (no single associativity reproduces it), so the
int8 tests pin the quantization math bitwise at the payload level and
the trajectory/consensus to a few-ulp tolerance instead.
"""

import numpy as np
import networkx as nx
import pytest

import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import metrics
from bluefog_tpu import watchdog
from bluefog_tpu import windows as win_mod
from bluefog_tpu.collective import ops as col_ops
from bluefog_tpu.collective.plan import (
    plan_from_topology,
    schedule_from_dynamic,
)
from bluefog_tpu.elastic import (
    Fault,
    FaultPlan,
    Membership,
    RankState,
    parse_fault_plan,
    repair_schedule,
    repaired_matrix,
    survivor_consensus,
)
from bluefog_tpu.elastic import repair as repair_mod
from bluefog_tpu.elastic.recovery import consensus_restore

SIZE = 8


@pytest.fixture(autouse=True)
def _fresh_state():
    metrics.reset()
    yield
    bf.elastic.stop()
    metrics.reset()


def _init(n=SIZE):
    import jax

    bf.init(devices=jax.devices("cpu")[:n])


def _settle(*trees):
    """Wait for a step before dispatching the next. With dozens of
    unsettled collective programs queued on XLA:CPU's virtual devices
    (a busy machine, the suite's compile cache off), one device's share
    of a collective-permute can fail to reach its rendezvous, and the
    runtime aborts the whole process at its 40 s termination timeout.
    A loop that settles each step keeps one program in flight."""
    import jax

    jax.block_until_ready(trees)


# -- fault-plan grammar -------------------------------------------------------


def test_fault_plan_grammar_roundtrip():
    plan = parse_fault_plan(
        "kill:rank=3,step=5; stall:rank=2,step=10,seconds=120 ;"
        "degrade:rank=1,step=4,factor=0.25;"
    )
    assert [f.kind for f in plan.faults] == ["degrade", "kill", "stall"]
    kill = plan.due(5)[0]
    assert (kill.rank, kill.step) == (3, 5)
    stall = plan.due(10)[0]
    assert stall.seconds == 120.0
    deg = plan.due(4)[0]
    assert deg.factor == 0.25
    assert parse_fault_plan("") .faults == ()
    assert parse_fault_plan(None).faults == ()


@pytest.mark.parametrize("bad", [
    "explode:rank=1,step=2",        # unknown kind
    "kill:rank=1",                  # missing step
    "kill:step=1",                  # missing rank
    "kill:rank=1,step=2,blast=3",   # unknown field
    "kill:rank=1 step=2",           # not key=value
    "degrade:rank=1,step=2,factor=0",   # factor out of range
    "degrade:rank=1,step=2,factor=1.5",
    "stall:rank=1,step=2,seconds=-1",
    "kill:rank=1,step=-3",
])
def test_fault_plan_grammar_rejects(bad):
    with pytest.raises(ValueError):
        parse_fault_plan(bad)


def test_slow_fault_grammar_roundtrip():
    """The rank-scoped compute-dilation kind: factor >= 1, optional
    steps= duration, no peer/seconds form."""
    plan = parse_fault_plan(
        "slow:rank=5,step=0,factor=10; slow:rank=2,step=4,factor=3,steps=6"
    )
    assert [f.kind for f in plan.faults] == ["slow", "slow"]
    f = plan.due(0)[0]
    assert (f.rank, f.factor, f.hold_steps) == (5, 10.0, 0)
    bounded = plan.due(4)[0]
    assert (bounded.rank, bounded.factor, bounded.hold_steps) == (2, 3.0, 6)
    plan.validate(SIZE)
    # factor defaults to 1.0 — a no-op dilation is legal
    parse_fault_plan("slow:rank=1,step=0")
    with pytest.raises(ValueError, match="9"):
        plan2 = parse_fault_plan("slow:rank=9,step=0,factor=2")
        plan2.validate(SIZE)


@pytest.mark.parametrize("bad", [
    "slow:rank=1,step=0,factor=0.5",       # a slowdown must dilate
    "slow:rank=1,step=0,factor=2,peer=3",  # rank-scoped by definition
    "slow:rank=1,step=0,factor=2,seconds=5",
])
def test_slow_fault_grammar_rejects(bad):
    with pytest.raises(ValueError):
        parse_fault_plan(bad)


def test_simulated_compute_dilation_window():
    """inject() parity + the step-clock activation window: a slow
    fault dilates from its step, expires after steps=, and never
    triggers repair or a death verdict."""
    _init()
    session = bf.elastic.start()
    session.inject("slow", rank=3, step=2, factor=10)
    session.inject("slow", rank=1, step=4, factor=4, steps=3)
    dilations = []
    for step in range(10):
        # the dilation map a dispatch at `step` would see
        dilations.append(dict(session.simulated_compute_dilation()))
        session.before_dispatch(None)  # replay faults, advance clock
    assert dilations[0] == {} and dilations[1] == {}
    assert dilations[2] == {3: 10.0}
    assert dilations[4] == {3: 10.0, 1: 4.0}
    assert dilations[6] == {3: 10.0, 1: 4.0}  # last active step for 1
    assert dilations[7] == {3: 10.0}          # steps=3 expired
    assert session.repairs == []              # never a repair trigger
    assert session.membership.live_ranks() == tuple(range(SIZE))
    assert metrics.snapshot()["bluefog.elastic.slow_faults"]["value"] == 2


def test_fault_plan_env_and_validate(monkeypatch):
    monkeypatch.setenv("BLUEFOG_FAULT_PLAN", "kill:rank=9,step=0")
    plan = FaultPlan.from_env()
    assert len(plan) == 1
    with pytest.raises(ValueError):
        plan.validate(world_size=8)
    plan.validate(world_size=16)


# -- membership ---------------------------------------------------------------


def test_membership_transitions_and_epoch():
    m = Membership(4)
    assert m.live_ranks() == (0, 1, 2, 3)
    e0 = m.epoch
    assert m.mark_suspect(2, "deadline", step=7)
    assert m.state(2) is RankState.SUSPECT
    assert m.is_live(2)  # suspicion does not leave the wire
    assert m.mark_dead(2, "killed", step=8)
    assert not m.mark_dead(2)  # idempotent
    assert m.live_ranks() == (0, 1, 3)
    assert m.dead_ranks() == (2,)
    assert not m.mark_suspect(2)  # dead stays dead
    assert m.revive(2, step=20)
    assert m.live_ranks() == (0, 1, 2, 3)
    assert m.epoch > e0
    # token changes with every transition (cache-key requirement)
    t0 = m.token()
    m.mark_dead(0)
    assert m.token() != t0
    with pytest.raises(ValueError):
        m.mark_dead(17)
    with pytest.raises(ValueError):
        m.mark_degraded(1, 0.0)
    assert m.mark_degraded(1, 0.5)
    assert m.degraded() == {1: 0.5}


# -- repair weight correctness (numpy oracles) --------------------------------

GENERATORS = {
    "ring": lambda n: bf.topology.RingGraph(n),
    "exp2": lambda n: bf.topology.ExponentialTwoGraph(n),
    "mesh": lambda n: bf.topology.MeshGrid2DGraph(n),
    "star": lambda n: bf.topology.StarGraph(n),
}


@pytest.mark.parametrize("name", sorted(GENERATORS))
def test_repair_stays_stochastic_for_every_single_rank_loss(name):
    """Every static generator, every single dead rank, every policy: the
    repaired matrix keeps the stochasticity its family needs (receiver
    sums = row-stochastic in the standard x' = W^T x convention)."""
    w = nx.to_numpy_array(GENERATORS[name](SIZE))
    for dead in range(SIZE):
        live = [r for r in range(SIZE) if r != dead]
        for policy in repair_mod.POLICIES:
            w2 = repaired_matrix(w, live, policy=policy)
            # dead slot frozen: self weight 1, no edges either direction
            assert w2[dead, dead] == 1.0
            assert np.count_nonzero(w2[dead]) == 1
            assert np.count_nonzero(w2[:, dead]) == 1
            if policy in ("average", "receiver"):
                np.testing.assert_allclose(
                    repair_mod.receiver_sums(w2, live), 1.0, atol=1e-12,
                    err_msg=f"{name} dead={dead} {policy}",
                )
            if policy in ("average", "push_sum"):
                np.testing.assert_allclose(
                    repair_mod.sender_sums(w2, live), 1.0, atol=1e-12,
                    err_msg=f"{name} dead={dead} {policy}",
                )
            if policy == "average":
                np.testing.assert_allclose(w2, w2.T, atol=1e-12)


@pytest.mark.parametrize("name", sorted(GENERATORS))
def test_average_repair_fixed_point_is_survivor_mean(name):
    """The doubly-stochastic repair's gossip iteration converges to the
    uniform survivor average — the consensus-preservation oracle."""
    w = nx.to_numpy_array(GENERATORS[name](SIZE))
    rng = np.random.RandomState(0)
    x = rng.randn(SIZE, 3)
    for dead in (0, 3, SIZE - 1):
        live = [r for r in range(SIZE) if r != dead]
        w2 = repaired_matrix(w, live, policy="average")
        y = x.copy()
        for _ in range(300):
            y = w2.T @ y
        target = survivor_consensus(x, live)
        for r in live:
            np.testing.assert_allclose(y[r], target, atol=1e-9)
        # the dead slot never mixes
        np.testing.assert_allclose(y[dead], x[dead])


def test_star_center_death_falls_back_to_connected_graph():
    """Killing the star's center disconnects every survivor; the repair
    engine unions in the survivor ring so gossip still mixes."""
    w = nx.to_numpy_array(bf.topology.StarGraph(SIZE, center_rank=0))
    live = list(range(1, SIZE))
    w2 = repaired_matrix(w, live, policy="average")
    g = nx.from_numpy_array(w2[np.ix_(live, live)])
    assert nx.is_connected(g)
    np.testing.assert_allclose(repair_mod.receiver_sums(w2, live), 1.0)
    np.testing.assert_allclose(repair_mod.sender_sums(w2, live), 1.0)


def test_degrade_scales_edges_and_keeps_stochasticity():
    w = nx.to_numpy_array(bf.topology.RingGraph(SIZE))
    live = list(range(SIZE))
    healthy = repaired_matrix(w, live, policy="average")
    degraded = repaired_matrix(
        w, live, policy="average", degraded={2: 0.25}
    )
    # the slow rank's cross edges shrank by exactly the factor
    for j in (1, 3):  # ring neighbors of 2
        assert degraded[2, j] == pytest.approx(healthy[2, j] * 0.25)
        assert degraded[j, 2] == pytest.approx(healthy[j, 2] * 0.25)
    np.testing.assert_allclose(repair_mod.receiver_sums(degraded, live), 1.0)
    np.testing.assert_allclose(repair_mod.sender_sums(degraded, live), 1.0)
    np.testing.assert_allclose(degraded, degraded.T)


def test_repair_rejects_bad_inputs():
    w = nx.to_numpy_array(bf.topology.RingGraph(4))
    with pytest.raises(ValueError):
        repaired_matrix(w, [], policy="average")
    with pytest.raises(ValueError):
        repaired_matrix(w, [0, 9], policy="average")
    with pytest.raises(ValueError):
        repaired_matrix(w, [0, 1], policy="nonsense")
    # lone survivor: identity on its slot
    w2 = repaired_matrix(w, [2], policy="average")
    assert w2[2, 2] == 1.0


# -- dynamic one-peer schedules skip dead peers -------------------------------


def test_dynamic_schedule_repair_preserves_period_and_skips_dead():
    topo = bf.topology.ExponentialTwoGraph(SIZE)
    sched = schedule_from_dynamic(
        SIZE,
        lambda r: bf.topology.GetDynamicOnePeerSendRecvRanks(topo, r),
    )
    assert sched.period == 3  # log2(8) one-peer rounds
    dead = 5
    live = [r for r in range(SIZE) if r != dead]
    rep = repair_schedule(sched, live, policy="receiver")
    # the period is preserved — skipping a dead peer must not break the
    # period detection the compiled lax.switch relies on
    assert rep.period == sched.period
    for p in rep.plans:
        edges = [(s, d) for rnd in p.rounds for (s, d) in rnd.perm]
        assert all(dead not in e for e in edges), edges
        np.testing.assert_allclose(
            repair_mod.receiver_sums(p.weight_matrix(), live), 1.0,
            atol=1e-12,
        )
    # ranks whose peer-of-the-round died now gossip with themselves that
    # round (weight 1 on self), other rounds unchanged in structure
    for p_old, p_new in zip(sched.plans, rep.plans):
        old_edges = {
            (s, d)
            for rnd in p_old.rounds for (s, d) in rnd.perm
            if dead not in (s, d)
        }
        new_edges = {
            (s, d) for rnd in p_new.rounds for (s, d) in rnd.perm
        }
        assert new_edges == old_edges


# -- live-set-aware plan cache ------------------------------------------------


def test_static_plan_cache_key_includes_live_set():
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    ctx = bf.get_context()
    assert ctx.live_token() is None  # no session: everyone lives
    p1 = col_ops._static_plan(ctx)
    assert col_ops._static_plan(ctx) is p1  # cached
    session = bf.elastic.start()
    tok = ctx.live_token()
    assert tok == (0, tuple(range(SIZE)))
    p2 = col_ops._static_plan(ctx)
    assert p2 is not p1  # token changed None -> epoch 0
    # a membership transition ALONE (no set_topology) must invalidate
    session.membership.mark_dead(3, "test")
    assert ctx.live_token() != tok
    p3 = col_ops._static_plan(ctx)
    assert p3 is not p2
    bf.elastic.stop()


# -- session mechanics --------------------------------------------------------


def test_session_exclusive_and_inject_validation():
    _init()
    session = bf.elastic.start()
    with pytest.raises(RuntimeError):
        bf.elastic.start()
    with pytest.raises(ValueError):
        session.inject("kill", rank=99, step=0)
    with pytest.raises(ValueError):
        bf.elastic.inject("explode", rank=0, step=0)
    bf.elastic.stop()
    bf.elastic.stop()  # idempotent
    with pytest.raises(RuntimeError):
        bf.elastic.inject("kill", rank=0, step=0)
    with pytest.raises(RuntimeError):
        bf.elastic.guard(object())


def test_transient_stall_does_not_repair_but_deadline_stall_does():
    _init()
    bf.set_topology(bf.topology.RingGraph(SIZE))
    session = bf.elastic.start(liveness_timeout_s=60.0)
    session.inject("stall", rank=1, step=0, seconds=5)  # transient
    session.inject("stall", rank=2, step=2, seconds=60)  # past deadline
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    session.before_dispatch(opt)
    assert session.repairs == [] and session.membership.dead_ranks() == ()
    assert metrics.snapshot()["bluefog.elastic.stalls"]["value"] == 1
    session.before_dispatch(opt)
    session.before_dispatch(opt)  # step 2: condemned + repaired
    assert session.membership.dead_ranks() == (2,)
    assert len(session.repairs) == 1
    reason = session.membership.reason(2)[0]
    assert "stalled" in reason and "deadline" in reason


def test_watchdog_stall_files_suspects():
    """A real blocking wait past the liveness deadline files SUSPECT
    verdicts for the ranks of the last dispatched plan — the
    watchdog-integrated detection path."""
    import time

    _init()
    session = bf.elastic.start(liveness_timeout_s=0.3)
    old = watchdog.stall_timeout()
    watchdog.set_stall_timeout(0.3)
    try:
        with watchdog.watch("combine dispatch (test)"):
            time.sleep(1.2)  # monitor polls every ~75 ms at this limit
    finally:
        watchdog.set_stall_timeout(old)
    assert all(
        session.membership.state(r) is RankState.SUSPECT
        for r in range(SIZE)
    )
    assert metrics.snapshot()["bluefog.elastic.suspects"]["value"] == SIZE
    # suspicion never removes a rank from the wire by itself
    assert session.membership.live_ranks() == tuple(range(SIZE))


# -- end-to-end chaos: kill mid-training, bitwise fp32 oracle -----------------


def _np_combine(v, plan):
    """Numpy replay of weighted_combine_operands: left-to-right in round
    order (bitwise on the CPU backend, verified)."""
    self_w, recv_w = plan.weight_operands()
    y = v * self_w[:, None]
    for r, rnd in enumerate(plan.rounds):
        recv = np.zeros_like(v)
        for s, d in rnd.perm:
            recv[d] = v[s]
        y = y + recv * recv_w[r][:, None]
    return y


def _np_fma(a, b, c):
    """Exact float32 FMA via float64 (f32 products are exact in f64)."""
    return np.float32(np.float64(a) * np.float64(b) + np.float64(c))


def _np_sgd_apply(p, g, lr, fma):
    return _np_fma(g, -lr, p) if fma else p + (-lr) * g


def _chaos_run(order, kill_rank=3, kill_step=5, steps=24, lr=0.05,
               compression=None):
    """Run the 8-worker chaos scenario on device; return everything the
    oracles need."""
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    ctx = bf.get_context()
    base_plan = col_ops._static_plan(ctx)

    session = bf.elastic.start(policy="average")
    session.inject("kill", rank=kill_rank, step=kill_step)
    factory = (
        bf.DistributedAdaptThenCombineOptimizer if order == "atc"
        else bf.DistributedAdaptWithCombineOptimizer
    )
    opt = factory(optax.sgd(lr))
    if compression:
        opt.compression = compression
    guard = bf.elastic.guard(opt)

    rng = np.random.RandomState(42)
    x0 = rng.randn(SIZE, 1536).astype(np.float32)
    grads = [
        rng.randn(SIZE, 1536).astype(np.float32) for _ in range(steps)
    ]
    params = {"w": bf.worker_values(lambda r: x0[r])}
    state = opt.init(params)
    trajectory = []
    for t in range(steps):
        params, state = guard.step(
            params, state, {"w": bf.worker_values(lambda r: grads[t][r])}
        )
        trajectory.append(np.asarray(params["w"]))

    live = session.membership.live_ranks()
    repaired_plan = col_ops._static_plan(ctx)
    assert repaired_plan is not base_plan
    result = dict(
        session=session, x0=x0, grads=grads, trajectory=trajectory,
        live=live, base_plan=base_plan, repaired_plan=repaired_plan,
        lr=np.float32(lr), kill_step=kill_step, kill_rank=kill_rank,
    )
    return result


def _np_replay(run, order, fma):
    """Full-trajectory numpy replay, switching plans at the repair step
    exactly where the guard did."""
    x = run["x0"].copy()
    out = []
    for t, g in enumerate(run["grads"]):
        plan = (
            run["base_plan"] if t < run["kill_step"]
            else run["repaired_plan"]
        )
        if order == "atc":
            x = _np_combine(_np_sgd_apply(x, g, run["lr"], fma), plan)
        else:  # cta
            x = _np_sgd_apply(_np_combine(x, plan), g, run["lr"], fma)
        out.append(x.copy())
    return out


@pytest.mark.chaos
@pytest.mark.parametrize("order", ["atc", "cta"])
def test_chaos_kill_fp32_bitwise_survivor_oracle(order):
    """8-worker mesh, rank killed mid-training: detected at its first
    would-be dispatch, repaired before it. Oracle pins, strongest that
    each phase admits:

    - pre-repair trajectory BITWISE vs the numpy replay (the 3-round
      Exp2 combine is a serial chain XLA evaluates left-to-right; the
      SGD apply's legal FMA contraction is calibrated, both variants
      computed);
    - the whole run — kill, detection, repair — BITWISE reproducible
      across two independent sessions (fresh context, fresh compiles):
      the determinism contract the chaos harness exists for;
    - post-repair trajectory within a few-ulp envelope of the replay
      (the repaired 5-round combine is reassociated by XLA's
      vectorizer, so per-element order is not replayable), and the
      survivors' consensus matches the numpy survivor oracle."""
    run = _chaos_run(order)
    session = run["session"]
    assert [r.detected for r in session.repairs] == [(run["kill_rank"],)]
    rec = session.repairs[0]
    assert rec.step == run["kill_step"]
    assert rec.steps_to_detect == {run["kill_rank"]: 0}
    assert rec.steps_to_repair == 0
    assert session.stale_dispatches == 0
    assert run["live"] == tuple(
        r for r in range(SIZE) if r != run["kill_rank"]
    )

    # 1. pre-repair phase: bitwise vs numpy (FMA-calibrated apply)
    matched = None
    for fma in (True, False):
        oracle = _np_replay(run, order, fma)
        if all(
            np.array_equal(d, o)
            for d, o in zip(
                run["trajectory"][: run["kill_step"]],
                oracle[: run["kill_step"]],
            )
        ):
            matched = fma
            break
    assert matched is not None, (
        "pre-repair device trajectory matches neither FMA nor "
        "plain-apply numpy oracle bitwise"
    )

    # 2. full-run trajectory stays in a tight envelope of the oracle
    # (reassociation of the repaired combine costs ~1 ulp per step and
    # gossip is non-expanding, so the envelope stays ulp-scale)
    oracle = _np_replay(run, order, matched)
    for t, (d, o) in enumerate(zip(run["trajectory"], oracle)):
        np.testing.assert_allclose(
            d, o, atol=1e-5, rtol=0,
            err_msg=f"step {t} left the oracle envelope",
        )

    # 3. survivor consensus: mean matches the oracle's survivor mean
    final = run["trajectory"][-1]
    live = list(run["live"])
    np.testing.assert_allclose(
        final[live].mean(axis=0),
        survivor_consensus(oracle[-1], live),
        atol=1e-5,
    )
    # the dead slot froze out of the mixing at the repair: from there it
    # only took local sgd steps (its combine is self-weight 1 plus
    # zero-weighted rounds), which ARE bitwise-replayable
    dead = run["kill_rank"]
    x = run["trajectory"][run["kill_step"] - 1][dead]
    for t in range(run["kill_step"], len(run["grads"])):
        x = _np_sgd_apply(x, run["grads"][t][dead], run["lr"], matched)
    np.testing.assert_array_equal(final[dead], x)

    # metrics wiring (before the rerun adds its own repair)
    snap = metrics.snapshot()
    assert snap["bluefog.elastic.repairs"]["value"] == 1
    assert snap["bluefog.elastic.dead_ranks"]["value"] == 1

    # 4. the whole chaos run is bitwise reproducible end to end
    rerun = _chaos_run(order)
    assert all(
        np.array_equal(a, b)
        for a, b in zip(run["trajectory"], rerun["trajectory"])
    ), "chaos replay is not deterministic"
    assert rerun["session"].repairs[0].detected == rec.detected


@pytest.mark.chaos
@pytest.mark.parametrize("order", ["atc", "cta"])
def test_chaos_kill_int8_converges_to_survivor_consensus(order):
    """Same scenario over the int8 difference-form wire. The int8
    accumulation is vectorized with mixed FMA lanes (no single numpy
    associativity is bitwise — see module docstring), so this pins the
    trajectory to a few-ulp envelope of the fp32 oracle plus the
    convergence contract: after the gradient phase ends the survivors
    contract to a consensus within quantization noise of the survivor
    average."""
    kill_step, grad_steps, steps = 5, 10, 80
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start(policy="average")
    session.inject("kill", rank=3, step=kill_step)
    factory = (
        bf.DistributedAdaptThenCombineOptimizer if order == "atc"
        else bf.DistributedAdaptWithCombineOptimizer
    )
    opt = factory(optax.sgd(0.05))
    opt.compression = "int8"
    guard = bf.elastic.guard(opt)

    rng = np.random.RandomState(7)
    x0 = rng.randn(SIZE, 1536).astype(np.float32)
    zeros = np.zeros((SIZE, 1536), np.float32)
    params = {"w": bf.worker_values(lambda r: x0[r])}
    state = opt.init(params)
    at_repair = None
    for t in range(steps):
        g = (
            rng.randn(SIZE, 1536).astype(np.float32) * 0.1
            if t < grad_steps else zeros
        )
        if t == kill_step:
            at_repair = np.asarray(params["w"])
        params, state = guard.step(
            params, state, {"w": bf.worker_values(lambda r: g[r])}
        )
        _settle(params, state)

    assert session.stale_dispatches == 0
    assert len(session.repairs) == 1
    live = list(session.membership.live_ranks())
    assert 3 not in live

    final = np.asarray(params["w"])
    at_repair_spread = np.abs(
        at_repair[live] - at_repair[live].mean(axis=0)
    ).max()
    spread = np.abs(final[live] - final[live].mean(axis=0)).max()
    # plain int8 (no error feedback) has a quantization noise floor: the
    # wire payload is the raw iterate, so chunk scales stay ~max|x|/127
    # ≈ 0.03 and the spread stalls there instead of contracting to zero
    # (inner.py's CHOCO docstring). Pin: hard contraction from the
    # at-repair spread down to the floor.
    assert spread < 0.05, spread
    assert spread < at_repair_spread / 5, (spread, at_repair_spread)
    # consensus value: the survivor mean is invariant under the doubly
    # stochastic combine (symmetric weights make the difference-form
    # cross terms cancel in the mean), so the target is the survivor
    # mean at repair plus the post-repair gradient drift — replay that
    # one-line recursion exactly
    mean = survivor_consensus(at_repair, live)
    rng2 = np.random.RandomState(7)
    _ = rng2.randn(SIZE, 1536)  # x0 draw
    g_seq = [
        rng2.randn(SIZE, 1536).astype(np.float32) * 0.1
        for _ in range(grad_steps)
    ]
    for t in range(kill_step, grad_steps):
        mean = mean - 0.05 * g_seq[t][live].mean(axis=0)
    np.testing.assert_allclose(
        final[live].mean(axis=0), mean, atol=2e-2
    )
    snap = metrics.snapshot()
    assert snap["bluefog.elastic.repairs"]["value"] == 1


# -- push-sum mass correction -------------------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("order", ["atc"])
def test_chaos_kill_chunked_plan_repairs_zero_stale(order, monkeypatch):
    """Elastic repair of a CHUNKED plan: with BLUEFOG_PLAN_CHUNKS set,
    the kill -> detect -> repair path recompiles the chunked lowering
    under the live-token cache key with zero stale dispatches, and the
    whole trajectory (through the repair) is bitwise the unchunked
    run's — chunking is a schedule change even across a membership
    change."""
    def run(chunks):
        monkeypatch.setenv("BLUEFOG_PLAN_CHUNKS", str(chunks))
        try:
            _init()
            bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
            session = bf.elastic.start(policy="average")
            session.inject("kill", rank=3, step=4)
            opt = bf.DistributedAdaptThenCombineOptimizer(
                optax.sgd(0.05)
            )
            guard = bf.elastic.guard(opt)
            rng = np.random.RandomState(7)
            x0 = rng.randn(SIZE, 1536).astype(np.float32)
            params = {"w": bf.worker_values(lambda r: x0[r])}
            state = opt.init(params)
            traj = []
            for t in range(10):
                g = rng.randn(SIZE, 1536).astype(np.float32) * 0.1
                params, state = guard.step(
                    params, state, {"w": bf.worker_values(lambda r: g[r])}
                )
                traj.append(np.asarray(params["w"]).copy())
            assert session.stale_dispatches == 0
            assert len(session.repairs) == 1
            assert 3 not in session.membership.live_ranks()
            # the repaired static plan sits under a live-token key
            ctx = bf.context.get_context()
            live_keyed = [
                k for k in ctx.op_cache
                if k and k[0] == "static_plan" and k[-1] is not None
            ]
            assert live_keyed, "repaired plan not keyed by live token"
            return traj
        finally:
            bf.elastic.stop()
            bf.shutdown()

    t2 = run(2)
    t1 = run(1)
    for a, b in zip(t1, t2):
        np.testing.assert_array_equal(a, b)


@pytest.mark.chaos
def test_chaos_pushsum_mass_corrected_consensus():
    """Push-sum family: kill a rank mid-run; the repaired split is
    mass-conserving over survivors, so x-lane and p-lane totals are
    invariant from the repair on, and every survivor's corrected iterate
    x/p converges to sum(x_live)/sum(p_live) at repair — the push-sum
    mass-corrected survivor consensus."""
    kill_step, steps = 4, 60
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start()
    session.inject("kill", rank=2, step=kill_step)
    opt = bf.DistributedPushSumOptimizer(optax.sgd(0.0))
    guard = bf.elastic.guard(opt)

    rng = np.random.RandomState(3)
    x0 = rng.randn(SIZE, 64).astype(np.float32)
    params = {"w": bf.worker_values(lambda r: x0[r])}
    state = opt.init(params)
    grads = {"w": jnp.zeros_like(params["w"])}
    ctx = bf.get_context()
    totals = []
    at_repair = None
    for t in range(steps):
        if t == kill_step:
            win = win_mod._get_win(ctx, opt._name)
            at_repair = (
                np.asarray(win.value).copy(), np.asarray(win.p).copy()
            )
        _, state = guard.step(state, grads)
        if t >= kill_step:
            win = win_mod._get_win(ctx, opt._name)
            live = list(session.membership.live_ranks())
            totals.append((
                np.asarray(win.value)[live].sum(axis=0),
                np.asarray(win.p)[live].sum(),
            ))

    assert session.repairs and session.repairs[0].policy == "push_sum"
    assert session.stale_dispatches == 0
    live = list(session.membership.live_ranks())
    assert live == [r for r in range(SIZE) if r != 2]

    # mass conservation from the repair on (x-lane and p-lane totals)
    x_tot0, p_tot0 = totals[0]
    for x_tot, p_tot in totals[1:]:
        np.testing.assert_allclose(x_tot, x_tot0, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(p_tot, p_tot0, rtol=1e-6)

    # corrected iterates converge to the mass-corrected consensus
    x_live, p_live = at_repair[0][live], at_repair[1][live]
    target = x_live.sum(axis=0) / p_live.sum()
    est = np.asarray(guard.optimizer.params()["w"])
    for r in live:
        np.testing.assert_allclose(est[r], target, atol=1e-4)


# -- fused train step under the guard ----------------------------------------


@pytest.mark.chaos
def test_chaos_fused_train_step_repairs():
    """The overlap-layer fused train step runs the same liveness + repair
    path: kill mid-training, repair, survivors keep converging."""
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start()
    session.inject("kill", rank=6, step=3)
    opt = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(0.1))
    guard = bf.elastic.guard(opt)

    rng = np.random.RandomState(11)
    x0 = rng.randn(SIZE, 32).astype(np.float32)
    target = rng.randn(32).astype(np.float32)
    params = {"w": bf.worker_values(lambda r: x0[r])}
    state = opt.init(params)
    batch = bf.worker_values(np.broadcast_to(target, (SIZE, 32)))

    def loss_fn(p, y):
        return jnp.sum((p["w"] - y) ** 2)

    train_step = guard.make_train_step(loss_fn)
    losses = []
    for _ in range(30):
        params, state, loss = train_step(params, state, batch)
        losses.append(np.asarray(loss))

    assert len(session.repairs) == 1
    assert session.repairs[0].detected == (6,)
    assert session.stale_dispatches == 0
    live = list(session.membership.live_ranks())
    final = np.asarray(params["w"])
    # the quadratic pulls every survivor to the shared target
    np.testing.assert_allclose(
        final[live], np.tile(target, (len(live), 1)), atol=1e-2
    )


# -- rejoin + consensus restore ----------------------------------------------


def test_consensus_restore_pure():
    x = np.arange(24, dtype=np.float32).reshape(4, 6)
    tree = {"w": jnp.asarray(x)}
    out = consensus_restore(tree, rank=1, live=(0, 2, 3))
    got = np.asarray(out["w"])
    np.testing.assert_allclose(got[1], x[[0, 2, 3]].mean(axis=0))
    np.testing.assert_array_equal(got[[0, 2, 3]], x[[0, 2, 3]])
    with pytest.raises(ValueError):
        consensus_restore(tree, rank=1, live=(1,))


@pytest.mark.chaos
def test_rejoin_restores_edges_and_consensus():
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    ctx = bf.get_context()
    session = bf.elastic.start()
    session.inject("kill", rank=4, step=2)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    guard = bf.elastic.guard(opt)
    rng = np.random.RandomState(5)
    x0 = rng.randn(SIZE, 16).astype(np.float32)
    params = {"w": bf.worker_values(lambda r: x0[r])}
    state = opt.init(params)
    grads = {"w": jnp.zeros_like(params["w"])}
    for _ in range(6):
        params, state = guard.step(params, state, grads)
    assert session.membership.dead_ranks() == (4,)

    params = session.rejoin(4, params=params, optimizer=opt)
    assert session.membership.dead_ranks() == ()
    # topology references the rejoined rank again
    topo = ctx.load_topology()
    assert any(4 in e for e in topo.edges() if e[0] != e[1])
    # its slot was restored to the survivors' consensus
    got = np.asarray(params["w"])
    survivors = [r for r in range(SIZE) if r != 4]
    np.testing.assert_allclose(
        got[4],
        np.mean(got[survivors].astype(np.float32), axis=0),
        atol=1e-6,
    )
    # and training proceeds with everyone back on the wire
    for _ in range(3):
        params, state = guard.step(params, state, grads)
    assert session.stale_dispatches == 0
    snap = metrics.snapshot()
    assert snap["bluefog.elastic.rejoins"]["value"] == 1


@pytest.mark.chaos
def test_pushsum_rejoin_reinstalls_sender_weights():
    """Rejoin must re-point the push-sum sender mass split at the full
    live set — stale pruned dst_weights would silently keep the rejoined
    rank off the wire forever."""
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start()
    session.inject("kill", rank=2, step=1)
    opt = bf.DistributedPushSumOptimizer(optax.sgd(0.0))
    guard = bf.elastic.guard(opt)
    params = {"w": bf.worker_values(
        lambda r: np.full((8,), float(r), np.float32)
    )}
    state = opt.init(params)
    grads = {"w": jnp.zeros((SIZE, 8), jnp.float32)}
    for _ in range(3):
        _, state = guard.step(state, grads)
    # post-repair: no sender routes mass to the dead rank
    assert all(2 not in d for d in opt.dst_weights)

    session.rejoin(2, optimizer=opt)
    # rank 2's in-edges are back in the installed sender split
    assert any(2 in d for d in opt.dst_weights), opt.dst_weights
    for _ in range(3):
        _, state = guard.step(state, grads)
    assert session.stale_dispatches == 0
    # mass flows again: rank 2's p-lane departs from its frozen value
    est = np.asarray(opt.params()["w"])
    live = list(session.membership.live_ranks())
    assert len(live) == SIZE


@pytest.mark.chaos
def test_winput_repair_prunes_put_wire():
    """The put diffusion family: repair must prune the EXCHANGE wire
    (dst_weights default to create-time out-neighbors and would keep
    shipping to the dead rank) and use the receiver policy (no added
    edges — window buffers only exist for create-time neighbors)."""
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start()
    session.inject("kill", rank=3, step=2)
    opt = bf.DistributedWinPutOptimizer(optax.sgd(0.0))
    guard = bf.elastic.guard(opt)
    x0 = np.random.RandomState(1).randn(SIZE, 8).astype(np.float32)
    params = {"w": bf.worker_values(lambda r: x0[r])}
    state = opt.init(params)
    grads = {"w": jnp.zeros((SIZE, 8), jnp.float32)}
    for _ in range(6):
        _, state = guard.step(state, grads)
    assert session.repairs and session.repairs[0].policy == "receiver"
    assert session.stale_dispatches == 0
    # no sender pushes to the dead rank anymore
    assert opt.dst_weights is not None
    assert all(3 not in d for d in opt.dst_weights), opt.dst_weights
    opt.free()


@pytest.mark.chaos
def test_user_set_topology_mid_session_becomes_repair_base():
    """A user-installed topology after bf.elastic.start() must become
    the base later repairs restrict — not be silently reverted to the
    session-start graph."""
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    guard = bf.elastic.guard(opt)
    params = {"w": bf.worker_values(
        lambda r: np.full(4, float(r), np.float32)
    )}
    state = opt.init(params)
    grads = {"w": jnp.zeros((SIZE, 4), jnp.float32)}
    _, state = guard.step(params, state, grads)

    bf.set_topology(bf.topology.RingGraph(SIZE))  # the user's new base
    session.inject("kill", rank=4, step=session.step)
    for _ in range(2):
        params, state = guard.step(params, state, grads)
    # the repaired graph derives from the RING: Exp2-only offset-2 jumps
    # like (0, 2) and (1, 3) must not reappear
    topo = bf.get_context().load_topology()
    live_edges = {
        tuple(sorted(e)) for e in topo.edges() if e[0] != e[1]
    }
    assert not (live_edges & {(0, 2), (1, 3)}), live_edges


@pytest.mark.chaos
def test_simultaneous_kills_all_detected_in_one_repair():
    """Two ranks killed at the same step: the repair prunes both and
    records BOTH detections — neither is stranded unrepaired after its
    edges are gone."""
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start()
    session.inject("kill", rank=2, step=3)
    session.inject("kill", rank=6, step=3)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    guard = bf.elastic.guard(opt)
    params = {"w": bf.worker_values(
        lambda r: np.full(4, float(r), np.float32)
    )}
    state = opt.init(params)
    grads = {"w": jnp.zeros((SIZE, 4), jnp.float32)}
    for _ in range(6):
        params, state = guard.step(params, state, grads)
    assert len(session.repairs) == 1
    assert session.repairs[0].detected == (2, 6)
    assert session.repairs[0].steps_to_detect == {2: 0, 6: 0}
    assert session._unrepaired == {}
    assert session.stale_dispatches == 0


@pytest.mark.chaos
@pytest.mark.parametrize("wire", ["int8_ef", "int4_ef"])
def test_chaos_kill_ef_residuals_self_invalidate(wire):
    """Repair under an active error-feedback session (int8_ef AND the
    int4_ef tier): the CHOCO copies integrate a fixed per-round source,
    so the membership change must zero-rebuild them — stale copies
    integrated under the pre-failure edge set would desynchronize the
    bit-identical sender/receiver replicas. After the rebuild the EF
    recursion re-converges: survivors reach a consensus far below the
    memoryless tier's quantization floor."""
    _init()
    bf.set_topology(bf.topology.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start(policy="average")
    session.inject("kill", rank=3, step=5)
    opt = bf.DistributedAdaptWithCombineOptimizer(optax.sgd(0.0))
    opt.compression = wire
    guard = bf.elastic.guard(opt)
    rng = np.random.RandomState(17)
    x0 = rng.randn(SIZE, 1024).astype(np.float32) * 4.0
    params = {"w": bf.worker_values(lambda r: x0[r])}
    state = opt.init(params)
    zero = {"w": bf.worker_values(lambda r: np.zeros(1024, np.float32))}
    ef_sig_pre = ef_pre = None
    for t in range(60):
        params, state = guard.step(params, state, zero)
        _settle(params, state)
        if t == 4:  # one step before the kill lands
            ef_sig_pre = opt._ef_sig
            ef_pre = opt._ef
    assert ef_sig_pre is not None
    # the repaired plan's perms differ -> the EF signature changed and
    # the copies were rebuilt (zeroed), not carried across the repair
    assert opt._ef_sig != ef_sig_pre
    assert opt._ef is not ef_pre
    live = sorted(session.membership.live_ranks())
    assert 3 not in live
    w = np.asarray(params["w"])[live]
    assert np.abs(w - w.mean(0)).max() < 1e-2, wire
    bf.elastic.stop()
