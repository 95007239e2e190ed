# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Weight-update sharding tests (``BLUEFOG_SHARD``, docs/sharding.md).

Three layers: pure layout algebra (every bucket layout x world sizes
2-8 x odd parameter shapes), the trajectory contract (sharded ==
replicated == numpy Adam oracle on the gradient-allreduce family; every
other family falls back to the replicated path BITWISE, fp32 and
``int8_ef`` both pinned), and the lifecycle composition (elastic
kill -> repair -> re-shard with zero stale dispatches, state values
preserved; health /fleet block; ``tools/shard_plan.py``).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import scaling, sharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    monkeypatch.delenv("BLUEFOG_SHARD", raising=False)
    monkeypatch.delenv("BLUEFOG_SHARD_MASTER", raising=False)
    monkeypatch.delenv("BLUEFOG_SHARD_GRADS", raising=False)
    bf.init(devices=cpu_devices[:SIZE])
    yield
    bf.shutdown()


def _shard_on(monkeypatch, master=False, grads=False):
    monkeypatch.setenv("BLUEFOG_SHARD", "1")
    if master:
        monkeypatch.setenv("BLUEFOG_SHARD_MASTER", "1")
    if grads:
        monkeypatch.setenv("BLUEFOG_SHARD_GRADS", "1")


# -- layout algebra (host-side, no mesh) -------------------------------------


@pytest.mark.parametrize("n_live", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("d", [1, 511, 512, 513, 4096, 8191, 10000])
def test_layout_partitions_exactly(n_live, d):
    live = tuple(range(n_live))
    lay = sharding.build_layout([("float32", d)], live, SIZE)
    g = lay.groups[0]
    assert g.slot % sharding.ALIGN_ELEMS == 0
    assert g.padded == g.slot * n_live >= d
    # every element owned exactly once, in owner order
    covered = []
    for row in lay.owner_map():
        covered.extend(range(row["start"], row["stop"]))
    assert covered == list(range(d))
    for elem in (0, d // 2, d - 1):
        r = lay.owner_of(0, elem)
        assert r in live


@pytest.mark.parametrize(
    "live", [(0, 1), (0, 2, 4, 6), (1, 3, 5, 7), tuple(range(7))]
)
def test_layout_live_subsets(live):
    lay = sharding.build_layout([("float32", 7000)], live, SIZE)
    assert lay.live == tuple(sorted(live))
    lidx = lay.live_index()
    assert lidx.shape == (SIZE,)
    for i, r in enumerate(lay.live):
        assert lidx[r] == i


def test_layout_slots_unique_across_groups():
    """Same element count in two dtype groups must still yield distinct
    slot lengths — the trailing dimension is the discriminator the
    re-shard/checkpoint leaf classifier relies on."""
    lay = sharding.build_layout(
        [("bfloat16", 1000), ("float32", 1000)], range(SIZE), SIZE
    )
    slots = [g.slot for g in lay.groups]
    assert len(set(slots)) == len(slots)


def test_gather_slice_roundtrip():
    rng = np.random.RandomState(0)
    lay = sharding.build_layout(
        [("float32", 3333)], (0, 1, 2, 4, 5, 6, 7), SIZE
    )
    full = rng.randn(3333).astype(np.float32)
    rows = sharding.slice_rows(full, lay, 0)
    assert rows.shape == (SIZE, lay.groups[0].slot)
    assert np.all(rows[3] == 0)  # dead rank owns nothing
    np.testing.assert_array_equal(sharding.gather_rows(rows, lay, 0), full)


def test_accounting_helpers():
    lay = sharding.build_layout([("float32", 262145)], range(SIZE), SIZE)
    g = lay.groups[0]
    assert sharding.state_bytes(lay, 2, sharded=True) == 2 * 4 * g.slot
    assert sharding.state_bytes(lay, 2, sharded=False) == 2 * 4 * g.elems
    assert sharding.gather_wire_bytes(lay) == (SIZE - 1) * 4 * g.slot
    mlay = sharding.build_layout(
        [("float32", 262145)], range(SIZE), SIZE, master=True
    )
    assert (
        sharding.state_bytes(mlay, 2, sharded=True)
        == 2 * 4 * g.slot + 4 * g.slot
    )


# -- trajectory contract -----------------------------------------------------


D1, D2 = 1537, 700  # two leaves, both odd, one packed group


def _targets():
    rng = np.random.RandomState(0)
    return (
        rng.randn(SIZE, D1).astype(np.float32),
        rng.randn(SIZE, D2).astype(np.float32),
    )


def _run_grad_family(steps=6, lr=0.05):
    c1, c2 = _targets()
    opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(lr))
    params = {
        "a": bf.worker_values(lambda r: np.zeros(D1, np.float32)),
        "b": bf.worker_values(lambda r: np.zeros(D2, np.float32)),
    }
    state = opt.init(params)
    for _ in range(steps):
        grads = {
            "a": params["a"] - jnp.asarray(c1),
            "b": params["b"] - jnp.asarray(c2),
        }
        params, state = opt.step(params, state, grads)
    return opt, params, state


def _np_adam_oracle(c_mean, steps, lr=0.05, b1=0.9, b2=0.999, eps=1e-8):
    x = np.zeros_like(c_mean)
    m = np.zeros_like(c_mean)
    v = np.zeros_like(c_mean)
    for t in range(1, steps + 1):
        g = x - c_mean
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        x = x - lr * (m / (1 - b1 ** t)) / (
            np.sqrt(v / (1 - b2 ** t)) + eps
        )
    return x


def test_sharded_matches_replicated_and_numpy_oracle(monkeypatch):
    """The headline pin: BLUEFOG_SHARD=1 on the gradient-allreduce
    family is a memory layout, not an algorithm — the trajectory
    matches the replicated path to the ulp envelope and the numpy Adam
    replay, and every rank stays a bit-identical replica."""
    c1, c2 = _targets()
    _, p_rep, _ = _run_grad_family()
    bf.shutdown()
    _shard_on(monkeypatch)
    bf.init(devices=jax.devices("cpu")[:SIZE])
    opt, p_sh, state = _run_grad_family()
    # the state really is the sharded form at 1/N (+ alignment slack)
    assert isinstance(state, sharding.ShardedOptState)
    lay = opt._shard_layout
    assert lay is not None and len(lay.groups) == 1
    assert lay.groups[0].elems == D1 + D2
    for leaf in jax.tree_util.tree_leaves(state):
        assert leaf.shape[0] == SIZE
        assert leaf.size <= SIZE * lay.groups[0].slot
    for key in ("a", "b"):
        ws, wr = np.asarray(p_sh[key]), np.asarray(p_rep[key])
        assert np.abs(ws - ws[0]).max() == 0.0  # bit-identical replicas
        np.testing.assert_allclose(ws, wr, rtol=0, atol=1e-6)
    oracle = _np_adam_oracle(c1.mean(0), 6)
    np.testing.assert_allclose(
        np.asarray(p_sh["a"])[0], oracle, rtol=0, atol=1e-4
    )


def test_fused_sharded_matches_two_program(monkeypatch):
    """The fused builder and opt.step share _combine_update, so the
    sharded fused step is the same math as the sharded two-program
    path (the PR-2 guarantee extended to the shard branch)."""
    _shard_on(monkeypatch)
    c1, _ = _targets()
    ct = jnp.asarray(c1)

    def loss_fn(params, c):
        return 0.5 * jnp.sum((params["a"] - c) ** 2)

    def make():
        opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.05))
        params = {"a": bf.worker_values(lambda r: np.zeros(D1, np.float32))}
        return opt, params, opt.init(params)

    opt, params, state = make()
    for _ in range(4):
        params, state = opt.step(
            params, state, {"a": params["a"] - ct}
        )
    opt2, p2, s2 = make()
    train = opt2.make_train_step(loss_fn)
    for _ in range(4):
        p2, s2, _loss = train(p2, s2, ct)
    np.testing.assert_allclose(
        np.asarray(p2["a"]), np.asarray(params["a"]), rtol=0, atol=1e-6
    )


def test_master_params_bf16(monkeypatch):
    """BLUEFOG_SHARD_MASTER=1: bf16 parameters update against fp32
    master slices; the trajectory tracks the fp32 run to bf16
    resolution instead of accumulating bf16 rounding in the moments."""
    _shard_on(monkeypatch, master=True)
    c1, _ = _targets()
    opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.05))
    params = {"a": bf.worker_values(
        lambda r: np.zeros(D1, np.dtype(jnp.bfloat16))
    )}
    state = opt.init(params)
    assert isinstance(state, sharding.ShardedOptState)
    assert len(state.master) == 1
    assert state.master[0].dtype == jnp.float32
    for _ in range(6):
        grads = {"a": params["a"] - jnp.asarray(c1, jnp.bfloat16)}
        params, state = opt.step(params, state, grads)
    w = np.asarray(params["a"], np.float32)
    assert np.isfinite(w).all()
    assert np.abs(w - w[0]).max() == 0.0
    # bf16 wire, fp32 master: tracks the fp32 oracle to the bf16
    # quantization envelope (the gradients themselves are bf16)
    oracle = _np_adam_oracle(c1.mean(0), 6)
    assert np.abs(w[0] - oracle).max() < 0.1


def test_grad_accumulation_composes_with_shard(monkeypatch):
    """num_steps_per_communication > 1: accumulation calls leave the
    sharded state untouched; the communicating call applies the summed
    gradient exactly like the replicated path."""
    c1, c2 = _targets()

    def run():
        opt = bf.DistributedGradientAllreduceOptimizer(
            optax.sgd(0.1), num_steps_per_communication=2
        )
        params = {
            "a": bf.worker_values(lambda r: np.zeros(D1, np.float32)),
            "b": bf.worker_values(lambda r: np.zeros(D2, np.float32)),
        }
        state = opt.init(params)
        for _ in range(4):
            grads = {
                "a": params["a"] - jnp.asarray(c1),
                "b": params["b"] - jnp.asarray(c2),
            }
            params, state = opt.step(params, state, grads)
        return np.asarray(params["a"])

    w_rep = run()
    bf.shutdown()
    _shard_on(monkeypatch)
    bf.init(devices=jax.devices("cpu")[:SIZE])
    w_sh = run()
    np.testing.assert_allclose(w_sh, w_rep, rtol=0, atol=1e-6)


# -- the off pin and the family fallback -------------------------------------


def _run_cta_int8_ef(steps=4):
    c1, _ = _targets()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = "int8_ef"
    params = {"w": bf.worker_values(lambda r: c1[r])}
    state = opt.init(params)
    for _ in range(steps):
        params, state = opt.step(
            params, state, {"w": params["w"] - jnp.asarray(c1)}
        )
    keys = [
        k for k in bf.get_context().op_cache
        if isinstance(k, tuple) and "shard" in map(str, k)
    ]
    return np.asarray(params["w"]), keys


def test_gossip_family_falls_back_bitwise_int8_ef(monkeypatch):
    """BLUEFOG_SHARD=1 on a gossip family (per-rank state, nothing
    redundant to shard) must warn once and dispatch the replicated
    path VERBATIM — bitwise trajectory, zero shard-tagged cache keys —
    under the int8_ef wire tier (the stateful tier most sensitive to
    any payload perturbation)."""
    from bluefog_tpu import logging_util

    w_off, keys_off = _run_cta_int8_ef()
    bf.shutdown()
    _shard_on(monkeypatch)
    logging_util._warned_once.discard(
        "shard-family:cta:neighbor.allreduce"
    )
    bf.init(devices=jax.devices("cpu")[:SIZE])
    w_on, keys_on = _run_cta_int8_ef()
    np.testing.assert_array_equal(w_on, w_off)
    assert keys_off == [] and keys_on == []


def test_gossip_family_falls_back_bitwise_fp32(monkeypatch):
    c1, _ = _targets()

    def run():
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
        params = {"w": bf.worker_values(lambda r: c1[r])}
        state = opt.init(params)
        for _ in range(4):
            params, state = opt.step(
                params, state, {"w": params["w"] - jnp.asarray(c1)}
            )
        return np.asarray(params["w"])

    a = run()
    bf.shutdown()
    _shard_on(monkeypatch)
    bf.init(devices=jax.devices("cpu")[:SIZE])
    b = run()
    np.testing.assert_array_equal(a, b)


def test_shard_off_is_replicated_with_clean_keys():
    """BLUEFOG_SHARD unset/0: plain state tree, no shard-tagged cache
    keys anywhere — the off path is the pre-shard code verbatim."""
    opt, _params, state = _run_grad_family(steps=3)
    assert not isinstance(state, sharding.ShardedOptState)
    assert opt._shard_layout is None
    assert not [
        k for k in bf.get_context().op_cache
        if isinstance(k, tuple) and "shard" in map(str, k)
    ]


def test_sharded_state_refused_without_flag(monkeypatch):
    """A sharded state handed to a shard-active optimizer whose state
    was built replicated (or vice versa) fails with the clear message,
    not a tracer shape error."""
    _shard_on(monkeypatch)
    c1, c2 = _targets()
    opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.05))
    params = {
        "a": bf.worker_values(lambda r: np.zeros(D1, np.float32)),
        "b": bf.worker_values(lambda r: np.zeros(D2, np.float32)),
    }
    monkeypatch.setenv("BLUEFOG_SHARD", "0")
    replicated = opt.init(params)
    monkeypatch.setenv("BLUEFOG_SHARD", "1")
    with pytest.raises(ValueError, match="not sharded"):
        opt.step(params, replicated, {
            "a": params["a"] - jnp.asarray(c1),
            "b": params["b"] - jnp.asarray(c2),
        })


# -- elastic composition -----------------------------------------------------


def test_elastic_kill_repair_reshards(monkeypatch):
    """kill -> repair -> re-shard: the layout follows the live set, the
    re-sharded program dispatches under a new cache key (zero stale
    dispatches), replicas stay bit-identical, and training continues."""
    _shard_on(monkeypatch)
    c1, _ = _targets()
    session = bf.elastic.start(policy="average")
    session.inject("kill", rank=3, step=4)
    opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.02))
    guard = bf.elastic.guard(opt)
    params = {"a": bf.worker_values(lambda r: np.zeros(D1, np.float32))}
    state = opt.init(params)
    lay0 = opt._shard_layout
    for _ in range(8):
        params, state = guard.step(
            params, state, {"a": params["a"] - jnp.asarray(c1)}
        )
    lay1 = opt._shard_layout
    assert lay0.live == tuple(range(SIZE))
    assert lay1.live == (0, 1, 2, 4, 5, 6, 7)
    assert opt._shard_reshards == 1
    assert session.stale_dispatches == 0
    # both layouts dispatched under their own keys
    shard_keys = {
        k for k in bf.get_context().op_cache
        if isinstance(k, tuple) and k and k[0] == "opt_step"
        and "shard" in map(str, k)
    }
    assert len(shard_keys) == 2
    w = np.asarray(params["a"])
    assert np.isfinite(w).all()
    assert np.abs(w - w[0]).max() == 0.0
    summary = sharding.summary()
    assert summary["reshards"] == 1 and summary["n_live"] == 7
    bf.elastic.stop()


def test_reshard_preserves_state_values(monkeypatch):
    """The re-shard transform is a pure re-layout: gathering the full
    per-coordinate vectors before and after must agree exactly."""
    _shard_on(monkeypatch)
    opt, _params, state = _run_grad_family(steps=3)
    ctx = bf.get_context()
    old = opt._shard_layout
    new = sharding.build_layout(
        [(g.dtype, g.elems) for g in old.groups],
        (0, 1, 2, 4, 5, 6, 7), SIZE, master=old.master, token=("x",),
    )
    state2 = opt._reshard_state(ctx, old, new, state)
    leaves_a = jax.tree_util.tree_leaves(state)
    leaves_b = jax.tree_util.tree_leaves(state2)
    checked = 0
    for a, b in zip(leaves_a, leaves_b):
        gi = opt._shard_slot_group(tuple(a.shape), old)
        if gi is None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            continue
        np.testing.assert_array_equal(
            sharding.gather_rows(np.asarray(a), old, gi),
            sharding.gather_rows(np.asarray(b), new, gi),
        )
        checked += 1
    assert checked >= 2  # at least mu and nu


# -- observability + accounting ----------------------------------------------


def test_state_bytes_measured_equals_analytic(monkeypatch):
    _shard_on(monkeypatch)
    opt, params, state = _run_grad_family(steps=1)
    measured = scaling.optimizer_state_bytes(state=state, world=SIZE)
    analytic = scaling.optimizer_state_bytes(params, opt, shard=True)
    assert measured == analytic
    monkeypatch.setenv("BLUEFOG_SHARD", "0")
    opt2 = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.05))
    state2 = opt2.init(params)
    measured2 = scaling.optimizer_state_bytes(state=state2, world=SIZE)
    analytic2 = scaling.optimizer_state_bytes(params, opt2, shard=False)
    assert measured2 == analytic2
    # the point of it all: ~1/N with the 512-alignment slack
    lay = opt._shard_layout
    assert measured <= measured2 * (lay.groups[0].slot
                                    / lay.groups[0].elems) + 4096


def test_health_fleet_report_carries_shard_block(monkeypatch):
    _shard_on(monkeypatch)
    _run_grad_family(steps=1)
    plane = bf.health.start()
    try:
        rep = plane.report()
        assert rep["shard"]["enabled"] is True
        assert rep["shard"]["n_live"] == SIZE
        assert rep["shard"]["state_bytes_sharded"] > 0
        assert (
            rep["shard"]["state_bytes_sharded"]
            < rep["shard"]["state_bytes_replicated"]
        )
        assert "state_bytes_measured" in rep["shard"]
    finally:
        bf.health.stop()


def test_shard_metrics_gauges_emitted(monkeypatch):
    from bluefog_tpu import metrics

    _shard_on(monkeypatch)
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    metrics.reset()
    _run_grad_family(steps=2)
    assert metrics.peek("bluefog.shard.enabled").value == 1
    assert metrics.peek("bluefog.shard.state_bytes").value > 0
    ratio = metrics.peek("bluefog.shard.ratio").value
    assert 0 < ratio < 1
    assert metrics.peek("bluefog.shard.gather_bytes").value > 0


def test_shard_plan_cli(tmp_path):
    out = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "tools", "shard_plan.py"),
            "--workers", "8", "--group", "float32:262145",
            "--live", "0,1,2,4,5,6,7", "--budget", "1048576", "--json",
        ],
        capture_output=True, text=True, timeout=60, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["n_live"] == 7
    assert rep["state_bytes_sharded"] < rep["state_bytes_replicated"]
    assert rep["sharded_fits"] is True
    assert rep["replicated_fits"] is False
    covered = sorted(
        (r["start"], r["stop"]) for r in rep["owner_map"]
    )
    assert covered[0][0] == 0 and covered[-1][1] == 262145
    # the ZeRO-2 gradient-leg columns ride along
    assert rep["scatter_bytes_per_step"] < rep["allreduce_bytes_per_step"]
    assert rep["grad_bytes_sharded"] < rep["grad_bytes_replicated"]
    assert 0 < rep["grad_ratio"] < 1
    assert rep["sharded_with_grads_fits"] is True
    assert rep["replicated_with_grads_fits"] is False


# -- review-hardening regressions --------------------------------------------


def test_coupled_inner_transform_refused(monkeypatch):
    """Cross-coordinate transforms (global-norm clipping, trust
    ratios) would silently break the trajectory-exact contract — the
    behavioral probe must refuse them with the reason, at init AND on
    a post-init tx rebind."""
    _shard_on(monkeypatch)
    params = {"a": bf.worker_values(lambda r: np.zeros(D1, np.float32))}
    opt = bf.DistributedGradientAllreduceOptimizer(
        optax.chain(optax.clip_by_global_norm(1.0), optax.adam(0.05))
    )
    with pytest.raises(ValueError, match="ELEMENTWISE"):
        opt.init(params)
    # elementwise chains pass (per-element clipping is local)
    opt2 = bf.DistributedGradientAllreduceOptimizer(
        optax.chain(optax.clip(1.0), optax.adam(0.05))
    )
    state = opt2.init(params)
    # rebinding to a coupled tx after init is caught on the next step
    opt2.tx = optax.chain(optax.clip_by_global_norm(1.0),
                          optax.sgd(0.1))
    c1, _ = _targets()
    with pytest.raises(ValueError, match="ELEMENTWISE"):
        opt2.step(params, state, {"a": params["a"] - jnp.asarray(c1)})


def test_master_flip_midrun_refused(monkeypatch):
    """BLUEFOG_SHARD_MASTER flipped between steps must refuse with the
    clear message, not die in a pytree mismatch inside the trace."""
    _shard_on(monkeypatch)
    c1, _ = _targets()
    opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.05))
    params = {"a": bf.worker_values(lambda r: np.zeros(D1, np.float32))}
    state = opt.init(params)
    params, state = opt.step(
        params, state, {"a": params["a"] - jnp.asarray(c1)}
    )
    monkeypatch.setenv("BLUEFOG_SHARD_MASTER", "1")
    with pytest.raises(ValueError, match="SHARD_MASTER"):
        opt.step(params, state, {"a": params["a"] - jnp.asarray(c1)})


def test_duplicate_live_ranks_refused():
    with pytest.raises(ValueError, match="duplicate live ranks"):
        sharding.build_layout([("float32", 1000)], (0, 0, 1), SIZE)


def test_owner_map_clamped_for_padding_owners():
    """A group smaller than (n_live-1)*slot leaves trailing owners
    with pure padding: their rows must read [elems, elems) + slot pad,
    never an inverted interval."""
    lay = sharding.build_layout([("float32", 600)], range(SIZE), SIZE)
    slot = lay.groups[0].slot
    rows = lay.owner_map()
    for row in rows:
        assert row["start"] <= row["stop"]
        assert 0 <= row["padding"] <= slot
    assert rows[0]["start"] == 0 and rows[0]["stop"] == slot
    assert rows[1]["stop"] == 600
    assert rows[1]["padding"] == 2 * slot - 600
    assert rows[-1]["start"] == rows[-1]["stop"] == 600
    assert rows[-1]["padding"] == slot

# -- ZeRO-2: reduce-scatter gradient sharding (BLUEFOG_SHARD_GRADS) ----------


def test_zero2_matches_replicated_and_numpy_oracle(monkeypatch):
    """The ZeRO-2 headline pin: lowering the gradient leg to the ring
    reduce-scatter (each rank receives ONLY its owned slot) keeps the
    trajectory inside the SAME envelope as the replicated allreduce and
    the numpy Adam replay — the scatter's fixed reduction order is the
    allreduce's reduction, delivered in pieces."""
    c1, _c2 = _targets()
    _, p_rep, _ = _run_grad_family()
    bf.shutdown()
    _shard_on(monkeypatch, grads=True)
    bf.init(devices=jax.devices("cpu")[:SIZE])
    opt, p_z2, state = _run_grad_family()
    assert isinstance(state, sharding.ShardedOptState)
    lay = opt._shard_layout
    assert lay is not None and lay.grads
    for key in ("a", "b"):
        wz, wr = np.asarray(p_z2[key]), np.asarray(p_rep[key])
        assert np.abs(wz - wz[0]).max() == 0.0  # bit-identical replicas
        np.testing.assert_allclose(wz, wr, rtol=0, atol=1e-6)
    oracle = _np_adam_oracle(c1.mean(0), 6)
    np.testing.assert_allclose(
        np.asarray(p_z2["a"])[0], oracle, rtol=0, atol=1e-4
    )
    # the dispatched program really is the scattered one
    assert [
        k for k in bf.get_context().op_cache
        if isinstance(k, tuple) and "scatter" in map(str, k)
    ]


def test_zero2_fused_matches_two_program(monkeypatch):
    """make_train_step and opt.step share _combine_update through the
    scatter branch too: the fused ZeRO-2 step is the same math as the
    two-program path."""
    _shard_on(monkeypatch, grads=True)
    c1, _ = _targets()
    ct = jnp.asarray(c1)

    def loss_fn(params, c):
        return 0.5 * jnp.sum((params["a"] - c) ** 2)

    def make():
        opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.05))
        params = {"a": bf.worker_values(lambda r: np.zeros(D1, np.float32))}
        return opt, params, opt.init(params)

    opt, params, state = make()
    for _ in range(4):
        params, state = opt.step(
            params, state, {"a": params["a"] - ct}
        )
    opt2, p2, s2 = make()
    train = opt2.make_train_step(loss_fn)
    for _ in range(4):
        p2, s2, _loss = train(p2, s2, ct)
    np.testing.assert_allclose(
        np.asarray(p2["a"]), np.asarray(params["a"]), rtol=0, atol=1e-6
    )


def test_zero2_quantized_scatter_tiers(monkeypatch):
    """The scatter leg speaks the PR-8 wire tiers: int8 block-scaled
    wire converges within the quantization envelope; int8_ef holds a
    per-slot CHOCO residual that accumulates shipped error."""
    c1, c2 = _targets()
    _, p_rep, _ = _run_grad_family()
    bf.shutdown()
    _shard_on(monkeypatch, grads=True)
    bf.init(devices=jax.devices("cpu")[:SIZE])

    def run(compression):
        opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.05))
        opt.compression = compression
        params = {
            "a": bf.worker_values(lambda r: np.zeros(D1, np.float32)),
            "b": bf.worker_values(lambda r: np.zeros(D2, np.float32)),
        }
        state = opt.init(params)
        for _ in range(6):
            grads = {
                "a": params["a"] - jnp.asarray(c1),
                "b": params["b"] - jnp.asarray(c2),
            }
            params, state = opt.step(params, state, grads)
        return opt, params

    opt8, p8 = run("int8")
    dev8 = max(
        np.abs(np.asarray(p8[k]) - np.asarray(p_rep[k])).max()
        for k in ("a", "b")
    )
    assert dev8 < 0.05
    optef, pef = run("int8_ef")
    assert optef._scatter_ef, "scatter residual state missing"
    devef = max(
        np.abs(np.asarray(pef[k]) - np.asarray(p_rep[k])).max()
        for k in ("a", "b")
    )
    assert devef < 0.05
    resid = sum(float(jnp.abs(e).sum()) for e in optef._scatter_ef)
    assert resid > 0


@pytest.mark.parametrize("wire,block_bytes", [
    ("bf16", 1024), ("int8", 516), ("int4", 258),
    ("int8_ef", 516), ("int4_ef", 258),
])
def test_scatter_wire_tiers_at_exact_block_scale_ratios(wire, block_bytes):
    """Slots sit on the 512-element grid, so each quantized tier's
    reduce-scatter bytes are EXACTLY its block-scale ratio of fp32's
    (a 512-element block ships 512 payload bytes plus a 4-byte scale for
    int8, 256 plus a 2-byte scale for int4), the error-feedback tiers
    ship their base tier's bytes, and every tier stays under the fp32
    allreduce it replaces."""
    lay = sharding.build_layout([("float32", 262145)], range(SIZE), SIZE)
    slot = lay.groups[0].slot
    assert slot % 512 == 0
    fp32 = scaling.reduce_scatter_bytes(((slot, 4),), SIZE)
    assert fp32 == sharding.scatter_wire_bytes(lay)
    b = scaling.reduce_scatter_bytes(((slot, 4),), SIZE, wire=wire)
    # one 512-element block is 2048 bytes at fp32
    assert b * 2048 == fp32 * block_bytes
    assert b < fp32 < sharding.allreduce_wire_bytes(lay)


def test_zero1_program_verbatim_when_grads_off(monkeypatch):
    """BLUEFOG_SHARD=1 WITHOUT gradient sharding is the PR-14 program
    verbatim: layout carries no grads flag, zero scatter-tagged cache
    keys, no scatter residual state."""
    _shard_on(monkeypatch)
    opt, _params, state = _run_grad_family(steps=3)
    assert isinstance(state, sharding.ShardedOptState)
    assert opt._shard_layout.grads is False
    assert not getattr(opt, "_scatter_ef", None)
    assert not [
        k for k in bf.get_context().op_cache
        if isinstance(k, tuple) and "scatter" in map(str, k)
    ]


def test_shard_grads_flip_no_reshard_no_alias(monkeypatch):
    """Flipping BLUEFOG_SHARD_GRADS between steps rebuilds the layout
    (new cache key — the two programs never alias) WITHOUT a reshard:
    the state rows are laid out identically, only the gradient leg's
    lowering changes."""
    _shard_on(monkeypatch, grads=True)
    c1, c2 = _targets()
    opt, params, state = None, None, None
    opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.05))
    params = {
        "a": bf.worker_values(lambda r: np.zeros(D1, np.float32)),
        "b": bf.worker_values(lambda r: np.zeros(D2, np.float32)),
    }
    state = opt.init(params)

    def one():
        grads = {
            "a": params["a"] - jnp.asarray(c1),
            "b": params["b"] - jnp.asarray(c2),
        }
        return opt.step(params, state, grads)

    params, state = one()
    assert opt._shard_layout.grads is True
    reshards0 = opt._shard_reshards

    def step_keys():
        return {
            k for k in bf.get_context().op_cache
            if isinstance(k, tuple) and k and k[0] == "opt_step"
        }

    keys_z2 = step_keys()
    monkeypatch.delenv("BLUEFOG_SHARD_GRADS")
    params, state = one()
    assert opt._shard_layout.grads is False
    assert opt._shard_reshards == reshards0  # flip is NOT a reshard
    keys_both = step_keys()
    assert keys_both > keys_z2  # the ZeRO-1 program got its own key
    monkeypatch.setenv("BLUEFOG_SHARD_GRADS", "1")
    params, state = one()
    assert opt._shard_layout.grads is True
    assert opt._shard_reshards == reshards0
    # back on ZeRO-2: the ORIGINAL key is reused, nothing new compiled
    assert step_keys() == keys_both
    scatter_tagged = {
        k for k in keys_both if "scatter" in map(str, k)
    }
    assert scatter_tagged and scatter_tagged < keys_both


def test_zero2_elastic_kill_repair_rescatters(monkeypatch):
    """kill -> repair under ZeRO-2: the re-shard rebuilds a
    grads-carrying layout, the re-scattered program dispatches under a
    new key with zero stale dispatches, and training continues with
    bit-identical replicas."""
    _shard_on(monkeypatch, grads=True)
    c1, _ = _targets()
    session = bf.elastic.start(policy="average")
    session.inject("kill", rank=3, step=4)
    opt = bf.DistributedGradientAllreduceOptimizer(optax.adam(0.02))
    guard = bf.elastic.guard(opt)
    params = {"a": bf.worker_values(lambda r: np.zeros(D1, np.float32))}
    state = opt.init(params)
    for _ in range(8):
        params, state = guard.step(
            params, state, {"a": params["a"] - jnp.asarray(c1)}
        )
    lay1 = opt._shard_layout
    assert lay1.live == (0, 1, 2, 4, 5, 6, 7)
    assert lay1.grads is True  # the re-shard kept the gradient leg
    assert opt._shard_reshards == 1
    assert session.stale_dispatches == 0
    scatter_keys = {
        k for k in bf.get_context().op_cache
        if isinstance(k, tuple) and k and k[0] == "opt_step"
        and "scatter" in map(str, k)
    }
    assert len(scatter_keys) == 2  # pre-kill and post-repair programs
    w = np.asarray(params["a"])
    assert np.isfinite(w).all()
    assert np.abs(w - w[0]).max() == 0.0
    bf.elastic.stop()


def test_zero2_metrics_and_accounting(monkeypatch):
    from bluefog_tpu import metrics

    _shard_on(monkeypatch, grads=True)
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    metrics.reset()
    opt, _params, _state = _run_grad_family(steps=2)
    assert metrics.peek("bluefog.shard.grads").value == 1
    assert metrics.peek("bluefog.shard.scatter_bytes").value > 0
    assert metrics.peek("bluefog.shard.grad_bytes").value > 0
    lay = opt._shard_layout
    g = lay.groups[0]
    # the layout algebra the gauges are built from
    assert sharding.scatter_wire_bytes(lay) == (SIZE - 1) * 4 * g.slot
    assert sharding.grad_bytes(lay, sharded=True) == 4 * g.slot
    assert sharding.grad_bytes(lay, sharded=False) == 4 * g.elems
    assert (
        sharding.scatter_wire_bytes(lay)
        < sharding.allreduce_wire_bytes(lay)
    )
    # wire accounting follows the scatter byte model when grads are on
    assert metrics.peek("bluefog.shard.scatter_bytes").value == (
        2 * scaling.reduce_scatter_bytes(
            ((g.slot, 4),), SIZE
        )
    )
