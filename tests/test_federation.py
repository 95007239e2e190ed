"""Hierarchical multi-pod federation tests (``bf.federation``).

Host-tier coverage: pod-spec parsing and validation, gateway election,
per-level mixing matrices (block-diagonal intra, gateway-only inter),
composed-rate prediction vs host-measured decay, DCN period choice,
per-leg wire accounting, the placement route/congestion contracts the
gateway legs rely on, and the fleetsim pod-loss repair semantics.

Device-tier coverage (8-CPU-device mesh): the federated optimizer
dispatch — key shapes, the bitwise flat-path pin (``BLUEFOG_PODS``
unset must dispatch the exact pre-federation program under the same
cache keys), mean preservation through the two-level combine, per-leg
wire counters, and the EF-wire fallback.
"""

import numpy as np
import pytest

import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import federation as fed
from bluefog_tpu import fleetsim
from bluefog_tpu import logging_util
from bluefog_tpu.topology import placement

SIZE = 8


# -- pod spec parsing ---------------------------------------------------------


def test_parse_pods_count():
    layout = fed.parse_pods("2", 16)
    assert layout.n_pods == 2
    assert list(layout.ranks(0)) == list(range(8))
    assert list(layout.ranks(1)) == list(range(8, 16))


def test_parse_pods_shape():
    layout = fed.parse_pods("4x16", 64)
    assert layout.n_pods == 4
    assert layout.pod_of(0) == 0
    assert layout.pod_of(17) == 1
    assert layout.pod_of(63) == 3


def test_parse_pods_ranges():
    layout = fed.parse_pods("0-3,4-11,12-15", 16)
    assert layout.n_pods == 3
    assert len(layout.ranks(1)) == 8


@pytest.mark.parametrize("spec", [
    "3",            # 16 % 3 != 0
    "2x9",          # 2*9 != 16
    "1",            # < 2 pods
    "0-7",          # single range = 1 pod
    "0-8,8-15",     # overlap
    "0-6,8-15",     # gap
    "8-15,0-7",     # out of order
    "bogus",
    "",
])
def test_parse_pods_rejects(spec):
    with pytest.raises(ValueError):
        fed.parse_pods(spec, 16)


def test_layout_from_env(monkeypatch):
    monkeypatch.delenv(fed.PODS_ENV, raising=False)
    assert fed.layout_from_env(16) is None
    monkeypatch.setenv(fed.PODS_ENV, "2x8")
    layout = fed.layout_from_env(16)
    assert layout is not None and layout.n_pods == 2


def test_dcn_wire_ef_falls_back(monkeypatch):
    monkeypatch.setenv(fed.DCN_WIRE_ENV, "int4_ef")
    logging_util._warned_once.discard("dcn-wire-ef")
    assert fed.dcn_wire() == "int4"
    assert "dcn-wire-ef" in logging_util._warned_once


def test_dcn_wire_exact(monkeypatch):
    monkeypatch.setenv(fed.DCN_WIRE_ENV, "exact")
    assert fed.dcn_wire() is None


# -- gateways -----------------------------------------------------------------


def test_gateways_lowest_live_rank():
    layout = fed.parse_pods("4x16", 64)
    assert list(layout.gateways()) == [0, 16, 32, 48]
    live = [r for r in range(64) if r not in (0, 1, 16)]
    assert list(layout.gateways(live)) == [2, 17, 32, 48]


def test_gateways_dead_pod_is_none():
    layout = fed.parse_pods("4x16", 64)
    live = [r for r in range(64) if not 16 <= r < 32]
    assert list(layout.gateways(live)) == [0, None, 32, 48]


# -- per-level matrices -------------------------------------------------------


def _columns_sum_to_one(n, edges):
    col = np.zeros(n)
    for (_i, j), v in edges.items():
        col[j] += v
    np.testing.assert_allclose(col, 1.0, atol=1e-12)


def test_intra_edges_block_diagonal_normalized():
    layout = fed.parse_pods("2x8", 16)
    edges = fed.intra_edges(layout, kind="exp2")
    _columns_sum_to_one(16, edges)
    for (i, j) in edges:
        assert layout.pod_of(i) == layout.pod_of(j), (i, j)


def test_inter_edges_gateways_only_normalized():
    layout = fed.parse_pods("4x16", 64)
    edges = fed.inter_edges(layout)
    _columns_sum_to_one(64, edges)
    gws = set(layout.gateways())
    for (i, j) in edges:
        if i != j:
            assert i in gws and j in gws, (i, j)
        elif j not in gws:
            # non-gateways carry the identity this step
            assert edges[(i, j)] == 1.0


# -- spectral composition -----------------------------------------------------


def test_composed_rate_matches_measured():
    layout = fed.parse_pods("2x8", 16)
    period = 4
    predicted, info = fed.composed_rate(layout, period)
    assert info["dcn_period"] == period
    w_ici = (16, fed.intra_edges(layout))
    w_dcn = (16, fed.inter_edges(layout))
    measured = fed.simulate_consensus(
        [w_ici] * period + [w_dcn], steps=64,
        comm_steps_per_cycle=period,
    )
    assert abs(predicted - measured) <= 0.02, (predicted, measured)


def test_choose_dcn_period_meets_target():
    layout = fed.parse_pods("2x8", 16)
    out = fed.choose_dcn_period(layout, target_rate=0.98)
    assert out["met"] is True
    assert out["predicted_rate"] <= 0.98
    # the chosen period is the LARGEST meeting the target
    worse = [
        row for row in out["table"]
        if row["period"] > out["period"] and row["rate"] <= 0.98
    ]
    assert not worse, out["table"]


def test_choose_dcn_period_unmeetable_discloses():
    layout = fed.parse_pods("2x8", 16)
    out = fed.choose_dcn_period(layout, target_rate=0.5)
    assert out["met"] is False
    assert out["period"] == 1


# -- wire accounting ----------------------------------------------------------


def test_wire_summary_per_edge_dcn_accounting():
    layout = fed.parse_pods("2x8", 16)
    ws = fed.wire_summary(
        layout, 1 << 16, itemsize=4, ici_wire=None,
        dcn_wire_tier="int4", period=8,
    )
    # 2-gateway ring = 2 directed cross edges; amortized over the period
    assert ws["dcn_wire_bytes_per_step"] == pytest.approx(
        ws["dcn_wire_bytes_per_event"] / 8
    )
    assert ws["flat_cross_pod_edges"] > 0
    assert ws["dcn_cut_ratio"] >= 8.0


def test_dcn_cut_at_matched_consensus_rate():
    """Against the cheapest flat Exp2 opponent that still contracts at
    least as fast as the federated fabric (flat gossip every k-th step,
    k the largest keeping its measured per-step rate no weaker), the
    federated fabric's period-chosen int4 DCN leg ships >= 8x fewer
    cross-pod bytes per step."""
    n = 16
    layout = fed.parse_pods("2x8", n)
    period = fed.choose_dcn_period(layout, 0.985)["period"]
    measured_fed = fed.simulate_consensus(
        [(n, fed.intra_edges(layout))] * period
        + [(n, fed.inter_edges(layout))],
        steps=max(4, 256 // period), comm_steps_per_cycle=period,
    )
    measured_flat = fed.simulate_consensus(
        [(n, fleetsim.base_edges(n, "exp2"))], steps=64
    )
    assert 0 < measured_flat < measured_fed < 1
    k = max(1, int(np.floor(np.log(measured_flat) / np.log(measured_fed))))
    # the matched flat opponent is at least as strong
    assert measured_flat ** (1.0 / k) <= measured_fed + 1e-6
    ws = fed.wire_summary(
        layout, 1 << 20, itemsize=4, ici_wire=None,
        dcn_wire_tier="int4", period=period,
    )
    cut = ws["flat_dcn_bytes_per_step"] / k / ws["dcn_wire_bytes_per_step"]
    assert cut >= 8.0, (cut, k, period)


def test_federation_report_renders_the_live_block(tmp_path, monkeypatch,
                                                  capsys):
    """tools/federation_report.py renders a health dump's federation
    block: pods, gateways, DCN period and wire, predicted rate."""
    import json

    from tools import federation_report

    monkeypatch.setenv(fed.PODS_ENV, "2x8")
    monkeypatch.setenv(fed.DCN_PERIOD_ENV, "4")
    block = fed.get_fabric(16).to_json()
    dump = tmp_path / "health.json"
    dump.write_text(json.dumps({"federation": block}))
    assert federation_report.main(["--health", str(dump), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["live"] == block
    assert federation_report.main(["--health", str(dump)]) == 0
    out = capsys.readouterr().out
    assert "2 pods over 16 ranks" in out and "DCN every 4 steps" in out
    assert federation_report.main([]) == 2


# -- CommPlan lowering / link classes -----------------------------------------


def test_intra_plan_link_class_ici():
    layout = fed.parse_pods("2x8", 16)
    plan = fed.intra_plan(layout)
    assert plan.compile_info is not None
    assert plan.compile_info.link_class == "ici"


def test_inter_plan_link_class_dcn():
    layout = fed.parse_pods("2x8", 16)
    plan = fed.inter_plan(layout)
    assert plan.compile_info is not None
    assert plan.compile_info.link_class == "dcn"


# -- fabric lifecycle ---------------------------------------------------------


def test_get_fabric_disabled_is_none(monkeypatch):
    monkeypatch.delenv(fed.PODS_ENV, raising=False)
    assert fed.enabled() is False
    assert fed.get_fabric(16) is None


def test_get_fabric_env_signature_cache(monkeypatch):
    monkeypatch.setenv(fed.PODS_ENV, "2x8")
    monkeypatch.setenv(fed.DCN_PERIOD_ENV, "4")
    fab = fed.get_fabric(16)
    assert fab is not None and fab.period == 4
    assert fed.get_fabric(16) is fab  # cached
    monkeypatch.setenv(fed.DCN_PERIOD_ENV, "8")
    fab2 = fed.get_fabric(16)
    assert fab2 is not fab and fab2.period == 8


def test_fabric_dcn_step_cadence(monkeypatch):
    monkeypatch.setenv(fed.PODS_ENV, "2")
    monkeypatch.setenv(fed.DCN_PERIOD_ENV, "4")
    fab = fed.get_fabric(16)
    assert [fab.dcn_step(c) for c in range(6)] == [
        True, False, False, False, True, False,
    ]


def test_fabric_to_json(monkeypatch):
    monkeypatch.setenv(fed.PODS_ENV, "2x8")
    fab = fed.get_fabric(16)
    doc = fab.to_json()
    assert doc["layout"]["n_pods"] == 2
    assert doc["gateways"] == [0, 8]
    assert 0.0 < doc["predicted_rate"] < 1.0


# -- placement route/congestion under multi-pod layouts (satellite) ----------


def test_gateway_routes_never_relay_through_foreign_pod():
    """A DCN leg between adjacent gateways must not transit a third
    pod: under the serpentine ring route model the gateway ring's
    relay chains stay inside the two endpoint pods."""
    layout = fed.parse_pods("4x16", 64)
    gws = layout.gateways()
    ring = list(zip(gws, gws[1:] + gws[:1]))
    for s, d in ring:
        chain = placement.route_ranks(s, d, 64)
        pods_ok = {layout.pod_of(s), layout.pod_of(d)}
        for m in chain:
            assert layout.pod_of(m) in pods_ok, (s, d, m, chain)


def test_inter_ring_congestion_one():
    """Adjacent-gateway routes are disjoint ring segments, so the
    whole gateway round serializes nothing: congestion 1."""
    layout = fed.parse_pods("4x16", 64)
    gws = layout.gateways()
    perm = list(zip(gws, gws[1:] + gws[:1]))
    assert placement.perm_congestion(perm, 64) == 1


def test_intra_routes_stay_in_pod():
    layout = fed.parse_pods("4x16", 64)
    for (i, j) in fed.intra_edges(layout, kind="exp2"):
        if i == j:
            continue
        for m in placement.route_ranks(i, j, 64):
            assert layout.pod_of(m) == layout.pod_of(i), (i, j, m)


def test_pods_misaligned_with_torus_warns(monkeypatch):
    monkeypatch.setenv("BLUEFOG_TORUS_DIMS", "4,4")
    key = "pods-torus-misaligned-16"
    logging_util._warned_once.discard(key)
    fed.parse_pods("0-5,6-15", 16)
    assert key in logging_util._warned_once


# -- torus-dims declaration (satellite regression) ---------------------------


def test_torus_dims_product_mismatch_warns_and_undeclares(monkeypatch):
    monkeypatch.setenv("BLUEFOG_TORUS_DIMS", "4,8")
    key = "torus-dims-mismatch-16"
    logging_util._warned_once.discard(key)
    assert placement.declared_torus_dims(16) is None
    assert key in logging_util._warned_once
    # degrade-and-continue: the second call is silent, same verdict
    n = len(logging_util._warned_once)
    assert placement.declared_torus_dims(16) is None
    assert len(logging_util._warned_once) == n


def test_torus_dims_matching_product_accepted(monkeypatch):
    monkeypatch.setenv("BLUEFOG_TORUS_DIMS", "4,4")
    assert placement.declared_torus_dims(16) == (4, 4)


# -- loss classification / federated fleetsim ---------------------------------


def test_classify_loss_classes():
    layout = fed.parse_pods("4x16", 64)
    assert fleetsim.classify_loss([], 64)["loss_class"] == "none"
    assert fleetsim.classify_loss([3], 64)["loss_class"] == "churn"
    pod1 = list(range(16, 32))
    out = fleetsim.classify_loss(pod1, 64, layout)
    assert out["loss_class"] == "pod_loss"
    assert out["pods_lost"] == [1]
    region = fleetsim.classify_loss(list(range(8, 16)), 64)
    assert region["loss_class"] == "region_loss"
    assert region["region"] == [8, 15]
    scattered = fleetsim.classify_loss(
        list(range(0, 64, 9)), 64
    )
    assert scattered["loss_class"] == "storm"


def test_federated_fleet_pod_loss_one_event():
    layout = fed.parse_pods("4x16", 64)
    plan = fleetsim.region_plan(64, 16, 32, step=3)
    ff = fed.FederatedFleet(layout, plan=plan, audit_edges=True, seed=0)
    ff.run(8)
    s = ff.summary()
    assert s["repairs"] == 1
    assert s["stale_dispatches"] == 0
    assert s["live"] == 48
    repairs = [
        e for e in ff.fleet.events if e["metric"] == "fleetsim_repair"
    ]
    assert len(repairs) == 1
    assert repairs[0]["loss_class"] == "pod_loss"
    assert repairs[0]["pods_lost"] == [1]
    assert repairs[0]["gateway_change"] is True
    assert s["federation"]["gateways"] == [0, 32, 48]


def test_federated_fleet_gateway_kill_reelects():
    from bluefog_tpu.elastic.faults import Fault, FaultPlan

    layout = fed.parse_pods("4x16", 64)
    plan = FaultPlan([Fault(kind="kill", rank=16, step=2)])
    ff = fed.FederatedFleet(layout, plan=plan, audit_edges=True, seed=0)
    ff.run(5)
    s = ff.summary()
    assert s["stale_dispatches"] == 0
    assert s["federation"]["gateways"] == [0, 17, 32, 48]


# -- optimizer dispatch (device tier) -----------------------------------------


@pytest.fixture
def fresh_context(cpu_devices):
    bf.init(devices=cpu_devices[:SIZE])
    yield bf.get_context()
    bf.shutdown()


def _na_opt(**kw):
    return bf.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), **kw
    )


def test_flat_key_bitwise_pin(fresh_context, monkeypatch):
    """BLUEFOG_PODS unset dispatches the bitwise-identical pre-PR
    program: the gossip key is the plain ("na", ...) tuple the flat
    path always produced — no federation marker anywhere in it."""
    monkeypatch.delenv(fed.PODS_ENV, raising=False)
    from bluefog_tpu.collective import ops as col_ops

    ctx = fresh_context
    opt = _na_opt()
    key, _fn, wops = opt._gossip_key_and_fn(ctx)
    plan = col_ops._resolve_plan(ctx, None, None, None, True)
    info = plan.compile_info
    assert key == (
        "na", plan.perms, 1, info.inject if info else None,
    )
    assert len(wops) == 2
    assert "fed" not in key


def test_fed_key_shapes(fresh_context, monkeypatch):
    monkeypatch.setenv(fed.PODS_ENV, "2")
    monkeypatch.setenv(fed.DCN_PERIOD_ENV, "4")
    ctx = fresh_context
    opt = _na_opt()
    key, _fn, wops = opt._gossip_key_and_fn(ctx)
    # comm_count 0 -> DCN step: both legs in the key, exact wires
    assert key[:3] == ("fed", "dcn", None)
    assert key[6] == "int4"  # default DCN tier
    assert len(wops) == 3  # self_w, recv_w, inter_recv (quantized leg)
    opt._comm_count = 1
    key2, _fn2, wops2 = opt._gossip_key_and_fn(ctx)
    assert key2[:3] == ("fed", "ici", None)
    assert len(wops2) == 2
    assert opt._last_plan is not None
    assert opt._last_plan.compile_info.link_class == "ici"


def test_fed_dispatch_preserves_mean_and_mixes(fresh_context,
                                               monkeypatch):
    monkeypatch.setenv(fed.PODS_ENV, "2")
    monkeypatch.setenv(fed.DCN_PERIOD_ENV, "2")
    opt = _na_opt()
    params = {"w": bf.worker_values(lambda r: jnp.full((16,), float(r)))}
    state = opt.init(params)
    step = bf.make_train_step(
        opt, lambda p, b: jnp.sum(p["w"] ** 2) * 0.0
    )
    w0 = np.asarray(params["w"])
    spread0 = float(w0.mean(1).max() - w0.mean(1).min())
    for _ in range(12):
        params, state, _loss = step(params, state, None)
    w = np.asarray(params["w"])
    assert np.isclose(float(w.mean()), (SIZE - 1) / 2.0, atol=1e-4)
    spread = float(w.mean(1).max() - w.mean(1).min())
    assert spread < 0.35 * spread0, (spread0, spread)


def test_fed_counters_reconcile(fresh_context, monkeypatch):
    monkeypatch.setenv(fed.PODS_ENV, "2")
    monkeypatch.setenv(fed.DCN_PERIOD_ENV, "4")
    monkeypatch.setenv("BLUEFOG_METRICS", "1")
    from bluefog_tpu import metrics as metrics_mod

    base = metrics_mod.snapshot()

    def delta(name):
        v = metrics_mod.snapshot().get(name, {}).get("value", 0.0)
        return v - base.get(name, {}).get("value", 0.0)

    opt = _na_opt()
    params = {"w": bf.worker_values(lambda r: jnp.full((64,), float(r)))}
    state = opt.init(params)
    step = bf.make_train_step(
        opt, lambda p, b: jnp.sum(p["w"] ** 2) * 0.0
    )
    for _ in range(8):
        params, state, _loss = step(params, state, None)
    ici = delta("bluefog.federation.ici_wire_bytes")
    dcn = delta("bluefog.federation.dcn_wire_bytes")
    total = delta("bluefog.wire_bytes")
    assert ici > 0 and dcn > 0
    assert total == ici + dcn
    # 8 steps at period 4 = 2 DCN events; the DCN leg ships the int4
    # payload only on those
    assert dcn < ici


def test_fed_ef_wire_falls_back_memoryless(fresh_context, monkeypatch):
    monkeypatch.setenv(fed.PODS_ENV, "2")
    logging_util._warned_once.discard("fed-ef-wire")
    ctx = fresh_context
    opt = _na_opt()
    opt.compression = "int8_ef"
    key, _fn, _wops = opt._gossip_key_and_fn(ctx)
    assert key[2] == "int8"  # memoryless base tier
    assert "fed-ef-wire" in logging_util._warned_once
    # _plan_step must not allocate CHOCO state on a fed key
    params = {"w": bf.worker_values(lambda r: jnp.zeros((8,)))}
    plan, _state = opt._plan_step(ctx, params, None, True)
    assert plan.ef is False


def test_flat_run_after_fed_env_removed(fresh_context, monkeypatch):
    """The fabric cache keys on the env signature: unsetting
    BLUEFOG_PODS mid-process restores the flat dispatch."""
    monkeypatch.setenv(fed.PODS_ENV, "2")
    ctx = fresh_context
    opt = _na_opt()
    key, _f, _w = opt._gossip_key_and_fn(ctx)
    assert key[0] == "fed"
    monkeypatch.delenv(fed.PODS_ENV)
    key2, _f2, _w2 = opt._gossip_key_and_fn(ctx)
    assert key2[0] == "na"
