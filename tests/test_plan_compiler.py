# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Comm-plan compiler coverage: minimal round packing, preserved fast
path, and semantic equivalence.

Three layers of proof, mirroring the repo's HLO-verification style
(test_fusion.py):

- *structural*: the edge-coloring pass hits the König bound
  ``max(max_in_degree, max_out_degree)`` on fuzzed digraphs, every round
  is a partial permutation, and circulant topologies keep their
  byte-identical offset-grouped lowering;
- *compiled*: the optimized HLO for star / mesh2d / sparse random
  digraphs contains exactly the bound's number of ``collective-permute``
  instructions (the naive lowering emits up to N-1);
- *semantic*: ``weighted_combine`` over the optimized plan is EXACTLY the
  naive plan's result. Round re-packing permutes the order of per-receiver
  additions, so genuine float inputs could differ in the last ulp without
  meaning anything; the equality tests therefore use dyadic-rational
  weights and integer-valued inputs, for which every product and partial
  sum is exactly representable — bitwise equality then PROVES semantic
  equivalence rather than sampling it.
"""

import functools

import numpy as np
import networkx as nx
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import bluefog_tpu.topology as topo
from bluefog_tpu import scaling
from bluefog_tpu.collective import compiler, inner, plan as planlib

SIZE = 8
AXIS = "workers"


def run_spmd(fn, *arrays, out_specs=P(AXIS)):
    m = jax.make_mesh((SIZE,), (AXIS,))
    wrapped = jax.jit(
        jax.shard_map(
            fn, mesh=m, in_specs=tuple(P(AXIS) for _ in arrays),
            out_specs=out_specs,
        )
    )
    return wrapped(*arrays)


def random_edges(rng, size):
    all_edges = [
        (i, j) for i in range(size) for j in range(size) if i != j
    ]
    k = rng.randint(0, len(all_edges) + 1)
    idx = rng.choice(len(all_edges), size=k, replace=False)
    return [all_edges[i] for i in idx]


# -- structural --------------------------------------------------------------


def test_coloring_meets_koenig_bound_fuzzed():
    rng = np.random.RandomState(0)
    for _ in range(100):
        size = rng.randint(2, 17)
        edges = random_edges(rng, size)
        perms = compiler.coloring_perms(edges, size)
        assert len(perms) == compiler.min_rounds(edges, size)
        # partition + partial-permutation invariants (also asserted
        # inside the pass; re-checked here from the public result)
        flat = [e for p in perms for e in p]
        assert sorted(flat) == sorted(set(map(tuple, edges)))
        for p in perms:
            assert len({s for s, _ in p}) == len(p)
            assert len({d for _, d in p}) == len(p)


def test_auto_never_worse_than_offset_and_reaches_bound():
    rng = np.random.RandomState(1)
    for _ in range(50):
        size = rng.randint(2, 17)
        edges = random_edges(rng, size)
        res = compiler.compile_edges(edges, size)
        assert res.lower_bound <= res.rounds <= res.offset_rounds
        # auto must always land ON the bound: either offsets already
        # meet it or the coloring is taken
        assert res.rounds == res.lower_bound or not edges


@pytest.mark.parametrize(
    "make,rounds",
    [
        (topo.ExponentialTwoGraph, 3),
        (topo.RingGraph, 2),  # offsets {+1, -1}; the self loop is no round
        (topo.FullyConnectedGraph, 7),
    ],
    ids=["exp2", "ring", "full"],
)
def test_circulant_topologies_keep_offset_fast_path(make, rounds):
    g = make(SIZE)
    plan = planlib.plan_from_topology(g, weighted=True)
    assert plan.compile_info.method == "offset"
    assert len(plan.rounds) == rounds
    # circulant rounds are FULL permutations riding ICI
    assert all(len(r.perm) == SIZE for r in plan.rounds)
    naive = planlib.plan_from_topology(g, weighted=True, method="offset")
    assert plan.perms == naive.perms


def test_compile_cache_dedupes_repeated_lowerings():
    edges = [(0, 1), (2, 1), (3, 1), (1, 5), (4, 5)]
    a = compiler.compile_edges(edges, SIZE)
    b = compiler.compile_edges(list(reversed(edges)), SIZE)
    assert a is b  # canonical edge set -> one host-side compile


def test_forced_methods_and_cost_model():
    edges = [(0, 1), (2, 1), (3, 1), (1, 5), (4, 5), (6, 2), (7, 3)]
    auto = compiler.compile_edges(edges, SIZE)
    off = compiler.compile_edges(edges, SIZE, method="offset")
    col = compiler.compile_edges(edges, SIZE, method="coloring")
    assert off.method == "offset" and off.rounds == off.offset_rounds
    assert col.rounds == col.lower_bound
    assert auto.method == "coloring" and auto.perms == col.perms
    # cost model: strictly fewer rounds -> strictly cheaper plan
    assert auto.predicted_cost_s < auto.offset_cost_s
    payload = 1024
    assert scaling.plan_cost_s(2, payload) == pytest.approx(
        2 * (scaling.ROUND_ALPHA_S + payload / scaling.ICI_LINK_BYTES_PER_S)
    )


@pytest.mark.parametrize("degree", [2, 6, 7])
def test_random_regular_digraph_properties(degree):
    """Sparse degrees come from rejection sampling; dense degrees (the
    rejection-hostile regime, up to the complete digraph) from the
    coloring-based completion — both must produce exact regularity."""
    g = topo.RandomRegularDigraph(SIZE, degree, seed=3)
    w = nx.to_numpy_array(g)
    off_diag = (w != 0) & ~np.eye(SIZE, dtype=bool)
    assert (off_diag.sum(1) == degree).all()
    assert (off_diag.sum(0) == degree).all()
    np.testing.assert_allclose(w.sum(0), 1.0, atol=1e-12)
    np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-12)


# -- compiled (HLO round-count regression) -----------------------------------


@pytest.mark.parametrize(
    "name,make",
    [
        ("star", lambda: topo.StarGraph(SIZE)),
        ("mesh2d", lambda: topo.MeshGrid2DGraph(SIZE)),
        ("random_d2", lambda: topo.RandomRegularDigraph(SIZE, 2, seed=3)),
        ("random_d3", lambda: topo.RandomRegularDigraph(SIZE, 3, seed=1)),
        ("exp2", lambda: topo.ExponentialTwoGraph(SIZE)),
    ],
)
def test_optimized_hlo_emits_bound_many_permutes(name, make):
    """The compiled program for an optimized plan contains exactly the
    König bound's number of collective-permutes."""
    plan = planlib.plan_from_topology(make(), weighted=True)
    info = plan.compile_info
    stats = scaling.gossip_comm_stats(plan, 256)
    cp = stats.get("collective-permute", {"count": 0})
    assert cp["count"] == info.lower_bound, (name, stats, info)
    assert cp["count"] <= info.offset_rounds


@pytest.mark.parametrize("degree,seed", [(2, 3), (3, 1)])
def test_random_digraph_hlo_beats_naive_lowering(degree, seed):
    """A random degree-d digraph lowers to exactly its König bound of d
    rounds, checked in the compiled HLO; the offset lowering takes more."""
    g = topo.RandomRegularDigraph(SIZE, degree, seed=seed)
    optimized = planlib.plan_from_topology(g, weighted=True)
    naive = planlib.plan_from_topology(g, weighted=True, method="offset")
    assert optimized.compile_info.method == "coloring"
    assert optimized.compile_info.lower_bound == degree
    assert len(optimized.rounds) == degree < len(naive.rounds)
    n_stats = scaling.gossip_comm_stats(naive, 256)
    o_stats = scaling.gossip_comm_stats(optimized, 256)
    assert o_stats["collective-permute"]["count"] == degree
    assert (
        n_stats["collective-permute"]["count"] == len(naive.rounds) > degree
    )


def test_gossip_comm_stats_plan_summary():
    g = topo.RandomRegularDigraph(SIZE, 2, seed=3)
    plan = planlib.plan_from_topology(g, weighted=True)
    stats = scaling.gossip_comm_stats(plan, 256, include_plan=True)
    summary = stats["plan"]
    assert summary["rounds"] == 2
    assert summary["decomposition"] == "coloring"
    assert summary["naive_rounds"] > 2 and summary["lower_bound"] == 2
    assert summary["predicted_cost_us"] < summary["naive_cost_us"]
    # default shape untouched: every non-plan entry is {count, bytes}
    plain = scaling.gossip_comm_stats(plan, 256)
    assert "plan" not in plain


# -- semantic equivalence ----------------------------------------------------


def dyadic_matrix(rng, size):
    """Random combine matrix with dyadic-rational entries (k/64) so the
    f32 combine arithmetic is exact regardless of summation order."""
    w = rng.randint(-64, 65, size=(size, size)).astype(np.float64) / 64.0
    mask = rng.rand(size, size) < 0.5
    np.fill_diagonal(mask, True)
    return np.where(mask, w, 0.0)


def combine(plan, x):
    got = run_spmd(
        functools.partial(
            inner.weighted_combine, plan=plan, axis_name=AXIS
        ),
        x,
    )
    return np.asarray(got)


def test_optimized_combine_bitwise_equals_naive():
    """Exact (same dtype path) equality on randomized weight matrices,
    including zero-weighted declared edges."""
    rng = np.random.RandomState(7)
    for trial in range(10):
        w = dyadic_matrix(rng, SIZE)
        # declare EVERY off-diagonal position an edge, including the
        # zero-weighted ones — pattern membership must not depend on the
        # weight value
        edges = [
            (i, j) for i in range(SIZE) for j in range(SIZE) if i != j
        ]
        naive = planlib.plan_from_matrix(w, edges=edges, method="offset")
        opt = planlib.plan_from_matrix(w, edges=edges, method="coloring")
        np.testing.assert_array_equal(
            naive.weight_matrix(), opt.weight_matrix()
        )
        x = rng.randint(-8, 9, size=(SIZE, 16)).astype(np.float32)
        got_naive, got_opt = combine(naive, x), combine(opt, x)
        assert got_naive.dtype == got_opt.dtype == np.float32
        np.testing.assert_array_equal(got_naive, got_opt), trial


def test_optimized_combine_sparse_auto_wins_and_matches():
    rng = np.random.RandomState(11)
    for trial in range(10):
        g = topo.RandomRegularDigraph(SIZE, 2, seed=100 + trial)
        adj = (nx.to_numpy_array(g) != 0) & ~np.eye(SIZE, dtype=bool)
        w = np.where(adj, dyadic_matrix(rng, SIZE), 0.0)
        np.fill_diagonal(w, rng.randint(-64, 65, SIZE) / 64.0)
        edges = [tuple(e) for e in zip(*np.nonzero(adj))]
        naive = planlib.plan_from_matrix(w, edges=edges, method="offset")
        auto = planlib.plan_from_matrix(w, edges=edges)
        assert len(auto.rounds) <= len(naive.rounds)
        x = rng.randint(-8, 9, size=(SIZE, 4)).astype(np.float32)
        np.testing.assert_array_equal(combine(naive, x), combine(auto, x))


def test_dynamic_schedule_offset_vs_coloring_identical():
    """Dynamic schedules lower per-step through the same compiler; the
    mass-conserving one-peer schedule has purely dyadic weights (0.5 /
    1.0), so offset and coloring plans must agree bitwise step by step."""
    g = topo.ExponentialTwoGraph(SIZE)
    mk = lambda method: planlib.schedule_from_dynamic(
        SIZE,
        lambda r: topo.GetDynamicOnePeerSendRecvRanks(g, r),
        self_weight=0.5,
        uniform=False,
        method=method,
    )
    s_off, s_col = mk("offset"), mk("coloring")
    assert s_off.period == s_col.period
    rng = np.random.RandomState(13)
    x = rng.randint(-8, 9, size=(SIZE, 4)).astype(np.float32)
    for p_off, p_col in zip(s_off.plans, s_col.plans):
        np.testing.assert_array_equal(
            p_off.weight_matrix(), p_col.weight_matrix()
        )
        np.testing.assert_array_equal(combine(p_off, x), combine(p_col, x))


def test_dynamic_schedule_uniform_close():
    """Uniform one-peer weights (1/(deg+1)) are not dyadic, so the
    guarantee is weight-matrix identity plus tight numeric agreement."""
    g = topo.ExponentialTwoGraph(SIZE)
    mk = lambda method: planlib.schedule_from_dynamic(
        SIZE,
        lambda r: topo.GetDynamicOnePeerSendRecvRanks(g, r),
        method=method,
    )
    s_off, s_col = mk("offset"), mk("coloring")
    x = np.random.RandomState(17).randn(SIZE, 4).astype(np.float32)
    for p_off, p_col in zip(s_off.plans, s_col.plans):
        np.testing.assert_array_equal(
            p_off.weight_matrix(), p_col.weight_matrix()
        )
        np.testing.assert_allclose(
            combine(p_off, x), combine(p_col, x), rtol=1e-6, atol=1e-6
        )


def test_windows_on_irregular_topology_use_packed_rounds():
    """The window subsystem lowers its put/get patterns through the same
    compiler; semantics (buffer contents) must be decomposition-blind."""
    import bluefog_tpu as bf

    bf.init(devices=jax.devices("cpu")[:SIZE])
    try:
        g = topo.RandomRegularDigraph(SIZE, 2, seed=3)
        bf.set_topology(g)
        x = bf.worker_values(lambda r: np.full((3,), float(r), np.float32))
        assert bf.win_create(x, "plan_test")
        bf.win_put(name="plan_test")
        adj = (nx.to_numpy_array(g) != 0) & ~np.eye(SIZE, dtype=bool)
        expected = np.zeros((SIZE, 3))
        for j in range(SIZE):
            srcs = sorted(np.nonzero(adj[:, j])[0])
            deg = len(srcs)
            # default win_update: uniform 1/(deg+1) over self + buffers,
            # each buffer holding dst_weight(=1.0) * src value
            expected[j] = (j + sum(srcs)) / (deg + 1.0)
        got = np.asarray(bf.win_update(name="plan_test"))
        np.testing.assert_allclose(got, expected, rtol=1e-6)
    finally:
        bf.win_free()
        bf.shutdown()


# -- bandwidth family: chunked / short-cut / Pareto chooser ------------------


@pytest.fixture
def clean_cost_model():
    """Calibration is process-global; tests that install one must not
    leak it into the class-constant assertions elsewhere."""
    compiler.clear_calibration()
    yield
    compiler.clear_calibration()


def test_chunk_bounds_512_aligned_and_covering():
    for n, k in ((4096, 4), (4097, 4), (513, 8), (1 << 20, 64), (511, 3)):
        bounds = inner.chunk_bounds(n, k)
        assert bounds[0][0] == 0 and bounds[-1][1] == n
        for (a0, b0), (a1, b1) in zip(bounds, bounds[1:]):
            assert b0 == a1
        for a, b in bounds[:-1]:
            assert (b - a) % 512 == 0, (n, k, bounds)
        assert len(bounds) <= max(1, k) + 1
    assert inner.chunk_bounds(256, 8) == [(0, 256)]  # sub-grid payload
    assert inner.chunk_bounds(4096, 1) == [(0, 4096)]


def test_shortcut_perms_structure_fuzzed():
    """Relay schedules on fuzzed digraphs: every round is a partial
    permutation of UNIT hops (ring-adjacent under the default fabric),
    chains occupy consecutive rounds, and the compiler's built-in relay
    simulation (delivery correctness) passes — shortcut_perms raises
    otherwise."""
    rng = np.random.RandomState(5)
    for _ in range(25):
        size = rng.randint(3, 12)
        edges = random_edges(rng, size)
        if not edges:
            continue
        perms, inject, delivery = compiler.shortcut_perms(edges, size)
        assert sorted(e for e, _ in delivery) == sorted(set(edges))
        for perm in perms:
            for s, d in perm:
                assert (d - s) % size in (1, size - 1), (s, d, size)
        for r, inj in enumerate(inject):
            assert set(inj) <= {s for s, _ in perms[r]}


def test_shortcut_combine_bitwise_dyadic():
    """Short-cut relay lowering == offset lowering to the bit under
    dyadic weights / integer inputs (the repo's exactness scheme for
    cross-decomposition equivalence)."""
    rng = np.random.RandomState(23)
    edges = [(i, j) for i in range(SIZE) for j in range(SIZE) if i != j]
    for _ in range(5):
        w = dyadic_matrix(rng, SIZE)
        naive = planlib.plan_from_matrix(w, edges=edges, method="offset")
        sc = planlib.plan_from_matrix(w, edges=edges, method="shortcut")
        assert sc.compile_info.route == "shortcut"
        assert sc.compile_info.inject is not None
        np.testing.assert_array_equal(
            naive.weight_matrix(), sc.weight_matrix()
        )
        x = rng.randint(-8, 9, size=(SIZE, 16)).astype(np.float32)
        np.testing.assert_array_equal(combine(naive, x), combine(sc, x))


def test_shortcut_neighbor_relations_and_allgather():
    """in/out-neighbors and gather slots of a short-cut plan come from
    the DELIVERY table (relay pairs are transport, not neighbors), so
    neighbor_allgather returns exactly the direct plan's output."""
    g = topo.RandomRegularDigraph(SIZE, 2, seed=3)
    direct = planlib.plan_from_topology(g, weighted=True)
    sc = planlib.plan_from_topology(g, weighted=True, method="shortcut")
    assert sc.in_neighbors == direct.in_neighbors
    assert sc.out_neighbors == direct.out_neighbors
    x = np.random.RandomState(3).randn(SIZE, 64).astype(np.float32)
    ga = run_spmd(
        lambda t: inner.neighbor_allgather(t, sc, AXIS), x,
        out_specs=(P(AXIS), P(AXIS)),
    )
    gb = run_spmd(
        lambda t: inner.neighbor_allgather(t, direct, AXIS), x,
        out_specs=(P(AXIS), P(AXIS)),
    )
    np.testing.assert_array_equal(np.asarray(ga[0]), np.asarray(gb[0]))
    np.testing.assert_array_equal(np.asarray(ga[1]), np.asarray(gb[1]))


@pytest.mark.parametrize("elems", [4096, 8192 + 1536])
def test_chunked_combine_bitwise_all_wires(elems):
    """Chunked == monolithic to the BIT for arbitrary float inputs, for
    the exact combine and both memoryless quantized wires (chunk bounds
    snap to the 512-element scale grid; the exact path concatenates
    received chunks back to full width before the accumulate so the
    arithmetic graph is shape-identical)."""
    g = topo.RandomRegularDigraph(SIZE, 2, seed=3)
    plan = planlib.plan_from_topology(g, weighted=True)
    x = np.random.RandomState(11).randn(SIZE, elems).astype(np.float32)

    base = combine(plan, x)
    for k in (2, 4, 8):
        got = np.asarray(run_spmd(
            functools.partial(
                inner.weighted_combine, plan=plan, axis_name=AXIS, chunks=k
            ), x,
        ))
        np.testing.assert_array_equal(base, got), k
    for wire in ("int8", "bf16", "int4"):
        qbase = np.asarray(run_spmd(
            functools.partial(
                inner.weighted_combine_quantized, plan=plan,
                axis_name=AXIS, wire=wire,
            ), x,
        ))
        for k in (2, 4):
            got = np.asarray(run_spmd(
                functools.partial(
                    inner.weighted_combine_quantized, plan=plan,
                    axis_name=AXIS, wire=wire, chunks=k,
                ), x,
            ))
            np.testing.assert_array_equal(qbase, got), (wire, k)


@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_chunked_ef_bitwise_output_and_state(wire):
    """int8_ef / int4_ef chunked == monolithic for output AND both CHOCO
    copies: the state is positional over the flat payload and slices
    with it (int4 additionally pins that per-chunk nibble-pack slices
    are whole scale groups)."""
    import jax.numpy as jnp

    g = topo.RandomRegularDigraph(SIZE, 2, seed=3)
    plan = planlib.plan_from_topology(g, weighted=True)
    perms = plan.perms
    _sw, recv_w = plan.weight_operands()
    elems = 4096
    x = np.random.RandomState(13).randn(SIZE, elems).astype(np.float32)
    e_self = np.random.RandomState(14).randn(SIZE, elems).astype(
        np.float32
    ) * 0.01
    e_recv = np.zeros((SIZE, len(perms), elems), np.float32)

    def run(chunks):
        def body(t, es, er):
            y, (es2, er2) = inner.weighted_combine_quantized_ef_operands(
                t, (es[0], er[0]), perms, jnp.asarray(recv_w), AXIS,
                chunks=chunks, wire=wire,
            )
            return y, jnp.expand_dims(es2, 0), jnp.expand_dims(er2, 0)
        out = run_spmd(
            body, x, e_self, e_recv, out_specs=(P(AXIS), P(AXIS), P(AXIS))
        )
        return [np.asarray(o) for o in out]

    y1, s1, r1 = run(1)
    for k in (2, 4):
        yk, sk, rk = run(k)
        np.testing.assert_array_equal(y1, yk)
        np.testing.assert_array_equal(s1, sk)
        np.testing.assert_array_equal(r1, rk)


def test_choose_chunks_env_override_and_forced_methods(
    clean_cost_model, monkeypatch
):
    g = topo.RandomRegularDigraph(SIZE, 2, seed=3)
    compiled = planlib.plan_from_topology(g, weighted=True).compile_info
    big = 100 * 1024 * 1024
    monkeypatch.setenv("BLUEFOG_PLAN_CHUNKS", "4")
    assert compiler.choose_chunks(compiled, big, n_elems=big // 4) == 4
    # the override is capped so every chunk keeps a 512-elem scale group
    assert compiler.choose_chunks(compiled, 4096, n_elems=1024) == 2
    monkeypatch.setenv("BLUEFOG_PLAN_CHUNKS", "zero")
    with pytest.raises(ValueError):
        compiler.choose_chunks(compiled, big)
    monkeypatch.delenv("BLUEFOG_PLAN_CHUNKS")
    # forced structure methods pin k=1 (A/B isolation)
    for m in ("offset", "coloring", "shortcut"):
        assert compiler.choose_chunks(
            compiled, big, n_elems=big // 4, method=m
        ) == 1


# the payload sizes of the chunk chooser's sweep: 64 KiB to 100 MiB
SWEEP_BYTES = (64 * 1024, 1 << 20, 16 << 20, 100 * 1024 * 1024)


@pytest.mark.parametrize(
    "payload", SWEEP_BYTES, ids=["64KiB", "1MiB", "16MiB", "100MiB"]
)
def test_choose_chunks_pareto_crossover(clean_cost_model, payload):
    """On the degree-3 random digraph, at each payload of the sweep, the
    chooser picks at least one chunk, and exactly the cost model's
    cheapest power of two, under the class constants and under an
    installed calibration alike. Small payloads stay at the
    latency-optimal k=1, large payloads pipeline, and a calibration that
    measured NO pipelining (pipeline_eff=0) never chunks — the chooser
    can only pick what the fabric delivered."""
    g = topo.RandomRegularDigraph(SIZE, 3, seed=1)
    compiled = planlib.plan_from_topology(g, weighted=True).compile_info
    assert compiled.rounds == 3
    elems = payload // 4
    cong = compiled.congestion

    def cheapest_k():
        kmax = min(compiler.MAX_CHUNKS, elems // compiler.CHUNK_ALIGN_ELEMS)
        ks = [1]
        while ks[-1] * 2 <= kmax:
            ks.append(ks[-1] * 2)
        return min(
            ks, key=lambda k: (compiler.pipelined_cost_s(payload, k, cong), k)
        )

    k = compiler.choose_chunks(compiled, payload, n_elems=elems)
    assert k >= 1 and k == cheapest_k()
    if payload <= 64 * 1024:
        assert k == 1
    if payload >= 100 * 1024 * 1024:
        assert k > 1
        # chunked cost at the chosen k beats the serial plan cost
        assert compiler.pipelined_cost_s(payload, k, cong) < (
            compiler.pipelined_cost_s(payload, 1, cong)
        )
    # a calibration of the probe's shape (slower link, partial overlap)
    # moves the pick to its own cheapest k
    compiler.set_calibration(
        2e-5, 5e9, pipeline_eff=0.5, source="measured-probe"
    )
    k_cal = compiler.choose_chunks(compiled, payload, n_elems=elems)
    assert k_cal >= 1 and k_cal == cheapest_k()
    compiler.set_calibration(
        1e-3, 1e9, pipeline_eff=0.0, source="test"
    )
    assert compiler.choose_chunks(compiled, payload, n_elems=elems) == 1


def test_calibrate_probe_installs_measured_constants(clean_cost_model):
    """``calibrate()`` probes the suite's mesh and installs what it
    measured: positive alpha and beta, a pipelining share in [0, 1], and
    the chooser prices with them from then on."""
    cal = compiler.calibrate(
        force=True, small_elems=1024, large_elems=1 << 15, steps=2,
        windows=1,
    )
    assert cal["source"] == "measured-probe"
    assert cal["probe_devices"] == min(len(jax.devices()), 8)
    assert cal["alpha_s"] > 0 and cal["beta_bytes_per_s"] > 0
    assert 0.0 <= cal["pipeline_eff"] <= 1.0
    assert compiler.calibration() == cal
    # an installed calibration is honored, not re-probed
    assert compiler.calibrate() == cal
    g = topo.RandomRegularDigraph(SIZE, 3, seed=1)
    compiled = planlib.plan_from_topology(g, weighted=True).compile_info
    big = 100 * 1024 * 1024
    k, _cost = compiler.chunk_option(big, compiled.congestion, big // 4)
    assert compiler.choose_chunks(compiled, big, n_elems=big // 4) == k


def test_calibration_roundtrip(clean_cost_model):
    base = compiler.round_cost_s(1024)
    compiler.set_calibration(0.5, 1024.0, pipeline_eff=0.5, source="test")
    cal = compiler.calibration()
    assert cal["source"] == "test" and cal["alpha_s"] == 0.5
    assert compiler.round_cost_s(1024) == pytest.approx(0.5 + 1.0)
    compiler.clear_calibration()
    assert compiler.calibration()["source"] == "class-constants"
    assert compiler.round_cost_s(1024) == pytest.approx(base)


def test_compile_cache_distinguishes_method_and_fabric(monkeypatch):
    edges = [(0, 3), (3, 6), (6, 1), (1, 0)]
    a = compiler.compile_edges(edges, SIZE, method="coloring")
    b = compiler.compile_edges(edges, SIZE, method="shortcut")
    assert a is not b and b.route == "shortcut"
    monkeypatch.setenv("BLUEFOG_TORUS_DIMS", "2,4")
    c = compiler.compile_edges(edges, SIZE, method="shortcut")
    assert c is not b  # declared fabric joins the compile-cache key
    monkeypatch.delenv("BLUEFOG_TORUS_DIMS")


def test_torus_routes_and_congestion():
    from bluefog_tpu.topology import placement

    # declared 4x4 torus: serpentine neighbors are unit hops; a pair far
    # apart in ring order can be few torus hops
    dims = (4, 4)
    for i in range(15):
        assert placement.hop_distance(i, i + 1, 16, dims) == 1
    assert placement.hop_distance(0, 15, 16, dims) <= 2
    route = placement.route_ranks(0, 15, 16, dims)
    assert route[0] == 0 and route[-1] == 15
    # ring model: an offset-2 full permutation loads every link twice
    perm = tuple((i, (i + 2) % SIZE) for i in range(SIZE))
    assert placement.perm_congestion(perm, SIZE) == 2
    assert placement.perm_congestion(
        tuple((i, (i + 1) % SIZE) for i in range(SIZE)), SIZE
    ) == 1
    # BLUEFOG_TORUS_DIMS validation: wrong product is ignored
    assert placement.declared_torus_dims(16) is None


def test_eager_cache_keys_unique_per_chunk_and_route(monkeypatch):
    """ops-level: a chunk-count or route change dispatches its own
    compiled program (cache-key uniqueness), with identical results."""
    import bluefog_tpu as bf
    from bluefog_tpu import context as ctx_mod

    bf.init(devices=jax.devices("cpu")[:SIZE])
    try:
        g = topo.RandomRegularDigraph(SIZE, 2, seed=3)
        bf.set_topology(g)
        x = bf.worker_values(
            lambda r: np.random.RandomState(r).randn(2048).astype(
                np.float32
            )
        )
        ctx = ctx_mod.get_context()

        def na_keys():
            return {
                k for k in ctx.op_cache if k[0] == "neighbor_allreduce"
            }

        monkeypatch.setenv("BLUEFOG_PLAN_CHUNKS", "1")
        a = np.asarray(bf.neighbor_allreduce(x))
        monkeypatch.setenv("BLUEFOG_PLAN_CHUNKS", "2")
        b = np.asarray(bf.neighbor_allreduce(x))
        assert len(na_keys()) == 2, na_keys()
        np.testing.assert_array_equal(a, b)
        monkeypatch.delenv("BLUEFOG_PLAN_CHUNKS")
        monkeypatch.setenv("BLUEFOG_PLAN_METHOD", "shortcut")
        c = np.asarray(bf.neighbor_allreduce(x))
        assert len(na_keys()) == 3, na_keys()
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-5)
    finally:
        bf.shutdown()


@pytest.mark.parametrize("order", ["atc", "cta"])
@pytest.mark.parametrize(
    "wire", [None, "int8", "int8_ef", "int4", "int4_ef"]
)
def test_optimizer_chunked_trajectory_bitwise(order, wire, monkeypatch):
    """The acceptance pin: BLUEFOG_PLAN_CHUNKS=4 vs =1 optimizer
    trajectories are bitwise-identical for ATC/CTA x
    fp32/int8/int8_ef/int4/int4_ef (PR-2 buckets are the chunking
    grain; chunking is a schedule change, never a numerics change)."""
    import bluefog_tpu as bf
    import optax

    def run(chunks):
        monkeypatch.setenv("BLUEFOG_PLAN_CHUNKS", str(chunks))
        bf.init(devices=jax.devices("cpu")[:SIZE])
        try:
            bf.set_topology(topo.ExponentialTwoGraph(SIZE))
            factory = (
                bf.DistributedAdaptThenCombineOptimizer if order == "atc"
                else bf.DistributedAdaptWithCombineOptimizer
            )
            opt = factory(
                optax.sgd(0.1, momentum=0.9),
                bf.CommunicationType.neighbor_allreduce,
            )
            if wire is not None:
                opt.compression = wire
            rng = np.random.RandomState(0)
            params = {
                "w": bf.worker_values(
                    lambda r: rng.randn(2048).astype(np.float32)
                    + np.float32(r)
                )
            }
            state = opt.init(params)
            traj = []
            for step in range(3):
                grads = {
                    "w": params["w"] * np.float32(0.01 * (step + 1))
                }
                params, state = opt.step(params, state, grads)
                traj.append(np.asarray(params["w"]).copy())
            return traj
        finally:
            bf.shutdown()

    t1, t4 = run(1), run(4)
    for a, b in zip(t1, t4):
        np.testing.assert_array_equal(a, b)
