"""The reference's mixing matrices against hand-written ones and against
the program's own topology definitions (which passed their numpy oracles on
four chips, chip_smoke.phase_collectives, PR 21)."""

import networkx as nx
import numpy as np
import pytest

from bluefog_tpu import topology as tu

from benchmarks.harness import reference

import toy


def test_static_exp2_four_workers():
    third = 1 / 3
    want = np.array([
        [third, 0, third, third],   # 0 hears 0, 3 (0-1) and 2 (0-2)
        [third, third, 0, third],
        [third, third, third, 0],
        [0, third, third, third],
    ])
    np.testing.assert_allclose(reference.w_static_exp2(4), want)
    # W[i, j] is what i takes from j: the program's y = W_graph^T x
    graph = nx.to_numpy_array(tu.ExponentialTwoGraph(4))
    np.testing.assert_allclose(reference.w_static_exp2(4), graph.T)


@pytest.mark.parametrize("round_index, want", [
    (0, [[.5, 0, 0, .5], [.5, .5, 0, 0], [0, .5, .5, 0], [0, 0, .5, .5]]),
    (1, [[.5, 0, .5, 0], [0, .5, 0, .5], [.5, 0, .5, 0], [0, .5, 0, .5]]),
    (2, [[.5, 0, 0, .5], [.5, .5, 0, 0], [0, .5, .5, 0], [0, 0, .5, .5]]),
])
def test_one_peer_exp2_rounds(round_index, want):
    np.testing.assert_allclose(reference.w_one_peer_exp2(4, round_index), want)


def test_one_peer_exp2_matches_the_programs_generator():
    graph = tu.ExponentialTwoGraph(4)
    gens = [tu.GetDynamicOnePeerSendRecvRanks(graph, r) for r in range(4)]
    for round_index in range(4):
        w = np.zeros((4, 4))
        for rank, gen in enumerate(gens):
            _send, recv = next(gen)
            w[rank, rank] = 0.5
            for src in recv:
                w[rank, src] = 0.5 / len(recv)
        np.testing.assert_allclose(reference.w_one_peer_exp2(4, round_index), w)


def test_ring_and_hierarchical():
    np.testing.assert_allclose(
        reference.w_ring(4), nx.to_numpy_array(tu.RingGraph(4)).T
    )
    w = reference.w_hierarchical(4, 2, reference.w_ring(2))
    np.testing.assert_allclose(w, np.full((4, 4), 0.25))
    assert (reference.w_one_peer_exp2(1, 5) == np.ones((1, 1))).all()


def test_every_matrix_is_row_stochastic():
    for mix in (
        toy.traffic(), toy.traffic(topology="exp2"), toy.traffic(topology="ring"),
        toy.traffic(schedule="one_peer_exp2"),
        toy.traffic(optimizer="gradient_allreduce"),
        toy.traffic(optimizer="hierarchical", nodes_per_machine=2),
    ):
        for w in reference.mixing_matrices(mix, 4, 3, first_round=7):
            np.testing.assert_allclose(w.sum(axis=1), 1.0)


def test_compare_separates_a_wrong_update():
    import jax.numpy as jnp

    p0 = {"w": jnp.zeros((2, 8))}
    ref = {"w": jnp.ones((2, 8))}
    tol = toy.TOLERANCE
    ok, report = reference.compare([[1.0, 1.0]], [[1.0, 1.0]], ref, ref, p0, tol)
    assert ok and report["update_l2_err"] == [0.0, 0.0]
    off = {"w": jnp.ones((2, 8)).at[1].mul(1.1)}
    ok, report = reference.compare([[1.0, 1.0]], [[1.0, 1.0]], off, ref, p0, tol)
    assert not ok
    assert report["update_l2_err"] == pytest.approx([0.0, 0.1], abs=1e-6)
    ok, _ = reference.compare([[1.0, 1.1]], [[1.0, 1.0]], ref, ref, p0, tol)
    assert not ok
    ok, _ = reference.compare([[1.0, float("nan")]], [[1.0, 1.0]], ref, ref, p0, tol)
    assert not ok


@pytest.mark.parametrize("config, mix, workers", [
    (toy.RESNET, toy.traffic(schedule="one_peer_exp2", nodes_per_machine=2), 4),
    (toy.LM, toy.traffic(seq=64), 1),
], ids=["resnet-onepeer", "lm-local"])
def test_parameters_held_on_the_host_change_no_bit_of_the_report(
    config, mix, workers, monkeypatch
):
    """While the reference runs the program's parameters wait on the host
    (``bench.run_cell``, the ``reference`` span). Kept on the device, as
    before PR 33 — ``device_get`` made the identity, which makes the
    ``device_put`` that follows a no-op — the check reports the same
    numbers to the last bit."""
    import jax

    gets = []
    host_get = jax.device_get

    def counted(tree):
        gets.append(tree)
        return host_get(tree)

    monkeypatch.setattr(jax, "device_get", counted)
    result, info = toy.rehearse(config, mix, workers)
    assert len(gets) == 1  # the one copy to the host is the parameters'
    assert all(
        isinstance(leaf, jax.Array) for leaf in jax.tree_util.tree_leaves(gets[0])
    )
    monkeypatch.setattr(jax, "device_get", lambda tree: tree)
    on_device, info_on_device = toy.rehearse(config, mix, workers)
    assert info["reference"] == info_on_device["reference"]
    for name in ("loss_abs_err", "update_l2_err"):
        assert result["compared"][name] == on_device["compared"][name]
    assert result["correct"] and on_device["correct"]
    assert list(result)[-1] == "compared"
    assert result["compared"]["update_l2_err"] == [
        max(info["reference"]["update_l2_err"]), toy.TOLERANCE["update_l2"]
    ]

