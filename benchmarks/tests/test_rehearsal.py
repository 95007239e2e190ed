"""A CPU rehearsal of the *harness*: the job builders, the loop, the
reference check and the result line, at toy sizes on the 4-device CPU mesh
(as tests/test_chip_smoke.py does for the smoke). Nothing here is a speed:
``run.py`` itself refuses to run off the chip."""

import copy
import json
import time

import jax
import pytest

from benchmarks.harness import bench, reference

import toy

UNITS, rehearse = toy.UNITS, toy.rehearse


@pytest.mark.parametrize("config, mix, workers", [
    (toy.RESNET, toy.traffic(), 1),
    (toy.RESNET, toy.traffic(schedule="one_peer_exp2", nodes_per_machine=2), 4),
    (toy.RESNET, toy.traffic(optimizer="gradient_allreduce"), 4),
    (toy.RESNET, toy.traffic(optimizer="hierarchical", nodes_per_machine=2), 4),
    (toy.LM, toy.traffic(seq=64), 1),
    (toy.LM, toy.traffic(seq=64, topology="exp2"), 4),
    (toy.LM, toy.traffic(seq=48, topology="ring"), 4),
    (toy.LAID_OUT_LM, toy.traffic(seq=64, topology="exp2"), 4),
], ids=[
    "resnet-local", "resnet-onepeer", "resnet-allreduce", "resnet-hier",
    "lm-local", "lm-exp2", "lm-ring", "lm-laid-out-as-its-source",
])
def test_cell_runs_and_agrees_with_the_reference(config, mix, workers, monkeypatch):
    toy.jobs_here(monkeypatch)
    result, info = rehearse(config, mix, workers)
    assert result["correct"], info["reference"]
    assert result["failed"] == 0
    assert result["attempted"] >= bench.STEPS_PER_BLOCK
    assert result["attempted"] % bench.STEPS_PER_BLOCK == 0
    assert set(result["metrics"]) == set(UNITS)
    assert result["metrics"]["throughput_per_chip"]["value"] > 0
    assert result["device"]["count"] == workers
    assert info["compile_events"]["window"]["programs"] == 0
    assert info["counters_window"]["bluefog.recompiles"] == 0
    assert max(info["reference"]["update_l2_err"]) < 1e-4


def test_a_wrong_mixing_weight_turns_correct_false(monkeypatch):
    right = reference.w_one_peer_exp2

    def wrong(n, round_index):
        w = right(n, round_index)
        return w + 0.1 * ((w == 0.5) * (2 * (jax.numpy.eye(n) > 0) - 1))

    monkeypatch.setattr(reference, "w_one_peer_exp2", wrong)
    mix = toy.traffic(schedule="one_peer_exp2", nodes_per_machine=2)
    result, info = rehearse(toy.RESNET, mix, 4)
    assert not result["correct"]
    assert min(info["reference"]["update_l2_err"]) > 0.02


def _state_unchanged(monkeypatch):
    real = bench.bf.make_train_step

    def make(opt, loss_fn, has_aux=False):
        fused = real(opt, loss_fn, has_aux=has_aux)

        def step(params, state, *operands):
            # the real step consumes what it is given: it gets copies
            copies = jax.tree_util.tree_map(jax.numpy.copy, (params, state))
            return (params, state, fused(*copies, *operands)[2])

        return step

    monkeypatch.setattr(bench.bf, "make_train_step", make)


def _half_of_the_batch(monkeypatch):
    real = bench.load_job

    def load(cell):
        job = real(cell)
        whole = job.loss_fn
        job.loss_fn = lambda params, tokens: whole(params, tokens[: len(tokens) // 2])
        return job

    monkeypatch.setattr(bench, "load_job", load)


def _no_exchange(monkeypatch):
    # planted in the reference, put in the program's place
    monkeypatch.setattr(reference, "_mix", lambda w, tree: tree)


@pytest.mark.parametrize("fault, least", [
    (_state_unchanged, 0.999), (_half_of_the_batch, 0.1), (_no_exchange, 0.01),
], ids=["state-unchanged", "half-of-the-batch", "no-exchange"])
def test_a_broken_step_turns_correct_false(fault, least, monkeypatch):
    """The faults a training cell can have, each under the rest of a whole
    run: a step that returns its state as it got it reads an update error of
    1 by this measure; the mean over half of the rows and a step without the
    exchange between the workers read far over the toy's 1e-3."""
    fault(monkeypatch)
    result, info = rehearse(toy.LM, toy.traffic(seq=64, topology="exp2"), 4)
    assert not result["correct"] and result["failed"] == 0
    read, limit = result["compared"]["update_l2_err"]
    assert read > least >= 10 * limit
    assert read == max(info["reference"]["update_l2_err"])


def test_a_quantized_wire_needs_its_stated_tolerance():
    """int8 is a stated approximation: the exact reference refuses it at
    the configuration's tolerance and takes it at the mix's own."""
    mix = toy.traffic(topology="exp2", wire="int8")
    result, info = rehearse(toy.RESNET, mix, 4)
    err = max(info["reference"]["update_l2_err"])
    assert not result["correct"] and err > toy.TOLERANCE["update_l2"]
    mix["tolerance"] = {"update_l2": 2 * err, "loss_abs": 0.1}
    result, _ = rehearse(toy.RESNET, mix, 4)
    assert result["correct"]


def test_a_wrong_parameter_count_is_refused():
    config = copy.deepcopy(toy.LM)
    config["n_params"] += 1
    with pytest.raises(ValueError, match="n_params"):
        rehearse(config, toy.traffic(seq=64), 1)


def test_a_traced_run_reports_the_per_layer_metrics(monkeypatch):
    """The CPU's profiler writes no ``/device:TPU`` plane, so the trace
    recorded on the v5e and its program's HLO stand in for the toy step's:
    the branch, the readers and the line are what is rehearsed."""
    import os

    from benchmarks.harness import cells, hlo_text, trace_reduce

    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    with open(os.path.join(data, "tiny_1chip.hlo.txt")) as f:
        recorded_hlo = f.read()
    real_index = hlo_text.HloIndex
    monkeypatch.setattr(
        trace_reduce, "load_dir",
        lambda _dir: trace_reduce.load(os.path.join(data, "tiny_1chip.xplane.pb")),
    )
    monkeypatch.setattr(hlo_text, "HloIndex", lambda _text: real_index(recorded_hlo))
    bench_json = cells.load_benchmark()
    every = [m["name"] for m in bench_json["per_layer"]]
    cell = toy.cell(toy.RESNET, toy.traffic(), 1, per_layer=every)
    cell.units.update({m["name"]: m["unit"] for m in bench_json["per_layer"]})
    lines = []
    result = bench.run_cell(
        cell, seed=0, seconds=0.2, trace=True,
        spans=bench.Spans(time.perf_counter()), info=lines.append,
        devices=jax.devices()[:1],
    )
    json.dumps([lines, result])
    assert result["correct"]
    # one worker, no Mosaic call: the collective and flash readers find
    # nothing and are left out
    assert set(result["metrics"]) == {
        "init_s", "compile_s", "host_dispatch_ms", "nonmatmul_device_ms",
        "matmul_conv_ms", "device_idle_share", "step_ms_p50",
    }
    assert result["metrics"]["matmul_conv_ms"]["value"] > 0
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert result["breakdown"]["device_ops"][0][0].startswith("[matmul_conv]")
    assert len(result["breakdown"]["idle_gaps"]) <= 10
