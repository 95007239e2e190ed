# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Keeps ``chip_smoke.py`` from rotting between chip runs: its phase
functions driven at ``TOY`` size on the 4-device CPU mesh (same
finiteness / oracle / sharding checks, the kernels on their XLA-ops
path), and the script itself refusing to run without a TPU."""

import os
import subprocess
import sys

import pytest

import bluefog_tpu as bf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def mesh4(cpu_devices):
    bf.init(devices=cpu_devices[:4], nodes_per_machine=2)
    yield
    bf.win_free()
    bf.shutdown()


def test_collectives_phase(mesh4):
    line = chip_smoke.phase_collectives(chip_smoke.TOY["collectives"])
    assert line["ok"] and line["n_devices"] >= 4
    assert set(line["max_abs_error"]) == {
        "neighbor_allreduce_static_exp2",
        "neighbor_allreduce_dynamic_one_peer",
        "hierarchical_neighbor_allreduce",
        "win_accumulate_update_then_collect",
        "win_associated_p",
    }


def test_resnet_and_wire_phases(mesh4):
    job = chip_smoke.ResNetJob(chip_smoke.TOY["resnet50"])
    line = chip_smoke.phase_resnet50(job)
    assert line["gossip"] == "dynamic one-peer Exp2"
    assert line["hlo"]["collective_permute"] > 0
    assert line["losses"][-1] < line["losses"][0]
    assert len(line["step_s_settle"]) == len(line["step_s_block_until_ready"])

    line = chip_smoke.phase_wire(job, native=False)
    assert line["elems"] == job.n_params
    for wire in ("int8", "int4"):
        assert line["kernels"][wire]["bitwise_vs_composite"]
        assert line["steps"][wire]["hlo"]["collective_permute"] > 0


def test_lm_phase(mesh4):
    line = chip_smoke.phase_lm(chip_smoke.TOY["lm"], native=False)
    assert line["gossip"] == "static Exp2"
    assert line["hlo"]["collective_permute"] > 0
    assert line["losses"][-1] < line["losses"][0]
    assert set(line["flash_vs_reference"]) == {"out", "dq", "dk", "dv"}


def test_one_device_prints_no_gossip(cpu_devices):
    bf.init(devices=cpu_devices[:1])
    try:
        line = chip_smoke.phase_collectives(chip_smoke.TOY["collectives"])
    finally:
        bf.shutdown()
    assert line["gossip"] == "none (1 device)"


def test_script_refuses_to_run_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout == ""
