"""Every data file loads, every name resolves, what the harness does not
know is refused, and the command refuses to run off the chip."""

import copy
import glob
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.harness import cells

import toy

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def names(table):
    return [entry["name"] for entry in BENCH[table]]


@pytest.mark.parametrize("cell_name", names("workloads"))
def test_cell_loads(cell_name):
    cell = cells.load_cell(cell_name)
    assert cell.chips in (1, 4)
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer
    assert set(cell.end_to_end) | set(cell.per_layer) <= set(cell.units)
    assert cells.tolerance(cell)["update_l2"] > 0


def test_every_file_is_named_by_benchmark_json_and_back():
    def stems(sub, ext):
        return {
            os.path.splitext(os.path.basename(p))[0]
            for p in glob.glob(os.path.join(cells.BENCH_DIR, sub, f"*.{ext}"))
        }

    assert stems("configs", "json") == set(names("configs"))
    assert stems("traffic", "json") == {w["traffic"] for w in BENCH["workloads"]}
    assert stems("layer_metrics", "py") == set(names("per_layer"))
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_benchmark_json_keeps_its_contract():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    every = [n for t in ("configs", "workloads", "end_to_end", "per_layer") for n in names(t)]
    assert len(every) == len(set(every)) and all(NAME.match(n) for n in every)
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(BENCH["workloads"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert all(len(e["why"]) <= 200 for e in BENCH["workloads"] + BENCH["configs"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e and "bound" not in m
        assert LAYER.match(m["layer"]), m["layer"]
        assert set(m.get("workloads", names("workloads"))) <= set(names("workloads"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(BENCH["command"]) <= 32 and 1 <= len(BENCH["paths"]) <= 16
    assert all(PLAIN_PATH.match(p) and ".." not in p for p in BENCH["paths"])
    tracked = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard", *BENCH["paths"]],
        cwd=cells.ROOT, capture_output=True, text=True,
    )
    if tracked.returncode == 0:  # the driver's checkout is no git repository
        assert all(PLAIN_PATH.match(p) for p in tracked.stdout.split())
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_every_reader_loads():
    from benchmarks.harness import bench

    for metric in names("per_layer"):
        assert callable(bench.load_reader(metric))


@pytest.mark.parametrize("change, message", [
    ({"batch_per_workr": 8}, "unknown keys"),
    ({"optimizer": "adamw_gossip"}, "optimizer"),
    ({"wire": "int2"}, "wire"),
    ({"schedule": "one_peer_exp2", "wire": "int8"}, "static plan"),
    ({"batch_per_worker": 0}, "batch_per_worker"),
    ({"env": {"XLA_FLAGS": "x"}}, "env"),
    ({"tolerance": {"update_max": 1}}, "unknown keys"),
])
def test_traffic_refusals(change, message):
    mix = toy.traffic()
    mix.update(change)
    with pytest.raises(cells.CellError, match=message):
        cells.check_traffic("t", mix)


def test_config_refusals():
    cfg = copy.deepcopy(toy.LM)
    cfg["n_layers"] = 3
    with pytest.raises(cells.CellError, match="unknown keys"):
        cells.check_config("c", cfg)
    cfg = copy.deepcopy(toy.LM)
    del cfg["tolerance"]
    with pytest.raises(cells.CellError, match="missing keys"):
        cells.check_config("c", cfg)
    cfg = copy.deepcopy(toy.LM)
    cfg["job"] = "diffusion"
    with pytest.raises(cells.CellError, match="no job builder"):
        cells.check_config("c", cfg)


def test_unknown_names_and_devices():
    with pytest.raises(cells.CellError, match="no workload"):
        cells.load_cell("resnet50_8chip")
    with pytest.raises(cells.CellError, match="not in harness/peaks.json"):
        cells.load_peaks("TPU v9")
    assert cells.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def run_command(*args, cwd=cells.ROOT):
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], *args], cwd=cwd,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300,
    )


def test_command_refuses_to_run_without_a_tpu():
    out = run_command("--workload", names("workloads")[0], "--seed", "0", "--seconds", "1", "--trace", "0")
    assert out.returncode == 2 and "no TPU" in out.stderr
    assert out.stdout == ""


def test_command_refuses_an_unknown_cell():
    out = run_command("--workload", "nope")
    assert out.returncode == 2 and "no workload" in out.stderr and out.stdout == ""
