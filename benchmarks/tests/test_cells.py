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


def laid_out(**changes):
    cfg = copy.deepcopy(toy.LAID_OUT_LM)
    cfg.update(changes)
    return cfg


def test_a_config_holds_its_sources_keys_at_the_top_level(monkeypatch):
    toy.jobs_here(monkeypatch)
    cfg = laid_out()
    cells.check_config("c", cfg)
    entry = cells.source_entry(cfg)
    assert list(entry) == list(toy.SOURCE_LM)  # the source's own order
    assert entry["num_hidden_layers"] == 2 and entry["rope_scaling"] is None
    assert entry["layer_types"] == ["full", "full"]
    # the two files the benchmark has declare nothing
    assert cells.source_entry(toy.LM) == {}
    cells.check_against_source("c", cfg, toy.SOURCE_LM)


def without(key):
    cfg = laid_out()
    del cfg[key]
    return cfg


@pytest.mark.parametrize("cfg, message", [
    (laid_out(n_layers=3), r"unknown keys \['n_layers'\]"),
    (without("hidden_size"), r"missing keys \['hidden_size'\]"),
    (laid_out(source_keys=[*toy.SOURCE_LM, "job"]), "harness's own keys"),
    (laid_out(source_keys=[*toy.SOURCE_LM, "vocab_size"]), "distinct"),
    (laid_out(reduced=["num_hidden_layers", "layer_types", "n_layer"]),
     "source_keys does not declare"),
    (laid_out(source_keys=[]), "unknown keys"),
], ids=["undeclared", "declared-absent", "collides", "twice", "reduced-undeclared",
        "none-declared"])
def test_config_layout_refusals(cfg, message, monkeypatch):
    toy.jobs_here(monkeypatch)
    with pytest.raises(cells.CellError, match=message):
        cells.check_config("c", cfg)


# what the ledger records of PR 32 (and of PR 26 / 27): the source's keys
# verbatim, but under ``model``
KANANA_HEAD = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "num_hidden_layers": 48,
    "q_lora_rank": None, "rope_scaling": None, "vocab_size": 128256,
}


def under_model(entry, **changes):
    return {**toy.LM, "model": {**entry, **changes}}


def at_top_level(entry, reduced=(), **changes):
    """A source's entry laid out the way the check wants it: its keys at the
    top level beside the harness's, declared in ``source_keys``."""
    return {
        **toy.LM, **entry, **changes, "model": {"compute_dtype": "bfloat16"},
        "source_keys": list(entry), "reduced": list(reduced),
    }


def test_the_check_against_the_source_reads_the_top_level():
    check = cells.check_against_source
    with pytest.raises(cells.CellError, match=(
        "gives first_k_dense_replace as null and its source gives 1"
    )):
        check("kanana", under_model(KANANA_HEAD), KANANA_HEAD)
    check("kanana", at_top_level(KANANA_HEAD), KANANA_HEAD)
    cut = at_top_level(KANANA_HEAD, ["num_hidden_layers"], num_hidden_layers=6)
    cells.check_config("kanana", cut)
    check("kanana", cut, KANANA_HEAD)
    with pytest.raises(cells.CellError, match=(
        "gives num_hidden_layers as 6 and its source gives 48"
    )):
        check("kanana", at_top_level(KANANA_HEAD, num_hidden_layers=6), KANANA_HEAD)
    # a null of the source's stays a null, a boolean and a string are not
    # compared; a null where the source has a number is refused though listed
    check("kanana", at_top_level(KANANA_HEAD, hidden_act="gelu"), KANANA_HEAD)
    with pytest.raises(cells.CellError, match="vocab_size as null"):
        check("kanana", at_top_level(KANANA_HEAD, ["vocab_size"], vocab_size=None), KANANA_HEAD)


@pytest.mark.parametrize("reduced, changes, message", [
    (["hidden_size"], {"hidden_size": 1024}, "a width"),
    (["head_dim"], {"head_dim": 32}, "a width"),
    (["num_layers"], {}, "no key of its source"),
    (["vocab_size"], {}, "holds the source's value"),
])
def test_what_reduced_may_not_name(reduced, changes, message):
    with pytest.raises(cells.CellError, match=message):
        cells.check_against_source(
            "kanana", at_top_level(KANANA_HEAD, reduced, **changes), KANANA_HEAD
        )


def test_nested_groups_and_lists_are_compared_whole():
    entry = {
        "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"},
        "layer_types": ["full", "linear"] * 2, "num_hidden_layers": 4,
    }
    cells.check_against_source("c", at_top_level(entry), entry)
    bent = {"rope_parameters": {"rope_theta": 1e4, "rope_type": "default"}}
    with pytest.raises(cells.CellError, match="gives rope_parameters as"):
        cells.check_against_source("c", at_top_level(entry, **bent), entry)
    cut = at_top_level(
        entry, ["layer_types", "num_hidden_layers"],
        layer_types=["full", "linear"], num_hidden_layers=2,
    )
    cells.check_against_source("c", cut, entry)


def catalog_rows():
    if not os.path.isfile(cells.CATALOG):
        return []
    with open(cells.CATALOG) as f:
        return [json.loads(line) for line in f]


def test_every_catalog_row_passes_laid_out_and_fails_nested():
    """Where the catalog is on the machine: each row's ``config`` at the top
    level passes, and nested under ``model`` fails on its first number, which
    is the key the ledger's three refusals name."""
    rows = catalog_rows()
    if not rows:
        pytest.skip("no catalog on this machine")
    first = {}
    for row in rows:
        entry = row["config"]
        assert not set(entry) & cells.HARNESS_KEYS, row["name"]
        cfg = at_top_level(entry)
        cells.check_config(row["name"], cfg)
        cells.check_against_source(row["name"], cfg, entry)
        assert cells.catalog_entry(row["source_url"]) is not None
        number = next(
            k for k, v in entry.items()
            if isinstance(v, (int, float, list, dict)) and not isinstance(v, bool)
        )
        first[row["name"]] = number
        with pytest.raises(cells.CellError, match=f"gives {number} as null"):
            cells.check_against_source(row["name"], under_model(entry), entry)
    assert first["granite-4.0-h-micro"] == "attention_multiplier"
    assert first["LFM2-8B-A1B"] == "conv_L_cache"
    assert first["kanana-2-30b-a3b-instruct-2601"] == "first_k_dense_replace"


@pytest.mark.parametrize("config", BENCH["configs"], ids=names("configs"))
def test_every_config_file_passes_the_checks(config):
    """What the driver checks before any run, of every file the benchmark
    has: a file whose ``source`` is a catalog row holds the row's entry."""
    with open(os.path.join(cells.ROOT, config["file"])) as f:
        cfg = json.load(f)
    cells.check_config(config["name"], cfg)
    entry = cells.catalog_entry(config["source"])
    if entry is not None:
        cells.check_against_source(config["name"], cfg, entry)
        assert set(cells.source_entry(cfg)) >= set(entry)


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
