"""Finds a cell's data by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads`` there: it names a configuration (whose
``file`` the ``configs`` table gives), a traffic mix
(``benchmarks/traffic/<mix>.json``) and the chips it needs. A per-layer
metric is an entry of ``per_layer`` and a reader of the same name in
``benchmarks/layer_metrics/``. Nothing here knows a cell, a model or a
metric by name, so a later PR adds entries and files and edits none.

Every file is checked on load and a key this harness does not take is an
error: a misspelt ``batch_per_worker`` must not run the default.
"""

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

CONFIG_REQUIRED = {
    "source", "job", "unit", "model", "n_params", "optimizer", "flops",
    "tolerance", "reduced", "assumed",
}
CONFIG_OPTIONAL = {"paper", "deployment"}
TOLERANCE_KEYS = {"loss_abs", "loss_reason", "update_l2", "update_reason"}

TRAFFIC_REQUIRED = {
    "optimizer", "topology", "schedule", "wire", "nodes_per_machine",
    "batch_per_worker", "seq", "env",
}
TRAFFIC_OPTIONAL = {"note", "tolerance"}
OPTIMIZERS = ("neighbor_allreduce", "gradient_allreduce", "hierarchical")
TOPOLOGIES = (None, "exp2", "ring")
SCHEDULES = (None, "one_peer_exp2")
WIRES = (None, "int8", "int4")


class CellError(ValueError):
    """A name that does not resolve or a file this harness cannot take."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: tuple  # metric names this cell reports with --trace 0
    per_layer: tuple   # metric names this cell reports with --trace 1
    units: dict        # metric name -> unit, as BENCHMARK.json states it


def _load_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"no such file: {path}") from None


def _check_keys(what, got, required, optional=frozenset()):
    missing = sorted(required - set(got))
    unknown = sorted(set(got) - required - optional)
    if missing or unknown:
        raise CellError(f"{what}: missing keys {missing}, unknown keys {unknown}")


def check_config(name, config):
    _check_keys(f"config {name!r}", config, CONFIG_REQUIRED, CONFIG_OPTIONAL)
    _check_keys(
        f"config {name!r} tolerance", config["tolerance"], TOLERANCE_KEYS
    )
    if not os.path.isfile(job_path(config["job"])):
        raise CellError(
            f"config {name!r}: no job builder {job_path(config['job'])}"
        )


def check_traffic(name, traffic):
    _check_keys(
        f"traffic {name!r}", traffic, TRAFFIC_REQUIRED, TRAFFIC_OPTIONAL
    )
    for key, allowed in (
        ("optimizer", OPTIMIZERS), ("topology", TOPOLOGIES),
        ("schedule", SCHEDULES), ("wire", WIRES),
    ):
        if traffic[key] not in allowed:
            raise CellError(
                f"traffic {name!r}: {key} = {traffic[key]!r}, not one of "
                f"{allowed}"
            )
    if traffic["schedule"] and traffic["wire"]:
        raise CellError(
            f"traffic {name!r}: the quantized wire rides the static plan "
            "(optimizers.py), not a schedule"
        )
    if not (
        isinstance(traffic["batch_per_worker"], int)
        and traffic["batch_per_worker"] > 0
    ):
        raise CellError(f"traffic {name!r}: batch_per_worker must be > 0")
    bad = [k for k in traffic["env"] if not k.startswith("BLUEFOG_")]
    if bad:
        raise CellError(f"traffic {name!r}: env holds non-BLUEFOG_ keys {bad}")
    if "tolerance" in traffic:
        _check_keys(
            f"traffic {name!r} tolerance", traffic["tolerance"],
            set(), TOLERANCE_KEYS,
        )


def job_path(job):
    return os.path.join(BENCH_DIR, "jobs", f"{job}.py")


def reader_path(metric):
    return os.path.join(BENCH_DIR, "layer_metrics", f"{metric}.py")


def traffic_path(mix):
    return os.path.join(BENCH_DIR, "traffic", f"{mix}.json")


def load_benchmark(root=ROOT):
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _metrics_of(entries, cell):
    return tuple(
        m["name"] for m in entries
        if "workloads" not in m or cell in m["workloads"]
    )


def load_cell(name, root=ROOT):
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(
            f"no workload {name!r} in BENCHMARK.json; it has {sorted(cells)}"
        )
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise CellError(f"cell {name!r}: no config {entry['config']!r}")
    config = _load_json(os.path.join(root, configs[entry["config"]]["file"]))
    check_config(entry["config"], config)
    traffic = _load_json(traffic_path(entry["traffic"]))
    check_traffic(entry["traffic"], traffic)
    per_layer = _metrics_of(bench["per_layer"], name)
    for metric in per_layer:
        if not os.path.isfile(reader_path(metric)):
            raise CellError(f"per-layer metric {metric!r} has no reader file")
    return Cell(
        name=name, chips=entry["chips"], config_name=entry["config"],
        traffic_name=entry["traffic"], config=config, traffic=traffic,
        end_to_end=_metrics_of(bench["end_to_end"], name),
        per_layer=per_layer,
        units={
            m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]
        },
    )


def load_peaks(device_kind):
    """The published peaks of ``device_kind``; a device that is not in the
    table is an error, not a default."""
    table = _load_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    if device_kind not in table:
        raise CellError(
            f"device_kind {device_kind!r} is not in harness/peaks.json "
            f"(it has {sorted(table)}): add its published peaks with their "
            "source before measuring on it"
        )
    return table[device_kind]


def tolerance(cell):
    """The configuration's tolerances, a traffic mix's own laid over them
    (a quantized wire is a stated approximation the exact reference does
    not make)."""
    return {**cell.config["tolerance"], **cell.traffic.get("tolerance", {})}
