"""Finds a cell's data by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads`` there: it names a configuration (whose
``file`` the ``configs`` table gives), a traffic mix
(``benchmarks/traffic/<mix>.json``) and the chips it needs. A per-layer
metric is an entry of ``per_layer`` and a reader of the same name in
``benchmarks/layer_metrics/``. Nothing here knows a cell, a model or a
metric by name, so a later PR adds entries and files and edits none.

Every file is checked on load and a key this harness does not take is an
error: a misspelt ``batch_per_worker`` must not run the default.

A configuration drawn from a public ``config.json`` holds that source's keys
at the top level of its file, under their own names and with their own
values, beside the harness's keys, and declares them in ``source_keys``
(``check_config``, ``source_entry``). ``check_against_source`` is the
driver's check of such a file against its source's entry, in the repo: what
it refuses there, before any run, fails a test here.
"""

import dataclasses
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

CONFIG_REQUIRED = {
    "source", "job", "unit", "model", "n_params", "optimizer", "flops",
    "tolerance", "reduced", "assumed",
}
CONFIG_OPTIONAL = {"paper", "deployment", "source_keys"}
HARNESS_KEYS = CONFIG_REQUIRED | CONFIG_OPTIONAL
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
    """The harness's keys, and beside them at the top level exactly the
    source's keys the file declares in ``source_keys``: one that is not
    declared is unknown (a misspelt ``n_layers`` stays an error), one that
    is declared and absent is missing. ``model`` stays the job's own group:
    what is not the source's (``compute_dtype``, a recomputation flag)."""
    what = f"config {name!r}"
    declared = config.get("source_keys", [])
    if not (
        isinstance(declared, list)
        and all(isinstance(k, str) for k in declared)
        and len(set(declared)) == len(declared)
    ):
        raise CellError(f"{what}: source_keys must be a list of distinct names")
    colliding = sorted(set(declared) & HARNESS_KEYS)
    if colliding:
        raise CellError(
            f"{what}: source_keys names the harness's own keys {colliding}"
        )
    _check_keys(what, config, CONFIG_REQUIRED | set(declared), CONFIG_OPTIONAL)
    undeclared = sorted(set(config["reduced"]) - set(declared))
    if "source_keys" in config and undeclared:
        raise CellError(
            f"{what}: reduced names {undeclared}, which source_keys does not "
            "declare: reduced lists the source's keys whose values differ "
            "from the source's"
        )
    _check_keys(f"{what} tolerance", config["tolerance"], TOLERANCE_KEYS)
    if not os.path.isfile(job_path(config["job"])):
        raise CellError(
            f"config {name!r}: no job builder {job_path(config['job'])}"
        )


def source_entry(config):
    """The source's keys a configuration's file declares, as one dict, in
    the file's own values: where a job builder reads the model's sizes."""
    return {key: config[key] for key in config.get("source_keys", [])}


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the names a width goes by in a config.json (the contract's list: a hidden,
# intermediate, latent, state or projection size, a key that ends in _dim or
# _rank, a head size, an expansion factor, the experts per token); a row does
# not say what a width is, so this is a net for the usual names, no proof
_WIDTH_RE = re.compile(
    r"(_dim|_rank)$|hidden_size|intermediate_size|state_size|d_model|d_ff"
    r"|head_dim|head_size|expand|experts_per_tok"
)


def catalog_entry(source, catalog=CATALOG):
    """The ``config`` of the catalog row whose ``source_url`` is ``source``;
    ``None`` for a source that is no row's, or where this machine has no
    catalog."""
    if not os.path.isfile(catalog):
        return None
    with open(catalog) as f:
        for line in f:
            row = json.loads(line)
            if row.get("source_url") == source:
                return row["config"]
    return None


def check_against_source(name, config, entry):
    """The driver's check of a configuration's file against its source's
    entry (the ``config`` of the catalog row whose ``source_url`` is the
    ``source`` ``BENCHMARK.json`` gives the configuration), which it makes
    before any run: for every key of ``entry`` whose value is a number (a
    boolean is none), a list or a nested group, the file's **top level**
    holds that key — never ``null`` or absent where the source has a value
    — and holds the source's value unless ``reduced`` lists the key. A
    nested group is copied whole and a changed one named by its top-level
    key.

    ``reduced`` may never name a width, and a width may not change inside a
    listed group either: it may name only a count of layers, of experts held
    or of vocabulary rows, and the per-layer lists that follow from a cut in
    depth (``layer_types``, ``mlp_only_layers``). What a width is a row does
    not say, so only the usual names are caught here (``_WIDTH_RE``); the
    rule is the builder's to keep."""
    what = f"config {name!r}"
    reduced = config["reduced"]
    for key, want in entry.items():
        if isinstance(want, bool) or not isinstance(want, (int, float, list, dict)):
            continue
        got = config.get(key)
        if got is None or (got != want and key not in reduced):
            raise CellError(
                f"{what} gives {key} as {json.dumps(got)} and its source gives "
                f"{json.dumps(want)}: the file holds every number, list and "
                "nested group of its source's entry at its top level under "
                "the same key, and reduced lists each key it changes"
            )
    for key in reduced:
        if key not in entry:
            raise CellError(f"{what}: reduced names {key}, no key of its source")
        if config.get(key) == entry[key]:
            raise CellError(
                f"{what}: reduced names {key}, which holds the source's value"
            )
        if _WIDTH_RE.search(key):
            raise CellError(
                f"{what}: reduced names {key}, a width: a width may not change"
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
    # the source the driver looks up is the one BENCHMARK.json states
    source = catalog_entry(configs[entry["config"]]["source"])
    if source is not None:
        check_against_source(entry["config"], config, source)
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
