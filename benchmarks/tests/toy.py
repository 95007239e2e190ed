"""Test-local toy cells: the harness's loop and job builders at sizes the
CPU mesh runs in seconds. Never a benchmark cell: BENCHMARK.json does not
name them."""

import json
import os
import time

import jax

from benchmarks.harness import bench, cells

TOLERANCE = {
    "loss_abs": 1e-3, "loss_reason": "float32 on the CPU",
    "update_l2": 1e-3, "update_reason": "float32 on the CPU",
}

RESNET = {
    "source": "test", "job": "resnet", "unit": "img",
    "model": {
        "arch": "ResNet18", "stage_sizes": [2, 2, 2, 2], "num_filters": 8,
        "num_classes": 10, "image_size": 32, "channels": 3,
        "compute_dtype": "float32", "param_dtype": "float32",
    },
    "n_params": 177362,
    "optimizer": {"name": "sgd", "learning_rate": 0.02, "momentum": 0.9},
    "flops": {"forward_flops_per_unit": 1.0, "formula": "none"},
    "tolerance": TOLERANCE, "reduced": [], "assumed": [],
}

LM = {
    "source": "test", "job": "lm", "unit": "tok",
    "model": {
        "n_ctx": 64, "n_embd": 32, "n_head": 4, "n_layer": 2,
        "n_positions": 64, "vocab_size": 97, "compute_dtype": "float32",
    },
    "n_params": 33569,
    "optimizer": {"name": "sgd", "learning_rate": 0.01, "momentum": 0.9},
    "flops": {"matmul_params": 27680, "formula": "none"},
    "tolerance": TOLERANCE, "reduced": [], "assumed": [],
}

# A configuration in the layout of one drawn from a public config.json: the
# source's keys (here a made-up entry, ``SOURCE_LM``) at the top level under
# their own names, declared in ``source_keys``; ``model`` holds what is not
# the source's. ``num_hidden_layers`` is cut from 4 and listed in ``reduced``.
SOURCE_LM = {
    "attention_bias": False, "hidden_size": 32, "num_attention_heads": 4,
    "num_hidden_layers": 4, "max_position_embeddings": 64, "vocab_size": 97,
    "hidden_act": "gelu", "rope_scaling": None, "layer_types": ["full"] * 4,
}
LAID_OUT_LM = {
    **{k: v for k, v in LM.items() if k not in ("job", "model", "reduced")},
    **SOURCE_LM, "num_hidden_layers": 2, "layer_types": ["full"] * 2,
    "job": "laid_out_lm", "model": {"compute_dtype": "float32"},
    "source_keys": list(SOURCE_LM),
    "reduced": ["num_hidden_layers", "layer_types"],
}
JOBS_HERE = ("laid_out_lm",)  # job builders of these tests, beside this file


def jobs_here(monkeypatch):
    """Let a configuration name a job builder that lives beside the tests
    (``JOBS_HERE``), not in ``benchmarks/jobs/``."""
    in_jobs = cells.job_path
    here = os.path.dirname(os.path.abspath(__file__))
    monkeypatch.setattr(cells, "job_path", lambda job: (
        os.path.join(here, f"{job}.py") if job in JOBS_HERE else in_jobs(job)
    ))


def traffic(**changes):
    mix = {
        "optimizer": "neighbor_allreduce", "topology": None, "schedule": None,
        "wire": None, "nodes_per_machine": None, "batch_per_worker": 8,
        "seq": None, "env": {},
    }
    mix.update(changes)
    cells.check_traffic("toy", mix)
    return mix


def cell(config, mix, chips, per_layer=()):
    cells.check_config("toy", config)
    return cells.Cell(
        name="toy", chips=chips, config_name="toy", traffic_name="toy",
        config=config, traffic=mix,
        end_to_end=("throughput_per_chip", "peak_hbm_gib", "setup_s"),
        per_layer=tuple(per_layer), units={},
    )


UNITS = {"throughput_per_chip": "unit/s", "peak_hbm_gib": "GiB", "setup_s": "s"}


def rehearse(config, mix, workers):
    """One untraced run of a toy cell on the CPU mesh -> (the result line,
    the last of the earlier lines)."""
    toy_cell = cell(config, mix, workers)
    toy_cell.units.update(UNITS)
    lines = []
    result = bench.run_cell(
        toy_cell, seed=3, seconds=0.3, trace=False,
        spans=bench.Spans(time.perf_counter()), info=lines.append,
        devices=jax.devices()[:workers],
    )
    json.dumps([lines, result])  # every line is JSON
    return result, lines[-1]
