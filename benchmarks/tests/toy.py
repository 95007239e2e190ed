"""Test-local toy cells: the harness's loop and job builders at sizes the
CPU mesh runs in seconds. Never a benchmark cell: BENCHMARK.json does not
name them."""

from benchmarks.harness import cells

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
