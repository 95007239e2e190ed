"""The yardstick's counts against numbers known from elsewhere."""

import json
import os

import pytest

from benchmarks.harness import cells, flops


def config(name):
    with open(os.path.join(cells.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_resnet50_is_24_6_gflop_per_image():
    cfg = config("resnet50")
    stated = 3 * cfg["flops"]["forward_flops_per_unit"]
    assert stated == pytest.approx(24.6e9)
    # the same count made from the shapes
    assert 3 * flops.resnet_forward_flops(cfg["model"]) == pytest.approx(stated, rel=0.01)
    # it reproduces the July record: 2510.48 img/s <-> MFU 0.3135
    assert 2510.48 * stated / 197e12 == pytest.approx(0.3135, abs=1e-4)


def test_gpt2_medium_counts():
    cfg = config("gpt2-medium")
    model = cfg["model"]
    assert flops.lm_param_count(model) == cfg["n_params"] == 406_238_289
    # GPT-2 medium as published (tied head, biases on qkv and projection)
    published = (
        flops.lm_param_count(model)
        - (model["n_embd"] * model["vocab_size"] + model["vocab_size"])
        + model["n_layer"] * 4 * model["n_embd"]
    )
    assert published == 354_823_168
    assert flops.lm_matmul_params(model) == cfg["flops"]["matmul_params"] == 353_453_056
    assert flops.lm_flops_per_token(model, 1024) == 6 * 353_453_056 + 6 * 24 * 1024 * 1024


def test_flash_cost_and_roofline():
    cost = flops.flash_attention_cost(batch=4, seq=1024, heads=16, head_dim=64, layers=24)
    assert cost["flops"] == 24 * 7 * 4 * 16 * 1024 * 1024 * 64
    assert cost["bytes"] == 24 * 12 * 4 * 1024 * 16 * 64 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = flops.roofline_share(cost, 0.01, peaks)
    assert roof["binds"] == "compute"
    assert roof["share"] == pytest.approx(cost["flops"] / 197e12 / 0.01)
    assert flops.roofline_share(cost, 0.0, peaks) is None
