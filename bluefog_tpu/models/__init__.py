# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Benchmark / example model zoo.

The reference treats models as externals (torchvision ResNet50 in
``examples/pytorch_benchmark.py``, a small conv/MLP net in
``examples/pytorch_mnist.py``); the TPU rebuild ships its own flax
implementations so the BASELINE configs are reproducible without torch.
"""

from bluefog_tpu.models.resnet import (
    ResNet, ResNet18, ResNet34, ResNet50, ResNet101, ResNet152,
)
from bluefog_tpu.models.mlp import MLP, MnistCNN
from bluefog_tpu.models.transformer import TransformerLM
from bluefog_tpu.models.decoder import (
    DecoderConfig, DecoderLM, block_diffusion_loss, next_token_loss,
)

__all__ = [
    "ResNet", "ResNet18", "ResNet34", "ResNet50", "ResNet101", "ResNet152",
    "MLP", "MnistCNN", "TransformerLM",
    "DecoderConfig", "DecoderLM", "block_diffusion_loss", "next_token_loss",
]
