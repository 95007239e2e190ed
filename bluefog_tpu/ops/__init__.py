# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Long-context sequence-parallel primitives.

The reference framework is data-parallel only (its docs scope this out
explicitly, ``docs/alg_spectrum.rst:11-23``) — these modules are the
capability the TPU rebuild adds so the framework scales in the sequence
dimension with the same mesh machinery the gossip layer runs on:
``ring_attention`` rotates K/V blocks around the worker ring with the
exact ``ppermute`` transport used by ``neighbor_allreduce``, and
``ulysses_attention`` re-shards sequence<->heads with ``all_to_all``.

Beside them the model-side hot ops: the Pallas flash-attention kernels with
their static mask kinds (``flash``), and the sparse-expert layer that holds
one chip's share of the experts (``moe``).
"""

from bluefog_tpu.ops.attention import (
    ring_attention_block,
    ulysses_attention_block,
    ring_attention,
    ulysses_attention,
    reference_attention,
)
from bluefog_tpu.ops.flash import (
    BlockDiffusionMask, flash_attention, flash_attention_supported,
)
from bluefog_tpu.ops.moe import expert_layer, route

__all__ = [
    "ring_attention_block",
    "ulysses_attention_block",
    "ring_attention",
    "ulysses_attention",
    "reference_attention",
    "flash_attention",
    "flash_attention_supported",
    "BlockDiffusionMask",
    "expert_layer",
    "route",
]
