# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Decoder-only transformer LM with pluggable sequence-parallel attention.

The reference has no transformer (its examples are ResNet/MNIST-scale,
data-parallel only); this model exists so the framework's long-context
layer (:mod:`bluefog_tpu.ops.attention`) can be exercised end-to-end: the
attention implementation is injected, so the SAME module runs dense on
one device or ring/Ulysses sequence-parallel inside ``shard_map`` —
weights are identical either way, which is what the equivalence tests
rely on.
"""

from typing import Any, Callable, Optional

import jax.numpy as jnp
import flax.linen as nn

from bluefog_tpu.models.decoder import record_attention_counts
from bluefog_tpu.ops.attention import reference_attention  # noqa: F401 (re-export)
from bluefog_tpu.ops.flash import flash_attention

__all__ = ["TransformerLM"]


class Block(nn.Module):
    dim: int
    heads: int
    attend: Callable
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.LayerNorm(dtype=self.dtype)(x)
        qkv = nn.Dense(3 * self.dim, use_bias=False, dtype=self.dtype)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        split = lambda t: t.reshape(
            t.shape[0], t.shape[1], self.heads, self.dim // self.heads
        )
        att = self.attend(split(q), split(k), split(v))
        att = att.reshape(x.shape[0], x.shape[1], self.dim)
        x = x + nn.Dense(self.dim, use_bias=False, dtype=self.dtype)(att)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = nn.Dense(4 * self.dim, dtype=self.dtype)(h)
        h = nn.gelu(h)
        return x + nn.Dense(self.dim, dtype=self.dtype)(h)


class TransformerLM(nn.Module):
    """Tiny causal LM. ``attend(q, k, v)`` defaults to dense causal
    attention; pass a sequence-parallel block function (closed over the
    mesh axis) to shard the sequence. Positions are GLOBAL: pass
    ``pos_offset`` = this worker's first token index so sequence-sharded
    workers embed their true positions.

    Caveat: the out-of-range check below only fires for *static* int
    offsets. A traced offset (e.g. computed from ``lax.axis_index`` inside
    ``shard_map``) that pushes positions past ``max_len`` silently clamps
    the position gather — ensure ``n_shards * block_len <= max_len`` at
    call-site when the offset is traced."""

    vocab: int = 64
    dim: int = 32
    heads: int = 4
    layers: int = 2
    max_len: int = 4096
    dtype: Any = jnp.float32
    attend: Optional[Callable] = None
    # rematerialize each block in the backward pass: activation memory
    # drops from O(layers * T * dim) to O(T * dim), buying ~2x longer
    # single-chip context (e.g. 32k on a 16 GB v5e at dim 1024 / 12
    # layers) for ~1.3x backward FLOPs
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, pos_offset=0):
        # default attention: Pallas flash kernels on TPU (fwd + custom-VJP
        # bwd; measured 2.6-14.6x fwd / 3.2-5.2x fwd+bwd over the dense XLA path at T>=4096 — see
        # docs/performance.md), dense XLA elsewhere (flash_attention falls
        # back by itself)
        attend = self.attend
        if attend is None:
            attend = lambda q, k, v: flash_attention(q, k, v, causal=True)
            record_attention_counts(
                tokens.shape[1], True, tokens.shape[0] * self.heads * self.layers
            )
        x = nn.Embed(self.vocab, self.dim, dtype=self.dtype)(tokens)
        pos_table = self.param(
            "pos", nn.initializers.normal(0.02), (self.max_len, self.dim)
        )
        if isinstance(pos_offset, int):
            # static offsets are checkable at trace time; the gather below
            # would silently CLAMP out-of-range positions otherwise
            if tokens.shape[1] + pos_offset > self.max_len:
                raise ValueError(
                    f"sequence of {tokens.shape[1]} tokens at offset "
                    f"{pos_offset} exceeds max_len={self.max_len}"
                )
        elif tokens.shape[1] > self.max_len:
            raise ValueError(
                f"block of {tokens.shape[1]} tokens exceeds "
                f"max_len={self.max_len}"
            )
        pos = (
            jnp.arange(tokens.shape[1]) + pos_offset
        )  # global positions under sequence sharding
        x = x + pos_table[pos][None].astype(self.dtype)
        block_cls = nn.remat(Block) if self.remat else Block
        for i in range(self.layers):
            # explicit names: nn.remat would otherwise rename modules to
            # CheckpointBlock_i, making params/checkpoints incompatible
            # across a remat toggle
            x = block_cls(
                dim=self.dim, heads=self.heads, attend=attend,
                dtype=self.dtype, name=f"Block_{i}",
            )(x)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        return nn.Dense(self.vocab, dtype=jnp.float32)(x)
