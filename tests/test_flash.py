# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Pallas flash-attention kernel vs dense reference.

The kernel runs in the Pallas interpreter here (CPU CI); the identical
kernel compiles to Mosaic on a real TPU (correctness re-verified on-chip,
errors at bf16 rounding level — see docs/attention.md).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.ops.attention import reference_attention
from bluefog_tpu.ops.flash import flash_attention, flash_attention_supported

B, T, H, D = 2, 256, 2, 128


def qkv(seed=0, t=T):
    rng = np.random.RandomState(seed)
    return [
        jnp.asarray(rng.randn(B, t, H, D), jnp.float32) for _ in range(3)
    ]


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense(causal):
    q, k, v = qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )


def test_blocks_tile_the_sequence():
    q, k, v = qkv(1)
    out = flash_attention(
        q, k, v, causal=True, block_q=64, block_k=128, interpret=True
    )
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )


def test_support_predicate_covers_ragged_shapes():
    """Arbitrary T and head_dim are kernel-supported (padded-masked
    tiles); only cross-attention shapes are excluded."""
    q, k, v = qkv()
    assert flash_attention_supported(q)
    assert flash_attention_supported(jnp.zeros((1, 100, 2, 128)))
    assert flash_attention_supported(jnp.zeros((1, 256, 2, 96)))
    assert flash_attention_supported(jnp.zeros((1, 4097, 2, 96)))
    assert not flash_attention_supported(
        jnp.zeros((1, 256, 2, 128)), jnp.zeros((1, 512, 2, 128))
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,d", [(100, 128), (130, 96), (257, 64)])
def test_ragged_tails_match_dense(causal, t, d):
    """Sequences and head dims off the 128 grid go through the kernel
    (padded + masked), not the O(T^2) dense fallback, and match it."""
    rng = np.random.RandomState(4)
    q, k, v = (
        jnp.asarray(rng.randn(B, t, H, d), jnp.float32) for _ in range(3)
    )
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    assert out.shape == ref.shape
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )


@pytest.mark.parametrize("causal", [False, True])
def test_whole_block_padding_masked(causal):
    """block_q != block_k can pad by WHOLE K blocks even when T divides
    block_k (lcm rounding: T=384, bq=256, bk=128 -> t_pad=512); those
    blocks must be masked or padded zero-keys get softmax weight."""
    rng = np.random.RandomState(9)
    q, k, v = (
        jnp.asarray(rng.randn(1, 384, 2, 64), jnp.float32)
        for _ in range(3)
    )
    out = flash_attention(q, k, v, causal=causal, block_q=256,
                          block_k=128, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )
    gf = jax.grad(
        lambda q: (flash_attention(q, k, v, causal=causal, block_q=256,
                                   block_k=128, interpret=True) ** 2).sum()
    )(q)
    gr = jax.grad(
        lambda q: (reference_attention(q, k, v, causal=causal) ** 2).sum()
    )(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=2e-4, atol=1e-4)


def test_ragged_tail_with_custom_blocks():
    rng = np.random.RandomState(6)
    q, k, v = (
        jnp.asarray(rng.randn(1, 200, 2, 128), jnp.float32)
        for _ in range(3)
    )
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=128,
                          interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )


def test_scale_override():
    q, k, v = qkv(3)
    out = flash_attention(q, k, v, scale=0.5, interpret=True)
    ref = reference_attention(q, k, v, scale=0.5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
    )


def test_cross_attention_shapes_fall_back():
    """Mismatched K/V sequence length must take the dense fallback, not
    crash in the kernel fold."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 128, 2, 128), jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, 2, 128), jnp.float32)
    v = jnp.asarray(rng.randn(1, 256, 2, 128), jnp.float32)
    assert not flash_attention_supported(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_dense(causal):
    """The custom-VJP backward kernels (FlashAttention-2 style: dK/dV over
    Q tiles, dQ over K tiles, probabilities recomputed from the saved
    logsumexp) must match autodiff through the dense path."""
    q, k, v = qkv(7)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    # atol: analytically-zero entries (e.g. causal row 0, where
    # ds = p*(dp - D) cancels exactly) accumulate ~1e-5 float noise
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-4,
            err_msg=f"d{name} causal={causal}",
        )


@pytest.mark.parametrize("t,d", [(100, 128), (257, 64)])
def test_backward_ragged_tails(t, d):
    """Gradients through padded-masked tiles: padding must contribute
    exactly zero gradient and real positions must match dense autodiff."""
    rng = np.random.RandomState(8)
    q, k, v = (
        jnp.asarray(rng.randn(1, t, 2, d), jnp.float32) for _ in range(3)
    )

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=f"d{name} t={t} d={d}",
        )


@pytest.mark.parametrize("causal", [False, True])
def test_with_lse_matches_dense_including_lse_gradient(causal):
    """flash_attention_with_lse: both outputs match the dense oracle, and
    the joint VJP (the dlse term folded into ds) matches dense autodiff
    through a loss that uses out AND lse."""
    from bluefog_tpu.ops.flash import (
        _dense_with_lse,
        flash_attention_with_lse,
    )

    rng = np.random.RandomState(11)
    t, d = 200, 64  # ragged tail: padded rows must carry lse=-inf
    q, k, v = (
        jnp.asarray(rng.randn(1, t, 2, d), jnp.float32) for _ in range(3)
    )
    out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                        interpret=True)
    out_r, lse_r = _dense_with_lse(q, k, v, causal, 1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               rtol=2e-5, atol=2e-5)

    def loss_of(fn):
        def loss(q, k, v):
            o, l = fn(q, k, v)
            return (o ** 2).sum() + (jnp.tanh(l) * 0.3).sum()
        return loss

    gf = jax.grad(loss_of(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=causal, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_of(lambda q, k, v: _dense_with_lse(
        q, k, v, causal, 1.0 / np.sqrt(d))), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-4,
            err_msg=f"d{name} causal={causal}",
        )


@pytest.mark.parametrize("t, h, h_kv", [(512, 2, 2), (512, 4, 1), (450, 2, 1)])
def test_with_lse_over_the_live_tiles_of_a_causal_square(t, h, h_kv):
    """Four tiles a side under ``causal=True``: the three kernels walk the
    list of the ten live ones (``flash._grid``), and ``dlse`` rides the index
    maps ``lse`` and ``delta`` ride. Both outputs and the joint gradient of
    a loss that uses both against dense autodiff."""
    from bluefog_tpu.ops import flash

    assert flash.tile_counts(t, True, 128, 128) == (10, 16)
    assert flash.grid_steps(t, True, 128, 128) == 10
    rng = np.random.RandomState(12)
    d = 64
    q = jnp.asarray(rng.randn(1, t, h, d), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, t, h_kv, d), jnp.float32) for _ in range(2))
    kernel = lambda q, k, v: flash.flash_attention_with_lse(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    dense = lambda q, k, v: flash._dense_with_lse(q, k, v, True, 1.0 / np.sqrt(d))
    for got, want in zip(kernel(q, k, v), dense(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    weight = jnp.asarray(rng.randn(1, h, t), jnp.float32)  # a dlse that is not zero

    def loss_of(fn):
        def loss(q, k, v):
            o, l = fn(q, k, v)
            return (o ** 2).sum() + (l * weight).sum()
        return loss

    gf = jax.grad(loss_of(kernel), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_of(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-4, err_msg=f"d{name}")


def test_merge_blocks_reassembles_full_attention():
    """The online-softmax merge rule: attending two key blocks separately
    and merging (out, lse) pairs equals attending the concatenation."""
    from bluefog_tpu.ops.attention import _merge_blocks
    from bluefog_tpu.ops.flash import _dense_with_lse

    rng = np.random.RandomState(12)
    q = jnp.asarray(rng.randn(2, 16, 2, 8), jnp.float32)
    k1, v1, k2, v2 = (
        jnp.asarray(rng.randn(2, 16, 2, 8), jnp.float32) for _ in range(4)
    )
    s = 1.0 / np.sqrt(8)
    o1, l1 = _dense_with_lse(q, k1, v1, False, s)
    o2, l2 = _dense_with_lse(q, k2, v2, False, s)
    merged, _ = _merge_blocks(
        o1.astype(jnp.float32), l1, o2.astype(jnp.float32), l2
    )
    full, _ = _dense_with_lse(
        q, jnp.concatenate([k1, k2], 1), jnp.concatenate([v1, v2], 1),
        False, s,
    )
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,h_kv", [(4, 2), (4, 1)])
def test_gqa_kernel_native(causal, h, h_kv):
    """Grouped-query K/V runs through the kernels COMPACT (index maps
    share each KV head across its query group — no expanded copy); must
    match the dense reference, which expands."""
    rng = np.random.RandomState(13)
    t, d = 200, 64
    q = jnp.asarray(rng.randn(2, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(2, t, h_kv, d), jnp.float32)
    v = jnp.asarray(rng.randn(2, t, h_kv, d), jnp.float32)
    assert flash_attention_supported(q, k, v)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)

    def loss_of(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    gf = jax.grad(loss_of(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_of(lambda q, k, v: reference_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        assert a.shape == b.shape  # dK/dV stay compact-headed
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-4,
            err_msg=f"d{name} h={h} h_kv={h_kv} causal={causal}",
        )


def test_gqa_with_lse_matches_dense():
    from bluefog_tpu.ops.flash import (
        _dense_with_lse,
        flash_attention_with_lse,
    )

    rng = np.random.RandomState(14)
    q = jnp.asarray(rng.randn(1, 128, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    out, lse = flash_attention_with_lse(q, k, v, causal=True,
                                        interpret=True)
    out_r, lse_r = _dense_with_lse(q, k, v, True, 1.0 / np.sqrt(32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               rtol=2e-5, atol=2e-5)


def test_mismatched_kv_head_counts_fall_back():
    """h_k != h_v must take the dense path: the kernels derive one group
    factor and share the KV index map, so routing such shapes into the
    kernel would silently read the wrong V heads."""
    q = jnp.zeros((1, 128, 4, 32))
    k = jnp.zeros((1, 128, 2, 32))
    v = jnp.zeros((1, 128, 4, 32))
    assert not flash_attention_supported(q, k, v)
    rng = np.random.RandomState(15)
    q, k, v = (
        jnp.asarray(rng.randn(*s), jnp.float32)
        for s in ((1, 128, 4, 32), (1, 128, 2, 32), (1, 128, 4, 32))
    )
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_gqa_lse_gradient():
    """GQA + nonzero lse cotangent — the exact combination ring-attention
    training exercises: the group-mapped dlse plumbing in the backward
    kernels must match dense autodiff."""
    from bluefog_tpu.ops.flash import (
        _dense_with_lse,
        flash_attention_with_lse,
    )

    rng = np.random.RandomState(16)
    q = jnp.asarray(rng.randn(1, 200, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 200, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 200, 2, 32), jnp.float32)

    def loss_of(fn):
        def loss(q, k, v):
            o, l = fn(q, k, v)
            return (o ** 2).sum() + (jnp.tanh(l) * 0.3).sum()
        return loss

    gf = jax.grad(loss_of(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_of(lambda q, k, v: _dense_with_lse(
        q, k, v, True, 1.0 / np.sqrt(32))), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-4,
            err_msg=f"d{name}",
        )
