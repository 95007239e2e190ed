"""The block-diffusion mask kind of ``ops/flash.py``: the kernels under
``interpret=True`` against a dense mask laid out from the definition
(forward and the three gradients, grouped heads, an L that is no multiple
of the tile), the live-tile list against the tiles that hold an allowed
pair, the refusals, and the causal call of ``gpt2-medium`` unchanged."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.ops import flash
from bluefog_tpu.ops.attention import reference_attention
from bluefog_tpu.ops.flash import BlockDiffusionMask, flash_attention

B, H, HKV, D = 2, 4, 2, 32


def dense_mask(seq, block):
    """From the definition, a pair at a time."""
    m = np.zeros((2 * seq, 2 * seq), bool)
    for i in range(seq):
        for j in range(seq):
            m[i, j] = j // block <= i // block            # clean sees clean
            m[seq + i, j] = j // block < i // block       # noised sees clean
            m[seq + i, seq + j] = j // block == i // block  # noised sees noised
    return m


def plain_attention(q, k, v, allowed):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkv(seq, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.randn(B, 2 * seq, h, D), jnp.float32)
    return mk(H), mk(HKV), mk(HKV)


@pytest.mark.parametrize("seq, block", [(8, 4), (100, 4), (96, 8), (30, 3)])
def test_the_mask_allows_what_the_definition_allows(seq, block):
    mask = BlockDiffusionMask(seq, block)
    pos = np.arange(2 * seq)
    got = mask.allowed(pos[:, None], pos[None, :], xp=np)
    assert (got == dense_mask(seq, block)).all()
    assert (np.asarray(mask.allowed(jnp.asarray(pos)[:, None], jnp.asarray(pos)[None, :])) == got).all()
    if seq % block == 0:
        assert got.sum() == seq * seq + seq * block


# L = 100 is no multiple of any tile, so tiles straddle the two halves and
# the tail is padded; 4 query heads on 2 key-value heads
CASES = [(100, 4, 64, 64), (100, 4, 32, 64), (100, 4, 64, 32), (96, 8, 128, 128)]


@pytest.mark.parametrize("seq, block, block_q, block_k", CASES)
@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_kernel_matches_the_dense_mask(seq, block, block_q, block_k, what):
    q, k, v = qkv(seq)
    mask, allowed = BlockDiffusionMask(seq, block), dense_mask(seq, block)
    kernel = lambda q, k, v: flash_attention(
        q, k, v, mask=mask, block_q=block_q, block_k=block_k, interpret=True
    )
    plain = lambda q, k, v: plain_attention(q, k, v, allowed)
    if what == "out":
        got, want = kernel(q, k, v), plain(q, k, v)
    else:
        probe = jnp.cos(jnp.arange(D, dtype=jnp.float32))
        argnum = ("dq", "dk", "dv").index(what)
        got, want = (
            jax.grad(lambda *a: jnp.sum(f(*a) * probe), argnum)(q, k, v)
            for f in (kernel, plain)
        )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("seq, block, block_q, block_k", CASES + [
    (4096, 4, 1024, 1024), (4096, 4, 512, 512), (1000, 8, 256, 128), (77, 7, 16, 8),
])
def test_live_tiles_are_the_tiles_that_hold_an_allowed_pair(seq, block, block_q, block_k):
    mask = BlockDiffusionMask(seq, block)
    live = flash.block_diffusion_live_tiles(mask, block_q, block_k)
    tile = int(np.lcm(block_q, block_k))
    t_pad = -(-2 * seq // tile) * tile
    pos = np.arange(t_pad)
    allowed = mask.allowed(pos[:, None], pos[None, :], xp=np)
    allowed &= (pos < 2 * seq)[:, None] & (pos < 2 * seq)[None, :]
    brute = np.argwhere(
        allowed.reshape(t_pad // block_q, block_q, t_pad // block_k, block_k).any(axis=(1, 3))
    )
    assert live.tolist() == brute.tolist()
    assert flash.tile_counts(2 * seq, mask, block_q, block_k) == (
        len(live), (t_pad // block_q) * (t_pad // block_k)
    )


def test_about_a_quarter_of_the_cells_tiles_are_live():
    mask = BlockDiffusionMask(4096, 4)
    assert flash.tile_counts(8192, mask, 1024, 1024) == (24, 64)
    assert flash.tile_counts(8192, mask, 512, 512) == (80, 256)
    assert flash.tile_counts(8192, mask, 256, 256) == (288, 1024)
    assert flash.tile_counts(1024, True) == (1, 1)
    assert flash.tile_counts(4096, True, 1024, 1024) == (10, 16)
    assert flash.tile_counts(4096, False, 1024, 1024) == (16, 16)


def test_the_dense_path_lays_the_same_mask_out():
    q, k, v = qkv(24, seed=2)
    mask = BlockDiffusionMask(24, 4)
    want = plain_attention(q, k, v, dense_mask(24, 4))
    for got in (reference_attention(q, k, v, mask=mask), flash_attention(q, k, v, mask=mask)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("case", ["wrong-length", "cross-attention", "with-causal", "kv-heads-differ"])
def test_a_mask_is_refused_not_run_densely(case):
    """What the kernels cannot take under a mask raises: the dense fall-back
    for mismatched shapes must not take such a model silently."""
    q, k, v = qkv(16)
    mask, kw = BlockDiffusionMask(16, 4), {}
    if case == "wrong-length":
        mask = BlockDiffusionMask(12, 4)
    elif case == "cross-attention":
        k, v = k[:, :16], v[:, :16]
    elif case == "with-causal":
        kw = {"causal": True}
    else:
        v = jnp.concatenate([v, v], axis=2)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, mask=mask, **kw)


def test_grouped_heads_and_head_dim_128_stay_on_the_kernels():
    q = jnp.zeros((2, 8192, 32, 128), jnp.bfloat16)
    kv = jnp.zeros((2, 8192, 4, 128), jnp.bfloat16)
    assert flash.flash_attention_supported(q, kv, kv)
    text = str(jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, mask=BlockDiffusionMask(4096, 4))
    )(q, kv, kv))
    assert text.count("pallas_call") == 1 and "bf_flash_fwd" in text
    # K and V go into the kernel with their own 4 heads, never 32
    assert "bf16[8,8192,128]" in text and "bf16[64,8192,128]" in text
    assert "repeat" not in text.split("pallas_call")[0].split("platform_index")[-1]


# sha256 of the jaxpr of gpt2-medium's call (4 x 1024 tokens, 16 heads of
# 64, bfloat16, causal; value_and_grad) at the parent of the PR that added
# the mask kinds (0319d2c): the three kernels, their grids, index maps and
# bodies, op for op
GPT2_MEDIUM_CALL = "6b4e0387f9d31bd77ade47c897c427b91c908d34e4c72b358e3342feb9b3fcf2"


def test_the_causal_call_of_gpt2_medium_lowers_as_before():
    x = jax.ShapeDtypeStruct((4, 1024, 16, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x))
    assert text.count("pallas_call") == 3
    assert hashlib.sha256(text.encode()).hexdigest() == GPT2_MEDIUM_CALL
