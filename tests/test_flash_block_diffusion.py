"""The block-diffusion mask kind of ``ops/flash.py``: the kernels under
``interpret=True`` against a dense mask laid out from the definition
(forward and the three gradients, grouped heads, an L that is no multiple
of the tile), the live-tile list against the tiles that hold an allowed
pair, the grids as long as that list (in the tables, in the jaxpr, and
every output block written), the refusals, and the causal call of
``gpt2-medium`` unchanged."""

import hashlib
import re

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


def qkv(seq, seed=0, h=H, hkv=HKV):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.randn(B, 2 * seq, h, D), jnp.float32)
    return mk(h), mk(hkv), mk(hkv)


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
# 2 L = 400 padded to the tiles' common multiple, 768: the last two rows of
# query tiles, or the last two columns of key tiles, hold padding only
DEAD_ROWS, DEAD_COLUMNS = (200, 8, 128, 384), (200, 8, 384, 128)
# 8 query heads on one key-value head, as the cell's 32 on 4
HEADS = [c + (H, HKV) for c in CASES + [DEAD_ROWS, DEAD_COLUMNS]] + [
    (100, 4, 32, 64, 8, 1), (128, 4, 64, 64, 8, 1),
]


@pytest.mark.parametrize("seq, block, block_q, block_k, h, hkv", HEADS)
@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_kernel_matches_the_dense_mask(seq, block, block_q, block_k, h, hkv, what):
    q, k, v = qkv(seq, h=h, hkv=hkv)
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


def folded(t, t_pad, heads, seed):
    """``[2 * heads, t_pad, D]``, zero past ``t``: what ``_flash`` hands the kernels."""
    x = np.random.RandomState(seed).randn(2 * heads, t, D)
    return jnp.asarray(np.pad(x, ((0, 0), (0, t_pad - t), (0, 0))), jnp.float32)


@pytest.mark.parametrize("kind, t, block_q, block_k, h, hkv", [
    (BlockDiffusionMask(*DEAD_ROWS[:2]), 400, *DEAD_ROWS[2:], 4, 2),
    (BlockDiffusionMask(*DEAD_COLUMNS[:2]), 400, *DEAD_COLUMNS[2:], 4, 2),
    (BlockDiffusionMask(100, 4), 200, 32, 64, 8, 1),
    (True, 450, 128, 64, 4, 2),
])
def test_every_output_block_is_written(kind, t, block_q, block_k, h, hkv):
    """Before the slice: the interpreter starts an output as NaN (the chip as
    whatever was there), so a block no grid step wrote shows. A row of query
    tiles with no live tile comes out as zeros and ``-inf``, a column of key
    tiles with none as zero gradients."""
    tile = int(np.lcm(block_q, block_k))
    t_pad = -(-t // tile) * tile
    assert flash._grid(kind, t, block_q, block_k)[1] is not None
    q, k, v = folded(t, t_pad, h, 0), folded(t, t_pad, hkv, 1), folded(t, t_pad, hkv, 2)
    do = folded(t, t_pad, h, 3)
    dlse = jnp.broadcast_to(folded(t, t_pad, h, 4)[:, :, :1], (2 * h, t_pad, 8))
    (out, lse), vjp = jax.vjp(
        flash._flash_lse_fn(kind, 0.2, block_q, block_k, t, True), q, k, v
    )
    dq, dk, dv = vjp((do, dlse))
    for name, x in (("out", out), ("dq", dq), ("dk", dk), ("dv", dv)):
        assert np.isfinite(np.asarray(x)).all(), name
    assert not np.isnan(np.asarray(lse)).any()
    live = flash._live(kind, t, block_q, block_k)
    for iq in np.flatnonzero(~live.any(axis=1)):
        rows = slice(iq * block_q, (iq + 1) * block_q)
        assert not np.asarray(out[:, rows]).any() and not np.asarray(dq[:, rows]).any()
        assert (np.asarray(lse[:, rows]) == -np.inf).all()
    for ik in np.flatnonzero(~live.any(axis=0)):
        rows = slice(ik * block_k, (ik + 1) * block_k)
        assert not np.asarray(dk[:, rows]).any() and not np.asarray(dv[:, rows]).any()


@pytest.mark.parametrize("by_key, group", [(False, 1), (True, 1), (True, 8)])
@pytest.mark.parametrize("kind, t, block_q, block_k", [
    (BlockDiffusionMask(4096, 4), 8192, 1024, 1024),
    (True, 4096, 1024, 1024),
    (BlockDiffusionMask(*DEAD_ROWS[:2]), 400, *DEAD_ROWS[2:]),
    (BlockDiffusionMask(*DEAD_COLUMNS[:2]), 400, *DEAD_COLUMNS[2:]),
    (BlockDiffusionMask(100, 4), 200, 32, 64),
    (True, 450, 128, 64),
])
def test_the_tile_list_is_the_rectangle_with_the_dead_tiles_left_out(
    kind, t, block_q, block_k, by_key, group
):
    live = flash._live(kind, t, block_q, block_k)
    live = live.T if by_key else live
    n_major, n_minor = live.shape
    (n,), (major, minor, flags) = flash._grid(kind, t, block_q, block_k, by_key, group)
    assert major.dtype == minor.dtype == flags.dtype == np.int32
    assert n == len(major) == len(minor) == len(flags)
    is_live = flags & flash._LIVE != 0
    # the rectangle's walk, major by major, a group member after another
    walk = [
        (i, g * n_minor + j)
        for i in range(n_major) for g in range(group) for j in range(n_minor)
        if live[i, j]
    ]
    assert list(zip(major[is_live], minor[is_live])) == walk
    assert is_live.sum() == group * live.sum()
    # a major with no live tile has one entry all the same; every major's
    # entries stand together, the first and the last flagged and no other
    assert sorted(major[~is_live]) == list(np.flatnonzero(~live.any(axis=1)))
    assert list(major) == sorted(major) and set(major) == set(range(n_major))
    starts = np.r_[True, major[1:] != major[:-1]]
    ends = np.r_[major[1:] != major[:-1], True]
    assert ((flags & flash._FIRST != 0) == starts).all()
    assert ((flags & flash._LAST != 0) == ends).all()


@pytest.mark.parametrize("kind, t, n", [
    (False, 4096, 4), (True, 1024, 1), (BlockDiffusionMask(512, 4), 1024, 1),
])
def test_a_configuration_with_no_dead_tile_keeps_the_rectangle(kind, t, n):
    assert flash._grid(kind, t, 1024, 1024) == ((n, n), None)
    assert flash._grid(kind, t, 1024, 1024, by_key=True, group=8) == ((n, 8 * n), None)
    assert flash.grid_steps(t, kind) == flash.tile_counts(t, kind)[1] == n * n


# the benchmark's two decoder cells: 2 x 8 192 positions under the block
# diffusion mask with 32 query heads on 4 (sdar30b_1chip_b2), 1 x 4 096
# causal with 32 on 32 (mistral4_1chip_b1); heads of 128, tiles of 1 024
CELLS = {
    "sdar30b_1chip_b2": (BlockDiffusionMask(4096, 4), 2, 8192, 32, 4, 24, 64),
    "mistral4_1chip_b1": (True, 1, 4096, 32, 32, 10, 16),
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_three_grids_are_as_long_as_the_live_tiles(cell):
    kind, b, t, h, hkv, live, total = CELLS[cell]
    assert flash.tile_counts(t, kind) == (live, total)
    assert flash.grid_steps(t, kind) == live
    q = jax.ShapeDtypeStruct((b, t, h, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, t, hkv, 128), jnp.bfloat16)
    kw = {"causal": True} if kind is True else {"mask": kind}

    def loss(q, k, v):
        return flash_attention(q, k, v, **kw).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv))
    calls = text.split("pallas_call[")[1:]
    grids = {
        re.search(r"name=(bf_flash_\w+)", call).group(1):
        tuple(int(n) for n in re.search(r"grid=\(([\d, ]+)\)", call).group(1).split(","))
        for call in calls
    }
    assert grids == {
        "bf_flash_fwd": (b * h, live),
        "bf_flash_dkv": (b * hkv, h // hkv * live),
        "bf_flash_dq": (b * h, live),
    }
    # and the lists ride in beside the tensors, int32 and as long as the grid
    for call in calls:
        assert f"i32[{live}]" in call or f"i32[{h // hkv * live}]" in call


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
