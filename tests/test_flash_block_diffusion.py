"""The block-diffusion mask kind of ``ops/flash.py``: the kernels under
``interpret=True`` against a dense mask laid out from the definition
(forward and the three gradients, grouped heads, an L that is no multiple
of the tile, tiles walked in sub-tiles), the classes of tiles and sub-tiles
and the sub-tile walks against every pair, the grids as long as the list of
the live tiles (in the tables, in the jaxpr, and every output block
written), the refusals, and the causal call of ``gpt2-medium`` pinned."""

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


def brute_classes(kind, t, size_q, size_k, t_pad=None):
    """Each ``size_q x size_k`` rectangle's class from every pair, in numpy:
    dead where no real query sees a real key, whole where every real query
    sees every key and every key is real, partial otherwise."""
    if t_pad is None:
        tile = int(np.lcm(size_q, size_k))
        t_pad = -(-t // tile) * tile
    pos = np.arange(t_pad)
    if kind is True:
        allowed = pos[:, None] >= pos[None, :]
    elif kind:
        allowed = kind.allowed(pos[:, None], pos[None, :], xp=np)
    else:
        allowed = np.ones((t_pad, t_pad), bool)
    keep = allowed & (pos < t)[None, :]
    real = (pos < t)[:, None]
    split = lambda x: x.reshape(t_pad // size_q, size_q, t_pad // size_k, size_k)
    some = split(keep & real).any(axis=(1, 3))
    every = split(keep | ~real).all(axis=(1, 3))
    return np.where(some, np.where(every, flash._WHOLE, flash._PARTIAL), flash._DEAD)


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
    tiles = flash._classes(kind, t, block_q, block_k, flash._padded(t, block_q, block_k))
    tiles = tiles.T if by_key else tiles
    live = tiles != flash._DEAD
    n_major, n_minor = live.shape
    sub = flash._sub_tile(block_q, block_k)
    (n,), (major, minor, flags, walk, subs) = flash._grid(
        kind, t, block_q, block_k, sub, by_key=by_key, group=group
    )
    assert {x.dtype for x in (major, minor, flags, walk, subs)} == {np.dtype(np.int32)}
    assert n == len(major) == len(minor) == len(flags) == len(walk)
    is_live = flags & flash._LIVE != 0
    # the rectangle's walk, major by major, a group member after another
    walk_order = [
        (i, g * n_minor + j)
        for i in range(n_major) for g in range(group) for j in range(n_minor)
        if live[i, j]
    ]
    assert list(zip(major[is_live], minor[is_live])) == walk_order
    assert is_live.sum() == group * live.sum()
    # whole tiles take the mask-free body and walk nothing; a partial one
    # points at a run of its sub-tiles
    whole = [tiles[i, j % n_minor] == flash._WHOLE for i, j in walk_order]
    assert list(flags[is_live] & flash._FULL != 0) == whole
    assert (walk[flags & flash._FULL != 0] == 0).all()
    assert (subs[walk[is_live & (flags & flash._FULL == 0)]] > 0).all()
    # a major with no live tile has one entry all the same; every major's
    # entries stand together, the first and the last flagged and no other
    assert sorted(major[~is_live]) == list(np.flatnonzero(~live.any(axis=1)))
    assert list(major) == sorted(major) and set(major) == set(range(n_major))
    starts = np.r_[True, major[1:] != major[:-1]]
    ends = np.r_[major[1:] != major[:-1], True]
    assert ((flags & flash._FIRST != 0) == starts).all()
    assert ((flags & flash._LAST != 0) == ends).all()


@pytest.mark.parametrize("kind, t, n, rectangle", [
    (False, 4096, 4, True), (True, 1024, 1, False),
    (BlockDiffusionMask(512, 4), 1024, 1, False),
])
def test_the_rectangle_stays_where_every_tile_is_whole(kind, t, n, rectangle):
    """No mask at a length the tiles divide: every tile whole, the
    rectangle, no tables. One causal or block-diffusion tile is partial: a
    list of one entry that walks its sub-tiles (gpt2-medium's call)."""
    assert flash.grid_steps(t, kind) == flash.tile_counts(t, kind)[1] == n * n
    for by_key, group, dims in ((False, 1, (n, n)), (True, 8, (n, 8 * n))):
        got, tables = flash._grid(kind, t, 1024, 1024, 512, by_key, group)
        if rectangle:
            assert (got, tables) == (dims, None)
        else:
            assert got == (group,) and tables[2].tolist() == [
                flash._LIVE | (flash._FIRST if g == 0 else 0)
                | (flash._LAST if g == group - 1 else 0)
                for g in range(group)
            ]


# (kind, t, block_q, block_k, sub): ragged lengths (a padded key or query
# tail), a seq that is no multiple of the tile, blocks of 7 and 3, no mask
CLASSES = [
    (BlockDiffusionMask(100, 4), 200, 64, 64, 16),
    (BlockDiffusionMask(100, 4), 200, 32, 64, None),
    (BlockDiffusionMask(77, 7), 154, 32, 32, 8),
    (BlockDiffusionMask(30, 3), 60, 32, 32, 16),
    (BlockDiffusionMask(1000, 8), 2000, 1024, 1024, 512),
    (BlockDiffusionMask(96, 8), 192, 128, 128, 32),
    (True, 450, 128, 128, 32),
    (True, 450, 128, 64, None),
    (True, 2000, 1024, 1024, 512),
    (False, 300, 128, 128, 64),
]


@pytest.mark.parametrize("kind, t, block_q, block_k, sub", CLASSES)
def test_each_rectangle_is_classed_as_every_pair_says(kind, t, block_q, block_k, sub):
    """Tiles and sub-tiles alike, against ``allowed`` / ``q >= k`` and the
    real length over every position: dead, whole or partial."""
    t_pad = flash._padded(t, block_q, block_k)
    got = flash._classes(kind, t, block_q, block_k, t_pad)
    assert (got == brute_classes(kind, t, block_q, block_k, t_pad)).all()
    sub_q, sub_k = flash._sub_shape(block_q, block_k, sub)
    got = flash._classes(kind, t, sub_q, sub_k, t_pad)
    assert (got == brute_classes(kind, t, sub_q, sub_k, t_pad)).all()


@pytest.mark.parametrize("kind, t, block_q, block_k, sub", CLASSES)
@pytest.mark.parametrize("by_key, group", [(False, 1), (True, 2)])
def test_a_partial_tile_walks_its_live_sub_tiles(kind, t, block_q, block_k, sub, by_key, group):
    """Decoded from the tables the kernels are given: each partial entry's
    run in ``subs`` is its tile's live sub-tiles, query-major, each flagged
    whole as every pair says; a whole tile's entry runs nothing."""
    t_pad = flash._padded(t, block_q, block_k)
    sub_q, sub_k = flash._sub_shape(block_q, block_k, sub)
    brute = brute_classes(kind, t, sub_q, sub_k, t_pad)
    n_q = t_pad // block_q
    _, tables = flash._grid(kind, t, block_q, block_k, sub, by_key, group)
    major, minor, flags, walk, subs = tables
    assert subs[0] == 0  # the empty run
    walked = 0
    for entry in np.flatnonzero(flags & (flash._LIVE | flash._FULL) == flash._LIVE):
        iq, ik = (minor[entry] % n_q, major[entry]) if by_key else (major[entry], minor[entry])
        start = walk[entry]
        codes = subs[start + 1:start + 1 + subs[start]]
        got = [(c >> 16, (c >> 1) & 0x7FFF, bool(c & 1)) for c in codes]
        block = brute[
            iq * block_q // sub_q:(iq + 1) * block_q // sub_q,
            ik * block_k // sub_k:(ik + 1) * block_k // sub_k,
        ]
        want = [
            (i * sub_q, j * sub_k, bool(block[i, j] == flash._WHOLE))
            for i, j in np.argwhere(block != flash._DEAD)
        ]
        assert got == want and any(not w for *_, w in want)
        walked += 1
    assert walked == group * (brute_classes(kind, t, block_q, block_k, t_pad) == flash._PARTIAL).sum()


@pytest.mark.parametrize("kind, t", [
    (True, 2000), (BlockDiffusionMask(1000, 8), 2000), (False, 1900),
    (True, 450), (BlockDiffusionMask(300, 5), 600), (BlockDiffusionMask(512, 4), 1024),
])
def test_subtile_counts_are_what_every_pair_says(kind, t):
    """At the tile and sub-tile sizes ``flash_attention`` chooses (tiles of
    1 024 walk sub-tiles of 512; smaller tiles none): a whole tile counts
    all its sub-tiles live and none masked, a partial one its live and its
    partial sub-tiles."""
    block = flash._auto_block(t)
    sub = flash._sub_tile(block, block)
    assert sub == (512 if block == 1024 else None)
    side = block if sub is None else sub
    t_pad = flash._padded(t, block, block)
    tiles = brute_classes(kind, t, block, block, t_pad)
    subs = brute_classes(kind, t, side, side, t_pad)
    n = block // side
    live = masked = 0
    for iq, ik in np.argwhere(tiles != flash._DEAD):
        inner = subs[iq * n:(iq + 1) * n, ik * n:(ik + 1) * n]
        if tiles[iq, ik] == flash._WHOLE:
            live += n * n
        else:
            live += (inner != flash._DEAD).sum()
            masked += (inner == flash._PARTIAL).sum()
    assert flash.subtile_counts(t, kind) == (live, masked)


# the four LM cells: one call's (batch, head) pair under its mask kind, at
# the tiles `flash_attention` chooses: tiles live / of the square, whole
# tiles, and sub-tiles (512² in a tile of 1 024) live / masked
COUNTS = {
    "sdar30b_1chip_b2": (BlockDiffusionMask(4096, 4), 8192, (24, 64), 12, (80, 24)),
    "mistral4_1chip_b1": (True, 4096, (10, 16), 6, (36, 8)),
    "gpt2m_1chip_full": (True, 1024, (1, 1), 0, (3, 2)),
    "gpt2m_1chip_b1": (True, 1024, (1, 1), 0, (3, 2)),
}


@pytest.mark.parametrize("cell", COUNTS)
def test_the_counts_at_the_four_cells_shapes(cell):
    kind, t, tiles, whole, subtiles = COUNTS[cell]
    assert flash.tile_counts(t, kind) == tiles
    classes = flash._classes(kind, t, 1024, 1024, flash._padded(t, 1024, 1024))
    assert (classes == flash._WHOLE).sum() == whole
    assert flash.subtile_counts(t, kind) == subtiles


def test_all_three_classes_occur_where_the_kernels_are_checked():
    """The two shapes below hold dead, whole and partial sub-tiles inside
    their partial tiles, and dead and partial tiles; the causal one whole
    tiles too (block diffusion's whole tiles need a seq of 2 048 or more at
    these tiles: the smaller tiles of the tests above hold them)."""
    for kind, t, classes in (
        (True, 2000, {0, 1, 2}), (BlockDiffusionMask(1100, 4), 2200, {0, 1}),
    ):
        tiles = flash._classes(kind, t, 1024, 1024, flash._padded(t, 1024, 1024))
        subs = flash._sub_classes(kind, t, 1024, 1024, 512)[tiles == flash._PARTIAL]
        assert set(tiles.ravel()) == classes and set(subs.ravel()) == {0, 1, 2}


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_sub_tile_walks_match_the_dense_mask(what):
    """Block diffusion at 2 200 positions (a padded key and query tail),
    tiles of 1 024 walked in sub-tiles of 512, 4 query heads on one key-value
    head, in the Pallas interpreter."""
    seq, block = 1100, 4
    rng = np.random.RandomState(5)
    mk = lambda h: jnp.asarray(rng.randn(1, 2 * seq, h, D), jnp.float32)
    q, k, v = mk(4), mk(1), mk(1)
    mask, allowed = BlockDiffusionMask(seq, block), dense_mask(seq, block)
    kernel = lambda q, k, v: flash_attention(
        q, k, v, mask=mask, block_q=1024, block_k=1024, interpret=True
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


def test_sub_tile_walks_with_lse_and_its_cotangent():
    """Causal at 2 000 positions (the key tail padded), tiles of 1 024 walked
    in sub-tiles of 512, 4 query heads on 2: the output, ``lse`` and the three
    gradients of a loss that uses both (a ``dlse`` that is not zero) against
    dense autodiff of ``_dense_with_lse``."""
    t, d = 2000, D
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, t, 4, d), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, t, 2, d), jnp.float32) for _ in range(2))
    kernel = lambda q, k, v: flash.flash_attention_with_lse(q, k, v, causal=True, interpret=True)
    dense = lambda q, k, v: flash._dense_with_lse(q, k, v, True, 1.0 / np.sqrt(d))
    for got, want in zip(kernel(q, k, v), dense(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    weight = jnp.asarray(rng.randn(1, 4, t), jnp.float32)

    def loss_of(fn):
        def loss(q, k, v):
            o, l = fn(q, k, v)
            return (o ** 2).sum() + (l * weight).sum()
        return loss

    got = jax.grad(loss_of(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_of(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-4, err_msg=f"d{name}")


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
# 64, bfloat16, causal; value_and_grad): the three kernels, their grids,
# index maps and bodies, op for op. Re-taken by PR 39, which changed
# gpt-2's kernel bodies on purpose (its one causal tile of 1 024 became a
# list of one entry that walks three sub-tiles of 512, two of them masked;
# the row statistics two-dimensional) and measured the `gpt2m_*` cells
# (PERF.md section 6); the pin was 6b4e0387... from 0319d2c to PR 38
GPT2_MEDIUM_CALL = "b20eb76c32fe4ba54dc3c2afe8467f2d0e0f189bdb66388f08980dae151e73e8"


def test_the_causal_call_of_gpt2_medium_lowers_as_before():
    x = jax.ShapeDtypeStruct((4, 1024, 16, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x))
    assert text.count("pallas_call") == 3
    assert hashlib.sha256(text.encode()).hexdigest() == GPT2_MEDIUM_CALL
