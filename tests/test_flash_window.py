"""The sliding-window mask kind of ``ops/flash.py``: the kernels under
``interpret=True`` against a dense mask laid out from the definition
(forward and the three gradients, for windows below, equal to and above the
sequence, a ragged length, a window that is no multiple of 128, a window
of 1, 7 query heads on a key-value head, tiles walked in sub-tiles), the
classes of tiles and sub-tiles against every pair, the tables the kernels
walk, the counts at the cell's shape, the dense path and the refusals."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.ops import flash
from bluefog_tpu.ops.attention import reference_attention
from bluefog_tpu.ops.flash import SlidingWindowMask, flash_attention

B, D = 2, 32


def dense_window(t, window):
    """From the definition, a pair at a time: query i sees key j iff j <= i
    and i - j < window."""
    m = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            m[i, j] = j <= i and i - j < window
    return m


def brute_classes(kind, t, size_q, size_k, t_pad):
    """Each rectangle's class from every pair: dead where no real query sees
    a real key, whole where every real query sees every key and every key
    is real, partial otherwise."""
    pos = np.arange(t_pad)
    if kind is True:
        allowed = pos[:, None] >= pos[None, :]
    else:
        allowed = kind.allowed(pos[:, None], pos[None, :], xp=np)
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


def qkv(t, h, hkv, batch=B, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda heads: jnp.asarray(rng.randn(batch, t, heads, D), jnp.float32)
    return mk(h), mk(hkv), mk(hkv)


def check_against_dense(t, window, block_q, block_k, h, hkv, what, batch=B):
    q, k, v = qkv(t, h, hkv, batch)
    allowed = dense_window(t, window)
    kernel = lambda q, k, v: flash_attention(
        q, k, v, mask=SlidingWindowMask(window), block_q=block_q,
        block_k=block_k, interpret=True,
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


# (t, window, block_q, block_k, query heads, key-value heads)
CASES = [
    (200, 64, 64, 64, 4, 2),    # a window below the length; 200 pads to 256
    (128, 128, 64, 64, 4, 2),   # a window equal to the length
    (96, 500, 32, 32, 4, 2),    # a window above it: the causal mask
    (150, 77, 32, 64, 7, 1),    # no multiple of 128 or of a tile; 7 on 1
    (64, 1, 32, 32, 4, 2),      # a window of 1: the diagonal alone
    (300, 100, 128, 128, 14, 2),  # 7 query heads a key-value head, ragged
]


@pytest.mark.parametrize("t, window, block_q, block_k, h, hkv", CASES)
@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_kernel_matches_the_dense_window(t, window, block_q, block_k, h, hkv, what):
    check_against_dense(t, window, block_q, block_k, h, hkv, what)


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_sub_tile_walks_match_the_dense_window(what):
    """2 100 positions (a padded key and query tail), a window of 1 300,
    tiles of 1 024 walked in sub-tiles of 512, 4 query heads on one
    key-value head: partial tiles on the diagonal and on the band's far
    edge, whole tiles between."""
    assert flash._sub_tile(1024, 1024) == 512
    check_against_dense(2100, 1300, 1024, 1024, 4, 1, what, batch=1)


@pytest.mark.parametrize("seq", [8, 33, 100])
@pytest.mark.parametrize("window", [1, 5, 16, 40, 1000])
def test_the_mask_allows_what_the_definition_allows(seq, window):
    mask = SlidingWindowMask(window)
    pos = np.arange(seq)
    got = mask.allowed(pos[:, None], pos[None, :], xp=np)
    assert (got == dense_window(seq, window)).all()
    jpos = jnp.asarray(pos)
    assert (np.asarray(mask.allowed(jpos[:, None], jpos[None, :])) == got).all()
    w = min(window, seq)
    assert got.sum() == w * (w + 1) // 2 + (seq - w) * w


def test_span_is_exact_over_every_rectangle():
    """``(some, every)`` of every rectangle of a small square against its
    pairs, windows 1 to 12."""
    t = 12
    pos = np.arange(t)
    for window in range(1, t + 1):
        mask = SlidingWindowMask(window)
        allowed = mask.allowed(pos[:, None], pos[None, :], xp=np)
        rects = [
            (q0, q1, k0, k1)
            for q0 in range(t) for q1 in range(q0 + 1, t + 1)
            for k0 in range(t) for k1 in range(k0 + 1, t + 1)
        ]
        q0, q1, k0, k1 = (np.asarray(x) for x in zip(*rects))
        some, every = mask.span(q0, q1, k0, k1)
        for i, (a, b, c, d) in enumerate(rects):
            block = allowed[a:b, c:d]
            assert some[i] == block.any() and every[i] == block.all(), (window, a, b, c, d)


# (t, window, block_q, block_k, sub): ragged lengths, windows that are no
# multiple of any tile, the cell's own shape cut down
CLASSES = [
    (200, 64, 64, 64, 16),
    (200, 77, 32, 64, None),
    (450, 129, 128, 128, 32),
    (2000, 700, 1024, 1024, 512),
    (2100, 1300, 1024, 1024, 512),
    (16384, 4096, 1024, 1024, 512),
    (300, 1, 128, 128, 64),
]


@pytest.mark.parametrize("t, window, block_q, block_k, sub", CLASSES)
def test_each_rectangle_is_classed_as_every_pair_says(t, window, block_q, block_k, sub):
    kind = SlidingWindowMask(window)
    t_pad = flash._padded(t, block_q, block_k)
    got = flash._classes(kind, t, block_q, block_k, t_pad)
    assert (got == brute_classes(kind, t, block_q, block_k, t_pad)).all()
    sub_q, sub_k = flash._sub_shape(block_q, block_k, sub)
    got = flash._classes(kind, t, sub_q, sub_k, t_pad)
    assert (got == brute_classes(kind, t, sub_q, sub_k, t_pad)).all()


@pytest.mark.parametrize("t", [100, 1000, 2000, 4096, 5000])
@pytest.mark.parametrize("extra", [0, 1, 3000])
def test_a_window_of_the_whole_sequence_is_the_causal_mask(t, extra):
    """Classes, tile and sub-tile counts and the three grids' tables alike."""
    kind = SlidingWindowMask(t + extra)
    block = flash._auto_block(t)
    t_pad = flash._padded(t, block, block)
    assert (flash._classes(kind, t, block, block, t_pad)
            == flash._classes(True, t, block, block, t_pad)).all()
    assert flash.tile_counts(t, kind) == flash.tile_counts(t, True)
    assert flash.subtile_counts(t, kind) == flash.subtile_counts(t, True)
    sub = flash._sub_tile(block, block)
    for by_key, group in ((False, 1), (True, 7)):
        window_grid = flash._grid(kind, t, block, block, sub, by_key, group)
        causal_grid = flash._grid(True, t, block, block, sub, by_key, group)
        assert window_grid[0] == causal_grid[0]
        for a, b in zip(window_grid[1], causal_grid[1]):
            assert (a == b).all()


@pytest.mark.parametrize("t, window, block_q, block_k, sub", CLASSES[:5])
@pytest.mark.parametrize("by_key, group", [(False, 1), (True, 7)])
def test_a_partial_tile_walks_its_live_sub_tiles(t, window, block_q, block_k, sub, by_key, group):
    """Decoded from the tables the kernels are given: the list is the
    rectangle's order with the dead tiles left out, and each partial
    entry's run is its tile's live sub-tiles, query-major, each flagged
    whole as every pair says."""
    kind = SlidingWindowMask(window)
    t_pad = flash._padded(t, block_q, block_k)
    sub_q, sub_k = flash._sub_shape(block_q, block_k, sub)
    tiles = brute_classes(kind, t, block_q, block_k, t_pad)
    brute = brute_classes(kind, t, sub_q, sub_k, t_pad)
    n_q = t_pad // block_q
    (n,), (major, minor, flags, walk, subs) = flash._grid(
        kind, t, block_q, block_k, sub, by_key, group
    )
    live = (tiles.T if by_key else tiles) != flash._DEAD
    n_minor = live.shape[1]
    is_live = flags & flash._LIVE != 0
    assert list(zip(major[is_live], minor[is_live])) == [
        (i, g * n_minor + j)
        for i in range(live.shape[0]) for g in range(group) for j in range(n_minor)
        if live[i, j]
    ]
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
        assert got == want
        walked += 1
    assert walked == group * (tiles == flash._PARTIAL).sum()


def test_the_cells_window_walks_the_band():
    """16 384 positions, tiles of 1 024, a window of 4 096: a query tile
    ``i >= 4`` meets key tiles ``i - 4 .. i``, whole between and partial at
    the two ends, each end walking 3 of its 4 sub-tiles of 512 (2 of them
    masked); 70 of 256 tiles, against 136 for the causal square."""
    kind = SlidingWindowMask(4096)
    assert flash.tile_counts(16384, kind) == (70, 256)
    assert flash.tile_counts(16384, True) == (136, 256)
    assert flash.grid_steps(16384, kind) == 70
    tiles = flash._classes(kind, 16384, 1024, 1024, 16384)
    assert (tiles == flash._WHOLE).sum() == 42 and (tiles == flash._PARTIAL).sum() == 28
    for i in range(4, 16):
        assert list(tiles[i, i - 4:i + 1]) == [1, 2, 2, 2, 1]
        assert not tiles[i, :i - 4].any() and not tiles[i, i + 1:].any()
    far = flash._sub_classes(kind, 16384, 1024, 1024, 512)[8, 4]
    assert far.tolist() == [[flash._PARTIAL, flash._WHOLE], [flash._DEAD, flash._PARTIAL]]
    assert flash.subtile_counts(16384, kind) == (42 * 4 + 28 * 3, 28 * 2)


def test_the_three_grids_are_as_long_as_the_band():
    """The cell's call: 28 query heads on 4, heads of 128, bfloat16; the
    kernels' grids in the jaxpr are the list of the 70 live tiles (the dK/dV
    kernel's seven times, once for each query head of a group)."""
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = flash._flash(q, k, v, SlidingWindowMask(4096), 128 ** -0.5, None, None, False)
        return out.astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv))
    grids = {
        re.search(r"name=(bf_flash_\w+)", call).group(1):
        tuple(int(n) for n in re.search(r"grid=\(([\d, ]+)\)", call).group(1).split(","))
        for call in text.split("pallas_call[")[1:]
    }
    assert grids == {
        "bf_flash_fwd": (28, 70), "bf_flash_dkv": (4, 7 * 70), "bf_flash_dq": (28, 70),
    }


def test_the_dense_path_lays_the_same_mask_out():
    q, k, v = qkv(40, 4, 2, seed=2)
    mask = SlidingWindowMask(9)
    want = plain_attention(q, k, v, dense_window(40, 9))
    for got in (reference_attention(q, k, v, mask=mask), flash_attention(q, k, v, mask=mask)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("case", ["with-causal", "cross-attention", "kv-heads-differ", "no-window"])
def test_a_window_is_refused_not_run_densely(case):
    q, k, v = qkv(16, 4, 2)
    kw = {}
    if case == "no-window":
        with pytest.raises(ValueError, match="positive"):
            SlidingWindowMask(0)
        return
    if case == "with-causal":
        kw = {"causal": True}
    elif case == "cross-attention":
        k, v = k[:, :8], v[:, :8]
    else:
        v = jnp.concatenate([v, v], axis=2)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, mask=SlidingWindowMask(4), **kw)
