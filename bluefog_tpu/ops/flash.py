# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Pallas flash-attention kernels for the local attention hot op.

The sequence-parallel layers (:mod:`bluefog_tpu.ops.attention`) delegate
their per-device block attention to XLA by default; this module provides
the hand-tiled TPU kernels for the same math — flash-attention online
softmax with one pass over K/V tiles, f32 accumulators in VMEM, and only
the allowed part of the square computed (below). Layout follows the
MXU/VPU tiling rules: Q/K/V tiles are ``[block, head_dim]`` with sequence blocks multiples of 128 lanes / 8
sublanes (``pallas_guide``: tiling constraints). Ragged sequence lengths
tile via zero padding + in-kernel masking along the SEQUENCE axis only
(an O(T·d) copy), never an O(T²) dense fallback; ``head_dim`` is
deliberately never padded — the kernel's block dim equals the array dim
(Mosaic handles lane packing for narrow heads, and an explicit pad to
128 would double the matmul FLOPs at d=64).

Training-ready: a ``jax.custom_vjp`` pairs the forward kernel (which also
emits the per-row logsumexp) with FlashAttention-2-style backward kernels
(dK/dV accumulated over Q tiles; dQ over K tiles; both recompute the
probabilities from Q/K and the saved logsumexp instead of materializing
the T×T matrix).

Mask kinds, all static (positions come from iotas in the kernel, no mask
tensor ever exists in HBM): none, ``causal=True``, and
``mask=BlockDiffusionMask(seq, block)`` — the training mask of block
diffusion over a doubled sequence (``seq`` clean positions, then their
``seq`` noised copies; causal over blocks of ``block``, bidirectional
inside one). What each part of the square holds is known from the shapes
(``_classes``, on the host): a tile, and a sub-tile inside it, is dead (no
allowed pair), whole (every pair allowed and every key real) or partial.
Where any tile is not whole the three kernels' grids walk a
scalar-prefetched list of the live tiles and are as long as that list
(``_grid``): a dead tile costs neither a grid step nor a K/V fetch. A whole
tile runs a mask-free body — no iotas, no mask, no select. A partial tile
walks its live sub-tiles (512² in a tile of 1 024, ``_sub_tile``) in a
rolled loop over a scalar-prefetched run of them, slicing Q, K, V, dO and
the accumulators' rows, and only its partial sub-tiles build a mask: the
causal diagonal computes three sub-tiles of four, block diffusion's
noised-noised diagonal two. A configuration whose every tile is whole (no
mask at a length the tiles divide) keeps the rectangular grid.

Grouped-query heads (K/V with ``h_kv`` heads, ``h % h_kv == 0``) never
exist expanded: a KV head serves its query group from the index maps, and
the dK/dV kernel sums the group in its VMEM accumulator. ``head_dim`` 128
fills the lane width and is the kernels' best case; any other size runs
unpadded (see above).

``flash_attention`` lowers to the dense XLA path on non-TPU platforms
and for cross-attention (mismatched Q/KV shapes), so callers can use it
unconditionally — except under a ``mask``: a model that asks for the
block-diffusion kernel is refused (``ValueError``) where the shapes would
send it down the dense path, never taken there silently.
``interpret=True`` runs the kernels in the Pallas interpreter (CPU CI).
"""

import dataclasses
import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "BlockDiffusionMask",
    "block_diffusion_live_tiles",
    "tile_counts",
    "grid_steps",
    "subtile_counts",
    "flash_attention",
    "flash_attention_with_lse",
    "flash_attention_supported",
]

_LANES = 128
# lse/delta row vectors ride in [bh, t_pad, _SUB] tensors: Mosaic requires
# the last block dim to be 128-divisible OR equal to the array dim, and a
# width-8 trailing dim keeps the residual 16x smaller than lane-width.
_SUB = 8
_NEG_INF = -jnp.inf


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """The block-diffusion training mask over ``2 * seq`` positions:
    positions ``0 .. seq-1`` are the clean tokens, ``seq .. 2 seq - 1`` their
    noised copies. With ``blk(i) = i // block`` on a half's own index, a
    clean query ``i`` sees clean key ``j`` iff ``blk(j) <= blk(i)`` and no
    noised key; a noised query ``seq + i`` sees clean key ``j`` iff
    ``blk(j) < blk(i)`` and noised key ``seq + j`` iff ``blk(j) == blk(i)``.
    ``seq**2 + seq * block`` of the ``4 seq**2`` pairs are allowed (for
    ``block`` dividing ``seq``). Static: it is part of the kernel's
    configuration."""

    seq: int
    block: int

    def __post_init__(self):
        if self.seq < 1 or self.block < 1:
            raise ValueError(f"seq and block must be positive: {self}")

    def allowed(self, qpos, kpos, xp=jnp):
        """Elementwise: may the query at ``qpos`` see the key at ``kpos``
        (positions in the doubled sequence; broadcastable integer arrays
        of ``xp``, ``numpy`` or ``jax.numpy``)."""
        q_noised, k_noised = qpos >= self.seq, kpos >= self.seq
        qb = _block_of(xp.where(q_noised, qpos - self.seq, qpos), self.block)
        kb = _block_of(xp.where(k_noised, kpos - self.seq, kpos), self.block)
        # booleans meet only in & | ~: Mosaic has no select between masks
        return (~k_noised & (kb + q_noised.astype(qb.dtype) <= qb)) | (
            k_noised & q_noised & (kb == qb)
        )

    def span(self, q0, q1, k0, k1):
        """``(some, every)``: is some / every pair of the rectangle ``[q0,
        q1) x [k0, k1)`` allowed (non-empty; integer ``numpy`` arrays, on
        the host: the kernels compute what this names, they never ask). A
        rectangle may straddle the two halves (``seq`` need be no multiple
        of a tile), so each of the four quadrants is asked by the first and
        last block its part of the rectangle touches; a position past ``2 *
        seq`` counts as noised, as ``allowed`` has it."""
        seq, blk = self.seq, lambda pos: _block_of(pos, self.block)
        q_clean, q_noised = q0 < seq, q1 > seq
        k_clean, k_noised = k0 < seq, k1 > seq
        qc_lo, qc_hi = blk(q0), blk(np.minimum(q1, seq) - 1)
        qn_lo, qn_hi = blk(np.maximum(q0, seq) - seq), blk(q1 - seq - 1)
        kc_lo, kc_hi = blk(k0), blk(np.minimum(k1, seq) - 1)
        kn_lo, kn_hi = blk(np.maximum(k0, seq) - seq), blk(k1 - seq - 1)
        cc, nc, nn_ = q_clean & k_clean, q_noised & k_clean, q_noised & k_noised
        some = (
            (cc & (kc_lo <= qc_hi)) | (nc & (kc_lo < qn_hi))
            | (nn_ & (kn_lo <= qn_hi) & (qn_lo <= kn_hi))
        )
        every = (
            (~cc | (kc_hi <= qc_lo)) & (~nc | (kc_hi < qn_lo))
            & ~(q_clean & k_noised)
            & (~nn_ | ((qn_lo == qn_hi) & (kn_lo == kn_hi) & (qn_lo == kn_lo)))
        )
        return some, every


def _block_of(pos, block):
    # a shift where it can be one: the vector units have no integer divide
    if block & (block - 1) == 0:
        return pos >> (block.bit_length() - 1)
    return pos // block


def _span(kind, q0, q1, k0, k1):
    """``(some, every)`` pair of each rectangle ``[q0, q1) x [k0, k1)``
    allowed under the mask kind ``kind`` (``False``, ``True`` for causal,
    or a ``BlockDiffusionMask``)."""
    if kind is True:
        return q1 - 1 >= k0, q0 >= k1 - 1
    if kind:
        return kind.span(q0, q1, k0, k1)
    return np.ones(q0.shape, bool), np.ones(q0.shape, bool)


def _padded(t, block_q, block_k):
    # the length `_flash` pads a sequence of `t` to: the tiles' common multiple
    tile = int(np.lcm(block_q, block_k))
    return -(-t // tile) * tile


# the class of a rectangle of the square (a tile, or a sub-tile of one):
# no allowed pair; some; every pair allowed and every key a real one
_DEAD, _PARTIAL, _WHOLE = 0, 1, 2


@functools.lru_cache(maxsize=None)
def _classes(kind, t, size_q, size_k, t_pad):
    """``int8 [t_pad // size_q, t_pad // size_k]``: the class of each
    ``size_q x size_k`` rectangle of the square of ``t_pad`` positions, of
    which the first ``t`` are real, under the mask kind ``kind``. A class
    is judged over the real queries: a padding query's output is sliced
    away and its cotangents are zero, so it may take a whole rectangle's
    mask-free body as well as be left out of a dead one (it then meets only
    finite scores: it sees every key of that rectangle in the forward pass
    too). Exact: the kernels mask what is ``_PARTIAL`` and nothing else."""
    q0, k0 = np.meshgrid(
        np.arange(0, t_pad, size_q), np.arange(0, t_pad, size_k),
        indexing="ij",
    )
    q1, k1 = np.minimum(q0 + size_q, t), k0 + size_k  # real queries only
    some, _ = _span(kind, q0, q1, k0, np.minimum(k1, t))
    _, every = _span(kind, q0, q1, k0, k1)
    live = some & (q0 < t) & (k0 < t)
    out = np.where(live, np.where(every & (k1 <= t), _WHOLE, _PARTIAL), _DEAD)
    out = out.astype(np.int8)
    out.setflags(write=False)  # one array for every caller
    return out


def _live(kind, t, block_q, block_k):
    """Which tiles hold an allowed pair under the mask kind ``kind``:
    ``bool [n_q, n_k]`` over the tiles of a sequence of ``t`` padded to the
    tiles' common multiple, as ``_flash`` pads it."""
    return _classes(kind, t, block_q, block_k, _padded(t, block_q, block_k)) != _DEAD


def _sub_shape(block_q, block_k, sub):
    # a partial tile's sub-tiles; the whole tile is its one sub-tile where
    # the kernels walk none
    return (block_q, block_k) if sub is None else (sub, sub)


def _sub_classes(kind, t, block_q, block_k, sub):
    """``int8 [n_q, n_k, n_sq, n_sk]``: each tile's sub-tiles' classes."""
    sub_q, sub_k = _sub_shape(block_q, block_k, sub)
    t_pad = _padded(t, block_q, block_k)
    n_sq, n_sk = block_q // sub_q, block_k // sub_k
    cls = _classes(kind, t, sub_q, sub_k, t_pad)
    return cls.reshape(t_pad // block_q, n_sq, t_pad // block_k, n_sk).transpose(0, 2, 1, 3)


# what an entry of a grid's tile list is: the first / the last of its major
# (the accumulator starts / is written out), a tile to compute at all, and
# a tile whose every pair is allowed (the mask-free body; a live tile
# without it walks its live sub-tiles)
_FIRST, _LAST, _LIVE, _FULL = 1, 2, 4, 8


@functools.lru_cache(maxsize=None)
def _grid(kind, t, block_q, block_k, sub=None, by_key=False, group=1):
    """``(dims, tables)``: a kernel's grid after its leading (batch, head)
    dimension, under the mask kind ``kind``, with partial tiles walked in
    sub-tiles of ``sub`` (None: whole). Majors are query tiles and minors
    key tiles, or the other way round ``by_key``; inside one major the
    minors run once for each of ``group`` members, as ``g * n_minor +
    minor`` (the dK/dV kernel's walk over the query heads of its group).

    Where every tile is whole the grid is the rectangle, ``dims = (n_major,
    group * n_minor)``, and ``tables`` is None. Else it is as long as the
    list of the live tiles, ``dims = (n,)``, and ``tables`` are ``int32``
    arrays the kernel takes as scalar-prefetch operands: ``(major, minor,
    flags, walk)``, each ``[n]``, and ``subs``. The list is the rectangle's
    order with the dead tiles left out, so every sum is the rectangle's. A
    major with no live tile (a row of padding) gets one entry that is not
    ``_LIVE``: its output block is still written, as zeros. A partial
    entry's ``walk`` is where its run starts in ``subs``: the run's length,
    then one code a live sub-tile, ``q_off << 16 | k_off << 1 | whole`` with
    the sub-tile's offsets in the tile, query-major; runs are shared by the
    tiles that have the same, and ``subs[0]`` is the empty run."""
    tiles = _classes(kind, t, block_q, block_k, _padded(t, block_q, block_k))
    if by_key:
        tiles = tiles.T
    n_major, n_minor = tiles.shape
    if (tiles == _WHOLE).all():
        return (n_major, group * n_minor), None
    sub_cls = _sub_classes(kind, t, block_q, block_k, sub)
    sub_q, sub_k = _sub_shape(block_q, block_k, sub)
    subs, runs = [0], {}

    def walk_of(iq, ik):
        live = np.argwhere(sub_cls[iq, ik] != _DEAD)
        whole = sub_cls[iq, ik][tuple(live.T)] == _WHOLE
        codes = tuple(
            (live[:, 0] * sub_q << 16 | live[:, 1] * sub_k << 1 | whole).tolist()
        )
        if codes not in runs:
            runs[codes] = len(subs)
            subs.extend((len(codes),) + codes)
        return runs[codes]

    majors, minors, flags, walks = [], [], [], []
    for major, row in enumerate(tiles):
        live = np.flatnonzero(row != _DEAD)
        if not live.size:
            majors.append(major)
            minors.append(0)
            flags.append(_FIRST | _LAST)
            walks.append(0)
            continue
        run_flags = np.where(row[live] == _WHOLE, _LIVE | _FULL, _LIVE)
        run_walks = [
            0 if row[j] == _WHOLE
            else walk_of(*((j, major) if by_key else (major, j)))
            for j in live
        ]
        run_flags = np.tile(run_flags, group)
        run_flags[0] |= _FIRST
        run_flags[-1] |= _LAST
        majors.extend([major] * run_flags.size)
        minors.extend((np.arange(group)[:, None] * n_minor + live).ravel())
        flags.extend(run_flags)
        walks.extend(run_walks * group)
    tables = tuple(
        np.asarray(x, np.int32) for x in (majors, minors, flags, walks, subs)
    )
    for table in tables:
        table.setflags(write=False)
    return (tables[0].size,), tables


def block_diffusion_live_tiles(mask, block_q, block_k):
    """The ``(iq, ik)`` of every tile the kernels visit under ``mask`` (an
    ``[n, 2]`` numpy array, row-major), at tiles of ``block_q x block_k``:
    the tiles ``BlockDiffusionMask.span`` finds some allowed pair in."""
    return np.argwhere(_live(mask, 2 * mask.seq, block_q, block_k))


def _blocks(t, block_q, block_k):
    # the tile sizes of a call that leaves either to `flash_attention`
    block_q = _auto_block(t) if block_q is None else block_q
    return block_q, block_q if block_k is None else block_k


def tile_counts(t, kind=False, block_q=None, block_k=None):
    """``(live, total)``: the tiles one forward pass visits for one (batch,
    head) at sequence length ``t`` under the mask kind ``kind`` (``False``,
    ``True`` for causal, or a ``BlockDiffusionMask``), and the tiles of the
    padded square, at the tile sizes ``flash_attention`` would choose."""
    live = _live(kind, t, *_blocks(t, block_q, block_k))
    return int(live.sum()), live.size


def grid_steps(t, kind=False, block_q=None, block_k=None):
    """The grid steps one forward pass takes for one (batch, head), read
    from the grid the forward kernel is given: ``tile_counts``' ``live``
    where the grid walks the list of live tiles, its ``total`` where it is
    the rectangle."""
    dims, _ = _grid(kind, t, *_blocks(t, block_q, block_k))
    return int(np.prod(dims))


def subtile_counts(t, kind=False, block_q=None, block_k=None):
    """``(live, masked)``: the sub-tiles one forward pass computes for one
    (batch, head) at sequence length ``t`` under the mask kind ``kind``, and
    those of them that build a mask, at the tile and sub-tile sizes
    ``flash_attention`` would choose. A whole tile counts all its sub-tiles
    as live and none as masked; where the kernels walk no sub-tiles
    (``_sub_tile`` is None) a tile is its one sub-tile."""
    block_q, block_k = _blocks(t, block_q, block_k)
    sub = _sub_tile(block_q, block_k)
    tiles = _classes(kind, t, block_q, block_k, _padded(t, block_q, block_k))
    sub_q, sub_k = _sub_shape(block_q, block_k, sub)
    in_partial = _sub_classes(kind, t, block_q, block_k, sub)[tiles == _PARTIAL]
    whole = (tiles == _WHOLE).sum() * (block_q // sub_q) * (block_k // sub_k)
    live = whole + (in_partial != _DEAD).sum()
    return int(live), int((in_partial == _PARTIAL).sum())


def _keep_mask(q0, k0, size_q, size_k, causal, kv_len, t_pad):
    """The validity mask of the ``size_q x size_k`` scores whose first
    query and key sit at ``q0`` and ``k0``, or None where every entry is
    valid. ``causal`` is the mask kind: ``False``, ``True`` or a
    ``BlockDiffusionMask``.

    Raggedness is judged against the PADDED length, not ``block_k``
    alone: with block_q != block_k the lcm rounding can append
    whole-block K padding even when kv_len divides block_k, and those
    tiles must be masked too."""
    ragged = kv_len < t_pad
    if not (causal or ragged):
        return None
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (size_q, size_k), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (size_q, size_k), 1)
    keep = None
    if causal is True:
        keep = qpos >= kpos
    elif causal:
        keep = causal.allowed(qpos, kpos)
    if ragged:
        valid = kpos < kv_len
        keep = valid if keep is None else keep & valid
    return keep


class _GridStep:
    """Where one grid step of a kernel stands: its tile ``(major, minor)``,
    whether that starts or ends its major's run, and what in it to compute.
    Read from the program ids on the rectangular grid, from the
    scalar-prefetched list of the live tiles (``tables``, `_grid`) on a grid
    as long as that list."""

    def __init__(self, tables=None, bodies=None):
        if tables is None:
            self.major, self.minor = pl.program_id(1), pl.program_id(2)
            self._flags = None
        else:
            major, minor, flags, walk, self._subs = tables
            entry = pl.program_id(1)
            self.major, self.minor = major[entry], minor[entry]
            self._flags, self._walk = flags[entry], walk[entry]
            self._bodies = bodies

    def _flag(self, flag):
        return (self._flags & flag) != 0

    def when_first(self, init):
        listed = self._flags is not None
        pl.when(self._flag(_FIRST) if listed else self.minor == 0)(init)

    def when_last(self, finalize):
        listed = self._flags is not None
        pl.when(
            self._flag(_LAST) if listed
            else self.minor == pl.num_programs(2) - 1
        )(finalize)

    def compute(self, update, block_q, block_k, sub):
        """Call ``update(q_off, k_off, size_q, size_k, masked)`` over what
        this step computes, the offsets within its tile: a whole tile once,
        unmasked; a partial tile once for each of its live sub-tiles, in a
        rolled loop over its run in ``subs``, masked where the sub-tile is
        partial; a dead row's entry nothing."""
        if self._flags is None:  # the rectangle: every tile is whole
            update(0, 0, block_q, block_k, False)
            return
        # only the bodies some entry takes are built (`_bodies`)
        whole_tiles, partial_tiles, whole_subs = self._bodies
        if whole_tiles:
            pl.when(self._flag(_FULL))(
                lambda: update(0, 0, block_q, block_k, False)
            )
        if not partial_tiles:
            return
        partial = (self._flags & (_LIVE | _FULL)) == _LIVE
        if sub is None:  # a partial tile is its one sub-tile
            pl.when(partial)(lambda: update(0, 0, block_q, block_k, True))
            return
        start, subs = self._walk, self._subs

        def sub_tile(i, carry):
            code = subs[start + 1 + i]
            q_off = pl.multiple_of(code >> 16, sub)
            k_off = pl.multiple_of((code >> 1) & 0x7FFF, sub)
            if not whole_subs:
                update(q_off, k_off, sub, sub, True)
                return carry
            whole = (code & 1) != 0
            pl.when(whole)(lambda: update(q_off, k_off, sub, sub, False))
            pl.when(~whole)(lambda: update(q_off, k_off, sub, sub, True))
            return carry

        @pl.when(partial)
        def _walk():
            jax.lax.fori_loop(0, subs[start], sub_tile, 0)


def _bodies(tables):
    """``(whole_tiles, partial_tiles, whole_subs)``: which bodies some entry
    of a listed grid takes, read on the host from its tables — a whole
    tile's, a partial tile's walk, and in a walk a whole sub-tile's."""
    _, _, flags, walk, subs = tables
    kind = flags & (_LIVE | _FULL)
    runs = {int(w) for w in walk[kind == _LIVE]}
    return (
        bool((kind == _LIVE | _FULL).any()), bool(runs),
        any((subs[w + 1:w + 1 + subs[w]] & 1).any() for w in runs),
    )


def _tiled_call(kernel, bh, grid, *, name, in_specs, out_specs,
                scratch_shapes, **kw):
    """The ``pl.pallas_call`` of ``kernel(step, *refs)`` over ``bh`` (batch,
    head) slots times ``grid = (dims, tables)`` (`_grid`). Each spec is
    ``(block_shape, index)`` with ``index`` written over ``(b, major,
    minor)``, whichever of the two grids carries them."""
    dims, tables = grid
    bodies = tables and _bodies(tables)
    tables = tables or ()  # the rectangle prefetches nothing

    def spec(shape, index):
        if not tables:
            return pl.BlockSpec(shape, index)
        return pl.BlockSpec(
            shape,
            lambda b, entry, major, minor, *_: index(
                b, major[entry], minor[entry]
            ),
        )

    call = pl.pallas_call(
        lambda *refs: kernel(
            _GridStep(refs[:len(tables)] or None, bodies),
            *refs[len(tables):]
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=(bh, *dims),
            in_specs=[spec(*s) for s in in_specs],
            out_specs=tuple(spec(*s) for s in out_specs),
            scratch_shapes=scratch_shapes,
        ),
        name=name, **kw,
    )
    return functools.partial(call, *tables)


# -- forward -----------------------------------------------------------------


def _lanes(x, n):
    """``x``, ``[rows, 128]`` with every lane alike, ``n`` lanes wide: the
    row statistics stay two-dimensional, and the vector units never lay a
    row vector out again (what a ``[:, 0]`` read and a ``[:, None]``
    broadcast cost at every sub-tile)."""
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return x[:, :n] if n < _LANES else jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fwd_kernel(step, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                l_ref, *, scale, causal, block_q, block_k, kv_len, t_pad, sub):
    iq, ik = step.major, step.minor
    d = acc_ref.shape[-1]

    @step.when_first
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def update(q_off, k_off, size_q, size_k, masked):
        rows, cols = pl.ds(q_off, size_q), pl.ds(k_off, size_k)
        s = jax.lax.dot_general(
            q_ref[0, rows], k_ref[0, cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [size_q, size_k]
        keep = _keep_mask(
            iq * block_q + q_off, ik * block_k + k_off, size_q, size_k,
            causal, kv_len, t_pad,
        ) if masked else None
        m_prev = m_ref[rows]  # [size_q, 128], every lane alike
        if keep is not None:
            s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        # unmasked, every score and every row's maximum is finite; masked, a
        # row that has met no allowed key yet subtracts 0: its -inf scores
        # still give 0, and so does its correction
        m_safe = m_new if keep is None else jnp.where(
            jnp.isfinite(m_new), m_new, 0.0
        )
        p = jnp.exp(s - _lanes(m_safe, size_k))
        corr = jnp.exp(m_prev - m_safe)
        l_ref[rows] = l_ref[rows] * corr + p.sum(-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, cols], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[rows] = acc_ref[rows] * _lanes(corr, d) + pv
        m_ref[rows] = m_new

    step.compute(update, block_q, block_k, sub)

    @step.when_last
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[:] / _lanes(l_safe, d)).astype(o_ref.dtype)
        # logsumexp per row; -inf marks rows with no valid key (padding)
        lse = jnp.where(l > 0, m_ref[:] + jnp.log(l_safe), _NEG_INF)
        lse_ref[0] = lse[:, :_SUB]


def _vma(x):
    # inside shard_map the outputs vary over the same mesh axes as the
    # inputs; pallas out_shapes must carry that or the vma check rejects
    # the trace (platform_dependent traces the kernel branch everywhere)
    return jax.typeof(x).vma


def _fwd_call(qf, kf, vf, causal, scale, block_q, block_k, kv_len,
              interpret, out_dtype=None):
    bh, t_pad, d_pad = qf.shape
    # grouped-query attention: folded KV carries b*h_kv leading slots; a
    # KV head serves its whole query group straight from the index map —
    # no expanded copy ever exists
    group = bh // kf.shape[0]
    out_dtype = qf.dtype if out_dtype is None else out_dtype
    vma = _vma(qf)
    sub = _sub_tile(block_q, block_k)
    q_spec = ((1, block_q, d_pad), lambda b, iq, ik: (b, iq, 0))
    kv_spec = ((1, block_k, d_pad), lambda b, iq, ik: (b // group, ik, 0))
    return _tiled_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, kv_len=kv_len, t_pad=t_pad, sub=sub,
        ),
        bh, _grid(causal, kv_len, block_q, block_k, sub),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t_pad, d_pad), out_dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t_pad, _SUB), jnp.float32, vma=vma),
        ),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=(
            q_spec,
            ((1, block_q, _SUB), lambda b, iq, ik: (b, iq, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d_pad), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="bf_flash_fwd",
    )(qf, kf, vf)


# -- backward (FlashAttention-2 style) ---------------------------------------


def _recompute_p(q, k, lse, q0, k0, scale, causal, kv_len, t_pad, masked):
    """Rebuild the probability tile from Q/K and the saved logsumexp: ``q``
    and ``lse`` rows from query ``q0`` on, ``k`` rows from key ``k0`` on."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    keep = _keep_mask(
        q0, k0, q.shape[0], k.shape[0], causal, kv_len, t_pad
    ) if masked else None
    lse = lse[:, :1]  # [size_q, 1] (stored _SUB wide)
    if keep is None:
        # every score is finite, and so is every row's lse: the forward
        # pass saw these same scores unmasked
        return jnp.exp(s - lse)
    # a masked score gives 0; a row with lse=-inf (padding, no valid key)
    # has every score masked here, as it had in the forward pass
    s = jnp.where(keep, s, _NEG_INF)
    return jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0))


def _bwd_dkv_kernel(step, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dlse_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                    causal, block_q, block_k, kv_len, t_pad, sub):
    # the minor enumerates (query head of the group, q tile): with
    # grouped-query attention one KV head accumulates dK/dV over every
    # query head it serves; iq is the tile index within one head
    ik, iq2 = step.major, step.minor
    n_q = t_pad // block_q
    iq = iq2 % n_q

    @step.when_first
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def update(q_off, k_off, size_q, size_k, masked):
        rows, cols = pl.ds(q_off, size_q), pl.ds(k_off, size_k)
        q = q_ref[0, rows]
        p = _recompute_p(
            q, k_ref[0, cols], lse_ref[0, rows], iq * block_q + q_off,
            ik * block_k + k_off, scale, causal, kv_len, t_pad, masked,
        )  # [size_q, size_k]
        do = do_ref[0, rows]  # [size_q, d]
        # dV += P^T dO
        dv_acc[cols] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dP = dO V^T ; dS = P * (dP - D) * scale
        dp = jax.lax.dot_general(
            do, v_ref[0, cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dlse: upstream cotangent on the logsumexp output (zero for
        # plain flash_attention; nonzero when lse feeds a cross-block
        # merge, e.g. ring attention) — dL/ds_ij picks up dlse_i * p_ij
        ds = p * (
            dp - delta_ref[0, rows][:, :1] + dlse_ref[0, rows][:, :1]
        ) * scale
        # dK += dS^T Q
        dk_acc[cols] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    step.compute(update, block_q, block_k, sub)

    @step.when_last
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(step, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dlse_ref, dq_ref, dq_acc, *, scale, causal, block_q,
                   block_k, kv_len, t_pad, sub):
    iq, ik = step.major, step.minor

    @step.when_first
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def update(q_off, k_off, size_q, size_k, masked):
        rows, cols = pl.ds(q_off, size_q), pl.ds(k_off, size_k)
        k = k_ref[0, cols]
        p = _recompute_p(
            q_ref[0, rows], k, lse_ref[0, rows], iq * block_q + q_off,
            ik * block_k + k_off, scale, causal, kv_len, t_pad, masked,
        )
        do = do_ref[0, rows]
        dp = jax.lax.dot_general(
            do, v_ref[0, cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (
            dp - delta_ref[0, rows][:, :1] + dlse_ref[0, rows][:, :1]
        ) * scale
        # dQ += dS K
        dq_acc[rows] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    step.compute(update, block_q, block_k, sub)

    @step.when_last
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_call(qf, kf, vf, of, lse, do, causal, scale, block_q, block_k,
              kv_len, interpret, dlse=None):
    bh, t_pad, d_pad = qf.shape
    # D_i = rowsum(dO_i * O_i) — O(T d) elementwise, fine in XLA
    delta = jnp.sum(
        do.astype(jnp.float32) * of.astype(jnp.float32), axis=-1
    )
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (_SUB,))
    if dlse is None:
        dlse_w = jnp.zeros_like(delta)
    else:
        dlse_w = jnp.broadcast_to(
            dlse.astype(jnp.float32)[..., None], dlse.shape + (_SUB,)
        )
    vma = _vma(qf)
    bh_kv = kf.shape[0]
    group = bh // bh_kv
    n_q = t_pad // block_q
    # dK/dV grid: (kv head, then per k tile every group member x q tile) —
    # the minor walks every query head served by this KV head, so the group
    # reduction happens in the VMEM accumulator with no expanded copy
    by_head = lambda b, ik, iq2: (b * group + iq2 // n_q, iq2 % n_q, 0)
    q_gqa = ((1, block_q, d_pad), by_head)
    r_gqa = ((1, block_q, _SUB), by_head)
    k_spec = ((1, block_k, d_pad), lambda b, ik, iq2: (b, ik, 0))
    sub = _sub_tile(block_q, block_k)
    kernel = lambda body: functools.partial(
        body, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        kv_len=kv_len, t_pad=t_pad, sub=sub,
    )
    dk, dv = _tiled_call(
        kernel(_bwd_dkv_kernel), bh_kv,
        _grid(causal, kv_len, block_q, block_k, sub, by_key=True, group=group),
        out_shape=(
            jax.ShapeDtypeStruct((bh_kv, t_pad, d_pad), kf.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh_kv, t_pad, d_pad), vf.dtype, vma=vma),
        ),
        in_specs=[q_gqa, k_spec, k_spec, q_gqa, r_gqa, r_gqa, r_gqa],
        out_specs=(k_spec, k_spec),
        scratch_shapes=[
            pltpu.VMEM((block_k, d_pad), jnp.float32),
            pltpu.VMEM((block_k, d_pad), jnp.float32),
        ],
        interpret=interpret,
        name="bf_flash_dkv",
    )(qf, kf, vf, do, lse, delta, dlse_w)
    q_spec2 = ((1, block_q, d_pad), lambda b, iq, ik: (b, iq, 0))
    k_spec2 = ((1, block_k, d_pad), lambda b, iq, ik: (b // group, ik, 0))
    r_spec2 = ((1, block_q, _SUB), lambda b, iq, ik: (b, iq, 0))
    (dq,) = _tiled_call(
        kernel(_bwd_dq_kernel), bh, _grid(causal, kv_len, block_q, block_k, sub),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t_pad, d_pad), qf.dtype, vma=vma),
        ),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2,
                  r_spec2],
        out_specs=(q_spec2,),
        scratch_shapes=[pltpu.VMEM((block_q, d_pad), jnp.float32)],
        interpret=interpret,
        name="bf_flash_dq",
    )(qf, kf, vf, do, lse, delta, dlse_w)
    return dq, dk, dv


# -- custom-vjp wrapper over padded folded tensors ---------------------------


@functools.lru_cache(maxsize=None)
def _flash_fn(causal, scale, block_q, block_k, kv_len, interpret):
    """Differentiable flash attention on folded-padded [bh, t_pad, d_pad]
    tensors; one cached custom_vjp per static configuration."""

    @jax.custom_vjp
    def f(qf, kf, vf):
        out, _lse = _fwd_call(
            qf, kf, vf, causal, scale, block_q, block_k, kv_len, interpret
        )
        return out

    def f_fwd(qf, kf, vf):
        out, lse = _fwd_call(
            qf, kf, vf, causal, scale, block_q, block_k, kv_len, interpret
        )
        return out, (qf, kf, vf, out, lse)

    def f_bwd(res, do):
        qf, kf, vf, out, lse = res
        return _bwd_call(
            qf, kf, vf, out, lse, do, causal, scale, block_q, block_k,
            kv_len, interpret,
        )

    f.defvjp(f_fwd, f_bwd)
    return f


@functools.lru_cache(maxsize=None)
def _flash_lse_fn(causal, scale, block_q, block_k, kv_len, interpret):
    """Like :func:`_flash_fn` but returns ``(out, lse)`` with a joint VJP:
    the backward receives ``(do, dlse)`` and folds the lse cotangent into
    ``ds`` (``dlse_i * p_ij``). This is the building block for cross-block
    online-softmax merges (ring attention): each block's normalized output
    plus its logsumexp is enough to combine blocks exactly."""

    @jax.custom_vjp
    def f(qf, kf, vf):
        return _fwd_call(
            qf, kf, vf, causal, scale, block_q, block_k, kv_len,
            interpret, out_dtype=jnp.float32,
        )

    def f_fwd(qf, kf, vf):
        out, lse = _fwd_call(
            qf, kf, vf, causal, scale, block_q, block_k, kv_len,
            interpret, out_dtype=jnp.float32,
        )
        return (out, lse), (qf, kf, vf, out, lse)

    def f_bwd(res, cts):
        do, dlse = cts
        qf, kf, vf, out, lse = res
        # dlse arrives [bh, t_pad, _SUB] (broadcast rows); one lane is the
        # true cotangent sum across the broadcast
        dlse_row = dlse.sum(axis=-1)
        return _bwd_call(
            qf, kf, vf, out, lse, do, causal, scale, block_q, block_k,
            kv_len, interpret, dlse=dlse_row,
        )

    f.defvjp(f_fwd, f_bwd)
    return f


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def _flash_with_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    """Padded/folded kernel invocation returning ``(out, lse)`` in the
    caller's layout: out ``[b, t, h, d]``, lse ``[b, h, t]`` (f32)."""
    b, t, h, d = q.shape
    block_q, block_k = _blocks(t, block_q, block_k)
    t_pad = _padded(t, block_q, block_k)
    qp, kp, vp = (_pad_to(x, t_pad, d) for x in (q, k, v))
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * x.shape[2], t_pad, d
    )
    fn = _flash_lse_fn(causal, float(scale), block_q, block_k, t, interpret)
    out, lse = fn(fold(qp), fold(kp), fold(vp))
    out = out.reshape(b, h, t_pad, d).transpose(0, 2, 1, 3)[:, :t]
    lse = lse[:, :, 0].reshape(b, h, t_pad)[:, :, :t]
    return out, lse


def _dense_with_lse(q, k, v, causal, scale):
    """Dense XLA attention returning ``(out f32, lse)`` — the fallback
    branch and the CPU oracle for the lse-carrying kernel path. K/V with
    fewer heads than Q run grouped-query attention, same as
    :func:`reference_attention`."""
    from bluefog_tpu.ops.attention import _expand_kv

    k, v = _expand_kv(q, k), _expand_kv(q, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = s.max(-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = p.sum(-1)
    l_safe = jnp.where(l > 0, l, 1.0)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", (p / l_safe[..., None]),
        v.astype(jnp.float32),
    )
    lse = jnp.where(l > 0, m + jnp.log(l_safe), _NEG_INF)
    return out, lse  # out stays f32: block results merge in f32


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: bool = False):
    """Self-attention returning ``(out [b,t,h,d] f32, lse [b,h,t] f32)``.

    The logsumexp output makes per-block results mergeable across blocks
    (online-softmax combination), which is what ring attention needs to
    run each round's block attention through the Pallas kernels; ``out``
    is f32 so an n-round merge never round-trips the accumulator through
    bf16. Differentiable in both outputs. Kernel path on TPU, dense
    otherwise (selected per lowering platform)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if not flash_attention_supported(q, k, v):
        return _dense_with_lse(q, k, v, causal, scale)
    if interpret:
        return _flash_with_lse(q, k, v, causal, float(scale), block_q,
                               block_k, True)
    return jax.lax.platform_dependent(
        q, k, v,
        tpu=lambda q, k, v: _flash_with_lse(
            q, k, v, causal, float(scale), block_q, block_k, False
        ),
        default=lambda q, k, v: _dense_with_lse(q, k, v, causal, scale),
    )


def flash_attention_supported(q, k=None, v=None, *, block_q: int = 128,
                              block_k: int = 128) -> bool:
    """Kernel applicability: self-attention shapes only (one shared
    sequence length). Arbitrary sequence length and head_dim are handled
    by padded-with-masking tiles (``head_dim`` 128, a full lane width, is
    the kernels' best case; 64 runs unpadded at half the MXU's depth), and
    grouped-query K/V (fewer heads, ``h % h_kv == 0``, K and V alike) is
    served natively from the index maps, never expanded in HBM — so only
    cross-attention (mismatched batch/seq/dim) and K/V with differing
    head counts fall back."""
    del block_q, block_k  # any T tiles via padding; kept for API compat
    if q.ndim != 4 or q.shape[1] < 1:
        return False
    b, t, h, d = q.shape
    for other in (k, v):
        if other is None:
            continue
        if other.ndim != 4:
            return False
        ob, ot, oh, od = other.shape
        if (ob, ot, od) != (b, t, d) or oh < 1 or h % oh != 0:
            return False  # cross-attention / mismatched shapes: fall back
    if k is not None and v is not None and k.shape[2] != v.shape[2]:
        # the kernels derive ONE group factor and share the KV index map;
        # differing K/V head counts must take the dense path
        return False
    return True


def _pad_to(x, t_pad, d_pad):
    b, t, h, d = x.shape
    if t == t_pad and d == d_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0), (0, d_pad - d)))


def _auto_block(t: int) -> int:
    """Largest tile in {1024..128} whose padding waste stays under ~15%.

    Big tiles are what make the kernel fast — at T=8192/d=64 the measured
    forward is 2.3 ms with 1024-tiles vs 23 ms with 128-tiles (the grid
    shrinks 64x, so per-tile overhead stops dominating) — but padding a
    ragged tail up to a huge tile would waste more compute than the tile
    saves."""
    for b in (1024, 512, 256, 128):
        t_pad = -(-t // b) * b
        if t_pad - t <= max(t // 8, 127):
            return b
    return 128


def _sub_tile(block_q, block_k):
    """The side of the square sub-tiles a partial tile is walked in, or
    None to compute it whole, masked: 512 in a tile of 1 024 or more, whole
    below.

    Measured on the v5e (PR 39: one layer's ``value_and_grad``, the own time
    of the three kernels, the forward kernel once) at the four cells' shapes
    — causal at T = 1 024 with heads of 64, causal at 4 096 and block
    diffusion at 8 192 with heads of 128, tiles of 1 024 — against the
    parent's whole masked tiles (ms): ``gpt2m_1chip_full`` 0.997 → 1.009
    whole (the mask-free bodies alone), 2.743 in sub-tiles of 128, 1.429 of
    256, **0.840 of 512**; ``gpt2m_1chip_b1`` 0.247 → 0.250 / 0.682 / 0.354 /
    **0.207**; ``mistral4_1chip_b1`` 5.38 → 4.85 / 8.38 / 5.70 / **4.52**;
    ``sdar30b_1chip_b2`` 35.31 → 26.84 / 35.91 / 25.41 / **21.69**. The walk
    is a rolled loop, and a turn of it costs ≈ 0.4 µs however little it
    computes (its matmul → softmax → matmul chain does not overlap the next
    turn's), so a sub-tile must be large to pay: the same side won at either
    head size and under either mask kind, and neither enters the rule. A
    tile of 512 or less has no sub-tile that large, so it stays whole."""
    if block_q == block_k and block_q > 512 and block_q % 512 == 0:
        return 512
    return None


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    b, t, h, d = q.shape
    block_q, block_k = _blocks(t, block_q, block_k)
    # ragged tails tile via zero padding: padded K positions are masked to
    # -inf in-kernel (zero softmax weight), padded Q rows are discarded.
    # Cost: one O(T*d) copy, not O(T^2). head_dim needs no padding — the
    # kernel blocks span the full head axis, and Mosaic accepts any block
    # dim equal to the overall array dim (lane packing is its job; an
    # explicit pad to 128 would double the matmul FLOPs at d=64).
    t_pad = _padded(t, block_q, block_k)
    d_pad = d
    qp, kp, vp = (_pad_to(x, t_pad, d_pad) for x in (q, k, v))
    # fold by each tensor's OWN head count: grouped-query K/V stays
    # compact all the way into the kernel
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * x.shape[2], t_pad, d_pad
    )
    fn = _flash_fn(causal, scale, block_q, block_k, t, interpret)
    out = fn(fold(qp), fold(kp), fold(vp))
    out = out.reshape(b, h, t_pad, d_pad).transpose(0, 2, 1, 3)
    return out[:, :t, :, :d]


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    mask: Optional[BlockDiffusionMask] = None):
    """Flash attention on ``[batch, seq, heads, head_dim]`` tensors.

    Uses the Pallas TPU kernels (forward AND backward — safe inside
    ``jax.grad``) for any self-attention shape; only cross-attention /
    mismatched shapes and non-TPU platforms fall back to the dense XLA
    attention (same math). Tile sizes default to the largest that fits
    the sequence without excessive padding (see :func:`_auto_block`);
    pass ``block_q``/``block_k`` to override.

    Mask kinds: none, ``causal=True``, or ``mask=`` a
    :class:`BlockDiffusionMask` over a sequence of ``2 * mask.seq``
    positions (not with ``causal``). A ``mask`` is never sent down the
    dense path for its shapes: what the kernels cannot take raises."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    from bluefog_tpu.ops.attention import reference_attention

    supported = flash_attention_supported(
        q, k, v, block_q=block_q, block_k=block_k
    )
    if mask is not None:
        if causal:
            raise ValueError("give causal=True or a mask, not both")
        if not supported or q.shape[1] != 2 * mask.seq:
            raise ValueError(
                f"{mask} needs self-attention over 2 * seq = "
                f"{2 * mask.seq} positions, got q {q.shape}, k {k.shape}, "
                f"v {v.shape}: refused rather than run densely"
            )
        causal = mask  # the kernels' one static mask kind
    if not supported:
        return reference_attention(q, k, v, causal=causal, scale=scale)
    if interpret:
        return _flash(q, k, v, causal, float(scale), block_q, block_k,
                      True)
    # The kernel-vs-dense choice follows the platform the computation
    # actually LOWERS for, not the default backend: platform_dependent
    # resolves at lowering time, per backend, and prunes the dead branch.
    return jax.lax.platform_dependent(
        q, k, v,
        tpu=lambda q, k, v: _flash(
            q, k, v, causal, float(scale), block_q, block_k, False
        ),
        default=lambda q, k, v: reference_attention(
            q, k, v, causal=causal is True, scale=scale, mask=mask
        ).astype(q.dtype),  # branch outputs must agree: dense promotes
    )
