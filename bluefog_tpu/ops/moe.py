# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Sparse-expert feed-forward: a router, and an expert layer that is told
which experts it holds.

A mixture-of-experts layer replaces the feed-forward block with
``experts_total`` gated feed-forward experts of which every token chooses
``k``. Under expert parallelism the experts are divided over chips, and
each chip computes **its own experts' part** of the result for the tokens
routed to them. That is the contract of :func:`expert_layer`:

- the router (:func:`route`) keeps its full width — ``experts_total``
  logits, a float32 softmax, the ``k`` largest, renormalised over the
  chosen ones where ``norm_topk_prob`` — whatever is held here;
- the layer holds the contiguous range ``[held_start, held_start +
  experts_held)`` (the leading axis of its three stacked weight leaves);
- a (token, choice) pair whose expert is held *lands* here: its row is
  grouped with its expert's other rows, goes through one grouped matrix
  product per projection (:func:`grouped_product`: on the TPU the Pallas
  kernels ``bf_gmm`` / ``bf_tgmm``, whose work follows the rows that
  landed in the forward pass and in both gradients), and comes back
  weighted into its token's output. A pair whose expert is absent adds nothing — what the absent
  experts would have added is the other chips' part, and a token none of
  whose choices is held gets zeros from the layer;
- **no pair that lands here is dropped, whatever the router does**: the
  row buffer holds ``tokens x k`` rows, all that can arrive (and a tile
  of slack a held expert), so there is no capacity factor to tune and
  nothing to overflow. One path at any imbalance, and the work on the
  buffer is as long as the tiles in use, not as the buffer: the products'
  grids, and the passes XLA runs round them — the gathers that fill the
  buffer (``_dispatch``, and the combine's backward pass), the activation
  between the products and its derivative, the sum of the buffer's two
  cotangents — which are loops over chunks of whole tiles whose trip
  count is read from what landed (``_over_tiles_in_use``). One write as
  long as the buffer is left: a buffer that a loop fills (the rows, the
  activation) starts as one value written all over (``_smeared``), and
  nothing reads the tiles past the last chunk.

The shares add up: over a partition of the experts into held ranges the
layers' outputs sum to the uncut layer's (``tests/test_moe.py``). On one
chip the layer runs without its exchange; nothing here stands in for the
absent chips or their traffic.

Everything that moves rows is a gather in both directions: grouping is a
permutation of the ``tokens x k`` pairs, so the transpose of "take the rows
in sorted order" is "take them back in the inverse order", never a
scatter-add (``_dispatch``, ``_combine``). And what the tiles past the last
one in use hold is never read: in a product's output it is whatever was
there (a kernel's grid ends with the tiles in use), so both directions
select (``where``), never multiply by zero.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "route", "expert_layer", "grouped_product", "row_tile", "buffer_tiles",
    "tiles_in_use",
]


def route(u, w_router, k, norm_topk_prob=True, dtype=jnp.float32):
    """The router: ``u [tokens, hidden]``, ``w_router [hidden,
    experts_total]`` -> ``(weights [tokens, k] float32, experts [tokens, k]
    int32)``. The logits are a ``dtype`` product at the highest matmul
    precision — float32 unless a caller says otherwise: a top-k choice is a
    comparison, and a bfloat16 logit flips it on near ties — and the
    softmax and the renormalisation are float32 whatever ``dtype``."""
    logits = jnp.dot(
        u.astype(dtype), w_router.astype(dtype),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def _tokens_of_rows(buf, row_of_pair, landed, scale=None):
    """Token space from the row buffer: token ``t`` gets the float32 sum
    over its choices ``s`` that landed of ``buf[row of pair (t, s)]``
    (times ``scale[t, s]``). One gather of ``tokens`` rows a choice: the
    ``[tokens, k, d]`` view of one big gather would put ``k`` on the
    sublanes and pad it. ``buf [rows, d]`` -> ``[tokens, d]`` float32."""
    index = jnp.minimum(row_of_pair, buf.shape[0] - 1)
    total = jnp.zeros((landed.shape[0], buf.shape[1]), jnp.float32)
    for s in range(landed.shape[1]):
        term = buf[index[:, s]].astype(jnp.float32)
        if scale is not None:
            term = term * scale[:, s, None]
        # a select, not a product with 0: the tiles past the ones in use
        # are whatever was there
        total = total + jnp.where(landed[:, s, None], term, 0.0)
    return total


# -- passes over the row buffer ------------------------------------------------
#
# The buffer is laid out in tiles of `tm` rows (below), and the tiles in use
# are its first `tiles_used`, a device scalar. Whatever XLA does to
# buffer-shaped values it does a chunk of whole tiles at a time, in a loop
# whose trip count is ceil(tiles_used / chunk): a pass costs what landed,
# not what could have. The loops live inside `custom_vjp` forward and
# backward functions, so nothing differentiates through one.

# Tiles a turn takes: a few, so that the last turn overshoots by little. Not
# fitted: 4 and 16 read the same step on the chip to 0.01 % (PERF.md, PR 35).
_CHUNK_TILES = 8


def _smeared(shape, x):
    """A buffer for a pass over the tiles in use to fill, holding one
    element of ``x`` all over: the one write as long as the buffer. (A loop
    whose carry starts as an instruction without operands — ``jax.lax.empty``,
    zeros — makes XLA's scheduler defer kernels all over the step, which
    then holds 1.5 GB more: PERF.md, PR 35.)"""
    return jnp.broadcast_to(x.reshape(-1)[0], shape)


def _over_tiles_in_use(tm, tiles_used, turn, bufs, reads=(), others=()):
    """The row buffers ``bufs`` after ``turn(tm, first_tile, *blocks of
    bufs, *blocks of reads, *others) -> new blocks of bufs`` over every
    chunk of ``_CHUNK_TILES`` whole tiles that holds a tile in use (a buffer
    is whole chunks long: ``buffer_tiles``); ``reads`` are row buffers too,
    ``others`` whatever else a turn reads. A buffer whose old rows the turn
    reads is rewritten in place: a cotangent takes the place of the value
    it is the last to read. ``turn`` is a function of the module, not a
    closure (see ``_body``)."""
    turns = -(-tiles_used // _CHUNK_TILES)
    carry = (tuple(bufs), tuple(reads), tuple(others))
    return jax.lax.fori_loop(0, turns, _body(turn, tm), carry)[0]


@functools.lru_cache(maxsize=None)
def _body(turn, tm):
    """One loop body a kind of pass and tile: jax keeps a body's trace by
    the function it is, so the six layers of a stack (and the passes over
    each) trace it once; what a turn reads goes round in the carry."""
    rows = _CHUNK_TILES * tm

    def body(i, carry):
        bufs, reads, others = carry
        take = lambda buf: jax.lax.dynamic_slice_in_dim(buf, i * rows, rows)
        blocks = turn(
            tm, i * _CHUNK_TILES, *map(take, bufs), *map(take, reads), *others
        )
        bufs = tuple(
            jax.lax.dynamic_update_slice_in_dim(buf, block, i * rows, 0)
            for buf, block in zip(bufs, blocks)
        )
        return bufs, reads, others

    return body


# Grouping is a permutation of the tokens x k pairs, so "take the rows in
# sorted order" transposes to "take them back along the inverse", a gather
# again; autodiff would emit a scatter-add, which the TPU runs an update at
# a time.


class _Moves(NamedTuple):
    """Where the pairs go: integers and booleans, no cotangent."""

    sorted_pairs: jax.Array  # [pairs + tm] the pairs by held expert, absent last
    tile_rank: jax.Array  # [tiles] sorted rank of a tile's first pair
    tile_rows: jax.Array  # [tiles] pairs a tile holds (0 past the ones in use)
    tiles_used: jax.Array  # [] the buffer's first tiles, the ones in use
    row_of_pair: jax.Array  # [tokens, k] where a pair landed
    landed: jax.Array  # [tokens, k] whether it did


def _pairs_of_rows(tm, first, moves):
    """The rows of the chunk of tiles from ``first``: ``(pair, valid)``, the
    pair each row holds and whether it holds one. A tile holds ``tile_rows``
    pairs, consecutive in sorted order from ``tile_rank`` — a slice of
    ``sorted_pairs`` a tile, not a lookup a row (it is padded by a tile, so
    that no slice is moved to fit) — then rows of zeros."""
    take = lambda per_tile: jax.lax.dynamic_slice_in_dim(per_tile, first, _CHUNK_TILES)
    run = lambda rank: jax.lax.dynamic_slice_in_dim(moves.sorted_pairs, rank, tm)
    valid = jnp.arange(tm, dtype=jnp.int32) < take(moves.tile_rows)[:, None]
    return jax.vmap(run)(take(moves.tile_rank)).reshape(-1), valid.reshape(-1)


def _rows_of_tokens(x, pair, valid, k):
    """Rows from token space: row ``r`` holds ``x[token of pair[r]]``,
    zeros where it holds no pair. ``x [tokens, d]`` -> ``[len(pair), d]``."""
    return jnp.where(valid[:, None], x[pair // k], 0)


def _fill_turn(tm, first, _, u, *moves):
    moves = _Moves(*moves)
    pair, valid = _pairs_of_rows(tm, first, moves)
    return (_rows_of_tokens(u, pair, valid, moves.landed.shape[1]),)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(tm, u, moves):
    xs = _smeared((moves.tile_rows.shape[0] * tm, u.shape[1]), u)
    return _over_tiles_in_use(
        tm, moves.tiles_used, _fill_turn, (xs,), others=(u, *moves)
    )[0]


def _dispatch_fwd(tm, u, moves):
    return _dispatch(tm, u, moves), moves


def _dispatch_bwd(tm, moves, dxs):
    return _tokens_of_rows(dxs, moves.row_of_pair, moves.landed).astype(dxs.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _sums_turn(tm, first, _, out, dy, *moves):
    moves = _Moves(*moves)
    pair, valid = _pairs_of_rows(tm, first, moves)
    dy_rows = _rows_of_tokens(dy, pair, valid, moves.landed.shape[1])  # float32
    products = jnp.where(valid[:, None], dy_rows * out.astype(jnp.float32), 0.0)
    return (jnp.sum(products, axis=-1),)


def _weigh_turn(tm, first, out, d_w_rows, dy, weights, *moves):
    moves = _Moves(*moves)
    pair, valid = _pairs_of_rows(tm, first, moves)
    dy_rows = _rows_of_tokens(dy, pair, valid, moves.landed.shape[1])
    w_rows = weights.reshape(-1)[pair]
    return (dy_rows * w_rows[:, None]).astype(out.dtype), d_w_rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(tm, out, weights, moves):
    del tm
    return _tokens_of_rows(out, moves.row_of_pair, moves.landed, weights)


def _combine_fwd(tm, out, weights, moves):
    return _combine(tm, out, weights, moves), (out, weights, moves)


def _combine_bwd(tm, res, dy):
    out, weights, moves = res
    # Two passes, so that the cotangent can take `out`'s place: the row sums
    # are the last to read `out`, and the second loop rewrites them as they
    # are, which orders it behind them. (Handed to it as one of `others`
    # they are dropped from its carry as unused, and XLA copies `out`: the
    # compile test holds that. One pass would read and write the buffer in
    # two kernels of a turn, and XLA then copies all of it every turn.)
    (d_w_rows,) = _over_tiles_in_use(
        tm, moves.tiles_used, _sums_turn,
        (_smeared(out.shape[:1], dy),), (out,), (dy, *moves),
    )
    d_out, d_w_rows = _over_tiles_in_use(
        tm, moves.tiles_used, _weigh_turn,
        (out, d_w_rows), others=(dy, weights, *moves),
    )
    index = jnp.minimum(moves.row_of_pair, out.shape[0] - 1)
    d_w = jnp.where(moves.landed, d_w_rows[index], 0.0)
    return d_out, d_w.astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _add_turn(tm, first, a, b):
    return (a + b,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _twice(tm, xs, tiles_used):
    """``xs`` for each of two readers; their cotangents are added over the
    tiles in use (autodiff's own sum would go over the whole buffer)."""
    del tm, tiles_used
    return xs, xs


def _twice_fwd(tm, xs, tiles_used):
    return _twice(tm, xs, tiles_used), tiles_used


def _twice_bwd(tm, tiles_used, ds):
    return *_over_tiles_in_use(tm, tiles_used, _add_turn, ds[:1], ds[1:]), None


_twice.defvjp(_twice_fwd, _twice_bwd)


def _silu_times(gate, up):
    return (
        jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    ).astype(gate.dtype)


def _act_turn(tm, first, _, gate, up):
    return (_silu_times(gate, up),)


def _act_bwd_turn(tm, first, gate, up, d_act):
    return jax.vjp(_silu_times, gate, up)[1](d_act)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _activation(tm, gate, up, tiles_used):
    """``silu(gate) * up`` in float32, back in the products' dtype, over
    the tiles in use; its derivative is autodiff's own, a chunk at a time."""
    act = _smeared(gate.shape, gate)
    return _over_tiles_in_use(tm, tiles_used, _act_turn, (act,), (gate, up))[0]


def _activation_fwd(tm, gate, up, tiles_used):
    return _activation(tm, gate, up, tiles_used), (gate, up, tiles_used)


def _activation_bwd(tm, res, d_act):
    *gate_up, tiles_used = res
    return *_over_tiles_in_use(tm, tiles_used, _act_bwd_turn, gate_up, (d_act,)), None


_activation.defvjp(_activation_fwd, _activation_bwd)


# -- the grouped product -------------------------------------------------------
#
# The row buffer is laid out in tiles of `tm` rows and every tile belongs to
# one expert: a group starts on a tile and is filled up to a tile with rows
# of zeros (an empty group keeps one tile of them). A grouped product is then
# a plain tiled matrix product whose weight block is chosen per row tile
# (`tile_group`, scalar-prefetched), over a grid as long as the tiles in use:
# no mask inside a tile, no tile visited twice, and the work follows the
# rows that landed in the forward product and in both gradients.


def _tile(size, most):
    """The largest multiple of 128 that divides ``size`` and is at most
    ``most``; ``None`` where there is none."""
    for tile in range(min(size, most) // 128 * 128, 0, -128):
        if size % tile == 0:
            return tile
    return None


def row_tile(rows, held, k, n, dtype):
    """Rows a tile of the buffer takes (static): 512 or 128 where the
    Mosaic kernels can run the products (``k`` and ``n`` tile by 128,
    bfloat16 or float32), 8 where only ``ragged_dot`` will."""
    if (_tile(k, 1024) and _tile(n, 1024)
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)):
        return 512 if rows >= 512 * held else 128
    return 8


def buffer_tiles(rows, held, tm):
    """Tiles the buffer holds (static): every pair that can arrive, and a
    tile of slack a held expert — a group ends inside a tile at most once —
    filled up to whole chunks of the passes over it."""
    return -(-(-(-rows // tm) + held) // _CHUNK_TILES) * _CHUNK_TILES


def _tiles_per_group(rows_per_expert, tm):
    # the layout: a group starts on a tile and keeps at least one
    return jnp.maximum(1, -(-rows_per_expert // tm))


def tiles_in_use(rows_per_expert, tm):
    """Tiles of ``tm`` rows the layer's passes went over, from the
    ``rows_per_expert [..., experts_held]`` it returned and the tile it
    laid them out in (``row_tile``; the gauge ``bluefog.moe.row_tile``):
    the sum over held experts of ``max(1, ceil(rows / tm))``. Times ``tm``,
    against ``buffer_tiles x tm`` (``bluefog.moe.buffer_rows``), it is the
    share of the buffer touched."""
    return _tiles_per_group(rows_per_expert, tm).sum(axis=-1)


def _vma(*xs):
    # inside shard_map a kernel's outputs vary over the mesh axes its inputs
    # do; a pallas out_shape must say so or the vma check rejects the trace
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _gmm_kernel(group_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *, transpose_rhs):
    del group_ref  # the index maps read it
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], contract, preferred_element_type=jnp.float32
    )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gmm(lhs, rhs, tile_group, tiles_used, tm, transpose_rhs, interpret):
    """``lhs [rows, k]`` x ``rhs[group of the row's tile]`` (``[k, n]``, or
    ``[n, k]`` transposed) -> ``[rows, n]``, the first ``tiles_used`` tiles
    of ``tm`` rows; later tiles are not written."""
    rows, k = lhs.shape
    n = rhs.shape[1 if transpose_rhs else 2]
    tk, tn = _tile(k, 1024), _tile(n, 1024)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, tn, tk), lambda j, i, kk, g: (g[i], j, kk))
    else:
        rhs_spec = pl.BlockSpec((None, tk, tn), lambda j, i, kk, g: (g[i], kk, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype, vma=_vma(lhs, rhs)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, i, kk, g: (i, kk)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, kk, g: (i, j)),
            grid=(n // tn, tiles_used, k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="bf_gmm",
    )(tile_group, lhs, rhs)


def _tgmm_kernel(group_ref, lhs_ref, d_ref, out_ref, acc_ref):
    i, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ref[i]

    @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the transpose in float32, as megablox's tgmm makes it
    lhs_t = lhs_ref[...].astype(jnp.float32).swapaxes(0, 1).astype(lhs_ref.dtype)
    acc_ref[...] += jnp.dot(lhs_t, d_ref[...], preferred_element_type=jnp.float32)

    @pl.when((i == last) | (group_ref[jnp.minimum(i + 1, last)] != group))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(lhs, d, tile_group, tiles_used, tm, groups, interpret):
    """``out[g] = sum over the tiles of group g of lhs_tile^T d_tile``:
    ``lhs [rows, k]``, ``d [rows, n]`` -> ``[groups, k, n]``. Every group
    has a tile, so every block of the output is written."""
    (rows, k), n = lhs.shape, d.shape[1]
    tk, tn = _tile(k, 1024), _tile(n, 1024)
    return pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype, vma=_vma(lhs, d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda kk, j, i, g: (i, kk)),
                pl.BlockSpec((tm, tn), lambda kk, j, i, g: (i, j)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), lambda kk, j, i, g: (g[i], kk, j)),
            grid=(k // tk, n // tn, tiles_used),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="bf_tgmm",
    )(tile_group, lhs, d)


def _ragged(lhs, rhs, tiles, tm):
    return jax.lax.ragged_dot(
        lhs, rhs, tiles[1] * tm, preferred_element_type=lhs.dtype
    )


def _mosaic_or_ragged(static, mosaic, ragged, *operands):
    """The Mosaic kernels where the computation lowers for a TPU (or in
    the Pallas interpreter, asked for), ``ragged_dot`` on the same layout —
    its groups the padded ones — anywhere else and for shapes that do not
    tile."""
    tm, interpret = static
    if tm == 8:
        return ragged(*operands)
    if interpret:
        return mosaic(*operands)
    return jax.lax.platform_dependent(*operands, tpu=mosaic, default=ragged)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def grouped_product(static, lhs, rhs, tiles):
    """``lhs [rows, k]`` x ``rhs [groups, k, n]`` -> ``[rows, n]`` in
    ``lhs``'s dtype over a float32 accumulator, the rows in tiles of ``tm``
    (``static = (tm, interpret)``) and ``tiles = (tile_group [rows // tm],
    tiles_per_group [groups])``: tile ``i`` is multiplied by
    ``rhs[tile_group[i]]``, the first ``sum(tiles_per_group)`` tiles only —
    the rest of the output is not written. On the TPU the kernels
    ``bf_gmm`` (forward and towards the rows) and ``bf_tgmm`` (towards the
    weights), elsewhere ``jax.lax.ragged_dot``."""
    tm, interpret = static
    return _mosaic_or_ragged(
        static,
        lambda a, b, t: _gmm(a, b, t[0], t[1].sum(), tm, False, interpret),
        lambda a, b, t: _ragged(a, b, t, tm),
        lhs, rhs, tiles,
    )


def _grouped_product_fwd(static, lhs, rhs, tiles):
    return grouped_product(static, lhs, rhs, tiles), (lhs, rhs, tiles)


def _grouped_product_bwd(static, res, d):
    tm, interpret = static

    def mosaic(lhs, rhs, tiles, d):
        used = tiles[1].sum()
        return (
            _gmm(d, rhs, tiles[0], used, tm, True, interpret),
            _tgmm(lhs, d, tiles[0], used, tm, rhs.shape[0], interpret),
        )

    def ragged(lhs, rhs, tiles, d):
        _, vjp = jax.vjp(lambda a, b: _ragged(a, b, tiles, tm), lhs, rhs)
        return vjp(d)

    return (*_mosaic_or_ragged(static, mosaic, ragged, *res, d), None)


grouped_product.defvjp(_grouped_product_fwd, _grouped_product_bwd)


def expert_layer(u, weights, experts, w_gate, w_up, w_down, *,
                 held_start=0, dtype=None, interpret=False):
    """This chip's experts' part of a sparse-expert feed-forward layer.

    ``u [tokens, hidden]``; ``weights`` / ``experts`` ``[tokens, k]`` from
    :func:`route` (over all the experts there are); ``w_gate`` / ``w_up``
    ``[experts_held, hidden, width]`` and ``w_down [experts_held, width,
    hidden]``, the stacked weights of experts ``held_start ..
    held_start + experts_held - 1``. Returns ``(y, counts)``:

    ``y [tokens, hidden]`` in ``dtype`` (default ``u``'s) is ``sum over the
    token's choices e that are held of weight_e * down_e(silu(gate_e(u)) *
    up_e(u))``; products in ``dtype``, accumulated in float32.

    One path at any imbalance, up to every choice of every token landing
    here: the buffer holds all ``tokens x k`` pairs (and a tile of slack a
    held expert), nothing is ever dropped and no setting tunes it.
    ``interpret`` runs the kernels in the Pallas interpreter (CPU tests).

    ``counts`` are the device's own int32 counts, for a caller to return
    beside its loss and read at its own sync: ``rows_per_expert
    [experts_held]`` (pairs that landed on each held expert),
    ``rows_absent`` (pairs whose expert is another chip's) and
    ``rows_dropped`` (landed pairs that reached no group: the buffer holds
    every pair, so this is 0 by construction and says so).
    """
    dtype = u.dtype if dtype is None else dtype
    n, k = experts.shape
    held = w_gate.shape[0]
    pairs = n * k  # what can arrive: every choice of every token
    tm = row_tile(pairs, held, w_gate.shape[1], w_gate.shape[2], dtype)
    tiles = buffer_tiles(pairs, held, tm)

    with jax.named_scope("bf.moe.route"):
        local = experts.reshape(pairs) - held_start
        landed = (local >= 0) & (local < held)
        # group by held expert; the pairs of absent experts sort last and
        # belong to no group
        key = jnp.where(landed, local, held)
        sorted_pairs = jnp.argsort(key, stable=True).astype(jnp.int32)
        rank_of_pair = jnp.argsort(sorted_pairs).astype(jnp.int32)
        # a compare-and-sum, not a bincount: that is a scatter-add of every
        # pair into a handful of bins, which the TPU runs an update at a time
        is_group = key[:, None] == jnp.arange(held + 1, dtype=key.dtype)
        sizes = jnp.sum(is_group, axis=0, dtype=jnp.int32)
        group_sizes, rows_absent = sizes[:held], sizes[held]
        # every pair that landed is in a group, and the buffer holds every
        # pair: the difference is 0 unless this arithmetic is broken
        rows_dropped = landed.sum().astype(jnp.int32) - group_sizes.sum()

        tiles_per_group = _tiles_per_group(group_sizes, tm)
        tiles_used = tiles_per_group.sum()
        tile_end = jnp.cumsum(tiles_per_group)
        first_tile = tile_end - tiles_per_group  # of a group, in the buffer
        first_rank = jnp.cumsum(group_sizes) - group_sizes  # in sorted order
        tile_group = jnp.minimum(
            jnp.sum(jnp.arange(tiles)[:, None] >= tile_end, axis=1, dtype=jnp.int32),
            held - 1,
        )
        # tile -> the pairs it holds: how many, and the first one's rank (a
        # tile past the ones in use holds none); the rows themselves are
        # worked out a chunk at a time, where they are moved
        before = (jnp.arange(tiles, dtype=jnp.int32) - first_tile[tile_group]) * tm
        tile_rows = jnp.clip(group_sizes[tile_group] - before, 0, tm)
        tile_rank = first_rank[tile_group] + before
        # pair -> its buffer row (where it landed)
        shift = jnp.append(first_tile * tm - first_rank, 0)
        row_of_pair = rank_of_pair + jnp.sum(is_group * shift, axis=1, dtype=jnp.int32)

        moves = _Moves(
            jnp.pad(sorted_pairs, (0, tm)), tile_rank, tile_rows, tiles_used,
            row_of_pair.reshape(n, k), landed.reshape(n, k),
        )
        xs = _dispatch(tm, u.astype(dtype), moves)
    with jax.named_scope("bf.moe.experts"):
        def product(lhs, rhs):
            return grouped_product(
                (tm, interpret), lhs, rhs.astype(dtype), (tile_group, tiles_per_group)
            )

        xs_gate, xs_up = _twice(tm, xs, tiles_used)
        act = _activation(
            tm, product(xs_gate, w_gate), product(xs_up, w_up), tiles_used
        )
        out = product(act, w_down)
    with jax.named_scope("bf.moe.combine"):
        y = _combine(tm, out, weights, moves).astype(dtype)

    counts = {
        "rows_per_expert": group_sizes,
        "rows_absent": rows_absent,
        "rows_dropped": rows_dropped,
    }
    return y, counts
