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
  nothing to overflow. One path at any imbalance: the products do work
  only for the rows that did arrive; the gathers that fill and read the
  buffer, and the activation between the products, go over all of it.

The shares add up: over a partition of the experts into held ranges the
layers' outputs sum to the uncut layer's (``tests/test_moe.py``). On one
chip the layer runs without its exchange; nothing here stands in for the
absent chips or their traffic.

Everything that moves rows is a gather in both directions: grouping is a
permutation of the ``tokens x k`` pairs, so the transpose of "take the rows
in sorted order" is "take them back in the inverse order", never a
scatter-add (``_dispatch``, ``_combine``). And what the grouped product
leaves in the tiles past the last one in use is never read: it is whatever
was there, so both directions select (``where``), never multiply by zero.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["route", "expert_layer", "grouped_product", "row_tile"]


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


def _rows_of_tokens(x, pair_of_row, k, valid):
    """The row buffer from token space: row ``r`` holds ``x[token of the
    pair laid out at r]``, zeros where the layout holds no pair. ``x [tokens, d]``
    -> ``[rows, d]``."""
    return jnp.where(valid[:, None], x[pair_of_row // k], 0)


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
        # are whatever the grouped product left there
        total = total + jnp.where(landed[:, s, None], term, 0.0)
    return total


# Grouping is a permutation of the tokens x k pairs, so "take the rows in
# sorted order" transposes to "take them back along the inverse", a gather
# again; autodiff would emit a scatter-add, which the TPU runs an update at
# a time. `moves` = (pair_of_row [rows], row_of_pair [tokens, k], landed
# [tokens, k], valid [rows]): integers and booleans, no cotangent.


@jax.custom_vjp
def _dispatch(u, moves):
    pair_of_row, _, landed, valid = moves
    return _rows_of_tokens(u, pair_of_row, landed.shape[1], valid)


def _dispatch_fwd(u, moves):
    return _dispatch(u, moves), moves


def _dispatch_bwd(moves, dxs):
    _, row_of_pair, landed, _ = moves
    return _tokens_of_rows(dxs, row_of_pair, landed).astype(dxs.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, weights, moves):
    _, row_of_pair, landed, _ = moves
    return _tokens_of_rows(out, row_of_pair, landed, weights)


def _combine_fwd(out, weights, moves):
    return _combine(out, weights, moves), (out, weights, moves)


def _combine_bwd(res, dy):
    out, weights, moves = res
    pair_of_row, row_of_pair, landed, valid = moves
    k = landed.shape[1]
    dy_rows = _rows_of_tokens(dy, pair_of_row, k, valid)  # float32
    w_rows = weights.reshape(-1)[pair_of_row]
    d_out = (dy_rows * w_rows[:, None]).astype(out.dtype)
    d_w_rows = jnp.sum(
        jnp.where(valid[:, None], dy_rows * out.astype(jnp.float32), 0.0), axis=-1
    )
    index = jnp.minimum(row_of_pair, out.shape[0] - 1)
    d_w = jnp.where(landed, d_w_rows[index], 0.0)
    return d_out, d_w.astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


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
    tiles = -(-pairs // tm) + held  # a group ends inside a tile at most once
    rows = tiles * tm

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

        # the layout: a group starts on a tile and keeps at least one
        tiles_per_group = jnp.maximum(1, -(-group_sizes // tm))
        tile_end = jnp.cumsum(tiles_per_group)
        first_row = (tile_end - tiles_per_group) * tm  # of a group, in the buffer
        first_rank = jnp.cumsum(group_sizes) - group_sizes  # in sorted order
        tile_group = jnp.minimum(
            jnp.sum(jnp.arange(tiles)[:, None] >= tile_end, axis=1, dtype=jnp.int32),
            held - 1,
        )
        # buffer row -> the pair it holds (tile by tile: small lookups)
        in_group = (
            jnp.arange(rows, dtype=jnp.int32).reshape(tiles, tm)
            - first_row[tile_group][:, None]
        )
        valid = (in_group < group_sizes[tile_group][:, None]).reshape(rows)
        rank_of_row = (first_rank[tile_group][:, None] + in_group).reshape(rows)
        pair_of_row = sorted_pairs[jnp.clip(rank_of_row, 0, pairs - 1)]
        # pair -> its buffer row (where it landed)
        shift = jnp.append(first_row - first_rank, 0)
        row_of_pair = rank_of_pair + jnp.sum(is_group * shift, axis=1, dtype=jnp.int32)
        landed = landed.reshape(n, k)

        moves = (pair_of_row, row_of_pair.reshape(n, k), landed, valid)
        xs = _dispatch(u.astype(dtype), moves)
    with jax.named_scope("bf.moe.experts"):
        def product(lhs, rhs):
            return grouped_product(
                (tm, interpret), lhs, rhs.astype(dtype), (tile_group, tiles_per_group)
            )

        gate, up = product(xs, w_gate), product(xs, w_up)
        act = (
            jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        ).astype(dtype)
        out = product(act, w_down)
    with jax.named_scope("bf.moe.combine"):
        y = _combine(out, weights, moves).astype(dtype)

    counts = {
        "rows_per_expert": group_sizes,
        "rows_absent": rows_absent,
        "rows_dropped": rows_dropped,
    }
    return y, counts
