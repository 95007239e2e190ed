# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Decentralized optimizer layer: every reference factory, optax-composed.

The reference wraps ``torch.optim`` objects and splices communication into
module forward/backward hooks so it overlaps compute
(``torch/optimizers.py:166-1554``); the combine order distinguishes the
families — CTA (combine-then-adapt: gossip the weights, then take the
local optimizer step) vs ATC (adapt-then-combine: step first, gossip the
result). On TPU there are two execution shapes. ``opt.step(params, state,
grads)`` compiles the update + gossip into one jitted shard_map program —
but the caller's forward/backward is a SEPARATE program, and XLA cannot
overlap collectives with compute across a program boundary: every ppermute
round in ``step`` sits fully exposed on the critical path between the two
dispatches. ``opt.make_train_step(loss_fn)`` removes that boundary — it
fuses forward, backward, inner update, and the gossip combine into ONE
program, the only place XLA's latency-hiding scheduler can actually run
the ppermute rounds concurrently with backward/update compute (see
``docs/performance.md`` "Overlapping communication with compute").
The reference's hand-rolled inner sgd/adam/rmsprop/
adagrad/adadelta re-implementations (optimizers.py:564-842) collapse into
"pass any optax transformation".

Factory parity map (reference torch/optimizers.py line refs):

- DistributedGradientAllreduceOptimizer (:1376) — psum-mean the gradients.
- DistributedAllreduceOptimizer        (:1301) — CTA, global allreduce.
- DistributedNeighborAllreduceOptimizer(:1326) — CTA, neighbor gossip.
- DistributedHierarchicalNeighborAllreduceOptimizer (:1352) — CTA,
  machine-level gossip.
- DistributedAdaptThenCombineOptimizer (:1426) — ATC, comm type selectable.
- DistributedAdaptWithCombineOptimizer (:1497) — CTA, comm type selectable.
- DistributedWinPutOptimizer   (:1271) — diffusion via win_put.
- DistributedPullGetOptimizer  (:1225) — diffusion via win_get.
- DistributedPushSumOptimizer  (:1180) — directed-graph push-sum via
  win_accumulate + associated-p correction.

Dynamic topology follows the reference idiom: assign
``opt.self_weight / opt.src_weights / opt.dst_weights`` (or a precompiled
``opt.schedule``) between steps; the compiled-step cache is keyed by the
resolved plan, so periodic schedules never retrace.

Distributed state model: parameters, optimizer state, and gradients are
worker-stacked pytrees (leading axis = worker), the same convention as
:mod:`bluefog_tpu.collective.ops`.
"""

import contextlib
import enum
import itertools
import re
import time
import warnings
from typing import Any, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
import optax

from bluefog_tpu import attribution
from bluefog_tpu import autotune as autotune_mod
from bluefog_tpu import context as ctx_mod
from bluefog_tpu import flight
from bluefog_tpu import sharding
from bluefog_tpu import health as health_mod
from bluefog_tpu import memory as memory_mod
from bluefog_tpu import metrics as metrics_mod
from bluefog_tpu import slo as slo_mod
from bluefog_tpu import staleness as staleness_mod
from bluefog_tpu import timeline as tl
from bluefog_tpu import windows as win_mod
from bluefog_tpu.collective import compiler, inner, ops as col_ops
from bluefog_tpu.collective.plan import SchedulePlan, plan_from_topology
from bluefog_tpu.logging_util import warn_once
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "CommunicationType",
    "DistributedGradientAllreduceOptimizer",
    "DistributedAllreduceOptimizer",
    "DistributedNeighborAllreduceOptimizer",
    "DistributedHierarchicalNeighborAllreduceOptimizer",
    "DistributedAdaptThenCombineOptimizer",
    "DistributedAdaptWithCombineOptimizer",
    "DistributedWinPutOptimizer",
    "DistributedPullGetOptimizer",
    "DistributedPushSumOptimizer",
]


class CommunicationType(enum.Enum):
    """Reference ``CommunicationType`` (torch/optimizers.py:28-32)."""

    neighbor_allreduce = "neighbor.allreduce"
    hierarchical_neighbor_allreduce = "hierarchical.neighbor.allreduce"
    allreduce = "allreduce"
    empty = "empty"


def _tree_block(tree):
    return jax.tree_util.tree_map(lambda t: t[0], tree)


def _dtype_groups(leaves):
    """Deterministic (dtype-sorted) same-dtype leaf groups:
    [(dtype_str, [leaf_idx...])]."""
    groups: dict = {}
    for i, l in enumerate(leaves):
        groups.setdefault(str(jnp.result_type(l)), []).append(i)
    return sorted(groups.items())


@jax.named_scope("bf.gossip")
def _bucketed_flat_gossip(flat, gossip_fn, step, wops, cap_bytes):
    """Gossip a flat payload in size-capped buckets (Horovod-style).

    Each bucket issues its own plan rounds, so independent buckets'
    ppermutes can pipeline — bucket k+1's combine arithmetic overlaps
    bucket k's transfer — instead of the whole model serializing behind
    one monolithic payload. Slicing a flat vector never reorders
    elements, and the combine is elementwise per element, so bucketed
    output is bitwise-identical to the monolithic combine (quantized
    wires included: bounds snap to the 512-element scale chunk)."""
    bounds = inner.bucket_bounds(flat.size, flat.dtype.itemsize, cap_bytes)
    if len(bounds) == 1:
        return gossip_fn(flat, step, wops)
    return jnp.concatenate(
        [gossip_fn(flat[a:b], step, wops) for a, b in bounds]
    )


def _travels_alone(n_elems, itemsize, cap_bytes):
    """The fusion threshold: a leaf of at least ``cap_bytes`` (it would
    fill a whole wire bucket by itself) gains nothing from packing."""
    return cap_bytes > 0 and n_elems * itemsize >= cap_bytes


def _packed_gossip(tree, gossip_fn, step, wops, cap_bytes=0, direct=False):
    """Apply a gossip combine to a whole pytree: small leaves packed per
    dtype group into size-capped wire buckets, large leaves alone.

    XLA does not combine per-leaf collective-permutes (a 6-leaf ATC step
    over a 3-round plan compiles to 18 of them — verified by
    tests/test_fusion.py), so a model-sized tree would pay
    O(leaves x rounds) message latencies. Packing the same-dtype leaves
    into one flat vector before the combine is the TPU-native analogue of
    the reference's tensor-fusion buffer (``tensor_queue.h:75-124``, 8 MiB
    threshold, ``global_state.h:91``): the many-leaf gossip becomes a
    single ppermute payload per round, at the price of one concat/split
    (a fused HBM copy) per step. Grouping by dtype keeps the wire policy
    intact — bf16 leaves gossip in bf16, never promoted by packing.

    ``cap_bytes`` > 0 re-splits each packed payload into independent
    buckets (:func:`bluefog_tpu.collective.inner.bucket_bounds`) so the
    scheduler can pipeline them; 0 keeps one payload per dtype group.

    ``direct`` (the exact wire, whose combine is elementwise over any
    shape) makes the cap the reference's fusion *threshold*: a leaf that
    :func:`_travels_alone` is combined whole and in its own shape — no
    flatten, bucket slices, concatenate and unpack, four parameter-sized
    copies on the TPU (PERF.md, PR 28) — and only the smaller leaves are
    packed. Without it (quantized wires, whose 512-element scale chunks
    run over the flat order) every leaf is packed.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = [None] * len(leaves)
    for _dt, idxs in _dtype_groups(leaves):
        if direct:
            for i in idxs:
                l = leaves[i]
                if _travels_alone(l.size, l.dtype.itemsize, cap_bytes):
                    with jax.named_scope("bf.gossip"):
                        out[i] = gossip_fn(l, step, wops)
            idxs = [i for i in idxs if out[i] is None]
            if not idxs:
                continue
        if len(idxs) == 1:
            i = idxs[0]
            l = leaves[i]
            bounds = inner.bucket_bounds(l.size, l.dtype.itemsize, cap_bytes)
            if len(bounds) == 1:
                with jax.named_scope("bf.gossip"):
                    out[i] = gossip_fn(l, step, wops)
            else:
                res = _bucketed_flat_gossip(
                    l.reshape(-1), gossip_fn, step, wops, cap_bytes
                )
                out[i] = res.reshape(l.shape)
            continue
        with jax.named_scope("bf.pack"):
            flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
        res = _bucketed_flat_gossip(flat, gossip_fn, step, wops, cap_bytes)
        with jax.named_scope("bf.unpack"):
            off = 0
            for i in idxs:
                n = leaves[i].size
                out[i] = res[off:off + n].reshape(leaves[i].shape)
                off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _packed_gossip_ef(tree, ef_blocks, ef_combine, cap_bytes=0):
    """Like :func:`_packed_gossip` but with sender error-feedback state:
    one f32 residual vector per dtype group, threaded through the combine
    (``ef_combine(flat, e) -> (y, e_new)``). Returns (tree', ef').

    Bucketing slices the residual state with the payload (the state is
    positional over the same flat vector), so each bucket carries its own
    error feedback and the reassembled state layout is unchanged."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = [None] * len(leaves)
    ef_out = []
    for gi, (_dt, idxs) in enumerate(_dtype_groups(leaves)):
        with jax.named_scope("bf.pack"):
            flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
        bounds = inner.bucket_bounds(
            flat.size, flat.dtype.itemsize, cap_bytes
        )
        e_self, e_recv = ef_blocks[gi]
        with jax.named_scope("bf.gossip"):
            if len(bounds) == 1:
                y, e_new = ef_combine(flat, (e_self, e_recv))
            else:
                ys, e_selfs, e_recvs = [], [], []
                for a, b in bounds:
                    yb, (es, er) = ef_combine(
                        flat[a:b], (e_self[a:b], e_recv[:, a:b])
                    )
                    ys.append(yb)
                    e_selfs.append(es)
                    e_recvs.append(er)
                y = jnp.concatenate(ys)
                e_new = (
                    jnp.concatenate(e_selfs),
                    jnp.concatenate(e_recvs, axis=1),
                )
        ef_out.append(e_new)
        with jax.named_scope("bf.unpack"):
            off = 0
            for i in idxs:
                n = leaves[i].size
                out[i] = y[off:off + n].reshape(leaves[i].shape)
                off += n
    return jax.tree_util.tree_unflatten(treedef, out), tuple(ef_out)


def _shard_check_groups(tree, layout, what):
    """The packed dtype groups of ``tree`` must be exactly the groups
    the shard layout was built for — a silent mismatch would slice the
    wrong coordinates."""
    leaves = jax.tree_util.tree_leaves(tree)
    got = tuple(
        (dt, sum(int(np.prod(leaves[i].shape)) for i in idxs))
        for dt, idxs in _dtype_groups(leaves)
    )
    want = tuple((g.dtype, g.elems) for g in layout.groups)
    if got != want:
        raise ValueError(
            f"BLUEFOG_SHARD: the {what} tree packs into dtype groups "
            f"{got} but the shard layout was built for {want}; "
            "gradients must share the parameter tree's dtypes (re-init "
            "the optimizer state after changing parameter avals)"
        )


def _shard_own_slices(tree, layout, axis):
    """Each rank's owned 512-aligned slot of every packed dtype group
    (traced): pack -> pad to the layout grid -> dynamic-slice at this
    rank's owner index. Dead ranks slice slot 0 — they compute an
    unused duplicate whose output the gather never selects."""
    packs = _pack_groups(tree)
    lidx = jnp.asarray(layout.live_index())
    i = lidx[jax.lax.axis_index(axis)]
    out = []
    with jax.named_scope("bf.pack"):
        for gi, gsh in enumerate(layout.groups):
            f = jnp.pad(packs[gi], (0, gsh.padded - packs[gi].shape[0]))
            out.append(
                jax.lax.dynamic_slice_in_dim(f, i * gsh.slot, gsh.slot)
            )
    return tuple(out)


def _sharded_inner_update(tx, layout, p, s, g, own_g=None):
    """The ZeRO-1 weight update (arxiv 2004.13336), valid exactly when
    the update inputs are rank-invariant (the gradient-allreduce
    family): each rank updates only its owned slot of the packed
    parameter vector with its 1/N optax-state shard (optionally against
    an fp32 master slice), then one ``all_gather`` redistributes the
    updated slices and the full tree is repacked. Runs inside the
    shard_map block on UNSTACKED trees; ``s`` is a
    :class:`bluefog_tpu.sharding.ShardedOptState`. Returns ``(p, s)``.

    ``own_g`` short-circuits the gradient slicing for the ZeRO-2 form:
    the reduce-scatter already delivered each rank its owned slot of
    the fleet-mean gradient, so the full-width gradient is never
    materialized here (:func:`_scatter_own_grads`).
    """
    _shard_check_groups(p, layout, "parameter")
    if own_g is None:
        _shard_check_groups(g, layout, "gradient")
        own_g = _shard_own_slices(g, layout, ctx_mod.WORKER_AXIS)
    own_p = _shard_own_slices(p, layout, ctx_mod.WORKER_AXIS)
    with jax.named_scope("bf.inner_update"):
        if layout.master:
            # fp32 master slices carry the reference values; the update
            # runs in fp32 and the wire ships the narrowed result
            own_g = tuple(x.astype(jnp.float32) for x in own_g)
            updates, inner_s = tx.update(own_g, s.inner, s.master)
            masters = optax.apply_updates(s.master, updates)
            new_own = tuple(
                m.astype(o.dtype) for m, o in zip(masters, own_p)
            )
            s_out = sharding.ShardedOptState(inner_s, tuple(masters))
        else:
            updates, inner_s = tx.update(own_g, s.inner, own_p)
            new_own = optax.apply_updates(own_p, updates)
            s_out = sharding.ShardedOptState(inner_s, ())
    live_rows = jnp.asarray(np.asarray(layout.live, np.int32))
    full = []
    with jax.named_scope("bf.gossip"):
        for gi, gsh in enumerate(layout.groups):
            gathered = jax.lax.all_gather(
                new_own[gi], ctx_mod.WORKER_AXIS
            )  # [size, slot]
            full.append(
                jnp.take(gathered, live_rows, axis=0).reshape(-1)[:gsh.elems]
            )
    return _unpack_groups(p, tuple(full)), s_out


def _scatter_own_grads(g, layout, wire, chunks, ef_blocks):
    """The ZeRO-2 gradient leg (arxiv 2004.13336's full
    weight-update-sharding form): ring reduce-scatter every packed
    dtype group so each rank receives ONLY its owned 512-aligned slot
    of the fleet-mean gradient — the full-width allreduce output is
    never materialized. The scatter speaks the same wire tiers as the
    gossip path (``wire``); the ``*_ef`` tiers hold their CHOCO
    residual per-slot in ``ef_blocks`` (one ``[padded]`` f32 per
    group). Reduction order is fixed inside
    :func:`bluefog_tpu.collective.inner.reduce_scatter` (own row
    first, then ring rounds in order), which is what keeps the
    sharded==replicated trajectory pins inside their envelopes.
    Returns ``(own_g, ef_blocks')`` — ``ef_blocks'`` is ``()`` for the
    residual-free tiers."""
    _shard_check_groups(g, layout, "gradient")
    packs = _pack_groups(g)
    live_index = tuple(int(v) for v in layout.live_index())
    live_set = set(layout.live)
    live_mask = tuple(
        1.0 if r in live_set else 0.0 for r in range(layout.size)
    )
    own, ef_out = [], []
    for gi, gsh in enumerate(layout.groups):
        with jax.named_scope("bf.pack"):
            f = jnp.pad(packs[gi], (0, gsh.padded - packs[gi].shape[0]))
        k = chunks[gi] if gi < len(chunks) else 1
        with jax.named_scope("bf.gossip"):
            if wire in ("int8_ef", "int4_ef"):
                y, e_new = inner.reduce_scatter(
                    f, ctx_mod.WORKER_AXIS, live_index, gsh.slot,
                    average=True, wire=wire, chunks=k,
                    ef=ef_blocks[gi], live_mask=live_mask,
                )
                ef_out.append(e_new)
            else:
                y = inner.reduce_scatter(
                    f, ctx_mod.WORKER_AXIS, live_index, gsh.slot,
                    average=True, wire=wire, chunks=k,
                )
        own.append(y)
    return tuple(own), tuple(ef_out)


@jax.named_scope("bf.inner_update")
def _inner_update(tx, g, s, p):
    """The optax step on UNSTACKED trees: ``(p', s')``."""
    updates, s = tx.update(g, s, p)
    return optax.apply_updates(p, updates), s


def _combine_update(order, tx, gossip_fn, wops, step, cap_bytes,
                    ef, ef_state, p, s, g, wire=None, with_metrics=False,
                    shard=None, scatter_wire=None, scatter_chunks=(),
                    direct=False):
    """The gossip+inner-update of one step, called from the one step body
    (:meth:`_GossipOptimizer._build_step`) that :meth:`_GossipOptimizer.step`
    and the fused ``train_step`` both dispatch. Runs inside a shard_map
    block on UNSTACKED (per-worker) trees; returns ``(p, s, ef_state',
    mvec)``.

    ``with_metrics=True`` additionally computes the gossip-health metric
    row (:func:`bluefog_tpu.metrics.build_probe_payload`) from the
    combine's own intermediates — purely extra *outputs*, never touching
    the values that feed ``p``/``s``, so metrics on/off stays
    bitwise-identical for the training state (tests/test_metrics.py);
    ``mvec`` is None when off. ``wire`` names the quantized wire in use
    so the metric row can include its quantization error. ``direct`` is
    :meth:`_GossipOptimizer._direct_route`'s verdict for
    :func:`_packed_gossip`.
    """
    mvec = None
    allreduce_fn = lambda t, _s, _w: inner.allreduce(
        t, ctx_mod.WORKER_AXIS, average=True
    )

    def probe(tree, ef_st, comb_fn):
        """The metrics SUB-GOSSIP: slice a 512-aligned prefix of the
        packed combine INPUT (touching inputs is free) and run the SAME
        wire on just that subsample — the combine is elementwise (and
        chunk-local for the quantized wires, with 512-aligned prefixes
        preserving chunk boundaries), so the tiny combine's output is
        bitwise the restriction of the full combine. The BIG combine's
        outputs are never consumed: any metric path touching them
        (tree-domain or packed, sliced or reduced) was measured to
        derail the CPU backend's schedule by ~a third of a step."""
        pairs = []
        for gi, (sub, scale) in enumerate(
            _packed_prefix(tree, metrics_mod.sample_elems_cap())
        ):
            if ef:
                e_self, e_recv = ef_st[gi]
                k = sub.shape[0]
                # restriction of the CHOCO combine: state slices are
                # INPUT values; the probe's updated copies are exported
                # for the residual metric and then discarded
                y_sub, (es_new, _er_new) = comb_fn(
                    sub, (e_self[:k], e_recv[:, :k]), wops
                )
                pairs.append((sub, y_sub, scale, es_new))
            else:
                y_sub = comb_fn(sub, step, wops)
                pairs.append((sub, y_sub, scale, None))
        g_subs = (
            _packed_prefix(g, metrics_mod.sample_elems_cap())
            if g is not None else ()
        )
        return metrics_mod.build_probe_payload(pairs, g_subs, wire=wire)

    if order == "grad":
        # order='grad' only exists with allreduce communication
        # (DistributedGradientAllreduceOptimizer); the "iterate" on the
        # wire IS the local gradient: disagreement = ||g_avg - g_local||
        if with_metrics:
            mvec = probe(g, ef_state, allreduce_fn)
        if shard is not None and shard.grads:
            # BLUEFOG_SHARD_GRADS=1 (ZeRO-2): lower the gradient
            # allreduce to reduce-scatter(own slot) — each rank
            # receives only the 1/N slot its update consumes, and the
            # ef_state slot carries the scatter wire's per-slot
            # residuals (not the gossip CHOCO copies)
            own_g, ef_state = _scatter_own_grads(
                g, shard, scatter_wire, scatter_chunks, ef_state
            )
            p, s = _sharded_inner_update(
                tx, shard, p, s, g, own_g=own_g
            )
            return p, s, ef_state, mvec
        g = _packed_gossip(g, allreduce_fn, step, wops, cap_bytes, direct)

    if shard is not None:
        # BLUEFOG_SHARD=1: the allreduce above made the gradient
        # rank-invariant, so the replicated inner update is redundant —
        # run the ZeRO-1 sharded form instead (1/N state, owned-slot
        # update, all-gather redistribution). `s` is a ShardedOptState.
        p, s = _sharded_inner_update(tx, shard, p, s, g)
        return p, s, ef_state, mvec

    def communicate(tree, ef_st):
        nonlocal mvec
        if with_metrics and order in ("cta", "atc"):
            mvec = probe(tree, ef_st, gossip_fn)
        if ef:
            return _packed_gossip_ef(
                tree,
                ef_st,
                lambda flat, e: gossip_fn(flat, e, wops),
                cap_bytes,
            )
        return _packed_gossip(
            tree, gossip_fn, step, wops, cap_bytes, direct
        ), ef_st

    if order == "cta":
        p, ef_state = communicate(p, ef_state)
    p, s = _inner_update(tx, g, s, p)
    if order == "atc":
        p, ef_state = communicate(p, ef_state)
    return p, s, ef_state, mvec


@jax.named_scope("bf.pack")
def _pack_groups(tree):
    """Per-dtype-group flat packed payloads of an UNSTACKED tree, in
    :func:`_dtype_groups` order — the wire layout `_packed_gossip` uses."""
    leaves = jax.tree_util.tree_leaves(tree)
    return tuple(
        jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
        if len(idxs) > 1
        else leaves[idxs[0]].reshape(-1)
        for _dt, idxs in _dtype_groups(leaves)
    )


@jax.named_scope("bf.pack")
def _packed_prefix(tree, cap):
    """``[(sub_flat, scale)]`` per dtype group: a 512-aligned prefix of
    the group's PACKED flat, built directly from whole input leaves
    plus at most one partial leaf slice — so only O(cap) elements are
    ever concatenated and only INPUT values are consumed. ``scale``
    (= group elems / covered elems) restores whole-group squared-sum
    estimates on the host; 1.0 (exact) when the group fits the cap.
    The 512 alignment matches the quantization chunk, keeping the
    metrics sub-gossip's chunk scales bit-identical to the full wire's
    for the covered region (:mod:`bluefog_tpu.metrics`)."""
    leaves = jax.tree_util.tree_leaves(tree)
    out = []
    for _dt, idxs in _dtype_groups(leaves):
        total = sum(int(leaves[i].size) for i in idxs)
        keep = min(total, max(512, cap - cap % 512))
        parts = []
        got = 0
        for i in idxs:
            if got >= keep:
                break
            n = int(leaves[i].size)
            take = min(n, keep - got)
            flat = leaves[i].reshape(-1)
            parts.append(flat if take == n else flat[:take])
            got += take
        sub = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        out.append((sub, total / keep))
    return out


@jax.named_scope("bf.unpack")
def _unpack_groups(tree, groups):
    """Scatter per-dtype-group flat packed values back onto a tree's
    leaves; the inverse of :func:`_pack_groups`."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = list(leaves)
    for gi, (_dt, idxs) in enumerate(_dtype_groups(leaves)):
        y = groups[gi]
        off = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = y[off:off + n].reshape(
                leaves[i].shape
            ).astype(leaves[i].dtype)
            off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _tree_restack(tree):
    return jax.tree_util.tree_map(lambda t: jnp.expand_dims(t, 0), tree)


def _aval_key(tree):
    return tuple(
        (tuple(l.shape), str(l.dtype))
        for l in jax.tree_util.tree_leaves(tree)
    ) + (str(jax.tree_util.tree_structure(tree)),)


def _gossip_routes(params, cap_bytes, direct):
    """How :func:`_packed_gossip` routes a worker-STACKED tree, per dtype
    group: ``(itemsize, alone, packed)`` — the per-worker element counts
    of the leaves that travel alone and the packed rest's total."""
    leaves = jax.tree_util.tree_leaves(params)
    out = []
    for dt, idxs in _dtype_groups(leaves):
        itemsize = np.dtype(dt).itemsize
        sizes = [int(np.prod(leaves[i].shape[1:])) for i in idxs]
        alone = [
            n for n in sizes
            if direct and _travels_alone(n, itemsize, cap_bytes)
        ]
        out.append((itemsize, alone, sum(sizes) - sum(alone)))
    return out


def _gossip_messages(params, cap_bytes, direct):
    """``(itemsize, n_elems)`` of every message one combine round of a
    worker-STACKED tree sends: each leaf that travels alone, then the
    buckets of each dtype group's packed rest."""
    out = []
    for itemsize, alone, packed in _gossip_routes(params, cap_bytes, direct):
        out += [(itemsize, n) for n in alone]
        if packed:
            out += [
                (itemsize, b - a)
                for a, b in inner.bucket_bounds(packed, itemsize, cap_bytes)
            ]
    return out


def _record_step_built(name, params, cap_bytes, direct, donated=(),
                       **data):
    """A step program is about to be built: count it, say how its gossip
    routes the parameters (gauges ``bluefog.gossip_direct_bytes`` /
    ``bluefog.gossip_packed_bytes``, per worker) and how much of its
    carry it writes in place (``bluefog.step_donated_bytes``: per worker,
    the bytes of the ``donated`` operands; 0 for a program that keeps its
    inputs), and put all three on the flight ring's ``compile`` event."""
    routes = _gossip_routes(params, cap_bytes, direct)
    direct_bytes = sum(item * sum(alone) for item, alone, _ in routes)
    packed_bytes = sum(item * packed for item, _, packed in routes)
    donated_bytes = sum(
        leaf.size // leaf.shape[0] * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(donated)
    )
    metrics_mod.counter("bluefog.recompiles").inc()
    metrics_mod.gauge("bluefog.gossip_direct_bytes").set(direct_bytes)
    metrics_mod.gauge("bluefog.gossip_packed_bytes").set(packed_bytes)
    metrics_mod.gauge("bluefog.step_donated_bytes").set(donated_bytes)
    # nothing known unusable yet: the first dispatch lowers the program
    metrics_mod.gauge("bluefog.step_donation_unused").set(0)
    flight.record(
        "compile", name=name, direct_bytes=direct_bytes,
        packed_bytes=packed_bytes, donated_bytes=donated_bytes, **data,
    )


def _check_donated_once(donated):
    """Raise if two of the leaves a step is about to donate are one
    buffer (a state that holds the parameters themselves, a tied weight
    stored twice): the runtime refuses such a dispatch only in
    ``Execute()``, on some backends after part of the mesh has started,
    and names no leaf. Read where the step is built — a cache miss — so
    the hot path walks nothing."""
    seen = {}
    for i, leaf in enumerate(jax.tree_util.tree_leaves(donated)):
        if not isinstance(leaf, jax.Array) or leaf.is_deleted():
            # a shape (an off-chip compile calls the step with
            # ShapeDtypeStructs) has no buffer; an array handed in again
            # after an earlier call consumed it is the dispatch's to
            # refuse, with jax's own error, which names it
            continue
        for shard in leaf.addressable_shards:
            ptr = shard.data.unsafe_buffer_pointer()
            first = seen.setdefault(ptr, i)
            if first != i:
                raise ValueError(
                    "the train step donates its carry (parameters, "
                    "optimizer state and the optimizer's own buffers) and "
                    f"donated leaves {first} and {i} of it, "
                    f"{leaf.dtype}{list(leaf.shape)}, are one buffer: a "
                    "buffer cannot be donated twice. Give each leaf its "
                    "own array (jnp.copy), or build the step with "
                    "make_train_step(..., donate=False)"
                )


def _first_difference(old, new):
    """Index of the first component at which two cache keys differ (the
    shorter key's length when one is a prefix of the other); ``None``
    with no key to compare against."""
    if old is None:
        return None
    for i, (a, b) in enumerate(zip(old, new)):
        if a != b:
            return i
    return min(len(old), len(new))


def _replicated(mesh, host_value):
    """A small operand the host builds for a step (the step index, a
    weight vector): numpy value -> array committed to and replicated over
    the step's own ``mesh``, the sharding ``in_specs ... P()`` asks for.
    Each chip gets it from its host (every process puts to its own chips;
    ``jax.device_put`` would first compare the value across processes).
    ``jnp.asarray`` instead makes it on device 0 alone, in order behind
    that device's previous step, and the jitted call then reshards it to
    the others on its slow path: on four v5e chips that hand-over cost
    every step 20-25 ms (PERF.md, PR 25)."""
    return jax.make_array_from_callback(
        host_value.shape, NamedSharding(mesh, P()),
        lambda index: host_value[index],
    )


def _stage_operands(mesh, comm_count, wops):
    """The host-built operands of one dispatch -> ``(step_idx, wops)`` on
    the chips: the communication index as ``int32[1]`` and each weight
    array the resolvers returned (numpy), every one through
    :func:`_replicated`, per call — so a weight reassigned between steps
    is the next step's operand of the same compiled program."""
    step_idx = _replicated(mesh, np.asarray([comm_count], np.int32))
    wops = tuple(_replicated(mesh, w) for w in wops)
    _count_resharded(mesh, (step_idx, wops))
    return step_idx, wops


def _count_resharded(mesh, operands):
    """``bluefog.step_operands_resharded``: how many of a dispatch's
    host-built operands did NOT come through :func:`_replicated` (not
    committed, or not replicated over exactly the step's mesh). 0 on
    every path of this module; it guards the next operand added."""
    want = NamedSharding(mesh, P())
    n = sum(
        not (
            isinstance(a, jax.Array) and a.committed
            and a.sharding.is_equivalent_to(want, a.ndim)
        )
        for a in jax.tree_util.tree_leaves(operands)
    )
    if n:
        metrics_mod.counter("bluefog.step_operands_resharded").inc(n)


def _timed_dispatch(name, fn, *args):
    """ENQUEUE-span dispatch, the analogue of the reference's optimizer
    timeline hooks (torch/optimizers.py:112-165); same plumbing as the
    eager facade's `_compiled` wrapper (collective/ops.py). The memory
    observatory's ``dispatch`` phase watermark brackets the same span
    (on the first call of a fresh program the lazy jit compile lands
    inside this bracket too — the ``compile`` phase the watermark
    decomposition reports is exactly that first-dispatch growth). With
    both the timeline and the observatory off — the common case — the
    fast path is two reads, an empty context and a direct call."""
    if memory_mod.active() is None:
        scope = contextlib.nullcontext()
    else:
        scope = memory_mod.phase_scope("dispatch")
    with scope:
        if not tl.timeline_enabled():
            return fn(*args)
        t0 = tl.timeline_now_us()
        out = fn(*args)
        tl.timeline_record_complete(name, "ENQUEUE", t0,
                                    tl.timeline_now_us() - t0)
        return out


_UNUSED_DONATION = "Some donated buffers were not usable:"


@contextlib.contextmanager
def _unused_donations_counted():
    """Round the first dispatch of a freshly built program, which is where
    jax traces, lowers and compiles it: jax's "Some donated buffers were
    not usable" warning, raised while it lowers, becomes the gauge
    ``bluefog.step_donation_unused`` (the number of donated leaves the
    program could not alias to an output; 0 without the warning) instead
    of a line on stderr. Any other warning is raised again. A context
    and not a function round the dispatch: one more Python frame under
    the trace cost the GPT-2-medium step 2 s of set-up on the v5e's host
    (PERF.md, PR 31)."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.filterwarnings("always", message=_UNUSED_DONATION)
        yield
    unused = 0
    for w in caught:
        text = str(w.message)
        if text.startswith(_UNUSED_DONATION):
            # "...: float32[8,3], int32[8].\nSee an explanation at ..."
            unused += len(re.findall(r"\w+\[[^\]]*\]", text.split("\n")[0]))
        else:
            warnings.warn_explicit(
                w.message, w.category, w.filename, w.lineno
            )
    if unused:
        metrics_mod.gauge("bluefog.step_donation_unused").set(unused)


class _StepPlan(NamedTuple):
    """What one call of :meth:`_GossipOptimizer.step` or of the fused
    ``train_step`` resolved before it looks its program up
    (:meth:`_GossipOptimizer._plan_step`): where the step runs, how it
    gossips, which state rides it, and the parts of the cache key the two
    program families share. Each family puts its own name and fields
    round them, in its own order (``tests`` and ``flight``'s ``compile``
    event read names and positions)."""

    comm_now: bool
    hier: bool
    mesh: Any
    spec: Any
    gossip_key: tuple
    gossip_fn: Any
    wops: tuple  # host numpy; `_stage_operands` puts them on the chips
    ef: bool  # the gossip's CHOCO copies ride this program
    cap_bytes: int
    direct: bool
    shard_l: Any  # the active ZeRO layout, or None
    scatter_wire: Optional[str]
    scatter_chunks: tuple
    scatter_ef: bool  # ZeRO-2's per-slot residuals ride this program
    met_enabled: bool
    met: bool  # this dispatch is the sampled one
    wire_now: Optional[str]
    key_ident: tuple  # (order, communication_type, uid, tx_version, ef)
    key_tail: tuple  # gossip key + shard signature + scatter key


_opt_uid = itertools.count()

# The operands of the fused program (``bf_step``) that a donating train
# step gives up: parameters (0) and optimizer state (1), which the caller
# rebinds from the outputs, and the two states the optimizer alone owns
# and replaces from the outputs in the same call — error feedback (4) and
# the ``delayed=True`` double buffer (5). Not the step index, ``wops`` or
# the batch, and not the gradient accumulator (6): the call that consumes
# it returns nothing of its shape that parameters and state have not
# already taken, so its donation would only ever be reported unusable.
_DONATED = (0, 1, 4, 5)


class _GossipOptimizer:
    """Shared engine for the allreduce/neighbor/hierarchical families.

    ``order``: 'cta' gossips the parameters before the inner update,
    'atc' after, 'grad' gossips the *gradients* (allreduce-mean) instead.
    """

    def __init__(self, base_optimizer, communication_type, order: str,
                 num_steps_per_communication: int = 1):
        # Unique id for compiled-step cache keys: id(self.tx) is unsafe
        # (CPython reuses addresses after GC).
        self._uid = next(_opt_uid)
        if not isinstance(communication_type, CommunicationType):
            raise TypeError(
                "communication_type must be a CommunicationType, got "
                f"{communication_type!r}"
            )
        assert not (
            order == "grad"
            and communication_type != CommunicationType.allreduce
        ), "gradient gossip is only defined for allreduce communication"
        self._tx_version = 0
        self._tx = base_optimizer
        self.communication_type = communication_type
        self.order = order
        # Dynamic-topology knobs, reference README.rst:108-123.
        self.self_weight = None
        self.src_weights = None
        self.dst_weights = None
        self.enable_topo_check = True
        # Quantized gossip wire: 'bf16' (2x fewer bytes), 'int8' (4x),
        # 'int4' (8x, block-scaled nibbles), or the error-feedback tiers
        # 'int8_ef'/'int4_ef' (CHOCO memory removes the quantization
        # noise floor; see inner.weighted_combine_quantized*).
        # Static-plan path only.
        self.compression = None
        self.schedule: Optional[SchedulePlan] = None
        # Hierarchical knobs (reference mpi_ops.py:648-821).
        self.neighbor_machine_weights = None
        self.send_neighbor_machines = None
        # Communicate every K-th step() call (reference
        # torch/optimizers.py:321): intermediate calls run the inner
        # update purely locally (cta/atc) or accumulate gradients with no
        # update at all (grad order — classic gradient accumulation).
        self.num_steps_per_communication = num_steps_per_communication
        self._step_count = 0
        self._comm_count = 0  # schedule index: advances per communication
        self._grad_accum = None  # grad-order local accumulator (sum)
        # Device-tier metrics: the 1-in-BLUEFOG_METRICS_INTERVAL sampled
        # step additionally OUTPUTS a pytree of tiny subsample slices
        # (metrics.build_probe_payload); the host folds the previous
        # sample's payload — by then long copied back — into the
        # registry at each new sample.
        self._pending_drain = None  # (wire, payload) copying to host
        self._metrics_hooked = False
        self._acct_cache: dict = {}  # per-program wire-byte accounting
        # The CommPlan behind the most recent gossip resolution (None
        # for allreduce/empty/hierarchical): the attribution doctor's
        # per-round probes measure exactly this plan's rounds.
        self._last_plan = None
        # BLUEFOG_SHARD=1 weight-update sharding (docs/sharding.md):
        # the active ShardLayout (None = replicated state) and the
        # membership-change re-shard count.
        self._shard_layout = None
        self._shard_reshards = 0

    @property
    def tx(self):
        """The inner optax transformation. Reassigning it retraces the
        compiled step (the old compiled program would silently keep the
        stale update rule otherwise); in-place mutation is not detectable —
        always rebind, as with any jitted closure."""
        return self._tx

    @tx.setter
    def tx(self, value):
        if value is not self._tx:
            self._tx = value
            self._tx_version += 1

    # -- state ---------------------------------------------------------------

    def init(self, params):
        """Per-worker inner-optimizer state, worker-stacked. Under
        ``BLUEFOG_SHARD=1`` (gradient-allreduce family) the state is a
        worker-stacked :class:`bluefog_tpu.sharding.ShardedOptState`:
        each rank's 1/N bucket-aligned optax shard plus the optional
        fp32 master slices (``BLUEFOG_SHARD_MASTER``)."""
        ctx = ctx_mod.get_context()
        if self._shard_active():
            return self._shard_init(ctx, params)
        key = ("opt_init", self._uid, self._tx_version) + _aval_key(params)
        fn = ctx.op_cache.get(key)
        if fn is None:
            spec = P(ctx_mod.WORKER_AXIS)

            def body(p):
                return _tree_restack(self.tx.init(_tree_block(p)))

            fn = jax.jit(
                jax.shard_map(
                    body, mesh=ctx.mesh, in_specs=spec, out_specs=spec
                )
            )
            ctx.op_cache[key] = fn
        return fn(params)

    # -- weight-update sharding (BLUEFOG_SHARD, docs/sharding.md) ------------

    def _shard_active(self) -> bool:
        """Sharding applies where it is trajectory-exact: the family
        whose post-communication update inputs are rank-invariant
        (order='grad', the arxiv 2004.13336 setting). Every other
        family holds genuinely per-rank state — already 1/N of the
        fleet total, nothing redundant to shard — so the knob warns
        once and the replicated path runs verbatim (bitwise, pinned in
        tests/test_sharding.py)."""
        if not sharding.enabled():
            return False
        if self.order == "grad" and self.schedule is None:
            return True
        warn_once(
            f"shard-family:{self.order}:{self.communication_type.value}",
            "BLUEFOG_SHARD=1 ignored for the %s/%s family: its optax "
            "state integrates each rank's own gradient stream (per-rank "
            "by construction, no cross-rank redundancy), so a "
            "coordinate-partitioned update would change the algorithm. "
            "Running the replicated path verbatim; weight-update "
            "sharding applies to the gradient-allreduce family "
            "(docs/sharding.md).",
            self.order, self.communication_type.value,
        )
        return False

    def _shard_groups(self, params):
        """``[(dtype, elems)]`` of the worker-stacked parameter tree in
        packed-wire order — the grain the shard layout is built on."""
        leaves = jax.tree_util.tree_leaves(params)
        return tuple(
            (dt, sum(int(np.prod(leaves[i].shape[1:])) for i in idxs))
            for dt, idxs in _dtype_groups(leaves)
        )

    def _ensure_shard_layout(self, ctx, params):
        """Resolve the current shard layout; returns ``(layout,
        changed)`` where ``changed`` means the stored layout no longer
        matches the live set / parameter avals (the caller must
        re-shard any existing state)."""
        token = ctx.live_token()
        groups = self._shard_groups(params)
        master = sharding.master_enabled()
        grads = sharding.grads_enabled()
        lay = self._shard_layout
        if (
            lay is not None
            and lay.token == token
            and lay.master == master
            and tuple((g.dtype, g.elems) for g in lay.groups) == groups
        ):
            if lay.grads != grads:
                # a pure BLUEFOG_SHARD_GRADS flip swaps the gradient
                # leg (allreduce <-> reduce-scatter), not the state
                # layout: rebuild so the layout signature (and thus
                # the compiled-step cache key) changes, but do NOT
                # report a membership change — the slot map is
                # identical and there is nothing to re-shard
                lay = sharding.build_layout(
                    groups, lay.live, ctx.size, master=master,
                    token=token, grads=grads,
                )
                self._shard_layout = lay
            return lay, False
        live = token[1] if token is not None else tuple(range(ctx.size))
        new = sharding.build_layout(
            groups, live, ctx.size, master=master, token=token,
            grads=grads,
        )
        changed = lay is not None
        self._shard_layout = new
        return new, changed

    def _shard_check_elementwise(self, ctx):
        """Refuse inner transformations with cross-coordinate coupling
        (global-norm clipping, LARS/LAMB trust ratios): their update of
        a slot depends on coordinates the slot's owner never sees, so
        sharding would silently train a different trajectory — the one
        failure mode docs/sharding.md promises cannot happen.

        Detection is behavioral, not by type: update a small vector
        twice with identical values in the probe region and different
        values outside it. An elementwise transform yields bit-equal
        probe-region updates; a coupled one almost surely differs."""
        key = ("shard_elementwise", self._uid, self._tx_version)
        ok = ctx.op_cache.get(key)
        if ok is None:
            d = 2 * sharding.ALIGN_ELEMS
            half = d // 2
            rng = np.random.RandomState(0)
            p = rng.randn(d).astype(np.float32)
            g1 = rng.randn(d).astype(np.float32)
            g2 = g1.copy()
            g2[half:] = rng.randn(half).astype(np.float32)
            s0 = self._tx.init(p)
            u1, _ = self._tx.update(g1, s0, p)
            u2, _ = self._tx.update(g2, self._tx.init(p), p)
            ok = bool(
                np.array_equal(
                    np.asarray(u1)[:half], np.asarray(u2)[:half]
                )
            )
            ctx.op_cache[key] = ok
        if not ok:
            raise ValueError(
                "BLUEFOG_SHARD=1 requires an ELEMENTWISE inner "
                "transformation: this optimizer's update of a "
                "coordinate depends on other coordinates (global-norm "
                "clipping, LARS/LAMB-style trust ratios, ...), so a "
                "1/N-slot update would silently diverge from the "
                "replicated trajectory. Use an elementwise transform "
                "(adam, sgd, rmsprop, adagrad, per-element clipping) "
                "or run with BLUEFOG_SHARD=0 (docs/sharding.md)"
            )

    def _shard_init(self, ctx, params):
        self._shard_check_elementwise(ctx)
        layout, _ = self._ensure_shard_layout(ctx, params)
        key = (
            "opt_shard_init", self._uid, self._tx_version,
        ) + layout.sig() + _aval_key(params)
        fn = ctx.op_cache.get(key)
        if fn is None:
            tx = self._tx

            def body(p_b):
                p = _tree_block(p_b)
                own = _shard_own_slices(p, layout, ctx_mod.WORKER_AXIS)
                master = (
                    tuple(x.astype(jnp.float32) for x in own)
                    if layout.master else ()
                )
                return _tree_restack(
                    sharding.ShardedOptState(tx.init(own), master)
                )

            spec = P(ctx_mod.WORKER_AXIS)
            fn = jax.jit(
                jax.shard_map(
                    body, mesh=ctx.mesh, in_specs=spec, out_specs=spec
                )
            )
            ctx.op_cache[key] = fn
        state = fn(params)
        self._register_shard(layout, state)
        return state

    def _register_shard(self, layout, state) -> None:
        from bluefog_tpu import scaling

        sharding.register_active(
            layout, reshards=self._shard_reshards,
            measured_state_bytes=scaling.optimizer_state_bytes(
                state=state, world=layout.size
            ),
        )

    @staticmethod
    def _shard_slot_group(arr_shape, layout):
        """The group index a worker-stacked state leaf of ``arr_shape``
        belongs to, or None for non-slot (scalar/replicated) leaves.
        Slot lengths are unique per layout (sharding.build_layout), so
        the trailing dimension is an unambiguous discriminator."""
        if len(arr_shape) != 2 or arr_shape[0] != layout.size:
            return None
        for gi, g in enumerate(layout.groups):
            if arr_shape[1] == g.slot:
                return gi
        return None

    def _reshard_state(self, ctx, old, new, opt_state):
        """Host-side membership-change re-shard: reconstruct each
        per-coordinate state group from its old owners' rows (the
        worker-stacked simulation holds every row; a real fleet would
        source a lost shard from the gather-on-save checkpoint — see
        docs/sharding.md) and re-slice it under the new owner map.
        Non-slot leaves (step counts) are replicated and carried over.
        """

        leaves, treedef = jax.tree_util.tree_flatten(opt_state)
        nd_sharding = NamedSharding(ctx.mesh, P(ctx_mod.WORKER_AXIS))
        out = []
        for leaf in leaves:
            gi = self._shard_slot_group(tuple(leaf.shape), old)
            if gi is None:
                out.append(leaf)
                continue
            full = sharding.gather_rows(np.asarray(leaf), old, gi)
            out.append(jax.device_put(
                sharding.slice_rows(full, new, gi), nd_sharding
            ))
        self._shard_reshards += 1
        metrics_mod.counter("bluefog.shard.reshards").inc()
        flight.record(
            "shard_reshard", live=len(new.live), was=len(old.live),
        )
        return jax.tree_util.tree_unflatten(treedef, out)

    def _shard_prepare(self, ctx, params, opt_state):
        """Per-dispatch shard prologue: resolve the layout against the
        current live set and re-shard the state on a membership change
        — the compiled-step cache key carries the layout signature, so
        a stale layout can never dispatch."""
        if not isinstance(opt_state, sharding.ShardedOptState):
            raise ValueError(
                "BLUEFOG_SHARD=1 but the optimizer state is not sharded "
                "(was it created with BLUEFOG_SHARD=0, or restored from "
                "a replicated checkpoint?); re-run init(params) or "
                "restore a gather-on-save sharded checkpoint"
            )
        # re-checked per tx_version: rebinding opt.tx after init must
        # not smuggle a coupled transform past the init-time probe
        self._shard_check_elementwise(ctx)
        old = self._shard_layout
        layout, changed = self._ensure_shard_layout(ctx, params)
        if changed:
            if old.master != layout.master:
                # a reshard can re-lay slot leaves but cannot invent
                # (or drop) the fp32 master slices mid-run; without
                # this the mismatch surfaces as an opaque pytree error
                # deep inside the jitted trace
                raise ValueError(
                    "BLUEFOG_SHARD_MASTER changed mid-run (was "
                    f"{int(old.master)}, now {int(layout.master)}); "
                    "the master slices are part of the optimizer "
                    "state — re-run init(params) (or restore a "
                    "checkpoint saved under the same master mode)"
                )
            opt_state = self._reshard_state(ctx, old, layout, opt_state)
            self._register_shard(layout, opt_state)
        return layout, opt_state

    def _scatter_active(self) -> bool:
        """ZeRO-2 (``BLUEFOG_SHARD_GRADS=1``) on top of an active shard
        family: the gradient leg lowers to reduce-scatter, so the
        gossip-path error-feedback state (full-width CHOCO copies) must
        not engage — the scatter leg holds its own per-slot residuals
        (:meth:`_ensure_scatter_ef`)."""
        return self._shard_active() and sharding.grads_enabled()

    def _scatter_chunks(self, ctx, layout):
        """Per-group transfer chunk counts for the reduce-scatter leg,
        chosen by the same calibrated alpha-beta model as the gossip
        plans — priced on the per-round SLOT payload (the scatter ships
        one slot per round, and a quantized wire ships fewer bytes per
        element than the storage dtype, cf. :meth:`_plan_chunks`)."""
        from bluefog_tpu import scaling

        out = []
        for g in layout.groups:
            itemsize = np.dtype(g.dtype).itemsize
            payload = (
                scaling.wire_payload_bytes(
                    g.slot, itemsize, self.compression
                )
                if self.compression is not None
                else g.slot * itemsize
            )
            out.append(compiler.reduce_scatter_chunks(
                ctx.size, payload, n_elems=g.slot
            ))
        return tuple(out)

    def _ensure_scatter_ef(self, ctx, layout, spec):
        """Per-group per-slot CHOCO residuals for the ZeRO-2 scatter
        wire's ``*_ef`` tiers: worker-stacked ``[size, padded]`` f32,
        rebuilt (zeroed) whenever the layout signature or the wire tier
        changes — a re-shard moves slot ownership, so stale residuals
        would integrate against the wrong coordinates, while zeroed
        ones merely re-transmit full magnitude for a few steps (same
        reset discipline as :meth:`_ensure_ef_state`)."""

        sig = (layout.sig(), self.compression)
        if getattr(self, "_scatter_ef_sig", None) == sig:
            return
        nd = NamedSharding(ctx.mesh, spec)
        self._scatter_ef = tuple(
            jax.device_put(
                np.zeros((ctx.size, g.padded), np.float32), nd
            )
            for g in layout.groups
        )
        self._scatter_ef_sig = sig

    def _scatter_prologue(self, ctx, shard_l, spec):
        """The ZeRO-2 dispatch prologue shared by :meth:`step` and the
        fused builder: resolve the scatter wire/chunks, materialize the
        per-slot EF residuals when the tier needs them, and build the
        cache-key appendix that keeps wire/chunk/kernel flips from
        aliasing compiled programs. Returns ``(scatter_key,
        scatter_wire, scatter_chunks, scatter_ef)`` — all empty/None
        when the layout does not shard gradients."""
        if shard_l is None or not shard_l.grads:
            return (), None, (), False
        scatter_wire = self.compression
        scatter_chunks = self._scatter_chunks(ctx, shard_l)
        scatter_ef = scatter_wire in ("int8_ef", "int4_ef")
        if scatter_ef:
            self._ensure_scatter_ef(ctx, shard_l, spec)
        # kernel token only for the kernel-gated tiers: the EF scatter
        # quantizes through the composite pair unconditionally (see
        # inner.reduce_scatter), so a kernel flip cannot change it
        scatter_key = (
            "scatter", scatter_wire or "fp32", scatter_chunks,
        ) + (
            inner._kernels.cache_token(scatter_wire)
            if scatter_wire in ("int8", "int4") else ()
        )
        return scatter_key, scatter_wire, scatter_chunks, scatter_ef

    # -- gossip resolution ---------------------------------------------------

    def _fabric(self, ctx):
        """The federation fabric a flat neighbor_allreduce dispatch rides
        (docs/federation.md), or None: a schedule or explicit per-step
        weights are the caller's own topology, and the other
        communication types have no federated form."""
        if (
            self.communication_type != CommunicationType.neighbor_allreduce
            or self.schedule is not None
            or self.self_weight is not None
            or self.src_weights is not None
            or self.dst_weights is not None
        ):
            return None
        from bluefog_tpu import federation

        return federation.get_fabric(ctx.size)

    def _direct_route(self, ctx) -> bool:
        """Whether :func:`_packed_gossip` may send a large leaf alone and
        in its own shape: only on the exact wire, whose combine is
        elementwise over any shape. The quantized wires (this
        optimizer's, or a fabric's DCN tier) scale 512-element chunks of
        the flat order, and the ZeRO paths slice the flat vector: they
        keep every leaf packed."""
        if self.compression is not None or self._shard_active():
            return False
        fed = self._fabric(ctx)
        return fed is None or fed.wire is None

    def _wire_payload(self, params, direct=False):
        """``(payload_bytes, n_elems)`` of the largest message this
        dispatch ships — the payload the compiler's chunk chooser prices:
        the largest leaf that travels alone (``direct``) or the largest
        bucket of the packed rest, whichever is bigger (each message is
        split into the chosen chunk count inside the combine)."""
        messages = _gossip_messages(params, inner.bucket_bytes_cap(), direct)
        if not messages:
            return None
        itemsize, elems = max(messages, key=lambda m: m[0] * m[1])
        return itemsize * elems, elems

    def _plan_chunks(self, plan, payload) -> int:
        """The (rounds, chunks, route) Pareto chooser for one static-plan
        dispatch; 1 when no payload is known (keying callers that never
        dispatch, e.g. structural tests). A quantized wire ships fewer
        bytes per element than the bucket's storage dtype — the chooser
        prices the wire payload (scale sidecar included), not the
        uncompressed input."""
        from bluefog_tpu import scaling

        if payload is None:
            return 1
        payload_bytes, n_elems = payload
        if self.compression is not None:
            payload_bytes = scaling.wire_payload_bytes(
                n_elems, payload_bytes // max(n_elems, 1), self.compression
            )
        compiled = plan.compile_info
        return compiler.choose_chunks(
            compiled if compiled is not None else len(plan.rounds),
            payload_bytes,
            n_elems=n_elems,
            method=col_ops._plan_method(),
        )

    def _gossip_key_and_fn(self, ctx, payload=None):
        """Resolve the communication into (cache-key piece, block fn,
        weight operands).

        The block fn signature is ``fn(t, step, wops)``. Weight *values*
        for plan-based gossip are returned here as host arrays and ride
        in ``wops`` as replicated device operands (each dispatch puts
        them through :func:`_stage_operands` on the step's mesh), so
        the reference's per-iteration weight-reassignment
        idiom (README.rst:108-123) reuses ONE compiled program per edge
        structure instead of compiling per weight vector.

        ``payload`` is ``(bytes, elems)`` of the largest wire bucket
        (:meth:`_wire_payload`); the static-plan neighbor_allreduce
        paths feed it to the chunk chooser, and the chosen chunk count
        plus the plan's route family join the cache-key piece — a
        chunk/route change compiles its own program.
        """
        comm = self.communication_type
        self._last_plan = None
        if self.schedule is not None and comm not in (
            CommunicationType.neighbor_allreduce,
            CommunicationType.hierarchical_neighbor_allreduce,
        ):
            raise ValueError(
                "opt.schedule (a SchedulePlan) only applies to "
                "neighbor_allreduce or hierarchical communication; "
                f"this optimizer uses {comm.value!r}"
            )
        if comm == CommunicationType.empty:
            return ("empty",), (lambda t, step, wops: t), ()
        if comm == CommunicationType.allreduce:
            return (
                ("allreduce",),
                lambda t, step, wops: inner.allreduce(
                    t, ctx_mod.WORKER_AXIS, average=True
                ),
                (),
            )
        if comm == CommunicationType.neighbor_allreduce:
            if self.schedule is not None:
                sched = self.schedule
                if sched.size != ctx.size:
                    raise ValueError(
                        f"opt.schedule is sized for {sched.size} workers "
                        f"but the mesh has {ctx.size}"
                    )
                for p in sched.plans:
                    # deduped: the whole period lands in the postmortem
                    # side table once, however many steps dispatch
                    flight.note_plan(p, ctx.topo_version, ctx.live_token())
                # the doctor probes whichever plan THIS step dispatches
                self._last_plan = sched.plans[
                    self._comm_count % sched.period
                ]
                return (
                    (sched,),
                    lambda t, step, wops: inner.neighbor_allreduce_step(
                        t, step, sched, ctx_mod.WORKER_AXIS
                    ),
                    (),
                )
            fed = self._fabric(ctx)
            if fed is not None:
                return self._federated_key_and_fn(ctx, fed, payload)
            plan = col_ops._resolve_plan(
                ctx,
                self.self_weight,
                self.src_weights,
                self.dst_weights,
                self.enable_topo_check,
            )
            self._last_plan = plan
            perms = plan.perms
            info = plan.compile_info
            inject = info.inject if info is not None else None
            chunks = self._plan_chunks(plan, payload)
            self_w, recv_w = plan.weight_operands()
            if self.compression is not None:
                inner._check_combine_normalized(
                    plan, f"compression={self.compression!r}"
                )
                # keyed on the edge STRUCTURE with weights as operands —
                # per-step varying weights reuse one compiled program,
                # same guarantee as the exact path
                wire = self.compression
                if wire in ("int8_ef", "int4_ef"):
                    if inject is not None:
                        raise ValueError(
                            f"compression={wire!r} cannot ride a "
                            "short-cut (relay) plan: the CHOCO copies "
                            "integrate a fixed per-round source, which "
                            "relay rounds do not have. Unset "
                            "BLUEFOG_PLAN_METHOD=shortcut or use a "
                            "memoryless wire (None/'int8'/'bf16'/"
                            "'int4')."
                        )
                    ef_wire = "int4" if wire == "int4_ef" else "int8"
                    return (
                        # the kernel token rides at the END of every
                        # quantized gossip key (flows into the opt_step
                        # key via tuple(gossip_key); _metrics_wire
                        # parses wire positionally at [1], so appending
                        # is the only safe spot)
                        ("na_q_ef", ef_wire, perms, chunks)
                        + inner._kernels.cache_token(ef_wire),
                        lambda flat, e, wops: (
                            inner.weighted_combine_quantized_ef_operands(
                                flat, e, perms, wops[0],
                                ctx_mod.WORKER_AXIS, chunks=chunks,
                                wire=ef_wire,
                            )
                        ),
                        (recv_w,),
                    )
                return (
                    ("na_q", wire, perms, chunks, inject)
                    + inner._kernels.cache_token(wire),
                    lambda t, step, wops: (
                        inner.weighted_combine_quantized_operands(
                            t, perms, wops[0], ctx_mod.WORKER_AXIS,
                            wire=wire, chunks=chunks, inject=inject,
                        )
                    ),
                    (recv_w,),
                )
            return (
                ("na", perms, chunks, inject),
                lambda t, step, wops: inner.weighted_combine_operands(
                    t, perms, wops[0], wops[1], ctx_mod.WORKER_AXIS,
                    chunks=chunks, inject=inject,
                ),
                (self_w, recv_w),
            )
        raise AssertionError(comm)

    def _federated_key_and_fn(self, ctx, fed, payload):
        """Two-level federated dispatch (docs/federation.md): every
        communicating step runs the intra-pod combine over ICI at full
        rate; every ``fed.period``-th communication appends the
        designated-gateway inter-pod combine on the aggressive DCN wire
        in the SAME compiled body, so XLA overlaps the slow cross-pod
        rounds with the tail of the intra-pod ones.

        Key shapes (the ``"fed"`` tag is what keeps the flat path
        bitwise-untouched — a flat run never produces one):

        - ICI-only step: ``("fed", "ici", wire, perms, chunks, inject)``
        - DCN step: ``("fed", "dcn", wire, perms, chunks, inject,
          dcn_wire, inter_perms, inter_chunks, inter_inject)``

        kernel cache tokens ride at the END (same contract as the flat
        quantized keys). ``wire`` is the intra-pod tier
        (``self.compression``); error-feedback tiers degrade to their
        memoryless base because the CHOCO residual recursion assumes
        the same combine every communicating step, which the periodic
        DCN leg breaks.
        """
        from bluefog_tpu import scaling

        intra = fed.intra
        inter = fed.inter
        self._last_plan = intra
        flight.note_plan(intra, ctx.topo_version, ctx.live_token())
        axis = ctx_mod.WORKER_AXIS
        perms = intra.perms
        info = intra.compile_info
        inject = info.inject if info is not None else None
        chunks = self._plan_chunks(intra, payload)
        self_w, recv_w = intra.weight_operands()
        wire = self.compression
        if wire in ("int8_ef", "int4_ef"):
            warn_once(
                "fed-ef-wire",
                "compression=%r under bf.federation falls back to the "
                "memoryless %r wire: error-feedback residuals would go "
                "stale across the BLUEFOG_DCN_PERIOD gap",
                wire, wire[:-3],
            )
            wire = wire[:-3]
        if wire is not None:
            inner._check_combine_normalized(
                intra, f"compression={wire!r}"
            )
        if not fed.dcn_step(self._comm_count):
            if wire is not None:
                return (
                    ("fed", "ici", wire, perms, chunks, inject)
                    + inner._kernels.cache_token(wire),
                    lambda t, step, wops: (
                        inner.weighted_combine_quantized_operands(
                            t, perms, wops[0], axis,
                            wire=wire, chunks=chunks, inject=inject,
                        )
                    ),
                    (recv_w,),
                )
            return (
                ("fed", "ici", None, perms, chunks, inject),
                lambda t, step, wops: inner.weighted_combine_operands(
                    t, perms, wops[0], wops[1], axis,
                    chunks=chunks, inject=inject,
                ),
                (self_w, recv_w),
            )
        # DCN step: the gateway leg composes AFTER the intra leg inside
        # one fn, giving the x -> W_dcn^T (W_ici^T x) composed step the
        # spectral scorer priced (federation.composed_rate)
        flight.note_plan(inter, ctx.topo_version, ctx.live_token())
        inter_perms = inter.perms
        inter_info = inter.compile_info
        inter_inject = (
            inter_info.inject if inter_info is not None else None
        )
        inter_self, inter_recv = inter.weight_operands()
        dcn_wire = fed.wire
        inter_chunks = 1
        if payload is not None and inter_info is not None:
            payload_bytes, n_elems = payload
            if dcn_wire is not None:
                payload_bytes = scaling.wire_payload_bytes(
                    n_elems, payload_bytes // max(n_elems, 1), dcn_wire
                )
            inter_chunks = compiler.choose_chunks(
                inter_info, payload_bytes, n_elems=n_elems,
                method=col_ops._plan_method(),
            )
        if dcn_wire is not None:
            inner._check_combine_normalized(
                inter, f"BLUEFOG_DCN_WIRE={dcn_wire!r}"
            )
        key = (
            ("fed", "dcn", wire, perms, chunks, inject,
             dcn_wire, inter_perms, inter_chunks, inter_inject)
            + (inner._kernels.cache_token(wire)
               if wire is not None else ())
            + (inner._kernels.cache_token(dcn_wire)
               if dcn_wire is not None else ())
        )
        if wire is not None:
            n_intra = 1
            intra_ops = (recv_w,)

            def intra_leg(t, wops):
                return inner.weighted_combine_quantized_operands(
                    t, perms, wops[0], axis,
                    wire=wire, chunks=chunks, inject=inject,
                )
        else:
            n_intra = 2
            intra_ops = (self_w, recv_w)

            def intra_leg(t, wops):
                return inner.weighted_combine_operands(
                    t, perms, wops[0], wops[1], axis,
                    chunks=chunks, inject=inject,
                )
        if dcn_wire is not None:
            def fed_fn(t, step, wops):
                return inner.weighted_combine_quantized_operands(
                    intra_leg(t, wops), inter_perms, wops[n_intra],
                    axis, wire=dcn_wire, chunks=inter_chunks,
                    inject=inter_inject,
                )

            wops = intra_ops + (inter_recv,)
        else:
            def fed_fn(t, step, wops):
                return inner.weighted_combine_operands(
                    intra_leg(t, wops), inter_perms, wops[n_intra],
                    wops[n_intra + 1], axis, chunks=inter_chunks,
                    inject=inter_inject,
                )

            wops = intra_ops + (inter_self, inter_recv)
        return key, fed_fn, wops

    def _self_weight_fn(self, ctx):
        """Per-rank SELF weight of the active combine, as a traced
        ``fn(step, wops) -> scalar``, for the delayed (one-step-stale) mix.

        The stale combine is ``y = C(buf) + s * (x - buf)``: wire payloads
        come from the stale buffer (so the ppermutes depend on nothing the
        current step computes), but the receiver swaps the stale SELF
        contribution ``s * buf`` for the fresh ``s * x``. That
        "self-fresh, neighbors-stale" recursion is the AD-PSGD-family
        stale-mixing form, stable for every row-stochastic nonnegative
        weight matrix (each root t of ``t^2 - s t - (lam - s)`` has
        ``|t| <= 1`` because Gershgorin puts ``|lam - s| <= 1 - s``),
        where the naive ``y = x + C(buf) - buf`` delta recursion diverges
        whenever the mixing matrix has eigenvalues left of ``Re = 0``.
        """
        comm = self.communication_type
        if comm == CommunicationType.empty:
            return lambda step, wops: jnp.float32(1.0)
        if comm == CommunicationType.allreduce:
            inv_n = 1.0 / ctx.size
            return lambda step, wops: jnp.float32(inv_n)
        if self.schedule is not None:
            sched = self.schedule
            sw = jnp.asarray(
                np.stack([p.self_weights for p in sched.plans]),
                jnp.float32,
            )

            def from_schedule(step, wops):
                idx = jax.lax.axis_index(ctx_mod.WORKER_AXIS)
                return sw[step % sched.period, idx]

            return from_schedule
        compression = self.compression
        if compression in ("int8_ef", "int4_ef"):
            if self._fabric(ctx) is not None:
                # federated EF fallback: the dispatch degraded to the
                # memoryless base tier, whose wops carry only recv_w
                compression = compression[:-3]
        if compression in ("int8", "bf16", "int4"):
            # quantized path carries only recv_w (wops[0], [rounds, size]);
            # the plan is validated normalized, so s = 1 - sum_r recv_w
            def from_recv(step, wops):
                idx = jax.lax.axis_index(ctx_mod.WORKER_AXIS)
                return 1.0 - wops[0][:, idx].astype(jnp.float32).sum()

            return from_recv

        def from_operands(step, wops):  # exact path: wops = (self_w, recv_w)
            idx = jax.lax.axis_index(ctx_mod.WORKER_AXIS)
            return wops[0][idx].astype(jnp.float32)

        return from_operands

    def _validate_compression(self):
        """Central knob validation for BOTH the flat and hierarchical
        paths: a silently-ignored or trace-time-erroring knob would make
        the user believe wire bytes dropped when nothing changed."""
        if self.compression is None:
            return
        comm = self.communication_type
        if self.compression not in (
            "int8", "bf16", "int8_ef", "int4", "int4_ef",
        ):
            raise ValueError(
                "compression must be None, 'int8', 'bf16', 'int4', "
                f"'int8_ef', or 'int4_ef', got {self.compression!r}"
            )
        if (
            comm == CommunicationType.allreduce
            and self.order == "grad"
            and self.schedule is None
            and sharding.enabled()
            and sharding.grads_enabled()
        ):
            # ZeRO-2 scatter wire: every tier rides the reduce-scatter
            # gradient leg (the *_ef residuals are held per-slot inside
            # the scatter, not as gossip CHOCO copies)
            return
        if self.compression in ("int8_ef", "int4_ef") and (
            comm != CommunicationType.neighbor_allreduce
            or self.schedule is not None
        ):
            raise ValueError(
                f"compression={self.compression!r} (error feedback "
                "carries per-worker state) is only supported on the "
                "static-plan neighbor_allreduce path"
            )
        if comm not in (
            CommunicationType.neighbor_allreduce,
            CommunicationType.hierarchical_neighbor_allreduce,
        ) or self.schedule is not None:
            raise ValueError(
                f"compression={self.compression!r} is only supported "
                "on the static-plan neighbor_allreduce and "
                "hierarchical paths (not schedules, allreduce, or "
                "empty communication)"
            )

    def _hier_key_and_fn(self, ctx):
        """Hierarchical communication: static machine plan (operand
        weights) or a dynamic machine-level SchedulePlan (the reference's
        GetExp2DynamicSendRecvMachineRanks training pattern,
        examples/pytorch_benchmark.py:182-202)."""
        # machine-mesh rounds are not probeable on the worker mesh: the
        # doctor keeps step-level attribution, skips per-round profiling
        self._last_plan = None
        if self.schedule is not None:
            sched = self.schedule
            if sched.size != ctx.machine_size:
                raise ValueError(
                    "hierarchical opt.schedule must be machine-level: "
                    f"sized {sched.size}, but there are {ctx.machine_size} "
                    "machines"
                )
            return (
                ("hier_sched", sched),
                lambda t, step, wops: inner.hierarchical_neighbor_allreduce_step(
                    t, step, sched, ctx_mod.MACHINE_AXIS, ctx_mod.LOCAL_AXIS
                ),
                (),
            )
        mplan = self._machine_plan(ctx)
        perms = mplan.perms
        self_w, recv_w = mplan.weight_operands()
        if self.compression is not None:
            # compress the MACHINE-level (DCN) leg — the transfer that
            # actually scales with pod count; the intra-host psum stays
            # exact on ICI
            inner._check_combine_normalized(
                mplan, f"compression={self.compression!r}"
            )
            wire = self.compression
            return (
                ("hier_q", wire, perms)
                + inner._kernels.cache_token(wire),
                lambda t, step, wops: (
                    inner.hierarchical_neighbor_allreduce_quantized(
                        t, perms, wops[0],
                        ctx_mod.MACHINE_AXIS, ctx_mod.LOCAL_AXIS,
                        wire=wire,
                    )
                ),
                (recv_w,),
            )
        return (
            ("hier", perms),
            lambda t, step, wops: inner.hierarchical_neighbor_allreduce_operands(
                t, perms, wops[0], wops[1],
                ctx_mod.MACHINE_AXIS, ctx_mod.LOCAL_AXIS
            ),
            (self_w, recv_w),
        )

    def _machine_plan(self, ctx):
        if self.neighbor_machine_weights is not None:
            from bluefog_tpu.collective.plan import plan_from_weights

            mplan = plan_from_weights(
                ctx.machine_size,
                self.self_weight if self.self_weight is not None else 0.5,
                self.neighbor_machine_weights,
                self.send_neighbor_machines,
                enable_topo_check=self.enable_topo_check
                and self.send_neighbor_machines is not None,
            )
            flight.note_plan(
                mplan, ctx.machine_topo_version, kind="machine"
            )
            return mplan
        mtopo = ctx.load_machine_topology()
        assert mtopo is not None, (
            "hierarchical optimizer needs bf.set_machine_topology() or "
            "explicit neighbor_machine_weights"
        )
        key = ("opt_machine_plan", ctx.machine_topo_version,
               ctx.is_machine_topo_weighted())
        plan = ctx.op_cache.get(key)
        if plan is None:
            plan = plan_from_topology(
                mtopo, weighted=ctx.is_machine_topo_weighted()
            )
            ctx.op_cache[key] = plan
            flight.note_plan(
                plan, ctx.machine_topo_version, kind="machine"
            )
        return plan

    # -- error-feedback state (compression='int8_ef') ------------------------

    def _ensure_ef_state(self, ctx, params, spec, perms):
        """Per-dtype-group CHOCO copies (x_hat_self, x_hat_recv),
        worker-stacked f32; rebuilt (zeroed) whenever the parameter avals
        OR the communication structure OR the EF wire tier change —
        x_hat_recv[r] integrates round-r's fixed source, so a new edge
        set invalidates every copy (stale copies would break the
        bit-identical-replica invariant; zeroed copies merely
        re-transmit full magnitude a few rounds), and copies integrated
        under one quantizer must not seed the other tier's recursion."""

        leaves = jax.tree_util.tree_leaves(params)
        sig = (
            tuple(
                (dt, sum(int(np.prod(leaves[i].shape[1:])) for i in idxs))
                for dt, idxs in _dtype_groups(leaves)
            ),
            perms,
            self.compression,
        )
        if getattr(self, "_ef_sig", None) == sig:
            return
        n_rounds = len(perms)
        sharding = NamedSharding(ctx.mesh, spec)
        self._ef = tuple(
            (
                jax.device_put(
                    np.zeros((ctx.size, d), np.float32), sharding
                ),
                jax.device_put(
                    np.zeros((ctx.size, n_rounds, d), np.float32), sharding
                ),
            )
            for _dt, d in sig[0]
        )
        self._ef_sig = sig

    # -- the step ------------------------------------------------------------

    def _comm_now(self) -> bool:
        """Communicate on the K-th call (reference torch/optimizers.py:321);
        validates the K knob on every dispatch."""
        k = int(self.num_steps_per_communication)
        if k < 1:
            raise ValueError(
                "num_steps_per_communication must be a positive int, got "
                f"{self.num_steps_per_communication!r}"
            )
        return self._step_count % k == k - 1

    def _plan_step(self, ctx, params, opt_state, comm_now, flat=False):
        """The dispatch prologue of :meth:`step` and of the fused
        ``train_step``, as one :class:`_StepPlan`: mesh/spec selection,
        gossip resolution, error-feedback state, the shard / scatter
        prologue, metric sampling, and the cache-key parts the two
        program families share. One implementation so a new
        communication type or validation rule cannot reach one entry
        point and skip the other. ``flat`` is a caller whose state is the
        packed flat payload (the ``delayed=True`` buffers): no leaf
        travels alone there."""
        self._validate_compression()
        direct = not flat and self._direct_route(ctx)
        hier = (
            self.communication_type
            == CommunicationType.hierarchical_neighbor_allreduce
        )
        if hier:
            mesh = ctx.machine_mesh
            spec = P((ctx_mod.MACHINE_AXIS, ctx_mod.LOCAL_AXIS))
        else:
            mesh = ctx.mesh
            spec = P(ctx_mod.WORKER_AXIS)
        if not comm_now:
            # between-communication cta/atc call: the SAME fused body, with
            # the identity combine — a purely local inner update
            gossip_key, gossip_fn, wops = (
                ("local",), (lambda t, step, wops: t), ()
            )
        elif hier:
            gossip_key, gossip_fn, wops = self._hier_key_and_fn(ctx)
        else:
            gossip_key, gossip_fn, wops = self._gossip_key_and_fn(
                ctx, self._wire_payload(params, direct)
            )
        ef = comm_now and not hier and self.compression in (
            "int8_ef", "int4_ef",
        ) and not self._scatter_active() and gossip_key[0] != "fed"
        if ef:
            self._ensure_ef_state(ctx, params, spec, gossip_key[2])
        shard_l = None
        if comm_now and self._shard_active():
            shard_l, opt_state = self._shard_prepare(ctx, params, opt_state)
        (
            scatter_key, scatter_wire, scatter_chunks, scatter_ef,
        ) = self._scatter_prologue(ctx, shard_l, spec)
        met_enabled = metrics_mod.enabled() and comm_now
        # Two-program sampling: only the 1-in-interval sampled step pays
        # the metric computation — every other step dispatches a program
        # whose cache key EQUALS the metrics-off key, so 9 of 10 steps
        # are the metrics-off program by construction (an in-graph
        # lax.cond was measured to drag every step).
        met = met_enabled and (
            self._comm_count % metrics_mod.metrics_interval() == 0
        )
        plan = _StepPlan(
            comm_now=comm_now, hier=hier, mesh=mesh, spec=spec,
            gossip_key=gossip_key, gossip_fn=gossip_fn, wops=wops, ef=ef,
            cap_bytes=inner.bucket_bytes_cap(), direct=direct,
            shard_l=shard_l, scatter_wire=scatter_wire,
            scatter_chunks=scatter_chunks, scatter_ef=scatter_ef,
            met_enabled=met_enabled, met=met,
            # the delayed probe measures the stale mix without a wire
            # payload (no quant/EF slots): see _build_step's delayed_probe
            wire_now=(
                None if flat
                else self._metrics_wire(comm_now, hier, gossip_key)
            ),
            key_ident=(
                self.order, self.communication_type, self._uid,
                self._tx_version, ef,
            ),
            # BLUEFOG_SHARD=0 leaves the key verbatim (bitwise shard-off
            # pin); an active layout keys on its full signature so a
            # membership change can never dispatch a stale owner map
            key_tail=tuple(gossip_key) + (
                shard_l.sig() if shard_l is not None else ()
            ) + scatter_key,
        )
        return plan, opt_state

    # -- device-tier metrics plumbing ----------------------------------------

    def _metrics_wire(self, comm_now, hier, gossip_key=None):
        """The quantized-wire name for this dispatch's metric row, or
        None. Hierarchical compression quantizes the machine-level
        local_sum (not the packed tree payload the metric helper sees),
        so its quantization error is not computed — the flat-path wires
        are the ones with a well-defined per-worker payload here."""
        if not comm_now or hier or self.schedule is not None:
            return None
        if gossip_key is not None and gossip_key[0] == "fed":
            # federated dispatch: the key carries the EFFECTIVE intra
            # wire (EF tiers degrade to their memoryless base there)
            return gossip_key[2]
        if self.compression in (
            "int8", "bf16", "int8_ef", "int4", "int4_ef",
        ):
            if (
                self.compression.endswith("_ef")
                and self._scatter_active()
            ):
                # ZeRO-2 scatter EF: the residual lives per-slot inside
                # the scatter (no probe-side CHOCO slice), so the metric
                # row replays the base tier's quantization error
                return self.compression[:-3]
            return self.compression
        return None

    @staticmethod
    def _fold_pending(pending, export):
        wire, payload = pending
        payload = jax.tree_util.tree_map(np.asarray, payload)
        metrics_mod.fold_device_payload(payload, wire=wire, export=export)

    def _drain_after_sample(self, wire, payload):
        """After a sampled dispatch, stash its subsample payload and
        START the device->host copy (``copy_to_host_async``); the
        registry fold happens at the NEXT sample (or at an explicit
        :func:`bluefog_tpu.metrics.flush`), by which point the copy has
        long completed. A synchronous ``np.asarray`` here would block
        the host mid-loop and forfeit a dispatch-pipeline's worth of
        overlap per drain."""
        if not self._metrics_hooked:
            # flush hook: bf.metrics_export()/shutdown fold the pending
            # payload so exports never miss the tail of a run
            metrics_mod.register_flush_hook(self)
            self._metrics_hooked = True
        if self._pending_drain is not None:
            self._fold_pending(self._pending_drain, export=True)
        for leaf in jax.tree_util.tree_leaves(payload):
            try:
                leaf.copy_to_host_async()
            except AttributeError:  # non-jax.Array stand-ins in tests
                pass
        self._pending_drain = (wire, payload)

    def _flush_metrics(self):
        """Fold the pending payload into the registry now (no exporter
        side effects — the caller, :func:`bluefog_tpu.metrics.flush`,
        owns what happens next)."""
        if self._pending_drain is not None:
            self._fold_pending(self._pending_drain, export=False)
            self._pending_drain = None

    def _record_comm_accounting(self, key, gossip_key, params, ctx,
                                shard=None):
        """Host-tier per-dispatch accounting: ppermute rounds and wire
        bytes for this communicating step (static per compiled program,
        so the numbers are computed once per cache key). TopoOpt-style
        per-edge traffic planning starts from exactly this counter.
        An active shard layout adds its all-gather redistribution bytes
        and publishes the ``bluefog.shard.*`` gauges."""
        acct = self._acct_cache.get(key)
        if acct is None:
            tag = gossip_key[0]
            wire = None
            rounds = 0
            ici_bytes = dcn_bytes = 0
            # gossip_key layouts: ("na", perms, chunks, inject),
            # ("na_q", wire, perms, chunks, inject),
            # ("na_q_ef", wire, perms, chunks), ("hier", perms),
            # ("hier_q", wire, perms) — perms sits at [1] except the
            # wire-tagged quantized keys where it sits at [2];
            # ("fed", leg, wire, perms, chunks, inject[, dcn_wire,
            # inter_perms, inter_chunks, inter_inject]) carries the
            # intra perms at [3] and (dcn leg) inter perms at [7]
            if tag in ("na", "hier"):
                rounds = len(gossip_key[1])
            elif tag in ("na_q", "na_q_ef", "hier_q"):
                wire = gossip_key[1]
                if tag == "na_q_ef":
                    # the key carries the inner quantizer name; the
                    # accounting tier is the _ef wire (same bytes)
                    wire = f"{wire}_ef"
                rounds = len(gossip_key[2])
            elif isinstance(tag, SchedulePlan):
                rounds = max(len(p.rounds) for p in tag.plans)
            elif tag == "hier_sched":
                rounds = max(len(p.rounds) for p in gossip_key[1].plans)
            elif tag == "allreduce":
                rounds = 1
            leaves = jax.tree_util.tree_leaves(params)
            by_item: dict = {}
            for l in leaves:
                n = int(np.prod(l.shape[1:])) if l.ndim > 1 else 1
                item = np.dtype(l.dtype).itemsize
                by_item[item] = by_item.get(item, 0) + n
            scatter_bytes = 0
            if tag == "allreduce":
                if shard is not None and shard.grads:
                    # ZeRO-2: the gradient leg is a reduce-scatter of
                    # owned slots (optionally quantized) — price what
                    # actually ships, not the allreduce formula the
                    # replicated family would have used
                    from bluefog_tpu import scaling

                    scatter_bytes = scaling.reduce_scatter_bytes(
                        tuple(
                            (g.slot, np.dtype(g.dtype).itemsize)
                            for g in shard.groups
                        ),
                        shard.size, wire=self.compression,
                    )
                    wire_bytes = scatter_bytes
                    rounds = shard.size - 1
                else:
                    # ring allreduce ships ~2 (n-1)/n payloads per worker
                    payload = sum(i * n for i, n in by_item.items())
                    wire_bytes = int(
                        2 * (ctx.size - 1) / max(ctx.size, 1) * payload
                    )
            elif tag == "fed":
                # per-leg accounting: the ICI leg ships the intra-pod
                # rounds on the optimizer's wire, the DCN leg (when this
                # key is a DCN step) the gateway rounds on the fabric's
                # aggressive tier
                ici_bytes = metrics_mod.wire_bytes_per_step(
                    by_item, len(gossip_key[3]), gossip_key[2]
                )
                rounds = len(gossip_key[3])
                if gossip_key[1] == "dcn":
                    dcn_bytes = metrics_mod.wire_bytes_per_step(
                        by_item, len(gossip_key[7]), gossip_key[6]
                    )
                    rounds += len(gossip_key[7])
                wire_bytes = ici_bytes + dcn_bytes
            else:
                wire_bytes = metrics_mod.wire_bytes_per_step(
                    by_item, rounds, wire
                )
            if shard is not None:
                # the sharded step ships the updated slices back over
                # the fabric: price the all-gather with the gossip wire
                wire_bytes += sharding.gather_wire_bytes(shard)
            acct = (rounds, wire_bytes, scatter_bytes, ici_bytes,
                    dcn_bytes)
            self._acct_cache[key] = acct
        rounds, wire_bytes, scatter_bytes, ici_bytes, dcn_bytes = acct
        metrics_mod.gauge("bluefog.gossip.rounds").set(rounds)
        metrics_mod.counter("bluefog.wire_bytes").inc(wire_bytes)
        metrics_mod.counter("bluefog.comm_steps").inc()
        if ici_bytes or dcn_bytes:
            metrics_mod.counter(
                "bluefog.federation.ici_wire_bytes"
            ).inc(ici_bytes)
            metrics_mod.counter(
                "bluefog.federation.dcn_wire_bytes"
            ).inc(dcn_bytes)
        if shard is not None:
            metrics_mod.gauge("bluefog.shard.enabled").set(1)
            metrics_mod.gauge("bluefog.shard.state_bytes").set(
                sharding.state_bytes(shard)
            )
            metrics_mod.gauge("bluefog.shard.ratio").set(
                sharding.state_bytes(shard)
                / max(sharding.state_bytes(shard, sharded=False), 1)
            )
            metrics_mod.counter("bluefog.shard.gather_bytes").inc(
                sharding.gather_wire_bytes(shard)
            )
            metrics_mod.gauge("bluefog.shard.grads").set(
                1 if shard.grads else 0
            )
            if shard.grads:
                metrics_mod.counter("bluefog.shard.scatter_bytes").inc(
                    scatter_bytes
                )
                metrics_mod.gauge("bluefog.shard.grad_bytes").set(
                    sharding.grad_bytes(shard)
                )

    def step(self, params, opt_state, grads):
        """One decentralized optimization step; returns (params, opt_state).

        The whole step is one compiled SPMD program (reference splits it
        across hooks + synchronize + inner step, optimizers.py:362-482).

        This entry point keeps its inputs: ``params``, ``opt_state`` and
        ``grads`` are all alive after the call. The fused
        :meth:`make_train_step` donates its carry by default and this
        does not, though both run one step core, because of who holds
        the operands: a fused step's caller has nothing to do with the
        old parameters but rebind them, while ``step``'s caller computed
        ``grads`` from ``params`` in a program of their own and commonly
        still holds both (to log, to clip against, to take the next
        gradient while this step runs). Same jit site, empty
        ``donate_argnums`` for this program.
        """
        ctx = ctx_mod.get_context()
        comm_now = self._comm_now()
        if not comm_now and self.order == "grad":
            # between communications, gradient order accumulates and leaves
            # params/state untouched (reference _DistributedOptimizer's
            # reduce-delay accumulation, optimizers.py:347,443)
            self._step_count += 1
            self._grad_accum = (
                grads if self._grad_accum is None
                else self._tree_add(ctx, self._grad_accum, grads)
            )
            return params, opt_state
        plan, opt_state = self._plan_step(ctx, params, opt_state, comm_now)
        key = (
            "opt_step", *plan.key_ident, plan.cap_bytes, plan.direct,
            plan.met, *plan.key_tail, *_aval_key(params),
        )
        fn = ctx.op_cache.get(key)
        if fn is None:
            _record_step_built("opt_step", params, plan.cap_bytes, plan.direct)
            fn = ctx.op_cache[key] = self._build_step(plan)
        if comm_now and self.order == "grad" and self._grad_accum is not None:
            grads = self._tree_add(ctx, self._grad_accum, grads)
            self._grad_accum = None
        flight.record("step_begin", step=self._step_count, comm=comm_now)
        cur_comm, ef_in = self._begin_step(ctx, plan, key, params)
        step_idx, wops = _stage_operands(plan.mesh, cur_comm, plan.wops)
        doc_t0 = attribution.dispatch_timer(comm_now)
        params_out, opt_state, ef_out, met_out = _timed_dispatch(
            "optimizer_step", fn, params, opt_state, grads, step_idx,
            wops, ef_in,
        )
        flight.record("step_dispatched", step=self._step_count - 1)
        # this path always gossips the fresh iterate: payload age 0
        self._finish_step(
            ctx, plan, doc_t0, params, params_out, params_out, opt_state,
            ef_out, met_out, grads=grads,
        )
        return params_out, opt_state

    # -- the step core: what step() and the fused train_step share -----------

    def _build_step(self, plan, value_and_grad=None, has_aux=False,
                    self_weight_fn=None, has_accum=False, n_batch=0,
                    donate=False):
        """The compiled step program of ``plan``: block the operands, get
        the gradients, run :func:`_combine_update` (or the
        ``delayed=True`` stale mix), restack — one ``shard_map`` body and
        one jit site for both entry points, so a change to the step
        reaches both. What tells the two programs apart is where the
        gradients come from. With ``value_and_grad`` (of the caller's
        loss) they are computed inside the program: the fused
        ``bf_step``, ``(params, state, step, wops, ef, delay buffers,
        accumulator, *batch) -> (params, state, loss, aux, ef, delay
        buffers — or the gradient, on an accumulation call —, metrics)``.
        Without it they are an operand: :meth:`step`'s program,
        ``(params, state, grads, step, wops, ef) -> (params, state, ef,
        metrics)``. ``self_weight_fn`` makes it the ``delayed=True``
        program; ``has_accum`` adds the host-side gradient accumulator
        to the gradient inside it. ``donate`` (the fused program only)
        donates the carry, :data:`_DONATED`: in and out specs are both
        ``spec``, so each of its leaves has an output of its shape,
        dtype and sharding to be written into."""
        order = self.order
        tx = self._tx
        comm_now, met = plan.comm_now, plan.met
        gossip_fn, cap_bytes = plan.gossip_fn, plan.cap_bytes
        fused = value_and_grad is not None
        delay_now = self_weight_fn is not None

        # its own name, not one more `body`: the name is the compiled
        # module's (`jit_bf_step` in a device trace) and part of the
        # persistent compile cache's key, which leaves metadata out
        def bf_step(params_b, state_b, step, wops, ef_b, buf_b, accum_b,
                    *batch_b):
            p = _tree_block(params_b)
            s = _tree_block(state_b)
            bat = tuple(_tree_block(b) for b in batch_b)
            step = step[0]
            if delay_now:
                # The stale combine's wire legs FIRST, on the carried
                # buffers: these ppermutes depend on nothing this step
                # computes, so the scheduler is free to run them under
                # the forward/backward below. Only the cheap elementwise
                # self-swap (see _self_weight_fn) touches fresh values.
                bufs = tuple(b[0] for b in buf_b)
                combined = tuple(
                    _bucketed_flat_gossip(
                        b, gossip_fn, step, wops, cap_bytes
                    )
                    for b in bufs
                )
                with jax.named_scope("bf.gossip"):
                    sw = self_weight_fn(step, wops)

                def stale_mix(tree):
                    fresh = _pack_groups(tree)
                    with jax.named_scope("bf.gossip"):
                        mixed = tuple(
                            c + sw.astype(c.dtype)
                            * (x.astype(c.dtype) - b.astype(c.dtype))
                            for c, x, b in zip(combined, fresh, bufs)
                        )
                    return _unpack_groups(tree, mixed)

                def delayed_probe(tree, grads):
                    """Metrics sub-gossip for the stale mix (same
                    rationale as _combine_update's probe: never consume
                    the big combine's outputs): re-run the mix on a
                    512-aligned prefix of the carried buffer + fresh
                    packs — bitwise the restriction of the full stale
                    combine."""
                    cap = metrics_mod.sample_elems_cap()
                    pairs = []
                    for gi, (f_sub, scale) in enumerate(
                        _packed_prefix(tree, cap)
                    ):
                        k = f_sub.shape[0]
                        b_sub = bufs[gi][:k]
                        c_sub = _bucketed_flat_gossip(
                            b_sub, gossip_fn, step, wops, cap_bytes,
                        )
                        y_sub = c_sub + sw.astype(c_sub.dtype) * (
                            f_sub.astype(c_sub.dtype)
                            - b_sub.astype(c_sub.dtype)
                        )
                        pairs.append((f_sub, y_sub, scale, None))
                    return metrics_mod.build_probe_payload(
                        pairs,
                        _packed_prefix(grads, cap),
                        wire=None,
                    )
            if fused:
                with jax.named_scope("bf.loss_grad"):
                    if has_aux:
                        (loss, aux), grads = value_and_grad(p, *bat)
                    else:
                        loss, grads = value_and_grad(p, *bat)
                        aux = ()
            else:
                (grads,) = bat
            if fused and order == "grad" and not comm_now:
                # accumulation call (step() accumulates on the host and
                # builds no program for it): params/state untouched, the
                # gradient comes OUT to the host-side accumulator
                return (
                    _tree_restack(p), _tree_restack(s),
                    jnp.reshape(loss, (1,)),
                    _tree_restack(aux) if has_aux else (),
                    (), _tree_restack(grads), (),
                )
            if has_accum:
                grads = jax.tree_util.tree_map(
                    jnp.add, _tree_block(accum_b), grads
                )
            mvec = None
            if delay_now:
                if order == "cta":
                    new_buf = _pack_groups(p)
                    if met:
                        # delayed mix: delta measured against the FRESH
                        # iterate (wire/EF metrics have no stale-payload
                        # form, see docs/metrics.md)
                        mvec = delayed_probe(p, grads)
                    p = stale_mix(p)
                    p, s = _inner_update(tx, grads, s, p)
                else:  # atc
                    p, s = _inner_update(tx, grads, s, p)
                    new_buf = _pack_groups(p)
                    if met:
                        mvec = delayed_probe(p, grads)
                    p = stale_mix(p)
                buf_out = tuple(jnp.expand_dims(b, 0) for b in new_buf)
                ef_out = ()
            else:
                # unstack whichever EF state rides this program: gossip
                # CHOCO pairs or the ZeRO-2 per-slot scatter residuals
                ef_in = jax.tree_util.tree_map(lambda a: a[0], ef_b)
                p, s, ef_out, mvec = _combine_update(
                    order, tx, gossip_fn, wops, step, cap_bytes,
                    plan.ef, ef_in, p, s, grads,
                    wire=plan.wire_now, with_metrics=met,
                    shard=plan.shard_l, scatter_wire=plan.scatter_wire,
                    scatter_chunks=plan.scatter_chunks, direct=plan.direct,
                )
                ef_out = jax.tree_util.tree_map(
                    lambda a: jnp.expand_dims(a, 0), ef_out
                )
                buf_out = ()
            met_out = (_tree_restack(mvec),) if met else ()
            p_out, s_out = _tree_restack(p), _tree_restack(s)
            if not fused:
                return p_out, s_out, ef_out, met_out
            return (
                p_out, s_out, jnp.reshape(loss, (1,)),
                _tree_restack(aux) if has_aux else (),
                ef_out, buf_out, met_out,
            )

        spec = plan.spec
        if fused:
            body, n_out = bf_step, 7
            in_specs = (spec, spec, P(), P(), spec, spec, spec) + (
                (spec,) * n_batch
            )
        else:
            # step()'s operand order; no delay buffer, no accumulator
            def body(params_b, state_b, grads_b, step, wops, ef_b):
                return bf_step(
                    params_b, state_b, step, wops, ef_b, (), (), grads_b
                )

            n_out = 4
            in_specs = (spec, spec, spec, P(), P(), spec)
        # "compile" phase watermark: the wrapper build is traced here;
        # the XLA compile itself lands in the first dispatch's bracket
        # (jit is lazy) — both attributed
        with memory_mod.phase_scope("compile"):
            return jax.jit(
                jax.shard_map(
                    body, mesh=plan.mesh, in_specs=in_specs,
                    out_specs=(spec,) * n_out,
                ),
                donate_argnums=_DONATED if fused and donate else (),
            )

    def _begin_step(self, ctx, plan, key, params):
        """Count this call and account its wire, before the dispatch (a
        dispatch that raises has still taken its step). Returns the
        communication index the dispatch runs at — dynamic schedules
        advance per COMMUNICATION, not per call, so a K>1 optimizer
        still walks every topology in the schedule — and the
        error-feedback state that rides the program."""
        cur_comm = self._comm_count
        self._step_count += 1
        if plan.comm_now:
            self._comm_count += 1
        if plan.met_enabled:
            self._record_comm_accounting(
                key, plan.gossip_key, params, ctx, shard=plan.shard_l
            )
        return cur_comm, self._ef_operand(plan)

    def _ef_operand(self, plan):
        """The error-feedback state that rides ``plan``'s program: the
        ZeRO-2 scatter's per-slot residuals, the gossip's CHOCO copies,
        or nothing."""
        if plan.scatter_ef:
            return self._scatter_ef
        return self._ef if plan.ef else ()

    def _finish_step(self, ctx, plan, doc_t0, params, outputs, params_out,
                     state_out, ef_out, met_out, grads=None, payload_age=0,
                     surface="sync"):
        """The epilogue of a dispatched step, for both entry points: keep
        the error-feedback state the program returned, start the sampled
        metric row's drain, and after a communicating step call each of
        the six observers once, in this order. Every one of them is
        host-side observation (plus, at most, tiny probe dispatches in
        an op-cache family of its own): the training program above is
        untouched — same cache key, same bits. ``outputs`` is what the
        attribution doctor waits on when it times a step (``step()``'s
        new parameters, the fused step's loss); ``grads`` joins the
        memory census where they are a buffer of the caller's;
        ``payload_age`` / ``surface`` say what the combine consumed
        (the fresh iterate, or ``delayed=True``'s double buffer)."""
        if plan.ef:
            self._ef = ef_out
        elif plan.scatter_ef:
            self._scatter_ef = ef_out
        if plan.met:
            self._drain_after_sample(plan.wire_now, met_out[0])
        if not plan.comm_now:
            return
        step = self._step_count - 1
        last_plan = self._last_plan
        # attribution doctor (BLUEFOG_DOCTOR)
        attribution.observe_step(
            ctx, step=step, outputs=outputs, plan=last_plan, params=params,
            wire=self.compression,
            dispatch_s=(
                time.perf_counter() - doc_t0 if doc_t0 is not None else None
            ),
        )
        # fleet health plane (BLUEFOG_HEALTH): host arithmetic + its own
        # tiny lane dispatches only
        health_mod.observe_step(ctx, step=step, plan=last_plan)
        # staleness observatory (BLUEFOG_STALENESS): stamps the payload's
        # birth and folds the delivered ages; age 0 is the lane's
        # per-sample self-check
        staleness_mod.observe_step(
            ctx, step=step, plan=last_plan, payload_age=payload_age,
            surface=surface,
        )
        # autotune controller (BLUEFOG_AUTOTUNE): decision logic only; a
        # migration it makes lands as a topology-version bump that the
        # next dispatch re-resolves, exactly like an elastic repair
        autotune_mod.observe_step(
            ctx, step=step, optimizer=self, plan=last_plan,
        )
        # memory observatory (BLUEFOG_MEMORY): census of the buffers THIS
        # dispatch left live (params + optax state + EF/delay copies)
        memory_mod.observe_step(
            ctx, step=step, optimizer=self, params=params_out,
            opt_state=state_out, grads=grads,
        )
        # SLO engine (BLUEFOG_SLO): LAST, so its sampled pass reads the
        # gauges the tiers above just refreshed; its canary probe
        # dispatches in its own op-cache family
        slo_mod.observe_step(
            ctx, step=step, plan=last_plan, wire=self.compression,
        )

    # -- the fused train step (overlap layer) --------------------------------

    def _ensure_delay_state(self, ctx, mesh, params, spec, struct_key):
        """Double buffer for ``delayed=True``: one worker-stacked flat
        payload per dtype group, holding the PREVIOUS step's gossip input
        (pre-update params for CTA, post-update for ATC). Seeded from the
        current params — step 0's combine is then exactly the fresh
        combine, and staleness starts at step 1. Rebuilt whenever the
        parameter avals or the communication structure change (a stale
        buffer under a new edge set would mix against the wrong sources,
        same invalidation rule as the error-feedback copies)."""

        leaves = jax.tree_util.tree_leaves(params)
        sig = (
            tuple(
                (dt, sum(int(np.prod(leaves[i].shape[1:])) for i in idxs))
                for dt, idxs in _dtype_groups(leaves)
            ),
            struct_key,
        )
        if getattr(self, "_delay_sig", None) == sig:
            return
        sharding = NamedSharding(mesh, spec)
        size = ctx.size
        bufs = []
        for _dt, idxs in _dtype_groups(leaves):
            parts = [jnp.reshape(leaves[i], (size, -1)) for i in idxs]
            # the seed owns its memory: a one-leaf group's reshape and
            # concatenate are the identity and hand back the parameter's
            # own buffer, which the step would then be given twice to
            # donate
            flat = (
                jnp.concatenate(parts, axis=1) if len(parts) > 1
                else jnp.copy(parts[0])
            )
            bufs.append(jax.device_put(flat, sharding))
        self._delay_buf = tuple(bufs)
        self._delay_sig = sig
        # provenance: a (re)seeded buffer holds the CURRENT params, so
        # the next combine's payload age is 0 — the staleness
        # observatory reads the age-0 transient at every topology swap
        # / elastic repair, then the steady-state age-1 again
        self._delay_birth_comm = self._comm_count

    def make_train_step(self, loss_fn, has_aux: bool = False,
                        delayed: bool = False, donate: bool = True):
        """Build the fused train step: forward, backward, inner optax
        update, and the gossip combine in ONE compiled shard_map program.

        ``loss_fn(params, *batch) -> loss`` (or ``(loss, aux)`` with
        ``has_aux=True``) is evaluated per worker on UNSTACKED trees; the
        returned callable takes worker-stacked operands::

            train_step = opt.make_train_step(loss_fn)
            params, opt_state, loss = train_step(params, opt_state, *batch)

        **The call consumes its carry** (``donate=True``, the default):
        ``params`` and ``opt_state`` are donated to the compiled program
        (``jax.jit``'s ``donate_argnums``), which writes the new
        parameters and state into the very buffers it was given. After
        the call every array of the two trees passed in is deleted
        (``is_deleted()``; reading one raises jax's own error) and the
        returned trees take their place — which is what the loop above
        does by rebinding both names. It saves a second copy of
        parameters and state on the device while a step is in flight,
        and on the host one buffer allocation per output leaf per call
        (PERF.md, PR 31). The batch operands (and a ``has_aux`` loss's
        mutable collections, which are batch operands) are never
        donated. Two leaves of the carry must not be one buffer (a state
        that holds a parameter itself): that raises ``ValueError`` when
        the step is built. A caller that reads ``params`` or
        ``opt_state`` again after the call — to compare, to roll back,
        to feed a second optimizer — either copies first
        (``jax.tree_util.tree_map(jnp.copy, params)``) or builds the
        step with ``donate=False``, which keeps every input alive at the
        old cost; both compute the same bits. It is an argument and not
        an environment variable because whether the caller reads an
        input again is the one thing the step cannot observe.
        :meth:`step` keeps its inputs: its callers compute ``grads`` from
        ``params`` outside the program and commonly hold both. What a
        built program donates is the gauge ``bluefog.step_donated_bytes``
        (per worker), and ``bluefog.step_donation_unused`` counts the
        donated leaves it could not write in place (docs/metrics.md).

        Why this exists: ``opt.step`` is its own program, so the caller's
        backward pass and the gossip collective live in different XLA
        programs and can never overlap — every ppermute round is exposed
        on the step critical path. Inside one program, XLA's
        latency-hiding scheduler hoists each round's ppermute start above
        independent backward/update compute and sinks the wait below it,
        hiding the transfer (the in-XLA analogue of the reference's
        backward-hook overlap, torch/optimizers.py:166-1554, and of the
        fused weight-update design in "Automatic Cross-Replica Sharding
        of Weight Update in Data-Parallel Training"). This callable and
        :meth:`step` are two entry points over one step core
        (:meth:`_plan_step`, :meth:`_build_step`, :meth:`_finish_step`):
        the same body, with the gradient computed inside the program
        here and an operand there. Where no matmul precedes the update
        the two agree to the bit (tests/test_step_core.py); behind a
        model's backward pass, under momentum, to a few float32 ulp,
        because XLA may round the update differently when it fuses it
        into the gradient's last kernel (tests/test_overlap.py states
        the tolerance).

        ``delayed=True`` (ATC/CTA only) takes communication off the
        critical path entirely: the combine at step k mixes the payload
        double-buffered from step k-1, so the ppermutes depend ONLY on a
        carried buffer — zero data dependency on this step's
        forward/backward — and the scheduler can run them concurrently
        with the whole step. The cost is one-step-stale mixing, a
        known-convergent decentralized-SGD variant (the same staleness
        family as asynchronous gossip; consensus and convergence are
        preserved, constants degrade slightly — see docs/performance.md
        for the caveat). ``compression='int8_ef'`` is refused with
        ``delayed=True``: the error-feedback copies integrate the payload
        round by round, and a one-step-stale payload would desynchronize
        sender and receiver copies, breaking the bit-identical-replica
        invariant that scheme relies on.
        """
        if self.order not in ("cta", "atc", "grad"):
            raise AssertionError(self.order)
        if delayed and self.order == "grad":
            raise ValueError(
                "delayed=True applies to the weight-gossip families "
                "(CTA/ATC); gradient allreduce has no stale-mix variant"
            )
        value_and_grad = jax.value_and_grad(loss_fn, has_aux=has_aux)
        # Per-builder cache-key component: two builders over the same
        # optimizer may close over different loss functions.
        fused_uid = next(_opt_uid)
        # the cache key of the call before: what a miss is compared with
        last_key = [None]

        def train_step(params, opt_state, *batch):
            with flight.StepPhases(self._step_count) as phases:
                return run_step(phases, params, opt_state, *batch)

        def run_step(phases, params, opt_state, *batch):
            ctx = ctx_mod.get_context()
            if delayed and self.compression in ("int8_ef", "int4_ef"):
                raise ValueError(
                    f"compression={self.compression!r} cannot carry "
                    "error feedback across a one-step delay (the CHOCO "
                    "copies would integrate a stale payload and "
                    "desynchronize); use delayed=False or a memoryless "
                    "wire (None/'int8'/'bf16'/'int4')"
                )
            comm_now = self._comm_now()
            plan, opt_state = self._plan_step(
                ctx, params, opt_state, comm_now, flat=delayed
            )
            if delayed and plan.hier:
                raise ValueError(
                    "delayed=True is not supported for hierarchical "
                    "communication (the intra-machine psum leg has no "
                    "stale-mix form); use flat neighbor_allreduce or "
                    "delayed=False"
                )
            delay_now = delayed and comm_now
            self_weight_fn = (
                self._self_weight_fn(ctx) if delay_now else None
            )
            if delay_now:
                self._ensure_delay_state(
                    ctx, plan.mesh, params, plan.spec, plan.gossip_key
                )
            accum = (
                self._grad_accum
                if comm_now and self.order == "grad" else None
            )
            phases.enter("key")
            key = (
                "opt_fused_step", fused_uid, *plan.key_ident, delay_now,
                donate, plan.cap_bytes, plan.direct, accum is not None,
                plan.met,
                *plan.key_tail,
                *_aval_key((params, opt_state, batch)),
            )
            fn = ctx.op_cache.get(key)
            first_dispatch = contextlib.nullcontext()
            buf_in = self._delay_buf if delay_now else ()
            if fn is None:
                donated = (
                    (params, opt_state, self._ef_operand(plan), buf_in)
                    if donate else ()
                )
                _check_donated_once(donated)
                _record_step_built(
                    "opt_fused_step", params, plan.cap_bytes, plan.direct,
                    donated=donated,
                    differs_at=_first_difference(last_key[0], key),
                )
                fn = ctx.op_cache[key] = self._build_step(
                    plan, value_and_grad, has_aux, self_weight_fn,
                    accum is not None, len(batch), donate,
                )
                first_dispatch = _unused_donations_counted()
            last_key[0] = key
            phases.enter("stage", comm=comm_now, fused=True)  # step_begin
            cur_comm, ef_in = self._begin_step(ctx, plan, key, params)
            # the age of the payload this dispatch's combine consumes: 0
            # on the fresh path, comm steps since the delay buffer was
            # written on the delayed path (1 in steady state, 0 right
            # after a reseed)
            payload_age = (
                cur_comm - self._delay_birth_comm if delay_now else 0
            )
            accum_in = accum if accum is not None else ()
            # single source of truth for debug/evidence lowering
            # (lower_last_fused_hlo): the compiled fn plus exactly the
            # operand structure this dispatch used — as avals, not live
            # arrays, so the hook never pins a superseded model-sized
            # buffer generation in device memory. The host-built operands
            # carry the sharding they are staged with just below (so the
            # lowering is the dispatched module, not a twin of it), and
            # are recorded first: staging needs a device to put to, which
            # an off-chip compile for a described topology does not have
            def avals(op, sharding=None):
                return jax.tree_util.tree_map(
                    lambda t: jax.ShapeDtypeStruct(
                        t.shape, t.dtype, sharding=sharding
                    ), op,
                )

            replicated = NamedSharding(plan.mesh, P())
            self._last_fused_step = jax.ShapeDtypeStruct(
                (1,), jnp.int32, sharding=replicated
            )
            self._last_fused = (
                fn, avals(plan.wops, replicated), avals(ef_in),
                avals(buf_in), avals(accum_in),
            )
            step_idx, wops = _stage_operands(plan.mesh, cur_comm, plan.wops)
            doc_t0 = attribution.dispatch_timer(comm_now)
            phases.enter("enqueue")
            with first_dispatch:
                params_o, state_o, loss, aux, ef_o, buf_o, met_o = (
                    _timed_dispatch(
                        "fused_train_step", fn, params, opt_state,
                        step_idx, wops, ef_in, buf_in, accum_in, *batch,
                    )
                )
            phases.enter("epilogue")  # step_dispatched
            if self.order == "grad":
                # an accumulation call's gradient comes out where the
                # delay buffer would; the communicating call consumed
                # the accumulator
                if comm_now:
                    self._grad_accum = None
                elif self._grad_accum is None:
                    self._grad_accum = buf_o
                else:
                    self._grad_accum = self._tree_add(
                        ctx, self._grad_accum, buf_o
                    )
            elif delay_now:
                self._delay_buf = buf_o
            # the staleness observatory gets the payload's REAL birth:
            # the delayed path gossips the double-buffered previous
            # iterate
            self._finish_step(
                ctx, plan, doc_t0, params, loss, params_o, state_o, ef_o,
                met_o, payload_age=payload_age,
                surface="delayed" if delay_now else "sync",
            )
            if delay_now:
                # the dispatch above refilled the double buffer with
                # this step's payload
                self._delay_birth_comm = cur_comm
            if has_aux:
                return params_o, state_o, (loss, aux)
            return params_o, state_o, loss

        return train_step

    def make_async_train_step(self, loss_fn, has_aux: bool = False,
                              **kwargs):
        """Build the fully *asynchronous* train step: per-rank-cadence
        push-sum gossip where no rank ever waits on a peer
        (:func:`bluefog_tpu.async_gossip.make_async_train_step` — this
        optimizer contributes its inner optax transformation and its
        ``compression`` knob as the default wire tier). With
        ``BLUEFOG_ASYNC=0`` this IS :meth:`make_train_step` — the
        synchronous path, bitwise identical. See docs/async.md."""
        from bluefog_tpu import async_gossip

        return async_gossip.make_async_train_step(
            self, loss_fn, has_aux=has_aux, **kwargs
        )

    def lower_last_fused_hlo(self, params, opt_state, *batch) -> str:
        """Optimized HLO text of the most recently dispatched fused train
        step, lowered against the given operands (only their avals
        matter; the recorded dispatch operands are kept as
        ShapeDtypeStructs). Debug hook for ``chip_smoke.py`` and the
        HLO tests (``tests/test_overlap.py`` and others) — it owns
        the compiled fn's operand structure so callers never have to
        poke cache-key internals."""
        fn, wops, ef_in, buf_in, accum_in = self._last_fused
        step_idx = self._last_fused_step
        return (
            fn.lower(
                params, opt_state, step_idx, wops, ef_in, buf_in,
                accum_in, *batch,
            )
            .compile()
            .as_text()
        )

    def _tree_add(self, ctx, a, b):
        # keyed by avals only: identical tree-adds from different
        # optimizer instances share one compiled program
        key = ("opt_tree_add",) + _aval_key(a)
        fn = ctx.op_cache.get(key)
        if fn is None:
            fn = jax.jit(
                lambda x, y: jax.tree_util.tree_map(jnp.add, x, y)
            )
            ctx.op_cache[key] = fn
        return fn(a, b)

def DistributedGradientAllreduceOptimizer(base_optimizer,
                                          num_steps_per_communication=1):
    """Synchronous gradient averaging, Horovod-style
    (reference optimizers.py:166-295, factory :1376)."""
    return _GossipOptimizer(
        base_optimizer, CommunicationType.allreduce, order="grad",
        num_steps_per_communication=num_steps_per_communication,
    )


def DistributedAllreduceOptimizer(base_optimizer,
                                  num_steps_per_communication=1):
    """CTA with global weight averaging (reference :1301)."""
    return _GossipOptimizer(
        base_optimizer, CommunicationType.allreduce, order="cta",
        num_steps_per_communication=num_steps_per_communication,
    )


def DistributedNeighborAllreduceOptimizer(base_optimizer,
                                          num_steps_per_communication=1):
    """CTA with neighbor weight gossip — the flagship decentralized
    optimizer (reference :1326; algebra comment :311-318)."""
    return _GossipOptimizer(
        base_optimizer, CommunicationType.neighbor_allreduce, order="cta",
        num_steps_per_communication=num_steps_per_communication,
    )


def DistributedHierarchicalNeighborAllreduceOptimizer(
    base_optimizer, num_steps_per_communication=1
):
    """CTA with intra-machine average + machine-level gossip
    (reference :1352)."""
    return _GossipOptimizer(
        base_optimizer,
        CommunicationType.hierarchical_neighbor_allreduce,
        order="cta",
        num_steps_per_communication=num_steps_per_communication,
    )


def DistributedAdaptThenCombineOptimizer(
    base_optimizer,
    communication_type: CommunicationType = CommunicationType.neighbor_allreduce,
    num_steps_per_communication=1,
):
    """ATC: local optax step first, then gossip the updated weights
    (reference :485-842, factory :1426 — its hand-written inner sgd/adam/
    rmsprop/adagrad/adadelta steps are any optax transformation here)."""
    return _GossipOptimizer(
        base_optimizer, communication_type, order="atc",
        num_steps_per_communication=num_steps_per_communication,
    )


def DistributedAdaptWithCombineOptimizer(
    base_optimizer,
    communication_type: CommunicationType = CommunicationType.neighbor_allreduce,
    num_steps_per_communication=1,
):
    """CTA with selectable communication (reference :1497)."""
    return _GossipOptimizer(
        base_optimizer, communication_type, order="cta",
        num_steps_per_communication=num_steps_per_communication,
    )


# -- window-based (asynchronous-algorithm) optimizers ------------------------


class _WindowOptimizer:
    """Shared engine for the win_put / pull-get / push-sum families.

    All pytree leaves are packed into ONE flat combo-vector window (shape
    ``[size, D]``), and the whole step — inner optax update, window
    exchange, combine — is ONE jitted shard_map program regardless of leaf
    count. This is the TPU answer to the reference's fusion buffer
    (``tensor_queue.h:75-124``): where the reference memcpys many small
    tensors into one MPI message, the packed lane makes the many-leaf
    window traffic a single ppermute payload, and O(1) host dispatches per
    step. Execution is step-synchronous (the buffered redesign, see
    :mod:`bluefog_tpu.windows`), preserving the reference algorithms'
    update maps (optimizers.py:844-1177) though not their wall-clock
    asynchrony (push-sum differs in iterate bookkeeping: see
    :func:`DistributedPushSumOptimizer`).
    """

    def __init__(self, base_optimizer, mode: str, window_prefix=None,
                 num_steps_per_communication: int = 1):
        self._uid = next(_opt_uid)  # compiled-step cache key component
        self._tx_version = 0
        self._tx = base_optimizer
        self.mode = mode  # 'put' | 'get' | 'push_sum'
        self.self_weight = None
        self.dst_weights = None
        self.src_weights = None
        self.force_barrier = False  # parity knob; barrier is implicit
        # Exchange every K-th step() call; intermediate calls update the
        # window value locally (reference optimizers.py:846,865-866).
        self.num_steps_per_communication = num_steps_per_communication
        self._step_count = 0
        if window_prefix is None:
            window_prefix = f"_wopt{self._uid}"
        self.prefix = window_prefix
        self._name = None  # the single combo window
        self._treedef = None
        self._leaf_shapes = None
        self._leaf_dtypes = None
        self._offsets = None
        self._pack_dtype = None
        self._enabled_p = False
        self._default_dst = None
        self._default_sw = None
        self._default_topo_v = None

    @property
    def tx(self):
        """Inner optax transformation; reassignment retraces the compiled
        step (see :class:`_GossipOptimizer`.tx)."""
        return self._tx

    @tx.setter
    def tx(self, value):
        if value is not self._tx:
            self._tx = value
            self._tx_version += 1

    # -- pack / unpack --------------------------------------------------------

    def _pack(self, leaves, size):
        return jnp.concatenate(
            [
                jnp.reshape(l, (size, -1)).astype(self._pack_dtype)
                for l in leaves
            ],
            axis=1,
        )

    def _unpack_block(self, flat):
        """[D] combo vector -> list of per-worker leaf blocks (traced)."""
        out = []
        for (start, end), shape, dtype in zip(
            self._offsets, self._leaf_shapes, self._leaf_dtypes
        ):
            out.append(flat[start:end].reshape(shape).astype(dtype))
        return out

    def init(self, params):
        """Create the combo-vector parameter window and inner state."""
        ctx = ctx_mod.get_context()
        leaves, treedef = jax.tree_util.tree_flatten(params)
        for i, l in enumerate(leaves):
            if l.ndim < 1 or l.shape[0] != ctx.size:
                raise ValueError(
                    f"window-optimizer parameter leaf {i} must be "
                    f"worker-stacked [size={ctx.size}, ...]; got shape "
                    f"{tuple(l.shape)}"
                )
            if not jnp.issubdtype(l.dtype, jnp.inexact):
                raise TypeError(
                    f"window-optimizer parameter leaf {i} has dtype "
                    f"{l.dtype}: all leaves share ONE packed float combo "
                    "window, and integer leaves would round-trip through "
                    "float on every step (silent truncation). Keep integer "
                    "state out of the optimized parameter tree."
                )
        self._treedef = treedef
        self._leaf_shapes = [tuple(l.shape[1:]) for l in leaves]
        self._leaf_dtypes = [l.dtype for l in leaves]
        self._pack_dtype = jnp.result_type(*leaves)
        sizes = [int(np.prod(s)) if s else 1 for s in self._leaf_shapes]
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        self._offsets = [
            (int(a), int(b)) for a, b in zip(bounds[:-1], bounds[1:])
        ]
        self._name = f"{self.prefix}.combo"
        packed = self._pack(leaves, ctx.size)
        created = win_mod.win_create(
            packed, self._name, zero_init=self.mode == "push_sum"
        )
        assert created, f"window {self._name} already exists"
        if self.mode == "push_sum":
            # refcounted: freeing one push-sum optimizer must not disable
            # the p lane under another live one; the hold is tagged with
            # the context generation so free() after shutdown/re-init
            # cannot touch a newer context's count
            self._p_ctx_uid = win_mod._acquire_associated_p()
            self._enabled_p = True
        gopt = _GossipOptimizer(
            self.tx, CommunicationType.empty, order="atc"
        )
        return gopt.init(params)

    def free(self):
        if self._name is not None:
            win_mod.win_free(self._name)
        self._name = None
        if self._enabled_p:
            win_mod._release_associated_p(self._p_ctx_uid)
            self._enabled_p = False

    def params(self):
        """Current parameter estimate held by the window."""
        ctx = ctx_mod.get_context()
        value = win_mod.win_read(self._name)
        if self.mode == "push_sum":
            p = win_mod.win_associated_p(self._name)
            value = value / jnp.asarray(p)[:, None].astype(value.dtype)
        leaves = [
            value[:, start:end]
            .reshape((ctx.size,) + shape)
            .astype(dtype)
            for (start, end), shape, dtype in zip(
                self._offsets, self._leaf_shapes, self._leaf_dtypes
            )
        ]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    # -- per-mode exchange/combine configuration ------------------------------

    def _exchange_config(self, ctx, win):
        """Resolve (mode, w_edges, self_vec) for this step."""
        outs = ctx.out_neighbor_ranks()
        size = ctx.size
        if self.mode == "push_sum":
            # x and the p lane share weights: column-stochastic split over
            # self + out-neighbors (reference optimizers.py:1026-1177).
            # Defaults are cached per topology version: rebuilding dicts
            # per step is host noise.
            if self._default_topo_v != ctx.topo_version:
                self._default_dst = None
                self._default_sw = None
                self._default_topo_v = ctx.topo_version
            if self.dst_weights is not None:
                dst = self.dst_weights
            else:
                if self._default_dst is None:
                    self._default_dst = [
                        {d: 1.0 / (len(outs[r]) + 1) for d in outs[r]}
                        for r in range(size)
                    ]
                dst = self._default_dst
            sw = self.self_weight
            if sw is None:
                if self._default_sw is None:
                    self._default_sw = [
                        1.0 / (len(outs[r]) + 1) for r in range(size)
                    ]
                sw = self._default_sw
            w, participating = win_mod._per_rank_edges(
                ctx, dst, win.out_neighbors, "dst_weights"
            )
            self_vec = win_mod._self_weight_vec(ctx, sw, participating)
            return "acc", w, self_vec
        if self.mode == "put":
            w, participating = win_mod._per_rank_edges(
                ctx, self.dst_weights, win.out_neighbors, "dst_weights"
            )
            self_vec = win_mod._self_weight_vec(
                ctx, self.self_weight, participating
            )
            return "put", w, self_vec
        # 'get': receiver-keyed spec, transposed to sender-keyed edges;
        # value is never self-rescaled by a get (see win_get_nonblocking).
        w_recv, participating = win_mod._per_rank_edges(
            ctx, self.src_weights, win.in_neighbors, "src_weights"
        )
        self_vec = win_mod._self_weight_vec(
            ctx, None, np.zeros_like(participating)
        )
        return "get", w_recv.T, self_vec

    def _update_config(self, ctx, win):
        """Combine weights after the exchange: push-sum collects (sum +
        reset), put/get use the window-update default (topology weights or
        uniform), matching the unfused op sequence."""
        if self.mode == "push_sum":
            ones = [{s: 1.0 for s in srcs} for srcs in win.in_neighbors]
            self_vec, w_recv, participating = win_mod._update_weights(
                ctx, win, 1.0, ones
            )
            return self_vec, w_recv, participating, True
        self_vec, w_recv, participating = win_mod._update_weights(
            ctx, win, None, None
        )
        return self_vec, w_recv, participating, False

    def _local_step(self, ctx, win, axis, opt_state, grads):
        """A between-communication call under num_steps_per_communication:
        the inner update adapts the raw window value; no exchange, no
        combine, buffers/versions/p untouched (reference
        _DistributedWinOptimizer's delay gate, optimizers.py:866,1000)."""
        key = (
            "wopt_local_step", self._uid, self._tx_version,
        ) + _aval_key((opt_state, grads))
        fn = ctx.op_cache.get(key)
        if fn is None:
            push_sum = self.mode == "push_sum"
            tx = self._tx

            def body(value, p, s_b, g_b):
                v, pv = value[0], p[0]
                s = _tree_block(s_b)
                g = _tree_block(g_b)
                cur = jax.tree_util.tree_unflatten(
                    self._treedef, self._unpack_block(v)
                )
                updates, s = tx.update(g, s, cur)
                cur = optax.apply_updates(cur, updates)
                xb = jnp.concatenate(
                    [
                        jnp.reshape(l, (-1,)).astype(self._pack_dtype)
                        for l in jax.tree_util.tree_leaves(cur)
                    ]
                )
                est = xb / pv.astype(xb.dtype) if push_sum else xb
                out = jax.tree_util.tree_unflatten(
                    self._treedef, self._unpack_block(est)
                )
                return (
                    jnp.expand_dims(xb, 0),
                    _tree_restack(out), _tree_restack(s),
                )

            spec = P(axis)
            fn = jax.jit(
                jax.shard_map(
                    body, mesh=ctx.mesh,
                    in_specs=(spec, spec, spec, spec),
                    out_specs=(spec, spec, spec),
                )
            )
            ctx.op_cache[key] = fn
        win.value, params_out, opt_state = _timed_dispatch(
            "window_optimizer_step_local", fn,
            win.value, win.p, opt_state, grads,
        )
        # a local adapt ages the neighbor buffers by one local step
        win_mod._note_local_step(win)
        return params_out, opt_state

    # -- the fused step -------------------------------------------------------

    def step(self, opt_state, grads):
        """One window-optimizer step from gradients evaluated at
        ``self.params()``; returns (new_params_estimate, opt_state).

        ONE compiled program: unpack -> optax update -> pack -> window
        exchange (ppermute rounds) -> combine -> repack params estimate.
        """
        assert self._name is not None, "call init(params) first"
        ctx = ctx_mod.get_context()
        win = win_mod._get_win(ctx, self._name)
        axis = ctx_mod.WORKER_AXIS
        update_p = win_mod._p_enabled()
        k = int(self.num_steps_per_communication)
        if k < 1:
            raise ValueError(
                "num_steps_per_communication must be a positive int, got "
                f"{self.num_steps_per_communication!r}"
            )
        comm_now = self._step_count % k == k - 1
        self._step_count += 1
        if not comm_now:  # between exchanges: pure local adapt
            return self._local_step(ctx, win, axis, opt_state, grads)

        # Weight *content* never enters the cache key: the compiled program
        # is keyed on the communication structure and takes the resolved
        # weight vectors as replicated operands, so per-step varying
        # weights (randomized gossip, time-varying push-sum) and in-place
        # mutation of the weight knobs are both safe and compile-free.
        # The price is O(size^2) numpy work per step — deliberately paid:
        # an identity-keyed fast path would reintroduce the stale-mutation
        # hazard this design removes. Measured (pinned by
        # tests/test_windows.py::test_host_weight_resolution_cost):
        # ~0.6 ms/step at 256 workers, ~3.5 ms at 1024, default specs.
        ex_mode, w_edges, ex_self = self._exchange_config(ctx, win)
        perms, slot_table = win_mod._lowered_exchange(ctx, win, w_edges)
        up_self, up_w, up_part, reset = self._update_config(ctx, win)
        slot_w = win_mod._slot_weights(win, up_w, ctx.size)
        wire = win_mod.window_wire()

        key = (
            "wopt_fused_step", self._uid, self._tx_version, ex_mode, perms,
            tuple(map(tuple, slot_table)), reset, update_p, wire,
        ) + _aval_key((opt_state, grads))
        fn = ctx.op_cache.get(key)
        if fn is None:
            slots_const = np.asarray(slot_table, np.int32)
            push_sum = self.mode == "push_sum"
            tx = self._tx
            # locals, not the _Window: a closure over `win` would pin its
            # device arrays in op_cache past opt.free()
            max_deg = win.max_deg
            win_shape = win.shape

            def body(value, buffers, versions, p, p_buffers, s_b, g_b, wops):
                (
                    ex_recv_w, ex_self_w, ex_sent_w,
                    up_self_w, up_slot_w, up_part_arr,
                ) = wops
                v, bufs, vers = value[0], buffers[0], versions[0]
                pv, pbufs = p[0], p_buffers[0]
                s = _tree_block(s_b)
                g = _tree_block(g_b)
                # inner update on the window's current (raw) iterate
                cur = jax.tree_util.tree_unflatten(
                    self._treedef, self._unpack_block(v)
                )
                updates, s = tx.update(g, s, cur)
                cur = optax.apply_updates(cur, updates)
                xb = jnp.concatenate(
                    [
                        jnp.reshape(l, (-1,)).astype(self._pack_dtype)
                        for l in jax.tree_util.tree_leaves(cur)
                    ]
                )
                # adopt the adapted x, then exchange + combine
                v, bufs, vers, pv, pbufs = win_mod._exchange_core(
                    axis, ex_mode, perms, slots_const, update_p,
                    max_deg, win_shape,
                    xb, bufs, vers, pv, pbufs, xb, ex_recv_w, ex_self_w,
                    wire=wire, sent_w=ex_sent_w,
                )
                v, bufs, vers, pv, pbufs = win_mod._update_core(
                    axis, reset, update_p, max_deg,
                    v, bufs, vers, pv, pbufs,
                    up_self_w, up_slot_w, up_part_arr,
                )
                est = v / pv.astype(v.dtype) if push_sum else v
                out_leaves = self._unpack_block(est)
                params_out = jax.tree_util.tree_unflatten(
                    self._treedef, out_leaves
                )
                expand = lambda t: jnp.expand_dims(t, 0)
                return (
                    expand(v), expand(bufs), expand(vers),
                    expand(pv), expand(pbufs),
                    _tree_restack(params_out), _tree_restack(s),
                )

            spec = P(axis)
            fn = jax.jit(
                jax.shard_map(
                    body, mesh=ctx.mesh,
                    in_specs=(spec,) * 7 + (P(),), out_specs=(spec,) * 7,
                )
            )
            ctx.op_cache[key] = fn
        wops = (
            jnp.asarray(win_mod._round_weights(perms, w_edges)),
            jnp.asarray(np.asarray(ex_self, np.float64)),
            jnp.asarray(np.asarray(w_edges.sum(axis=1), np.float64)),
            jnp.asarray(np.asarray(up_self, np.float64)),
            jnp.asarray(np.asarray(slot_w, np.float64)),
            jnp.asarray(up_part, bool),
        )
        (
            win.value, win.buffers, win.versions, win.p, win.p_buffers,
            params_out, opt_state,
        ) = _timed_dispatch(
            "window_optimizer_step", fn,
            win.value, win.buffers, win.versions, win.p, win.p_buffers,
            opt_state, grads, wops,
        )
        # age lane: ONE dispatched program = one local step (exchange +
        # combine fused), so the update note applies collect semantics
        # without a second clock tick
        win_mod._note_exchange_age(win, slot_table, ex_mode)
        win_mod._note_update_age(win, up_part, reset, tick=False)
        staleness_mod.observe_window(
            ctx, win, step=self._step_count - 1
        )
        return params_out, opt_state


def DistributedWinPutOptimizer(base_optimizer, window_prefix=None,
                               num_steps_per_communication=1):
    """Diffusion by pushing updated weights into neighbor buffers
    (reference :1271, engine :844-1023)."""
    return _WindowOptimizer(
        base_optimizer, mode="put", window_prefix=window_prefix,
        num_steps_per_communication=num_steps_per_communication,
    )


def DistributedPullGetOptimizer(base_optimizer, window_prefix=None,
                                num_steps_per_communication=1):
    """Diffusion by pulling neighbors' current weights (reference :1225)."""
    return _WindowOptimizer(
        base_optimizer, mode="get", window_prefix=window_prefix,
        num_steps_per_communication=num_steps_per_communication,
    )


def DistributedPushSumOptimizer(base_optimizer, window_prefix=None,
                                num_steps_per_communication=1):
    """Push-sum (directed-graph) asynchronous SGD: sender-stochastic
    win_accumulate of (x, p) with the x/p correction (reference :1180,
    engine :1026-1177).

    Iterate bookkeeping departs deliberately from the reference: this is
    the textbook accumulated-p recursion (push raw x, never reset p),
    where the reference pushes the corrected iterate and resets its
    ps-weight to 1 every round. On weight-balanced digraphs (ring, Exp2 —
    every uniform-weight regular graph) the two recursions are provably
    identical step for step; on non-balanced digraphs they diverge at
    step 2, and the accumulated-p form is the one that preserves
    push-sum's exact-average guarantee. The committed numpy oracle for
    both recursions, the sequence-equality proof, and the divergence pin
    live in ``tests/test_pushsum_oracle.py``."""
    return _WindowOptimizer(
        base_optimizer, mode="push_sum", window_prefix=window_prefix,
        num_steps_per_communication=num_steps_per_communication,
    )
