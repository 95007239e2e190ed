# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Scaling-efficiency instrumentation: comm accounting + weak-scaling timing.

The reference's headline scaling claim is >95 % efficiency at 128 GPUs for
``neighbor_allreduce`` vs ~66 % for ring-allreduce (reference
``docs/performance.rst:26-53``, ``README.rst:26-34``), backed analytically by
the per-iteration cost table (``README.rst:51-60``): a dynamic one-peer
topology sends ONE model-sized message per step regardless of world size,
while ring allreduce pays ``2(N-1)`` latency units and ``2(N-1)/N`` model
transmissions. The reference proves linear speedup empirically with
``scripts/pytorch_opt_linear_speedup_test.py``.

The TPU-native analogue has two parts:

1. **Static comm accounting** (:func:`hlo_collective_stats`,
   :func:`gossip_comm_stats`): the whole step is ONE compiled XLA program, so
   per-step communication is *statically inspectable* — count
   ``collective-permute`` / ``all-reduce`` instructions and their payload
   bytes straight from the optimized HLO. No NCCL trace needed: the compiler
   IS the negotiation, and what it emitted is what runs. This yields a
   machine-checkable form of the README cost table (see
   ``tests/test_scaling.py``).

2. **Weak-scaling timing** (:func:`weak_scaling_times`): per-step wall time
   of the same jitted train step over meshes of 1..N devices with fixed
   per-worker batch — efficiency(N) = t(1)/t(N). On the CI virtual CPU mesh
   the numbers validate the harness, not the hardware; on a real TPU slice
   the same code produces the ICI scaling curve.
"""

import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu.collective import inner
from bluefog_tpu.collective.plan import CommPlan, SchedulePlan

# Alpha-beta wire-model constants shared with the comm-plan compiler's
# cost model (bluefog_tpu.collective.compiler): per-round fixed latency
# plus payload/bandwidth over an ICI link — the class defaults that the
# compiler's one-shot measured probe (compiler.calibrate) replaces at
# runtime; pipelined_cost_s / calibration are re-exported with them so
# analytic accounting and the chunk chooser can never disagree.
from bluefog_tpu.collective.compiler import (  # noqa: F401  (re-export)
    ROUND_ALPHA_S,
    ICI_LINK_BYTES_PER_S,
    plan_cost_s,
    pipelined_cost_s,
    calibration,
)

__all__ = [
    "hlo_collective_stats",
    "gossip_comm_stats",
    "plan_comm_summary",
    "wire_payload_bytes",
    "wire_bytes_per_step",
    "quantized_temporaries_bytes",
    "optimizer_state_bytes",
    "LINEAGE_TAG_BYTES",
    "ring_allreduce_cost",
    "reduce_scatter_bytes",
    "ring_reduce_scatter_cost",
    "one_peer_gossip_cost",
    "weak_scaling_times",
    "ROUND_ALPHA_S",
    "ICI_LINK_BYTES_PER_S",
    "plan_cost_s",
    "pipelined_cost_s",
    "calibration",
]

# Per-block scale sidecar of each quantized tier, in bytes per
# 512-element quantization block (inner._QUANT_CHUNK): int8/int8_ef ship
# one f32 scale per block, int4/int4_ef one bf16 scale (bf16 keeps f32's
# exponent range so the zero-guard survives narrowing, and the 2-byte
# sidecar is what preserves the exact 2x reduction vs int8).
_SCALE_BYTES_PER_BLOCK = {
    "int8": 4, "int8_ef": 4, "int4": 2, "int4_ef": 2,
}

# The staleness observatory's lineage tag: one int32 per
# staleness.LINEAGE_FIELDS entry (birth_step, topo_version, membership
# epoch), shipped once per edge per round on sampled steps. The single
# definition lives with the fields in bluefog_tpu.staleness (stdlib +
# numpy only, no import cycle) and is re-exported here — the
# accounting home — so the observatory's wire-byte counter, the
# evidence artifacts, and plan_comm_summary can never disagree with
# the lane about what the provenance sidecar weighs.
from bluefog_tpu.staleness import LINEAGE_TAG_BYTES  # noqa: E402


def wire_payload_bytes(n_elems: int, itemsize: int,
                       wire: Optional[str] = None,
                       lineage: bool = False) -> int:
    """Bytes ONE round of one wire tier ships for an ``n_elems`` payload,
    scale sidecar included — the single accounting the chunk chooser,
    the metrics counters, and ``plan_comm_summary`` all price from (a
    free scale sidecar here would let the Pareto chooser and the
    evidence artifacts disagree about what is on the wire).

    The block-scaled tiers ship whole 512-element blocks (the quantized
    payload is padded to the scale grid before the ppermute), so their
    byte count rounds n_elems UP to the block: int8 = 512 B payload +
    4 B f32 scale per block; int4 = 256 B packed nibbles + 2 B bf16
    scale per block — exactly half of int8 at every payload size. bf16
    halves the raw bytes; fp32/unquantized ships ``itemsize`` per
    element. ``lineage=True`` adds the staleness observatory's
    :data:`LINEAGE_TAG_BYTES` provenance sidecar (one tag per edge per
    round, shipped on sampled steps only — callers price the sampled
    dispatch, not every step).
    """
    from bluefog_tpu.collective.inner import _QUANT_CHUNK

    extra = LINEAGE_TAG_BYTES if lineage else 0
    if wire in ("int8", "int8_ef", "int4", "int4_ef"):
        blocks = -(-int(n_elems) // _QUANT_CHUNK) if n_elems else 0
        per_block = (
            _QUANT_CHUNK if wire in ("int8", "int8_ef")
            else _QUANT_CHUNK // 2
        )
        return blocks * (per_block + _SCALE_BYTES_PER_BLOCK[wire]) + extra
    if wire == "bf16":
        return 2 * int(n_elems) + extra
    return int(itemsize) * int(n_elems) + extra


def wire_bytes_per_step(n_elems_by_itemsize, n_rounds: int,
                        wire: Optional[str] = None,
                        lineage: bool = False) -> int:
    """Per-worker wire bytes one gossip step puts on the interconnect.

    ``n_elems_by_itemsize`` maps payload dtype itemsize -> element count
    (the per-dtype-group packing of the optimizer layer); quantized
    wires replace the payload dtype per :func:`wire_payload_bytes`.
    Every round re-ships the payload, so the total scales with the
    plan's round count — the per-edge traffic accounting TopoOpt-style
    co-optimization presumes. ``lineage=True`` prices a staleness
    lineage tag onto ONE dtype group per round (the tag is per edge,
    not per payload group)."""
    per_round = sum(
        wire_payload_bytes(n, itemsize, wire)
        for itemsize, n in n_elems_by_itemsize.items()
    ) + (LINEAGE_TAG_BYTES if lineage else 0)
    return per_round * n_rounds

def quantized_temporaries_bytes(n_elems: int,
                                wire: Optional[str] = None,
                                fused: bool = False) -> int:
    """Analytic bytes of the full-width temporaries the COMPOSITE
    quantized wire path materializes per round today — the
    quantize → pack → ppermute → unpack → dequant chain runs as
    separate XLA ops, so beyond the wire payload itself it stages (a)
    the int8 quantize output before packing (plus the packed nibble
    copy for the int4 tiers) and (b) the dequantized **full-width f32
    reconstruction** of every received payload. That f32 temporary is
    exactly what a fused Pallas kernel (EQuARX, arxiv 2506.17615)
    would never materialize, which makes this function the committed
    *before*-baseline the ROADMAP kernel-fusion item must beat
    (tests/test_wire_kernels.py::test_fused_scratch_below_fp32_row
    pairs it with the measured XLA ``temp_size_in_bytes`` of the
    compiled combine).

    Block-scaled tiers stage whole 512-element blocks (the payload is
    padded to the scale grid before the ppermute). fp32 ships verbatim
    — no conversion temporaries — and returns 0.

    ``fused=True`` prices the kernel-fused wire instead
    (``BLUEFOG_WIRE_KERNELS``, :mod:`bluefog_tpu.collective.kernels`):
    the encode kernel writes the packed wire buffer + scale sidecar
    directly and the decode+accumulate kernel folds each received
    payload into the accumulator in one pass, so the only temporaries
    are the local packed buffer + sidecar and one in-flight received
    copy of the same — **no full-width reconstruction ever exists**.
    bf16/fp32 have no fused path and price identically.
    """
    from bluefog_tpu.collective.inner import _QUANT_CHUNK

    if not n_elems:
        return 0
    if wire in ("int8", "int8_ef", "int4", "int4_ef"):
        blocks = -(-int(n_elems) // _QUANT_CHUNK)
        padded = blocks * _QUANT_CHUNK
        if fused:
            # local packed buffer + scale sidecar, times two: the
            # encode output and the in-flight received copy the
            # decode+accumulate kernel reads. No full-width staging.
            if wire in ("int4", "int4_ef"):
                packed = padded // 2     # nibble-packed lanes
                sidecar = blocks * 2     # bf16 scale per block
            else:
                packed = padded          # int8 lanes
                sidecar = blocks * 4     # f32 scale per block
            return 2 * (packed + sidecar)
        full_width = 4 * padded      # f32 dequant of the received payload
        staging = padded             # int8 quantize output pre-send
        if wire in ("int4", "int4_ef"):
            staging += padded // 2   # the packed-nibble copy
        return full_width + staging
    if wire == "bf16":
        # the f32 reconstruction of the received bf16 payload
        return 4 * int(n_elems)
    return 0


def _leaf_bytes(leaf) -> int:
    """Bytes of one array-like leaf (works on jax/numpy arrays and
    ShapeDtypeStructs alike)."""
    return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize


def _named_dtype(name: str):
    """dtype instance for a ``str(jnp.result_type(...))`` name —
    extension dtypes (bfloat16) are not in numpy's string registry."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def optimizer_state_bytes(
    params=None,
    opt=None,
    *,
    shard: bool = False,
    master: Optional[bool] = None,
    live: Optional[Sequence[int]] = None,
    state=None,
    world: Optional[int] = None,
) -> int:
    """Canonical PER-RANK optimizer-state byte accounting — the single
    number the shard tests, the health ``/fleet`` report's shard block,
    and ``tools/shard_plan.py`` all quote (docs/sharding.md).

    Two modes:

    - **measured**: pass ``state=`` (a live worker-stacked state tree)
      — returns the real allocated bytes divided by the worker count
      (``world=``, default inferred from the leading axis). This is
      what the 1/N claim is held to
      (tests/test_sharding.py::test_state_bytes_measured_equals_analytic):
      actual array bytes, not a model.
    - **analytic**: pass ``params`` (worker-stacked) and ``opt`` (a
      distributed optimizer or a raw optax transformation) — sizes the
      state via ``jax.eval_shape`` of ``tx.init`` without allocating
      anything. ``shard=True`` prices the bucket-aligned 1/N shard of
      :mod:`bluefog_tpu.sharding` instead of the replicated tree
      (``master=`` adds the fp32 master slices; defaults to
      ``BLUEFOG_SHARD_MASTER``; ``live=`` restricts the owner set,
      default all ranks).
    """
    from bluefog_tpu import sharding

    if state is not None:
        leaves = jax.tree_util.tree_leaves(state)
        if not leaves:
            return 0
        n = int(world) if world else int(leaves[0].shape[0])
        return sum(_leaf_bytes(l) for l in leaves) // max(n, 1)
    if params is None or opt is None:
        raise ValueError(
            "optimizer_state_bytes needs either state= (measured) or "
            "params + opt (analytic)"
        )
    tx = getattr(opt, "tx", opt)
    leaves = jax.tree_util.tree_leaves(params)
    size = int(leaves[0].shape[0])
    if not shard:
        blocks = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(tuple(l.shape[1:]), l.dtype),
            params,
        )
        st = jax.eval_shape(tx.init, blocks)
        return sum(_leaf_bytes(l) for l in jax.tree_util.tree_leaves(st))
    if master is None:
        master = sharding.master_enabled()
    groups = []
    by_dtype: Dict[str, int] = {}
    for l in leaves:
        dt = str(jnp.result_type(l))
        by_dtype[dt] = by_dtype.get(dt, 0) + int(np.prod(l.shape[1:]))
    groups = sorted(by_dtype.items())
    layout = sharding.build_layout(
        groups, live if live is not None else range(size), size,
        master=master,
    )
    slices = tuple(
        jax.ShapeDtypeStruct((g.slot,), _named_dtype(g.dtype))
        for g in layout.groups
    )
    st = jax.eval_shape(tx.init, slices)
    total = sum(_leaf_bytes(l) for l in jax.tree_util.tree_leaves(st))
    if master:
        total += sum(4 * g.slot for g in layout.groups)
    return total


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
}

# `dtype[d0,d1,...]{layout} collective-permute(` — the result shape of the
# instruction is its wire payload (one logical transfer per participating
# device pair). TPU compilation lowers collectives to async
# `-start`/`-done` pairs; the `-start` carries the op and payload, so it is
# counted and the `-done` is not. The shape before the op name may be a
# TUPLE — async starts are `(operands..., results..., contexts...)` and
# variadic (fusion-combined) collectives return one result per leaf — so
# the whole shape string is captured and every `dtype[dims]` element
# parsed, not just the first.
_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\()?\w+\[[\d,]*\][^=\n]*?)\s"
    r"(collective-permute|all-reduce|all-gather|reduce-scatter|"
    r"all-to-all)(-start)?\("
)

_SHAPE_ELEM_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


# Async `-start` ops whose result tuple is `(operands..., results...,
# contexts...)` with operands aliasing results shape-for-shape. all-reduce/
# reduce-scatter/all-to-all starts return results only (no alias leaves).
_ALIASING_STARTS = ("collective-permute", "all-gather")


def _instruction_bytes(shape_str: str, kind: str, is_start: bool) -> int:
    """Payload bytes of one collective given its (possibly tuple) shape.

    Plain shape: that shape IS the payload. Tuple on a variadic collective:
    one result per leaf, so the payload is the sum. Tuple on an aliasing
    async ``-start`` (collective-permute / all-gather): operands alias
    results shape-for-shape, so after dropping the scalar u32/s32 context
    lanes the payload is the second half (counting the whole tuple would
    double it). Unknown dtypes fall back to 4 bytes rather than vanishing
    from the accounting.
    """
    elems = _SHAPE_ELEM_RE.findall(shape_str)
    if not shape_str.lstrip().startswith("("):
        return _shape_bytes(*elems[0]) if elems else 0
    if is_start and kind in _ALIASING_STARTS:
        data = [e for e in elems if e[1]]  # drop scalar context lanes
        if len(data) % 2 == 0 and data:
            data = data[len(data) // 2:]  # results half
        return sum(_shape_bytes(dt, dims) for dt, dims in data)
    return sum(_shape_bytes(dt, dims) for dt, dims in elems)


def hlo_collective_stats(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Count collective instructions and payload bytes in optimized HLO.

    Returns ``{op_kind: {"count": int, "bytes": int}}`` over
    collective-permute / all-reduce / all-gather / reduce-scatter /
    all-to-all. ``bytes`` sums each instruction's result payload — for a
    ppermute that is exactly the per-device wire transfer; for all-reduce it
    is the logical payload (the wire cost depends on the algorithm; see
    :func:`ring_allreduce_cost`).
    """
    stats: Dict[str, Dict[str, int]] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, kind, start = m.group(1), m.group(2), m.group(3)
        entry = stats.setdefault(kind, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += _instruction_bytes(shape_str, kind,
                                             start is not None)
    return stats


def _mesh(n: int) -> Mesh:
    devices = jax.devices()
    assert len(devices) >= n, (
        f"need {n} devices for comm accounting, have {len(devices)}"
    )
    return Mesh(np.array(devices[:n]), ("workers",))


def plan_comm_summary(plan: CommPlan, payload_bytes: int,
                      wire: Optional[str] = None,
                      itemsize: int = 4) -> Dict[str, object]:
    """Per-plan round/byte accounting: the compiler's decomposition
    decision, naive-vs-chosen round counts, the König lower bound, the
    alpha-beta predicted step cost for a given gossip payload, and the
    bandwidth-family record (route, modeled congestion, the chunk count
    the Pareto chooser would pipeline at this payload with its predicted
    cost). ``payload_bytes`` is the UNCOMPRESSED per-bucket payload;
    ``wire`` reprices it per :func:`wire_payload_bytes` (scale sidecar
    included) and reports the per-bucket ``effective_compression_ratio``
    = uncompressed bytes / wire bytes, the ratio behind the >=2x
    int4-vs-int8 wire claim
    (tests/test_compression.py::test_wire_tiers_ship_bytes_in_tier_order)."""
    from bluefog_tpu.collective import compiler as _compiler

    info = plan.compile_info
    rounds = len(plan.rounds)
    naive_rounds = info.offset_rounds if info else rounds
    congestion = (
        info.congestion if info and info.congestion else (1.0,) * rounds
    )
    link_class = getattr(info, "link_class", "ici") if info else "ici"
    n_elems = int(payload_bytes) // max(int(itemsize), 1)
    wire_bytes = wire_payload_bytes(n_elems, itemsize, wire)
    auto_chunks, chunked_cost = _compiler.chunk_option(
        wire_bytes, congestion, n_elems=n_elems, link_class=link_class
    )
    return {
        "rounds": rounds,
        "decomposition": info.method if info else "offset",
        "route": info.route if info else "direct",
        "link_class": link_class,
        "naive_rounds": naive_rounds,
        "lower_bound": info.lower_bound if info else rounds,
        "wire": wire or "exact",
        "wire_bytes_per_round": wire_bytes,
        "effective_compression_ratio": (
            round(payload_bytes / wire_bytes, 4) if wire_bytes else 1.0
        ),
        "max_congestion": max(congestion, default=1.0),
        "lineage_sidecar_bytes_per_round": LINEAGE_TAG_BYTES,
        "predicted_cost_us": plan_cost_s(
            rounds, wire_bytes, link_class=link_class
        ) * 1e6,
        "naive_cost_us": plan_cost_s(
            naive_rounds, wire_bytes, link_class=link_class
        ) * 1e6,
        "auto_chunks": auto_chunks,
        "chunked_cost_us": chunked_cost * 1e6,
    }


def gossip_comm_stats(
    plan: CommPlan,
    payload_elems: int,
    dtype=jnp.float32,
    mode: str = "neighbor_allreduce",
    include_plan: bool = False,
) -> Dict[str, Dict[str, int]]:
    """Compile one combine step over ``plan`` and account its collectives.

    ``mode`` is ``"neighbor_allreduce"`` (the plan's ppermute rounds) or
    ``"allreduce"`` (``lax.psum``, the Horovod-style baseline the reference
    compares against). The compiled program is the *exact* per-iteration
    communication — this is the TPU-native replacement for wire-level
    NCCL/MPI tracing. ``include_plan=True`` adds a ``"plan"`` entry with
    the compiler's per-plan round accounting (:func:`plan_comm_summary`);
    it is opt-in because the other entries are homogeneous
    ``{count, bytes}`` dicts that callers aggregate over.
    """
    n = plan.size
    mesh = _mesh(n)
    x = jnp.zeros((n, payload_elems), dtype)

    if mode == "neighbor_allreduce":
        body = lambda t: inner.neighbor_allreduce(t, plan, "workers")
    elif mode == "allreduce":
        body = lambda t: inner.allreduce(t, "workers", average=True)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=P("workers"), out_specs=P("workers")
        )
    )
    compiled = fn.lower(
        jax.device_put(x, NamedSharding(mesh, P("workers")))
    ).compile()
    stats = hlo_collective_stats(compiled.as_text())
    if include_plan:
        stats["plan"] = plan_comm_summary(
            plan, payload_elems * np.dtype(dtype).itemsize
        )
    return stats


def ring_allreduce_cost(n: int, payload_bytes: int) -> Dict[str, float]:
    """Analytical ring-allreduce per-device cost (the Horovod baseline in
    reference ``README.rst:51-60``): ``2(N-1)`` sequential hops moving
    ``2(N-1)/N`` of the payload."""
    return {
        "latency_hops": 2 * (n - 1),
        "wire_bytes": 2.0 * (n - 1) / n * payload_bytes,
    }


def one_peer_gossip_cost(payload_bytes: int) -> Dict[str, float]:
    """Analytical dynamic one-peer gossip cost: one hop, one payload,
    independent of N (reference ``README.rst:51-60`` row 'Bluefog')."""
    return {"latency_hops": 1, "wire_bytes": float(payload_bytes)}


def reduce_scatter_bytes(groups, n: int,
                         wire: Optional[str] = None) -> int:
    """Per-rank wire bytes of one ZeRO-2 reduce-scatter gradient leg:
    ``N-1`` ring rounds each shipping ONE owned slot per dtype group,
    priced through :func:`wire_payload_bytes` so the quantized tiers
    (scale sidecar included) and the evidence artifacts agree with
    what the metrics counter records. ``groups`` is ``[(slot_elems,
    itemsize)]`` — the shard layout's slot grid. This is the byte model
    ``bluefog.wire_bytes`` routes through when ``BLUEFOG_SHARD_GRADS=1``
    replaces the gradient allreduce (which would ship ``~2 (N-1)/N``
    FULL payloads instead of ``N-1`` slots ≈ one payload)."""
    return sum(
        (max(int(n), 1) - 1) * wire_payload_bytes(slot, itemsize, wire)
        for slot, itemsize in groups
    )


def ring_reduce_scatter_cost(n: int, slot_bytes: int) -> Dict[str, float]:
    """Analytical ring reduce-scatter per-device cost (the ZeRO-2
    gradient leg, arxiv 2004.13336): ``N-1`` sequential hops each
    moving one owned slot — with ``slot = payload/N`` this is
    ``(N-1)/N`` of the payload, HALF of :func:`ring_allreduce_cost`'s
    wire at the same width, and the scatter+gather pair together match
    one allreduce."""
    return {
        "latency_hops": n - 1,
        "wire_bytes": float((n - 1) * slot_bytes),
    }


def weak_scaling_times(
    make_step: Callable[[Mesh], Tuple[Callable, tuple]],
    ns: Sequence[int],
    steps: int = 10,
    warmup: int = 3,
) -> List[Dict[str, float]]:
    """Time one jitted step over meshes of each size in ``ns``.

    ``make_step(mesh)`` returns ``(fn, args)`` where ``fn(*args)`` runs one
    step and returns outputs whose first leaf is safe to read back (the
    readback is the synchronization point of
    :func:`bluefog_tpu.timing.timed_differenced`). Per-worker work must be constant
    across ``ns`` (weak scaling), so ``efficiency = t[0] / t[n]``.
    """
    from bluefog_tpu.timing import timed_differenced

    out = []
    t1 = None
    for n in ns:
        mesh = _mesh(n)
        fn, args = make_step(mesh)
        for _ in range(warmup):
            res = fn(*args)
        dt = timed_differenced(lambda: fn(*args), steps, windows=2)[0]
        if t1 is None:
            t1 = dt
        out.append(
            {"n": n, "ms_per_step": dt * 1e3, "efficiency": t1 / dt}
        )
    return out
