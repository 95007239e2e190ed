# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Fully asynchronous gossip training: ``bf.make_async_train_step``.

The reference's headline robustness axis is its win_put/win_accumulate
push-sum *asynchronous* optimizers (torch/optimizers.py:166-1554): each
rank trains at its own cadence, pushes weighted parameter mass into
neighbor windows, and folds whatever mass has arrived — no rank ever
blocks on a peer, so a 10x-slow straggler costs only its own
throughput, not the fleet's. Synchronous gossip cannot reach that
scenario: one slow rank gates every neighbor's ppermute.

**Execution model.** Under single-controller SPMD there is no
per-process wall clock to decouple, so asynchrony is modeled the same
way the window subsystem models one-sided RMA (:mod:`bluefog_tpu.
windows`): the *algorithmic* contract is preserved while execution
stays step-synchronous. The engine runs on a virtual **tick** clock.
Each tick dispatches ONE compiled program over the whole mesh in which
only the ranks *due* this tick (their cadence divides the tick) take a
local step:

1. evaluate ``loss_fn`` and the inner optax update at the push-sum
   estimate ``z = x / p``, applying the update to the raw window mass
   ``x`` (the accumulated-p recursion of
   :func:`~bluefog_tpu.optimizers.DistributedPushSumOptimizer`);
2. ``win_accumulate`` the updated mass into every out-neighbor's
   buffer slot under column-stochastic weights (self keeps its share)
   — the wire optionally quantized (see *Wire tiers* below), with the
   sender absorbing the shipped quantization residual so **sender mass
   is conserved exactly under every tier** (the
   :func:`bluefog_tpu.windows._exchange_core` column-sum identity);
3. fold (``win_update``-style collect) every pending buffer slot the
   bounded-staleness gate admits, zeroing exactly the folded slots —
   un-folded mass stays pending, never discarded.

Ranks not due this tick pass every lane through bitwise-unchanged:
their edge weights are zero *operands* of the same compiled program,
so a cadence pattern never recompiles. Participation masks, fold
masks, and all weights ride as runtime operands; the program is keyed
only on the communication structure.

**Bounded staleness.** The gate thresholds the host-side window age
lane (:func:`bluefog_tpu.windows.get_win_age`) at
``BLUEFOG_ASYNC_MAX_AGE`` local window steps. When an in-edge's buffer
falls past the bound the rank does not stall; per
``BLUEFOG_ASYNC_STALE_POLICY`` it either

- ``drop`` (default): excludes the stale edge from this fold (the
  pending mass stays buffered for a later fold — push-sum mass
  conservation is never traded for freshness), or
- ``throttle``: skips its own local step this tick, letting the
  laggard catch up (the classic bounded-staleness barrier, minus the
  barrier).

Either way an ``async_staleness`` advisory naming the stale edges (and
thereby the slow rank) files through the PR-7 plumbing: a
``bluefog.doctor.advisory.async_staleness`` counter, the flight side
table, a timeline instant, and the engine's own record list.

**Wire tiers** (``BLUEFOG_ASYNC_WIRE`` or the ``wire=`` argument):
``fp32`` (exact, default), ``bf16``, ``int8``, ``int4``, plus the
aliases ``int8_ef``/``int4_ef`` — on the push-sum accumulate surface
the sender-side residual absorption *is* the error feedback: the
quantization residual of every shipped payload is folded back into
the sender's own mass and re-transmitted on its next push, so the
``_ef`` spellings map to the int8/int4 window wire and inherit the
exact mass-conservation identity (tests/test_pushsum_oracle.py pins
the drift at f32 rounding, not quantization precision).

**Composition with the stack.**

- *Elastic*: the engine registers as a ``mode='push_sum'`` optimizer
  with the active :class:`~bluefog_tpu.elastic.recovery.
  ElasticSession` — every tick runs ``before_dispatch`` (chaos replay,
  repair); a membership change or an edge set the create-time window
  cannot carry triggers a **re-window**: the current estimate
  ``x / p`` is preserved as the new window value with ``p`` reset to 1
  over the live set. The new ``slow`` fault kind
  (:mod:`bluefog_tpu.elastic.faults`) dilates a rank's cadence
  deterministically — the 10x-straggler chaos scenario as a tier-1
  unit test.
- *Staleness*: delivered buffer ages fold into the observatory every
  tick under surface ``"async"`` (:func:`bluefog_tpu.staleness.
  observe_window`), so ``bf.staleness`` reports the async lane's ages
  and the fleet plane aggregates them.
- *Health*: the health report/``/fleet`` surface carries the engine
  summary next to the autotune block, and the age-adjusted mixing
  score (:func:`bluefog_tpu.staleness.age_adjusted_rate`) consumes the
  async lane's measured ages through the observatory.
- *Watchdog*: every tick's dispatch is a registered host blocking
  point (``watchdog.watch("async_fold:<window>")``), so a hung
  neighbor-window wait files SUSPECT liveness verdicts through the
  existing ``add_stall_handler`` -> elastic recovery hook.
- *Autotune*: decision records carry ``async_mode`` so the audit trail
  distinguishes choices made for an asynchronous lane.

**Async off** (``BLUEFOG_ASYNC=0`` or ``enabled=False``):
:func:`make_async_train_step` returns the wrapped optimizer's own
``make_train_step`` callable — the current synchronous path, bitwise
identical by construction (pinned by
tests/test_async.py::test_async_off_is_bitwise_synchronous_path).

Env knobs: ``BLUEFOG_ASYNC`` (default on — the builder is the opt-in),
``BLUEFOG_ASYNC_MAX_AGE`` (default 8 local window steps),
``BLUEFOG_ASYNC_STALE_POLICY`` (``drop``/``throttle``),
``BLUEFOG_ASYNC_WIRE`` (see above). See docs/async.md.
"""

import itertools
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "AsyncGossipEngine",
    "make_async_train_step",
    "async_enabled",
    "async_max_age",
    "async_stale_policy",
    "async_wire",
    "active",
    "on_init",
    "on_shutdown",
]

ENABLE_ENV = "BLUEFOG_ASYNC"
MAX_AGE_ENV = "BLUEFOG_ASYNC_MAX_AGE"
POLICY_ENV = "BLUEFOG_ASYNC_STALE_POLICY"
WIRE_ENV = "BLUEFOG_ASYNC_WIRE"

_POLICIES = ("drop", "throttle")

# advisory re-fire mute per stale edge, in ticks — the staleness
# observatory's cooldown discipline: a persistently stale edge keeps
# its counter raised without flooding the flight ring, while a
# different edge's first breach is never swallowed
BREACH_COOLDOWN = 8


def async_enabled() -> bool:
    """The kill switch (``BLUEFOG_ASYNC``, default on). Calling
    :func:`make_async_train_step` is the opt-in; the env var exists so
    a deployment can force the synchronous path without a code change
    — and so the bitwise async-off pin has a dispatchable form."""
    return os.environ.get(ENABLE_ENV, "1").lower() not in (
        "0", "false", "off", "no",
    )


def async_max_age() -> int:
    """Bounded-staleness threshold in local window steps
    (``BLUEFOG_ASYNC_MAX_AGE``, default 8): an in-neighbor buffer older
    than this trips the gate. Chosen above the delivered ages any
    healthy cadence spread produces but below the 10x-dilation chaos
    scenario, so the gate engages exactly when a genuine straggler
    appears."""
    from bluefog_tpu.logging_util import env_int

    return max(1, env_int(MAX_AGE_ENV, 8))


def async_stale_policy() -> str:
    """``BLUEFOG_ASYNC_STALE_POLICY``: ``drop`` (default — exclude the
    stale edge from the fold, mass stays pending) or ``throttle`` (the
    rank skips its own local step to let the laggard catch up)."""
    p = os.environ.get(POLICY_ENV, "drop").strip().lower()
    if p not in _POLICIES:
        raise ValueError(
            f"{POLICY_ENV} must be one of {_POLICIES}, got {p!r}"
        )
    return p


def async_wire(requested: Optional[str] = None) -> Optional[str]:
    """Resolve the async push wire tier to the underlying window wire:
    ``None``/``fp32`` (exact), ``bf16``, ``int8``, ``int4``; the
    ``int8_ef``/``int4_ef`` aliases map to ``int8``/``int4`` — on the
    push-sum accumulate surface the sender's exact residual absorption
    already recycles the quantization error (the error-feedback role),
    see the module docstring."""
    w = (requested if requested is not None
         else os.environ.get(WIRE_ENV, "")).strip().lower()
    if w in ("", "0", "off", "none", "fp32", "f32", "exact"):
        return None
    if w in ("int8_ef", "int4_ef"):
        return w[:4]
    if w in ("bf16", "int8", "int4"):
        return w
    raise ValueError(
        "async wire must be one of fp32/bf16/int8/int4/int8_ef/int4_ef "
        f"(or unset for exact), got {w!r}"
    )


_engine_uid = itertools.count()


class AsyncGossipEngine:
    """One asynchronous gossip lane over a combo push-sum window.

    Built by :func:`make_async_train_step`; drive it through the
    returned callable. ``mode = 'push_sum'`` is the registration
    contract with the elastic repair engine: a membership repair
    installs its renormalized sender-stochastic weights on
    ``self.dst_weights`` / ``self.self_weight`` exactly as it does for
    :class:`~bluefog_tpu.optimizers._WindowOptimizer`.
    """

    mode = "push_sum"  # elastic _policy_for / _install_topology contract

    def __init__(self, opt, loss_fn, has_aux: bool = False,
                 cadence: Optional[Dict[int, int]] = None,
                 max_age: Optional[int] = None,
                 policy: Optional[str] = None,
                 wire: Optional[str] = None):
        self._uid = next(_engine_uid)
        self.opt = opt
        self.loss_fn = loss_fn
        self.has_aux = bool(has_aux)
        self.cadence = {int(r): int(p) for r, p in (cadence or {}).items()}
        for r, p in self.cadence.items():
            if p < 1:
                raise ValueError(
                    f"cadence period for rank {r} must be >= 1, got {p}"
                )
        if max_age is None:
            self.max_age = async_max_age()
        else:
            self.max_age = int(max_age)
            if self.max_age < 1:
                raise ValueError(
                    f"max_age must be >= 1 local window steps, got "
                    f"{max_age!r}"
                )
        self.policy = policy if policy is not None else async_stale_policy()
        if self.policy not in _POLICIES:
            raise ValueError(
                f"policy must be one of {_POLICIES}, got {self.policy!r}"
            )
        # wire: explicit arg > env > the wrapped optimizer's compression
        if wire is None and not os.environ.get(WIRE_ENV, "").strip():
            wire = getattr(opt, "compression", None)
        self.wire = async_wire(wire)
        self.wire_name = (
            (wire or os.environ.get(WIRE_ENV, "") or "fp32")
            .strip().lower() or "fp32"
        )
        # elastic repair installs renormalized weights here (push_sum
        # policy, recovery._install_topology)
        self.self_weight = None
        self.dst_weights = None
        self._name = f"_async{self._uid}.combo"
        self._win_sig = None          # (aval sig, live_token) at creation
        self._win_slots: Optional[tuple] = None  # create-time in-neighbors
        self._treedef = None
        self._leaf_shapes = None
        self._leaf_dtypes = None
        self._offsets = None
        self._pack_dtype = None
        self._tick = 0
        self._local_steps = 0
        self._throttled = 0
        self._stale_drops = 0
        self._rewindows = 0
        self._default_dst = None
        self._default_sw = None
        self._default_topo_v = None
        self._breach_mutes: Dict[Tuple[int, int], int] = {}
        # bounded like every other side table in the stack (flight
        # ring, autotune decisions): a permanent straggler fires one
        # advisory per cooldown window forever
        import collections as _collections

        self.advisories: Any = _collections.deque(maxlen=256)
        self._advisory_total = 0

    # -- packing --------------------------------------------------------------

    def _prepare_layout(self, ctx, params):
        import jax
        import jax.numpy as jnp

        leaves, treedef = jax.tree_util.tree_flatten(params)
        for i, l in enumerate(leaves):
            if l.ndim < 1 or l.shape[0] != ctx.size:
                raise ValueError(
                    f"async parameter leaf {i} must be worker-stacked "
                    f"[size={ctx.size}, ...]; got shape {tuple(l.shape)}"
                )
            if not jnp.issubdtype(l.dtype, jnp.inexact):
                raise TypeError(
                    f"async parameter leaf {i} has dtype {l.dtype}: the "
                    "push-sum lane packs every leaf into one float combo "
                    "window (integer state would round-trip through float "
                    "each tick)"
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

    def _pack(self, leaves, size):
        import jax.numpy as jnp

        return jnp.concatenate(
            [
                jnp.reshape(l, (size, -1)).astype(self._pack_dtype)
                for l in leaves
            ],
            axis=1,
        )

    def _unpack_block(self, flat):
        """[D] combo vector -> per-worker leaf blocks (traced)."""
        out = []
        for (start, end), shape, dtype in zip(
            self._offsets, self._leaf_shapes, self._leaf_dtypes
        ):
            out.append(flat[start:end].reshape(shape).astype(dtype))
        return out

    # -- window lifecycle -----------------------------------------------------

    def _aval_sig(self, params):
        import jax

        return tuple(
            (tuple(l.shape), str(l.dtype))
            for l in jax.tree_util.tree_leaves(params)
        )

    def _topology_fits_window(self, ctx) -> bool:
        """True when every current-topology in-edge has a create-time
        buffer slot — repairs only prune, so they fit; a rejoin or
        controller migration can add edges back and force a
        re-window."""
        if self._win_slots is None:
            return False
        for r, srcs in enumerate(ctx.in_neighbor_ranks()):
            if not set(srcs) <= set(self._win_slots[r]):
                return False
        return True

    def _ensure_window(self, ctx, params) -> None:
        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu import windows as win_mod

        sig = (self._aval_sig(params), ctx.live_token())
        win = win_mod._windows(ctx).get(self._name)
        if (win is not None and self._win_sig == sig
                and self._topology_fits_window(ctx)):
            return
        import jax

        if win is None or self._win_sig is None or (
            self._win_sig[0] != sig[0]
        ):
            # first creation (or a parameter-shape change): seed the
            # window mass from the given params, p = 1
            self._prepare_layout(ctx, params)
            packed = self._pack(
                jax.tree_util.tree_flatten(params)[0], ctx.size
            )
        else:
            # re-window (membership change / edge superset): the
            # current estimate x/p becomes the new mass with p reset
            # to 1 — consensus state survives the seam, mass
            # accounting restarts over the live set
            packed = win.value / win.p[:, None].astype(win.value.dtype)
            self._rewindows += 1
            metrics_mod.counter("bluefog.async.rewindows").inc()
        win_mod.win_free(self._name)
        created = win_mod.win_create(packed, self._name, zero_init=True)
        assert created, f"window {self._name} already exists"
        self._win_sig = sig
        self._win_slots = win_mod._get_win(ctx, self._name).in_neighbors
        # weight defaults follow the topology the window was cut for
        self._default_topo_v = None

    def free(self) -> None:
        from bluefog_tpu import context as ctx_mod
        from bluefog_tpu import windows as win_mod

        if ctx_mod.is_initialized():
            win_mod.win_free(self._name)
        self._win_sig = None
        self._win_slots = None

    def params(self):
        """The current push-sum estimate ``x / p`` as the parameter
        pytree."""
        import jax

        from bluefog_tpu import context as ctx_mod
        from bluefog_tpu import windows as win_mod

        ctx = ctx_mod.get_context()
        win = win_mod._get_win(ctx, self._name)
        est = win.value / win.p[:, None].astype(win.value.dtype)
        leaves = [
            est[:, start:end].reshape((ctx.size,) + shape).astype(dtype)
            for (start, end), shape, dtype in zip(
                self._offsets, self._leaf_shapes, self._leaf_dtypes
            )
        ]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    # -- cadence --------------------------------------------------------------

    def _periods(self, ctx, session) -> np.ndarray:
        """Per-rank local-step period on the tick clock: the explicit
        cadence times any active ``slow`` fault's compute dilation
        (deterministic chaos, :meth:`~bluefog_tpu.elastic.recovery.
        ElasticSession.simulated_compute_dilation`)."""
        periods = np.ones(ctx.size, np.int64)
        for r, p in self.cadence.items():
            if 0 <= r < ctx.size:
                periods[r] = p
        if session is not None:
            dil = session.simulated_compute_dilation()
            for r, f in dil.items():
                if 0 <= r < ctx.size:
                    periods[r] *= max(1, int(np.ceil(f)))
        return periods

    # -- the staleness gate ---------------------------------------------------

    def _slot_ages(self, win) -> np.ndarray:
        """[size, max_deg] local-step ages of each buffer slot, -1 where
        no slot exists (the host age lane, :mod:`bluefog_tpu.windows`)."""
        size = len(win.in_neighbors)
        max_deg = max(win.max_deg, 1)
        ages = np.full((size, max_deg), -1, np.int64)
        clock = int(win.clock)
        for r, srcs in enumerate(win.in_neighbors):
            for k in range(len(srcs)):
                ages[r, k] = clock - int(win.slot_written[r, k])
        return ages

    def _gate(self, ctx, win, participating, ages):
        """Apply the bounded-staleness policy. Returns
        ``(participating, fold_mask, breached_edges)`` — ``fold_mask``
        [size, max_deg] bool; breached edges are (src, dst) pairs past
        the bound this tick (pre-cooldown)."""
        size = ctx.size
        max_deg = max(win.max_deg, 1)
        slot_exists = np.zeros((size, max_deg), bool)
        stale = np.zeros((size, max_deg), bool)
        for r, srcs in enumerate(win.in_neighbors):
            for k, s in enumerate(srcs):
                slot_exists[r, k] = True
                if ages[r, k] > self.max_age:
                    stale[r, k] = True
        participating = participating.copy()
        # only edges the gate ACTS on this tick are advisory-worthy: a
        # stale slot whose receiver is not due folds nothing anyway, so
        # reporting action='dropped'/'throttled' for it would make the
        # advisory stream disagree with the drop/throttle counters
        breached: List[Tuple[int, int]] = [
            (int(s), int(r))
            for r, srcs in enumerate(win.in_neighbors)
            for k, s in enumerate(srcs)
            if stale[r, k] and participating[r]
        ]
        if self.policy == "throttle":
            # a rank whose in-edges fell behind sits this tick out
            throttle_rows = stale.any(axis=1) & participating
            self._throttled += int(throttle_rows.sum())
            if throttle_rows.any():
                from bluefog_tpu import metrics as metrics_mod

                metrics_mod.counter("bluefog.async.throttled").inc(
                    int(throttle_rows.sum())
                )
            participating &= ~throttle_rows
            fold_mask = slot_exists & participating[:, None]
        else:  # drop: fold everything fresh, keep stale mass pending
            fold_mask = slot_exists & participating[:, None] & ~stale
            drops = int((stale & participating[:, None]).sum())
            if drops:
                from bluefog_tpu import metrics as metrics_mod

                self._stale_drops += drops
                metrics_mod.counter("bluefog.async.stale_drops").inc(
                    drops
                )
        return participating, fold_mask, breached

    def _decay_mutes(self) -> None:
        """Advance the advisory re-fire mutes by one TICK — called every
        tick (not only on breach ticks), so the documented in-ticks
        cooldown expires on wall progress and a recovered edge's next
        genuine incident is never swallowed by a stale counter."""
        for k in list(self._breach_mutes):
            self._breach_mutes[k] -= 1
            if self._breach_mutes[k] <= 0:
                del self._breach_mutes[k]

    def _advise(self, ctx, ages_by_edge: Dict[Tuple[int, int], int],
                breached: List[Tuple[int, int]]) -> None:
        """File the ``async_staleness`` advisory for un-muted breached
        edges through the PR-7 plumbing, naming the stale edges (and
        thereby the slow source ranks)."""
        fresh = [e for e in breached if e not in self._breach_mutes]
        if not fresh:
            return
        for e in fresh:
            self._breach_mutes[e] = BREACH_COOLDOWN
        fresh.sort(key=lambda e: (-ages_by_edge.get(e, 0), e))
        from bluefog_tpu import flight as flight_mod
        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu import timeline as tl
        from bluefog_tpu.attribution import Advisory

        adv = Advisory(
            kind="async_staleness", step=self._tick,
            detail={
                "edges": [[int(s), int(d)] for s, d in fresh[:8]],
                "ages": {
                    f"{s}->{d}": int(ages_by_edge.get((s, d), 0))
                    for s, d in fresh[:8]
                },
                "slow_ranks": sorted({int(s) for s, _d in fresh}),
                "bound": self.max_age,
                "policy": self.policy,
                "action": (
                    "dropped_from_fold" if self.policy == "drop"
                    else "throttled_receivers"
                ),
                "surface": "async",
                "topo_version": int(ctx.topo_version),
            },
        )
        self.advisories.append(adv)
        self._advisory_total += 1
        metrics_mod.counter(
            f"bluefog.doctor.advisory.{adv.kind}"
        ).inc()
        metrics_mod.gauge("bluefog.doctor.last_advisory_step").set(
            adv.step
        )
        flight_mod.note_advisory(kind=adv.kind, step=adv.step,
                                 **adv.detail)
        tl.timeline_record_advisory(adv.kind, adv.detail)

    # -- weights --------------------------------------------------------------

    def _exchange_weights(self, ctx, win):
        """(w_edges [size, size], self_vec [size]) — explicit (elastic-
        installed) weights or the uniform column-stochastic default
        over the CURRENT topology's out-neighbors, cached per topology
        version (the :class:`~bluefog_tpu.optimizers._WindowOptimizer`
        push-sum resolution)."""
        from bluefog_tpu import windows as win_mod

        size = ctx.size
        if self._default_topo_v != ctx.topo_version:
            self._default_dst = None
            self._default_sw = None
            self._default_topo_v = ctx.topo_version
        if self.dst_weights is None or self.self_weight is None:
            if self._default_dst is None:
                # cached per topology version: the O(N*E) neighbor walk
                # must not sit in the per-tick hot path
                outs = ctx.out_neighbor_ranks()
                self._default_dst = [
                    {d: 1.0 / (len(outs[r]) + 1) for d in outs[r]}
                    for r in range(size)
                ]
                self._default_sw = [
                    1.0 / (len(outs[r]) + 1) for r in range(size)
                ]
        dst = (
            self.dst_weights if self.dst_weights is not None
            else self._default_dst
        )
        sw = (
            self.self_weight if self.self_weight is not None
            else self._default_sw
        )
        w, participating = win_mod._per_rank_edges(
            ctx, dst, win.out_neighbors, "dst_weights"
        )
        self_vec = win_mod._self_weight_vec(ctx, sw, participating)
        return w, self_vec

    # -- the compiled tick ----------------------------------------------------

    def _tick_fn(self, ctx, win, perms, slot_table, n_batch, state_aval,
                 batch_aval):
        """One compiled program per communication structure: masked
        local update + masked push (``_exchange_core``, the single
        source of truth for the wire) + masked per-slot fold. All
        masks and weights are runtime operands — a new participation
        pattern or weight assignment never recompiles."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from bluefog_tpu import context as ctx_mod
        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu import flight as flight_mod
        from bluefog_tpu import windows as win_mod

        from bluefog_tpu.collective import kernels as wire_kernels

        key = (
            "async_tick", self._uid, getattr(self.opt, "_tx_version", 0),
            perms, tuple(map(tuple, slot_table)), self.wire,
            self.has_aux, n_batch, state_aval, batch_aval,
            win.shape, str(win.dtype),
        ) + wire_kernels.cache_token(self.wire)
        fn = ctx.op_cache.get(key)
        if fn is not None:
            return fn
        metrics_mod.counter("bluefog.recompiles").inc()
        flight_mod.record("compile", name="async_tick")

        import optax

        axis = ctx_mod.WORKER_AXIS
        slots_const = np.asarray(slot_table, np.int32)
        max_deg, shape = win.max_deg, win.shape
        # sender of each buffer slot, -1 where none: gates the version
        # lane so only writes from participating senders count as mass
        # arrivals (the structural slot table writes every round)
        sender_idx = np.full((len(win.in_neighbors), max(max_deg, 1)),
                             -1, np.int32)
        for r, srcs in enumerate(win.in_neighbors):
            for k, s in enumerate(srcs):
                sender_idx[r, k] = s
        sender_idx_const = jnp.asarray(sender_idx)
        tx = self.opt.tx
        wire = self.wire
        has_aux = self.has_aux
        value_and_grad = jax.value_and_grad(self.loss_fn, has_aux=has_aux)
        unpack = self._unpack_block
        treedef = self._treedef
        pack_dtype = self._pack_dtype

        def tree_block(tree):
            return jax.tree_util.tree_map(lambda t: t[0], tree)

        def restack(tree):
            return jax.tree_util.tree_map(
                lambda t: jnp.expand_dims(t, 0), tree
            )

        def body(value, buffers, versions, p, p_buffers, s_b, wops,
                 *batch_b):
            (recv_w, self_w, sent_w, part_arr, fold_w) = wops
            v, bufs, vers = value[0], buffers[0], versions[0]
            pv, pbufs = p[0], p_buffers[0]
            s = tree_block(s_b)
            bat = tuple(tree_block(b) for b in batch_b)
            idx = lax.axis_index(axis)
            part = part_arr[idx]

            # 1. local step at the push-sum estimate z = x/p, update
            #    applied to the RAW mass x (accumulated-p recursion)
            est = v / pv.astype(v.dtype)
            z_tree = jax.tree_util.tree_unflatten(treedef, unpack(est))
            if has_aux:
                (loss, aux), grads = value_and_grad(z_tree, *bat)
            else:
                loss, grads = value_and_grad(z_tree, *bat)
                aux = ()
            x_tree = jax.tree_util.tree_unflatten(treedef, unpack(v))
            updates, s_new = tx.update(grads, s, x_tree)
            x_new = optax.apply_updates(x_tree, updates)
            xb_new = jnp.concatenate(
                [
                    jnp.reshape(l, (-1,)).astype(pack_dtype)
                    for l in jax.tree_util.tree_leaves(x_new)
                ]
            )
            xb = jnp.where(part, xb_new, v)
            s_out = jax.tree_util.tree_map(
                lambda a, b: jnp.where(part, a, b), s_new, s
            )

            # 2. masked push: non-participating rows carry zero edge
            #    weight, self weight 1, sent mass 0 — bitwise identity
            #    on their lanes; the shared wire core conserves sender
            #    mass exactly under every tier
            v2, bufs2, vers2, pv2, pbufs2 = win_mod._exchange_core(
                axis, "acc", perms, slots_const, True, max_deg, shape,
                xb, bufs, vers, pv, pbufs, xb, recv_w, self_w,
                wire=wire, sent_w=sent_w,
            )
            # version lane: count only mass from participating senders
            srow = sender_idx_const[idx]                  # [max_deg]
            sgate = jnp.where(
                srow >= 0, part_arr[jnp.clip(srow, 0)], False
            )
            vers2 = vers + (vers2 - vers) * sgate.astype(vers.dtype)

            # 3. masked per-slot fold (push-sum collect): folded slots
            #    zero, un-folded mass stays pending
            kw = fold_w[idx]                              # [max_deg]
            v3 = v2 + jnp.tensordot(kw.astype(v2.dtype), bufs2,
                                    axes=(0, 0))
            keep = (1.0 - kw)
            bufs3 = bufs2 * keep[:, None].astype(bufs2.dtype)
            pv3 = pv2 + jnp.dot(kw.astype(pv2.dtype), pbufs2)
            pbufs3 = pbufs2 * keep.astype(pbufs2.dtype)
            vers3 = jnp.where(kw > 0, 0, vers2).astype(vers2.dtype)

            est_out = v3 / pv3.astype(v3.dtype)
            params_out = jax.tree_util.tree_unflatten(
                treedef, unpack(est_out)
            )
            expand = lambda t: jnp.expand_dims(t, 0)
            outs = (
                expand(v3), expand(bufs3), expand(vers3),
                expand(pv3), expand(pbufs3),
                restack(params_out), restack(s_out),
                jnp.reshape(loss, (1,)),
            )
            return outs + ((restack(aux),) if has_aux else ((),))

        spec = P(axis)
        fn = jax.jit(
            jax.shard_map(
                body, mesh=ctx.mesh,
                in_specs=(spec,) * 6 + (P(),) + (spec,) * n_batch,
                out_specs=(spec,) * 9,
            )
        )
        ctx.op_cache[key] = fn
        return fn

    # -- the tick -------------------------------------------------------------

    def step(self, params, opt_state, *batch):
        """One tick: ranks due on the tick clock take a local step and
        push; everyone folds what the staleness gate admits. Returns
        ``(params_estimate, opt_state, loss)`` (loss worker-stacked;
        ranks that sat out report their previous-estimate loss).

        ``params`` seeds the window on the first call (and after a
        parameter-shape change); afterwards the window is the source
        of truth — the returned estimate IS what the next call should
        be fed."""
        import jax.numpy as jnp

        from bluefog_tpu import context as ctx_mod
        from bluefog_tpu import elastic as elastic_mod
        from bluefog_tpu import flight as flight_mod
        from bluefog_tpu import metrics as metrics_mod
        from bluefog_tpu import staleness as staleness_mod
        from bluefog_tpu import watchdog
        from bluefog_tpu import windows as win_mod
        from bluefog_tpu.optimizers import _aval_key, _timed_dispatch

        ctx = ctx_mod.get_context()
        session = elastic_mod.active_session()
        if session is not None:
            # chaos replay + repair BEFORE the window/weight resolution:
            # a repair this tick must shape this tick's dispatch
            session.before_dispatch(self)
        self._ensure_window(ctx, params)
        win = win_mod._get_win(ctx, self._name)

        periods = self._periods(ctx, session)
        live = np.ones(ctx.size, bool)
        if session is not None:
            live[:] = False
            live[list(session.membership.live_ranks())] = True
        participating = live & (self._tick % periods == 0)

        ages = self._slot_ages(win)
        self._decay_mutes()
        participating, fold_mask, breached = self._gate(
            ctx, win, participating, ages
        )
        ages_by_edge = {
            (int(s), int(r)): int(ages[r, k])
            for r, srcs in enumerate(win.in_neighbors)
            for k, s in enumerate(srcs)
        }
        if breached:
            self._advise(ctx, ages_by_edge, breached)

        # age telemetry every tick (the gate computed it anyway)
        if ages_by_edge:
            vals = list(ages_by_edge.values())
            hist = metrics_mod.histogram("bluefog.async.age")
            for a in vals:
                hist.observe(a)
            metrics_mod.gauge("bluefog.async.age_max").set(
                float(max(vals))
            )

        w_edges, self_vec = self._exchange_weights(ctx, win)
        # masking rides in the OPERANDS: zero edge rows / self 1 /
        # sent 0 for ranks sitting this tick out — one compiled
        # program per structure, never per participation pattern
        w_masked = w_edges * participating[:, None]
        self_masked = np.where(participating, self_vec, 1.0)
        sent_masked = w_masked.sum(axis=1)

        perms, slot_table = win_mod._lowered_exchange(ctx, win, w_edges)
        n_batch = len(batch)
        fn = self._tick_fn(
            ctx, win, perms, slot_table, n_batch,
            _aval_key(opt_state), _aval_key(batch),
        )
        fold_f = np.zeros(
            (ctx.size, max(win.max_deg, 1)), np.float64
        )
        fold_f[fold_mask] = 1.0
        wops = (
            jnp.asarray(win_mod._round_weights(perms, w_masked)),
            jnp.asarray(np.asarray(self_masked, np.float64)),
            jnp.asarray(np.asarray(sent_masked, np.float64)),
            jnp.asarray(participating, bool),
            jnp.asarray(fold_f),
        )

        flight_mod.record(
            "async_tick", tick=self._tick,
            participants=int(participating.sum()),
        )
        # the tick's host blocking point: a hung neighbor-window wait
        # here is what the watchdog must see (SUSPECT verdicts flow
        # through the elastic stall handler)
        with watchdog.watch(f"async_fold:{self._name}"):
            outs = _timed_dispatch(
                "async_tick", fn,
                win.value, win.buffers, win.versions, win.p,
                win.p_buffers, opt_state, wops, *batch,
            )
        (win.value, win.buffers, win.versions, win.p, win.p_buffers,
         params_out, state_out, loss, aux) = outs

        # host age lane: one tick = one local window step; stamp only
        # the slots whose SENDER participated, then clear the folds
        written = np.zeros_like(fold_mask)
        for r, srcs in enumerate(win.in_neighbors):
            for k, s in enumerate(srcs):
                written[r, k] = participating[s]
        win_mod._note_async_tick(win, written, fold_mask)

        n_part = int(participating.sum())
        self._local_steps += n_part
        metrics_mod.counter("bluefog.async.ticks").inc()
        metrics_mod.counter("bluefog.async.local_steps").inc(n_part)
        metrics_mod.gauge("bluefog.async.participants").set(n_part)
        n_elems = int(np.prod(win.shape)) if win.shape else 1
        metrics_mod.counter("bluefog.async.wire_bytes").inc(
            metrics_mod.wire_bytes_per_step(
                {np.dtype(win.dtype).itemsize: n_elems}, len(perms),
                self.wire,
            )
        )
        # the staleness observatory folds the async lane's delivered
        # ages on its own per-window sampling clock
        staleness_mod.observe_window(
            ctx, win, step=self._tick, surface="async"
        )
        self._tick += 1
        if self.has_aux:
            return params_out, state_out, (loss, aux)
        return params_out, state_out, loss

    # -- observability --------------------------------------------------------

    def summary(self) -> dict:
        """The engine block the health report / ``/fleet`` surface
        attaches (next to the autotune summary)."""
        return {
            "ticks": self._tick,
            "local_steps": self._local_steps,
            "throttled": self._throttled,
            "stale_drops": self._stale_drops,
            "rewindows": self._rewindows,
            "advisories": self._advisory_total,
            "policy": self.policy,
            "wire": self.wire_name,
            "max_age": self.max_age,
            "cadence": {
                str(r): int(p) for r, p in sorted(self.cadence.items())
            },
        }


# -- module-level engine registry ---------------------------------------------

_active: Optional[AsyncGossipEngine] = None


def active() -> Optional[AsyncGossipEngine]:
    """The most recently built (still current) async engine, or None —
    what the health report and autotune decision records consult."""
    return _active


def on_init(ctx) -> None:
    """``bf.init()`` hook: a new mesh must not inherit a torn-down
    mesh's engine (its window died with the old context)."""
    global _active
    _active = None


def on_shutdown() -> None:
    global _active
    _active = None


def make_async_train_step(opt, loss_fn, has_aux: bool = False,
                          cadence: Optional[Dict[int, int]] = None,
                          max_age: Optional[int] = None,
                          policy: Optional[str] = None,
                          wire: Optional[str] = None,
                          enabled: Optional[bool] = None,
                          donate: bool = True):
    """Build the fully asynchronous train step (``bf.
    make_async_train_step``): per-rank-cadence push-sum gossip where no
    rank ever waits on a peer.

    ``opt`` is any gossip-family distributed optimizer — its inner
    optax transformation drives the local updates, and its
    ``compression`` knob seeds the wire tier. With async OFF
    (``enabled=False`` or ``BLUEFOG_ASYNC=0``) this returns
    ``opt.make_train_step(loss_fn, has_aux=..., donate=...)`` — the
    current synchronous path, bitwise identical by construction, which
    like it consumes ``params`` and ``opt_state`` unless ``donate=False``
    (the asynchronous engine's own programs keep their inputs, and
    ``donate`` does not reach them).

    With async ON the returned callable has the same signature
    (``step(params, opt_state, *batch) -> (params, opt_state, loss)``)
    but each call is one *tick*: ranks whose cadence divides the tick
    take a local step and push; everyone folds what the
    bounded-staleness gate admits. ``cadence`` maps rank -> period in
    ticks (default 1 everywhere); active ``slow`` chaos faults dilate
    it deterministically. See the module docstring and docs/async.md.
    """
    on = async_enabled() if enabled is None else bool(enabled)
    if not on:
        return opt.make_train_step(loss_fn, has_aux=has_aux, donate=donate)
    global _active
    engine = AsyncGossipEngine(
        opt, loss_fn, has_aux=has_aux, cadence=cadence,
        max_age=max_age, policy=policy, wire=wire,
    )
    _active = engine

    def train_step(params, opt_state, *batch):
        return engine.step(params, opt_state, *batch)

    train_step.engine = engine
    return train_step
