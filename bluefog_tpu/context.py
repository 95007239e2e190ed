# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Process-global runtime context: mesh ownership and topology state.

TPU-native replacement for the reference's ``BlueFogBasics`` object plus the
C-side global state (reference ``common/basics.py:37-568``,
``common/global_state.h``). There is no background thread, no coordinator
and no ctypes boundary: the single controller owns a ``jax.sharding.Mesh``
over the worker devices, and every collective is a compiled SPMD program
over that mesh.

Deliberate API departures from the per-process reference model (documented
here once; individual functions cite back):

- A "worker" is a mesh device, not an OS process. ``size()`` is the number
  of worker devices.
- Per-rank queries (``in_neighbor_ranks`` etc.) take an explicit ``rank``
  argument; with ``rank=None`` they return every rank's answer, because the
  single controller sees all ranks at once. The reference's implicit "my
  rank" does not exist under SPMD.
- ``rank()`` / ``local_rank()`` report the *controller process* position
  (``jax.process_index``), which matches the reference only in the one
  launch regime both share (one process per host, multi-host DCN).
"""

import itertools
import os
import threading
from typing import Callable, List, Optional, Sequence

import numpy as np
import networkx as nx

import jax
from jax.sharding import Mesh

from bluefog_tpu.topology import ExponentialGraph, serpentine_device_order
from bluefog_tpu.topology.graphs import IsTopologyEquivalent

__all__ = ["BluefogContext", "get_context", "init", "shutdown", "is_initialized"]

WORKER_AXIS = "workers"
MACHINE_AXIS = "machines"
LOCAL_AXIS = "local"

_lock = threading.Lock()
_context: Optional["BluefogContext"] = None
_distributed_initialized = False


def maybe_init_distributed() -> bool:
    """Join the multi-host jax.distributed service if the launcher asked.

    ``bfrun-tpu -H host1:4,host2:4 …`` starts one controller process per
    host with BLUEFOG_COORDINATOR/NUM_PROCESSES/PROCESS_ID set (see
    :mod:`bluefog_tpu.run.run`); this is the moment the reference's
    ``mpirun`` process bring-up (run/run.py:180-203) maps to. Returns True
    when an initialize call was made.
    """
    global _distributed_initialized
    coordinator = os.environ.get("BLUEFOG_COORDINATOR")
    if not coordinator or _distributed_initialized:
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(os.environ["BLUEFOG_NUM_PROCESSES"]),
        process_id=int(os.environ.get("BLUEFOG_PROCESS_ID", "0")),
    )
    _distributed_initialized = True
    return True


def order_devices_for_mesh(devices: Sequence, multi_process: bool) -> List:
    """Gossip-friendly 1-D ordering of the worker devices (pure helper).

    The machines x local split chunks this ordered list, so the order must
    be host-contiguous or the "local" psum would span hosts over DCN.
    Serpentine within each host keeps intra-host hops short; hosts are
    ordered by process index (DCN neighbors in typical pod wiring).
    """
    if not multi_process:
        return serpentine_device_order(devices)
    by_proc: dict = {}
    for d in devices:
        by_proc.setdefault(d.process_index, []).append(d)
    return [
        d
        for proc in sorted(by_proc)
        for d in serpentine_device_order(by_proc[proc])
    ]


def default_nodes_per_machine(
    devices: Sequence, process_count: int
) -> Optional[int]:
    """Machines x local split width when none was requested (pure helper):
    on a multi-host pod, one "machine" = one controller process's devices;
    single-host has no natural split (None -> trivial 1-machine split)."""
    if process_count > 1:
        return len([d for d in devices if d.process_index == 0])
    return None


def _resolve_devices(requested: Optional[int]) -> List:
    """Device list honoring BLUEFOG_NUM_WORKERS (set by bfrun-tpu -np).

    Always the default backend's devices: a backend with fewer devices
    than requested is an error, never a switch to another platform (CPU
    is chosen from outside, with ``JAX_PLATFORMS=cpu``).
    """
    devices = jax.devices()
    if requested is None:
        return list(devices)
    if jax.process_count() > 1:
        # Multi-host: the global device list is partitioned across
        # controllers; truncating it would strand some controllers with
        # none of their addressable devices in the mesh. The per-host
        # device counts (bfrun-tpu host slots) must simply add up.
        if len(devices) != requested:
            raise RuntimeError(
                f"BLUEFOG_NUM_WORKERS={requested} but the "
                f"{jax.process_count()}-process pod exposes {len(devices)} "
                "devices; host slot counts must sum to -np"
            )
        return list(devices)
    if len(devices) < requested:
        raise RuntimeError(
            f"BLUEFOG_NUM_WORKERS={requested} but the "
            f"{jax.default_backend()!r} backend has only {len(devices)} "
            "devices; for a virtual CPU mesh set JAX_PLATFORMS=cpu and "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={requested}"
            " (bfrun-tpu --platform cpu does both)"
        )
    return list(devices[:requested])


# Fixed, so that a later process finds what an earlier one compiled: a
# cache at a path that moves (temp name, pid, timestamp) never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins and then nothing is set in code
    (jax reads the variable itself); otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR` inside the checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


_ctx_uid = itertools.count()


class BluefogContext:
    """Owns the device mesh, the active topology, and compiled-op caches."""

    def __init__(
        self,
        topology_fn: Optional[Callable[[int], nx.DiGraph]] = None,
        is_weighted: bool = False,
        devices: Optional[Sequence] = None,
        nodes_per_machine: Optional[int] = None,
    ):
        if devices is None:
            requested = os.environ.get("BLUEFOG_NUM_WORKERS")
            devices = _resolve_devices(
                int(requested) if requested else None
            )
            devices = order_devices_for_mesh(
                devices, jax.process_count() > 1
            )
        # Generation id: state holders acquired against one context (e.g.
        # the associated-p refcount) must not act on a later context.
        self.uid: int = next(_ctx_uid)
        self.devices: List = list(devices)
        self.size: int = len(self.devices)

        # 1-D gossip mesh over all workers.
        self.mesh = Mesh(np.array(self.devices), (WORKER_AXIS,))

        # Optional machines × local submesh split for hierarchical ops.
        # Mirrors BLUEFOG_NODES_PER_MACHINE faking of multi-node on one host
        # (reference common/mpi_context.cc:320-337); on a real multi-host
        # pod the natural split is jax.local_device_count() per process.
        if nodes_per_machine is None:
            env = os.environ.get("BLUEFOG_NODES_PER_MACHINE")
            if env:
                nodes_per_machine = int(env)
            else:
                nodes_per_machine = default_nodes_per_machine(
                    self.devices, jax.process_count()
                )
        self.local_size: int = nodes_per_machine or self.size
        assert self.size % self.local_size == 0, (
            f"nodes_per_machine={self.local_size} must divide the worker "
            f"count {self.size}"
        )
        self.machine_size: int = self.size // self.local_size
        self.machine_mesh = Mesh(
            np.array(self.devices).reshape(self.machine_size, self.local_size),
            (MACHINE_AXIS, LOCAL_AXIS),
        )

        self._topology: Optional[nx.DiGraph] = None
        self._topo_weighted: bool = False
        # in-neighbor set cache, invalidated by topo_version: the eager
        # explicit-weights hot path validates src keys against these on
        # EVERY call, and rebuilding them is an O(N*E) networkx walk
        self._neighbor_sets_cache: Optional[tuple] = None
        self._machine_topology: Optional[nx.DiGraph] = None
        self._machine_topo_weighted: bool = False
        # Monotonic versions for cache keys: id(graph) is unsafe (CPython
        # reuses addresses after GC), so compiled-plan caches key on these.
        self.topo_version: int = 0
        self.machine_topo_version: int = 0

        # Compiled-function cache: key -> jitted callable. Keys include the
        # (hashable) plan/schedule and input avals, so topology changes that
        # reuse an already-seen plan hit the cache instead of recompiling.
        self.op_cache: dict = {}

        # Elastic live-set state (bluefog_tpu.elastic): None until an
        # ElasticSession installs a Membership. Static-plan cache keys
        # fold live_token() in, so a membership change can never
        # dispatch a stale plan.
        self.elastic_membership = None

        if topology_fn is not None:
            topo = topology_fn(self.size)
            assert topo is not None, "topology_fn returned None"
            self.set_topology(topo, is_weighted)
        else:
            # Reference default: ExponentialGraph, unweighted combine
            # (common/basics.py:65-69).
            self.set_topology(ExponentialGraph(self.size), is_weighted)

    # -- topology management (reference basics.py:311-419) ------------------

    def set_topology(self, topology: nx.DiGraph, is_weighted: bool = False) -> bool:
        if not isinstance(topology, nx.DiGraph):
            raise TypeError("topology must be a networkx.DiGraph")
        if topology.number_of_nodes() != self.size:
            raise ValueError(
                f"topology has {topology.number_of_nodes()} nodes but the "
                f"mesh has {self.size} workers"
            )
        if IsTopologyEquivalent(topology, self._topology) and (
            is_weighted == self._topo_weighted
        ):
            return True  # no-op, parity with basics.py:340-345
        self._topology = topology
        self._topo_weighted = is_weighted
        self.topo_version += 1
        return True

    def load_topology(self) -> nx.DiGraph:
        return self._topology

    def is_topo_weighted(self) -> bool:
        return self._topo_weighted

    def set_machine_topology(self, topology: nx.DiGraph, is_weighted: bool = False) -> bool:
        if not isinstance(topology, nx.DiGraph):
            raise TypeError("machine topology must be a networkx.DiGraph")
        if topology.number_of_nodes() != self.machine_size:
            raise ValueError(
                f"machine topology has {topology.number_of_nodes()} nodes "
                f"but there are {self.machine_size} machines"
            )
        self._machine_topology = topology
        self._machine_topo_weighted = is_weighted
        self.machine_topo_version += 1
        return True

    def load_machine_topology(self) -> nx.DiGraph:
        return self._machine_topology

    def is_machine_topo_weighted(self) -> bool:
        return self._machine_topo_weighted

    # -- elastic live set (bluefog_tpu.elastic) ------------------------------

    def live_token(self):
        """Hashable (epoch, live-rank tuple) identifying the current live
        set, or None when no elastic session is active (everyone lives).
        Compiled-plan caches key on this so membership changes invalidate
        exactly the plans they must."""
        m = self.elastic_membership
        return None if m is None else m.token()

    # -- neighbor queries (reference basics.py:203-265) ----------------------

    def in_neighbor_sets(self):
        """Per-rank frozen in-neighbor sets of the active topology,
        cached on ``topo_version``: the warm path is one version compare
        and a tuple return, so per-call weight validation
        (:func:`bluefog_tpu.collective.ops._resolve_plan`) does O(1)
        host work instead of an O(N*E) graph walk per eager dispatch
        (pinned by tests/test_collective.py, mirroring the window
        layer's host-cost pin)."""
        cached = self._neighbor_sets_cache
        if cached is not None and cached[0] == self.topo_version:
            return cached[1]
        assert self._topology is not None
        sets = tuple(
            frozenset(
                r for r in self._topology.predecessors(rank) if r != rank
            )
            for rank in range(self.size)
        )
        self._neighbor_sets_cache = (self.topo_version, sets)
        return sets

    def in_neighbor_ranks(self, rank: Optional[int] = None):
        assert self._topology is not None
        if rank is None:
            return [self.in_neighbor_ranks(r) for r in range(self.size)]
        return sorted(r for r in self._topology.predecessors(rank) if r != rank)

    def out_neighbor_ranks(self, rank: Optional[int] = None):
        assert self._topology is not None
        if rank is None:
            return [self.out_neighbor_ranks(r) for r in range(self.size)]
        return sorted(r for r in self._topology.successors(rank) if r != rank)

    def in_neighbor_machine_ranks(self, machine_rank: Optional[int] = None):
        if self._machine_topology is None:
            return None
        if machine_rank is None:
            return [
                self.in_neighbor_machine_ranks(m) for m in range(self.machine_size)
            ]
        return sorted(
            m
            for m in self._machine_topology.predecessors(machine_rank)
            if m != machine_rank
        )

    def out_neighbor_machine_ranks(self, machine_rank: Optional[int] = None):
        if self._machine_topology is None:
            return None
        if machine_rank is None:
            return [
                self.out_neighbor_machine_ranks(m) for m in range(self.machine_size)
            ]
        return sorted(
            m
            for m in self._machine_topology.successors(machine_rank)
            if m != machine_rank
        )


def init(
    topology_fn: Optional[Callable[[int], nx.DiGraph]] = None,
    is_weighted: bool = False,
    devices: Optional[Sequence] = None,
    nodes_per_machine: Optional[int] = None,
) -> BluefogContext:
    """Initialize the global context (reference ``bf.init``, basics.py:49-70).

    ``topology_fn`` receives the worker count and returns the initial
    topology (default ``ExponentialGraph``). ``devices`` overrides the mesh
    device list (default: all devices in serpentine torus order);
    ``nodes_per_machine`` configures the machines×local split for
    hierarchical ops (default from BLUEFOG_NODES_PER_MACHINE or the
    per-process device count on multi-host).
    """
    global _context
    maybe_init_distributed()
    configure_compile_cache()
    # An elastic session is bound to one context's membership; a re-init
    # must not leave it pointing at the torn-down mesh.
    from bluefog_tpu import elastic as _elastic

    _elastic.stop()
    with _lock:
        _context = BluefogContext(
            topology_fn=topology_fn,
            is_weighted=is_weighted,
            devices=devices,
            nodes_per_machine=nodes_per_machine,
        )
    # Reference behavior: BLUEFOG_TIMELINE=<prefix> activates tracing at
    # init (operations.cc:464-473).
    from bluefog_tpu import attribution as _attribution
    from bluefog_tpu import flight as _flight
    from bluefog_tpu import health as _health
    from bluefog_tpu import metrics as _metrics
    from bluefog_tpu import timeline as _tl

    _tl.maybe_init_from_env()
    # Flight recorder opens AFTER the timeline so its session_start
    # clock handshake can pair the timeline clock with wall/monotonic —
    # the anchor tools/trace_merge.py aligns ranks with.
    _flight.on_init(_context)
    # Attribution doctor (BLUEFOG_DOCTOR=1): fresh session per mesh so
    # stale baselines never advise a new topology.
    _attribution.on_init(_context)
    # Fleet health plane (BLUEFOG_HEALTH=1 observatory,
    # BLUEFOG_HEALTH_PORT serving): fresh session per mesh, same
    # stale-baseline rationale as the doctor.
    _health.on_init(_context)
    # Staleness observatory (BLUEFOG_STALENESS=1): fresh session per
    # mesh — a torn-down mesh's per-edge age table must not alias the
    # new graph's edges.
    from bluefog_tpu import staleness as _staleness

    _staleness.on_init(_context)
    # Memory observatory (BLUEFOG_MEMORY=1) + OOM crash hooks: fresh
    # session per mesh — a torn-down mesh's census and watermark must
    # not read as the new mesh's footprint. Installed AFTER the flight
    # recorder so its excepthook runs FIRST on an uncaught error (the
    # ranked census must land in the side table before the crash dump
    # is written).
    from bluefog_tpu import memory as _memory

    _memory.on_init(_context)
    # Autotune controller (BLUEFOG_AUTOTUNE=1): fresh session per mesh
    # — stale hysteresis state or a rollback target captured against a
    # torn-down mesh must never actuate on the new one.
    from bluefog_tpu import autotune as _autotune

    _autotune.on_init(_context)
    # Async gossip engine registry: an engine's window died with the
    # old mesh — a new context must not report (or repair) it.
    from bluefog_tpu import async_gossip as _async_gossip

    _async_gossip.on_init(_context)
    # SLO engine (BLUEFOG_SLO=1): fresh session per mesh — a new mesh
    # must not inherit a torn-down mesh's error-budget history.
    # Installed LAST among the observatories: its sampled pass reads
    # the series every tier above publishes.
    from bluefog_tpu import slo as _slo

    _slo.on_init(_context)
    # Mesh-shape gauges: every metrics export carries the context the
    # series were recorded under (a JSONL file divorced from its run is
    # otherwise uninterpretable).
    _metrics.gauge("bluefog.size").set(_context.size)
    _metrics.gauge("bluefog.machine_size").set(_context.machine_size)
    return _context


def shutdown() -> None:
    """Drop the global context (reference ``bf.shutdown``). Closes a
    timeline the context implicitly opened from BLUEFOG_TIMELINE; a
    timeline the user opened with ``timeline_init`` stays open (it is
    theirs to close)."""
    global _context
    from bluefog_tpu import attribution as _attribution
    from bluefog_tpu import elastic as _elastic
    from bluefog_tpu import flight as _flight
    from bluefog_tpu import health as _health
    from bluefog_tpu import metrics as _metrics
    from bluefog_tpu import timeline as _tl

    from bluefog_tpu import autotune as _autotune
    from bluefog_tpu import staleness as _staleness

    from bluefog_tpu import async_gossip as _async_gossip

    _elastic.stop()
    # the SLO engine goes first: its budget tail must flush while the
    # tiers it reads (and the surfaces it writes through) are still up
    from bluefog_tpu import slo as _slo

    _slo.on_shutdown()
    # then the controller: its session_end summary must flush while
    # the surfaces it writes through are still up
    _autotune.on_shutdown()
    _async_gossip.on_shutdown()
    _attribution.on_shutdown()
    _health.on_shutdown()
    _staleness.on_shutdown()
    from bluefog_tpu import memory as _memory

    _memory.on_shutdown()
    # the shard registry is per-session observability state: a stale
    # layout summary must not survive into the next init's /fleet
    from bluefog_tpu import sharding as _sharding

    _sharding.clear_active()
    if _context is not None:
        # session_end lands in the ring (and the crash hooks detach)
        # while the timeline is still open for the clock pairing
        _flight.on_shutdown()

    # Final flush of deferred device drains + the env-configured
    # exporters (JSONL / Prometheus / timeline counters) BEFORE an
    # env-owned timeline closes, so the last drained values land in both
    # the files and the trace.
    _metrics.flush()
    _metrics.auto_export()
    if _tl.timeline_env_owned():
        _tl.timeline_shutdown()
    with _lock:
        _context = None


def is_initialized() -> bool:
    return _context is not None


def get_context() -> BluefogContext:
    if _context is None:
        raise RuntimeError(
            "bluefog_tpu is not initialized; call bluefog_tpu.init() first."
        )
    return _context
