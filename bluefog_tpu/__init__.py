# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""bluefog_tpu: a TPU-native decentralized (gossip) training framework.

Capability parity with BlueFog (reference at /root/reference) re-designed
for JAX/XLA SPMD over TPU meshes: neighbor collectives are ``ppermute``
schedules over ICI, window-style asynchronous algorithms are buffered
step-synchronous neighbor state, and the optimizer wrappers drive
pjit-compiled train steps.

The user-facing facade mirrors ``bluefog.torch`` lifted to the
single-controller model — distributed values are stacked "worker arrays"
with one leading slot per worker::

    import bluefog_tpu as bf
    bf.init()                                 # mesh + default Exp graph
    x = bf.worker_values(lambda rank: ...)    # stacked [size, ...] array
    y = bf.neighbor_allreduce(x)              # weighted gossip step
    h = bf.neighbor_allreduce_nonblocking(x)  # async-dispatch handle
    y = bf.synchronize(h)

See :mod:`bluefog_tpu.context` for the documented API departures from the
reference's per-process model.
"""

import sys as _sys
import time as _time

# before anything heavy is imported: see flight.note_import below
_import_t0 = _time.perf_counter()
_jax_preloaded = "jax" in _sys.modules

import jax as _jax

from bluefog_tpu.version import __version__
from bluefog_tpu import topology
from bluefog_tpu import topology as topology_util  # reference-style alias
from bluefog_tpu import collective
from bluefog_tpu.context import (
    BluefogContext,
    get_context,
    init,
    is_initialized,
    shutdown,
)
from bluefog_tpu.windows import (
    win_create,
    win_free,
    win_update,
    win_update_then_collect,
    win_put,
    win_put_nonblocking,
    win_get,
    win_get_nonblocking,
    win_accumulate,
    win_accumulate_nonblocking,
    win_wait,
    win_poll,
    win_mutex,
    win_read,
    get_win_version,
    get_win_age,
    get_current_created_window_names,
    turn_on_win_ops_with_associated_p,
    turn_off_win_ops_with_associated_p,
    win_associated_p,
)
from bluefog_tpu.optimizers import (
    CommunicationType,
    DistributedGradientAllreduceOptimizer,
    DistributedAllreduceOptimizer,
    DistributedNeighborAllreduceOptimizer,
    DistributedHierarchicalNeighborAllreduceOptimizer,
    DistributedAdaptThenCombineOptimizer,
    DistributedAdaptWithCombineOptimizer,
    DistributedWinPutOptimizer,
    DistributedPullGetOptimizer,
    DistributedPushSumOptimizer,
)
from bluefog_tpu.utility import (
    broadcast_parameters,
    broadcast_optimizer_state,
    allreduce_parameters,
)
from bluefog_tpu import async_gossip
from bluefog_tpu.async_gossip import make_async_train_step
from bluefog_tpu import checkpoint
from bluefog_tpu import elastic
from bluefog_tpu import ops
from bluefog_tpu.timeline import (
    timeline_init,
    timeline_shutdown,
    timeline_enabled,
    timeline_start_activity,
    timeline_end_activity,
    timeline_context,
)
from bluefog_tpu.logging_util import logger, set_log_level
from bluefog_tpu import flight
from bluefog_tpu.flight import dump as flight_dump
from bluefog_tpu import attribution
from bluefog_tpu import attribution as doctor  # bf.doctor facade
from bluefog_tpu import autotune
from bluefog_tpu import health
from bluefog_tpu import memory
from bluefog_tpu import fleetsim
from bluefog_tpu import federation
from bluefog_tpu import sharding
from bluefog_tpu import slo
from bluefog_tpu import staleness
from bluefog_tpu import metrics
from bluefog_tpu.metrics import (
    metrics_export,
    snapshot as metrics_snapshot,
)
from bluefog_tpu.timeline import (
    timeline_record_counter,
    timeline_record_instant,
)
from bluefog_tpu.watchdog import set_stall_timeout
from bluefog_tpu.watchdog import suspend, resume
from bluefog_tpu.collective.ops import (
    worker_values,
    allreduce,
    allreduce_nonblocking,
    allgather,
    allgather_nonblocking,
    broadcast,
    broadcast_nonblocking,
    neighbor_allreduce,
    neighbor_allreduce_nonblocking,
    neighbor_allgather,
    neighbor_allgather_nonblocking,
    hierarchical_neighbor_allreduce,
    hierarchical_neighbor_allreduce_nonblocking,
    pair_gossip,
    pair_gossip_nonblocking,
    poll,
    synchronize,
    wait,
    barrier,
)

# the last import: what the package's own import cost rides the flight
# ring's ``session_start`` (``import_s``), beside the process's age
flight.note_import(_time.perf_counter() - _import_t0, _jax_preloaded)


# -- fused train step (overlap layer) ----------------------------------------


def make_train_step(optimizer, loss_fn, has_aux: bool = False,
                    delayed: bool = False, donate: bool = True):
    """Compile ``loss_fn`` + backward + inner update + gossip into ONE
    program so XLA can overlap the ppermute rounds with compute.

    Free-function facade over ``optimizer.make_train_step`` for any of the
    gossip-family distributed optimizers::

        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
        train_step = bf.make_train_step(opt, loss_fn)
        params, opt_state, loss = train_step(params, opt_state, batch)

    ``delayed=True`` mixes each step against the previous step's payload
    (one-step-stale gossip), removing communication from the critical path
    entirely; see :meth:`bluefog_tpu.optimizers._GossipOptimizer.make_train_step`
    and docs/performance.md for semantics and the staleness caveat.

    The call **consumes** ``params`` and ``opt_state`` (``donate=True``,
    the default): the compiled step writes the new parameters and state
    into the buffers it was given, so after the call the arrays passed in
    are deleted and the returned ones take their place — rebind both, as
    the loop above does. To read an input again after the call, copy it
    first (``jax.tree_util.tree_map(jnp.copy, params)``) or pass
    ``donate=False``, which keeps the inputs alive at the cost of a second
    copy of parameters and state on the device and one buffer allocation
    per output leaf per call. ``optimizer.step`` never donates.
    """
    return optimizer.make_train_step(
        loss_fn, has_aux=has_aux, delayed=delayed, donate=donate
    )


# -- size / rank queries (reference basics.py:112-201) -----------------------


def size() -> int:
    """Number of workers (= mesh devices; the reference's MPI world size)."""
    return get_context().size


def local_size() -> int:
    """Workers per machine (reference local communicator size)."""
    return get_context().local_size


def machine_size() -> int:
    """Number of machines in the hierarchical split."""
    return get_context().machine_size


def rank() -> int:
    """Controller process index. 0 under single-controller; equals the
    reference's rank only in the shared one-process-per-host regime. Worker
    identity lives in the mesh axis, not the process — see
    :mod:`bluefog_tpu.context`."""
    return _jax.process_index()


def local_rank() -> int:
    """Process-local analogue of :func:`rank` (0 on a single controller)."""
    return 0


def machine_rank(worker_rank: int) -> int:
    """Machine index of a worker rank (reference basics.py:180-188)."""
    return worker_rank // get_context().local_size


def is_homogeneous() -> bool:
    """All machines have the same worker count — always true here because
    the machines×local split is a mesh reshape (reference basics.py:190-201
    discovers this over MPI)."""
    return True


# -- topology management -----------------------------------------------------


def set_topology(topology_graph=None, is_weighted: bool = False) -> bool:
    """Install a new virtual topology (reference basics.py:311-419). With
    ``None`` restores the default ExponentialGraph."""
    ctx = get_context()
    if topology_graph is None:
        topology_graph = topology.ExponentialGraph(ctx.size)
    return ctx.set_topology(topology_graph, is_weighted)


def load_topology():
    """The active topology digraph (reference basics.py:292-309)."""
    return get_context().load_topology()


def is_topo_weighted() -> bool:
    return get_context().is_topo_weighted()


def set_machine_topology(topology_graph, is_weighted: bool = False) -> bool:
    """Install the machine-level topology for hierarchical ops
    (reference basics.py:267-309)."""
    return get_context().set_machine_topology(topology_graph, is_weighted)


def load_machine_topology():
    return get_context().load_machine_topology()


def is_machine_topo_weighted() -> bool:
    return get_context().is_machine_topo_weighted()


def in_neighbor_ranks(rank: int = None):
    """In-neighbors of ``rank``; all ranks' lists when ``rank`` is None
    (single-controller lift of reference basics.py:203-233)."""
    return get_context().in_neighbor_ranks(rank)


def out_neighbor_ranks(rank: int = None):
    return get_context().out_neighbor_ranks(rank)


def in_neighbor_machine_ranks(machine_rank: int = None):
    return get_context().in_neighbor_machine_ranks(machine_rank)


def out_neighbor_machine_ranks(machine_rank: int = None):
    return get_context().out_neighbor_machine_ranks(machine_rank)


__all__ = [
    "__version__",
    "topology",
    "topology_util",
    "collective",
    "BluefogContext",
    "init",
    "shutdown",
    "is_initialized",
    "get_context",
    "size",
    "local_size",
    "machine_size",
    "rank",
    "local_rank",
    "machine_rank",
    "is_homogeneous",
    "set_topology",
    "load_topology",
    "is_topo_weighted",
    "set_machine_topology",
    "load_machine_topology",
    "is_machine_topo_weighted",
    "in_neighbor_ranks",
    "out_neighbor_ranks",
    "in_neighbor_machine_ranks",
    "out_neighbor_machine_ranks",
    "worker_values",
    "allreduce",
    "allreduce_nonblocking",
    "allgather",
    "allgather_nonblocking",
    "broadcast",
    "broadcast_nonblocking",
    "neighbor_allreduce",
    "neighbor_allreduce_nonblocking",
    "neighbor_allgather",
    "neighbor_allgather_nonblocking",
    "hierarchical_neighbor_allreduce",
    "hierarchical_neighbor_allreduce_nonblocking",
    "pair_gossip",
    "pair_gossip_nonblocking",
    "poll",
    "synchronize",
    "wait",
    "barrier",
    "win_create",
    "win_free",
    "win_update",
    "win_update_then_collect",
    "win_put",
    "win_put_nonblocking",
    "win_get",
    "win_get_nonblocking",
    "win_accumulate",
    "win_accumulate_nonblocking",
    "win_wait",
    "win_poll",
    "win_mutex",
    "win_read",
    "get_win_version",
    "get_win_age",
    "get_current_created_window_names",
    "turn_on_win_ops_with_associated_p",
    "turn_off_win_ops_with_associated_p",
    "win_associated_p",
    "make_train_step",
    "async_gossip",
    "make_async_train_step",
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
    "broadcast_parameters",
    "broadcast_optimizer_state",
    "allreduce_parameters",
    "timeline_init",
    "timeline_shutdown",
    "timeline_enabled",
    "timeline_start_activity",
    "timeline_end_activity",
    "timeline_record_instant",
    "timeline_record_counter",
    "timeline_context",
    "elastic",
    "flight",
    "flight_dump",
    "attribution",
    "doctor",
    "autotune",
    "health",
    "sharding",
    "memory",
    "fleetsim",
    "federation",
    "slo",
    "staleness",
    "metrics",
    "metrics_snapshot",
    "metrics_export",
    "logger",
    "set_log_level",
    "set_stall_timeout",
    "suspend",
    "resume",
]
