"""combine_ms — layer: collectives (``collective/inner.py``, ``plan.py``); unit
ms; moves ``throughput_per_chip``; every cell. Own device time per step and
chip of the instructions under ``bf.gossip``: permutes, the ``lax.switch``
over the schedule, the weighted sum, wire kernels — collectives included, so
beside ``collective_exposed_ms`` it says what gossip costs apart from the
wire. ``None`` where nothing ran under the scope, which is what a one-chip
cell should show: a number there means the "no-op" combine is not one."""

from benchmarks.harness import scopes


def read(run):
    parts = scopes.device_ms_by_scope(run)
    return (parts and parts[scopes.COMBINE]) or None
