"""moe_experts_ms — layer: models (``ops/moe.py``); unit ms; moves
``throughput_per_chip``; the sparse-expert cell. Own device time per step
and chip of the instructions under ``bf.moe.experts``: the held experts'
grouped products (gate, up, down; forward, recomputed and backward: the
kernels ``bf_gmm`` and ``bf_tgmm``), the casts of their weights and the
activation between them. ``None`` for a step without the scopes."""

from benchmarks.harness import scopes, sdar_costs


def read(run):
    parts = scopes.device_ms_by_scopes(run, sdar_costs.PARTS)
    return parts and parts[sdar_costs.EXPERTS]
