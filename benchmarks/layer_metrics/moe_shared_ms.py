"""moe_shared_ms — layer: models (``models/decoder.py``); unit ms; moves
``throughput_per_chip``; the latent-attention cell. Own device time per
step and chip of the instructions under ``bf.moe.shared``: the shared
expert's three products and its activation, which every position goes
through beside its routed choices, forward, recomputed and backward.
``None`` for a step without the scope."""

from benchmarks.harness import mistral4_costs, scopes


def read(run):
    parts = scopes.device_ms_by_scopes(run, mistral4_costs.PARTS)
    return parts and parts[mistral4_costs.SHARED]
