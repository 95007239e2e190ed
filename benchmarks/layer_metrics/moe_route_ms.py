"""moe_route_ms — layer: models (``ops/moe.py``); unit ms; moves
``throughput_per_chip``; the sparse-expert cell. Own device time per step
and chip of the instructions under ``bf.moe.route`` (the router's product,
softmax and top-k, the held filter, the sorts that group the rows, the
gather into the row buffer) and ``bf.moe.combine`` (the gather back, the
weighting and the sum over a token's choices): everything of the expert
layer that is not its products. ``None`` for a step without the scopes."""

from benchmarks.harness import scopes, sdar_costs


def read(run):
    parts = scopes.device_ms_by_scopes(run, sdar_costs.PARTS)
    return parts and parts[sdar_costs.ROUTE] + parts[sdar_costs.COMBINE]
