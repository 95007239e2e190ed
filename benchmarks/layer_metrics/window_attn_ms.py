"""window_attn_ms — layer: models (``models/decoder.py``); unit ms; moves
``throughput_per_chip``; the window/global sparse-expert cell. Own device
time per step and chip of the instructions under ``bf.attn.window``: the
sliding-window layers' q, k, v projections, rotary positions, the flash
kernels under the window's mask and ``o_proj`` — forward, recomputed and
backward. ``None`` for a step without the scope (a stack with no window
layer)."""

from benchmarks.harness import scopes, smallthinker_costs


def read(run):
    parts = scopes.device_ms_by_scopes(run, smallthinker_costs.PARTS)
    return (parts and parts[smallthinker_costs.WINDOW]) or None
