"""head_loss_ms — layer: models (``models/decoder.py``); unit ms; moves
``throughput_per_chip``; the block-diffusion cell. Own device time per step
and chip of the instructions under ``bf.head``: the final norm, the float32
head over the vocabulary slice and the masked, weighted cross-entropy,
forward and backward. ``None`` for a step without the scope."""

from benchmarks.harness import scopes, sdar_costs


def read(run):
    parts = scopes.device_ms_by_scopes(run, sdar_costs.PARTS)
    return parts and parts[sdar_costs.HEAD]
