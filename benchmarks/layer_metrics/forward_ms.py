"""forward_ms — layer: models (``models/*.py``); unit ms; moves
``throughput_per_chip``; every cell. Own device time per step and chip of
the instructions under ``bf.loss_grad`` (the user's ``value_and_grad``) that
are not the transposed half of the differentiation (no ``transpose(jvp(``):
the forward pass. The scope is in each instruction's ``op_name`` in the
compiled step's text (``harness/scopes.py``); with the other five parts it
partitions the step. ``None`` without a trace, or for a step that carries no
``bf.`` scope."""

from benchmarks.harness import scopes


def read(run):
    parts = scopes.device_ms_by_scope(run)
    return parts and parts[scopes.FORWARD]
