"""backward_ms — layer: models (``models/*.py``); unit ms; moves
``throughput_per_chip``; every cell. Own device time per step and chip of
the instructions under ``bf.loss_grad`` whose ``op_name`` holds
``transpose(jvp(``: the backward pass, and whatever XLA fused into its
kernels (the inner update of a matrix ends the matmul of its gradient: see
``inner_update_ms``). The scope is in each instruction's ``op_name`` in the
compiled step's text (``harness/scopes.py``); with the other five parts it
partitions the step. ``None`` without a trace, or for a
step that carries no ``bf.`` scope."""

from benchmarks.harness import scopes


def read(run):
    parts = scopes.device_ms_by_scope(run)
    return parts and parts[scopes.BACKWARD]
