"""pack_unpack_ms — layer: optimizer_path; unit ms; moves
``throughput_per_chip``; every cell. Own device time per step and chip of
the instructions under ``bf.pack`` and ``bf.unpack``: flattening the tree
into per-dtype wire buffers and slicing it back. The scope is in each
instruction's ``op_name`` in the compiled step's text
(``harness/scopes.py``); with the other five parts it partitions the step.
``None`` without a trace, or for a step that carries no ``bf.`` scope."""

from benchmarks.harness import scopes


def read(run):
    parts = scopes.device_ms_by_scope(run)
    return parts and parts[scopes.PACK_UNPACK]
