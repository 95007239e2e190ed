"""unscoped_device_ms — layer: device; unit ms; moves ``throughput_per_chip``;
every cell. Own device time per step and chip of the instructions under no
``bf.*`` scope: what XLA made itself (copies, ``copy-done``,
``dynamic-update-slice``: no ``op_name``) and the glue the program leaves
bare, fusions included unless an instruction inside them carries a scope
(``harness/scopes.py``). How complete the split is. With the other five
parts it partitions the step. ``None`` without a trace, or for a step that
carries no ``bf.`` scope."""

from benchmarks.harness import scopes


def read(run):
    parts = scopes.device_ms_by_scope(run)
    return parts and parts[scopes.UNSCOPED]
