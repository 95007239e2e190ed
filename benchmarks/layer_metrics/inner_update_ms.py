"""inner_update_ms — layer: optimizer_path; unit ms; moves
``throughput_per_chip``; every cell. Own device time per step and chip of
the kernels that are the inner update's — ``tx.update`` and
``optax.apply_updates`` under ``bf.inner_update`` — and **not the whole
update**: a fusion is one kernel and goes whole to one part
(``harness/scopes.py``: its own ``op_name``'s scope or, where XLA kept bare
glue's or none, the scope most instructions inside it carry). XLA fuses the
update of every large matrix into the matmul that makes its gradient, and
that kernel is ``backward_ms``'s: in the gpt2-medium cells 97 of the 322
kernels that hold update arithmetic, 353 M of the 406 M parameters (PERF.md
section 6, PR 24). A change to the update moves this metric and
``backward_ms`` together. With the other
five parts it partitions the step. ``None`` without a trace, or for a step
that carries no ``bf.`` scope."""

from benchmarks.harness import scopes


def read(run):
    parts = scopes.device_ms_by_scope(run)
    return parts and parts[scopes.INNER_UPDATE]
