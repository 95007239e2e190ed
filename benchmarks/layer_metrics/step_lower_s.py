"""step_lower_s — layer: optimizer_path; unit s; moves ``setup_s``; every
cell. The lowering of the step's jaxpr to an MLIR module, the Pallas
kernels' included: the sum of ``dur_us`` over the outer ``build`` events of
phase ``lower`` on the flight ring inside the first ``train_step`` call of
``warm_steps``. The same with or without a compile cache. Read through
``harness/setup_spans.py``."""

from benchmarks.harness import setup_spans


def read(run):
    split = setup_spans.setup_split(run)
    return split and split["step_lower_s"]
