"""step_trace_s — layer: optimizer_path; unit s; moves ``setup_s``; every
cell. jax's trace of the step program to a jaxpr: the sum of ``dur_us`` over
the outer ``build`` events of phase ``trace`` on the flight ring inside the
first ``train_step`` call of ``warm_steps`` (the children — one for each
inner ``jit`` the trace meets — say where it goes and are not summed).
Python's part of a start: the same with or without a compile cache. Read
through ``harness/setup_spans.py``."""

from benchmarks.harness import setup_spans


def read(run):
    split = setup_spans.setup_split(run)
    return split and split["step_trace_s"]
