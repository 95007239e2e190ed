"""step_backend_s — layer: optimizer_path; unit s; moves ``setup_s``; every
cell. The XLA compile of the step program, or its load from the persistent
cache: the sum of ``dur_us`` over the outer ``build`` events of phase
``backend`` on the flight ring inside the first ``train_step`` call of
``warm_steps`` (the event's ``cache`` says which: ``hit`` with its
``retrieval_us``, or ``miss``). Read through ``harness/setup_spans.py``."""

from benchmarks.harness import setup_spans


def read(run):
    split = setup_spans.setup_split(run)
    return split and split["step_backend_s"]
