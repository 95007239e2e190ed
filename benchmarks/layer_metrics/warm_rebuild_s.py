"""warm_rebuild_s — layer: optimizer_path; unit s; moves ``setup_s``; every
cell. The sum of ``dur_us`` over every outer ``build`` event on the flight
ring inside the second and the third ``train_step`` call of ``warm_steps``:
0 when the step is built once, and otherwise what ``bench.py``'s comment on
``WARM_STEPS`` ("the second call of a fused step compiles again") costs.
Read through ``harness/setup_spans.py``; ``None`` with fewer than three
warm calls."""

from benchmarks.harness import setup_spans


def read(run):
    split = setup_spans.setup_split(run)
    return split and split["warm_rebuild_s"]
