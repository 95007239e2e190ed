"""step_first_call_s — layer: optimizer_path (``optimizers.py``
``make_train_step.train_step``); unit s; moves ``setup_s``; every cell. The
first whole ``train_step`` call inside the harness's ``warm_steps`` span on
the host's clock: ``step_resolve`` to ``step_end`` in the flight ring. The
program is built in it (``step_trace_s`` + ``step_lower_s`` +
``step_backend_s``, which do not exceed it); the device's first run is not:
``warm_step_s[0]`` less this is what ``block_until_ready`` waited. Read
through ``harness/setup_spans.py``; ``None`` off the chip, from a program
without the events or from a ring that no longer holds the span."""

from benchmarks.harness import setup_spans


def read(run):
    split = setup_spans.setup_split(run)
    return split and split["step_first_call_s"]
