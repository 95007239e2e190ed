"""import_s — layer: entry (``bluefog_tpu/__init__.py``); unit s; moves
``setup_s``; every cell. The package's own import with what it pulls in:
from the first statement of ``bluefog_tpu/__init__.py`` to its last import,
``import_s`` of the flight ring's ``session_start`` event (beside it
``jax_preloaded``: the harness imports jax first, so jax's import is not in
it). A part of ``reach_init_s``. Read through ``harness/setup_spans.py``;
``None`` off the chip or from a program that does not say."""

from benchmarks.harness import setup_spans


def read(run):
    split = setup_spans.setup_split(run)
    return split and split["import_s"]
