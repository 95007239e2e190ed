"""reach_init_s — layer: entry; unit s; moves ``setup_s``; every cell. How
old the process is when ``bf.init()`` opens the session's flight ring:
``process_age_s`` of the ring's ``session_start`` event (Linux: the
process's start time in ``/proc/self/stat`` against ``CLOCK_BOOTTIME``).
Interpreter start, ``import jax``, the backend's start, the package's import
(``import_s``) and the caller's own imports are all inside it: the part of
``setup_s`` before the harness's ``init`` span ends, seen from the program.
Read through ``harness/setup_spans.py``; ``None`` off the chip or from a
program that does not say."""

from benchmarks.harness import setup_spans


def read(run):
    split = setup_spans.setup_split(run)
    return split and split["reach_init_s"]
