"""step_ms_p50 — layer: device; unit ms; information beside
``throughput_per_chip``; every cell. Median over the window's blocks of the
block's mean step time (host clock, one sync per block)."""

import statistics

from benchmarks.harness import bench


def read(run):
    if not run.block_s:
        return None
    return 1e3 * statistics.median(run.block_s) / bench.STEPS_PER_BLOCK
