#!/usr/bin/env python3
"""One run of one benchmark cell on the chips this machine holds.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children, no server. The cell is looked up by name in
``BENCHMARK.json`` (benchmarks/harness/cells.py). The last line of the
standard output is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, traced ``breakdown``, and last
``compared``: each number ``correct`` was decided by beside its limit, which
are also the last lines of the standard error. Earlier
lines are JSON too: spans, block times, losses, the reference's errors,
cache hits.

Exit code 2 and no result line: no TPU, fewer or more chips than the cell
asks for, an unknown cell, or a checkout without the program.
"""

import time

_STARTED = time.perf_counter()  # before the heavy imports: they are set-up

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def refuse(message):
    print(f"benchmarks/run.py: {message}", file=sys.stderr)
    return 2


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks.harness import cells

    try:
        cell = cells.load_cell(args.workload)
        seconds = args.seconds
        if seconds is None:
            seconds = cells.load_benchmark()["run_seconds"]
    except cells.CellError as e:
        return refuse(str(e))

    try:
        import jax
        import bluefog_tpu  # noqa: F401
    except ImportError as e:
        return refuse(f"this checkout does not hold the program: {e}")
    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        return refuse(f"no accelerator: {e}")
    if backend != "tpu":
        return refuse(
            f"no TPU: jax.default_backend() is {backend!r}; a cell is "
            "measured on the chip or not at all"
        )
    if len(jax.devices()) != cell.chips:
        return refuse(
            f"cell {cell.name!r} asks for {cell.chips} chips, this machine "
            f"holds {len(jax.devices())}"
        )

    from benchmarks.harness import bench

    def info(line):
        print(json.dumps(line), flush=True)

    result = bench.run_cell(
        cell, args.seed, seconds, bool(args.trace), bench.Spans(_STARTED), info
    )
    print(json.dumps(result), flush=True)
    for name, (read, limit) in result["compared"].items():
        print(f"compared {name}: {read!r} limit {limit!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
