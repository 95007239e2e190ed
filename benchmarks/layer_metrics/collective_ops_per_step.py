"""collective_ops_per_step — layer: collectives; a count; moves
``throughput_per_chip``; cells with more than one chip. Collective
instructions *executed* per step and chip in the trace (a ``-start`` /
``-done`` pair counts once). The compiled step's static count (earlier
``hlo`` line) holds every branch of a ``lax.switch`` schedule; a step runs
one. A count that must repeat exactly."""

from benchmarks.harness import trace_reduce


def read(run):
    if run.trace is None or run.n == 1:
        return None
    executed = run.trace.per_device_mean(
        lambda d: sum(
            1 for op in d.ops
            if trace_reduce.is_collective(op.opcode)
            and not op.opcode.endswith("-done")
        )
    )
    return executed / run.traced_steps
