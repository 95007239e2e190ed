"""collective_inflight_ms — layer: collectives; unit ms; moves
``throughput_per_chip``; cells with more than one chip. Per step and chip,
the time during which at least one collective is in flight (the union of
the ``Async XLA Ops`` events from each ``-start`` to its ``-done``). Beside
``collective_exposed_ms``: in flight less exposed is what other
instructions hide. The v5e's profiler writes that line for one chip of the
four only (PR 22), so this is the mean over the chips that have it."""

from benchmarks.harness import trace_reduce


def read(run):
    if run.trace is None or run.n == 1:
        return None
    in_flight = [
        trace_reduce.length(
            (op.start, op.end) for op in d.in_flight
            if trace_reduce.is_collective(op.opcode)
        )
        for d in run.trace.devices if d.in_flight
    ]
    if not in_flight:
        return None
    return sum(in_flight) / len(in_flight) / 1e6 / run.traced_steps
