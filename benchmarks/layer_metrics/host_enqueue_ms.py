"""host_enqueue_ms — layer: optimizer_path (``optimizers.py``
``make_train_step.train_step``); unit ms; moves ``throughput_per_chip``
where the host is the limit; every cell. Mean time of the ``enqueue`` phase
of a ``train_step`` call over the untraced window's calls (sum / calls:
throughput follows the mean, and the phases add up to
``host_train_step_mean_ms`` less the epilogue): the jitted call: the pjit
fast path over hundreds of buffers, and any blocking. Read from the
program's flight ring (``step_enqueue`` to ``step_dispatched``; the span
``bf.train_step/enqueue`` in an open profiler session), through
``harness/scopes.py``; ``None`` off the chip or from a program without the
phases."""

from benchmarks.harness import scopes


def read(run):
    stats = scopes.host_phases(run)
    return stats and stats["enqueue"]
