"""host_resolve_ms — layer: optimizer_path (``optimizers.py``
``make_train_step.train_step``); unit ms; moves ``throughput_per_chip``
where the host is the limit; every cell. Mean time of the ``resolve`` phase
of a ``train_step`` call over the untraced window's calls (sum / calls:
throughput follows the mean, and the phases add up to
``host_train_step_mean_ms`` less the epilogue): context, ``_comm_now``,
``_resolve_dispatch`` (wire payload, gossip key and function), the shard /
scatter prologue, delay state. Read from the program's flight ring
(``step_resolve`` to ``step_key``; the span ``bf.train_step/resolve`` in an
open profiler session), through ``harness/scopes.py``; ``None`` off the chip
or from a program without the phases."""

from benchmarks.harness import scopes


def read(run):
    stats = scopes.host_phases(run)
    return stats and stats["resolve"]
