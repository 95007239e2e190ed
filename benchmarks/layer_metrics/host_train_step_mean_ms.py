"""host_train_step_mean_ms — layer: optimizer_path; unit ms; moves
``throughput_per_chip`` where the host is the limit; every cell. Mean time
of a whole ``train_step`` call (the root span ``bf.train_step``:
``step_resolve`` to ``step_end`` in the flight ring) over the untraced
window's calls. Beside the outside median ``host_dispatch_ms`` it says
whether the time is in a tail; less the four phase means it is the epilogue
(state writes, the six ``observe_step`` hooks)."""

from benchmarks.harness import scopes


def read(run):
    stats = scopes.host_phases(run)
    return stats and stats["mean"]
