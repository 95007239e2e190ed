"""host_train_step_p90_ms — layer: optimizer_path; unit ms; moves
``throughput_per_chip`` where the host is the limit; every cell. 90th
percentile of a whole ``train_step`` call (root span ``bf.train_step``) over
the untraced window's 85-200 calls: the highest percentile with about ten
calls beyond it. Far above ``host_train_step_mean_ms``: the time is in a
tail of slow calls."""

from benchmarks.harness import scopes


def read(run):
    stats = scopes.host_phases(run)
    return stats and stats["p90"]
