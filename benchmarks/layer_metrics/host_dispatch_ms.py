"""host_dispatch_ms — layer: optimizer_path; unit ms; moves
``throughput_per_chip`` once the device is no longer the limit (today it
predicts no move); every cell. Median time for ``train_step(...)`` to
*return* in the untraced window: key building, flight events, the six
``observe_step`` hooks and the enqueue."""

import statistics


def read(run):
    if not run.dispatch_s:
        return None
    return 1e3 * statistics.median(run.dispatch_s)
