"""collective_exposed_ms — layer: collectives (``collective/inner.py``,
``plan.py``); unit ms; moves ``throughput_per_chip``; cells with more than
one chip. Per step and chip, the time the core spends *in* collective
instructions (``-start``, ``-done`` and synchronous ones): the core runs one
instruction at a time, so this is the part of the transfer that no other
instruction hides; what is hidden lies between a ``-start`` and its ``-done``
and shows as other instructions' time. Mean over the chips."""


def read(run):
    kinds = run.device_ms_by_kind()
    if kinds is None or run.n == 1:
        return None
    return kinds["collective"]
