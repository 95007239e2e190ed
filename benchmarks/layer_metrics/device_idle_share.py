"""device_idle_share — layer: device; share of 1; moves
``throughput_per_chip``; every cell. 1 - (union of the instructions'
intervals / traced window), mean over the chips. The window runs from a
chip's first traced instruction to its last, so it holds the gap at each
block's sync and none before the first step."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_share()
