"""init_s — layer: entry (``context.py``); unit s; moves ``setup_s``; every
cell. The harness's span round ``bf.init()``: mesh, topology, compile-cache
placement, the ten tiers' ``on_init`` hooks."""


def read(run):
    return run.spans.seconds("init")
