"""compile_s — layer: optimizer_path (``optimizers.py``); unit s; moves
``setup_s``; every cell. The first call of the fused step less a warm one:
trace, lowering and the XLA compile, or the load from a warm cache (the
earlier ``compile_events`` line says which)."""


def read(run):
    if len(run.warm_step_s) < 2:
        return None
    return run.warm_step_s[0] - run.warm_step_s[-1]
