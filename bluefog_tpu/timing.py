# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Measurement helpers shared by bench.py, tools/, and
:mod:`bluefog_tpu.scaling`.

:func:`settle` synchronizes by reading one element back through a tiny
jitted gather that produces a FRESH device array each call
(``np.asarray`` directly on an output caches its host value on the array
object, so a second readback of the same object measures ~0), and
:func:`timed_differenced` cancels that readback's cost by differencing
two windows. Both were written for a PJRT client on which
``jax.block_until_ready`` could return before the device had finished.

On the machine the chip tool provides (TPU v5e, libtpu 0.0.34, the
process on the host that holds the chip) ``block_until_ready`` is
honest: ``chip_smoke.py`` (PR 21) times the same warm ResNet50 steps
both ways — 53-60 ms ended by ``block_until_ready``, 54-63 ms ended by
``settle`` — and a ``settle`` issued right after ``block_until_ready``
has returned waits 1-3 ms, its own dispatch and readback, not a step.
So a plain ``block_until_ready`` around the timed region is a valid
clock there; this harness stays until the benchmark (ROADMAP S1, D6)
replaces it with a median and its spread.
"""

__all__ = ["settle", "timed_differenced"]

_TAKE = None


def timed_differenced(step, steps: int, windows: int,
                      with_degenerate: bool = False):
    """Differenced-window timing: per window, time ``steps`` calls +
    settle and ``2*steps`` calls + settle; the difference is ``steps``
    calls of pure compute with the settle cost cancelled EXACTLY — the
    single-window readback correction used through round 4 cancelled it
    only in expectation and swung results several % either way.

    A window whose difference comes out ``<= 0`` (an ambient stall
    landed inside the first half) is DEGENERATE: its clamped value would
    publish as a fake ~0 time (the r05 evidence artifact's
    ``dense_fwdbwd_ms: 0.0``). Each degenerate window gets one retry;
    windows still degenerate after that are excluded from the result as
    long as at least one clean window exists. Only when EVERY window is
    degenerate do the clamped values come back, flagged.

    ``step()`` advances whatever state it closes over and returns the
    settle target (keep it SCALAR — settling a large tensor pays its
    transfer). Returns the per-call times of the clean windows,
    sorted ascending (``[0]`` is the best window; the spread is the
    honest noise disclosure). With ``with_degenerate=True`` returns
    ``(times, degenerate)`` where ``degenerate`` is True only in the
    all-windows-clamped case."""
    import time

    out = step()
    settle(out)
    settle(out)  # warm the readback path's own compile

    def one_window():
        nonlocal out
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step()
        settle(out)
        t1 = time.perf_counter()
        for _ in range(2 * steps):
            out = step()
        settle(out)
        t2 = time.perf_counter()
        return (t2 - t1) - (t1 - t0)

    diffs = []
    for _ in range(windows):
        diff = one_window()
        if diff <= 0:
            diff = one_window()  # one retry: stalls are transient
        diffs.append(diff)
    clean = sorted(d / steps for d in diffs if d > 0)
    if clean:
        return (clean, False) if with_degenerate else clean
    clamped = sorted(max(d, 1e-9) / steps for d in diffs)
    return (clamped, True) if with_degenerate else clamped


def settle(x) -> float:
    """Block until ``x`` (any array, or a pytree's leaf) is computed, by
    reading one element back through a fresh jitted gather; returns it."""
    import numpy as np
    import jax

    global _TAKE
    if _TAKE is None:
        _TAKE = jax.jit(lambda t: t.ravel()[0])
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(np.asarray(_TAKE(leaf)))
