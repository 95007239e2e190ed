# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Every ``tools/*.py`` CLI answers ``--help`` fast and exits 0.

The tools are the operator surface of the observability stack; a tool
whose ``--help`` initializes a jax backend (or worse, starts running)
fails the 3 a.m. test. The jax-heavy profilers gate their CLI parse
BEFORE the heavy imports, so this smoke test doubles as the
lazy-import regression guard — the time bound is what pins it.
"""

import glob
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = sorted(
    p for p in glob.glob(os.path.join(REPO, "tools", "*.py"))
    if os.path.basename(p) != "__init__.py"
)

# Hard kill bound for the subprocess itself...
HELP_TIMEOUT_S = 60.0


def _help_wall_bound_s() -> float:
    """The bound that actually pins the --help-before-jax-import rule,
    for EVERY tool: an argparse-before-jax --help is interpreter
    startup + argparse (~0.12 s measured), so the rule is SUB-SECOND.
    A tool that re-grows a module-level ``import jax`` (+ flax/optax +
    backend init) lands at several seconds even on a fast host. The
    bound scales off a measured bare-interpreter baseline so an
    overloaded CI host degrades the bound, never fakes a regression —
    but on any healthy host it stays at the 1-second rule."""
    t0 = time.perf_counter()
    subprocess.run([sys.executable, "-c", "pass"], capture_output=True)
    baseline = time.perf_counter() - t0
    return max(1.0, 8.0 * baseline)


HELP_WALL_BOUND_S = _help_wall_bound_s()


@pytest.mark.parametrize(
    "tool", TOOLS, ids=[os.path.basename(t) for t in TOOLS]
)
def test_tool_help_exits_zero(tool):
    # best of two runs: one transient CI load spike during a single
    # subprocess must not read as a lazy-import regression, while a
    # genuine module-level `import jax` (seconds, every run) still
    # fails both attempts
    elapsed = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, tool, "--help"],
            capture_output=True, text=True, timeout=HELP_TIMEOUT_S,
            cwd=REPO,
        )
        elapsed = min(elapsed, time.perf_counter() - t0)
        assert proc.returncode == 0, (
            f"{os.path.basename(tool)} --help exited "
            f"{proc.returncode}: {proc.stderr[-400:]}"
        )
        assert proc.stdout.strip(), (
            f"{os.path.basename(tool)} --help printed nothing"
        )
        if elapsed < HELP_WALL_BOUND_S:
            break
    assert elapsed < HELP_WALL_BOUND_S, (
        f"{os.path.basename(tool)} --help took {elapsed:.2f}s (best "
        f"of 2) against the {HELP_WALL_BOUND_S:.1f}s sub-second-rule "
        "bound — a CLI gate probably slipped below a heavy import"
    )


def test_tools_enumerated():
    """The glob found the expected operator surface (a rename that
    drops a tool from the smoke test should be deliberate)."""
    names = {os.path.basename(t) for t in TOOLS}
    assert {
        "autotune_report.py", "bench_diff.py", "doctor.py",
        "federation_report.py", "fleet_report.py",
        "fleetsim_report.py", "memory_report.py",
        "metrics_report.py",
        "shard_plan.py", "slo_report.py", "staleness_report.py",
        "trace_merge.py",
        "hlo_overlap_scan.py", "hlo_dump.py", "perf_probe.py",
    } <= names
