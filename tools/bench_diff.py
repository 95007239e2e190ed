# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Pair two bench evidence artifacts and attribute their deltas.

The perf-attribution harness of ROADMAP item 1: round-over-round bench
movements are only meaningful when the artifacts are *comparable* —
same jax/jaxlib, same CPU, same timing method — and the delta clears the
run's own disclosed noise floor. This tool mechanizes that judgment:

- parses both artifacts (driver-wrapper ``{"tail": ...}`` JSON or raw
  JSONL), builds one *cell* per (metric, identifying-config) pair;
- pairs cells across the artifacts by metric + config, flags cells
  present on one side only;
- checks the PR-4 provenance line on both sides and flags
  non-comparability: jax/jaxlib mismatch, CPU model mismatch,
  timing-method mismatch, or a missing provenance block (artifacts
  predating PR-4 — their deltas are attributed to "harness unknown",
  never to the code);
- computes per-cell deltas with a noise floor taken from the
  measurements' own disclosed spread (best-of-N ``value``/``median``/
  ``min`` windows, ``aa_noise_pct`` A/A lines) — a delta inside the
  floor is reported as noise, not regression;
- consumes the ``ambient_anchor`` line each round emits (fixed bf16
  matmul TFLOP/s) to classify headline deltas: a ``value`` that moved
  while its anchor-normalized ``vs_anchor`` held still is AMBIENT host
  drift; a delta that survives anchor normalization is real.

``--check`` exits nonzero when either artifact is structurally unusable
(no JSON lines, ambiguous duplicate cells), the mode CI wires in so
future artifact pairs stay machine-comparable by default.

Usage::

    python tools/bench_diff.py OLD.json NEW.json [--json]
        [--check] [--note "..."] [--out report.json]
"""

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# Identifying config keys: integers that select WHAT was measured (not
# how fast it was). Everything string/bool-valued is identity by default.
CONFIG_INT_KEYS = {
    "n", "n_workers", "seq_len", "heads", "head_dim", "layers", "dim",
    "batch", "payload_elems", "payload_bytes", "interval",
    "workers_on_chip", "rounds", "shortcut_rounds", "naive_rounds",
    "optimized_rounds", "lower_bound", "hlo_collective_permutes",
    "params_m", "auto_chunks", "kill_step",
}

# Harness metadata: neither identity nor a measurement to diff.
# anchor_tflops is the run-level ambient anchor replicated into the
# headline cell — diffing it as a measurement would report pure host
# drift as "deltas beyond the noise floor" while the classifier
# simultaneously (and correctly) calls the same movement ambient.
HARNESS_KEYS = {
    "windows", "degenerate", "degenerate_cells", "unit",
    "harness_validation", "rejected", "anchor_tflops",
    # host-memory context on the provenance line (and any row that
    # replicates it): describes the measuring process, not the thing
    # measured — never a comparability break
    "peak_rss_bytes",
}

# Derived normalization fields that arrived WITH the anchor feature:
# absent from every pre-anchor artifact, so a one-sided appearance is
# the tooling gaining a column, not a timing-harness change — it only
# disables ambient classification for that pair.
ANCHOR_DERIVED = {"vs_anchor"}

# Wire-byte accounting columns that arrived with the quantized-wire
# evidence family (scale-sidecar-inclusive pricing): like ANCHOR_DERIVED
# they are static accounting derived from the config, not timed
# measurements, so their one-sided appearance against an older artifact
# is the tooling gaining a column — never a timing-harness change.
WIRE_DERIVED = {
    "wire_bytes_per_step", "wire_bytes_per_round", "wire_bytes_int8",
    "wire_bytes_int4", "wire_bytes_int4_ef", "effective_compression_ratio",
    "wire_reduction_int4_vs_int8",
}

# Mixing-observatory columns that arrived with the fleet health plane
# (BENCH_MODE=health): spectral predictions and fitted decay rates are
# derived analysis, not timed measurements, so a one-sided appearance
# against a pre-health artifact is the tooling gaining a column —
# never a timing-harness change.
HEALTH_DERIVED = {
    "predicted_rate", "measured_rate", "mixing_efficiency",
    "rate_ratio", "time_to_eps_steps", "fleet_residual",
}

# Autotune controller columns that arrived with the closed-loop
# evidence family (BENCH_MODE=autotune): decision counts and predicted
# objectives are controller bookkeeping derived from the telemetry, not
# timed measurements, so their one-sided appearance against a
# pre-autotune artifact is the tooling gaining a column — never a
# timing-harness change.
AUTOTUNE_DERIVED = {
    "decisions", "swaps", "rollbacks", "holds",
    "objective_before_s", "objective_after_s", "predicted_gain_frac",
    "recovered_step_ratio", "recovered_efficiency",
    "autotune_overhead_pct",
}

# Asynchronous-gossip columns that arrived with the async evidence
# family (BENCH_MODE=async): participation ratios, mass-drift pins and
# gate statistics are cadence-replay bookkeeping derived from engine
# counters, not timed measurements, so their one-sided appearance
# against a pre-async artifact is the tooling gaining a column — never
# a timing-harness change.
ASYNC_DERIVED = {
    "fleet_ratio_async", "fleet_ratio_sync", "local_steps",
    "mass_drift_max", "stale_drops", "age_max",
    "dist_to_opt_sync", "dist_to_opt_async",
    "fresh_edges_within_bound",
}

# Weight-update-sharding columns that arrived with the shard evidence
# family (BENCH_MODE=shard): state-byte accounting, shard ratios and
# redistribution pricing are layout arithmetic derived from the config,
# not timed measurements, so their one-sided appearance against a
# pre-shard artifact is the tooling gaining a column — never a
# timing-harness change.
SHARD_DERIVED = {
    "state_bytes_replicated", "state_bytes_sharded",
    "state_bytes_measured", "shard_ratio", "pad_ratio",
    "gather_bytes_per_step", "budget_bytes", "slot_elems",
    "traj_max_dev",
    # ZeRO-2 gradient-leg columns (BLUEFOG_SHARD_GRADS): reduced-
    # gradient buffer bytes and reduce-scatter wire pricing are the
    # same layout arithmetic, extended down the memory axis.
    "grad_bytes_replicated_measured", "grad_bytes_sharded_measured",
    "grad_ratio_measured", "grad_pad_ratio", "scatter_bytes_per_step",
    "allreduce_bytes_per_step", "scatter_plus_gather",
    "allreduce_plus_gather", "zero2_max_dev", "zero2_oracle_max_dev",
}

# Memory-observatory columns that arrived with the memory evidence
# family (BENCH_MODE=memory): buffer-census byte accounting, analytic
# reconciliation residuals and XLA temp-size readings are memory
# bookkeeping derived from the program/config, not timed measurements,
# so their one-sided appearance against a pre-memory artifact is the
# tooling gaining a column — never a timing-harness change.
MEMORY_DERIVED = {
    "live_bytes_per_rank", "measured_state_bytes",
    "analytic_state_bytes", "reconcile_rel_err", "temp_bytes_measured",
    "temp_bytes_analytic", "full_width_bytes", "headroom_bytes",
}

# Fused-wire-kernel columns that arrived with the quant_kernel rows
# (BLUEFOG_WIRE_KERNELS, BENCH_MODE=quant): kernel-vs-composite scratch
# readings, analytic fused-staging models and step-time pairings are
# compile-time/memory bookkeeping new to the kernel evidence, so their
# one-sided appearance against a pre-kernel QUANT_EVIDENCE artifact is
# the tooling gaining a column — never a comparability break.
WIRE_KERNEL_DERIVED = {
    "temp_bytes_composite", "temp_bytes_fused", "temp_bytes_fp32",
    "temp_bytes_analytic_fused", "temp_bytes_analytic_composite",
    "step_time_composite_us", "step_time_fused_us",
}

# Fleet-scale columns that arrived with the fleetscale evidence family
# (BENCH_MODE=fleetscale): per-membership-event control-plane costs,
# growth-exponent fits, the disclosed dense-baseline extrapolation and
# the decision-latency/agreement readings are simulator bookkeeping
# derived from the control plane (no device dispatch ever runs), so
# their one-sided appearance against a pre-fleetsim artifact is the
# tooling gaining a column — never a timing-harness change.
FLEETSCALE_DERIVED = {
    "event_ms_mean", "event_ms_max", "growth_exponent",
    "dense_growth_exponent", "dense_at_1024_ms_extrapolated",
    "sparse_at_1024_ms", "speedup_at_1024_extrapolated",
    "stale_dispatches", "worst_event_ms", "decision_ms",
    "worst_abs_diff",
}

# Federation columns that arrived with the federate evidence family
# (BENCH_MODE=federate): composed consensus-rate predictions vs host
# measurements, per-leg wire-byte totals, matched-rate cut ratios and
# pod-loss repair bookkeeping are control-plane/accounting readings
# derived from the two-level fabric (the one device leg reads counters,
# not timings), so their one-sided appearance against a pre-federation
# artifact is the tooling gaining a column — never a comparability
# break.
FEDERATE_DERIVED = {
    "predicted_rate", "measured_rate", "abs_err", "chosen_period",
    "dcn_cut_ratio_matched", "fed_dcn_bytes_per_step",
    "flat_dcn_bytes_per_step_matched", "ici_wire_bytes_per_step",
    "ici_wire_bytes", "dcn_wire_bytes", "consensus_spread",
    "measured_rate_fed", "measured_rate_flat_dense",
    "measured_rate_flat_matched",
}

# SLO-engine columns that arrived with the slo evidence family
# (BENCH_MODE=slo): burn rates, error-budget accounts, page-bound
# arithmetic and canary deviation readings are budget bookkeeping
# derived from sampled flags (the one timed reading, the overhead
# rotation, carries its own A/A control), so their one-sided
# appearance against a pre-slo artifact is the tooling gaining a
# column — never a timing-harness change.
SLO_DERIVED = {
    "page_sample_bound", "samples_to_page", "aa_false_alarms",
    "hygiene_max_abs_z", "bad_samples", "clean_max_dev",
    "lossy_max_dev", "max_burn_err_vs_oracle",
    "max_budget_err_vs_oracle", "slo_overhead_pct", "worst_burn",
    "budget_remaining", "canary_programs",
}

# Every one-sided-tolerated derived column set.
TOOLING_DERIVED = (
    ANCHOR_DERIVED | WIRE_DERIVED | HEALTH_DERIVED | AUTOTUNE_DERIVED
    | ASYNC_DERIVED | SHARD_DERIVED | MEMORY_DERIVED
    | WIRE_KERNEL_DERIVED | FLEETSCALE_DERIVED | FEDERATE_DERIVED
    | SLO_DERIVED
)

PROVENANCE_COMPARE = ("jax", "jaxlib", "cpu_model", "timing_method")


def parse_artifact(path: str) -> Tuple[List[dict], List[str]]:
    """JSON lines of one artifact + structural problems found."""
    problems: List[str] = []
    with open(path) as f:
        text = f.read()
    lines: List[dict] = []
    try:
        wrapper = json.loads(text)
        if isinstance(wrapper, dict) and "tail" in wrapper:
            raw = wrapper["tail"].splitlines()
            if isinstance(wrapper.get("parsed"), dict):
                # the driver's parsed headline — covered by tail, but a
                # truncated tail may hold ONLY the headline
                raw.append(json.dumps(wrapper["parsed"]))
        elif isinstance(wrapper, list):
            raw = [json.dumps(o) for o in wrapper]
        else:
            raw = text.splitlines()
    except ValueError:
        raw = text.splitlines()
    for line in raw:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            lines.append(obj)
    if not lines:
        problems.append(f"{path}: no metric JSON lines found")
    return lines, problems


def cell_identity(obj: dict) -> Tuple:
    ident = []
    for k in sorted(obj):
        if k in ("metric",) or k in HARNESS_KEYS:
            continue
        v = obj[k]
        if isinstance(v, str) or isinstance(v, bool) or k in CONFIG_INT_KEYS:
            ident.append((k, v))
    return (obj["metric"], tuple(ident))


def cell_values(obj: dict) -> Dict[str, float]:
    out = {}
    for k, v in obj.items():
        if k in ("metric",) or k in HARNESS_KEYS or k in CONFIG_INT_KEYS:
            continue
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[k] = float(v)
    return out


def noise_floor_pct(obj: dict) -> Optional[float]:
    """The cell's own disclosed spread, as a percent of its headline
    value: best-of-N windows publish value (best) + median + min, A/A
    cells publish aa_noise_pct directly. None when nothing is
    disclosed — the delta is then unattributable, not 'significant'."""
    if "aa_noise_pct" in obj:
        return float(obj["aa_noise_pct"])
    v = obj.get("value")
    lo = obj.get("min")
    if isinstance(v, (int, float)) and isinstance(lo, (int, float)) and lo:
        return abs(v - lo) / abs(lo) * 100.0
    return None


def build_cells(lines: List[dict], problems: List[str], path: str):
    cells: Dict[Tuple, dict] = {}
    provenance = None
    anchor = None
    for obj in lines:
        if obj.get("metric") == "provenance":
            provenance = obj
            continue
        if obj.get("metric") == "ambient_anchor":
            # the ambient-drift anchor is run metadata, like
            # provenance: consumed for delta classification, never
            # diffed as a cell
            if isinstance(obj.get("tflops"), (int, float)):
                anchor = obj
            continue
        key = cell_identity(obj)
        if key in cells:
            problems.append(
                f"{path}: duplicate cell {key[0]} {dict(key[1])} — "
                "ambiguous pairing"
            )
        cells[key] = obj
    return cells, provenance, anchor


def classify_ambient(entry: dict, floor: Optional[float],
                     anchor_delta_pct: Optional[float]) -> None:
    """Classify a headline delta as ambient vs real using the anchor
    (ROADMAP item 1 / VERDICT "Next round" #1): ``vs_anchor`` is the
    headline normalized by the run's own ambient-compute anchor, so a
    ``value`` that moved while ``vs_anchor`` held still is the HOST
    moving, not the code. Writes ``headline_delta_class`` onto the
    entry when both fields were diffed."""
    deltas = entry.get("deltas", {})
    dv = deltas.get("value")
    da = deltas.get("vs_anchor")
    if dv is None or da is None or dv.get("delta_pct") is None or (
        da.get("delta_pct") is None
    ):
        return
    eff_floor = max(floor if floor is not None else 0.0, 2.0)
    value_moved = abs(dv["delta_pct"]) > eff_floor
    anchored_moved = abs(da["delta_pct"]) > eff_floor
    if not value_moved:
        cls = "noise (value within floor)"
    elif not anchored_moved:
        cls = "ambient (value tracks the anchor: host drift)"
    else:
        cls = "real (delta survives anchor normalization)"
    entry["headline_delta_class"] = cls
    if anchor_delta_pct is not None:
        entry["ambient_anchor_delta_pct"] = round(anchor_delta_pct, 2)


def compare(path_a: str, path_b: str, notes: List[str]) -> dict:
    problems: List[str] = []
    lines_a, pa = parse_artifact(path_a)
    lines_b, pb = parse_artifact(path_b)
    problems += pa + pb
    cells_a, prov_a, anchor_a = build_cells(lines_a, problems, path_a)
    cells_b, prov_b, anchor_b = build_cells(lines_b, problems, path_b)
    anchor_delta_pct = None
    if anchor_a and anchor_b and anchor_a.get("n") == anchor_b.get("n"):
        ta, tb = anchor_a["tflops"], anchor_b["tflops"]
        if ta:
            anchor_delta_pct = (tb - ta) / ta * 100.0

    incomparable: List[str] = []
    if prov_a is None:
        incomparable.append(
            f"{os.path.basename(path_a)} has no provenance line (predates "
            "the PR-4 provenance contract): platform/timing attribution "
            "unknown"
        )
    if prov_b is None:
        incomparable.append(
            f"{os.path.basename(path_b)} has no provenance line (predates "
            "the PR-4 provenance contract): platform/timing attribution "
            "unknown"
        )
    if prov_a and prov_b:
        for k in PROVENANCE_COMPARE:
            va, vb = prov_a.get(k, ""), prov_b.get(k, "")
            if va != vb:
                incomparable.append(
                    f"provenance mismatch on {k!r}: {va!r} vs {vb!r}"
                )

    report_cells = []
    for key in sorted(set(cells_a) | set(cells_b), key=str):
        metric, ident = key
        a, b = cells_a.get(key), cells_b.get(key)
        entry = {"metric": metric, "config": dict(ident)}
        if a is None or b is None:
            entry["status"] = "unpaired"
            entry["present_in"] = (
                os.path.basename(path_a) if b is None
                else os.path.basename(path_b)
            )
            # a cell appearing/disappearing between rounds is itself a
            # harness change worth flagging for headline metrics
            report_cells.append(entry)
            continue
        va, vb = cell_values(a), cell_values(b)
        shared = sorted(set(va) & set(vb))
        only_a = sorted(set(va) - set(vb) - TOOLING_DERIVED)
        only_b = sorted(set(vb) - set(va) - TOOLING_DERIVED)
        floors = [
            f for f in (noise_floor_pct(a), noise_floor_pct(b))
            if f is not None
        ]
        floor = max(floors) if floors else None
        deltas = {}
        for k in shared:
            if va[k] == 0:
                deltas[k] = {"a": va[k], "b": vb[k], "delta_pct": None}
                continue
            pct = (vb[k] - va[k]) / abs(va[k]) * 100.0
            deltas[k] = {
                "a": va[k],
                "b": vb[k],
                "delta_pct": round(pct, 2),
                "exceeds_noise_floor": (
                    None if floor is None else bool(abs(pct) > floor)
                ),
            }
        entry["status"] = "paired"
        entry["noise_floor_pct"] = (
            None if floor is None else round(floor, 2)
        )
        entry["deltas"] = deltas
        classify_ambient(entry, floor, anchor_delta_pct)
        if only_a or only_b:
            entry["fields_only_in_one"] = {
                "a": only_a, "b": only_b,
            }
            # a measurement field appearing/disappearing (e.g. the
            # windows/median/min spread block) marks a timing-harness
            # change — the delta cannot be pinned on the code
            entry["harness_change"] = True
        comparable = not incomparable and not (only_a or only_b)
        if not comparable:
            entry["verdict"] = "non-comparable"
            entry["reasons"] = incomparable + (
                ["measurement fields changed between rounds "
                 "(timing-harness change)"] if (only_a or only_b) else []
            )
        elif floor is None:
            entry["verdict"] = "comparable, no disclosed noise floor"
        else:
            sig = [
                k for k, d in deltas.items()
                if d.get("exceeds_noise_floor")
            ]
            entry["verdict"] = (
                f"comparable; deltas beyond the {round(floor, 2)}% noise "
                f"floor: {sig}" if sig
                else f"comparable; all deltas within the "
                     f"{round(floor, 2)}% noise floor"
            )
        report_cells.append(entry)

    return {
        "a": path_a,
        "b": path_b,
        "provenance_a": prov_a,
        "provenance_b": prov_b,
        "ambient_anchor_a": anchor_a,
        "ambient_anchor_b": anchor_b,
        "ambient_anchor_delta_pct": (
            None if anchor_delta_pct is None
            else round(anchor_delta_pct, 2)
        ),
        "comparability_problems": incomparable,
        "structural_problems": problems,
        "cells": report_cells,
        "notes": notes,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("artifact_a")
    ap.add_argument("artifact_b")
    ap.add_argument("--json", action="store_true",
                    help="print the full JSON report to stdout")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero on structurally unusable artifacts")
    ap.add_argument("--note", action="append", default=[],
                    help="annotation(s) embedded in the report")
    ap.add_argument("--out", help="also write the JSON report to this path")
    args = ap.parse_args(argv)

    report = compare(args.artifact_a, args.artifact_b, args.note)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
            f.write("\n")
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        probs = report["comparability_problems"]
        print(f"bench_diff: {args.artifact_a} vs {args.artifact_b}")
        if probs:
            print("NON-COMPARABLE:")
            for p in probs:
                print(f"  - {p}")
        for cell in report["cells"]:
            name = cell["metric"]
            cfg = {k: v for k, v in cell["config"].items()
                   if k not in ("unit",)}
            if cell["status"] == "unpaired":
                print(f"  {name} {cfg}: only in {cell['present_in']}")
                continue
            print(f"  {name} {cfg}: {cell['verdict']}")
            if cell.get("headline_delta_class"):
                print(
                    f"    anchor classification: "
                    f"{cell['headline_delta_class']}"
                )
            for k, d in cell.get("deltas", {}).items():
                if d.get("delta_pct") is not None:
                    print(
                        f"    {k}: {d['a']} -> {d['b']} "
                        f"({d['delta_pct']:+.2f}%)"
                    )
    if args.check and report["structural_problems"]:
        for p in report["structural_problems"]:
            print(f"CHECK FAILED: {p}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
