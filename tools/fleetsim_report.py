#!/usr/bin/env python
# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Reconstruct a fleet-simulator storm timeline from its event dump.

The fleet simulator (:mod:`bluefog_tpu.fleetsim`, docs/fleetsim.md)
appends one JSON line per virtual repair, rejoin, advisory and decision
to its event dump (``BLUEFOG_FLEETSIM_FILE``). This tool joins one or
more dumps into the storm timeline an operator reads first:

- the **storm timeline** (step-ordered repairs with detected ranks,
  survivor count, epoch, topology version, per-event cost; whole-pod /
  whole-region outages rendered as their own loss class with the
  gateway re-election inline; advisories inline; the worst event
  flagged),
- the **decision block** (controller candidates, chosen topology,
  measured decision latency),
- the verdict line: repair events.

Usage::

    python tools/fleetsim_report.py --dump /tmp/fleetsim.jsonl
    python tools/fleetsim_report.py --dump /tmp/fleetsim.jsonl --json

No jax import, no live fleet needed. Exit status 0 on a parseable
input set, 2 when nothing could be read.
"""

import argparse
import json
import sys
from typing import List


def load_lines(path: str) -> List[dict]:
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return rows


def build_report(rows: List[dict]) -> dict:
    decisions = [r for r in rows if r.get("metric") == "fleetsim_decision"]
    repairs = sorted(
        (r for r in rows if r.get("metric") == "fleetsim_repair"),
        key=lambda r: r.get("step", 0),
    )
    rejoins = sorted(
        (r for r in rows if r.get("metric") == "fleetsim_rejoin"),
        key=lambda r: r.get("step", 0),
    )
    advisories = [
        r for r in rows if r.get("metric") == "fleetsim_advisory"
    ]
    worst = None
    for r in repairs:
        if worst is None or r.get("event_ms", 0) > worst.get("event_ms", 0):
            worst = r
    return {
        "decisions": decisions,
        "repairs": repairs,
        "rejoins": rejoins,
        "advisories": advisories,
        "worst_event": worst,
        "verdict": {"repair_events": len(repairs)},
    }


def _loss_label(r: dict) -> str:
    """A whole-pod or whole-region outage must read as its own class in
    the storm timeline — a 16-rank pod loss is operationally one event
    (gateway re-election, inter-pod renormalization), not 16 lines of
    scattered churn (``bluefog_tpu.fleetsim.classify_loss``)."""
    cls = r.get("loss_class")
    if cls == "pod_loss":
        return f"  [POD LOSS: pods {r.get('pods_lost')}]"
    if cls == "region_loss":
        region = r.get("region")
        span = f" ranks {region[0]}-{region[1]}" if region else ""
        return f"  [REGION LOSS:{span}]"
    if cls == "storm":
        return "  [storm]"
    return ""


def render(report: dict) -> str:
    out = []
    if report["repairs"]:
        out.append("== repair timeline ==")
        for r in report["repairs"]:
            flag = " <-- worst" if r is report["worst_event"] else ""
            out.append(
                f"step {r['step']:>6}: -{len(r.get('detected', []))} "
                f"ranks, live={r['live']}, epoch={r['epoch']}, "
                f"topo v{r['topo_version']}, {r['event_ms']:.4f} ms"
                f"{_loss_label(r)}{flag}"
            )
            if r.get("gateway_change"):
                out.append(
                    f"        gateways re-elected: {r.get('gateways')}"
                )
        out.append("")
    for r in report["rejoins"]:
        out.append(f"step {r['step']:>6}: rank {r['rank']} rejoined, "
                   f"live={r['live']}")
    for a in report["advisories"]:
        out.append(f"advisory @{a.get('step')}: {a.get('kind')}")
    for d in report["decisions"]:
        out.append("== decision ==")
        out.append(
            f"n_live={d['n_live']} chosen={d['chosen']} "
            f"latency={d['decision_ms']} ms"
        )
        for name, cand in d.get("candidates", {}).items():
            spec = cand.get("spectral", {})
            out.append(
                f"  {name:>8}: rate={cand['rate']:.6f} "
                f"rounds={cand['rounds']} engine={spec.get('engine')} "
                f"matvecs={spec.get('matvecs')}"
            )
    v = report["verdict"]
    out.append("")
    out.append(f"verdict: repair_events={v['repair_events']}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "--dump", action="append", default=[],
        help="fleetsim JSONL event dump (BLUEFOG_FLEETSIM_FILE); "
             "repeatable",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="emit the joined report as JSON instead of the table",
    )
    args = ap.parse_args(argv)

    rows: List[dict] = []
    readable = 0
    for path in args.dump:
        try:
            rows.extend(load_lines(path))
            readable += 1
        except OSError as e:
            print(f"unreadable: {path}: {e}", file=sys.stderr)
    if not readable:
        print("no readable inputs", file=sys.stderr)
        return 2
    report = build_report(rows)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
