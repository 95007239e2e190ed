#!/usr/bin/env python
# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Render the live two-level federation fabric from a health dump.

The hierarchical fabric (:mod:`bluefog_tpu.federation`,
docs/federation.md) publishes its live state in a health dump's
``federation`` block (``/fleet``, ``bf.health``): the pod layout, the
elected gateways, the DCN period and wire, and the predicted composed
consensus rate. This tool renders that block as the operator's first
read.

Usage::

    python tools/federation_report.py --health /tmp/health.json
    python tools/federation_report.py --health /tmp/health.json --json

No jax import, no live fabric needed. Exit status 0 on a parseable
input set, 2 when nothing could be read.
"""

import argparse
import json
import sys
from typing import Optional


def load_health_federation(path: str) -> Optional[dict]:
    """The ``federation`` block of a health dump (``/fleet`` JSON or
    ``HealthPlane.dump`` artifact), when one is present."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if isinstance(doc, dict):
        return doc.get("federation")
    return None


def build_report(health_fed: Optional[dict]) -> dict:
    return {"live": health_fed}


def render(report: dict) -> str:
    live = report["live"]
    if not live:
        return "no federation block (BLUEFOG_PODS unset on that run)"
    layout = live.get("layout", {})
    return (
        "== live fabric (health dump) ==\n"
        f"{layout.get('n_pods')} pods over {layout.get('size')} "
        f"ranks (spec {layout.get('spec')!r}); gateways "
        f"{live.get('gateways')}; DCN every "
        f"{live.get('dcn_period')} steps on {live.get('dcn_wire')}"
        f"; predicted rate {live.get('predicted_rate')}"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "--health", action="append", default=[],
        help="health dump JSON (bf.health /fleet artifact) whose "
             "federation block describes the LIVE fabric; repeatable",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="emit the joined report as JSON instead of the table",
    )
    args = ap.parse_args(argv)

    readable = 0
    health_fed = None
    for path in args.health:
        fed = load_health_federation(path)
        if fed is not None:
            health_fed = fed
        readable += 1
    if not readable:
        print("no readable inputs", file=sys.stderr)
        return 2
    report = build_report(health_fed)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
