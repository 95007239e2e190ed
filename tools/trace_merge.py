#!/usr/bin/env python
# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Fuse per-rank timelines + flight dumps into one Perfetto trace,
then attribute stragglers and reconstruct hangs.

Per-rank Chrome traces are disjoint files with unaligned clocks; flight
dumps (``bluefog_tpu.flight``, docs/flight.md) are per-process event
rings. This tool merges N of each into ONE chrome://tracing / Perfetto
JSON with a ``pid`` lane per worker rank (plus one host lane per
controller process), aligning clocks through the wall/monotonic/timeline
handshake every dump records — and then *reads* the fused record:

- **Straggler report** — per communicating step, the per-rank step
  durations, the slowest rank, its lag over the median, and the exact
  plan rounds/edges that rank's slowness delays (per-edge gossip means a
  slow peer delays only its neighbors — the per-link cost sensitivity
  the CommPlan compiler's alpha-beta model assumes, here measured).
- **Hang postmortem** — when any dump was triggered by a stall, an
  elastic SUSPECT/DEAD verdict, a crash, or SIGTERM: names the condemned
  rank(s), the last step every rank completed, and for each waiting
  neighbor the exact edge and plan round it was stalled on.

Usage::

    python tools/trace_merge.py DUMP_DIR                 # summary table
    python tools/trace_merge.py DUMP_DIR -o merged.json  # + fused trace
    python tools/trace_merge.py DUMP_DIR --report r.json --json

``DUMP_DIR`` holds ``flight_<proc>.json`` dumps and the per-process
timeline files (any other ``*.json`` that parses as a Chrome-trace
array). Collect it with ``bfrun-tpu --flight-dir`` +
``--timeline-filename`` (docs/launcher.md).

Clock model: each dump carries ``clock = {unix_ns, mono_us,
timeline_us}`` sampled at one instant. Flight event times are monotonic
(``t_us``); timeline ``ts`` are on the writer clock. Both convert to
shared wall microseconds via the dump's triple, and the merged trace is
rebased to the earliest event — so cross-process ordering is correct to
wall-clock sync (NTP-grade, adequate for >100 us straggler lags).
"""

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

__all__ = [
    "load_dir",
    "merge_trace",
    "analyze",
    "merge_and_analyze",
    "main",
]

# pid offset for controller-process host lanes (worker ranks occupy
# [0, size); offset far above any plausible mesh)
HOST_PID_BASE = 100000


# -- loading ------------------------------------------------------------------


def _proc_of_trace(path: str) -> int:
    """Per-process timeline files are named ``<prefix><index>.json``
    (timeline.maybe_init_from_env); the trailing digits are the index."""
    stem = os.path.basename(path)[: -len(".json")]
    digits = ""
    while stem and stem[-1].isdigit():
        digits = stem[-1] + digits
        stem = stem[:-1]
    return int(digits) if digits else 0


def load_dir(path: str) -> Tuple[List[dict], Dict[int, list]]:
    """Load ``flight_*.json`` dumps and Chrome-trace JSONs from a dump
    directory. Returns ``(dumps, traces)`` with ``traces`` keyed by
    process index. Unparseable files are skipped with a warning — a
    postmortem tool must degrade, not add its own crash."""
    dumps: List[dict] = []
    traces: Dict[int, list] = {}
    for f in sorted(glob.glob(os.path.join(path, "*.json"))):
        base = os.path.basename(f)
        if base.startswith("merged"):
            continue  # our own output from a previous run
        try:
            with open(f) as fh:
                obj = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"warning: skipping {base}: {e}", file=sys.stderr)
            continue
        if isinstance(obj, dict) and "events" in obj and "clock" in obj:
            dumps.append(obj)
        elif isinstance(obj, list):
            traces[_proc_of_trace(f)] = obj
        elif isinstance(obj, dict) and isinstance(
            obj.get("traceEvents"), list
        ):
            traces[_proc_of_trace(f)] = obj["traceEvents"]
    dumps.sort(key=lambda d: d.get("process_index", 0))
    return dumps, traces


# -- clock alignment ----------------------------------------------------------


def _anchors(dump: dict) -> Tuple[float, Optional[float]]:
    """(wall_us - mono_us, wall_us - timeline_us) for this process: add
    a flight ``t_us`` / timeline ``ts`` to get wall microseconds."""
    clock = dump.get("clock", {})
    wall_us = clock.get("unix_ns", 0) / 1000.0
    mono_anchor = wall_us - clock.get("mono_us", 0)
    tl_us = clock.get("timeline_us")
    tl_anchor = None if tl_us is None else wall_us - tl_us
    return mono_anchor, tl_anchor


# -- per-dump event digestion -------------------------------------------------


def _plan_by_version(dump: dict) -> Dict[int, dict]:
    """Worker-rank plans by topology version. Machine-graph plans (the
    hierarchical families) use an independent version counter and their
    node ids are machines, not ranks — matching a rank fault against one
    would fabricate edges, so they are excluded here."""
    return {
        p["topo_version"]: p
        for p in dump.get("comm_plans", [])
        if p.get("kind", "worker") == "worker"
    }


def _steps_of(dump: dict) -> List[dict]:
    """Fold step_begin/step_dispatched pairs into per-step records with
    the plan (round structure) active at each step — plan_compile events
    precede the step_begin of the dispatch that compiled them, so a
    seq-ordered walk tracks the active plan exactly."""
    plans = _plan_by_version(dump)
    active: Optional[dict] = None
    open_steps: Dict[int, dict] = {}
    out: List[dict] = []
    for e in dump.get("events", []):
        kind, data = e["kind"], e.get("data", {})
        if kind == "plan_compile":
            active = plans.get(data.get("topo_version"), active)
        elif kind == "step_begin":
            open_steps[data.get("step", -1)] = {
                "step": data.get("step", -1),
                "comm": bool(data.get("comm", True)),
                "t_begin_us": e["t_us"],
                "t_end_us": None,
                "rounds": (
                    active["n_rounds"]
                    if (active and data.get("comm", True)) else 0
                ),
                "plan": active if data.get("comm", True) else None,
            }
        elif kind == "step_dispatched":
            rec = open_steps.pop(data.get("step", -1), None)
            if rec is not None:
                rec["t_end_us"] = e["t_us"]
                out.append(rec)
    out.sort(key=lambda r: r["step"])
    return out


_INSTANT_KINDS = {
    "fault", "membership", "repair", "stall", "crash", "sigterm",
    "window_op", "compile", "build",
}


def merge_trace(dumps: List[dict], traces: Dict[int, list]) -> dict:
    """Build the fused Perfetto JSON: per-rank ``pid`` lanes carrying
    step spans and fault/verdict instants, per-process host lanes
    carrying the raw timeline spans, all on one wall-aligned axis."""
    events: List[dict] = []
    t0_candidates: List[float] = []

    digested = []
    for dump in dumps:
        mono_anchor, tl_anchor = _anchors(dump)
        steps = _steps_of(dump)
        digested.append((dump, mono_anchor, tl_anchor, steps))
        for e in dump.get("events", []):
            t0_candidates.append(mono_anchor + e["t_us"])
        proc = dump.get("process_index", 0)
        if tl_anchor is not None and proc in traces:
            for ev in traces[proc]:
                if isinstance(ev, dict) and "ts" in ev:
                    t0_candidates.append(tl_anchor + ev["ts"])
    t0 = min(t0_candidates) if t0_candidates else 0.0

    ranks_seen = set()
    for dump, mono_anchor, tl_anchor, steps in digested:
        proc = dump.get("process_index", 0)
        host_pid = HOST_PID_BASE + proc
        world = dump.get("world", {})
        owned = world.get("ranks") or [0]
        ranks_seen.update(owned)
        events.append({
            "name": "process_name", "ph": "M", "pid": host_pid,
            "args": {"name": f"host {proc} (controller)"},
        })
        # per-rank step spans: under single-controller SPMD one dispatch
        # drives every owned rank, so the host-observed step span is the
        # per-rank lane content; with one controller per host the lanes
        # genuinely diverge and the straggler report below reads them
        for rec in steps:
            ts = int(mono_anchor + rec["t_begin_us"] - t0)
            dur = max(1, int(rec["t_end_us"] - rec["t_begin_us"]))
            for r in owned:
                events.append({
                    "name": f"step {rec['step']}",
                    "cat": "STEP" if rec["comm"] else "STEP_LOCAL",
                    "ph": "X", "ts": ts, "dur": dur, "pid": r, "tid": 0,
                    "args": {
                        "step": rec["step"], "comm": rec["comm"],
                        "rounds": rec["rounds"],
                    },
                })
        for e in dump.get("events", []):
            kind, data = e["kind"], e.get("data", {})
            if kind not in _INSTANT_KINDS:
                continue
            ts = int(mono_anchor + e["t_us"] - t0)
            label = kind
            if kind == "fault":
                label = (
                    f"fault:{data.get('fault_kind')} "
                    f"rank={data.get('rank')}"
                )
            elif kind == "membership":
                label = (
                    f"verdict:{data.get('state')} rank={data.get('rank')}"
                )
            elif kind == "repair":
                label = f"repair epoch={data.get('epoch')}"
            elif kind == "stall":
                label = f"stall:{data.get('name')}"
            elif kind == "build":  # written at the phase's end
                label = f"build:{data.get('phase')} {data.get('fun')}"
            pid = (
                data["rank"] if kind in ("fault", "membership")
                and "rank" in data else host_pid
            )
            events.append({
                "name": label, "cat": "FLIGHT", "ph": "i", "ts": ts,
                "pid": pid, "tid": 0, "s": "p", "args": data,
            })
        if tl_anchor is not None and proc in traces:
            for ev in traces[proc]:
                if not isinstance(ev, dict) or "ts" not in ev:
                    continue
                ev = dict(ev)
                ev["ts"] = int(tl_anchor + ev["ts"] - t0)
                ev["pid"] = host_pid
                events.append(ev)

    for r in sorted(ranks_seen):
        events.append({
            "name": "process_name", "ph": "M", "pid": r,
            "args": {"name": f"rank {r}"},
        })
    events.sort(key=lambda e: (e.get("ts", 0), e.get("pid", 0)))
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "generator": "bluefog_tpu tools/trace_merge.py",
            "processes": len(dumps),
            "ranks": sorted(ranks_seen),
        },
    }


# -- analysis: stragglers + hang postmortem -----------------------------------


def _straggler_steps(digested) -> List[dict]:
    """Per communicating step: per-rank durations, the slowest rank,
    its lag over the median, and the plan rounds/edges it delays."""
    by_step: Dict[int, dict] = {}
    for dump, _mono, _tl, steps in digested:
        owned = dump.get("world", {}).get("ranks") or [0]
        for rec in steps:
            if not rec["comm"]:
                continue
            cell = by_step.setdefault(
                rec["step"],
                {"step": rec["step"], "rounds": rec["rounds"],
                 "per_rank_us": {}, "plan": rec["plan"]},
            )
            dur = rec["t_end_us"] - rec["t_begin_us"]
            for r in owned:
                cell["per_rank_us"][r] = int(dur)
    out = []
    for step in sorted(by_step):
        cell = by_step[step]
        durs = cell["per_rank_us"]
        vals = sorted(durs.values())
        median = vals[len(vals) // 2]
        slow = max(durs, key=lambda r: durs[r])
        lag = durs[slow] - median
        delayed = []
        plan = cell.pop("plan")
        if plan and lag > 0:
            for ri, rnd in enumerate(plan["rounds"]):
                delayed += [
                    {"round": ri, "edge": [s, d]}
                    for s, d in rnd if s == slow
                ][:4]
        out.append({
            "step": step,
            "rounds": cell["rounds"],
            "per_rank_us": {str(r): v for r, v in durs.items()},
            "straggler": slow,
            "lag_us": int(lag),
            "delayed_edges": delayed[:16],
        })
    return out


def _postmortem(dumps: List[dict], digested) -> Optional[dict]:
    """Reconstruct a hang/failure: condemned ranks, the plan active when
    each was condemned, which neighbors were waiting on which edge in
    which round, and the last step each rank completed."""
    verdicts = []
    for dump in dumps:
        m = dump.get("membership") or {}
        for rank, state, reason, step in m.get("history", []):
            if state in ("dead", "suspect"):
                verdicts.append({
                    "rank": rank, "state": state, "reason": reason,
                    "step": step,
                })
    triggered = [
        r for d in dumps
        for r in (d.get("dump_history") or [d.get("reason", "")])
        if r and not str(r).startswith("explicit")
    ]
    dead = sorted({
        r for dump in dumps
        for r in (dump.get("membership") or {}).get("dead", [])
    })
    if not verdicts and not triggered and not dead:
        return None

    # last completed step per rank: the last dispatched step of the
    # owning process; a condemned rank's ends at its fault step
    last_completed: Dict[int, int] = {}
    fault_by_rank: Dict[int, dict] = {}
    for dump, _mono, _tl, steps in digested:
        owned = dump.get("world", {}).get("ranks") or [0]
        last = max((rec["step"] for rec in steps), default=-1)
        for r in owned:
            last_completed[r] = max(last_completed.get(r, -1), last)
        # the dump's bounded fault side table survives ring eviction on
        # long runs; ring events are only the fallback for old dumps
        for data in dump.get("fault_events", []):
            fault_by_rank.setdefault(data.get("rank"), data)
        for e in dump.get("events", []):
            if e["kind"] == "fault":
                data = e.get("data", {})
                fault_by_rank.setdefault(data.get("rank"), data)

    waiters = []
    for dump in dumps:
        plans = _plan_by_version(dump)
        worker_plans = [
            p for p in dump.get("comm_plans", [])
            if p.get("kind", "worker") == "worker"
        ]
        for k in dead:
            fault = fault_by_rank.get(k)
            plan = None
            if fault is not None:
                plan = plans.get(fault.get("topo_version"))
                last_completed[k] = min(
                    last_completed.get(k, fault.get("step", 0)),
                    fault.get("step", 0) - 1,
                )
            if plan is None and worker_plans:
                plan = worker_plans[0]  # base (pre-repair) plan
            if plan is None:
                continue
            for ri, rnd in enumerate(plan["rounds"]):
                for s, d in rnd:
                    if s == k:
                        waiters.append({
                            "rank": d, "waiting_on": k,
                            "round": ri, "edge": [k, d],
                        })
    # one entry per (waiter, victim): the FIRST round that blocks it
    seen = set()
    uniq = []
    for w in sorted(waiters, key=lambda w: (w["rank"], w["round"])):
        key = (w["rank"], w["waiting_on"])
        if key not in seen:
            seen.add(key)
            uniq.append(w)
    return {
        "dump_reasons": triggered,
        "verdicts": verdicts,
        "dead_ranks": dead,
        "waiters": uniq,
        "last_completed_step": {
            str(r): s for r, s in sorted(last_completed.items())
        },
    }


def analyze(dumps: List[dict], traces: Optional[Dict[int, list]] = None
            ) -> dict:
    digested = []
    for dump in dumps:
        mono_anchor, tl_anchor = _anchors(dump)
        digested.append((dump, mono_anchor, tl_anchor, _steps_of(dump)))
    steps = _straggler_steps(digested)
    comm_plans = [
        p for d in dumps for p in d.get("comm_plans", [])
        if p.get("kind", "worker") == "worker"
    ]
    return {
        "processes": len(dumps),
        "ranks": sorted({
            r for d in dumps
            for r in (d.get("world", {}).get("ranks") or [])
        }),
        "plan_rounds": comm_plans[0]["n_rounds"] if comm_plans else None,
        "steps": steps,
        "per_step_rounds": [
            {"step": s["step"], "rounds": s["rounds"]} for s in steps
        ],
        "hang_postmortem": _postmortem(dumps, digested),
    }


def merge_and_analyze(path: str) -> Tuple[dict, dict]:
    """One-call API for bench/tests: load a dump directory, return
    ``(merged_trace, report)``."""
    dumps, traces = load_dir(path)
    if not dumps:
        raise FileNotFoundError(f"no flight_*.json dumps under {path!r}")
    return merge_trace(dumps, traces), analyze(dumps, traces)


# -- CLI ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dump_dir", help="directory of flight_*.json dumps "
                    "and per-process timeline JSONs")
    ap.add_argument("-o", "--out", default=None,
                    help="write the merged Perfetto trace here "
                    "(default <dump_dir>/merged_trace.json)")
    ap.add_argument("--report", default=None,
                    help="write the straggler/postmortem report JSON here")
    ap.add_argument("--json", action="store_true",
                    help="print the report as JSON instead of a summary")
    args = ap.parse_args(argv)

    try:
        merged, report = merge_and_analyze(args.dump_dir)
    except (FileNotFoundError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    out = args.out or os.path.join(args.dump_dir, "merged_trace.json")
    with open(out, "w") as f:
        json.dump(merged, f)
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1)

    if args.json:
        print(json.dumps(report))
        return 0

    n_ev = len(merged["traceEvents"])
    print(f"merged {report['processes']} process(es), "
          f"{len(report['ranks'])} rank lanes, {n_ev} events -> {out}")
    if report["plan_rounds"] is not None:
        print(f"comm plan: {report['plan_rounds']} round(s)/gossip step")
    if report["steps"]:
        worst = max(report["steps"], key=lambda s: s["lag_us"])
        print(
            f"steps analyzed: {len(report['steps'])}; worst straggler: "
            f"rank {worst['straggler']} at step {worst['step']} "
            f"(+{worst['lag_us']} us over median)"
        )
    pm = report["hang_postmortem"]
    if pm is None:
        print("no hang/verdict evidence: postmortem not required")
    else:
        print("hang postmortem:")
        for v in pm["verdicts"]:
            print(f"  rank {v['rank']} -> {v['state']} ({v['reason']}) "
                  f"at step {v['step']}")
        for w in pm["waiters"]:
            print(
                f"  rank {w['rank']} was waiting on rank "
                f"{w['waiting_on']} (round {w['round']}, edge "
                f"{w['edge'][0]}->{w['edge'][1]})"
            )
        last = pm["last_completed_step"]
        if last:
            print("  last completed step per rank: "
                  + ", ".join(f"{r}:{s}" for r, s in last.items()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
