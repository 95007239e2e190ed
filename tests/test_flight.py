# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Flight recorder + pod-level trace fusion.

Covers the black-box contract end to end: ring-buffer mechanics, dump
triggers (explicit, watchdog stall, elastic DEAD verdict, crash hooks),
cross-rank clock alignment, the fused Perfetto trace, straggler/round
analysis against the compiled CommPlan, and the hang postmortem naming
the fault-plan-killed rank and the exact edge/round its neighbors
stalled on. Every JSON artifact emitted here must round-trip
``json.loads`` — a trace that does not parse explains nothing.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

import bluefog_tpu as bf
import bluefog_tpu.topology as topo
from bluefog_tpu import flight
from bluefog_tpu import watchdog
from bluefog_tpu.collective.plan import plan_from_topology

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 8


def assert_valid_json_artifacts(dirpath):
    """Every timeline/flight/merged JSON a run emitted must parse — the
    suite-wide trace-validity check (a half-written or interleaved file
    is precisely the corruption the writer locks/atomic renames exist
    to prevent)."""
    files = sorted(glob.glob(os.path.join(str(dirpath), "*.json")))
    assert files, f"no JSON artifacts under {dirpath}"
    for f in files:
        with open(f) as fh:
            json.load(fh)  # raises on corruption
    return files


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch, tmp_path):
    monkeypatch.delenv("BLUEFOG_FLIGHT", raising=False)
    monkeypatch.delenv("BLUEFOG_FLIGHT_DIR", raising=False)
    bf.init(devices=cpu_devices[:SIZE])
    yield
    bf.elastic.stop()
    if bf.timeline_enabled():
        bf.timeline_shutdown()
    bf.shutdown()
    flight.reconfigure()


# -- ring mechanics ------------------------------------------------------------


def test_ring_bounded_and_ordered():
    rec = flight.FlightRecorder(capacity=16)
    for i in range(40):
        rec.record("e", {"i": i})
    evs = rec.events()
    assert len(evs) == 16  # bounded: old events overwritten
    assert [e["data"]["i"] for e in evs] == list(range(24, 40))
    assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)


def test_record_disabled_is_noop(monkeypatch):
    monkeypatch.setenv("BLUEFOG_FLIGHT", "0")
    flight.reconfigure()
    assert not flight.enabled()
    assert flight.record("x") == -1
    assert flight.events() == []
    monkeypatch.delenv("BLUEFOG_FLIGHT")
    flight.reconfigure()
    assert flight.enabled()  # default ON


def test_concurrent_writers_never_corrupt():
    import threading

    rec = flight.FlightRecorder(capacity=1024)

    def spam(tid):
        for i in range(500):
            rec.record("t", {"tid": tid, "i": i})

    threads = [
        threading.Thread(target=spam, args=(t,)) for t in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = rec.events()
    assert len(evs) == 1024
    seqs = [e["seq"] for e in evs]
    assert len(set(seqs)) == len(seqs)  # unique slots: no torn writes


# -- the phases of a fused train_step call, read back from plain events ---------


def _call_events(step, t0, gaps=(5, 7, 11, 13, 17), seq0=0, upto=6):
    """The six boundary events of one fused call, ``gaps`` us apart."""
    kinds = list(flight.STEP_PHASES.values()) + [flight.STEP_END]
    stamps = [t0]
    for g in gaps:
        stamps.append(stamps[-1] + g)
    return [
        {"seq": seq0 + i, "t_us": t, "kind": k, "data": {"step": step}}
        for i, (k, t) in enumerate(zip(kinds, stamps))
    ][:upto]


def test_step_phases_of_a_whole_call():
    (call,) = flight.step_phases(evs=_call_events(7, 1000))
    assert call == {
        "step": 7, "t_us": 1000, "total": 53, "resolve": 5, "key": 7,
        "stage": 11, "enqueue": 13, "epilogue": 17,
    }


@pytest.mark.parametrize("case", [
    "raised", "half_overwritten", "two_program", "other_kinds_between",
])
def test_step_phases_keeps_whole_calls_only(case):
    whole = _call_events(3, 5000, seq0=100)
    if case == "raised":
        # died in `enqueue`, then the same step number ran again
        evs = _call_events(3, 1000, upto=4) + whole
    elif case == "half_overwritten":
        # the ring wrapped inside the call before: its first half is gone
        evs = _call_events(2, 1000)[3:] + whole
    elif case == "two_program":
        # opt.step writes step_begin / step_dispatched alone
        evs = [
            {"seq": 1, "t_us": 10, "kind": "step_begin",
             "data": {"step": 3, "comm": True}},
            {"seq": 2, "t_us": 20, "kind": "step_dispatched",
             "data": {"step": 3}},
        ] + whole
    else:
        evs = list(whole)
        evs.insert(2, {"seq": 99, "t_us": 5006, "kind": "compile",
                       "data": {"name": "opt_fused_step", "differs_at": 5}})
        evs.insert(4, {"seq": 98, "t_us": 5020, "kind": "plan_compile"})
    calls = flight.step_phases(evs=evs)
    assert [(c["step"], c["t_us"], c["total"]) for c in calls] == [(3, 5000, 53)]


def test_fused_step_events_sit_beside_the_two_program_ones():
    """``opt.step`` keeps its lone pair; a fused call brackets the same two
    kinds with the four new boundaries, and trace_merge folds both."""
    import optax

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_merge

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    params = {"w": bf.worker_values(lambda r: np.float32([r]))}
    state = opt.init(params)
    params, state = opt.step(params, state, {"w": jnp.zeros_like(params["w"])})
    fused = bf.make_train_step(opt, lambda p, x: jnp.sum(p["w"] * x))
    x = bf.worker_values(lambda r: np.float32([1.0]))
    params, state, loss = fused(params, state, x)
    loss.block_until_ready()
    kinds = [e["kind"] for e in flight.events() if e["kind"].startswith("step_")]
    assert kinds == ["step_begin", "step_dispatched"] + list(
        flight.STEP_PHASES.values()
    ) + [flight.STEP_END]
    (call,) = flight.step_phases()
    assert call["step"] == 1
    steps = trace_merge._steps_of({"events": flight.events(), "comm_plans": []})
    assert [s["step"] for s in steps] == [0, 1]
    # the merge tool's span of the fused call is stage + enqueue
    assert steps[1]["t_end_us"] - steps[1]["t_begin_us"] == (
        call["stage"] + call["enqueue"]
    )


# -- session events + explicit dump ---------------------------------------------


def test_session_and_step_events_recorded():
    import optax

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    params = {"w": bf.worker_values(lambda r: np.float32([r]))}
    state = opt.init(params)
    for _ in range(3):
        params, state = opt.step(
            params, state, {"w": jnp.zeros_like(params["w"])}
        )
    kinds = [e["kind"] for e in flight.events()]
    assert kinds.count("session_start") == 1
    assert kinds.count("step_begin") == 3
    assert kinds.count("step_dispatched") == 3
    assert "plan_compile" in kinds
    assert "compile" in kinds


def test_explicit_dump_schema(tmp_path):
    import optax

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    params = {"w": bf.worker_values(lambda r: np.float32([r]))}
    state = opt.init(params)
    opt.step(params, state, {"w": jnp.zeros_like(params["w"])})
    path = bf.flight_dump(str(tmp_path / "flight_0.json"))
    dump = json.load(open(path))
    assert dump["version"] == flight.DUMP_VERSION
    assert dump["reason"] == "explicit"
    assert dump["world"]["size"] == SIZE
    assert dump["world"]["ranks"] == list(range(SIZE))
    clock = dump["clock"]
    assert clock["unix_ns"] > 0 and clock["mono_us"] > 0
    assert dump["comm_plans"], "compiled plan structure missing"
    plan = dump["comm_plans"][-1]
    assert plan["n_rounds"] == len(plan["rounds"])
    assert all(
        len(edge) == 2 for rnd in plan["rounds"] for edge in rnd
    )
    assert any(e["kind"] == "step_begin" for e in dump["events"])
    assert_valid_json_artifacts(tmp_path)


def test_window_ops_recorded():
    x = bf.worker_values(lambda r: np.float32([r]))
    assert bf.win_create(x, "flight_win")
    try:
        bf.win_put(name="flight_win")
        bf.win_update(name="flight_win")
    finally:
        bf.win_free("flight_win")
    ops = [
        e["data"]["op"] for e in flight.events()
        if e["kind"] == "window_op"
    ]
    assert "put" in ops and "update" in ops


# -- automatic dump triggers -----------------------------------------------------


def test_stall_triggers_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("BLUEFOG_FLIGHT_DIR", str(tmp_path))
    watchdog.set_stall_timeout(0.1)
    try:
        with watchdog.watch("flight-stall-op"):
            time.sleep(0.5)
    finally:
        watchdog.set_stall_timeout(60)
    files = glob.glob(str(tmp_path / "flight_*.json"))
    assert files, "stall did not trigger a flight dump"
    dump = json.load(open(files[0]))
    assert dump["reason"].startswith("stall:flight-stall-op")
    assert any(e["kind"] == "stall" for e in dump["events"])


def test_verdict_triggers_dump_with_history(tmp_path, monkeypatch):
    import optax

    monkeypatch.setenv("BLUEFOG_FLIGHT_DIR", str(tmp_path))
    bf.set_topology(topo.ExponentialTwoGraph(SIZE))
    session = bf.elastic.start()
    session.inject("kill", rank=2, step=1)
    opt = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(0.05))
    guard = bf.elastic.guard(opt)
    params = {"w": bf.worker_values(lambda r: np.float32([r]))}
    state = opt.init(params)
    for _ in range(3):
        params, state = guard.step(
            params, state, {"w": jnp.zeros_like(params["w"])}
        )
    files = glob.glob(str(tmp_path / "flight_*.json"))
    assert files, "DEAD verdict did not trigger a flight dump"
    dump = json.load(open(files[0]))
    assert any(
        r.startswith("verdict:dead:rank=2") for r in dump["dump_history"]
    )
    assert dump["membership"]["dead"] == [2]
    # a later explicit dump must preserve the trigger history
    bf.flight_dump()
    dump2 = json.load(open(files[0]))
    assert dump2["reason"] == "explicit"
    assert any(
        r.startswith("verdict:dead") for r in dump2["dump_history"]
    )


def test_maybe_dump_noop_without_dir(tmp_path):
    assert flight.dump_dir() is None
    assert flight.maybe_dump("stall:x") is None  # no litter, no crash


def test_excepthook_dumps_and_chains(tmp_path, monkeypatch):
    monkeypatch.setenv("BLUEFOG_FLIGHT_DIR", str(tmp_path))
    seen = []
    monkeypatch.setattr(
        sys, "excepthook", lambda *a: seen.append(a)
    )
    flight._install_crash_hooks()
    try:
        try:
            raise ValueError("boom")
        except ValueError:
            sys.excepthook(*sys.exc_info())
    finally:
        flight._uninstall_crash_hooks()
    assert seen and seen[0][0] is ValueError  # previous hook chained
    files = glob.glob(str(tmp_path / "flight_*.json"))
    assert files
    dump = json.load(open(files[0]))
    assert dump["reason"] == "exception:ValueError"
    crash = [e for e in dump["events"] if e["kind"] == "crash"]
    assert crash and crash[0]["data"]["message"] == "boom"


def test_sigterm_dumps_and_chains(tmp_path, monkeypatch):
    monkeypatch.setenv("BLUEFOG_FLIGHT_DIR", str(tmp_path))
    seen = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    flight._install_crash_hooks()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        # the python-level handler runs at the next bytecode boundary
        for _ in range(100):
            if seen:
                break
            time.sleep(0.01)
    finally:
        flight._uninstall_crash_hooks()
        signal.signal(signal.SIGTERM, prev)
    assert seen == [signal.SIGTERM]  # previous handler chained
    files = glob.glob(str(tmp_path / "flight_*.json"))
    assert files
    assert json.load(open(files[0]))["reason"] == "sigterm"


# -- trace fusion ----------------------------------------------------------------


def _run_killed_session(tmp_path, kill_rank=3, kill_step=4, steps=8):
    import optax

    os.environ["BLUEFOG_FLIGHT_DIR"] = str(tmp_path)
    os.environ["BLUEFOG_TIMELINE"] = str(tmp_path / "trace_")
    try:
        flight.reconfigure()
        bf.init()  # re-init picks up the timeline + flight env
        bf.set_topology(topo.ExponentialTwoGraph(SIZE))
        session = bf.elastic.start(policy="average")
        session.inject("kill", rank=kill_rank, step=kill_step)
        opt = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(0.05))
        guard = bf.elastic.guard(opt)
        params = {"w": bf.worker_values(lambda r: np.float32([r, r]))}
        state = opt.init(params)
        for _ in range(steps):
            params, state = guard.step(
                params, state, {"w": jnp.zeros_like(params["w"])}
            )
        bf.flight_dump()
        bf.elastic.stop()
        bf.shutdown()  # closes the env-owned timeline -> valid JSON
    finally:
        os.environ.pop("BLUEFOG_FLIGHT_DIR", None)
        os.environ.pop("BLUEFOG_TIMELINE", None)


def test_merge_postmortem_and_round_counts(tmp_path):
    from tools.trace_merge import merge_and_analyze

    kill_rank, kill_step = 3, 4
    _run_killed_session(tmp_path, kill_rank, kill_step)
    assert_valid_json_artifacts(tmp_path)
    merged, report = merge_and_analyze(str(tmp_path))

    # one valid Perfetto JSON with a pid lane per rank + host lane
    events = merged["traceEvents"]
    assert json.loads(json.dumps(merged))  # round-trips
    lane_names = {
        (e["pid"], e["args"]["name"])
        for e in events if e.get("ph") == "M"
    }
    for r in range(SIZE):
        assert (r, f"rank {r}") in lane_names
    assert any(n.startswith("host 0") for _p, n in lane_names)
    spans = [e for e in events if e.get("ph") == "X" and e["pid"] < SIZE]
    assert spans and all(e["dur"] >= 1 for e in spans)
    assert all(isinstance(e.get("ts"), int) for e in spans)

    # per-step round count matches the independently compiled CommPlan
    base_plan = plan_from_topology(topo.ExponentialTwoGraph(SIZE))
    pre_kill = [
        s for s in report["per_step_rounds"] if s["step"] < kill_step
    ]
    assert pre_kill
    assert all(s["rounds"] == len(base_plan.rounds) for s in pre_kill)
    # post-repair steps run the repaired (7-rank) plan, not the base one
    post = [s for s in report["per_step_rounds"] if s["step"] > kill_step]
    assert post and all(s["rounds"] != 0 for s in post)

    # hang postmortem: the killed rank, and each neighbor's exact
    # edge/round, straight against the compiled plan structure
    pm = report["hang_postmortem"]
    assert pm is not None
    assert pm["dead_ranks"] == [kill_rank]
    assert any(
        v["rank"] == kill_rank and v["state"] == "dead"
        for v in pm["verdicts"]
    )
    rounds_by_edge = {}
    for ri, rnd in enumerate(base_plan.rounds):
        for s, d in rnd.perm:
            rounds_by_edge.setdefault((s, d), ri)
    expected = sorted(d for (s, d) in rounds_by_edge if s == kill_rank)
    assert sorted(w["rank"] for w in pm["waiters"]) == expected
    for w in pm["waiters"]:
        assert w["waiting_on"] == kill_rank
        assert rounds_by_edge[(kill_rank, w["rank"])] == w["round"]
        assert w["edge"] == [kill_rank, w["rank"]]
    assert pm["last_completed_step"][str(kill_rank)] == kill_step - 1

    # straggler scaffolding is present for every communicating step
    assert report["steps"]
    for s in report["steps"]:
        assert set(s["per_rank_us"]) and "straggler" in s


def test_postmortem_survives_ring_eviction(tmp_path, monkeypatch):
    """The fault -> plan linkage must not depend on the fault event
    still being in the ring: with a tiny ring and a long post-kill run,
    the side tables (comm_plans + fault_events) alone must carry the
    postmortem."""
    from tools.trace_merge import merge_and_analyze

    monkeypatch.setenv("BLUEFOG_FLIGHT_CAPACITY", "256")  # the floor
    kill_rank, kill_step = 3, 4
    _run_killed_session(tmp_path, kill_rank, kill_step, steps=200)
    dump = json.load(
        open(glob.glob(str(tmp_path / "flight_*.json"))[0])
    )
    # precondition: the kill's ring event was actually evicted
    assert not any(
        e["kind"] == "fault" for e in dump["events"]
    ), "ring did not wrap; raise steps"
    assert dump["fault_events"], "fault side table missing"
    _merged, report = merge_and_analyze(str(tmp_path))
    pm = report["hang_postmortem"]
    assert pm["dead_ranks"] == [kill_rank]
    base_plan = plan_from_topology(topo.ExponentialTwoGraph(SIZE))
    expected = sorted({
        d for rnd in base_plan.rounds for s, d in rnd.perm
        if s == kill_rank
    })
    assert sorted(w["rank"] for w in pm["waiters"]) == expected
    assert pm["last_completed_step"][str(kill_rank)] == kill_step - 1


def test_clock_alignment_across_processes():
    """Synthetic two-process merge: the same wall instant expressed
    through two different monotonic origins must land at the same
    merged timestamp (the offset-handshake contract)."""
    from tools.trace_merge import merge_trace

    def mk_dump(proc, unix_ns, mono_us, ranks):
        return {
            "version": 1, "reason": "explicit", "process_index": proc,
            "clock": {"unix_ns": unix_ns, "mono_us": mono_us,
                      "timeline_us": None},
            "world": {"size": 4, "ranks": ranks},
            "comm_plans": [{
                "topo_version": 1, "n_rounds": 1,
                "rounds": [[[0, 1], [1, 0], [2, 3], [3, 2]]],
                "live": None,
            }],
            "events": [
                {"seq": 0, "t_us": mono_us, "kind": "plan_compile",
                 "data": {"topo_version": 1, "n_rounds": 1}},
                {"seq": 1, "t_us": mono_us + 10, "kind": "step_begin",
                 "data": {"step": 0, "comm": True}},
                {"seq": 2, "t_us": mono_us + 110,
                 "kind": "step_dispatched", "data": {"step": 0}},
            ],
        }

    base = 1_700_000_000_000_000_000  # same wall epoch...
    dumps = [
        mk_dump(0, base, 5_000_000, [0, 1]),  # ...different mono origins
        mk_dump(1, base, 9_999_000, [2, 3]),
    ]
    merged = merge_trace(dumps, {})
    spans = [
        e for e in merged["traceEvents"] if e.get("ph") == "X"
    ]
    by_rank = {e["pid"]: e["ts"] for e in spans}
    # both processes' step 0 began 10 us after their shared wall anchor
    assert by_rank[0] == by_rank[2]
    assert by_rank[1] == by_rank[3]


def test_trace_merge_cli(tmp_path):
    _run_killed_session(tmp_path, kill_rank=3, kill_step=4)
    report_path = tmp_path / "report.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_merge.py"),
         str(tmp_path), "--report", str(report_path)],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")),
    )
    assert out.returncode == 0, out.stderr
    assert "hang postmortem" in out.stdout
    assert "waiting on rank 3" in out.stdout
    merged = json.load(open(tmp_path / "merged_trace.json"))
    assert merged["traceEvents"]
    report = json.load(open(report_path))
    assert report["hang_postmortem"]["dead_ranks"] == [3]
    assert_valid_json_artifacts(tmp_path)


def test_metrics_report_flight_mode(tmp_path):
    _run_killed_session(tmp_path, kill_rank=3, kill_step=4)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         "--flight", str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=60, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")),
    )
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert report["dead_ranks"] == [3]
    assert report["dumps"] and report["dumps"][0]["events"] > 0


# -- launcher integration ---------------------------------------------------------


def test_launcher_flight_dir_env_and_artifacts(tmp_path):
    from bluefog_tpu.run.run import (
        build_child_env,
        flight_artifacts,
        parse_args,
        report_flight_artifacts,
    )

    args = parse_args(
        ["-np", "4", "--flight-dir", str(tmp_path), "ls"]
    )
    env = build_child_env(args, base_env={})
    assert env["BLUEFOG_FLIGHT_DIR"] == str(tmp_path)

    assert flight_artifacts(str(tmp_path / "missing")) == []
    (tmp_path / "flight_0.json").write_text("{}")
    (tmp_path / "trace_0.json").write_text("[]")
    files = flight_artifacts(str(tmp_path))
    assert [os.path.basename(f) for f in files] == [
        "flight_0.json", "trace_0.json",
    ]
    import io

    buf = io.StringIO()
    listed = report_flight_artifacts(str(tmp_path), out=buf)
    assert listed == files
    assert "trace_merge.py" in buf.getvalue()


def test_flight_evidence_file_committed():
    """FLIGHT_EVIDENCE.json (the committed BENCH_MODE=flight output)
    carries the acceptance facts: <=1% recorder overhead, bitwise
    on/off pin, merged-trace round counts matching the compiled plan,
    and a postmortem that names the fault-plan-killed rank."""
    path = os.path.join(REPO, "FLIGHT_EVIDENCE.json")
    assert os.path.exists(path), "FLIGHT_EVIDENCE.json missing"
    lines = [
        json.loads(l) for l in open(path).read().splitlines()
        if l.startswith("{")
    ]
    prov = [l for l in lines if l.get("metric") == "provenance"]
    assert prov and prov[0]["git_sha"]
    over = [
        l for l in lines if l.get("metric") == "flight_recorder_overhead"
    ]
    assert over and over[0]["overhead_pct"] <= 1.0
    assert over[0]["bitwise_identical"] is True
    merge = [
        l for l in lines if l.get("metric") == "flight_trace_merge"
    ]
    assert merge and merge[0]["merged_valid_json"]
    assert merge[0]["per_step_rounds_match_plan"]
    assert (
        merge[0]["plan_rounds_reported"]
        == merge[0]["plan_rounds_compiled"]
    )
    pm = [l for l in lines if l.get("metric") == "flight_postmortem"]
    assert pm and pm[0]["named_correctly"] is True
    assert pm[0]["dead_ranks_reported"] == [pm[0]["kill_rank"]]
