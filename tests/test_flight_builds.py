# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Set-up seen from inside: the ``build`` events jax's own reports leave on
the flight ring (one at the end of every trace, lowering and
compile-or-load), ``flight.build_phases``, the side table and the five
counters beside them, and the way to ``bf.init()`` on ``session_start``. No
test asserts a time: only order, containment and that parts do not exceed
the whole."""

import json
import sys
import threading
import time

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

import bluefog_tpu as bf
from bluefog_tpu import flight, metrics

SIZE = 4
TRIPLE = ["trace", "lower", "backend"]
COUNTERS = (
    "bluefog.build.trace_s", "bluefog.build.lower_s",
    "bluefog.build.backend_s",
)


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    monkeypatch.delenv("BLUEFOG_FLIGHT", raising=False)
    monkeypatch.delenv("BLUEFOG_FLIGHT_CAPACITY", raising=False)
    bf.init(devices=cpu_devices[:SIZE])
    yield
    bf.shutdown()
    flight.reconfigure()


def now_us():
    return time.monotonic_ns() // 1000


def builds_of(call):
    """-> (the build records a call left, the call's wall time in us)."""
    t0 = now_us()
    jax.block_until_ready(call())
    t1 = now_us()
    return flight.build_phases(t0, t1), t1 - t0


def outer(records):
    return [r for r in records if r["outer"]]


def counter(name):
    series = metrics.peek(name)
    return series.value if series is not None else 0.0


# -- the events ------------------------------------------------------------------


def test_a_fresh_jit_gives_one_outer_triple_with_its_name():
    def fresh_fn(x):
        return x * 3.0 + 1.0

    x = jnp.ones((7, 3))  # made before: its own programs are not fresh_fn's
    records, wall = builds_of(lambda: jax.jit(fresh_fn)(x))
    assert [r["phase"] for r in outer(records)] == TRIPLE
    assert [r["fun"] for r in outer(records)] == [
        "fresh_fn", "jit(fresh_fn)", "jit(fresh_fn)",
    ]
    stamps = [t for r in outer(records) for t in (r["start_us"], r["t_us"])]
    assert stamps == sorted(stamps)  # one after the other, each whole
    assert all(r["start_us"] == r["t_us"] - r["dur_us"] for r in records)
    assert 0 < sum(r["dur_us"] for r in outer(records)) <= wall
    # the suite runs without a persistent cache: it was not asked
    assert outer(records)[-1]["cache"] is None
    assert "retrieval_us" not in outer(records)[-1]
    # a second call of the same program builds nothing
    again, _ = builds_of(lambda: jax.jit(fresh_fn)(x))
    assert again == []


def test_an_inner_jit_is_a_child_that_outer_leaves_out(monkeypatch):
    monkeypatch.setattr(flight, "_CHILD_MIN_US", 0)  # every child is written

    @jax.jit
    def inner_fn(x):
        return jnp.tanh(x) * 2.0

    def outer_fn(x):
        return inner_fn(x) + inner_fn(x + 1.0)

    x = jnp.ones((5, 2))
    records, wall = builds_of(lambda: jax.jit(outer_fn)(x))
    assert [(r["phase"], r["fun"]) for r in outer(records)] == [
        ("trace", "outer_fn"), ("lower", "jit(outer_fn)"),
        ("backend", "jit(outer_fn)"),
    ]
    parent = outer(records)[0]
    children = [r for r in records if not r["outer"]]
    # how often jax traced inner_fn is what the children are there to say
    # (its cache may or may not take the second call); what inner_fn calls
    # is jitted too: children of children
    assert [r["fun"] for r in children].count("inner_fn") in (1, 2)
    assert {"tanh", "inner_fn"} <= {r["fun"] for r in children}
    assert all(r["phase"] == "trace" for r in children)
    for child in children:  # inside the parent, and written before it
        assert parent["start_us"] <= child["start_us"]
        assert child["t_us"] <= parent["t_us"] and child["seq"] < parent["seq"]
    assert sum(r["dur_us"] for r in outer(records)) <= wall
    # a sum over every event would count the inner seconds twice
    inner = [r for r in children if r["fun"] == "inner_fn"][0]
    assert inner["dur_us"] <= parent["dur_us"]
    # the parent says how many it had, and its direct children's total:
    # here the children that are inside no other child
    assert parent["inner"] == len(children)
    direct = [
        c for c in children if not any(
            o is not c and o["start_us"] <= c["start_us"] and c["t_us"] <= o["t_us"]
            for o in children
        )
    ]
    assert {"inner_fn", "add"} <= {c["fun"] for c in direct}
    assert "tanh" not in {c["fun"] for c in direct}
    assert parent["inner_us"] == sum(c["dur_us"] for c in direct) <= parent["dur_us"]
    assert all("inner" not in r for r in records if r is not parent)


def test_a_short_child_is_counted_on_its_outer_event_and_not_written(monkeypatch):
    monkeypatch.setattr(flight, "_CHILD_MIN_US", 10 ** 9)  # none is long

    @jax.jit
    def inner_fn(x):
        return jnp.tanh(x) * 2.0

    x = jnp.ones((5, 2))
    records, _ = builds_of(lambda: jax.jit(lambda x: inner_fn(x) + 1.0)(x))
    assert [r["phase"] for r in records] == TRIPLE  # the outer ones alone
    trace = records[0]
    assert trace["inner"] >= 3  # inner_fn, tanh, multiply, add
    assert 0 < trace["inner_us"] <= trace["dur_us"]
    # the next outer event starts its own count
    again, _ = builds_of(lambda: jax.jit(lambda x: x * 9.0)(x))
    assert again[0]["inner"] == 1 and again[0]["inner_us"] <= again[0]["dur_us"]


def test_a_trace_that_raises_leaves_the_next_build_an_outer_one():
    def broken(x):
        raise ValueError("while tracing")

    with pytest.raises(ValueError, match="while tracing"):
        jax.jit(broken)(jnp.ones(3))

    def after_it(x):
        return x - 2.0

    x = jnp.ones(3)
    records, _ = builds_of(lambda: jax.jit(after_it)(x))
    assert [r["phase"] for r in outer(records)] == TRIPLE
    # jax closes the failed phase too: it is on the ring, with its name
    assert [
        r["fun"] for r in flight.build_phases() if r["fun"] == "broken"
    ] == ["broken"]


@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compile cache in a temporary directory that takes every
    program, for one test (the suite runs without one: conftest.py)."""
    from jax.experimental.compilation_cache import compilation_cache

    names = (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {name: getattr(jax.config, name) for name in names}
    for name, value in zip(names, (True, str(tmp_path), 0, 0)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()  # jax decides once whether it has one
    yield tmp_path
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_the_persistent_cache_says_miss_then_hit(persistent_cache):
    def cached_fn(x):
        return jnp.sin(x) @ x.T

    x = jnp.ones((6, 6))
    hits, misses = (
        counter("bluefog.build.cache_hits"), counter("bluefog.build.cache_misses")
    )
    first, _ = builds_of(lambda: jax.jit(cached_fn)(x))
    assert [r["phase"] for r in outer(first)] == TRIPLE
    assert first[-1]["cache"] == "miss" and "retrieval_us" not in first[-1]
    assert list(persistent_cache.iterdir())  # the entry was written
    jax.clear_caches()  # the in-memory programs go, the directory stays
    second, wall = builds_of(lambda: jax.jit(cached_fn)(x))
    assert [r["phase"] for r in outer(second)] == TRIPLE
    assert second[-1]["cache"] == "hit"
    assert 0 <= second[-1]["retrieval_us"] <= second[-1]["dur_us"] <= wall
    assert counter("bluefog.build.cache_hits") == hits + 1
    assert counter("bluefog.build.cache_misses") == misses + 1
    # the answer belongs to one backend event: the next program asks anew
    third, _ = builds_of(lambda: jax.jit(lambda x: x + 5.0)(x))
    assert third[-1]["cache"] == "miss"


# -- the step programs ------------------------------------------------------------


def toy():
    params = {
        "w": bf.worker_values(lambda r: np.full((4, 3), r, np.float32)),
        "b": bf.worker_values(lambda r: np.zeros((3,), np.float32)),
    }
    x = bf.worker_values(lambda r: np.ones((5, 4), np.float32) * (r + 1))
    return params, x


def loss_fn(p, x):
    return jnp.mean((x @ p["w"] + p["b"] - 1.0) ** 2)


def kinds_between(first, last):
    """The ring's events from the first of kind ``first`` to the first of
    kind ``last`` after it, both included."""
    evs = flight.events()
    i = next(k for k, e in enumerate(evs) if e["kind"] == first)
    j = next(k for k, e in enumerate(evs) if k > i and e["kind"] == last)
    return evs[i:j + 1]


def test_the_first_fused_call_holds_its_builds_and_the_next_two_hold_none():
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    step = bf.make_train_step(opt, loss_fn)
    params, x = toy()
    state = opt.init(params)
    walls = []
    for _ in range(3):
        t0 = now_us()
        params, state, loss = step(params, state, x)
        jax.block_until_ready(loss)
        walls.append(now_us() - t0)
    calls = flight.step_phases()
    assert [c["step"] for c in calls] == [0, 1, 2]
    per_call = [
        flight.build_phases(c["t_us"], c["t_us"] + c["total"]) for c in calls
    ]
    first = outer(per_call[0])
    assert [(r["phase"], r["fun"]) for r in first] == [
        ("trace", "bf_step"), ("lower", "jit(bf_step)"),
        ("backend", "jit(bf_step)"),
    ]
    assert sum(r["dur_us"] for r in first) <= calls[0]["total"] <= walls[0]
    # the ring's own order: the `compile` event, then every build event of
    # the call, all between `step_key` and `step_dispatched`
    span = kinds_between("step_key", "step_dispatched")
    kinds = [e["kind"] for e in span]
    assert kinds.index("compile") < kinds.index("build")
    assert kinds.count("build") == len(per_call[0])
    assert [e["data"]["step"] for e in (span[0], span[-1])] == [0, 0]
    # as found on the CPU mesh: the second and third call build nothing (on
    # the chip `warm_rebuild_s` reads the same interval, PERF.md §7)
    assert per_call[1] == [] and per_call[2] == []


def test_the_two_program_step_holds_its_builds_between_begin_and_dispatched():
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    params, x = toy()
    state = opt.init(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    jax.block_until_ready(grads)
    seq0 = flight.events()[-1]["seq"]
    params, state = opt.step(params, state, grads)
    jax.block_until_ready(params)
    evs = [e for e in flight.events() if e["seq"] > seq0]
    kinds = [e["kind"] for e in evs]
    built = next(
        i for i, e in enumerate(evs)
        if e["kind"] == "compile" and e["data"]["name"] == "opt_step"
    )
    begin, dispatched = kinds.index("step_begin"), kinds.index("step_dispatched")
    assert built < begin < dispatched
    records = flight.build_phases(evs[begin]["t_us"], evs[dispatched]["t_us"])
    # this program has kept the name it had before the fused one got its own
    assert [(r["phase"], r["fun"]) for r in outer(records)] == [
        ("trace", "body"), ("lower", "jit(body)"), ("backend", "jit(body)"),
    ]
    assert kinds.count("build") == len(records)  # all of them lie in there
    # a second step of the same shapes builds nothing
    seq1 = flight.events()[-1]["seq"]
    params, state = opt.step(params, state, grads)
    jax.block_until_ready(params)
    assert [
        e["kind"] for e in flight.events()
        if e["seq"] > seq1 and e["kind"] in ("build", "compile")
    ] == []


# -- the switch, the guard, the callback's manners ---------------------------------


def test_flight_off_writes_nothing_and_the_callbacks_return_at_once(
    cpu_devices, monkeypatch
):
    monkeypatch.setenv("BLUEFOG_FLIGHT", "0")
    bf.init(devices=cpu_devices[:SIZE])
    before = {name: counter(name) for name in COUNTERS}
    x = jnp.ones(9)
    jax.block_until_ready(jax.jit(lambda x: x * 7.0)(x))
    assert flight.events() == [] and flight.build_phases() == []
    assert flight._builds == []
    assert {name: counter(name) for name in COUNTERS} == before
    # at once: before the event's name or arguments are even looked at
    assert flight._on_jax_duration(None, object()) is None
    assert flight._on_jax_event(None) is None
    assert flight._on_jax_scalar(None, None) is None
    assert flight._build_thread.depth == 0


def test_a_second_init_does_not_double_the_events(cpu_devices):
    from jax._src import monitoring  # the public module cannot list them

    def registered():
        return (
            monitoring.get_event_duration_listeners().count(flight._on_jax_duration),
            monitoring.get_event_listeners().count(flight._on_jax_event),
            monitoring.get_scalar_listeners().count(flight._on_jax_scalar),
        )

    assert registered() == (1, 1, 1)
    bf.init(devices=cpu_devices[:SIZE])
    bf.init(devices=cpu_devices[:2])
    assert registered() == (1, 1, 1)
    x = jnp.ones(11)
    records, _ = builds_of(lambda: jax.jit(lambda x: x / 3.0)(x))
    assert [r["phase"] for r in outer(records)] == TRIPLE


TRACE = "/jax/core/compile/jaxpr_trace_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


@pytest.mark.parametrize("args, kwargs, written", [
    ((TRACE, 0.25), {}, {"phase": "trace", "fun": None, "dur_us": 250000}),
    ((TRACE, 0.5), {"fun_name": "f", "tier": 3, "why": "new"},
     {"phase": "trace", "fun": "f", "dur_us": 500000}),
    ((BACKEND, 1.0), {"other": 1},
     {"phase": "backend", "fun": None, "dur_us": 1000000, "cache": None}),
    ((TRACE, "not a number"), {"fun_name": "f"}, None),
    (("/jax/some/other/event", 2.0), {"fun_name": "f"}, None),
    ((TRACE,), {}, {"phase": "trace", "fun": None, "dur_us": 0}),
])
def test_the_callback_takes_what_jax_may_pass_and_never_raises(
    args, kwargs, written
):
    seq0 = flight.events()[-1]["seq"]
    assert flight._on_jax_duration(*args, **kwargs) is None
    new = [e for e in flight.events() if e["seq"] > seq0]
    assert [e["data"] for e in new] == ([written] if written else [])
    assert flight._on_jax_scalar("/jax/some/scalar", 1.0, extra="x") is None
    assert flight._on_jax_event("/jax/some/event", extra="x") is None


def test_another_threads_cache_answer_is_not_this_threads():
    def asks_and_hits():
        flight._on_jax_event("/jax/compilation_cache/compile_requests_use_cache")
        flight._on_jax_event("/jax/compilation_cache/cache_hits")

    other = threading.Thread(target=asks_and_hits)
    other.start()
    other.join()
    flight._on_jax_duration(BACKEND, 0.001, fun_name="jit(mine)")
    assert flight.events()[-1]["data"]["cache"] is None


# -- build_phases over written events ----------------------------------------------


def ev(seq, end, dur, phase="trace", fun="f", **more):
    return {"seq": seq, "t_us": end, "kind": "build",
            "data": {"phase": phase, "fun": fun, "dur_us": dur, **more}}


NESTED = [
    {"seq": 0, "t_us": 5, "kind": "compile", "data": {"name": "opt_fused_step"}},
    ev(1, 120, 10, fun="relu"),            # [110, 120] in body, in bf_step
    ev(2, 150, 45, fun="body"),            # [105, 150] in bf_step
    ev(3, 170, 10, fun="relu2"),           # [160, 170] in bf_step
    ev(4, 200, 100, fun="bf_step"),        # [100, 200] the outer trace
    ev(5, 260, 50, "lower", "jit(bf_step)"),             # [210, 260]
    ev(6, 400, 130, "backend", "jit(bf_step)", cache="hit", retrieval_us=90),
    {"seq": 7, "t_us": 410, "kind": "step_dispatched", "data": {"step": 0}},
    ev(8, 500, 20, fun="later"),           # [480, 500] another outer trace
]


def test_build_phases_says_which_events_are_outer():
    records = flight.build_phases(evs=NESTED)
    assert [r["seq"] for r in records] == [1, 2, 3, 4, 5, 6, 8]
    assert [r["outer"] for r in records] == [
        False, False, False, True, True, True, True,
    ]
    assert [r["start_us"] for r in records] == [110, 105, 160, 100, 210, 270, 480]
    assert records[5]["cache"] == "hit" and records[5]["retrieval_us"] == 90
    by_phase = {
        p: sum(r["dur_us"] for r in records if r["outer"] and r["phase"] == p)
        for p in TRIPLE
    }
    assert by_phase == {"trace": 120, "lower": 50, "backend": 130}


@pytest.mark.parametrize("t0, t1, seqs, outers", [
    (100, 410, [1, 2, 3, 4, 5, 6], [4, 5, 6]),   # the first call
    (None, 199, [1, 2, 3], []),   # a child stays one when its parent ends later
    (106, None, [1, 3, 5, 6, 8], [5, 6, 8]),     # ... or began earlier
    (411, 470, [], []),
    (None, None, [1, 2, 3, 4, 5, 6, 8], [4, 5, 6, 8]),
])
def test_build_phases_cuts_the_interval_after_reading_the_nesting(
    t0, t1, seqs, outers
):
    records = flight.build_phases(t0, t1, evs=NESTED)
    assert [r["seq"] for r in records] == seqs
    assert [r["seq"] for r in records if r["outer"]] == outers


def test_nesting_is_across_phases_and_equal_intervals_have_one_parent():
    evs = [
        ev(1, 150, 20, "backend", "jit(eager)"),  # an eager op inside a trace
        ev(2, 200, 100, fun="outer_fn"),
        ev(3, 260, 10, fun="index_map"),          # a trace inside a lowering
        ev(4, 280, 70, "lower", "jit(outer_fn)"),
        ev(5, 300, 20, fun="twin_child"),
        ev(6, 300, 20, fun="twin_parent"),        # same interval, written later
    ]
    records = flight.build_phases(evs=evs)
    assert [r["outer"] for r in records] == [
        False, True, False, True, False, True,
    ]


def test_a_trace_inside_a_lowering_is_the_lowerings_child(monkeypatch):
    """A Pallas kernel's lowering traces its index maps and helpers: jax
    reports each as a trace, after the outer trace has ended."""
    monkeypatch.setattr(flight, "_CHILD_MIN_US", 0)
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    seq0 = flight.events()[-1]["seq"]
    flight._on_jax_scalar(TRACE, 0.0, fun_name="step")
    flight._on_jax_duration(TRACE, 0.004, fun_name="step")
    flight._on_jax_scalar(lower, 0.0, fun_name="jit(step)")
    for _ in range(3):
        flight._on_jax_scalar(TRACE, 0.0, fun_name="index_map")
        flight._on_jax_duration(TRACE, 1e-5, fun_name="index_map")
    flight._on_jax_duration(lower, 0.002, fun_name="jit(step)")
    new = [e["data"] for e in flight.events() if e["seq"] > seq0]
    assert [d["fun"] for d in new] == ["step"] + ["index_map"] * 3 + ["jit(step)"]
    assert new[-1]["inner"] == 3 and new[-1]["inner_us"] == 30
    assert "inner" not in new[0]
    assert [e["data"]["fun"] for e in flight._builds[-2:]] == ["step", "jit(step)"]


# -- the side table, the counters, the dump ----------------------------------------


def test_the_side_table_holds_the_outer_events_as_the_ring_does(monkeypatch):
    monkeypatch.setattr(flight, "_CHILD_MIN_US", 0)  # every child is written

    @jax.jit
    def inner_fn(x):
        return jnp.cos(x)

    x = jnp.ones(13)
    before = {name: counter(name) for name in COUNTERS}
    n0 = len(flight._builds)
    records, _ = builds_of(lambda: jax.jit(lambda x: inner_fn(x) * 2.0)(x))
    assert len(records) > len(outer(records)) == 3
    assert len(records) == 3 + outer(records)[0]["inner"]
    added = flight._builds[n0:]
    assert [e["seq"] for e in added] == [r["seq"] for r in outer(records)]
    ring = {e["seq"]: e for e in flight.events()}
    assert all(ring[e["seq"]] == e for e in added)  # the same event, whole
    # what was known on arrival is what the intervals say afterwards
    assert flight.build_phases(evs=flight._builds) == outer(flight.build_phases())
    # the counters: the outer seconds, each phase its own
    for name, r in zip(COUNTERS, outer(records)):
        assert counter(name) - before[name] == pytest.approx(
            r["dur_us"] / 1e6, abs=2e-6
        )


def test_the_side_table_keeps_the_first_and_the_newest():
    kept = flight._BUILDS_KEPT
    n0 = len(flight._builds)
    assert n0 < kept
    for i in range(3 * kept):
        flight._on_jax_scalar(TRACE, 0.0, fun_name=f"f{i}")
        flight._on_jax_scalar(TRACE, 0.0, fun_name=f"child{i}")
        flight._on_jax_duration(TRACE, 1e-6, fun_name=f"child{i}")
        flight._on_jax_duration(TRACE, 2e-6, fun_name=f"f{i}")
    funs = [e["data"]["fun"] for e in flight._builds]
    assert len(funs) == 2 * kept and not any(f.startswith("child") for f in funs)
    assert funs[n0:kept] == [f"f{i}" for i in range(kept - n0)]
    assert funs[kept:] == [f"f{i}" for i in range(2 * kept, 3 * kept)]
    assert flight._builds_dropped == n0 + 3 * kept - 2 * kept
    bf.init(devices=jax.devices("cpu")[:SIZE])  # a new session, a new table
    assert flight._builds_dropped == 0
    assert all(e["data"]["fun"][:1] != "f" for e in flight._builds)


def test_the_dump_carries_the_side_table_and_trace_merge_merges_it(tmp_path):
    from tools.trace_merge import merge_trace

    x = jnp.ones(17)
    jax.block_until_ready(jax.jit(lambda x: x * x + 4.0)(x))
    path = flight.dump(str(tmp_path / "flight_0.json"))
    with open(path) as f:
        dump = json.load(f)
    assert dump["builds_dropped"] == 0
    assert [e["data"]["phase"] for e in dump["builds"][-3:]] == TRIPLE
    assert dump["builds"] == flight._builds
    # the side table reads like the ring
    assert [r["outer"] for r in flight.build_phases(evs=dump["builds"])] == (
        [True] * len(dump["builds"])
    )
    assert dump["metrics"]["bluefog.build.trace_s"]["value"] > 0
    merged = merge_trace([dump], {})
    json.dumps(merged)
    labels = [
        e["name"] for e in merged["traceEvents"]
        if e.get("cat") == "FLIGHT" and e["name"].startswith("build:")
    ]
    n_builds = sum(e["kind"] == "build" for e in dump["events"])
    assert len(labels) == n_builds >= 3
    assert any(name.startswith("build:backend jit(") for name in labels)


# -- the way to bf.init() -----------------------------------------------------------


def test_session_start_says_how_the_process_got_here():
    (start,) = [e for e in flight.events() if e["kind"] == "session_start"]
    data = start["data"]
    assert data["import_s"] > 0
    # conftest.py imports jax before the package: its import is not in import_s
    assert data["jax_preloaded"] is True
    if sys.platform.startswith("linux"):
        assert data["process_age_s"] >= data["import_s"]
    else:
        assert data["process_age_s"] is None or data["process_age_s"] >= 0
    json.dumps(start)


def test_import_s_rides_every_sessions_event(cpu_devices):
    first = [e for e in flight.events() if e["kind"] == "session_start"][0]["data"]
    bf.init(devices=cpu_devices[:2])
    (again,) = [e for e in flight.events() if e["kind"] == "session_start"]
    assert again["data"]["import_s"] == first["import_s"]  # measured once
    if first["process_age_s"] is not None:
        assert again["data"]["process_age_s"] >= first["process_age_s"]
