"""The readers PR 38 adds (``harness/setup_spans.py`` and the seven files
that use it): its functions on hand-written ring events, the benchmark's
entries by name, and every reader ``None`` where the rehearsal runs. Nothing
here is a speed."""

import os
import time

import jax
import pytest

from bluefog_tpu import flight

from benchmarks.harness import bench, cells, scopes, setup_spans

import toy

ON_THE_CHIP = {"bf16_flops_per_s": 197e12}  # what Run.peaks holds on a v5e
LAYERS = {
    "import_s": "entry", "reach_init_s": "entry",
    "step_first_call_s": "optimizer_path", "step_trace_s": "optimizer_path",
    "step_lower_s": "optimizer_path", "step_backend_s": "optimizer_path",
    "warm_rebuild_s": "optimizer_path",
}


@pytest.mark.parametrize("name", setup_spans.NAMES)
def test_the_benchmark_names_this_reader(name):
    """Each entry looked up by its name: where it stands in ``per_layer``
    and what a later PR appends after it is not this test's business."""
    (entry,) = [m for m in cells.load_benchmark()["per_layer"] if m["name"] == name]
    assert (entry["source"], entry["layer"]) == ("program_span", LAYERS[name])
    assert (entry["unit"], entry["better"]) == ("s", "lower")
    assert entry["moves"] == "setup_s"
    assert "workloads" not in entry  # every cell
    assert callable(bench.load_reader(name))
    assert set(LAYERS) == set(setup_spans.NAMES)


# -- the functions, over written events ------------------------------------------

BOUNDARIES = list(flight.STEP_PHASES.values()) + [flight.STEP_END]


def call(seq0, step, stamps):
    """The six boundary events of one whole ``train_step`` call."""
    return [
        {"seq": seq0 + i, "t_us": t, "kind": k, "data": {"step": step}}
        for i, (k, t) in enumerate(zip(BOUNDARIES, stamps))
    ]


def build(seq, end_us, dur_us, phase, fun, **more):
    return {"seq": seq, "t_us": end_us, "kind": "build",
            "data": {"phase": phase, "fun": fun, "dur_us": dur_us, **more}}


def ring(first_seq=0):
    """A session: eager builds before the first call; a first call that
    builds the step (a trace with nested children, a lowering that traces
    an index map, a load from the cache) and one small program the cache
    did not hold; a second call
    that builds again; a third that builds nothing."""
    first = call(10, 0, [1_000_000, 1_000_100, 1_000_200, 1_000_300, 9_000_000, 9_000_500])
    second = call(40, 1, [9_100_000, 9_100_010, 9_100_020, 9_100_030, 9_600_000, 9_600_100])
    third = call(50, 2, [9_700_000, 9_700_010, 9_700_020, 9_700_030, 9_700_040, 9_700_100])
    evs = [
        {"seq": 0, "t_us": 100, "kind": "session_start",
         "data": {"import_s": 2.5, "jax_preloaded": True, "process_age_s": 16.25}},
        build(1, 500_000, 200_000, "trace", "eager"),       # before the call
        build(2, 600_000, 50_000, "backend", "jit(eager)", cache="miss"),
        *first[:4],
        {"seq": 14, "t_us": 1_000_250, "kind": "compile",
         "data": {"name": "opt_fused_step"}},
        build(20, 2_000_000, 400_000, "trace", "relu"),      # [1.6, 2.0] in body
        build(21, 3_000_000, 1_500_000, "trace", "body"),    # [1.5, 3.0] in bf_step
        build(22, 4_000_000, 3_000_000 - 400, "trace", "bf_step"),  # [1.0004, 4.0]
        build(23, 4_500_000, 2_000, "trace", "index_map"),   # in the lowering
        build(24, 5_000_000, 1_000_000, "lower", "jit(bf_step)"),
        build(25, 8_000_000, 2_500_000, "backend", "jit(bf_step)",
              cache="hit", retrieval_us=2_000_000),
        build(26, 8_400_000, 100_000, "trace", "_where"),
        build(27, 8_500_000, 50_000, "lower", "jit(_where)"),
        build(28, 8_900_000, 250_000, "backend", "jit(_where)", cache="miss"),
        *first[4:],
        *second[:4],
        build(44, 9_300_000, 150_000, "trace", "bf_step"),
        build(45, 9_400_000, 20_000, "trace", "inner"),      # [9.38, 9.4] alone
        build(46, 9_550_000, 125_000, "backend", "jit(bf_step)", cache="hit",
              retrieval_us=100_000),
        *second[4:],
        *third,
    ]
    evs.sort(key=lambda e: e["seq"])
    for e in evs:
        e["seq"] += first_seq
    return evs


def test_the_first_call_is_split_and_no_nested_second_counted_twice():
    evs = ring()
    calls = scopes.calls_between(evs, 900_000, 9_800_000, flight.step_phases)
    assert [c["step"] for c in calls] == [0, 1, 2]
    split = setup_spans.split_first_calls(evs, calls, flight.build_phases)
    assert split["step_first_call_s"] == 8.0005
    # bf_step's trace, and _where's; relu and body are inside bf_step's,
    # and the kernel's index map is traced inside the lowering
    assert split["step_trace_s"] == pytest.approx(2.9996 + 0.1)
    assert split["step_lower_s"] == pytest.approx(1.0 + 0.05)
    # a hit and a miss: both are backend time of the call
    assert split["step_backend_s"] == pytest.approx(2.5 + 0.25)
    parts = sum(split[f"step_{p}_s"] for p in setup_spans.PHASES)
    assert parts <= split["step_first_call_s"]
    # the second call built again (a trace, a child-less inner trace and a
    # load), the third nothing; what was built before the first call is no
    # call's
    assert split["warm_rebuild_s"] == pytest.approx(0.15 + 0.02 + 0.125)
    records = setup_spans.builds_in_call(evs, calls[0], flight.build_phases)
    assert [r["fun"] for r in records if not r["outer"]] == [
        "relu", "body", "index_map",
    ]
    assert [r.get("cache") for r in records if r["phase"] == "backend"] == [
        "hit", "miss",
    ]


def test_fewer_than_three_warm_calls_give_no_rebuild_number():
    evs = ring()
    calls = scopes.calls_between(evs, 900_000, 9_650_000, flight.step_phases)
    assert [c["step"] for c in calls] == [0, 1]
    split = setup_spans.split_first_calls(evs, calls, flight.build_phases)
    assert split["warm_rebuild_s"] is None
    assert split["step_first_call_s"] == 8.0005


def test_outer_seconds_leaves_the_children_and_other_phases_out():
    records = flight.build_phases(evs=ring())
    seconds = setup_spans.outer_seconds(records)
    assert seconds == pytest.approx({
        "trace": 0.2 + 2.9996 + 0.1 + 0.15 + 0.02,
        "lower": 1.0 + 0.05, "backend": 0.05 + 2.5 + 0.25 + 0.125,
    })
    assert setup_spans.outer_seconds([]) == dict.fromkeys(setup_spans.PHASES, 0.0)
    odd = [{"outer": True, "phase": "link", "dur_us": 5}, {"outer": True, "dur_us": 5}]
    assert setup_spans.outer_seconds(odd) == dict.fromkeys(setup_spans.PHASES, 0.0)


def test_session_start_is_found_until_the_ring_wraps_past_it():
    assert setup_spans.session_start(ring())["process_age_s"] == 16.25
    assert setup_spans.session_start(ring()[1:]) is None
    assert setup_spans.session_start([]) is None


# -- setup_split on a Run ------------------------------------------------------------


class Spans:
    def __init__(self, *items):
        self.items = list(items)


def a_run(peaks=ON_THE_CHIP, spans=(("warm_steps", 0.9, 9.8),)):
    return bench.Run(None, 1, None, peaks, Spans(*spans))


def test_setup_split_reads_the_ring_the_program_holds(monkeypatch):
    assert abs(time.perf_counter() - time.monotonic()) < scopes.CLOCK_SLACK_S
    monkeypatch.setattr(flight, "events", ring)
    split = setup_spans.setup_split(a_run())
    assert set(split) == set(setup_spans.NAMES)
    assert split["import_s"] == 2.5 and split["reach_init_s"] == 16.25
    assert split["step_first_call_s"] == 8.0005
    assert split["import_s"] <= split["reach_init_s"]
    for name in setup_spans.NAMES:
        assert bench.load_reader(name)(a_run()) == split[name] is not None
    # the window's span is not the one read, and the last warm span counts
    windowed = a_run(spans=(("warm_steps", 0.0, 0.5), ("window", 0.9, 9.8)))
    assert setup_spans.setup_split(windowed) is None
    both = a_run(spans=(("warm_steps", 0.0, 0.5), ("warm_steps", 0.9, 9.8)))
    assert setup_spans.setup_split(both)["step_first_call_s"] == 8.0005


@pytest.mark.parametrize("why", [
    "off_the_chip", "no_build_phases", "no_step_phases", "no_warm_span",
    "ring_wrapped", "no_call_in_the_span", "clocks_disagree",
])
def test_setup_split_gives_none_rather_than_a_wrong_number(why, monkeypatch):
    monkeypatch.setattr(flight, "events", ring)
    run = a_run()
    if why == "off_the_chip":
        run = a_run(peaks=None)
    elif why == "no_build_phases":  # the parent of PR 38
        monkeypatch.delattr(flight, "build_phases")
    elif why == "no_step_phases":  # the parent of PR 24
        monkeypatch.delattr(flight, "step_phases")
    elif why == "no_warm_span":
        run = a_run(spans=(("window", 0.9, 9.8),))
    elif why == "ring_wrapped":
        # the oldest event left is not the first written and is younger
        # than the span's start: the first call may be half gone
        monkeypatch.setattr(flight, "events", lambda: ring(first_seq=9000)[12:])
    elif why == "no_call_in_the_span":
        run = a_run(spans=(("warm_steps", 20.0, 30.0),))
    elif why == "clocks_disagree":
        monkeypatch.setattr(time, "monotonic", lambda: time.perf_counter() + 5.0)
    assert setup_spans.setup_split(run) is None
    assert all(bench.load_reader(name)(run) is None for name in setup_spans.NAMES)


def test_a_program_that_says_nothing_of_its_way_gives_none_for_those_two(monkeypatch):
    """A ring with the build events and a ``session_start`` without the two
    fields: the five others are read, these two are left out."""
    def older():
        evs = ring()
        evs[0] = {**evs[0], "data": {"pid": 1}}
        return evs

    monkeypatch.setattr(flight, "events", older)
    split = setup_spans.setup_split(a_run())
    assert split["import_s"] is None and split["reach_init_s"] is None
    assert split["step_first_call_s"] == 8.0005


# -- the rehearsal -----------------------------------------------------------------


def test_a_toy_run_off_the_chip_reads_none_for_all_seven_and_raises_nothing(
    monkeypatch,
):
    """A traced run is what calls the readers. The CPU's profiler writes no
    ``/device:TPU`` plane, so the trace recorded on the v5e and its
    program's HLO stand in for the toy step's, as in test_rehearsal.py."""
    from benchmarks.harness import hlo_text, trace_reduce

    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    with open(os.path.join(data, "tiny_1chip.hlo.txt")) as f:
        recorded_hlo = f.read()
    real_index = hlo_text.HloIndex
    monkeypatch.setattr(
        trace_reduce, "load_dir",
        lambda _dir: trace_reduce.load(os.path.join(data, "tiny_1chip.xplane.pb")),
    )
    monkeypatch.setattr(hlo_text, "HloIndex", lambda _text: real_index(recorded_hlo))
    seen = []

    class Kept(bench.Run):
        def __init__(self, *args):
            super().__init__(*args)
            seen.append(self)

    monkeypatch.setattr(bench, "Run", Kept)
    cell = toy.cell(toy.RESNET, toy.traffic(), 1, per_layer=setup_spans.NAMES)
    cell.units.update(toy.UNITS)
    cell.units.update(dict.fromkeys(setup_spans.NAMES, "s"))
    spans = bench.Spans(time.perf_counter())
    result = bench.run_cell(
        cell, seed=7, seconds=0.2, trace=True, spans=spans,
        info=lambda line: None, devices=jax.devices()[:1],
    )
    assert result["correct"]
    assert result["metrics"] == {}  # all seven read None and are left out
    (run,) = seen
    assert run.peaks is None  # the harness's mark of "not a TPU"
    assert all(bench.load_reader(name)(run) is None for name in setup_spans.NAMES)
    # the same ring, marked as on the chip: the program wrote what the
    # readers read (no time is asserted: order and containment)
    on_chip = bench.Run(cell, 1, None, ON_THE_CHIP, spans)
    split = setup_spans.setup_split(on_chip)
    parts = sum(split[f"step_{p}_s"] for p in setup_spans.PHASES)
    assert 0 < parts <= split["step_first_call_s"] <= run.warm_step_s[0]
    assert 0 < split["import_s"] <= split["reach_init_s"]
    assert split["warm_rebuild_s"] >= 0
