"""The reduction from a trace to numbers, against a small trace recorded on
the v5e (``data/tiny_1chip.xplane.pb``: two blocks of three runs of
``tanh(x @ w) * 1.5 + x`` at 1024 x 1024 bf16, PR 22, with the compiled
program's HLO text beside it; ``data/tiny_4chip.xplane.pb``: two blocks of two
runs of a matmul, an 8 MB ``ppermute`` to the next chip and a second matmul,
under ``shard_map`` on the 2 x 2 host) and against hand-made intervals."""

import os

import pytest

from benchmarks.harness import hlo_text, trace_reduce
from benchmarks.harness.trace_reduce import Op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def tiny():
    return trace_reduce.load(os.path.join(DATA, "tiny_1chip.xplane.pb"))


@pytest.fixture(scope="module")
def tiny_hlo():
    with open(os.path.join(DATA, "tiny_1chip.hlo.txt")) as f:
        return hlo_text.HloIndex(f.read())


def test_recorded_trace_structure(tiny, tiny_hlo):
    (device,) = tiny.devices
    assert device.name == "/device:TPU:0"
    assert len(device.modules) == 6 and len(device.ops) == 18
    assert tiny_hlo.module == "jit__lambda"
    assert len(device.runs_of(tiny_hlo.module)) == 6
    assert len(device.ops_of(tiny_hlo.module)) == 18
    assert device.ops_of("jit_other") == []
    assert {(op.name, op.opcode) for op in device.ops} == {
        ("copy-start", "copy-start"), ("copy-done", "copy-done"),
        ("fusion.1", "fusion"),
    }
    assert [op.opcode for op in device.in_flight] == ["copy-start"] * 6
    names = [a.name for a in tiny.annotations]
    assert names.count(trace_reduce.DISPATCH) == 6 and names.count(trace_reduce.SYNC) == 2


def test_recorded_trace_busy_and_idle(tiny):
    (device,) = tiny.devices
    busy = trace_reduce.length(device.busy())
    # six runs of about 16.5 us each, measured by the profiler on the chip
    assert busy == pytest.approx(6 * 16.5e3, rel=0.02)
    assert tiny.busy_s() == pytest.approx(busy / 1e9)
    start, end = device.window()
    assert tiny.window_s() == pytest.approx((end - start) / 1e9)
    # the chip waits for the host between these tiny programs
    assert 0.95 < tiny.idle_share() < 0.99
    gaps = tiny.idle_by_host_span(5)
    assert sum(s for _, s in gaps) == pytest.approx(tiny.window_s() - tiny.busy_s())
    assert any(label.startswith(trace_reduce.DISPATCH) for label, _ in gaps)


def test_recorded_trace_classification(tiny, tiny_hlo):
    # the trace names a fusion; the compiled text says it holds the dot
    assert tiny_hlo.kind("fusion.1") == hlo_text.MATMUL_CONV
    assert tiny_hlo.kind("copy-start") == hlo_text.OTHER
    assert tiny_hlo.kind("fusion.999") is None
    assert tiny_hlo.family("fusion.1") == "[matmul_conv] jit(<lambda>)/dot_general"
    assert tiny_hlo.family("copy-start.12") == "[?] copy-start.*"
    top = tiny.top_ops(1, per_step=6, label=lambda op: tiny_hlo.family(op.name))
    assert top[0][0] == "[matmul_conv] jit(<lambda>)/dot_general"
    assert top[0][1] == pytest.approx(16.5e-6, rel=0.02)


@pytest.mark.parametrize("text, want", [
    ("%fusion.1 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(bf16[8,8]{1,0} %x.1), kind=kOutput, calls=%fused_computation.2",
     ("fusion.1", "fusion")),
    ("%collective-permute-start.4 = (f32[25]{0}, f32[25]{0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(f32[25]{0} %p), source_target_pairs={{0,1}}",
     ("collective-permute-start.4", "collective-permute-start")),
    ("%_flash.24 = (bf16[64,1024,64]{2,1,0}, /*index=1*/f32[64,1024,8]{2,1,0}) custom-call(bf16[64,1024,64]{2,1,0} %b), custom_call_target=\"tpu_custom_call\"",
     ("_flash.24", "custom-call")),
    ("not an instruction", ("not an instruction", "")),
])
def test_event_text_parses(text, want):
    assert trace_reduce.parse_event(text) == want


@pytest.fixture(scope="module")
def four():
    return trace_reduce.load(os.path.join(DATA, "tiny_4chip.xplane.pb"))


@pytest.fixture(scope="module")
def four_hlo():
    with open(os.path.join(DATA, "tiny_4chip.hlo.txt")) as f:
        return hlo_text.HloIndex(f.read())


def test_recorded_four_chip_trace(four, four_hlo):
    assert [d.name for d in four.devices] == [f"/device:TPU:{i}" for i in range(4)]
    assert four_hlo.summary()["collectives"] == {
        "collective-permute": {"count": 1, "bytes": 2048 * 2048 * 2}
    }
    for device in four.devices:
        ops = device.ops_of(four_hlo.module)
        assert len(device.runs_of(four_hlo.module)) == 4 and len(ops) == 24
        by_kind = dict.fromkeys((hlo_text.MATMUL_CONV, hlo_text.COLLECTIVE, hlo_text.OTHER), 0)
        for op, ns in trace_reduce.self_times(ops):
            by_kind[four_hlo.kind(op.name)] += ns / 4
        # per run: two 2048^3 matmuls of ~91 us, and a permute whose -done
        # the core sits in for ~181 us: 8 MB at the ~46 GB/s of one link
        # direction, none of it hidden (the second matmul needs the result)
        assert by_kind[hlo_text.MATMUL_CONV] == pytest.approx(182e3, rel=0.02)
        assert by_kind[hlo_text.COLLECTIVE] == pytest.approx(182.5e3, rel=0.02)
        assert by_kind[hlo_text.OTHER] < 100
        executed = sum(
            1 for op in ops
            if trace_reduce.is_collective(op.opcode) and not op.opcode.endswith("-done")
        )
        assert executed == 4
    # the profiler writes the in-flight line for the first chip only
    assert [len(d.in_flight) for d in four.devices] == [8, 0, 0, 0]
    in_flight = trace_reduce.length(
        (op.start, op.end) for op in four.devices[0].in_flight
        if trace_reduce.is_collective(op.opcode)
    )
    assert in_flight / 4 == pytest.approx(183.5e3, rel=0.02)
    assert 0.4 < four.idle_share() < 0.55
    assert four.top_ops(1, per_step=4, label=lambda op: four_hlo.family(op.name))[0][0] == (
        "[collective] jit(body)/shard_map/ppermute"
    )


def test_interval_arithmetic():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]
    assert trace_reduce.length([(0, 2), (1, 3), (10, 11)]) == 4
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [(0, 2), (3, 5), (7, 9)]
    assert trace_reduce.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]
    assert trace_reduce.gaps([(1, 2), (4, 5)], (0, 6)) == [(0, 1), (2, 4), (5, 6)]


def test_exposed_collective_arithmetic_on_hand_made_intervals():
    """A step of 100: a conditional (10..60) holds a permute start (10..12),
    a fusion (12..40) and the permute's done (40..60); then a fusion. The
    transfer is in flight 10..60, the core sits in collective instructions
    for 2 + 20, and 28 of the 50 in flight are hidden behind the fusion."""
    ops = [
        Op("conditional.1", "conditional", 10, 60),
        Op("collective-permute-start.1", "collective-permute-start", 10, 12),
        Op("fusion.7", "fusion", 12, 40),
        Op("collective-permute-done.1", "collective-permute-done", 40, 60),
        Op("fusion.8", "fusion", 60, 100),
    ]
    own = {op.name: ns for op, ns in trace_reduce.self_times(ops)}
    assert own == {
        "conditional.1": 0, "collective-permute-start.1": 2, "fusion.7": 28,
        "collective-permute-done.1": 20, "fusion.8": 40,
    }
    exposed = sum(
        ns for op, ns in trace_reduce.self_times(ops)
        if trace_reduce.is_collective(op.opcode)
    )
    in_flight = trace_reduce.length([(10, 60)])
    assert (exposed, in_flight - exposed) == (22, 28)
    device = trace_reduce.DeviceTrace(
        "/device:TPU:0", ops,
        [Op("collective-permute-start.1", "collective-permute-start", 10, 60)],
        [Op("jit_body(1)", "", 5, 100)],
    )
    assert device.window() == (10, 100) and trace_reduce.length(device.busy()) == 90
    assert len(device.ops_of("jit_body")) == 5


def test_collective_stats_copy_agrees_with_the_programs():
    from bluefog_tpu.scaling import hlo_collective_stats

    hlo = "\n".join([
        "ENTRY %main (p: f32[4,8]) -> f32[4,8] {",
        "  %cp = (f32[4,8]{1,0}, f32[4,8]{1,0}, u32[], u32[]) collective-permute-start(f32[4,8]{1,0} %p), source_target_pairs={{0,1}}",
        "  %cpd = f32[4,8]{1,0} collective-permute-done(%cp)",
        "  %ar = f32[16]{0} all-reduce(f32[16]{0} %x), to_apply=%add",
        "  %ag = (bf16[2,8]{1,0}, bf16[8,8]{1,0}) all-gather-start(bf16[2,8]{1,0} %y), dimensions={0}",
        "}",
    ])
    stats = hlo_text.collective_stats(hlo)
    assert stats == hlo_collective_stats(hlo)
    assert stats["collective-permute"] == {"count": 1, "bytes": 128}
    index = hlo_text.HloIndex(hlo)
    assert index.kind("cp") == index.kind("cpd") == index.kind("ar") == hlo_text.COLLECTIVE
