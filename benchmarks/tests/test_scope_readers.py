"""The readers PR 24 adds (``harness/scopes.py`` and the fourteen files
that use it): the host function on the flight ring a toy CPU run really
left, the device function on a hand-written step and a handful of events,
and every reader ``None`` where the rehearsal runs and a number on a ``Run``
marked as on the chip. Nothing here is a speed."""

import json
import os
import time

import jax
import pytest

from bluefog_tpu import flight

from benchmarks.harness import bench, cells, hlo_text, scopes, trace_reduce
from benchmarks.harness.trace_reduce import Op

import toy

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
HOST = (
    "host_resolve_ms", "host_key_ms", "host_stage_ms", "host_enqueue_ms",
    "host_train_step_mean_ms", "host_train_step_p90_ms",
)
DEVICE = (
    "forward_ms", "backward_ms", "pack_unpack_ms", "inner_update_ms",
    "combine_ms", "unscoped_device_ms",
)
FLASH = ("flash_fwd_ms", "flash_bwd_ms")
ON_THE_CHIP = {"bf16_flops_per_s": 197e12}  # what Run.peaks holds on a v5e


LM_CELLS = ["gpt2m_1chip_full", "gpt2m_1chip_b1"]


@pytest.mark.parametrize("name, source, layer, workloads", [
    *((n, "program_span", "optimizer_path", None) for n in HOST),
    ("forward_ms", "device_trace", "models", None),
    ("backward_ms", "device_trace", "models", None),
    ("pack_unpack_ms", "device_trace", "optimizer_path", None),
    ("inner_update_ms", "device_trace", "optimizer_path", None),
    ("combine_ms", "device_trace", "collectives", ["resnet50_4chip_onepeer"]),
    ("unscoped_device_ms", "device_trace", "device", None),
    ("flash_fwd_ms", "device_trace", "attention_kernels", LM_CELLS),
    ("flash_bwd_ms", "device_trace", "attention_kernels", LM_CELLS),
])
def test_the_benchmark_names_this_reader(name, source, layer, workloads):
    """Each entry looked up by its name: where it stands in ``per_layer``
    and what a later PR appends after it is not this test's business."""
    (entry,) = [m for m in cells.load_benchmark()["per_layer"] if m["name"] == name]
    assert (entry["source"], entry["layer"]) == (source, layer)
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
    assert entry["moves"] == "throughput_per_chip"
    assert entry.get("workloads") == workloads
    assert callable(bench.load_reader(name))


# -- host ---------------------------------------------------------------------------


def toy_run(monkeypatch, steps_per_block=None, capacity=None):
    """One untraced toy run on the CPU; -> (a Run over the ring and the
    spans it left, marked as on the chip; the steps of its window)."""
    if capacity:
        monkeypatch.setenv("BLUEFOG_FLIGHT_CAPACITY", str(capacity))
    if steps_per_block:
        monkeypatch.setattr(bench, "STEPS_PER_BLOCK", steps_per_block)
    cell = toy.cell(toy.RESNET, toy.traffic(), 1)
    cell.units.update({"throughput_per_chip": "unit/s", "peak_hbm_gib": "GiB", "setup_s": "s"})
    spans = bench.Spans(time.perf_counter())
    result = bench.run_cell(
        cell, seed=5, seconds=0.2, trace=False, spans=spans,
        info=lambda line: None, devices=jax.devices()[:1],
    )
    assert result["correct"]
    run = bench.Run(cell, 1, None, ON_THE_CHIP, spans)
    run.dispatch_s = [0.0] * result["attempted"]
    return run, result["attempted"]


def test_host_phases_of_the_ring_a_toy_run_left(monkeypatch):
    assert abs(time.perf_counter() - time.monotonic()) < scopes.CLOCK_SLACK_S
    run, steps = toy_run(monkeypatch)
    stats = scopes.host_phases(run)
    assert stats["calls"] == steps >= bench.STEPS_PER_BLOCK
    four = sum(stats[name] for name in ("resolve", "key", "stage", "enqueue"))
    assert 0 < four <= stats["mean"] * (1 + 1e-9)
    assert abs(four + stats["epilogue"] - stats["mean"]) < 1e-9
    assert stats["p90"] >= stats["p50"] > 0
    values = {name: bench.load_reader(name)(run) for name in HOST}
    assert values["host_train_step_mean_ms"] == stats["mean"]
    assert values["host_train_step_p90_ms"] == stats["p90"]
    assert values["host_enqueue_ms"] == stats["enqueue"]
    assert all(v is not None and v >= 0 for v in values.values())
    # the warm, traced and reference steps lie outside the window's span
    assert len(flight.step_phases()) == steps + bench.WARM_STEPS + bench.CHECK_STEPS
    # another span of the harness: what the profiler adds to the call is
    # read as ``traced_steps`` beside ``window`` (PERF.md, PR 24)
    assert scopes.host_phases(run, "warm_steps")["calls"] == bench.WARM_STEPS
    assert scopes.host_phases(run, "traced_steps") is None  # an untraced run

    # the same ring off the chip: a host time there is not a speed
    off_chip = bench.Run(run.cell, 1, None, None, run.spans)
    off_chip.dispatch_s = run.dispatch_s
    assert all(bench.load_reader(name)(off_chip) is None for name in HOST)
    # the harness counted another number of calls than the ring holds
    short = bench.Run(run.cell, 1, None, ON_THE_CHIP, run.spans)
    short.dispatch_s = run.dispatch_s[1:]
    assert scopes.host_phases(short) is None


def test_a_ring_too_small_for_the_window_gives_none(monkeypatch):
    # 64 asks for less than the ring's floor of 256 slots; a block of 50
    # steps writes 300 events, so the window's start is overwritten
    run, steps = toy_run(monkeypatch, steps_per_block=50, capacity=64)
    assert steps >= 50 and len(flight.events()) == 256
    assert scopes.host_phases(run) is None
    assert all(bench.load_reader(name)(run) is None for name in HOST)
    (t0, t1), = [(a, b) for name, a, b in run.spans.items if name == "window"]
    # and not a mean over the calls that are left
    assert flight.step_phases(int(t0 * 1e6), int(t1 * 1e6) + 1)


def test_a_program_without_the_phases_gives_none(monkeypatch):
    run, _ = toy_run(monkeypatch)
    monkeypatch.delattr(flight, "step_phases")  # the parent of PR 24
    assert scopes.host_phases(run) is None


# -- device -------------------------------------------------------------------------

SCOPED_HLO = """HloModule jit_bf_step, entry_computation_layout={()->f32[8]{0}}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %convolution.9 = f32[8]{0} convolution(%p, %p), dim_labels=b0f_0io->b0f
}

%fused_computation.2 (p.2: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  %convolution.20 = f32[8]{0} convolution(%p.2, %p.2), dim_labels=b0f_0io->b0f, metadata={op_name="jit(bf_step)/shard_map/bf.loss_grad/transpose(jvp(Net))/conv_general_dilated"}
  %multiply.21 = f32[8]{0} multiply(%convolution.20, %p.2), metadata={op_name="jit(bf_step)/shard_map/bf.inner_update/mul"}
  ROOT %add.22 = f32[8]{0} add(%multiply.21, %p.2), metadata={op_name="jit(bf_step)/shard_map/bf.inner_update/add"}
}

%fused_computation.3 (p.3: f32[8]) -> f32[1,8] {
  %p.3 = f32[8]{0} parameter(0)
  %constant.30 = f32[] constant(0.9)
  %broadcast.31 = f32[8]{0} broadcast(%constant.30), dimensions={}
  %slice.32 = f32[8]{0} slice(%p.3), slice={[0:8]}, metadata={op_name="jit(bf_step)/shard_map/bf.unpack/slice"}
  %multiply.33 = f32[8]{0} multiply(%slice.32, %broadcast.31), metadata={op_name="jit(bf_step)/shard_map/bf.inner_update/mul"}
  %add.34 = f32[8]{0} add(%multiply.33, %p.3), metadata={op_name="jit(bf_step)/shard_map/bf.inner_update/add"}
  ROOT %bitcast.35 = f32[1,8]{1,0} bitcast(%add.34), metadata={op_name="jit(bf_step)/shard_map/broadcast_in_dim"}
}

%fused_computation.4 (p.4: f32[8]) -> f32[8] {
  %p.4 = f32[8]{0} parameter(0)
  ROOT %bitcast.40 = f32[8]{0} bitcast(%p.4), metadata={op_name="jit(bf_step)/shard_map/bf.pack/reshape;jit(bf_step)/shard_map/squeeze"}
}

%fused_computation.5 (p.5: f32[8], q.5: f32[16]) -> f32[16] {
  %p.5 = f32[8]{0} parameter(0)
  %q.5 = f32[16]{0} parameter(1)
  %fusion.50 = f32[8]{0} fusion(%p.5), kind=kLoop, calls=%fused_computation.4
  %constant.51 = s32[] constant(8)
  ROOT %dynamic-update-slice.52 = f32[16]{0} dynamic-update-slice(%q.5, %fusion.50, %constant.51)
}

%fused_computation.6 (p.6: f32[8]) -> f32[8] {
  %p.6 = f32[8]{0} parameter(0)
  ROOT %copy.60 = f32[8]{0} copy(%p.6)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="params_b['w']"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(bf_step)/shard_map/bf.loss_grad/jvp(Net)/conv_general_dilated"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(bf_step)/shard_map/bf.loss_grad/transpose(jvp(Net))/conv_general_dilated"}
  %transpose.3 = f32[8]{0} transpose(%fusion.1), dimensions={0}, metadata={op_name="jit(bf_step)/shard_map/bf.loss_grad/jvp(Net)/transpose"}
  %bf_flash_fwd.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(bf_step)/shard_map/bf.loss_grad/jvp(Net)/jit(_flash)/bf_flash_fwd/pallas_call"}
  %bf_flash_dkv.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(bf_step)/shard_map/bf.loss_grad/transpose(jvp(Net))/jit(_flash)/bf_flash_dkv/pallas_call"}
  %bf_flash_dq.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(bf_step)/shard_map/bf.loss_grad/transpose(jvp(Net))/jit(_flash)/bf_flash_dq/pallas_call"}
  %unnamed.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(bf_step)/shard_map/bf.gossip/_encode8_body/pallas_call"}
  %concatenate.4 = f32[8]{0} concatenate(%fusion.2), dimensions={0}, metadata={op_name="jit(bf_step)/shard_map/bf.pack/concatenate"}
  %conditional.5 = f32[8]{0} conditional(%a, %concatenate.4), metadata={op_name="jit(bf_step)/shard_map/bf.gossip/cond"}
  %collective-permute-start.6 = f32[8]{0} collective-permute-start(%concatenate.4), metadata={op_name="jit(bf_step)/shard_map/bf.gossip/cond/branch_1_fun/ppermute"}
  %slice.7 = f32[8]{0} slice(%conditional.5), slice={[0:8]}, metadata={op_name="jit(bf_step)/shard_map/bf.unpack/slice"}
  %multiply.8 = f32[8]{0} multiply(%slice.7, %fusion.2), metadata={op_name="jit(bf_step)/shard_map/bf.inner_update/mul"}
  %add.9 = f32[8]{0} add(%multiply.8, %a), metadata={op_name="jit(bf_step)/shard_map/add"}
  %fusion.11 = f32[1,8]{1,0} fusion(%add.9), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(bf_step)/shard_map/broadcast_in_dim"}
  %bitcast_dynamic-update-slice_fusion.12 = f32[16]{0} fusion(%a, %a), kind=kLoop, calls=%fused_computation.5
  %fusion.13 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.6
  ROOT %copy.10 = f32[8]{0} copy(%add.9)
}
"""

# (instruction, opcode, start, end) ns: the permute runs inside the conditional
EVENTS = [
    Op("fusion.1", "fusion", 0, 100),
    Op("transpose.3", "transpose", 100, 110),
    Op("bf_flash_fwd.1", "custom-call", 110, 150),
    Op("fusion.2", "fusion", 150, 400),
    Op("bf_flash_dkv.1", "custom-call", 400, 470),
    Op("bf_flash_dq.1", "custom-call", 470, 500),
    Op("unnamed.1", "custom-call", 500, 505),
    Op("concatenate.4", "concatenate", 505, 525),
    Op("conditional.5", "conditional", 525, 600),
    Op("collective-permute-start.6", "collective-permute-start", 530, 590),
    Op("slice.7", "slice", 600, 615),
    Op("multiply.8", "multiply", 615, 650),
    Op("add.9", "add", 650, 657),
    Op("copy.10", "copy", 657, 670),
    Op("not-in-this-module.1", "fusion", 670, 673),
    Op("fusion.11", "fusion", 673, 773),
    Op("bitcast_dynamic-update-slice_fusion.12", "fusion", 773, 793),
    Op("fusion.13", "fusion", 793, 800),
]


def test_part_of_an_op_name():
    part = scopes.part_of
    assert part("jit(bf_step)/shard_map/bf.loss_grad/jvp(Net)/dot_general") == scopes.FORWARD
    assert part("jit(bf_step)/shard_map/bf.loss_grad/jvp(Net)/transpose") == scopes.FORWARD
    assert part("jit(bf_step)/shard_map/bf.loss_grad/transpose(jvp(Net))/mul") == scopes.BACKWARD
    assert part("jit(bf_step)/shard_map/bf.loss_grad/reshape") == scopes.FORWARD
    assert part("jit(loss)/jvp(bf.pack)/concatenate") == scopes.PACK_UNPACK
    assert part("jit(bf_step)/bf.inner_update/bf.pack/concatenate") == scopes.PACK_UNPACK
    assert part("jit(bf_step)/shard_map/bf.gossip/cond/branch_0_fun/mul") == scopes.COMBINE
    assert part("jit(bf_step)/shard_map/bf.inner_update/add") == scopes.INNER_UPDATE
    assert part("jit(bf_step)/shard_map/add") == scopes.UNSCOPED
    assert part("jit(bf_step)/shard_map/bf.packed/add") == scopes.UNSCOPED
    assert part(None) == part("") == scopes.UNSCOPED


def test_a_fusion_without_a_scope_of_its_own_takes_the_one_inside_it():
    hlo = hlo_text.HloIndex(SCOPED_HLO)
    bodies = scopes.fusion_bodies(SCOPED_HLO)
    assert bodies["fusion.11"] == [
        "p.3", "constant.30", "broadcast.31", "slice.32", "multiply.33",
        "add.34", "bitcast.35",
    ]
    assert bodies["fusion.50"] == ["p.4", "bitcast.40"]
    parts = scopes.instruction_parts(hlo.op_names, bodies)
    # rooted in `_tree_restack`'s bare reshape; two of the three scoped
    # instructions inside are the inner update's
    assert scopes.part_of(hlo.op_names["fusion.11"]) == scopes.UNSCOPED
    assert parts["fusion.11"] == scopes.INNER_UPDATE
    # no metadata; the scope is in the fusion inside the fusion
    assert parts["fusion.50"] == scopes.PACK_UNPACK
    assert parts["bitcast_dynamic-update-slice_fusion.12"] == scopes.PACK_UNPACK
    # nothing inside carries a scope
    assert parts["fusion.13"] == scopes.UNSCOPED
    # its own op_name names a scope: kept, though the update is fused in
    assert parts["fusion.2"] == scopes.BACKWARD
    # a tie goes to the first of PARTS
    tie = scopes.instruction_parts(
        {"a": "bf.inner_update/mul", "b": "bf.pack/reshape"}, {"f": ["a", "b"]},
    )
    assert tie["f"] == scopes.PACK_UNPACK
    assert "not-in-this-module.1" not in parts


def test_the_six_parts_sum_to_the_own_time_exactly():
    hlo = hlo_text.HloIndex(SCOPED_HLO)
    own = trace_reduce.self_times(EVENTS)
    parts = scopes.ns_by_part(
        scopes.instruction_parts(hlo.op_names, scopes.fusion_bodies(SCOPED_HLO)), own
    )
    assert set(parts) == set(scopes.PARTS)
    assert sum(parts.values()) == sum(ns for _, ns in own) == 800
    assert parts == {
        scopes.FORWARD: 100 + 10 + 40,
        scopes.BACKWARD: 250 + 70 + 30,
        scopes.PACK_UNPACK: 20 + 15 + 20,
        scopes.INNER_UPDATE: 35 + 100,
        scopes.COMBINE: 5 + (75 - 60) + 60,  # the conditional's own + the permute
        # bare glue, no metadata, not in the module, a fusion of XLA's own
        scopes.UNSCOPED: 7 + 13 + 3 + 7,
    }
    assert scopes.ns_by_kernel(hlo, own) == {
        "bf_flash_fwd": 40, "bf_flash_dkv": 70, "bf_flash_dq": 30,
        "_encode8_body": 5,  # a call without name=: in no reader's sum
    }


def chip_run(hlo_text_, events, steps=1, chips=2):
    run = bench.Run(None, chips, None, ON_THE_CHIP, bench.Spans(0.0))
    run.hlo = hlo_text.HloIndex(hlo_text_)
    end = max(e.end for e in events)
    run.trace = trace_reduce.Trace([
        trace_reduce.DeviceTrace(
            f"/device:TPU:{i}", events, [], [Op(f"{run.hlo.module}(7)", "", 0, end)],
        ) for i in range(chips)
    ], [])
    run.traced_steps = steps
    return run


def test_readers_on_a_scoped_step_marked_as_on_the_chip():
    run = chip_run(SCOPED_HLO, EVENTS)
    values = {n: bench.load_reader(n)(run) for n in DEVICE + FLASH}
    assert all(isinstance(v, float) and v > 0 for v in values.values()), values
    assert values["forward_ms"] == pytest.approx(150e-6)
    assert values["flash_fwd_ms"] == pytest.approx(40e-6)
    assert values["flash_bwd_ms"] == pytest.approx(100e-6)
    by_kind = run.device_ms_by_kind()
    assert sum(values[n] for n in DEVICE) == pytest.approx(sum(by_kind.values()))
    assert values["flash_fwd_ms"] + values["flash_bwd_ms"] == pytest.approx(
        by_kind["mosaic"] - 5e-6
    )
    # two steps traced: half the time per step
    assert bench.load_reader("backward_ms")(
        chip_run(SCOPED_HLO, EVENTS, steps=2)
    ) == pytest.approx(values["backward_ms"] / 2)


def test_nothing_under_the_gossip_scope_is_none_not_zero():
    local = "\n".join(
        line for line in SCOPED_HLO.splitlines() if "bf.gossip" not in line
    )
    gone = ("unnamed.1", "conditional.5", "collective-permute-start.6")
    run = chip_run(local, [e for e in EVENTS if e.name not in gone])
    assert bench.load_reader("combine_ms")(run) is None
    assert bench.load_reader("pack_unpack_ms")(run) > 0


def test_a_step_without_any_scope_gives_none():
    bare = SCOPED_HLO.replace("bf.", "").replace("bf_flash", "flash")
    run = chip_run(bare, EVENTS)
    assert scopes.device_ms_by_scope(run) is None
    assert all(bench.load_reader(n)(run) is None for n in DEVICE + FLASH)
    run.trace = None
    assert scopes.mosaic_ms_by_kernel(run) is None


@pytest.mark.parametrize("peaks", [None, ON_THE_CHIP], ids=["cpu", "chip"])
def test_every_new_reader_is_none_on_the_recorded_rehearsal(peaks):
    """What test_rehearsal.py's traced run hands the readers: the trace and
    HLO recorded on the v5e before the step carried scopes, and a ring with
    no window in it."""
    with open(os.path.join(DATA, "tiny_1chip.hlo.txt")) as f:
        recorded = f.read()
    run = bench.Run(None, 1, None, peaks, bench.Spans(time.perf_counter()))
    run.hlo = hlo_text.HloIndex(recorded)
    run.trace = trace_reduce.load(os.path.join(DATA, "tiny_1chip.xplane.pb"))
    run.traced_steps = 20
    assert run.device_ms_by_kind()["matmul_conv"] > 0
    for name in HOST + DEVICE + FLASH:
        assert bench.load_reader(name)(run) is None, name
    json.dumps(scopes.mosaic_ms_by_kernel(run))


def test_device_readers_on_a_scoped_trace_recorded_on_the_v5e():
    """``data/tiny_scoped.*``: two blocks of three fused steps of a one-layer
    toy LM (flash kernels) through ``bf.make_train_step`` on one v5e chip,
    recorded with PR 24's scopes and kernel names in the compiled step."""
    with open(os.path.join(DATA, "tiny_scoped.hlo.txt")) as f:
        hlo = hlo_text.HloIndex(f.read())
    run = bench.Run(None, 1, None, ON_THE_CHIP, bench.Spans(0.0))
    run.hlo = hlo
    run.trace = trace_reduce.load(os.path.join(DATA, "tiny_scoped.xplane.pb"))
    run.traced_steps = 6
    assert hlo.module == "jit_bf_step"
    assert len(run.trace.devices[0].runs_of(hlo.module)) == 6
    parts, kinds = scopes.device_ms_by_scope(run), run.device_ms_by_kind()
    assert sum(parts.values()) == pytest.approx(sum(kinds.values()))
    assert kinds["unresolved"] == 0
    for part in (scopes.FORWARD, scopes.BACKWARD, scopes.PACK_UNPACK, scopes.UNSCOPED):
        assert parts[part] > 0, part
    assert parts[scopes.BACKWARD] > parts[scopes.FORWARD]
    # the kernels rooted in `_tree_restack`'s bare `broadcast_in_dim` hold
    # the inner update's arithmetic and are read there, not as unscoped
    bodies = scopes.fusion_bodies(hlo.text)
    by_instruction = scopes.instruction_parts(hlo.op_names, bodies)
    restacked = [
        name for name in bodies
        if hlo.op_names.get(name) == "jit(bf_step)/broadcast_in_dim"
    ]
    assert len(restacked) == 4
    assert {by_instruction[name] for name in restacked} == {scopes.INNER_UPDATE}
    by_op_name_alone = scopes.ns_by_part(
        {name: scopes.part_of(op_name) for name, op_name in hlo.op_names.items()},
        trace_reduce.self_times(run.trace.devices[0].ops_of(hlo.module)),
    )
    assert parts[scopes.INNER_UPDATE] > 1.5 * by_op_name_alone[scopes.INNER_UPDATE] / 6e6
    kernels = scopes.mosaic_ms_by_kernel(run)
    assert set(kernels) == {"bf_flash_fwd", "bf_flash_dkv", "bf_flash_dq"}
    assert sum(kernels.values()) == pytest.approx(kinds["mosaic"])
    assert bench.load_reader("flash_fwd_ms")(run) == pytest.approx(kernels["bf_flash_fwd"])


# -- any scope names, any kernel names (PR 33) --------------------------------------

BF_SCOPES = ("bf.loss_grad", "bf.pack", "bf.unpack", "bf.gossip", "bf.inner_update")


def recorded_run(stem, steps):
    with open(os.path.join(DATA, f"{stem}.hlo.txt")) as f:
        hlo = hlo_text.HloIndex(f.read())
    run = bench.Run(None, 1, None, ON_THE_CHIP, bench.Spans(0.0))
    run.hlo = hlo
    run.trace = trace_reduce.load(os.path.join(DATA, f"{stem}.xplane.pb"))
    run.traced_steps = steps
    return run


def test_the_six_parts_of_the_recorded_trace_to_the_last_digit():
    """What ``device_ms_by_scope`` read of ``data/tiny_scoped.*`` before its
    vote became ``instruction_labels``: the same floats, not nearly."""
    run = recorded_run("tiny_scoped", 6)
    assert scopes.device_ms_by_scope(run) == {
        "forward": 0.022736333333333334, "backward": 0.029945166666666665,
        "pack_unpack": 0.0011586666666666666, "inner_update": 0.005178,
        "combine": 0.0004761666666666667, "unscoped": 0.0021923333333333335,
    }
    assert {n: bench.load_reader(n)(run) for n in DEVICE} == {
        "forward_ms": 0.022736333333333334, "backward_ms": 0.029945166666666665,
        "pack_unpack_ms": 0.0011586666666666666, "inner_update_ms": 0.005178,
        "combine_ms": 0.0004761666666666667,
        "unscoped_device_ms": 0.0021923333333333335,
    }


@pytest.mark.parametrize("stem", ["tiny_1chip", "tiny_4chip"])
def test_a_trace_recorded_before_the_scopes_names_none_of_them(stem):
    run = recorded_run(stem, 20)
    assert scopes.device_ms_by_scope(run) is None
    assert scopes.device_ms_by_scopes(run, BF_SCOPES) is None
    assert scopes.device_ms_by_scopes(run, BF_SCOPES, halves=True) is None
    # any word of an op_name is a scope name to this reader: with the
    # operation's own name it splits the step as ``device_ms_by_kind`` does
    split = scopes.device_ms_by_scopes(run, ("dot_general",))
    kinds = run.device_ms_by_kind()
    assert split["dot_general"] == pytest.approx(kinds["matmul_conv"])
    assert split["dot_general"] + split[None] == pytest.approx(sum(kinds.values()))


def test_the_step_by_the_scope_names_the_caller_gives():
    run = recorded_run("tiny_scoped", 6)
    parts = scopes.device_ms_by_scope(run)
    by_name = scopes.device_ms_by_scopes(run, BF_SCOPES)
    assert set(by_name) == {*BF_SCOPES, None}
    assert by_name["bf.loss_grad"] == pytest.approx(parts["forward"] + parts["backward"])
    assert by_name["bf.pack"] + by_name["bf.unpack"] == pytest.approx(parts["pack_unpack"])
    assert by_name["bf.gossip"] == parts["combine"]
    assert by_name["bf.inner_update"] == parts["inner_update"]
    assert by_name[None] == parts["unscoped"]
    halves = scopes.device_ms_by_scopes(run, BF_SCOPES, halves=True)
    assert halves["bf.loss_grad", "forward"] == parts["forward"]
    assert halves["bf.loss_grad", "backward"] == parts["backward"]
    assert sum(halves.values()) == pytest.approx(sum(parts.values()))
    # a made-up inner scope of a model's own: flax names the one block of
    # this toy LM ``Block_0``, and the innermost name decides, so its time
    # leaves ``bf.loss_grad``'s and the step's sum stays
    inner = scopes.device_ms_by_scopes(run, (*BF_SCOPES, "Block_0"), halves=True)
    block = inner["Block_0", "forward"], inner["Block_0", "backward"]
    assert 0 < block[0] < parts["forward"] and 0 < block[1] < parts["backward"]
    assert inner["bf.loss_grad", "forward"] == pytest.approx(parts["forward"] - block[0])
    assert inner["bf.loss_grad", "backward"] == pytest.approx(parts["backward"] - block[1])
    assert inner[None] == parts["unscoped"]
    # the flash kernels run inside the block
    kernels = scopes.mosaic_ms_by_kernel(run)
    assert block[0] > kernels["bf_flash_fwd"]
    # a name that is in no op_name reads 0.0 beside the others; none at all
    # is None, and `bf.pack` is not in `bf.packed`
    assert scopes.device_ms_by_scopes(run, ("bf.gossip", "router"))["router"] == 0.0
    assert scopes.device_ms_by_scopes(run, ("router", "experts")) is None
    assert scopes.scope_of(("bf.pack",))("jit(f)/bf.packed/add") is None
    assert scopes.scope_of(("a", "b"))("jit(f)/a/b/a/mul") == "a"
    assert scopes.scope_of(("a",))(None) is None


def test_the_vote_of_a_fusions_body_over_any_labels():
    hlo = hlo_text.HloIndex(SCOPED_HLO)
    labels = scopes.instruction_labels(
        hlo.op_names, scopes.fusion_bodies(SCOPED_HLO),
        scopes.scope_of(("bf.unpack", "bf.inner_update")),
        ("bf.unpack", "bf.inner_update"),
    )
    assert labels["fusion.11"] == "bf.inner_update"  # two votes to one
    assert labels["fusion.13"] is None
    # its own op_name names a scope, but none of these two: its body votes
    assert labels["fusion.2"] == "bf.inner_update"
    assert labels["slice.7"] == "bf.unpack"


def test_a_kernels_roof_is_read_against_the_kernels_it_names():
    run = recorded_run("tiny_scoped", 6)
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    kernels, mosaic = scopes.mosaic_ms_by_kernel(run), run.device_ms_by_kind()["mosaic"]
    cost = {"flops": 197e12 * 1e-6, "bytes": 819e9 * 5e-7}  # 1 us, compute binds
    every = scopes.kernel_roofline(run, cost)
    assert every == {"share": pytest.approx(1e-6 / (mosaic / 1e3)), "binds": "compute"}
    # what `flash_roofline` reads, which this PR leaves alone
    run.job = type("J", (), {"kernel_costs": lambda self: {"flash": cost}})()
    assert bench.load_reader("flash_roofline")(run) == 100.0 * every["share"]
    forward = scopes.kernel_roofline(run, {**cost, "kernels": ["bf_flash_fwd"]})
    assert forward["share"] == pytest.approx(1e-6 / (kernels["bf_flash_fwd"] / 1e3))
    backward = scopes.kernel_roofline(
        run, {**cost, "kernels": ["bf_flash_dkv", "bf_flash_dq"]}
    )
    assert 1 / forward["share"] + 1 / backward["share"] == pytest.approx(1 / every["share"])
    # a kernel that did not run has no roof to stand under, and off the
    # chip nothing has
    assert scopes.kernel_roofline(run, {**cost, "kernels": ["bf_expert_gmm"]}) is None
    run.peaks = None
    assert scopes.kernel_roofline(run, cost) is None
