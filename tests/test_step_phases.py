# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""The measurement inside the fused train step: five host phases per
``train_step`` call (flight ring events + profiler annotations, one
``step`` on all of them), the ``bf.*`` scopes in the compiled step's
``op_name``s, and a name on every Pallas kernel. No test asserts a time:
only order, nesting and that the parts add up."""

import ast
import glob
import os
import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

import bluefog_tpu as bf
from bluefog_tpu import flight

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 8
BOUNDARIES = list(flight.STEP_PHASES.values()) + [flight.STEP_END]
SCOPES = ("bf.loss_grad", "bf.pack", "bf.gossip", "bf.unpack", "bf.inner_update")


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    monkeypatch.delenv("BLUEFOG_FLIGHT", raising=False)
    monkeypatch.delenv("BLUEFOG_FLIGHT_CAPACITY", raising=False)
    bf.init(devices=cpu_devices[:SIZE], nodes_per_machine=2)
    yield
    bf.shutdown()
    flight.reconfigure()


def toy():
    """Two f32 leaves, so that packing has something to concatenate."""
    params = {
        "w": bf.worker_values(lambda r: np.full((4, 3), r, np.float32)),
        "b": bf.worker_values(lambda r: np.zeros((3,), np.float32)),
    }
    x = bf.worker_values(lambda r: np.ones((5, 4), np.float32) * (r + 1))
    return params, x


def loss_fn(p, x):
    return jnp.mean((x @ p["w"] + p["b"] - 1.0) ** 2)


def run_steps(opt, step, k, params=None, x=None):
    if params is None:
        params, x = toy()
    state = opt.init(params)
    for _ in range(k):
        params, state, loss = step(params, state, x)
        jax.block_until_ready(loss)  # the CPU mesh's rendezvous wants it
    return params, state


def step_events():
    return [e for e in flight.events() if e["kind"] in BOUNDARIES]


# -- A: host phases ------------------------------------------------------------


def test_five_phases_in_order_with_one_step_and_they_sum_to_the_root():
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    run_steps(opt, bf.make_train_step(opt, loss_fn), 3)
    evs = step_events()
    assert [e["kind"] for e in evs] == BOUNDARIES * 3
    for i in range(3):
        call = evs[6 * i:6 * i + 6]
        assert [e["data"]["step"] for e in call] == [i] * 6
        stamps = [e["t_us"] for e in call]
        assert stamps == sorted(stamps)
    calls = flight.step_phases()
    assert [c["step"] for c in calls] == [0, 1, 2]
    for c, first in zip(calls, evs[::6]):
        assert c["t_us"] == first["t_us"]
        # each phase starts where the one before ended: no gap, no overlap
        assert sum(c[name] for name in flight.STEP_PHASES) == c["total"]
        assert all(c[name] >= 0 for name in flight.STEP_PHASES)


def test_step_begin_and_step_dispatched_keep_count_and_payload():
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    run_steps(opt, bf.make_train_step(opt, loss_fn), 3)
    evs = flight.events()
    begins = [e["data"] for e in evs if e["kind"] == "step_begin"]
    ends = [e["data"] for e in evs if e["kind"] == "step_dispatched"]
    assert begins == [{"step": i, "comm": True, "fused": True} for i in range(3)]
    assert ends == [{"step": i} for i in range(3)]


def test_step_phases_keeps_only_the_calls_inside_the_interval():
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    run_steps(opt, bf.make_train_step(opt, loss_fn), 4)
    calls = flight.step_phases()
    t0, t1 = calls[1]["t_us"], calls[2]["t_us"] + calls[2]["total"]
    assert [c["step"] for c in flight.step_phases(t0, t1)] == [1, 2]
    assert flight.step_phases(t1 + 1, None) == calls[3:]
    assert flight.step_phases(None, t0 - 1) == calls[:1]


class CountingAnnotation:
    """Stands in for the profiler's annotations: counts what is open."""

    opened = closed = 0
    names = []

    def __init__(self, name, **kwargs):
        self.name = name
        CountingAnnotation.names.append((name, kwargs))

    def __enter__(self):
        CountingAnnotation.opened += 1
        return self

    def __exit__(self, *exc):
        CountingAnnotation.closed += 1


def test_a_raising_loss_leaves_no_annotation_open(monkeypatch):
    monkeypatch.setattr(flight._profiler, "TraceAnnotation", CountingAnnotation)
    monkeypatch.setattr(flight._profiler, "StepTraceAnnotation", CountingAnnotation)
    CountingAnnotation.opened = CountingAnnotation.closed = 0
    CountingAnnotation.names = []
    broken = {"now": True}

    def sometimes(p, x):
        if broken["now"]:
            raise ValueError("the loss function raised")
        return loss_fn(p, x)

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    step = bf.make_train_step(opt, sometimes)
    params, x = toy()
    state = opt.init(params)
    with pytest.raises(ValueError, match="the loss function raised"):
        step(params, state, x)
    # root + resolve, key, stage, enqueue were opened, and all were closed
    assert CountingAnnotation.opened == CountingAnnotation.closed == 5
    kinds = [e["kind"] for e in step_events()]
    assert kinds == BOUNDARIES[:4]  # it died in `enqueue`: no step_end
    assert flight.step_phases() == []

    broken["now"] = False
    params, state, loss = step(params, state, x)
    jax.block_until_ready(loss)
    assert CountingAnnotation.opened == CountingAnnotation.closed == 5 + 6
    (call,) = flight.step_phases()
    assert sum(call[name] for name in flight.STEP_PHASES) == call["total"]
    # every annotation of the whole call carries the call's step
    whole = CountingAnnotation.names[5:]
    assert [n for n, _ in whole] == [flight.STEP_ROOT] + [
        f"{flight.STEP_ROOT}/{name}" for name in flight.STEP_PHASES
    ]
    assert whole[0][1] == {"step_num": call["step"]}
    assert all(kw == {"step": call["step"]} for _, kw in whole[1:])


def test_flight_off_records_nothing_and_trains_the_same_bits(
    cpu_devices, monkeypatch
):
    def train():
        opt = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.1, momentum=0.9)
        )
        params, _ = run_steps(opt, bf.make_train_step(opt, loss_fn), 3)
        return {k: np.asarray(v).view(np.uint32) for k, v in params.items()}

    on = train()
    assert len(flight.step_phases()) == 3
    bf.shutdown()
    monkeypatch.setenv("BLUEFOG_FLIGHT", "0")
    bf.init(devices=cpu_devices[:SIZE], nodes_per_machine=2)
    off = train()
    assert flight.events() == [] and flight.step_phases() == []
    for k in on:
        np.testing.assert_array_equal(on[k], off[k])


def test_differs_at_names_the_component_a_forced_miss_changed():
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    step = bf.make_train_step(opt, loss_fn)
    params, x = toy()
    state = opt.init(params)

    def compiles():
        return [
            e["data"] for e in flight.events()
            if e["kind"] == "compile" and e["data"]["name"] == "opt_fused_step"
        ]

    params, state, loss = step(params, state, x)
    jax.block_until_ready(loss)
    # the toy's three small leaves are all packed (PR 28's two numbers)
    assert compiles() == [{
        "name": "opt_fused_step", "differs_at": None,
        "direct_bytes": 0, "packed_bytes": 60,
        # the carry, consumed: the parameters (plain SGD has no state)
        "donated_bytes": 60,
    }]
    params, state, loss = step(params, state, x)
    jax.block_until_ready(loss)
    assert len(compiles()) == 1  # a hit writes nothing
    opt.tx = optax.sgd(0.2)  # same state structure, new update rule
    params, state, loss = step(params, state, x)
    jax.block_until_ready(loss)
    # ("opt_fused_step", builder, order, communication, optimizer,
    #  tx version, ...): the inner transformation's version is component 5
    assert compiles()[-1]["differs_at"] == 5
    x2 = bf.worker_values(lambda r: np.ones((7, 4), np.float32))
    params, state, loss = step(params, state, x2)
    jax.block_until_ready(loss)
    # a new batch shape: the batch is the last leaf of the aval key, just
    # before the tree structure that closes the key
    newest = [k for k in bf.get_context().op_cache if k[0] == "opt_fused_step"][-1]
    assert compiles()[-1]["differs_at"] == len(newest) - 2


# -- B: device scopes and kernel names -------------------------------------------


def _optimizer(kind):
    tx = optax.sgd(0.1, momentum=0.9)
    if kind == "grad":
        return bf.DistributedGradientAllreduceOptimizer(tx), {}
    if kind == "atc":
        return bf.DistributedAdaptThenCombineOptimizer(tx), {}
    if kind == "hierarchical":
        bf.set_machine_topology(
            bf.topology.RingGraph(bf.machine_size()), is_weighted=True
        )
        return bf.DistributedHierarchicalNeighborAllreduceOptimizer(tx), {}
    opt = bf.DistributedNeighborAllreduceOptimizer(tx)
    return opt, ({"delayed": True} if kind == "delayed" else {})


@pytest.mark.parametrize("kind", ["cta", "atc", "grad", "delayed", "hierarchical"])
def test_every_scope_is_in_the_compiled_steps_op_names(kind):
    opt, kwargs = _optimizer(kind)
    step = bf.make_train_step(opt, loss_fn, **kwargs)
    params, x = toy()
    params, state = run_steps(opt, step, 1, params, x)
    hlo = opt.lower_last_fused_hlo(params, state, x)
    # the step has a name of its own (one more `jit_body` would share the
    # parent's persistent-cache key, which leaves metadata out, and with it
    # the parent's compiled text without the scopes)
    assert re.search(r"^HloModule jit_bf_step\b", hlo)
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in SCOPES:
        assert any(f"/{scope}/" in name for name in op_names), (scope, kind)
    # forward and backward are told apart under the loss's scope
    under = [n for n in op_names if "/bf.loss_grad/" in n]
    assert any("transpose(" in n for n in under)
    assert any("jvp(" in n and "transpose(" not in n for n in under)
    # and no scope is spelt any other way
    spelt = set(re.findall(r"bf\.[a-z_]+", " ".join(op_names)))
    assert spelt <= set(SCOPES), spelt


def test_every_pallas_call_has_a_name():
    calls = []
    for path in glob.glob(
        os.path.join(REPO, "bluefog_tpu", "**", "*.py"), recursive=True
    ):
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and (
                getattr(node.func, "attr", None) == "pallas_call"
                or getattr(node.func, "id", None) == "pallas_call"
            ):
                calls.append((os.path.relpath(path, REPO), node.lineno, {
                    k.arg for k in node.keywords
                }))
    assert len(calls) >= 4  # flash forward, dkv, dq and the wire kernels' one
    for path, line, keywords in calls:
        assert "name" in keywords, f"{path}:{line}: pallas_call without name="


# -- the profiler sees the same phases -------------------------------------------


def test_a_profiler_trace_holds_the_root_with_its_five_children(tmp_path):
    from jax.profiler import ProfileData

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    step = bf.make_train_step(opt, loss_fn)
    params, x = toy()
    state = opt.init(params)
    params, state, loss = step(params, state, x)  # compile outside the trace
    jax.block_until_ready(loss)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            params, state, loss = step(params, state, x)
            jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    found = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(flight.STEP_ROOT):
                    found.append((
                        e.start_ns, e.start_ns + e.duration_ns, e.name,
                        dict(e.stats),
                    ))
    found.sort()
    roots = [f for f in found if f[2] == flight.STEP_ROOT]
    assert [r[3]["step_num"] for r in roots] == [1, 2, 3]
    for start, end, _, stats in roots:
        children = [
            f for f in found
            if f[2] != flight.STEP_ROOT and start <= f[0] and f[1] <= end
        ]
        assert [c[2] for c in children] == [
            f"{flight.STEP_ROOT}/{name}" for name in flight.STEP_PHASES
        ]
        assert all(c[3]["step"] == stats["step_num"] for c in children)
        # in order, none inside another
        assert all(a[1] <= b[0] for a, b in zip(children, children[1:]))
    assert len(found) == 3 * 6
