"""The fused train step donates its carry (``make_train_step(...,
donate=True)``, the default): parameters, optimizer state and the states the
optimizer owns (error feedback, the ``delayed=True`` double buffer) go into
the compiled program as ``donate_argnums`` and come back written in place.

jax 0.9 honours donation on the CPU backend, so every statement here bites on
the virtual CPU mesh: the inputs really are deleted, the compiled module
really carries an ``input_output_alias`` table. What is pinned: (a) who is
deleted after a call, (b) that donation changes no bit of any optimizer
family's trajectory, (c) the alias table and the two gauges, (d) the error a
carry that shares a buffer gets, (e) ``lower_last_fused_hlo`` after donated
steps, (f) the observation tiers never read a donated input's data, (g)
``opt.step`` keeps its inputs.
"""

import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import attribution, flight, health, memory
from bluefog_tpu import metrics, optimizers as opt_mod, slo, staleness
from bluefog_tpu import topology as tu
from bluefog_tpu.collective.plan import schedule_from_dynamic

SIZE = 8
# two dtypes, so the packed payload (and `delayed=True`'s double buffer) has
# two groups; `v` alone is over the 4 KiB cap and gossips in its own shape
SHAPES = {"w": ((6, 5), np.float32), "b": ((5,), np.float32),
          "v": ((1100,), np.float32), "h": ((12,), np.float16)}
PER_WORKER_BYTES = (30 + 5 + 1100) * 4 + 12 * 2


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", "4096")
    for name in ("BLUEFOG_SHARD", "BLUEFOG_SHARD_GRADS", "BLUEFOG_ASYNC"):
        monkeypatch.delenv(name, raising=False)
    yield
    bf.shutdown()


def _init(cpu_devices, **kw):
    bf.init(devices=cpu_devices[:SIZE], **kw)
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))


def _tree(seed, shapes=SHAPES):
    rng = np.random.RandomState(seed)
    out = {}
    for name, (shape, dtype) in shapes.items():
        rows = rng.randn(SIZE, *shape).astype(dtype)
        out[name] = bf.worker_values(lambda r, rows=rows: rows[r])
    return out


def quad_loss(p, c):
    return sum(
        0.5 * jnp.sum((p[k].astype(jnp.float32) - c[k].astype(jnp.float32)) ** 2)
        for k in sorted(p)
    )


def aux_loss(p, c):
    loss = quad_loss(p, c)
    return loss, {"seen": p["b"] * 2.0}


def _bits(tree):
    return [
        np.asarray(leaf).view(np.uint8).copy()
        for leaf in jax.tree_util.tree_leaves(tree)
    ]


def _deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(tree)]


def _gauge(name):
    series = metrics.peek(name)
    return None if series is None else series.value


def _aliases(hlo):
    """Entries of the compiled module's ``input_output_alias`` table."""
    table = re.search(r"input_output_alias=\{(.*?)\}, entry_computation", hlo)
    return re.findall(r"\(\d+, \{\}", table.group(1)) if table else []


# -- (a) who is deleted ----------------------------------------------------------


@pytest.mark.parametrize("donate", [True, False])
def test_a_call_consumes_its_carry_unless_told_not_to(cpu_devices, donate):
    _init(cpu_devices)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1, momentum=0.9))
    kw = {} if donate else {"donate": False}  # donation is the default
    step = bf.make_train_step(opt, quad_loss, **kw)
    params, c = _tree(0), _tree(1)
    state = opt.init(params)
    for _ in range(2):
        new_params, new_state, loss = step(params, state, c)
        jax.block_until_ready(loss)
        assert _deleted((params, state)) == [donate] * 8
        assert not any(_deleted((new_params, new_state, c)))
        params, state = new_params, new_state
    assert np.isfinite(np.asarray(loss)).all()


def test_opt_step_keeps_its_inputs(cpu_devices):
    """(g) ``opt.step`` never donates: its callers hold ``params`` (they
    took ``grads`` from them) and often ``grads`` too."""
    _init(cpu_devices)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1, momentum=0.9))
    params, grads = _tree(0), _tree(1)
    state = opt.init(params)
    new_params, new_state = opt.step(params, state, grads)
    jax.block_until_ready(new_params)
    assert not any(_deleted((params, state, grads, new_params, new_state)))
    assert _gauge("bluefog.step_donated_bytes") == 0
    assert _gauge("bluefog.step_donation_unused") == 0
    np.testing.assert_array_equal(  # and reads them again, to the bit
        _bits(opt.step(params, state, grads)[0])[0], _bits(new_params)[0]
    )


# -- (b) the same bits, family by family -----------------------------------------


def _dynamic(opt):
    exp2 = tu.ExponentialTwoGraph(SIZE)
    opt.schedule = schedule_from_dynamic(
        SIZE, lambda r: tu.GetDynamicOnePeerSendRecvRanks(exp2, r)
    )


def _atc(tx, **kw):
    return bf.DistributedAdaptThenCombineOptimizer(
        tx, bf.CommunicationType.neighbor_allreduce, **kw
    )


def _int8_ef(opt):
    opt.compression = "int8_ef"


# name -> (factory, configure(opt), make_train_step kwargs, env, init kwargs)
FAMILIES = {
    "cta": (bf.DistributedNeighborAllreduceOptimizer, None, {}, {}, {}),
    "cta_dynamic": (bf.DistributedNeighborAllreduceOptimizer, _dynamic, {}, {}, {}),
    "atc": (_atc, None, {}, {}, {}),
    "cta_k2": (
        lambda tx: bf.DistributedNeighborAllreduceOptimizer(
            tx, num_steps_per_communication=2
        ), None, {}, {}, {},
    ),
    "grad_allreduce_k1": (bf.DistributedGradientAllreduceOptimizer, None, {}, {}, {}),
    "grad_allreduce_k2": (
        lambda tx: bf.DistributedGradientAllreduceOptimizer(
            tx, num_steps_per_communication=2
        ), None, {}, {}, {},
    ),
    "has_aux": (
        bf.DistributedNeighborAllreduceOptimizer, None, {"has_aux": True}, {}, {},
    ),
    "delayed_cta": (
        bf.DistributedNeighborAllreduceOptimizer, None, {"delayed": True}, {}, {},
    ),
    "delayed_atc": (_atc, None, {"delayed": True}, {}, {}),
    "int8_ef": (bf.DistributedNeighborAllreduceOptimizer, _int8_ef, {}, {}, {}),
    "hierarchical": (
        bf.DistributedHierarchicalNeighborAllreduceOptimizer, None, {}, {},
        {"nodes_per_machine": 4},
    ),
    "zero1": (
        bf.DistributedGradientAllreduceOptimizer, None, {},
        {"BLUEFOG_SHARD": "1"}, {},
    ),
    "zero2_int8_ef": (
        bf.DistributedGradientAllreduceOptimizer, _int8_ef, {},
        {"BLUEFOG_SHARD": "1", "BLUEFOG_SHARD_GRADS": "1"}, {},
    ),
    "metrics_sampled": (
        bf.DistributedNeighborAllreduceOptimizer, None, {},
        {"BLUEFOG_METRICS": "1", "BLUEFOG_METRICS_INTERVAL": "2"}, {},
    ),
}


def _optimizer_state(opt, names=("_ef", "_scatter_ef", "_delay_buf")):
    """What the optimizer itself carries from step to step and the step
    writes in place. (The gradient accumulator of K > 1 is not donated:
    the call that consumes it returns nothing of its shape.)"""
    return [getattr(opt, name, None) or () for name in names]


def _three_steps(cpu_devices, family, donate):
    factory, configure, step_kw, _env, init_kw = FAMILIES[family]
    _init(cpu_devices, **init_kw)
    if family == "hierarchical":
        bf.set_machine_topology(tu.RingGraph(2))
    opt = factory(optax.sgd(0.05, momentum=0.9))
    if configure:
        configure(opt)
    loss_fn = aux_loss if step_kw.get("has_aux") else quad_loss
    step = bf.make_train_step(opt, loss_fn, donate=donate, **step_kw)
    shapes = SHAPES
    if family.startswith("zero"):  # a ZeRO layout shards one dtype group
        shapes = {k: v for k, v in SHAPES.items() if v[1] is np.float32}
    params = _tree(0, shapes)
    c = _tree(1, shapes)
    state = opt.init(params)
    outs = []
    for _ in range(3):
        was = (params, state, _optimizer_state(opt))
        params, state, out = step(params, state, c)
        jax.block_until_ready((params, state, out))
        gone = _deleted(was)
        assert all(gone) if donate else not any(gone), (family, gone)
        outs.append(_bits(out))
    unused = _gauge("bluefog.step_donation_unused")
    carried = _optimizer_state(opt) + _optimizer_state(opt, ("_grad_accum",))
    result = _bits((params, state, carried)), outs, unused
    bf.shutdown()
    return result


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_donation_changes_no_bit(cpu_devices, monkeypatch, family):
    """Three steps with and without donation: parameters, state, the
    optimizer's own buffers, loss and aux are bit-equal, every input of a
    donating call is deleted (the optimizer's EF / scatter-EF / delay
    buffers included) and none of a keeping one — and every family writes
    all of its donated leaves in place (``step_donation_unused`` 0)."""
    for name, value in FAMILIES[family][3].items():
        monkeypatch.setenv(name, value)
    kept, kept_outs, _ = _three_steps(cpu_devices, family, donate=False)
    donated, donated_outs, unused = _three_steps(cpu_devices, family, donate=True)
    assert len(kept) == len(donated) and len(kept) > 0
    for a, b in zip(kept, donated):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(sum(kept_outs, []), sum(donated_outs, [])):
        np.testing.assert_array_equal(a, b)
    assert unused == 0


@pytest.mark.parametrize("wrapper", ["async_off_env", "async_off_arg", "elastic_guard"])
@pytest.mark.parametrize("donate", [True, False])
def test_the_wrappers_forward_donate(cpu_devices, monkeypatch, wrapper, donate):
    """``make_async_train_step`` with async off IS the synchronous step,
    and ``bf.elastic.guard(opt).make_train_step`` wraps it: both hand
    ``donate`` through, donating by default."""
    if wrapper == "async_off_env":
        monkeypatch.setenv("BLUEFOG_ASYNC", "0")
    _init(cpu_devices)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    kw = {} if donate else {"donate": False}
    if wrapper == "elastic_guard":
        bf.elastic.start()
        step = bf.elastic.guard(opt).make_train_step(quad_loss, **kw)
    elif wrapper == "async_off_arg":
        step = bf.make_async_train_step(opt, quad_loss, enabled=False, **kw)
    else:
        step = opt.make_async_train_step(quad_loss, **kw)
    assert not hasattr(step, "engine")
    params, c = _tree(0), _tree(1)
    state = opt.init(params)
    new_params, _state, loss = step(params, state, c)
    jax.block_until_ready(loss)
    assert _deleted(params) == [donate] * 4
    assert not any(_deleted(new_params))
    if wrapper == "elastic_guard":
        bf.elastic.stop()


# -- (c) the alias table and the two gauges --------------------------------------


@pytest.mark.parametrize("case", ["sync", "delayed", "int8_ef", "kept"])
def test_alias_table_and_gauges(cpu_devices, case):
    """One alias per donated leaf in the compiled step; the gauges say how
    many bytes a worker's step writes in place and that none was left
    over; the ``compile`` event carries the bytes. (e) The text
    ``lower_last_fused_hlo`` gives after donated steps is the donating
    program's — it holds avals, not the arrays the steps consumed."""
    _init(cpu_devices)
    flight.reconfigure()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1, momentum=0.9))
    if case == "int8_ef":
        opt.compression = "int8_ef"
    step = bf.make_train_step(
        opt, quad_loss, delayed=case == "delayed", donate=case != "kept"
    )
    params, c = _tree(0), _tree(1)
    state = opt.init(params)
    for _ in range(2):
        params, state, loss = step(params, state, c)
        jax.block_until_ready(loss)
    f32_elems = 30 + 5 + 1100
    leaves, want_bytes = {
        # parameters + momentum
        "sync": (8, 2 * PER_WORKER_BYTES),
        # + one double buffer per dtype group, as wide as the group
        "delayed": (10, 3 * PER_WORKER_BYTES),
        # + per group x_hat_self [d] and x_hat_recv [rounds, d], float32
        "int8_ef": (12, None),
        "kept": (0, 0),
    }[case]
    if case == "int8_ef":
        rounds = opt._ef[0][1].shape[1]
        want_bytes = 2 * PER_WORKER_BYTES + 4 * (1 + rounds) * (f32_elems + 12)
    assert _gauge("bluefog.step_donated_bytes") == want_bytes
    assert _gauge("bluefog.step_donation_unused") == 0
    compiles = [
        e["data"] for e in flight.events()
        if e["kind"] == "compile" and e["data"]["name"] == "opt_fused_step"
    ]
    assert compiles and compiles[-1]["donated_bytes"] == want_bytes
    hlo = opt.lower_last_fused_hlo(params, state, c)
    assert len(_aliases(hlo)) == leaves
    assert not any(_deleted((params, state)))  # lowering consumes nothing
    params, state, loss = step(params, state, c)  # and the step still runs
    assert np.isfinite(np.asarray(loss)).all()


def test_donate_is_part_of_the_cache_key(cpu_devices):
    """Two builders over one optimizer and one loss, one donating and one
    not, are two programs: neither may be handed the other's."""
    _init(cpu_devices)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    keeping = opt.make_train_step(quad_loss, donate=False)
    donating = opt.make_train_step(quad_loss)
    params, c = _tree(0), _tree(1)
    state = opt.init(params)
    p1, s1, _ = keeping(params, state, c)
    assert not any(_deleted(params))
    p2, s2, _ = donating(p1, s1, c)
    assert all(_deleted(p1))
    keeping(p2, s2, c)
    assert not any(_deleted(p2))
    keys = [k for k in bf.get_context().op_cache if k[0] == "opt_fused_step"]
    assert sorted(k[8] for k in keys) == [False, True]


def test_unusable_donations_become_a_number():
    """``_unused_donations_counted`` turns jax's "Some donated buffers
    were not usable" into ``bluefog.step_donation_unused`` (one per leaf
    named) and raises every other warning again."""
    def shrink(a, b, keep):
        warnings.warn("something else", UserWarning)
        return a.sum() + b.sum() + keep

    fn = jax.jit(shrink, donate_argnums=(0, 1))
    a, b = jnp.ones((8, 3)), jnp.ones((4,), jnp.int32)
    metrics.gauge("bluefog.step_donation_unused").set(0)
    with pytest.warns(UserWarning, match="something else") as caught:
        with opt_mod._unused_donations_counted():
            out = fn(a, b, 1.0)
    assert float(out) == 29.0
    assert not any("donated buffers" in str(w.message) for w in caught)
    assert _gauge("bluefog.step_donation_unused") == 2


# -- (d) a carry that shares a buffer --------------------------------------------


@pytest.mark.parametrize("shared", ["state_holds_a_parameter", "a_parameter_twice"])
def test_a_shared_buffer_is_refused_where_the_step_is_built(cpu_devices, shared):
    _init(cpu_devices)
    params, c = _tree(0), _tree(1)
    if shared == "a_parameter_twice":
        params["w2"], c["w2"] = params["w"], c["w"]
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
        state = opt.init(params)
    else:
        # an inner transformation whose state IS the parameters it was
        # initialised with (a "previous iterate" kept by reference)
        tx = optax.GradientTransformation(
            lambda p: {"anchor": p},
            lambda g, s, p=None: (
                jax.tree_util.tree_map(lambda x: -0.1 * x, g), s
            ),
        )
        opt = bf.DistributedNeighborAllreduceOptimizer(tx)
        state = jax.tree_util.tree_map(lambda x: x, {"anchor": params})
    step = bf.make_train_step(opt, quad_loss)
    with pytest.raises(ValueError, match="one buffer.*donate=False"):
        step(params, state, c)
    assert not any(_deleted((params, state)))  # refused before any dispatch
    # the way out the message names
    keeping = bf.make_train_step(opt, quad_loss, donate=False)
    new_params, _state, loss = keeping(params, state, c)
    assert np.isfinite(np.asarray(loss)).all()
    assert not any(_deleted((params, state)))


# -- (f) the observation tiers ---------------------------------------------------


def test_tiers_on_touch_no_donated_array(cpu_devices, monkeypatch):
    """Doctor, health, staleness, memory, SLO and the device metrics, every
    step sampled: what runs after the dispatch reads the outputs, and of
    a donated input only shapes and dtypes."""
    for tier in ("DOCTOR", "HEALTH", "STALENESS", "MEMORY", "SLO", "METRICS"):
        monkeypatch.setenv(f"BLUEFOG_{tier}", "1")
        monkeypatch.setenv(f"BLUEFOG_{tier}_INTERVAL", "1")
    _init(cpu_devices)
    try:
        for tier in (attribution, health, staleness, memory, slo):
            assert tier.active() is not None, tier.__name__
        for delayed in (False, True):
            opt = bf.DistributedNeighborAllreduceOptimizer(
                optax.sgd(0.05, momentum=0.9)
            )
            step = bf.make_train_step(opt, quad_loss, delayed=delayed)
            params, c = _tree(0), _tree(1)
            state = opt.init(params)
            for _ in range(4):
                was = (params, state)
                params, state, loss = step(params, state, c)
                jax.block_until_ready(loss)
                assert all(_deleted(was))
            assert np.isfinite(np.asarray(loss)).all()
            assert _gauge("bluefog.step_donation_unused") == 0
        assert memory.active().samples, "the census never ran"
    finally:
        for tier in (attribution, health, staleness, memory):
            tier.stop()
        slo.activate(None)
