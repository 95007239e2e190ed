"""The small operands the host builds for a step — the step index and the
weight operands ``wops`` — reach every chip from the host, committed to and
replicated over the step's own mesh (``optimizers._replicated``). Built with
``jnp.asarray`` they live on device 0 alone and every call reshards them:
on four v5e chips that hand-over cost each step 20-25 ms (PERF.md, PR 25).

Here, on a 4-device CPU mesh: what the compiled step really receives, the
counter that guards it, that nothing in the trajectory moved by a bit, and
that weights reassigned between steps still ride one compiled program.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import metrics
from bluefog_tpu import optimizers as opt_mod
from bluefog_tpu import topology as tu
from bluefog_tpu.collective.plan import schedule_from_dynamic

SIZE = 4
DIM = 6
RESHARDED = "bluefog.step_operands_resharded"


CTA = bf.DistributedNeighborAllreduceOptimizer
ATC = bf.DistributedAdaptThenCombineOptimizer


def _init_static(devices, factory=CTA):
    bf.init(devices=devices)
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    return factory(_tx())


def _init_one_peer(devices, factory=CTA):
    bf.init(devices=devices)
    exp2 = tu.ExponentialTwoGraph(SIZE)
    opt = factory(_tx())
    opt.schedule = schedule_from_dynamic(
        SIZE, lambda r: tu.GetDynamicOnePeerSendRecvRanks(exp2, r)
    )
    return opt


def _init_hierarchical(devices):
    bf.init(devices=devices, nodes_per_machine=2)
    bf.set_machine_topology(tu.RingGraph(2))
    return bf.DistributedHierarchicalNeighborAllreduceOptimizer(_tx())


# name -> (builder, number of weight operands the step takes)
FAMILIES = {
    "static_exp2": (_init_static, 2),
    "one_peer_exp2": (_init_one_peer, 0),
    "hierarchical": (_init_hierarchical, 2),
}


@pytest.fixture
def devices(cpu_devices):
    metrics.reset()
    yield cpu_devices[:SIZE]
    bf.shutdown()
    metrics.reset()


def _tx():
    return optax.sgd(0.1, momentum=0.9)


def _params():
    rng = np.random.RandomState(0)
    w = rng.randn(SIZE, DIM, DIM).astype(np.float32)
    b = rng.randn(SIZE, DIM).astype(np.float32)
    return {
        "w": bf.worker_values(lambda r: w[r]),
        "b": bf.worker_values(lambda r: b[r]),
    }


def _batch():
    x = np.random.RandomState(1).randn(SIZE, 3, DIM).astype(np.float32)
    return bf.worker_values(lambda r: x[r])


def loss_fn(p, x):
    return jnp.mean(jnp.tanh(x @ p["w"] + p["b"]) ** 2)


def _stepper(opt, path):
    """``(params, state) -> (params, state, loss)`` through the fused step
    or through ``opt.step`` behind a gradient program of the caller's."""
    x = _batch()
    if path == "fused":
        fused = bf.make_train_step(opt, loss_fn)
        return lambda p, s: fused(p, s, x)
    grad = jax.jit(jax.vmap(jax.value_and_grad(loss_fn)))

    def step(p, s):
        loss, g = grad(p, x)
        p, s = opt.step(p, s, g)
        return p, s, loss

    return step


def _run(step, params, state, n):
    losses = []
    for _ in range(n):
        params, state, loss = step(params, state)
        jax.block_until_ready((params, state, loss))
        losses.append(loss)
    return params, state, losses


def _spy_on_compiled_steps(ctx, seen):
    """Wrap every compiled step in ``ctx.op_cache`` so that ``seen`` gets
    the step index and the weight operands of each later call."""
    index_at = {"opt_fused_step": 2, "opt_step": 3}
    for key, fn in list(ctx.op_cache.items()):
        if isinstance(key, tuple) and key and key[0] in index_at:
            def spy(*args, _fn=fn, _at=index_at[key[0]]):
                seen.append((args[_at], args[_at + 1]))
                return _fn(*args)

            ctx.op_cache[key] = spy


def _resharded():
    series = metrics.peek(RESHARDED)
    return 0 if series is None else series.value


def _bits(tree):
    return [
        np.asarray(leaf).view(np.uint32)
        for leaf in jax.tree_util.tree_leaves(tree)
    ]


@pytest.mark.parametrize("path", ["fused", "opt_step"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_host_operands_arrive_replicated_on_the_steps_mesh(
    devices, family, path
):
    build, n_wops = FAMILIES[family]
    opt = build(devices)
    ctx = bf.get_context()
    mesh = ctx.machine_mesh if family == "hierarchical" else ctx.mesh
    step = _stepper(opt, path)
    params = _params()
    params, state, _ = _run(step, params, opt.init(params), 1)
    seen = []
    _spy_on_compiled_steps(ctx, seen)
    _run(step, params, state, 3)
    assert len(seen) == 3
    for k, (step_idx, wops) in enumerate(seen):
        assert step_idx.dtype == jnp.int32 and step_idx.shape == (1,)
        assert int(step_idx[0]) == k + 1
        assert len(wops) == n_wops
        for a in (step_idx,) + tuple(wops):
            assert isinstance(a, jax.Array) and a.committed
            assert a.sharding.is_fully_replicated
            assert set(a.sharding.device_set) == set(mesh.devices.flat)
    assert _resharded() == 0
    assert metrics.peek("bluefog.recompiles").value == 1


@pytest.mark.parametrize("path", ["fused", "opt_step"])
def test_counter_counts_an_operand_made_on_device_zero(
    devices, path, monkeypatch
):
    """The parent's construction, handed to the same step: one count per
    operand and call (index + self_w + recv_w on a static topology)."""
    monkeypatch.setattr(opt_mod, "_replicated", lambda mesh, v: jnp.asarray(v))
    opt = _init_static(devices)
    params = _params()
    _run(_stepper(opt, path), params, opt.init(params), 2)
    assert _resharded() == 2 * 3


@pytest.mark.parametrize("family", ["static_exp2", "one_peer_exp2"])
@pytest.mark.parametrize("order", ["cta", "atc"])
def test_fused_trajectory_bitwise_equals_old_operand_construction(
    devices, order, family, monkeypatch
):
    def three_steps():
        opt = FAMILIES[family][0](devices, ATC if order == "atc" else CTA)
        params = _params()
        out = _run(_stepper(opt, "fused"), params, opt.init(params), 3)
        bits = _bits(out)
        bf.shutdown()
        return bits

    new = three_steps()
    assert _resharded() == 0
    monkeypatch.setattr(opt_mod, "_replicated", lambda mesh, v: jnp.asarray(v))
    old = three_steps()
    assert _resharded() > 0  # the old construction really ran
    assert len(new) == len(old) > 3
    for a, b in zip(new, old):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("path", ["fused", "opt_step"])
def test_weights_reassigned_between_steps_ride_one_program(devices, path):
    """README's idiom on a fixed edge set: new ``self_weight`` /
    ``src_weights`` values are the very next step's operands, and the
    step compiles once. The learning rate is 0, so a step is the combine."""
    bf.init(devices=devices)
    bf.set_topology(tu.RingGraph(SIZE))
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    step = _stepper(opt, path)
    params = _params()
    state = opt.init(params)
    for self_w in (0.5, 0.2, 0.8):
        nb_w = (1.0 - self_w) / 2
        opt.self_weight = self_w
        opt.src_weights = [
            {(r - 1) % SIZE: nb_w, (r + 1) % SIZE: nb_w} for r in range(SIZE)
        ]
        before = {k: np.asarray(v) for k, v in params.items()}
        params, state, _ = _run(step, params, state, 1)
        for k, v in before.items():
            want = self_w * v + nb_w * (
                np.roll(v, 1, axis=0) + np.roll(v, -1, axis=0)
            )
            np.testing.assert_allclose(np.asarray(params[k]), want, rtol=1e-5)
    assert metrics.peek("bluefog.recompiles").value == 1
    assert _resharded() == 0
