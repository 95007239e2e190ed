"""The step core ``opt.step`` and ``make_train_step`` share
(``_GossipOptimizer._plan_step`` / ``_build_step`` / ``_finish_step``): the
two entry points run one body and one epilogue.

An exact pin on the body: with a loss linear in the parameters the gradient
is the constant ``c`` in both programs and no matmul precedes the update, so
the compiler has nothing to fuse the momentum update into and the two entry
points must agree to the bit (tests/test_overlap.py holds them to a few ulp
behind a transformer's backward pass, and says why). And the epilogue's
contract: the six ``observe_step`` hooks, once each, in the documented
order, after a communicating step only.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import optimizers as opt_mod
from bluefog_tpu import topology as tu
from bluefog_tpu.collective.plan import schedule_from_dynamic

SIZE = 8
SHAPES = {"w": (6, 5), "b": (5,), "v": (1100,)}  # `v` is over the 4 KiB cap
HOOKS = ["attribution", "health", "staleness", "autotune", "memory", "slo"]

FACTORIES = {
    "cta": bf.DistributedNeighborAllreduceOptimizer,
    "atc": lambda tx, **kw: bf.DistributedAdaptThenCombineOptimizer(
        tx, bf.CommunicationType.neighbor_allreduce, **kw
    ),
}


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    # a cap between the leaves' sizes: the gossip packs two and sends one alone
    monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", "4096")
    bf.init(devices=cpu_devices[:SIZE])
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    yield
    bf.shutdown()


def _tree(seed):
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape in SHAPES.items():
        rows = rng.randn(SIZE, *shape).astype(np.float32)
        out[name] = bf.worker_values(lambda r, rows=rows: rows[r])
    return out


def _copy(tree):
    """The fused step consumes its carry: what a test reads again after
    the call, or hands to a second optimizer, goes in as a copy."""
    return jax.tree_util.tree_map(jnp.copy, tree)


def linear_loss(p, c):
    return sum(jnp.sum(p[k] * c[k]) for k in sorted(p))


def _optimizer(order, schedule, **kw):
    opt = FACTORIES[order](optax.sgd(0.1, momentum=0.9), **kw)
    if schedule == "dynamic":
        exp2 = tu.ExponentialTwoGraph(SIZE)
        opt.schedule = schedule_from_dynamic(
            SIZE, lambda r: tu.GetDynamicOnePeerSendRecvRanks(exp2, r)
        )
    return opt


@pytest.mark.parametrize("schedule", ["static", "dynamic"])
@pytest.mark.parametrize("order", ["cta", "atc"])
def test_linear_loss_fused_equals_opt_step_to_the_bit(order, schedule):
    params, c = _tree(0), _tree(1)
    opt1 = _optimizer(order, schedule)
    p1, s1 = params, opt1.init(params)
    opt2 = _optimizer(order, schedule)
    p2, s2 = _copy(params), opt2.init(params)
    train_step = opt2.make_train_step(linear_loss)
    for _ in range(3):
        p1, s1 = opt1.step(p1, s1, c)
        p2, s2, loss = train_step(p2, s2, c)
        jax.block_until_ready((p1, s1, p2, s2))
    assert np.isfinite(np.asarray(loss)).all()
    moved = False
    for a, b, p0 in zip(*map(jax.tree_util.tree_leaves, (p1, p2, params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        moved |= not np.array_equal(np.asarray(a), np.asarray(p0))
    assert moved
    for a, b in zip(*map(jax.tree_util.tree_leaves, (s1, s2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _stepper(opt, path, c):
    if path == "fused":
        fused = opt.make_train_step(linear_loss)
        return lambda p, s: fused(p, s, c)[:2]
    return lambda p, s: opt.step(p, s, c)


@pytest.mark.parametrize("path", ["fused", "opt_step"])
def test_epilogue_calls_each_hook_once_in_order(path, monkeypatch):
    """A communicating step calls attribution, health, staleness, autotune,
    memory, SLO — once each, in that order, with the step's index; a call
    between communications (K = 2, cta and atc) calls none."""
    calls = []
    modules = {
        "attribution": opt_mod.attribution, "health": opt_mod.health_mod,
        "staleness": opt_mod.staleness_mod, "autotune": opt_mod.autotune_mod,
        "memory": opt_mod.memory_mod, "slo": opt_mod.slo_mod,
    }
    for name, module in modules.items():
        monkeypatch.setattr(
            module, "observe_step",
            lambda ctx, *, step, _name=name, **kw: calls.append(
                (_name, step, kw)
            ),
        )
    params, c = _tree(0), _tree(1)

    opt = _optimizer("cta", "static")
    step = _stepper(opt, path, c)
    p, s = _copy(params), opt.init(params)
    for k in range(2):
        del calls[:]
        p, s = step(p, s)
        assert [(name, at) for name, at, _ in calls] == [
            (name, k) for name in HOOKS
        ]
        by_name = {name: kw for name, _, kw in calls}
        assert by_name["staleness"]["payload_age"] == 0
        assert by_name["staleness"]["surface"] == "sync"
        # what the doctor waits on: the new parameters, or the fused loss
        outputs = by_name["attribution"]["outputs"]
        assert (outputs is p) if path == "opt_step" else (
            outputs.shape == (SIZE,)
        )
        # the caller's gradients are a live buffer of opt.step only
        assert (by_name["memory"]["grads"] is c) == (path == "opt_step")
        assert by_name["memory"]["params"] is p

    for order in ("cta", "atc"):
        opt = _optimizer(order, "static", num_steps_per_communication=2)
        step = _stepper(opt, path, c)
        p, s = _copy(params), opt.init(params)
        del calls[:]
        p, s = step(p, s)  # call 0 of K = 2: local update only
        assert calls == []
        p, s = step(p, s)  # call 1 communicates
        assert [(name, at) for name, at, _ in calls] == [
            (name, 1) for name in HOOKS
        ]
