# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Large leaves gossip in their own shape (PERF.md, PR 28).

``BLUEFOG_BUCKET_BYTES`` is a fusion *threshold* on the exact wire, as in
the reference's tensor-fusion buffer: leaves smaller than it are packed
into one flat payload and cut into buckets; a leaf at or over it is
combined alone, whole, with no flatten / slice / concatenate / unpack.
The quantized wires, error feedback, ``delayed=True`` and the ZeRO paths
keep every leaf packed: their state is positional over the flat vector.

On the 8-device CPU mesh: the trajectory is the all-packed one to the bit
and the dense-``W`` product, the compiled program holds exactly
rounds x (leaves alone + packed buckets) permutes and no array of a dtype
group's whole flat length, the two gauges say how the bytes were routed,
and ``_wire_payload`` prices the largest message.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import context as ctx_mod
from bluefog_tpu import flight
from bluefog_tpu import metrics
from bluefog_tpu import scaling
from bluefog_tpu import topology as tu
from bluefog_tpu.collective import inner
from bluefog_tpu.collective.plan import schedule_from_dynamic

SIZE = 8
ROUNDS = 3  # ExponentialTwoGraph(8) lowers to log2(8) ppermute rounds
CAP = 4096  # bytes: 1024 f32 elements
LR = 0.1
DIRECT = "bluefog.gossip_direct_bytes"
PACKED = "bluefog.gossip_packed_bytes"

# name -> (shape, dtype). f32: two leaves at or over the cap (one of them
# exactly one bucket) and three under it, 1370 elements = two buckets; bf16:
# one over (6144 B) and one small leaf, which is then alone in its group
MIXED = {
    "big": ((40, 32), np.float32),
    "exact": ((32, 32), np.float32),
    "s1": ((10, 7), np.float32),
    "s2": ((600,), np.float32),
    "s3": ((700,), np.float32),
    "hbig": ((64, 48), jnp.bfloat16),
    "hs": ((5,), jnp.bfloat16),
}
F32_FLAT = 40 * 32 + 32 * 32 + 70 + 600 + 700  # the f32 group, all packed
SMALL_ONLY = {k: MIXED[k] for k in ("s1", "s2", "s3")}
LARGE_ONLY = {k: MIXED[k] for k in ("big", "exact")}


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices, monkeypatch):
    monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", str(CAP))
    metrics.reset()
    bf.init(devices=cpu_devices[:SIZE])
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    yield
    bf.shutdown()
    metrics.reset()


def make_tree(spec, seed=0):
    rng = np.random.RandomState(seed)
    host = {
        k: rng.randn(SIZE, *shape).astype(np.float32)
        for k, (shape, _dt) in sorted(spec.items())
    }
    return {
        k: bf.worker_values(lambda r, v=host[k]: v[r], dtype=spec[k][1])
        for k in host
    }


def loss_fn(p, c):
    return 0.5 * sum(
        jnp.sum((p[k].astype(jnp.float32) - c[k].astype(jnp.float32)) ** 2)
        for k in p
    )


FACTORIES = {
    "cta": bf.DistributedNeighborAllreduceOptimizer,
    "atc": lambda tx: bf.DistributedAdaptThenCombineOptimizer(
        tx, bf.CommunicationType.neighbor_allreduce
    ),
}


def one_peer_schedule():
    exp2 = tu.ExponentialTwoGraph(SIZE)
    return schedule_from_dynamic(
        SIZE, lambda r: tu.GetDynamicOnePeerSendRecvRanks(exp2, r)
    )


def run(order, topology, path, spec=None, steps=2, **fused_kw):
    """``steps`` steps from the same start; the optimizer and the final
    parameters."""
    params = make_tree(spec or MIXED, seed=0)
    targets = make_tree(spec or MIXED, seed=1)
    opt = FACTORIES[order](optax.sgd(LR))
    if topology == "one_peer":
        opt.schedule = one_peer_schedule()
    state = opt.init(params)
    if path == "fused":
        fused = opt.make_train_step(loss_fn, **fused_kw)
        step = lambda p, s: fused(p, s, targets)[:2]
    else:
        grad = jax.jit(jax.vmap(jax.grad(loss_fn)))
        step = lambda p, s: opt.step(p, s, grad(p, targets))
    for _ in range(steps):
        params, state = step(params, state)
        jax.block_until_ready(params)  # the CPU mesh's rendezvous wants it
    return opt, params, (state, targets)


def dense_w_oracle(order, topology, steps=2):
    """The same steps in numpy, f64, with the topology's dense ``W``
    (combine: ``y_j = sum_i W[i, j] x_i``)."""
    params = {k: np.asarray(v, np.float64) for k, v in make_tree(MIXED, 0).items()}
    targets = {k: np.asarray(v, np.float64) for k, v in make_tree(MIXED, 1).items()}
    ctx = ctx_mod.get_context()
    if topology == "one_peer":
        plans = one_peer_schedule().plans
    else:
        from bluefog_tpu.collective import ops as col_ops

        plans = [col_ops._resolve_plan(ctx, None, None, None, True)]
    for k in range(steps):
        w = plans[k % len(plans)].weight_matrix()
        mix = lambda x: np.einsum("ij,i...->j...", w, x)
        for name, x in params.items():
            g = x - targets[name]
            params[name] = (
                mix(x) - LR * g if order == "cta" else mix(x - LR * g)
            )
    return params


def bits(tree):
    return {
        k: np.asarray(v).view(np.uint16 if v.dtype == jnp.bfloat16 else np.uint32)
        for k, v in tree.items()
    }


# -- (a) the trajectory ---------------------------------------------------------


@pytest.mark.parametrize("path", ["fused", "opt_step"])
@pytest.mark.parametrize("topology", ["static_exp2", "one_peer"])
@pytest.mark.parametrize("order", ["cta", "atc"])
def test_direct_equals_all_packed_and_dense_w(order, topology, path,
                                              monkeypatch):
    opt, direct, _ = run(order, topology, path)
    assert metrics.peek(DIRECT).value > 0  # the mechanism did engage
    monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", "0")  # one payload per group
    _opt, packed, _ = run(order, topology, path)
    assert metrics.peek(DIRECT).value == 0
    got, want = bits(direct), bits(packed)
    for k in got:  # to the bit: the combine is elementwise, whatever the shape
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    oracle = dense_w_oracle(order, topology)
    for k, v in direct.items():
        tol = 2e-2 if v.dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(
            np.asarray(v, np.float64), oracle[k], rtol=tol, atol=tol,
            err_msg=k,
        )


def test_gradient_allreduce_direct_equals_all_packed(monkeypatch):
    """The gradient-allreduce family routes its gradients by the same rule
    (a ``psum`` per large leaf; XLA:CPU merges them again, so no count is
    pinned); same bits as one packed ``psum``."""
    def trajectory():
        params = make_tree(MIXED, seed=0)
        targets = make_tree(MIXED, seed=1)
        opt = bf.DistributedGradientAllreduceOptimizer(optax.sgd(LR))
        state = opt.init(params)
        fused = opt.make_train_step(loss_fn)
        for _ in range(2):
            params, state, _loss = fused(params, state, targets)
            jax.block_until_ready(params)
        return params

    direct = trajectory()
    assert metrics.peek(DIRECT).value == (1280 + 1024) * 4 + 3072 * 2
    monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", "0")
    packed = trajectory()
    assert metrics.peek(DIRECT).value == 0
    got, want = bits(direct), bits(packed)
    for k in got:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


# -- (b) the compiled program -----------------------------------------------------


def _permutes(txt):
    stats = scaling.hlo_collective_stats(txt)
    return stats.get("collective-permute", {"count": 0})["count"]


def _has_flat(txt, n_elems):
    """An array whose (last) dimension is a whole group's flat length."""
    return re.search(rf"\[(\d+,)*{n_elems}\]", txt) is not None


@pytest.mark.parametrize("topology", ["static_exp2", "one_peer"])
def test_program_permutes_and_no_flat_payload(topology, monkeypatch):
    opt, params, (state, targets) = run("cta", topology, "fused", steps=1)
    txt = opt.lower_last_fused_hlo(params, state, targets)
    # messages a round: f32 big + exact alone, the 1370 small elements in
    # two buckets; bf16 hbig alone, hs the one leaf left in its group
    messages = 2 + len(inner.bucket_bounds(1370, 4, CAP)) + 1 + 1
    assert messages == 6
    # the one-peer schedule compiles every branch of its lax.switch
    branches = ROUNDS if topology == "one_peer" else 1
    rounds = 1 if topology == "one_peer" else ROUNDS
    assert _permutes(txt) == branches * rounds * messages
    assert not _has_flat(txt, F32_FLAT)
    monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", "0")
    opt, params, (state, targets) = run("cta", topology, "fused", steps=1)
    txt = opt.lower_last_fused_hlo(params, state, targets)
    assert _permutes(txt) == branches * rounds * 2  # one payload per dtype group
    assert _has_flat(txt, F32_FLAT)  # what the scan looks for is there to find


# -- (c) who keeps the flat payload -------------------------------------------------


def _flat_family(kind, monkeypatch):
    """An optimizer whose state is the flat payload, after one fused step
    on the mixed f32 tree, with that step's HLO."""
    spec = {k: v for k, v in MIXED.items() if v[1] is np.float32}
    params = make_tree(spec, seed=0)
    targets = make_tree(spec, seed=1)
    fused_kw = {}
    if kind == "shard":
        monkeypatch.setenv("BLUEFOG_SHARD", "1")
        opt = bf.DistributedGradientAllreduceOptimizer(optax.sgd(LR))
    else:
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(LR))
        if kind == "delayed":
            fused_kw["delayed"] = True
        else:
            opt.compression = kind
    state = opt.init(params)
    fused = opt.make_train_step(loss_fn, **fused_kw)
    params, state, _loss = fused(params, state, targets)
    jax.block_until_ready(params)
    return opt, opt.lower_last_fused_hlo(params, state, targets)


@pytest.mark.parametrize(
    "kind", ["int8", "int4", "bf16", "int8_ef", "int4_ef", "delayed", "shard"]
)
def test_flat_families_keep_every_leaf_packed(kind, monkeypatch):
    opt, txt = _flat_family(kind, monkeypatch)
    assert metrics.peek(DIRECT).value == 0
    assert metrics.peek(PACKED).value == F32_FLAT * 4
    if kind == "shard":
        # ZeRO-1 slices its owned slot out of the flat group, padded to
        # the owner grid
        assert _has_flat(txt, opt._shard_layout.groups[0].padded)
        return
    # the same buckets of the one flat vector as before PR 28: every
    # message is a capped slice, none is a leaf
    n_buckets = len(inner.bucket_bounds(F32_FLAT, 4, CAP))
    assert n_buckets == 4
    permutes = _permutes(txt)
    per_message = {"int8": 2, "int4": 2, "int8_ef": 2, "int4_ef": 2}.get(kind, 1)
    assert permutes == ROUNDS * n_buckets * per_message  # (q, scales) pairs
    assert not re.search(r"collective-permute[^\n]*\[40,32\]", txt)


# -- (d) the gauges and the flight ring ---------------------------------------------


@pytest.mark.parametrize(
    "spec, direct_bytes, packed_bytes",
    [
        (SMALL_ONLY, 0, 1370 * 4),
        (LARGE_ONLY, (1280 + 1024) * 4, 0),
        (MIXED, (1280 + 1024) * 4 + 3072 * 2, 1370 * 4 + 5 * 2),
    ],
    ids=["small_only", "large_only", "mixed"],
)
@pytest.mark.parametrize("path", ["fused", "opt_step"])
def test_gauges_say_how_the_bytes_were_routed(path, spec, direct_bytes,
                                              packed_bytes):
    assert metrics.peek(DIRECT) is None  # set when a step program is built
    run("cta", "static_exp2", path, spec, steps=1)
    assert metrics.peek(DIRECT).value == direct_bytes
    assert metrics.peek(PACKED).value == packed_bytes
    name = "opt_fused_step" if path == "fused" else "opt_step"
    (event,) = [
        e["data"] for e in flight.events()
        if e["kind"] == "compile" and e["data"]["name"] == name
    ][-1:]
    assert event["direct_bytes"] == direct_bytes
    assert event["packed_bytes"] == packed_bytes


def test_no_cap_routes_nothing_directly(monkeypatch):
    """``BLUEFOG_OVERLAP=0`` ("no cap"): one payload per dtype group."""
    monkeypatch.setenv("BLUEFOG_OVERLAP", "0")
    run("cta", "static_exp2", "fused", steps=1)
    assert metrics.peek(DIRECT).value == 0
    assert metrics.peek(PACKED).value == F32_FLAT * 4 + (3072 + 5) * 2


# -- (e) the chunk chooser's payload ----------------------------------------------


@pytest.mark.parametrize(
    "spec, direct, want",
    [
        (MIXED, True, (64 * 48 * 2, 64 * 48)),  # the largest leaf alone
        (MIXED, False, (CAP, 2048)),  # a full bucket of a flat group (bf16)
        (SMALL_ONLY, True, (CAP, 1024)),  # buckets of the packed rest
        ({"s1": MIXED["s1"], "big": MIXED["big"]}, True, (5120, 1280)),
        ({"s1": MIXED["s1"]}, True, (280, 70)),
    ],
    ids=["mixed_direct", "mixed_flat", "small_only", "leaf_over_rest", "tiny"],
)
def test_wire_payload_is_the_largest_message(spec, direct, want):
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(LR))
    assert opt._wire_payload(make_tree(spec), direct) == want


def test_direct_route_is_the_exact_wire_only(monkeypatch):
    ctx = ctx_mod.get_context()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(LR))
    assert opt._direct_route(ctx)
    for wire in ("int8", "bf16", "int4", "int8_ef", "int4_ef"):
        opt.compression = wire
        assert not opt._direct_route(ctx)
    opt.compression = None
    monkeypatch.setenv("BLUEFOG_SHARD", "1")
    grad = bf.DistributedGradientAllreduceOptimizer(optax.sgd(LR))
    assert not grad._direct_route(ctx)
    monkeypatch.delenv("BLUEFOG_SHARD")
    assert grad._direct_route(ctx)
    # a federation fabric with a quantized DCN tier needs the flat operand
    from bluefog_tpu import federation

    monkeypatch.setenv(federation.PODS_ENV, "2")
    fabric = federation.get_fabric(SIZE)
    assert opt._direct_route(ctx) == (fabric.wire is None)
