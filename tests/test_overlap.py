# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Overlap-layer tests: the fused train step, bucketed gossip, the
delayed (one-step-stale) combine, and the static HLO overlap scan.

The load-bearing guarantee: ``make_train_step`` fuses
forward/backward/update/gossip into one program for SCHEDULING reasons
only — it runs the body the two-program path runs (grad program +
``opt.step``; one builder, ``_GossipOptimizer._build_step``), with and
without wire bucketing. Bucketing never changes a bit, nor does fusing
wherever the compiler has nothing to round differently (plain SGD, the
int4 wire, gradient allreduce, the first momentum step); under momentum
behind a model's backward pass the two agree to a stated few ulp
(``test_fused_bitwise_matches_two_program`` says why). A wire that changed
a single ULP between replicas would break the bit-identical-replica
invariant the compression paths rely on: an invariant across the
replicas of one program, not across two programs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import context as ctx_mod
from bluefog_tpu import topology as tu
from bluefog_tpu.collective import inner, ops as col_ops
from bluefog_tpu.collective.plan import schedule_from_dynamic
from jax.sharding import PartitionSpec as P

from tools.hlo_overlap_scan import scan_overlap

SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices):
    bf.init(devices=cpu_devices[:SIZE])
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    yield
    bf.shutdown()


# -- a small transformer workload --------------------------------------------


def make_transformer():
    from bluefog_tpu.models.transformer import TransformerLM

    return TransformerLM(
        vocab=64, dim=32, heads=2, layers=2, max_len=16
    )


def transformer_setup(seed=0):
    model = make_transformer()
    rng = np.random.RandomState(seed)
    tokens_np = rng.randint(0, 64, (SIZE, 2, 16)).astype(np.int32)
    p0 = model.init(
        jax.random.PRNGKey(0), jnp.asarray(tokens_np[0])
    )["params"]
    params = jax.tree_util.tree_map(
        lambda t: bf.worker_values(np.asarray(t)), p0
    )
    tokens = bf.worker_values(lambda r: tokens_np[r])

    def loss_fn(p, toks):
        logits = model.apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], toks[:, 1:]
        ).mean()

    return params, tokens, loss_fn


def legacy_grad_fn(loss_fn, example_params):
    ctx = ctx_mod.get_context()
    spec = P(ctx_mod.WORKER_AXIS)

    def body(p_b, t_b):
        p = jax.tree_util.tree_map(lambda t: t[0], p_b)
        g = jax.grad(loss_fn)(p, t_b[0])
        return jax.tree_util.tree_map(lambda t: jnp.expand_dims(t, 0), g)

    return jax.jit(
        jax.shard_map(
            body, mesh=ctx.mesh, in_specs=(spec, spec), out_specs=spec
        )
    )


def assert_trees_bitwise(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


FACTORIES = {
    "cta": bf.DistributedNeighborAllreduceOptimizer,
    "atc": lambda tx: bf.DistributedAdaptThenCombineOptimizer(
        tx, bf.CommunicationType.neighbor_allreduce
    ),
}


FUSION_ULPS = 16


def assert_trees_within_ulps(a, b, ulps=FUSION_ULPS):
    """Leaf by leaf: max |a - b| <= ``ulps`` float32 ulp at the leaf's
    largest magnitude."""
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype == np.float32
        ulp = np.spacing(np.float32(max(np.abs(x).max(), np.abs(y).max())))
        worst = np.abs(x - y).max()
        assert worst <= ulps * ulp, (worst, ulp)


@pytest.mark.parametrize("order", ["cta", "atc"])
@pytest.mark.parametrize("schedule", ["static", "dynamic"])
@pytest.mark.parametrize("bucketed", [False, True])
def test_fused_bitwise_matches_two_program(order, schedule, bucketed,
                                           monkeypatch):
    """make_train_step == grad-program + opt.step on a small transformer
    under momentum SGD — for ATC and CTA, static and dynamic schedules,
    with and without wire bucketing: to the bit after the first step, and
    after three within ``FUSION_ULPS`` float32 ulp of each leaf's largest
    magnitude.

    Why not to the bit throughout: both entry points run ONE body
    (``_GossipOptimizer._build_step``), so the bits that can differ are
    the compiler's. Inside one program XLA:CPU fuses the momentum update
    ``g + 0.9 t`` behind the gradient's last kernel and rounds it
    differently than it does as a program of its own. On the first step
    ``t`` is zero and there is nothing to round; after the second the two
    differ by 1 ulp in under 1 % of the elements; the third step's
    backward pass then starts from parameters 1 ulp apart. Read when the
    tolerance was set, over the eight cases: 3.25 ulp on the parameters,
    6 on the momentum, largest |a - b| 1.8e-07. The pin that stays exact
    over three steps, and fails if the two entry points' math ever parts,
    is tests/test_step_core.py's linear loss, where no matmul precedes
    the update. The sibling pins below (plain SGD, int4, gradient
    allreduce) stay bitwise."""
    monkeypatch.setenv(
        "BLUEFOG_BUCKET_BYTES", "2048" if bucketed else "0"
    )
    params, tokens, loss_fn = transformer_setup()

    def configure(opt):
        if schedule == "dynamic":
            opt.schedule = schedule_from_dynamic(
                SIZE,
                lambda r: tu.GetDynamicOnePeerSendRecvRanks(
                    tu.ExponentialGraph(SIZE), r
                ),
            )

    opt1 = FACTORIES[order](optax.sgd(0.1, momentum=0.9))
    configure(opt1)
    p1 = params
    s1 = opt1.init(p1)
    grad_fn = legacy_grad_fn(loss_fn, params)

    opt2 = FACTORIES[order](optax.sgd(0.1, momentum=0.9))
    configure(opt2)
    p2 = params
    s2 = opt2.init(p2)
    train_step = opt2.make_train_step(loss_fn)

    for k in range(3):
        g = grad_fn(p1, tokens)
        p1, s1 = opt1.step(p1, s1, g)
        p2, s2, loss = train_step(p2, s2, tokens)
        if k == 0:
            assert_trees_bitwise(p1, p2)
            assert_trees_bitwise(s1, s2)
    assert_trees_within_ulps(p1, p2)
    assert_trees_within_ulps(s1, s2)
    assert np.isfinite(np.asarray(loss)).all()


def test_bucketed_gossip_bitwise_matches_monolithic(monkeypatch):
    """Bucketing is pure payload slicing: same bits out, whatever the
    cap (the combine is elementwise; concat/split never reorders leaf
    math)."""
    params, tokens, loss_fn = transformer_setup()
    results = {}
    for cap in ("0", "2048"):
        monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", cap)
        opt = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.1, momentum=0.9)
        )
        # the step consumes its carry: each cap trains its own copy
        p = jax.tree_util.tree_map(jnp.copy, params)
        s = opt.init(p)
        train_step = opt.make_train_step(loss_fn)
        for _ in range(2):
            p, s, _loss = train_step(p, s, tokens)
        results[cap] = (p, s)
    assert_trees_bitwise(results["0"][0], results["2048"][0])
    assert_trees_bitwise(results["0"][1], results["2048"][1])


@pytest.mark.parametrize("wire", ["int8_ef", "int4_ef"])
def test_bucketed_ef_bitwise_matches_monolithic(wire, monkeypatch):
    """Error-feedback compression under bucketing: the residual state is
    sliced with the payload and bucket bounds snap to the quantization
    chunk, so bucketed int8_ef / int4_ef is bitwise the monolithic
    wire — state included (int4_ef additionally exercises the packed
    nibble wire across bucket boundaries)."""
    n = 2048
    rng = np.random.RandomState(3)
    c = rng.randn(SIZE, n).astype(np.float32)
    results = {}
    for cap in ("0", "4096"):  # 1024-elem buckets, 512-aligned
        monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", cap)
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
        opt.compression = wire
        params = {"w": bf.worker_values(lambda r: c[r])}
        s = opt.init(params)
        p = params
        for _ in range(3):
            p, s = opt.step(p, s, {"w": p["w"] - jnp.asarray(c)})
        results[cap] = (p, opt._ef)
    assert_trees_bitwise(results["0"][0], results["4096"][0])
    assert_trees_bitwise(results["0"][1], results["4096"][1])


def test_fused_int4_bitwise_matches_two_program(monkeypatch):
    """The fused train step with the int4 wire == grad-program +
    opt.step, to the bit, bucketed — the new tier rides the shared
    _combine_update core like every other wire."""
    monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", "4096")
    n = 2048
    rng = np.random.RandomState(9)
    c = rng.randn(SIZE, n).astype(np.float32)
    cvals = bf.worker_values(lambda r: c[r])

    def loss_fn(p, cv):
        return 0.5 * jnp.sum((p["w"] - cv) ** 2)

    opt1 = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt1.compression = "int4"
    params = {"w": bf.worker_values(lambda r: c[r] + 1.0)}
    p1, s1 = params, opt1.init(params)
    grad_fn = legacy_grad_fn(loss_fn, params)
    opt2 = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt2.compression = "int4"
    p2, s2 = params, opt2.init(params)
    train_step = opt2.make_train_step(loss_fn)
    for _ in range(3):
        g = grad_fn(p1, cvals)
        p1, s1 = opt1.step(p1, s1, g)
        p2, s2, _loss = train_step(p2, s2, cvals)
    assert_trees_bitwise(p1, p2)
    assert_trees_bitwise(s1, s2)


def test_fused_gradient_allreduce_matches_two_program():
    """order='grad' fused path: gradient averaging inside the fused
    program tracks the legacy two-program path bitwise."""
    params, tokens, loss_fn = transformer_setup()
    opt1 = bf.DistributedGradientAllreduceOptimizer(optax.sgd(0.1))
    p1, s1 = params, opt1.init(params)
    grad_fn = legacy_grad_fn(loss_fn, params)
    opt2 = bf.DistributedGradientAllreduceOptimizer(optax.sgd(0.1))
    p2, s2 = params, opt2.init(params)
    train_step = opt2.make_train_step(loss_fn)
    for _ in range(2):
        g = grad_fn(p1, tokens)
        p1, s1 = opt1.step(p1, s1, g)
        p2, s2, _loss = train_step(p2, s2, tokens)
    assert_trees_bitwise(p1, p2)
    assert_trees_bitwise(s1, s2)


def test_fused_num_steps_per_communication_matches_legacy():
    """K=2 through the fused builder: local call then communicating
    call, identical to the legacy path's own K=2 sequence."""
    params, tokens, loss_fn = transformer_setup()
    tx = optax.sgd(0.1)
    opt1 = bf.DistributedNeighborAllreduceOptimizer(
        tx, num_steps_per_communication=2
    )
    p1, s1 = params, opt1.init(params)
    grad_fn = legacy_grad_fn(loss_fn, params)
    opt2 = bf.DistributedNeighborAllreduceOptimizer(
        tx, num_steps_per_communication=2
    )
    p2, s2 = params, opt2.init(params)
    train_step = opt2.make_train_step(loss_fn)
    for _ in range(4):
        g = grad_fn(p1, tokens)
        p1, s1 = opt1.step(p1, s1, g)
        p2, s2, _loss = train_step(p2, s2, tokens)
    assert opt2._step_count == 4 and opt2._comm_count == 2
    assert_trees_bitwise(p1, p2)


# -- delayed (one-step-stale) gossip ------------------------------------------


def quad_setup():
    rng = np.random.RandomState(0)
    c = rng.randn(SIZE, 4).astype(np.float32)
    params = {"w": bf.worker_values(lambda r: c[r])}
    cvals = bf.worker_values(lambda r: c[r])

    def loss_fn(p, cv):
        return 0.5 * jnp.sum((p["w"] - cv) ** 2)

    return c, params, cvals, loss_fn


def test_delayed_matches_stale_mix_oracle():
    """Pin the delayed-CTA semantics against a numpy oracle of the
    self-fresh/neighbors-stale recursion:

        mix_k = s * p_k + N @ p_{k-1}        (N = W minus its diagonal)
        p_{k+1} = mix_k - lr * (p_k - c)     (grads at the ENTERING p_k)

    with the buffer seeded at p_0 (so step 0 mixes fresh). One-step
    staleness is the whole point — a fresh-mix implementation would
    diverge from this oracle at step 1."""
    c, params, cvals, loss_fn = quad_setup()
    ctx = ctx_mod.get_context()
    plan = col_ops._resolve_plan(ctx, None, None, None, True)
    w = plan.weight_matrix()  # combine: y_j = sum_i W[i, j] x_i
    s_diag = np.diag(w).copy()
    n_part = w - np.diag(s_diag)
    lr = 0.2

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(lr))
    p = params
    st = opt.init(p)
    train_step = opt.make_train_step(loss_fn, delayed=True)

    x = np.asarray(params["w"]).copy()  # [size, dim]
    buf = x.copy()
    for _ in range(5):
        p, st, _loss = train_step(p, st, cvals)
        mix = s_diag[:, None] * x + n_part.T @ buf
        buf, x = x, mix - lr * (x - c)
    np.testing.assert_allclose(
        np.asarray(p["w"]), x, rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("order", ["cta", "atc"])
def test_delayed_convergence_smoke(order):
    """Delayed gossip is a known-convergent staleness variant: on the
    gossip oracle problem (decentralized quadratic, same harness as
    test_optimizers/test_pushsum_oracle) the global loss decreases and
    the consensus distance shrinks."""
    c, params, cvals, loss_fn = quad_setup()
    opt = FACTORIES[order](
        optax.sgd(optax.exponential_decay(0.3, 10, 0.5))
    )
    p = params
    s = opt.init(p)
    train_step = opt.make_train_step(loss_fn, delayed=True)

    def global_loss(p):
        w = np.asarray(p["w"])
        return float(np.mean(0.5 * np.sum((w - c.mean(0)) ** 2, -1)))

    def disagreement(p):
        w = np.asarray(p["w"])
        return float(np.max(np.abs(w - w.mean(0))))

    start_loss, start_dis = global_loss(p), disagreement(p)
    for _ in range(80):
        p, s, _loss = train_step(p, s, cvals)
        # dependent steps queued without a sync can deadlock the CPU
        # mesh's collective rendezvous (it aborts the worker after 40 s)
        jax.block_until_ready(p)
    assert global_loss(p) < 0.05 * start_loss
    assert disagreement(p) < 0.1 and disagreement(p) < start_dis


def test_delayed_refuses_int8_ef():
    """Error feedback cannot ride a one-step-stale payload (the CHOCO
    copies would desynchronize); the refusal must be loud, not a silent
    wrong answer."""
    c, params, cvals, loss_fn = quad_setup()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = "int8_ef"
    s = opt.init(params)
    train_step = opt.make_train_step(loss_fn, delayed=True)
    with pytest.raises(ValueError, match="int8_ef"):
        train_step(params, s, cvals)


def test_delayed_refuses_hierarchical(cpu_devices):
    bf.init(devices=cpu_devices[:SIZE], nodes_per_machine=4)
    bf.set_machine_topology(tu.RingGraph(2))
    c, params, cvals, loss_fn = quad_setup()
    opt = bf.DistributedHierarchicalNeighborAllreduceOptimizer(
        optax.sgd(0.1)
    )
    s = opt.init(params)
    train_step = opt.make_train_step(loss_fn, delayed=True)
    with pytest.raises(ValueError, match="hierarchical"):
        train_step(params, s, cvals)


def test_delayed_refuses_grad_order():
    opt = bf.DistributedGradientAllreduceOptimizer(optax.sgd(0.1))
    with pytest.raises(ValueError, match="delayed"):
        opt.make_train_step(lambda p: 0.0, delayed=True)


def test_delayed_int8_quantized_converges():
    """The delayed mix composes with the quantized wire (payloads are
    stale AND int8): still converges on the oracle problem."""
    c, params, cvals, loss_fn = quad_setup()
    opt = bf.DistributedNeighborAllreduceOptimizer(
        optax.sgd(optax.exponential_decay(0.3, 10, 0.5))
    )
    opt.compression = "int8"
    p = params
    s = opt.init(p)
    train_step = opt.make_train_step(loss_fn, delayed=True)

    def global_loss(p):
        w = np.asarray(p["w"])
        return float(np.mean(0.5 * np.sum((w - c.mean(0)) ** 2, -1)))

    start = global_loss(p)
    for _ in range(80):
        p, s, _loss = train_step(p, s, cvals)
        # dependent steps queued without a sync can deadlock the CPU
        # mesh's collective rendezvous (it aborts the worker after 40 s)
        jax.block_until_ready(p)
    assert global_loss(p) < 0.05 * start


# -- compiled-program structure ----------------------------------------------


def _fused_hlo(opt, p, s, *batch):
    return opt.lower_last_fused_hlo(p, s, *batch)


def test_fused_is_one_cached_program():
    """Repeated fused calls reuse ONE compiled program (no cache growth,
    no per-call retrace)."""
    c, params, cvals, loss_fn = quad_setup()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    p, s = params, opt.init(params)
    train_step = opt.make_train_step(loss_fn)
    p, s, _ = train_step(p, s, cvals)
    cache = ctx_mod.get_context().op_cache
    n = len(cache)
    for _ in range(4):
        p, s, _ = train_step(p, s, cvals)
    assert len(cache) == n
    assert sum(1 for k in cache if k[0] == "opt_fused_step") == 1


# leaves (f32 element counts) -> messages a round, whether every one is capped
PERMUTE_CASES = {
    # many small leaves: packed and cut into capped buckets
    "many_small": ([300] * 10, 6, True),
    # one large leaf: alone and whole, one permute a round, over the cap
    "one_large": ([3000], 1, False),
    # both: the large leaf whole beside the small ones' buckets
    "mixed": ([3000] + [300] * 10, 7, False),
}


@pytest.mark.parametrize("case", sorted(PERMUTE_CASES))
def test_fused_program_buckets_permutes(case, monkeypatch):
    """With a small cap the fused program's permute count is
    messages x rounds (each message issues its own plan rounds): the
    leaves under the cap travel packed, in capped buckets; a leaf at or
    over it travels alone and whole (PR 28 — before it this test pinned
    "every permute <= cap" on the one 3000-element leaf, which the cap
    then split into six)."""
    monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", "2048")  # 512 f32 elems
    sizes, messages, all_capped = PERMUTE_CASES[case]
    rng = np.random.RandomState(0)
    c = [rng.randn(SIZE, n).astype(np.float32) for n in sizes]
    params = [bf.worker_values(lambda r, v=v: v[r]) for v in c]
    cvals = [bf.worker_values(lambda r, v=v: v[r]) for v in c]

    def loss_fn(p, cv):
        return 0.5 * sum(jnp.sum((a - b) ** 2) for a, b in zip(p, cv))

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    p, s = params, opt.init(params)
    train_step = opt.make_train_step(loss_fn)
    p, s, _ = train_step(p, s, cvals)
    txt = _fused_hlo(opt, p, s, cvals)
    scan = scan_overlap(txt)
    rounds = 3  # ExponentialTwoGraph(8) -> log2(8) rounds
    small = sum(n for n in sizes if n * 4 < 2048)
    n_alone = sum(n * 4 >= 2048 for n in sizes)
    n_buckets = len(inner.bucket_bounds(small, 4, 2048)) if small else 0
    assert n_alone + n_buckets == messages
    total = scan["async_pairs"] + scan["sync_collective_permutes"]
    assert total == rounds * messages, scan
    payloads = sorted(pm["payload_bytes"] for pm in scan["permutes"])
    assert all(b <= 2048 for b in payloads) == all_capped, payloads
    if n_alone:
        assert payloads[-rounds:] == [3000 * 4] * rounds, payloads


def test_delayed_program_permutes_independent_of_compute():
    """The delayed program's permutes consume only the carried buffer:
    the def-use scan must find compute they are independent of (what
    makes them schedulable under the whole forward/backward)."""
    c, params, cvals, loss_fn = quad_setup()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    p, s = params, opt.init(params)
    train_step = opt.make_train_step(loss_fn, delayed=True)
    p, s, _ = train_step(p, s, cvals)
    txt = _fused_hlo(opt, p, s, cvals)
    scan = scan_overlap(txt)
    total = scan["async_pairs"] + scan["sync_collective_permutes"]
    assert total >= 1
    assert scan["overlappable_permutes"] == total, scan


# -- the scan tool itself -----------------------------------------------------


SYNTHETIC_ASYNC_HLO = """\
HloModule test

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %cps = (f32[1024]{0}, f32[1024]{0}) collective-permute-start(f32[1024]{0} %p0), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %fusion.1 = f32[1024]{0} fusion(f32[1024]{0} %p0), kind=kLoop, calls=%fused_x
  %dot.1 = f32[1024]{0} dot(f32[1024]{0} %fusion.1, f32[1024]{0} %fusion.1)
  %cpd = f32[1024]{0} collective-permute-done((f32[1024]{0}, f32[1024]{0}) %cps)
  ROOT %add = f32[1024]{0} add(f32[1024]{0} %cpd, f32[1024]{0} %dot.1)
}
"""

SYNTHETIC_SERIAL_HLO = """\
HloModule test

ENTRY %main (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  %fusion.1 = f32[256]{0} fusion(f32[256]{0} %p0), kind=kLoop, calls=%f
  %cp = f32[256]{0} collective-permute(f32[256]{0} %fusion.1), channel_id=1, source_target_pairs={{0,1}}
  ROOT %fusion.2 = f32[256]{0} fusion(f32[256]{0} %cp), kind=kLoop, calls=%g
}
"""


def test_scan_counts_async_pairs():
    scan = scan_overlap(SYNTHETIC_ASYNC_HLO)
    assert scan["async_pairs"] == 1
    assert scan["overlapped_async_pairs"] == 1  # fusion+dot between
    assert scan["sync_collective_permutes"] == 0
    (pm,) = scan["permutes"]
    assert pm["compute_between"] == 2
    assert pm["payload_bytes"] == 4096 * 2  # start's tuple shape
    assert pm["independent_compute_ops"] == 2


def test_scan_serial_permute_has_no_independence():
    """A permute whose producers and consumers span all compute is NOT
    overlappable; the scan must not report false capability."""
    scan = scan_overlap(SYNTHETIC_SERIAL_HLO)
    assert scan["async_pairs"] == 0
    assert scan["sync_collective_permutes"] == 1
    (pm,) = scan["permutes"]
    assert pm["independent_compute_ops"] == 0
    assert scan["overlappable_permutes"] == 0


# -- facade -------------------------------------------------------------------


def test_facade_make_train_step():
    c, params, cvals, loss_fn = quad_setup()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.2))
    s = opt.init(params)
    train_step = bf.make_train_step(opt, loss_fn)
    p, s, loss = train_step(params, s, cvals)
    assert np.asarray(loss).shape == (SIZE,)
    assert "make_train_step" in bf.__all__
