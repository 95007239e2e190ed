# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""int8-quantized gossip: 4x fewer wire bytes, bounded error, converges.

Beyond-reference capability (EQuARX-style quantized collectives lifted to
the gossip setting): the wire payload of every ppermute round is int8
with a rider scale; the HLO-level byte accounting proves the 4x claim
and the optimizer tests prove training still reaches consensus.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import scaling
from bluefog_tpu import topology as tu
from bluefog_tpu.collective import inner, plan as planlib
from jax.sharding import NamedSharding, PartitionSpec as P

SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices):
    bf.init(devices=cpu_devices[:SIZE])
    yield
    bf.shutdown()


def test_quantized_combine_close_to_exact():
    bf.set_topology(tu.RingGraph(SIZE))
    x = np.random.RandomState(0).randn(SIZE, 64).astype(np.float32)
    exact = np.asarray(bf.neighbor_allreduce(x))
    quant = np.asarray(bf.neighbor_allreduce(x, compression="int8"))
    # error bounded by the neighbor weight mass * one quantization step
    step = np.abs(x).max(axis=1, keepdims=True) / 127.0
    assert np.abs(quant - exact).max() < 1.5 * step.max()
    assert not np.array_equal(quant, exact)  # it IS quantized


def test_consensus_is_fixed_point():
    """All-equal state must be exactly preserved (self term full
    precision + identical payloads)."""
    bf.set_topology(tu.RingGraph(SIZE))
    x = np.tile(np.random.RandomState(1).randn(1, 16), (SIZE, 1)).astype(
        np.float32
    )
    out = np.asarray(bf.neighbor_allreduce(x, compression="int8"))
    np.testing.assert_allclose(out, x, rtol=1e-6, atol=1e-7)


def test_wire_bytes_are_one_quarter():
    """HLO proof of the 4x: the quantized program's collective-permute
    payloads are int8 (+ a scalar scale) vs the f32 baseline."""
    D = 4096
    plan = planlib.plan_from_topology(tu.RingGraph(SIZE), weighted=True)
    mesh = bf.get_context().mesh
    spec = P("workers")

    def lower(combine):
        fn = jax.jit(
            jax.shard_map(
                lambda t: combine(t, plan, "workers"),
                mesh=mesh, in_specs=spec, out_specs=spec,
            )
        )
        x = jax.device_put(
            jnp.zeros((SIZE, D), jnp.float32), NamedSharding(mesh, spec)
        )
        return scaling.hlo_collective_stats(fn.lower(x).compile().as_text())

    base = lower(inner.weighted_combine)["collective-permute"]
    quant = lower(inner.weighted_combine_quantized)["collective-permute"]
    assert base["bytes"] == 2 * D * 4  # 2 ring rounds, f32
    # int8 payload + per-512-chunk f32 scales (~0.8% of payload) per round
    assert quant["bytes"] <= int(base["bytes"] // 4 * 1.05), (base, quant)


def test_optimizer_with_compression_converges():
    c = np.random.RandomState(2).randn(SIZE, 4).astype(np.float32)
    opt = bf.DistributedNeighborAllreduceOptimizer(
        optax.sgd(optax.exponential_decay(0.3, 10, 0.5))
    )
    opt.compression = "int8"
    params = {"w": bf.worker_values(lambda r: c[r])}
    state = opt.init(params)
    for _ in range(60):
        grads = {"w": params["w"] - jnp.asarray(c)}
        params, state = opt.step(params, state, grads)
    w = np.asarray(params["w"])
    target = c.mean(0)
    start_spread = np.abs(c - target).max()
    assert np.abs(w - target).max() < 0.15 * start_spread
    assert np.abs(w - w.mean(0)).max() < 0.1


def test_bad_compression_rejected():
    x = bf.worker_values(lambda r: np.ones(4, np.float32))
    with pytest.raises(ValueError, match="int8"):
        bf.neighbor_allreduce(x, compression="fp4")
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = "fp4"
    params = {"w": x}
    state = opt.init(params)
    with pytest.raises(ValueError, match="int8"):
        opt.step(params, state, params)


def test_fp16_all_zero_no_nan():
    """The f32 scale floor: an all-zero fp16 tensor must combine to
    zeros, not NaN (fp16 would flush a tiny f32 literal to 0)."""
    bf.set_topology(tu.RingGraph(SIZE))
    x = bf.worker_values(lambda r: np.zeros(8, np.float16))
    out = np.asarray(bf.neighbor_allreduce(x, compression="int8"),
                     np.float32)
    assert np.isfinite(out).all() and (out == 0).all()


def test_non_normalized_weights_refused():
    """Push-sum-style column-stochastic weights break the difference
    form's algebra (silent O(x) error); they must be refused."""
    sw = 0.8
    srcs = [{(r - 1) % SIZE: 0.8} for r in range(SIZE)]  # sums to 1.6
    x = bf.worker_values(lambda r: np.ones(4, np.float32))
    with pytest.raises(ValueError, match="normalized"):
        bf.neighbor_allreduce(x, self_weight=sw, src_weights=srcs,
                              compression="int8")


def test_compression_refused_off_static_path():
    """opt.compression must raise, not silently no-op, on paths that do
    not support it (schedules / allreduce)."""
    from bluefog_tpu.collective.plan import schedule_from_dynamic

    x = bf.worker_values(lambda r: np.ones(4, np.float32))
    params = {"w": x}

    opt = bf.DistributedAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = "int8"
    state = opt.init(params)
    with pytest.raises(ValueError, match="static-plan"):
        opt.step(params, state, params)

    opt2 = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt2.compression = "int8"
    opt2.schedule = schedule_from_dynamic(
        SIZE,
        lambda r: tu.GetDynamicOnePeerSendRecvRanks(
            tu.ExponentialGraph(SIZE), r
        ),
    )
    state2 = opt2.init(params)
    with pytest.raises(ValueError, match="static-plan"):
        opt2.step(params, state2, params)


def test_compressed_varying_weights_single_program():
    """Per-step weight changes with compression reuse ONE compiled
    program (operand-keyed, same guarantee as the exact path)."""
    ctx = bf.get_context()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = "int8"
    c = np.random.RandomState(3).randn(SIZE, 4).astype(np.float32)
    params = {"w": bf.worker_values(lambda r: c[r])}
    state = opt.init(params)
    before = None
    for i in range(6):
        wv = 0.4 + 0.02 * i  # same ring EDGES, different weight VALUES
        opt.self_weight = 1.0 - wv
        opt.src_weights = [{(r - 1) % SIZE: wv} for r in range(SIZE)]
        opt.dst_weights = [[(r + 1) % SIZE] for r in range(SIZE)]
        params, state = opt.step(params, state,
                                 {"w": params["w"] - jnp.asarray(c)})
        if i == 0:
            before = len(ctx.op_cache)
    assert len(ctx.op_cache) == before  # no recompiles across weights


def test_hierarchical_compression_converges(cpu_devices):
    """int8 on the machine-level (DCN) leg: intra-host psum exact,
    cross-host gossip quantized; training still reaches consensus."""
    bf.shutdown()
    bf.init(devices=cpu_devices[:SIZE], nodes_per_machine=4)
    bf.set_machine_topology(tu.RingGraph(2))
    c = np.random.RandomState(4).randn(SIZE, 4).astype(np.float32)
    opt = bf.DistributedHierarchicalNeighborAllreduceOptimizer(
        optax.sgd(optax.exponential_decay(0.3, 10, 0.5))
    )
    opt.compression = "int8"
    params = {"w": bf.worker_values(lambda r: c[r])}
    state = opt.init(params)
    for _ in range(60):
        params, state = opt.step(params, state,
                                 {"w": params["w"] - jnp.asarray(c)})
    w = np.asarray(params["w"])
    target = c.mean(0)
    assert np.abs(w - target).max() < 0.15 * np.abs(c - target).max()
    assert np.abs(w - w.mean(0)).max() < 0.1


def test_bf16_wire_close_and_half_bytes():
    """compression='bf16': near-lossless, half the wire bytes."""
    bf.set_topology(tu.RingGraph(SIZE))
    x = np.random.RandomState(5).randn(SIZE, 64).astype(np.float32)
    exact = np.asarray(bf.neighbor_allreduce(x))
    half = np.asarray(bf.neighbor_allreduce(x, compression="bf16"))
    assert np.abs(half - exact).max() < 0.02  # bf16 mantissa error
    # consensus fixed point holds for bf16 too
    c = np.tile(x[:1], (SIZE, 1))
    out = np.asarray(bf.neighbor_allreduce(c, compression="bf16"))
    np.testing.assert_allclose(out, c, rtol=1e-6, atol=1e-7)

    D = 4096
    plan = planlib.plan_from_topology(tu.RingGraph(SIZE), weighted=True)
    mesh = bf.get_context().mesh
    spec = P("workers")
    fn = jax.jit(
        jax.shard_map(
            lambda t: inner.weighted_combine_quantized_operands(
                t, plan.perms,
                jnp.asarray(plan.weight_operands()[1]), "workers",
                wire="bf16",
            ),
            mesh=mesh, in_specs=spec, out_specs=spec,
        )
    )
    xd = jax.device_put(jnp.zeros((SIZE, D), jnp.float32),
                        NamedSharding(mesh, spec))
    # the EMITTED program carries bf16 on the wire (the CPU backend then
    # legalizes bf16 collectives by widening to f32 — visible only in its
    # optimized HLO; TPU moves bf16 natively). Bind the assertion to the
    # collective op's own operand/result types in the lowering.
    import re

    lowered = fn.lower(xd).as_text()
    cp_lines = [l for l in lowered.splitlines()
                if "collective_permute" in l]
    assert cp_lines, lowered[:2000]
    for line in cp_lines:
        assert re.search(r"tensor<1x4096xbf16>\)?\s*->", line), line


def test_bf16_optimizer_converges():
    c = np.random.RandomState(6).randn(SIZE, 4).astype(np.float32)
    opt = bf.DistributedNeighborAllreduceOptimizer(
        optax.sgd(optax.exponential_decay(0.3, 10, 0.5))
    )
    opt.compression = "bf16"
    params = {"w": bf.worker_values(lambda r: c[r])}
    state = opt.init(params)
    for _ in range(60):
        params, state = opt.step(params, state,
                                 {"w": params["w"] - jnp.asarray(c)})
    w = np.asarray(params["w"])
    assert np.abs(w - c.mean(0)).max() < 0.1 * np.abs(c - c.mean(0)).max()


def test_bf16_wire_fp16_extremes_finite():
    """fp16 values near the fp16 max must survive the bf16 wire: the
    difference arithmetic runs in f32 (bf16 rounds 65504 to 65536, which
    is inf in fp16)."""
    bf.set_topology(tu.RingGraph(SIZE))
    x = bf.worker_values(lambda r: np.full(8, 65504.0, np.float16))
    out = np.asarray(bf.neighbor_allreduce(x, compression="bf16"),
                     np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, 65504.0, rtol=1e-3)


def test_unknown_wire_raises_in_inner():
    with pytest.raises(ValueError, match="wire"):
        inner.weighted_combine_quantized_operands(
            jnp.ones((4,)), (), jnp.zeros((0, SIZE)), "workers",
            wire="fp4",
        )


def test_error_feedback_removes_constant_lr_noise_floor():
    """Plain int8 gossip stalls at a quantization noise floor; error
    feedback (int8_ef) keeps shrinking the consensus residual — the
    reason the EF variant exists. Pure consensus (zero gradients)
    isolates the floor from the CTA constant-lr bias."""
    c = np.random.RandomState(7).randn(SIZE, 64).astype(np.float32) * 5.0
    zero = {"w": jnp.zeros((SIZE, 64), jnp.float32)}

    def run(compression):
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
        opt.compression = compression
        params = {"w": bf.worker_values(lambda r: c[r])}
        state = opt.init(params)
        for _ in range(150):
            params, state = opt.step(params, state, zero)
        w = np.asarray(params["w"])
        return np.abs(w - w.mean(0)).max()

    spread_plain = run("int8")
    spread_ef = run("int8_ef")
    assert spread_ef < 0.1 * spread_plain, (spread_plain, spread_ef)
    assert spread_ef < 1e-3


def test_error_feedback_single_program():
    ctx = bf.get_context()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = "int8_ef"
    c = np.random.RandomState(8).randn(SIZE, 6).astype(np.float32)
    params = {"a": bf.worker_values(lambda r: c[r, :3]),
              "b": bf.worker_values(lambda r: c[r, 3:])}
    state = opt.init(params)
    before = None
    for i in range(5):
        params, state = opt.step(params, state,
                                 {"a": params["a"], "b": params["b"]})
        if i == 0:
            before = len(ctx.op_cache)
    assert len(ctx.op_cache) == before


def test_error_feedback_restricted_paths():
    opt = bf.DistributedAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = "int8_ef"
    params = {"w": bf.worker_values(lambda r: np.ones(4, np.float32))}
    state = opt.init(params)
    with pytest.raises(ValueError, match="int8_ef"):
        opt.step(params, state, params)


def test_ef_state_resets_on_topology_change():
    """Dynamic weight reassignment changes the per-round sources; stale
    CHOCO copies would break the bit-identical-replica invariant, so the
    EF state must be rebuilt (and training stays correct through the
    change)."""
    c = np.random.RandomState(9).randn(SIZE, 16).astype(np.float32)
    zero = {"w": jnp.zeros((SIZE, 16), jnp.float32)}
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
    opt.compression = "int8_ef"
    params = {"w": bf.worker_values(lambda r: c[r])}
    state = opt.init(params)
    for _ in range(10):
        params, state = opt.step(params, state, zero)
    ef_before = opt._ef
    # move to a ring (different edge set, 2 rounds)
    opt.self_weight = 1.0 / 3.0
    opt.src_weights = [
        {(r - 1) % SIZE: 1 / 3, (r + 1) % SIZE: 1 / 3} for r in range(SIZE)
    ]
    opt.dst_weights = [[(r - 1) % SIZE, (r + 1) % SIZE] for r in range(SIZE)]
    for _ in range(60):
        params, state = opt.step(params, state, zero)
    assert opt._ef is not ef_before  # rebuilt for the new structure
    w = np.asarray(params["w"])
    np.testing.assert_allclose(w, np.tile(c.mean(0), (SIZE, 1)), atol=5e-3)


def test_hierarchical_rejects_int8_ef(cpu_devices):
    bf.shutdown()
    bf.init(devices=cpu_devices[:SIZE], nodes_per_machine=4)
    bf.set_machine_topology(tu.RingGraph(2))
    opt = bf.DistributedHierarchicalNeighborAllreduceOptimizer(
        optax.sgd(0.1)
    )
    opt.compression = "int8_ef"
    params = {"w": bf.worker_values(lambda r: np.ones(4, np.float32))}
    state = opt.init(params)
    with pytest.raises(ValueError, match="int8_ef"):
        opt.step(params, state, params)


# -- the int4 tier (block-scaled nibble-packed, bf16 scales) ------------------


@pytest.mark.parametrize("n", [1, 511, 512, 513])
def test_int4_pack_unpack_roundtrip_oracle(n):
    """Numpy oracle for the nibble wire at every 512-block remainder
    width: quantize -> pack -> unpack -> dequantize on device must equal
    the host replica bit for bit, and pack/unpack must round-trip every
    int4 value exactly."""
    from bluefog_tpu import metrics

    rng = np.random.RandomState(n)
    x = (rng.randn(n) * 3).astype(np.float32)

    dev_q, dev_s, dev_xhat = jax.jit(inner._chunk_quantize4)(
        jnp.asarray(x)
    )
    # host replica reconstructs through the packed wire format
    np.testing.assert_array_equal(
        np.asarray(dev_xhat), metrics._np_chunk_quantize4(x)
    )
    # receivers reconstruct from the PACKED bits: bitwise the sender's
    # own xhat (the property the difference form and EF copies rely on)
    recon = jax.jit(lambda q, s: inner._dequant4(q, s, n))(dev_q, dev_s)
    np.testing.assert_array_equal(np.asarray(recon), np.asarray(dev_xhat))
    # pack/unpack is exact for every representable nibble value
    n_chunks = -(-n // 512)
    q_all = rng.randint(-7, 8, size=(n_chunks, 512)).astype(np.int8)
    rt = np.asarray(
        inner._unpack_nibbles(inner._pack_nibbles(jnp.asarray(q_all)))
    )
    np.testing.assert_array_equal(rt, q_all)
    hostrt = metrics._np_unpack_nibbles(metrics._np_pack_nibbles(q_all))
    np.testing.assert_array_equal(hostrt, q_all)


def test_int4_combine_close_and_fixed_point():
    bf.set_topology(tu.RingGraph(SIZE))
    x = np.random.RandomState(20).randn(SIZE, 700).astype(np.float32)
    exact = np.asarray(bf.neighbor_allreduce(x))
    quant = np.asarray(bf.neighbor_allreduce(x, compression="int4"))
    step = np.abs(x).max(axis=1, keepdims=True) / 7.0
    assert np.abs(quant - exact).max() < 1.5 * step.max()
    assert not np.array_equal(quant, exact)
    # consensus is an exact fixed point (difference form)
    c = np.tile(x[:1], (SIZE, 1))
    out = np.asarray(bf.neighbor_allreduce(c, compression="int4"))
    np.testing.assert_allclose(out, c, rtol=1e-6, atol=1e-7)


def test_int4_wire_bytes_are_one_eighth_and_2x_vs_int8():
    """HLO proof of the 8x-vs-f32 / 2x-vs-int8 claims: packed nibbles +
    bf16 block scales. The byte accounting (scale sidecar included) is
    exactly 2x at every payload width; the CPU backend's optimized HLO
    widens the bf16 scale sidecar to f32 (its collective legalization,
    same as the bf16 wire — TPU ships it natively), so the HLO-counted
    ratio is bounded slightly under 2."""
    D = 4096
    plan = planlib.plan_from_topology(tu.RingGraph(SIZE), weighted=True)
    mesh = bf.get_context().mesh
    spec = P("workers")

    def lower(wire):
        import functools

        combine = (
            inner.weighted_combine if wire is None
            else functools.partial(
                inner.weighted_combine_quantized, wire=wire
            )
        )
        fn = jax.jit(
            jax.shard_map(
                lambda t: combine(t, plan, "workers"),
                mesh=mesh, in_specs=spec, out_specs=spec,
            )
        )
        x = jax.device_put(
            jnp.zeros((SIZE, D), jnp.float32), NamedSharding(mesh, spec)
        )
        return scaling.hlo_collective_stats(
            fn.lower(x).compile().as_text()
        )["collective-permute"]

    base, q8, q4 = lower(None), lower("int8"), lower("int4")
    assert q4["bytes"] <= int(base["bytes"] // 8 * 1.05), (base, q4)
    assert q8["bytes"] / q4["bytes"] > 1.9, (q8, q4)
    # the accounting (what the chooser and the evidence price) is exact
    for n in (1, 511, 512, 513, D):
        assert scaling.wire_payload_bytes(n, 4, "int8") == (
            2 * scaling.wire_payload_bytes(n, 4, "int4")
        ), n


def test_int4_scales_ride_bf16_on_the_wire():
    """The lowering ships the block scales as bf16 (the sidecar that
    preserves the full 2x vs int8); bind to the emitted collective's
    own types like the bf16-wire test."""
    import re

    D = 4096
    plan = planlib.plan_from_topology(tu.RingGraph(SIZE), weighted=True)
    mesh = bf.get_context().mesh
    spec = P("workers")
    fn = jax.jit(
        jax.shard_map(
            lambda t: inner.weighted_combine_quantized(
                t, plan, "workers", wire="int4"
            ),
            mesh=mesh, in_specs=spec, out_specs=spec,
        )
    )
    xd = jax.device_put(jnp.zeros((SIZE, D), jnp.float32),
                        NamedSharding(mesh, spec))
    lowered = fn.lower(xd).as_text()
    cp_types = re.findall(
        r"collective_permute.*?->\s*tensor<([^>]+)>", lowered
    )
    assert any("i8" in t and "256" in t for t in cp_types), cp_types
    assert any("bf16" in t for t in cp_types), cp_types


def test_int4_optimizer_converges():
    c = np.random.RandomState(21).randn(SIZE, 4).astype(np.float32)
    opt = bf.DistributedNeighborAllreduceOptimizer(
        optax.sgd(optax.exponential_decay(0.3, 10, 0.5))
    )
    opt.compression = "int4"
    params = {"w": bf.worker_values(lambda r: c[r])}
    state = opt.init(params)
    for _ in range(60):
        params, state = opt.step(params, state,
                                 {"w": params["w"] - jnp.asarray(c)})
    w = np.asarray(params["w"])
    target = c.mean(0)
    assert np.abs(w - target).max() < 0.2 * np.abs(c - target).max()


def test_int4_ef_removes_int4_noise_floor():
    """Plain int4's quantization floor is far coarser than int8's; the
    CHOCO error-feedback tier erases it the same way int8_ef erases
    int8's — the fact that makes a 4-bit wire trajectory-safe."""
    c = np.random.RandomState(22).randn(SIZE, 640).astype(np.float32) * 5.0
    zero = {"w": jnp.zeros((SIZE, 640), jnp.float32)}

    def run(compression):
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
        opt.compression = compression
        params = {"w": bf.worker_values(lambda r: c[r])}
        state = opt.init(params)
        for _ in range(150):
            params, state = opt.step(params, state, zero)
        w = np.asarray(params["w"])
        return np.abs(w - w.mean(0)).max()

    spread_plain = run("int4")
    spread_ef = run("int4_ef")
    assert spread_ef < 0.01 * spread_plain, (spread_plain, spread_ef)
    assert spread_ef < 1e-3


@pytest.mark.parametrize("dim", [512, 4096])
def test_wire_tiers_ship_bytes_in_tier_order(dim):
    """Over one Exp2 step, at whole 512-element scale groups, the six
    wire tiers ship int4 < int8 < bf16 < fp32 bytes, the block-scale
    sidecar priced in; each error-feedback tier ships exactly its base
    tier's bytes, and int4 halves int8."""
    plan = planlib.plan_from_topology(
        tu.ExponentialTwoGraph(SIZE), weighted=True
    )
    b = {
        w: plan.wire_bytes(dim, 4, wire=None if w == "fp32" else w)
        for w in ("fp32", "bf16", "int8", "int8_ef", "int4", "int4_ef")
    }
    assert b["fp32"] == len(plan.rounds) * dim * 4
    assert 0 < b["int4"] < b["int8"] < b["bf16"] < b["fp32"], b
    assert b["int8_ef"] == b["int8"] and b["int4_ef"] == b["int4"], b
    assert b["int8"] == 2 * b["int4"], b


def test_int4_ef_consensus_no_worse_than_int8():
    """On the same pure-consensus problem (zero gradients isolate the
    wire), the 4-bit error-feedback tier reaches a consensus at least
    as tight as plain int8's at half its wire bytes: error feedback
    erases the coarser quantizer's floor."""
    c = np.random.RandomState(23).randn(SIZE, 1024).astype(np.float32)
    zero = {"w": jnp.zeros((SIZE, 1024), jnp.float32)}

    def spread(compression):
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.0))
        opt.compression = compression
        params = {"w": bf.worker_values(lambda r: c[r])}
        state = opt.init(params)
        for _ in range(60):
            params, state = opt.step(params, state, zero)
            jax.block_until_ready((params, state))
        w = np.asarray(params["w"])
        return np.abs(w - w.mean(0)).max()

    s8, s4ef = spread("int8"), spread("int4_ef")
    assert s4ef <= s8, (s8, s4ef)


def test_int4_ef_restricted_paths():
    opt = bf.DistributedAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = "int4_ef"
    params = {"w": bf.worker_values(lambda r: np.ones(4, np.float32))}
    state = opt.init(params)
    with pytest.raises(ValueError, match="int4_ef"):
        opt.step(params, state, params)

    opt2 = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt2.compression = "int4_ef"
    state2 = opt2.init(params)
    train_step = opt2.make_train_step(
        lambda p, t: jnp.sum(p["w"] * t), delayed=True
    )
    with pytest.raises(ValueError, match="int4_ef"):
        train_step(params, state2, params["w"])


def test_hierarchical_int4_converges(cpu_devices):
    """int4 on the machine-level (DCN) leg: the 8x-compressed cross-host
    gossip still reaches consensus."""
    bf.shutdown()
    bf.init(devices=cpu_devices[:SIZE], nodes_per_machine=4)
    bf.set_machine_topology(tu.RingGraph(2))
    c = np.random.RandomState(23).randn(SIZE, 4).astype(np.float32)
    opt = bf.DistributedHierarchicalNeighborAllreduceOptimizer(
        optax.sgd(optax.exponential_decay(0.3, 10, 0.5))
    )
    opt.compression = "int4"
    params = {"w": bf.worker_values(lambda r: c[r])}
    state = opt.init(params)
    for _ in range(60):
        params, state = opt.step(params, state,
                                 {"w": params["w"] - jnp.asarray(c)})
    w = np.asarray(params["w"])
    target = c.mean(0)
    assert np.abs(w - target).max() < 0.2 * np.abs(c - target).max()
    assert np.abs(w - w.mean(0)).max() < 0.15


def test_quantized_allgather_all_wires():
    """Compressed neighbor_allgather: every wire returns a bounded
    approximation of the exact gather (bf16 near-lossless, int8/int4 at
    their block-scaled steps), same neighbor order and shapes."""
    bf.set_topology(tu.RingGraph(SIZE))
    x = np.random.RandomState(24).randn(SIZE, 600).astype(np.float32)
    exact = bf.neighbor_allgather(x)
    steps = {"bf16": 0.02 * np.abs(x).max(),
             "int8": np.abs(x).max() / 127.0 * 1.5,
             "int4": np.abs(x).max() / 7.0 * 1.5}
    for wire, bound in steps.items():
        got = bf.neighbor_allgather(x, compression=wire)
        assert len(got) == len(exact)
        for e, g in zip(exact, got):
            assert np.asarray(g).shape == np.asarray(e).shape
            assert np.abs(np.asarray(g) - np.asarray(e)).max() < bound, (
                wire
            )
    with pytest.raises(ValueError, match="int4"):
        bf.neighbor_allgather(x, compression="fp4")
    with pytest.raises(ValueError, match="float"):
        bf.neighbor_allgather(
            bf.worker_values(lambda r: np.ones(8, np.int32)),
            compression="int8",
        )
