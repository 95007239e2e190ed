# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Fused quantized wire kernels (``BLUEFOG_WIRE_KERNELS``,
``bluefog_tpu/collective/kernels.py``).

The contract under test is the one the module ships on: flipping the
kernel flag changes the STAGING a program materializes, never a bit of
any trajectory. So the matrix here is bitwise kernel-on == kernel-off
across every tier (int8 / int4 / int8_ef / int4_ef) and every dispatch
surface (monolithic and chunked combines, bucketed optimizer gossip,
the fused train step, the async tick, the quantized window exchange),
plus the pins that anchor both implementations to the shared numpy
wire reference (``collective/wire_ref.py``), the exhaustive nibble
sign-extension oracle, the cache-token semantics that keep toggles
from dispatching stale programs, and the measured-scratch gate the
kernels exist for (fused temp bytes below the fp32 row).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import metrics as bf_metrics
from bluefog_tpu import topology as tu
from bluefog_tpu.collective import inner, plan as planlib, wire_ref
from jax.sharding import NamedSharding, PartitionSpec as P

pytestmark = pytest.mark.wire_kernels

SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(cpu_devices):
    bf.init(devices=cpu_devices[:SIZE])
    yield
    bf.elastic.stop()
    bf.win_free()
    bf.shutdown()
    bf_metrics.reset()


def _kernels():
    from bluefog_tpu.collective import kernels

    return kernels


def _on_off(monkeypatch, build):
    """Run ``build()`` twice — kernels pinned off, then forced on —
    and return both results. ``build`` must construct a FRESH program
    each call (the flag is read at trace time)."""
    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "0")
    off = build()
    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "1")
    on = build()
    return off, on


# -- shared constants & reference pins -----------------------------------------


def test_scale_grid_constants_agree():
    """One 512-element scale grid across the kernels, the composite
    quantizers, the metrics replay, and the numpy reference — the
    bitwise matrix below is meaningless if these ever drift."""
    k = _kernels()
    assert k.CHUNK == inner._QUANT_CHUNK == bf_metrics._ROW == wire_ref.ROW


@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_kernel_and_composite_pin_to_numpy_reference(wire, monkeypatch):
    """Both implementations produce the numpy reference's exact wire
    bits AND reconstruction bits — including the padded tail block and
    the int4 bf16 scale snap."""
    k = _kernels()
    n = 1000  # two blocks, the second padded
    xf = np.random.RandomState(5).randn(n).astype(np.float32) * 5.0
    ref_payload, ref_scales, ref_xhat = wire_ref.np_encode(xf, wire)
    ref_decode = wire_ref.np_decode(ref_payload, ref_scales, n, wire)
    np.testing.assert_array_equal(ref_xhat, ref_decode)

    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "1")
    payload, scales = jax.jit(k.encode, static_argnums=1)(
        jnp.asarray(xf), wire
    )
    assert str(scales.dtype) == str(ref_scales.dtype)
    np.testing.assert_array_equal(np.asarray(payload), ref_payload)
    np.testing.assert_array_equal(
        np.asarray(scales).view(np.uint8), ref_scales.view(np.uint8)
    )
    out = jax.jit(k.decode, static_argnums=(2, 3))(
        payload, scales, n, wire
    )
    np.testing.assert_array_equal(np.asarray(out), ref_decode)

    quantize, dequant = inner._composite_block_quantizer(wire)
    cq, cs, cxhat = jax.jit(quantize)(jnp.asarray(xf))
    np.testing.assert_array_equal(np.asarray(cq), ref_payload)
    np.testing.assert_array_equal(
        np.asarray(cs).view(np.uint8), ref_scales.view(np.uint8)
    )
    np.testing.assert_array_equal(np.asarray(cxhat), ref_xhat)


def test_metrics_replay_delegates_to_wire_ref():
    """The metrics-tier numpy replays are thin wrappers over the shared
    reference (the former three copies are one now)."""
    xf = np.random.RandomState(6).randn(700).astype(np.float32)
    _q8, _s8, rxhat8 = wire_ref.np_encode(xf, "int8")
    np.testing.assert_array_equal(
        bf_metrics._np_chunk_quantize(xf), rxhat8
    )
    _q4, _s4, rxhat4 = wire_ref.np_encode(xf, "int4")
    np.testing.assert_array_equal(
        bf_metrics._np_chunk_quantize4(xf), rxhat4
    )
    q = np.random.RandomState(7).randint(-7, 8, (2, 512)).astype(np.int8)
    packed = bf_metrics._np_pack_nibbles(q)
    np.testing.assert_array_equal(packed, wire_ref.np_pack_nibbles(q))
    np.testing.assert_array_equal(
        bf_metrics._np_unpack_nibbles(packed), q
    )


def test_nibble_decoders_agree_on_all_256_bytes(monkeypatch):
    """Exhaustive one-block pin of the sign-extension trap: every
    possible packed byte decodes to the same signed nibble pair in the
    kernel, the composite ``_unpack_nibbles``, and the numpy reference
    (``(p << 4) >> 4`` must arithmetic-shift; a logical shift or an
    unsigned intermediate silently maps -1..-8 to 15..8)."""
    k = _kernels()
    packed = np.arange(256, dtype=np.uint8).view(np.int8).reshape(1, 256)
    ref = wire_ref.np_unpack_nibbles(packed)
    assert set(np.unique(ref)) == set(range(-8, 8))  # all 16 values hit

    comp = np.asarray(inner._unpack_nibbles(jnp.asarray(packed)))
    np.testing.assert_array_equal(comp, ref)

    # kernel decode with exact unit scales: the f32 output IS the nibble
    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "1")
    ones = jnp.ones((1,), jnp.bfloat16)
    out = jax.jit(k.decode, static_argnums=(2, 3))(
        jnp.asarray(packed), ones, 512, "int4"
    )
    np.testing.assert_array_equal(
        np.asarray(out), ref.reshape(-1).astype(np.float32)
    )


def test_cache_token_semantics(monkeypatch):
    """Kernel-off keys must be byte-identical to pre-kernel keys (empty
    token), the token only rides quantized-integer tiers, and the
    kernels are the default."""
    k = _kernels()
    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "0")
    assert not k.wire_kernels_on()
    assert k.cache_token("int8") == ()
    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "1")
    assert k.wire_kernels_on()
    for wire in ("int8", "int4", "int8_ef", "int4_ef"):
        assert k.cache_token(wire) == ("wire_kernels",)
    for wire in (None, "bf16", "fp32"):
        assert k.cache_token(wire) == ()
    monkeypatch.delenv("BLUEFOG_WIRE_KERNELS")
    assert k.wire_kernels_on()


# -- the bitwise kernel-on == kernel-off matrix ---------------------------------


def _sharded_combine(wire, chunks, dim=2048):
    plan = planlib.plan_from_topology(tu.RingGraph(SIZE), weighted=True)
    mesh = bf.get_context().mesh
    x = jax.device_put(
        jnp.asarray(
            np.random.RandomState(11).randn(SIZE, dim).astype(np.float32)
            * 5.0
        ),
        NamedSharding(mesh, P("workers")),
    )
    fn = jax.jit(jax.shard_map(
        lambda t: inner.weighted_combine_quantized(
            t, plan, "workers", wire=wire, chunks=chunks
        ),
        mesh=mesh, in_specs=P("workers"), out_specs=P("workers"),
    ))
    return np.asarray(fn(x))


@pytest.mark.parametrize("chunks", [1, 4])
@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_combine_kernel_on_off_bitwise(wire, chunks, monkeypatch):
    bf.set_topology(tu.RingGraph(SIZE))
    off, on = _on_off(
        monkeypatch, lambda: _sharded_combine(wire, chunks)
    )
    np.testing.assert_array_equal(off, on)


@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_chunked_matches_monolithic_with_kernels_on(wire, monkeypatch):
    """The chunked wavefront quantizes per 512-block exactly like the
    monolithic combine, kernels included."""
    bf.set_topology(tu.RingGraph(SIZE))
    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "1")
    np.testing.assert_array_equal(
        _sharded_combine(wire, 1), _sharded_combine(wire, 4)
    )


def _optimizer_trajectory(wire, steps=5, dim=1500):
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    c = np.random.RandomState(12).randn(SIZE, dim).astype(np.float32)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    opt.compression = wire
    params = {"w": bf.worker_values(lambda r: c[r])}
    state = opt.init(params)
    for _ in range(steps):
        grads = {"w": params["w"] - jnp.asarray(c)}
        params, state = opt.step(params, state, grads)
    return np.asarray(params["w"])


@pytest.mark.parametrize("wire", ["int8", "int4", "int8_ef", "int4_ef"])
def test_optimizer_kernel_on_off_bitwise(wire, monkeypatch):
    """Every tier through the real optimizer dispatch (the EF tiers run
    the fused ``encode_diff`` sender when the kernels are on)."""
    off, on = _on_off(
        monkeypatch, lambda: _optimizer_trajectory(wire)
    )
    np.testing.assert_array_equal(off, on)


@pytest.mark.parametrize("wire", ["int4", "int4_ef"])
def test_bucketed_gossip_kernel_on_off_bitwise(wire, monkeypatch):
    """A bucket cap small enough to split the payload exercises the
    bucketed dispatch (each bucket runs its own kernel programs)."""
    monkeypatch.setenv("BLUEFOG_BUCKET_BYTES", "4096")  # 1024 f32 elems
    off, on = _on_off(
        monkeypatch, lambda: _optimizer_trajectory(wire, dim=3000)
    )
    np.testing.assert_array_equal(off, on)


def test_fused_step_matches_two_program_with_kernels_on(monkeypatch):
    """The fused train step stays bitwise the two-program path with the
    kernels on (both dispatch the same kernel-keyed gossip core)."""
    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "1")
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    from bluefog_tpu import context as ctx_mod

    c = np.random.RandomState(13).randn(SIZE, 1024).astype(np.float32)
    target = bf.worker_values(lambda r: c[r] * 0.5)

    def loss_fn(p, t):
        return 0.5 * jnp.sum((p["w"] - t) ** 2)

    ctx = ctx_mod.get_context()
    spec = P(ctx_mod.WORKER_AXIS)

    def grad_body(p_b, t_b):
        p = jax.tree_util.tree_map(lambda a: a[0], p_b)
        g = jax.grad(loss_fn)(p, t_b[0])
        return jax.tree_util.tree_map(
            lambda a: jnp.expand_dims(a, 0), g
        )

    grad_fn = jax.jit(jax.shard_map(
        grad_body, mesh=ctx.mesh, in_specs=(spec, spec), out_specs=spec
    ))

    def make(wire):
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.05))
        opt.compression = wire
        params = {"w": bf.worker_values(lambda r: c[r])}
        return opt, params, opt.init(params)

    opt1, p1, s1 = make("int4")
    opt2, p2, s2 = make("int4")
    train_step = opt2.make_train_step(loss_fn)
    for _ in range(3):
        g = grad_fn(p1, target)
        p1, s1 = opt1.step(p1, s1, g)
        p2, s2, _loss = train_step(p2, s2, target)
    np.testing.assert_array_equal(
        np.asarray(p1["w"]), np.asarray(p2["w"])
    )


def test_async_tick_kernel_on_off_bitwise(monkeypatch):
    """The async engine's tick (its quantized push rides the window
    wire core) is bitwise flag-invariant; each build makes a fresh
    engine (unique window + cache uid)."""
    z0 = np.random.RandomState(14).randn(SIZE, 600).astype(np.float32)
    batch = jnp.asarray(z0)

    def loss_fn(p, t):
        return 0.5 * jnp.sum((p["w"] - t) ** 2)

    def build():
        bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
        opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.05))
        params = {"w": jnp.asarray(z0)}
        state = opt.init(params)
        step = bf.make_async_train_step(
            opt, loss_fn, wire="int4", cadence={0: 3, 5: 2}
        )
        assert hasattr(step, "engine")
        for _ in range(8):
            params, state, _ = step(params, state, batch)
        return np.asarray(params["w"])

    off, on = _on_off(monkeypatch, build)
    np.testing.assert_array_equal(off, on)


# -- push-sum mass conservation with the kernels on -----------------------------


@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_push_sum_mass_conserved_with_kernels_on(wire, monkeypatch):
    """The window wire's sender-residual-absorption mass conservation
    (docs/windows.md) holds through the fused encode/decode: drift
    stays at f32 rounding, not quantization magnitude."""
    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "1")
    monkeypatch.setenv("BLUEFOG_WINDOW_WIRE", wire)
    from bluefog_tpu import windows as win_mod

    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    bf.turn_on_win_ops_with_associated_p()
    x0 = np.random.RandomState(15).randn(SIZE, 600).astype(np.float32) * 3
    bf.win_create(bf.worker_values(lambda r: x0[r]), "psk", zero_init=True)
    outs = bf.get_context().out_neighbor_ranks()
    dst = [
        {d: 1.0 / (len(outs[r]) + 1) for d in outs[r]}
        for r in range(SIZE)
    ]
    sw = [1.0 / (len(outs[r]) + 1) for r in range(SIZE)]
    total0 = x0.sum(0, dtype=np.float64)
    for _ in range(15):
        bf.win_accumulate(name="psk", self_weight=sw, dst_weights=dst)
        bf.win_update_then_collect("psk")
        v = np.asarray(bf.win_read("psk"), np.float64)
        assert np.abs(v.sum(0) - total0).max() < 5e-4
    p = win_mod.win_associated_p("psk")
    np.testing.assert_allclose(p.sum(), SIZE, rtol=1e-6)
    est = np.asarray(bf.win_read("psk")) / p[:, None].astype(np.float32)
    noise = {"int8": 0.1, "int4": 0.6}[wire]
    assert np.abs(est - x0.mean(0)).max() < noise


# -- the scratch gate (the kernels' reason to exist) -----------------------------


def test_fused_scratch_below_fp32_row(monkeypatch):
    """Measured XLA scratch, int8 and int4: the fused
    combine's temp bytes land BELOW the uncompressed fp32 combine's
    (the full-width temporary never materializes), while the composite
    path still stages at least the full-width reconstruction."""
    dim = 4096
    plan = planlib.plan_from_topology(tu.RingGraph(SIZE))
    mesh = bf.get_context().mesh
    x = jax.device_put(
        jnp.zeros((SIZE, dim), jnp.float32),
        NamedSharding(mesh, P("workers")),
    )

    def temp_bytes(wire):
        if wire is None:
            body = lambda t: inner.neighbor_allreduce(t, plan, "workers")
        else:
            body = lambda t, w=wire: inner.weighted_combine_quantized(
                t, plan, "workers", wire=w
            )
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("workers"),
            out_specs=P("workers"),
        ))
        ma = fn.lower(x).compile().memory_analysis()
        return int(ma.temp_size_in_bytes)

    monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "0")
    fp32 = temp_bytes(None)
    assert fp32 >= 4 * dim
    for wire in ("int8", "int4"):
        monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "0")
        composite = temp_bytes(wire)
        monkeypatch.setenv("BLUEFOG_WIRE_KERNELS", "1")
        fused = temp_bytes(wire)
        assert composite >= 4 * dim, (wire, composite)
        assert fused < fp32, (wire, fused, fp32)
        assert fused < composite, (wire, fused, composite)


# -- the overlap scan recognizes pallas custom-calls -----------------------------


def test_overlap_scan_counts_pallas_custom_calls():
    """A Mosaic ``tpu_custom_call`` (the kernels' native lowering) is
    real compute the scan must count — and the overlap verdicts around
    it are unchanged (the permute here is independent of both compute
    ops, so it stays overlappable)."""
    from tools.hlo_overlap_scan import scan_overlap

    txt = """HloModule m

ENTRY %main (p0: f32[8,512]) -> f32[8,512] {
  %p0 = f32[8,512] parameter(0)
  %k = (s8[8,512], f32[8,1]) custom-call(%p0), custom_call_target="tpu_custom_call"
  %cp = f32[8,512] collective-permute(%p0), source_target_pairs={{0,1},{1,0}}
  ROOT %f = f32[8,512] fusion(%p0), kind=kLoop, calls=%fused_add
}
"""
    scan = scan_overlap(txt)
    assert scan["pallas_custom_calls"] == 1
    assert scan["total_compute_ops"] == 2  # the fusion AND the kernel
    assert scan["sync_collective_permutes"] == 1
    assert scan["overlappable_permutes"] == 1
    rec = scan["permutes"][0]
    assert rec["independent_compute_ops"] == 2
