#!/usr/bin/env python3
# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""chip_smoke.py — the quickest proof that the system still trains on the chip.

One process, no children, every device ``jax.devices()`` returns, synthetic
data from a seed, and only the entry points a user calls: ``bf.init()``,
``bf.worker_values``, ``bf.Distributed*Optimizer``, ``bf.make_train_step``
and the ``bf.*`` eager facade. Four phases, each printing one JSON line:

- ``collectives``  the eager gossip ops against their numpy oracles, and
  that a worker-stacked array has one row on each device (n > 1 only);
- ``resnet50``     the source paper's benchmark model at full width through
  the fused train step, dynamic one-peer Exp2 gossip inside the step;
- ``lm``           the 189 M-parameter TransformerLM at T = 4096 with the
  Pallas flash kernels compiled natively inside the train step;
- ``wire``         the quantized wire with its Pallas kernels compiled
  natively, bitwise against the composite path and the numpy reference.

It refuses to run unless ``jax.default_backend() == "tpu"``, never shrinks a
shape because of the platform, and any failed check is a raised exception:
the failing phase is named on stderr and the exit code is non-zero. The
times it prints are information for whoever builds the benchmark, not
metrics. The last stdout line is ``{"ok": true, "device": {...}}``.

tests/test_chip_smoke.py drives the same phase functions at ``TOY`` size on
the 4-device CPU mesh (``native=False``: the kernels' XLA-ops path).
"""

import contextlib
import functools
import json
import os
import re
import sys
import time
from importlib import metadata

import jax
import jax.numpy as jnp
import jaxlib
import networkx as nx
import numpy as np
import optax

import bluefog_tpu as bf
from bluefog_tpu import models
from bluefog_tpu import topology as tu
from bluefog_tpu.collective import inner, kernels, wire_ref
from bluefog_tpu.collective.plan import schedule_from_dynamic
from bluefog_tpu.ops import flash_attention
from bluefog_tpu.ops.attention import reference_attention
from bluefog_tpu.timing import settle

SEED = 0

FULL = {
    "collectives": {"dim": 1 << 16},
    "resnet50": {
        "model": "ResNet50", "model_kwargs": {}, "num_classes": 1000,
        "image": 224, "batch": 64, "steps": 6, "timed_steps": 5,
        # ISSUE 21's payload size; checked against the model built here
        "n_params": 25_557_032,
    },
    "lm": {
        "vocab": 16384, "dim": 1024, "heads": 16, "layers": 12,
        "seq": 4096, "batch": 2, "steps": 4,
    },
}

TOY = {
    "collectives": {"dim": 1 << 10},
    "resnet50": {
        "model": "ResNet18", "model_kwargs": {"num_filters": 8},
        "num_classes": 10, "image": 32, "batch": 4, "steps": 6,
        "timed_steps": 2, "n_params": None,
    },
    "lm": {
        "vocab": 256, "dim": 64, "heads": 4, "layers": 2,
        "seq": 128, "batch": 2, "steps": 4,
    },
}

# Stated bf16 tolerance of flash vs reference_attention (f32 at the
# highest matmul precision): max |a - b| over max |b|. The kernel rounds
# its probabilities and its output to bf16 (8 mantissa bits: 2^-9 = 0.002
# relative per rounding) and sums up to T of them per row; measured on
# the v5e at T = 4096 (PR 21): out 0.0027, dq/dk/dv 0.0050-0.0056.
FLASH_TOL = {"out": 1e-2, "grad": 2e-2}

_MOSAIC = 'custom_call_target="tpu_custom_call"'
_PERMUTE = re.compile(r"\bcollective-permute(?:-start)?\(")


def require(cond, msg):
    """A check that survives ``python -O`` (``assert`` does not)."""
    if not cond:
        raise RuntimeError(msg)


class CacheEvents:
    """Persistent-compile-cache hits and misses, as jax itself counts."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self):
        out = {"hits": self.hits, "misses": self.misses}
        self.hits = self.misses = 0
        return out


def device_report():
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        "workers": bf.size(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }


def peak_bytes():
    """Peak device memory of the process so far (max over devices); None
    where the backend does not report it."""
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def emit(phase, cache, **fields):
    line = {"phase": phase, "ok": True, **device_report()}
    line["cache"] = cache.take() if cache is not None else None
    line["peak_bytes_in_use"] = peak_bytes()
    line.update(fields)
    print(json.dumps(line), flush=True)
    return line


def check_stacked(x, what):
    """A worker-stacked array spans all n devices, one row on each."""
    devices = set(bf.get_context().devices)
    require(
        x.sharding.device_set == devices,
        f"{what}: lives on {len(x.sharding.device_set)} of "
        f"{len(devices)} devices",
    )
    shards = x.addressable_shards
    require(
        {s.device for s in shards} == devices
        and all(s.data.shape[0] == 1 for s in shards),
        f"{what}: shard rows {[s.data.shape[0] for s in shards]}",
    )


@jax.jit
def _all_finite(leaves):
    return jnp.stack([jnp.isfinite(leaf).all() for leaf in leaves]).all()


def all_finite(tree):
    return bool(_all_finite(jax.tree_util.tree_leaves(tree)))


def stack_params(tree):
    """The same initial model on every worker, worker-stacked."""
    return jax.tree_util.tree_map(
        lambda t: bf.worker_values(np.asarray(t)), tree
    )


def one_peer_schedule(n):
    graph = tu.ExponentialTwoGraph(n)
    return schedule_from_dynamic(
        n, lambda r: tu.GetDynamicOnePeerSendRecvRanks(graph, r)
    )


def run_steps(step, carry, steps):
    """``steps`` calls of ``step(carry) -> (carry, loss)``, each ended by
    ``block_until_ready``; -> (carry, per-step seconds, per-step losses).
    The first call of a new step compiles."""
    times, losses = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        carry, loss = step(carry)
        jax.block_until_ready((carry, loss))
        times.append(round(time.perf_counter() - t0, 4))
        losses.append(np.asarray(loss, np.float64))
    return carry, times, losses


def check_training(phase, params, losses):
    """Every loss and parameter finite, and the loss lower at the end
    than at the start; -> the mean loss per step."""
    require(
        all(np.isfinite(l).all() for l in losses),
        f"{phase}: non-finite loss in {[l.tolist() for l in losses]}",
    )
    require(all_finite(params), f"{phase}: non-finite parameter")
    trace = [round(float(l.mean()), 4) for l in losses]
    require(trace[-1] < trace[0], f"{phase}: loss did not fall: {trace}")
    return trace


def hlo_counts(hlo):
    return {
        "tpu_custom_call": hlo.count(_MOSAIC),
        "collective_permute": len(_PERMUTE.findall(hlo)),
    }


def step_report(times, losses):
    """compile_s is the first call minus a warm step: trace, lowering and
    XLA compile (or the load from a warm cache)."""
    warm = float(np.median(times[1:]))
    return {
        "compile_s": round(times[0] - warm, 2), "step_s": times,
        "losses": losses,
    }


# -- collectives ---------------------------------------------------------------


def phase_collectives(cfg, cache=None):
    n = bf.size()
    if n == 1:
        return emit("collectives", cache, gossip="none (1 device)")
    rng = np.random.RandomState(SEED)
    data = rng.randn(n, cfg["dim"]).astype(np.float32)
    x = bf.worker_values(lambda r: data[r])
    check_stacked(x, "worker_values")
    errors = {}

    def close(name, got, want):
        check_stacked(got, name)
        err = float(np.abs(np.asarray(got) - want).max())
        require(err < 1e-5, f"{name}: max error {err} vs its numpy oracle")
        errors[name] = err

    # static Exp2: y = W^T x
    graph = tu.ExponentialTwoGraph(n)
    bf.set_topology(graph, is_weighted=True)
    close(
        "neighbor_allreduce_static_exp2",
        bf.neighbor_allreduce(x), nx.to_numpy_array(graph).T @ data,
    )

    # dynamic one-peer Exp2, one whole period of the schedule
    gens = [tu.GetDynamicOnePeerSendRecvRanks(graph, r) for r in range(n)]
    y, want = x, data
    for _ in range(one_peer_schedule(n).period):
        sr = [next(g) for g in gens]
        y = bf.neighbor_allreduce(
            y, self_weight=0.5,
            src_weights=[{s: 0.5 for s in recv} for _send, recv in sr],
            dst_weights=[list(send) for send, _recv in sr],
        )
        want = np.stack([
            0.5 * want[r] + 0.5 * sum(want[s] for s in sr[r][1])
            for r in range(n)
        ])
    close("neighbor_allreduce_dynamic_one_peer", y, want)

    # hierarchical: local mean, machine-level gossip, broadcast back
    local, machines = bf.local_size(), bf.machine_size()
    require(
        (machines, local) == (n // 2, 2),
        f"expected a {n // 2} x 2 machines x local mesh, got "
        f"{machines} x {local}",
    )
    ring = tu.RingGraph(machines)
    bf.set_machine_topology(ring, is_weighted=True)
    means = data.reshape(machines, local, -1).mean(1)
    close(
        "hierarchical_neighbor_allreduce",
        bf.hierarchical_neighbor_allreduce(x),
        np.repeat(nx.to_numpy_array(ring).T @ means, local, axis=0),
    )

    # push-sum windows: x <- W^T x, p <- W^T p (W sender-stochastic)
    outs = bf.out_neighbor_ranks()
    w = np.zeros((n, n))
    for r in range(n):
        w[r, [r] + outs[r]] = 1.0 / (len(outs[r]) + 1)
    bf.turn_on_win_ops_with_associated_p()
    try:
        bf.win_create(x, "smoke_ps", zero_init=True)
        want_x, want_p = data, np.ones(n)
        for _ in range(3):
            bf.win_accumulate(
                None, "smoke_ps",
                self_weight=[w[r, r] for r in range(n)],
                dst_weights=[{d: w[r, d] for d in outs[r]} for r in range(n)],
            )
            got = bf.win_update_then_collect("smoke_ps")
            want_x, want_p = w.T @ want_x, w.T @ want_p
        close("win_accumulate_update_then_collect", got, want_x)
        p_err = float(np.abs(bf.win_associated_p("smoke_ps") - want_p).max())
        require(p_err < 1e-5, f"push-sum weights off by {p_err}")
        errors["win_associated_p"] = p_err
    finally:
        bf.turn_off_win_ops_with_associated_p()
        bf.win_free("smoke_ps")
    return emit("collectives", cache, max_abs_error=errors)


# -- resnet50 ------------------------------------------------------------------


class ResNetJob:
    """Model, worker-stacked parameters and one seeded batch per worker —
    shared by the ``resnet50`` phase and the ``wire`` phase's steps."""

    def __init__(self, cfg):
        self.cfg = cfg
        n = bf.size()
        model = getattr(models, cfg["model"])(
            num_classes=cfg["num_classes"], **cfg["model_kwargs"]
        )
        image, batch = cfg["image"], cfg["batch"]
        variables = jax.jit(functools.partial(model.init, train=True))(
            jax.random.PRNGKey(SEED),
            jnp.ones((1, image, image, 3), jnp.bfloat16),
        )
        self.n_params = sum(
            l.size for l in jax.tree_util.tree_leaves(variables["params"])
        )
        require(
            cfg["n_params"] in (None, self.n_params),
            f"{cfg['model']} has {self.n_params} parameters, expected "
            f"{cfg['n_params']}",
        )
        self.params = stack_params(variables["params"])
        self.batch_stats = stack_params(variables["batch_stats"])
        rng = np.random.default_rng(SEED)
        images = rng.standard_normal(
            (n, batch, image, image, 3), np.float32
        ).astype(jnp.bfloat16)
        labels = rng.integers(0, cfg["num_classes"], (n, batch))
        self.images = bf.worker_values(lambda r: images[r])
        self.labels = bf.worker_values(lambda r: labels[r].astype(np.int32))

        def loss_fn(params, batch_stats, x, y):
            logits, mutated = model.apply(
                {"params": params, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            return loss, mutated["batch_stats"]

        self.loss_fn = loss_fn

    def start(self, configure):
        """A fresh optimizer (``configure(opt)`` sets its gossip), its
        state, and the fused train step as ``step(carry) -> (carry,
        loss)`` with ``carry = (params, opt_state, batch_stats)``: the
        statistics ride as a batch operand and come back as aux."""
        opt = bf.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.1, momentum=0.9)
        )
        configure(opt)
        fused = bf.make_train_step(opt, self.loss_fn, has_aux=True)

        def step(carry):
            params, state, (loss, stats) = fused(
                *carry, self.images, self.labels
            )
            return (params, state, stats), loss

        # the fused step consumes its parameters and state, and every
        # phase starts from the job's: each start trains a copy
        params = jax.tree_util.tree_map(jnp.copy, self.params)
        carry = (params, opt.init(params), self.batch_stats)
        return opt, step, carry

    def hlo(self, opt, carry):
        return opt.lower_last_fused_hlo(*carry, self.images, self.labels)


def timing_honesty(step, carry, k):
    """Is ``block_until_ready`` honest here? The same warm steps, ended
    once by ``block_until_ready`` and once by ``timing.settle`` (a fresh
    jitted gather and a host read), and what a settle still waits for
    after ``block_until_ready`` has returned: nothing, if it is honest."""
    by_block, after_block, by_settle = [], [], []
    carry, loss = step(carry)
    settle(loss)  # settle's own gather compiles here
    for _ in range(k):
        t0 = time.perf_counter()
        carry, loss = step(carry)
        jax.block_until_ready((carry, loss))
        t1 = time.perf_counter()
        settle(loss)
        t2 = time.perf_counter()
        by_block.append(round(t1 - t0, 4))
        after_block.append(round(t2 - t1, 4))
    for _ in range(k):
        t0 = time.perf_counter()
        carry, loss = step(carry)
        settle(loss)
        by_settle.append(round(time.perf_counter() - t0, 4))
    return {
        "step_s_block_until_ready": by_block,
        "settle_after_block_s": after_block,
        "step_s_settle": by_settle,
    }


def phase_resnet50(job, cache=None):
    cfg, n = job.cfg, bf.size()

    def dynamic_one_peer(opt):
        if n > 1:
            opt.schedule = one_peer_schedule(n)

    opt, step, carry = job.start(dynamic_one_peer)
    carry, times, losses = run_steps(step, carry, cfg["steps"])
    trace = check_training("resnet50", carry[0], losses)
    for tree in carry:
        check_stacked(
            jax.tree_util.tree_leaves(tree)[0], "resnet50 train-step output"
        )
    counts = hlo_counts(job.hlo(opt, carry))
    require(
        n == 1 or counts["collective_permute"] > 0,
        "resnet50: no collective-permute in the compiled step",
    )
    return emit(
        "resnet50", cache, model=cfg["model"], n_params=job.n_params,
        batch_per_worker=cfg["batch"], image=cfg["image"],
        gossip="dynamic one-peer Exp2" if n > 1 else "none (1 device)",
        hlo=counts, **step_report(times, trace),
        **timing_honesty(step, carry, cfg["timed_steps"]),
    )


# -- lm ------------------------------------------------------------------------


def check_flash(cfg, native):
    """``flash_attention`` forward and ``jax.grad`` at the train step's
    attention shape against ``reference_attention``; -> the errors.
    Off-TPU the kernels run in the Pallas interpreter."""
    shape = (cfg["batch"], cfg["seq"], cfg["heads"], cfg["dim"] // cfg["heads"])
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, do = (jax.random.normal(key, shape, jnp.bfloat16) for key in keys)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=not native)

    def reference(q, k, v):
        # one sequence at a time: the dense T x T scores of the whole
        # batch in f32 would not leave room for their own backward
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(
                lambda qkv: reference_attention(
                    *(t[None].astype(jnp.float32) for t in qkv), causal=True
                )[0],
                (q, k, v),
            )

    def out_and_grads(attend):
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(do.astype(out.dtype))

    got = jax.jit(lambda: out_and_grads(flash))()
    want = jax.jit(lambda: out_and_grads(reference))()
    errors = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        errors[name] = float(np.abs(a - b).max() / np.abs(b).max())
        tol = FLASH_TOL["out" if name == "out" else "grad"]
        require(
            np.isfinite(a).all() and errors[name] <= tol,
            f"flash {name}: max |a - b| / max |b| = {errors[name]} > {tol}",
        )
    return {key: round(err, 5) for key, err in errors.items()}


def phase_lm(cfg, cache=None, native=True):
    n = bf.size()
    flash_errors = check_flash(cfg, native)

    model = models.TransformerLM(
        vocab=cfg["vocab"], dim=cfg["dim"], heads=cfg["heads"],
        layers=cfg["layers"], max_len=cfg["seq"], dtype=jnp.bfloat16,
    )
    host_tokens = np.random.RandomState(SEED).randint(
        0, cfg["vocab"], (n, cfg["batch"], cfg["seq"])
    )
    tokens = bf.worker_values(lambda r: host_tokens[r].astype(np.int32))
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(SEED), jnp.zeros((1, cfg["seq"]), jnp.int32)
    )
    n_params = sum(
        l.size for l in jax.tree_util.tree_leaves(variables["params"])
    )
    params = stack_params(variables["params"])
    del variables

    def loss_fn(p, tok):
        logits = model.apply({"params": p}, tok)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tok[:, 1:]
        ).mean()

    # static Exp2 plan: the active topology, no schedule
    bf.set_topology(tu.ExponentialTwoGraph(n), is_weighted=True)
    opt = bf.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.01, momentum=0.9)
    )
    fused = bf.make_train_step(opt, loss_fn)

    def step(carry):
        p, s, loss = fused(*carry, tokens)
        return (p, s), loss

    carry = (params, opt.init(params))
    del params
    carry, times, losses = run_steps(step, carry, cfg["steps"])
    trace = check_training("lm", carry[0], losses)
    counts = hlo_counts(opt.lower_last_fused_hlo(*carry, tokens))
    # a step that ran dense attention is a failure, not a pass
    require(
        not native or counts["tpu_custom_call"] > 0,
        "lm: no Mosaic custom call (tpu_custom_call) in the compiled step",
    )
    require(
        n == 1 or counts["collective_permute"] > 0,
        "lm: no collective-permute in the compiled step",
    )
    return emit(
        "lm", cache, n_params=n_params, seq=cfg["seq"],
        batch_per_worker=cfg["batch"],
        gossip="static Exp2" if n > 1 else "none (1 device)",
        attention="pallas flash (native)" if native else "dense (not a TPU)",
        hlo=counts, flash_vs_reference=flash_errors,
        **step_report(times, trace),
    )


# -- wire ----------------------------------------------------------------------


def _bits(x):
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _differing(a, b):
    return int((_bits(a) != _bits(b)).sum())


def against_wire_ref(wire, x, payload, scales, decoded):
    """The numpy wire reference on whole blocks of the device's output.

    Decoding is exact arithmetic, so the device's reconstruction of its
    own wire bits must equal ``np_decode`` of them bitwise. Encoding
    divides, and XLA divides by a constant through its reciprocal
    (measured on XLA:CPU: a block scale 1 ulp off numpy's quotient in a
    few percent of blocks), which moves a lane that sits on a rounding
    boundary by one step: those are counted and bounded, not hidden.
    -> (scales differing, payload lanes differing)."""
    n = x.size
    want = wire_ref.np_decode(payload, scales, n, wire)
    diff = _differing(decoded, want)
    require(
        diff == 0,
        f"wire {wire}: decode differs from wire_ref.np_decode in {diff} "
        f"of {n} elements",
    )
    ref_payload, ref_scales, _xhat = wire_ref.np_encode(x, wire)
    ulps = np.abs(
        _bits(scales).astype(np.int64) - _bits(ref_scales).astype(np.int64)
    )
    require(
        ulps.max() <= 1,
        f"wire {wire}: a block scale is {ulps.max()} ulp from wire_ref's",
    )
    dev, ref = (
        wire_ref.np_unpack_nibbles(p) if wire == "int4" else p
        for p in (payload, ref_payload)
    )
    steps = np.abs(dev.astype(np.int16) - ref.astype(np.int16))
    require(
        steps.max() <= 1 and (steps != 0).mean() < 1e-3,
        f"wire {wire}: payload is up to {steps.max()} steps from "
        f"wire_ref's in {(steps != 0).mean():.2%} of lanes",
    )
    return int((ulps != 0).sum()), int((steps != 0).sum())


def wire_kernels_vs_composite(wire, elems, native):
    """``kernels.encode`` / ``decode`` / ``decode_accumulate`` at ``elems``
    f32 elements, bitwise against the composite quantizer (the same gate
    every combine reads, flipped in-process), and their first and ragged
    last blocks against the numpy wire reference."""
    kx, kr = jax.random.split(jax.random.PRNGKey(SEED))
    x = jax.random.normal(kx, (elems,), jnp.float32)
    recv = x + 0.1 * jax.random.normal(kr, (elems,), jnp.float32)
    weights = jnp.asarray([0.5], jnp.float32)

    def fused(x, recv):
        q, s = kernels.encode(x, wire)
        rq, rs = kernels.encode(recv, wire)
        y = kernels.decode_accumulate(x, q, s, [(rq, rs)], weights, wire)
        return q, s, kernels.decode(q, s, elems, wire), y

    def composite(x, recv):
        quantize, dequant = inner._block_quantizer(wire)
        q, s, xhat = quantize(x)
        rq, rs, _ = quantize(recv)
        return q, s, xhat, x + (dequant(rq, rs, elems) - xhat) * weights[0]

    flag = os.environ.get("BLUEFOG_WIRE_KERNELS")
    try:
        os.environ["BLUEFOG_WIRE_KERNELS"] = "0"  # read while tracing
        want = jax.jit(composite)(x, recv)
    finally:
        if flag is None:
            del os.environ["BLUEFOG_WIRE_KERNELS"]
        else:
            os.environ["BLUEFOG_WIRE_KERNELS"] = flag
    compiled = jax.jit(fused).lower(x, recv).compile()
    got = compiled(x, recv)
    calls = compiled.as_text().count(_MOSAIC)
    require(
        not native or calls >= 4,
        f"wire {wire}: {calls} Mosaic custom calls in 2 x encode + decode "
        "+ decode_accumulate, expected 4",
    )
    names = ("payload", "scales", "decode", "combine")
    for name, a, b in zip(names, got, want):
        require(
            a.dtype == b.dtype and a.shape == b.shape
            and _differing(a, b) == 0,
            f"wire {wire}: fused {name} ({a.dtype}{a.shape}) differs from "
            f"the composite path's ({b.dtype}{b.shape}) in "
            f"{_differing(a, b)} elements",
        )
    payload, scales, decoded, _y = got
    n_chunks, row = payload.shape[0], wire_ref.ROW
    rows = min(64, n_chunks)
    off_ref = [0, 0]
    for lo in (0, n_chunks - rows):
        hi = lo + rows
        counts = against_wire_ref(
            wire, np.asarray(x[lo * row: hi * row]),
            np.asarray(payload[lo:hi]), np.asarray(scales[lo:hi]),
            np.asarray(decoded[lo * row: hi * row]),
        )
        off_ref = [a + b for a, b in zip(off_ref, counts)]
    return {
        "tpu_custom_call": calls, "bitwise_vs_composite": True,
        "decode_bitwise_vs_wire_ref": True,
        "encode_vs_wire_ref": {
            "blocks": 2 * rows, "scales_1ulp_off": off_ref[0],
            "lanes_1step_off": off_ref[1],
        },
    }


def phase_wire(job, cache=None, native=True):
    n = bf.size()
    path = "pallas (native Mosaic)" if native else "kernel bodies as XLA ops"
    report = {"elems": job.n_params, "kernels": {}, "steps": {}}
    for wire in ("int8", "int4"):
        report["kernels"][wire] = wire_kernels_vs_composite(
            wire, job.n_params, native
        )
    if n == 1:
        report["steps"] = "none (1 device)"
        return emit("wire", cache, wire_kernel_path=path, **report)
    # the quantized wire rides the static plan: the active topology
    bf.set_topology(tu.ExponentialTwoGraph(n), is_weighted=True)
    for wire in ("int8", "int4"):

        def quantized(opt, wire=wire):
            opt.compression = wire

        opt, step, carry = job.start(quantized)
        carry, times, losses = run_steps(step, carry, 2)
        trace = check_training(f"wire {wire}", carry[0], losses)
        counts = hlo_counts(job.hlo(opt, carry))
        require(
            not native or counts["tpu_custom_call"] > 0,
            f"wire {wire}: no Mosaic custom call in the compiled step",
        )
        require(
            counts["collective_permute"] > 0,
            f"wire {wire}: no collective-permute in the compiled step",
        )
        report["steps"][wire] = {"hlo": counts, **step_report(times, trace)}
    return emit("wire", cache, wire_kernel_path=path, **report)


# -- main ----------------------------------------------------------------------


@contextlib.contextmanager
def named(phase):
    """Names the failing phase on stderr; the exception still ends the
    process with a non-zero exit code."""
    try:
        yield
    except BaseException:
        print(f"chip_smoke: phase {phase!r} FAILED", file=sys.stderr)
        raise


def main():
    if jax.default_backend() != "tpu":
        print(
            "chip_smoke: no TPU: jax.default_backend() is "
            f"{jax.default_backend()!r}; this script only runs on the chip",
            file=sys.stderr,
        )
        return 2
    cache = CacheEvents()
    n = len(jax.devices())
    with named("init"):
        bf.init(nodes_per_machine=2 if n % 2 == 0 else None)
        print(
            json.dumps({
                "phase": "init", **device_report(),
                "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            }),
            flush=True,
        )
    with named("collectives"):
        phase_collectives(FULL["collectives"], cache)
    with named("resnet50"):
        job = ResNetJob(FULL["resnet50"])
        phase_resnet50(job, cache)
    with named("wire"):
        phase_wire(job, cache)
    del job
    # lm last: peak_bytes_in_use is the peak of the process so far, and
    # the LM's is the largest — every line's reading stays its own phase's
    with named("lm"):
        phase_lm(FULL["lm"], cache)
    bf.shutdown()
    dev = jax.devices()[0]
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind, "count": n,
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
