# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Perf probe: where does the ResNet50 step time go on the real chip?

Experiments (select with PROBE=name, comma-separated):

- ``matmul``    — peak-achievable bf16 matmul TFLOP/s (roofline anchor).
- ``dispatch``  — per-call dispatch overhead: time a trivial jitted op.
- ``resnet``    — per-step time of the bench train step at a given batch,
                  both one-call-per-step and K-steps-per-call (lax.fori_loop)
                  to separate device time from host dispatch.
- ``fwd``       — forward-only and forward+backward split.

Writes one JSON line per measurement.
"""

import json
import os
import sys
import time

if __name__ == "__main__":
    # CLI gate BEFORE the jax import: --help must answer in
    # milliseconds (and exit 0), not after a backend initializes.
    # Probe selection is env-driven (PROBE=matmul,dispatch,...).
    import argparse

    argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="configuration: PROBE (comma-separated subset of "
               "matmul,dispatch,resnet,fwd), PROBE_BATCH",
    ).parse_args()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp


def _settle(out):
    """Readback sync (bluefog_tpu.timing.settle), imported lazily so
    the probe stays runnable with only jax+numpy installed."""
    from bluefog_tpu.timing import settle

    return settle(out)


def timed(fn, *args, iters=10, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    _settle(out)
    _settle(out)  # warm the settle gather's own compile cache
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _settle(out)
    t1 = time.perf_counter()
    _settle(out)  # already materialized: pure readback latency
    t_read = time.perf_counter() - t1
    return max((t1 - t0 - t_read), 1e-9) / iters


def emit(**kw):
    print(json.dumps(kw), flush=True)


def matmul_tflops(n: int, iters: int = 10, warmup: int = 3) -> float:
    """Dense bf16 ``n x n`` matmul throughput in TFLOP/s — the fixed
    roofline anchor. ``bench.py`` emits this (8192 on TPU) as the
    ambient-drift anchor line every ``BENCH_MODE`` carries, so
    cross-round headline deltas are classifiable as ambient host drift
    vs real change (``tools/bench_diff.py`` consumes it); the
    attribution doctor times a miniature of the same anchor per sample
    (:mod:`bluefog_tpu.attribution`)."""
    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.ones((n, n), jnp.bfloat16)
    f = jax.jit(lambda a, b: a @ b)
    dt = timed(f, a, b, iters=iters, warmup=warmup)
    return 2 * n ** 3 / dt / 1e12


def probe_matmul():
    for n in (4096, 8192):
        dt_tflops = matmul_tflops(n)
        emit(probe="matmul", n=n,
             ms=round(2 * n**3 / dt_tflops / 1e9, 3),
             tflops=round(dt_tflops, 1))


def probe_dispatch():
    x = jnp.ones((8,), jnp.float32)
    f = jax.jit(lambda x: x + 1)
    dt = timed(f, x, iters=50)
    emit(probe="dispatch", ms=round(dt * 1e3, 3))


def _resnet_setup(batch):
    import optax
    from bluefog_tpu.models import ResNet50

    model = ResNet50(num_classes=1000)
    rng = jax.random.PRNGKey(0)
    sample = jnp.ones((batch, 224, 224, 3), jnp.bfloat16)
    variables = model.init(rng, sample, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)
    rng_np = np.random.RandomState(0)
    images = jnp.asarray(
        rng_np.randn(batch, 224, 224, 3), jnp.bfloat16
    )
    labels = jnp.asarray(rng_np.randint(0, 1000, size=(batch,)), jnp.int32)
    return model, tx, params, batch_stats, opt_state, images, labels


def probe_resnet():
    import optax

    for batch in [int(b) for b in os.environ.get("PROBE_BATCH", "64,128,256").split(",")]:
        model, tx, params, batch_stats, opt_state, images, labels = _resnet_setup(batch)

        def train_step(state, images, labels):
            params, batch_stats, opt_state = state

            def loss_fn(p):
                logits, mutated = model.apply(
                    {"params": p, "batch_stats": batch_stats},
                    images, train=True, mutable=["batch_stats"],
                )
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels).mean()
                return loss, mutated["batch_stats"]

            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, new_stats, opt_state), loss

        state = (params, batch_stats, opt_state)
        one = jax.jit(train_step)
        dt1 = timed(lambda s: one(s, images, labels)[0], state, iters=10)

        # K steps inside one dispatch: isolates host overhead.
        K = 10

        def k_steps(state, images, labels):
            def body(i, s):
                s, _ = train_step(s, images, labels)
                return s
            return jax.lax.fori_loop(0, K, body, state)

        kfn = jax.jit(k_steps)
        dtk = timed(lambda s: kfn(s, images, labels), state, iters=3) / K

        # 2*MAC convention (matches bench.py): fwd ~= 8.2 GFLOP/img
        flops_img = 3 * 8.2e9
        emit(probe="resnet", batch=batch,
             ms_per_step_1call=round(dt1 * 1e3, 2),
             ms_per_step_kloop=round(dtk * 1e3, 2),
             imgs_per_sec_1call=round(batch / dt1, 1),
             imgs_per_sec_kloop=round(batch / dtk, 1),
             mfu_kloop=round(batch * flops_img / dtk / 197e12, 3))


def probe_fwd():
    import optax

    batch = int(os.environ.get("PROBE_BATCH", "64").split(",")[0])
    model, tx, params, batch_stats, opt_state, images, labels = _resnet_setup(batch)

    fwd = jax.jit(lambda p, x: model.apply(
        {"params": p, "batch_stats": batch_stats}, x, train=True,
        mutable=["batch_stats"])[0])
    dt_f = timed(fwd, params, images, iters=10)

    def loss_fn(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    fb = jax.jit(jax.grad(loss_fn))
    dt_fb = timed(fb, params, iters=10)

    # eval-mode (running-stats BN) fwd+bwd: isolates the cost of the
    # batch-statistics reductions in the backward pass
    def loss_eval(p):
        logits = model.apply(
            {"params": p, "batch_stats": batch_stats}, images, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    fbe = jax.jit(jax.grad(loss_eval))
    dt_fbe = timed(fbe, params, iters=10)
    emit(probe="fwd", batch=batch, fwd_ms=round(dt_f * 1e3, 2),
         fwdbwd_ms=round(dt_fb * 1e3, 2),
         fwdbwd_evalbn_ms=round(dt_fbe * 1e3, 2))


def main():
    emit(probe="env", device=str(jax.devices()[0]),
         kind=jax.devices()[0].device_kind, n=len(jax.devices()))
    which = os.environ.get("PROBE", "dispatch,matmul,fwd,resnet").split(",")
    for name in which:
        dict(matmul=probe_matmul, dispatch=probe_dispatch,
             resnet=probe_resnet, fwd=probe_fwd)[name.strip()]()
    return 0


if __name__ == "__main__":
    sys.exit(main())
