#!/usr/bin/env python
# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Per-stage ResNet50 train-step breakdown on the real chip.

Answers the VERDICT r04 question "is ~35% MFU the default-flags ceiling?"
with measurements: compiles fwd+bwd through PREFIXES of the network
(stem, stem+stage1, ..., full) in ONE process, times each with
differenced windows (settle-cost-free), and reports the incremental time,
FLOPs (XLA cost analysis), and per-stage MFU. The early high-resolution
stages run far below peak on the MXU (small channel counts / 7x7 stem —
a systolic array wants deep contractions), which is what caps the whole
model; the late stages run near the achievable peak, showing the gap is
structural to ResNet50 rather than left on the table by the step program.

Prints one JSON line per stage plus a markdown table for
docs/performance.md.
"""

import json

if __name__ == "__main__":
    # CLI gate BEFORE the jax import: --help must answer in
    # milliseconds (and exit 0), not after a backend initializes.
    import argparse

    argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="configuration: PROFILE_STEPS, PROFILE_WINDOWS",
    ).parse_args()

import numpy as np
import jax
import jax.numpy as jnp
import optax

from bluefog_tpu.models.resnet import ResNet, BottleneckBlock
from bluefog_tpu.timing import timed_differenced

BATCH = 64
IMAGE = 224
# windows must be compute-dominated so the settle readback's jitter
# stays small against them: 40 steps of even the ~2 ms stem prefix stays measurable
STEPS = int(__import__("os").environ.get("PROFILE_STEPS", "40"))
WINDOWS = int(__import__("os").environ.get("PROFILE_WINDOWS", "5"))

PREFIXES = [
    ("stem", []),
    ("stage1 (56x56, 256ch)", [3]),
    ("stage2 (28x28, 512ch)", [3, 4]),
    ("stage3 (14x14, 1024ch)", [3, 4, 6]),
    ("stage4 (7x7, 2048ch) = full", [3, 4, 6, 3]),
]

_PEAK = 197e12  # v5e dense bf16


def timed(fn, state0, x, steps=STEPS, windows=WINDOWS):
    carry = [state0]

    def _step():
        carry[0] = fn(carry[0], x)
        return carry[0][-1]  # the scalar loss

    return timed_differenced(_step, steps, windows)[0]


def main():
    x = jnp.asarray(
        np.random.RandomState(0).randn(BATCH, IMAGE, IMAGE, 3), jnp.bfloat16
    )
    rows = []
    prev_t, prev_f = 0.0, 0.0
    for name, stages in PREFIXES:
        model = ResNet(
            stage_sizes=stages or [1],
            block_cls=BottleneckBlock,
            num_classes=1000,
        )
        if not stages:
            # stem only: cut the ResNet before the residual stages by
            # reusing stage_sizes=[] semantics via a tiny wrapper
            import flax.linen as nn
            import functools

            class Stem(nn.Module):
                @nn.compact
                def __call__(self, x, train=True):
                    conv = functools.partial(
                        nn.Conv, use_bias=False, dtype=jnp.bfloat16,
                        padding="SAME",
                    )
                    norm = functools.partial(
                        nn.BatchNorm, use_running_average=not train,
                        momentum=0.9, epsilon=1e-5, dtype=jnp.bfloat16,
                    )
                    x = x.astype(jnp.bfloat16)
                    x = conv(64, (7, 7), (2, 2), name="conv_init")(x)
                    x = norm(name="bn_init")(x)
                    x = nn.relu(x)
                    x = nn.max_pool(x, (3, 3), strides=(2, 2),
                                    padding="SAME")
                    return jnp.mean(x, axis=(1, 2)).astype(jnp.float32)

                # noqa: the head is a mean so the fwd+bwd has a scalar loss

            model = Stem()
        variables = model.init(jax.random.PRNGKey(0), x, train=True)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        tx = optax.sgd(0.1, momentum=0.9)
        opt_state = tx.init(params)

        # a REAL carried train step: params/opt_state flow through so the
        # backward pass and optimizer update are live (a loss-only return
        # would let XLA dead-code the entire backward)
        def step(state, x):
            params, batch_stats, opt_state = state

            def loss_fn(p):
                out = model.apply(
                    {"params": p, "batch_stats": batch_stats}, x,
                    train=True,
                    mutable=["batch_stats"] if batch_stats else [],
                )
                logits, mutated = out if batch_stats else (out, {})
                return (
                    jnp.mean(logits.astype(jnp.float32) ** 2),
                    mutated.get("batch_stats", batch_stats),
                )

            (loss, new_bs), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return (new_params, new_bs, new_opt, loss)

        fn = jax.jit(lambda s, x: step(s[:3], x))
        state0 = (params, batch_stats, opt_state, jnp.float32(0))
        compiled = fn.lower(state0, x).compile()
        flops = float(compiled.cost_analysis().get("flops", 0.0))
        dt = timed(fn, state0, x)
        inc_t, inc_f = dt - prev_t, flops - prev_f
        rows.append({
            "metric": "resnet50_stage_profile",
            "prefix": name,
            "cum_ms": round(dt * 1e3, 2),
            "inc_ms": round(inc_t * 1e3, 2),
            "inc_gflops": round(inc_f / 1e9, 1),
            "inc_mfu": round(inc_f / max(inc_t, 1e-9) / _PEAK, 4),
        })
        print(json.dumps(rows[-1]), flush=True)
        prev_t, prev_f = dt, flops
    print("\n| prefix | cumulative ms | stage ms | stage GFLOP | stage MFU |")
    print("|---|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['prefix']} | {r['cum_ms']} | {r['inc_ms']} | "
            f"{r['inc_gflops']} | {r['inc_mfu']*100:.1f}% |"
        )


if __name__ == "__main__":
    main()
