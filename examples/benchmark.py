#!/usr/bin/env python
# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Synthetic training-throughput benchmark across distributed optimizers.

TPU-native rendition of reference ``examples/pytorch_benchmark.py``: times
the full decentralized train step (forward + backward + inner update +
gossip, one compiled program) for a chosen model and optimizer family and
prints imgs/sec. The benchmark of record is ``benchmarks/run.py`` (its
cells in ``BENCHMARK.json``, its numbers in ``PERF.md``); this example is
the user-facing knob-twiddling version.
"""

import argparse
import sys
import time

from _common import setup_devices

devices = setup_devices()

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

import bluefog_tpu as bf  # noqa: E402
from bluefog_tpu import topology as tu  # noqa: E402

OPTIMIZERS = {
    "neighbor_allreduce": lambda tx: bf.DistributedNeighborAllreduceOptimizer(tx),
    "allreduce": lambda tx: bf.DistributedAllreduceOptimizer(tx),
    "gradient_allreduce": lambda tx: bf.DistributedGradientAllreduceOptimizer(tx),
    "atc": lambda tx: bf.DistributedAdaptThenCombineOptimizer(tx),
    "win_put": lambda tx: bf.DistributedWinPutOptimizer(tx),
    "push_sum": lambda tx: bf.DistributedPushSumOptimizer(tx),
}
WINDOW_MODES = ("win_put", "push_sum")


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--model", default="mlp",
        choices=["mlp", "resnet18", "resnet34", "resnet50", "resnet101",
                 "resnet152"],
    )
    parser.add_argument(
        "--dist-optimizer", default="neighbor_allreduce",
        choices=sorted(OPTIMIZERS),
    )
    parser.add_argument("--batch-size", type=int, default=16)
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--num-warmup", type=int, default=2)
    parser.add_argument(
        "--dynamic", action="store_true",
        help="use the one-peer dynamic Exp2 schedule (lax.switch lowered)",
    )
    args = parser.parse_args()

    bf.init(devices=devices)
    size = bf.size()

    if args.model.startswith("resnet"):
        from bluefog_tpu import models as model_zoo

        model = getattr(model_zoo, args.model.replace("resnet", "ResNet"))(
            num_classes=1000
        )
        sample = jnp.ones((args.batch_size, 64, 64, 3), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), sample, train=False)
        apply = lambda p, x: model.apply(p, x, train=False)
        classes = 1000
    else:
        from bluefog_tpu.models import MLP

        model = MLP(features=(256, 256, 10))
        sample = jnp.ones((args.batch_size, 128), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), sample)
        apply = model.apply
        classes = 10

    params = jax.tree_util.tree_map(
        lambda t: bf.worker_values(np.asarray(t)), variables
    )
    window_mode = args.dist_optimizer in WINDOW_MODES
    opt = OPTIMIZERS[args.dist_optimizer](optax.sgd(0.01, momentum=0.9))
    if args.dynamic:
        if window_mode:
            parser.error("--dynamic applies to the gossip optimizers only")
        from bluefog_tpu.collective.plan import schedule_from_dynamic

        topo = tu.ExponentialTwoGraph(size)
        opt.schedule = schedule_from_dynamic(
            size, lambda r: tu.GetDynamicOnePeerSendRecvRanks(topo, r)
        )
    state = opt.init(params)

    rng = np.random.RandomState(0)
    x = jnp.asarray(
        rng.randn(size, args.batch_size, *sample.shape[1:]).astype(np.float32)
    )
    y = jnp.asarray(rng.randint(0, classes, (size, args.batch_size)))

    def worker_loss(p, xb, yb):
        logits = apply(p, xb)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, yb
        ).mean()

    grad_fn = jax.jit(jax.vmap(jax.grad(worker_loss)))

    if window_mode:
        # window optimizers own the iterate: gradients are evaluated at
        # the current window estimate; step(state, grads)
        def one_step(params, state):
            grads = grad_fn(params, x, y)
            return opt.step(state, grads)

    else:
        def one_step(params, state):
            grads = grad_fn(params, x, y)
            return opt.step(params, state, grads)

    for _ in range(args.num_warmup):
        params, state = one_step(params, state)
    jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        params, state = one_step(params, state)
    jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
    dt = time.perf_counter() - t0

    total = size * args.batch_size * args.num_iters
    print(
        f"[{args.model} / {args.dist_optimizer}"
        f"{' / dynamic' if args.dynamic else ''}] "
        f"{total / dt:.1f} imgs/sec total "
        f"({total / dt / size:.1f} per worker, {size} workers)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
