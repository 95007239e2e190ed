# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Real multi-controller bring-up: two OS processes, one global mesh.

The reference launcher exists to start N communicating processes
(``run/run.py:180-203``); the TPU analogue is ``jax.distributed.initialize``
joined from each controller (``context.maybe_init_distributed``). The
mocked launcher test (test_launcher.py) checks only the argument contract —
THIS test actually spawns two controller processes over the env contract
the launcher emits (BLUEFOG_COORDINATOR/NUM_PROCESSES/PROCESS_ID), forms a
4-device global mesh (2 local CPU devices per process, Gloo collectives),
runs a decentralized neighbor_allreduce training loop to consensus, and
exits cleanly.
"""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """
import os, sys
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=2"
).strip()
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import optax
import bluefog_tpu as bf
from jax.sharding import NamedSharding, PartitionSpec as P

bf.init()  # BLUEFOG_COORDINATOR env => jax.distributed.initialize runs HERE
assert jax.process_count() == 2, jax.process_count()
ctx = bf.get_context()
assert bf.size() == 4, bf.size()
# one "machine" per controller process by default
assert ctx.machine_size == 2 and ctx.local_size == 2, (
    ctx.machine_size, ctx.local_size)

SIZE, DIM = 4, 3
c = np.random.RandomState(0).randn(SIZE, DIM).astype(np.float32)
opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.4))
params = {"w": jnp.asarray(c)}  # same value on both controllers
state = opt.init(params)

grad_fn = jax.jit(lambda w, tgt: w - tgt)
mesh = ctx.mesh
loss_fn = jax.jit(
    lambda w, m: 0.5 * jnp.mean(jnp.sum((w - m) ** 2, -1)),
    out_shardings=NamedSharding(mesh, P()),
)
start = float(np.asarray(loss_fn(params["w"], c.mean(0))))
for _ in range(50):
    grads = {"w": grad_fn(params["w"], c)}
    params, state = opt.step(params, state, grads)
    jax.block_until_ready(params["w"])  # CPU Gloo rendezvous: don't queue deep
final = float(np.asarray(loss_fn(params["w"], c.mean(0))))
# CTA gossip with a constant step size keeps a steady-state consensus
# residual; 5x loss reduction proves communication is really averaging
# across the two OS processes (local-only SGD would stay at `start`).
assert final < 0.2 * start, (start, final)

# hierarchical across REAL machine boundaries: machine = controller
# process, intra-machine psum on each host's devices, machine-level
# gossip across the process boundary
import bluefog_tpu.topology as tu
bf.set_machine_topology(tu.RingGraph(2))
hopt = bf.DistributedHierarchicalNeighborAllreduceOptimizer(optax.sgd(0.4))
hparams = {"w": jnp.asarray(c)}
hstate = hopt.init(hparams)
for _ in range(40):
    hgrads = {"w": grad_fn(hparams["w"], c)}
    hparams, hstate = hopt.step(hparams, hstate, hgrads)
    jax.block_until_ready(hparams["w"])
hfinal = float(np.asarray(loss_fn(hparams["w"], c.mean(0))))
assert hfinal < 0.2 * start, (start, hfinal)

# window family across REAL controller processes: push-sum diffusion on a
# directed ring over the global mesh. The window's value/buffer/p lanes
# are worker-stacked arrays sharded across devices owned by BOTH
# processes, so every buffered ppermute exchange crosses the process
# boundary — the one surface the gossip legs above don't touch.
bf.set_topology(tu.RingGraph(SIZE, connect_style=1), is_weighted=True)
wopt = bf.DistributedPushSumOptimizer(
    optax.sgd(optax.exponential_decay(0.4, 20, 0.5))
)
wparams = {"w": jnp.asarray(c)}
wstate = wopt.init(wparams)
cur = wparams
for _ in range(60):
    cur, wstate = wopt.step(wstate, {"w": grad_fn(cur["w"], c)})
    jax.block_until_ready(cur["w"])
wfinal = float(np.asarray(loss_fn(cur["w"], c.mean(0))))
assert wfinal < 0.2 * start, (start, wfinal)
wopt.free()
bf.turn_off_win_ops_with_associated_p()

bf.shutdown()
print("MP_OK", jax.process_index(), start, final, hfinal, wfinal, flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


@pytest.mark.example
def test_two_controller_processes_end_to_end(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    port = _free_port()
    base = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BLUEFOG_NUM_WORKERS")
    }
    base["PYTHONPATH"] = REPO + os.pathsep + base.get("PYTHONPATH", "")
    procs = []
    for pid in range(2):
        env = dict(
            base,
            BLUEFOG_COORDINATOR=f"localhost:{port}",
            BLUEFOG_NUM_PROCESSES="2",
            BLUEFOG_PROCESS_ID=str(pid),
            BLUEFOG_NUM_WORKERS="4",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                env=env,
                cwd=str(tmp_path),
            )
        )
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    if any(
        "Multiprocess computations aren't implemented on the CPU backend"
        in err
        for _rc, _out, err in outs
    ):
        # Known environment gap, not a framework regression: this jaxlib
        # build ships no cross-process CPU collective backend (Gloo), so
        # the two-controller global mesh cannot execute any computation.
        # The launcher/env-contract surface is still covered by
        # test_launcher.py; this end-to-end tier needs a jaxlib with CPU
        # collectives (or a real multi-host slice). Tracked in
        # CHANGES.md (PR 3 triage note).
        pytest.skip(
            "jaxlib lacks multiprocess CPU collectives "
            "(XlaRuntimeError: 'Multiprocess computations aren't "
            "implemented on the CPU backend') — environment gap, see "
            "PR 3 triage note in CHANGES.md"
        )
    for rc, out, err in outs:
        assert rc == 0, err[-3000:]
        assert "MP_OK" in out, (out, err[-2000:])
    # Both controllers converged to the same consensus losses (gossip,
    # hierarchical, AND push-sum window legs — the last three tokens).
    finals = {
        tuple(o.split()[-3:])
        for _rc, o, _e in outs
        for o in [o.strip().splitlines()[-1]]
    }
    assert len(finals) == 1, outs
