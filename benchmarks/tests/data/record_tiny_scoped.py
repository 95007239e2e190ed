"""Records ``tiny_scoped.xplane.pb`` and ``tiny_scoped.hlo.txt``, the fixture
of ``test_scope_readers.py``: two blocks of three fused steps of a one-layer
toy LM (flash kernels) through ``bf.make_train_step`` on one v5e chip, traced
the way the harness traces a cell, with the compiled step's text.

    chiprun --chips 1 -- python3 benchmarks/tests/data/record_tiny_scoped.py

writes both under ``chiprun_out/``; copy them here. The trace is kept as the
profiler wrote it. The text loses what no reader looks at and what would make
it large: each Mosaic call's serialized kernel and the stack-frame tables
with their ``stack_frame_id``s (``stripped``). On a backend that is no TPU
the script stops before it records anything."""

import glob
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
STEPS_PER_BLOCK, BLOCKS = 3, 2

_FRAME_TABLES_RE = re.compile(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", re.S)
_FRAME_ID_RE = re.compile(r" stack_frame_id=\d+")
_MOSAIC_CONFIG_RE = re.compile(
    r'(custom_call_target="tpu_custom_call".*?backend_config=)\{.*\}$', re.M
)


def stripped(hlo):
    hlo = _FRAME_TABLES_RE.sub("\n", hlo, count=1)
    hlo = _FRAME_ID_RE.sub("", hlo)
    return _MOSAIC_CONFIG_RE.sub(
        r'\1{"stripped":"the Mosaic payload, PR 24"}', hlo
    )


def main(out):
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import models

    if jax.default_backend() != "tpu":
        sys.exit(f"no TPU: the backend is {jax.default_backend()}")
    bf.init()
    n = bf.size()
    model = models.TransformerLM(
        vocab=512, dim=128, heads=2, layers=1, max_len=256, dtype=jnp.bfloat16
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (n, 2, 256), 0, 512, jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[0])["params"]
    params = jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), params
    )

    def loss_fn(p, t):
        logits = model.apply({"params": p}, t)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], t[:, 1:]
        ).mean()

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.01, momentum=0.9))
    step = bf.make_train_step(opt, loss_fn)
    state = opt.init(params)
    for _ in range(3):
        params, state, loss = step(params, state, tokens)
        jax.block_until_ready(loss)
    trace_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(BLOCKS):
            for _ in range(STEPS_PER_BLOCK):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    params, state, loss = step(params, state, tokens)
            with jax.profiler.TraceAnnotation("bench.sync"):
                jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    (recorded,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    shutil.copy(recorded, os.path.join(out, "tiny_scoped.xplane.pb"))
    shutil.rmtree(trace_dir)
    with open(os.path.join(out, "tiny_scoped.hlo.txt"), "w") as f:
        f.write(stripped(opt.lower_last_fused_hlo(params, state, tokens)))
    print("recorded", os.path.getsize(os.path.join(out, "tiny_scoped.xplane.pb")), float(loss[0]))
    bf.shutdown()


if __name__ == "__main__":
    main(os.path.join(ROOT, "chiprun_out"))
