"""One linear-attention layer's scan at the ``solar2_1chip_seq8k`` cell's
shape, its loss and all five gradients, compiled for a *described* TPU v5e
(no chip attached): what the CPU cannot show about the two kernels — that
Mosaic takes the in-chunk work they now do (the cumulative decay, the pair
products, the triangular solve, and in the backward kernel their
derivatives) inside the scoped VMEM limit, and that none of that work is
left to XLA in either pass.

Marked ``slow`` (``-m 'not slow'`` leaves it out; ``python -m pytest
tests/test_delta_compile_v5e.py -m slow``): the TPU compiler takes every
core for a while, and the suite's timing tests (``test_doctor.py``) run
beside it in another worker.

The topology is described inside a fixture: the TPU's library loads in the
one process that runs this file, never while a module is imported. A compile
that passes is not a chip run."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bluefog_tpu.ops import delta

pytestmark = pytest.mark.slow
BATCH, TOKENS, HEADS, DIM = 1, 8192, 16, 128  # solar2_1chip_seq8k: 16 of 64 heads held


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    qkg = spec(BATCH, TOKENS, HEADS, DIM)
    args = (qkg, qkg, qkg, qkg, spec(BATCH, TOKENS, HEADS))
    loss = lambda *a: jnp.sum(delta.chunk_gated_delta(*a))
    fn = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable off the chip
    try:
        return jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_the_forward_pass_leaves_the_in_chunk_work_to_the_kernel(compiled):
    """And the backward pass to ``bf_delta_bwd``, which takes the in-chunk
    derivative too."""
    text = compiled.as_text()
    # one forward kernel and one backward kernel, within the scoped VMEM
    # limit (Mosaic refuses the compile otherwise)
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\".*?op_name=\"([^\"]*)\"", text)
    assert sorted(name.rsplit("/", 2)[-2] for name in calls) == ["bf_delta_bwd", "bf_delta_fwd"]
    # neither pass (the backward's names hold a transpose) has a triangular
    # solve or a cumulative sum outside the kernels: the forward's are in
    # bf_delta_fwd, the backward's and their derivatives in bf_delta_bwd
    forward, backward = [], []
    for name in re.findall(r'op_name="([^"]*)"', text):
        (backward if "transpose(" in name else forward).append(name)
    assert forward and backward
    for word in ("triangular_solve", "cumsum"):
        assert not [n for n in forward if word in n], word
        assert not [n for n in backward if word in n], word
