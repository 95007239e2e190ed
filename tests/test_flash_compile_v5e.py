"""The attention kernels' ``value_and_grad`` at the benchmark's two decoder
shapes, compiled for a *described* TPU v5e (no chip attached): what the
Pallas interpreter cannot show about a grid that walks the scalar-prefetched
list of the live tiles (``ops/flash.py``, ``_grid``) — that Mosaic lowers
index maps which read a table beside ``b // group`` and the flattened dK/dV
list, that the three kernels keep the names the benchmark's readers divide
by, and that handing the kernels their tables makes XLA copy no tensor it
did not copy before.

Marked ``slow`` (``-m 'not slow'`` leaves it out; ``python -m pytest
tests/test_flash_compile_v5e.py -m slow``, 15 s): the TPU compiler takes
every core, and the suite's timing tests run beside it in another worker.

The topology is described inside a fixture: the TPU's library loads in the
one process that runs this file, never while a module is imported. A compile
that passes is not a chip run."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bluefog_tpu.ops import flash
from bluefog_tpu.ops.flash import BlockDiffusionMask, SlidingWindowMask

pytestmark = pytest.mark.slow

# mask kind, batch, positions, query heads, key-value heads, live tiles, the
# length of the sub-tile runs' table, the `copy` / `copy-start` instructions
# of the tables into scalar memory, and those of a tensor (f16631d: the
# layouts into and out of the folded [batch x heads, positions, 128],
# operands moved between memory spaces)
SHAPES = {
    "sdar30b_1chip_b2": (BlockDiffusionMask(4096, 4), 2, 8192, 32, 4, 24, 8, 9, 7),
    "mistral4_1chip_b1": (True, 1, 4096, 32, 32, 10, 5, 9, 10),
    # the window layers: 70 of 256 tiles, 7 query heads a key-value head
    "smallthinker_1chip_seq16k": (SlidingWindowMask(4096), 1, 16384, 28, 4, 70, 9, 0, 6),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell", SHAPES)
def test_the_live_tile_grids_compile_for_the_v5e(one_chip, cell):
    kind, b, t, h, hkv, live, subs, table_copies, tensor_copies = SHAPES[cell]
    assert flash.grid_steps(t, kind) == live  # the list, not the rectangle

    def loss(q, k, v):
        # the kernels' branch itself: `flash_attention` asks the platform,
        # and the process's platform is the CPU
        out = flash._flash(q, k, v, kind, 128 ** -0.5, None, None, False)
        return out.astype(jnp.float32).sum()

    spec = lambda heads: jax.ShapeDtypeStruct(
        (b, t, heads, 128), jnp.bfloat16, sharding=one_chip
    )
    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable off the chip
    try:
        text = grad.lower(spec(h), spec(hkv), spec(hkv)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    calls = re.findall(r"%(bf_flash_[a-z]+)[.\d]* = .* custom-call\(.*tpu_custom_call", text)
    assert sorted(calls) == ["bf_flash_dkv", "bf_flash_dq", "bf_flash_fwd"]
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # each kernel takes its four tables, as long as its grid, and the runs
    # of sub-tiles they point into
    tables = re.findall(
        r"operand_layout_constraints=\{s32\[(\d+)\]\{0\}, s32\[\1\]\{0\}, "
        r"s32\[\1\]\{0\}, s32\[\1\]\{0\}, s32\[(\d+)\]\{0\}, bf16", text
    )
    assert sorted(int(n) for n, _ in tables) == [live, live, h // hkv * live]
    assert {int(m) for _, m in tables} == {subs}
    # the tables are copied into scalar memory (the forward and the dQ
    # kernel read one set, the dK/dV kernel its own four beside the runs
    # all three share; at the window's shape XLA copies none); no tensor
    # is, that was not
    copies = re.findall(r"= \(?(\w+)\[[\d,]+\].* copy(?:-start)?\(", text)
    assert copies.count("s32") == table_copies, copies
    assert copies.count("bf16") == tensor_copies, copies
    assert len(copies) == table_copies + tensor_copies, copies
