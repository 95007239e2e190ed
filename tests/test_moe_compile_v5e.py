"""The expert layer's gradient at the benchmark cell's size, compiled for a
*described* TPU v5e (no chip attached): what the CPU cannot show about the
passes over the row buffer — that XLA keeps them in place. A loop that reads
and writes its carried buffer in two kernels of a turn compiles to a copy of
the whole buffer every turn, and passes every test on the CPU.

Marked ``slow`` (``-m 'not slow'`` leaves it out; ``python -m pytest
tests/test_moe_compile_v5e.py -m slow``, 30 s): the TPU compiler takes every
core for a quarter of a minute, and the suite's timing tests
(``test_doctor.py``) run beside it in another worker.

The topology is described inside a fixture: the TPU's library loads in the
one process that runs this file, never while a module is imported. A compile
that passes is not a chip run."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bluefog_tpu.ops import moe

pytestmark = pytest.mark.slow
TOKENS, HIDDEN, WIDTH, HELD, K = 16384, 2048, 768, 16, 8  # sdar30b_1chip_b2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def loss(u, top, chosen, gate, up, down):
    y, _ = moe.expert_layer(u, top, chosen, gate, up, down, dtype=jnp.bfloat16)
    return y.astype(jnp.float32).sum()


def compile_for(one_chip, fn):
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (
        spec((TOKENS, HIDDEN), jnp.bfloat16), spec((TOKENS, K), jnp.float32),
        spec((TOKENS, K), jnp.int32), spec((HELD, HIDDEN, WIDTH), jnp.float32),
        spec((HELD, HIDDEN, WIDTH), jnp.float32), spec((HELD, WIDTH, HIDDEN), jnp.float32),
    )
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable off the chip
    try:
        return jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


@pytest.fixture(scope="module")
def compiled(one_chip):
    return compile_for(one_chip, jax.grad(loss, argnums=(0, 1, 3, 4, 5)))


@pytest.fixture(scope="module")
def compiled_with_value(one_chip):
    # the loss as well, as a training step has it: the combine's forward
    # pass, which a gradient alone leaves out
    return compile_for(one_chip, jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5)))


def test_the_passes_over_the_buffer_stay_loops_and_stay_in_place(compiled):
    tm = moe.row_tile(TOKENS * K, HELD, HIDDEN, WIDTH, jnp.bfloat16)
    rows = moe.buffer_tiles(TOKENS * K, HELD, tm) * tm
    assert (tm, rows) == (512, 139264)
    text = compiled.as_text()
    # the fill, the activation, the combine's two backward passes, the
    # activation's derivative, the sum of the buffer's two cotangents, the
    # way back to token space of the dispatch's backward pass (the
    # compiler makes loops of its own of the gathers of a tile's pairs)
    assert len(re.findall(r' while\(.*op_name="[^"]*/while"', text)) == 7
    assert text.count("tpu_custom_call") == 9
    # nowhere — in no loop body either — a copy of a whole row buffer
    copies = re.findall(rf"= \w+\[{rows},\d+\]\S* copy\(", text)
    assert not copies, copies
    # and beside its operands and results the layer holds under five row
    # buffers' worth (2.75 GB; 3.11 with the passes over the whole buffer
    # and the float32 copy of the output's cotangent they made)
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 5 * rows * HIDDEN * 2, stats
    # and its code is no longer than it was without the loops (13.55 MB; 13.44
    # now): six layers of it, twice each, go into a step whose executable
    # the harness's compile cache has to hold (PERF.md, PR 35: a tree that
    # stood every loop's first turn before it did not fit)
    assert stats.generated_code_size_in_bytes < 13.8e6, stats


def test_the_way_back_to_token_space_adds_in_place(compiled_with_value):
    """The combine and the dispatch's backward pass go back to token space
    in a loop over the slots past the first, a turn for each up to the most
    choices any token landed: where tokens land two choices the loop runs.
    The trip count is read on the device, so this one program serves every
    routing, and it must add each turn's gather into the carried float32
    total in place — a turn that reads the total in one kernel and writes
    it in another gets a copy of all ``[tokens, hidden]`` of it."""
    text = compiled_with_value.as_text()
    total = rf"f32\[{TOKENS},{HIDDEN}\]"
    loops = dict(
        re.findall(rf" = \(s32\[\]\S*, {total}\S*, .* body=%(\S+), metadata=\{{op_name=\"([^\"]*)\"", text)
    )
    assert sorted(loops.values()) == [
        "jit(loss)/jvp(bf.moe.combine)/while",
        "jit(loss)/transpose(jvp(bf.moe.route))/while",
    ], loops
    copies = re.findall(rf"= {total}\S* copy\(", text)
    assert not copies, copies
    # a turn writes the total once: the select and the sum, one kernel
    for body in loops:
        lines = text.split(f"\n%{body} ", 1)[1].split("\n}", 1)[0]
        writes = re.findall(rf"= {total}\S* (\w[\w-]*)\(", lines)
        assert sorted(writes) == ["fusion", "get-tuple-element"], (body, writes)
