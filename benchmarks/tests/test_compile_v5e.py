"""Off-chip compile of the cells' fused steps for a *described* v5e:2x2
(on-chip-measurement guide, section 2): what the chip's compiler would
refuse, what one step holds in HBM, and which collectives and kernels the
compiled step holds — at no chip time. Minutes, not seconds: marked
``slow`` (``-m 'not slow'`` leaves them out). A compile that passes is not
a chip run.

The topology is described inside a fixture, and everything built from it
in the tests: the TPU's library loads in the one process that runs this
file, never while a module is imported."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec

import bluefog_tpu as bf

from benchmarks.harness import bench, cells, hlo_text, reference

pytestmark = pytest.mark.slow
GIB = 2 ** 30
HBM_GIB = 15.75  # what the v5e's runtime gives a process (bytes_limit, PR 22)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def compile_step(cell, topo):
    """The cell's fused step, compiled for ``cell.chips`` described chips.
    The program builds its step when it is first called, so it is called
    with shapes: the dispatch compiles and then fails for want of a device,
    and the compiled program is lowered again from what it recorded."""
    bf.init(
        devices=topo.devices[:cell.chips],
        nodes_per_machine=cell.traffic["nodes_per_machine"],
    )
    try:
        n = bf.size()
        stacked = NamedSharding(bf.get_context().mesh, PartitionSpec("workers"))
        job = bench.load_job(cell)
        tx = reference.make_tx(cell.config["optimizer"])
        key = jax.random.PRNGKey(0)

        def shapes(fn, *args):
            return jax.tree_util.tree_map(
                lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=stacked),
                jax.eval_shape(fn, *args),
            )

        def stack(tree):
            return jax.tree_util.tree_map(
                lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), tree
            )

        params, aux = shapes(lambda k: stack(job.init(k)), key)
        batch = shapes(lambda k: job.make_batch(k, n), key)
        state = shapes(jax.vmap(tx.init), params)
        operands = ((aux,) if job.has_aux else ()) + tuple(batch)
        opt = bench.build_optimizer(cell.traffic, tx)
        fused = bf.make_train_step(opt, job.loss_fn, has_aux=job.has_aux)
        with pytest.raises(Exception):
            fused(params, state, *operands)
        fn, wops, ef_in, buf_in, accum_in = opt._last_fused
        step_index = jax.ShapeDtypeStruct((1,), jnp.int32)
        return fn.lower(
            params, state, step_index, wops, ef_in, buf_in, accum_in, *operands
        ).compile()
    finally:
        bf.shutdown()


def step_gib(compiled):
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    ) / GIB


@pytest.mark.parametrize("name", [w["name"] for w in cells.load_benchmark()["workloads"]])
def test_cell_compiles_for_the_v5e(topo, name):
    cell = cells.load_cell(name)
    compiled = compile_step(cell, topo)
    hlo = hlo_text.HloIndex(compiled.as_text()).summary()
    held = step_gib(compiled)
    # a quarter of the chip at least (the benchmark's floor), a tenth free
    assert 0.25 * 16e9 / GIB <= held <= 0.9 * HBM_GIB, held
    permutes = hlo["collectives"].get("collective-permute", {"count": 0, "bytes": 0})
    if cell.chips == 1:
        assert not hlo["collectives"]
    else:
        # one-peer Exp2 on four workers: two rounds, each a branch of the
        # switch, each sending the whole f32 payload once, in 19 messages
        # (10 leaves of at least BLUEFOG_BUCKET_BYTES alone, the rest in 9
        # buckets: PR 28)
        assert permutes["count"] == 38
        assert permutes["bytes"] == 2 * 4 * cell.config["n_params"]
    assert hlo["tpu_custom_call"] == bench.load_job(cell).mosaic_calls
    print(name, f"{held:.2f} GiB", hlo["collectives"], hlo["tpu_custom_call"])
