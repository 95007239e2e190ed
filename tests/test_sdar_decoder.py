"""The config-driven decoder stack (``models/decoder.py``) at toy size:
its loss and gradients through ``bf.make_train_step`` against the ``sdar``
job's plain float32 ``jax.numpy`` reference, a bfloat16 router failing the
same comparison, the device counts, the scopes and the host gauges."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks", "tests")]

import bluefog_tpu as bf  # noqa: E402
from bluefog_tpu import metrics, models  # noqa: E402
from bluefog_tpu.ops import flash  # noqa: E402
from benchmarks.harness import bench, cells, sdar_costs  # noqa: E402

import toy  # noqa: E402

SRC = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 32, "max_position_embeddings": 256,
    "mlp_only_layers": [], "moe_intermediate_size": 24, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 1, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 64,
}
TOTAL, START, SEQ, BATCH, LR = 8, 2, 24, 2, 0.1


def config(**own):
    return {
        "source": "test", "job": "sdar", "unit": "tok", **SRC,
        "source_keys": list(SRC),
        "model": {
            "experts_total": TOTAL, "experts_start": START, "block_length": 4,
            "noise_level_min": 0.001, "qk_norm": True, "compute_dtype": "float32",
            "param_dtype": "float32", "head_dtype": "float32",
            "router_dtype": "float32", "remat": True, "initializer_range": 0.3,
            "router_init": "normal",
            "mosaic_calls_per_layer": 0, **own,
        },
        "n_params": sdar_costs.param_count(SRC, TOTAL),
        "optimizer": {"name": "sgd", "learning_rate": LR, "momentum": 0.9},
        "flops": {
            "matmul_params_per_token": sdar_costs.matmul_params_per_token(SRC, TOTAL),
            "formula": "none",
        },
        "tolerance": toy.TOLERANCE, "reduced": [], "assumed": [],
    }


def job_of(cfg):
    cells.check_config("toy", cfg)
    module = bench._load_module("benchmarks.jobs.sdar", cells.job_path("sdar"))
    return module.Job(cfg, toy.traffic(seq=SEQ, batch_per_worker=BATCH))


@pytest.fixture
def one_worker(cpu_devices):
    bf.init(devices=cpu_devices[:1])
    yield
    bf.shutdown()


def one_step(job, key=0):
    """One fused step of the program on one worker and the plain
    reference's loss and gradients at the same weights and batch ->
    (program loss, reference loss, relative error of the update, program
    counts, reference counts)."""
    k_w, k_b = jax.random.split(jax.random.PRNGKey(key))
    stack = lambda tree: jax.tree_util.tree_map(lambda t: t[None], tree)
    params, counts = job.init(k_w)
    batch = job.make_batch(k_b, 1)
    (ref_loss, ref_counts), grads = jax.value_and_grad(
        job.reference_loss_fn, has_aux=True
    )(params, counts, *(t[0] for t in batch))
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(LR))
    step = bf.make_train_step(opt, job.loss_fn, has_aux=True)
    p0 = stack(params)
    p1, _, (loss, got_counts) = step(
        jax.tree_util.tree_map(jnp.copy, p0), opt.init(p0), stack(counts), *batch
    )
    sq_diff = sq_ref = 0.0
    for a, z, g in zip(*map(jax.tree_util.tree_leaves, (p1, p0, grads))):
        update, want = np.asarray(a[0] - z[0], np.float64), -LR * np.asarray(g, np.float64)
        sq_diff += ((update - want) ** 2).sum()
        sq_ref += (want ** 2).sum()
    unstack = lambda tree: jax.tree_util.tree_map(lambda t: np.asarray(t[0]), tree)
    return (
        float(loss[0]), float(ref_loss), float(np.sqrt(sq_diff / sq_ref)),
        unstack(got_counts), jax.tree_util.tree_map(np.asarray, ref_counts),
    )


def test_the_step_agrees_with_the_plain_reference(one_worker):
    loss, ref_loss, err, counts, ref_counts = one_step(job_of(config()))
    assert abs(loss - ref_loss) < 1e-5 * ref_loss
    assert err < 1e-4, err
    positions = BATCH * 2 * SEQ
    for name in ("rows_per_expert", "rows_absent", "rows_dropped"):
        assert (counts[name] == ref_counts[name]).all(), name
    assert counts["rows_per_expert"].shape == (1, SRC["num_experts"])
    landed = counts["rows_per_expert"].sum(axis=1)
    assert (landed + counts["rows_absent"] == positions * 2).all()
    assert not counts["rows_dropped"].any() and landed.all()


def test_a_bfloat16_router_fails_the_same_comparison(one_worker):
    """The control in the precision below: only the router's product drops
    to bfloat16, and the update is several times further from the
    reference than the limit the float32 program is held to above."""
    _, _, err, _, _ = one_step(job_of(config(router_dtype="bfloat16")))
    assert err > 5e-4, err


def test_without_the_per_head_norms_the_reference_follows(one_worker):
    loss, ref_loss, err, _, _ = one_step(job_of(config(qk_norm=False)), key=1)
    assert abs(loss - ref_loss) < 1e-5 * ref_loss and err < 1e-4


def test_a_whole_toy_cell_is_correct_on_two_workers():
    result, info = toy.rehearse(
        config(), toy.traffic(seq=SEQ, batch_per_worker=BATCH, topology="ring"), 2
    )
    assert result["correct"], info["reference"]
    assert max(info["reference"]["update_l2_err"]) < 1e-4
    assert info["n_params"] == sdar_costs.param_count(SRC, TOTAL)


def test_the_parameter_tree_is_what_the_counts_say():
    job = job_of(config())
    params, _ = jax.eval_shape(job.init, jax.random.PRNGKey(0))
    sizes = {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }
    assert sizes["layer_0/experts/w_gate"] == (4, 32, 24)
    assert sizes["layer_0/experts/w_down"] == (4, 24, 32)
    assert sizes["layer_0/experts/router"] == (32, TOTAL)  # the full width
    assert sizes["layer_0/attn/q_proj/kernel"] == (32, 4 * 16)
    assert sizes["layer_0/attn/k_proj/kernel"] == (32, 2 * 16)
    assert sizes["layer_0/attn/q_norm/scale"] == (16,)
    assert sizes["lm_head/kernel"] == (32, 64) and sizes["embed/embedding"] == (64, 32)
    total = sum(int(np.prod(s)) for s in sizes.values())
    assert total == sdar_costs.param_count(SRC, TOTAL)


def test_the_cells_counts_are_the_issues():
    cell = cells.load_cell("sdar30b_1chip_b2")
    src = cells.source_entry(cell.config)
    assert sdar_costs.param_count(src, 128) == cell.config["n_params"] == 645623296
    assert sdar_costs.allowed_pairs(4096, 4) == 4096 ** 2 + 4096 * 4
    per_token = sdar_costs.flops_per_token(src, 128, 4096, 4)
    assert per_token == 6 * 325156864 + 12 * 4100 * 128 * 32 * 6
    assert abs(per_token - 3.16e9) < 1e7


@pytest.mark.parametrize("name, masked, steps, live, total, sub_live, sub_masked", [
    # 24 of 64 tiles x 64 x 6; 12 whole (4 sub-tiles of 512 each), 8 with
    # 3 live sub-tiles of which 2 partial, 4 with 2 partial
    ("sdar30b_1chip_b2", True, 9216, 9216, 24576, 80 * 384, 24 * 384),
    # 10 of 16 tiles x 32 x 4: 6 whole, 4 diagonal ones with 3 live and 2 partial
    ("mistral4_1chip_b1", True, 1280, 1280, 2048, 36 * 128, 8 * 128),
    ("sdar30b_1chip_b2", False, 24576, 24576, 24576, 64 * 4 * 384, 0),
])
def test_the_attention_gauges_of_the_benchmarks_decoder_cells(
    name, masked, steps, live, total, sub_live, sub_masked
):
    """``bluefog.attn.grid_steps`` is read from the grid the forward kernel
    is given: the live tiles under either cell's mask kind, the whole
    rectangle for an unmasked call. ``subtiles_live`` / ``_masked`` count
    the sub-tiles of 512 computed and masked."""
    from bluefog_tpu.models import decoder
    from bluefog_tpu.ops.flash import BlockDiffusionMask

    job = bench.load_job(cells.load_cell(name))
    if name.startswith("sdar"):
        positions, mask = 2 * job.seq, BlockDiffusionMask(job.seq, job.block)
    else:
        positions, mask = job.seq, "causal"
    decoder._record_static_counts(job.cfg, job.batch, positions, mask if masked else None)
    peek = lambda gauge: metrics.peek(f"bluefog.attn.{gauge}").value
    assert (peek("grid_steps"), peek("tiles_live"), peek("tiles_total")) == (steps, live, total)
    assert (peek("subtiles_live"), peek("subtiles_masked")) == (sub_live, sub_masked)


@pytest.mark.parametrize("change", [
    {"attention_bias": True}, {"tie_word_embeddings": True}, {"hidden_act": "gelu"},
    {"decoder_sparse_step": 2}, {"mlp_only_layers": [0]}, {"use_sliding_window": True},
    {"rope_scaling": {"type": "yarn"}},
])
def test_settings_the_stack_cannot_honour_are_refused(change):
    with pytest.raises(ValueError, match=next(iter(change))):
        models.DecoderConfig.from_source({**SRC, **change}, experts_total=TOTAL)


@pytest.mark.parametrize("change, message", [
    ({"num_key_value_heads": 3}, "multiple of kv heads"),
    ({"head_dim": 15}, "even head_dim"),
    ({"num_experts": 8}, "held experts"),
])
def test_sizes_that_do_not_fit_are_refused(change, message):
    with pytest.raises(ValueError, match=message):
        models.DecoderConfig.from_source(
            {**SRC, **change}, experts_total=TOTAL, experts_start=START
        )


def test_scopes_and_gauges_of_one_traced_loss():
    job = job_of(config())
    params, counts = job.init(jax.random.PRNGKey(0))
    batch = tuple(t[0] for t in job.make_batch(jax.random.PRNGKey(1), 1))
    grad = jax.jit(jax.grad(lambda p: job.loss_fn(p, counts, *batch)[0]))
    text = grad.lower(params).compile().as_text()
    for scope in sdar_costs.PARTS:
        assert scope in text, scope
    positions, layers, k = BATCH * 2 * SEQ, 1, 2
    peek = lambda name: metrics.peek(name).value
    assert peek("bluefog.moe.rows_offered") == positions * k * layers
    assert peek("bluefog.moe.rows_capacity") == positions * k * layers
    # the buffers' true size: tiles of 8 rows off the kernels' shapes, a row
    # for every pair and a tile of slack a held expert, in whole chunks of 8
    tiles = positions * k // 8 + job.model.cfg.num_experts
    assert peek("bluefog.moe.row_tile") == 8
    assert peek("bluefog.moe.buffer_rows") == -(-tiles // 8) * 8 * 8 * layers
    live, total = peek("bluefog.attn.tiles_live"), peek("bluefog.attn.tiles_total")
    assert 0 < live <= total and total == BATCH * 4 * layers  # one tile a head
    assert peek("bluefog.attn.grid_steps") == total  # a list of that one tile
    # the one tile of 128 walks no sub-tiles; by every pair it is partial
    pos = np.arange(128)
    keep = flash.BlockDiffusionMask(SEQ, 4).allowed(pos[:, None], pos[None, :], xp=np)
    keep &= (pos < 2 * SEQ)[None, :]
    real = (pos < 2 * SEQ)[:, None]
    assert (keep & real).any() and not (keep | ~real).all()
    assert peek("bluefog.attn.subtiles_live") == peek("bluefog.attn.subtiles_masked") == total


def test_the_causal_mask_kind_sees_no_later_token():
    cfg = models.DecoderConfig.from_source(
        SRC, experts_total=TOTAL, compute_dtype=jnp.float32
    )
    model = models.DecoderLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0, 64)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), tokens)["params"]
    apply = jax.jit(lambda tokens: model.apply({"params": params}, tokens))
    logits, counts = apply(tokens)
    moved, _ = apply(tokens.at[0, -1].set((tokens[0, -1] + 1) % 64))
    assert logits.shape == (1, 12, 64) and logits.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(moved[0, :-1]), np.asarray(logits[0, :-1]), rtol=1e-6)
    assert np.abs(np.asarray(moved[0, -1] - logits[0, -1])).max() > 1e-4
    assert counts["rows_per_expert"].shape == (1, 4)


@pytest.mark.parametrize("start", [0, 4])
def test_a_tiled_router_lands_one_choice_of_every_position_on_every_share(start):
    """``router_init="tiled"``: the router is experts_total / k draws, each
    repeated k times over, so a position's k choices are the k copies of
    its best draw, one on each share of experts_total / k experts —
    whatever the tokens are, the same token everywhere included."""
    cfg = models.DecoderConfig.from_source(
        {**SRC, "num_hidden_layers": 2}, experts_total=TOTAL, experts_start=start,
        compute_dtype=jnp.float32, router_init="tiled",
    )
    model = models.DecoderLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 12), 0, 64)
    params = jax.jit(model.init)(jax.random.PRNGKey(6), tokens)["params"]
    router = np.asarray(params["layer_0"]["experts"]["router"])
    assert router.shape == (32, TOTAL) and (router[:, :4] == router[:, 4:]).all()
    assert len({tuple(c) for c in router[:, :4].T}) == 4  # the draws differ
    for batch in (tokens, jnp.zeros_like(tokens)):
        _, counts = jax.jit(lambda t: model.apply({"params": params}, t))(batch)
        np.testing.assert_array_equal(counts["rows_per_expert"].sum(axis=1), [24, 24])
        np.testing.assert_array_equal(counts["rows_absent"], [24, 24])
    with pytest.raises(ValueError, match="router_init"):
        models.DecoderConfig.from_source(SRC, experts_total=TOTAL, router_init="zeros")


def test_the_readers_parts_find_scopes_and_the_grouped_products():
    """The per-layer readers split the device step by ``sdar_costs.PARTS``:
    the model's scopes; the grouped-product kernels are under
    ``bf.moe.experts``."""
    from benchmarks.harness import scopes

    find = scopes.scope_of(sdar_costs.PARTS)
    step = "jit(bf_step)/bf.loss_grad/jvp(DecoderLM.hidden)/layer_0/"
    assert find(step + "experts/bf.moe.experts/convert_element_type") == sdar_costs.EXPERTS
    assert find(step + "experts/bf.moe.route/sort") == sdar_costs.ROUTE
    assert find(step + "experts/bf.moe.combine/gather") == sdar_costs.COMBINE
    assert find(step + "bf.attn/attn/cond/branch_1_fun/jit(_flash)/bf_flash_fwd/pallas_call") == sdar_costs.ATTN
    assert find("jit(bf_step)/bf.loss_grad/bf.head/reduce_max") == sdar_costs.HEAD
    assert find(step + "experts/bf.moe.experts/bf_gmm/pallas_call") == sdar_costs.EXPERTS
    assert find("jit(bf_step)/bf.inner_update/mul") is None


def test_kernel_costs_name_the_flash_kernels_and_count_the_masks_area():
    cell = cells.load_cell("sdar30b_1chip_b2")
    module = bench._load_module("benchmarks.jobs.sdar", cells.job_path("sdar"))
    costs = module.Job(cell.config, cell.traffic).kernel_costs()
    attention, experts = costs["flash"], costs["moe_experts"]
    assert attention["kernels"] == ["bf_flash_fwd", "bf_flash_dkv", "bf_flash_dq"]
    pairs = 2 * (4096 ** 2 + 4096 * 4)  # two sequences, the mask's area
    assert attention["flops"] == 6 * 7 * 2 * pairs * 32 * 128
    assert attention["bytes"] == 6 * 6 * 16384 * (32 + 4) * 128 * 2
    rows = 16384 * 8 * 16 // 128  # expected rows a layer over the held experts
    assert experts["flops"] == 6 * 9 * 2 * rows * 2048 * 768 and experts["kernels"] == ["bf_gmm", "bf_tgmm"]
    job = module.Job(cell.config, cell.traffic)
    assert job.units_per_worker_step == 2 * 4096 and job.mosaic_calls == 6 * 16
