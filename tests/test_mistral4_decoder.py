"""Latent attention, yarn positions and the shared expert of the
config-driven decoder stack (``models/decoder.py``) at toy size, with every
ratio of the real widths kept (``qk_nope_head_dim`` != ``qk_rope_head_dim``,
an original length short enough that yarn's ramp and the llama-4 factor are
not 1): the fused step against the ``mistral4`` job's plain float32
``jax.numpy`` reference, a bfloat16 router failing the same comparison, the
one rotary key, the shares adding up, the refusals, the scopes, the gauges
and the cell's counts."""

import math
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
from bluefog_tpu.models import decoder  # noqa: E402
from benchmarks.harness import bench, cells, mistral4_costs, scopes  # noqa: E402

import toy  # noqa: E402

ROPE = {
    "beta_fast": 32, "beta_slow": 1, "factor": 16, "llama_4_scaling_beta": 0.1,
    "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 16,
    "rope_theta": 100, "rope_type": "yarn", "type": "yarn",
}
SRC = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 96,
    "kv_lora_rank": 8, "max_position_embeddings": 256, "mlp_bias": False,
    "model_type": "mistral4", "moe_intermediate_size": 24, "n_group": 1,
    "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2, "num_hidden_layers": 1,
    "num_key_value_heads": 4, "q_lora_rank": 12, "qk_head_dim": 16,
    "qk_nope_head_dim": 6, "qk_rope_head_dim": 10, "rms_norm_eps": 1e-6,
    "rope_interleave": True, "rope_parameters": ROPE, "routed_scaling_factor": 1,
    "sliding_window": None, "tie_word_embeddings": False, "topk_group": 1,
    "v_head_dim": 16, "vocab_size": 64,
}
TOTAL, START, SEQ, BATCH, LR = 8, 2, 24, 2, 0.1


def config(src=SRC, **own):
    return {
        "source": "test", "job": "mistral4", "unit": "tok", **src,
        "source_keys": list(src),
        "model": {
            "experts_total": TOTAL, "experts_start": START,
            "compute_dtype": "float32", "param_dtype": "float32",
            "head_dtype": "float32", "router_dtype": "float32", "remat": True,
            "initializer_range": 0.3, "router_init": "normal",
            "mosaic_calls_per_layer": 0, **own,
        },
        "n_params": mistral4_costs.param_count(src, TOTAL),
        "optimizer": {"name": "sgd", "learning_rate": LR, "momentum": 0.9},
        "flops": {
            "matmul_params_per_token": mistral4_costs.matmul_params_per_token(src, TOTAL),
            "formula": "none",
        },
        "tolerance": toy.TOLERANCE, "reduced": [], "assumed": [],
    }


def load_module():
    return bench._load_module("benchmarks.jobs.mistral4", cells.job_path("mistral4"))


def job_of(cfg):
    cells.check_config("toy", cfg)
    return load_module().Job(cfg, toy.traffic(seq=SEQ, batch_per_worker=BATCH))


def toy_model():
    cfg = models.DecoderConfig.from_source(
        SRC, experts_total=TOTAL, compute_dtype=jnp.float32
    )
    return cfg, models.DecoderLM(cfg)


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
    for name in ("rows_per_expert", "rows_absent", "rows_dropped"):
        assert (counts[name] == ref_counts[name]).all(), name
    assert counts["rows_per_expert"].shape == (1, SRC["n_routed_experts"])
    landed = counts["rows_per_expert"].sum(axis=1)
    assert (landed + counts["rows_absent"] == BATCH * SEQ * 2).all()
    assert not counts["rows_dropped"].any() and landed.all()


def test_a_bfloat16_router_fails_the_same_comparison(one_worker):
    """The control in the precision below: only the router's product drops
    to bfloat16, and the update is several times further from the
    reference than the limit the float32 program is held to above."""
    _, _, err, _, _ = one_step(job_of(config(router_dtype="bfloat16")))
    assert err > 5e-4, err


def test_plain_positions_and_a_scaled_routed_sum_follow_the_reference(one_worker):
    """``rope_type`` default (no ramp, no rescaled softmax, no llama-4
    factor) and a ``routed_scaling_factor`` that is not 1."""
    src = {
        **SRC, "routed_scaling_factor": 2.5,
        "rope_parameters": {"rope_theta": 100, "rope_type": "default"},
    }
    loss, ref_loss, err, _, _ = one_step(job_of(config(src)), key=1)
    assert abs(loss - ref_loss) < 1e-5 * ref_loss and err < 1e-4


def test_a_whole_toy_cell_is_correct_on_two_workers():
    result, info = toy.rehearse(
        config(), toy.traffic(seq=SEQ, batch_per_worker=BATCH, topology="ring"), 2
    )
    assert result["correct"], info["reference"]
    assert max(info["reference"]["update_l2_err"]) < 1e-4
    assert info["n_params"] == mistral4_costs.param_count(SRC, TOTAL)


def test_yarn_blends_the_frequencies_and_rescales_the_softmax():
    """The toy group's numbers by hand: 5 frequencies of theta 100 over 10
    dims, original length 16 — ``corr(32) < 0`` and ``corr(1) = 1.01``, so
    the ramp is 0, 1/2, 1, 1, 1 — and the cell's: low 12, high 25."""
    rope = decoder.RopeParameters.from_source(ROPE)
    plain = 100.0 ** (-np.arange(5) / 5)
    ramp = np.array([0, 0.5, 1, 1, 1])
    np.testing.assert_allclose(
        np.asarray(rope.inv_freq(10)), plain / 16 * ramp + plain * (1 - ramp), rtol=1e-6
    )
    m = 0.1 * math.log(16) + 1
    assert rope.cos_sin_scale == 1.0
    assert rope.softmax_scale(16) == pytest.approx(m * m / 4)
    np.testing.assert_allclose(
        np.asarray(rope.query_scale(jnp.array([0, 15, 16, 31, 32]))),
        1 + 0.1 * np.log1p([0, 0, 1, 1, 2]), rtol=1e-6,
    )
    cell = cells.load_cell("mistral4_1chip_b1")
    rope = decoder.RopeParameters.from_source(cell.config["rope_parameters"])
    freq = np.asarray(rope.inv_freq(64))
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(freq[:13], plain[:13], rtol=1e-6)  # up to low = 12
    np.testing.assert_allclose(freq[25:], plain[25:] / 128, rtol=1e-6)  # from high = 25
    assert (freq[13:25] < plain[13:25]).all() and (freq[13:25] > plain[13:25] / 128).all()
    assert rope.softmax_scale(128) == pytest.approx(0.19497, rel=1e-4)
    assert (np.asarray(rope.query_scale(jnp.arange(4096))) == 1).all()
    # without an all-dim scale the family rescales cos and sin instead
    one_scale = decoder.RopeParameters.from_source({**ROPE, "mscale_all_dim": 0})
    assert one_scale.cos_sin_scale == pytest.approx(m) and one_scale.softmax_scale(16) == 0.25


def test_the_rotary_key_is_one_key_shared_by_all_heads():
    """The stream's rotary columns are ``qk_rope_head_dim`` wide, not a key
    a head, and perturbing them moves every head's output."""
    cfg, _ = toy_model()
    heads, dv, rank = 4, 16, SRC["kv_lora_rank"]
    attn = decoder.LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, 32))
    params = attn.init(jax.random.PRNGKey(1), x, jnp.arange(SEQ), "causal")["params"]
    assert params["kv_a_proj"]["kernel"].shape == (32, rank + SRC["qk_rope_head_dim"])
    # an output projection that keeps the heads apart: head h's first 8 dims
    pick = np.zeros((heads * dv, 32), np.float32)
    for h in range(heads):
        pick[h * dv + np.arange(8), h * 8 + np.arange(8)] = 1
    params = {**params, "o_proj": {"kernel": jnp.asarray(pick)}}
    apply = jax.jit(lambda p: attn.apply({"params": p}, x, jnp.arange(SEQ), "causal"))
    kernel = params["kv_a_proj"]["kernel"]
    moved = {**params, "kv_a_proj": {"kernel": kernel.at[:, rank:].add(0.5)}}
    change = np.abs(np.asarray(apply(moved) - apply(params)))[0].reshape(SEQ, heads, 8)
    assert (change.max(axis=(0, 2)) > 1e-4).all(), change.max(axis=(0, 2))


def test_the_causal_kind_sees_no_later_token_through_the_latent_path():
    _, model = toy_model()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, SEQ), 0, 64)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), tokens)["params"]
    apply = jax.jit(lambda tokens: model.apply({"params": params}, tokens))
    logits, counts = apply(tokens)
    moved, _ = apply(tokens.at[0, -1].set((tokens[0, -1] + 1) % 64))
    assert logits.shape == (1, SEQ, 64) and logits.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(moved[0, :-1]), np.asarray(logits[0, :-1]), rtol=1e-6)
    assert np.abs(np.asarray(moved[0, -1] - logits[0, -1])).max() > 1e-4
    assert counts["rows_per_expert"].shape == (1, 4)


@pytest.mark.parametrize("shares", [1, 2, 4, 16])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(shares):
    """The routed parts of all the shares of a layer's 16 experts plus the
    shared expert, which every chip computes alike, **counted once**: the
    plain reference's uncut layer with all experts held."""
    total = 16
    src = {**SRC, "n_routed_experts": total // shares}
    whole = decoder.DecoderConfig.from_source(
        {**SRC, "n_routed_experts": total}, experts_total=total,
        compute_dtype=jnp.float32, initializer_range=0.3,
    )
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32))
    routed = decoder.SparseExperts(whole).init(jax.random.PRNGKey(1), u)["params"]
    shared = decoder.SharedExpert(whole).init(jax.random.PRNGKey(2), u)["params"]
    reference = load_module()
    with jax.default_matmul_precision("highest"):
        want, per_expert, absent = reference._routed_experts(
            routed, u[0], {**SRC, "n_routed_experts": total}, 0
        )
        want = want + reference._shared_expert(shared, u[0])
    assert int(absent) == 0 and int(per_expert.sum()) == 40 * 2

    got = decoder.SharedExpert(whole).apply({"params": shared}, u)[0]  # once
    landed = 0
    for s in range(shares):
        held = total // shares
        cfg = decoder.DecoderConfig.from_source(
            src, experts_total=total, experts_start=s * held,
            compute_dtype=jnp.float32,
        )
        part = {
            "router": routed["router"],
            **{k: routed[k][s * held:(s + 1) * held] for k in ("w_gate", "w_up", "w_down")},
        }
        y, counts = decoder.SparseExperts(cfg).apply({"params": part}, u)
        got = got + y[0]
        landed += int(counts["rows_per_expert"].sum())
    assert landed == 40 * 2  # every pair landed on exactly one share
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_the_parameter_tree_is_what_the_counts_say():
    job = job_of(config())
    params, _ = jax.eval_shape(job.init, jax.random.PRNGKey(0))
    sizes = {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }
    assert sizes["layer_0/attn/q_a_proj/kernel"] == (32, 12)
    assert sizes["layer_0/attn/q_a_norm/scale"] == (12,)
    assert sizes["layer_0/attn/q_b_proj/kernel"] == (12, 4 * 16)
    assert sizes["layer_0/attn/kv_a_proj/kernel"] == (32, 8 + 10)
    assert sizes["layer_0/attn/kv_a_norm/scale"] == (8,)
    assert sizes["layer_0/attn/kv_b_proj/kernel"] == (8, 4 * (6 + 16))
    assert sizes["layer_0/attn/o_proj/kernel"] == (4 * 16, 32)
    assert sizes["layer_0/experts/router"] == (32, TOTAL)  # the full width
    assert sizes["layer_0/experts/w_gate"] == (4, 32, 24)
    assert sizes["layer_0/shared_expert/gate_proj/kernel"] == (32, 24)
    assert sizes["layer_0/shared_expert/down_proj/kernel"] == (24, 32)
    assert sizes["lm_head/kernel"] == (32, 64) and sizes["embed/embedding"] == (64, 32)
    total = sum(int(np.prod(s)) for s in sizes.values())
    assert total == mistral4_costs.param_count(SRC, TOTAL)


def test_the_cells_counts_are_the_issues():
    cell = cells.load_cell("mistral4_1chip_b1")
    src = cells.source_entry(cell.config)
    assert mistral4_costs.attention_params(src) == 28049408
    assert mistral4_costs.param_count(src, 128) == cell.config["n_params"] == 1154524160
    per_token = mistral4_costs.matmul_params_per_token(src, 128)
    assert per_token == cell.config["flops"]["matmul_params_per_token"] == 307232768
    flops = mistral4_costs.flops_per_token(src, 128, 4096)
    assert flops == 6 * 307232768 + 6 * 4097 * 128 * 32 * 4
    assert abs(flops - 2.25e9) < 1e7
    assert cell.config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cell.traffic["batch_per_worker"] == 1 and cell.traffic["seq"] == 4096
    assert {"attn_latent_ms", "moe_shared_ms"} <= set(cell.per_layer)


def test_kernel_costs_name_the_kernels_and_count_the_triangle():
    cell = cells.load_cell("mistral4_1chip_b1")
    job = load_module().Job(cell.config, cell.traffic)
    costs = job.kernel_costs()
    attention, experts = costs["flash"], costs["moe_experts"]
    assert attention["kernels"] == ["bf_flash_fwd", "bf_flash_dkv", "bf_flash_dq"]
    pairs = 4096 * 4097 // 2
    assert attention["flops"] == 4 * 7 * 2 * pairs * 32 * 128
    assert attention["bytes"] == 4 * 6 * 4096 * (32 + 32) * 128 * 2
    rows = 4096 * 4 * 8 // 128  # expected rows a layer over the held experts
    assert experts["flops"] == 4 * 9 * 2 * rows * 4096 * 2048
    assert experts["kernels"] == ["bf_gmm", "bf_tgmm"]
    assert job.units_per_worker_step == 4096 and job.mosaic_calls == 4 * 16
    assert job.cfg.kv_lora_rank == 256 and job.cfg.n_shared_experts == 1
    assert job.cfg.num_experts == 8 and job.cfg.experts_total == 128


@pytest.mark.parametrize("name, change", [
    ("n_group", {"n_group": 2}), ("topk_group", {"topk_group": 2}),
    ("first_k_dense_replace", {"first_k_dense_replace": 1}),
    ("mlp_bias", {"mlp_bias": True}), ("sliding_window", {"sliding_window": 4096}),
    ("rope_type", {"rope_parameters": {**ROPE, "rope_type": "linear", "type": "linear"}}),
    ("rope_type", {"rope_parameters": {**ROPE, "type": "default"}}),
])
def test_settings_the_stack_cannot_honour_are_refused(name, change):
    with pytest.raises(ValueError, match=name):
        models.DecoderConfig.from_source({**SRC, **change}, experts_total=TOTAL)


@pytest.mark.parametrize("change, message", [
    ({"q_lora_rank": None}, "low-rank query path"),
    ({"rope_interleave": False}, "interleaved pairs"),
    ({"v_head_dim": 24}, "one head size"),
    ({"qk_nope_head_dim": 8}, "one head size"),
    ({"num_key_value_heads": 2}, "a key and a value per query head"),
    ({"rope_parameters": {**ROPE, "yarn_only": 1}}, "nothing reads"),
    ({"rope_parameters": {**ROPE, "original_max_position_embeddings": None}},
     "original_max_position_embeddings"),
    ({"kv_lora_rank": None}, "latent attention only"),  # yarn without it
])
def test_sizes_that_do_not_fit_are_refused(change, message):
    with pytest.raises(ValueError, match=message):
        models.DecoderConfig.from_source({**SRC, **change}, experts_total=TOTAL)


def test_scopes_and_gauges_of_one_traced_loss():
    job = job_of(config())
    params, counts = job.init(jax.random.PRNGKey(0))
    batch = tuple(t[0] for t in job.make_batch(jax.random.PRNGKey(1), 1))
    grad = jax.jit(jax.grad(lambda p: job.loss_fn(p, counts, *batch)[0]))
    text = grad.lower(params).compile().as_text()
    for scope in mistral4_costs.PARTS:
        assert scope in text, scope
    positions, layers = BATCH * SEQ, 1
    peek = lambda name: metrics.peek(name).value
    assert peek("bluefog.attn.kv_latent_bytes") == positions * (8 + 10) * layers * 4
    assert peek("bluefog.attn.kv_expanded_bytes") == positions * 4 * (16 + 16) * layers * 4
    assert peek("bluefog.moe.shared_rows") == positions * layers
    assert peek("bluefog.moe.rows_offered") == positions * 2 * layers
    live, total = peek("bluefog.attn.tiles_live"), peek("bluefog.attn.tiles_total")
    assert 0 < live <= total and total == BATCH * 4 * layers  # one tile a head
    assert peek("bluefog.attn.grid_steps") == total  # a list of that one tile
    # the one causal tile of 128 over 24 positions walks no sub-tiles, and
    # by every pair it is partial: built and masked
    pos = np.arange(128)
    keep = (pos[:, None] >= pos[None, :]) & (pos < SEQ)[None, :]
    real = (pos < SEQ)[:, None]
    assert (keep & real).any() and not (keep | ~real).all()
    assert peek("bluefog.attn.subtiles_live") == peek("bluefog.attn.subtiles_masked") == total


def test_the_readers_parts_tell_the_latent_path_from_the_rest_of_attention():
    """``bf.attn.latent`` lies inside ``bf.attn``: the innermost decides,
    and the kernels and ``o_proj`` stay ``bf.attn``'s."""
    find = scopes.scope_of(mistral4_costs.PARTS)
    layer = "jit(bf_step)/bf.loss_grad/jvp(DecoderLM.hidden)/layer_0/"
    assert find(layer + "bf.attn/attn/bf.attn.latent/q_a_proj/dot_general") == mistral4_costs.LATENT
    assert find(layer + "bf.attn/attn/o_proj/dot_general") == mistral4_costs.ATTN
    assert find(
        layer + "bf.attn/attn/cond/branch_1_fun/jit(_flash)/bf_flash_fwd/pallas_call"
    ) == mistral4_costs.ATTN
    assert find(layer + "bf.moe.shared/shared_expert/gate_proj/dot_general") == mistral4_costs.SHARED
    assert find(layer + "experts/bf.moe.experts/bf_gmm/pallas_call") == mistral4_costs.EXPERTS
    assert find("jit(bf_step)/bf.loss_grad/bf.head/reduce_max") == mistral4_costs.HEAD
    assert find("jit(bf_step)/bf.inner_update/mul") is None


def test_the_job_refuses_a_file_whose_counts_are_not_its_sizes():
    wrong = config()
    wrong["n_params"] += 1
    with pytest.raises(ValueError, match="n_params"):
        job_of(wrong)
    wrong = config()
    wrong["flops"]["matmul_params_per_token"] += 1
    with pytest.raises(ValueError, match="matmul_params_per_token"):
        job_of(wrong)
