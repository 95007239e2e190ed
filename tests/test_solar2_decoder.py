"""Per-layer kinds, gated NoPE grouped-query attention, Kimi Delta
Attention and head shares in the config-driven decoder stack
(``models/decoder.py``) at toy widths that keep every ratio of the
``solar-open2-250b`` row (64 query heads of 8 a key-value head, 64 linear
heads, four shares of them; 320 experts, 8 a token, 8 held; the layer
pattern's period of 4): the fused step against the ``solar2`` job's plain
float32 ``jax.numpy`` reference, a bfloat16 control failing the same
comparison, causality through the conv and the scan, the shares adding up,
the refusals, the scopes and gauges and the cell's counts."""

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
from benchmarks.harness import bench, cells, scopes, solar2_costs  # noqa: E402

import toy  # noqa: E402

LINEAR = {"short_conv_kernel_size": 4, "head_dim": 8, "num_heads": 64, "num_kv_heads": None}
SRC = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": LINEAR, "hidden_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 64, "head_dim": 8, "num_key_value_heads": 8,
    "vocab_size": 48, "intermediate_size": 80, "moe_intermediate_size": 10,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1024, "first_k_dense_replace": 0, "use_rope": False,
    "gqa_interval": 3, "gqa_layers": [0, 4, 8], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 8, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8,
}
TOTAL, SHARES, SEQ, BATCH, LR = 320, 4, 72, 2, 0.1


def config(src=SRC, **own):
    return {
        "source": "test", "job": "solar2", "unit": "tok", **src,
        "source_keys": list(src),
        "model": {
            "experts_total": TOTAL, "experts_start": 8, "attention_shares": SHARES,
            "qk_norm": False,
            "compute_dtype": "float32", "param_dtype": "float32",
            "head_dtype": "float32", "router_dtype": "float32", "remat": True,
            "initializer_range": 0.3, "router_init": "normal", **own,
        },
        "n_params": solar2_costs.param_count(src, TOTAL, SHARES),
        "optimizer": {"name": "sgd", "learning_rate": LR, "momentum": 0.9},
        "flops": {
            "matmul_params_per_token": solar2_costs.matmul_params_per_token(
                src, TOTAL, SHARES
            ),
            "formula": "none",
        },
        "tolerance": toy.TOLERANCE, "reduced": [], "assumed": [],
    }


def load_module():
    return bench._load_module("benchmarks.jobs.solar2", cells.job_path("solar2"))


def job_of(cfg):
    cells.check_config("toy", cfg)
    return load_module().Job(cfg, toy.traffic(seq=SEQ, batch_per_worker=BATCH))


def whole(**own):
    """The toy configuration with every head held."""
    return decoder.DecoderConfig.from_source(
        SRC, experts_total=TOTAL, compute_dtype=jnp.float32, qk_norm=False,
        initializer_range=0.3, **own,
    )


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
    landed = counts["rows_per_expert"].sum(axis=1)
    assert (landed + counts["rows_absent"] == BATCH * SEQ * 8).all()
    assert landed.all() and not counts["rows_dropped"].any()


def test_a_bfloat16_control_fails_the_same_comparison(one_worker):
    """The control in the precision below: the blocks' compute in bfloat16
    (the scan, its gates and norms stay float32), and the update is far
    further from the reference than the float32 program's limit above."""
    _, _, err, _, _ = one_step(job_of(config(compute_dtype="bfloat16")))
    assert err > 1e-3, err


def test_the_layer_kinds_follow_gqa_layers():
    cfg = whole()
    assert cfg.layer_kinds == ("softmax", "linear", "linear", "linear")
    cell = cells.load_cell("solar2_1chip_seq8k")
    job = load_module().Job(cell.config, cell.traffic)
    assert job.cfg.layer_kinds == ("softmax", "linear", "linear", "linear")
    assert job.cfg.held(64) == 16 and job.cfg.held(8) == 2


def test_no_position_sees_a_later_token_through_the_conv_and_the_scan():
    cfg = decoder.DecoderConfig.from_source(
        {**SRC, "num_hidden_layers": 2}, experts_total=TOTAL, experts_start=0,
        compute_dtype=jnp.float32, qk_norm=False, initializer_range=0.3,
    )
    model = models.DecoderLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, SEQ), 0, 48)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), tokens)["params"]
    apply = jax.jit(lambda tokens: model.apply({"params": params}, tokens)[0])
    logits = apply(tokens)
    for j in (0, 3, 64, SEQ - 1):
        moved = apply(tokens.at[0, j].set((tokens[0, j] + 1) % 48))
        np.testing.assert_array_equal(np.asarray(moved[0, :j]), np.asarray(logits[0, :j]))
        assert np.abs(np.asarray(moved[0, j:] - logits[0, j:])).max() > 1e-4


def _share_of(params, share, shares):
    """The slice of an uncut attention layer's parameters that share
    ``share`` of ``shares`` holds: its heads' columns (rows of ``o_proj``),
    the low-rank gates' first factors whole."""
    def cut(x, axis):
        n = x.shape[axis] // shares
        return jax.lax.slice_in_dim(x, share * n, (share + 1) * n, axis=axis)

    out = {}
    for name, leaf in params.items():
        if name in ("f_a_proj", "g_a_proj", "o_norm"):
            out[name] = leaf
        elif name == "o_proj":
            out[name] = {"kernel": cut(leaf["kernel"], 0)}
        elif isinstance(leaf, dict):
            out[name] = {"kernel": cut(leaf["kernel"], 1)}
        else:  # A_log, dt_bias (heads, channels), the convs [K, width]
            out[name] = cut(leaf, leaf.ndim - 1)
    return out


@pytest.mark.parametrize("kind", ["softmax", "linear"])
def test_the_four_head_shares_add_up_to_the_uncut_layer(kind):
    full = whole()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32))
    pos = jnp.arange(40)
    if kind == "softmax":
        module = lambda cfg: decoder.Attention(cfg, cfg.use_rope)
        call = lambda m, p: m.apply({"params": p}, x, pos, "causal")
    else:
        module = lambda cfg: decoder.DeltaAttention(cfg)
        call = lambda m, p: m.apply({"params": p}, x)
    params = (
        module(full).init(jax.random.PRNGKey(1), x, pos, "causal") if kind == "softmax"
        else module(full).init(jax.random.PRNGKey(1), x)
    )["params"]
    with jax.default_matmul_precision("highest"):
        want = call(module(full), params)
        got = 0.0
        cfg = whole(attention_shares=SHARES)
        for s in range(SHARES):
            got = got + call(module(cfg), _share_of(params, s, SHARES))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_the_forty_expert_shares_and_the_shared_expert_once_add_up():
    """The routed parts of all forty shares of a layer's 320 experts plus
    the shared expert, which every chip computes alike, **counted once**:
    the plain reference's uncut layer with all experts held."""
    src = {**SRC, "n_routed_experts": TOTAL}
    cfg = decoder.DecoderConfig.from_source(
        src, experts_total=TOTAL, compute_dtype=jnp.float32, initializer_range=0.3,
    )
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32))
    routed = decoder.SparseExperts(cfg).init(jax.random.PRNGKey(1), u)["params"]
    shared = decoder.SharedExpert(cfg).init(jax.random.PRNGKey(2), u)["params"]
    reference = load_module()
    with jax.default_matmul_precision("highest"):
        want, per_expert, absent = reference._routed_experts(routed, u[0], src, 0)
        want = want + reference._shared_expert(shared, u[0])
    assert int(absent) == 0 and int(per_expert.sum()) == 40 * 8

    got = decoder.SharedExpert(cfg).apply({"params": shared}, u)[0]  # once
    landed, held = 0, SRC["n_routed_experts"]
    for s in range(TOTAL // held):
        part_cfg = decoder.DecoderConfig.from_source(
            SRC, experts_total=TOTAL, experts_start=s * held, compute_dtype=jnp.float32,
        )
        part = {
            "router": routed["router"],
            **{k: routed[k][s * held:(s + 1) * held] for k in ("w_gate", "w_up", "w_down")},
        }
        y, counts = decoder.SparseExperts(part_cfg).apply({"params": part}, u)
        got = got + y[0]
        landed += int(counts["rows_per_expert"].sum())
    assert landed == 40 * 8  # every pair landed on exactly one share
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("change, message", [
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"partial_rotary_factor": 0.5}, "partial_rotary_factor"),
    ({"gqa_layers": [0, 3, 6]}, "gqa_interval"),
    ({"gqa_interval": 2}, "gqa_interval"),
    ({"linear_attn_config": {**LINEAR, "num_kv_heads": 16}}, "num_kv_heads"),
    ({"num_key_value_heads": 2}, "do not divide"),
])
def test_settings_the_stack_cannot_build_are_refused(change, message):
    with pytest.raises(ValueError, match=message):
        decoder.DecoderConfig.from_source(
            {**SRC, **change}, experts_total=TOTAL, attention_shares=SHARES,
        )


def test_linear_layers_refuse_a_mask_other_than_causal():
    cfg = whole()
    layer = decoder.DecoderLayer(cfg, "linear", rope=False)
    x = jnp.zeros((1, 8, 32))
    with pytest.raises(ValueError, match="linear attention is causal"):
        layer.init(jax.random.PRNGKey(0), x, jnp.arange(8), None)


def test_scopes_and_gauges_of_one_traced_loss():
    job = job_of(config())
    params, counts = job.init(jax.random.PRNGKey(0))
    batch = tuple(t[0] for t in job.make_batch(jax.random.PRNGKey(1), 1))
    grad = jax.jit(jax.grad(lambda p: job.loss_fn(p, counts, *batch)[0]))
    text = grad.lower(params).compile().as_text()
    for scope in solar2_costs.PARTS:
        assert scope in text, scope
    peek = lambda name: metrics.peek(name).value
    chunks = BATCH * 16 * 2 * 3  # 16 heads held, 72 tokens in 2 chunks, 3 linear layers
    assert peek("bluefog.delta.chunks") == chunks
    assert peek("bluefog.delta.fused_fwd_chunks") == 0  # the CPU's scan builds them in XLA
    assert peek("bluefog.delta.fused_bwd_chunks") == 0  # and jax differentiates them there
    assert peek("bluefog.delta.state_bytes") == chunks * 8 * 8 * 4
    assert peek("bluefog.attn.heads_held") == 16
    assert peek("bluefog.attn.tiles_total") == BATCH * 16 * 1  # one softmax layer
    assert peek("bluefog.moe.rows_offered") == BATCH * SEQ * 8 * 4


def test_the_readers_parts_tell_the_linear_layers_from_the_rest_of_attention():
    find = scopes.scope_of(solar2_costs.PARTS)
    layer = "jit(bf_step)/bf.loss_grad/jvp(DecoderLM.hidden)/layer_1/"
    assert find(layer + "bf.attn/bf.attn.delta/attn/q_proj/dot_general") == solar2_costs.DELTA
    assert find(layer + "bf.attn/bf.attn.delta/attn/bf_delta_fwd/pallas_call") == solar2_costs.DELTA
    assert find(layer + "bf.attn/attn/o_proj/dot_general") == solar2_costs.ATTN
    assert find(layer + "bf.moe.shared/shared_expert/gate_proj/dot_general") == solar2_costs.SHARED
    assert find("jit(bf_step)/bf.loss_grad/bf.head/reduce_max") == solar2_costs.HEAD


def test_the_parameter_tree_is_what_the_counts_say():
    job = job_of(config())
    params, _ = jax.eval_shape(job.init, jax.random.PRNGKey(0))
    sizes = {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }
    assert sizes["layer_0/attn/q_proj/kernel"] == (32, 16 * 8)
    assert sizes["layer_0/attn/gate_proj/kernel"] == (32, 16 * 8)
    assert sizes["layer_0/attn/k_proj/kernel"] == (32, 2 * 8)
    assert "q_norm" not in str(sizes)
    assert sizes["layer_1/attn/q_conv"] == (4, 16 * 8)
    assert sizes["layer_1/attn/f_a_proj/kernel"] == (32, 8)
    assert sizes["layer_1/attn/f_b_proj/kernel"] == (8, 16 * 8)
    assert sizes["layer_1/attn/b_proj/kernel"] == (32, 16)
    assert sizes["layer_1/attn/A_log"] == (16,)
    assert sizes["layer_1/attn/dt_bias"] == (16 * 8,)
    assert sizes["layer_1/attn/o_norm/scale"] == (8,)
    assert sizes["layer_1/experts/router"] == (32, TOTAL)  # the full width
    assert sizes["layer_1/experts/w_gate"] == (8, 32, 10)
    total = sum(int(np.prod(s)) for s in sizes.values())
    assert total == solar2_costs.param_count(SRC, TOTAL, SHARES)


def test_the_cells_counts_are_the_issues():
    cell = cells.load_cell("solar2_1chip_seq8k")
    src = cells.source_entry(cell.config)
    assert solar2_costs.softmax_matrices(src, 4) == 27262976
    assert solar2_costs.param_count(src, 320, 4) == cell.config["n_params"] == 905759152
    per_token = solar2_costs.matmul_params_per_token(src, 320, 4)
    assert per_token == cell.config["flops"]["matmul_params_per_token"]
    assert abs(solar2_costs.flops_per_token(src, 320, 4, 8192) - 2.006e9) < 1e6
    assert cell.config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cell.traffic["batch_per_worker"] == 1 and cell.traffic["seq"] == 8192
    assert {"linear_attn_ms", "delta_kernel_roofline"} <= set(cell.per_layer)


def test_kernel_costs_name_the_kernels():
    cell = cells.load_cell("solar2_1chip_seq8k")
    job = load_module().Job(cell.config, cell.traffic)
    costs = job.kernel_costs()
    assert costs["delta"]["kernels"] == ["bf_delta_fwd", "bf_delta_bwd"]
    slots = 16 * 128 * 3  # heads held x chunks x linear layers
    assert costs["delta"]["flops"] == slots * 2 * (9 * 64 * 128 ** 2 + 3 * 64 ** 2 * 128)
    assert costs["flash"]["kernels"] == ["bf_flash_fwd", "bf_flash_dkv", "bf_flash_dq"]
    assert costs["flash"]["flops"] == 7 * 2 * (8192 * 8193 // 2) * 16 * 128
    assert costs["moe_experts"]["kernels"] == ["bf_gmm", "bf_tgmm"]
    assert job.mosaic_calls == 12 * 4 + 4 + 3 * 3


def test_the_job_refuses_a_file_whose_counts_are_not_its_sizes():
    wrong = config()
    wrong["n_params"] += 1
    with pytest.raises(ValueError, match="n_params"):
        job_of(wrong)
