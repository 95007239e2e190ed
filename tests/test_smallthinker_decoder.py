"""Sliding-window and global NoPE layers, a router on the attention's input
and ReGLU experts in the config-driven decoder stack (``models/decoder.py``)
at toy widths that keep the ratios of the ``smallthinker-21ba3b-instruct``
row (28 query heads on 4 key-value heads, 64 experts of which 6 a token and
16 held, the layouts' period of four as published): the fused step against
the ``smallthinker`` job's plain float32 ``jax.numpy`` reference, a
bfloat16 control failing the same comparison, each layer's window and
rotary from the layouts, the expert shares adding up, the ReLU programs
beside the unchanged SiLU one, the refusals, the scopes, gauges and the
cell's counts, and the two readers."""

import hashlib
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
from bluefog_tpu.ops import flash, moe  # noqa: E402
from benchmarks.harness import bench, cells, scopes, smallthinker_costs  # noqa: E402

import toy  # noqa: E402
from test_scope_readers import chip_run  # noqa: E402

LAYOUT = [0, 1, 1, 1] * 13  # as published: 52 entries, the stack reads its first
SRC = {
    "head_dim": 8, "hidden_size": 32, "max_position_embeddings": 256,
    "model_name": "smallthinker_toy", "moe_ffn_hidden_size": 12,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 16,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 4, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-6, "rope_layout": LAYOUT, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": LAYOUT,
    "sliding_window_size": 16, "tie_word_embeddings": False, "vocab_size": 48,
}
TOTAL, START, SEQ, BATCH, LR = 64, 16, 72, 2, 0.1


def config(src=SRC, **own):
    model = {
        "experts_total": TOTAL, "experts_start": START, "qk_norm": False,
        "hidden_act": "relu", "router_input": "input_norm",
        "compute_dtype": "float32", "param_dtype": "float32",
        "head_dtype": "float32", "router_dtype": "float32", "remat": True,
        "initializer_range": 0.3, "router_init": "normal", **own,
    }
    return {
        "source": "test", "job": "smallthinker", "unit": "tok", **src,
        "source_keys": list(src), "model": model,
        "n_params": smallthinker_costs.param_count(src, TOTAL),
        "optimizer": {"name": "sgd", "learning_rate": LR, "momentum": 0.9},
        "flops": {
            "matmul_params_per_token": smallthinker_costs.matmul_params_per_token(src, model),
            "formula": "none",
        },
        "tolerance": toy.TOLERANCE, "reduced": [], "assumed": [],
    }


def load_module():
    return bench._load_module("benchmarks.jobs.smallthinker", cells.job_path("smallthinker"))


def job_of(cfg, seq=SEQ):
    cells.check_config("toy", cfg)
    return load_module().Job(cfg, toy.traffic(seq=seq, batch_per_worker=BATCH))


def stack_cfg(src=SRC, **own):
    """The toy configuration as the model takes it."""
    kw = dict(
        experts_total=TOTAL, compute_dtype=jnp.float32, qk_norm=False,
        initializer_range=0.3, hidden_act="relu", router_input="input_norm",
    )
    return decoder.DecoderConfig.from_source(src, **{**kw, **own})


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
    for name in ("rows_per_expert", "rows_absent", "rows_dropped", "act_zeros"):
        assert (counts[name] == ref_counts[name]).all(), name
    landed = counts["rows_per_expert"].sum(axis=1)
    assert (landed + counts["rows_absent"] == BATCH * SEQ * 6).all()
    assert landed.all() and not counts["rows_dropped"].any()
    # ReLU leaves about half of each landed row's gate at 0
    share = counts["act_zeros"] / (landed * SRC["moe_ffn_hidden_size"])
    assert ((share > 0.2) & (share < 0.8)).all(), share


def test_a_bfloat16_control_fails_the_same_comparison(one_worker):
    """The control in the precision below: the blocks' compute in bfloat16,
    and the update is far further from the reference than the float32
    program's limit above."""
    _, _, err, _, _ = one_step(job_of(config(compute_dtype="bfloat16")))
    assert err > 1e-3, err


def test_the_toy_cell_runs_through_the_harness():
    """The harness's whole run (warm steps, the window, the reference check
    with the counts carried beside the parameters) on one CPU worker."""
    import time

    cell = toy.cell(config(), toy.traffic(seq=SEQ, batch_per_worker=BATCH), 1)
    cell.units.update(toy.UNITS)
    lines = []
    result = bench.run_cell(
        cell, seed=2 ** 31 + 5, seconds=0.2, trace=False,
        spans=bench.Spans(time.perf_counter()), info=lines.append,
        devices=jax.devices()[:1],
    )
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["compared"]["compiled_in_window"][0] == 0
    (info,) = [line for line in lines if "reference" in line]
    assert max(info["reference"]["update_l2_err"]) < 1e-4


@pytest.mark.parametrize("seed", [0, 1])
def test_the_four_shares_land_every_choice_once(seed):
    """Independent router columns over all 64 experts: on every layer each
    share's landed and absent rows are every position's 6 choices, and on
    the first layer (whose router input no share's experts have touched yet)
    the four shares of 16 together land each of them once."""
    landed = 0
    for start in (0, 16, 32, 48):
        job = job_of(config(experts_start=start))
        params, _ = job.init(jax.random.PRNGKey(seed))
        (tokens,) = job.make_batch(jax.random.PRNGKey(seed + 2), 1)
        _, counts = jax.jit(job.loss_fn)(params, None, tokens[0])
        here = np.asarray(counts["rows_per_expert"]).sum(axis=1)
        assert (here + np.asarray(counts["rows_absent"]) == 6 * BATCH * SEQ).all()
        landed = landed + here
    assert landed[0] == 6 * BATCH * SEQ, landed


def test_a_tiled_router_is_refused_where_k_does_not_divide_the_experts():
    """64 is not a multiple of 6: the tiled router (k copies of
    experts_total / k columns) cannot be built for this row."""
    with pytest.raises(ValueError, match="a tiled router needs k to divide experts_total"):
        job_of(config(router_init="tiled"))


def test_each_layer_reads_its_window_and_rotary_from_the_layouts():
    cfg = stack_cfg()
    assert cfg.layer_windows == (None, 16, 16, 16)
    assert cfg.layer_rope == (False, True, True, True)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size) == (16, 6, 12)
    cell = cells.load_cell("smallthinker_1chip_seq16k")
    job = load_module().Job(cell.config, cell.traffic)
    assert job.cfg.layer_windows == (None, 4096, 4096, 4096)
    assert job.cfg.layer_rope == (False, True, True, True)
    assert job.cfg.layer_kinds == ("softmax",) * 4
    assert (job.cfg.num_experts, job.cfg.experts_total, job.cfg.num_experts_per_tok) == (16, 64, 6)
    assert job.cfg.hidden_act == "relu" and job.cfg.router_input == "input_norm"
    # a stack without layouts keeps use_rope on every layer and no window
    plain = decoder.DecoderConfig.from_source(
        {**SRC, "rope_layout": None, "sliding_window_layout": None},
        experts_total=TOTAL,
    )
    assert plain.layer_windows == (None,) * 4 and plain.layer_rope == (True,) * 4


@pytest.mark.parametrize("rope", [False, True])
@pytest.mark.parametrize("window", [None, 16])
def test_a_layers_attention_follows_its_rotary_and_window(rope, window):
    """One attention sublayer against the job's reference with the same
    rotary flag and window, and against it with the other flag: the two
    differ, so the flag reaches the layer."""
    cfg = stack_cfg()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32))
    pos = jnp.arange(40)
    module = decoder.Attention(cfg, rope)
    mask = "causal" if window is None else flash.SlidingWindowMask(window)
    params = module.init(jax.random.PRNGKey(1), x, pos, mask)["params"]
    reference = load_module()
    with jax.default_matmul_precision("highest"):
        got = module.apply({"params": params}, x, pos, mask)[0]
        want = reference._attention(params, x[0], pos, SRC, window, rope)
        other = reference._attention(params, x[0], pos, SRC, window, not rope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(other - want)).max() > 1e-3


def test_no_position_sees_a_key_outside_its_window():
    """A one-layer stack under a window of 16: a changed token moves the
    outputs at its own position and the 15 after it, and no other."""
    src = {**SRC, "num_hidden_layers": 1, "sliding_window_layout": [1], "rope_layout": [1]}
    cfg = stack_cfg(src, experts_start=0)
    model = models.DecoderLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, SEQ), 0, 48)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), tokens)["params"]
    apply = jax.jit(lambda tokens: model.apply({"params": params}, tokens)[0])
    logits = apply(tokens)
    for j in (0, 5, 30):
        moved = apply(tokens.at[0, j].set((tokens[0, j] + 1) % 48))
        delta = np.abs(np.asarray(moved[0] - logits[0])).max(axis=-1)
        assert not delta[:j].any() and not delta[j + 16:].any()
        assert (delta[j:j + 16] > 1e-6).all()


def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of a layer's 64 experts, each
    routed from the attention's input ``u`` and computed on ``u'``: the
    plain reference's uncut layer with all experts held."""
    src = {**SRC, "moe_num_primary_experts": TOTAL}
    cfg = stack_cfg(src)
    u_route = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32))
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 32))
    whole = decoder.SparseExperts(cfg).init(jax.random.PRNGKey(1), u, u_route)["params"]
    reference = load_module()
    with jax.default_matmul_precision("highest"):
        want, per_expert, absent, zeros = reference._reglu_experts(
            whole, u_route[0], u[0], src, 0
        )
    assert int(absent) == 0 and int(per_expert.sum()) == 40 * 6
    held = SRC["moe_num_primary_experts"]
    got, landed, got_zeros = 0.0, 0, 0
    for s in range(TOTAL // held):
        part_cfg = stack_cfg(experts_start=s * held)
        part = {
            "router": whole["router"],
            **{k: whole[k][s * held:(s + 1) * held] for k in ("w_gate", "w_up", "w_down")},
        }
        with jax.default_matmul_precision("highest"):
            y, counts = decoder.SparseExperts(part_cfg).apply({"params": part}, u, u_route)
        got = got + y[0]
        landed += int(counts["rows_per_expert"].sum())
        got_zeros += int(counts["act_zeros"])
    assert landed == 40 * 6  # every pair landed on exactly one share
    assert got_zeros == int(zeros)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    # routed from u' instead, the choices are others
    with jax.default_matmul_precision("highest"):
        other, *_ = reference._reglu_experts(whole, u[0], u[0], src, 0)
    assert np.abs(np.asarray(other - want)).max() > 1e-3


def _expert_layer_loss(activation):
    def f(u, w_r, w_g, w_u, w_d):
        weights, experts = moe.route(u, w_r, 2)
        kw = {} if activation is None else {"activation": activation}
        y, counts = moe.expert_layer(
            u, weights, experts, w_g, w_u, w_d, held_start=2, dtype=jnp.bfloat16, **kw
        )
        return y.astype(jnp.float32).sum(), counts
    return f


SHAPES = [(64, 256), (256, 8), (4, 256, 384), (4, 256, 384), (4, 384, 256)]
# sha256 of the jaxpr of the SiLU expert layer's value_and_grad at SHAPES
# (4 of 8 experts held, 2 a token, bfloat16 products): the program before
# ReGLU came in, op for op
SILU_LAYER = "f117376eafbe3dad13d6692bd6cbb33109dc3d8b5e5605267a2168e1c84a82f1"


def test_the_silu_program_is_unchanged_by_relu():
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in SHAPES]
    texts = {
        act: str(jax.make_jaxpr(jax.value_and_grad(
            _expert_layer_loss(act), argnums=(0, 1, 2, 3, 4), has_aux=True
        ))(*args))
        for act in (None, "silu", "relu")
    }
    assert hashlib.sha256(texts[None].encode()).hexdigest() == SILU_LAYER
    assert texts["silu"] == texts[None]
    assert texts["relu"] != texts["silu"]


@pytest.mark.parametrize("interpret", [False, True])
def test_relu_experts_and_their_zeros_against_the_plain_formula(interpret):
    """ReGLU through the grouped products (``ragged_dot``, or the Pallas
    kernels in the interpreter) against ``relu(u Wg) * (u Wu) Wd`` a held
    expert, and ``act_zeros`` against the zeros of ``relu(u Wg)`` over the
    pairs that landed; SiLU on the same weights differs."""
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    u, w_r = (jax.random.normal(keys[0], SHAPES[0]), jax.random.normal(keys[1], SHAPES[1]))
    w_g, w_u, w_d = (0.1 * jax.random.normal(k, s) for k, s in zip(keys[2:], SHAPES[2:]))
    weights, experts = moe.route(u, w_r, 2)
    run = lambda act: moe.expert_layer(
        u, weights, experts, w_g, w_u, w_d, held_start=2, activation=act,
        interpret=interpret,
    )
    (y, counts), (y_silu, silu_counts) = run("relu"), run("silu")
    assert "act_zeros" not in silu_counts
    want, zeros = jnp.zeros_like(u), 0
    with jax.default_matmul_precision("highest"):
        for e in range(4):
            gate = u @ w_g[e]
            chosen = experts == 2 + e
            w = jnp.sum(jnp.where(chosen, weights, 0.0), axis=1)
            want = want + w[:, None] * ((jax.nn.relu(gate) * (u @ w_u[e])) @ w_d[e])
            zeros += int(jnp.sum(jnp.where(chosen.any(axis=1)[:, None], gate <= 0, False)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-4)
    assert int(counts["act_zeros"]) == zeros
    assert np.abs(np.asarray(y - y_silu)).max() > 1e-3
    grads = jax.grad(lambda w: moe.expert_layer(
        u, weights, experts, w, w_u, w_d, held_start=2, activation="relu",
        interpret=interpret,
    )[0].sum())(w_g)
    assert np.isfinite(np.asarray(grads)).all() and np.abs(np.asarray(grads)).max() > 0


@pytest.mark.parametrize("change, message", [
    ({"moe_primary_router_apply_softmax": False}, "moe_primary_router_apply_softmax"),
    ({"sliding_window_layout": [0, 1]}, "sliding_window_layout has 2 entries"),
    ({"rope_layout": [0, 1, 1]}, "rope_layout has 3 entries"),
    ({"sliding_window_size": None}, "sliding_window_size"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_settings_the_stack_cannot_build_are_refused(change, message):
    with pytest.raises(ValueError, match=message):
        decoder.DecoderConfig.from_source({**SRC, **change}, experts_total=TOTAL)


def test_a_window_layer_refuses_a_mask_other_than_causal():
    cfg = stack_cfg()
    layer = decoder.DecoderLayer(cfg, "softmax", 16, rope=True)
    x = jnp.zeros((1, 8, 32))
    with pytest.raises(ValueError, match="sliding window narrows causal attention"):
        layer.init(jax.random.PRNGKey(0), x, jnp.arange(8), None)
    with pytest.raises(ValueError, match="router_input"):
        stack_cfg(router_input="before_attention")


def test_scopes_and_gauges_of_one_traced_loss():
    job = job_of(config())
    params, counts = job.init(jax.random.PRNGKey(0))
    batch = tuple(t[0] for t in job.make_batch(jax.random.PRNGKey(1), 1))
    grad = jax.jit(jax.grad(lambda p: job.loss_fn(p, counts, *batch)[0]))
    text = grad.lower(params).compile().as_text()
    for scope in smallthinker_costs.PARTS:
        assert scope in text, scope
    peek = lambda name: metrics.peek(f"bluefog.attn.{name}").value
    window, causal = flash.tile_counts(SEQ, flash.SlidingWindowMask(16)), flash.tile_counts(SEQ, True)
    slots = BATCH * 28
    assert peek("window_layers") == 3
    assert peek("window_tiles_live") == 3 * slots * window[0]
    assert peek("window_tiles_total") == 3 * slots * window[1]
    assert peek("tiles_live") == slots * (3 * window[0] + causal[0])
    assert peek("tiles_total") == 4 * slots * causal[1]
    assert peek("heads_held") == 28


def test_the_cells_gauges_count_each_layer_under_its_own_mask():
    """At the cell's shape (one sequence of 16 384, tiles of 1 024): the
    three window layers walk 70 tiles of 256 a head, the global layer 136."""
    cell = cells.load_cell("smallthinker_1chip_seq16k")
    job = load_module().Job(cell.config, cell.traffic)
    decoder._record_static_counts(job.cfg, 1, 16384, "causal")
    peek = lambda name: metrics.peek(f"bluefog.attn.{name}").value
    assert peek("window_layers") == 3
    assert peek("window_tiles_live") == 70 * 28 * 3 == 5880
    assert peek("window_tiles_total") == 256 * 28 * 3
    assert peek("tiles_live") == peek("grid_steps") == 28 * (3 * 70 + 136)
    assert peek("subtiles_live") == 28 * (3 * 252 + 528)
    assert peek("subtiles_masked") == 28 * (3 * 56 + 32)


@pytest.mark.parametrize("seq, window", [(1, 1), (10, 3), (100, 100), (100, 7), (64, 1000)])
def test_the_band_is_every_pair_the_window_allows(seq, window):
    pos = np.arange(seq)
    allowed = flash.SlidingWindowMask(window).allowed(pos[:, None], pos[None, :], xp=np)
    assert smallthinker_costs.band_pairs(seq, window) == allowed.sum()
    assert smallthinker_costs.band_pairs(seq, None) == (pos[:, None] >= pos[None, :]).sum()


def test_the_cells_counts_follow_from_its_sizes():
    cell = cells.load_cell("smallthinker_1chip_seq16k")
    src = cells.source_entry(cell.config)
    assert smallthinker_costs.attention_params(src) == 20971520
    assert smallthinker_costs.param_count(src, 64) == cell.config["n_params"] == 656529920
    model = cell.config["model"]
    assert model["router_init"] == "normal"
    assert smallthinker_costs.local_choices(src, model) == 1.5  # 6 x 16 / 64
    per_token = smallthinker_costs.matmul_params_per_token(src, model)
    assert per_token == cell.config["flops"]["matmul_params_per_token"] == 217169920
    assert abs(smallthinker_costs.flops_per_token(src, model, 16384) - 2.118e9) < 1e6
    assert smallthinker_costs.band_pairs(16384, 4096) / 16384 == 3584.125
    assert cell.config["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert len(src["sliding_window_layout"]) == len(src["rope_layout"]) == 52
    assert cell.traffic["batch_per_worker"] == 1 and cell.traffic["seq"] == 16384
    assert {"window_attn_ms", "window_kernel_roofline"} <= set(cell.per_layer)


def test_kernel_costs_name_the_kernels():
    cell = cells.load_cell("smallthinker_1chip_seq16k")
    job = load_module().Job(cell.config, cell.traffic)
    costs = job.kernel_costs()
    band, triangle = 58722304, 16384 * 16385 // 2
    assert costs["flash_window"]["flops"] == 3 * 7 * 2 * band * 28 * 128
    assert costs["flash_window"]["bytes"] == 3 * 6 * 16384 * 32 * 128 * 2
    assert costs["flash_window"]["scope"] == "bf.attn.window"
    assert costs["flash"]["flops"] == 7 * 2 * (3 * band + triangle) * 28 * 128
    for name in ("flash_window", "flash"):
        assert costs[name]["kernels"] == ["bf_flash_fwd", "bf_flash_dkv", "bf_flash_dq"]
    assert costs["moe_experts"]["kernels"] == ["bf_gmm", "bf_tgmm"]
    assert costs["moe_experts"]["flops"] == 4 * 9 * 2 * 16384 * 1.5 * 2560 * 768
    assert job.mosaic_calls == 64 and job.units_per_worker_step == 16384


def test_the_job_refuses_a_file_whose_counts_are_not_its_sizes():
    wrong = config()
    wrong["n_params"] += 1
    with pytest.raises(ValueError, match="n_params"):
        job_of(wrong)


def test_the_parameter_tree_is_what_the_counts_say():
    job = job_of(config())
    params, counts = jax.eval_shape(job.init, jax.random.PRNGKey(0))
    sizes = {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }
    assert sizes["layer_0/attn/q_proj/kernel"] == (32, 28 * 8)
    assert sizes["layer_1/attn/k_proj/kernel"] == (32, 4 * 8)
    assert "q_norm" not in str(sizes) and "gate_proj" not in str(sizes)
    assert sizes["layer_1/experts/router"] == (32, TOTAL)  # the full width
    assert sizes["layer_1/experts/w_gate"] == (16, 32, 12)
    total = sum(int(np.prod(s)) for s in sizes.values())
    assert total == smallthinker_costs.param_count(SRC, TOTAL)
    assert counts["act_zeros"].shape == (4,)


# a step's kernels as the compiled v5e step names them: a window layer's
# forward and dK/dV kernels, the global layer's forward, a grouped product
_PREFIX = "jit(bf_step)/bf.loss_grad/jvp(DecoderLM.hidden)"
_KERNEL_HLO = f"""HloModule jit_bf_step

ENTRY %main.1 (p.1: bf16[8]) -> bf16[8] {{
  %p.1 = bf16[8]{{0}} parameter(0)
  %custom-call.1 = bf16[8]{{0}} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={{op_name="{_PREFIX}/layer_1/bf.attn/bf.attn.window/attn/cond/branch_0_fun/jit(_flash)/bf_flash_fwd/pallas_call"}}
  %custom-call.2 = bf16[8]{{0}} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={{op_name="jit(bf_step)/bf.loss_grad/transpose(jvp(DecoderLM.hidden))/layer_2/bf.attn/bf.attn.window/attn/cond/branch_0_fun/jit(_flash)/bf_flash_dkv/pallas_call"}}
  %custom-call.3 = bf16[8]{{0}} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={{op_name="{_PREFIX}/layer_0/bf.attn/attn/cond/branch_0_fun/jit(_flash)/bf_flash_fwd/pallas_call"}}
  %custom-call.4 = bf16[8]{{0}} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={{op_name="{_PREFIX}/layer_1/bf.moe.experts/bf_gmm/pallas_call"}}
  ROOT %fusion.5 = bf16[8]{{0}} fusion(%p.1), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="{_PREFIX}/layer_1/bf.attn/bf.attn.window/attn/q_proj/dot_general"}}
}}
"""


class _WindowJob:
    def kernel_costs(self):
        return {"flash_window": {
            "flops": 197e12 * 40e-9, "bytes": 0, "scope": "bf.attn.window",
            "kernels": ["bf_flash_fwd", "bf_flash_dkv", "bf_flash_dq"],
        }}


def test_the_readers_take_the_window_layers_alone():
    from benchmarks.harness.trace_reduce import Op

    events = [
        Op("custom-call.1", "custom-call", 0, 30),
        Op("custom-call.2", "custom-call", 30, 50),
        Op("custom-call.3", "custom-call", 50, 150),
        Op("custom-call.4", "custom-call", 150, 250),
        Op("fusion.5", "fusion", 250, 260),
    ]
    run = chip_run(_KERNEL_HLO, events)
    run.job = _WindowJob()
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # own time under the window's scope: its two kernels and its projection
    assert bench.load_reader("window_attn_ms")(run) == pytest.approx(60e-6)
    # the least time, 40 ns, over the window layers' kernels' 50 ns
    assert bench.load_reader("window_kernel_roofline")(run) == pytest.approx(80.0)
    # a step without the scope reads nothing, and raises nothing
    bare = chip_run(_KERNEL_HLO.replace("bf.attn.window/", ""), events)
    bare.job, bare.peaks = _WindowJob(), run.peaks
    assert bench.load_reader("window_attn_ms")(bare) is None
    assert bench.load_reader("window_kernel_roofline")(bare) is None
    assert scopes.scope_of(smallthinker_costs.PARTS)(
        _PREFIX + "/layer_1/bf.attn/bf.attn.window/attn/o_proj/dot_general"
    ) == smallthinker_costs.WINDOW
