"""The expert layer that holds a share of the experts (``ops/moe.py``)
against a plain ``jax.numpy`` layer over all experts: the shares add up, no
row is lost at any imbalance, gradients agree, and the device counts say
what happened."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.ops import moe

N, D, F, E, K = 48, 16, 12, 16, 4


def weights(seed=0, experts=E):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(0.3 * rng.randn(*shape), jnp.float32)
    return {
        "u": mk(N, D), "router": mk(D, experts),
        "gate": mk(experts, D, F), "up": mk(experts, D, F), "down": mk(experts, F, D),
    }


def plain_layer(u, top, chosen, gate, up, down):
    """Every expert for every token, kept where it was chosen: the uncut
    layer, in plain jax.numpy."""
    with jax.default_matmul_precision("highest"):
        y = jnp.zeros_like(u)
        for e in range(gate.shape[0]):
            w = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
            y = y + w[:, None] * ((jax.nn.silu(u @ gate[e]) * (u @ up[e])) @ down[e])
        return y


def share(w, top, chosen, start, held):
    sl = slice(start, start + held)
    return moe.expert_layer(
        w["u"], top, chosen, w["gate"][sl], w["up"][sl], w["down"][sl],
        held_start=start,
    )


# Rows a held expert gets (the pattern repeats over the held experts), for
# what a pass bounded by the tiles in use (tiles of 8 rows off the TPU,
# chunks of 8 tiles) can get wrong. The rest of the pairs go elsewhere.
IMBALANCES = {
    "one-tile-each": [3, 1, 8, 5],   # exactly one tile in use a group
    "tile-boundary": [16, 8, 24, 0],  # groups that end on a tile's last row
    "ragged-chunks": [30, 17, 9, 12],  # 11 tiles in use of 4 groups: 1 3/8 chunks
}


def forced_choices(case, rng, start, held):
    """``chosen [N, K]`` with ``IMBALANCES[case]`` rows on each held expert
    and every other pair on an absent one; ``"all-held"``: every pair lands."""
    if case == "all-held":
        return np.stack([rng.permutation(held)[:K] for _ in range(N)]) + start
    want = np.resize(IMBALANCES[case], held)
    flat = np.full(N * K, (start + held) % E)
    flat[rng.permutation(N * K)[:want.sum()]] = np.repeat(np.arange(held), want) + start
    return flat.reshape(N, K)


def equations(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters, each
    with the names of the primitives it sits inside."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub, inside + (eqn.primitive.name,))


@pytest.mark.parametrize("shares", [1, 2, 4, 8, 16])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    w = weights()
    top, chosen = moe.route(w["u"], w["router"], K)
    held = E // shares
    total, landed = 0.0, 0
    for s in range(shares):
        y, counts = share(w, top, chosen, s * held, held)
        total = total + y
        landed += int(counts["rows_per_expert"].sum())
        assert int(counts["rows_absent"]) == N * K - int(counts["rows_per_expert"].sum())
        assert int(counts["rows_dropped"]) == 0
    assert landed == N * K  # every pair landed on exactly one share
    want = plain_layer(w["u"], top, chosen, w["gate"], w["up"], w["down"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-5, atol=2e-6)


def test_the_router_keeps_its_width_and_normalises_the_chosen():
    w = weights(1)
    top, chosen = moe.route(w["u"], w["router"], K)
    assert top.shape == chosen.shape == (N, K) and chosen.dtype == jnp.int32
    np.testing.assert_allclose(np.asarray(top.sum(-1)), 1.0, rtol=1e-6)
    probs = jax.nn.softmax(w["u"] @ w["router"], axis=-1)
    want = np.argsort(-np.asarray(probs), axis=-1)[:, :K]
    assert (np.sort(np.asarray(chosen), -1) == np.sort(want, -1)).all()
    raw, _ = moe.route(w["u"], w["router"], K, norm_topk_prob=False)
    np.testing.assert_allclose(
        np.asarray(raw), np.take_along_axis(np.asarray(probs), np.asarray(chosen), -1),
        rtol=1e-6,
    )


@pytest.mark.parametrize(
    "case", ["all-held", "one-expert", "two-experts", "none-held", *IMBALANCES]
)
def test_no_row_is_lost_whatever_the_router_does(case):
    """Every choice of every token forced onto the held range (the row
    buffer's worst case, nearly every tile in use), onto one expert, onto
    two, and onto none; and what stresses a pass bounded by the tiles in
    use (``IMBALANCES``)."""
    w = weights(2)
    held, start = 4, 8
    rng = np.random.RandomState(3)
    top = jnp.asarray(rng.dirichlet(np.ones(K), N), jnp.float32)
    if case == "all-held" or case in IMBALANCES:
        chosen = forced_choices(case, rng, start, held)
    elif case == "one-expert":
        chosen = np.full((N, K), start + 2)
    elif case == "two-experts":
        chosen = np.tile([start, start + 3, start, start + 3], (N, 1))
    else:
        chosen = np.stack([rng.permutation(start)[:K] for _ in range(N)])
    chosen = jnp.asarray(chosen, jnp.int32)
    y, counts = share(w, top, chosen, start, held)
    local = np.asarray(chosen).ravel() - start
    want_rows = np.bincount(local[(local >= 0) & (local < held)], minlength=held)
    if case in IMBALANCES:
        assert want_rows.tolist() == IMBALANCES[case]
    assert np.asarray(counts["rows_per_expert"]).tolist() == want_rows.tolist()
    assert int(counts["rows_dropped"]) == 0
    assert int(counts["rows_absent"]) == N * K - want_rows.sum()
    sl = slice(start, start + held)  # the plain layer over the held experts
    want = plain_layer(w["u"], top, chosen - start, w["gate"][sl], w["up"][sl], w["down"][sl])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-6)
    assert not np.asarray(y)[~((local >= 0) & (local < held)).reshape(N, K).any(1)].any()
    if case == "none-held":
        assert not np.asarray(y).any()  # a token with no held choice gets zeros


@pytest.mark.parametrize("imbalance", ["router", "all-held", *IMBALANCES])
@pytest.mark.parametrize("wrt", ["u", "gate", "up", "down", "top"])
def test_gradients_match_the_plain_layer(wrt, imbalance):
    w = weights(4)
    top, chosen = moe.route(w["u"], w["router"], K)
    start, held = 4, 8
    if imbalance != "router":
        chosen = jnp.asarray(
            forced_choices(imbalance, np.random.RandomState(5), start, held), jnp.int32
        )
    probe = jnp.cos(jnp.arange(N * D, dtype=jnp.float32)).reshape(N, D)

    def through(fn, x):
        args = dict(w, top=top)
        args[wrt] = x
        sl = slice(start, start + held)
        if fn is plain_layer:
            local = jnp.where(
                (chosen >= start) & (chosen < start + held), chosen - start, held
            )
            y = plain_layer(args["u"], args["top"], local, args["gate"][sl],
                            args["up"][sl], args["down"][sl])
        else:
            y, _ = moe.expert_layer(
                args["u"], args["top"], chosen, args["gate"][sl], args["up"][sl],
                args["down"][sl], held_start=start,
            )
        return jnp.sum(y * probe)

    x = top if wrt == "top" else w[wrt]
    got = jax.grad(lambda x: through(moe.expert_layer, x))(x)
    want = jax.grad(lambda x: through(plain_layer, x))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-5, atol=5e-6)


def test_moving_rows_is_a_gather_in_both_directions():
    """The backward pass of the whole layer holds no scatter: grouping is a
    permutation, and its transpose is a gather along the inverse. And it
    is one path: no branch on what landed."""
    w = weights(6)
    top, chosen = moe.route(w["u"], w["router"], K)
    grad = jax.grad(lambda u: moe.expert_layer(
        u, top, chosen, w["gate"][:2], w["up"][:2], w["down"][:2],
    )[0].sum())
    jaxpr = jax.make_jaxpr(grad)(w["u"])
    text = str(jaxpr)
    assert "scatter" not in text and "ragged_dot" in text
    # the primitive, not the word: a loop's jaxpr prints its `cond_jaxpr`
    primitives = {eqn.primitive.name for eqn, _ in equations(jaxpr.jaxpr)}
    assert "cond" not in primitives and "while" in primitives


@pytest.mark.parametrize("path", ["ragged_dot", "kernels"])
def test_no_pass_outside_a_loop_is_as_long_as_the_buffer(path):
    """In the gradient of the whole layer (all five) nothing that moves or
    touches rows — a gather, a select, a cast, a product, a sum — makes a
    value as long as the row buffer outside a loop over the tiles in use or
    a kernel. The one exception is named: the write that starts a buffer a
    loop fills (``_smeared``: a scalar broadcast), one each for the rows,
    the activation and the row sums of the combine's backward pass."""
    if path == "kernels":
        n, d, f, held, interpret = 256, 128, 128, 4, True
    else:
        n, d, f, held, interpret = N, D, F, 4, False
    rng = np.random.RandomState(8)
    mk = lambda *shape: jnp.asarray(0.1 * rng.randn(*shape), jnp.float32)
    top = jnp.asarray(rng.dirichlet(np.ones(K), n), jnp.float32)
    chosen = jnp.asarray(rng.randint(0, E, (n, K)), jnp.int32)
    tm = moe.row_tile(n * K, held, d, f, jnp.float32)
    assert tm == (128 if interpret else 8)
    rows = moe.buffer_tiles(n * K, held, tm) * tm
    assert rows >= 2 * 8 * tm  # more than a chunk of 8 tiles

    def loss(u, top, gate, up, down):
        return moe.expert_layer(u, top, chosen, gate, up, down, interpret=interpret)[0].sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        mk(n, d), top, mk(held, d, f), mk(held, d, f), mk(held, f, d)
    )
    passes = (
        "gather", "select_n", "convert_element_type", "mul", "add", "add_any",
        "broadcast_in_dim",
    )
    long, started, in_loops = [], [], 0
    for eqn, inside in equations(jaxpr.jaxpr):
        shapes = [getattr(v.aval, "shape", ()) for v in eqn.outvars]
        if not any(shape and shape[0] == rows for shape in shapes):
            continue
        if "while" in inside or "pallas_call" in inside:
            in_loops += 1
        elif eqn.primitive.name == "broadcast_in_dim" and eqn.invars[0].aval.shape == ():
            started.append(shapes[0][1:])
        elif eqn.primitive.name in passes:
            long.append(str(eqn))
    assert not long, long
    assert sorted(started) == sorted([(), (d,), (f,)]), started
    assert in_loops  # the buffers are written where the test looks away


@pytest.mark.parametrize("case", ["router", "all-held", "none-held", *IMBALANCES])
def test_tiles_in_use_is_the_layout_the_products_are_given(case, monkeypatch):
    """``tiles_in_use`` of the ``rows_per_expert`` a call returns is the
    number of tiles its grouped products and its loops went over: the
    layout's first that many tiles belong to the held experts in order,
    each as many as its rows take and at least one."""
    w = weights(9)
    held, start, tm = 4, 8, 8
    rng = np.random.RandomState(10)
    top, chosen = moe.route(w["u"], w["router"], K)
    if case == "none-held":
        chosen = chosen % start
    elif case != "router":
        chosen = jnp.asarray(forced_choices(case, rng, start, held), jnp.int32)
    seen = []
    product = moe.grouped_product

    def spy(static, lhs, rhs, tiles):
        seen.append((static[0], lhs.shape[0], *map(np.asarray, tiles)))
        return product(static, lhs, rhs, tiles)

    monkeypatch.setattr(moe, "grouped_product", spy)
    _, counts = share(w, top, chosen, start, held)
    rows = np.asarray(counts["rows_per_expert"])
    used = int(moe.tiles_in_use(rows, tm))
    assert used == sum(max(1, -(-int(r) // tm)) for r in rows)
    assert len(seen) == 3
    for tile, buffer_rows, tile_group, per_group in seen:
        assert tile == tm == moe.row_tile(N * K, held, D, F, jnp.float32)
        assert buffer_rows == moe.buffer_tiles(N * K, held, tm) * tm >= used * tm
        assert per_group.sum() == used
        assert tile_group[:used].tolist() == np.repeat(np.arange(held), per_group).tolist()
    # stacked over layers (or workers), as a caller's `aux` holds them
    stacked = moe.tiles_in_use(np.stack([rows, rows[::-1]]), tm)
    assert np.asarray(stacked).tolist() == [used, used]


PATHS = {  # tm, k, n, interpret: the kernels in the interpreter, and ragged_dot
    "kernels": (128, 128, 256, True), "ragged_dot": (8, D, F, False),
}


@pytest.mark.parametrize("tile_group, per_group", [
    ([0, 0, 1, 2, 3, 3, 3], [2, 1, 1, 2]),   # two tiles past the ones in use
    ([0, 1, 2, 3, 3, 3, 3], [1, 1, 1, 1]),   # every group its one tile
    ([0, 0, 0, 0, 1, 2, 3], [4, 1, 1, 1]),   # all in use
], ids=["uneven", "one_tile_each", "full"])
@pytest.mark.parametrize("wrt", ["forward", "lhs", "rhs"])
@pytest.mark.parametrize("path", list(PATHS))
def test_grouped_product_against_a_loop_over_tiles(path, wrt, tile_group, per_group):
    """Tile ``i`` of the rows times ``rhs[tile_group[i]]``, forward and
    both gradients, over the tiles in use only: what the product leaves
    in the later ones is never read."""
    tm, k, n, interpret = PATHS[path]
    rng = np.random.RandomState(11)
    tiles, used = len(tile_group), sum(per_group)
    lhs = rng.randn(tiles * tm, k).astype(np.float32)
    rhs = rng.randn(4, k, n).astype(np.float32)
    probe = rng.randn(tiles * tm, n).astype(np.float32)
    in_use = (np.arange(tiles * tm) < used * tm)[:, None]
    plan = (jnp.asarray(tile_group, jnp.int32), jnp.asarray(per_group, jnp.int32))

    def product(a, b):
        return jnp.where(in_use, moe.grouped_product((tm, interpret), a, b, plan), 0.0)

    def through(a, b):
        return jnp.sum(product(a, b) * probe)

    tile = lambda x, i: x[i * tm:(i + 1) * tm]
    with jax.default_matmul_precision("highest"):
        if wrt == "forward":
            got = product(jnp.asarray(lhs), jnp.asarray(rhs))
            want = [tile(lhs, i) @ rhs[tile_group[i]] for i in range(used)]
        elif wrt == "lhs":
            got = jnp.where(in_use, jax.grad(through)(jnp.asarray(lhs), jnp.asarray(rhs)), 0.0)
            want = [tile(probe, i) @ rhs[tile_group[i]].T for i in range(used)]
        else:
            got = jax.grad(through, argnums=1)(jnp.asarray(lhs), jnp.asarray(rhs))
            want = [
                sum(tile(lhs, i).T @ tile(probe, i) for i in range(used) if tile_group[i] == g)
                for g in range(4)
            ]
    want = np.concatenate(want) if wrt != "rhs" else np.stack(want)
    got = np.asarray(got)[:want.shape[0]] if wrt != "rhs" else np.asarray(got)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)


@pytest.mark.parametrize("case", ["router", "every_choice_held", "nothing_held"])
def test_the_kernels_in_the_interpreter_give_the_layer_ragged_dot_gives(case):
    """The whole layer at shapes the kernels tile (rows in tiles of 128),
    in the Pallas interpreter, against the plain layer: output and the
    gradient towards the tokens, at the router's own imbalance and with
    every choice (or none) on the held experts."""
    n, d, f, held = 64, 128, 128, 4
    rng = np.random.RandomState(5)
    mk = lambda *shape: jnp.asarray(0.1 * rng.randn(*shape), jnp.float32)
    u, gate, up, down = mk(n, d), mk(E, d, f), mk(E, d, f), mk(E, f, d)
    top = jnp.asarray(rng.dirichlet(np.ones(K), n), jnp.float32)
    low = {"router": 0, "every_choice_held": 0, "nothing_held": held}[case]
    high = {"router": E, "every_choice_held": held, "nothing_held": E}[case]
    chosen = jnp.asarray(
        np.stack([low + rng.permutation(high - low)[:K] for _ in range(n)]), jnp.int32
    )
    assert moe.row_tile(n * K, held, d, f, jnp.float32) == 128

    def loss(layer, u):
        y = layer(u)
        return jnp.sum(y * jnp.cos(jnp.arange(d, dtype=jnp.float32))), y

    kernels = lambda u: moe.expert_layer(
        u, top, chosen, gate[:held], up[:held], down[:held], interpret=True
    )[0]
    local = jnp.where(chosen < held, chosen, held)  # absent: no expert of the plain layer
    plain = lambda u: plain_layer(u, top, local, gate[:held], up[:held], down[:held])
    (_, y), g = jax.value_and_grad(lambda u: loss(kernels, u), has_aux=True)(u)
    (_, want_y), want_g = jax.value_and_grad(lambda u: loss(plain, u), has_aux=True)(u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(want_g), rtol=1e-4, atol=1e-5)
    if case == "nothing_held":
        assert not np.asarray(y).any()


@pytest.mark.parametrize("platform, kernels", [("tpu", 9), ("cpu", 0)])
def test_the_layer_lowers_to_the_mosaic_kernels_on_the_tpu_only(platform, kernels):
    """Where the shapes tile, a TPU lowering of the layer's gradient calls
    the grouped-product kernels — 3 forward, and for each a product
    towards the rows and one towards the weights — and any other platform
    XLA's ``ragged_dot`` on the same layout; shapes that do not tile take
    ``ragged_dot`` anywhere."""
    n, d, f, held = 64, 128, 256, 2
    rng = np.random.RandomState(3)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    u, gate, up, down = mk(n, d), mk(held, d, f), mk(held, d, f), mk(held, f, d)
    top = jnp.full((n, K), 1.0 / K, jnp.float32)
    chosen = jnp.asarray(rng.randint(0, E, (n, K)), jnp.int32)
    assert moe.row_tile(n * K, held, d, f, jnp.bfloat16) == 128
    assert moe.row_tile(N * K, 2, D, F, jnp.float32) == 8

    def loss(*args):
        return moe.expert_layer(args[0], args[1], chosen, *args[2:])[0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(
        u, top, gate, up, down
    ).lower(lowering_platforms=(platform,)).as_text()
    names = re.findall(r'kernel_name = "(\w+)"', text)
    assert sorted(names) == ["bf_gmm"] * (kernels * 2 // 3) + ["bf_tgmm"] * (kernels // 3)
    assert text.count("tpu_custom_call") == kernels


def test_the_layer_computes_in_the_dtype_it_is_told():
    w = weights(7)
    top, chosen = moe.route(w["u"], w["router"], K)
    y, _ = moe.expert_layer(
        w["u"], top, chosen, w["gate"], w["up"], w["down"], dtype=jnp.bfloat16
    )
    assert y.dtype == jnp.bfloat16
    want = plain_layer(w["u"], top, chosen, w["gate"], w["up"], w["down"])
    err = np.abs(np.asarray(y, np.float32) - np.asarray(want)).max()
    assert 1e-4 < err < 0.05 * np.abs(np.asarray(want)).max()
