"""The chunked gated delta rule (``ops/delta.py``) against its token
recurrence, at toy widths: forward and all five gradients, the Pallas
kernels in the interpreter and the ``lax.scan`` path the CPU takes, at
``beta`` near 0 and near 2, at gates as strong as the cell's initial ones
(``A`` = 16, ``dt`` = 0.1: -1.6 a token) and far beyond, and at lengths the
chunk does not divide (padded)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.ops import delta

B, H, D = 2, 3, 16

# (name, beta's range, -g's range a token)
CASES = [
    ("beta_near_0", (0.0, 0.02), (0.0, 0.2)),
    ("beta_near_2", (1.95, 2.0), (0.0, 0.2)),
    ("cell_gates", (0.0, 2.0), (1.2, 1.6)),  # A = 16, dt = 0.1 and a little less
    ("beyond", (0.0, 2.0), (5.0, 40.0)),  # e^{G} of a chunk far below float32's range
]


def inputs(key, t, beta_range, decay_range, d=D, b=B, h=H):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, d))) / np.sqrt(d)
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    g = -jax.random.uniform(ks[3], (b, t, h, d), minval=decay_range[0], maxval=decay_range[1])
    beta = jax.random.uniform(ks[4], (b, t, h), minval=beta_range[0], maxval=beta_range[1])
    return q, k, v, g, beta


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-30)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * scale, np.abs(got - want).max() / scale


@pytest.mark.parametrize("interpret", [False, True], ids=["scan", "kernels"])
@pytest.mark.parametrize("t", [64, 100])
@pytest.mark.parametrize("name, beta_range, decay_range", CASES, ids=[c[0] for c in CASES])
def test_forward_matches_the_token_recurrence(name, beta_range, decay_range, t, interpret):
    args = inputs(0, t, beta_range, decay_range)
    want = delta.gated_delta_reference(*args)
    got = jax.jit(lambda *a: delta.chunk_gated_delta(*a, interpret=interpret))(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    close(got, want, 2e-5)


@pytest.mark.parametrize("interpret", [False, True], ids=["scan", "kernels"])
@pytest.mark.parametrize("name, beta_range, decay_range", CASES, ids=[c[0] for c in CASES])
def test_all_five_gradients_match_the_token_recurrence(name, beta_range, decay_range, interpret):
    args = inputs(1, 100, beta_range, decay_range)
    weight = jax.random.normal(jax.random.PRNGKey(2), (B, 100, H, D))
    grads = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)
    ))(*args)
    want = grads(delta.gated_delta_reference)
    got = grads(lambda *a: delta.chunk_gated_delta(*a, interpret=interpret))
    for name_, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape, name_
        close(a, b, 5e-5)


@pytest.mark.parametrize("t", [128, 100])
@pytest.mark.parametrize("name, beta_range, decay_range", CASES, ids=[c[0] for c in CASES])
def test_the_fused_forward_kernel_matches_the_xla_chunk_operands(name, beta_range, decay_range, t):
    """``bf_delta_fwd`` builds its chunk operands itself (in VMEM on the
    chip, here in the interpreter): the same ``o`` and the same states
    entering each chunk as the CPU's ``_fwd_scan(*_intra(...))``."""
    folded = [delta._fold(x) for x in inputs(5, t, beta_range, decay_range)]
    o, z = jax.jit(lambda *a: delta._fwd_call(*a, interpret=True))(*folded)
    want_o, want_z = jax.jit(delta._fwd_xla)(*folded)
    assert o.shape == want_o.shape and z.shape == want_z.shape == (B * H, 2, D, D)
    close(o, want_o, 2e-5)
    close(z, want_z, 2e-5)


@pytest.mark.parametrize("name, beta_range, decay_range", CASES, ids=[c[0] for c in CASES])
def test_the_chunk_derivative_is_jaxs_derivative_of_the_chunk(name, beta_range, decay_range):
    """``_chunk_grads`` (the backward kernel's body, written out) against
    jax's derivative of the forward kernel's chunk: ``_chunk_operands`` then
    ``_chunk_forward``, from the states entering it."""
    q, k, v, g, beta = (
        delta._fold(x)[:, 0] for x in inputs(7, delta.CHUNK, beta_range, decay_range)
    )
    beta = beta[:, None]
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    z, dz = (jax.random.normal(key, (B * H, D, D)) for key in ks[:2])
    do = jax.random.normal(ks[2], (B * H, delta.CHUNK, D))
    chunk = lambda q, k, v, g, beta, z: delta._chunk_forward(
        z, *delta._chunk_operands(q, k, v, g, beta)
    )
    _, pull = jax.vjp(chunk, q, k, v, g, beta, z)
    *want, want_dz = jax.jit(pull)((do, dz))
    got_dz, *got = jax.jit(delta._chunk_grads)(q, k, v, g, beta, z, dz, do)
    for name_, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape, name_
        close(a, b, 5e-5)
    close(got_dz, want_dz, 5e-5)


_fused_bwd = jax.jit(lambda *a: delta._bwd_call(*a, interpret=True))
_xla_bwd = jax.jit(delta._bwd_xla)


def check_fused_backward(key, t, beta_range, decay_range, tol, b=B, h=H):
    folded = [delta._fold(x) for x in inputs(key, t, beta_range, decay_range, b=b, h=h)]
    _, z = jax.jit(delta._fwd_xla)(*folded)
    do = delta._fold(jax.random.normal(jax.random.PRNGKey(key + 1), (b, t, h, D)))
    got = _fused_bwd(*folded, z, do)
    want = _xla_bwd(*folded, z, do)
    for name_, a, b_ in zip("q k v g beta".split(), got, want):
        assert a.shape == b_.shape, name_
        close(a, b_, tol)


@pytest.mark.parametrize("t", [128, 100])
@pytest.mark.parametrize("name, beta_range, decay_range", CASES, ids=[c[0] for c in CASES])
def test_the_fused_backward_kernel_matches_the_xla_derivative(name, beta_range, decay_range, t):
    """``bf_delta_bwd`` gives ``dq, dk, dv, dg, dbeta`` itself (in VMEM on
    the chip, here in the interpreter): the same as the CPU's branch, jax's
    derivative of ``_intra`` around ``_bwd_scan``. Beyond the cell's gates
    each path's ``dg`` lies up to 6e-5 of the largest from the float64
    token recurrence (the fused one the nearer), so the two may differ by
    twice that there."""
    check_fused_backward(9, t, beta_range, decay_range, 1.2e-4 if name == "beyond" else 2e-5)


@pytest.mark.parametrize("heads", [1, 2, 8])
def test_the_fused_backward_kernel_at_one_two_and_four_heads_a_step(heads):
    check_fused_backward(11, 100, (0.0, 2.0), (1.2, 1.6), 2e-5, b=1, h=heads)


def test_the_kernels_solve_holds_where_every_key_is_the_same():
    """The in-VMEM solve (block forward substitution) at the worst-conditioned
    system a chunk can hold: one key repeated, ``beta`` = 2, almost no decay.
    Against the float64 solve, no further off than XLA's triangular solve."""
    c = delta.CHUNK
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    k = jnp.broadcast_to(jax.random.normal(ks[0], (1, D)), (c, D))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = jax.random.normal(ks[1], (c, D)) / 4
    v = jax.random.normal(ks[2], (c, D))
    for decay, b in ((0.0, 2.0), (0.01, 2.0), (0.05, 1.99)):
        g, beta = jnp.full((c, D), -decay), jnp.full((1, 1, c), b)
        _, w, ut, *_ = jax.jit(delta._chunk_operands)(q[None], k[None], v[None], g[None], beta)
        kk, vv, gcum = (np.asarray(x, np.float64) for x in (k, v, np.cumsum(np.asarray(g, np.float64), 0)))
        seen = np.arange(c)[:, None] > np.arange(c)[None, :]
        a = b * np.where(seen, (kk @ kk.T) * np.exp(-decay * (np.arange(c)[:, None] - np.arange(c)[None, :])), 0)
        x = np.linalg.solve(np.eye(c) + a, b * np.concatenate([kk * np.exp(gcum), vv], axis=1))
        close(w[0], x[:, :D], 1e-4)
        close(ut[0], x[:, D:], 1e-4)


def test_no_output_sees_a_later_token():
    args = inputs(3, 130, (0.0, 2.0), (0.0, 1.6))
    base = delta.chunk_gated_delta(*args)
    for j in (0, 63, 64, 129):
        moved = [x.at[:, j].add(0.5) for x in args]
        out = delta.chunk_gated_delta(*moved)
        np.testing.assert_array_equal(np.asarray(out[:, :j]), np.asarray(base[:, :j]))
        assert np.abs(np.asarray(out[:, j] - base[:, j])).max() > 1e-4


def test_the_in_chunk_pair_products_backward_is_their_derivative():
    """``_diag_dots``' written-out backward against jax's derivative of the
    same expression."""
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x, y = (jax.random.normal(k, (3, 16, 8)) for k in ks[:2])
    g = jnp.cumsum(-jax.random.uniform(ks[2], (3, 16, 8), maxval=3.0), axis=-2)
    w = jax.random.normal(ks[3], (3, 16, 16))
    for strict in (False, True):
        plain = lambda x, y, g: jnp.sum(
            x[..., :, None, :] * y[..., None, :, :] * delta._decay(g, g, strict), -1
        )
        want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(x, y, g)
        got = jax.grad(
            lambda *a: jnp.sum(delta._diag_dots(*a, strict) * w), argnums=(0, 1, 2)
        )(x, y, g)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_chunk_count():
    assert delta.chunk_count(8192) == 128 and delta.chunk_count(100) == 2
    assert delta.chunk_count(64) == 1 and delta.chunk_count(65) == 2
