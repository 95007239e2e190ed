# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""The gated delta rule (Kimi Delta Attention's recurrence) in chunks.

Per head, with a state ``S [dk, dv]`` that starts at 0, a per-channel
log-decay ``g_t [dk]`` (``alpha_t = exp(g_t)``) and a step size ``beta_t``::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(Kimi Linear, arXiv:2510.26692; Gated DeltaNet, arXiv:2412.06464;
``beta`` in (0, 2) is Grazzi et al. 2024, arXiv:2411.12537).
:func:`gated_delta_reference` is that recurrence token by token.

:func:`chunk_gated_delta` computes the same thing over chunks of ``C``
tokens in the UT / WY form (Yang et al. 2024, arXiv:2406.06484). With
``G_r`` the in-chunk cumulative log-decay and ``S_0`` the state entering a
chunk, ``S_r = Diag(e^{G_r}) S_0 + sum_{s<=r} Diag(e^{G_r - G_s}) k_s u_s^T``
where the ``u`` solve a unit lower-triangular system::

    A_rs = beta_r sum_c k_rc k_sc e^{G_rc - G_sc}      (s < r)
    P_rs =        sum_c q_rc k_sc e^{G_rc - G_sc}      (s <= r)
    [W | U~] = (I + A)^{-1} Diag(beta) [k * e^G | v]
    U   = U~ - W S_0
    O   = (q * e^G) S_0 + P U
    S_C = Diag(e^{G_C}) S_0 + (k * e^{G_C - G})^T U

Two parts, split where the work is parallel and where it is sequential:

- **Inside a chunk**: ``G``, ``A``, ``P``, the triangular solve and the
  four decayed operands. No factor is ever ``e^{+G}``: ``e^{G_r}`` is at
  most 1, and ``e^{G_r - G_s}`` for ``s <= r`` is built from the difference
  of the exponents — inside a sub-chunk of ``SUB`` tokens elementwise,
  between sub-chunks as a product of two factors that both decay, through
  the cumulative decay at the start of the row's sub-chunk. Nothing is
  clamped: at a decay of -1.6 a token (``A`` = 16, ``dt`` = 0.1) the
  factorised ``(q e^{G}) (k e^{-G})^T`` of a chunk of 64 would need
  ``e^{102}``, past float32. Where it runs: **on the TPU both kernels
  build them themselves**, a chunk at a time in VMEM from the folded ``q,
  k, v, g`` and ``beta`` (``_chunk_build``: ``G`` as a product with a
  triangle of ones, the pair products a row block of ``SUB`` at a time, the
  solve by block forward substitution), and the backward kernel takes their
  derivative there too (``_chunk_grads``: the solve's by block back
  substitution, the pair products' by ``_diag_dots_bwd``'s rule, the
  cumulative sum's as a product with the transposed triangle). Off the TPU
  ``_intra`` builds them in plain XLA over every chunk at once and jax
  differentiates it (``_diag_dots``' backward is written out so that the
  ``[SUB, SUB, dk]`` exponentials stay inside their fusions).
- **Across chunks** (the recurrence in ``S``): the Pallas kernel
  ``bf_delta_fwd``, grid (batch x heads ÷ ``STEP_HEADS``, chunks), chunks in
  order, the states in VMEM, float32; it writes the state entering every
  chunk for the backward kernel ``bf_delta_bwd``, which walks the chunks in
  reverse with ``dS`` in VMEM and gives ``dq, dk, dv, dg, dbeta``.

Everything is float32, matrix products at ``Precision.HIGHEST``. Off the TPU
the same two recurrences run as ``lax.scan`` (``_fwd_scan`` /
``_bwd_scan``); ``interpret=True`` runs the kernels in the Pallas
interpreter (CPU CI), as ``ops/flash.py`` does. A length that the chunk
does not divide is padded with steps that change nothing (``k = v = 0``,
``beta = 0``, ``g = 0``).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["chunk_gated_delta", "gated_delta_reference", "chunk_count"]

CHUNK = 64
SUB = 16  # tokens whose mutual decays are built elementwise; divides CHUNK
# heads a step of either kernel takes at most: a chunk's work is chains of
# small dependent products, and several heads' chains side by side keep the
# core busy (one layer of the solar2 cell on a v5e: the forward kernel 3.10
# ms with one head a step, 1.87 with four, 1.82 with eight; the backward
# 5.61 with two, 5.24 with four, and eight overflow the scoped VMEM)
STEP_HEADS = 4
_HI = jax.lax.Precision.HIGHEST


def _einsum(spec, *xs):
    return jnp.einsum(spec, *xs, precision=_HI, preferred_element_type=jnp.float32)


def chunk_count(t):
    """Chunks one head of a sequence of ``t`` tokens is cut into."""
    return -(-t // CHUNK)


# -- the plain recurrence ------------------------------------------------------


def gated_delta_reference(q, k, v, g, beta):
    """The token recurrence, float32: ``q, k, g [b, t, h, dk]``, ``v [b, t,
    h, dv]``, ``beta [b, t, h]`` -> ``o [b, t, h, dv]``."""
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)

    def step(s, xs):
        q, k, v, g, beta = xs  # [b, h, ...]
        s = jnp.exp(g)[..., None] * s
        err = v - _einsum("bhkv,bhk->bhv", s, k)
        s = s + beta[..., None, None] * k[..., None] * err[..., None, :]
        return s, _einsum("bhkv,bhk->bhv", s, q)

    b, _, h, dk = q.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(map(f32, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)


# -- inside a chunk --------------------------------------------------------------


def _decay(g_r, g_s, strict):
    """``e^{G_r - G_s}`` for ``s <= r`` (``s < r`` if ``strict``), 0 above:
    ``g [..., SUB, dk]`` -> ``[..., SUB (r), SUB (s), dk]``."""
    n = g_r.shape[-2]
    r = jnp.arange(n)[:, None, None]
    s = jnp.arange(n)[None, :, None]
    seen = s < r if strict else s <= r
    diff = g_r[..., :, None, :] - g_s[..., None, :, :]
    return jnp.exp(jnp.where(seen, diff, -jnp.inf))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _diag_dots(x, y, g, strict):
    """``sum_c x_rc y_sc e^{g_rc - g_sc}`` over the pairs of one sub-chunk
    (``[..., SUB, dk]`` each -> ``[..., SUB, SUB]``), exact for any decay."""
    return jnp.sum(x[..., :, None, :] * y[..., None, :, :] * _decay(g, g, strict), -1)


def _diag_dots_fwd(x, y, g, strict):
    return _diag_dots(x, y, g, strict), (x, y, g)


def _diag_dots_bwd(strict, res, dm):
    x, y, g = res
    e = _decay(g, g, strict) * dm[..., None]
    dx = jnp.sum(e * y[..., None, :, :], -2)
    dy = jnp.sum(e * x[..., :, None, :], -3)
    return dx, dy, x * dx - y * dy


_diag_dots.defvjp(_diag_dots_fwd, _diag_dots_bwd)


def _pair_products(x, y, gb, strict):
    """``sum_c x_rc y_sc e^{G_rc - G_sc}`` over a chunk, ``s <= r`` (``s <
    r`` if ``strict``), 0 above: ``x, y, gb [..., m, SUB, dk]`` (the chunk
    in ``m`` sub-chunks) -> ``[..., C, C]``. Between sub-chunks ``i > j``
    through ``R_i``, the cumulative decay before sub-chunk ``i`` starts:
    ``(x_r e^{G_r - R_i}) . (y_s e^{R_i - G_s})``, both factors at most 1."""
    *lead, m, sub, dk = x.shape
    c = m * sub
    ref = jnp.concatenate(
        [jnp.zeros_like(gb[..., :1, -1, :]), gb[..., :-1, -1, :]], axis=-2
    )  # [..., m, dk]
    left = x * jnp.exp(gb - ref[..., None, :])
    flat = lambda a: a.reshape(*lead, c, dk)
    s_block = jnp.arange(c) // sub  # [C]
    before = s_block[None, :, None] < jnp.arange(m)[:, None, None]  # [m, C, 1]
    right = flat(y)[..., None, :, :] * jnp.exp(
        jnp.where(before, ref[..., :, None, :] - flat(gb)[..., None, :, :], -jnp.inf)
    )  # [..., m, C, dk]
    off = _einsum("...ird,...isd->...irs", left, right).reshape(*lead, c, c)
    diag = _diag_dots(x, y, gb, strict)  # [..., m, SUB, SUB]
    eye = jnp.eye(m, dtype=diag.dtype)
    block = (diag[..., :, :, None, :] * eye[:, None, :, None]).reshape(*lead, c, c)
    return off + block


def _intra(q, k, v, g, beta):
    """The chunk operands of the scan, every chunk at once in XLA, from
    ``[BH, N, C, d]`` inputs and ``beta [BH, N, C]``: ``q e^G``, ``W``,
    ``U~``, ``P``, ``k e^{G_C - G}`` and ``e^{G_C} [BH, N, 1, dk]``."""
    bh, n, c, dk = q.shape
    m = c // SUB
    gcum = jnp.cumsum(g, axis=2)
    sub = lambda a: a.reshape(bh, n, m, SUB, a.shape[-1])
    qb, kb, gb = sub(q), sub(k), sub(gcum)
    p = _pair_products(qb, kb, gb, strict=False)
    a = beta[..., None] * _pair_products(kb, kb, gb, strict=True)
    rhs = beta[..., None] * jnp.concatenate([k * jnp.exp(gcum), v], axis=-1)
    x = jax.lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True
    )
    last = gcum[:, :, -1:, :]
    return (
        q * jnp.exp(gcum), x[..., :dk], x[..., dk:], p,
        k * jnp.exp(last - gcum), jnp.exp(last),
    )


# -- across chunks: the recurrence in S (kept transposed, Z = S^T [dv, dk]) ------


def _mm(a, b, contract=(1, 0)):
    """A matrix product over the last two axes (``contract`` counts from
    the first of them), batched over any leading ones."""
    lead = tuple(range(a.ndim - 2))
    return jax.lax.dot_general(
        a, b, (((contract[0] + len(lead),), (contract[1] + len(lead),)), (lead, lead)),
        precision=_HI, preferred_element_type=jnp.float32,
    )


def _chunk_forward(z, qt, w, ut, p, kh, gam):
    """One chunk: the state entering it ``z`` -> ``(o, z after)``."""
    u = ut - _mm(w, z, (1, 1))
    o = _mm(qt, z, (1, 1)) + _mm(p, u)
    return o, z * gam + _mm(jnp.swapaxes(u, -1, -2), kh)


def _chunk_backward(z, dz, qt, w, ut, p, kh, gam, do):
    """One chunk in reverse: ``dz`` is the cotangent of the state leaving
    it -> ``(cotangent of the state entering it, dqt, dw, dut, dp, dkh,
    dgam)``."""
    t = lambda a: jnp.swapaxes(a, -1, -2)
    u = ut - _mm(w, z, (1, 1))
    du = _mm(t(p), do) + _mm(kh, dz, (1, 1))
    dz_in = dz * gam + _mm(t(do), qt) - _mm(t(du), w)
    return (
        dz_in, _mm(do, z), -_mm(du, z), du, _mm(do, u, (1, 1)), _mm(u, dz),
        jnp.sum(dz * z, axis=-2, keepdims=True),
    )


def _fwd_scan(qt, w, ut, p, kh, gam):
    bh, _, _, dk = qt.shape
    dv = ut.shape[-1]
    step = jax.vmap(_chunk_forward)

    def body(z, xs):
        o, z_next = step(z, *xs)
        return z_next, (o, z)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (qt, w, ut, p, kh, gam))
    # zeros_like: the carry varies over the mesh axes its inputs do
    z0 = jnp.zeros_like(qt, shape=(bh, dv, dk))
    _, (o, z) = jax.lax.scan(body, z0, xs)
    return jnp.moveaxis(o, 0, 1), jnp.moveaxis(z, 0, 1)


def _bwd_scan(qt, w, ut, p, kh, gam, z, do):
    bh, _, _, dk = qt.shape
    dv = ut.shape[-1]
    step = jax.vmap(_chunk_backward)

    def body(dz, xs):
        dz_in, *grads = step(xs[0], dz, *xs[1:])
        return dz_in, tuple(grads)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (z, qt, w, ut, p, kh, gam, do))
    dz0 = jnp.zeros_like(qt, shape=(bh, dv, dk))
    _, grads = jax.lax.scan(body, dz0, xs, reverse=True)
    return tuple(jnp.moveaxis(a, 0, 1) for a in grads)


# -- inside a chunk, in VMEM: ``_intra`` and its derivative for the kernels ----------


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _chunk_build(q, k, v, g, beta):
    """``_intra`` for one chunk of each of a few heads: ``q, k, v, g [H, C,
    d]``, ``beta [H, 1, C]`` -> ``(G, beta as a column [H, C, 1], P, X = [W |
    U~], kk)``, with the same exactness rule, a row block of ``SUB`` tokens at
    a time. ``kk`` holds ``sum_c k_rc k_sc e^{G_rc - G_sc}`` (``s < r``, no
    ``beta``) as the solve made it, a row block each: the rows before the
    block (``None`` in the first) and the block's own columns ``[H, SUB,
    1]``. The solve is block forward substitution: ``X_i = (I + A_ii)^{-1}
    (Y_i - A_i,<i X_<i)``, the block's own rows eliminated one at a time (a
    row of ``X`` is final once the rows above it are subtracted)."""
    heads, c, dk = q.shape
    m = c // SUB
    f32 = jnp.float32
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    tri = jnp.broadcast_to((col <= row).astype(f32), (heads, c, c))
    gcum = _mm(tri, g)  # the cumulative decay on the MXU
    beta_col = jnp.sum(jnp.where(row == col, beta, 0.0), axis=2, keepdims=True)
    y = beta_col * jnp.concatenate([k * jnp.exp(gcum), v], axis=2)
    tok, sub, lane = _iota((c, dk), 0), _iota((SUB, dk), 0), _iota((SUB, c), 1)
    p_rows, xs, kk = [], [], []
    for i in range(m):
        rows = slice(i * SUB, (i + 1) * SUB)
        qb, kb, gb, bb = q[:, rows], k[:, rows], gcum[:, rows], beta_col[:, rows]
        pb, z, kk_off = jnp.zeros((heads, SUB, c), f32), y[:, rows], None
        if i:  # the sub-chunks before: two factors that both decay, through R_i
            ref = gcum[:, i * SUB - 1:i * SUB]
            left = jnp.exp(gb - ref)
            right = k * jnp.exp(jnp.where(tok < i * SUB, ref - gcum, -jnp.inf))
            off = _mm(jnp.concatenate([qb * left, kb * left], axis=1), right, (1, 1))
            # Y_i - A_i,<i X_<i: A_i,<i is beta times the keys' rows of off
            done = jnp.concatenate(xs + [jnp.zeros((heads, (m - i) * SUB, 2 * dk), f32)], axis=1)
            pb, kk_off = off[:, :SUB], off[:, SUB:]
            z = z - _mm(bb * kk_off, done)
        kk_cols = []
        for j in range(SUB):  # the diagonal block, a column s at a time
            s = i * SUB + j
            e = jnp.exp(jnp.where(sub >= j, gb - gcum[:, s:s + 1], -jnp.inf)) * k[:, s:s + 1]
            pb = jnp.where(lane == s, jnp.sum(qb * e, axis=2, keepdims=True), pb)
            kk_cols.append(jnp.where(sub[:, :1] > j, jnp.sum(kb * e, axis=2, keepdims=True), 0.0))
        a_cols = [bb * a for a in kk_cols]  # A[., s] over the block
        for j in range(SUB - 1):
            z = z - a_cols[j] * z[:, j:j + 1]
        p_rows.append(pb)
        xs.append(z)
        kk.append((kk_off, kk_cols))
    return gcum, beta_col, jnp.concatenate(p_rows, axis=1), jnp.concatenate(xs, axis=1), kk


def _decays(gcum):
    """``(e^G, e^{G_C - G}, e^{G_C} [H, 1, dk])``."""
    last = gcum[:, -1:]
    return jnp.exp(gcum), jnp.exp(last - gcum), jnp.exp(last)


def _chunk_operands(q, k, v, g, beta):
    """The forward kernel's chunk operands (``_chunk_build``): ``(q e^G, W,
    U~, P, k e^{G_C - G}, e^{G_C} [H, 1, dk])``."""
    dk = q.shape[-1]
    gcum, _, p, x, _ = _chunk_build(q, k, v, g, beta)
    eg, ec, gam = _decays(gcum)
    return q * eg, x[..., :dk], x[..., dk:], p, k * ec, gam


def _chunk_grads(q, k, v, g, beta, z, dz, do):
    """One chunk of each of a few heads in reverse, from its raw inputs:
    ``z`` the states entering it, ``dz`` the cotangent of those leaving it
    ``[H, dv, dk]``, ``do [H, C, dv]`` -> ``(cotangent of the states entering
    it, dq, dk, dv, dg, dbeta [H, 1, C])``. The operands are built again
    (``_chunk_build``), the recurrence's step taken back (``_chunk_backward``),
    then ``_intra``'s derivative written out under the same exactness rule:
    the solve's by block back substitution from the last row block up (``dY =
    (I + A)^{-T} dX``), the pair products' by ``_diag_dots_bwd``'s rule
    (between sub-chunks through ``R_i`` on the MXU, inside one from the
    exponents' differences a column at a time), the cumulative decay's as a
    product with the transposed triangle of ones."""
    heads, c, dk = q.shape
    m = c // SUB
    f32 = jnp.float32
    gcum, beta_col, p, x, kk = _chunk_build(q, k, v, g, beta)
    eg, ec, gam = _decays(gcum)
    qt, kh = q * eg, k * ec
    dz_in, dqt, dw, dut, dp, dkh, dgam = _chunk_backward(
        z, dz, qt, x[..., :dk], x[..., dk:], p, kh, gam, do
    )
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    tok, sub, lane = _iota((c, dk), 0), _iota((SUB, dk), 0), _iota((SUB, c), 1)
    # the keys' pair products without beta, and A, as [H, C, C]
    mkk = []
    for i, (kk_off, kk_cols) in enumerate(kk):
        rows = jnp.zeros((heads, SUB, c), f32) if kk_off is None else kk_off
        for j, a in enumerate(kk_cols):
            rows = jnp.where(lane == i * SUB + j, a, rows)
        mkk.append(rows)
    mkk = jnp.concatenate(mkk, axis=1)
    at = jnp.swapaxes(beta_col * mkk, -1, -2)
    # dY = (I + A)^{-T} dX: unit upper triangular, from the last row up
    dx = jnp.concatenate([dw, dut], axis=2)
    dys = []
    for i in reversed(range(m)):
        rows = slice(i * SUB, (i + 1) * SUB)
        atb, zb = at[:, rows], dx[:, rows]  # atb[r', s] = A[s, r']
        if dys:
            done = jnp.concatenate([jnp.zeros((heads, (i + 1) * SUB, 2 * dk), f32)] + dys, axis=1)
            zb = zb - _mm(atb, done)
        a_rows = [
            jnp.sum(jnp.where(lane == i * SUB + j, atb, 0.0), axis=2, keepdims=True)
            for j in range(SUB)
        ]
        for j in reversed(range(1, SUB)):
            zb = zb - a_rows[j] * zb[:, j:j + 1]
        dys.insert(0, zb)
    dy = jnp.concatenate(dys, axis=1)
    da = -jnp.where(col < row, _mm(dy, x, (1, 1)), 0.0)
    dmkk = beta_col * da
    kg = k * eg
    dbeta = (
        jnp.sum(dy * jnp.concatenate([kg, v], axis=2), axis=2, keepdims=True)
        + jnp.sum(da * mkk, axis=2, keepdims=True)
    )
    dkg = beta_col * dy[..., :dk]  # the cotangent of k e^G
    # the pair products: P with dP (s <= r), the keys' with dM (s < r)
    dxq, dxk, dyk_diag = [], [], []
    dyk = jnp.zeros((heads, c, dk), f32)
    for i in range(m):
        rows = slice(i * SUB, (i + 1) * SUB)
        qb, kb, gb = q[:, rows], k[:, rows], gcum[:, rows]
        dpb, dmb = dp[:, rows], dmkk[:, rows]
        dq_b = dk_b = jnp.zeros((heads, SUB, dk), f32)
        if i:  # the sub-chunks before, through R_i
            ref = gcum[:, i * SUB - 1:i * SUB]
            left = jnp.exp(gb - ref)
            ex = jnp.exp(jnp.where(tok < i * SUB, ref - gcum, -jnp.inf))
            both = jnp.concatenate([dpb, dmb], axis=1)
            t = _mm(both, k * ex)
            dq_b, dk_b = left * t[:, :SUB], left * t[:, SUB:]
            dyk = dyk + ex * _mm(
                jnp.swapaxes(both, -1, -2),
                jnp.concatenate([qb * left, kb * left], axis=1),
            )
        dy_b = jnp.zeros((heads, SUB, dk), f32)
        for j in range(SUB):  # the diagonal block, a column s at a time
            s = i * SUB + j
            e = jnp.exp(jnp.where(sub >= j, gb - gcum[:, s:s + 1], -jnp.inf))
            cp = jnp.sum(jnp.where(lane == s, dpb, 0.0), axis=2, keepdims=True)
            cm = jnp.sum(jnp.where(lane == s, dmb, 0.0), axis=2, keepdims=True)
            ek = e * k[:, s:s + 1]
            dq_b = dq_b + cp * ek
            dk_b = dk_b + cm * ek
            dy_b = jnp.where(
                sub == j, jnp.sum((cp * qb + cm * kb) * e, axis=1, keepdims=True), dy_b
            )
        dxq.append(dq_b)
        dxk.append(dk_b)
        dyk_diag.append(dy_b)
    dxq = jnp.concatenate(dxq, axis=1)
    dxk = jnp.concatenate(dxk, axis=1)
    dyk = dyk + jnp.concatenate(dyk_diag, axis=1)
    # the decayed operands, then G = tri g taken back
    dgcum = dkg * kg + q * dxq + k * (dxk - dyk) + dqt * qt - dkh * kh
    # e^{G_C} and k e^{G_C - G} through G_C: added to the last row first
    dlast = jnp.sum(dkh * kh, axis=1, keepdims=True) + dgam * gam
    dgcum = dgcum + jnp.where(tok == c - 1, dlast, 0.0)
    triu = jnp.broadcast_to((col >= row).astype(f32), (heads, c, c))
    return (
        dz_in,
        dqt * eg + dxq,
        dkg * eg + dxk + dyk + dkh * ec,
        beta_col * dy[..., dk:],
        _mm(triu, dgcum),
        jnp.sum(jnp.where(row == col, dbeta, 0.0), axis=1, keepdims=True),
    )


# -- the kernels -------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, zs_ref, z_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        z_ref[:] = jnp.zeros_like(z_ref)

    z = z_ref[:]
    zs_ref[:, 0] = z
    operands = _chunk_operands(
        q_ref[:, 0], k_ref[:, 0], v_ref[:, 0], g_ref[:, 0], beta_ref[:, 0]
    )
    o, z_next = _chunk_forward(z, *operands)
    o_ref[:, 0] = o
    z_ref[:] = z_next


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, zs_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dz_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        dz_ref[:] = jnp.zeros_like(dz_ref)

    dz_in, *grads = _chunk_grads(
        q_ref[:, 0], k_ref[:, 0], v_ref[:, 0], g_ref[:, 0], beta_ref[:, 0],
        zs_ref[:, 0], dz_ref[:], do_ref[:, 0],
    )
    for ref, grad in zip((dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref), grads):
        ref[:, 0] = grad
    dz_ref[:] = dz_in


def _call(kernel, name, inputs, outputs, reverse, interpret):
    """``pallas_call`` over (batch x heads ÷ ``STEP_HEADS``, chunks), one
    chunk's block of every ``[BH, N, a, b]`` operand a step for ``STEP_HEADS``
    heads (fewer where they do not divide ``BH``), the chunks in order (or in
    reverse), a float32 ``[dv, dk]`` carry a head in VMEM."""
    bh, n, _, dk = inputs[0].shape
    heads = math.gcd(bh, STEP_HEADS)
    dv = inputs[2].shape[-1]
    chunk = (lambda b, i: (b, n - 1 - i, 0, 0)) if reverse else (
        lambda b, i: (b, i, 0, 0)
    )
    spec = lambda shape: pl.BlockSpec((heads, 1, *shape[2:]), chunk)

    vma = jax.typeof(inputs[0]).vma
    return pl.pallas_call(
        kernel,
        grid=(bh // heads, n),
        in_specs=[spec(x.shape) for x in inputs],
        out_specs=tuple(spec(s) for s in outputs),
        out_shape=tuple(
            jax.ShapeDtypeStruct(s, jnp.float32, vma=vma) for s in outputs
        ),
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name=name,
    )(*inputs)


def _fwd_call(q, k, v, g, beta, interpret=False):
    bh, n, c, dk = q.shape
    dv = v.shape[-1]
    return _call(
        _fwd_kernel, "bf_delta_fwd", (q, k, v, g, beta[:, :, None]),
        ((bh, n, c, dv), (bh, n, dv, dk)), False, interpret,
    )


def _fwd_xla(q, k, v, g, beta):
    return _fwd_scan(*_intra(q, k, v, g, beta))


def _bwd_call(q, k, v, g, beta, z, do, interpret=False):
    bh, n, c, _ = q.shape
    shapes = tuple(x.shape for x in (q, k, v, g)) + ((bh, n, 1, c),)
    *grads, dbeta = _call(
        _bwd_kernel, "bf_delta_bwd", (q, k, v, g, beta[:, :, None], z, do),
        shapes, True, interpret,
    )
    return (*grads, dbeta.reshape(beta.shape))


def _bwd_xla(q, k, v, g, beta, z, do):
    operands, pull = jax.vjp(_intra, q, k, v, g, beta)
    return pull(tuple(_bwd_scan(*operands, z, do)))


def _on_platform(kernel, scan, interpret, *args):
    if interpret:
        return kernel(*args, interpret=True)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=scan)


# -- the op --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _delta(q, k, v, g, beta, interpret):
    return _delta_fwd(q, k, v, g, beta, interpret)[0]


def _delta_fwd(q, k, v, g, beta, interpret):
    o, z = _on_platform(_fwd_call, _fwd_xla, interpret, q, k, v, g, beta)
    return o, (q, k, v, g, beta, z)


def _delta_bwd(interpret, res, do):
    return _on_platform(_bwd_call, _bwd_xla, interpret, *res, do)


_delta.defvjp(_delta_fwd, _delta_bwd)


def _fold(x):
    """``[b, t, h, ...]`` -> ``[b * h, N, C, ...]`` float32, the length padded
    to whole chunks with steps that change nothing."""
    b, t, h = x.shape[:3]
    n = chunk_count(t)
    x = jnp.pad(x.astype(jnp.float32), [(0, 0), (0, n * CHUNK - t)] + [(0, 0)] * (x.ndim - 2))
    x = jnp.moveaxis(x, 2, 1)  # [b, h, t_pad, ...]
    return x.reshape(b * h, n, CHUNK, *x.shape[3:])


def chunk_gated_delta(q, k, v, g, beta, *, interpret=False):
    """The gated delta rule over ``q, k, g [b, t, h, dk]``, ``v [b, t, h,
    dv]``, ``beta [b, t, h]`` -> ``o [b, t, h, dv]`` float32 (the module's
    header has the equations); differentiable in all five."""
    b, t, h, _ = q.shape
    o = _delta(*map(_fold, (q, k, v, g, beta)), interpret)
    o = o.reshape(b, h, -1, o.shape[-1])[:, :, :t]
    return jnp.moveaxis(o, 1, 2)
