# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Sequence-parallel attention over the worker mesh.

Two standard long-context strategies, both expressed with the same
primitives the gossip layer compiles to (so they ride ICI the same way):

- **Ring attention** (`ring_attention_block`): the sequence is sharded
  across workers; K/V blocks rotate around the ring with one
  ``lax.ppermute`` per round while each worker accumulates its queries'
  attention with a numerically-stable online softmax (flash-attention
  style running max / normalizer). Communication per round is one K/V
  block regardless of world size — the attention analogue of the one-peer
  gossip cost model — and XLA overlaps the permute with the block matmuls.
  Causal masking skips fully-masked (future) blocks by zero-weighting
  them, so the math matches dense causal attention exactly.

- **Ulysses / all-to-all** (`ulysses_attention_block`): re-shard
  sequence -> heads with ``lax.all_to_all``, run ordinary full attention
  on the now-complete local sequence for the local head slice, and
  re-shard back. Two all-to-alls per call; requires the head count to be
  divisible by the mesh size.

Both are differentiable through JAX AD (the transport ops have exact
adjoints), tested against dense reference attention in
``tests/test_attention.py``.

Inputs follow the framework's worker-array convention at the facade level
(stacked ``[size, batch, seq_block, heads, dim]``) and plain per-worker
blocks (``[batch, seq_block, heads, dim]``) inside ``shard_map``.
"""

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu import context as ctx_mod

__all__ = [
    "ring_attention_block",
    "ulysses_attention_block",
    "ring_attention",
    "ulysses_attention",
    "reference_attention",
]


def _expand_kv(q, kv):
    """Grouped-query attention: K/V may carry fewer heads than Q
    (``h % h_kv == 0``); repeat each KV head over its query group."""
    h, h_kv = q.shape[2], kv.shape[2]
    if h == h_kv:
        return kv
    if h % h_kv != 0:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({h_kv})"
        )
    return jnp.repeat(kv, h // h_kv, axis=2)


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None, mask=None):
    """Dense softmax attention on full (unsharded) tensors
    ``[batch, seq, heads, dim]`` — the numpy-oracle-grade reference the
    sequence-parallel paths are tested against. K/V with fewer heads than
    Q run grouped-query attention (each KV head serves ``h/h_kv``
    query heads). ``mask`` is a static mask kind of
    :mod:`bluefog_tpu.ops.flash` (a ``BlockDiffusionMask``), laid out
    densely from its elementwise definition."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    k, v = _expand_kv(q, k), _expand_kv(q, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        pos = jnp.arange(s.shape[-1])
        s = jnp.where(mask.allowed(pos[:, None], pos[None, :]), s, -jnp.inf)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _merge_blocks(out_a, lse_a, out_b, lse_b):
    """Exactly combine two normalized attention results over disjoint key
    blocks, given their logsumexps (the online-softmax merge rule).
    ``out``: [b, t, h, d] f32; ``lse``: [b, h, t]. lse=-inf marks an
    empty/excluded block (weight zero)."""
    m = jnp.maximum(lse_a, lse_b)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    wa = jnp.where(jnp.isfinite(lse_a), jnp.exp(lse_a - m_safe), 0.0)
    wb = jnp.where(jnp.isfinite(lse_b), jnp.exp(lse_b - m_safe), 0.0)
    tot = wa + wb
    tot_safe = jnp.where(tot > 0, tot, 1.0)
    tr = lambda w: (w / tot_safe).transpose(0, 2, 1)[..., None]
    out = tr(wa) * out_a + tr(wb) * out_b
    lse = jnp.where(tot > 0, m_safe + jnp.log(tot_safe), -jnp.inf)
    return out, lse


def ring_attention_block(q, k, v, axis_name: str, causal: bool = False,
                         scale: Optional[float] = None):
    """Ring attention on per-worker blocks, for use inside ``shard_map``.

    ``q/k/v``: ``[batch, block_len, heads, dim]`` — this worker's slice of
    the sequence (worker ``i`` owns positions ``[i*T, (i+1)*T)``).
    Returns this worker's output block: mathematically the causal/full
    softmax attention of the logically-concatenated sequence, computed
    with f32 online-softmax accumulation (reductions are reordered vs a
    dense computation, so equality is numerical — rtol ~1e-5 at f32 —
    not bitwise). Grouped-query attention (K/V with fewer heads) rotates
    the COMPACT K/V around the ring AND keeps it compact inside the
    kernels (no receiver-side expansion), so GQA divides both the ring's
    wire bytes and the block-attention HBM traffic by the group factor.

    The per-round block attention runs through the Pallas flash kernels
    on TPU (``flash_attention_with_lse``; dense XLA elsewhere, selected
    per lowering platform — the ppermute transport stays OUTSIDE any
    platform branch since dead collectives are not DCE'd). Causal
    structure is resolved per round without traced kernel configs: the
    diagonal block is always round 0 (static causal kernel); every later
    round's block is wholly past or wholly future of this worker, so it
    enters the online-softmax merge with its logsumexp gated to -inf
    when excluded.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    from bluefog_tpu.ops.flash import flash_attention_with_lse

    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block_attend(kcur, vcur, block_causal):
        # compact (grouped-query) K/V goes straight in: the kernels serve
        # each KV head to its query group from the index maps, and the
        # dense fallback expands internally — no receiver-side expanded
        # copy exists on either path
        out, lse = flash_attention_with_lse(
            q, kcur, vcur, causal=block_causal, scale=scale
        )
        return out.astype(jnp.float32), lse

    # round 0: own block — the diagonal, the only block needing intra-
    # block causal masking (statically known, so the kernel config is
    # static too). The accumulators inherit device-varyingness from
    # q/k/v, so the fori_loop carry types line up without pvary.
    out_acc, lse_acc = block_attend(k, v, causal)

    def round_fn(r, carry):
        kcur, vcur, out_acc, lse_acc = carry
        kcur = lax.ppermute(kcur, axis_name, perm)
        vcur = lax.ppermute(vcur, axis_name, perm)
        # after r rotations this worker holds block (my - r) mod n: for
        # r >= 1 it is never the diagonal, so it is wholly past (keep,
        # unmasked) or wholly future (gate out via lse=-inf) of my rows
        src = (my - r) % n
        out_b, lse_b = block_attend(kcur, vcur, False)
        if causal:
            lse_b = jnp.where(src < my, lse_b, -jnp.inf)
        out_acc, lse_acc = _merge_blocks(out_acc, lse_acc, out_b, lse_b)
        return kcur, vcur, out_acc, lse_acc

    _kcur, _vcur, out_acc, lse_acc = lax.fori_loop(
        1, n, round_fn, (k, v, out_acc, lse_acc)
    )
    return out_acc.astype(q.dtype)


def ulysses_attention_block(q, k, v, axis_name: str, causal: bool = False,
                            scale: Optional[float] = None):
    """All-to-all (Ulysses-style) sequence parallelism inside shard_map.

    Re-shards ``[b, S/n, H, d] -> [b, S, H/n, d]`` with one
    ``lax.all_to_all`` per operand, runs dense attention on the full local
    sequence for the local head slice, and re-shards back. Head count must
    be divisible by the mesh size.
    """
    n = lax.psum(1, axis_name)
    h, h_kv = q.shape[2], k.shape[2]
    if h % n != 0:
        raise ValueError(
            f"ulysses attention needs heads ({h}) divisible by mesh "
            f"size ({n})"
        )
    if h % h_kv != 0:
        # validate at entry with the GLOBAL head counts; otherwise the
        # failure surfaces mid-trace with confusing per-shard counts
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({h_kv})"
        )
    # GQA: reshard the compact KV when its head count divides the mesh
    # (group alignment holds because both splits are contiguous);
    # otherwise expand to full heads first — correct, just not compact.
    if h_kv % n != 0:
        k, v = _expand_kv(q, k), _expand_kv(q, v)

    def seq_to_heads(x):
        # [b, t, h, d] -> concat seq, split heads -> [b, t*n, h/n, d]
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # local attention hot op: Pallas flash kernels on TPU, dense XLA
    # otherwise (same math; see ops/flash.py). A compact-resharded KV
    # stays compact end to end: the wire was compact, and the kernels
    # serve grouped-query heads natively from their index maps.
    from bluefog_tpu.ops.flash import flash_attention

    out = flash_attention(qf, kf, vf, causal=causal, scale=scale)
    return heads_to_seq(out)


# -- worker-array facades ------------------------------------------------------


def _facade(block_fn):
    def run(q, k, v, causal: bool = False, scale: Optional[float] = None):
        ctx = ctx_mod.get_context()
        from bluefog_tpu.collective import ops as col_ops
        from jax.sharding import PartitionSpec as P

        q = col_ops._check_worker_array(ctx, q)
        k = col_ops._check_worker_array(ctx, k)
        v = col_ops._check_worker_array(ctx, v)
        key = (
            block_fn.__name__, causal, scale,
        ) + col_ops._aval_key(q, k, v)
        spec = P(ctx_mod.WORKER_AXIS)
        fn = col_ops._compiled(
            ctx,
            block_fn.__name__,
            key,
            lambda qb, kb, vb: jnp.expand_dims(
                block_fn(
                    qb[0], kb[0], vb[0], ctx_mod.WORKER_AXIS,
                    causal=causal, scale=scale,
                ),
                0,
            ),
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )
        return fn(q, k, v)

    return run


ring_attention = _facade(ring_attention_block)
ring_attention.__doc__ = (
    "Eager facade: ring attention over worker-stacked "
    "``[size, batch, block, heads, dim]`` arrays (sequence sharded across "
    "workers in rank order)."
)
ulysses_attention = _facade(ulysses_attention_block)
ulysses_attention.__doc__ = (
    "Eager facade: all-to-all (Ulysses) sequence-parallel attention over "
    "worker-stacked arrays."
)
