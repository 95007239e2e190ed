# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""A decoder stack driven by a public ``config.json``'s own keys.

Where :mod:`bluefog_tpu.models.transformer` hard-codes one shape, this one
is built from the keys a sparse-expert decoder's ``config.json`` carries —
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim`` (which need not be hidden / heads), ``rms_norm_eps``,
``rope_theta``, ``num_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``norm_topk_prob``, ``num_hidden_layers``,
``vocab_size`` — plus what no such file says (:class:`DecoderConfig`).

One layer, input ``x [positions, hidden]``::

    u = RMSNorm(x);  q, k, v = u Wq, u Wk, u Wv   (heads x head_dim)
    q, k <- RMSNorm_head_dim(q) * g_q, RMSNorm_head_dim(k) * g_k   per head
    q, k <- rotary(q, k; theta, rotate-half over all of head_dim)
    h = x + concat(softmax(q k^T / sqrt(head_dim) under the mask) v) Wo
    u' = RMSNorm(h);  r = softmax(u' Wr) over experts_total, float32
    y = h + sum over the k largest e of r that are held here of
            (r_e / sum of the k largest) * Wd_e (silu(u' Wg_e) * (u' Wu_e))

Every layer is a sparse-expert layer, held as one chip's share of an
expert-parallel deployment (:mod:`bluefog_tpu.ops.moe`): the router keeps
``experts_total`` outputs, the layer's three stacked leaves hold
``num_experts`` of them from ``experts_start``. After the last layer an
RMSNorm and an untied float32 head.

The model is a mask-kind away from either objective: causal next-token
prediction (``mask="causal"``) or **block diffusion**
(:func:`block_diffusion_loss`; BD3-LMs, Arriola et al. 2025, the objective
the SDAR family trains with): the clean sequence and a noised copy go
through the stack together under
:class:`bluefog_tpu.ops.flash.BlockDiffusionMask`, and the head reads the
noised half only.

Scopes (``jax.named_scope``, read back from the compiled step's
``op_name``s): ``bf.attn`` (projections, norms, rotary, the kernel),
``bf.moe.route`` / ``bf.moe.experts`` / ``bf.moe.combine``
(:func:`bluefog_tpu.ops.moe.expert_layer`; the router is under
``bf.moe.route``), ``bf.head`` (final norm, head, loss). Host gauges, set
when the model is traced (nothing is synced in the step):
``bluefog.moe.rows_offered`` (positions x k x layers a call),
``bluefog.moe.rows_capacity`` (pairs the expert layers' buffers have a
row for a call), ``bluefog.moe.row_tile`` (rows a tile of those buffers
takes) and ``bluefog.moe.buffer_rows`` (the buffers' true rows a call,
slack tiles included: with :func:`bluefog_tpu.ops.moe.tiles_in_use` of the
returned ``rows_per_expert``, rows touched against rows held),
``bluefog.attn.tiles_live`` / ``bluefog.attn.tiles_total`` (tiles a
forward pass of the attention kernels visits / would visit unmasked, over
batch, heads and layers). The device's own counts come back beside the
hidden states (``counts``), for the caller to return beside its loss.
"""

import dataclasses
from typing import Any, Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from bluefog_tpu import metrics as metrics_mod
from bluefog_tpu.ops import flash, moe

__all__ = ["DecoderConfig", "DecoderLM", "block_diffusion_loss"]

MaskKind = Union[None, str, flash.BlockDiffusionMask]


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The source's keys under their own names, then what it does not say.

    ``num_experts`` is the number of experts **held here** (the leading
    axis of the expert leaves); ``experts_total`` the router's width, the
    published count; the held range starts at ``experts_start``."""

    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # not the source's
    experts_total: Optional[int] = None
    experts_start: int = 0
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    head_dtype: Any = jnp.float32
    router_dtype: Any = jnp.float32
    qk_norm: bool = True
    remat: bool = True
    initializer_range: float = 0.02
    # "normal": every column of the router its own draw. "tiled": the draw
    # has experts_total / num_experts_per_tok columns and the router repeats
    # it num_experts_per_tok times over, expert e taking column e mod that
    # many — a position's k choices are then the k copies of its best
    # column, one on each of k equal shares of the experts, whatever the
    # hidden states are: the balance a trained router has, at step 0
    router_init: str = "normal"

    def __post_init__(self):
        if self.experts_total is None:
            object.__setattr__(self, "experts_total", self.num_experts)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of kv heads")
        if self.head_dim % 2:
            raise ValueError("rotary positions need an even head_dim")
        if self.router_init not in ("normal", "tiled"):
            raise ValueError(f"router_init {self.router_init!r}")
        if (self.router_init == "tiled"
                and self.experts_total % self.num_experts_per_tok):
            raise ValueError("a tiled router needs k to divide experts_total")
        if not (0 <= self.experts_start
                and self.experts_start + self.num_experts <= self.experts_total):
            raise ValueError(
                f"held experts {self.experts_start}.."
                f"{self.experts_start + self.num_experts} of {self.experts_total}"
            )

    @classmethod
    def from_source(cls, entry, **own):
        """From a ``config.json``'s keys (``entry``; keys this stack does
        not read are ignored, settings it cannot honour are refused) and
        what the source does not say (``own``)."""
        refusals = {
            "attention_bias": (False,), "tie_word_embeddings": (False,),
            "hidden_act": ("silu",), "decoder_sparse_step": (1,),
            "mlp_only_layers": ([],), "rope_scaling": (None,),
            "use_sliding_window": (False,),
        }
        for key, allowed in refusals.items():
            if key in entry and entry[key] not in allowed:
                raise ValueError(
                    f"{key} = {entry[key]!r}: this stack builds only {allowed}"
                )
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in entry.items() if k in names}, **own)


def _kernel_kind(mask: MaskKind):
    """A model's mask kind as ``ops/flash.py`` names it: ``False`` (none),
    ``True`` (``"causal"``) or the ``BlockDiffusionMask`` itself."""
    return mask if isinstance(mask, flash.BlockDiffusionMask) else mask == "causal"


def _init(cfg):
    return nn.initializers.normal(cfg.initializer_range)


def _router_init(cfg):
    if cfg.router_init == "normal":
        return _init(cfg)
    columns = cfg.experts_total // cfg.num_experts_per_tok

    def tiled(key, shape, dtype):
        draw = _init(cfg)(key, (shape[0], columns), dtype)
        return jnp.tile(draw, (1, cfg.num_experts_per_tok))

    return tiled


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (x32 * scale).astype(self.dtype)


def rotary(x, positions, theta):
    """Rotate-half rotary positions over all of the last axis: ``x [b, t,
    heads, d]``, ``positions [t]``; angles in float32."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


class Attention(nn.Module):
    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        b, t, _ = x.shape

        def proj(name, heads):
            y = nn.Dense(
                heads * cfg.head_dim, use_bias=False, dtype=dt,
                param_dtype=cfg.param_dtype, kernel_init=_init(cfg), name=name,
            )(x)
            return y.reshape(b, t, heads, cfg.head_dim)

        q = proj("q_proj", cfg.num_attention_heads)
        k = proj("k_proj", cfg.num_key_value_heads)
        v = proj("v_proj", cfg.num_key_value_heads)
        if cfg.qk_norm:
            q = RMSNorm(cfg.rms_norm_eps, dt, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, dt, name="k_norm")(k)
        q = rotary(q, positions, cfg.rope_theta)
        k = rotary(k, positions, cfg.rope_theta)
        kind = _kernel_kind(mask)
        if isinstance(kind, bool):
            att = flash.flash_attention(q, k, v, causal=kind)
        else:
            att = flash.flash_attention(q, k, v, mask=kind)
        att = att.reshape(b, t, cfg.num_attention_heads * cfg.head_dim)
        return nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=dt,
            param_dtype=cfg.param_dtype, kernel_init=_init(cfg),
            name="o_proj",
        )(att)


class SparseExperts(nn.Module):
    """The router over ``experts_total`` and this chip's ``num_experts``
    experts as three stacked leaves (``ops/moe.py`` has the contract)."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        d, f, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        w_router = self.param(
            "router", _router_init(cfg), (d, cfg.experts_total), cfg.param_dtype
        )
        w_gate = self.param("w_gate", _init(cfg), (held, d, f), cfg.param_dtype)
        w_up = self.param("w_up", _init(cfg), (held, d, f), cfg.param_dtype)
        w_down = self.param("w_down", _init(cfg), (held, f, d), cfg.param_dtype)
        b, t, _ = u.shape
        rows = u.reshape(b * t, d)
        with jax.named_scope("bf.moe.route"):
            weights, experts = moe.route(
                rows, w_router, cfg.num_experts_per_tok, cfg.norm_topk_prob,
                dtype=cfg.router_dtype,
            )
        # for `apply(..., mutable=["intermediates"])`: who chose what
        self.sow("intermediates", "experts_chosen", experts.reshape(b, t, -1))
        y, counts = moe.expert_layer(
            rows, weights, experts, w_gate, w_up, w_down,
            held_start=cfg.experts_start, dtype=cfg.compute_dtype,
        )
        return y.reshape(b, t, d), counts


class DecoderLayer(nn.Module):
    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.compute_dtype, name=name)
        with jax.named_scope("bf.attn"):
            x = x + Attention(cfg, name="attn")(
                norm("input_norm")(x), positions, mask
            )
        y, counts = SparseExperts(cfg, name="experts")(norm("post_attn_norm")(x))
        return x + y, counts


class DecoderLM(nn.Module):
    """``hidden(tokens, positions, mask)`` -> ``(h, counts)``: the stack's
    output before the final norm, and the expert layers' device counts
    stacked over layers; ``head(h)`` -> float32 logits. ``__call__`` is
    both, for the whole sequence."""

    cfg: DecoderConfig

    def setup(self):
        cfg = self.cfg
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.compute_dtype,
            param_dtype=cfg.param_dtype, embedding_init=_init(cfg),
        )
        # the mask kind is static: it configures the attention kernel
        layer = (
            nn.remat(DecoderLayer, static_argnums=(3,)) if cfg.remat
            else DecoderLayer
        )
        self.layers = [
            layer(cfg, name=f"layer_{i}") for i in range(cfg.num_hidden_layers)
        ]
        self.final_norm = RMSNorm(cfg.rms_norm_eps, cfg.compute_dtype)
        self.lm_head = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.head_dtype,
            param_dtype=cfg.param_dtype, kernel_init=_init(cfg),
        )

    def hidden(self, tokens, positions=None, mask: MaskKind = "causal"):
        cfg = self.cfg
        b, t = tokens.shape
        if positions is None:
            positions = jnp.arange(t)
        _record_static_counts(cfg, b, t, mask)
        x = self.embed(tokens)
        counts = []
        for layer in self.layers:
            x, c = layer(x, positions, mask)
            counts.append(c)
        return x, jax.tree_util.tree_map(lambda *cs: jnp.stack(cs), *counts)

    def head(self, h):
        with jax.named_scope("bf.head"):
            return self.lm_head(self.final_norm(h))

    def __call__(self, tokens, positions=None, mask: MaskKind = "causal"):
        h, counts = self.hidden(tokens, positions, mask)
        return self.head(h), counts


def _record_static_counts(cfg, batch, positions, mask):
    """What one call offers its expert layers and its attention kernels,
    known from the shapes: host gauges, written while tracing."""
    pairs = batch * positions * cfg.num_experts_per_tok  # a layer
    rows = pairs * cfg.num_hidden_layers
    metrics_mod.gauge("bluefog.moe.rows_offered").set(rows)
    metrics_mod.gauge("bluefog.moe.rows_capacity").set(rows)
    tm = moe.row_tile(
        pairs, cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size,
        cfg.compute_dtype,
    )
    metrics_mod.gauge("bluefog.moe.row_tile").set(tm)
    metrics_mod.gauge("bluefog.moe.buffer_rows").set(
        moe.buffer_tiles(pairs, cfg.num_experts, tm) * tm * cfg.num_hidden_layers
    )
    live, total = flash.tile_counts(positions, _kernel_kind(mask))
    scale = batch * cfg.num_attention_heads * cfg.num_hidden_layers
    metrics_mod.gauge("bluefog.attn.tiles_live").set(live * scale)
    metrics_mod.gauge("bluefog.attn.tiles_total").set(total * scale)


def block_diffusion_loss(model, params, tokens, draws, levels, *, block,
                         mask_id):
    """The block-diffusion training loss of one batch, ``-> (loss,
    counts)``.

    ``tokens [b, seq]`` are the clean ids ``x0``; ``levels [b, seq //
    block]`` a noise level ``t`` in (0, 1] per block; ``draws [b, seq]``
    uniform draws: position ``i`` is masked where ``draws_i < t`` of its
    block, ``x_t = where(masked, mask_id, x0)``. ``x0`` and ``x_t`` go
    through the stack as one sequence of ``2 seq`` positions (position
    ``i`` and ``seq + i`` share rotary position ``i``) under the
    block-diffusion mask; the head reads the noised half, and ::

        loss = 1 / (b seq) * sum over masked i of (1 / t) CE(logits_{seq+i}, x0_i)

    (no shift: position ``seq + i`` predicts token ``i``)."""
    b, seq = tokens.shape
    t = jnp.repeat(levels, block, axis=1)
    masked = draws < t
    noised = jnp.where(masked, mask_id, tokens)
    doubled = jnp.concatenate([tokens, noised], axis=1)
    positions = jnp.concatenate([jnp.arange(seq)] * 2)
    h, counts = model.apply(
        {"params": params}, doubled, positions,
        flash.BlockDiffusionMask(seq, block), method="hidden",
    )
    with jax.named_scope("bf.head"):
        logits = model.apply({"params": params}, h[:, seq:], method="head")
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        loss = jnp.sum(jnp.where(masked, (lse - picked) / t, 0.0)) / (b * seq)
    return loss, counts
