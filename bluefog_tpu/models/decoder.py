# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""A decoder stack driven by a public ``config.json``'s own keys.

Where :mod:`bluefog_tpu.models.transformer` hard-codes one shape, this one
is built from the keys a sparse-expert decoder's ``config.json`` carries —
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim`` (which need not be hidden / heads), ``rms_norm_eps``,
``rope_theta``, ``num_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``norm_topk_prob``, ``num_hidden_layers``,
``vocab_size`` — plus what no such file says (:class:`DecoderConfig`).
Two of the source's keys choose a layer's kind: ``kv_lora_rank`` present
makes its attention **latent** (below), ``n_shared_experts`` > 0 puts a
shared expert beside the routed ones.

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

**Latent attention** (DeepSeek-V2's MLA, :class:`LatentAttention`) takes the
place of the first three lines: queries go through a low-rank pair with a
norm between, keys and values are expanded per head from one compressed
stream, and the rotary part of the key is ONE vector shared by all heads::

    c_q = RMSNorm(u W_qa);  q = c_q W_qb -> heads x [q_nope | q_rope]
    [c_kv | k_r] = u W_kva;  c_kv = RMSNorm(c_kv)
    c_kv W_kvb -> heads x [k_nope | v]
    q_rope, k_r <- rotary over interleaved pairs (2i, 2i+1), the
                   frequencies of ``rope_parameters`` (:class:`RopeParameters`)
    k = [k_nope | k_r broadcast over heads]
    h = x + concat(softmax(s q k^T, causal) v) W_o

**A shared expert** adds ``W_d(silu(u' W_g) * (u' W_u))`` of width
``n_shared_experts x moe_intermediate_size`` to ``y``, whole on every chip;
the routed sum is scaled by ``routed_scaling_factor``.

The model is a mask-kind away from either objective: causal next-token
prediction (``mask="causal"``, :func:`next_token_loss`) or **block diffusion**
(:func:`block_diffusion_loss`; BD3-LMs, Arriola et al. 2025, the objective
the SDAR family trains with): the clean sequence and a noised copy go
through the stack together under
:class:`bluefog_tpu.ops.flash.BlockDiffusionMask`, and the head reads the
noised half only.

Scopes (``jax.named_scope``, read back from the compiled step's
``op_name``s): ``bf.attn`` (projections, norms, rotary, the kernel) and
inside it ``bf.attn.latent`` (latent attention's down- and up-projections,
its two norms, rotary and the assembly of the keys: what a latent-aware
kernel would not need; the kernels and ``o_proj`` stay directly under
``bf.attn``), ``bf.moe.route`` / ``bf.moe.experts`` / ``bf.moe.combine``
(:func:`bluefog_tpu.ops.moe.expert_layer`; the router is under
``bf.moe.route``), ``bf.moe.shared`` (the shared expert), ``bf.head``
(final norm, head, loss). Host gauges, set
when the model is traced (nothing is synced in the step):
``bluefog.moe.rows_offered`` (positions x k x layers a call),
``bluefog.moe.rows_capacity`` (pairs the expert layers' buffers have a
row for a call), ``bluefog.moe.row_tile`` (rows a tile of those buffers
takes) and ``bluefog.moe.buffer_rows`` (the buffers' true rows a call,
slack tiles included: with :func:`bluefog_tpu.ops.moe.tiles_in_use` of the
returned ``rows_per_expert``, rows touched against rows held),
``bluefog.attn.tiles_live`` / ``bluefog.attn.tiles_total`` (tiles of a
forward pass of the attention kernels that hold an allowed pair / of the
whole square, over batch, heads and layers) and ``bluefog.attn.grid_steps``
(the grid steps that pass takes: ``tiles_live`` where the kernels walk the
live tiles' list, ``tiles_total`` on the rectangle),
``bluefog.attn.subtiles_live`` / ``bluefog.attn.subtiles_masked`` (the
sub-tiles that pass computes / of them those that build a mask: a whole
tile counts all its sub-tiles live and none masked); with latent attention
``bluefog.attn.kv_latent_bytes`` (the compressed stream a call makes:
positions x (kv_lora_rank + qk_rope_head_dim) x layers x itemsize) and
``bluefog.attn.kv_expanded_bytes`` (the per-head keys and values the
kernels read instead); with a shared expert ``bluefog.moe.shared_rows``
(positions x layers a call). The device's own counts come back beside the
hidden states (``counts``), for the caller to return beside its loss.
"""

import dataclasses
import math
from typing import Any, Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from bluefog_tpu import metrics as metrics_mod
from bluefog_tpu.ops import flash, moe

__all__ = [
    "DecoderConfig", "DecoderLM", "RopeParameters", "block_diffusion_loss",
    "next_token_loss",
]

MaskKind = Union[None, str, flash.BlockDiffusionMask]


@dataclasses.dataclass(frozen=True)
class RopeParameters:
    """A source's ``rope_parameters`` group under its own keys. ``yarn``
    (Peng et al. 2023, in the convention of the family the keys come from,
    DeepSeek-V2 / V3's) blends each rotary frequency between the plain one
    and one ``factor`` times slower, by where its wavelength falls against
    ``original_max_position_embeddings``, and rescales the softmax."""

    rope_theta: float = 10000.0
    rope_type: str = "default"
    factor: float = 1.0
    original_max_position_embeddings: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    llama_4_scaling_beta: Optional[float] = None

    @classmethod
    def from_source(cls, group):
        group = dict(group)
        legacy = group.pop("type", None)  # the key's older name, kept beside it
        kind = group.setdefault("rope_type", legacy or "default")
        if legacy not in (None, kind) or kind not in ("default", "yarn"):
            raise ValueError(
                f"rope_type = {kind!r}: this stack builds only 'default' and 'yarn'"
            )
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(group) - names)
        if unknown:
            raise ValueError(f"rope_parameters holds keys {unknown} nothing reads")
        if kind == "yarn" and not group.get("original_max_position_embeddings"):
            raise ValueError("yarn needs original_max_position_embeddings")
        return cls(**group)

    def _mscale(self, scale):
        # m(f, a) = 0.1 a ln f + 1
        if self.rope_type != "yarn" or self.factor <= 1:
            return 1.0
        return 0.1 * scale * math.log(self.factor) + 1.0

    def inv_freq(self, d):
        """The ``d / 2`` rotary frequencies, float32: ``theta^(-2i/d)``, and
        under yarn ``(f / factor) ramp_i + f (1 - ramp_i)`` with ``ramp``
        rising from 0 to 1 between the dimensions whose wavelengths make
        ``beta_fast`` and ``beta_slow`` turns over the original length."""
        theta = float(self.rope_theta)
        freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        if self.rope_type != "yarn":
            return freq

        def corr(turns):
            return (
                d * math.log(self.original_max_position_embeddings
                             / (turns * 2 * math.pi)) / (2 * math.log(theta))
            )

        low = max(math.floor(corr(self.beta_fast)), 0)
        high = min(math.ceil(corr(self.beta_slow)), d - 1)
        span = (high - low) or 0.001  # the family's guard against 0 / 0
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / span, 0, 1)
        return freq / self.factor * ramp + freq * (1 - ramp)

    @property
    def cos_sin_scale(self):
        """What yarn multiplies cos and sin by."""
        return self._mscale(self.mscale) / self._mscale(self.mscale_all_dim)

    def softmax_scale(self, d):
        """``m(factor, mscale_all_dim)^2 / sqrt(d)``."""
        return self._mscale(self.mscale_all_dim) ** 2 / math.sqrt(d)

    def query_scale(self, positions):
        """``1 + beta ln(1 + floor(p / original_max_position_embeddings))``
        per position, float32 (llama-4's length-dependent temperature: 1
        inside the original length); ``None`` without a beta."""
        if not self.llama_4_scaling_beta:
            return None
        chunks = jnp.floor(
            positions.astype(jnp.float32) / self.original_max_position_embeddings
        )
        return 1.0 + self.llama_4_scaling_beta * jnp.log1p(chunks)


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The source's keys under their own names, then what it does not say.

    ``num_experts`` is the number of experts **held here** (the leading
    axis of the expert leaves; a source that calls it ``n_routed_experts``
    is read under that name); ``experts_total`` the router's width, the
    published count; the held range starts at ``experts_start``.
    ``kv_lora_rank`` chooses latent attention, ``n_shared_experts`` the
    shared expert."""

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
    # latent attention, a shared expert (the source's keys still)
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: Optional[int] = None
    rope_interleave: bool = False
    rope_parameters: Optional[RopeParameters] = None
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
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
        if self.kv_lora_rank is None:
            if self.rope_parameters and self.rope_parameters.rope_type != "default":
                raise ValueError(
                    "rope_parameters of kind yarn are built for latent attention only"
                )
            return
        if not self.q_lora_rank:
            raise ValueError("latent attention here has the low-rank query path only")
        if not self.rope_interleave or self.qk_rope_head_dim % 2:
            raise ValueError(
                "latent attention rotates interleaved pairs: rope_interleave "
                "and an even qk_rope_head_dim"
            )
        if not (self.qk_nope_head_dim + self.qk_rope_head_dim == self.head_dim
                == (self.v_head_dim or self.head_dim)):
            raise ValueError(
                "the attention kernels take one head size: qk_nope_head_dim + "
                "qk_rope_head_dim, v_head_dim and head_dim must be equal"
            )
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention expands a key and a value per query head")

    @classmethod
    def from_source(cls, entry, **own):
        """From a ``config.json``'s keys (``entry``; keys this stack does
        not read are ignored, settings it cannot honour are refused) and
        what the source does not say (``own``)."""
        refusals = {
            "attention_bias": (False,), "tie_word_embeddings": (False,),
            "hidden_act": ("silu",), "decoder_sparse_step": (1,),
            "mlp_only_layers": ([],), "rope_scaling": (None,),
            "use_sliding_window": (False,), "sliding_window": (None,),
            "mlp_bias": (False,), "first_k_dense_replace": (0,),
            "n_group": (1,), "topk_group": (1,),
        }
        for key, allowed in refusals.items():
            if key in entry and entry[key] not in allowed:
                raise ValueError(
                    f"{key} = {entry[key]!r}: this stack builds only {allowed}"
                )
        entry = dict(entry)
        if "n_routed_experts" in entry:
            entry.setdefault("num_experts", entry["n_routed_experts"])
        if entry.get("rope_parameters") is not None:
            rope = RopeParameters.from_source(entry["rope_parameters"])
            entry.update(rope_parameters=rope, rope_theta=rope.rope_theta)
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in entry.items() if k in names}, **own)


def _kernel_kind(mask: MaskKind):
    """A model's mask kind as ``ops/flash.py`` names it: ``False`` (none),
    ``True`` (``"causal"``) or the ``BlockDiffusionMask`` itself."""
    return mask if isinstance(mask, flash.BlockDiffusionMask) else mask == "causal"


def _attend(q, k, v, mask: MaskKind, scale=None):
    kind = _kernel_kind(mask)
    if isinstance(kind, bool):
        return flash.flash_attention(q, k, v, causal=kind, scale=scale)
    return flash.flash_attention(q, k, v, mask=kind, scale=scale)


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


def rotary_interleaved(x, positions, inv_freq, scale=1.0):
    """Rotary positions over the pairs ``(2i, 2i+1)`` of the last axis:
    ``x [b, t, heads, d]``, ``positions [t]``, ``inv_freq [d / 2]``; cos and
    sin times ``scale``; angles in float32."""
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angles) * scale)[None, :, None, :]
    sin = (jnp.sin(angles) * scale)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    rotated = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


def _dense(cfg, width, name):
    return nn.Dense(
        width, use_bias=False, dtype=cfg.compute_dtype,
        param_dtype=cfg.param_dtype, kernel_init=_init(cfg), name=name,
    )


class LatentAttention(nn.Module):
    """Causal or unmasked latent attention (the module's header has the
    equations); the per-head keys and values are expanded for the same
    flash kernels every other attention here runs."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        b, t, _ = x.shape
        heads, nope, rope = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        )
        pos = cfg.rope_parameters or RopeParameters(rope_theta=cfg.rope_theta)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dt, name=name)
        with jax.named_scope("bf.attn.latent"):
            c_q = norm("q_a_norm")(_dense(cfg, cfg.q_lora_rank, "q_a_proj")(x))
            q = _dense(cfg, heads * cfg.head_dim, "q_b_proj")(c_q)
            q_nope, q_rope = jnp.split(q.reshape(b, t, heads, cfg.head_dim), [nope], axis=-1)
            c_kv, k_rope = jnp.split(
                _dense(cfg, cfg.kv_lora_rank + rope, "kv_a_proj")(x),
                [cfg.kv_lora_rank], axis=-1,
            )
            kv = _dense(cfg, heads * (nope + cfg.head_dim), "kv_b_proj")(
                norm("kv_a_norm")(c_kv)
            )
            k_nope, v = jnp.split(kv.reshape(b, t, heads, -1), [nope], axis=-1)
            freq = pos.inv_freq(rope)
            turn = lambda y: rotary_interleaved(y, positions, freq, pos.cos_sin_scale)
            # ONE rotary key a position, shared by all heads
            k_rope = jnp.broadcast_to(turn(k_rope[:, :, None]), (b, t, heads, rope))
            q = jnp.concatenate([q_nope, turn(q_rope)], axis=-1)
            k = jnp.concatenate([k_nope, k_rope], axis=-1)
            q_scale = pos.query_scale(positions)
            if q_scale is not None:
                q = (q * q_scale[None, :, None, None]).astype(dt)
        att = _attend(q, k, v, mask, pos.softmax_scale(cfg.head_dim))
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            att.reshape(b, t, heads * cfg.head_dim)
        )


class Attention(nn.Module):
    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        b, t, _ = x.shape

        def proj(name, heads):
            y = _dense(cfg, heads * cfg.head_dim, name)(x)
            return y.reshape(b, t, heads, cfg.head_dim)

        q = proj("q_proj", cfg.num_attention_heads)
        k = proj("k_proj", cfg.num_key_value_heads)
        v = proj("v_proj", cfg.num_key_value_heads)
        if cfg.qk_norm:
            q = RMSNorm(cfg.rms_norm_eps, dt, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, dt, name="k_norm")(k)
        q = rotary(q, positions, cfg.rope_theta)
        k = rotary(k, positions, cfg.rope_theta)
        att = _attend(q, k, v, mask)
        att = att.reshape(b, t, cfg.num_attention_heads * cfg.head_dim)
        return _dense(cfg, cfg.hidden_size, "o_proj")(att)


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
        if cfg.routed_scaling_factor != 1:
            weights = weights * cfg.routed_scaling_factor
        # for `apply(..., mutable=["intermediates"])`: who chose what
        self.sow("intermediates", "experts_chosen", experts.reshape(b, t, -1))
        y, counts = moe.expert_layer(
            rows, weights, experts, w_gate, w_up, w_down,
            held_start=cfg.experts_start, dtype=cfg.compute_dtype,
        )
        return y.reshape(b, t, d), counts


class SharedExpert(nn.Module):
    """The gated feed-forward every position goes through beside its routed
    choices, ``n_shared_experts`` experts wide as one; whole on every chip
    (over the shares of a layer it is counted once)."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        width = cfg.n_shared_experts * cfg.moe_intermediate_size
        gate = _dense(cfg, width, "gate_proj")(u).astype(jnp.float32)
        up = _dense(cfg, width, "up_proj")(u).astype(jnp.float32)
        # the activation in float32, as the routed experts' (``ops/moe.py``)
        return _dense(cfg, cfg.hidden_size, "down_proj")(jax.nn.silu(gate) * up)


class DecoderLayer(nn.Module):
    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.compute_dtype, name=name)
        attention = Attention if cfg.kv_lora_rank is None else LatentAttention
        with jax.named_scope("bf.attn"):
            x = x + attention(cfg, name="attn")(
                norm("input_norm")(x), positions, mask
            )
        u = norm("post_attn_norm")(x)
        y, counts = SparseExperts(cfg, name="experts")(u)
        if cfg.n_shared_experts:
            with jax.named_scope("bf.moe.shared"):
                y = y + SharedExpert(cfg, name="shared_expert")(u)
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


def record_attention_counts(positions, kind, slots):
    """The attention kernels' host gauges for a call at ``positions`` under
    the mask kind ``kind`` (``ops.flash``'s), summed over ``slots`` (batch,
    head, layer) slots: written while tracing, the step does not change."""
    live, total = flash.tile_counts(positions, kind)
    sub_live, sub_masked = flash.subtile_counts(positions, kind)
    for name, n in (
        ("tiles_live", live), ("tiles_total", total),
        ("grid_steps", flash.grid_steps(positions, kind)),
        ("subtiles_live", sub_live), ("subtiles_masked", sub_masked),
    ):
        metrics_mod.gauge(f"bluefog.attn.{name}").set(n * slots)


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
    record_attention_counts(
        positions, _kernel_kind(mask),
        batch * cfg.num_attention_heads * cfg.num_hidden_layers,
    )
    per_layer = batch * positions * cfg.num_hidden_layers
    if cfg.kv_lora_rank is not None:
        itemsize = jnp.dtype(cfg.compute_dtype).itemsize
        metrics_mod.gauge("bluefog.attn.kv_latent_bytes").set(
            per_layer * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * itemsize
        )
        metrics_mod.gauge("bluefog.attn.kv_expanded_bytes").set(
            per_layer * cfg.num_attention_heads * 2 * cfg.head_dim * itemsize
        )
    if cfg.n_shared_experts:
        metrics_mod.gauge("bluefog.moe.shared_rows").set(per_layer)


def next_token_loss(model, params, tokens):
    """The causal training loss of one batch, ``-> (loss, counts)``: the
    mean over positions ``0 .. seq - 2`` of the cross-entropy of position
    ``i``'s float32 logits against token ``i + 1``."""
    h, counts = model.apply({"params": params}, tokens, method="hidden")
    with jax.named_scope("bf.head"):
        logits = model.apply({"params": params}, h[:, :-1], method="head")
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
        loss = jnp.mean(lse - picked)
    return loss, counts


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
