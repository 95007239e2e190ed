"""The block-diffusion sparse-expert job: model, loss, seeded data and a
plain reference for a configuration whose ``job`` is ``sdar`` — a
``sdar_moe`` ``config.json`` (the source's keys at the top level of the
file, ``cells.source_entry``) run through ``models.DecoderLM`` and
``models.block_diffusion_loss``, as one chip's share of an expert-parallel
deployment: ``num_experts`` held of ``model.experts_total``, a slice of the
vocabulary whose last id stands for the mask token.

The reference (``reference_loss``) is the forward pass and the loss in
plain ``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``
from the equations in ``models/decoder.py``'s header: no flax module, no
kernel, nothing of ``bluefog_tpu``; a dense masked softmax with the mask
laid out from its definition, a loop over the held experts that computes
each expert for every position and keeps the chosen ones. It is computed
in blocks (a layer, a sequence, a key-value head's query group and a chunk
of queries at a time, each under ``jax.checkpoint``) so that it fits
beside its own parameters, momentum and gradients."""

import math

import jax
import jax.numpy as jnp

from bluefog_tpu import models

from benchmarks.harness import cells, sdar_costs

QUERY_CHUNK = 1024  # queries whose dense scores the reference holds at once


class Job:
    has_aux = True  # the expert layers' device counts come back beside the loss

    def __init__(self, config, traffic):
        src = self.src = cells.source_entry(config)
        own = self.own = config["model"]
        self.batch, self.seq = traffic["batch_per_worker"], traffic["seq"]
        self.block = own["block_length"]
        if not self.seq or self.seq % self.block:
            raise ValueError(f"seq {self.seq!r} must be a multiple of the block")
        if 2 * self.seq > src["max_position_embeddings"]:
            raise ValueError("the doubled sequence exceeds max_position_embeddings")
        total = own["experts_total"]
        want = sdar_costs.param_count(src, total)
        if config["n_params"] != want:
            raise ValueError(
                f"n_params {config['n_params']} is not what the sizes give, {want}"
            )
        want = sdar_costs.matmul_params_per_token(src, total)
        if config["flops"]["matmul_params_per_token"] != want:
            raise ValueError(
                f"flops.matmul_params_per_token is not what the sizes give, {want}"
            )
        self.cfg = models.DecoderConfig.from_source(
            src, experts_total=total, experts_start=own["experts_start"],
            compute_dtype=jnp.dtype(own["compute_dtype"]),
            param_dtype=jnp.dtype(own["param_dtype"]),
            head_dtype=jnp.dtype(own["head_dtype"]),
            router_dtype=jnp.dtype(own["router_dtype"]),
            qk_norm=own["qk_norm"], remat=own["remat"],
            initializer_range=own["initializer_range"],
            router_init=own["router_init"],
        )
        self.model = models.DecoderLM(self.cfg)
        self.mask_id = src["vocab_size"] - 1  # the slice's last id
        self.units_per_worker_step = self.batch * self.seq
        self.flops_per_unit = sdar_costs.flops_per_token(
            src, total, self.seq, self.block
        )
        # per layer 4 flash kernels (forward, again in the backward pass's
        # recomputation, dkv, dq) and 12 grouped products: gate, up, down
        # forward, again in the recomputation, and for each a product
        # towards the rows and one towards the weights
        self.mosaic_calls = own["mosaic_calls_per_layer"] * src["num_hidden_layers"]

    def init(self, key):
        """-> (params, counts of a step not yet run) of one worker."""
        cfg, key = self.cfg, _fast_key(key)
        tokens = jnp.zeros((1, 2 * self.block), jnp.int32)
        params = self.model.init(key, tokens)["params"]
        layers, held = cfg.num_hidden_layers, cfg.num_experts
        counts = {
            "rows_per_expert": jnp.zeros((layers, held), jnp.int32),
            "rows_absent": jnp.zeros((layers,), jnp.int32),
            "rows_dropped": jnp.zeros((layers,), jnp.int32),
        }
        return params, counts

    def make_batch(self, key, n):
        """Clean tokens from the vocabulary slice without its mask id, a
        uniform draw per position and a noise level per block, for each of
        ``n`` workers."""
        k_tok, k_draw, k_level = jax.random.split(_fast_key(key), 3)
        shape = (n, self.batch, self.seq)
        tokens = jax.random.randint(k_tok, shape, 0, self.mask_id, jnp.int32)
        draws = jax.random.uniform(k_draw, shape, jnp.float32)
        levels = jax.random.uniform(
            k_level, (n, self.batch, self.seq // self.block), jnp.float32,
            self.own["noise_level_min"], 1.0,
        )
        return tokens, draws, levels

    def loss_fn(self, params, counts, tokens, draws, levels):
        del counts  # last step's; this step returns its own
        return models.block_diffusion_loss(
            self.model, params, tokens, draws, levels,
            block=self.block, mask_id=self.mask_id,
        )

    def reference_loss_fn(self, params, counts, tokens, draws, levels):
        del counts
        return reference_loss(
            params, tokens, draws, levels, src=self.src,
            experts_start=self.own["experts_start"], block=self.block,
            mask_id=self.mask_id, qk_norm=self.own["qk_norm"],
        )

    def kernel_costs(self):
        total = self.own["experts_total"]
        return {
            "flash": sdar_costs.attention_cost(
                self.src, self.batch, self.seq, self.block
            ),
            "moe_experts": sdar_costs.grouped_products_cost(
                self.src, total, self.batch, self.seq
            ),
        }


def _fast_key(key):
    """The harness's key (threefry, two words) as a key of jax's ``rbg``
    generator, the same function of the seed: 645 M normal draws take the
    chip's own generator a moment and threefry half a minute, four times a
    run."""
    return jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")


# -- the plain reference ------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, positions, theta):
    """``x [t, heads, d]``: rotate-half over all of ``d``."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def block_diffusion_mask(seq, block, q_positions):
    """``[len(q_positions), 2 seq]`` from the definition: with ``blk(i) = i
    // block``, a clean query ``i`` sees clean key ``j`` iff ``blk(j) <=
    blk(i)`` and no noised key; a noised query ``seq + i`` sees clean key
    ``j`` iff ``blk(j) < blk(i)`` and noised key ``seq + j`` iff ``blk(j) ==
    blk(i)``."""
    k_positions = jnp.arange(2 * seq)
    q_noised = (q_positions >= seq)[:, None]
    k_noised = (k_positions >= seq)[None, :]
    q_blk = ((q_positions % seq) // block)[:, None]
    k_blk = ((k_positions % seq) // block)[None, :]
    return jnp.where(
        q_noised,
        jnp.where(k_noised, k_blk == q_blk, k_blk < q_blk),
        jnp.where(k_noised, False, k_blk <= q_blk),
    )


def _attend_group(q, k, v, seq, block):
    """One sequence, one key-value head and its query group: ``q [t, group,
    d]``, ``k, v [t, d]`` -> ``[t, group, d]``; dense masked softmax, a
    chunk of queries at a time."""
    t, group, d = q.shape
    chunk = math.gcd(t, QUERY_CHUNK)

    @jax.checkpoint
    def attend_chunk(args):
        q_chunk, q_positions = args
        scores = jnp.einsum("qgd,kd->gqk", q_chunk, k) / math.sqrt(d)
        allowed = block_diffusion_mask(seq, block, q_positions)
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(attend_chunk, (
        q.reshape(t // chunk, chunk, group, d),
        jnp.arange(t).reshape(t // chunk, chunk),
    ))
    return out.reshape(t, group, d)


def _attention(p, u, positions, src, seq, block, qk_norm):
    """``u [t, hidden]`` of one sequence -> ``[t, hidden]``."""
    heads, kv, d = (
        src["num_attention_heads"], src["num_key_value_heads"], src["head_dim"]
    )
    t = u.shape[0]
    q = (u @ p["q_proj"]["kernel"]).reshape(t, heads, d)
    k = (u @ p["k_proj"]["kernel"]).reshape(t, kv, d)
    v = (u @ p["v_proj"]["kernel"]).reshape(t, kv, d)
    if qk_norm:
        q = _rms_norm(q, p["q_norm"]["scale"], src["rms_norm_eps"])
        k = _rms_norm(k, p["k_norm"]["scale"], src["rms_norm_eps"])
    q = _rotary(q, positions, float(src["rope_theta"]))
    k = _rotary(k, positions, float(src["rope_theta"]))
    # query head h is served by key-value head h // (heads // kv)
    q = q.reshape(t, kv, heads // kv, d).transpose(1, 0, 2, 3)
    out = jax.lax.map(
        lambda args: _attend_group(*args, seq, block),
        (q, k.transpose(1, 0, 2), v.transpose(1, 0, 2)),
    )  # [kv, t, group, d]
    out = out.transpose(1, 0, 2, 3).reshape(t, heads * d)
    return out @ p["o_proj"]["kernel"]


def _experts(p, u, src, experts_start):
    """``u [t, hidden]`` -> this share's part of the layer's output, and
    the counts the program returns: the router over all of its outputs,
    the ``num_experts_per_tok`` largest, and every held expert computed
    for every position, kept where it was chosen."""
    probs = jax.nn.softmax(u @ p["router"], axis=-1)
    top, chosen = jax.lax.top_k(probs, src["num_experts_per_tok"])
    if src["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    held = p["w_gate"].shape[0]

    @jax.checkpoint
    def one_expert(y, args):
        e, w_gate, w_up, w_down = args
        weight = jnp.sum(jnp.where(chosen == experts_start + e, top, 0.0), axis=-1)
        out = (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down
        return y + weight[:, None] * out, None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]),
    )
    local = chosen - experts_start
    landed = (local >= 0) & (local < held)
    per_expert = jnp.sum(
        (local[..., None] == jnp.arange(held)) & landed[..., None], axis=(0, 1)
    )
    return y, per_expert.astype(jnp.int32), jnp.sum(~landed).astype(jnp.int32)


def reference_loss(params, tokens, draws, levels, *, src, experts_start, block,
                   mask_id, qk_norm=True):
    """The block-diffusion loss of one worker's batch, ``-> (loss,
    counts)``, float32 throughout at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), params)
        b, seq = tokens.shape
        eps = src["rms_norm_eps"]
        t_level = jnp.repeat(levels, block, axis=1)
        masked = draws < t_level
        noised = jnp.where(masked, mask_id, tokens)
        doubled = jnp.concatenate([tokens, noised], axis=1)
        positions = jnp.concatenate([jnp.arange(seq), jnp.arange(seq)])
        x = params["embed"]["embedding"][doubled]  # [b, 2 seq, hidden]

        @jax.checkpoint
        def layer(p, x):
            def one_sequence(x):
                u = _rms_norm(x, p["input_norm"]["scale"], eps)
                h = x + _attention(
                    p["attn"], u, positions, src, seq, block, qk_norm
                )
                u = _rms_norm(h, p["post_attn_norm"]["scale"], eps)
                y, per_expert, absent = _experts(
                    p["experts"], u, src, experts_start
                )
                return h + y, per_expert, absent

            x, per_expert, absent = jax.lax.map(one_sequence, x)
            return x, per_expert.sum(axis=0), absent.sum()

        per_layer = []
        for i in range(src["num_hidden_layers"]):
            x, per_expert, absent = layer(params[f"layer_{i}"], x)
            per_layer.append((per_expert, absent))
        h = _rms_norm(x[:, seq:], params["final_norm"]["scale"], eps)
        logits = h @ params["lm_head"]["kernel"]
        picked = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        ce = jax.nn.logsumexp(logits, axis=-1) - picked
        loss = jnp.sum(jnp.where(masked, ce / t_level, 0.0)) / (b * seq)
        counts = {
            "rows_per_expert": jnp.stack([c[0] for c in per_layer]),
            "rows_absent": jnp.stack([c[1] for c in per_layer]),
            "rows_dropped": jnp.zeros((len(per_layer),), jnp.int32),
        }
        return loss, counts
