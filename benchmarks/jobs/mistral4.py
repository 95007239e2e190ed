"""The latent-attention sparse-expert job: model, loss, seeded data and a
plain reference for a configuration whose ``job`` is ``mistral4`` — a
``mistral4`` ``config.json`` (the source's keys at the top level of the
file, ``cells.source_entry``) trained on next-token prediction through
``models.DecoderLM`` and ``models.next_token_loss``, as one chip's share of
an expert-parallel deployment: ``n_routed_experts`` held of
``model.experts_total`` beside a shared expert that is whole here, and a
slice of the vocabulary the token ids are drawn from.

The reference (``reference_loss``) is the forward pass and the loss in
plain ``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``
from the equations in ``models/decoder.py``'s header: no flax module, no
kernel, nothing of ``bluefog_tpu``; a dense causal softmax over explicit
per-head keys (the one rotary key broadcast), yarn's frequencies from the
``rope_parameters`` group, the shared expert once, and a loop over the held
experts that computes each for every position and keeps the chosen ones. It
is computed in blocks (a layer, a sequence, a head, a chunk of queries or
of the head's positions at a time, each under ``jax.checkpoint``) so that
it fits beside its own parameters, momentum and gradients."""

import math

import jax
import jax.numpy as jnp

from bluefog_tpu import models

from benchmarks.harness import cells, mistral4_costs
from benchmarks.jobs.sdar import _fast_key, _rms_norm

QUERY_CHUNK = 1024  # queries whose dense scores the reference holds at once
HEAD_CHUNK = 1024  # positions whose logits over the vocabulary it holds at once


class Job:
    has_aux = True  # the expert layers' device counts come back beside the loss

    def __init__(self, config, traffic):
        if not hasattr(models, "next_token_loss"):
            raise RuntimeError(
                "this checkout's models/decoder.py builds no latent attention "
                "and has no next_token_loss: it cannot run a mistral4 configuration"
            )
        src = self.src = cells.source_entry(config)
        own = self.own = config["model"]
        self.batch, self.seq = traffic["batch_per_worker"], traffic["seq"]
        if not self.seq or self.seq > src["max_position_embeddings"]:
            raise ValueError(f"seq {self.seq!r} exceeds max_position_embeddings")
        total = own["experts_total"]
        want = mistral4_costs.param_count(src, total)
        if config["n_params"] != want:
            raise ValueError(
                f"n_params {config['n_params']} is not what the sizes give, {want}"
            )
        want = mistral4_costs.matmul_params_per_token(src, total)
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
            remat=own["remat"], initializer_range=own["initializer_range"],
            router_init=own["router_init"],
        )
        self.model = models.DecoderLM(self.cfg)
        self.units_per_worker_step = self.batch * self.seq
        self.flops_per_unit = mistral4_costs.flops_per_token(src, total, self.seq)
        # per layer 4 flash kernels (forward, again in the backward pass's
        # recomputation, dkv, dq) and 12 grouped products: gate, up, down
        # forward, again in the recomputation, and for each a product
        # towards the rows and one towards the weights
        self.mosaic_calls = own["mosaic_calls_per_layer"] * src["num_hidden_layers"]

    def init(self, key):
        """-> (params, counts of a step not yet run) of one worker."""
        cfg = self.cfg
        tokens = jnp.zeros((1, 8), jnp.int32)
        params = self.model.init(_fast_key(key), tokens)["params"]
        layers, held = cfg.num_hidden_layers, cfg.num_experts
        counts = {
            "rows_per_expert": jnp.zeros((layers, held), jnp.int32),
            "rows_absent": jnp.zeros((layers,), jnp.int32),
            "rows_dropped": jnp.zeros((layers,), jnp.int32),
        }
        return params, counts

    def make_batch(self, key, n):
        """Token ids uniform over the vocabulary slice, for each of ``n``
        workers: ``batch`` sequences of ``seq`` tokens, no padding."""
        shape = (n, self.batch, self.seq)
        return (
            jax.random.randint(
                _fast_key(key), shape, 0, self.src["vocab_size"], jnp.int32
            ),
        )

    def loss_fn(self, params, counts, tokens):
        del counts  # last step's; this step returns its own
        return models.next_token_loss(self.model, params, tokens)

    def reference_loss_fn(self, params, counts, tokens):
        del counts
        return reference_loss(
            params, tokens, src=self.src, experts_start=self.own["experts_start"]
        )

    def kernel_costs(self):
        total = self.own["experts_total"]
        return {
            "flash": mistral4_costs.attention_cost(self.src, self.batch, self.seq),
            "moe_experts": mistral4_costs.grouped_products_cost(
                self.src, total, self.batch, self.seq
            ),
        }


# -- the plain reference ------------------------------------------------------


def rotary_tables(rope, d, head_dim, positions):
    """``(cos, sin) [t, d / 2]`` and the softmax's scale for rotary
    positions over ``d`` dims under a ``rope_parameters`` group. Yarn
    (``rope_type``): with ``f_i = theta^(-2i/d)`` and ``corr(b) = d ln(L / (2
    pi b)) / (2 ln theta)`` over the original length ``L``, ``low =
    max(floor(corr(beta_fast)), 0)``, ``high = min(ceil(corr(beta_slow)), d -
    1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``, the frequency
    is ``(f_i / factor) ramp_i + f_i (1 - ramp_i)``; with ``m(a) = 0.1 a ln
    factor + 1``, cos and sin are scaled by ``m(mscale) / m(mscale_all_dim)``
    and the softmax by ``m(mscale_all_dim)^2 / sqrt(head_dim)``."""
    theta = float(rope["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * i / d)
    amplitude, scale = 1.0, 1.0 / math.sqrt(head_dim)
    if rope["rope_type"] == "yarn":
        length, factor = rope["original_max_position_embeddings"], rope["factor"]

        def corr(turns):
            return d * math.log(length / (2 * math.pi * turns)) / (2 * math.log(theta))

        low = max(math.floor(corr(rope["beta_fast"])), 0)
        high = min(math.ceil(corr(rope["beta_slow"])), d - 1)
        ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
        freq = freq / factor * ramp + freq * (1.0 - ramp)
        m = lambda a: 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0
        amplitude = m(rope.get("mscale", 1)) / m(rope.get("mscale_all_dim", 0))
        scale = m(rope.get("mscale_all_dim", 0)) ** 2 / math.sqrt(head_dim)
    angles = positions.astype(jnp.float32)[:, None] * freq[None, :]
    return amplitude * jnp.cos(angles), amplitude * jnp.sin(angles), scale


def _rotate_pairs(x, cos, sin):
    """Rotary positions over the interleaved pairs ``(2i, 2i+1)`` of the
    last axis; ``cos``, ``sin`` broadcast against ``x[..., 0::2]``."""
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def _attend_head(q, k, v, scale):
    """One head of one sequence: ``q, k, v [t, d]`` -> ``[t, d]``; a dense
    causal softmax, a chunk of queries at a time."""
    t, d = q.shape
    chunk = math.gcd(t, QUERY_CHUNK)

    @jax.checkpoint
    def attend_chunk(args):
        q_chunk, q_index = args
        scores = scale * (q_chunk @ k.T)
        seen = q_index[:, None] >= jnp.arange(t)[None, :]
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(attend_chunk, (
        q.reshape(t // chunk, chunk, d), jnp.arange(t).reshape(t // chunk, chunk),
    ))
    return out.reshape(t, d)


def _latent_attention(p, u, positions, src):
    """``u [t, hidden]`` of one sequence -> ``[t, hidden]``."""
    heads, eps = src["num_attention_heads"], src["rms_norm_eps"]
    nope, rope, rank = (
        src["qk_nope_head_dim"], src["qk_rope_head_dim"], src["kv_lora_rank"]
    )
    t = u.shape[0]
    c_q = _rms_norm(u @ p["q_a_proj"]["kernel"], p["q_a_norm"]["scale"], eps)
    q = (c_q @ p["q_b_proj"]["kernel"]).reshape(t, heads, nope + rope)
    stream = u @ p["kv_a_proj"]["kernel"]
    c_kv = _rms_norm(stream[:, :rank], p["kv_a_norm"]["scale"], eps)
    kv = (c_kv @ p["kv_b_proj"]["kernel"]).reshape(t, heads, -1)
    group = src["rope_parameters"]
    cos, sin, scale = rotary_tables(group, rope, nope + rope, positions)
    q_rope = _rotate_pairs(q[..., nope:], cos[:, None], sin[:, None])
    k_rope = _rotate_pairs(stream[:, rank:], cos, sin)  # one key a position
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    beta = group.get("llama_4_scaling_beta")
    if beta:
        chunks = jnp.floor(positions / group["original_max_position_embeddings"])
        q = q * (1.0 + beta * jnp.log1p(chunks))[:, None, None]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (t, heads, rope))], axis=-1
    )
    per_head = lambda x: x.transpose(1, 0, 2)
    out = jax.lax.map(
        lambda args: _attend_head(*args, scale),
        (per_head(q), per_head(k), per_head(kv[..., nope:])),
    )  # [heads, t, v_head_dim]
    return per_head(out).reshape(t, -1) @ p["o_proj"]["kernel"]


def _gated(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _shared_expert(p, u):
    return _gated(
        u, p["gate_proj"]["kernel"], p["up_proj"]["kernel"], p["down_proj"]["kernel"]
    )


def _routed_experts(p, u, src, experts_start):
    """``u [t, hidden]`` -> this share's part of the routed sum, and the
    counts the program returns: a float32 softmax over all of the router's
    outputs, the ``num_experts_per_tok`` largest renormalised, and every
    held expert computed for every position, kept where it was chosen."""
    probs = jax.nn.softmax(u @ p["router"], axis=-1)
    top, chosen = jax.lax.top_k(probs, src["num_experts_per_tok"])
    if src["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    held = p["w_gate"].shape[0]

    @jax.checkpoint
    def part(e, w_gate, w_up, w_down):
        weight = jnp.sum(jnp.where(chosen == experts_start + e, top, 0.0), axis=-1)
        return weight[:, None] * _gated(u, w_gate, w_up, w_down)

    y, _ = jax.lax.scan(
        lambda y, expert: (y + part(*expert), None), jnp.zeros_like(u),
        (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]),
    )
    local = chosen - experts_start
    landed = (local >= 0) & (local < held)
    per_expert = jnp.sum(
        (local[..., None] == jnp.arange(held)) & landed[..., None], axis=(0, 1)
    )
    return y, per_expert.astype(jnp.int32), jnp.sum(~landed).astype(jnp.int32)


def _next_token_ce(h, w_head, tokens):
    """One sequence: the sum over positions ``0 .. t - 2`` of the
    cross-entropy of ``h_i @ w_head`` against ``tokens_{i+1}``, a chunk of
    positions at a time (the last position goes through the head too, and
    counts for nothing)."""
    t = h.shape[0]
    chunk = math.gcd(t, HEAD_CHUNK)
    in_chunks = lambda x: x.reshape(t // chunk, chunk, *x.shape[1:])

    @jax.checkpoint
    def ce_chunk(total, args):
        h_chunk, wanted, counted = args
        logits = h_chunk @ w_head
        picked = jnp.take_along_axis(logits, wanted[:, None], axis=-1)[:, 0]
        ce = jax.nn.logsumexp(logits, axis=-1) - picked
        return total + jnp.sum(jnp.where(counted, ce, 0.0)), None

    total, _ = jax.lax.scan(ce_chunk, jnp.zeros((), jnp.float32), (
        in_chunks(h), in_chunks(jnp.roll(tokens, -1)),
        in_chunks(jnp.arange(t) < t - 1),
    ))
    return total


def reference_loss(params, tokens, *, src, experts_start):
    """The next-token loss of one worker's batch, ``-> (loss, counts)``,
    float32 throughout at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), params)
        eps = src["rms_norm_eps"]
        positions = jnp.arange(tokens.shape[1])
        x = params["embed"]["embedding"][tokens]  # [b, seq, hidden]

        @jax.checkpoint
        def layer(p, x):
            def one_sequence(x):
                u = _rms_norm(x, p["input_norm"]["scale"], eps)
                h = x + _latent_attention(p["attn"], u, positions, src)
                u = _rms_norm(h, p["post_attn_norm"]["scale"], eps)
                routed, per_expert, absent = _routed_experts(
                    p["experts"], u, src, experts_start
                )
                y = _shared_expert(p["shared_expert"], u)
                return (
                    h + y + src["routed_scaling_factor"] * routed, per_expert, absent
                )

            x, per_expert, absent = jax.lax.map(one_sequence, x)
            return x, per_expert.sum(axis=0), absent.sum()

        per_layer = []
        for i in range(src["num_hidden_layers"]):
            x, per_expert, absent = layer(params[f"layer_{i}"], x)
            per_layer.append((per_expert, absent))
        h = _rms_norm(x, params["final_norm"]["scale"], eps)
        sums = jax.lax.map(
            lambda args: _next_token_ce(args[0], params["lm_head"]["kernel"], args[1]),
            (h, tokens),
        )
        loss = jnp.sum(sums) / (tokens.shape[0] * (tokens.shape[1] - 1))
        counts = {
            "rows_per_expert": jnp.stack([c[0] for c in per_layer]),
            "rows_absent": jnp.stack([c[1] for c in per_layer]),
            "rows_dropped": jnp.zeros((len(per_layer),), jnp.int32),
        }
        return loss, counts
