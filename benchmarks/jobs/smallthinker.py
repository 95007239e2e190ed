"""The window/global sparse-expert job: model, loss, seeded data and a plain
reference for a configuration whose ``job`` is ``smallthinker`` — a
SmallThinker ``config.json`` (the source's keys at the top level of the
file, ``cells.source_entry``) trained on next-token prediction through
``models.DecoderLM`` and ``models.next_token_loss``, as one chip's share of
an expert-parallel deployment: ``moe_num_primary_experts`` held of
``model.experts_total`` and a slice of the vocabulary the token ids are
drawn from. The layers ``sliding_window_layout`` marks attend to the last
``sliding_window_size`` positions, the others causally over the whole
sequence; ``rope_layout`` marks the layers with rotary positions; the
router reads the attention's input and the experts are ReGLU.

The reference (``reference_loss``) is the forward pass and the loss in
plain ``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``
from the equations in ``models/decoder.py``'s header: no flax module, no
kernel, nothing of ``bluefog_tpu``. Attention is a dense softmax a chunk of
queries at a time over every key, with the window written out here from its
definition; the experts are computed for every position and kept where
chosen; the cross-entropy is ``jobs/mistral4.py``'s, a chunk at a time."""

import math

import jax
import jax.numpy as jnp

from bluefog_tpu import models
# the parent has no window: it fails here, at once
from bluefog_tpu.ops.flash import SlidingWindowMask  # noqa: F401

from benchmarks.harness import cells, smallthinker_costs
from benchmarks.jobs.mistral4 import QUERY_CHUNK, _next_token_ce
from benchmarks.jobs.sdar import _fast_key, _rms_norm


class Job:
    has_aux = True  # the expert layers' device counts come back beside the loss

    def __init__(self, config, traffic):
        src = self.src = cells.source_entry(config)
        own = self.own = config["model"]
        self.batch, self.seq = traffic["batch_per_worker"], traffic["seq"]
        if not self.seq or self.seq > src["max_position_embeddings"]:
            raise ValueError(f"seq {self.seq!r} exceeds max_position_embeddings")
        total = own["experts_total"]
        want = smallthinker_costs.param_count(src, total)
        if config["n_params"] != want:
            raise ValueError(
                f"n_params {config['n_params']} is not what the sizes give, {want}"
            )
        want = smallthinker_costs.matmul_params_per_token(src, own)
        if config["flops"]["matmul_params_per_token"] != want:
            raise ValueError(
                f"flops.matmul_params_per_token is not what the sizes give, {want}"
            )
        self.cfg = models.DecoderConfig.from_source(
            src, experts_total=total, experts_start=own["experts_start"],
            qk_norm=own["qk_norm"], hidden_act=own["hidden_act"],
            router_input=own["router_input"],
            compute_dtype=jnp.dtype(own["compute_dtype"]),
            param_dtype=jnp.dtype(own["param_dtype"]),
            head_dtype=jnp.dtype(own["head_dtype"]),
            router_dtype=jnp.dtype(own["router_dtype"]),
            remat=own["remat"], initializer_range=own["initializer_range"],
            router_init=own["router_init"],
        )
        self.model = models.DecoderLM(self.cfg)
        self.units_per_worker_step = self.batch * self.seq
        self.flops_per_unit = smallthinker_costs.flops_per_token(src, own, self.seq)
        # per layer 4 flash kernels (forward, again in the backward pass's
        # recomputation, dkv, dq) and 12 grouped products: gate, up, down
        # forward, again in the recomputation, and for each a product
        # towards the rows and one towards the weights
        self.mosaic_calls = 16 * src["num_hidden_layers"]

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
            "act_zeros": jnp.zeros((layers,), jnp.int32),
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
        return reference_loss(params, tokens, src=self.src, own=self.own)

    def kernel_costs(self):
        return {
            "flash_window": smallthinker_costs.attention_cost(
                self.src, self.batch, self.seq, window_only=True
            ),
            "flash": smallthinker_costs.attention_cost(self.src, self.batch, self.seq),
            "moe_experts": smallthinker_costs.grouped_products_cost(
                self.src, self.own, self.batch, self.seq
            ),
        }


# -- the plain reference ------------------------------------------------------


def _rotate_half(x, positions, theta):
    """Rotary positions over all of the last axis, its two halves turned as
    pairs ``(i, i + d / 2)``: ``x [t, heads, d]``, ``positions [t]``."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    )


def _attend_head(q, k, v, scale, window):
    """One head of one sequence: ``q, k, v [t, d]`` -> ``[t, d]``; query
    ``i`` sees key ``j`` iff ``j <= i`` and, with a ``window``, ``i - j <
    window``; a dense softmax a chunk of queries at a time."""
    t, d = q.shape
    chunk = math.gcd(t, QUERY_CHUNK)

    @jax.checkpoint
    def attend_chunk(args):
        q_chunk, q_index = args
        scores = scale * (q_chunk @ k.T)
        behind = q_index[:, None] - jnp.arange(t)[None, :]  # i - j
        seen = behind >= 0
        if window is not None:
            seen = seen & (behind < window)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(attend_chunk, (
        q.reshape(t // chunk, chunk, d), jnp.arange(t).reshape(t // chunk, chunk),
    ))
    return out.reshape(t, d)


def _attention(p, u, positions, src, window, rope):
    """``u [t, hidden]`` of one sequence -> ``[t, hidden]``: grouped-query
    heads, each key-value head serving ``heads / kv`` query heads."""
    t, hd, heads = u.shape[0], src["head_dim"], src["num_attention_heads"]
    q = (u @ p["q_proj"]["kernel"]).reshape(t, heads, hd)
    k = (u @ p["k_proj"]["kernel"]).reshape(t, -1, hd)
    v = (u @ p["v_proj"]["kernel"]).reshape(t, -1, hd)
    if rope:
        q, k = (_rotate_half(x, positions, float(src["rope_theta"])) for x in (q, k))
    group = heads // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    per_head = lambda x: x.transpose(1, 0, 2)
    out = jax.lax.map(
        lambda args: _attend_head(*args, 1.0 / math.sqrt(hd), window),
        (per_head(q), per_head(k), per_head(v)),
    )  # [heads, t, hd]
    return per_head(out).reshape(t, heads * hd) @ p["o_proj"]["kernel"]


def _reglu_experts(p, u_route, u, src, experts_start):
    """This share's part of the routed sum for ``u [t, hidden]``, routed
    from ``u_route``, and the counts the program returns: a float32 softmax
    over all of the router's outputs, the ``moe_num_active_primary_experts``
    largest renormalised, and every held expert ``down(relu(u W_gate) * (u
    W_up))`` computed for every position, kept where it was chosen;
    ``act_zeros`` counts the entries of ``relu(u W_gate)`` that are 0 at the
    positions that chose the expert."""
    probs = jax.nn.softmax(u_route @ p["router"], axis=-1)
    top, chosen = jax.lax.top_k(probs, src["moe_num_active_primary_experts"])
    if src["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    held = p["w_gate"].shape[0]

    @jax.checkpoint
    def part(e, w_gate, w_up, w_down):
        is_e = chosen == experts_start + e
        weight = jnp.sum(jnp.where(is_e, top, 0.0), axis=-1)
        gate = u @ w_gate
        y = (jax.nn.relu(gate) * (u @ w_up)) @ w_down
        zeros = jnp.sum(jnp.where(is_e.any(axis=-1)[:, None], gate <= 0, False))
        return weight[:, None] * y, zeros.astype(jnp.int32)

    def add(carry, expert):
        y, zeros = carry
        part_y, part_zeros = part(*expert)
        return (y + part_y, zeros + part_zeros), None

    (y, act_zeros), _ = jax.lax.scan(
        add, (jnp.zeros_like(u), jnp.zeros((), jnp.int32)),
        (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]),
    )
    local = chosen - experts_start
    landed = (local >= 0) & (local < held)
    per_expert = jnp.sum(
        (local[..., None] == jnp.arange(held)) & landed[..., None], axis=(0, 1)
    )
    return y, per_expert.astype(jnp.int32), jnp.sum(~landed).astype(jnp.int32), act_zeros


def reference_loss(params, tokens, *, src, own):
    """The next-token loss of one worker's batch, ``-> (loss, counts)``,
    float32 throughout at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), params)
        eps = src["rms_norm_eps"]
        positions = jnp.arange(tokens.shape[1])
        x = params["embed"]["embedding"][tokens]  # [b, seq, hidden]

        def layer_of(window, rope):
            @jax.checkpoint
            def layer(p, x):
                def one_sequence(x):
                    u = _rms_norm(x, p["input_norm"]["scale"], eps)
                    h = x + _attention(p["attn"], u, positions, src, window, rope)
                    u_post = _rms_norm(h, p["post_attn_norm"]["scale"], eps)
                    routed, per_expert, absent, zeros = _reglu_experts(
                        p["experts"], u, u_post, src, own["experts_start"]
                    )
                    return h + routed, per_expert, absent, zeros

                x, per_expert, absent, zeros = jax.lax.map(one_sequence, x)
                return x, per_expert.sum(axis=0), absent.sum(), zeros.sum()

            return layer

        per_layer = []
        for i in range(src["num_hidden_layers"]):
            window = src["sliding_window_size"] if src["sliding_window_layout"][i] else None
            x, *counts = layer_of(window, bool(src["rope_layout"][i]))(
                params[f"layer_{i}"], x
            )
            per_layer.append(counts)
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
            "act_zeros": jnp.stack([c[2] for c in per_layer]),
        }
        return loss, counts
