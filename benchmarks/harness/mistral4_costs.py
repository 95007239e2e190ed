"""Operations and bytes of the ``mistral4`` job's step, computed from
shapes: latent attention (MLA) through the causal flash kernels, the shared
expert, the grouped products of the held routed experts at their expected
rows, and the model's FLOPs per token. Kept with the yardstick like
``flops.py`` and ``sdar_costs.py`` (whose rules it follows: what the
forward and backward passes require, no recomputation, no padding; a
multiply-add is two operations), in a file of its own because a PR that
adds a configuration may add files here and edit none.

Also the ``jax.named_scope``s ``bluefog_tpu/models/decoder.py`` puts round
this stack's parts, which the job's per-layer readers split the device step
by. ``bf.attn.latent`` lies inside ``bf.attn`` and stands before it: the
first name that matches at a place in an ``op_name`` is taken
(``scopes.scope_of``), and the innermost of an ``op_name`` decides."""

from benchmarks.harness import sdar_costs

LATENT, ATTN, ROUTE, EXPERTS, COMBINE, SHARED, HEAD = PARTS = (
    "bf.attn.latent", "bf.attn", "bf.moe.route", "bf.moe.experts",
    "bf.moe.combine", "bf.moe.shared", "bf.head",
)
FLASH_KERNELS, GROUPED_KERNELS = sdar_costs.FLASH_KERNELS, sdar_costs.GROUPED_KERNELS
expert_params = sdar_costs.expert_params  # a gated expert: 3 x hidden x width


def attention_params(src):
    """Latent attention's five matrices: the query's low-rank pair, the
    compressed key-value stream with its one rotary key, its expansion to a
    key and a value a head, and the output projection."""
    d, heads = src["hidden_size"], src["num_attention_heads"]
    qk = src["qk_nope_head_dim"] + src["qk_rope_head_dim"]
    return (
        d * src["q_lora_rank"] + src["q_lora_rank"] * heads * qk
        + d * (src["kv_lora_rank"] + src["qk_rope_head_dim"])
        + src["kv_lora_rank"] * heads * (src["qk_nope_head_dim"] + src["v_head_dim"])
        + heads * src["v_head_dim"] * d
    )


def param_count(src, experts_total):
    """Parameters of ``models.DecoderLM`` at the file's sizes
    (``n_routed_experts`` held of ``experts_total``): per layer latent
    attention, four RMSNorm scales (input, the two latents', post-attention),
    the router over ``experts_total``, the shared expert and the held
    experts; the token table, the final norm and the untied head."""
    d = src["hidden_size"]
    layer = (
        attention_params(src)
        + 2 * d + src["q_lora_rank"] + src["kv_lora_rank"]
        + d * experts_total
        + (src["n_shared_experts"] + src["n_routed_experts"]) * expert_params(src)
    )
    return src["num_hidden_layers"] * layer + 2 * src["vocab_size"] * d + d


def expected_local_choices(src, experts_total):
    """Of a position's ``num_experts_per_tok`` choices, how many a uniform
    router lands on the ``n_routed_experts`` held here."""
    return src["num_experts_per_tok"] * src["n_routed_experts"] / experts_total


def matmul_params_per_token(src, experts_total):
    """``P_mm``: a token is one position of every layer — latent attention,
    the router, the shared expert and the expected local routed choices —
    and of the head."""
    d = src["hidden_size"]
    layer = (
        attention_params(src) + d * experts_total
        + (src["n_shared_experts"] + expected_local_choices(src, experts_total))
        * expert_params(src)
    )
    return src["num_hidden_layers"] * layer + d * src["vocab_size"]


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def flops_per_token(src, experts_total, seq):
    """``6 P_mm + 12 (seq + 1) / 2 x head_dim x heads x layers``: forward
    and backward of the matrix multiplications, and of attention over the
    causal triangle — per pair and head ``QK^T`` and ``PV`` forward
    (``4 head_dim``) and twice that backward, ``(seq + 1) / 2`` pairs a
    token."""
    attention = (
        12 * causal_pairs(seq) * src["head_dim"] * src["num_attention_heads"]
        * src["num_hidden_layers"] // seq
    )
    return 6 * matmul_params_per_token(src, experts_total) + attention


def attention_cost(src, batch, seq, itemsize=2):
    """Operations and HBM bytes of causal flash attention at the expanded
    heads, forward and backward, for one step, as ``sdar_costs`` counts its
    own: per layer 2 matrix products forward and 5 backward, each ``2 x
    pairs x head_dim`` over the triangle and every head; q, o forward and
    q, o, do, dq backward, k, v forward and k, v, dk, dv backward (a key
    and a value a head: latent attention is expanded before the kernels),
    each read or written once."""
    heads, hd = src["num_attention_heads"], src["head_dim"]
    layers = src["num_hidden_layers"]
    return {
        "flops": layers * 7 * 2 * batch * causal_pairs(seq) * heads * hd,
        "bytes": layers * 6 * batch * seq * 2 * heads * hd * itemsize,
        "kernels": list(FLASH_KERNELS),
    }


def grouped_products_cost(src, experts_total, batch, seq, itemsize=2):
    """Operations and HBM bytes of the held routed experts' grouped
    products for one step at the expected rows (a uniform router), as
    ``sdar_costs.grouped_products_cost`` counts them: 3 products forward
    and two backward for each, the stacked weights read once a pass. The
    rows of zeros that fill a group up to a tile are not counted."""
    d, f = src["hidden_size"], src["moe_intermediate_size"]
    layers = src["num_hidden_layers"]
    rows = batch * seq * expected_local_choices(src, experts_total)
    weights = src["n_routed_experts"] * expert_params(src)
    return {
        "flops": layers * 3 * 3 * 2 * rows * d * f,
        "bytes": layers * 3 * itemsize * (weights + rows * (2 * d + 3 * f)),
        "kernels": list(GROUPED_KERNELS),
    }
