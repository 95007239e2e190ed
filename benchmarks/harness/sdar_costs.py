"""Operations and bytes of the ``sdar`` job's step, computed from shapes:
block-diffusion attention with grouped-query heads, the grouped products of
the held experts at their expected rows, and the model's FLOPs per clean
token. Kept with the yardstick like ``flops.py`` (which it follows: what
the forward and backward passes require, no recomputation, no padding; a
multiply-add is two operations), in a file of its own because a PR that
adds a configuration may add files here and edit none.

Also the ``jax.named_scope``s the decoder stack puts round its parts
(``bluefog_tpu/models/decoder.py``), which this job's per-layer readers
split the device step by."""

ATTN, ROUTE, EXPERTS, COMBINE, HEAD = PARTS = (
    "bf.attn", "bf.moe.route", "bf.moe.experts", "bf.moe.combine", "bf.head",
)
FLASH_KERNELS = ("bf_flash_fwd", "bf_flash_dkv", "bf_flash_dq")
# the grouped products' own Pallas kernels (`ops/moe.py`): forward and
# towards the rows, and towards the weights; all under `bf.moe.experts`
GROUPED_KERNELS = ("bf_gmm", "bf_tgmm")


def allowed_pairs(seq, block):
    """Pairs the block-diffusion mask allows over the ``2 seq`` positions
    of one sequence (``block`` divides ``seq``): a clean query sees the
    clean keys of its own and earlier blocks, a noised query the clean keys
    of earlier blocks and the noised keys of its own —
    ``seq^2 + seq x block`` of ``4 seq^2``."""
    if seq % block:
        raise ValueError("block must divide seq")
    blocks = seq // block
    clean_clean = block * block * blocks * (blocks + 1) // 2
    noised_clean = block * block * blocks * (blocks - 1) // 2
    noised_noised = block * block * blocks
    total = clean_clean + noised_clean + noised_noised
    assert total == seq * seq + seq * block
    return total


def layer_matmul_params(src):
    """Parameters of one layer that sit in matrix multiplications every
    position goes through: q, k, v, o and the router."""
    d, hd = src["hidden_size"], src["head_dim"]
    heads, kv = src["num_attention_heads"], src["num_key_value_heads"]
    return 2 * d * heads * hd + 2 * d * kv * hd


def expert_params(src):
    return 3 * src["hidden_size"] * src["moe_intermediate_size"]


def param_count(src, experts_total):
    """Parameters of ``models.DecoderLM`` at the file's sizes
    (``num_experts`` held of ``experts_total``): per layer q, k, v, o, two
    RMSNorms, the per-head q and k norms, the router over
    ``experts_total`` and the held experts; the token table, the final
    norm and the untied head."""
    d = src["hidden_size"]
    layer = (
        layer_matmul_params(src) + 2 * d + 2 * src["head_dim"]
        + d * experts_total + src["num_experts"] * expert_params(src)
    )
    return src["num_hidden_layers"] * layer + 2 * src["vocab_size"] * d + d


def expected_local_choices(src, experts_total):
    """Of a position's ``num_experts_per_tok`` choices, how many a uniform
    router lands on the ``num_experts`` held here."""
    return src["num_experts_per_tok"] * src["num_experts"] / experts_total


def matmul_params_per_token(src, experts_total):
    """``P_mm`` of one clean token: it is two positions (itself and its
    noised copy) through every layer — q, k, v, o, the router, and the
    expected local expert choices — and one position through the head
    (the head reads the noised half only)."""
    d = src["hidden_size"]
    layer = (
        layer_matmul_params(src) + d * experts_total
        + expected_local_choices(src, experts_total) * expert_params(src)
    )
    return 2 * src["num_hidden_layers"] * layer + d * src["vocab_size"]


def flops_per_token(src, experts_total, seq, block):
    """``6 P_mm + 12 (seq + block) head_dim heads layers``: forward and
    backward of the matrix multiplications, and of attention over the
    mask's area — per pair and head ``QK^T`` and ``PV`` forward
    (``4 head_dim``) and twice that backward, ``allowed_pairs / seq`` pairs
    a clean token."""
    attention = (
        12 * allowed_pairs(seq, block) // seq * src["head_dim"]
        * src["num_attention_heads"] * src["num_hidden_layers"]
    )
    return 6 * matmul_params_per_token(src, experts_total) + attention


def attention_cost(src, batch, seq, block, itemsize=2):
    """Operations and HBM bytes of block-diffusion flash attention, forward
    and backward, for one step, as ``flops.flash_attention_cost`` counts
    them for the causal kernel: per layer 2 matrix products forward and 5
    backward (the scores are recomputed once), each ``2 x pairs x
    head_dim`` over the mask's area and every query head; the
    query-sized tensors q, o forward and q, o, do, dq backward, the
    key-value-sized k, v forward and k, v, dk, dv backward (grouped-query
    heads: ``num_key_value_heads`` of them), each read or written once."""
    heads, kv, hd = (
        src["num_attention_heads"], src["num_key_value_heads"], src["head_dim"]
    )
    layers = src["num_hidden_layers"]
    pairs = batch * allowed_pairs(seq, block)
    positions = batch * 2 * seq
    return {
        "flops": layers * 7 * 2 * pairs * heads * hd,
        "bytes": layers * 6 * positions * (heads + kv) * hd * itemsize,
        "kernels": list(FLASH_KERNELS),
    }


def grouped_products_cost(src, experts_total, batch, seq, itemsize=2):
    """Operations and HBM bytes of the held experts' grouped products for
    one step at the expected rows (a uniform router): per layer
    ``rows = positions x expected_local_choices`` go through gate, up and
    down — 3 products forward, and for each its two backward products
    (towards the rows and towards the weights); the stacked weights are
    read once for each of the three passes, and each pass moves a row's
    hidden vector in and out and its three ``moe_intermediate_size``
    vectors. The rows of zeros that fill a group up to a tile are not
    counted: the kernels multiply them, the roof does not."""
    d, f = src["hidden_size"], src["moe_intermediate_size"]
    layers = src["num_hidden_layers"]
    rows = batch * 2 * seq * expected_local_choices(src, experts_total)
    weights = src["num_experts"] * expert_params(src)
    return {
        "flops": layers * 3 * 3 * 2 * rows * d * f,
        "bytes": layers * 3 * itemsize * (weights + rows * (2 * d + 3 * f)),
        "kernels": list(GROUPED_KERNELS),
    }
