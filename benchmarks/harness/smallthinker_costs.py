"""Operations and bytes of the ``smallthinker`` job's step, computed from
shapes: grouped-query attention through the flash kernels, causal on the
global layers and over the band of a sliding window on the others, the held
ReGLU experts at the rows that land, and the model's FLOPs per token. Kept
with the yardstick like ``mistral4_costs.py`` (whose rules it follows: what
the forward and backward passes require, no recomputation, no padding; a
multiply-add is two operations), in a file of its own because a PR that
adds a configuration may add files here and edit none.

The source names its sizes SmallThinker's way (``moe_num_primary_experts``
held here, ``moe_num_active_primary_experts`` a token,
``moe_ffn_hidden_size``) and marks each layer in ``sliding_window_layout``
(1: a window of ``sliding_window_size``). ``PARTS`` are the
``jax.named_scope``s ``models/decoder.py`` puts round this stack's parts;
``bf.attn.window`` lies inside ``bf.attn`` and stands before it
(``scopes.scope_of``: the innermost of an ``op_name`` decides)."""

from benchmarks.harness import mistral4_costs, sdar_costs

WINDOW, ATTN, ROUTE, EXPERTS, COMBINE, HEAD = PARTS = (
    "bf.attn.window", "bf.attn", "bf.moe.route", "bf.moe.experts",
    "bf.moe.combine", "bf.head",
)
FLASH_KERNELS, GROUPED_KERNELS = sdar_costs.FLASH_KERNELS, sdar_costs.GROUPED_KERNELS


def windows(src):
    """Of the file's ``num_hidden_layers``, the window of each (``None``: a
    global layer), from the first entries of ``sliding_window_layout``."""
    layout = src["sliding_window_layout"]
    return [
        src["sliding_window_size"] if layout[i] else None
        for i in range(src["num_hidden_layers"])
    ]


def band_pairs(seq, window):
    """Pairs ``(i, j)`` with ``j <= i < seq`` and ``i - j < window``: query
    ``i`` sees ``min(i + 1, window)`` keys. ``window`` None: the causal
    triangle."""
    if window is None or window >= seq:
        return mistral4_costs.causal_pairs(seq)
    return window * (window + 1) // 2 + (seq - window) * window


def attention_params(src):
    """q and o (hidden x heads x head_dim each), k and v (hidden x kv heads x
    head_dim each): no bias, no q/k norm."""
    d, hd = src["hidden_size"], src["head_dim"]
    return 2 * d * hd * (src["num_attention_heads"] + src["num_key_value_heads"])


def expert_params(src):
    """A ReGLU expert: gate, up and down, 3 x hidden x width."""
    return 3 * src["hidden_size"] * src["moe_ffn_hidden_size"]


def param_count(src, experts_total):
    """Parameters of ``models.DecoderLM`` at the file's sizes
    (``moe_num_primary_experts`` held of ``experts_total``): per layer the
    attention's four matrices, two RMSNorm scales, the router over
    ``experts_total`` and the held experts; the token table, the final norm
    and the untied head."""
    d = src["hidden_size"]
    layer = (
        attention_params(src) + 2 * d + d * experts_total
        + src["moe_num_primary_experts"] * expert_params(src)
    )
    return src["num_hidden_layers"] * layer + 2 * src["vocab_size"] * d + d


def local_choices(src, model):
    """Of a position's ``moe_num_active_primary_experts`` choices, how many
    land on the ``moe_num_primary_experts`` held here in expectation, of
    ``model.experts_total``: the router's columns are independent draws
    (``router_init`` normal; the tiled router needs k to divide the total)."""
    k, held = src["moe_num_active_primary_experts"], src["moe_num_primary_experts"]
    return k * held / model["experts_total"]


def matmul_params_per_token(src, model):
    """``P_mm``: a token is one position of every layer — the attention's
    matrices, the router and the local choices — and of the head."""
    d = src["hidden_size"]
    layer = (
        attention_params(src) + d * model["experts_total"]
        + local_choices(src, model) * expert_params(src)
    )
    return src["num_hidden_layers"] * layer + d * src["vocab_size"]


def flops_per_token(src, model, seq):
    """``6 P_mm + 12 x pairs / seq x head_dim x heads`` a layer: forward and
    backward of the matrix multiplications, and of attention over each
    layer's allowed pairs (``band_pairs``: the causal triangle on a global
    layer, the window's band on the others) — per pair and head ``QK^T``
    and ``PV`` forward (``4 head_dim``) and twice that backward."""
    per_pair = 12 * src["head_dim"] * src["num_attention_heads"]
    attention = sum(band_pairs(seq, w) for w in windows(src)) * per_pair // seq
    return 6 * matmul_params_per_token(src, model) + attention


def attention_cost(src, batch, seq, window_only=False, itemsize=2):
    """Operations and HBM bytes of the flash kernels for one step, as
    ``sdar_costs.attention_cost`` counts them: per layer 2 matrix products
    forward and 5 backward, each ``2 x pairs x head_dim`` over the layer's
    allowed pairs and every query head; q, o forward and q, o, do, dq
    backward at the query heads, k, v forward and k, v, dk, dv backward at
    the key-value heads (a grouped kernel reads each once). ``window_only``:
    the window layers' kernels alone, which ``"scope"`` names for a reader
    that splits the kernels' time by it (``kernels`` still names every
    flash kernel: ``scopes.kernel_roofline`` divides by all of them)."""
    heads, kv, hd = (
        src["num_attention_heads"], src["num_key_value_heads"], src["head_dim"]
    )
    layers = [w for w in windows(src) if w is not None or not window_only]
    cost = {
        "flops": sum(7 * 2 * batch * band_pairs(seq, w) * heads * hd for w in layers),
        "bytes": len(layers) * 6 * batch * seq * (heads + kv) * hd * itemsize,
        "kernels": list(FLASH_KERNELS),
    }
    if window_only:
        cost["scope"] = WINDOW
    return cost


def grouped_products_cost(src, model, batch, seq, itemsize=2):
    """Operations and HBM bytes of the held experts' grouped products for
    one step at the rows that land (``local_choices``), as
    ``mistral4_costs.grouped_products_cost`` counts them: 3 products forward
    and two backward for each, the stacked weights read once a pass. The
    rows of zeros that fill a group up to a tile are not counted."""
    d, f = src["hidden_size"], src["moe_ffn_hidden_size"]
    rows = batch * seq * local_choices(src, model)
    weights = src["moe_num_primary_experts"] * expert_params(src)
    layers = src["num_hidden_layers"]
    return {
        "flops": layers * 3 * 3 * 2 * rows * d * f,
        "bytes": layers * 3 * itemsize * (weights + rows * (2 * d + 3 * f)),
        "kernels": list(GROUPED_KERNELS),
    }
