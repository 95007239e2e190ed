"""Operations and bytes the arithmetic needs, computed from shapes.

These are the yardstick's counts: what the forward and backward passes
*require* (no recomputation, no padding), not what a compiled program
happens to execute. A job builder turns them into its configuration's
FLOPs per unit; a per-layer reader divides them by a kernel's device time.
A multiply-add is two operations.

A job's ``kernel_costs()`` is ``{entry: cost}``, a cost ``{"flops", "bytes"}``
of one step and, where the step holds more than one kind of kernel,
``"kernels"``: the ``pallas_call`` names (their ``name=``) whose device time
the cost is measured against (``scopes.kernel_roofline``). An entry that
names none is measured against all Mosaic time of the step, which is right
only while every Mosaic call is that kernel's. How many Mosaic calls one
compiled step of the job holds is its ``mosaic_calls``.
"""


def conv_flops(out_h, out_w, k, c_in, c_out):
    return 2 * out_h * out_w * k * k * c_in * c_out


def resnet_forward_flops(model):
    """Convolutions and the classifier of a bottleneck ResNet (stride in
    each later stage's first 3x3, as ``models/resnet.py`` builds it) for
    one image; batch norm, ReLU and pooling are not matrix work and are
    not counted."""
    f, size = model["num_filters"], model["image_size"] // 2
    total = conv_flops(size, size, 7, model["channels"], f)
    size //= 2  # the 3x3 max pool, stride 2
    c_in = f
    for stage, blocks in enumerate(model["stage_sizes"]):
        width = f * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = size // stride
            total += conv_flops(size, size, 1, c_in, width)
            total += conv_flops(out, out, 3, width, width)
            total += conv_flops(out, out, 1, width, 4 * width)
            if c_in != 4 * width or stride != 1:
                total += conv_flops(out, out, 1, c_in, 4 * width)
            c_in, size = 4 * width, out
    return total + 2 * c_in * model["num_classes"]


def lm_param_count(model):
    """Parameters of ``models.TransformerLM`` at a GPT-2 config's sizes:
    token and position tables, per block two LayerNorms, qkv and the
    attention projection without bias, the 4x MLP with bias, the final
    LayerNorm and an untied output head with bias."""
    d, v = model["n_embd"], model["vocab_size"]
    block = 2 * 2 * d + 3 * d * d + d * d + (4 * d * d + 4 * d) + (4 * d * d + d)
    return (
        v * d + model["n_positions"] * d + model["n_layer"] * block
        + 2 * d + d * v + v
    )


def lm_matmul_params(model):
    """``P_mm``: the parameters that sit in matrix multiplications — the
    block stack's four projections and the output head; not the token and
    position tables (gathers), not biases and norms."""
    d = model["n_embd"]
    return model["n_layer"] * 12 * d * d + d * model["vocab_size"]


def lm_flops_per_token(model, seq):
    """``6 P_mm + 6 L T d``: forward + backward of the matrix
    multiplications, and of causal attention (QK^T and PV over half of the
    square: 2 T d forward per token and layer, twice that backward)."""
    return (
        6 * lm_matmul_params(model)
        + 6 * model["n_layer"] * seq * model["n_embd"]
    )


def flash_attention_cost(batch, seq, heads, head_dim, layers, itemsize=2):
    """Operations and HBM bytes of causal flash attention, forward and
    backward, for one step: per layer 2 matrix products forward and 5
    backward (the scores are recomputed once: the algorithm stores no
    T x T matrix), each 2 B H T^2 d over half of the square; q, k, v, o
    read or written once forward (4 tensors) and q, k, v, o, do read and
    dq, dk, dv written backward (8 tensors)."""
    product = batch * heads * seq * seq * head_dim  # 2 T^2 d, causal half
    tensor = batch * seq * heads * head_dim * itemsize
    return {
        "flops": layers * 7 * product,
        "bytes": layers * 12 * tensor,
    }


def roofline_share(cost, seconds, peaks):
    """Least time the chip could take over the time it took, and which
    roof binds; ``None`` without a time."""
    if not seconds or seconds <= 0:
        return None
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "share": max(t_flops, t_bytes) / seconds,
        "binds": "compute" if t_flops >= t_bytes else "memory",
    }
