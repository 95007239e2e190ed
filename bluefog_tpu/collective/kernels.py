# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Fused Pallas kernels for the block-scaled quantized wire.

The composite int8/int4 wire (``inner._chunk_quantize`` /
``inner._chunk_quantize4`` + the dequantize-and-accumulate in the
combines) pays its quantize -> pack -> ppermute -> unpack -> dequant
chain as separate XLA ops with full-width f32 intermediates: the
``xhat`` reconstruction the difference form needs, and one dequantized
full-width temporary per received round. The consequence, measured on
XLA:CPU at payload 4096: the quantized combines' scratch *exceeds* the
exact path's (int8 24 736 B / int4 20 640 B vs fp32 16 384 B; priced by
``scaling.quantized_temporaries_bytes``).

This module erases that staging cost with two fused kernels (the
XLA-collective analogue of EQuARX's fused quantized allreduce,
arXiv:2506.17615):

- :func:`encode` — per-512-block absmax -> scale (bf16-snapped for
  int4) -> quantize -> nibble-pack, writing the packed wire buffer and
  the scale sidecar directly; the full-width quantized intermediate
  never materializes.
- :func:`decode_accumulate` — ALL receive rounds in one kernel: unpack
  -> dequant -> difference-form accumulate
  ``acc += (xhat_recv_r - xhat_self) * w_r`` per block, with the
  accumulator aliased in place (``input_output_aliases``), so neither
  any received round's nor the sender's own dequantized full-width
  temporary ever exists: the ``xhat_self`` the difference form
  subtracts is re-decoded from the sender's OWN packed buffer inside
  the kernel — the same bits every receiver reconstructs, preserving
  the PR-8 sender/receiver-identical-bits contract (and with it exact
  push-sum mass conservation).

Plus the EF/CHOCO pair (:func:`encode_diff` — the fused sender, whose
``xhat_self`` integration ``h + Q(x - h)`` also happens in-kernel — and
:func:`decode_add`) and a full-width :func:`decode` for the surfaces
whose receive buffer must exist (window slots, allgather rows, the EF
hat copies).

Every kernel body mirrors the composite op sequence per element
EXACTLY — same zero-guard, same bf16 snap, same deinterleaved-halves
nibble layout, same multiply/add/cast order (including the composite
accumulate's casts to the combine ``wdt``) — so kernel-on ==
kernel-off is a bitwise pin, not a tolerance (asserted across the tier
matrix in ``tests/test_wire_kernels.py``; the numpy oracle both paths
pin against is :mod:`bluefog_tpu.collective.wire_ref`).

Tiling: on TPU the kernels lower natively through Mosaic with
``_ROWS`` scale blocks per grid step (payload blocks ``(_ROWS, 512)`` /
``(_ROWS, 256)``, scale blocks ``(_ROWS, 1)``): Mosaic wants the last
two block dims divisible by the dtype's tile (8x128 f32, 32x128 int8)
or equal to the array's, which one-row blocks are not. Rows are
independent, so the ragged last block needs no masking — its
out-of-range rows read garbage and their writes are dropped. Scales
cross the kernel boundary in f32 (the int4 wire's bf16 snap happens
in-kernel; the cast to the bf16 sidecar outside is exact), and the
nibble arithmetic runs in int32: Mosaic does not legalize int8 vector
shifts on the v5e (``arith.shli`` on ``vector<..xi8>``, PR 21's chip
probe). Everywhere else the SAME bodies run as
plain XLA ops over one whole-array block (:class:`_Block`), not through
``pallas_call(interpret=True)``: the Pallas interpreter evaluates an
already-traced kernel jaxpr, which ``shard_map(check_vma=True)``
rejects on jax 0.9 (no ``pvary`` is inserted between the varying blocks
and the body's constants), and its grid loop would carry
double-buffered full-width copies — *adding* the scratch the kernels
exist to remove. The bodies are written rank-generically (axis-1
keepdims reductions) so both tilings run the same arithmetic.

One XLA:CPU quirk needs an explicit pin (:func:`_pin_wire_buffer`): the
CPU fusion pass REMATERIALIZES cheap producer chains into consumer
fusions, so the final accumulate fusion re-derives ``xhat_self`` from
the f32 input instead of reading the int8 wire buffer — and stops at
the expensive ``divide``, materializing the very full-width f32
temporary the kernel exists to remove (``lax.optimization_barrier``
does not survive to the fusion pass on CPU and cannot block this). A
data-dependent always-true ``lax.cond`` over the sender's own payload
is a boundary the fusion pass cannot rematerialize through, forcing
the accumulate to READ the materialized wire buffer — exactly what the
Mosaic custom-call boundary enforces for free on TPU. Bitwise
identity: the taken branch returns the payload unchanged.

Gating: ``BLUEFOG_WIRE_KERNELS`` = ``0``/``off`` selects the composite
path; anything else (the default) the kernels. :func:`cache_token`
joins every op/optimizer cache key whose program embeds a quantized
wire, so toggling the flag can never dispatch a stale program.
"""

import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "wire_kernels_on",
    "cache_token",
    "encode",
    "encode_diff",
    "decode",
    "decode_add",
    "decode_accumulate",
    "block_quantizer",
    "pad_blocks",
    "unpad_blocks",
]

# Must equal inner._QUANT_CHUNK (asserted in tests): the kernels and the
# composite quantizers share one scale grid.
CHUNK = 512
_HALF = CHUNK // 2
# Scale blocks per native grid step: a multiple of every wire dtype's
# sublane tile (32 for int8), and 512 KiB of f32 payload per block —
# the widest kernel (decode_accumulate over several rounds) stays far
# inside the default scoped-VMEM limit with double buffering.
_ROWS = 256

# Wire tiers with a packed integer payload a kernel can fuse. bf16 is a
# pure dtype cast (nothing to fuse); the _ef spellings ride the same two
# quantizers.
_KERNEL_WIRES = ("int8", "int4", "int8_ef", "int4_ef")


def wire_kernels_on() -> bool:
    """Resolve ``BLUEFOG_WIRE_KERNELS``: ``0``/``off``/``false``/``no``
    selects the composite path, anything else (the default) the fused
    kernels. Read per call so tests can toggle per program; the
    :func:`cache_token` in every quantized cache key keeps toggles from
    dispatching stale programs."""
    raw = os.environ.get("BLUEFOG_WIRE_KERNELS", "auto").strip().lower()
    return raw not in ("0", "off", "false", "no")


def cache_token(wire: Optional[str]) -> tuple:
    """The cache-key suffix for a program embedding wire tier ``wire``:
    ``("wire_kernels",)`` when the fused kernels are active for that
    tier, else ``()`` — so kernel-off keys are byte-identical to the
    pre-kernel keys (no recompiles for exact/bf16 programs, and the
    kernel-off pin dispatches the historical program)."""
    if wire in _KERNEL_WIRES and wire_kernels_on():
        return ("wire_kernels",)
    return ()


def _native() -> bool:
    """Mosaic lowering on TPU; elsewhere the kernel bodies run as plain
    XLA ops over one whole-array block (see the module docstring), so
    every backend runs the same kernel arithmetic."""
    return jax.default_backend() == "tpu"


class _Block:
    """Whole-array stand-in for a kernel ref off-TPU: loads index the
    array, and the one store a body makes per output replaces it."""

    def __init__(self, value=None):
        self.value = value

    def __getitem__(self, idx):
        return self.value[idx]

    def __setitem__(self, idx, value):
        assert idx is Ellipsis, idx
        self.value = value


def pad_blocks(xf: jnp.ndarray) -> jnp.ndarray:
    """Flat ``[n]`` -> ``[n_chunks, CHUNK]`` zero-padded blocks (the
    layout every kernel works in)."""
    n = xf.size
    n_chunks = -(-n // CHUNK)
    return jnp.pad(xf.ravel(), (0, n_chunks * CHUNK - n)).reshape(
        n_chunks, CHUNK
    )


def unpad_blocks(x2: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of :func:`pad_blocks` (drops the zero tail)."""
    return x2.reshape(-1)[:n]


def _pin_wire_buffer(payload: jnp.ndarray, scales: jnp.ndarray):
    """Pin the sender's own wire buffer as a materialized READ on the
    XLA-ops path (no-op wrapper on TPU, where the Mosaic custom-call
    boundary already is one). The ``lax.cond`` predicate is
    data-dependent (scales are zero-guard-clipped strictly positive, so
    ``s[0] > -1`` always holds but cannot be constant-folded), the taken
    branch returns the payload bit-unchanged, and a conditional is a
    boundary XLA:CPU's producer-fusion rematerialization cannot walk
    through — without it the accumulate fusion re-derives the quantize
    chain from the f32 input and materializes its full-width ``divide``
    (16 KiB at payload 4096, the exact temporary this module removes;
    measured by
    tests/test_wire_kernels.py::test_fused_scratch_below_fp32_row)."""
    if _native():
        return payload
    pred = scales.reshape(-1)[0].astype(jnp.float32) > -1.0
    return lax.cond(pred, lambda: payload, lambda: jnp.zeros_like(payload))


# -- kernel bodies -------------------------------------------------------------
#
# Rank-generic: a block is ``(rows, CHUNK)`` payload-side (``(rows,
# _HALF)`` packed) with ``(rows, 1)`` f32 scale cells — ``rows`` is
# ``_ROWS`` per grid step native, n_chunks on the XLA-ops path. The
# arithmetic is copied from the composite quantizers op for op — the
# bitwise kernel-on == kernel-off pin depends on it. Quantized values
# stay int32 until the store (and widen to int32 right after the load):
# the same values as the composite's int8 ops.


def _quant8(x):
    """``(rows, CHUNK)`` f32 -> (int32 q in [-127, 127], ``(rows, 1)``
    f32 scale); mirrors inner._chunk_quantize's per-row arithmetic."""
    s = jnp.maximum(
        jnp.max(jnp.abs(x), axis=1, keepdims=True),
        jnp.finfo(jnp.float32).tiny,
    ) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int32)
    return q, s


def _quant4(x):
    """``(rows, CHUNK)`` f32 -> (int32 q in [-7, 7], ``(rows, 1)`` scale
    snapped to bf16 and widened back to f32); mirrors
    inner._chunk_quantize4: the scale snaps to bf16 FIRST and the
    quantize divides by the widened bf16 value, so sender and every
    receiver reconstruct identical bits."""
    s = jnp.maximum(
        jnp.max(jnp.abs(x), axis=1, keepdims=True),
        jnp.finfo(jnp.float32).tiny,
    ) / 7.0
    sw = s.astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / sw), -7, 7).astype(jnp.int32)
    return q, sw


def _pack(q):
    """``(rows, CHUNK)`` int32 values in [-8, 7] -> ``(rows, _HALF)``
    packed int8 lanes: element ``k`` low nibble of lane ``k``, element
    ``_HALF + k`` the high nibble (the composite deinterleaved-halves
    layout of inner._pack_nibbles). The last step folds the unsigned
    byte into int8's range so the narrowing cast is exact."""
    byte = (q[:, :_HALF] & 0x0F) | ((q[:, _HALF:] & 0x0F) << 4)
    return (byte - ((byte & 0x80) << 1)).astype(jnp.int8)


def _unpack(p):
    """Inverse of :func:`_pack`, to ``(rows, CHUNK)`` int32 in [-8, 7]:
    the packed lanes doubled side by side, then ONE per-lane shift pair
    — the left half keeps the low nibble (``<< 28 >> 28``), the right
    half the high one (``<< 24 >> 28``); the arithmetic right shift
    sign-extends exactly as inner._unpack_nibbles does
    (tests/test_wire_kernels.py pins both decoders lane-exhaustively
    over all 256 int8 values). Doubling the int8 lanes BEFORE widening
    keeps the one concatenate a byte per element, so the decode fuses
    into its consumer instead of staging a full-width int32 block."""
    p2 = jnp.concatenate([p, p], axis=1).astype(jnp.int32)
    lane = lax.broadcasted_iota(jnp.int32, p2.shape, 1)
    up = jnp.where(lane < _HALF, 28, 24)
    return jnp.right_shift(jnp.left_shift(p2, up), 28)


def _deq(payload, scales, packed):
    """f32 reconstruction of one (payload, f32 scales) block pair; the
    composite _dequant8/_dequant4 arithmetic (every step exact in f32,
    so fusion order cannot perturb it)."""
    q = _unpack(payload) if packed else payload.astype(jnp.int32)
    return q.astype(jnp.float32) * scales


def _encode8_body(x_ref, q_ref, s_ref):
    q, s = _quant8(x_ref[...])
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = s


def _encode4_body(x_ref, p_ref, s_ref):
    q, sw = _quant4(x_ref[...])
    p_ref[...] = _pack(q)
    s_ref[...] = sw


def _encode_diff8_body(x_ref, h_ref, q_ref, s_ref, o_ref):
    q, s = _quant8(x_ref[...] - h_ref[...])
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = s
    # the sender-side copy integration h + Q(x - h): q pre-pack is
    # exactly what unpack(pack(q)) reconstructs (values in range), so
    # this is the composite xhat_self + dhat bit for bit
    o_ref[...] = h_ref[...] + q.astype(jnp.float32) * s


def _encode_diff4_body(x_ref, h_ref, p_ref, s_ref, o_ref):
    q, sw = _quant4(x_ref[...] - h_ref[...])
    p_ref[...] = _pack(q)
    s_ref[...] = sw
    o_ref[...] = h_ref[...] + q.astype(jnp.float32) * sw


def _make_decode_body(packed):
    def body(p_ref, s_ref, o_ref):
        o_ref[...] = _deq(p_ref[...], s_ref[...], packed)

    return body


def _make_decode_add_body(packed):
    def body(b_ref, p_ref, s_ref, o_ref):
        o_ref[...] = b_ref[...] + _deq(p_ref[...], s_ref[...], packed)

    return body


def _make_dacc_body(n_rounds, packed, wdt):
    """ALL-rounds difference-form accumulate: refs are ``(w, acc,
    self_payload, self_scales, (recv_payload, recv_scales) * n_rounds,
    out)``. The casts to ``wdt`` replicate the composite combine's
    ``(dequant(...).astype(wdt) - xhat_self.astype(wdt)) *
    w.astype(wdt)`` per-lane op sequence exactly."""

    def body(*refs):
        w_ref, acc_ref, qs_ref, ss_ref = refs[:4]
        out_ref = refs[-1]
        deq_s = _deq(qs_ref[...], ss_ref[...], packed).astype(wdt)
        acc = acc_ref[...]
        for r in range(n_rounds):
            qr_ref, sr_ref = refs[4 + 2 * r], refs[5 + 2 * r]
            deq_r = _deq(qr_ref[...], sr_ref[...], packed).astype(wdt)
            acc = acc + (deq_r - deq_s) * w_ref[r, 0].astype(wdt)
        out_ref[...] = acc

    return body


# -- pallas_call wrappers ------------------------------------------------------


def _is_packed(wire: str) -> bool:
    return wire in ("int4", "int4_ef")


def _payload_width(wire: str) -> int:
    return _HALF if _is_packed(wire) else CHUNK


def _scale_dtype(wire: str):
    """The scale sidecar's dtype ON THE WIRE (inside the kernels scales
    are always f32; the int4 values are bf16-exact)."""
    return jnp.bfloat16 if _is_packed(wire) else jnp.float32


def _scale_cells(scales: jnp.ndarray) -> jnp.ndarray:
    """Wire scales ``[n_chunks]`` -> the kernels' ``(n_chunks, 1)`` f32
    cells (widening bf16 is exact)."""
    return scales.astype(jnp.float32).reshape(-1, 1)


def _call(what, body, operands, widths, out_widths, out_dtypes, n_chunks,
          aliases=None):
    """Dispatch one kernel: native TPU grids ``_ROWS`` scale blocks per
    step (width 0 marks the weight vector, read as scalars from SMEM);
    elsewhere the body runs once over whole-array blocks. ``what`` names
    the Mosaic call ``bf_wire_<what>``, which is how a device trace tells
    the wire kernels from each other and from flash."""
    if not _native():
        outs = tuple(_Block() for _ in out_widths)
        body(*(_Block(op) for op in operands), *outs)
        return tuple(o.value for o in outs)
    # inside shard_map the outputs vary over the mesh axes the operands
    # do; check_vma=True rejects an out_shape that does not say so
    vma = frozenset().union(*(jax.typeof(op).vma for op in operands))
    out_shape = tuple(
        jax.ShapeDtypeStruct((n_chunks, w), dt, vma=vma)
        for w, dt in zip(out_widths, out_dtypes)
    )
    # a block spanning the whole array is legal at any row count; a
    # partial one must be tile-aligned, which _ROWS is for every dtype
    rows = min(_ROWS, n_chunks)

    def block(w):
        return pl.BlockSpec((rows, w), lambda i: (i, 0))

    return pl.pallas_call(
        body,
        grid=(pl.cdiv(n_chunks, rows),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM) if w == 0 else block(w)
            for w in widths
        ],
        out_specs=tuple(block(w) for w in out_widths),
        out_shape=out_shape,
        input_output_aliases=aliases or {},
        name=f"bf_wire_{what}",
    )(*operands)


def encode(xf: jnp.ndarray, wire: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused quantize of a flat f32 vector: ``(payload, scales)`` with
    ``payload`` ``[n_chunks, 512]`` int8 (int8 wire) or ``[n_chunks,
    256]`` packed nibbles (int4 wire) and ``scales`` ``[n_chunks]`` f32
    / bf16 — the same wire bits as the composite quantizers, with no
    full-width quantized intermediate. The barrier pins the payload
    dtypes at the wire (same role as the composite bf16 sidecar's:
    without it XLA commutes the widening across the ppermute and ships
    f32 scales)."""
    x2 = pad_blocks(xf)
    n_chunks = x2.shape[0]
    w = _payload_width(wire)
    body = _encode4_body if w == _HALF else _encode8_body
    payload, s = _call(
        "encode", body, (x2,), (CHUNK,), (w, 1), (jnp.int8, jnp.float32), n_chunks,
    )
    return lax.optimization_barrier(
        (payload, s.reshape(n_chunks).astype(_scale_dtype(wire)))
    )


def encode_diff(
    xf: jnp.ndarray, xhat_self: jnp.ndarray, wire: str
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused EF/CHOCO sender: ``(payload, scales, xhat_self_new)`` for
    ``Q(xf - xhat_self)``, with neither the full-width difference nor
    its dequantized update ever materialized — the copy integration
    ``xhat_self + dhat`` happens inside the kernel, from the very ``q``
    the wire ships (the PR-8 identical-bits contract)."""
    x2 = pad_blocks(xf)
    h2 = pad_blocks(xhat_self)
    n_chunks = x2.shape[0]
    w = _payload_width(wire)
    body = _encode_diff4_body if w == _HALF else _encode_diff8_body
    payload, s, h_new = _call(
        "encode_diff", body, (x2, h2), (CHUNK, CHUNK), (w, 1, CHUNK),
        (jnp.int8, jnp.float32, jnp.float32), n_chunks,
    )
    payload, s = lax.optimization_barrier(
        (payload, s.reshape(n_chunks).astype(_scale_dtype(wire)))
    )
    return payload, s, unpad_blocks(h_new, xf.size)


def decode(
    payload: jnp.ndarray, scales: jnp.ndarray, n: int, wire: str
) -> jnp.ndarray:
    """Fused full-width reconstruction (flat ``[n]`` f32) — for the
    surfaces where the receive buffer must exist (window slots,
    allgather rows, the EF hat copies)."""
    n_chunks = payload.shape[0]
    pw = payload.shape[1]
    (out,) = _call(
        "decode", _make_decode_body(pw == _HALF),
        (payload, _scale_cells(scales)), (pw, 1), (CHUNK,),
        (jnp.float32,), n_chunks,
    )
    return unpad_blocks(out, n)


def decode_add(
    base: jnp.ndarray, payload: jnp.ndarray, scales: jnp.ndarray, wire: str
) -> jnp.ndarray:
    """Fused ``base + dequant(payload, scales)`` (flat f32, same length
    as ``base``) — the EF copy integration, without a separate
    full-width dequantized temporary (the base is aliased in place)."""
    n = base.size
    b2 = pad_blocks(base)
    n_chunks = payload.shape[0]
    pw = payload.shape[1]
    (out,) = _call(
        "decode_add", _make_decode_add_body(pw == _HALF),
        (b2, payload, _scale_cells(scales)), (CHUNK, pw, 1),
        (CHUNK,), (jnp.float32,), n_chunks, aliases={0: 0},
    )
    return unpad_blocks(out, n)


def decode_accumulate(
    xw: jnp.ndarray,
    payload: jnp.ndarray,
    scales: jnp.ndarray,
    rounds: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
    weights: jnp.ndarray,
    wire: str,
) -> jnp.ndarray:
    """The fused difference-form combine epilogue: ``y = xw + sum_r
    (dequant(recv_r) - dequant(self)) * weights[r]`` with every round
    folded into ONE kernel and the accumulator aliased in place —
    no received round's dequantized full-width temporary, and no
    ``xhat_self`` one either (re-decoded per block from the sender's
    own packed buffer, bitwise what receivers reconstruct).

    ``xw`` is the combine input already cast to the weight dtype
    ``wdt`` (any shape); ``payload``/``scales`` the sender's own
    :func:`encode` outputs; ``rounds`` the per-round received
    ``(payload, scales)`` pairs; ``weights`` the ``[n_rounds]`` traced
    weight vector (runtime operands — never recompiles)."""
    wdt = xw.dtype
    n = xw.size
    x2 = pad_blocks(xw.ravel())
    n_chunks = payload.shape[0]
    pw = payload.shape[1]
    wvec = jnp.asarray(weights).reshape(len(rounds), 1)
    operands = [
        wvec, x2, _pin_wire_buffer(payload, scales), _scale_cells(scales),
    ]
    widths = [0, CHUNK, pw, 1]
    for rq, rs in rounds:
        operands += [rq, _scale_cells(rs)]
        widths += [pw, 1]
    (out,) = _call(
        "decode_accumulate", _make_dacc_body(len(rounds), pw == _HALF, wdt),
        tuple(operands), tuple(widths), (CHUNK,), (wdt,), n_chunks,
        aliases={1: 0},
    )
    return unpad_blocks(out, n).reshape(xw.shape)


def block_quantizer(wire: str):
    """Kernel-backed ``(quantize, dequantize)`` pair with the composite
    :func:`inner._block_quantizer` signatures — ``quantize(xf) -> (q, s,
    xhat)``, ``dequant(q, s, n) -> xhat`` — for the surfaces that keep
    full-width receives (windows, allgather, the chunked wavefronts).
    ``xhat`` is the fused decode of the sender's own packed buffer:
    bitwise what every receiver reconstructs (the PR-8 contract), and
    DCE drops it on the surfaces that never read it."""

    def quantize(xf):
        payload, scales = encode(xf, wire)
        return payload, scales, decode(payload, scales, xf.size, wire)

    def dequant(payload, scales, n):
        return decode(payload, scales, n, wire)

    return quantize, dequant
