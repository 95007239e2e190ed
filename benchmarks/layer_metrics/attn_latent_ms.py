"""attn_latent_ms — layer: models (``models/decoder.py``); unit ms; moves
``throughput_per_chip``; the latent-attention cell. Own device time per
step and chip of the instructions under ``bf.attn.latent``: the query's and
the key-value stream's down-projections, the two latent norms, the two
up-projections, rotary over the interleaved pairs and the assembly of the
per-head keys, forward, recomputed and backward — what stands between the
hidden state and the attention kernels, which (with ``o_proj``) stay
directly under ``bf.attn``. ``None`` for a step without the scope."""

from benchmarks.harness import mistral4_costs, scopes


def read(run):
    parts = scopes.device_ms_by_scopes(run, mistral4_costs.PARTS)
    return parts and parts[mistral4_costs.LATENT]
