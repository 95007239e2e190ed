"""flash_bwd_ms — layer: attention_kernels (``ops/flash.py``); unit ms; moves
``throughput_per_chip``; the LM cells. Own device time per step and chip of
the Mosaic calls named ``bf_flash_dkv`` and ``bf_flash_dq`` (the ``name=``
of their ``pallas_call``, which ends their ``op_name``): flash attention's
backward pass. Unlike ``flash_ms`` it stays right when a wire kernel joins
the step."""

from benchmarks.harness import scopes

KERNELS = ("bf_flash_dkv", "bf_flash_dq")


def read(run):
    kernels = scopes.mosaic_ms_by_kernel(run)
    if kernels is None:
        return None
    return sum(kernels.get(k, 0.0) for k in KERNELS) or None
