"""flash_ms — layer: attention_kernels (``ops/flash.py``); unit ms; moves
``throughput_per_chip``; the LM cells. Summed device time per step of the
Mosaic custom calls: in the LM cells every one is a flash kernel (n_layer x
forward, dkv, dq), which stops being true when a wire kernel joins the step
(the ``tracing`` issue names each ``pallas_call``)."""


def read(run):
    kinds = run.device_ms_by_kind()
    if kinds is None or not kinds["mosaic"]:
        return None
    return kinds["mosaic"]
