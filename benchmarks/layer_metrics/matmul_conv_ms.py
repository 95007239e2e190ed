"""matmul_conv_ms — layer: models (``models/*.py``); unit ms; moves
``throughput_per_chip``; every cell. Device time per step of the
convolution and dot instructions and of the fusions that hold one."""


def read(run):
    kinds = run.device_ms_by_kind()
    if kinds is None:
        return None
    return kinds["matmul_conv"]
