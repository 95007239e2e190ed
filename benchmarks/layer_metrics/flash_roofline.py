"""flash_roofline — layer: attention_kernels; unit %; moves
``throughput_per_chip``; the LM cells. The least time the chip could take
for one step's causal flash attention, forward and backward (operations and
bytes from the shapes, ``harness/flops.py``), over ``flash_ms``. The bound
that binds is on the earlier ``kernels`` line."""

from benchmarks.harness import flops


def read(run):
    kinds = run.device_ms_by_kind()
    cost = run.job.kernel_costs().get("flash")
    if kinds is None or cost is None or not kinds["mosaic"] or not run.peaks:
        return None
    return 100.0 * flops.roofline_share(
        cost, kinds["mosaic"] / 1e3, run.peaks
    )["share"]
