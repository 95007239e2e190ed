"""window_kernel_roofline — layer: attention_kernels; unit %; moves
``throughput_per_chip``; the window/global sparse-expert cell. The least
time the chip could take for one step's attention over the sliding-window
layers' band, forward and backward (operations and bytes from the shapes,
the job's ``kernel_costs()["flash_window"]``: ``harness/smallthinker_costs.py``,
the band's exact allowed pairs at 28 query and 4 key-value heads), over the
own device time of the flash kernels that entry names whose ``op_name``
lies under the scope it names (``bf.attn.window``): the window layers'
kernels alone, not the global layers'. A forward pass the step runs twice
(recomputation) is counted once in the work and twice in the time. ``None``
off the chip, without a trace, for a job without the entry or where no
kernel ran under the scope."""

import re

from benchmarks.harness import flops, hlo_text, scopes, trace_reduce

_KERNEL_RE = re.compile(r"([^/]+)/pallas_call$")  # the pallas_call's name=


def kernel_ms_under_scope(run, kernels, scope):
    """ms per step and chip of the Mosaic calls named one of ``kernels``
    (their ``pallas_call``'s ``name=``) whose ``op_name`` holds ``scope``."""
    under = scopes.scope_of((scope,))

    def counted(op):
        if run.hlo.kind(op.name) != hlo_text.MOSAIC:
            return False
        op_name = run.hlo.op_names.get(op.name, "")
        kernel = _KERNEL_RE.search(op_name)
        return bool(kernel) and kernel.group(1) in kernels and under(op_name) is not None

    ns = sum(
        ns
        for device in run.trace.devices
        for op, ns in trace_reduce.self_times(device.ops_of(run.hlo.module))
        if counted(op)
    )
    return ns / (1e6 * len(run.trace.devices) * run.traced_steps)


def read(run):
    cost = run.job.kernel_costs().get("flash_window")
    if not cost or not run.peaks or run.trace is None or run.hlo is None:
        return None
    ms = kernel_ms_under_scope(run, cost["kernels"], cost["scope"])
    share = flops.roofline_share(cost, ms / 1e3, run.peaks)
    return share and 100.0 * share["share"]
