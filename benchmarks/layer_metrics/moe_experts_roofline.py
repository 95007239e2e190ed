"""moe_experts_roofline — layer: models; unit %; moves
``throughput_per_chip``; the sparse-expert cell. The least time the chip
could take for one step's grouped products at the expected rows (operations
and bytes from the shapes, the job's ``kernel_costs()["moe_experts"]``:
``harness/sdar_costs.py``) over the own device time of the kernels that
entry names (``bf_gmm``, ``bf_tgmm``). A forward product the step runs
twice (recomputation) is counted once in the work and twice in the time,
and the rows of zeros that fill an expert's rows up to a tile in the time
only. ``None`` for a job whose entry names no kernels."""

from benchmarks.harness import scopes


def read(run):
    cost = run.job.kernel_costs().get("moe_experts")
    if not cost or not cost.get("kernels"):
        return None
    share = scopes.kernel_roofline(run, cost)
    return share and 100.0 * share["share"]
