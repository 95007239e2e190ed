"""attn_kernel_roofline — layer: attention_kernels; unit %; moves
``throughput_per_chip``; the block-diffusion cell. The least time the chip
could take for one step's attention over the mask's allowed area, forward
and backward (operations and bytes from the shapes, the job's
``kernel_costs()["flash"]``: ``harness/sdar_costs.py``), over the own
device time of the kernels that entry names (``bf_flash_fwd``,
``bf_flash_dkv``, ``bf_flash_dq``: what ``flash_fwd_ms`` + ``flash_bwd_ms``
read in the cells they are listed for). A
forward pass the step runs twice (recomputation) is counted once in the
work and twice in the time. The accepted ``flash_roofline`` divides by all
Mosaic time of the step, which here holds the grouped products too; this
one goes by the kernels' names. ``None`` for a job whose ``flash`` entry
names no kernels. The roof that binds is on the earlier ``kernels`` line."""

from benchmarks.harness import scopes


def read(run):
    cost = run.job.kernel_costs().get("flash")
    if not cost or not cost.get("kernels"):
        return None
    share = scopes.kernel_roofline(run, cost)
    return share and 100.0 * share["share"]
