"""nonmatmul_device_ms — layer: optimizer_path; unit ms; moves
``throughput_per_chip`` (most of the step in the small-batch LM cell);
every cell. Device time per step of every instruction that is not a
convolution / dot fusion, a collective or a Mosaic call: packing, the optax
update, copies, normalisation. Instructions are told apart by joining the
trace's event names to the compiled step's HLO text."""


def read(run):
    kinds = run.device_ms_by_kind()
    if kinds is None:
        return None
    return kinds["other"]
