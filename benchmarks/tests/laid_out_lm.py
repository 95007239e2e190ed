"""A job builder of the tests (``toy.jobs_here``): the ``lm`` job for a
configuration laid out as one drawn from a public ``config.json`` is — its
sizes come from ``cells.source_entry``, under the source's names, and
``model`` holds only what is not the source's."""

from benchmarks.harness import bench, cells

lm = bench._load_module("benchmarks.jobs.lm", cells.job_path("lm"))


class Job(lm.Job):
    def __init__(self, config, traffic):
        source = cells.source_entry(config)
        model = {
            "n_embd": source["hidden_size"],
            "n_head": source["num_attention_heads"],
            "n_layer": source["num_hidden_layers"],
            "n_positions": source["max_position_embeddings"],
            "n_ctx": source["max_position_embeddings"],
            "vocab_size": source["vocab_size"], **config["model"],
        }
        super().__init__({**config, "model": model}, traffic)
