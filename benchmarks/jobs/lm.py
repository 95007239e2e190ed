"""The language-model job: model, loss, seeded data and reference loss for
a configuration whose ``job`` is ``lm`` — a GPT-2 shaped ``config.json``
run through ``models.TransformerLM`` (which hard-codes exactly that shape:
pre-LayerNorm, GELU, learned positions, 4x MLP). Copied from
``chip_smoke.py``'s ``phase_lm``, with weights and tokens made on the
device."""

import math

import jax
import jax.numpy as jnp
import optax

from bluefog_tpu import models

from benchmarks.harness import flops


def dense_attention(q, k, v):
    """Plain causal softmax attention in float32 at the highest matmul
    precision, under ``jax.checkpoint`` so the T x T scores are not kept
    for the backward pass. The reference's stand-in for the flash kernel."""

    @jax.checkpoint
    def attend(q, k, v):
        with jax.default_matmul_precision("highest"):
            q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q32, k32)
            scores = scores / math.sqrt(q.shape[-1])
            t = q.shape[1]
            causal = jnp.tril(jnp.ones((t, t), bool))
            scores = jnp.where(causal, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v32)

    return attend(q, k, v).astype(q.dtype)


class Job:
    has_aux = False

    def __init__(self, config, traffic):
        m = self.model_cfg = config["model"]
        self.batch, self.seq = traffic["batch_per_worker"], traffic["seq"]
        if not self.seq or self.seq > m["n_positions"]:
            raise ValueError(
                f"seq {self.seq!r} must be 1..n_positions = {m['n_positions']}"
            )
        if m["n_ctx"] != m["n_positions"]:
            raise ValueError("n_ctx and n_positions differ")
        want = flops.lm_param_count(m)
        if config["n_params"] != want:
            raise ValueError(
                f"n_params {config['n_params']} is not what the sizes give, {want}"
            )
        if config["flops"]["matmul_params"] != flops.lm_matmul_params(m):
            raise ValueError("flops.matmul_params is not what the sizes give")
        kwargs = dict(
            vocab=m["vocab_size"], dim=m["n_embd"], heads=m["n_head"],
            layers=m["n_layer"], max_len=m["n_positions"],
            dtype=jnp.dtype(m["compute_dtype"]),
        )
        self.model = models.TransformerLM(**kwargs)
        self.reference_model = models.TransformerLM(
            attend=dense_attention, **kwargs
        )
        self.units_per_worker_step = self.batch * self.seq
        self.mosaic_calls = 3 * m["n_layer"]  # flash forward, dkv, dq
        self.flops_per_unit = flops.lm_flops_per_token(m, self.seq)

    def init(self, key):
        tokens = jnp.zeros((1, self.seq), jnp.int32)
        return self.model.init(key, tokens)["params"], ()

    def make_batch(self, key, n):
        return (
            jax.random.randint(
                key, (n, self.batch, self.seq), 0,
                self.model_cfg["vocab_size"], jnp.int32,
            ),
        )

    @staticmethod
    def _loss(model, params, tokens):
        logits = model.apply({"params": params}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]
        ).mean()

    def loss_fn(self, params, tokens):
        return self._loss(self.model, params, tokens)

    def reference_loss_fn(self, params, tokens):
        return self._loss(self.reference_model, params, tokens)

    def kernel_costs(self):
        """Every Mosaic call in this job's step is flash attention:
        ``n_layer`` x (forward, dkv, dq)."""
        m = self.model_cfg
        return {
            "flash": flops.flash_attention_cost(
                self.batch, self.seq, m["n_head"], m["n_embd"] // m["n_head"],
                m["n_layer"],
            )
        }
