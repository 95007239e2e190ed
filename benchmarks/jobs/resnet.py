"""The ResNet job: model, loss, seeded data and reference loss for a
configuration whose ``job`` is ``resnet``. Copied from ``chip_smoke.py``'s
``ResNetJob`` (sound, proven on one and four chips in PR 21), with the
weights and the data made on the device instead of the host."""

import jax
import jax.numpy as jnp
import optax

from bluefog_tpu import models

class Job:
    has_aux = True  # batch statistics ride as a batch operand, return as aux
    mosaic_calls = 0  # no Pallas kernel runs in this job's step

    def __init__(self, config, traffic):
        m = self.model_cfg = config["model"]
        self.batch = traffic["batch_per_worker"]
        # the stated n_params, asserted by the harness, pins the architecture
        self.model = getattr(models, m["arch"])(
            num_classes=m["num_classes"], num_filters=m["num_filters"],
            dtype=jnp.dtype(m["compute_dtype"]),
        )
        self.units_per_worker_step = self.batch
        self.flops_per_unit = 3 * config["flops"]["forward_flops_per_unit"]
        self.loss_fn = self.reference_loss_fn = self._loss

    def init(self, key):
        """-> (params, aux) of one worker, from the seed, on the device."""
        m = self.model_cfg
        variables = self.model.init(
            key,
            jnp.ones(
                (1, m["image_size"], m["image_size"], m["channels"]),
                jnp.dtype(m["compute_dtype"]),
            ),
            train=True,
        )
        return variables["params"], variables["batch_stats"]

    def make_batch(self, key, n):
        """One batch for each of ``n`` workers, worker-stacked."""
        m = self.model_cfg
        k_x, k_y = jax.random.split(key)
        shape = (n, self.batch, m["image_size"], m["image_size"], m["channels"])
        images = jax.random.normal(k_x, shape, jnp.dtype(m["compute_dtype"]))
        labels = jax.random.randint(
            k_y, (n, self.batch), 0, m["num_classes"], jnp.int32
        )
        return images, labels

    def _loss(self, params, batch_stats, images, labels):
        logits, mutated = self.model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()
        return loss, mutated["batch_stats"]

    def kernel_costs(self):
        """No Pallas kernel runs in this job's step."""
        return {}
