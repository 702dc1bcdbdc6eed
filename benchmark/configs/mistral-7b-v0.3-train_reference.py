"""Plain reference of the configuration ``mistral-7b-v0.3-train``: the dense decoder of
``benchmark/reference/dense_decoder.py`` (float32 ``jax.numpy``, matmuls at
``highest``, weights drawn from the seed), at the sizes of ``mistral-7b-v0.3-train.json``."""

from benchmark.reference.dense_decoder import *  # noqa: F401,F403
from benchmark.reference.dense_decoder import hidden, init_params, logits_at, loss_row  # noqa: F401
