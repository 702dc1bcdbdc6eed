"""Plain reference of the configuration ``joyai-llm-flash-serve``: the latent-attention
decoder with routed experts of ``benchmark/reference/latent_moe_decoder.py`` (float32
``jax.numpy``, matmuls at ``highest``, every held expert over every row, weights drawn
from the seed), at the sizes of ``joyai-llm-flash-serve.json``.  Its departures from the
published model are in that module's docstring and under ``assumed`` in the
configuration's file."""

from benchmark.reference.latent_moe_decoder import *  # noqa: F401,F403
from benchmark.reference.latent_moe_decoder import hidden, init_params, logits_at, loss_row  # noqa: F401
