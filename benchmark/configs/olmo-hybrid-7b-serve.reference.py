"""Plain reference of the configuration ``olmo-hybrid-7b-serve``: the hybrid decoder of
``benchmark/reference/hybrid_decoder.py`` (float32 ``jax.numpy``, matmuls at
``highest``, the gated delta rule token by token, weights drawn from the seed), at the
sizes of ``olmo-hybrid-7b-serve.json``.  Its departures from the published model are in
that module's docstring and under ``assumed`` in the configuration's file."""

from benchmark.reference.hybrid_decoder import *  # noqa: F401,F403
from benchmark.reference.hybrid_decoder import hidden, init_params, logits_at, loss_row  # noqa: F401
