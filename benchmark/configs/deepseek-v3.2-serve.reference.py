"""Plain reference of the configuration ``deepseek-v3.2-serve``: the latent-attention
decoder with a lightning indexer and group-limited routed experts of
``benchmark/reference/sparse_latent_decoder.py`` (float32 ``jax.numpy``, matmuls at
``highest``, an explicit top-``index_topk`` a query row by blocks of queries, every held
expert over every row, weights drawn from the seed and kept in the host's memory), at the
sizes of ``deepseek-v3.2-serve.json``.  Its departures from the published model are in
that module's docstring and under ``assumed`` in the configuration's file.

The harness hands a reference the configuration's top-level keys that are no groups
(``drive_lm_server.finish``), so ``rope_scaling``, the YaRN group, is read here from the
file beside this one where the keys handed over lack it."""

import json
from pathlib import Path

from benchmark.reference import sparse_latent_decoder as _decoder
from benchmark.reference.sparse_latent_decoder import *  # noqa: F401,F403

_ROPE = json.loads(Path(__file__).with_name("deepseek-v3.2-serve.json").read_text())[
    "rope_scaling"]


def _whole(cfg):
    return cfg if "rope_scaling" in cfg else {**cfg, "rope_scaling": _ROPE}


def init_params(seed, cfg):
    return _decoder.init_params(seed, _whole(cfg))


def hidden(params, tokens, cfg, *args, **kwargs):
    return _decoder.hidden(params, tokens, _whole(cfg), *args, **kwargs)


def logits_at(params, tokens, rows, cfg, *args, **kwargs):
    return _decoder.logits_at(params, tokens, rows, _whole(cfg), *args, **kwargs)


def loss_row(params, tokens, targets, cfg, *args, **kwargs):
    return _decoder.loss_row(params, tokens, targets, _whole(cfg), *args, **kwargs)
