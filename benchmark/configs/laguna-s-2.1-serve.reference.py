"""Plain reference of the configuration ``laguna-s-2.1-serve``: the decoder of window
and full attention layers with routed experts of
``benchmark/reference/window_moe_decoder.py`` (float32 ``jax.numpy``, matmuls at
``highest``, whole sequences under explicit masks, every held expert over every row,
weights drawn from the seed), at the sizes of ``laguna-s-2.1-serve.json``.  Its
departures from the published model are in that module's docstring and under
``assumed`` in the configuration's file.

The harness hands a reference the configuration's top-level keys that are no groups
(``drive_lm_server.finish``), so ``rope_parameters``, a group by layer kind, is read
here from the file beside this one where the keys handed over lack it."""

import json
from pathlib import Path

from benchmark.reference import window_moe_decoder as _decoder
from benchmark.reference.window_moe_decoder import *  # noqa: F401,F403

_ROPE = json.loads(Path(__file__).with_name("laguna-s-2.1-serve.json").read_text())[
    "rope_parameters"]


def _whole(cfg):
    return cfg if "rope_parameters" in cfg else {**cfg, "rope_parameters": _ROPE}


def init_params(seed, cfg):
    return _decoder.init_params(seed, _whole(cfg))


def hidden(params, tokens, cfg, *args, **kwargs):
    return _decoder.hidden(params, tokens, _whole(cfg), *args, **kwargs)


def logits_at(params, tokens, rows, cfg, *args, **kwargs):
    return _decoder.logits_at(params, tokens, rows, _whole(cfg), *args, **kwargs)


def loss_row(params, tokens, targets, cfg, *args, **kwargs):
    return _decoder.loss_row(params, tokens, targets, _whole(cfg), *args, **kwargs)
