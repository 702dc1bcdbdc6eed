"""Training feed: the shapes of the stream a trainer is driven with.  The
token ids themselves are drawn in the worker from the seed, as ``lm_train``
draws them (numpy ``default_rng(seed)``, one batch, every row different)."""

from __future__ import annotations

from typing import Dict, List

DRIVER = "train_window"


def schedule(traffic: Dict, seed: int, seconds: float, vocab_size: int) -> List[Dict]:
    return [{
        "phase": "stream",
        "seq": int(traffic["seq"]),
        "batch": int(traffic["batch"]),
        "warm_steps": int(traffic.get("warm_steps", 3)),
        "trace_steps": int(traffic.get("trace_steps", 3)),
        "seed": int(seed),
    }]
