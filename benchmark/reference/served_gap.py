"""The comparison that decides ``correct`` for a served model.

For the requests sampled from those the window finished, the configuration's
plain reference runs over each prompt with its served tokens and reads, at
every served token, the gap by which its logit lies below the reference's best
at that position (0 where the served token is the reference's own first
choice).  Valid for greedy tokens.

Requests that share a prefix (the questions put to one document) go through
the reference together: the prefix once, each request's own tokens after it as
a segment that sees the prefix and itself and nothing else, at the positions it
had when served.  That is the same arithmetic as one pass per request, and it
lets a run compare several times the served tokens in the same time.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def lay_out(shared: List[int], requests: List[Dict[str, Any]]) -> Dict[str, np.ndarray]:
    """One group as one sequence: ``shared`` then, per request, the rest of its
    prompt and its served tokens but the last.  ``rows[j]`` is the row whose
    logits chose ``served[j]``."""
    tokens, pos, seg = list(shared), list(range(len(shared))), [0] * len(shared)
    rows, served = [], []
    for i, r in enumerate(requests, start=1):
        rest = list(r["prompt"][len(shared):])
        own = rest + list(r["tokens"][:-1])
        start = len(tokens)
        tokens += own
        pos += range(len(shared), len(shared) + len(own))
        seg += [i] * len(own)
        first = start + len(rest) - 1 if rest else len(shared) - 1
        rows += [first] + list(range(start + len(rest), start + len(own)))
        served += list(r["tokens"])
    return {"tokens": np.asarray(tokens, np.int32), "pos": np.asarray(pos, np.int32),
            "seg": np.asarray(seg, np.int32), "rows": np.asarray(rows, np.int32),
            "served": np.asarray(served, np.int32)}


def token_gaps(ref, params, cfg: Dict[str, Any], groups: List[Dict[str, Any]],
               pad_tokens_to: int, pad_rows_to: int) -> Dict[str, np.ndarray]:
    """``groups`` = [{"shared": [ids], "requests": [{"prompt", "tokens"}, ...]}].
    Per served token: its ``gap`` below the reference's best, and the
    reference's own ``margin`` between its first and second choice there."""
    fn = jax.jit(lambda p, t, r, pos, seg: ref.logits_at(p, t, r, cfg, "float32", pos, seg))
    gaps, margins = [], []
    for g in groups:
        lay = lay_out(g["shared"], g["requests"])
        n, t_pad = len(lay["rows"]), _pad(len(lay["tokens"]), pad_tokens_to)
        padded = {k: np.zeros(t_pad, np.int32) for k in ("tokens", "pos", "seg")}
        for k in padded:
            padded[k][: len(lay[k])] = lay[k]
        padded["seg"][len(lay["seg"]):] = len(g["requests"]) + 1
        rows = np.zeros(_pad(n, pad_rows_to), np.int32)
        rows[:n] = lay["rows"]
        logits = fn(params, jnp.asarray(padded["tokens"]), jnp.asarray(rows),
                    jnp.asarray(padded["pos"]), jnp.asarray(padded["seg"]))[:n]
        top2 = jax.lax.top_k(logits, 2)[0]
        at_served = jnp.take_along_axis(logits, jnp.asarray(lay["served"])[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(top2[:, 0] - at_served, np.float64))
        margins.append(np.asarray(top2[:, 0] - top2[:, 1], np.float64))
    return {"gap": np.concatenate(gaps), "margin": np.concatenate(margins)}


def summarize(gap: np.ndarray, margin: np.ndarray) -> Dict[str, Any]:
    n = max(len(gap), 1)
    return {
        "served_gap_mean_square": float(np.square(gap).sum() / n),
        "served_gap_mean": float(gap.sum() / n),
        "served_gap_widest": float(gap.max()) if len(gap) else None,
        "tokens_compared": int(len(gap)),
        "tokens_differ": int((gap > 0).sum()),
    }
