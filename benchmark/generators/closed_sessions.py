"""Closed loop: a fixed number of clients, each sending its next request when
the last reply is read.  A pure function of (traffic file, seed, seconds): one
list of requests that the clients pull from in order.

Documents come in groups of ``document_group`` whose lengths are the
distribution's quantiles, so any run of whole groups holds the same multiset
of lengths.  A window holds only the list's head, and which documents fall
into it decides how much there is to prefill: so the ORDER of the lengths is
the mix's own (``order_seed``), the same for every ``--seed``, and the seed
draws the token ids only.  ``documents_in_rotation`` documents are open at
a time and take turns, so between two questions on one document lie the
questions on the others; a finished document's place is taken by the next.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import strata

DRIVER = "closed_loop"


def schedule(traffic: Dict, seed: int, seconds: float, vocab_size: int) -> List[Dict]:
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(int(traffic["order_seed"]))
    n_docs = int(traffic["documents"])
    group = int(traffic["document_group"])
    per_doc = int(traffic["questions_per_document"])
    doc_lens: List[int] = []
    for _ in range(-(-n_docs // group)):
        doc_lens.extend(strata.lengths(traffic["document_tokens"], group, order))
    doc_lens = doc_lens[:n_docs]
    n_req = n_docs * per_doc
    block = group * per_doc
    q_lens: List[int] = []
    a_lens: List[int] = []
    for _ in range(-(-n_req // block)):
        q_lens.extend(strata.lengths(traffic["question_tokens"], block, order))
        a_lens.extend(strata.lengths(traffic["answer_tokens"], block, order))
    docs = [rng.integers(0, vocab_size, n).tolist() for n in doc_lens]

    rotation = int(traffic["documents_in_rotation"])
    open_docs = list(range(min(rotation, n_docs)))
    asked = [0] * n_docs
    next_doc = len(open_docs)
    out: List[Dict] = []
    turn = 0
    while open_docs:
        d = open_docs[turn % len(open_docs)]
        i = len(out)
        question = rng.integers(0, vocab_size, q_lens[i]).tolist()
        out.append({
            "phase": "list",
            "document": d,
            "shared_tokens": len(docs[d]),
            "prompt": docs[d] + question,
            "max_new": int(a_lens[i]),
            "temperature": float(traffic.get("temperature", 0.0)),
        })
        asked[d] += 1
        if asked[d] == per_doc:
            pos = open_docs.index(d)
            if next_doc < n_docs:
                open_docs[pos] = next_doc
                next_doc += 1
                turn += 1
            else:
                open_docs.pop(pos)
                turn = pos
        else:
            turn += 1
    return out
