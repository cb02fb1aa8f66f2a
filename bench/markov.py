"""The training cells' data: sequences of a seeded Markov chain over a
token alphabet, the benchmark's own copy of the program's synthetic
language task (``data.synthetic.CharLMTask``: a fixed random transition
table, rows softmax(N(0, 1) * order_temp), so the loss has a known
entropy floor).

The mix's ``task`` block sets the alphabet (``vocab``), ``order_temp`` and
how many distinct batches a run holds (``batches``); the run's seed draws
the table and every sequence. All sequences are drawn at once on the host
(inverse-CDF sampling, one table row per position), so set-up pays a
fixed amount of work whatever the seed.
"""
from __future__ import annotations

import numpy as np


def transition_cdf(vocab: int, order_temp: float, rng) -> np.ndarray:
    logits = rng.normal(size=(vocab, vocab)) * order_temp
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.cumsum(p, axis=-1)


def batches(seed31: int, task: dict, workers: int, batch: int,
            seq_len: int):
    """``(tokens, labels)``, each int32 of shape (batches, workers, batch,
    seq_len): the labels are the tokens shifted by one."""
    rng = np.random.default_rng(seed31)
    vocab = int(task["vocab"])
    cdf = transition_cdf(vocab, float(task["order_temp"]), rng)
    rows = int(task["batches"]) * workers * batch
    toks = np.empty((rows, seq_len + 1), np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=rows)
    u = rng.random((seq_len, rows))
    for t in range(seq_len):
        nxt = (u[t][:, None] < cdf[toks[:, t]]).argmax(-1)
        toks[:, t + 1] = nxt
    toks = toks.astype(np.int32).reshape(int(task["batches"]), workers,
                                         batch, seq_len + 1)
    return toks[..., :-1].copy(), toks[..., 1:].copy()
