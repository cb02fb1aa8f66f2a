"""The work of one step of the training cells, counted from shapes by the
algorithm, never by how the program happens to do it, so that a rewrite
of the step or of a kernel is read against the same work."""
from __future__ import annotations

from typing import Sequence

import program


def step_flops(hf: dict, tokens: int, seq_len: int) -> float:
    """Model FLOPs of one step's forward and backward passes over all
    workers' ``tokens``: 6 x the matrix-product parameters (the untied
    head among them) per token, plus three times the causal attention's
    forward FLOPs per sequence. Recomputation does not count."""
    seqs = tokens / seq_len
    return (6.0 * program.matmul_params(hf) * tokens
            + 3.0 * program.attn_flops_fwd(hf, seq_len, seq_len) * seqs)


def masked_avg_bytes(leaf_sizes: Sequence[int], n: int, s: int,
                     itemsize: int) -> int:
    """HBM bytes of one exchange round's renormalised masked average
    (the paper's Algorithm 1, line 6): read each of the n workers' copies
    of every parameter once, read every block's n delivery flags once
    (a byte each), write each block's average once."""
    params = sum(int(x) for x in leaf_sizes)
    return (n + 1) * params * itemsize + len(leaf_sizes) * n * s
