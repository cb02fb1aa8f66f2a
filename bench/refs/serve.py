"""Plain reference of greedy serving: run the float32 model once over each
sampled prompt followed by the tokens the program served, and read at
each served position how far the served token's logit lies below the
reference's best (0 where the program chose the reference's argmax).

The model is applied one layer at a time, each layer's weights made
again from the seed, so that the whole reference never holds more than
one layer and the sampled sequences (padded to one length, so that it
compiles once).
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights
from refs import dense

F32 = jnp.float32


class Reference:
    """Compiled once per configuration, batch of sequences and length."""

    def __init__(self, hf: dict, lowp=None):
        self.hf = hf

        def apply_layer(key, l, x):
            w = weights.layer(key, hf, l)
            return jax.lax.map(lambda s: dense.layer(w, s, hf, lowp), x)

        def first(key, tokens):
            e = weights.embed(key, hf)
            return dense.embed_tokens(e, tokens)

        def last(key, x):
            e = weights.embed(key, hf)
            return jax.lax.map(lambda s: dense.logits(e, s, hf, lowp), x)

        self.apply_layer = jax.jit(apply_layer, donate_argnums=(2,))
        self.first = jax.jit(first)
        self.last = jax.jit(last)

    def logits(self, seed31: int, tokens: np.ndarray) -> jax.Array:
        """(B, S, V) float32 logits of a (B, S) batch of sequences."""
        key = weights.root_key(seed31)
        x = self.first(key, jnp.asarray(tokens))
        for l in range(self.hf["num_hidden_layers"]):
            x = self.apply_layer(key, jnp.int32(l), x)
        return self.last(key, x)


def pack(seqs: Sequence[np.ndarray], length: int) -> np.ndarray:
    """Sequences padded with token 0 at the end to one length (a causal
    model's earlier positions do not see the padding)."""
    out = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out


def served_positions(prompts: Sequence[np.ndarray],
                     served: Sequence[Sequence[int]]):
    """Input sequences (prompt + served tokens but the last), and per
    sequence the (position, served token) pairs: the token served after
    position p is read from the logits at p."""
    seqs, pairs = [], []
    for p, g in zip(prompts, served):
        g = np.asarray(g, np.int32)
        seqs.append(np.concatenate([np.asarray(p, np.int32), g[:-1]]))
        P = len(p)
        pairs.append([(P - 1 + k, int(t)) for k, t in enumerate(g)])
    return seqs, pairs


@jax.jit
def _gaps(logits, pos, tok):
    """Per (b, k): max logit at pos[b, k] minus the logit of tok[b, k]."""
    row = jnp.take_along_axis(logits, pos[:, :, None], axis=1)
    best = row.max(-1)
    chosen = jnp.take_along_axis(row, tok[:, :, None], axis=-1)[..., 0]
    return best - chosen


def gaps(logits, pairs: List[list], choose=None) -> np.ndarray:
    """The gaps of the given (position, token) pairs, flattened. With
    ``choose`` (another model's (B, S, V) logits), the token at each
    position is the one ``choose`` puts first instead."""
    K = max(len(p) for p in pairs)
    pos = np.zeros((len(pairs), K), np.int32)
    tok = np.zeros((len(pairs), K), np.int32)
    valid = np.zeros((len(pairs), K), bool)
    for b, pr in enumerate(pairs):
        for k, (p, t) in enumerate(pr):
            pos[b, k], tok[b, k], valid[b, k] = p, t, True
    if choose is not None:
        row = jnp.take_along_axis(choose, jnp.asarray(pos)[:, :, None],
                                  axis=1)
        tok = np.asarray(row.argmax(-1), np.int32)
    g = np.asarray(_gaps(logits, jnp.asarray(pos), jnp.asarray(tok)))
    return g[valid]
