"""Plain reference of a dense decoder (llama layout: RMSNorm, rotary
attention with grouped KV heads, SwiGLU MLP, untied head), in float32.

It imports nothing of the program. Weights come from ``bench/weights.py``
by name and seed. Every matrix product runs at ``Precision.HIGHEST``, so
that on a TPU float32 stays float32. ``lowp`` swaps that for inputs
rounded to a lower precision (``float8_e4m3fn`` for a bfloat16
configuration) with float32 sums: the control that a sound comparison has
to reject.

Formulas, per layer l, on a (S, d) sequence x:

    h = x + Wo · attn(rope(Wq n1), rope(Wk n1), Wv n1),  n1 = rms(x)(1 + g1)
    y = h + W2 · (silu(Wg n2) * (Wi n2)),                 n2 = rms(h)(1 + g2)

with causal softmax(q k^T / sqrt(hd)); logits = Wh · rms(x_L)(1 + gf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def mm(eq: str, a, b, lowp=None):
    if lowp is None:
        return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision=HIGHEST)
    # round the inputs to the lower precision, then multiply exactly (the
    # rounded values are bf16-representable) and sum in f32
    a = a.astype(lowp).astype(jnp.bfloat16)
    b = b.astype(lowp).astype(jnp.bfloat16)
    return jnp.einsum(eq, a, b, preferred_element_type=F32)


def rms_norm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(F32))


def rope(x, pos, theta):
    """x: (S, H, hd), rotating the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, lowp=None):
    """Causal attention of one sequence: q (S, h, hd), k, v (S, kv, hd)."""
    S, h, hd = q.shape
    kvh = k.shape[1]
    q = q.reshape(S, kvh, h // kvh, hd)
    s = mm("qkgd,skd->kgqs", q, k, lowp) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("kgqs,skd->qkgd", p, v, lowp)
    return o.reshape(S, h, hd)


def layer(w: dict, x, hf: dict, lowp=None):
    """One decoder layer on one (S, d) sequence."""
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    pos = jnp.arange(x.shape[0])
    n1 = rms_norm(x, w["ln1"], eps)
    q = rope(mm("sd,dhe->she", n1, w["wq"], lowp), pos, theta)
    k = rope(mm("sd,dhe->she", n1, w["wk"], lowp), pos, theta)
    v = mm("sd,dhe->she", n1, w["wv"], lowp)
    h = x + mm("she,hed->sd", attention(q, k, v, lowp), w["wo"], lowp)
    n2 = rms_norm(h, w["ln2"], eps)
    a = jax.nn.silu(mm("sd,df->sf", n2, w["wg"], lowp)) \
        * mm("sd,df->sf", n2, w["wi"], lowp)
    return h + mm("sf,fd->sd", a, w["w2"], lowp)


def logits(e: dict, x, hf: dict, lowp=None):
    return mm("sd,dv->sv", rms_norm(x, e["final_norm"], hf["rms_norm_eps"]),
              e["head"], lowp)


def embed_tokens(e: dict, tokens):
    return e["tok"][tokens].astype(F32)


def loss(params: dict, tokens, labels, hf: dict, lowp=None):
    """Mean next-token cross entropy of a (B, S) batch;
    ``params = {"embed": {...}, "layers": [layer dicts]}``."""
    def one(tok, lab):
        x = embed_tokens(params["embed"], tok)
        for w in params["layers"]:
            x = layer(w, x, hf, lowp)
        lg = logits(params["embed"], x, hf, lowp)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, lab[:, None], -1)[:, 0]
        return jnp.mean(nll)
    return jnp.mean(jax.vmap(one)(tokens, labels))
