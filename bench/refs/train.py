"""Plain reference of the simulator's training step: n workers, each on
its own batch, take a plain SGD step on a dense decoder (llama layout, as
``refs/dense.py`` writes it) and then exchange their models by the
paper's Algorithm 1 (RPS model averaging) under the step's drop masks.

It imports nothing of the program. Weights come from ``bench/weights.py``
by name and seed; the batches and the step's masks are handed in as data.
Everything is computed in float32 with every matrix product at
``Precision.HIGHEST``; the parameters are held between steps in the
configuration's bfloat16, as the program holds them:

    loss_i = mean next-token cross entropy of worker i's batch
    y_i    = bf16(x_i - bf16(lr * grad loss_i(x_i)))           (SGD)
    for every tensor, flattened and cut into s equal blocks (the last
    padded), with rs, ag the step's (n, s) masks:
      avg_j  = sum_i rs[i, j] y_i[j] / max(sum_i rs[i, j], 1)  (reduce-scatter,
                                                   renormalised, in f32)
      x_i[j] = bf16(avg_j) if ag[i, j] else y_i[j]             (all-gather,
                                                   local fallback)

The model is applied one layer at a time and each worker in turn, the
backward pass one layer at a time too, and each layer's update applied as
soon as its gradient is known, so that the reference holds only the n
workers' bfloat16 parameters and one layer's float32 gradient.

``lowp`` computes every matrix product, forward and backward, on inputs
rounded to a lower precision (float8 e4m3 for this bfloat16
configuration) under one scale per tensor, with float32 sums: the
control that a sound comparison has to reject. With ``backward_only``
only the backward pass's products are so rounded: a gradient computed in
lower precision, which the comparison has to reject as well. ``half_batch``
(each worker's loss over the first half of its sequences) and
``exchange=False`` (each worker keeps its own update) are faults a
broken step could have.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights
from refs import dense

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
EMBED = ("tok", "head", "final_norm")
LAYER = weights.LAYER_KEYS
#: leaves whose step-one gradient in the reference is under this share of
#: the median leaf's are nought to rounding, and left out of the change
NOUGHT = 1e-3


def mm32(eq: str, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def scaled_round(x, dtype):
    """``x`` rounded to ``dtype`` under one scale for the whole tensor that
    maps its largest magnitude to the format's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    q = jnp.clip(x / scale, -top, top).astype(dtype)
    return q.astype(F32) * scale


def lowp_mm(dtype, forward: bool = True):
    """A matrix product whose inputs in the backward pass (the incoming
    gradient too), and in the forward pass unless ``forward`` is false,
    are rounded to ``dtype`` first."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def mm(eq, a, b):
        return fwd(eq, a, b)[0]

    def fwd(eq, a, b):
        qa, qb = scaled_round(a, dtype), scaled_round(b, dtype)
        return mm32(eq, qa, qb) if forward else mm32(eq, a, b), (qa, qb)

    def bwd(eq, res, ct):
        _, vjp = jax.vjp(lambda x, y: mm32(eq, x, y), *res)
        return vjp(scaled_round(ct, dtype))

    mm.defvjp(fwd, bwd)
    return mm


# ---------------------------------------------------------------------------
# the model, on a (B, S, d) batch of sequences
# ---------------------------------------------------------------------------

def attention(mm, q, k, v):
    """Causal attention: q (B, S, h, hd), k, v (B, S, kv, hd)."""
    B, S, h, hd = q.shape
    kvh = k.shape[2]
    q = q.reshape(B, S, kvh, h // kvh, hd)
    s = mm("bqkgd,bskd->bkgqs", q, k) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return mm("bkgqs,bskd->bqkgd", p, v).reshape(B, S, h, hd)


def layer(mm, w: dict, x, hf: dict):
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    pos = jnp.arange(x.shape[1])
    n1 = dense.rms_norm(x, w["ln1"], eps)
    q = dense.rope(mm("bsd,dhe->bshe", n1, w["wq"]), pos, theta)
    k = dense.rope(mm("bsd,dhe->bshe", n1, w["wk"]), pos, theta)
    v = mm("bsd,dhe->bshe", n1, w["wv"])
    h = x + mm("bshe,hed->bsd", attention(mm, q, k, v), w["wo"])
    n2 = dense.rms_norm(h, w["ln2"], eps)
    a = jax.nn.silu(mm("bsd,df->bsf", n2, w["wg"])) \
        * mm("bsd,df->bsf", n2, w["wi"])
    return h + mm("bsf,fd->bsd", a, w["w2"])


def head_loss(mm, e: dict, x, labels, hf: dict):
    lg = mm("bsd,dv->bsv", dense.rms_norm(x, e["final_norm"],
                                          hf["rms_norm_eps"]), e["head"])
    nll = jax.nn.logsumexp(lg, -1) \
        - jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(nll)


def round_to(x, dtype):
    """``x`` rounded to ``dtype``'s precision, kept in float32 (never
    folded away, as a pair of converts may be)."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


class Reference:
    """Compiled once per configuration and batch shape."""

    def __init__(self, hf: dict, lowp=None, backward_only: bool = False,
                 half_batch: bool = False, exchange: bool = True):
        self.hf, self.exchange_on = hf, exchange
        self.z = z = weights.dims(hf)
        stored = weights.stored_dtype(hf)
        mm = mm32 if lowp is None else lowp_mm(jnp.dtype(lowp),
                                               forward=not backward_only)
        cut = (lambda a: a[:, :a.shape[1] // 2]) if half_batch \
            else (lambda a: a)

        def w32(stacks, i, l):
            return {k: stacks[k][i, l].astype(F32) for k in LAYER}

        @functools.partial(jax.jit, static_argnums=(1,))
        def start_tensor(key, name, l):
            return weights.tensor(key, name, z, stored, l)

        def start(key, name):
            """One tensor of the start as stored (layers stacked), made
            again from the seed, a layer at a time (the layer index
            traced: XLA's TPU compiler took minutes and tens of GB of
            host memory over two layers' draws in one program)."""
            if name in EMBED:
                return start_tensor(key, name, 0)
            return jnp.stack([start_tensor(key, name, l)
                              for l in range(z["L"])])

        @jax.jit
        def embed_fwd(tok, i, tokens):
            return tok[i, cut(tokens)].astype(F32)

        @jax.jit
        def layer_fwd(stacks, i, l, x):
            return layer(mm, w32(stacks, i, l), x, hf)

        @jax.jit
        def head(e, i, x, labels):
            e32 = {k: e[k][i].astype(F32) for k in ("head", "final_norm")}
            loss, (ge, gx) = jax.value_and_grad(
                lambda a, b: head_loss(mm, a, b, cut(labels), hf),
                argnums=(0, 1))(e32, x)
            return loss, ge, gx

        @jax.jit
        def layer_bwd(stacks, i, l, x, gy):
            _, vjp = jax.vjp(lambda w, a: layer(mm, w, a, hf),
                             w32(stacks, i, l), x)
            return vjp(gy)

        @jax.jit
        def embed_bwd(tok, tokens, gx):
            d = tok.shape[-1]
            return jnp.zeros(tok.shape[1:], F32).at[
                cut(tokens).reshape(-1)].add(gx.reshape(-1, d))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def sgd(stack, idx, g, lr):
            """SGD on one worker's slice ``idx`` of a stacked tensor, the
            update cast to bfloat16 and subtracted in it; also returns the
            squared norm of the gradient."""
            x = stack[idx].astype(F32)
            y = (x - round_to(lr * g, stack.dtype)).astype(stack.dtype)
            return stack.at[idx].set(y), jnp.sum(g * g)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def rps(stack, rs, ag):
            n, s = rs.shape
            flat = stack.reshape(n, -1)
            size = flat.shape[1]
            pad = (-size) % s
            if pad:
                flat = jnp.pad(flat, ((0, 0), (0, pad)))
            blocks = flat.reshape(n, s, -1)
            w = rs.astype(F32)
            total = jnp.sum(w[:, :, None] * blocks.astype(F32), axis=0)
            avg = (total / jnp.maximum(w.sum(0), 1.0)[:, None]
                   ).astype(stack.dtype)
            out = jnp.where(ag[:, :, None], avg[None], blocks)
            return out.reshape(n, -1)[:, :size].reshape(stack.shape)

        @jax.jit
        def change(stack, start):
            """Per worker, the norm of the change of a stacked tensor."""
            d = stack.astype(F32) - start.astype(F32)[None]
            return jnp.sqrt(jnp.sum(d * d, axis=tuple(range(1, d.ndim))))

        self.start = start
        self._fns = dict(embed_fwd=embed_fwd, layer_fwd=layer_fwd,
                         head=head, layer_bwd=layer_bwd,
                         embed_bwd=embed_bwd, sgd=sgd, rps=rps,
                         change=change)

    def run(self, seed31: int, tokens: np.ndarray, labels: np.ndarray,
            masks: Sequence, lr: float, steps: int) -> dict:
        """Follow ``steps`` steps from the seed's start. ``tokens``,
        ``labels``: (steps, n, B, S); ``masks``: per step the (rs, ag)
        pair of (n, s) booleans. Returns each step's loss (mean over the
        workers), each tensor's gradient norm per worker at the first
        step, and per worker the norm of each tensor's change from the
        start after the first step and after the last."""
        f = self._fns
        n = tokens.shape[1]
        L = self.z["L"]
        key = weights.root_key(seed31)
        x = {k: jnp.repeat(self.start(key, k)[None], n, axis=0)
             for k in EMBED + LAYER}
        losses: List[float] = []
        grad_sq = {k: np.zeros(n) for k in x}
        changes: Dict[int, Dict[str, np.ndarray]] = {}
        for t in range(steps):
            step_loss = []
            for i in range(n):
                tok, lab = jnp.asarray(tokens[t, i]), jnp.asarray(labels[t, i])
                acts = [f["embed_fwd"](x["tok"], i, tok)]
                lay = {k: x[k] for k in LAYER}
                for l in range(L):
                    acts.append(f["layer_fwd"](lay, i, l, acts[-1]))
                e = {k: x[k] for k in ("head", "final_norm")}
                loss, ge, gx = f["head"](e, i, acts.pop(), lab)
                step_loss.append(float(loss))
                del e
                for k in ("head", "final_norm"):
                    x[k], sq = f["sgd"](x[k], i, ge[k], lr)
                    grad_sq[k][i] += float(sq) if t == 0 else 0.0
                for l in reversed(range(L)):
                    lay = {k: x[k] for k in LAYER}
                    gw, gx = f["layer_bwd"](lay, i, l, acts.pop(), gx)
                    del lay
                    for k in LAYER:
                        x[k], sq = f["sgd"](x[k], (i, l), gw[k], lr)
                        grad_sq[k][i] += float(sq) if t == 0 else 0.0
                    del gw
                g_tok = f["embed_bwd"](x["tok"], tok, gx)
                x["tok"], sq = f["sgd"](x["tok"], i, g_tok, lr)
                grad_sq["tok"][i] += float(sq) if t == 0 else 0.0
                del g_tok, gx
            losses.append(float(np.mean(step_loss)))
            if self.exchange_on:
                rs, ag = (jnp.asarray(m) for m in masks[t])
                for k in x:
                    x[k] = f["rps"](x[k], rs, ag)
            if t + 1 in (1, steps):
                changes[t + 1] = {k: np.asarray(f["change"](
                    x[k], self.start(key, k))) for k in x}
        return {"loss": losses,
                "grad": {k: np.sqrt(v) for k, v in grad_sq.items()},
                "change": changes}


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared, from the program's and the reference's
    readings (``Reference.run``'s form): the widest relative gap of a
    step's loss; and for the change after the first step (``change1_gap``)
    and after the last (``change_gap``), the widest gap, over every
    worker's copy of every tensor, between the program's norm of the
    change and the reference's, over the reference's norm of that tensor
    or of the median tensor, whichever is larger. Tensors whose
    step-one gradient in the reference is under ``NOUGHT`` of the median
    tensor's are left out of both."""
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    names = sorted(ref["grad"])
    g = np.array([ref["grad"][k] for k in names])          # (leaves, n)
    kept = g >= NOUGHT * np.median(g)
    last = max(ref["change"])
    worst = {}
    for label, t in (("change1_gap", 1), ("change_gap", last)):
        r = np.array([ref["change"][t][k] for k in names])
        p = np.array([prog["change"][t][k] for k in names])
        den = np.maximum(r, np.median(r[kept]))
        gap = np.where(kept, np.abs(p - r) / den, 0.0)
        out[label] = float(gap.max())
        j, i = np.unravel_index(int(gap.argmax()), gap.shape)
        worst[label] = {"tensor": names[j], "worker": int(i),
                        "program": float(p[j, i]),
                        "reference": float(r[j, i])}
    out["left_out"] = [f"{names[j]}[{i}]" for j, i in zip(*np.where(~kept))]
    out["worst"] = worst
    return out
