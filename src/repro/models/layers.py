"""Shared model primitives: norms, RoPE, GQA attention (full / blocked-local /
decode-with-cache), gated MLP, sort-based MoE.

Parameters are plain nested dicts of jnp arrays so they stack cleanly for
scan-over-layers and shard with NamedSharding.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig

# A window value meaning "attend to everything" for per-layer window arrays.
FULL_WINDOW = np.int32(2**30)


def constrain(x, cfg: "ArchConfig", batch_dims: int = 1):
    """Activation sharding constraint hook. Under the mesh trainer's
    ``vmap(..., spmd_axis_name=<rps axes>)`` this is what pins the worker
    dim of every scanned carry/residual to the RPS axes (without it the
    compiled scan residuals replicate across data — 16x HBM)."""
    if not cfg.shard_acts:
        return x
    from jax.sharding import PartitionSpec as P
    entries = [None] * x.ndim
    if cfg.act_batch_axis is not None:
        entries[0] = cfg.act_batch_axis
    return jax.lax.with_sharding_constraint(x, P(*entries))


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x, gamma, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + gamma.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs          # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig, d_model=None):
    d = d_model or cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    dt = cfg.jnp_dtype
    return {
        "wq": _init(ks[0], (d, h, hd), s, dt),
        "wk": _init(ks[1], (d, kv, hd), s, dt),
        "wv": _init(ks[2], (d, kv, hd), s, dt),
        "wo": _init(ks[3], (h, hd, d), (h * hd) ** -0.5, dt),
    }


def _sdpa(q, k, v, mask):
    """q: (B,Sq,h,hd) k,v: (B,Sk,kv,hd) mask: broadcast (B,1,Sq,Sk) or (Sq,Sk)."""
    B, Sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q = q.reshape(B, Sq, kvh, g, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    logits = logits * (hd ** -0.5)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, h, hd)


def full_attention(q, k, v, *, causal=True, window=None, q_offset=0):
    """Quadratic attention with optional banded window mask.

    window may be a *traced* scalar (per-layer value inside a scan) — the
    mask is computed arithmetically so local/global layers share one code
    path (gemma3's 5:1 pattern).
    """
    Sq, Sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    delta = qpos[:, None] - kpos[None, :]
    mask = delta >= 0 if causal else jnp.ones((Sq, Sk), bool)
    if window is not None:
        mask = mask & (delta < window)
    return _sdpa(q, k, v, mask)


def chunked_attention(q, k, v, *, causal=True, window=None,
                      q_chunk: int = 512, k_chunk: int = 1024):
    """Memory-efficient (flash-style) attention in pure JAX: online-softmax
    over KV chunks, q-chunks unrolled so causally-dead KV blocks are skipped
    *statically* (exact FLOPs, no wasted upper-triangle compute). Each KV
    step is checkpointed, so backward recomputes the (qc x kc) score tiles
    instead of saving SxS f32 score matrices — this is what lets 32k-token
    prefill and 4k training of the full-attention archs fit HBM.
    """
    B, S, h, hd = q.shape
    Sk = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    qc = min(q_chunk, S)
    kc = min(k_chunk, Sk)
    pad_q = (-S) % qc
    pad_k = (-Sk) % kc
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = (S + pad_q) // qc, (Sk + pad_k) // kc
    kb = k.reshape(B, nk, kc, kvh, hd)
    vb = v.reshape(B, nk, kc, kvh, hd)
    scale = hd ** -0.5
    outs = []
    for iq in range(nq):
        q_i = q[:, iq * qc:(iq + 1) * qc].reshape(B, qc, kvh, g, hd)
        q_lo, q_hi = iq * qc, iq * qc + qc - 1
        # static KV-block range: causal upper bound, window lower bound
        j_hi = nk - 1 if not causal else min(nk - 1, q_hi // kc)
        j_lo = 0
        if window is not None:
            j_lo = max(0, (q_lo - int(window)) // kc)
        idx = jnp.arange(j_lo, j_hi + 1)

        @jax.checkpoint
        def step(carry, j, q_i=q_i, q_lo=q_lo):
            acc, m, l = carry
            kj = kb[:, j]
            vj = vb[:, j]
            s = jnp.einsum("bqkgh,bskh->bkgqs", q_i, kj)
            s = s.astype(jnp.float32) * scale
            qpos = q_lo + jnp.arange(qc)
            kpos = j * kc + jnp.arange(kc)
            delta = qpos[:, None] - kpos[None, :]
            mask = (kpos < Sk)[None, :] if not causal else (delta >= 0)
            if window is not None:
                mask = mask & (delta < window)
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(vj.dtype), vj)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, kvh, g, qc, hd), jnp.float32)
        m0 = jnp.full((B, kvh, g, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((B, kvh, g, qc), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), idx)
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        o = jnp.moveaxis(o, 3, 1).reshape(B, qc, h, hd)
        outs.append(o.astype(q.dtype))
    out = jnp.concatenate(outs, axis=1)
    return out[:, :S]


def blocked_local_attention(q, k, v, *, window: int):
    """Exact sliding-window causal attention in O(S·window).

    Queries in block b attend to key blocks b-1 and b (block size = window),
    masked to `qpos - kpos ∈ [0, window)`. Static `window` only.
    """
    B, S, h, hd = q.shape
    kvh = k.shape[2]
    w = int(window)
    if S <= 2 * w:      # not worth blocking
        return full_attention(q, k, v, causal=True, window=w)
    pad = (-S) % w
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nb = Sp // w
    qb = q.reshape(B, nb, w, h, hd)
    kb = k.reshape(B, nb, w, kvh, hd)
    vb = v.reshape(B, nb, w, kvh, hd)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    kw = jnp.concatenate([kprev, kb], axis=2)       # (B, nb, 2w, kvh, hd)
    vw = jnp.concatenate([vprev, vb], axis=2)
    i = jnp.arange(w)[:, None]
    j = jnp.arange(2 * w)[None, :]
    delta = (i + w) - j
    mask = (delta >= 0) & (delta < w)               # (w, 2w)
    # block 0 has no previous block: mask out its zero-padded first half
    blk = jnp.arange(nb)[:, None, None]
    mask = mask[None] & ((blk > 0) | (j[None] >= w))  # (nb, w, 2w)
    mask = mask[:, None, None]                        # (nb, 1, 1, w, 2w)
    g = h // kvh
    qb = qb.reshape(B, nb, w, kvh, g, hd)
    logits = jnp.einsum("bnqkgh,bnskh->bnkgqs", qb, kw).astype(jnp.float32)
    logits = logits * (hd ** -0.5)
    logits = jnp.where(mask, logits, -1e30)         # broadcasts over (B, kv, g)
    # first block has zero-padded "previous" keys — already masked by delta>=0
    probs = jax.nn.softmax(logits, axis=-1).astype(vw.dtype)
    out = jnp.einsum("bnkgqs,bnskh->bnqkgh", probs, vw)
    out = out.reshape(B, Sp, h, hd)
    return out[:, :S]


def decode_attention(q, k_cache, v_cache, pos, *, window=None, ring: bool = False):
    """One-token attention vs cache.

    q: (B,1,h,hd); caches: (B,C,kv,hd). `pos` is the absolute position of the
    new token — a scalar (the contiguous serving path, all requests in
    lock-step) or a (B,) vector (the paged path, per-request positions). If
    `ring`, the cache is a ring buffer of size C=window and all slots written
    so far are valid; otherwise slots with index<=pos are valid.
    """
    B, C, kvh, hd = k_cache.shape
    idx = jnp.arange(C)
    pos = jnp.asarray(pos)
    if ring:
        valid = idx < jnp.minimum(pos + 1, C)        # ring fully valid once warm
        mask = valid.reshape(1, 1, 1, 1, C)
    elif pos.ndim == 0:
        valid = idx <= pos
        if window is not None:
            valid = valid & (idx > pos - window)
        mask = valid.reshape(1, 1, 1, 1, C)
    else:                                            # per-request positions
        valid = idx[None, :] <= pos[:, None]
        if window is not None:
            valid = valid & (idx[None, :] > pos[:, None] - window)
        mask = valid.reshape(B, 1, 1, 1, C)
    g = q.shape[2] // kvh
    qr = q.reshape(B, 1, kvh, g, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qr, k_cache).astype(jnp.float32)
    logits = logits * (hd ** -0.5)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v_cache)
    return out.reshape(B, 1, q.shape[2], hd)


def attention_fwd(p, x, *, cfg: ArchConfig, window, q_offset=0,
                  kv_override=None, causal=True, blocked=False):
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    src = x if kv_override is None else kv_override
    k = jnp.einsum("bsd,dhe->bshe", src, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", src, p["wv"])
    if kv_override is None:   # self-attention -> RoPE
        q = rope(q, jnp.arange(q.shape[1]) + q_offset, cfg.rope_theta)
        k = rope(k, jnp.arange(k.shape[1]), cfg.rope_theta)
    S = q.shape[1]
    static_w = window is not None and not isinstance(window, jax.core.Tracer)
    if blocked and static_w and S > 2 * int(window):
        out = blocked_local_attention(q, k, v, window=int(window))
    elif S > 2048 and not isinstance(window, jax.core.Tracer):
        out = chunked_attention(q, k, v, causal=causal,
                                window=int(window) if static_w else None)
    else:
        out = full_attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
    out = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return out, (k, v)


def attention_decode(p, x, k_cache, v_cache, pos, *, cfg: ArchConfig,
                     window=None, ring=False):
    """One-step decode. Writes (k,v) at pos (mod C if ring). Returns
    (out, new_k_cache, new_v_cache)."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    q = rope(q, jnp.full((1,), pos), cfg.rope_theta)
    k = rope(k, jnp.full((1,), pos), cfg.rope_theta)
    C = k_cache.shape[1]
    slot = pos % C if ring else pos
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                           (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                           (0, slot, 0, 0))
    out = decode_attention(q, k_cache, v_cache, pos, window=window, ring=ring)
    out = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# Paged KV attention (DESIGN.md §18)
# ---------------------------------------------------------------------------

def paged_gather(pool, layer, bt, page: int):
    """Reconstruct one layer's per-request contiguous cache view from the
    stacked slot pool.

    pool: (n_layers, n_slots, kvh, hd) flat token slots of every layer of
    a kind; layer: the member index into its leading axis (a Python int or
    a traced scalar); bt: (B, P) int32 block table. Returns
    (B, P·page, kvh, hd) where row i is the slot holding absolute position
    i of that request — bit-identical to the contiguous cache when the
    request's blocks were allocated in order (pinned by test). One gather
    of rows from the stacked buffer seen as (n_layers·n_slots, kvh, hd): no
    layer-sized slice is made first, and the row index is 1-D, which a v5e
    gathers faster than a (layer, slot) index pair (a decode step at
    Mistral-7B widths, 24 lanes x 2560 positions: 39.2 against 41.5 ms).
    Unwritten positions read whatever the pointed-to slot holds (block 0 =
    the null block for unallocated pages); the decode mask hides them.
    """
    B, P = bt.shape
    slots = bt[:, :, None] * page + jnp.arange(page)[None, None, :]
    n_layers, n_slots = pool.shape[:2]
    rows = pool.reshape((n_layers * n_slots,) + pool.shape[2:])
    return rows[layer * n_slots + slots.reshape(B, P * page)]


def paged_write(pool, layer, new, bt, pos, page: int):
    """Scatter one token's K or V into each request's slot at `pos`, in
    layer `layer` of the stacked pool (n_layers, n_slots, kvh, hd).

    new: (B, 1, kvh, hd); pos: (B,) absolute positions. Inactive lanes point
    at the null block (id 0) and harmlessly overwrite its slots; active
    lanes own their blocks exclusively, so the scatter indices never collide
    across live requests. The pool is the decode loop's state, so XLA
    updates it in place. The index stays a (layer, slot) pair: a scatter
    through the flat row view of ``paged_gather`` made XLA copy a pool
    with a unit head axis.
    """
    B = bt.shape[0]
    flat = bt[jnp.arange(B), pos // page] * page + pos % page
    return pool.at[layer, flat].set(new[:, 0].astype(pool.dtype))


def attention_decode_paged(p, x, pool_k, pool_v, layer, pos, *, bt,
                           page: int, cfg: ArchConfig, window=None, tp=None,
                           tp_masks=None, site=None, key=None):
    """One-step decode against the paged pool: write the new token's K/V
    into layer `layer` of the stacked pools through the block table, gather
    that layer's contiguous view, attend with per-request positions. `tp`
    (a serve.tp.TPContext) reroutes the output projection through the
    drop-masked exchange — `site` indexes this layer's collective's packet
    masks in `tp_masks`. Returns (out, new_pool_k, new_pool_v), the whole
    stacked pools."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)
    pool_k = paged_write(pool_k, layer, k, bt, pos, page)
    pool_v = paged_write(pool_v, layer, v, bt, pos, page)
    kc = paged_gather(pool_k, layer, bt, page)
    vc = paged_gather(pool_v, layer, bt, page)
    out = decode_attention(q, kc, vc, pos, window=window, ring=False)
    if tp is None:
        out = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    else:
        out = tp.combine_attn(out, p["wo"], tp_masks, site, key)
    return out, pool_k, pool_v


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d_model=None, d_ff=None):
    d = d_model or cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = cfg.jnp_dtype
    return {
        "wi": _init(ks[0], (d, ff), d ** -0.5, dt),
        "wg": _init(ks[1], (d, ff), d ** -0.5, dt),
        "wo": _init(ks[2], (ff, d), ff ** -0.5, dt),
    }


def mlp(p, x):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    h = jax.nn.silu(g) * h
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


def init_moe(key, cfg: ArchConfig):
    ks = jax.random.split(key, 4)
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = cfg.jnp_dtype
    return {
        "router": _init(ks[0], (d, E), d ** -0.5, jnp.float32),
        "wi": _init(ks[1], (E, d, ff), d ** -0.5, dt),
        "wg": _init(ks[2], (E, d, ff), d ** -0.5, dt),
        "wo": _init(ks[3], (E, ff, d), ff ** -0.5, dt),
    }


def moe(p, x, cfg: ArchConfig, expert_sharding=None):
    """Sort-based top-k MoE with per-expert capacity (Megablocks-style
    permutation dispatch rather than (T,E,C) one-hot — the one-hot tensor is
    O(T·E·C) and infeasible at 1M tokens × 384 experts).

    Returns (out, aux_loss)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, d)
    logits = (xt.astype(jnp.float32) @ p["router"])           # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)             # (T, K)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # load-balance auxiliary loss (Switch-style)
    me = probs.mean(0)                                        # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[gate_idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    cap = int(np.ceil(T * K / E * cfg.capacity_factor))
    cap = max(cap, 4)
    flat_e = gate_idx.reshape(-1)                             # (T*K,)
    # position of each assignment within its expert, via sort
    order = jnp.argsort(flat_e, stable=True)                  # (T*K,)
    sorted_e = flat_e[order]
    # rank within expert = index - start_of_expert
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank_sorted = jnp.arange(T * K) - starts[sorted_e]
    rank = jnp.zeros((T * K,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < cap
    slot = flat_e * cap + jnp.where(keep, rank, 0)            # (T*K,)

    tok_idx = jnp.repeat(jnp.arange(T), K)
    buf = jnp.zeros((E * cap, d), x.dtype)
    contrib = jnp.where(keep[:, None], xt[tok_idx], 0)
    buf = buf.at[slot].add(contrib)                           # scatter dispatch
    ebuf = buf.reshape(E, cap, d)
    if expert_sharding is not None:
        ebuf = jax.lax.with_sharding_constraint(ebuf, expert_sharding)
    elif cfg.shard_acts:
        from jax.sharding import PartitionSpec as P
        # expert-parallel buffer when E divides the model axis, else TP on d
        espec = P("model", None, None) if E % 16 == 0 else P(None, None, None)
        ebuf = jax.lax.with_sharding_constraint(ebuf, espec)
    h = jnp.einsum("ecd,edf->ecf", ebuf, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", ebuf, p["wg"])
    if cfg.shard_acts:
        from jax.sharding import PartitionSpec as P
        hspec = P("model", None, None) if E % 16 == 0 \
            else P(None, None, "model")
        h = jax.lax.with_sharding_constraint(h, hspec)
        g = jax.lax.with_sharding_constraint(g, hspec)
    h = jax.nn.silu(g) * h
    out_e = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(E * cap, d)
    # combine: gather back and weight by gates
    gathered = out_e[slot] * (gate_vals.reshape(-1, 1).astype(x.dtype))
    gathered = jnp.where(keep[:, None], gathered, 0)
    out = jnp.zeros((T, d), x.dtype).at[tok_idx].add(gathered)
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ArchConfig):
    ks = jax.random.split(key, 2)
    dt = cfg.jnp_dtype
    V = cfg.padded_vocab        # Megatron-style padding: shardable over model
    return {
        "tok": _init(ks[0], (V, cfg.d_model), 1.0, dt),
        "head": _init(ks[1], (cfg.d_model, V), cfg.d_model ** -0.5, dt),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }


def embed(p, tokens):
    return p["tok"][tokens]


def lm_head(p, x, vocab_size: Optional[int] = None):
    """Returns logits over the PADDED vocab with padding masked to -inf;
    real-vocab slicing happens at the serving API boundary."""
    x = rms_norm(x, p["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, p["head"])
    V = logits.shape[-1]
    if vocab_size is not None and vocab_size < V:
        mask = jnp.arange(V) >= vocab_size
        logits = jnp.where(mask, jnp.asarray(-1e30, logits.dtype), logits)
    return logits


def softmax_xent(logits, labels, mask=None):
    """Mean next-token cross-entropy; logits (B,S,V), labels (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)
