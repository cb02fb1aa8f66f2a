"""Generic layer-stack machinery.

An architecture is described by a *kind sequence*: one entry per layer, in
faithful order, e.g. gemma3 = [local, local, local, local, local, global] * k.
Each distinct kind gets its layers' params stacked along a leading axis and
executed with ``jax.lax.scan`` (+ per-layer ``jax.checkpoint``), which keeps
HLO size O(#kinds) instead of O(#layers) — essential for 126-layer configs
on the dry-run path.

Two execution orders:
  - grouped=True  (default for full configs): run each kind group as one
    scan, groups in first-appearance order. Layer *order* is permuted w.r.t.
    the faithful model, which leaves FLOPs / bytes / collective volume — the
    dry-run observables — unchanged (DESIGN.md §5).
  - grouped=False (faithful): unroll layers in the exact kind-sequence order,
    slicing each layer's params out of its group stack. Used by smoke tests
    and the training demos.

A *kind* is implemented by a :class:`KindSpec` with init / train / prefill /
decode functions. ``aux`` threads side inputs (e.g. the Whisper encoder
output) into every layer.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as _L


@dataclasses.dataclass(frozen=True)
class KindSpec:
    name: str
    init: Callable[..., Any]                   # (key, cfg) -> layer params
    train: Callable[..., Any]                  # (p, x, aux, cfg) -> (x, auxloss)
    prefill: Callable[..., Any]                # (p, x, aux, cfg) -> (x, cache_l)
    decode: Callable[..., Any]                 # (p, x, cache_l, pos, aux, cfg)
                                               #   -> (x, new_cache_l)
    init_cache: Callable[..., Any]             # (cfg, batch, max_len) -> pytree
    # paged serving path (DESIGN.md §18) — optional; kinds without it
    # cannot serve through the continuous-batching engine
    decode_paged: Optional[Callable[..., Any]] = None
    # (p, x, pool, m, pos, aux, cfg) -> (x, new_pool); pool is the kind's
    # whole stacked slot pool: {"k"/"v": (n_members, n_slots, kvh, hd),
    # "layer_id": (n_members,) i32}, m the member index of this layer (a
    # traced scalar in a scan, a Python int unrolled); the step updates
    # member m in place and returns the whole pool. pos is (B,) per-request
    # positions and aux["paged"] carries the block table / page size /
    # exchange hooks
    paged_spec: Optional[Callable[..., Any]] = None
    # (cfg, n_slots) -> per-layer pool pytree


def group_layout(kinds: Sequence[str]) -> Dict[str, List[int]]:
    """kind name -> faithful layer indices, in first-appearance order."""
    out: Dict[str, List[int]] = {}
    for i, k in enumerate(kinds):
        out.setdefault(k, []).append(i)
    return out


def init_stack(key, cfg: ArchConfig, kinds: Sequence[str],
               specs: Dict[str, KindSpec]):
    """Returns {kind: stacked_params} with leading axis = #layers of kind."""
    layout = group_layout(kinds)
    params = {}
    keys = jax.random.split(key, len(kinds))
    for kname, idxs in layout.items():
        spec = specs[kname]
        per_layer = [spec.init(keys[i], cfg) for i in idxs]
        params[kname] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    return params


def _remat_group_size(n_layers: int) -> int:
    """Two-level (sqrt) remat group size: the outer scan saves one carry
    per *group*; backward recomputes each group with per-layer remat. Cuts
    persistent activation memory from O(L) to O(sqrt(L)) carries at the cost
    of one extra forward recompute per layer (126-layer llama3: 17 GB -> ~2
    GB of saved carries per device)."""
    import math
    g = max(1, int(round(math.sqrt(n_layers))))
    while n_layers % g:
        g -= 1
    return g


def _scan_group(spec: KindSpec, stacked, x, aux, cfg, mode: str,
                cache=None, pos=None, remat: bool = True):
    """Run one kind group. mode in {train, prefill, decode, decode_paged}."""
    if mode == "train":
        def body(carry, p):
            h, aloss = carry
            h = _L.constrain(h, cfg)
            if cfg.shard_acts:
                from repro.launch import sharding as _sh
                p = jax.tree_util.tree_map_with_path(
                    lambda pa, a: jax.lax.with_sharding_constraint(
                        a, _sh.leaf_pin_spec(_sh._path_str(pa), a.shape,
                                             cfg)), p)
            h, al = spec.train(p, h, aux, cfg)
            return (h, aloss + al), None

        n_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        G = _remat_group_size(n_layers) if remat else 1
        if remat and G > 1:
            inner = jax.checkpoint(body)

            @jax.checkpoint
            def group_body(carry, pg):
                return jax.lax.scan(inner, carry, pg)

            grouped_params = jax.tree.map(
                lambda a: a.reshape((n_layers // G, G) + a.shape[1:]),
                stacked)
            (x, aloss), _ = jax.lax.scan(group_body,
                                         (x, jnp.float32(0.0)),
                                         grouped_params)
            return x, aloss
        body = jax.checkpoint(body) if remat else body
        (x, aloss), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), stacked)
        return x, aloss
    if mode == "prefill":
        def body(h, p):
            h, cache_l = spec.prefill(p, h, aux, cfg)
            return h, cache_l
        x, cache_stack = jax.lax.scan(body, x, stacked)
        return x, cache_stack
    if mode == "decode":
        def body(h, pc):
            p, cache_l = pc
            h, new_cache = spec.decode(p, h, cache_l, pos, aux, cfg)
            return h, new_cache
        x, new_cache = jax.lax.scan(body, x, (stacked, cache))
        return x, new_cache
    # decode_paged: the stacked pool is loop state, and each layer updates
    # its member in place; as scan xs/ys it would be copied every layer
    step = _paged_step(spec)

    def body(carry, pm):
        h, pool = carry
        p, m = pm
        return step(p, h, pool, m, pos, aux, cfg), None
    n = cache["layer_id"].shape[0]
    (x, pool), _ = jax.lax.scan(body, (x, cache),
                                (stacked, jnp.arange(n, dtype=jnp.int32)))
    return x, pool


def _paged_step(spec: KindSpec):
    if spec.decode_paged is None:
        raise ValueError(f"kind {spec.name!r} has no paged decode path")
    return spec.decode_paged


def apply_stack(params, x, aux, cfg: ArchConfig, kinds: Sequence[str],
                specs: Dict[str, KindSpec], *, mode: str, grouped: bool,
                cache=None, pos=None, remat: bool = True):
    """Run the whole stack.

    Returns:
      train:   (x, aux_loss)
      prefill: (x, cache)      cache = {kind: stacked cache}
      decode:  (x, new_cache)
    """
    layout = group_layout(kinds)
    if grouped:
        aux_acc = jnp.float32(0.0)
        out_cache = {}
        for kname in layout:
            spec = specs[kname]
            if mode == "train":
                x, al = _scan_group(spec, params[kname], x, aux, cfg, mode,
                                    remat=remat)
                aux_acc = aux_acc + al
            elif mode == "prefill":
                x, c = _scan_group(spec, params[kname], x, aux, cfg, mode)
                out_cache[kname] = c
            else:
                x, c = _scan_group(spec, params[kname], x, aux, cfg, mode,
                                   cache=cache[kname], pos=pos)
                out_cache[kname] = c
        if mode == "train":
            return x, aux_acc
        return x, out_cache
    # faithful interleaved order: unroll, slicing layer params from groups
    group_pos = {k: 0 for k in layout}
    aux_acc = jnp.float32(0.0)
    caches: Dict[str, list] = {k: [] for k in layout}
    pools = dict(cache) if mode == "decode_paged" else None
    for kname in kinds:
        i = group_pos[kname]
        group_pos[kname] += 1
        spec = specs[kname]
        p = jax.tree.map(lambda a: a[i], params[kname])
        if mode == "train":
            x, al = spec.train(p, x, aux, cfg)
            aux_acc = aux_acc + al
        elif mode == "prefill":
            x, c = spec.prefill(p, x, aux, cfg)
            caches[kname].append(c)
        elif mode == "decode_paged":
            x, pools[kname] = _paged_step(spec)(p, x, pools[kname], i, pos,
                                                aux, cfg)
        else:
            cache_l = jax.tree.map(lambda a, i=i: a[i], cache[kname])
            x, c = spec.decode(p, x, cache_l, pos, aux, cfg)
            caches[kname].append(c)
    if mode == "train":
        return x, aux_acc
    if mode == "decode_paged":
        return x, pools
    out_cache = {k: jax.tree.map(lambda *xs: jnp.stack(xs), *v)
                 for k, v in caches.items() if v}
    return x, out_cache


def init_cache(cfg: ArchConfig, kinds: Sequence[str],
               specs: Dict[str, KindSpec], batch: int, max_len: int):
    """{kind: stacked empty cache} matching apply_stack decode layout."""
    layout = group_layout(kinds)
    out = {}
    for kname, idxs in layout.items():
        c = specs[kname].init_cache(cfg, batch, max_len)
        out[kname] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (len(idxs),) + a.shape).copy(), c)
    return out


def init_paged(cfg: ArchConfig, kinds: Sequence[str],
               specs: Dict[str, KindSpec], n_slots: int):
    """{kind: stacked slot pool} for the paged serving path (DESIGN.md §18).

    Each kind's pool carries a ``"layer_id"`` leaf — the faithful layer
    index of every group member. The grouped decode carries the pool
    through its scan and reads ``layer_id`` at the member index, so
    per-layer data (which collective site's drop masks apply) rides inside
    it: ``aux`` is closed over by the scan body and cannot vary per layer.
    """
    layout = group_layout(kinds)
    out = {}
    for kname, idxs in layout.items():
        spec = specs[kname]
        if spec.paged_spec is None:
            raise ValueError(f"kind {kname!r} has no paged cache spec")
        c = spec.paged_spec(cfg, n_slots)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (len(idxs),) + a.shape).copy(), c)
        stacked["layer_id"] = jnp.asarray(idxs, jnp.int32)
        out[kname] = stacked
    return out
