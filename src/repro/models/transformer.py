"""Dense decoder-only transformer kinds: GQA + RoPE, optional sliding window,
optional MoE FFN. Covers starcoder2 / gemma3 / deepseek / llama3 / mistral
(llava backbone) / mixtral / kimi.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.stack import KindSpec


def _win(kind_name: str) -> Optional[int]:
    """Kind names encode the static window: 'attn', 'attn@4096', 'moe_attn@…'."""
    if "@" not in kind_name:
        return None
    return int(kind_name.split("@", 1)[1])


def _is_moe(kind_name: str) -> bool:
    return kind_name.startswith("moe_attn")


def make_dense_kind(kind_name: str) -> KindSpec:
    window = _win(kind_name)
    moe = _is_moe(kind_name)

    def init(key, cfg: ArchConfig):
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": jnp.zeros((cfg.d_model,), cfg.jnp_dtype),
            "ln2": jnp.zeros((cfg.d_model,), cfg.jnp_dtype),
            "attn": L.init_attention(k1, cfg),
        }
        p["moe" if moe else "mlp"] = (L.init_moe(k2, cfg) if moe
                                      else L.init_mlp(k2, cfg))
        return p

    def _ffn(p, x, cfg):
        if moe:
            out, aux = L.moe(p["moe"], L.rms_norm(x, p["ln2"]), cfg)
            return x + out, 0.01 * aux
        return x + L.mlp(p["mlp"], L.rms_norm(x, p["ln2"])), jnp.float32(0.0)

    def train(p, x, aux, cfg: ArchConfig):
        h, _ = L.attention_fwd(p["attn"], L.rms_norm(x, p["ln1"]), cfg=cfg,
                               window=window, blocked=True)
        x = x + h
        return _ffn(p, x, cfg)

    def prefill(p, x, aux, cfg: ArchConfig):
        h, (k, v) = L.attention_fwd(p["attn"], L.rms_norm(x, p["ln1"]),
                                    cfg=cfg, window=window, blocked=True)
        x = x + h
        x, _ = _ffn(p, x, cfg)
        if aux.get("paged_prefill"):
            # paged cache: keep every position untrimmed/unpadded — the
            # engine scatters rows [0, S) into the request's slots and the
            # decode mask applies any window over absolute positions
            return x, {"k": k, "v": v}
        if window is not None:                    # ring buffer: keep last w
            k, v = k[:, -window:], v[:, -window:]
        else:
            # grow to decode capacity: later writes land at slot == position
            cap = aux.get("max_len")
            if cap is not None and cap > k.shape[1]:
                padw = ((0, 0), (0, cap - k.shape[1]), (0, 0), (0, 0))
                k, v = jnp.pad(k, padw), jnp.pad(v, padw)
        return x, {"k": k, "v": v}

    def decode(p, x, cache_l, pos, aux, cfg: ArchConfig):
        h, kc, vc = L.attention_decode(p["attn"], L.rms_norm(x, p["ln1"]),
                                       cache_l["k"], cache_l["v"], pos,
                                       cfg=cfg, window=window,
                                       ring=window is not None)
        x = x + h
        x, _ = _ffn(p, x, cfg)
        return x, {"k": kc, "v": vc}

    def decode_paged(p, x, pool, m, pos, aux, cfg: ArchConfig):
        pg = aux["paged"]
        tp = pg.get("tp")
        li = pool["layer_id"][m]
        h, kc, vc = L.attention_decode_paged(
            p["attn"], L.rms_norm(x, p["ln1"]), pool["k"], pool["v"], m,
            pos, bt=pg["bt"], page=pg["page"], cfg=cfg, window=window,
            tp=tp, tp_masks=pg.get("masks"), site=2 * li, key=pg.get("key"))
        x = x + h
        if tp is None or moe:
            # MoE FFN keeps the dense expert path: expert dispatch is an
            # all-to-all, not an RS+AG — its loss semantics land with the
            # expert-parallel leg (ROADMAP item 2)
            x, _ = _ffn(p, x, cfg)
        else:
            out = tp.combine_mlp(p["mlp"], L.rms_norm(x, p["ln2"]),
                                 pg.get("masks"), 2 * li + 1, pg.get("key"))
            x = x + out
        return x, {**pool, "k": kc, "v": vc}

    def cache_spec(cfg: ArchConfig, batch: int, max_len: int):
        C = min(window, max_len) if window is not None else max_len
        shape = (batch, C, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shape, cfg.jnp_dtype),
                "v": jnp.zeros(shape, cfg.jnp_dtype)}

    def paged_spec(cfg: ArchConfig, n_slots: int):
        shape = (n_slots, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shape, cfg.jnp_dtype),
                "v": jnp.zeros(shape, cfg.jnp_dtype)}

    return KindSpec(kind_name, init, train, prefill, decode, cache_spec,
                    decode_paged=decode_paged, paged_spec=paged_spec)


def dense_kind_sequence(cfg: ArchConfig) -> list[str]:
    """Per-layer kind names in faithful order."""
    base = "moe_attn" if cfg.is_moe else "attn"
    kinds = []
    for i in range(cfg.n_layers):
        w = cfg.window
        if cfg.global_every is not None and (i + 1) % cfg.global_every == 0:
            w = None                               # global (full-attention) layer
        kinds.append(f"{base}@{w}" if w is not None else base)
    return kinds
