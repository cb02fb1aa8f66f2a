"""Seeded weights of a dense decoder (llama layout), made on the device.

Every tensor is drawn from the run's seed and its own name, so the program
and the reference see the same bits without sharing an array: the
program's whole tree is made in one jitted call (``program_params``), and
the reference makes one layer at a time (``layer``, ``embed``). Scales
follow the usual fan-in rule (uniform draws of that standard deviation);
the norm weights start at 1 (stored as the offset from 1, 0, which is how
the program parametrises them).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "wi", "wg", "w2")


def dims(hf: dict) -> dict:
    d = hf["hidden_size"]
    h = hf["num_attention_heads"]
    return {"d": d, "h": h, "kv": hf["num_key_value_heads"],
            "hd": hf.get("head_dim") or d // h,
            "ff": hf["intermediate_size"], "V": hf["vocab_size"],
            "L": hf["num_hidden_layers"]}


def stored_dtype(hf: dict):
    return jnp.dtype(hf["torch_dtype"])


def _shape_scale(name: str, z: dict):
    d, h, kv, hd, ff, V = (z[k] for k in ("d", "h", "kv", "hd", "ff", "V"))
    return {"tok": ((V, d), 1.0), "head": ((d, V), d ** -0.5),
            "final_norm": ((d,), 0.0),
            "ln1": ((d,), 0.0), "ln2": ((d,), 0.0),
            "wq": ((d, h, hd), d ** -0.5), "wk": ((d, kv, hd), d ** -0.5),
            "wv": ((d, kv, hd), d ** -0.5),
            "wo": ((h, hd, d), (h * hd) ** -0.5),
            "wi": ((d, ff), d ** -0.5), "wg": ((d, ff), d ** -0.5),
            "w2": ((ff, d), ff ** -0.5)}[name]


def tensor(key, name: str, z: dict, dtype, layer=0):
    """One named tensor of one layer (``layer`` may be traced), uniform
    with standard deviation ``scale`` (zeros where the scale is 0), stored
    in ``dtype``.

    Drawn from 24-bit integers with exact arithmetic and one rounded
    multiply, so that every compiled program that makes it gets the same
    bits: a normal draw goes through transcendental approximations whose
    last f32 bit depends on how XLA fuses them, which flips the stored
    bf16 value of some elements between two programs."""
    shape, scale = _shape_scale(name, z)
    if scale == 0.0:
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer)
    u = (jax.random.bits(k, shape, jnp.uint32) >> 8).astype(jnp.int32)
    centred = (u - (1 << 23)).astype(jnp.float32) + 0.5   # exact
    return (centred * (scale * 12 ** 0.5 / (1 << 24))).astype(dtype)


def root_key(seed31: int):
    return jax.random.PRNGKey(seed31)


def layer(key, hf: dict, l) -> dict:
    """Layer ``l``'s tensors (``l`` may be traced), as stored."""
    z, st = dims(hf), stored_dtype(hf)
    return {n: tensor(key, n, z, st, l) for n in LAYER_KEYS}


def embed(key, hf: dict) -> dict:
    z, st = dims(hf), stored_dtype(hf)
    return {n: tensor(key, n, z, st) for n in ("tok", "head", "final_norm")}


#: the program's leaf path (below the layer group) -> our tensor name
PROGRAM_LAYER = {("ln1",): "ln1", ("ln2",): "ln2",
                  ("attn", "wq"): "wq", ("attn", "wk"): "wk",
                  ("attn", "wv"): "wv", ("attn", "wo"): "wo",
                  ("mlp", "wi"): "wi", ("mlp", "wg"): "wg",
                  ("mlp", "wo"): "w2"}


def path_of(p) -> tuple:
    return tuple(getattr(k, "key", getattr(k, "idx", k)) for k in p)


def program_params(key, hf: dict, shapes):
    """The program's parameter tree (``shapes`` is its ``eval_shape``)
    filled with the named tensors. Call under ``jax.jit``. Raises on a leaf it does
    not know, so a change of the program's layout cannot pass unseen."""
    z = dims(hf)
    st = stored_dtype(hf)

    def leaf(p, s):
        path = path_of(p)
        if path[0] == "embed" and len(path) == 2:
            x = tensor(key, path[1], z, st)
        elif path[0] == "layers" and path[2:] in PROGRAM_LAYER:
            if path[1] != "attn":
                raise ValueError(f"layer kind {path[1]!r} is not a plain "
                                 f"dense attention layer")
            name = PROGRAM_LAYER[path[2:]]
            x = jnp.stack([tensor(key, name, z, st, l)
                           for l in range(z["L"])])
        else:
            raise ValueError(f"unknown parameter {path}")
        if x.shape != tuple(s.shape) or x.dtype != s.dtype:
            raise ValueError(f"{path}: made {x.shape} {x.dtype}, the "
                             f"program has {s.shape} {s.dtype}")
        return x

    return jax.tree_util.tree_map_with_path(leaf, shapes)
