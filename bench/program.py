"""What the benchmark takes from the program: its configuration type, built
from a configuration file, and small helpers shared by the drivers."""
from __future__ import annotations



def arch_config(hf: dict):
    """The program's ``ArchConfig`` of a dense decoder configuration file
    (Hugging Face key names)."""
    from repro.configs.base import ArchConfig
    d = hf["hidden_size"]
    h = hf["num_attention_heads"]
    return ArchConfig(
        name=hf["name"], family="dense", n_layers=hf["num_hidden_layers"],
        d_model=d, n_heads=h, n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        head_dim=hf.get("head_dim") or d // h,
        window=hf.get("sliding_window"), rope_theta=float(hf["rope_theta"]),
        dtype=hf["torch_dtype"], max_seq=hf["max_position_embeddings"])


def matmul_params(hf: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and the untied head (the embedding lookup is a
    gather)."""
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    kv, ff = hf["num_key_value_heads"], hf["intermediate_size"]
    hd = hf.get("head_dim") or d // h
    layer = d * h * hd * 2 + 2 * d * kv * hd + 3 * d * ff
    return hf["num_hidden_layers"] * layer + d * hf["vocab_size"]


def attn_flops_fwd(hf: dict, q_len: int, ctx_len: int) -> float:
    """Forward FLOPs of causal attention scores and values for ``q_len``
    queries over ``ctx_len`` keys, in every layer (causal: a query at
    position i sees i + 1 keys)."""
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    hd = hf.get("head_dim") or d // h
    if q_len == ctx_len:
        pairs = q_len * (q_len + 1) / 2
    else:
        pairs = q_len * ctx_len
    return 4.0 * pairs * h * hd * hf["num_hidden_layers"]


def peak_bytes(devices) -> int:
    """The peak of device memory in use on the fullest chip."""
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best
