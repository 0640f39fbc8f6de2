"""Operations and bytes that a dense transformer's serving steps need.

These count the work of the algorithm, not of an implementation: a decode
step reads the weights once and each real row's KV at the row's own length,
and writes one token's KV per row.  The model FLOPs follow
``analysis/roofline.py``: 2 per parameter per token, plus the attention
term (QK^T and PV) over the positions a token attends to.
"""
from __future__ import annotations


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def params(m: dict) -> int:
    """Parameters, the tied embedding counted once (it is the output head)."""
    d, f, L, V = m["d_model"], m["d_ff"], m["num_layers"], m["vocab_size"]
    H, K, hd = m["num_heads"], m["num_kv_heads"], _hd(m)
    mlp = (3 if m.get("mlp_type", "swiglu") in ("swiglu", "geglu") else 2) * d * f
    emb = V * d * (1 if m.get("tie_embeddings", False) else 2)
    return emb + L * (2 * d * H * hd + 2 * d * K * hd + mlp)


def kv_bytes_per_token(m: dict, itemsize: int) -> int:
    return 2 * m["num_layers"] * m["num_kv_heads"] * _hd(m) * itemsize


def decode(m: dict, lengths, itemsize: int) -> "tuple[float, float]":
    """One decode step over real rows whose resident lengths are ``lengths``
    (tokens before this step's): (FLOPs, bytes)."""
    n, L, H, hd = params(m), m["num_layers"], m["num_heads"], _hd(m)
    kv = kv_bytes_per_token(m, itemsize)
    flops = sum(2.0 * n + 4.0 * L * H * hd * (int(t) + 1) for t in lengths)
    nbytes = n * itemsize + sum(kv * (int(t) + 1) for t in lengths)
    return flops, float(nbytes)


def prefill(m: dict, tokens: int, itemsize: int) -> "tuple[float, float]":
    """One prompt of ``tokens`` tokens, causal: (FLOPs, bytes)."""
    n, L, H, hd = params(m), m["num_layers"], m["num_heads"], _hd(m)
    T = int(tokens)
    flops = 2.0 * n * T + 2.0 * L * H * hd * T * T
    return flops, float(n * itemsize + kv_bytes_per_token(m, itemsize) * T)


def kv_write_bytes(m: dict, tokens: int, itemsize: int) -> float:
    """Bytes a prompt's KV takes when paged into the pool."""
    return float(kv_bytes_per_token(m, itemsize) * int(tokens))
