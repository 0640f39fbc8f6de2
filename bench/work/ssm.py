"""Operations and bytes that a Mamba-2 model's serving steps need.

A decode step reads the weights once and each real row's recurrent state
(SSM state and conv window), and writes the state back.  The model FLOPs
follow ``analysis/roofline.py``: 2 per parameter per token, plus the SSD
term of 4 x heads x d_state x head_dim per token and layer (the state
update and the read-out of the one-token recurrence).
"""
from __future__ import annotations


def _dims(m: dict):
    s = m["ssm"]
    di = s["expand"] * m["d_model"]
    return s, di, di // s["head_dim"], s["head_dim"], s["n_groups"], s["d_state"]


def params(m: dict) -> int:
    s, di, H, P, G, N = _dims(m)
    d, L, V = m["d_model"], m["num_layers"], m["vocab_size"]
    conv = di + 2 * G * N
    per_layer = (d                                   # pre-norm scale
                 + d * (di + conv + H)               # z, xBC, dt projections
                 + s["d_conv"] * conv + conv         # depthwise conv and bias
                 + 3 * H + di                        # A_log, D, dt_bias, gate norm
                 + di * d)                           # output projection
    return V * d * (1 if m.get("tie_embeddings", False) else 2) + L * per_layer + d


def state_bytes(m: dict, itemsize: int) -> int:
    """One sequence's resident state: SSM state and conv window, all layers."""
    s, di, H, P, G, N = _dims(m)
    conv = di + 2 * G * N
    return m["num_layers"] * (H * N * P + (s["d_conv"] - 1) * conv) * itemsize


def _ssd(m: dict) -> float:
    s, di, H, P, G, N = _dims(m)
    return 4.0 * H * N * P * m["num_layers"]


def decode(m: dict, lengths, itemsize: int) -> "tuple[float, float]":
    rows = len(lengths)
    flops = rows * (2.0 * params(m) + _ssd(m))
    return flops, float(params(m) * itemsize + rows * 2 * state_bytes(m, itemsize))


def prefill(m: dict, tokens: int, itemsize: int) -> "tuple[float, float]":
    T = int(tokens)
    flops = T * (2.0 * params(m) + _ssd(m))
    return flops, float(params(m) * itemsize + state_bytes(m, itemsize))


def kv_write_bytes(m: dict, tokens: int, itemsize: int) -> float:
    return 0.0
