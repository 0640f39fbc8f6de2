"""Plain reference of a dense decoder-only transformer (OLMo-1B style), and
the seeded weights the benchmark serves.

``forward`` follows the published architecture in straightforward
``jax.numpy``: pre-norm blocks, rotary positions (rotate-half pairs,
frequencies ``theta ** (-2i / head_dim)``), causal multi-head attention,
SwiGLU feed-forward, tied embeddings.  It knows nothing of pages, batches or
caches.  Every width comes from the configuration file's ``model`` object.

``init`` makes the weights from a key, in the parameter layout that the
served engine takes (``embed.table``, stacked ``layers.{attn,mlp}``), on the
device, in one jitted call.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def init(m: dict, key, dtype=F32) -> dict:
    if not m.get("tie_embeddings", False):
        raise NotImplementedError("only tied embeddings are configured")
    d, f, L, V = m["d_model"], m["d_ff"], m["num_layers"], m["vocab_size"]
    H, K, hd = m["num_heads"], m["num_kv_heads"], _hd(m)
    ks = jax.random.split(key, 8)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32) / math.sqrt(fan_in)).astype(dtype)

    norm = {} if m["norm_type"] == "nonparam_layernorm" else {"scale": jnp.ones((L, d), dtype)}
    return {
        "embed": {"table": (0.02 * jax.random.normal(ks[0], (V, d), F32)).astype(dtype)},
        "layers": {
            "ln1": dict(norm),
            "attn": {"wq": w(ks[1], (L, d, H * hd), d), "wk": w(ks[2], (L, d, K * hd), d),
                     "wv": w(ks[3], (L, d, K * hd), d), "wo": w(ks[4], (L, H * hd, d), H * hd)},
            "ln2": dict(norm),
            "mlp": {"wi_gate": w(ks[5], (L, d, f), d), "wi_up": w(ks[6], (L, d, f), d),
                    "wo": w(ks[7], (L, f, d), f)},
        },
        "final_norm": {} if m["norm_type"] == "nonparam_layernorm" else {"scale": jnp.ones((d,), dtype)},
    }


def _norm(m, x, p):
    xf = x.astype(F32)
    if m["norm_type"] == "nonparam_layernorm":
        mu = xf.mean(-1, keepdims=True)
        y = (xf - mu) / jnp.sqrt(((xf - mu) ** 2).mean(-1, keepdims=True) + m["norm_eps"])
    elif m["norm_type"] == "rmsnorm":
        y = xf / jnp.sqrt((xf * xf).mean(-1, keepdims=True) + m["norm_eps"]) * p["scale"].astype(F32)
    else:
        raise NotImplementedError(m["norm_type"])
    return y.astype(x.dtype)


def _rope(x, pos, theta):
    """x: (T, H, hd); rotate-half pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2].astype(F32), x[..., hd // 2:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


def forward(m: dict, params: dict, tokens) -> jax.Array:
    """tokens (T,) int32 -> logits (T, V) float32, causal, teacher-forced."""
    H, K, hd = m["num_heads"], m["num_kv_heads"], _hd(m)
    T = tokens.shape[0]
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]
    x = params["embed"]["table"][tokens]

    def block(x, lp):
        h = _norm(m, x, lp["ln1"])
        q = (h @ lp["attn"]["wq"]).reshape(T, H, hd)
        k = (h @ lp["attn"]["wk"]).reshape(T, K, hd)
        v = (h @ lp["attn"]["wv"]).reshape(T, K, hd)
        if m.get("rope_type", "rope") == "rope":
            q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k).astype(F32) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1).astype(x.dtype)
        o = jnp.einsum("hts,shd->thd", p, v).reshape(T, H * hd)
        x = x + o @ lp["attn"]["wo"]
        h = _norm(m, x, lp["ln2"])
        g = h @ lp["mlp"]["wi_gate"]
        x = x + (jax.nn.silu(g) * (h @ lp["mlp"]["wi_up"])) @ lp["mlp"]["wo"]
        return x, None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = _norm(m, x, params["final_norm"])
    return jnp.einsum("td,vd->tv", x, params["embed"]["table"], preferred_element_type=F32)
