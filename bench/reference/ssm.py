"""Plain reference of an attention-free Mamba-2 language model, and the
seeded weights the benchmark serves.

``forward`` follows the Mamba-2 paper (arXiv:2405.21060) in straightforward
``jax.numpy``: pre-RMSNorm blocks; input projections to z, xBC and dt; a
causal depthwise convolution over xBC with SiLU; the SSD layer in its
quadratic ("attention") form, ``y_t = sum_{s<=t} (C_t . B_s)
exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t``, which is exact and shares
nothing with the chunked scan or the one-token recurrence the engine runs;
the gated RMSNorm ``norm(y * silu(z))``; the output projection; tied
embeddings.

``init`` makes the weights from a key, in the parameter layout that the
served engine takes, on the device, in one jitted call.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _dims(m: dict):
    s = m["ssm"]
    di = s["expand"] * m["d_model"]
    return s, di, di // s["head_dim"], s["head_dim"], s["n_groups"], s["d_state"]


def init(m: dict, key, dtype=F32) -> dict:
    if not m.get("tie_embeddings", False):
        raise NotImplementedError("only tied embeddings are configured")
    s, di, H, P, G, N = _dims(m)
    d, L, V, W = m["d_model"], m["num_layers"], m["vocab_size"], s["d_conv"]
    conv = di + 2 * G * N
    ks = jax.random.split(key, 9)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32) / math.sqrt(fan_in)).astype(dtype)

    a = jax.random.uniform(ks[6], (L, H), F32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[7], (L, H), F32, math.log(1e-3), math.log(1e-1)))
    return {
        "embed": {"table": (0.02 * jax.random.normal(ks[0], (V, d), F32)).astype(dtype)},
        "layers": {
            "ln": {"scale": jnp.ones((L, d), dtype)},
            "ssm": {
                "w_z": w(ks[1], (L, d, di), d),
                "w_xbc": w(ks[2], (L, d, conv), d),
                "w_dt": w(ks[3], (L, d, H), d),
                "conv_w": w(ks[4], (L, W, conv), W),
                "conv_b": jnp.zeros((L, conv), dtype),
                "A_log": jnp.log(a),
                "D": jnp.ones((L, H), F32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) = dt
                "norm_scale": jnp.ones((L, di), dtype),
                "w_out": w(ks[5], (L, di, d), di),
            },
        },
        "final_norm": {"scale": jnp.ones((d,), dtype)},
    }


def _rms(x, scale, eps):
    xf = x.astype(F32)
    return (xf / jnp.sqrt((xf * xf).mean(-1, keepdims=True) + eps) * scale.astype(F32)).astype(x.dtype)


def forward(m: dict, params: dict, tokens) -> jax.Array:
    """tokens (T,) int32 -> logits (T, V) float32, teacher-forced."""
    s, di, H, P, G, N = _dims(m)
    if G != 1:
        raise NotImplementedError("one B/C group is configured")
    W, eps = s["d_conv"], m["norm_eps"]
    T = tokens.shape[0]
    t = jnp.arange(T)
    causal = (t[None, :] <= t[:, None])[:, :, None]       # (t, s, 1)
    x = params["embed"]["table"][tokens]

    def block(x, lp):
        p = lp["ssm"]
        h = _rms(x, lp["ln"]["scale"], eps)
        z = h @ p["w_z"]
        xbc = h @ p["w_xbc"]
        dt = jax.nn.softplus((h @ p["w_dt"]).astype(F32) + p["dt_bias"])   # (T, H)
        pad = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), xbc.dtype), xbc])
        conv = sum(pad[i:i + T] * p["conv_w"][i] for i in range(W)) + p["conv_b"]
        xbc = jax.nn.silu(conv)
        xs = xbc[:, :di].reshape(T, H, P).astype(F32)
        Bm = xbc[:, di:di + N].astype(F32)
        Cm = xbc[:, di + N:].astype(F32)
        cum = jnp.cumsum(dt * -jnp.exp(p["A_log"]), axis=0)                 # (T, H)
        seg = jnp.where(causal, cum[:, None, :] - cum[None, :, :], -jnp.inf)
        mix = (Cm @ Bm.T)[:, :, None] * jnp.exp(seg) * dt[None, :, :]       # (t, s, H)
        y = jnp.einsum("tsh,shp->thp", mix.astype(x.dtype), xs.astype(x.dtype)).astype(F32)
        y = (y + xs * p["D"][None, :, None]).reshape(T, di).astype(x.dtype)
        y = _rms(y * jax.nn.silu(z), p["norm_scale"], eps)
        return x + y @ p["w_out"], None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return jnp.einsum("td,vd->tv", x, params["embed"]["table"], preferred_element_type=F32)
