"""Shared model building blocks (pure JAX, functional).

Conventions
-----------
* Activations are ``(batch, seq, ...)``; params are plain dicts of arrays.
* Matmuls accumulate in fp32 (``preferred_element_type``), softmax in fp32.
* ``constrain`` tags logical shardings; no-ops outside a rules context.
* Attention never materializes the full (Sq, Skv) score matrix for long
  sequences: queries are processed in blocks via ``lax.scan`` (exact, not
  online-softmax — each block sees all keys).  The Pallas flash kernel
  (``repro.kernels.flash_attention``) is the TPU-target replacement; the
  chunked path is the XLA-lowerable baseline used by the dry-run.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distribution.sharding import constrain

F32 = jnp.float32
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def ninit(key, shape, scale: "float | None" = None, dtype=jnp.float32):
    """Truncated-normal init, fan-in scaled by default."""
    if scale is None:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps: float):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def layernorm(x, w, b, eps: float):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(F32)
    if b is not None:
        y = y + b.astype(F32)
    return y.astype(x.dtype)


def apply_norm(cfg, x, p):
    """Dispatch on cfg.norm_type; ``p`` is the layer's norm param dict."""
    if cfg.norm_type == "rmsnorm":
        return rmsnorm(x, p["scale"], cfg.norm_eps)
    if cfg.norm_type == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    if cfg.norm_type == "layernorm_nobias":
        return layernorm(x, p["scale"], None, cfg.norm_eps)
    if cfg.norm_type == "nonparam_layernorm":  # olmo
        return layernorm(x, None, None, cfg.norm_eps)
    raise ValueError(cfg.norm_type)


def init_norm(cfg, key, dtype):
    if cfg.norm_type == "rmsnorm" or cfg.norm_type == "layernorm_nobias":
        return {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype), "bias": jnp.zeros((cfg.d_model,), dtype)}
    return {}  # nonparam


def norm_specs(cfg):
    if cfg.norm_type in ("rmsnorm", "layernorm_nobias"):
        return {"scale": ("p_none",)}
    if cfg.norm_type == "layernorm":
        return {"scale": ("p_none",), "bias": ("p_none",)}
    return {}


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE / partial RoPE / M-RoPE)
# ---------------------------------------------------------------------------

def rope_angles(positions, rotary_dim: int, theta: float, sections=()):
    """positions: (B, S) int — or (3, B, S) for M-RoPE (t, h, w streams).

    Returns (cos, sin) of shape (B, S, rotary_dim) using the rotate-half
    convention (angles duplicated across the two halves).
    """
    half = rotary_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    if sections:
        # M-RoPE: head-dim frequency bands split between t/h/w position ids
        assert positions.ndim == 3, "mrope needs (3, B, S) positions"
        assert sum(sections) == half, (sections, half)
        parts = []
        start = 0
        for i, sec in enumerate(sections):
            f = positions[i].astype(F32)[..., None] * inv_freq[start : start + sec]
            parts.append(f)
            start += sec
        freqs = jnp.concatenate(parts, axis=-1)  # (B, S, half)
    else:
        freqs = positions.astype(F32)[..., None] * inv_freq  # (B, S, half)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x, cos, sin):
    """x: (B, S, H, D_rot_or_more); rotates the first cos.shape[-1] dims."""
    rot = cos.shape[-1]
    xr, xp = x[..., :rot], x[..., rot:]
    c = cos[:, :, None, :].astype(F32)
    s = sin[:, :, None, :].astype(F32)
    xf = xr.astype(F32)
    out = (xf * c + _rotate_half(xf) * s).astype(x.dtype)
    if xp.shape[-1]:
        out = jnp.concatenate([out, xp], axis=-1)
    return out


# ---------------------------------------------------------------------------
# attention core
# ---------------------------------------------------------------------------

def _block_attend(q, k, v, qpos, kpos, *, causal, window, softcap, valid_len=None):
    """q: (B, Sq, K, R, D); k/v: (B, Skv, K, D); qpos: (Sq,); kpos: (Skv,).

    Returns (B, Sq, K, R, D). Scores/softmax in fp32.  ``valid_len`` may be
    a scalar (one cache fill level for the whole batch) or a ``(B,)`` array
    (ragged paged decode: each row attends over its own prefix).  The
    scalar path's op sequence is unchanged by the batched branch.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqkrd,bskd->bkrqs", q, k, preferred_element_type=F32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    if valid_len is not None and getattr(valid_len, "ndim", 0) == 1:
        mask_b = mask[None] & (kpos[None, None, :] < valid_len[:, None, None])
        s = jnp.where(mask_b[:, None, None], s, NEG_INF)
    else:
        if valid_len is not None:
            mask &= kpos[None, :] < valid_len
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkrqs,bskd->bqkrd", w, v)


def attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: "Optional[int]" = None,
    q_offset=0,
    softcap: "Optional[float]" = None,
    q_block: "Optional[int]" = None,
    valid_len=None,
    kpos=None,
):
    """GQA attention. q: (B, Sq, H, D); k/v: (B, Skv, K, D); H % K == 0.

    ``q_block``: process queries in blocks of this size via lax.scan so the
    peak score tensor is (B, H, q_block, Skv) — required for 32k+ prefill.
    ``valid_len``: number of valid cache slots (decode) — scalar, or a
    ``(B,)`` array for ragged per-row prefixes (paged decode); ``kpos``:
    explicit key positions (defaults to arange(Skv)).
    """
    B, Sq, H, D = q.shape
    K = k.shape[2]
    R = H // K
    qr = q.reshape(B, Sq, K, R, D)
    if kpos is None:
        kpos = jnp.arange(k.shape[1])
    qpos_all = q_offset + jnp.arange(Sq)

    if q_block is None or Sq <= q_block:
        o = _block_attend(
            qr, k, v, qpos_all, kpos, causal=causal, window=window, softcap=softcap, valid_len=valid_len
        )
        return o.reshape(B, Sq, H, D)

    pad = (-Sq) % q_block
    if pad:  # tail-pad queries (outputs sliced off below; keys unaffected)
        qr = jnp.pad(qr, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        qpos_all = jnp.concatenate([qpos_all, qpos_all[-1] + 1 + jnp.arange(pad)])
    Sp = Sq + pad
    nb = Sp // q_block
    qs = qr.reshape(B, nb, q_block, K, R, D).swapaxes(0, 1)  # (nb, B, qb, K, R, D)
    ps = qpos_all.reshape(nb, q_block)

    def step(_, xs):
        qb, pb = xs
        o = _block_attend(qb, k, v, pb, kpos, causal=causal, window=window, softcap=softcap, valid_len=valid_len)
        return None, o

    _, os = jax.lax.scan(step, None, (qs, ps))
    return os.swapaxes(0, 1).reshape(B, Sp, H, D)[:, :Sq]


def local_block_attention(q, k, v, *, window: int, q_offset=0):
    """Sliding-window attention in O(S·window): queries in blocks of
    ``window`` attend to their own and the previous key block only.

    Exact for window-limited causal attention when Sq == Skv and
    Sq % window == 0 (pad upstream otherwise).
    """
    B, S, H, D = q.shape
    K = k.shape[2]
    R = H // K
    assert S % window == 0, (S, window)
    nb = S // window
    qr = q.reshape(B, nb, window, K, R, D).swapaxes(0, 1)
    kr = k.reshape(B, nb, window, K, D).swapaxes(0, 1)
    vr = v.reshape(B, nb, window, K, D).swapaxes(0, 1)
    kprev = jnp.concatenate([jnp.zeros_like(kr[:1]), kr[:-1]], axis=0)
    vprev = jnp.concatenate([jnp.zeros_like(vr[:1]), vr[:-1]], axis=0)

    def step(_, xs):
        i, qb, kb, vb, kp, vp = xs
        kk = jnp.concatenate([kp, kb], axis=1)  # (B, 2w, K, D)
        vv = jnp.concatenate([vp, vb], axis=1)
        qpos = i * window + jnp.arange(window)
        kpos = (i - 1) * window + jnp.arange(2 * window)
        o = _block_attend(qb, kk, vv, qpos, kpos, causal=True, window=window, softcap=None)
        return None, o

    idx = jnp.arange(nb)
    _, os = jax.lax.scan(step, None, (idx, qr, kr, vr, kprev, vprev))
    return os.swapaxes(0, 1).reshape(B, S, H, D)


# ---------------------------------------------------------------------------
# attention block (projections + rope + core) and its params
# ---------------------------------------------------------------------------

def init_attn(cfg, key, dtype):
    ks = jax.random.split(key, 4)
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    p = {
        "wq": ninit(ks[0], (d, H * hd), dtype=dtype),
        "wk": ninit(ks[1], (d, K * hd), dtype=dtype),
        "wv": ninit(ks[2], (d, K * hd), dtype=dtype),
        "wo": ninit(ks[3], (H * hd, d), scale=1.0 / math.sqrt(H * hd), dtype=dtype),
    }
    if cfg.attn_qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    if cfg.attn_out_bias:
        p["bo"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def attn_specs(cfg):
    s = {
        "wq": ("p_embed", "p_heads"),
        "wk": ("p_embed", "p_kv_heads"),
        "wv": ("p_embed", "p_kv_heads"),
        "wo": ("p_heads", "p_embed"),
    }
    if cfg.attn_qkv_bias:
        s.update({"bq": ("p_heads",), "bk": ("p_kv_heads",), "bv": ("p_kv_heads",)})
    if cfg.attn_out_bias:
        s["bo"] = ("p_none",)
    return s


def qkv_proj(cfg, p, x):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,K,hd), sharded on heads.

    preferred_element_type follows the activation dtype: the MXU still
    accumulates bf16 inputs in f32 internally, while keeping the *stored*
    value and — critically — the BACKWARD cotangents in bf16 (an
    accumulate-f32-then-cast pattern would upcast the whole backward pass
    to f32 through the astype transpose; §Perf "bf16-cotangent").
    """
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"], preferred_element_type=x.dtype)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"], preferred_element_type=x.dtype)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"], preferred_element_type=x.dtype)
    if cfg.attn_qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q.reshape(B, S, H, hd), "batch", "seq", "heads", "head_dim")
    k = constrain(k.reshape(B, S, K, hd), "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v.reshape(B, S, K, hd), "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def out_proj(cfg, p, o):
    # row-parallel matmul: the contraction dim is TP-sharded, so the output
    # is all-reduced — accumulate in the activation dtype (bf16) so the
    # collective runs at half width (fp32 partial sums would double wire
    # bytes; EXPERIMENTS.md §Perf "bf16-psum").
    B, S = o.shape[:2]
    y = jnp.einsum(
        "bsh,hd->bsd", o.reshape(B, S, -1), p["wo"], preferred_element_type=o.dtype
    )
    if cfg.attn_out_bias:
        y = y + p["bo"]
    return constrain(y, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(cfg, key, dtype, d_ff: "Optional[int]" = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        p = {
            "wi_gate": ninit(ks[0], (d, f), dtype=dtype),
            "wi_up": ninit(ks[1], (d, f), dtype=dtype),
            "wo": ninit(ks[2], (f, d), dtype=dtype),
        }
        if cfg.mlp_bias:
            p["bi_gate"] = jnp.zeros((f,), dtype)
            p["bi_up"] = jnp.zeros((f,), dtype)
            p["bo"] = jnp.zeros((d,), dtype)
        return p
    p = {"wi": ninit(ks[0], (d, f), dtype=dtype), "wo": ninit(ks[2], (f, d), dtype=dtype)}
    if cfg.mlp_bias:
        p["bi"] = jnp.zeros((f,), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    return p


def mlp_specs(cfg):
    if cfg.mlp_type in ("swiglu", "geglu"):
        s = {"wi_gate": ("p_embed", "p_mlp"), "wi_up": ("p_embed", "p_mlp"), "wo": ("p_mlp", "p_embed")}
        if cfg.mlp_bias:
            s.update({"bi_gate": ("p_mlp",), "bi_up": ("p_mlp",), "bo": ("p_none",)})
        return s
    s = {"wi": ("p_embed", "p_mlp"), "wo": ("p_mlp", "p_embed")}
    if cfg.mlp_bias:
        s.update({"bi": ("p_mlp",), "bo": ("p_none",)})
    return s


def mlp(cfg, p, x):
    if cfg.mlp_type in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"], preferred_element_type=x.dtype)
        u = jnp.einsum("bsd,df->bsf", x, p["wi_up"], preferred_element_type=x.dtype)
        if cfg.mlp_bias:
            g, u = g + p["bi_gate"], u + p["bi_up"]
        g = constrain(g, "batch", "seq", "mlp")
        u = constrain(u, "batch", "seq", "mlp")
        act = jax.nn.silu(g) if cfg.mlp_type == "swiglu" else jax.nn.gelu(g)
        h = act * u
    else:
        h = jnp.einsum("bsd,df->bsf", x, p["wi"], preferred_element_type=x.dtype)
        if cfg.mlp_bias:
            h = h + p["bi"]
        h = constrain(h, "batch", "seq", "mlp")
        h = jax.nn.gelu(h)
    # row-parallel: bf16 partial sums -> half-width TP all-reduce
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"], preferred_element_type=x.dtype)
    if cfg.mlp_bias:
        y = y + p["bo"]
    return constrain(y, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embed(cfg, key, dtype):
    p = {"table": ninit(key, (cfg.vocab_size, cfg.d_model), scale=0.02, dtype=dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = ninit(
            jax.random.fold_in(key, 1), (cfg.d_model, cfg.vocab_size), dtype=dtype
        )
    return p


def embed_specs(cfg):
    s = {"table": ("p_vocab", "p_embed")}
    if not cfg.tie_embeddings:
        s["unembed"] = ("p_embed", "p_vocab")
    return s


def embed(cfg, p, tokens):
    e = jnp.take(p["table"], tokens, axis=0)
    return constrain(e, "batch", "seq", "embed")


def unembed(cfg, p, x):
    w = p["table"].T if cfg.tie_embeddings else p["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=F32)
    return constrain(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# KV cache helpers (contiguous per-layer cache, ring buffer for SWA)
# ---------------------------------------------------------------------------

def xent_loss(logits, labels, mask=None):
    """Mean next-token cross-entropy in fp32."""
    logits = logits.astype(F32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def cache_update(ck, cv, k_new, v_new, pos, *, ring: "Optional[int]" = None):
    """Insert (B, s, K, D) new keys/values at ``pos``; returns updated cache.

    ``ring``: sliding-window ring-buffer length (slot = pos % ring).
    """
    slot = pos if ring is None else pos % ring
    ck = jax.lax.dynamic_update_slice(ck, k_new.astype(ck.dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v_new.astype(cv.dtype), (0, slot, 0, 0))
    return ck, cv


def decode_attend(cfg, q, ck, cv, pos, *, window: "Optional[int]" = None):
    """One-token attention against a cache. q: (B, 1, H, D); cache (B, S, K, D).

    For ring-buffer (window) caches every resident entry is in-window and in
    the past, so masking reduces to slot-validity.
    """
    if window is None:
        return attention(q, ck, cv, causal=True, q_offset=pos, valid_len=pos + 1)
    ring = ck.shape[1]
    valid = jnp.minimum(pos + 1, ring)
    return attention(q, ck, cv, causal=False, valid_len=valid)


# ---------------------------------------------------------------------------
# paged KV helpers (DESIGN.md §17): the model side of the paging contract
# ---------------------------------------------------------------------------

def page_scatter(kp, vp, k_new, v_new, tables, positions):
    """Scatter one decode-step token per row into a page slab.

    kp/vp: (N, P, K, D) pool slabs for ONE layer; k_new/v_new: (B, 1, K, D);
    tables: (B, M) page tables; positions: (B,) — the token's slot, i.e.
    the row's current length (token ``t`` lives at
    ``pages[table[b, t // P], t % P]``, the kernel-layer layout contract).
    """
    P = kp.shape[1]
    page = jnp.take_along_axis(tables, positions[:, None] // P, axis=1)[:, 0]
    slot = positions % P
    kp = kp.at[page, slot].set(k_new[:, 0].astype(kp.dtype))
    vp = vp.at[page, slot].set(v_new[:, 0].astype(vp.dtype))
    return kp, vp


def page_gather(pages, tables):
    """(N, P, K, D) slab + (B, M) table -> (B, M*P, K, D) contiguous cache.

    Position order: slot ``t`` of the result is token ``t`` of the row, so
    the gathered cache is drop-in for ``decode_attend``'s contiguous cache
    — bit-for-bit, stale slots past ``length`` included (they are masked
    to exact-zero weight downstream).
    """
    N, P, K, D = pages.shape
    B, M = tables.shape
    return pages[tables].reshape(B, M * P, K, D)


def paged_decode_attend(q, kp, vp, tables, lengths):
    """One-token GQA attention against paged KV, bit-equal to the padded
    ``decode_attend(..., pos)`` oracle when each row's ``pos == lengths[b]``
    and the oracle cache width equals ``tables.shape[1] * P``.

    q: (B, 1, H, D); kp/vp: (N, P, K, D); lengths: (B,) tokens already
    resident EXCLUDING the one scattered this step (so rows attend over
    ``lengths + 1`` slots — the fig9 toy's contract).
    """
    kc = page_gather(kp, tables)
    vc = page_gather(vp, tables)
    return attention(q, kc, vc, causal=False, valid_len=lengths + 1)


def slab_decode_attend(q, k_new, v_new, k_pages, v_pages, layer, tables, positions, lengths):
    """One-token GQA attention of layer ``layer`` read in place from the
    full multi-layer slabs, which it leaves unchanged.

    q: (B, 1, H, D); k_new/v_new: (B, 1, K, D), this step's token;
    k_pages/v_pages: (L, N, P, K, D) slabs; tables: (B, M); positions ==
    lengths: (B,).  Each row's pages are gathered straight out of the slab
    into a (B, M*P, K, D) cache, and the row's own new token is set into
    that copy at ``positions``: the values attended over are bit-for-bit
    those ``page_scatter`` then ``paged_decode_attend`` would give.

    Returns ``(o, k_row, v_row)``: the attention output and the (B, K, D)
    K and V read back from the copies at ``positions``, which are what
    ``slab_write_tokens`` writes into the slabs after the layer scan, so
    the pages hold exactly the values attended over.
    """
    B, M = tables.shape
    _, _, P, K, D = k_pages.shape
    rows = jnp.arange(B)

    def cache(pages, new):
        c = pages[layer, tables].reshape(B, M * P, K, D)
        return c.at[rows, positions].set(new[:, 0].astype(c.dtype))

    kc, vc = cache(k_pages, k_new), cache(v_pages, v_new)
    o = attention(q, kc, vc, causal=False, valid_len=lengths + 1)
    return o, kc[rows, positions], vc[rows, positions]


def slab_write_tokens(k_pages, v_pages, k_steps, v_steps, tables, positions):
    """Write one decode step's token of every layer into the slabs in one
    scatter per slab: the only write a step makes to its pages.

    k_pages/v_pages: (L, N, P, K, D) slabs (donated by the caller, so the
    scatter is in place); k_steps/v_steps: (L, B, K, D), the token of each
    layer and row; tables: (B, M); positions: (B,).  Token ``t`` of row
    ``b`` lands at ``[:, table[b, t // P], t % P]``, as ``page_scatter``
    places it.  Rows that repeat another row's table, position and token
    (the decode lane's pad rows) write identical values at identical
    indices, so the order of the scatter cannot matter.
    """
    P = k_pages.shape[2]
    page = jnp.take_along_axis(tables, positions[:, None] // P, axis=1)[:, 0]
    slot = positions % P
    k_pages = k_pages.at[:, page, slot].set(k_steps.astype(k_pages.dtype))
    v_pages = v_pages.at[:, page, slot].set(v_steps.astype(v_pages.dtype))
    return k_pages, v_pages


def ring_gather(pages, tables, positions, ring: int):
    """Reconstruct a sliding-window ring cache (B, ring, K, D) from paged
    full-history KV.

    Slot ``s`` of a ring cache written via ``cache_update(..., ring=ring)``
    holds the newest token whose absolute position ``p`` satisfies
    ``p % ring == s`` and ``p <= pos``; that is
    ``p = pos - ((pos - s) % ring)``.  Negative ``p`` (slot not yet
    written) is clamped to 0 — those slots are masked by the caller's
    ``valid_len=min(pos+1, ring)`` exactly as the oracle masks its
    zero-initialized slots, and masked lanes contribute exact 0.0 either
    way.
    """
    N, P, K, D = pages.shape
    s = jnp.arange(ring)
    p = positions[:, None] - ((positions[:, None] - s[None, :]) % ring)  # (B, ring)
    p = jnp.maximum(p, 0)
    page = jnp.take_along_axis(tables, p // P, axis=1)  # (B, ring)
    return pages[page, p % P]  # (B, ring, K, D)


def paged_ring_attend(q, kp, vp, tables, positions, *, ring: int):
    """Sliding-window one-token attention against paged KV: gather the
    ring layout the oracle's ring cache would hold at ``pos = positions``
    (new token already scattered), then run the same windowed attend —
    bit-equal to ``decode_attend(..., window=w)`` per row."""
    kc = ring_gather(kp, tables, positions, ring)
    vc = ring_gather(vp, tables, positions, ring)
    valid = jnp.minimum(positions + 1, ring)
    return attention(q, kc, vc, causal=False, valid_len=valid)
