"""Whisper-style encoder-decoder backbone [arXiv:2212.04356].

The conv/mel frontend is a STUB: the encoder consumes precomputed frame
embeddings (B, S_enc, D) supplied by ``input_specs()``; sinusoidal
positions are added here.  Decoder: learned positions, causal self-attn
with KV cache + cross-attn over encoder states (K/V precomputed at
prefill).  4+4 layers — unrolled loops (no scan needed).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.models import layers as L


def _sinusoid(S, D):
    pos = np.arange(S)[:, None]
    dim = np.arange(D // 2)[None]
    inv = 1.0 / (10_000 ** (dim / max(D // 2 - 1, 1)))
    ang = pos * inv
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), jnp.float32)


def _init_block(cfg, key, dtype, cross: bool):
    ks = jax.random.split(key, 6)
    p = {
        "ln1": L.init_norm(cfg, ks[0], dtype),
        "attn": L.init_attn(cfg, ks[1], dtype),
        "ln2": L.init_norm(cfg, ks[2], dtype),
        "mlp": L.init_mlp(cfg, ks[3], dtype),
    }
    if cross:
        p["ln_x"] = L.init_norm(cfg, ks[4], dtype)
        p["xattn"] = L.init_attn(cfg, ks[5], dtype)
    return p


def _block_specs(cfg, cross: bool):
    s = {
        "ln1": L.norm_specs(cfg),
        "attn": L.attn_specs(cfg),
        "ln2": L.norm_specs(cfg),
        "mlp": L.mlp_specs(cfg),
    }
    if cross:
        s["ln_x"] = L.norm_specs(cfg)
        s["xattn"] = L.attn_specs(cfg)
    return s


def init(cfg, key, dtype=jnp.float32):
    e = cfg.encdec
    kE, kEnc, kDec, kP, kF1, kF2 = jax.random.split(key, 6)
    enc_keys = jax.random.split(kEnc, e.encoder_layers)
    dec_keys = jax.random.split(kDec, cfg.num_layers)
    return {
        "embed": L.init_embed(cfg, kE, dtype),
        "dec_pos": L.ninit(kP, (e.max_target_positions, cfg.d_model), scale=0.02, dtype=dtype),
        "enc_layers": [_init_block(cfg, k, dtype, cross=False) for k in enc_keys],
        "dec_layers": [_init_block(cfg, k, dtype, cross=True) for k in dec_keys],
        "enc_norm": L.init_norm(cfg, kF1, dtype),
        "final_norm": L.init_norm(cfg, kF2, dtype),
    }


def param_specs(cfg):
    e = cfg.encdec
    return {
        "embed": L.embed_specs(cfg),
        "dec_pos": ("p_none", "p_embed"),
        "enc_layers": [_block_specs(cfg, False) for _ in range(e.encoder_layers)],
        "dec_layers": [_block_specs(cfg, True) for _ in range(cfg.num_layers)],
        "enc_norm": L.norm_specs(cfg),
        "final_norm": L.norm_specs(cfg),
    }


def _self_block(cfg, lp, x, *, causal, q_block):
    h = L.apply_norm(cfg, x, lp["ln1"])
    q, k, v = L.qkv_proj(cfg, lp["attn"], h)
    o = L.attention(q, k, v, causal=causal, q_block=q_block)
    x = x + L.out_proj(cfg, lp["attn"], o)
    return x, (k, v)


def _cross(cfg, lp, x, ek, ev):
    h = L.apply_norm(cfg, x, lp["ln_x"])
    B, S, _ = h.shape
    H, hd = cfg.num_heads, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", h, lp["xattn"]["wq"], preferred_element_type=h.dtype)
    if cfg.attn_qkv_bias:
        q = q + lp["xattn"]["bq"]
    o = L.attention(q.reshape(B, S, H, hd), ek, ev, causal=False)
    return x + L.out_proj(cfg, lp["xattn"], o)


def _mlp_block(cfg, lp, x):
    return x + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, x, lp["ln2"]))


def encode(cfg, params, frames, remat: str = "none"):
    """frames: (B, S_enc, D) stub embeddings -> encoder states."""
    x = frames + _sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)

    def enc_layer(x, lp):
        x, _ = _self_block(cfg, lp, x, causal=False, q_block=None)
        return _mlp_block(cfg, lp, x)

    if remat in ("dots", "full"):
        enc_layer = jax.checkpoint(enc_layer)
    for lp in params["enc_layers"]:
        x = enc_layer(x, lp)
    return L.apply_norm(cfg, x, params["enc_norm"])


def _cross_kv(cfg, params, enc):
    """Precompute cross-attention K/V per decoder layer."""
    B, Se, _ = enc.shape
    K, hd = cfg.num_kv_heads, cfg.hd
    out = []
    for lp in params["dec_layers"]:
        k = jnp.einsum("bsd,dh->bsh", enc, lp["xattn"]["wk"], preferred_element_type=enc.dtype)
        v = jnp.einsum("bsd,dh->bsh", enc, lp["xattn"]["wv"], preferred_element_type=enc.dtype)
        if cfg.attn_qkv_bias:
            k, v = k + lp["xattn"]["bk"], v + lp["xattn"]["bv"]
        out.append((k.reshape(B, Se, K, hd), v.reshape(B, Se, K, hd)))
    return out


def forward(cfg, params, batch, *, q_block=512, remat: str = "none", return_kv: bool = False, last_only: bool = False):
    """batch: {'frames': (B, S_enc, D) stub, 'tokens': (B, S_dec)}."""
    enc = encode(cfg, params, batch["frames"], remat=remat)
    xkv = _cross_kv(cfg, params, enc)

    tokens = batch["tokens"]
    B, S = tokens.shape
    x = L.embed(cfg, params["embed"], tokens)
    pos_tab = params["dec_pos"]
    idx = jnp.arange(S) % pos_tab.shape[0]  # structural cells may exceed 448
    x = x + pos_tab[idx][None].astype(x.dtype)

    def dec_layer(x, lp, ek, ev):
        x, kv = _self_block(cfg, lp, x, causal=True, q_block=q_block)
        x = _cross(cfg, lp, x, ek, ev)
        x = _mlp_block(cfg, lp, x)
        return x, kv

    if remat in ("dots", "full"):
        dec_layer = jax.checkpoint(dec_layer, static_argnums=())

    kvs = []
    for lp, (ek, ev) in zip(params["dec_layers"], xkv):
        x, kv = dec_layer(x, lp, ek, ev)
        kvs.append(kv)

    x = L.apply_norm(cfg, x, params["final_norm"])
    if last_only:
        x = x[:, -1:]
    logits = L.unembed(cfg, params["embed"], x)
    aux = jnp.zeros((), jnp.float32)
    if return_kv:
        return logits, aux, {"self": kvs, "cross": xkv}
    return logits, aux


def loss_fn(cfg, params, batch, **kw):
    logits, _ = forward(cfg, params, batch, **kw)
    return L.xent_loss(logits, batch["labels"], batch.get("loss_mask"))


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    e = cfg.encdec
    Ld, K, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hd
    return {
        "self_k": jnp.zeros((Ld, batch, max_seq, K, hd), dtype),
        "self_v": jnp.zeros((Ld, batch, max_seq, K, hd), dtype),
        "cross_k": jnp.zeros((Ld, batch, e.encoder_seq, K, hd), dtype),
        "cross_v": jnp.zeros((Ld, batch, e.encoder_seq, K, hd), dtype),
    }


def cache_specs(cfg):
    kv = ("layers", "batch", "seq", "kv_heads", "head_dim")
    return {"self_k": kv, "self_v": kv, "cross_k": kv, "cross_v": kv}


def decode_step(cfg, params, cache, tokens, pos, *, positions=None):
    """One decoder token against self-cache + precomputed cross K/V."""
    B = tokens.shape[0]
    x = L.embed(cfg, params["embed"], tokens)
    pos_tab = params["dec_pos"]
    x = x + pos_tab[pos % pos_tab.shape[0]][None, None].astype(x.dtype)

    cache = dict(cache)
    for i, lp in enumerate(params["dec_layers"]):
        h = L.apply_norm(cfg, x, lp["ln1"])
        q, k, v = L.qkv_proj(cfg, lp["attn"], h)
        ck, cv = L.cache_update(cache["self_k"][i], cache["self_v"][i], k, v, pos)
        cache["self_k"] = cache["self_k"].at[i].set(ck)
        cache["self_v"] = cache["self_v"].at[i].set(cv)
        o = L.decode_attend(cfg, q, ck, cv, pos)
        x = x + L.out_proj(cfg, lp["attn"], o)
        x = _cross(cfg, lp, x, cache["cross_k"][i], cache["cross_v"][i])
        x = _mlp_block(cfg, lp, x)

    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], x)
    return logits, cache


# ---------------------------------------------------------------------------
# paged serving contract (DESIGN.md §17)
# ---------------------------------------------------------------------------

def paged_spec(cfg):
    """Decoder self-KV lives in pages; the fixed-size cross K/V (one
    entry per encoder frame, never grows) rides as per-sequence state."""
    from repro.serving.paged import PAGE_SIZE, PageSpec

    return PageSpec(
        layers=cfg.num_layers,
        page_size=PAGE_SIZE,
        kv_heads=cfg.num_kv_heads,
        head_dim=cfg.hd,
        dtype=jnp.float32,
    )


def paged_prefill(cfg, params, tokens, extras=None):
    """tokens: (B, T); extras['frames']: (B, S_enc, D) stub embeddings.

    Returns (k, v, state, last_logits): self-KV rows (B, L, T, K, hd)
    for the pages, cross K/V stacked batch-leading as resident state.
    """
    frames = extras["frames"]
    logits, _, kv = forward(
        cfg, params, {"frames": frames, "tokens": tokens},
        return_kv=True, last_only=True,
    )
    k = jnp.stack([kv_l[0] for kv_l in kv["self"]], axis=1)  # (B, L, T, K, hd)
    v = jnp.stack([kv_l[1] for kv_l in kv["self"]], axis=1)
    state = {
        "cross_k": jnp.stack([x[0] for x in kv["cross"]], axis=1),  # (B, L, Se, K, hd)
        "cross_v": jnp.stack([x[1] for x in kv["cross"]], axis=1),
    }
    return k, v, state, logits[:, -1]


def paged_decode_step(cfg, params, k_pages, v_pages, state, tokens, positions, tables, lengths):
    """One ragged decoder step: scatter self-KV into pages, attend over
    each row's own prefix, cross-attend the resident encoder K/V.
    Per-row math is op-for-op ``decode_step``'s."""
    tokens = tokens.reshape(-1, 1)
    x = L.embed(cfg, params["embed"], tokens)
    pos_tab = params["dec_pos"]
    x = x + pos_tab[positions % pos_tab.shape[0]][:, None].astype(x.dtype)

    for i, lp in enumerate(params["dec_layers"]):
        h = L.apply_norm(cfg, x, lp["ln1"])
        q, k, v = L.qkv_proj(cfg, lp["attn"], h)
        kp, vp = L.page_scatter(k_pages[i], v_pages[i], k, v, tables, positions)
        k_pages = k_pages.at[i].set(kp)
        v_pages = v_pages.at[i].set(vp)
        o = L.paged_decode_attend(q, kp, vp, tables, lengths)
        x = x + L.out_proj(cfg, lp["attn"], o)
        x = _cross(cfg, lp, x, state["cross_k"][:, i], state["cross_v"][:, i])
        x = _mlp_block(cfg, lp, x)

    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], x)
    return k_pages, v_pages, state, logits[:, 0]
