"""Hymba hybrid-head model [arXiv:2411.13676].

Each block runs attention heads and Mamba(SSD) heads IN PARALLEL on the
same normalized input; per-path outputs are normalized, scaled and
averaged (approximation of the paper's output-mean fusion — recorded in
DESIGN.md).  Sliding-window attention everywhere except
``cfg.global_attn_layers``; consecutive SWA layers share KV
(``kv_share_group=2``: even layers produce K/V, odd layers reuse them);
``cfg.meta_tokens`` learned registers are prepended to the sequence.

Layers are heterogeneous (global/local, producer/consumer), so the stack
is an unrolled python loop over per-layer param lists rather than a scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distribution.sharding import constrain
from repro.models import layers as L
from repro.models import ssm as S


def _is_global(cfg, l: int) -> bool:
    return l in cfg.global_attn_layers


def _kv_producer(cfg, l: int) -> int:
    """Index of the layer whose K/V layer ``l`` consumes."""
    if _is_global(cfg, l) or cfg.kv_share_group <= 1:
        return l
    base = l - (l % cfg.kv_share_group)
    return l if _is_global(cfg, base) else base


def kv_producers(cfg) -> "list[int]":
    return sorted({_kv_producer(cfg, l) for l in range(cfg.num_layers)})


def _init_layer(cfg, key, dtype, l: int):
    ks = jax.random.split(key, 6)
    produces = _kv_producer(cfg, l) == l
    attn = L.init_attn(cfg, ks[0], dtype)
    if not produces:  # consumer layers have no K/V projections
        attn.pop("wk"), attn.pop("wv")
        attn.pop("bk", None), attn.pop("bv", None)
    return {
        "ln1": L.init_norm(cfg, ks[1], dtype),
        "attn": attn,
        "ssm": S.init_ssm(cfg, ks[2], dtype),
        "fuse_attn": jnp.ones((cfg.d_model,), dtype),
        "fuse_ssm": jnp.ones((cfg.d_model,), dtype),
        "ln2": L.init_norm(cfg, ks[3], dtype),
        "mlp": L.init_mlp(cfg, ks[4], dtype),
    }


def init(cfg, key, dtype=jnp.float32):
    kE, kM, kL, kF = jax.random.split(key, 4)
    layer_keys = jax.random.split(kL, cfg.num_layers)
    return {
        "embed": L.init_embed(cfg, kE, dtype),
        "meta": L.ninit(kM, (cfg.meta_tokens, cfg.d_model), scale=0.02, dtype=dtype)
        if cfg.meta_tokens
        else jnp.zeros((0, cfg.d_model), dtype),
        "layers": [_init_layer(cfg, k, dtype, l) for l, k in enumerate(layer_keys)],
        "final_norm": L.init_norm(cfg, kF, dtype),
    }


def param_specs(cfg):
    def layer(l):
        attn = L.attn_specs(cfg)
        if _kv_producer(cfg, l) != l:
            attn.pop("wk"), attn.pop("wv")
            attn.pop("bk", None), attn.pop("bv", None)
        return {
            "ln1": L.norm_specs(cfg),
            "attn": attn,
            "ssm": S.ssm_specs(cfg),
            "fuse_attn": ("p_none",),
            "fuse_ssm": ("p_none",),
            "ln2": L.norm_specs(cfg),
            "mlp": L.mlp_specs(cfg),
        }

    return {
        "embed": L.embed_specs(cfg),
        "meta": ("p_none", "p_embed"),
        "layers": [layer(l) for l in range(cfg.num_layers)],
        "final_norm": L.norm_specs(cfg),
    }


def _pad_to(x, mult: int):
    S_ = x.shape[1]
    pad = (-S_) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x, pad


def forward(cfg, params, batch, *, q_block=512, remat: str = "none", return_kv: bool = False, last_only: bool = False):
    x = L.embed(cfg, params["embed"], batch["tokens"])
    B = x.shape[0]
    if cfg.meta_tokens:
        meta = jnp.broadcast_to(params["meta"][None], (B, cfg.meta_tokens, cfg.d_model))
        x = jnp.concatenate([meta.astype(x.dtype), x], axis=1)
    S_ = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S_)[None], (B, S_))
    rot = int(cfg.hd * cfg.partial_rotary)
    cos, sin = L.rope_angles(pos, rot, cfg.rope_theta)

    shared_kv = None
    kvs = {}
    for l, lp in enumerate(params["layers"]):
        def block(x, lp=lp, l=l, shared=shared_kv):
            h = L.apply_norm(cfg, x, lp["ln1"])
            # --- ssm path
            y_ssm = S.ssm_block(cfg, lp["ssm"], h)
            # --- attention path (possibly reusing shared K/V)
            hd, H, K = cfg.hd, cfg.num_heads, cfg.num_kv_heads
            q = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wq"], preferred_element_type=h.dtype)
            if cfg.attn_qkv_bias:
                q = q + lp["attn"]["bq"]
            q = L.apply_rope(q.reshape(B, S_, H, hd), cos, sin)
            if "wk" in lp["attn"]:
                k = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wk"], preferred_element_type=h.dtype)
                v = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wv"], preferred_element_type=h.dtype)
                if cfg.attn_qkv_bias:
                    k, v = k + lp["attn"]["bk"], v + lp["attn"]["bv"]
                k = L.apply_rope(k.reshape(B, S_, K, hd), cos, sin)
                v = v.reshape(B, S_, K, hd)
            else:
                k, v = shared
            if _is_global(cfg, l) or cfg.sliding_window is None or S_ <= cfg.sliding_window:
                o = L.attention(q, k, v, causal=True, q_block=q_block)
            else:
                w = cfg.sliding_window
                qp, _ = _pad_to(q, w)
                kp, _ = _pad_to(k, w)
                vp, pad = _pad_to(v, w)
                o = L.local_block_attention(qp, kp, vp, window=w)[:, :S_]
            y_attn = L.out_proj(cfg, lp["attn"], o)
            # --- fuse: mean of per-path normalized outputs
            fused = 0.5 * (
                L.rmsnorm(y_attn, lp["fuse_attn"], cfg.norm_eps)
                + L.rmsnorm(y_ssm, lp["fuse_ssm"], cfg.norm_eps)
            )
            x = x + fused
            x = x + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, x, lp["ln2"]))
            return constrain(x, "batch", "seq", "embed"), (k, v)

        if remat in ("full", "dots"):
            block = jax.checkpoint(block)
        x, (k_l, v_l) = block(x)
        if _kv_producer(cfg, l) == l:
            shared_kv = (k_l, v_l)
            if return_kv:
                kvs[l] = (k_l, v_l)

    x = L.apply_norm(cfg, x, params["final_norm"])
    if cfg.meta_tokens:
        x = x[:, cfg.meta_tokens :]
    if last_only:
        x = x[:, -1:]
    logits = L.unembed(cfg, params["embed"], x)
    aux = jnp.zeros((), jnp.float32)
    if return_kv:
        return logits, aux, kvs
    return logits, aux


def loss_fn(cfg, params, batch, **kw):
    logits, _ = forward(cfg, params, batch, **kw)
    return L.xent_loss(logits, batch["labels"], batch.get("loss_mask"))


# ---------------------------------------------------------------------------
# decode: ring caches for SWA producers, full caches for global layers,
# SSM state for every layer
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    K, hd = cfg.num_kv_heads, cfg.hd
    producers = kv_producers(cfg)
    swa = [l for l in producers if not _is_global(cfg, l)]
    glob = [l for l in producers if _is_global(cfg, l)]
    ring = min(cfg.sliding_window or max_seq, max_seq)
    ssm1 = S.init_ssm_cache(cfg, batch, dtype)
    cache = {
        "swa_k": jnp.zeros((len(swa), batch, ring, K, hd), dtype),
        "swa_v": jnp.zeros((len(swa), batch, ring, K, hd), dtype),
        "glob_k": jnp.zeros((len(glob), batch, max_seq, K, hd), dtype),
        "glob_v": jnp.zeros((len(glob), batch, max_seq, K, hd), dtype),
        "ssm_state": jnp.broadcast_to(ssm1["state"], (cfg.num_layers,) + ssm1["state"].shape).copy(),
        "ssm_conv": jnp.broadcast_to(ssm1["conv"], (cfg.num_layers,) + ssm1["conv"].shape).copy(),
    }
    return cache


def cache_specs(cfg):
    kv = (None, "batch", "seq", "kv_heads", "head_dim")
    return {
        "swa_k": kv,
        "swa_v": kv,
        "glob_k": kv,
        "glob_v": kv,
        "ssm_state": ("layers", "batch", "ssm_heads", "ssm_state", None),
        "ssm_conv": ("layers", "batch", None, "ssm_inner"),
    }


def decode_step(cfg, params, cache, tokens, pos, *, positions=None):
    """tokens (B,1); pos counts *content* tokens; meta offset added here."""
    x = L.embed(cfg, params["embed"], tokens)
    B = x.shape[0]
    apos = pos + cfg.meta_tokens
    p1 = jnp.full((B, 1), apos, dtype=jnp.int32)
    rot = int(cfg.hd * cfg.partial_rotary)
    cos, sin = L.rope_angles(p1, rot, cfg.rope_theta)

    producers = kv_producers(cfg)
    swa = [l for l in producers if not _is_global(cfg, l)]
    glob = [l for l in producers if _is_global(cfg, l)]
    swa_ix = {l: i for i, l in enumerate(swa)}
    glob_ix = {l: i for i, l in enumerate(glob)}

    cache = dict(cache)
    shared = None
    for l, lp in enumerate(params["layers"]):
        h = L.apply_norm(cfg, x, lp["ln1"])
        y_ssm, new_ssm = S.ssm_decode_step(
            cfg, lp["ssm"], h, {"state": cache["ssm_state"][l], "conv": cache["ssm_conv"][l]}
        )
        cache["ssm_state"] = cache["ssm_state"].at[l].set(new_ssm["state"])
        cache["ssm_conv"] = cache["ssm_conv"].at[l].set(new_ssm["conv"])

        hd, H, K = cfg.hd, cfg.num_heads, cfg.num_kv_heads
        q = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wq"], preferred_element_type=h.dtype)
        if cfg.attn_qkv_bias:
            q = q + lp["attn"]["bq"]
        q = L.apply_rope(q.reshape(B, 1, H, hd), cos, sin)

        if "wk" in lp["attn"]:
            k = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wk"], preferred_element_type=h.dtype)
            v = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wv"], preferred_element_type=h.dtype)
            if cfg.attn_qkv_bias:
                k, v = k + lp["attn"]["bk"], v + lp["attn"]["bv"]
            k = L.apply_rope(k.reshape(B, 1, K, hd), cos, sin)
            v = v.reshape(B, 1, K, hd)
            if _is_global(cfg, l):
                i = glob_ix[l]
                ck, cv = L.cache_update(cache["glob_k"][i], cache["glob_v"][i], k, v, apos)
                cache["glob_k"] = cache["glob_k"].at[i].set(ck)
                cache["glob_v"] = cache["glob_v"].at[i].set(cv)
                o = L.decode_attend(cfg, q, ck, cv, apos)
            else:
                i = swa_ix[l]
                ring = cache["swa_k"].shape[2]
                ck, cv = L.cache_update(cache["swa_k"][i], cache["swa_v"][i], k, v, apos, ring=ring)
                cache["swa_k"] = cache["swa_k"].at[i].set(ck)
                cache["swa_v"] = cache["swa_v"].at[i].set(cv)
                o = L.decode_attend(cfg, q, ck, cv, apos, window=cfg.sliding_window)
                shared = (ck, cv, True)
        else:
            ck, cv, is_ring = shared
            if is_ring:
                o = L.decode_attend(cfg, q, ck, cv, apos, window=cfg.sliding_window)
            else:
                o = L.decode_attend(cfg, q, ck, cv, apos)
        y_attn = L.out_proj(cfg, lp["attn"], o)
        fused = 0.5 * (
            L.rmsnorm(y_attn, lp["fuse_attn"], cfg.norm_eps)
            + L.rmsnorm(y_ssm, lp["fuse_ssm"], cfg.norm_eps)
        )
        x = x + fused
        x = x + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, x, lp["ln2"]))

    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], x)
    return logits, cache


# ---------------------------------------------------------------------------
# paged serving contract (DESIGN.md §17)
# ---------------------------------------------------------------------------

def paged_spec(cfg):
    """One slab layer per KV *producer* (consumers share the producer's
    pages, exactly as they share its cache in ``decode_step``).  SWA
    layers keep FULL history in pages; the ring layout the oracle's
    ``cache_update(..., ring=...)`` would hold is reconstructed at decode
    via ``layers.ring_gather`` — so pages stay position-addressed for
    every layer and one table serves the whole stack."""
    from repro.serving.paged import PAGE_SIZE, PageSpec

    return PageSpec(
        layers=len(kv_producers(cfg)),
        page_size=PAGE_SIZE,
        kv_heads=cfg.num_kv_heads,
        head_dim=cfg.hd,
        dtype=jnp.float32,
    )


def paged_prefill(cfg, params, tokens, extras=None):
    """tokens: (B, T) -> (k, v, state, last_logits).

    k/v: (B, Lp, T', K, hd) over producer layers with T' = meta + T —
    meta registers live in the pages too, so the sequence's page length
    and the decode-step positions are the same absolute coordinate.
    state: batch-leading per-layer recurrent {'ssm_state', 'ssm_conv'}.
    The block math is op-for-op ``forward``'s (ssm path via
    ``S.ssm_prefill``, the cache-returning twin of ``S.ssm_block``).
    """
    x = L.embed(cfg, params["embed"], tokens)
    B = x.shape[0]
    if cfg.meta_tokens:
        meta = jnp.broadcast_to(params["meta"][None], (B, cfg.meta_tokens, cfg.d_model))
        x = jnp.concatenate([meta.astype(x.dtype), x], axis=1)
    S_ = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S_)[None], (B, S_))
    rot = int(cfg.hd * cfg.partial_rotary)
    cos, sin = L.rope_angles(pos, rot, cfg.rope_theta)

    producers = kv_producers(cfg)
    shared_kv = None
    kvs = {}
    states, convs = [], []
    for l, lp in enumerate(params["layers"]):
        shared = shared_kv
        h = L.apply_norm(cfg, x, lp["ln1"])
        y_ssm, ssm_cache = S.ssm_prefill(cfg, lp["ssm"], h)
        states.append(ssm_cache["state"])
        convs.append(ssm_cache["conv"])
        hd, H, K = cfg.hd, cfg.num_heads, cfg.num_kv_heads
        q = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wq"], preferred_element_type=h.dtype)
        if cfg.attn_qkv_bias:
            q = q + lp["attn"]["bq"]
        q = L.apply_rope(q.reshape(B, S_, H, hd), cos, sin)
        if "wk" in lp["attn"]:
            k = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wk"], preferred_element_type=h.dtype)
            v = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wv"], preferred_element_type=h.dtype)
            if cfg.attn_qkv_bias:
                k, v = k + lp["attn"]["bk"], v + lp["attn"]["bv"]
            k = L.apply_rope(k.reshape(B, S_, K, hd), cos, sin)
            v = v.reshape(B, S_, K, hd)
        else:
            k, v = shared
        if _is_global(cfg, l) or cfg.sliding_window is None or S_ <= cfg.sliding_window:
            o = L.attention(q, k, v, causal=True, q_block=512)
        else:
            w = cfg.sliding_window
            qp, _ = _pad_to(q, w)
            kp, _ = _pad_to(k, w)
            vp, _ = _pad_to(v, w)
            o = L.local_block_attention(qp, kp, vp, window=w)[:, :S_]
        y_attn = L.out_proj(cfg, lp["attn"], o)
        fused = 0.5 * (
            L.rmsnorm(y_attn, lp["fuse_attn"], cfg.norm_eps)
            + L.rmsnorm(y_ssm, lp["fuse_ssm"], cfg.norm_eps)
        )
        x = x + fused
        x = x + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, x, lp["ln2"]))
        x = constrain(x, "batch", "seq", "embed")
        if _kv_producer(cfg, l) == l:
            shared_kv = (k, v)
            kvs[l] = (k, v)

    xf = L.apply_norm(cfg, x, params["final_norm"])
    if cfg.meta_tokens:
        xf = xf[:, cfg.meta_tokens :]
    logits = L.unembed(cfg, params["embed"], xf[:, -1:])

    k_rows = jnp.stack([kvs[l][0] for l in producers], axis=1)  # (B, Lp, S', K, hd)
    v_rows = jnp.stack([kvs[l][1] for l in producers], axis=1)
    state = {
        "ssm_state": jnp.stack(states, axis=1),  # (B, L, H, N, P) f32
        "ssm_conv": jnp.stack(convs, axis=1),    # (B, L, W-1, C)
    }
    return k_rows, v_rows, state, logits[:, 0]


def paged_decode_step(cfg, params, k_pages, v_pages, state, tokens, positions, tables, lengths):
    """k_pages/v_pages: (Lp, N, P, K, hd); positions == lengths: (B,)
    ABSOLUTE page coordinates (meta included — prefill registered the
    meta registers as page tokens).  Per-row math is ``decode_step``'s:
    global producers scatter + full-prefix attend, SWA producers scatter
    + ring-reconstructed windowed attend, consumers reuse the producer's
    gathered cache, SSM state advances every layer.
    """
    tokens = tokens.reshape(-1, 1)
    x = L.embed(cfg, params["embed"], tokens)
    B = x.shape[0]
    p1 = positions[:, None].astype(jnp.int32)
    rot = int(cfg.hd * cfg.partial_rotary)
    cos, sin = L.rope_angles(p1, rot, cfg.rope_theta)

    producers = kv_producers(cfg)
    prod_ix = {l: i for i, l in enumerate(producers)}
    P_ = k_pages.shape[2]
    width = tables.shape[1] * P_
    ring = min(cfg.sliding_window, width) if cfg.sliding_window else width

    new_states, new_convs = [], []
    shared = None
    for l, lp in enumerate(params["layers"]):
        h = L.apply_norm(cfg, x, lp["ln1"])
        y_ssm, new_ssm = S.ssm_decode_step(
            cfg, lp["ssm"], h,
            {"state": state["ssm_state"][:, l], "conv": state["ssm_conv"][:, l]},
        )
        new_states.append(new_ssm["state"])
        new_convs.append(new_ssm["conv"])

        hd, H, K = cfg.hd, cfg.num_heads, cfg.num_kv_heads
        q = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wq"], preferred_element_type=h.dtype)
        if cfg.attn_qkv_bias:
            q = q + lp["attn"]["bq"]
        q = L.apply_rope(q.reshape(B, 1, H, hd), cos, sin)

        if "wk" in lp["attn"]:
            k = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wk"], preferred_element_type=h.dtype)
            v = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wv"], preferred_element_type=h.dtype)
            if cfg.attn_qkv_bias:
                k, v = k + lp["attn"]["bk"], v + lp["attn"]["bv"]
            k = L.apply_rope(k.reshape(B, 1, K, hd), cos, sin)
            v = v.reshape(B, 1, K, hd)
            i = prod_ix[l]
            kp, vp = L.page_scatter(k_pages[i], v_pages[i], k, v, tables, positions)
            k_pages = k_pages.at[i].set(kp)
            v_pages = v_pages.at[i].set(vp)
            if _is_global(cfg, l):
                ck = L.page_gather(kp, tables)
                cv = L.page_gather(vp, tables)
                o = L.attention(q, ck, cv, causal=False, valid_len=lengths + 1)
            else:
                ck = L.ring_gather(kp, tables, positions, ring)
                cv = L.ring_gather(vp, tables, positions, ring)
                valid = jnp.minimum(positions + 1, ring)
                o = L.attention(q, ck, cv, causal=False, valid_len=valid)
                shared = (ck, cv, True, valid)
        else:
            ck, cv, is_ring, valid = shared
            if is_ring:
                o = L.attention(q, ck, cv, causal=False, valid_len=valid)
            else:
                o = L.attention(q, ck, cv, causal=False, valid_len=lengths + 1)
        y_attn = L.out_proj(cfg, lp["attn"], o)
        fused = 0.5 * (
            L.rmsnorm(y_attn, lp["fuse_attn"], cfg.norm_eps)
            + L.rmsnorm(y_ssm, lp["fuse_ssm"], cfg.norm_eps)
        )
        x = x + fused
        x = x + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, x, lp["ln2"]))

    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], x)
    state = {
        "ssm_state": jnp.stack(new_states, axis=1),
        "ssm_conv": jnp.stack(new_convs, axis=1),
    }
    return k_pages, v_pages, state, logits[:, 0]
