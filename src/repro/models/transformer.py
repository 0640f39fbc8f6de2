"""Decoder-only transformer LM (dense / MoE / VLM backbones).

Layers are *stacked* (leading L axis) and applied with ``lax.scan`` so the
HLO stays one-layer-sized regardless of depth (deepseek-67b: 95 layers),
with a configurable remat policy on the scanned body.

VLM (qwen2-vl): the vision frontend is a STUB — precomputed patch
embeddings (B, P, D) are written over positions [1, P+1) of the token
embedding, and M-RoPE consumes the stub's (3, B, S) t/h/w position ids.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distribution.sharding import constrain
from repro.models import layers as L
from repro.models import moe as M

AUX_LOSS_COEF = 0.01


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(cfg, key, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.init_norm(cfg, ks[0], dtype),
        "attn": L.init_attn(cfg, ks[1], dtype),
        "ln2": L.init_norm(cfg, ks[2], dtype),
    }
    if cfg.moe is not None:
        p["moe"] = M.init_moe(cfg, ks[3], dtype)
    else:
        p["mlp"] = L.init_mlp(cfg, ks[3], dtype)
    return p


def _layer_specs(cfg):
    s = {
        "ln1": L.norm_specs(cfg),
        "attn": L.attn_specs(cfg),
        "ln2": L.norm_specs(cfg),
    }
    if cfg.moe is not None:
        s["moe"] = M.moe_specs(cfg)
    else:
        s["mlp"] = L.mlp_specs(cfg)
    return s


def init(cfg, key, dtype=jnp.float32):
    kE, kL, kF = jax.random.split(key, 3)
    layer_keys = jax.random.split(kL, cfg.num_layers)
    return {
        "embed": L.init_embed(cfg, kE, dtype),
        "layers": jax.vmap(lambda k: _init_layer(cfg, k, dtype))(layer_keys),
        "final_norm": L.init_norm(cfg, kF, dtype),
    }


def param_specs(cfg):
    """Logical-axis names for every param; layer params gain a 'layers' dim."""
    layer = _layer_specs(cfg)
    stacked = jax.tree.map(
        lambda names: ("layers",) + names,
        layer,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(n, (str, type(None))) for n in x),
    )
    return {
        "embed": L.embed_specs(cfg),
        "layers": stacked,
        "final_norm": L.norm_specs(cfg),
    }


# ---------------------------------------------------------------------------
# embedding (+ VLM patch merge)
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, params, batch):
    x = L.embed(cfg, params["embed"], batch["tokens"])
    if cfg.vision_stub and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)  # (B, P, D) from the stub
        P = pe.shape[1]
        x = jax.lax.dynamic_update_slice(x, pe, (0, 1, 0))  # positions [1, P+1)
        del P
    return x


def _positions(cfg, batch, S):
    if cfg.rope_type == "mrope":
        pos = batch.get("positions")
        if pos is None:  # text-only fallback: all three streams equal
            B = batch["tokens"].shape[0]
            pos = jnp.broadcast_to(jnp.arange(S)[None, None], (3, B, S))
        return pos
    B = batch["tokens"].shape[0]
    return jnp.broadcast_to(jnp.arange(S)[None], (B, S))


def _rope(cfg, positions):
    if cfg.rope_type in ("rope", "mrope"):
        rot = int(cfg.hd * cfg.partial_rotary)
        return L.rope_angles(positions, rot, cfg.rope_theta, cfg.mrope_sections)
    return None, None


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _attn_mlp_layer(cfg, lp, x, cos, sin, *, q_block, return_kv, moe_groups=None):
    h = L.apply_norm(cfg, x, lp["ln1"])
    q, k, v = L.qkv_proj(cfg, lp["attn"], h)
    if cos is not None:
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    if cfg.sliding_window is not None and x.shape[1] > cfg.sliding_window:
        o = L.local_block_attention(q, k, v, window=cfg.sliding_window)
    else:
        o = L.attention(q, k, v, causal=True, q_block=q_block, softcap=cfg.attn_logit_softcap)
    x = x + L.out_proj(cfg, lp["attn"], o)

    h = L.apply_norm(cfg, x, lp["ln2"])
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        y, aux = M.moe_block(cfg, lp["moe"], h, groups=moe_groups)
    else:
        y = L.mlp(cfg, lp["mlp"], h)
    x = constrain(x + y, "batch", "seq", "embed")
    return x, aux, (k, v)


def forward(
    cfg,
    params,
    batch,
    *,
    q_block: "Optional[int]" = 512,
    remat: str = "none",
    return_kv: bool = False,
    last_only: bool = False,
    moe_groups: "Optional[int]" = None,
):
    """Teacher-forcing forward. batch["tokens"]: (B, S) int32.

    Returns (logits, aux_loss) or (logits, aux_loss, kv_cache) with
    ``return_kv`` (prefill: kv_cache is {'k','v'}: (L, B, S, K, hd)).
    """
    x = _embed_inputs(cfg, params, batch)
    S = x.shape[1]
    cos, sin = _rope(cfg, _positions(cfg, batch, S))

    def body(x, lp):
        x, aux, kv = _attn_mlp_layer(cfg, lp, x, cos, sin, q_block=q_block,
                                     return_kv=return_kv, moe_groups=moe_groups)
        ys = (aux, kv) if return_kv else (aux, (jnp.zeros((), x.dtype),) * 2)
        return x, ys

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    x, (auxs, kvs) = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(cfg, x, params["final_norm"])
    if last_only:  # prefill: only the final position feeds sampling
        x = x[:, -1:]
    logits = L.unembed(cfg, params["embed"], x)
    aux = jnp.sum(auxs)
    if return_kv:
        return logits, aux, {"k": kvs[0], "v": kvs[1]}
    return logits, aux


def loss_fn(cfg, params, batch, **kw):
    """Mean next-token cross-entropy (fp32) + MoE aux loss."""
    logits, aux = forward(cfg, params, batch, **kw)
    xent = L.xent_loss(logits, batch["labels"], batch.get("loss_mask"))
    return xent + AUX_LOSS_COEF * aux


# ---------------------------------------------------------------------------
# decode (one token, stacked KV cache, scan over layers)
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_specs(cfg):
    names = ("layers", "batch", "seq", "kv_heads", "head_dim")
    return {"k": names, "v": names}


def decode_step(cfg, params, cache, tokens, pos, *, positions=None):
    """tokens: (B, 1) int32; pos: scalar int32 (current write position).

    Returns (logits (B, 1, V), new_cache).
    """
    x = L.embed(cfg, params["embed"], tokens)
    B = x.shape[0]
    if cfg.rope_type == "mrope":
        p3 = jnp.full((3, B, 1), pos, dtype=jnp.int32)  # text decode: t=h=w=pos
        cos, sin = _rope(cfg, p3)
    elif cfg.rope_type == "rope":
        p1 = jnp.full((B, 1), pos, dtype=jnp.int32)
        cos, sin = _rope(cfg, p1)
    else:
        cos, sin = None, None

    def body(x, xs):
        lp, ck, cv = xs
        h = L.apply_norm(cfg, x, lp["ln1"])
        q, k, v = L.qkv_proj(cfg, lp["attn"], h)
        if cos is not None:
            q = L.apply_rope(q, cos, sin)
            k = L.apply_rope(k, cos, sin)
        ck, cv = L.cache_update(ck, cv, k, v, pos)
        o = L.decode_attend(cfg, q, ck, cv, pos)
        x = x + L.out_proj(cfg, lp["attn"], o)
        h = L.apply_norm(cfg, x, lp["ln2"])
        if cfg.moe is not None:
            y, _ = M.moe_block(cfg, lp["moe"], h)
        else:
            y = L.mlp(cfg, lp["mlp"], h)
        return x + y, (ck, cv)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], x)
    return logits, {"k": ks, "v": vs}


# ---------------------------------------------------------------------------
# paged serving contract (DESIGN.md §17)
# ---------------------------------------------------------------------------

def paged_spec(cfg):
    """Multi-layer KV folded into ONE page geometry: layer is the leading
    slab dim, so a sequence's pages for every layer share one table."""
    from repro.serving.paged import PageSpec

    return PageSpec(
        layers=cfg.num_layers,
        page_size=0,  # 0 -> REPRO_PAGE_SIZE default
        kv_heads=cfg.num_kv_heads,
        head_dim=cfg.hd,
        dtype=jnp.float32,
    )


def paged_prefill(cfg, params, tokens, extras=None):
    """tokens: (B, T) int32 -> (k, v, state, last_logits).

    k/v: (B, L, T, K, hd) per-request KV rows ready for
    ``PagedKVCache.append``; state: None (attention-only arch);
    last_logits: (B, V) fp32 for the sampling stage.  KV bits equal
    ``forward(..., return_kv=True)`` — the padded oracle's prefill.
    """
    batch = {"tokens": tokens}
    if extras:
        batch.update(extras)
    # moe_groups=B: capacity buckets stay per-row, so each request's
    # prefill logits are independent of which rows batched with it.
    logits, _, kv = forward(cfg, params, batch, return_kv=True, last_only=True,
                            moe_groups=tokens.shape[0])
    k = jnp.moveaxis(kv["k"], 0, 1)  # (L, B, T, K, hd) -> (B, L, T, K, hd)
    v = jnp.moveaxis(kv["v"], 0, 1)
    return k, v, None, logits[:, -1]


def paged_decode_step(cfg, params, k_pages, v_pages, state, tokens, positions, tables, lengths):
    """One ragged decode step straight against the page pool.

    k_pages/v_pages: (L, N, P, K, hd) slabs; tokens: (B,) int32 last
    tokens; positions == lengths: (B,) per-row write slot / tokens already
    resident; tables: (B, M).  Returns (k_pages, v_pages, state, logits
    (B, V)).  Per-row math is op-for-op ``decode_step``'s — the new token
    sits at ``positions`` and each row attends over ``lengths + 1`` slots
    — so greedy tokens are bit-identical to the padded oracle.

    Write contract: the layer scan reads the slabs in place, as loop
    invariants (``L.slab_decode_attend``), and carries only each layer's
    new (B, K, hd) K and V out; after the scan one scatter per slab writes
    the step's tokens (``L.slab_write_tokens``).  The slabs are written
    nowhere else, so with the slabs donated no slab-sized array is made.
    """
    tokens = tokens.reshape(-1, 1)
    x = L.embed(cfg, params["embed"], tokens)
    B = x.shape[0]
    if cfg.rope_type == "mrope":
        p3 = jnp.broadcast_to(positions[None, :, None], (3, B, 1)).astype(jnp.int32)
        cos, sin = _rope(cfg, p3)
    elif cfg.rope_type == "rope":
        cos, sin = _rope(cfg, positions[:, None].astype(jnp.int32))
    else:
        cos, sin = None, None

    def body(x, xs):
        lp, l = xs
        h = L.apply_norm(cfg, x, lp["ln1"])
        q, k, v = L.qkv_proj(cfg, lp["attn"], h)
        if cos is not None:
            q = L.apply_rope(q, cos, sin)
            k = L.apply_rope(k, cos, sin)
        o, k_row, v_row = L.slab_decode_attend(q, k, v, k_pages, v_pages, l, tables, positions, lengths)
        x = x + L.out_proj(cfg, lp["attn"], o)
        h = L.apply_norm(cfg, x, lp["ln2"])
        if cfg.moe is not None:
            # per-row capacity buckets: a row's expert drops cannot depend
            # on which other sequences share the decode micro-batch
            y, _ = M.moe_block(cfg, lp["moe"], h, groups=B)
        else:
            y = L.mlp(cfg, lp["mlp"], h)
        return x + y, (k_row, v_row)

    layer_ids = jnp.arange(cfg.num_layers)
    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], layer_ids))
    k_pages, v_pages = L.slab_write_tokens(k_pages, v_pages, ks, vs, tables, positions)
    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], x)
    return k_pages, v_pages, state, logits[:, 0]
