"""Mamba-2 language model (attention-free): embed -> scan(norm+SSD) -> lm head."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import ssm as S


def init(cfg, key, dtype=jnp.float32):
    kE, kL, kF = jax.random.split(key, 3)
    layer_keys = jax.random.split(kL, cfg.num_layers)

    def layer(k):
        k1, k2 = jax.random.split(k)
        return {"ln": L.init_norm(cfg, k1, dtype), "ssm": S.init_ssm(cfg, k2, dtype)}

    return {
        "embed": L.init_embed(cfg, kE, dtype),
        "layers": jax.vmap(layer)(layer_keys),
        "final_norm": L.init_norm(cfg, kF, dtype),
    }


def param_specs(cfg):
    layer = {"ln": L.norm_specs(cfg), "ssm": S.ssm_specs(cfg)}
    stacked = jax.tree.map(
        lambda names: ("layers",) + names,
        layer,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(n, (str, type(None))) for n in x),
    )
    return {"embed": L.embed_specs(cfg), "layers": stacked, "final_norm": L.norm_specs(cfg)}


def forward(cfg, params, batch, *, remat: str = "none", q_block=None, return_kv: bool = False, last_only: bool = False):
    x = L.embed(cfg, params["embed"], batch["tokens"])

    def body(x, lp):
        h = L.apply_norm(cfg, x, lp["ln"])
        return x + S.ssm_block(cfg, lp["ssm"], h), jnp.zeros((), jnp.float32)

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(cfg, x, params["final_norm"])
    if last_only:
        x = x[:, -1:]
    logits = L.unembed(cfg, params["embed"], x)
    aux = jnp.zeros((), jnp.float32)
    if return_kv:
        return logits, aux, init_cache(cfg, x.shape[0], 0)
    return logits, aux


def loss_fn(cfg, params, batch, **kw):
    logits, _ = forward(cfg, params, batch, **kw)
    return L.xent_loss(logits, batch["labels"], batch.get("loss_mask"))


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """SSM decode cache: per-layer recurrent state + conv window (no KV)."""
    one = S.init_ssm_cache(cfg, batch, dtype)
    return jax.tree.map(lambda t: jnp.broadcast_to(t, (cfg.num_layers,) + t.shape).copy(), one)


def cache_specs(cfg):
    return {
        "state": ("layers", "batch", "ssm_heads", "ssm_state", None),
        "conv": ("layers", "batch", None, "ssm_inner"),
    }


def decode_step(cfg, params, cache, tokens, pos, *, positions=None):
    x = L.embed(cfg, params["embed"], tokens)

    def body(x, xs):
        lp, st, cv = xs
        h = L.apply_norm(cfg, x, lp["ln"])
        y, new = S.ssm_decode_step(cfg, lp["ssm"], h, {"state": st, "conv": cv})
        return x + y, (new["state"], new["conv"])

    x, (states, convs) = jax.lax.scan(body, x, (params["layers"], cache["state"], cache["conv"]))
    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], x)
    return logits, {"state": states, "conv": convs}


# ---------------------------------------------------------------------------
# paged serving contract (DESIGN.md §17)
# ---------------------------------------------------------------------------

def paged_spec(cfg):
    """Attention-free arch: a minimal 1x1 KV geometry keeps the engine's
    page machinery (tables, placement, defrag) uniform while the real
    memory — the recurrent state — rides as per-sequence resident state
    whose bytes the sequence's AGAS registration carries."""
    from repro.serving.paged import PAGE_SIZE, PageSpec

    return PageSpec(layers=1, page_size=PAGE_SIZE, kv_heads=1, head_dim=1, dtype=jnp.float32)


def paged_prefill(cfg, params, tokens, extras=None):
    """tokens: (B, T) -> (k, v, state, last_logits).

    k/v are zero dummies (nothing attends over them); ``state`` is the
    batch-leading {'state': (B, L, H, N, P), 'conv': (B, L, W-1, C)}
    recurrent cache the decode step threads.
    """
    x = L.embed(cfg, params["embed"], tokens)

    def body(x, lp):
        h = L.apply_norm(cfg, x, lp["ln"])
        y, cache = S.ssm_prefill(cfg, lp["ssm"], h)
        return x + y, (cache["state"], cache["conv"])

    x, (states, convs) = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], x[:, -1:])
    B, T = tokens.shape
    k = jnp.zeros((B, 1, T, 1, 1), jnp.float32)
    state = {"state": jnp.moveaxis(states, 0, 1), "conv": jnp.moveaxis(convs, 0, 1)}
    return k, k, state, logits[:, 0]


def paged_decode_step(cfg, params, k_pages, v_pages, state, tokens, positions, tables, lengths):
    """Pages pass through untouched; the recurrent state advances one
    token.  Position-free math, so ragged rows batch freely."""
    tokens = tokens.reshape(-1, 1)
    cache = {
        "state": jnp.moveaxis(state["state"], 0, 1),
        "conv": jnp.moveaxis(state["conv"], 0, 1),
    }
    logits, new = decode_step(cfg, params, cache, tokens, positions)
    state = {
        "state": jnp.moveaxis(new["state"], 0, 1),
        "conv": jnp.moveaxis(new["conv"], 0, 1),
    }
    return k_pages, v_pages, state, logits[:, 0]
