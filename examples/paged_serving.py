"""Paged-KV serving demo (DESIGN.md §15).

A toy GQA language model is served by ``PagedServeEngine``: prompts of
assorted lengths are prefilled in token-budgeted groups, their KV lands
in fixed-size pages from a per-device ``PagePool``, and a continuous
decode lane steps every resident sequence over its page table through
the ``paged_attention`` reference kernel.  The same prompts then run
one-at-a-time for comparison, and the generated tokens are asserted
identical — paging and batching change the schedule, never the math.

    PYTHONPATH=src python examples/paged_serving.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

PAGE = 8
MAXLEN = 64
VOCAB = 128
HEADS = 4
KV_HEADS = 2
HEAD_DIM = 16


def make_model():
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention.ref import paged_attention_ref

    Dm = HEADS * HEAD_DIM
    rng = np.random.default_rng(0)
    s = 1.0 / np.sqrt(Dm)
    shapes = {"emb": (VOCAB, Dm), "wq": (Dm, Dm), "wk": (Dm, KV_HEADS * HEAD_DIM),
              "wv": (Dm, KV_HEADS * HEAD_DIM), "wu": (Dm, VOCAB)}
    params = {n: rng.normal(size=sh).astype(np.float32) * s for n, sh in shapes.items()}

    @jax.jit
    def prefill_fn(p, tokens, extras):
        x = p["emb"][tokens]                              # (B, T, Dm)
        B, T, _ = x.shape
        k = (x @ p["wk"]).reshape(B, T, KV_HEADS, HEAD_DIM)
        v = (x @ p["wv"]).reshape(B, T, KV_HEADS, HEAD_DIM)
        q = (x[:, -1] @ p["wq"]).reshape(B, KV_HEADS, HEADS // KV_HEADS, HEAD_DIM)
        sc = jnp.einsum("bkrd,btkd->bkrt", q, k) / np.sqrt(HEAD_DIM)
        o = jnp.einsum("bkrt,btkd->bkrd", jax.nn.softmax(sc, -1), v)
        # KV gains a layer axis; no resident state; the engine samples
        return k[:, None], v[:, None], None, o.reshape(B, Dm) @ p["wu"]

    @jax.jit
    def decode_fn(p, kp, vp, state, tokens, positions, tables, lengths):
        x = p["emb"][tokens]                              # (b, Dm)
        b = tokens.shape[0]
        q = (x @ p["wq"]).reshape(b, HEADS, HEAD_DIM)
        k = (x @ p["wk"]).reshape(b, KV_HEADS, HEAD_DIM)
        v = (x @ p["wv"]).reshape(b, KV_HEADS, HEAD_DIM)
        page = tables[jnp.arange(b), positions // PAGE]
        kp = kp.at[0, page, positions % PAGE].set(k)      # scatter the new token
        vp = vp.at[0, page, positions % PAGE].set(v)
        o = paged_attention_ref(q, kp[0], vp[0], tables, lengths + 1)
        return kp, vp, None, o.reshape(b, Dm) @ p["wu"]

    return params, prefill_fn, decode_fn


def main() -> None:
    import jax

    from repro.serving import LanePolicy, PagedKVCache, PagedServeEngine, PageSpec

    params, prefill_fn, decode_fn = make_model()
    # prefill groups same-length prompts (no intra-group padding), so the
    # stream repeats a few lengths the way real traffic repeats templates
    prompts = [np.arange(n, dtype=np.int32) % VOCAB
               for n in (5, 5, 5, 12, 12, 12, 30, 30)]
    new = 8

    def serve(label, **policies):
        kv = PagedKVCache(PageSpec(1, PAGE, KV_HEADS, HEAD_DIM), pool_pages=64)
        # one params replica per pool device, keyed by device key
        weights = {key: jax.device_put(params, pool.device.jax_device)
                   for key, pool in kv.pools.items()}
        with PagedServeEngine(kv, prefill_fn, decode_fn, weights=weights,
                              max_seq_len=MAXLEN, name=label, **policies) as eng:
            futs = [eng.submit(p, max_new_tokens=new) for p in prompts]
            outs = [np.asarray(f.get()) for f in futs]
            m = eng.metrics()
        print(f"{label:>10}: {m['prefill_batches']} prefill batches, "
              f"{m['decode_steps']} decode steps, "
              f"waste {m['padding_waste']:.2f}, "
              f"spilled {m['kv']['spilled_bytes']} B")
        return outs

    # one sequence at a time: every prompt pays its own prefill + decode
    serial = serve("serial", prefill=LanePolicy(max_batch=1, max_delay_s=0.0),
                   decode=LanePolicy(max_batch=1, max_delay_s=0.0))
    # disaggregated: grouped prefill, continuous batched decode
    paged = serve("paged",
                  prefill=LanePolicy(max_batch=8, max_delay_s=0.05,
                                     token_budget=128),
                  decode=LanePolicy(max_batch=8, max_delay_s=0.02),
                  decode_shapes=(1, 2, 4, 8))

    assert all(np.array_equal(a, b) for a, b in zip(serial, paged)), \
        "schedules diverged"
    print("tokens identical across schedules OK")


if __name__ == "__main__":
    main()
