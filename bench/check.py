"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the
finished requests, drawn from the seed with the longest among them, is run
through the family's plain reference (``bench/reference/<family>.py``) at
float32 and ``highest`` matmul precision, teacher-forced over prompt and
served tokens.  Three numbers are compared, each against the configuration
file's limit:

* ``choice_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best logit at that position, over every served
  token of the sample (the first comes from the prefill step, the rest from
  decode steps through the page pool or the resident state);
* ``prefill_logit_err``: the largest absolute difference between the last
  prompt position's logits that the timed prefill step returned and the
  reference's;
* ``stored_dtype_off``: how many floating dtypes other than the
  configuration's ``dtype`` the timed steps were handed or returned as
  weights, KV pages or resident state (exact, limit 0).

The control is the reference put in the program's place, one precision
step below what the configuration states.  The configuration stores
float32 and computes at the TPU's default matmul precision, one bfloat16
pass (its ``precision`` entry), so the step below is fp8: the control's
weights are rounded to float8_e4m3fn with one scale per matrix (per layer
for stacked ones), and it computes in bfloat16.  It is read at the same
positions (the gap of the token that it puts first, and its
last-prompt-position logits) and judged by ``decide`` against the same
limits as the program, where it has to come out not correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.loadgen import rng_for

NUMBERS = ("choice_gap", "prefill_logit_err", "stored_dtype_off")


def decide(got: dict, limits: dict) -> dict:
    """Each compared number beside its limit."""
    return {k: {"value": got[k], "limit": limits[k]} for k in NUMBERS if k in got}


def within(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())


def sample(finished, *, seed: int, min_tokens: int, max_requests: int, homes):
    """The longest finished request, then others drawn from the seed until
    the sample serves ``min_tokens`` tokens or holds ``max_requests``.
    ``homes`` gives the device each request was served on: the first drawn
    of each device that the sample lacks goes in before the rest, so that
    every weights replica and decode lane is checked."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(finished[i][0].size + finished[i][1].size))
    picked = [order[0]]
    rest = [order[i] for i in rng_for(seed, 2).permutation(len(order) - 1) + 1]
    seen = {homes[order[0]]}
    for i in rest:
        if homes[i] not in seen and len(picked) < max_requests:
            seen.add(homes[i])
            picked.append(i)
    rest = [i for i in rest if i not in picked]
    for i in rest:
        if len(picked) >= max_requests or sum(finished[j][1].size for j in picked) >= min_tokens:
            break
        picked.append(i)
    return [finished[i] for i in picked]


def _to_fp8(path, a):
    """A float32 weight matrix as (float8_e4m3fn values, float32 scale), one
    scale per matrix; anything else as it is."""
    stacked = any(getattr(k, "key", None) == "layers" for k in path)
    if a.dtype != jnp.float32 or a.ndim < (3 if stacked else 2):
        return a
    scale = jnp.max(jnp.abs(a), axis=(-2, -1), keepdims=True) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn), scale


def _from_fp8(a):
    if isinstance(a, tuple):
        q, scale = a
        return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)
    return a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a


def fp8_weights(params):
    """The control's weights: float32 matrices rounded to float8_e4m3fn with
    one scale per matrix (per layer for stacked ones), vectors (norm
    scales, biases, per-head SSM parameters) to bfloat16; all returned in
    bfloat16.  The fp8 values come out of one program and go into another,
    so no compiler can fold the rounding away as excess precision."""
    q = jax.jit(lambda p: jax.tree_util.tree_map_with_path(_to_fp8, p))(params)
    return jax.jit(lambda q: jax.tree_util.tree_map(_from_fp8, q, is_leaf=lambda x: isinstance(x, tuple)))(q)


class Reference:
    """Jitted teacher-forced reference over one padded sequence length."""

    def __init__(self, cfg: dict, ref_module, params, length: int):
        m = cfg["model"]
        self.length = int(length)
        self.params = params

        def f32(p, seq, targets, last):
            with jax.default_matmul_precision("highest"):
                logits = ref_module.forward(m, p, seq)
            chosen = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
            return logits.max(axis=1) - chosen, jax.lax.dynamic_index_in_dim(logits, last, 0, False)

        def low(p, seq, last):
            logits = ref_module.forward(m, p, seq)
            return logits.argmax(axis=1).astype(jnp.int32), jax.lax.dynamic_index_in_dim(logits, last, 0, False)

        self._f32 = jax.jit(f32)
        self._low = jax.jit(low)
        self._low_params = None

    def _pad(self, prompt, served):
        seq = np.zeros(self.length, np.int32)
        full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        seq[:full.size] = full
        return seq

    def program(self, prompt, served):
        """Gaps of the served tokens and the reference's last-prompt logits."""
        T, n = prompt.size, served.size
        tgt = np.zeros(self.length, np.int32)
        tgt[T - 1:T - 1 + n] = served
        gaps, row = self._f32(self.params, self._pad(prompt, served), tgt, np.int32(T - 1))
        return np.asarray(gaps)[T - 1:T - 1 + n], np.asarray(row)

    def control(self, prompt, served):
        """Gaps of the tokens the fp8 control puts first, and the distance
        of its last-prompt logits from the float32 reference's."""
        if self._low_params is None:
            self._low_params = fp8_weights(self.params)
        T, n = prompt.size, served.size
        seq = self._pad(prompt, served)
        top, low_row = self._low(self._low_params, seq, np.int32(T - 1))
        gaps, row = self._f32(self.params, seq, np.asarray(top), np.int32(T - 1))
        return (float(np.asarray(gaps)[T - 1:T - 1 + n].max()),
                float(np.max(np.abs(np.asarray(low_row) - np.asarray(row)))))


def compare(ref: Reference, picked, prefill_logits) -> dict:
    """The program's two numbers over the sample.  ``prefill_logits`` maps a
    prompt's bytes to the last logits its timed prefill returned."""
    gap, err, tokens = 0.0, 0.0, 0
    for prompt, served in picked:
        g, row = ref.program(prompt, served)
        gap = max(gap, float(g.max()))
        tokens += served.size
        got = prefill_logits.get(prompt.tobytes())
        err = max(err, float("inf") if got is None else float(np.max(np.abs(np.asarray(got) - row))))
    return {"choice_gap": gap, "prefill_logit_err": err, "requests": len(picked), "tokens": tokens}


def control(ref: Reference, picked) -> dict:
    gap, err = 0.0, 0.0
    for prompt, served in picked:
        g, e = ref.control(prompt, served)
        gap, err = max(gap, g), max(err, e)
    return {"choice_gap": gap, "prefill_logit_err": err}
