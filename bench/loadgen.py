"""Seeded traffic: one generator for every mix in ``bench/traffic/``.

A mix file gives the arrival process and lognormal prompt and output
lengths (median, sigma, clip range, and for prompts a palette that lengths
are rounded up to).  The arrival process is open or closed:

* open, ``{"process": "gamma", "cv": ...}`` with the mix's mean
  ``rate_per_s``: Gamma inter-arrival times of the stated coefficient of
  variation (cv 1 is Poisson).  The lead-in and the measured window are two
  parts, each offered ``round(rate x its seconds)`` requests that span it
  exactly;
* closed, ``{"process": "closed", "clients": N, "requests": n}``: N clients
  that each send the next request the moment their previous one resolves.
  The schedule is one ordered list of ``n`` requests that carry no due time
  (``due_s`` 0), taken in order by whichever client frees first; ``n`` is
  many times what a run can serve, so the clients never run dry.

Every seed gets the same sizes (and gaps) in each part, in the same order:
the quantiles of the stated distributions, paired and ordered once by a
fixed permutation.  The seed draws only the prompt token ids.  So two seeds
offer the same work (at the same times), and a run's spread measures the
system rather than the draw.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
from scipy import stats


@dataclass(frozen=True)
class Request:
    rid: int
    due_s: float          # seconds after the schedule's origin
    prompt: np.ndarray    # (T,) int32
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)), int(seed < 0), stream]))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lognormal quantiles, clipped, rounded up to the palette if any."""
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * stats.norm.ppf(_quantiles(n)))
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)
    palette = spec.get("palette")
    if palette:
        pal = np.asarray(sorted(palette), np.int64)
        x = pal[np.minimum(np.searchsorted(pal, x, side="left"), pal.size - 1)]
    return x


def gaps(arrival: dict, rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival quantiles of a Gamma process with mean 1/rate."""
    if arrival.get("process", "gamma") != "gamma":
        raise ValueError(f"unknown arrival process {arrival.get('process')!r}")
    cv = float(arrival.get("cv", 1.0))
    shape = 1.0 / (cv * cv)
    g = stats.gamma.ppf(_quantiles(n), shape)
    return g * (n / rate) / g.sum()


def _sizes(traffic: dict, n: int, max_seq_len: int, base: np.random.Generator):
    """``n`` (prompt length, output length) pairs, paired and ordered by
    ``base``'s first two permutations."""
    prompt = lengths(traffic["prompt_tokens"], n)[base.permutation(n)]
    out = lengths(traffic["output_tokens"], n)[base.permutation(n)]
    return prompt, np.minimum(out, max_seq_len - prompt)


def _part(traffic: dict, n: int, span: float, offset: float, max_seq_len: int):
    """``n`` (due, prompt length, output length) spread over ``span`` seconds."""
    base = np.random.default_rng(0)  # fixed pairing and order, the same for every seed
    prompt, out = _sizes(traffic, n, max_seq_len, base)
    gap = gaps(traffic["arrival"], n / span, n)[base.permutation(n)]
    due = offset + np.concatenate([[0.0], np.cumsum(gap)[:-1]])
    return zip(due.tolist(), prompt.tolist(), out.tolist())


def is_closed(traffic: dict) -> bool:
    return traffic["arrival"].get("process") == "closed"


def schedule(traffic: dict, *, seed: int, seconds: float, vocab: int,
             max_seq_len: int, rate: "float | None" = None) -> "list[Request]":
    """An open loop's requests: the lead-in's, then the window's, due from
    the origin on.  A closed loop's: its ordered list, all due at 0."""
    rng = rng_for(seed, 1)
    if is_closed(traffic):
        if rate is not None:
            raise ValueError("a closed loop has no rate; vary its clients instead")
        n = int(traffic["arrival"]["requests"])
        prompt, out = _sizes(traffic, n, max_seq_len, np.random.default_rng(0))
        return [Request(i, 0.0, rng.integers(1, vocab, size=int(T), dtype=np.int32), int(new))
                for i, (T, new) in enumerate(zip(prompt.tolist(), out.tolist()))]
    rate = float(rate if rate is not None else traffic["rate_per_s"])
    lead = float(traffic["lead_in_s"])
    reqs: list = []
    for span, offset in ((lead, 0.0), (float(seconds), lead)):
        n = max(1, round(rate * span))
        for due, T, new in _part(traffic, n, span, offset, max_seq_len):
            toks = rng.integers(1, vocab, size=int(T), dtype=np.int32)
            reqs.append(Request(len(reqs), due, toks, int(new)))
    return reqs
