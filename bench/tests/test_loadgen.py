"""The traffic generator: seeded, palette-rounded, the same work at the same
times for every seed, at the rate the mix states."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _sched(mix, seed, seconds=51.0):
    return loadgen.schedule(mix, seed=seed, seconds=seconds, vocab=50000, max_seq_len=2048)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = _sched(_mix(name), 7), _sched(_mix(name), 7)
    assert [(r.due_s, r.max_new) for r in a] == [(r.due_s, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_offer_the_same_work(name):
    mix = _mix(name)
    a, b = _sched(mix, 1), _sched(mix, 2**31 + 11)
    assert [r.prompt.tolist() for r in a[:3]] != [r.prompt.tolist() for r in b[:3]]
    key = lambda r: (r.due_s, r.prompt.size, r.max_new)  # noqa: E731
    assert list(map(key, a)) == list(map(key, b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_on_palette_and_in_range(name):
    mix = _mix(name)
    reqs = _sched(mix, 3)
    pal = set(mix["prompt_tokens"]["palette"])
    assert all(r.prompt.size in pal for r in reqs)
    o = mix["output_tokens"]
    assert all(o["min"] <= r.max_new <= o["max"] for r in reqs)
    assert all(r.prompt.size + r.max_new <= 2048 for r in reqs)
    assert all(r.prompt.dtype == np.int32 and r.prompt.min() >= 1 for r in reqs)


@pytest.mark.parametrize("name", MIXES)
def test_rate_reached(name):
    mix = _mix(name)
    lead = mix["lead_in_s"]
    for seed in (0, 5, 99):
        reqs = _sched(mix, seed)
        window = [r for r in reqs if lead <= r.due_s < lead + 51.0]
        assert len(window) == max(1, round(mix["rate_per_s"] * 51.0))
        assert len(reqs) - len(window) == max(1, round(mix["rate_per_s"] * lead))
        assert all(0 <= r.due_s < lead + 51.0 for r in reqs)
        assert all(a.due_s <= b.due_s for a, b in zip(reqs, reqs[1:]))


def test_burstiness_follows_cv():
    gp = loadgen.gaps({"process": "gamma", "cv": 1.0}, 2.0, 4000)
    gb = loadgen.gaps({"process": "gamma", "cv": 2.0}, 2.0, 4000)
    assert gp.mean() == pytest.approx(0.5) and gb.mean() == pytest.approx(0.5)
    assert gp.std() / gp.mean() == pytest.approx(1.0, rel=0.05)
    assert gb.std() / gb.mean() == pytest.approx(2.0, rel=0.15)


def test_lognormal_median_and_palette_rounding():
    spec = {"median": 1024, "sigma": 0.6, "min": 128, "max": 1792}
    x = loadgen.lengths(spec, 1001)
    assert np.median(x) == 1024 and x.min() >= 128 and x.max() <= 1792
    y = loadgen.lengths(dict(spec, palette=[128, 512, 1024, 1792]), 1001)
    assert set(y.tolist()) <= {128, 512, 1024, 1792} and np.all(y >= x)
