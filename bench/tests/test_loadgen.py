"""The traffic generator: seeded, palette-rounded, the same work at the same
times for every seed, at the rate the mix states; a closed loop's one
ordered list; open-loop schedules unchanged since closed loops came in."""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


OPEN = [m for m in MIXES if not loadgen.is_closed(_mix(m))]
CLOSED = [m for m in MIXES if loadgen.is_closed(_mix(m))]


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


@pytest.mark.parametrize("name", OPEN)
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


def _digest(reqs):
    h = hashlib.sha256()
    for r in reqs:
        h.update(repr((r.rid, r.due_s, r.max_new)).encode())
        h.update(r.prompt.tobytes())
    return h.hexdigest()


# sha256 of each open-loop mix's schedule as the generator made it before it
# learned closed loops: (mix, vocab, seed, seconds) -> digest.
BEFORE_CLOSED_LOOPS = {
    ("conv", 50304, 2**31 + 5, 51.0): "1e25bef9e99e730331e00e90951056e26363cc07e0f470504b4ab203a4a33774",
    ("conv", 50304, 7, 2.0): "53714ec0c36240c4c73607e79e2d43bcad0b9081cd58e99ebf6edbeda1fd950d",
    ("conv-x4", 50304, 2**31 + 5, 51.0): "9226c04def904adae7f271dab6939629bca4aadff5da69b37a49ec52c7cdbfff",
    ("conv-x4", 50304, 7, 2.0): "39cfc2b24b8ef1568dcaa9d312c1679cdef046878079724d347c8ff3f5cc8e92",
    ("chat-burst", 50280, 2**31 + 5, 51.0): "2c32da590171fd69af3e9a9d2567f25aa6f279e4472a01a0de5315ff9baa9dfc",
    ("chat-burst", 50280, 7, 2.0): "4cfd2b461774fd5d6159f17511b755453dcd38a52f79a39b591dcaac624c5893",
}


@pytest.mark.parametrize("case", sorted(BEFORE_CLOSED_LOOPS))
def test_open_loop_schedules_are_byte_identical_to_before(case):
    name, vocab, seed, seconds = case
    reqs = loadgen.schedule(_mix(name), seed=seed, seconds=seconds, vocab=vocab, max_seq_len=2048)
    assert _digest(reqs) == BEFORE_CLOSED_LOOPS[case]


@pytest.mark.parametrize("name", CLOSED)
def test_closed_loop_is_one_ordered_list_of_the_quantiles(name):
    """One list of ``requests``, none due before another, whose sizes are
    the stated quantiles, the same for every seed and every window length."""
    mix = _mix(name)
    n = mix["arrival"]["requests"]
    a = _sched(mix, 2**31 + 3)
    b = _sched(mix, 11, seconds=5.0)
    assert len(a) == n and [r.rid for r in a] == list(range(n))
    assert all(r.due_s == 0.0 for r in a)
    assert [(r.prompt.size, r.max_new) for r in a] == [(r.prompt.size, r.max_new) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert sorted(r.prompt.size for r in a) == sorted(loadgen.lengths(mix["prompt_tokens"], n).tolist())
    assert sorted(r.max_new for r in a) == sorted(loadgen.lengths(mix["output_tokens"], n).tolist())
    # Ordered once by a fixed permutation: not sorted by size.
    assert [r.prompt.size for r in a] != sorted(r.prompt.size for r in a)


@pytest.mark.parametrize("name", CLOSED)
def test_closed_loop_takes_no_rate(name):
    with pytest.raises(ValueError, match="closed loop"):
        loadgen.schedule(_mix(name), seed=1, seconds=51.0, vocab=50000, max_seq_len=2048, rate=1.0)


def test_unknown_arrival_process_raises():
    mix = dict(_mix(OPEN[0]), arrival={"process": "weibull", "shape": 1.5})
    with pytest.raises(ValueError, match="unknown arrival process"):
        _sched(mix, 1)
