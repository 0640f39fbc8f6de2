"""The trace reduction (``bench/xplane.py``) and the per-layer readers
(``bench/metrics``) on a hand-made trace with known intervals, and the
reduction on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import run, xplane
from bench.work import dense

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6  # ns


@pytest.fixture(scope="module")
def synthetic():
    raw = ProfileData.text_proto_to_serialized_xspace((DATA / "synthetic.pbtxt").read_text())
    return xplane.reduce(ProfileData.from_serialized_xspace(raw))


def test_window_devices_and_busy(synthetic):
    assert synthetic.window == (0.0, 100 * MS)
    assert synthetic.window_s == pytest.approx(0.1)
    assert synthetic.devices == [0]
    assert xplane.busy_s(synthetic) == pytest.approx(0.042)
    assert xplane.idle_gaps(synthetic, 0) == [
        (0.0, 10 * MS), (20 * MS, 21 * MS), (23 * MS, 30 * MS), (40 * MS, 45 * MS),
        (55 * MS, 70 * MS), (80 * MS, 100 * MS)]


MODULES = {"prefill": "jit__unknown", "decode": "jit__unknown", "kv_write": "jit__slab_scatter"}


def test_executions_go_to_the_spans_that_launched_them(synthetic):
    assert [e[1:] for e in xplane.executions(synthetic, "jit__unknown")] == [
        (10 * MS, 20 * MS), (30 * MS, 40 * MS), (45 * MS, 55 * MS), (70 * MS, 80 * MS)]
    assert xplane.calls(synthetic, MODULES, "decode") == [
        (0, 30 * MS, 40 * MS), (1, 45 * MS, 55 * MS), (2, 70 * MS, 80 * MS)]
    assert xplane.calls(synthetic, MODULES, "prefill") == [(0, 10 * MS, 20 * MS)]
    assert len(xplane.executions(synthetic, "jit__slab_scatter")) == 2


def test_breakdown(synthetic):
    b = xplane.breakdown(synthetic)
    ops = dict(b["device_ops"])
    assert ops["%gather.3"] == pytest.approx(0.018) and ops["%fusion.1"] == pytest.approx(0.010)
    # Each gap goes to the host event overlapping it most (the shorter on a tie).
    assert [g[0] for g in b["idle_gaps"]] == [
        "np.asarray(jax.Array)", "bench.complete", "bench.prefill", "np.asarray(jax.Array)",
        "bench.decode", "np.asarray(jax.Array)"]
    assert b["idle_gaps"][0][1] == pytest.approx(0.020)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def _run_data(trace):
    cfg = json.loads((Path(run.BENCH) / "configs" / "olmo-1b.json").read_text())
    decodes = [(8, np.array([1, 2]), np.array([100, 200]), 0),
               (8, np.array([1, 2]), np.array([101, 201]), 0),
               (4, np.array([3]), np.array([50]), 0)]
    samples = [(0.0, 3, (100,)), (0.1, 4, (300,)), (0.2, 2, (150,))]
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return run.RunData(cfg, {"chips": 1}, trace, decodes, [1024], samples, 1535,
                       peak, dense, 4), cfg, peak


def test_readers(synthetic):
    data, cfg, peak = _run_data(synthetic)
    m = cfg["model"]
    read = {name: run._reader(name)(data) for name in (
        "prefill_ms_per_ktok", "decode_step_ms", "decode_host_gap_ms", "kv_write_ms_per_ktok",
        "kv_pages_used_share", "decode_roofline", "step_mfu", "device_idle_share")}
    assert read["prefill_ms_per_ktok"] == pytest.approx(10 / 1.024)
    assert read["decode_step_ms"] == pytest.approx(10.0)
    # Calls 0 -> 1 share rows (one token longer): counted; 1 -> 2 share none
    # and 2 is under the row cap: the lane may have waited, not counted.
    assert read["decode_host_gap_ms"] == pytest.approx(5.0)
    assert read["kv_write_ms_per_ktok"] == pytest.approx(2 / 1.024)
    assert read["kv_pages_used_share"] == pytest.approx(100 * 300 / 1535)
    least = sum(max(f / peak["flops_per_s"], b / peak["hbm_bytes_per_s"])
                for f, b in (dense.decode(m, d[2], 4) for d in data.decodes))
    assert read["decode_roofline"] == pytest.approx(100 * least / 0.030)
    flops = dense.prefill(m, 1024, 4)[0] + sum(dense.decode(m, d[2], 4)[0] for d in data.decodes)
    assert read["step_mfu"] == pytest.approx(100 * flops / (0.1 * peak["flops_per_s"]))
    assert read["device_idle_share"] == pytest.approx(58.0)


def test_readers_find_nothing_without_a_trace(synthetic):
    data, _, _ = _run_data(None)
    for name in ("prefill_ms_per_ktok", "decode_step_ms", "decode_host_gap_ms", "kv_write_ms_per_ktok",
                 "decode_roofline", "step_mfu", "device_idle_share"):
        assert run._reader(name)(data) is None
    data.samples = []
    assert run._reader("kv_pages_used_share")(data) is None


@pytest.fixture(scope="module")
def two_devices():
    raw = ProfileData.text_proto_to_serialized_xspace((DATA / "two_devices.pbtxt").read_text())
    return xplane.reduce(ProfileData.from_serialized_xspace(raw))


def test_each_device_gets_its_own_calls(two_devices):
    """Two lanes' calls interleave in time on two threads; an execution goes
    to a span of its own device, and the prefill and decode step that one
    device runs in turn go to their own spans."""
    t = two_devices
    assert t.devices == [0, 1]
    assert xplane.calls(t, MODULES, "decode") == [
        (0, 10 * MS, 14 * MS), (1, 12 * MS, 16 * MS), (3, 19 * MS, 23 * MS),
        (2, 20 * MS, 24 * MS), (4, 35 * MS, 39 * MS)]
    assert xplane.calls(t, MODULES, "prefill") == [(0, 27 * MS, 35 * MS)]
    assert xplane.span_call("bench.decode#3:2@1") == (3, 1)
    assert xplane.span_call("bench.decode#3:2") == (3, None)


def test_readers_over_two_devices(two_devices):
    data, _, _ = _run_data(two_devices)
    data.decodes = [(2, np.array([1]), np.array([100]), 0), (2, np.array([1]), np.array([50]), 1),
                    (2, np.array([1]), np.array([101]), 0), (2, np.array([1]), np.array([51]), 1),
                    (2, np.array([1]), np.array([102]), 0)]
    data.samples = [(0.0, 2, (10, 40)), (0.1, 3, (60, 20)), (0.2, 3, (30, 55))]
    data.pool_pages = 100
    # Each lane's consecutive calls pair up (0 -> 2 -> 4 on device 0, 1 -> 3
    # on device 1); the device-0 gap 24-35 ms holds the prefill's 8 ms.
    assert run._reader("decode_host_gap_ms")(data) == pytest.approx((6 + 3 + 3) / 3)
    assert run._reader("kv_pages_used_share")(data) == pytest.approx(60.0)
    assert run._reader("decode_step_ms")(data) == pytest.approx(4.0)
    # The devices are busy 20 and 8 ms of the 50 ms window.
    assert run._reader("device_idle_share")(data) == pytest.approx(100 * (1 - 0.014 / 0.05))


def test_recorded_tpu_trace():
    """``record_trace.py`` on a TPU v5 lite: three calls of each program,
    launched inside ``bench.prefill``/``bench.decode`` spans."""
    t = xplane.load(str(DATA / "small.xplane.pb"))
    assert t.devices == [0] and 0.05 < t.window_s < 0.5
    modules = {"prefill": "jit_prefill_step", "decode": "jit_decode_step"}
    for kind in modules:
        got = xplane.calls(t, modules, kind)
        assert [i for i, _, _ in got] == [0, 1, 2]
        assert all(b > a for _, a, b in got)
    busy = xplane.busy_s(t)
    assert 0 < busy < t.window_s
    assert xplane.idle_gaps(t, 0) and len(xplane.breakdown(t)["idle_gaps"]) <= 10
