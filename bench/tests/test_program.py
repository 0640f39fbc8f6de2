"""The readers of the program's own spans and counters (``bench/program.py``
and the six metrics that use it) on hand-made traces whose values are
known, and on traces of a program that records none."""
import json
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import program, run, xplane
from bench.work import dense

DATA = Path(__file__).resolve().parent / "data"
CELL = "olmo-1b.conv"
READERS = ("admission_wait_ms", "decode_sitout_share", "kv_host_ms_per_ktok",
           "decode_state_host_ms", "host_copy_mb_per_token", "kv_spill_pages_per_ktok")


def _write_trace(root: Path, pbtxt: str) -> xplane.Trace:
    """Write a text-proto trace where ``run.py`` puts a cell's trace, and
    return it reduced as the harness hands it to the readers."""
    raw = ProfileData.text_proto_to_serialized_xspace((DATA / pbtxt).read_text())
    path = root / CELL / "plugins" / "profile" / "1" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    return xplane.reduce(ProfileData.from_serialized_xspace(raw))


def _run_data(trace, workload=None):
    cfg = json.loads((run.BENCH / "configs" / "olmo-1b.json").read_text())
    return run.RunData(cfg, workload or {"name": CELL, "chips": 1}, trace, [], [], [], 895,
                       {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, dense, 4)


@pytest.fixture
def trace_root(tmp_path, monkeypatch):
    monkeypatch.setattr(program, "TRACE_ROOT", tmp_path)
    return tmp_path


def test_counts_total_the_counter_events_inside_the_window(trace_root):
    data = _run_data(_write_trace(trace_root, "program.pbtxt"))
    c = program.counts(data)
    assert c["admitted"] == 2 and c["admission_wait_s"] == pytest.approx(0.30)
    assert c["prefill_tokens"] == 2048
    assert c["decode_steps"] == 2 and c["output_tokens"] == 5
    assert c["h2d_bytes"] == {"prefill_tokens": 4096, "state": 4000000}
    assert c["d2h_bytes"] == {"prompt_kv": 2 * 8388608, "logits": 400000, "state": 4000000}


def test_readers(trace_root):
    data = _run_data(_write_trace(trace_root, "program.pbtxt"))
    read = {name: run._reader(name)(data) for name in READERS}
    # Prefills B and C launched in the window, 0.25 s and 0.05 s after submission.
    assert read["admission_wait_ms"] == pytest.approx(150.0)
    # One of 3 and none of 2 active rows sat out the window's two steps.
    assert read["decode_sitout_share"] == pytest.approx(20.0)
    # B's KV round trip (10 + 4 ms) and C's, which runs past the window's
    # end (8 + 2 ms), over their 2 x 1,024 prompt tokens; A's is before it.
    assert read["kv_host_ms_per_ktok"] == pytest.approx(24.0 / 2.048)
    # State in and out of the window's two steps: (2 + 3) + (1 + 2) ms.
    assert read["decode_state_host_ms"] == pytest.approx(4.0)
    moved = 4096 + 4000000 + 2 * 8388608 + 400000 + 4000000
    assert read["host_copy_mb_per_token"] == pytest.approx(moved / 1e6 / 5)
    # Prompts prefilled in the window, and no page spilled or fetched back.
    assert read["kv_spill_pages_per_ktok"] == 0


def test_spill_reader_counts_the_window_only(trace_root):
    data = _run_data(_write_trace(trace_root, "spill.pbtxt"))
    c = program.counts(data)
    assert (c["prefill_tokens"], c["spilled_pages"], c["refetched_pages"]) == (2048, 48, 24)
    # (40 + 8) pages spilled and 24 fetched back over 2 x 1,024 prompt tokens.
    assert run._reader("kv_spill_pages_per_ktok")(data) == pytest.approx(72 / 2.048)


def test_readers_find_nothing_in_a_program_that_records_none(trace_root):
    """A trace of the benchmark's own spans alone, as the commits before the
    program's spans and counters give; a run without a trace file; and one
    without a trace."""
    data = _run_data(_write_trace(trace_root, "synthetic.pbtxt"))
    assert program.events(data) is None and program.counts(data) is None
    missing = _run_data(data.trace, {"name": "no-such-cell", "chips": 1})
    untraced = _run_data(None)
    for d in (data, missing, untraced):
        for name in READERS:
            assert run._reader(name)(d) is None, name
