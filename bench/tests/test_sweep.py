"""The client sweep's table and its reading of saturation, on made-up runs."""
from bench import sweep

# tokens/s, failed, left-out and active row steps, pages spilled and refetched
RUNS = {4: (63.0, 0, 0, 100, 0, 0), 8: (102.0, 0, 0, 100, 0, 0), 16: (112.0, 0, 30, 100, 5, 4),
        24: (114.0, 2, 50, 100, 9, 8)}


def _child(workload, seed, seconds, flag, n):
    tps, failed, out, active, spilled, refetched = RUNS[int(n)]
    counted = {"decode_left_out_row_steps": out, "decode_active_row_steps": active}
    if spilled:
        counted.update(spilled_pages=spilled, refetched_pages=refetched)
    return {"seed": seed, "rc": 0, "output_tokens_per_s": tps, "failed": failed, "correct": not failed,
            "counted_in_window": counted}


def test_clients_sweep_reads_the_counters_and_the_saturation(monkeypatch, capsys):
    monkeypatch.setattr(sweep, "child", _child)
    assert sweep.main(["--workload", "w", "--clients", "4,8,16,24", "--seconds", "1"]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("SWEEP ")]
    table = [ln.split(" | ") for ln in lines[1:-1]]
    assert [r[0] for r in table] == ["SWEEP 4", "SWEEP 8", "SWEEP 16", "SWEEP 24"]
    # 16 -> 24 adds under 3%: 16 is where the loop saturates; 24 fails requests.
    assert lines[-1] == "SWEEP saturated_at 16 failing [24]"
    cols = lines[0][len("SWEEP "):].split(" | ")
    row16 = dict(zip(cols, table[2]))
    assert (row16["decode_sitout_share"], row16["spilled_pages"], row16["refetched_pages"]) == ("30.0", "5", "4")
    assert abs(float(row16["gain"]) - (112 / 102 - 1)) < 1e-12


def test_rates_sweep_reads_the_made_share(monkeypatch, capsys):
    def child(workload, seed, seconds, flag, rate):
        assert flag == "--rate"
        made = {"0.5": 95, "1.0": 80}[rate]
        return {"seed": seed, "rc": 0, "failed": 0, "tokens_in_window": made, "offered_tokens_in_window": 100}

    monkeypatch.setattr(sweep, "child", child)
    assert sweep.main(["--workload", "w", "--rates", "0.5,1.0", "--seconds", "1"]) == 0
    rows = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("SWEEP ")]
    assert [r.split(" | ")[-1] for r in rows[1:]] == ["True", "False"]  # made_share 0.95 holds, 0.8 does not
