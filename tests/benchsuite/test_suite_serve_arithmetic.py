"""The serve cells' arithmetic on records written by hand: which requests a
window judges, what a rate and a percentile are taken over, and what a
failed request does to them."""

from __future__ import annotations

import pytest

from benchmarks.suite.kinds import serve


def _record(due, chunks, budget, error=""):
    rec = {"index": 0, "due": due, "sent": due + 0.001, "n_prompt": 10,
           "budget": budget, "chunks": chunks, "error": error,
           "tokens": [1] * sum(n for _, n in chunks), "prompt": [0] * 10,
           "end": chunks[-1][0] if chunks else due + 1.0}
    serve._finish(rec)
    return rec


def test_nearest_rank_percentiles():
    values = list(range(1, 11))
    assert serve.quantile(values, 90) == 9
    assert serve.quantile(values, 50) == 5
    assert serve.quantile([7.0], 90) == 7.0
    assert serve.quantile([], 90) is None


def test_per_request_readings_from_chunk_stamps():
    rec = _record(10.0, [(10.5, 9), (10.7, 8), (11.3, 8)], 25)
    assert rec["ok"] and rec["ttft_s"] == pytest.approx(0.5)
    assert rec["tpot_ms"] == pytest.approx(800.0 / 24)
    assert rec["stall_ms"] == pytest.approx(600.0)
    assert rec["gen_lag_ms"] == pytest.approx(1.0)
    short = _record(10.0, [(10.5, 9)], 25)
    assert not short["ok"]  # 9 of 25 tokens: not its budget


def test_closed_loop_judges_what_finished_in_the_window():
    cell = {"traffic": {"loop": "closed"}}
    records = [
        _record(0.5, [(1.0, 4), (3.0, 4)], 8),     # before the window
        _record(4.0, [(6.0, 4), (8.0, 4)], 8),     # inside
        _record(9.0, [(12.0, 4), (16.0, 4)], 8),   # ends after it
    ]
    run = {"t0": 5.0, "t1": 15.0, "window_s": 10.0, "records": records,
           "setup_s": 1.0}
    got = serve.summarise(cell, run)
    assert got["attempted"] == 1 and got["failed"] == 0
    assert len(got["judged"]) == 1
    # Tokens count by when they arrived: 4 + 4 of the second, 4 of the third.
    assert got["end_to_end"]["out_tok_s"] == pytest.approx(12 / 10.0)
    assert got["end_to_end"]["tpot_p90_ms"] == pytest.approx(2000.0 / 7)


def test_open_loop_judges_what_was_due_and_a_failure_never_answers():
    cell = {"traffic": {"loop": "open"}}
    records = [_record(5.0 + i, [(5.2 + i, 4), (5.4 + i, 4)], 8)
               for i in range(9)]
    records.append(_record(14.5, [], 8, error="refused"))
    records.append(_record(20.0, [(20.1, 8)], 8))  # due after the window
    run = {"t0": 5.0, "t1": 15.0, "window_s": 10.0, "records": records,
           "setup_s": 1.0}
    got = serve.summarise(cell, run)
    assert got["attempted"] == 10 and got["failed"] == 1
    assert got["end_to_end"]["ttft_p50_s"] == pytest.approx(0.2)
    # Nine answered in 0.2 s, one never: the 90th percentile is still one
    # that answered, the 100th would not be.
    assert got["end_to_end"]["ttft_p90_s"] == pytest.approx(0.2)
    assert serve.quantile(
        [0.2] * 9 + [float("inf")], 100) == float("inf")
