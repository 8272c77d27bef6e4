"""`run.slices`: the window as consecutive pieces, from recorded `due` and
`done` times — what one long run reads as many short windows with."""

import pytest

import loadgen
import run


def records(n=6000, interval=0.005, t_first=100.0, latency=0.1):
    """A launch of verdicts every 10 ms (two requests each), one request in
    97 refused; a 120 ms hole after the 2400th."""
    log, t = loadgen.RecordLog(), t_first
    for i in range(n):
        if i % 2 == 0:
            t += 2 * interval + (0.120 if i == 2400 else 0.0)
        refused = i % 97 == 0
        log.add(i % 50, t - latency, t - latency, t,
                None if refused else [True] * (1 + i % 8), "E" if refused else "")
    return log.records(), t


def test_record_log_round_trip():
    recs, _ = records(200)
    assert recs[0].verdicts is None and recs[0].error == "E"
    assert recs[1].verdicts == [True, True] and recs[1].req == 1
    assert [len(r.verdicts) for r in recs[1:9]] == [2, 3, 4, 5, 6, 7, 8, 1]
    assert recs[7].done - recs[7].due == pytest.approx(0.1)


def test_slices_add_up_to_the_window():
    recs, t_last = records()
    t0, t1 = 100.0, 130.05
    pieces = run.slices(recs, t0, t1, 10.0, [(112.045, 0.03, 2)])
    assert [round(p["seconds"], 2) for p in pieces] == [10.0, 10.0, 10.05]
    done = [r for r in recs if r.verdicts is not None and t0 <= r.done <= t1]
    issued = [r for r in recs if r.verdicts is not None and t0 <= r.due < t1]
    assert sum(p["requests_completed"] for p in pieces) == len(done)
    assert sum(p["candidates_completed"] for p in pieces) == sum(
        len(r.verdicts) for r in done)
    assert sum(p["latency_samples"] for p in pieces) == len(issued)
    for p in pieces:
        assert p["p50_ms"] == pytest.approx(100.0) and p["p95_ms"] == pytest.approx(100.0)
        assert p["candidates_per_s"] == pytest.approx(
            p["candidates_completed"] / p["seconds"])
        assert p["launches"] == p["completion"]["bursts"]
    # the hole falls in the second piece, and under the one long collection
    assert [p["completion"]["stalls"] for p in pieces] == [0, 1, 0]
    assert pieces[1]["completion"]["stalled_ms"] == pytest.approx(120.0, abs=0.5)
    assert pieces[1]["completion"]["stalls_with_collection"] == 1
    assert pieces[1]["collections"] == 1 and pieces[0]["collections"] == 0
    assert pieces[1]["longest_collection_ms"] == pytest.approx(30.0)


def test_no_slices_under_two_steps():
    recs, _ = records()
    assert run.slices(recs, 100.0, 119.9) == []
    assert len(run.slices(recs, 100.0, 120.0)) == 2
    assert len(run.slices(recs, 100.0, 130.0, 15.0)) == 2


def test_a_collection_shorter_than_the_limit_explains_no_stall():
    recs, _ = records()
    whole = run.stalls([r.done for r in recs], 100.0, 130.05, [(112.045, 0.01, 2)])
    assert whole["stalls"] == 1 and whole["stalls_with_collection"] == 0
    assert whole["stalled_ms_with_collection"] == 0.0
