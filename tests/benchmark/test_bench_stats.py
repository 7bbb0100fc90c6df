"""The metric arithmetic on fixed ledger records."""

import pytest

from benchmark.harness import load, stats


def rec(i, first, duration, tokens, queue=0.0, outcome="ok"):
    return {"trace_id": load.trace_id(i), "route": "generate",
            "marks": {"admitted": queue, "first_token": first},
            "tokens_out": tokens, "duration_s": duration,
            "queue_wait_s": queue, "outcome": outcome}


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    (list(range(1, 11)), 90, 9.1),
    (list(range(1, 101)), 90, 90.1),
    ([5.0], 90, 5.0),
    ([2, 1], 100, 2.0),
    ([2, 1], 0, 1.0),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_tpot_is_the_mean_gap_after_the_first_token():
    r = rec(0, first=0.5, duration=2.5, tokens=11)
    assert stats.ttft_s(r) == 0.5
    assert stats.tpot_s(r) == pytest.approx(0.2)
    assert stats.tpot_s(rec(1, 0.5, 0.5, 1)) is None


def test_slot_occupancy():
    records = [rec(0, 0.1, 4.0, 10, queue=1.0), rec(1, 0.1, 2.0, 10)]
    assert stats.slot_occupancy(records, window_s=10.0, slots=2) == \
        pytest.approx((3.0 + 2.0) / 20.0)


def test_serving_summary_counts_every_completed_request():
    records = [rec(i, first=0.1 * (i + 1), duration=0.1 * (i + 1) + 1.0,
                   tokens=11) for i in range(10)]
    s = stats.serving_summary(records, window_s=5.0)
    assert s["completed"] == 10 and s["out_tokens"] == 110
    assert s["out_tokens_per_s"] == pytest.approx(22.0)
    assert s["ttft_p90_ms"] == pytest.approx(910.0)
    assert s["tpot_p90_ms"] == pytest.approx(100.0)


def test_window_records_joins_by_trace_id_and_counts_failures():
    t0, t1 = 100.0, 130.0
    sent = [
        # completed inside the window
        {"i": 0, "t_send": 99.0, "t_done": 101.0, "ok": True},
        {"i": 1, "t_send": 105.0, "t_done": 110.0, "ok": True},
        # returned an error inside the window
        {"i": 2, "t_send": 106.0, "t_done": 107.0, "ok": False},
        # completed before the window: not this window's
        {"i": 3, "t_send": 90.0, "t_done": 99.5, "ok": True},
        # sent in the first half, still open at the end: failed
        {"i": 4, "t_send": 110.0, "t_done": None, "ok": False},
        # sent in the second half, still open: neither
        {"i": 5, "t_send": 120.0, "t_done": 135.0, "ok": True},
        # ok for the client, but the ledger has no record of it: failed
        {"i": 6, "t_send": 101.0, "t_done": 102.0, "ok": True},
    ]
    ledger = [rec(i, 0.2, 1.0, 5) for i in (0, 1, 3, 5)]
    ledger.append(dict(rec(9, 0.2, 1.0, 5), route="predict"))
    completed, failed, attempted = load.window_records(sent, ledger, t0, t1)
    assert [r["i"] for r in completed] == [0, 1]
    assert failed == 3 and attempted == 5
