"""The metric arithmetic: percentiles and the serving latencies, from exact
per-request records (never from histogram buckets)."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks (numpy's default rule), over all of `values`."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_s(rec: dict) -> float:
    """Request opened in the HTTP handler -> first token sampled."""
    return float(rec["marks"]["first_token"])


def tpot_s(rec: dict):
    """The request's mean gap between output tokens; None for a request of
    one token, which has no gap."""
    n = int(rec["tokens_out"])
    if n < 2:
        return None
    return (float(rec["duration_s"]) - ttft_s(rec)) / (n - 1)


def slot_occupancy(records, window_s: float, slots: int) -> float:
    """Share of slot-seconds of the window held by the requests that
    completed in it (queue wait is not in a slot)."""
    held = sum(float(r["duration_s"]) - float(r["queue_wait_s"])
               for r in records)
    return held / (window_s * slots)


def serving_summary(records, window_s: float) -> dict:
    """End-to-end serving numbers over ALL requests completed in the
    window: no trimming, no warm subset."""
    tokens = sum(int(r["tokens_out"]) for r in records)
    tpots = [t for t in map(tpot_s, records) if t is not None]
    return {
        "out_tokens_per_s": tokens / window_s,
        "ttft_p90_ms": 1e3 * percentile([ttft_s(r) for r in records], 90),
        "tpot_p90_ms": 1e3 * percentile(tpots, 90),
        "completed": len(records),
        "out_tokens": tokens,
    }
