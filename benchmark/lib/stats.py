"""Arithmetic from request records and step rows to numbers. Pure Python.

The measured window is ``[w0, w1)`` on the clock the records were stamped
with. What is counted, and why:

* a request is *counted* when it was due inside the window (open loop) or
  ended inside it (closed loop, where a request's start is the previous
  one's end and nothing is due);
* time to first token runs from when the request was due, not from when it
  was sent;
* a gap between tokens is counted when it ended inside the window, whichever
  request it belongs to, so the tail is the tail of everything users saw in
  the window and no request is left out for being long;
* completed output tokens per second is every token that arrived inside the
  window over the window's length.
"""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between order
    statistics (numpy's default). Raises on an empty list."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def gaps_in_window(records: list, w0: float, w1: float) -> list:
    """Seconds between consecutive tokens of a request, for every gap that
    ended in the window."""
    out = []
    for rec in records:
        times = rec["token_times"]
        for a, b in zip(times, times[1:]):
            if w0 <= b < w1:
                out.append(b - a)
    return out


def ttfts_due_in_window(records: list, w0: float, w1: float) -> list:
    """Seconds from due time to first token, for requests due in the window
    that produced a token."""
    return [rec["token_times"][0] - rec["due"] for rec in records
            if w0 <= rec["due"] < w1 and rec["token_times"]]


def tokens_in_window(records: list, w0: float, w1: float) -> int:
    return sum(1 for rec in records for t in rec["token_times"]
               if w0 <= t < w1)


def lateness(records: list, w0: float, w1: float) -> dict:
    """How late the generator sent what was due in the window (seconds)."""
    late = [rec["sent"] - rec["due"] for rec in records
            if w0 <= rec["due"] < w1 and rec["sent"] is not None]
    if not late:
        return {"n": 0}
    return {"n": len(late), "p50_s": percentile(late, 50),
            "p99_s": percentile(late, 99), "max_s": max(late)}


def counter_delta(before: dict, after: dict, name: str):
    """Change of a ``/metrics`` counter between two scrapes (the series may
    carry a ``_total`` suffix); None when a scrape lacks it."""
    def get(m):
        return m.get(name, m.get(name + "_total"))
    a, b = get(before), get(after)
    return None if a is None or b is None else b - a


def distribution(values: list) -> dict:
    if not values:
        return {"n": 0}
    return {"n": len(values), "min": min(values),
            "p50": percentile(values, 50), "p95": percentile(values, 95),
            "max": max(values), "sum": sum(values)}


def step_window(rows: list, warmup_steps: int, seconds: float) -> dict:
    """The training window from step rows stamped as they were seen
    (``seen`` = host time at which the step's results were on the host).

    It opens when step ``warmup_steps`` is done and closes with the last
    step done within ``seconds`` of that, so it holds whole steps only and
    a rate taken over it is not quantised by a step cut in two."""
    done = [r for r in rows if r["step"] >= warmup_steps]
    if len(done) < 2:
        raise ValueError(f"{len(done)} steps seen after warm-up; a window "
                         f"needs two")
    w0 = done[0]["seen"]
    inside = [r for r in done[1:] if r["seen"] <= w0 + seconds]
    if not inside:
        raise ValueError("no step finished inside the window")
    return {"w0": w0, "w1": inside[-1]["seen"], "rows": inside,
            "steps": len(inside), "seconds": inside[-1]["seen"] - w0}
