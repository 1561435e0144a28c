"""What the stepper's own books say of a window, from its two ``/metrics``
scrapes (``ctx["metrics_before"]``, ``ctx["metrics_after"]``: the whole
window, tracer off or on).

The program keeps an always-on phase clock on the thread that steps the
engine (``dlti_tpu/telemetry/ledger.py`` ``StepperAccount``): wall seconds by
innermost open phase in
``dlti_stepper_phase_seconds_total{phase="...",kind="..."}``, under the names
of the spans ``attribute_idle`` reads, summing to the thread's wall time. The
program says what each phase is in its ``kind`` label (``host``: work on the
path of a decode round; ``wait``: for work; ``device_wait``: for the device),
so no list of names is kept here. A labelled series is a key of a scrape with
its label text. A program without the series (the parent of the PR that added
this file) gives every reader here None.
"""

from __future__ import annotations

import re

import stats

PHASE_SECONDS = "dlti_stepper_phase_seconds_total"
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def scrapes(ctx) -> tuple:
    return ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}


def series(scrape: dict, family: str) -> list:
    """``[(labels, number)]`` of one family's labelled samples in a scrape,
    ``labels`` a dict."""
    head = family + "{"
    return [(dict(_LABEL.findall(k[len(head):])), v)
            for k, v in scrape.items() if k.startswith(head)]


def labelled(scrape: dict, family: str, label: str) -> dict:
    """``{label value: number}`` of one family's samples in a scrape."""
    return {labels[label]: v for labels, v in series(scrape, family)
            if label in labels}


def phase_seconds(before: dict, after: dict, names=None):
    """Seconds the window added to the phases ``names`` (None: to every
    phase of kind ``host``). A phase the first scrape lacks began at 0. None
    when the later scrape has none of them."""
    was = labelled(before, PHASE_SECONDS, "phase")
    mine = [(labels["phase"], v) for labels, v in series(after, PHASE_SECONDS)
            if (labels.get("kind") == "host" if names is None
                else labels.get("phase") in names)]
    if not mine:
        return None
    return sum(v - was.get(name, 0.0) for name, v in mine)


def ms_per_step(ctx, names=None):
    """``phase_seconds`` over the window's decode steps, in milliseconds;
    None without the series or in a window without a decode step."""
    a, b = scrapes(ctx)
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    seconds = phase_seconds(a, b, names)
    if not steps or seconds is None:
        return None
    return 1000.0 * seconds / steps


def window_seconds(ctx):
    """Between the two scrapes, by the stamps the harness gave them."""
    a, b = scrapes(ctx)
    if "_t" not in a or "_t" not in b or b["_t"] <= a["_t"]:
        return None
    return b["_t"] - a["_t"]
