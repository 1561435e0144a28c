"""Pin for the flash-crowd SLO drill's committed artifact
(``results/slo_drill_cpu.json``, satellite of the SLO-engine PR): the
watchdog's ``slo_burn`` rule pages *before* the error budget is exhausted,
the burst costs latency but zero client errors, and loadgen's client-side
SLO recomputation agrees with the server's ``GET /debug/slo`` within 1% per
(objective, class) pair.

The artifact's generator (``benchmarks_dev/slo_drill.py``) was never
committed — ``.gitignore`` listed ``benchmarks_dev/`` until PR 21 — so the
smoke test that re-ran it is gone with it; the pin on the artifact stays."""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_committed_artifact_meets_the_bar():
    """The checked-in results/slo_drill_cpu.json is the PR's evidence;
    pin the acceptance bar so a regenerated artifact that misses it
    fails CI instead of silently shipping."""
    path = os.path.join(REPO, "results", "slo_drill_cpu.json")
    report = json.loads(open(path).read())
    assert report["pass"] is True
    c = report["criteria"]
    assert c["alert_fired"] and c["budget_remained_at_first_alert"]
    assert c["zero_client_errors"] and c["slo_agreement_within_1pct"]
    # The page landed early: well over half the budget was still there.
    assert report["alerts"]["first_alert"]["budget_remaining"] > 0.05
    assert report["alerts"]["first_alert"]["objective"] == "ttft"
    assert report["slo"]["max_delta"] <= 0.01
    assert report["load"]["errors"] == []
    # The committed trace replays to exactly the recorded request count.
    from dlti_tpu.benchmarks.traces import read_trace

    tpath = os.path.join(REPO, "results",
                         report["workload"]["trace_file"])
    header, events = read_trace(tpath)
    assert header["num_events"] == len(events) == \
        report["load"]["num_requests"]
