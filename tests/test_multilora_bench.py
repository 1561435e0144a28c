"""Pin for the multi-LoRA A/B microbench's committed artifact
(``results/multilora_cpu.json``, satellite of the multi-LoRA serving PR):
every request's tokens byte-identical between the shared-base engine and
the per-adapter merged engines, with a genuinely heterogeneous batch on
the measured path, and the weight-bytes arithmetic.

The artifact's generator (``benchmarks_dev/multilora_ab.py``) was never
committed — ``.gitignore`` listed ``benchmarks_dev/`` until PR 21 — so the
smoke test that re-ran it is gone with it; the pin on the artifact stays.
Equivalence itself is tier-1 in ``tests/test_adapters.py``."""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_committed_artifact_meets_the_bar():
    """The checked-in results/multilora_cpu.json is the PR's evidence;
    pin the acceptance bar (≥4 adapters concurrent on one engine,
    outputs_equal, lower total weight bytes) so a regenerated artifact
    that misses it fails CI instead of silently shipping."""
    path = os.path.join(REPO, "results", "multilora_cpu.json")
    report = json.loads(open(path).read())
    assert report["outputs_equal"] is True
    assert report["adapters"] >= 8
    assert report["max_concurrent_adapters"] >= 4
    assert (report["shared"]["weight_bytes"]["total"]
            < report["merged"]["weight_bytes"]["total"])
    assert report["weight_bytes_saving_frac"] >= 0.5
