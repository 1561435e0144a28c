"""CI smoke for the host-overlap microbench (satellite of the
host-latency-hiding PR): the artifact generator must stay runnable and its
two headline claims must hold on a cold CPU run — prefetch stall strictly
below the no-prefetch stall, and one upload and one program call for every
decode round."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks_dev", "host_overlap.py")


@pytest.mark.slow
def test_host_overlap_bench_smoke(tmp_path):
    out = tmp_path / "host_overlap.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, BENCH, str(out)], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-1000:]
    report = json.loads(out.read_text())

    tr = report["train"]
    # Prefetch hides the synthetic gather delay: strictly less stall, and
    # the loss trajectory is untouched (bit-identical final loss).
    assert tr["prefetch_on"]["host_stall_s"] < tr["prefetch_off"]["host_stall_s"]
    assert tr["prefetch_on"]["final_loss"] == tr["prefetch_off"]["final_loss"]

    sv = report["serving"]["packed_rounds"]
    # Every decode round is one packed upload and one program call.
    assert sv["decode_steps"] > 0
    assert sv["decode_host_uploads"] == sv["decode_steps"]
    assert sv["decode_program_calls"] == sv["decode_steps"]
