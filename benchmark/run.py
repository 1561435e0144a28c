#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Every run is a new process: it pays the whole set-up (start the program's
own entry point in a child that holds the chip, warm every shape the cell's
traffic uses), measures for ``--seconds``, prints one JSON object as the
last line of its standard output and exits 0. With ``--trace 0`` the metrics
are the cell's end-to-end metrics; with ``--trace 1`` the same run carries a
short profiler window and the metrics are the cell's per-layer metrics.
What a cell is, is data: see ``benchmark/lib/spec.py``.

Without an accelerator, or with fewer chips than the cell asks for, the run
prints no result and exits 3. ``--rehearsal`` (not a measurement: the result
says ``"rehearsal": true`` and the platform it ran on) runs the cell's tiny
stand-in on the CPU backend, to debug the harness.

This process never imports JAX: the chip belongs to the child.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "lib"))

import harness  # noqa: E402
import spec as spec_lib  # noqa: E402

EXIT_NO_ACCELERATOR = 3


def _reduce_trace(run: harness.Run, profile_dir: str):
    """The trace reduced in a process of its own (reading it imports jax)."""
    out = run.path("trace_reduced.json")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "lib", "reduce_trace.py"),
         profile_dir, "--out", out], env=env, cwd=run.root,
        capture_output=True, text=True, timeout=300)
    if proc.returncode != 0 or not os.path.isfile(out):
        raise harness.RunFailure(
            f"reducing the trace failed: {proc.stderr[-800:]}")
    return harness.read_json(out)


def _metrics(names: list, values: dict) -> dict:
    out = {}
    for m in names:
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true")
    p.add_argument("--sweep", default="", help="rates (req/s, comma-"
                   "separated) to offer in turn before the run proper: how "
                   "a serving cell's knee is found; not for measurements")
    args = p.parse_args()
    try:
        cell = spec_lib.resolve_cell(args.workload)
    except spec_lib.SpecError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    entry = os.path.join(cell["root"], cell["cell"].get("entry", ""))
    if not os.path.isfile(entry):
        print(f"benchmark/run.py: {entry} is not here: the benchmark runs "
              f"from a checkout of the program", file=sys.stderr)
        return 2
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                      args.rehearsal, T_START,
                      [float(x) for x in args.sweep.split(",") if x])
    try:
        if cell["cell"]["kind"] == "train":
            import train_cell as kind
        else:
            import serve_cell as kind
        res = kind.run(run)
        values = dict(res["e2e"])
        values["setup_s"] = res["w0"] - T_START
        device = run.device(res["facts"])
        result = {"correct": res["correct"], "attempted": res["attempted"],
                  "failed": res["failed"]}
        if args.trace:
            reduced = None
            if res.get("profile_dir"):
                reduced = _reduce_trace(run, res["profile_dir"])
            if not reduced and not args.rehearsal:
                raise harness.RunFailure(
                    "the traced run left no device trace to read")
            ctx = {**res, "trace": reduced, "values": values,
                   "config": run.config, "spec": run.spec, "cell": cell,
                   "device": device, "seconds": args.seconds}
            layer = {}
            for m in cell["per_layer"]:
                layer[m["name"]] = spec_lib.load_layer_reader(
                    m["name"], cell["bench_dir"])(ctx)
            result["metrics"] = _metrics(cell["per_layer"], layer)
            if reduced:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                result["breakdown"] = {"device_ops": reduced["device_ops"],
                                       "idle_gaps": reduced["idle_gaps"]}
            run.notes["end_to_end_in_traced_run"] = values
        else:
            result["metrics"] = _metrics(cell["end_to_end"], values)
        result["device"] = device
        if args.rehearsal:
            result["rehearsal"] = True
        run.notes["total_s"] = time.time() - T_START
        print(json.dumps({"notes": run.notes}))
        print(json.dumps(result))
        sys.stdout.flush()
        return 0
    except harness.NoAccelerator as e:
        print(f"benchmark/run.py: no accelerator: {e}", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    except harness.RunFailure as e:
        print(f"benchmark/run.py: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        run.stop_all()


if __name__ == "__main__":
    sys.exit(main())
