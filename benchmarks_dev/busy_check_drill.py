#!/usr/bin/env python3
"""The busy-check drill: the last phase of a serving cell's benchmark run,
many times over against one server.

    python benchmarks_dev/busy_check_drill.py --workload serve.qwen2_7b.batch \
        --seed 7 --rounds 200 --out chiprun_out/drill

A benchmark run of a serving cell ends with three greedy ``logprobs``
requests sent together into the engine while the closed loop's clients are
still asking (``benchmark/lib/serve_cell.py``: ``greedy_cases(..., "busy",
True)``), and one 500 there loses the run. That phase is 3 s of a 140 s run;
this drill starts the server as the harness does (the cell's arguments, the
harness's warm-up, the cell's traffic mix), keeps the mix's load on it and
repeats the phase ``--rounds`` times: every answer has to be 200.

Then the induced case: with half the clients still streaming, ``--rows``
requests of ``--tokens`` prompt tokens are released together behind a long
prefill (the harness's way of forming a group), twice. Such a wave is one
prefill call of rows x bucket; where that program cannot be built
(8 x 1,024 reads ``Used 18.28G of 15.75G hbm`` for qwen2_7b, 8 x 2,048
``16.83G``: ``--tokens 1500``) the engine has to run it as one-row calls
(``InferenceEngine._prefill_refused``): every request of both waves
answered, no stream ended, ``prefill_calls_split`` up by one, the server's
log naming the shape, the second wave compiling nothing.

Prints one JSON line; exit 0 when every check above held (``--expect-split``
makes the split itself a check: on the CPU nothing is refused). The server's
log goes to ``--out`` when anything failed. ``--rehearsal`` runs the cell's
tiny stand-in on the CPU. This process never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time
import urllib.error

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "lib"))

import harness  # noqa: E402
import loadgen  # noqa: E402
import serve_cell  # noqa: E402
import spec as spec_lib  # noqa: E402
import traffic as traffic_lib  # noqa: E402

ENGINE_COUNTERS = ("prefill_calls_split", "prefill_calls_failed",
                   "prefill_batches", "prefill_widest_call_tokens",
                   "decode_steps", "decode_rounds_launched_ahead",
                   "decode_rows_discarded", "preemptions")


def engine_counters(port: int) -> dict:
    """The engine stats this drill reads (a program older than a counter
    does not have it: left out)."""
    got = serve_cell.scrape(port)
    out = {}
    for name in ENGINE_COUNTERS:
        for key in ("dlti_" + name, "dlti_" + name + "_total"):
            if key in got:
                out[name] = got[key]
    return out


def start_server(r: harness.Run, port: int):
    """``serve_cell.run``'s own start: the cell's arguments, ready on
    ``/health``."""
    vocab = int(r.config["model"]["vocab_size"])
    argv = harness.flags(r.spec["args"]) + [
        "--random-init", r.model_name, "--tokenizer", f"id:{vocab}",
        "--port", str(port)]
    proc, log_path = r.spawn_entry("serve", r.spec["entry"], argv)
    r.wait_device(proc, log_path, time.time() + 300)
    deadline = time.time() + float(r.spec["setup_limit_s"])
    while True:
        if proc.poll() is not None:
            raise harness.RunFailure(
                f"scripts/serve.py exited {proc.returncode} before serving: "
                f"{harness.tail(log_path)}")
        if time.time() > deadline:
            raise harness.RunFailure("server not ready in time")
        try:
            if serve_cell.http(port, "/health", timeout=2.0)[0] == 200:
                return proc, log_path
        except (OSError, urllib.error.URLError):
            pass
        time.sleep(0.25)


def closed_loop(port: int, mix: dict, pool: list, clients: int):
    gen = loadgen.LoadGenerator(port)
    gen.start_closed(pool, clients, time.time() + 0.2,
                     float(mix["arrivals"]["stagger_s"]))
    return gen


def failed_streams(gen) -> list:
    return [x for x in list(gen.records) if loadgen.request_failed(x)]


def wave(port: int, warm: dict, vocab: int, rows: int, tokens: int,
         rng: random.Random) -> list:
    """A long prefill, and ``rows`` requests sent while it holds the engine:
    they wait together and are admitted in one pass."""
    reqs = [serve_cell._warm_request(rng, int(warm["blocker_tokens"]),
                                     vocab, 0)]
    reqs += [serve_cell._warm_request(rng, tokens, vocab, 1 + i)
             for i in range(rows)]
    return serve_cell._burst(port, reqs, float(warm["stagger_s"]))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="serve.qwen2_7b.batch")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--rounds", type=int, default=200)
    p.add_argument("--rows", type=int, default=8)
    p.add_argument("--tokens", type=int, default=700)
    p.add_argument("--expect-split", action="store_true")
    p.add_argument("--out", default="")
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args()

    cell = spec_lib.resolve_cell(args.workload)
    r = harness.Run(cell, args.seed, 0.0, False, args.rehearsal, T_START)
    mix = cell["traffic"]
    if args.rehearsal:
        mix = harness.overlay(mix, mix.get("rehearsal", {}))
    if mix["arrivals"]["loop"] != "closed":
        print("busy_check_drill: the cell's mix is not a closed loop",
              file=sys.stderr)
        return 2
    vocab = int(r.config["model"]["vocab_size"])
    clients = int(mix["arrivals"]["clients"])
    port = serve_cell._free_port()
    summary: dict = {"workload": args.workload, "seed": args.seed,
                     "rehearsal": args.rehearsal}
    problems: list = []
    log_path = None
    try:
        proc, log_path = start_server(r, port)
        serve_cell.warm_up(port, r.spec["warm_up"], vocab, summary)
        first = serve_cell.greedy_cases(port, r.spec["check"], vocab,
                                        "alone", False)
        summary["setup_s"] = round(time.time() - T_START, 1)

        # -- the busy check, over and over ---------------------------------
        pool = traffic_lib.request_pool(mix, int(mix["arrivals"]["pool"]),
                                        args.seed, vocab)
        gen = closed_loop(port, mix, pool, clients)
        time.sleep(float(mix["ramp_s"]))
        t_rounds = time.time()
        took, drift = [], 0
        for n in range(args.rounds):
            if proc.poll() is not None:
                raise harness.RunFailure(
                    f"scripts/serve.py exited {proc.returncode} in round {n}")
            t = time.time()
            try:
                cases = serve_cell.greedy_cases(port, r.spec["check"], vocab,
                                                "busy", True)
            except harness.RunFailure as e:
                problems.append(f"round {n}: {e}")
                break
            took.append(time.time() - t)
            drift += any(c["tokens"] != f["tokens"]
                         for c, f in zip(cases, first))
        summary["rounds_answered"] = len(took)
        summary["round_s"] = {"median": sorted(took)[len(took) // 2],
                              "max": max(took)} if took else None
        summary["rounds_with_other_tokens_than_alone"] = drift
        summary["compilations_in_rounds"] = len(
            r.compilations_between(t_rounds, time.time()))
        summary["streams_in_flight"] = gen.in_flight
        bad = failed_streams(gen)
        summary["streams_ended"] = len(gen.records)
        if bad:
            problems.append(f"{len(bad)} stream(s) of the closed loop "
                            f"failed: {bad[0]['error']}")
        summary["after_rounds"] = engine_counters(port)
        gen.stop(0.0)

        # -- the induced wave ----------------------------------------------
        if args.rows and not problems:
            half = max(1, clients // 2)
            gen = closed_loop(port, mix, pool, half)
            time.sleep(half * float(mix["arrivals"]["stagger_s"]) + 3.0)
            rng = random.Random(args.seed)
            waves = []
            for n in range(2):
                before, t0 = engine_counters(port), time.time()
                recs = wave(port, r.spec["warm_up"], vocab, args.rows,
                            args.tokens, rng)
                after = engine_counters(port)
                failed = [x for x in recs if loadgen.request_failed(x)]
                if failed:
                    problems.append(f"wave {n}: {len(failed)} of {len(recs)} "
                                    f"failed: {failed[0]['error']}")
                waves.append({
                    "seconds": round(time.time() - t0, 2),
                    "answered": len(recs) - len(failed),
                    "compilations": len(r.compilations_between(
                        t0, time.time())),
                    **{k: after[k] - before[k] for k in
                       ("prefill_calls_split", "prefill_calls_failed",
                        "prefill_batches") if k in after and k in before}})
            summary["waves"] = waves
            summary["streams_in_flight_after_waves"] = gen.in_flight
            bad = failed_streams(gen)
            if bad or gen.in_flight != half:
                problems.append(
                    f"the running streams did not all go on: {len(bad)} "
                    f"failed, {gen.in_flight} of {half} in flight")
            if waves[1]["compilations"]:
                problems.append("the second wave compiled "
                                f"{waves[1]['compilations']} program(s)")
            if args.expect_split and \
                    waves[0].get("prefill_calls_split") != 1:
                problems.append("prefill_calls_split did not rise by one in "
                                "the first wave")
            summary["after_waves"] = engine_counters(port)
            gen.stop(0.0)

        os.killpg(proc.pid, signal.SIGTERM)
        try:
            summary["server_exit"] = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            summary["server_exit"] = None
        if summary["server_exit"] != 0:
            problems.append(f"the server exited {summary['server_exit']} "
                            "on SIGTERM")
    except harness.NoAccelerator as e:
        print(f"busy_check_drill: no accelerator: {e}", file=sys.stderr)
        return 3
    except harness.RunFailure as e:
        problems.append(str(e))
    finally:
        r.stop_all()
    if log_path and os.path.isfile(log_path):
        lines = [x for x in harness.read_text(log_path).splitlines()
                 if "cpu_aot_loader" not in x]
        summary["log_refused"] = [x[:400] for x in lines
                                  if "prefill program refused" in x][:8]
        if problems and args.out:
            os.makedirs(args.out, exist_ok=True)
            shutil.copy(log_path, os.path.join(
                args.out, f"{args.workload}.drill.serve.log"))
    summary["problems"] = problems
    print(json.dumps(summary))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
