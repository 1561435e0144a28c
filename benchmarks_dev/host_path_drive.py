"""What a streamed token costs the host, read here on the CPU.

``scripts/serve.py`` of a checkout under the harness's own shim and load
generator: the rehearsal stand-in of ``serve.phi4_mini_flash.reasoning_turns``
(12 tiny layers; its window and model length raised to the cell's), the
cell's server arguments and its traffic file, so 32 streams of 256-704
tokens each. A token an event through 32 handler threads under one
interpreter lock is the same work here as on the chip's host; the tiny
model's step is not, so read the counters and not the tokens per second:

    handler_cpu_us_per_event   d dlti_sse_handler_cpu_seconds_total
                               / d dlti_sse_events_total
    host_ms_per_step           the stepper's host phases a decode step

    python3 benchmarks_dev/host_path_drive.py [CHECKOUT] [--ramp 40] [--seconds 30]

One JSON line. Alternate two checkouts (before, after, before, after): one
run differs from the next by a tenth on a busy sandbox. PR 53's refusal
round read 233, 249 -> 79, 134 us an event for the whole-answer decode
taken out of the stream handler; the chip's traced run then 527 -> 414.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "benchmark", "lib"))
import loadgen  # noqa: E402
import serve_cell  # noqa: E402
import stats  # noqa: E402
import traffic as traffic_lib  # noqa: E402

CELL = "serve.phi4_mini_flash.reasoning_turns"


def stand_in() -> tuple:
    """(configuration, server arguments, traffic): the cell at its
    rehearsal sizes, the window and the
    model length at the cell's own."""
    def read(*parts):
        with open(os.path.join(HERE, "benchmark", *parts)) as f:
            return json.load(f)

    config = read("configs", "phi4_mini_flash.json")
    over = read("cells", CELL + ".json")["rehearsal"]
    config["model"] = {**config["model"], **over["model_overrides"],
                       "max_position_embeddings": 2048,
                       "sliding_window": 512}
    program = dict(over["program_overrides"])
    program["layer_windows"] = [512 if w else 0
                                for w in program["layer_windows"]]
    config["program"] = {**config.get("program", {}), **program}
    return config, read("cells", CELL + ".json")["args"], \
        read("traffic", "reasoning_turns.json")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("checkout", nargs="?", default=HERE)
    p.add_argument("--ramp", type=float, default=40.0)
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args()
    root = os.path.abspath(args.checkout)
    config, server_args, mix = stand_in()
    vocab = int(config["model"]["vocab_size"])
    out = tempfile.mkdtemp(prefix="host_path_drive.")
    model_file = os.path.join(out, "model.json")
    with open(model_file, "w") as f:
        json.dump(config, f)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    argv = []
    for k, v in {**server_args, "--kv-cache-dtype": "float32"}.items():
        argv += [k, str(v)]
    cmd = [sys.executable,
           os.path.join(root, "benchmark", "lib", "chip_child.py"),
           "--entry", "scripts/serve.py", "--model-file", model_file,
           "--model-name", "bench_stand_in",
           "--events", os.path.join(out, "events.jsonl"),
           "--facts", os.path.join(out, "device.json"), "--", *argv,
           "--random-init", "bench_stand_in", "--tokenizer", f"id:{vocab}",
           "--port", str(port)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONUNBUFFERED": "1"}
    with open(os.path.join(out, "serve.log"), "wb") as log:
        proc = subprocess.Popen(cmd, cwd=root, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    try:
        deadline = time.time() + 300
        while True:
            try:
                if serve_cell.http(port, "/health", timeout=2)[0] == 200:
                    break
            except OSError:
                pass
            if proc.poll() is not None or time.time() > deadline:
                sys.exit(f"the server did not start: {out}/serve.log")
            time.sleep(0.5)
        pool = traffic_lib.request_pool(mix, int(mix["arrivals"]["pool"]),
                                        7, vocab)
        gen = loadgen.LoadGenerator(port)
        t0 = time.time() + 0.5
        gen.start_closed(pool, int(mix["arrivals"]["clients"]), t0,
                         float(mix["arrivals"]["stagger_s"]))
        w0 = t0 + args.ramp
        w1 = w0 + args.seconds
        time.sleep(max(0.0, w0 - time.time()))
        before = serve_cell.scrape(port)
        time.sleep(max(0.0, w1 - time.time()))
        after = serve_cell.scrape(port)
        gen.stop(0.0)

        def grew(name: str) -> float:
            return stats.counter_delta(before, after, name) or 0.0

        steps = grew("dlti_decode_steps")
        host_s = sum(after[k] - before.get(k, 0.0) for k in after
                     if k.startswith("dlti_stepper_phase_seconds_total")
                     and 'kind="host"' in k)
        print(json.dumps({
            "checkout": root,
            "handler_cpu_us_per_event":
                1e6 * grew("dlti_sse_handler_cpu_seconds")
                / max(1.0, grew("dlti_sse_events")),
            "host_ms_per_step": 1e3 * host_s / steps if steps else None,
            "decode_steps": steps,
            "tokens_per_s": stats.tokens_in_window(gen.records, w0, w1)
            / args.seconds}))
    finally:
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            proc.wait(30)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
    shutil.rmtree(out, ignore_errors=True)


if __name__ == "__main__":
    main()
