#!/usr/bin/env python3
"""chip_smoke.py — does the program still start on the chip?

Drives the README Quickstart once, through the entry points a user calls,
on one TPU chip at the published widths of ``mistral_7b`` (GQA 32/8,
head_dim 128, window 4096; depth is cut — see ``DEFAULT_DEPTH`` — and the
weights are seeded random):

    scripts/prepare_dataset.py --synthetic N
 -> scripts/train.py   (LoRA-SFT via the Trainer, one checkpoint save
                        inside the run, merged export at the end)
 -> scripts/serve.py   --model-dir <that export>   (InferenceEngine behind
                        the HTTP server)
 -> concurrent /v1/completions + one repeated greedy prompt -> SIGTERM

and judges outcomes, not exit codes (see ``_check_train`` / ``_check_serve``).
Stdout ends with two lines, each one JSON object: the summary (model, depth,
dtypes, which attention path each leg took, per-leg outcome and times), then
the result, exactly ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": 1}}`` with the device as the first child's JAX reports it; the exit
code is 0 only when ``ok`` is true. The times in the summary are set-up facts
of this run (compile, load, save), not performance claims.

This process never imports jax or a jax-importing package: a chip belongs
to one process, so every leg is a child running the real CLI, one after the
other, and the load is plain HTTP from here. Without an accelerator (the
first child reports its platform) the run stops at once with a one-line
reason, a non-zero code and no result line.

    python chip_smoke.py                   # one chip, the contract run
    python chip_smoke.py --legs serve_full_depth
                                           # one chip: serve all 32 layers
                                           # (random init, no export) in
                                           # the planned pool
    python chip_smoke.py --chips 4         # four-chip host: FSDP and TP
                                           # training, --tensor 4 and
                                           # --replicas 4 serving, and the
                                           # multi-process entry points'
                                           # refusal (--legs picks some)
    python chip_smoke.py --cpu-rehearsal   # llama_tiny on the CPU backend,
                                           # to debug this script only;
                                           # says platform: cpu
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "smoke_out")
PY = sys.executable

MODEL = "mistral_7b"
PUBLISHED_DEPTH = 32
VOCAB = 32000
# Depth run by default. Whole layers are cut, never a width, and the result
# line says so. Why 8 of 32 (measured on the v5e machine, PR 21): the
# layers are unrolled, so compile time grows with depth — the 4-layer
# train step alone compiled for 90 s — and the contract's 1200 s must
# hold a cold compile of the train step, the decode program and two
# prefill programs; and at full depth the trainer's merged export needs
# the weights twice over in host memory (tree + store payload, 29 GB) on
# top of the ~14 GB the TPU runtime maps per process, on a 45 GiB host.
DEFAULT_DEPTH = 8
# Depth of the four-chip legs: the depth they were run at (PR 21). Each of
# the six legs pays its own process start and cold compiles — the FSDP
# train step alone compiled for 94 s at 2 layers — and a four-chip call is
# charged four times over: 10 minutes at this depth.
DEFAULT_DEPTH_4CHIPS = 2
TIME_LIMIT_S = 1140  # the contract allows 1200 s, compilation included

_children: list = []
_device: dict = {}  # as the first child that reached JAX reported it


class SmokeFailure(Exception):
    """A leg's outcome check failed; the message is the one-line reason."""


class NoAccelerator(SmokeFailure):
    """The first child did not report platform == "tpu"."""


# ----------------------------------------------------------------------
# Children: spawn, wait, stop
# ----------------------------------------------------------------------

def _spawn(name: str, cmd: list, layers: int = 0):
    """Start a child in its own process group, output to a log file.
    ``layers`` cuts the depth of the model preset the child names
    (``DLTI_MODEL_LAYERS``, dlti_tpu/config.py); 0 leaves it whole."""
    log_path = os.path.join(OUT, "logs", f"{name}.log")
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    log = open(log_path, "wb")
    env = {**os.environ, "PYTHONUNBUFFERED": "1"}
    env.pop("DLTI_MODEL_LAYERS", None)
    if layers:
        env["DLTI_MODEL_LAYERS"] = str(layers)
    proc = subprocess.Popen(cmd, cwd=HERE, env=env,
                            stdout=log, stderr=subprocess.STDOUT,
                            start_new_session=True)
    log.close()  # the child holds its own descriptor
    _children.append(proc)
    return proc, log_path


def _stop(proc, grace_s: float = 15.0) -> None:
    if proc.poll() is not None:
        return
    for sig, wait_s in ((signal.SIGTERM, grace_s), (signal.SIGKILL, 10.0)):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(timeout=wait_s)
            break
        except subprocess.TimeoutExpired:
            continue


def _stop_all() -> None:
    for proc in _children:
        _stop(proc)


def _wait(proc, deadline: float, what: str, watch=None) -> int:
    """Wait for ``proc`` until ``deadline`` (monotonic), calling ``watch``
    (which may raise SmokeFailure) a few times a second."""
    while True:
        if watch is not None:
            watch()
        rc = proc.poll()
        if rc is not None:
            return rc
        if time.monotonic() > deadline:
            raise SmokeFailure(f"{what}: not done within its time limit")
        time.sleep(0.25)


def _read(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def _tail(path: str, n: int = 15) -> str:
    return " | ".join(_read(path).strip().splitlines()[-n:])[-1500:]


def _build_facts(log_path: str, role: str) -> list:
    """Every ``<role> build: {json}`` line the child logged."""
    facts = []
    marker = f"{role} build: "
    for line in _read(log_path).splitlines():
        at = line.find(marker)
        if at >= 0:
            try:
                facts.append(json.loads(line[at + len(marker):]))
            except ValueError:
                pass  # a line still being written
    return facts


def _logged_json(log_path: str, marker: str) -> dict:
    """The JSON object after the last ``marker`` in a child's log."""
    found = {}
    for line in _read(log_path).splitlines():
        at = line.find(marker)
        if at >= 0:
            found = json.loads(line[at + len(marker):])
    return found


def _require_platform(facts: dict, want: str) -> None:
    if not _device:
        _device.update(platform=facts["platform"], kind=facts["device_kind"],
                       count=facts["device_count"])
    if facts["platform"] != want:
        raise NoAccelerator(
            f"no accelerator: the first child reports platform "
            f"{facts['platform']!r} ({facts['device_kind']!r} x "
            f"{facts['device_count']}), not {want!r}")


# ----------------------------------------------------------------------
# Compile cache bookkeeping (where the children keep it: the same rule as
# dlti_tpu/utils/platform.py, restated here because this process imports
# nothing of the package)
# ----------------------------------------------------------------------

def _cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache")


def _cache_entries() -> int:
    try:
        return sum(1 for n in os.listdir(_cache_dir())
                   if not n.endswith("-atime"))
    except OSError:
        return 0


# ----------------------------------------------------------------------
# Training leg
# ----------------------------------------------------------------------

def _steplog_rows(path: str) -> list:
    rows = []
    for line in _read(path).splitlines():
        try:
            rows.append(json.loads(line))
        except ValueError:
            pass
    return rows


def _train_leg(name: str, cfg: dict, extra: list, deadline: float,
               want_platform: str, gate_platform: bool) -> dict:
    """Run scripts/train.py to the end and check what it left behind."""
    out_dir = os.path.join(OUT, name)
    step_log = os.path.join(out_dir, "steps.jsonl")
    export_dir = os.path.join(out_dir, "export")
    cmd = [PY, "scripts/train.py", "--model", cfg["model"],
           "--tokenizer", "byte", "--lora-r", "16",
           "--max-seq-len", str(cfg["seq_len"]),
           "--dataset-path", os.path.join(OUT, "data"),
           "--per-device-batch-size", str(cfg["micro_batch"]),
           "--gradient-accumulation-steps", "2",
           "--max-steps", str(cfg["steps"]), "--warmup-steps", "2",
           "--save-strategy", "steps", "--save-steps", str(cfg["steps"] - 1),
           "--logging-steps", "1", "--step-log", step_log,
           "--output-dir", os.path.join(out_dir, "ckpt"),
           "--export-dir", export_dir,
           "--metrics-csv", os.path.join(out_dir, "metrics.csv"), *extra]
    t0 = time.monotonic()
    proc, log_path = _spawn(name, cmd, cfg["cut_layers"])
    marks = {}

    def watch():
        if gate_platform and "platform" not in marks:
            facts = _build_facts(log_path, "trainer")
            if facts:
                marks["platform"] = facts[0]["platform"]
                _require_platform(facts[0], want_platform)
        n = sum(1 for r in _steplog_rows(step_log) if r.get("type") == "step")
        if n >= 1 and "first_step" not in marks:
            marks["first_step"] = time.monotonic()
        if n >= cfg["steps"] and "last_step" not in marks:
            marks["last_step"] = time.monotonic()

    rc = _wait(proc, deadline, name, watch)
    watch()
    t_end = time.monotonic()
    if rc != 0:
        raise SmokeFailure(f"{name}: scripts/train.py exited {rc}: "
                           f"{_tail(log_path)}")
    facts = _build_facts(log_path, "trainer")
    if not facts:
        raise SmokeFailure(f"{name}: no 'trainer build:' line in its log")
    result = _check_train(name, cfg, facts[0], step_log, out_dir, export_dir,
                          log_path, want_platform)
    first = marks.get("first_step", t_end)
    last = marks.get("last_step", t_end)
    result.update({
        "wall_s": round(t_end - t0, 1),
        # process start, weight init, compile and the first step
        "setup_s": round(first - t0, 1),
        # the remaining optimizer steps (the save starts inside them)
        "steps_s": round(last - first, 1),
        # settling the checkpoint, device->host fetch, merge, export write
        "save_export_s": round(t_end - last, 1),
    })
    return result


def _check_train(name, cfg, facts, step_log, out_dir, export_dir, log_path,
                 want_platform) -> dict:
    _require_platform(facts, want_platform)
    on_chip = want_platform == "tpu"
    if facts["model_layers"] != cfg["layers"]:
        raise SmokeFailure(f"{name}: trained {facts['model_layers']} layers, "
                           f"asked for {cfg['layers']}")
    if on_chip and facts["flash"] != "pallas":
        raise SmokeFailure(
            f"{name}: training attention resolved to {facts['flash']!r} "
            f"({facts['flash_reason']}), not the Pallas kernel")
    rows = [r for r in _steplog_rows(step_log) if r.get("type") == "step"]
    if len(rows) != cfg["steps"]:
        raise SmokeFailure(f"{name}: step log has {len(rows)} step rows, "
                           f"expected {cfg['steps']}")
    ln_vocab = math.log(cfg["vocab"])
    for r in rows:
        if not math.isfinite(r["loss"]):
            raise SmokeFailure(f"{name}: step {r['step']} loss {r['loss']}")
        if not (math.isfinite(r["grad_norm"]) and r["grad_norm"] > 0):
            raise SmokeFailure(
                f"{name}: step {r['step']} grad_norm {r['grad_norm']}")
        if r["skipped_update"]:
            raise SmokeFailure(f"{name}: step {r['step']} skipped its "
                               f"update (nonfinite gate)")
        if r["peak_memory_source"] != ("device" if on_chip else "host_rss"):
            raise SmokeFailure(
                f"{name}: peak memory came from "
                f"{r['peak_memory_source']!r}")
        if on_chip and r["mfu_percent"] is None:
            raise SmokeFailure(f"{name}: no MFU on a chip (peak lookup)")
        if not on_chip and r["mfu_percent"] is not None:
            raise SmokeFailure(f"{name}: an MFU was computed on CPU")
    # Seeded random weights: the first loss is ln(vocab) plus half the
    # logit variance (head init 0.02 x sqrt(hidden) -> ~0.8 at 7B widths).
    if abs(rows[0]["loss"] - ln_vocab) > 1.5:
        raise SmokeFailure(
            f"{name}: first loss {rows[0]['loss']:.3f} is more than 1.5 "
            f"from ln(vocab) = {ln_vocab:.2f}")
    ckpt_root = os.path.join(out_dir, "ckpt")
    committed = [d for d in (os.listdir(ckpt_root)
                             if os.path.isdir(ckpt_root) else [])
                 if os.path.isfile(os.path.join(ckpt_root, d, "COMMIT"))]
    if not committed:
        raise SmokeFailure(f"{name}: no committed checkpoint in {ckpt_root}")
    if not os.path.isfile(os.path.join(export_dir, "model", "MANIFEST.json")):
        raise SmokeFailure(f"{name}: the export has no MANIFEST.json")
    per_device = _logged_json(log_path, "device memory: ")
    at_init = _logged_json(log_path, "device memory after init: ")
    if on_chip:
        _require_all_chips_used(name, per_device, facts["device_count"],
                                "peak_bytes_in_use")
        _require_all_chips_used(name, at_init, facts["device_count"],
                                "bytes_in_use", min_bytes=1)
        # The state is born sharded: while it was built no chip held a
        # multiple of what it ends up holding (init-then-shard put the
        # whole tree on chip 0: 4.1x its share at FSDP x4, 3.2x at
        # DP 2 x TP 2; the margin is for the initialiser's f32 scratch).
        over = {d: s for d, s in at_init.items()
                if s["peak_bytes_in_use"]
                > 2.5 * s["bytes_in_use"] + (64 << 20)}
        if over and facts["device_count"] > 1:
            raise SmokeFailure(
                f"{name}: while the state was built a chip held far more "
                f"than its share: {json.dumps(over)}")
    return {
        "ok": True, "facts": facts, "export_dir": export_dir,
        "first_loss": round(rows[0]["loss"], 4),
        "last_loss": round(rows[-1]["loss"], 4),
        "peak_memory_gb": rows[-1]["peak_memory_gb"],
        "committed_checkpoints": sorted(committed),
        "per_device_peak_gb": {
            d: round(s.get("peak_bytes_in_use", 0) / 2**30, 3)
            for d, s in per_device.items()},
        "per_device_after_init_gb": {
            d: {"in_use": round(s.get("bytes_in_use", 0) / 2**30, 3),
                "peak": round(s.get("peak_bytes_in_use", 0) / 2**30, 3)}
            for d, s in at_init.items()},
    }


def _require_all_chips_used(name, per_device: dict, count: int, key: str,
                            min_bytes: int = 64 << 20) -> None:
    """Every chip of the host holds its share: a per-device reading, not
    device 0's and not a sum."""
    if len(per_device) != count:
        raise SmokeFailure(f"{name}: memory stats for {len(per_device)} "
                           f"devices, expected {count}")
    idle = [d for d, s in per_device.items() if s.get(key, 0) < min_bytes]
    if idle:
        raise SmokeFailure(f"{name}: device(s) {idle} hold under "
                           f"{min_bytes >> 20} MiB — not every chip is in use")


# ----------------------------------------------------------------------
# Serving leg
# ----------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, path: str, body: dict | None = None,
          timeout: float = 600.0):
    """(status, parsed-or-text). Never raises on an HTTP error status."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    text = raw.decode("utf-8", "replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def _complete(port: int, prompt: str, max_tokens: int) -> dict:
    status, out = _http(port, "/v1/completions", {
        "prompt": prompt, "max_tokens": max_tokens, "temperature": 0.0,
        "logprobs": True})
    if status != 200:
        raise SmokeFailure(f"/v1/completions -> {status}: {str(out)[:300]}")
    got = out["usage"]["completion_tokens"]
    if got != max_tokens:
        raise SmokeFailure(
            f"/v1/completions returned {got} tokens of {max_tokens} "
            f"(finish_reason {out['choices'][0]['finish_reason']!r})")
    lp = out["choices"][0]["logprobs"]
    if not all(math.isfinite(x) for x in lp["token_logprobs"]):
        raise SmokeFailure("/v1/completions returned a non-finite logprob")
    return {"tokens": lp["tokens"], "logprobs": lp["token_logprobs"]}


def _serve_leg(name: str, cfg: dict, source: list, extra: list,
               deadline: float, want_platform: str,
               expect_engines: int = 1, expect_decode: str = "pallas") -> dict:
    """``source``: ``["--model-dir", export]`` or ``["--random-init",
    preset]`` (then ``cfg["cut_layers"]`` cuts the preset's depth)."""
    port = _free_port()
    # Pool: scripts/memory_plan.py --model mistral_7b --serving plans bf16
    # weights (13.49 GiB) + a 0.5 GiB pool = 256 blocks x 16 tokens x
    # 128 KiB/token to 13.99 GiB of the chip's 16.
    cmd = [PY, "scripts/serve.py", *source,
           "--tokenizer", "byte", "--port", str(port),
           "--max-seqs", "8", "--block-size", "16",
           "--num-blocks", "256", "--max-model-len", "256", *extra]
    t0 = time.monotonic()
    proc, log_path = _spawn(
        name, cmd, cfg["cut_layers"] if source[0] == "--random-init" else 0)
    try:
        while True:  # ready = weights loaded + decode programs compiled
            if proc.poll() is not None:
                raise SmokeFailure(f"{name}: scripts/serve.py exited "
                                   f"{proc.returncode} before serving: "
                                   f"{_tail(log_path)}")
            if time.monotonic() > deadline:
                raise SmokeFailure(f"{name}: server not ready in time: "
                                   f"{_tail(log_path, 5)}")
            for facts in _build_facts(log_path, "engine")[:1]:
                _require_platform(facts, want_platform)  # before compiling
            try:
                if _http(port, "/health", timeout=2.0)[0] == 200:
                    break
            except (OSError, urllib.error.URLError):
                pass
            time.sleep(0.5)
        t_ready = time.monotonic()
        result = _check_serve(name, cfg, port, log_path, want_platform,
                              expect_engines, expect_decode)
        t_load = time.monotonic()
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            rc = proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: no exit within 90 s of SIGTERM")
        if rc != 0:
            raise SmokeFailure(f"{name}: exited {rc} after SIGTERM: "
                               f"{_tail(log_path, 5)}")
    finally:
        _stop(proc)
    log = _read(log_path)
    if "falling back to jit dispatch" in log:
        raise SmokeFailure(f"{name}: an AOT decode executable rejected its "
                           f"inputs and dispatch fell back to jit")
    if "engine step failed" in log:
        raise SmokeFailure(f"{name}: the engine stepper faulted: "
                           f"{_tail(log_path)}")
    result.update({
        "wall_s": round(time.monotonic() - t0, 1),
        # process start, export load + digest check, device placement,
        # decode-program compile
        "setup_s": round(t_ready - t0, 1),
        # first request (compiles its prefill program), the concurrent
        # wave (compiles the batched one), the repeated prompt, scrapes
        "requests_s": round(t_load - t_ready, 1),
    })
    return result


def _check_serve(name, cfg, port, log_path, want_platform, expect_engines,
                 expect_decode) -> dict:
    on_chip = want_platform == "tpu"
    facts = _build_facts(log_path, "engine")
    if len(facts) != expect_engines:
        raise SmokeFailure(f"{name}: {len(facts)} 'engine build:' lines, "
                           f"expected {expect_engines}")
    for f in facts:
        _require_platform(f, want_platform)
        if f["model_layers"] != cfg["layers"]:
            raise SmokeFailure(f"{name}: serving {f['model_layers']} layers, "
                               f"asked for {cfg['layers']}")
        if on_chip and f["paged_decode"] != expect_decode:
            raise SmokeFailure(
                f"{name}: paged decode resolved to {f['paged_decode']!r} "
                f"({f['paged_decode_reason']}), expected {expect_decode!r}")
    placements = [tuple(f["engine_devices"]) for f in facts]
    if len(set(placements)) != len(placements):
        raise SmokeFailure(f"{name}: engines share devices: {placements}")

    n_tok = cfg["max_tokens"]
    t0 = time.monotonic()
    _complete(port, "def reverse(xs):  # first request", n_tok)
    first_s = time.monotonic() - t0

    # >= 8 concurrent requests, so every decode slot is live at once.
    prompts = [f"Question {i}: how do I {what} in Python?" for i, what in
               enumerate(["reverse a list", "read a file", "sort a dict",
                          "parse some JSON", "merge two sets", "time a call",
                          "join strings", "copy a tree"])]
    results: list = [None] * len(prompts)

    def one(i):
        try:
            results[i] = _complete(port, prompts[i], n_tok)
        except Exception as e:  # noqa: BLE001 — reported below, per request
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    bad = [f"#{i}: {r}" for i, r in enumerate(results)
           if not isinstance(r, dict)]
    if bad:
        raise SmokeFailure(f"{name}: concurrent requests failed: {bad[:3]}")

    # One greedy prompt, twice: same engine, same programs -> same bytes.
    a = _complete(port, "Explain binary search in one line.", n_tok)
    b = _complete(port, "Explain binary search in one line.", n_tok)
    if a != b:
        raise SmokeFailure(f"{name}: the repeated greedy prompt differed: "
                           f"{a['tokens']} vs {b['tokens']}")

    status, metrics = _http(port, "/metrics")
    decode_steps = 0.0
    for line in str(metrics).splitlines():
        if line.startswith("dlti_decode_steps "):
            decode_steps = float(line.split()[1])
    if status != 200 or decode_steps <= 0:
        raise SmokeFailure(f"{name}: /metrics shows no decode steps")
    # Per-device memory as each engine read it from its own chips once
    # weights and pool were placed (a replicated fleet has no single
    # /debug/memory ledger; the build lines cover every engine).
    per_device = {d: {"bytes_in_use": b or 0} for f in facts
                  for d, b in f["device_bytes_in_use"].items()}
    if on_chip:
        _require_all_chips_used(name, per_device, facts[0]["device_count"],
                                "bytes_in_use")
    memory_source = None
    if expect_engines == 1:
        status, mem = _http(port, "/debug/memory")
        if status != 200:
            raise SmokeFailure(f"{name}: /debug/memory -> {status}")
        memory_source = mem["source"]
        if memory_source != ("device" if on_chip else "live_arrays"):
            raise SmokeFailure(
                f"{name}: /debug/memory source is {memory_source!r}")
    return {
        "ok": True, "facts": facts[0], "engines": len(facts),
        "engine_devices": [list(p) for p in placements],
        "decode_steps": int(decode_steps),
        "first_request_s": round(first_s, 1),
        "memory_source": memory_source,
        "per_device_in_use_gb": {d: round(s["bytes_in_use"] / 2**30, 3)
                                 for d, s in per_device.items()},
    }


# ----------------------------------------------------------------------
# Entry points that must refuse on a TPU host (one process per chip)
# ----------------------------------------------------------------------

def _refusal_leg(deadline: float) -> dict:
    train = [PY, "scripts/train.py", "--model", "llama_tiny", "--tokenizer",
             "byte", "--dataset-path", os.path.join(OUT, "data"),
             "--max-steps", "1", "--save-strategy", "no",
             "--output-dir", os.path.join(OUT, "refused")]
    cases = {
        "serve_fleet_workers": [
            PY, "scripts/serve.py", "--random-init", "llama_tiny",
            "--tokenizer", "byte", "--port", str(_free_port()),
            "--fleet-workers", "2"],
        "launch_num_processes": [
            PY, "scripts/launch.py", "--num-processes", "2", "--", *train],
        "launch_elastic": [
            PY, "scripts/launch.py", "--num-processes", "2", "--elastic",
            "--", *train],
    }
    out = {"ok": True}
    for name, cmd in cases.items():
        t0 = time.monotonic()
        proc, log_path = _spawn(f"refuse_{name}", cmd)
        rc = _wait(proc, min(deadline, t0 + 180), name)
        log = _read(log_path)
        if rc == 0 or "not supported on TPU" not in log:
            raise SmokeFailure(
                f"{name}: expected a refusal at start on a TPU host, got "
                f"exit {rc}: {_tail(log_path, 5)}")
        out[name] = {"exit": rc, "seconds": round(time.monotonic() - t0, 1)}
    return out


# ----------------------------------------------------------------------

def _prepare(cfg: dict, deadline: float) -> None:
    proc, log_path = _spawn("prepare", [
        PY, "scripts/prepare_dataset.py", "--synthetic", str(cfg["examples"]),
        "--output-dir", os.path.join(OUT, "data")])
    if _wait(proc, deadline, "prepare") != 0:
        raise SmokeFailure(f"prepare_dataset.py failed: {_tail(log_path)}")


# Legs by --chips; the contract run is the first two of the one-chip set.
LEGS = {1: ("train", "serve", "serve_full_depth"),
        4: ("train_fsdp4", "train_tp2", "serve_tensor4", "serve_replicas4",
            "refusals")}


def _run(args) -> dict:
    rehearsal = args.cpu_rehearsal
    want = "cpu" if rehearsal else "tpu"
    layers = args.layers or (2 if rehearsal else DEFAULT_DEPTH_4CHIPS
                             if args.chips == 4 else DEFAULT_DEPTH)
    if rehearsal:
        cfg = dict(model="llama_tiny", published_depth=2, vocab=512,
                   seq_len=128, micro_batch=2, steps=4, examples=128,
                   max_tokens=8)
    else:
        cfg = dict(model=MODEL, published_depth=PUBLISHED_DEPTH, vocab=VOCAB,
                   seq_len=512, micro_batch=2, steps=4, examples=128,
                   max_tokens=24)
    cfg["layers"] = layers
    cfg["cut_layers"] = 0 if layers == cfg["published_depth"] else layers
    t_start = time.monotonic()
    deadline = t_start + TIME_LIMIT_S * (3 if args.chips == 4 else 1)
    cache_before = _cache_entries()
    legs: dict = {}
    summary = {
        "ok": False, "device": None, "chips": args.chips,
        "model": cfg["model"], "depth": layers,
        "published_depth": cfg["published_depth"],
        "widths": "llama_tiny (CPU rehearsal)" if rehearsal else "published",
        "legs": legs,
        "times_are": "set-up facts of this run, not performance claims",
    }
    try:
        _prepare(cfg, deadline)
        if args.chips == 1:
            if "train" in args.legs:
                legs["train"] = _train_leg(
                    "train", cfg,
                    ["--preset", "baseline", "--num-devices", "1"],
                    deadline, want, gate_platform=True)
            if "serve" in args.legs:
                legs["serve"] = _serve_leg(
                    "serve", cfg,
                    ["--model-dir", legs["train"]["export_dir"]],
                    [], deadline, want,
                    expect_decode="pallas" if not rehearsal else "xla")
            if "serve_full_depth" in args.legs:
                # Every published layer in the planned pool, weights random
                # from a seed in the server itself: the trainer's export
                # at this depth does not fit the one-chip host's memory
                # (DEFAULT_DEPTH), serving it does not need one.
                full = {**cfg, "layers": cfg["published_depth"],
                        "cut_layers": 0}
                legs["serve_full_depth"] = {
                    **_serve_leg(
                        "serve_full_depth", full,
                        ["--random-init", cfg["model"]], [], deadline, want,
                        expect_decode="pallas" if not rehearsal else "xla"),
                    "depth": full["layers"],
                    "weights": "random init in the server, no export"}
        else:
            # A four-chip call costs four times over, so one failed leg
            # does not stop the others: each reports for itself.
            def leg(name, fn, *a, **kw):
                if name not in args.legs:
                    return
                try:
                    legs[name] = fn(name, *a, **kw)
                except NoAccelerator:
                    raise
                except SmokeFailure as e:
                    legs[name] = {"ok": False, "error": str(e)[:1500]}

            leg("train_fsdp4", _train_leg, cfg,
                ["--preset", "zero3", "--num-devices", "4"],
                deadline, want, gate_platform=True)
            leg("train_tp2", _train_leg, cfg,
                ["--preset", "baseline", "--tensor", "2"],
                deadline, want, gate_platform="train_fsdp4" not in legs)
            export = next((legs[n]["export_dir"]
                           for n in ("train_fsdp4", "train_tp2")
                           if legs.get(n, {}).get("ok")),
                          os.path.join(OUT, "no_export"))
            # llama_tiny has 2 kv heads: the rehearsal shards them 2-way.
            leg("serve_tensor4", _serve_leg, cfg, ["--model-dir", export],
                ["--tensor", "2" if rehearsal else "4"], deadline,
                want, expect_decode="xla")
            leg("serve_replicas4", _serve_leg, cfg, ["--model-dir", export],
                ["--replicas", "4"], deadline, want, expect_engines=4,
                expect_decode="pallas" if not rehearsal else "xla")
            if not rehearsal:
                leg("refusals", lambda _n, d: _refusal_leg(d), deadline)
            failed = {n: l["error"] for n, l in legs.items() if not l["ok"]}
            if failed:
                raise SmokeFailure("; ".join(
                    f"{n}: {e[:300]}" for n, e in failed.items()))
        train_facts = [v["facts"] for k, v in legs.items()
                       if k.startswith("train")]
        serve_facts = [v["facts"] for k, v in legs.items()
                       if k.startswith("serve")]
        summary["dtypes"] = {}
        if train_facts:
            summary["dtypes"].update(
                compute=train_facts[0]["compute_dtype"],
                params=train_facts[0]["param_dtype"],
                frozen_base=train_facts[0]["frozen_base"])
        if serve_facts:
            summary["dtypes"]["kv_cache"] = serve_facts[0]["kv_cache_dtype"]
            summary["block_allocator"] = serve_facts[0]["block_allocator"]
        summary["attention"] = {
            **{k: v["facts"]["flash"] for k, v in legs.items()
               if k.startswith("train")},
            **{k: {"prefill": v["facts"]["prefill_attention"],
                   "decode": v["facts"]["paged_decode"]}
               for k, v in legs.items() if k.startswith("serve")},
        }
        summary["ok"] = all(leg["ok"] for leg in legs.values())
    except NoAccelerator:
        raise
    except SmokeFailure as e:
        summary["error"] = str(e)[:2000]
    finally:
        _stop_all()
        for leg in legs.values():
            leg.pop("facts", None)
            leg.pop("export_dir", None)
        summary["device"] = dict(_device) or None
        summary["wall_s"] = round(time.monotonic() - t_start, 1)
        summary["compile_cache"] = {
            "dir": _cache_dir(), "entries_before": cache_before,
            "entries_added": _cache_entries() - cache_before}
    return summary


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="1 = the contract run; 4 = the four-chip legs")
    p.add_argument("--layers", type=int, default=0,
                   help=f"run this many whole layers of the model (default "
                        f"{DEFAULT_DEPTH} of {PUBLISHED_DEPTH}, "
                        f"{DEFAULT_DEPTH_4CHIPS} with --chips 4); widths are "
                        f"never cut")
    p.add_argument("--legs", default="", type=lambda v: v.split(","),
                   help="run only these legs, comma-separated: of "
                        f"{', '.join(LEGS[1])} on one chip (default: the "
                        f"first two, the contract run), of "
                        f"{', '.join(LEGS[4])} with --chips 4 (default: all)")
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="run llama_tiny on the CPU backend to debug this "
                        "script; the result says platform: cpu")
    args = p.parse_args()
    if args.legs == [""]:
        args.legs = list(LEGS[args.chips][:2] if args.chips == 1
                         else LEGS[args.chips])
    if set(args.legs) - set(LEGS[args.chips]) or (
            "serve" in args.legs and "train" not in args.legs):
        p.error(f"--legs with --chips {args.chips} takes "
                f"{', '.join(LEGS[args.chips])} (serve needs train's export)")
    if not os.path.isfile(os.path.join(HERE, "scripts", "train.py")):
        print("chip_smoke.py: not in a checkout of the repo (no "
              "scripts/train.py next to it)", file=sys.stderr)
        return 2
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    shutil.rmtree(OUT, ignore_errors=True)  # a stale checkpoint would resume
    os.makedirs(OUT)
    env_platform = os.environ.get("JAX_PLATFORMS", "")
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4")
    try:
        summary = _run(args)
    except NoAccelerator as e:
        _stop_all()
        print(f"chip_smoke.py: {e} (JAX_PLATFORMS={env_platform!r}); "
              f"--cpu-rehearsal runs the CPU rehearsal", file=sys.stderr)
        return 3
    finally:
        _stop_all()
    if not summary["ok"]:
        print(f"chip_smoke.py: FAILED: {summary.get('error')}",
              file=sys.stderr)
    print(json.dumps(summary))
    if _device:  # the result: these keys and no others, the last line
        print(json.dumps({"ok": summary["ok"], "device": _device}))
    sys.stdout.flush()
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
