"""One run of a training cell: the program's ``scripts/train.py`` on seeded
documents, a window of whole optimizer steps, stopped by SIGTERM."""

from __future__ import annotations

import json
import math
import os
import re
import signal
import subprocess
import time

import harness
import stats
import traffic as traffic_lib


def _tail_steps(path: str, state: dict) -> list:
    """Step rows that appeared since the last call, stamped with the host
    time at which they were seen here (their results were then on the
    host: the trainer writes a row after ``device_get`` of the metrics)."""
    if "f" not in state:
        if not os.path.isfile(path):
            return []
        state["f"], state["buf"] = open(path), ""
    chunk = state["f"].read()
    if not chunk:
        return []
    now = time.time()
    state["buf"] += chunk
    *lines, state["buf"] = state["buf"].split("\n")
    rows = []
    for line in lines:
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if row.get("type") == "step":
            rows.append({**row, "seen": now})
    return rows


CHECK_INPUTS = ("lora_r", "seed", "rows", "seq_len", "doc_median")


def check(r: harness.Run, check_spec: dict) -> dict:
    """The program held against the float32 reference on seeded packed
    rows, after the trainer has given the chip back. The reference's side
    is kept per (configuration, rows); the program's side is computed in
    this run, whatever ran before in this checkout."""
    spec_file = r.path("check_spec.json")
    with open(spec_file, "w") as f:
        json.dump(check_spec, f)
    kept, hit = r.cached_reference(
        {k: check_spec[k] for k in CHECK_INPUTS},
        lambda out: r.run_check("train-reference", ["--spec", spec_file],
                                out, check_spec["timeout_s"]))
    out = r.path("check_program.json")
    r.run_check("train-program", ["--spec", spec_file, "--reference", kept],
                out, check_spec["timeout_s"])
    verdict = harness.read_json(out)
    r.notes["reference_check"] = {**verdict, "reference_from_cache": hit,
                                  **r.judged_by()}
    return verdict


def run(r: harness.Run) -> dict:
    spec, cell = r.spec, r.cell
    mix = cell["traffic"]
    if r.rehearsal:
        mix = harness.overlay(mix, mix.get("rehearsal", {}))
    seq_len = int(spec["args"]["--max-seq-len"])
    texts = traffic_lib.training_documents(mix, r.seed)
    data = r.path("data.jsonl")
    traffic_lib.write_jsonl(data, texts)
    doc_tokens = traffic_lib.document_tokens(texts, seq_len)

    steplog = r.path("steps.jsonl")
    argv = harness.flags(spec["args"]) + [
        "--model", r.model_name, "--dataset-path", data,
        "--output-dir", r.path("ckpt"), "--step-log", steplog,
        "--metrics-csv", r.path("metrics.csv"),
        "--seed", str(r.seed % (2**31 - 1))]
    warmup = int(spec["warmup_steps"])
    if r.trace:
        argv += ["--profile-dir", r.path("profile"),
                 "--profile-start-step",
                 str(warmup + int(spec["trace"]["after_warmup_steps"])),
                 "--profile-num-steps", str(spec["trace"]["steps"])]
    proc, log_path = r.spawn_entry("train", spec["entry"], argv)
    facts = r.wait_device(proc, log_path, time.time() + 300)

    rows, state, w0 = [], {}, None
    deadline = time.time() + float(spec["setup_limit_s"])
    while True:
        rows += _tail_steps(steplog, state)
        if w0 is None and rows and rows[-1]["step"] >= warmup:
            w0 = next(x["seen"] for x in rows if x["step"] >= warmup)
        now = time.time()
        if w0 is not None and now >= w0 + r.seconds:
            break
        if proc.poll() is not None:
            raise harness.RunFailure(
                f"scripts/train.py exited {proc.returncode} before the "
                f"window closed: {harness.tail(log_path)}")
        if w0 is None and now > deadline:
            raise harness.RunFailure(
                f"no step {warmup} within {spec['setup_limit_s']} s: "
                f"{harness.tail(log_path, 4)}")
        time.sleep(0.002)
    os.killpg(proc.pid, signal.SIGTERM)  # -> Trainer.request_stop()
    try:
        rc = proc.wait(timeout=180)
    except subprocess.TimeoutExpired:
        raise harness.RunFailure("the trainer did not stop within 180 s "
                                 "of SIGTERM")
    if rc != 0:
        raise harness.RunFailure(f"scripts/train.py exited {rc} after "
                                 f"SIGTERM: {harness.tail(log_path)}")

    verdict = check(r, spec["check"])
    win = stats.step_window(rows, warmup, r.seconds)
    if r.trace:
        # The profiler's start and stop stall the loop for seconds: rates
        # and waits of a traced run are taken over the steps before it.
        first_traced = warmup + int(spec["trace"]["after_warmup_steps"])
        clean = [x for x in win["rows"] if x["step"] <= first_traced]
        if clean:
            win = {**win, "rows": clean, "steps": len(clean),
                   "w1": clean[-1]["seen"],
                   "seconds": clean[-1]["seen"] - win["w0"]}
    log = harness.read_text(log_path)
    found = re.search(r"steps/epoch: (\d+)", log)
    if not found:
        raise harness.RunFailure("no 'steps/epoch:' line in the trainer log")
    chips = cell["chips"]
    rows_per_step = int(spec["args"]["--per-device-batch-size"]) * chips \
        * int(spec["args"]["--gradient-accumulation-steps"])
    # Non-padding tokens of a step: the epoch's real tokens spread over its
    # rows (the packer decides which row gets which document; the fill of
    # the window's rows differs from the epoch's by a fraction of a percent).
    slots = int(found.group(1)) * rows_per_step * seq_len
    fill = min(1.0, doc_tokens / slots)
    tokens_per_step = rows_per_step * seq_len * fill
    rate = win["steps"] * tokens_per_step / win["seconds"] / chips
    compiled = r.compilations_between(win["w0"], win["w1"])
    bad = [x for x in win["rows"]
           if not math.isfinite(x["loss"]) or x["skipped_update"]
           or not math.isfinite(x["grad_norm"])]
    first_loss = rows[0]["loss"] if rows else float("nan")
    ln_vocab = math.log(r.config["model"]["vocab_size"])
    sane_start = abs(first_loss - ln_vocab) <= float(spec["first_loss_band"])
    events = harness.read_jsonl(r.events_path)
    before = [e for e in events if e["t"] < win["w0"]]
    r.notes["setup_breakdown"] = {
        "first_step_seen_s": rows[0]["seen"] - r.t_start,
        "window_open_s": win["w0"] - r.t_start,
        "backend_compiles": len([e for e in before if e["event"].endswith(
            "backend_compile_duration")]),
        "backend_compile_s": sum(e.get("seconds", 0.0) for e in before
                                 if e["event"].endswith(
                                     "backend_compile_duration")),
        "cache_hits": len([e for e in before
                           if e["event"].endswith("cache_hits")]),
        "cache_misses": len([e for e in before
                             if e["event"].endswith("cache_misses")]),
    }
    r.notes["step_rows_sample"] = [
        {k: x.get(k) for k in ("step", "step_time_s", "data_wait_s",
                               "sync_s")} for x in win["rows"][:4]]
    r.notes.update({
        "window": {"steps": win["steps"], "seconds": win["seconds"],
                   "step_s_median": stats.percentile(
                       [b["seen"] - a["seen"] for a, b in
                        zip([{"seen": win["w0"]}] + win["rows"],
                            win["rows"])], 50)},
        "tokens_per_step": tokens_per_step, "fill": fill,
        "documents": stats.distribution(
            [min(len(t) + 2, seq_len) for t in texts]),
        "compilations_in_window": len(compiled),
        "first_loss": first_loss, "last_loss": win["rows"][-1]["loss"],
        "peak_memory_gb_steplog": win["rows"][-1].get("peak_memory_gb"),
    })
    return {
        "kind": "train",
        "correct": bool(verdict["ok"] and not bad and sane_start
                        and not compiled),
        "attempted": win["steps"], "failed": len(bad),
        "w0": win["w0"], "w1": win["w1"],
        "e2e": {"train_tokens_per_s_per_chip": rate},
        "facts": facts, "window": win, "rows": win["rows"], "texts": texts,
        "tokens_per_step": tokens_per_step, "seq_len": seq_len,
        "profile_dir": r.path("profile") if r.trace else None,
    }
