"""One run of a serving cell: the program's ``scripts/serve.py`` (engine
behind the HTTP/SSE server) under seeded open- or closed-loop load."""

from __future__ import annotations

import asyncio
import json
import os
import random
import signal
import socket
import subprocess
import threading
import time
import urllib.error
import urllib.request

import harness
import loadgen
import stats
import traffic as traffic_lib


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port: int, path: str, body: dict | None = None,
         timeout: float = 300.0):
    """(status, parsed JSON or text); an HTTP error status is returned."""
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


def scrape(port: int) -> dict:
    """``/metrics`` as {series name: value} (labelled series keep their
    label text in the name), stamped with when it was read."""
    status, text = http(port, "/metrics", timeout=30)
    if status != 200:
        raise harness.RunFailure(f"/metrics -> {status}")
    out = {"_t": time.time()}
    for line in str(text).splitlines():
        if line.startswith("#") or " " not in line:
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            pass
    return out


def counter(metrics: dict, name: str) -> float:
    for key in (name, name + "_total"):
        if key in metrics:
            return metrics[key]
    raise harness.RunFailure(f"/metrics has no series {name!r}")


def _burst(port: int, reqs: list, stagger_s: float = 0.0) -> list:
    async def go():
        async def one(i, req):
            await asyncio.sleep(stagger_s if i else 0.0)  # 0 is the blocker
            return await loadgen.stream_completion(port, req, time.time(),
                                                   600.0)
        return await asyncio.gather(*(one(i, q) for i, q in enumerate(reqs)))

    return asyncio.run(go())


def _warm_request(rng: random.Random, tokens: int, vocab: int,
                  index: int) -> dict:
    ids = [rng.randrange(3, vocab) for _ in range(tokens - 1)]
    return {"index": index, "prompt": " ".join(f"<{t}>" for t in ids),
            "max_tokens": 2, "seed": 1 + index, "temperature": 1.0}


def warm_up(port: int, warm: dict, vocab: int, notes: dict) -> None:
    """Touch every prefill program the window can use: each prompt bucket
    at each padded row count. Rows of one admission pass are the requests
    that were waiting when it began, so a group of ``n`` is made by sending
    ``n`` requests while one long prefill (the blocker) holds the engine.
    ``prefill_batches`` tells whether the group went through as one call;
    a group that split is sent again."""
    rng = random.Random(int(warm["seed"]))
    blocker_tokens = int(warm["blocker_tokens"])
    splits = 0
    for bucket, row_counts in warm["shapes"].items():
        bucket = int(bucket)
        tokens = bucket - int(warm["below_bucket_by"])
        for rows in row_counts:
            for _attempt in range(int(warm["retries"]) + 1):
                before = counter(scrape(port), "dlti_prefill_batches")
                if rows == 1:
                    reqs = [_warm_request(rng, tokens, vocab, 0)]
                    want = 1
                else:
                    reqs = [_warm_request(rng, blocker_tokens, vocab, 0)] + [
                        _warm_request(rng, tokens, vocab, 1 + i)
                        for i in range(rows)]
                    want = 2
                recs = _burst(port, reqs, float(warm["stagger_s"]))
                bad = [x for x in recs if loadgen.request_failed(x)]
                if bad:
                    raise harness.RunFailure(
                        f"warm-up request failed at bucket {bucket} x "
                        f"{rows}: {bad[0]['error']}")
                got = counter(scrape(port), "dlti_prefill_batches") - before
                if got <= want:
                    break
                splits += 1
    notes["warm_up_groups_resent"] = splits


def greedy_cases(port: int, check: dict, vocab: int, label: str,
                 concurrent: bool) -> list:
    """Seeded prompts answered greedily with log-probs: what the reference
    is held against. ``concurrent`` sends them together (into a busy
    batch); otherwise one after the other (each alone in the engine)."""
    rng = random.Random(int(check["seed"]))
    prompts = []
    for n in check["prompt_tokens"]:
        prompts.append([rng.randrange(3, vocab) for _ in range(n - 1)])
    results: list = [None] * len(prompts)

    def ask(i: int) -> None:
        status, out = http(port, "/v1/completions", {
            "prompt": " ".join(f"<{t}>" for t in prompts[i]),
            "max_tokens": int(check["max_tokens"]), "temperature": 0.0,
            "logprobs": True})
        if status != 200:
            results[i] = harness.RunFailure(
                f"check request -> {status}: {str(out)[:200]}")
            return
        lp = out["choices"][0]["logprobs"]
        results[i] = {"key": f"{label}/{i}", "prompt_ids": [1] + prompts[i],
                      "tokens": [int(t) for t in lp["tokens"]],
                      "server_logprobs": lp["token_logprobs"]}

    if concurrent:
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    else:
        for i in range(len(prompts)):
            ask(i)
    for res in results:
        if not isinstance(res, dict):
            raise res or harness.RunFailure("a check request did not return")
    return results


def judge(cases: list, reference: dict, tol: dict) -> dict:
    """Hold the server's log-probs of its own greedy tokens against the
    reference's, and its choices against the reference's best."""
    ref = {c["key"]: c for c in reference["cases"]}
    worst_lp = worst_gap = 0.0
    for case in cases:
        r = ref[case["key"]]
        for mine, theirs, best in zip(case["server_logprobs"], r["logprobs"],
                                      r["best_logprobs"]):
            worst_lp = max(worst_lp, abs(mine - theirs))
            worst_gap = max(worst_gap, best - theirs)
    return {"max_abs_logprob_diff": worst_lp, "max_greedy_gap": worst_gap,
            "ok": bool(worst_lp <= tol["logprob_abs"]
                       and worst_gap <= tol["greedy_gap"])}


def offer(r, port: int, mix: dict, vocab: int, spec: dict, alive,
          trace: bool) -> dict:
    """Offer the mix: a ramp, then the measured window ``[w0, w1)``.
    Returns the generator (still running), the window, the ``/metrics``
    scrapes at its ends and, when traced, around the profiler window."""
    ramp = float(mix["ramp_s"])
    t0 = time.time() + 0.5
    w0, w1 = t0 + ramp, t0 + ramp + r.seconds
    gen = loadgen.LoadGenerator(port)
    closed = mix["arrivals"]["loop"] == "closed"
    if closed:
        pool = traffic_lib.request_pool(mix, int(mix["arrivals"]["pool"]),
                                        r.seed, vocab)
        schedule = []
        gen.start_closed(pool, int(mix["arrivals"]["clients"]), t0,
                         float(mix["arrivals"]["stagger_s"]))
    else:
        offsets = traffic_lib.arrival_offsets(
            mix, ramp + r.seconds + float(mix["after_window_s"]))
        pool = traffic_lib.request_pool(mix, len(offsets), r.seed, vocab)
        schedule = [{**q, "due_s": t} for q, t in zip(pool, offsets)]
        gen.start_open(schedule, t0)

    def sleep_until(t: float) -> None:
        while time.time() < t:
            alive()
            time.sleep(min(0.05, max(0.0, t - time.time())))

    sleep_until(w0)
    before = scrape(port)
    traced = None
    if trace:
        sleep_until(w0 + float(spec["trace"]["after_window_start_s"]))
        t_before = scrape(port)
        status, out = http(port, "/debug/profile",
                           {"seconds": float(spec["trace"]["seconds"])})
        t_after = scrape(port)
        if status != 200:
            raise harness.RunFailure(f"/debug/profile -> {status}: {out}")
        traced = {"before": t_before, "after": t_after,
                  "dir": out["trace_dir"]}
    sleep_until((w0 + w1) / 2)
    in_flight_mid = gen.in_flight
    sleep_until(w1)
    after = scrape(port)
    return {"gen": gen, "t0": t0, "w0": w0, "w1": w1, "closed": closed,
            "pool": pool, "schedule": schedule, "before": before,
            "after": after, "traced": traced,
            "in_flight_mid": in_flight_mid, "in_flight_end": gen.in_flight}


def summarise(ph: dict) -> dict:
    """Counts and latencies of one offered phase (after its generator was
    stopped, so that every counted request has ended or was dropped)."""
    records, w0, w1 = ph["gen"].records, ph["w0"], ph["w1"]
    if ph["closed"]:
        counted = [x for x in records if w0 <= x["ended"] < w1]
        attempted = len(counted)
        failed = sum(1 for x in counted if loadgen.request_failed(x))
    else:
        due = [q for q in ph["schedule"] if w0 <= ph["t0"] + q["due_s"] < w1]
        done = {x["index"]: x for x in records}
        attempted = len(due)
        failed = sum(1 for q in due if q["index"] not in done
                     or loadgen.request_failed(done[q["index"]]))
        counted = [done[q["index"]] for q in due if q["index"] in done]
    gaps = [1e3 * g for g in stats.gaps_in_window(records, w0, w1)]
    ttfts = [1e3 * t for t in stats.ttfts_due_in_window(records, w0, w1)]
    out_tokens = stats.tokens_in_window(records, w0, w1)

    def pct(xs, q):
        return stats.percentile(xs, q) if xs else None

    def mean(xs):
        return sum(xs) / len(xs) if xs else None

    return {
        "attempted": attempted, "failed": failed,
        "in_flight_mid": ph["in_flight_mid"],
        "in_flight_end": ph["in_flight_end"],
        "ttft_mean_ms": mean(ttfts), "itl_mean_ms": mean(gaps),
        "ttft_p50_ms": pct(ttfts, 50), "ttft_p95_ms": pct(ttfts, 95),
        "itl_p50_ms": pct(gaps, 50), "itl_p95_ms": pct(gaps, 95),
        "output_tokens_per_s": out_tokens / (w1 - w0),
        "lateness": stats.lateness(records, w0, w1),
        "itl_ms": stats.distribution(gaps),
        "ttft_ms": stats.distribution(ttfts),
        "output_tokens_in_window": out_tokens,
        "prompt_tokens_counted": stats.distribution(
            [ph["pool"][x["index"] % len(ph["pool"])]["prompt_tokens"]
             for x in counted]),
        "output_tokens_asked": stats.distribution(
            [x["asked"] for x in counted]),
    }


def run(r: harness.Run) -> dict:
    spec, cell = r.spec, r.cell
    mix = cell["traffic"]
    if r.rehearsal:
        mix = harness.overlay(mix, mix.get("rehearsal", {}))
    vocab = int(r.config["model"]["vocab_size"])
    port = _free_port()
    argv = harness.flags(spec["args"]) + [
        "--random-init", r.model_name, "--tokenizer", f"id:{vocab}",
        "--port", str(port)]
    if r.trace:
        argv += ["--trace-dir", r.path("trace")]
    proc, log_path = r.spawn_entry("serve", spec["entry"], argv)
    facts = r.wait_device(proc, log_path, time.time() + 300)
    deadline = time.time() + float(spec["setup_limit_s"])
    while True:
        if proc.poll() is not None:
            raise harness.RunFailure(
                f"scripts/serve.py exited {proc.returncode} before "
                f"serving: {harness.tail(log_path)}")
        if time.time() > deadline:
            raise harness.RunFailure(f"server not ready in time: "
                                     f"{harness.tail(log_path, 4)}")
        try:
            if http(port, "/health", timeout=2.0)[0] == 200:
                break
        except (OSError, urllib.error.URLError):
            pass
        time.sleep(0.25)
    r.notes["ready_s"] = time.time() - r.t_start

    warm_up(port, spec["warm_up"], vocab, r.notes)
    cases = greedy_cases(port, spec["check"], vocab, "alone", False)
    r.notes["warm_s"] = time.time() - r.t_start

    def alive() -> None:
        if proc.poll() is not None:
            raise harness.RunFailure(
                f"scripts/serve.py exited {proc.returncode} under load: "
                f"{harness.tail(log_path)}")

    if r.sweep:
        # Finding the knee (a builder's tool, not a measurement of a cell):
        # the same mix at each rate in turn, against one server.
        table = []
        for rate in r.sweep:
            at = harness.overlay(mix, {"arrivals": {"rate_per_s": rate}})
            ph = offer(r, port, at, vocab, spec, alive, trace=False)
            ph["gen"].stop(float(at["drain_s"]))
            row = summarise(ph)
            table.append({"rate_per_s": rate, **{
                k: row[k] for k in ("attempted", "failed", "in_flight_mid",
                                    "in_flight_end", "ttft_p50_ms",
                                    "ttft_p95_ms", "itl_p50_ms",
                                    "itl_p95_ms", "output_tokens_per_s")}})
            print(json.dumps(table[-1]), flush=True)
        r.notes["sweep"] = table
    ph = offer(r, port, mix, vocab, spec, alive, trace=r.trace)
    cases += greedy_cases(port, spec["check"], vocab, "busy", True)
    ph["gen"].stop(0.0 if ph["closed"] else float(mix["drain_s"]))
    final = scrape(port)

    os.killpg(proc.pid, signal.SIGTERM)
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        raise harness.RunFailure("the server did not exit within 120 s of "
                                 "SIGTERM")
    if rc != 0:
        raise harness.RunFailure(f"scripts/serve.py exited {rc} after "
                                 f"SIGTERM: {harness.tail(log_path, 5)}")

    cases_file = r.path("cases.json")
    with open(cases_file, "w") as f:
        json.dump(cases, f)
    kept, hit = r.cached_reference(
        [[c["key"], c["prompt_ids"], c["tokens"]] for c in cases],
        lambda out: r.run_check("serve", ["--cases", cases_file], out,
                                spec["check"]["timeout_s"]))
    reference = harness.read_json(kept)
    verdict = judge(cases, reference, spec["check"]["tolerance"])
    r.notes["reference_check"] = {**verdict, "from_cache": hit,
                                  "reference_device": reference["device"],
                                  **r.judged_by()}

    row = summarise(ph)
    w0, w1 = ph["w0"], ph["w1"]
    compiled = r.compilations_between(w0, w1)
    # Means over every gap and every request of the window: the tails
    # beside them (per-layer metrics) rest on too few requests to hold a
    # bound of 0.10 (PERF.md section 2).
    e2e = {"itl_mean_ms": row["itl_mean_ms"],
           "output_tokens_per_s": row["output_tokens_per_s"]}
    if not ph["closed"]:
        e2e["ttft_mean_ms"] = row["ttft_mean_ms"]
    r.notes.update(row)
    r.notes.update({
        "waiting_at_window_end": ph["after"].get("dlti_waiting"),
        "compilations_in_window": len(compiled),
        "compiled_in_window": [[x["event"], x.get("seconds")]
                               for x in compiled[:5]],
        "preemptions": counter(final, "dlti_preemptions"),
        "prefill_batches_in_window":
            counter(ph["after"], "dlti_prefill_batches")
            - counter(ph["before"], "dlti_prefill_batches"),
    })
    return {
        "kind": "serve",
        "correct": bool(verdict["ok"] and not compiled),
        "attempted": row["attempted"], "failed": row["failed"],
        "w0": w0, "w1": w1, "e2e": e2e, "facts": facts,
        "metrics_before": ph["before"], "metrics_after": ph["after"],
        "latencies": {k: row[k] for k in ("ttft_p50_ms", "ttft_p95_ms",
                                          "itl_p50_ms", "itl_p95_ms")},
        "profile_dir": ph["traced"]["dir"] if ph["traced"] else None,
    }
