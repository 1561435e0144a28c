"""HTTP server + load generator tests (tiny model, ephemeral port, CPU).

End-to-end over real sockets: OpenAI-compatible routes, streaming SSE,
chat templating, the async engine facade, and the Locust-equivalent load
generator driving the live server.
"""

import http.client
import json
import subprocess
import threading

import jax
import jax.numpy as jnp
import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.data.tokenizer import ByteTokenizer
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from dlti_tpu.serving.server import ServerConfig, llama2_chat_prompt, make_server

CFG = MODEL_PRESETS["llama_tiny"]


@pytest.fixture(scope="module")
def live_server():
    model = LlamaForCausalLM(CFG, None)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=128, max_model_len=128,
                      cache_dtype="float32", eos_token_id=-1)
    engine = InferenceEngine(CFG, params, ec)
    tok = ByteTokenizer()
    httpd, async_engine = make_server(
        engine, tok, ServerConfig(host="127.0.0.1", port=0,
                                  default_params=SamplingParams(max_tokens=8)))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield "127.0.0.1", port
    httpd.shutdown()
    async_engine.shutdown()
    httpd.server_close()


def _post(host, port, path, body):
    conn = http.client.HTTPConnection(host, port, timeout=120)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _get(host, port, path):
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = json.loads(resp.read())
    conn.close()
    return resp.status, data


def test_health_models_stats(live_server):
    host, port = live_server
    assert _get(host, port, "/health") == (200, {"status": "ok"})
    status, models = _get(host, port, "/v1/models")
    assert status == 200 and models["data"][0]["id"] == "dlti-tpu-model"
    status, stats = _get(host, port, "/stats")
    assert status == 200 and "free_blocks" in stats


def test_debug_slo_404_when_disabled(live_server):
    # This server was started without TelemetryConfig.slo — the route
    # must say so instead of returning an empty objectives dict (the
    # live-agreement path in test_traces.py covers the enabled side).
    host, port = live_server
    status, body = _get(host, port, "/debug/slo")
    assert status == 404
    assert "slo" in body.get("error", {}).get("message", "").lower()


def test_metrics_prometheus_exposition(live_server):
    """GET /metrics renders the /stats counters in Prometheus text
    format (vLLM-parity observability): TYPE lines + numeric samples,
    scrapeable without an adapter."""
    host, port = live_server
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type", "").startswith("text/plain")
    text = resp.read().decode()
    conn.close()
    assert "# TYPE dlti_free_blocks gauge" in text
    assert "# TYPE dlti_requests counter" in text
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, value = line.split()
        assert name.startswith("dlti_")
        float(value)  # every sample parses as a number


def test_completions_roundtrip(live_server):
    host, port = live_server
    status, data = _post(host, port, "/v1/completions", {
        "prompt": "hello", "max_tokens": 6, "temperature": 0.0,
    })
    assert status == 200, data
    obj = json.loads(data)
    assert obj["object"] == "text_completion"
    assert obj["usage"]["completion_tokens"] == 6
    assert obj["choices"][0]["finish_reason"] == "length"
    assert isinstance(obj["choices"][0]["text"], str)


def test_completions_deterministic_greedy(live_server):
    host, port = live_server
    body = {"prompt": "abc", "max_tokens": 5, "temperature": 0.0}
    _, d1 = _post(host, port, "/v1/completions", body)
    _, d2 = _post(host, port, "/v1/completions", body)
    assert json.loads(d1)["choices"][0]["text"] == json.loads(d2)["choices"][0]["text"]


def test_stop_matcher_invariants():
    """Property test for the windowed stop scanner (no server needed):
    over randomized stops and incremental text feeds, the emitted prefix
    never contains a stop string, the cut always equals the earliest
    full-text match, and the safe boundary never retracts emitted
    text."""
    import random

    from dlti_tpu.serving.server import _Handler

    rng = random.Random(7)
    alphabet = "abc"
    for _ in range(300):
        stops = tuple(
            "".join(rng.choice(alphabet) for _ in range(rng.randint(1, 3)))
            for _ in range(rng.randint(1, 3)))
        full = "".join(rng.choice(alphabet) for _ in range(rng.randint(1, 30)))
        matcher = _Handler._StopMatcher(stops)
        text, emitted = "", 0
        cut = None
        while len(text) < len(full) and cut is None:
            text = full[: len(text) + rng.randint(1, 4)]
            cut, safe = matcher.feed(text)
            if cut is not None:
                break
            assert safe >= emitted, (full, stops, text, safe, emitted)
            for s in stops:
                assert s not in text[:safe], (full, stops, text, safe)
            emitted = safe
        expected = min((i for i in (full[: len(text)].find(s)
                                    for s in stops) if i != -1),
                       default=None)
        assert cut == expected, (full, stops, text, cut, expected)


def _pick_stop(host, port):
    """(full_text, stop, request_body): a per-request-seeded sampled
    completion (reproducible by the engine's seed contract) and an inner
    2-gram whose FIRST occurrence is past index 0, so truncation is
    non-trivial; falls back to index 0 if the tiny model's output is too
    repetitive."""
    base = {"prompt": "abcdef", "max_tokens": 12, "temperature": 1.0,
            "seed": 11}
    _, d = _post(host, port, "/v1/completions", base)
    full = json.loads(d)["choices"][0]["text"]
    assert len(full) >= 2, f"output too short to test stops: {full!r}"
    stop = full[0:2]
    for i in range(1, len(full) - 1):
        cand = full[i:i + 2]
        if full.find(cand) == i:
            stop = cand
            break
    return full, stop, base


def test_stop_strings_full_response(live_server):
    """OpenAI `stop` strings (token-boundary-agnostic, matched on
    detokenized text): the response truncates BEFORE the match, excludes
    the stop string, reports finish_reason stop, and the engine is
    early-cancelled instead of decoding to max_tokens."""
    host, port = live_server
    full, stop, base = _pick_stop(host, port)
    _, d = _post(host, port, "/v1/completions", {**base, "stop": stop})
    obj = json.loads(d)
    got = obj["choices"][0]["text"]
    assert got == full[: full.find(stop)], (full, stop, got)
    assert stop not in got
    assert obj["choices"][0]["finish_reason"] == "stop"
    # invalid stop values are a 400, not a crashed stepper
    status, d = _post(host, port, "/v1/completions",
                      {**base, "stop": ["a", "b", "c", "d", "e"]})
    assert status == 400


def test_stop_strings_streaming(live_server):
    """Streaming with `stop`: the stop string is never emitted in any
    delta (held back across token boundaries), and the final chunk
    carries finish_reason stop."""
    host, port = live_server
    full, stop, base = _pick_stop(host, port)

    conn = http.client.HTTPConnection(host, port, timeout=120)
    conn.request("POST", "/v1/completions",
                 json.dumps({**base, "stop": stop, "stream": True}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    raw = resp.read().decode()
    conn.close()
    deltas, finish = [], None
    for line in raw.splitlines():
        if not line.startswith("data: ") or line == "data: [DONE]":
            continue
        ev = json.loads(line[len("data: "):])
        ch = ev["choices"][0]
        if ch.get("text"):
            deltas.append(ch["text"])
        if ch.get("finish_reason"):
            finish = ch["finish_reason"]
    text = "".join(deltas)
    assert text == full[: full.find(stop)], (full, stop, text)
    assert stop not in text
    assert finish == "stop"


def test_stop_strings_streaming_tail_flush(live_server):
    """A stop string that never matches but whose PREFIX ends the output
    engages the hold-back; the done-event flush must still deliver the
    held tail so streaming equals non-streaming."""
    host, port = live_server
    full, _, base = _pick_stop(host, port)
    stop = full[-1] + "\x00"  # prefix = final char; full match impossible

    conn = http.client.HTTPConnection(host, port, timeout=120)
    conn.request("POST", "/v1/completions",
                 json.dumps({**base, "stop": stop, "stream": True}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    raw = resp.read().decode()
    conn.close()
    deltas, finish = [], None
    for line in raw.splitlines():
        if not line.startswith("data: ") or line == "data: [DONE]":
            continue
        ev = json.loads(line[len("data: "):])
        ch = ev["choices"][0]
        if ch.get("text"):
            deltas.append(ch["text"])
        if ch.get("finish_reason"):
            finish = ch["finish_reason"]
    assert "".join(deltas) == full, (full, deltas)
    assert finish == "length"


def test_n_choices(live_server):
    """OpenAI `n`: n concurrent engine requests -> n indexed choices;
    a user seed derives per-choice seeds so choices differ but the whole
    response reproduces; guards reject stream+n and greedy+n."""
    host, port = live_server
    body = {"prompt": "abcdef", "max_tokens": 6, "temperature": 1.0,
            "seed": 3, "n": 3}
    status, d = _post(host, port, "/v1/completions", body)
    assert status == 200, d
    obj = json.loads(d)
    texts = [c["text"] for c in sorted(obj["choices"],
                                       key=lambda c: c["index"])]
    assert len(texts) == 3
    assert len(set(texts)) > 1, "per-choice seeds produced identical samples"
    assert obj["usage"]["completion_tokens"] == 18
    # Reproducible end to end.
    _, d2 = _post(host, port, "/v1/completions", body)
    assert [c["text"] for c in sorted(json.loads(d2)["choices"],
                                      key=lambda c: c["index"])] == texts
    # Loud rejections.
    status, _ = _post(host, port, "/v1/completions",
                      {**body, "stream": True})
    assert status == 400
    status, _ = _post(host, port, "/v1/completions",
                      {**body, "temperature": 0.0})
    assert status == 400


def test_n_choices_submit_fault_cancels_submitted(live_server):
    """Orphan-burn fix: when a submit raises mid-loop for
    n > 1, every already-submitted choice gets cancel_requested set —
    they must not decode to max_tokens into queues nobody reads."""
    host, port = live_server
    # Reach the handler class and its AsyncEngine through the live server
    # (the BoundHandler type holds them as class attributes).
    import dlti_tpu.serving.server as server_mod

    # Fetch the async_engine via a throwaway request? Not needed: the
    # fixture's engine is reachable through the module-level make_server
    # wiring only, so patch at the AsyncEngine class level instead —
    # fail the SECOND submit of an n=3 request, then restore.
    orig_submit = server_mod.AsyncEngine.submit
    state = {"calls": 0, "submitted": []}

    def flaky_submit(self, prompt_ids, params, request_id=None):
        state["calls"] += 1
        if state["calls"] == 2:
            raise RuntimeError("injected: stepper parked mid-loop")
        req, q = orig_submit(self, prompt_ids, params, request_id)
        state["submitted"].append(req)
        return req, q

    server_mod.AsyncEngine.submit = flaky_submit
    try:
        status, d = _post(host, port, "/v1/completions",
                          {"prompt": "abcdef", "max_tokens": 64,
                           "temperature": 1.0, "n": 3})
    finally:
        server_mod.AsyncEngine.submit = orig_submit
    assert status == 503, d
    assert len(state["submitted"]) == 1
    assert state["submitted"][0].cancel_requested, \
        "already-submitted choice left decoding after mid-loop fault"


def test_chat_completions(live_server):
    host, port = live_server
    status, data = _post(host, port, "/v1/chat/completions", {
        "messages": [{"role": "system", "content": "Be brief."},
                     {"role": "user", "content": "hi"}],
        "max_tokens": 4, "temperature": 0.0,
    })
    assert status == 200, data
    obj = json.loads(data)
    assert obj["object"] == "chat.completion"
    assert obj["choices"][0]["message"]["role"] == "assistant"


def test_streaming_sse(live_server):
    host, port = live_server
    conn = http.client.HTTPConnection(*live_server, timeout=120)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": "xy", "max_tokens": 5, "temperature": 0.0, "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/event-stream")
    raw = resp.read().decode()
    conn.close()
    events = [l[5:].strip() for l in raw.splitlines() if l.startswith("data:")]
    assert events[-1] == "[DONE]"
    finals = [json.loads(e) for e in events[:-1]]
    assert any(c["choices"][0]["finish_reason"] == "length" for c in finals)


def test_error_paths(live_server):
    host, port = live_server
    status, data = _post(host, port, "/v1/completions", {"prompt": ""})
    assert status == 400
    status, _ = _post(host, port, "/v1/chat/completions", {"messages": []})
    assert status == 400
    status, _ = _post(host, port, "/nope", {})
    assert status == 404
    # Prompt longer than max_model_len rejected cleanly.
    status, data = _post(host, port, "/v1/completions",
                         {"prompt": "z" * 500, "max_tokens": 2})
    assert status == 400
    assert b"max_model_len" in data
    # Malformed sampling params must 400 this request, not crash the
    # engine stepper thread (which would error out every in-flight stream).
    for bad in ({"seed": "abc"}, {"temperature": "hot"}, {"top_k": [1]}):
        status, data = _post(host, port, "/v1/completions",
                             {"prompt": "hi", **bad})
        assert status == 400, (bad, data)
    # Server still healthy after the bad requests.
    status, out = _post(host, port, "/v1/completions",
                        {"prompt": "hi", "max_tokens": 2, "seed": 1})
    assert status == 200


def test_llama2_chat_template():
    """Serve-time template must match the training format contract
    (scripts/prepare_dataset.py:12-25: "<s>[INST] q [/INST] a</s>")."""
    s = llama2_chat_prompt([{"role": "user", "content": "Q1"}])
    assert s == "[INST] Q1 [/INST]"
    s = llama2_chat_prompt([
        {"role": "system", "content": "SYS"},
        {"role": "user", "content": "Q1"},
        {"role": "assistant", "content": "A1"},
        {"role": "user", "content": "Q2"},
    ])
    assert s == "[INST] <<SYS>>\nSYS\n<</SYS>>\n\nQ1 [/INST] A1 [INST] Q2 [/INST]"


@pytest.fixture(scope="module")
def id_tok_server():
    """A server whose tokenizer renders EVERY sampled id as visible text
    (IdTokenizer — built for exactly this: a random-weight model's argmax
    ids exceed the byte tokenizer's printable range, so ByteTokenizer
    suppresses every SSE delta and zeroes streaming TTFT/TPOT)."""
    from dlti_tpu.data.tokenizer import IdTokenizer

    model = LlamaForCausalLM(CFG, None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    ec = EngineConfig(max_seqs=4, block_size=8, num_blocks=128,
                      max_model_len=128, cache_dtype="float32",
                      eos_token_id=-1)
    engine = InferenceEngine(CFG, params, ec)
    httpd, async_engine = make_server(
        engine, IdTokenizer(vocab_size=CFG.vocab_size),
        ServerConfig(host="127.0.0.1", port=0,
                     default_params=SamplingParams(max_tokens=8)))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield "127.0.0.1", port
    httpd.shutdown()
    async_engine.shutdown()
    httpd.server_close()


def test_loadgen_against_live_server(id_tok_server):
    from dlti_tpu.benchmarks import LoadGenConfig, run_load_test

    host, port = id_tok_server
    report = run_load_test(LoadGenConfig(
        host=host, port=port, num_requests=8, concurrency=4,
        max_tokens=4, stream=True, prompt="bench", timeout_s=120,
        scrape_server_metrics=True))
    assert report.num_ok == 8, report.errors
    assert report.output_tokens_per_s > 0
    assert report.ttft_p50_s > 0
    assert report.latency_p99_s >= report.latency_p50_s
    # On-engine histograms rode back with the report: the engine observed
    # every request's TTFT and queue time itself.
    ttft = report.server_histograms["dlti_request_ttft_seconds"]
    assert ttft["count"] >= 8 and ttft["mean"] > 0
    assert report.server_histograms["dlti_request_queue_time_seconds"][
        "count"] >= 8

    # Non-streaming path exercises usage-based token accounting.
    report = run_load_test(LoadGenConfig(
        host=host, port=port, num_requests=4, concurrency=2,
        max_tokens=4, stream=False, prompt="bench", timeout_s=120))
    assert report.num_ok == 4, report.errors
    assert report.output_tokens_per_s > 0


@pytest.mark.parametrize("n", [1, 2, 17, 448])
def test_id_tokenizer_extends_its_text_a_token_at_a_time(n):
    """``decode_appended`` is what lets a stream pay one piece a token:
    folded over an answer it has to be ``decode`` of every prefix."""
    from dlti_tpu.data.tokenizer import ByteTokenizer, IdTokenizer

    tok = IdTokenizer(vocab_size=200064)
    ids = [(7919 * i + 3) % 200064 for i in range(n)]
    text = ""
    for k in range(1, n + 1):
        text = tok.decode_appended(text, ids[:k])
        assert text == tok.decode(ids[:k])
    # a character of the byte tokenizer may span tokens: it offers none
    assert not hasattr(ByteTokenizer(), "decode_appended")


def _streamed(host, port, body):
    conn = http.client.HTTPConnection(host, port, timeout=120)
    conn.request("POST", "/v1/completions",
                 json.dumps({**body, "stream": True}),
                 {"Content-Type": "application/json"})
    raw = conn.getresponse().read().decode()
    conn.close()
    events = [json.loads(l[6:]) for l in raw.splitlines()
              if l.startswith("data: ") and l != "data: [DONE]"]
    return ([e["choices"][0]["text"] for e in events],
            [e["choices"][0]["finish_reason"] for e in events][-1])


@pytest.mark.parametrize("with_stop", [False, True], ids=["plain", "stop"])
def test_a_stream_extended_by_the_token_is_the_whole_answers_text(
        id_tok_server, with_stop):
    """The handler extends the id tokenizer's text by one token an event
    (no decode of the whole list): the deltas still add up to the text
    the whole answer decodes to, a delta a token, and a stop string cuts
    where it cuts the whole text."""
    host, port = id_tok_server
    body = {"prompt": "<5> <9> <11>", "max_tokens": 12, "temperature": 0.0}
    status, data = _post(host, port, "/v1/completions", body)
    assert status == 200
    full = json.loads(data)["choices"][0]["text"]
    pieces = full.split(" ")
    assert len(pieces) == 12 and all(p.startswith("<") for p in pieces)
    if not with_stop:
        deltas, finish = _streamed(host, port, body)
        assert [d for d in deltas if d] == \
            [pieces[0]] + [" " + p for p in pieces[1:]]
        assert finish == "length"
        return
    # the first piece that did not occur before it, from its middle on and
    # over the token boundary: held back, then matched across two events
    at = next(i for i in range(3, 11) if pieces[i] not in pieces[:i])
    stop = pieces[at][2:] + " " + pieces[at + 1][:2]
    deltas, finish = _streamed(host, port, {**body, "stop": stop})
    assert "".join(deltas) == full[:full.find(stop)]
    assert finish == "stop"


def test_native_allocator_contract(tmp_path):
    """C++ allocator obeys the same contract as the Python fallback; the
    loader builds it from native/*.cc when it is missing or stale."""
    from dlti_tpu.utils import native as native_mod

    # Fresh load (bypass module cache).
    native_mod._TRIED = False
    native_mod._LIB = None
    lib = native_mod.load_native_runtime()
    if lib is None:
        pytest.skip("native toolchain unavailable")

    from dlti_tpu.serving import BlockManager

    bm = BlockManager(num_blocks=8, block_size=4)
    assert bm._native is not None
    assert bm.num_free == 7
    a = bm.allocate(3)
    assert a is not None and len(set(a)) == 3 and 0 not in a
    assert bm.allocate(5) is None
    assert bm.num_free == 4
    bm.free(a)
    assert bm.num_free == 7


def test_stepper_fault_aborts_cleanly():
    """A faulted engine.step() errors exactly the in-flight consumers and
    leaves the engine EMPTY (slots + waiting freed): no hot-loop on a
    persistent fault, no decoding into deleted queues after a transient
    one."""
    from dlti_tpu.serving.server import AsyncEngine

    model = LlamaForCausalLM(CFG, None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=32,
                      max_model_len=32, cache_dtype="float32",
                      eos_token_id=-1)
    eng = InferenceEngine(CFG, params, ec)
    boom = {"n": 0}
    real_step = eng.step

    def flaky_step():
        boom["n"] += 1
        raise RuntimeError("injected device fault")

    eng.step = flaky_step
    aeng = AsyncEngine(eng)
    try:
        _, q = aeng.submit([3, 1, 4, 1, 5], SamplingParams(max_tokens=4))
        kind, payload = q.get(timeout=30)[:2]
        assert kind == "error" and "injected device fault" in payload
        # Engine drained: nothing left to step, stepper idles (no
        # unbounded retry of the failing program).
        assert not eng.has_work
        assert all(s.free for s in eng.slots) and not eng.waiting
        n_after_error = boom["n"]
        import time as _t
        _t.sleep(0.5)
        assert boom["n"] == n_after_error  # stepper is parked, not looping
        # Recovery: the engine works again for new requests.
        eng.step = real_step
        _, q2 = aeng.submit([2, 7, 1], SamplingParams(temperature=0.0,
                                                      max_tokens=3))
        events = [q2.get(timeout=60) for _ in range(4)]
        assert events[-1][0] == "done"
        assert sum(1 for e in events if e[0] == "token") == 3
    finally:
        aeng.shutdown()


# -- the engine's round in flight, at a stop and at a fault --------------------

def _tiny_engine(**over):
    model = LlamaForCausalLM(CFG, None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    kw = dict(max_seqs=4, block_size=8, num_blocks=64, max_model_len=96,
              cache_dtype="float32", eos_token_id=-1)
    kw.update(over)
    return InferenceEngine(CFG, params, EngineConfig(**kw))


def test_a_stop_with_a_round_in_flight_settles_it_and_ends_every_stream():
    """``AsyncEngine.shutdown()`` while requests decode: between two steps a
    round is in flight, so the stepper's way out (one ``abort_all``) waits
    for it and throws it away; every open stream gets its terminal frame,
    the thread ends, and no request holds a token that was not emitted to
    it."""
    from dlti_tpu.serving.server import AsyncEngine

    eng = _tiny_engine()
    in_flight_at_stop = []
    real_abort = eng.abort_all

    def abort_all(reason="abort"):
        in_flight_at_stop.append((reason, eng._inflight is not None))
        return real_abort(reason=reason)
    eng.abort_all = abort_all
    aeng = AsyncEngine(eng)
    streams = [aeng.submit([3 + i, 1, 4, 1, 5], SamplingParams(
        max_tokens=80, temperature=0.9, seed=i)) for i in range(3)]
    try:
        for _req, q in streams:
            assert [q.get(timeout=120)[0] for _ in range(3)] == ["token"] * 3
    finally:
        aeng.shutdown()
    assert not aeng._thread.is_alive()
    assert in_flight_at_stop == [("shutdown", True)]
    assert eng._inflight is None and all(s.free for s in eng.slots)
    for req, q in streams:
        events = []
        while not q.empty():
            events.append(q.get_nowait())
        assert events[-1] == ("error", "server shutting down")
        assert [e[0] for e in events[:-1]] == ["token"] * (len(events) - 1)
        assert 3 + len(events) - 1 == len(req.output_token_ids)


def test_a_step_that_faults_with_a_round_in_flight_ends_in_one_clean_abort():
    """The numeric guard judges a round when it is fetched, one launch later:
    the fault drops the round behind it too, the server's recovery is one
    ``abort_all`` that finds nothing in flight, nothing of the dead round
    was streamed, and the engine serves on."""
    from dlti_tpu.serving.engine import NumericFault
    from dlti_tpu.serving.server import AsyncEngine

    eng = _tiny_engine()
    real_fetch = eng.executor.fetch
    rounds = {"fetched": 0, "poison_at": 4}

    def fetch(arrays):
        host = real_fetch(arrays)
        if host[0].shape == (eng.cfg.max_seqs,):      # a decode round's
            rounds["fetched"] += 1
            if rounds["fetched"] == rounds["poison_at"]:
                host[1] = host[1] * float("nan")
        return host
    eng.executor.fetch = fetch
    aborts = []
    real_abort = eng.abort_all

    def abort_all(reason="abort"):
        aborts.append((reason, eng._inflight is not None))
        return real_abort(reason=reason)
    eng.abort_all = abort_all
    aeng = AsyncEngine(eng)
    try:
        req, q = aeng.submit([3, 1, 4, 1, 5], SamplingParams(
            max_tokens=40, temperature=0.0))
        events = []
        while not events or events[-1][0] == "token":
            events.append(q.get(timeout=120))
        assert events[-1][0] == "error" and "NumericFault" in events[-1][1]
        # the prefill's token and three rounds' were streamed, the fourth
        # round's was not, and the fifth (launched behind it) went with it
        assert len(events) - 1 == len(req.output_token_ids) == 4
        assert aborts == [("error", False)]
        assert eng._inflight is None and not eng.has_work
        assert eng.stats["numeric_faults"] == 1
        rounds["poison_at"] = -1
        _, q2 = aeng.submit([2, 7, 1], SamplingParams(temperature=0.0,
                                                      max_tokens=3))
        again = [q2.get(timeout=60) for _ in range(4)]
        assert [e[0] for e in again] == ["token"] * 3 + ["done"]
    finally:
        aeng.shutdown()
    assert isinstance(NumericFault("x"), RuntimeError)


def test_sigterm_mid_stream_exits_zero_and_ends_the_stream(tmp_path):
    """``scripts/serve.py`` under SIGTERM while a stream is open and a round
    is in flight: the process exits 0 (what the benchmark's harness waits
    for, 120 s at most) and the stream ends instead of hanging."""
    import os
    import signal
    import socket
    import sys
    import time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    log = open(tmp_path / "serve.log", "w")
    proc = subprocess.Popen(
        [sys.executable, "scripts/serve.py", "--random-init", "llama_tiny",
         "--tokenizer", "byte", "--host", "127.0.0.1", "--port", str(port),
         "--max-seqs", "4", "--num-blocks", "64", "--block-size", "8",
         "--max-model-len", "128"],
        cwd=root, stdout=log, stderr=subprocess.STDOUT)
    try:
        deadline = time.time() + 120
        while True:
            try:
                if _get("127.0.0.1", port, "/health")[0] == 200:
                    break
            except OSError:
                pass
            assert proc.poll() is None and time.time() < deadline, \
                open(tmp_path / "serve.log").read()[-2000:]
            time.sleep(0.25)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/v1/completions", json.dumps({
            "prompt": "hello there", "max_tokens": 100, "stream": True,
            "temperature": 1.0, "seed": 3}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        frames = []
        while len(frames) < 3:
            line = resp.readline()
            if line.startswith(b"data: "):
                frames.append(line)
        proc.send_signal(signal.SIGTERM)
        rest = resp.read()      # returns: the stream was ended, not left open
        assert proc.wait(timeout=60) == 0, \
            open(tmp_path / "serve.log").read()[-2000:]
        tail = [l for l in rest.split(b"\n") if l.startswith(b"data: ")]
        # whatever was written last is a whole frame: an answer's chunk, the
        # shutdown's error frame, or the end marker
        assert all(l == b"data: [DONE]" or json.loads(l[6:]) for l in tail)
    finally:
        if proc.poll() is None:
            proc.kill()
        log.close()
