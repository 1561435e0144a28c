"""The load generator against a small fake SSE server: open-loop timing
from the due time, lateness, failures, and the closed loop."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import bench_paths  # noqa: F401

import loadgen
import stats


class FakeServer:
    """Streams ``max_tokens`` tokens ``<7> `` spaced ``gap_s`` apart after
    ``first_s``; a prompt starting with ``<666>`` gets a 429; one starting
    with ``<555>`` stops one token short without an EOS."""

    def __init__(self, first_s=0.05, gap_s=0.01):
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                body = json.loads(self.rfile.read(
                    int(self.headers["Content-Length"])))
                outer.seen.append((time.time(), body))
                if body["prompt"].startswith("<666>"):
                    self.send_response(429)
                    self.end_headers()
                    self.wfile.write(b'{"error": "busy"}')
                    return
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def chunk(obj):
                    payload = f"data: {json.dumps(obj)}\n\n".encode()
                    self.wfile.write(f"{len(payload):x}\r\n".encode()
                                     + payload + b"\r\n")
                    self.wfile.flush()

                n = body["max_tokens"]
                if body["prompt"].startswith("<555>"):
                    n -= 1
                time.sleep(first_s)
                for i in range(n):
                    if i:
                        time.sleep(gap_s)
                    chunk({"choices": [{"text": "<7> ",
                                        "finish_reason": None}]})
                chunk({"choices": [{"text": "", "finish_reason": "length"}],
                       "usage": {"completion_tokens": n}})

        self.seen = []
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       daemon=True)
        self.thread.start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self.thread.join(timeout=5)
        assert not self.thread.is_alive()


@pytest.fixture()
def server():
    s = FakeServer()
    yield s
    s.close()


def req(i, prompt="<5> <6>", n=4):
    return {"index": i, "prompt": prompt, "max_tokens": n, "seed": 1 + i}


def test_open_loop_sends_at_the_due_time_and_times_from_it(server):
    gen = loadgen.LoadGenerator(server.port, timeout_s=10)
    t0 = time.time() + 0.2
    schedule = [{**req(i), "due_s": 0.1 * i} for i in range(5)]
    gen.start_open(schedule, t0)
    time.sleep(1.2)
    gen.stop(drain_s=3.0)
    recs = sorted(gen.records, key=lambda r: r["index"])
    assert len(recs) == 5 and gen.in_flight == 0
    for i, r in enumerate(recs):
        assert r["due"] == pytest.approx(t0 + 0.1 * i)
        assert 0 <= r["sent"] - r["due"] < 0.1, "lateness is what it says"
        assert len(r["token_times"]) == r["tokens"] == 4
        assert not loadgen.request_failed(r)
    ttft = stats.ttfts_due_in_window(recs, t0, t0 + 10)
    assert all(0.05 <= t < 0.3 for t in ttft)
    late = stats.lateness(recs, t0, t0 + 10)
    assert late["n"] == 5 and late["max_s"] < 0.1


def test_a_generator_that_starts_late_reports_it_and_the_wait_counts(server):
    gen = loadgen.LoadGenerator(server.port, timeout_s=10)
    t0 = time.time() - 0.5  # everything was due half a second ago
    gen.start_open([{**req(0), "due_s": 0.0}], t0)
    time.sleep(0.5)
    gen.stop(drain_s=3.0)
    (r,) = gen.records
    assert r["sent"] - r["due"] >= 0.5
    assert r["token_times"][0] - r["due"] >= 0.55  # due time + first token


def test_refused_and_short_requests_count_as_failed(server):
    gen = loadgen.LoadGenerator(server.port, timeout_s=10)
    schedule = [{**req(0, "<666> <1>"), "due_s": 0.0},
                {**req(1, "<555> <1>"), "due_s": 0.0},
                {**req(2), "due_s": 0.0}]
    gen.start_open(schedule, time.time())
    time.sleep(0.6)
    gen.stop(drain_s=3.0)
    by = {r["index"]: r for r in gen.records}
    assert loadgen.request_failed(by[0]) and "429" in by[0]["error"]
    assert loadgen.request_failed(by[1]) and by[1]["tokens"] == 3
    assert not loadgen.request_failed(by[2])
    eos = dict(by[2], finish="stop", tokens=1)
    assert not loadgen.request_failed(eos), "an early EOS is no failure"


def test_closed_loop_keeps_its_clients_busy_and_stops_on_request(server):
    gen = loadgen.LoadGenerator(server.port, timeout_s=10)
    pool = [req(i) for i in range(3)]  # offered round and round
    gen.start_closed(pool, clients=2, t0=time.time(), stagger_s=0.05)
    time.sleep(0.8)
    assert gen.in_flight <= 2
    gen.stop(drain_s=0.0)
    assert gen.in_flight == 0
    assert len(gen.records) >= 6, "the pool was offered more than once"
    starts = sorted(r["sent"] for r in gen.records)
    assert starts[1] - starts[0] >= 0.04, "first requests are staggered"
