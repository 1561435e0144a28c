"""The load generator: one thread, one asyncio loop, plain sockets.

It runs in a process that never imports JAX (the harness parent), so it
shares no interpreter lock with the engine. Open-loop requests are sent at
their due time whatever the server does, and every latency is counted from
when the request was *due*, so a stall shows in the requests it delayed;
how late each was actually sent is recorded as the generator's lateness. A
closed loop keeps ``clients`` requests in flight, each client sending its
next request when its last one ended.

Every streamed token gets a timestamp (``time.time()``, the clock the
chip-holding child stamps its compile events with). A record is one request:

    {"index", "due", "sent", "token_times": [...], "tokens", "asked",
     "finish", "error", "ended"}
"""

from __future__ import annotations

import asyncio
import json
import threading
import time

HOST = "127.0.0.1"


def _request_bytes(path: str, body: dict) -> bytes:
    data = json.dumps(body).encode()
    head = (f"POST {path} HTTP/1.1\r\nHost: {HOST}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(data)}\r\nConnection: close\r\n\r\n")
    return head.encode() + data


async def stream_completion(port: int, req: dict, due: float,
                            timeout_s: float) -> dict:
    """POST one streaming completion; stamp every token as it arrives."""
    rec = {"index": req["index"], "due": due, "sent": None,
           "token_times": [], "tokens": 0, "asked": req["max_tokens"],
           "finish": None, "error": None, "ended": None}
    body = {"prompt": req["prompt"], "max_tokens": req["max_tokens"],
            "temperature": req.get("temperature", 1.0), "stream": True,
            "seed": req["seed"]}
    writer = None
    try:
        reader, writer = await asyncio.open_connection(HOST, port)
        rec["sent"] = time.time()
        writer.write(_request_bytes("/v1/completions", body))
        await writer.drain()
        status = await asyncio.wait_for(reader.readline(), timeout_s)
        if b" 200 " not in status:
            rest = await asyncio.wait_for(reader.read(2048), timeout_s)
            rec["error"] = (status + rest).decode("utf-8", "replace")[:300]
            return rec
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout_s)
            if not line:
                break
            if not line.startswith(b"data: "):
                continue  # headers, chunk sizes, blank lines
            now = time.time()
            event = json.loads(line[6:])
            if "error" in event:
                rec["error"] = str(event["error"])[:300]
                break
            choice = event["choices"][0]
            n = choice.get("text", "").count("<")  # id tokenizer: "<id> "
            if n:
                rec["token_times"].extend([now] * n)
            if choice.get("finish_reason"):
                rec["finish"] = choice["finish_reason"]
                rec["tokens"] = event["usage"]["completion_tokens"]
                break
    except (OSError, asyncio.TimeoutError, ValueError, KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        rec["ended"] = time.time()
        if writer is not None:
            writer.close()
    return rec


def request_failed(rec: dict) -> bool:
    """Failed, refused, or fewer tokens than asked without an EOS."""
    if rec["error"] or rec["finish"] is None:
        return True
    if rec["finish"] == "length":
        return rec["tokens"] != rec["asked"]
    return rec["finish"] != "stop"


class LoadGenerator:
    """Runs offered load on a thread of its own until ``stop()``."""

    def __init__(self, port: int, timeout_s: float = 120.0):
        self.port = port
        self.timeout_s = timeout_s
        self.records: list = []       # finished requests, any order
        self.in_flight = 0
        self._loop = asyncio.new_event_loop()
        self._thread = None
        self._stopping = False
        self._tasks: set = set()

    # -- what the harness calls (from its own thread) ------------------
    def start_open(self, schedule: list, t0: float) -> None:
        """``schedule``: requests with ``due_s`` offsets from ``t0``."""
        self._start(self._open(schedule, t0))

    def start_closed(self, pool: list, clients: int, t0: float,
                     stagger_s: float = 0.0) -> None:
        """Client ``i`` sends its first request ``i * stagger_s`` after
        ``t0``: jobs ramp up, they do not all arrive in one instant."""
        self._start(self._closed(pool, clients, t0, stagger_s))

    def stop(self, drain_s: float) -> None:
        """Offer nothing new; give requests in flight ``drain_s`` to end,
        then drop them (the server cancels on disconnect)."""
        self._stopping = True
        deadline = time.time() + drain_s
        while self.in_flight and time.time() < deadline:
            time.sleep(0.05)
        try:
            self._loop.call_soon_threadsafe(self._cancel_all)
        except RuntimeError:
            pass  # the loop ran out of work and closed itself
        self._thread.join(timeout=30)

    # -- inside the loop -------------------------------------------------
    def _start(self, coro) -> None:
        def run():
            asyncio.set_event_loop(self._loop)
            try:
                self._loop.run_until_complete(coro)
            except asyncio.CancelledError:
                pass
            finally:
                # Let every cancelled request close its socket before the
                # loop goes away.
                left = asyncio.all_tasks(self._loop)
                for task in left:
                    task.cancel()
                self._loop.run_until_complete(
                    asyncio.gather(*left, return_exceptions=True))
                self._loop.close()

        self._thread = threading.Thread(target=run, name="loadgen",
                                        daemon=True)
        self._thread.start()

    def _cancel_all(self) -> None:
        for task in asyncio.all_tasks(self._loop):
            task.cancel()

    async def _one(self, req: dict, due: float) -> None:
        self.in_flight += 1
        try:
            rec = await stream_completion(self.port, req, due, self.timeout_s)
            self.records.append(rec)
        finally:
            self.in_flight -= 1

    async def _open(self, schedule: list, t0: float) -> None:
        for req in schedule:
            due = t0 + req["due_s"]
            delay = due - time.time()
            if delay > 0:
                await asyncio.sleep(delay)
            if self._stopping:
                break
            task = asyncio.ensure_future(self._one(req, due))
            self._tasks.add(task)
            task.add_done_callback(self._tasks.discard)
        while self._tasks:
            await asyncio.sleep(0.05)

    async def _closed(self, pool: list, clients: int, t0: float,
                      stagger_s: float) -> None:
        sent = [0]  # the pool is offered round and round: load never ends
        delay = t0 - time.time()
        if delay > 0:
            await asyncio.sleep(delay)

        async def client(i: int) -> None:
            await asyncio.sleep(i * stagger_s)
            while not self._stopping:
                req = pool[sent[0] % len(pool)]
                sent[0] += 1
                await self._one(req, time.time())

        await asyncio.gather(*(client(i) for i in range(clients)))
