"""One general traffic generator, driven by a traffic file's parameters.

The arithmetic (clamped lognormal lengths, Poisson arrivals, a flash crowd
by thinning) is copied from ``dlti_tpu/benchmarks/traces.py`` so that the
yardstick lives with the benchmark. What differs: a serving mix is a fixed
trace. Its due times and the lengths of its requests, in their order, are
drawn from the ``shape_seed`` in the traffic file; ``--seed`` draws the
prompts' token ids and the sampling seeds. Every seed therefore offers the
same work at the same moments (with the order permuted by seed, which
requests fell into a 40 s window moved completed tokens per second by
+-12 % - my chip runs, PR 23). Training documents keep one multiset of
lengths and are ordered by ``--seed``: the packer evens the order out.

Standard library only; the same seed gives byte-identical output.
"""

from __future__ import annotations

import json
import math
import random


def lognormal_int(rng: random.Random, median: float, sigma: float,
                  lo: int, hi: int) -> int:
    v = int(round(rng.lognormvariate(math.log(max(1.0, median)), sigma)))
    return max(lo, min(hi, v))


def _lengths(rng: random.Random, spec: dict, n: int) -> list:
    return [lognormal_int(rng, spec["median"], spec["sigma"],
                          spec["min"], spec["max"]) for _ in range(n)]


def arrival_offsets(traffic: dict, horizon_s: float) -> list:
    """Due times (seconds from the start of offered load) of an open-loop
    mix over ``horizon_s``, from the shape seed alone; a ``burst``
    multiplies the rate inside its span by thinning a process at the
    ceiling rate."""
    arr = traffic["arrivals"]
    rate = float(arr["rate_per_s"])
    burst = arr.get("burst")
    ceiling = rate * (float(burst["factor"]) if burst else 1.0)
    shape = random.Random(int(traffic["shape_seed"]))
    n = int(math.ceil(ceiling * horizon_s * 1.5)) + 16
    gaps = [shape.expovariate(ceiling) for _ in range(n)]
    thin = [shape.random() for _ in range(n)]
    out, t = [], 0.0
    for gap, u in zip(gaps, thin):
        t += gap
        if t >= horizon_s:
            break
        if burst:
            lo = float(burst["start_frac"]) * horizon_s
            hi = lo + float(burst["span_frac"]) * horizon_s
            r = ceiling if lo <= t < hi else rate
            if u * ceiling > r:
                continue
        out.append(round(t, 6))
    return out


def request_pool(traffic: dict, n: int, seed: int, vocab_size: int) -> list:
    """``n`` requests: prompt and output lengths from the shape seed;
    prompt token ids and the sampling seed from ``seed``.
    ``prompt_tokens`` counts the BOS the server prepends."""
    shape = random.Random(int(traffic["shape_seed"]) + 1)
    prompts = _lengths(shape, traffic["prompt_tokens"], n)
    outputs = _lengths(shape, traffic["output_tokens"], n)
    rng = random.Random(int(seed))
    pool = []
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        ids = [rng.randrange(3, vocab_size) for _ in range(p - 1)]
        pool.append({"index": i, "prompt_tokens": p, "max_tokens": o,
                     "prompt": " ".join(f"<{t}>" for t in ids),
                     "seed": rng.randrange(1, 2**31 - 1)})
    return pool


def training_documents(traffic: dict, seed: int) -> list:
    """Synthetic instruction/answer documents for the training cells: byte
    lengths from the shape seed (so every seed packs the same multiset),
    order and text from ``seed``. The byte tokenizer makes one token of one
    byte and adds BOS and EOS."""
    docs = traffic["documents"]
    shape = random.Random(int(traffic["shape_seed"]))
    lengths = _lengths(shape, docs["tokens"], int(docs["count"]))
    rng = random.Random(int(seed))
    rng.shuffle(lengths)
    alphabet = "abcdefghijklmnopqrstuvwxyz      \n"
    head = "### Instruction:\n"
    texts = []
    for n in lengths:
        body = max(1, n - 2)  # BOS and EOS make up the rest
        texts.append((head + "".join(rng.choices(alphabet, k=body)))[:body])
    return texts


def document_tokens(texts: list, seq_len: int) -> int:
    """Tokens the byte tokenizer makes of ``texts`` (BOS + bytes + EOS,
    truncated to ``seq_len``): the non-padding tokens of one epoch."""
    return sum(min(len(t.encode("utf-8")) + 2, seq_len) for t in texts)


def write_jsonl(path: str, texts: list) -> None:
    with open(path, "w") as f:
        for t in texts:
            f.write(json.dumps({"text": t}) + "\n")
