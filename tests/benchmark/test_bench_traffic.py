"""The traffic generator: the same seed gives the same bytes, another seed
the same trace with other tokens (training: another order of the same
documents)."""

import json
import os

import pytest

from bench_paths import BENCH

import traffic


def mix(name):
    return json.load(open(os.path.join(BENCH, "traffic", name + ".json")))


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_request_pool_is_byte_identical_for_a_seed(name):
    a = traffic.request_pool(mix(name), 50, 2147483659, 32000)
    b = traffic.request_pool(mix(name), 50, 2147483659, 32000)
    assert json.dumps(a) == json.dumps(b)


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_another_seed_offers_the_same_trace_with_other_tokens(name):
    spec = mix(name)
    a = traffic.request_pool(spec, 80, 1, 32000)
    b = traffic.request_pool(spec, 80, 2, 32000)
    assert [q["prompt"] for q in a] != [q["prompt"] for q in b]
    assert [q["seed"] for q in a] != [q["seed"] for q in b]
    seq = lambda pool: [(q["prompt_tokens"], q["max_tokens"]) for q in pool]
    assert seq(a) == seq(b), "same lengths in the same order"


def test_the_schedule_comes_from_the_shape_seed_alone():
    chat = mix("chat")
    assert traffic.arrival_offsets(chat, 30.0) == \
        traffic.arrival_offsets(chat, 30.0)
    other = dict(chat, shape_seed=chat["shape_seed"] + 1)
    assert traffic.arrival_offsets(chat, 30.0) != \
        traffic.arrival_offsets(other, 30.0)
    seq = lambda pool: [(q["prompt_tokens"], q["max_tokens"]) for q in pool]
    assert seq(traffic.request_pool(chat, 60, 1, 32000)) != \
        seq(traffic.request_pool(other, 60, 1, 32000))


def test_prompts_hold_the_tokens_they_say_and_stay_in_their_clamp():
    spec = mix("chat")
    for q in traffic.request_pool(spec, 200, 7, 32000):
        words = q["prompt"].split()
        assert len(words) + 1 == q["prompt_tokens"]  # + BOS
        assert spec["prompt_tokens"]["min"] <= q["prompt_tokens"] \
            <= spec["prompt_tokens"]["max"]
        assert spec["output_tokens"]["min"] <= q["max_tokens"] \
            <= spec["output_tokens"]["max"]
        assert all(3 <= int(w[1:-1]) < 32000 for w in words)


def test_arrivals_come_at_the_rate_the_file_names():
    spec = mix("chat")
    offsets = traffic.arrival_offsets(spec, 400.0)
    assert offsets == sorted(offsets) and offsets[-1] < 400.0
    rate = len(offsets) / 400.0
    assert abs(rate - spec["arrivals"]["rate_per_s"]) < 0.25


def test_a_burst_multiplies_the_rate_inside_its_span_only():
    spec = dict(mix("chat"))
    spec["arrivals"] = {"loop": "open", "rate_per_s": 2.0,
                        "burst": {"factor": 4.0, "start_frac": 0.4,
                                  "span_frac": 0.2}}
    offsets = traffic.arrival_offsets(spec, 1000.0)
    inside = sum(1 for t in offsets if 400 <= t < 600) / 200.0
    outside = sum(1 for t in offsets if not 400 <= t < 600) / 800.0
    assert 6.5 < inside < 9.5 and 1.6 < outside < 2.4


def test_training_documents_same_multiset_other_order_same_tokens():
    spec = mix("lora_sft")
    a = traffic.training_documents(spec, 11)
    b = traffic.training_documents(spec, 12)
    assert a == traffic.training_documents(spec, 11)
    assert a != b
    assert sorted(map(len, a)) == sorted(map(len, b))
    assert traffic.document_tokens(a, 2048) == traffic.document_tokens(b, 2048)
    assert max(len(t) + 2 for t in a) <= 2048
    assert len(a) == spec["documents"]["count"]


def test_document_tokens_counts_bos_eos_and_truncates():
    assert traffic.document_tokens(["abc", "x" * 100], 50) == 5 + 50
