"""A prefill call that cannot be built does not take the engine down.

On the chip a prefill shape nobody compiled can raise from its first call
(``RESOURCE_EXHAUSTED: XLA:TPU compile permanent error ... Used 18.28G of
15.75G hbm``): the program never ran and the donated cache is whole. The
executor turns that into ``PrefillCallRefused``; the scheduler runs the rows
again as one-row calls, and a one-row call that is refused costs its own
request (``InferenceEngine._prefill_refused``). Here on the tiny model and
the CPU, the refusal injected where the device raises it: at the program
call. The scheduler's half alone is in ``tests/test_engine_scheduler.py``.
"""

import jax
import numpy as np
import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from dlti_tpu.serving.executor import PrefillCallRefused

CFG = MODEL_PRESETS["llama_tiny"]

# What the chip says (chiprun_out/c_change, PR 39), shortened.
OOM = ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
       "memory in memory space hbm. Used 18.28G of 15.75G hbm. Exceeded hbm "
       "capacity by 2.53G.\n\nTotal hbm usage >= 18.79G:\n    reserved ...")


@pytest.fixture(scope="module")
def tiny_params():
    import jax.numpy as jnp

    from dlti_tpu.models import LlamaForCausalLM

    model = LlamaForCausalLM(CFG, None)
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, **over):
    kw = dict(max_seqs=4, block_size=8, num_blocks=64, max_model_len=64,
              cache_dtype="float32", eos_token_id=-1)
    kw.update(over)
    return InferenceEngine(CFG, params, EngineConfig(**kw))


def _refuse(eng, monkeypatch, when, spoil=False):
    """Prefill programs of ``eng`` whose call raises as the device does
    when ``when(rows, bucket)``; the calls that were refused. ``spoil``:
    the cache is gone when it raises (the call did run)."""
    real = eng.executor._prefill_fn
    refused = []

    def prefill_fn(bucket):
        fn = real(bucket)

        def call(params, cache, input_ids, *rest):
            if when(input_ids.shape[0], bucket):
                refused.append((input_ids.shape[0], bucket))
                if spoil:
                    for leaf in jax.tree_util.tree_leaves(cache):
                        leaf.delete()
                raise jax.errors.JaxRuntimeError(OOM)
            return fn(params, cache, input_ids, *rest)
        return call

    monkeypatch.setattr(eng.executor, "_prefill_fn", prefill_fn)
    return refused


def _no_abort(eng, monkeypatch):
    def abort_all(reason="abort"):
        raise AssertionError("abort_all was called")
    monkeypatch.setattr(eng, "abort_all", abort_all)


def _drain(eng):
    while eng.has_work:
        eng.step()


PROMPTS = [[1, 2, 3, 4, 5], [6, 7, 8, 9, 10, 11], [12, 13, 14, 15, 16],
           [17, 18, 19, 20, 21, 22, 23]]


@pytest.mark.parametrize("kind", ["greedy", "seeded"])
def test_a_refused_four_row_call_serves_what_the_four_row_call_serves(
        tiny_params, monkeypatch, engine_log, kind):
    """Tokens, log-probs and the cache after the four one-row calls equal
    those of the one call on an engine that is not refused it (each row's
    key and count are its own); the shape is not asked for twice."""
    sps = [SamplingParams(temperature=0.0 if kind == "greedy" else 0.9,
                          seed=None if kind == "greedy" else 40 + i,
                          max_tokens=6) for i in range(4)]
    whole = _engine(tiny_params)
    split = _engine(tiny_params)
    _no_abort(split, monkeypatch)
    refused = _refuse(split, monkeypatch, lambda rows, bucket: rows == 4)
    outs = []
    for eng in (whole, split):
        reqs = [eng.submit(p, sp) for p, sp in zip(PROMPTS, sps)]
        eng.step()      # the admission: one call of four rows, or four of one
        assert [len(r.output_token_ids) for r in reqs] == [1] * 4
        cache = [np.asarray(x) for x in
                 jax.tree_util.tree_leaves(eng.executor.cache)]
        _drain(eng)
        outs.append(([(r.output_token_ids, r.finish_reason) for r in reqs],
                     [r.output_logprobs for r in reqs], cache))
    (tokens, lps, cache), (tokens_s, lps_s, cache_s) = outs
    assert tokens_s == tokens
    np.testing.assert_allclose(lps_s, lps, atol=1e-5)
    for a, b in zip(cache, cache_s):
        # (block 0 is the trash block: padding rows' writes land there)
        np.testing.assert_allclose(a[1:], b[1:], atol=1e-5)
    assert whole.stats["prefill_batches"] == 1
    st = split.stats
    assert (st["prefill_batches"], st["prefill_calls_split"],
            st["prefill_calls_failed"]) == (4, 1, 0)
    assert refused == [(4, 8)]
    assert split.executor.refused_prefill_shapes == {(4, 8, 1)}
    lines = [r.getMessage() for r in engine_log.records
             if r.levelname == "WARNING"]
    assert len(lines) == 1, [l[:60] + l[-40:] for l in lines]
    assert lines[0].startswith("jit_prefill: prefill program refused at "
                               "4 rows x 8 tokens x 1 blocks a row: ")
    assert lines[0].endswith("Used 18.28G of 15.75G hbm. Exceeded hbm "
                             "capacity by 2.53G.")
    # the same wave again: four calls of one row, nothing refused, no line
    again = [split.submit(p, sp) for p, sp in zip(PROMPTS, sps)]
    _drain(split)
    assert [(r.output_token_ids, r.finish_reason) for r in again] == tokens
    assert refused == [(4, 8)] and st["prefill_batches"] == 8
    assert len([r for r in engine_log.records
                if r.levelname == "WARNING"]) == 1


def test_a_refused_one_row_call_fails_that_request_alone(
        tiny_params, monkeypatch, engine_log):
    """Three streams run on to their ends with the tokens they have when
    nothing is refused; the fourth request, whose bucket has no program
    that fits, ends as an error and gives its slot and blocks back."""
    sp = SamplingParams(temperature=0.0, max_tokens=14)
    want = _engine(tiny_params).generate(PROMPTS[:3], sp)
    eng = _engine(tiny_params)
    _no_abort(eng, monkeypatch)
    refused = _refuse(eng, monkeypatch, lambda rows, bucket: bucket == 32)
    running = [eng.submit(p, sp) for p in PROMPTS[:3]]
    for _ in range(4):
        eng.step()
    assert eng._inflight is not None     # a round in flight all the while
    bad = eng.submit(list(range(30, 50)), sp)
    _drain(eng)
    assert bad.finish_reason == "error" and bad.output_token_ids == []
    assert [(r.output_token_ids, r.finish_reason) for r in running] == \
        [(w.output_token_ids, w.finish_reason) for w in want]
    st = eng.stats
    assert (st["prefill_calls_split"], st["prefill_calls_failed"]) == (0, 1)
    assert refused == [(1, 32)]
    assert eng.block_manager.num_free == eng.cfg.num_blocks - 1
    lines = [r.getMessage() for r in engine_log.records
             if r.levelname == "WARNING"]
    assert len(lines) == 2   # the executor's, then the request's
    assert lines[0].startswith("jit_prefill: prefill program refused at "
                               "1 rows x 32 tokens x 4 blocks a row: ")
    assert bad.request_id in lines[1] and "Used 18.28G" in lines[1]
    # and the engine serves on, that bucket's next request failing alone too
    later = eng.submit(PROMPTS[3], sp)
    worse = eng.submit(list(range(60, 80)), sp)
    _drain(eng)
    assert later.finish_reason == "length" and worse.finish_reason == "error"
    assert st["prefill_calls_failed"] == 2


def test_a_call_that_ran_is_not_taken_for_one_that_was_refused(
        tiny_params, monkeypatch):
    """The executor asserts what the retry rests on: the donated cache is
    whole after the call raised. With the cache gone the program did run,
    and the error is passed on as it is (``abort_all``'s case)."""
    eng = _engine(tiny_params)
    _refuse(eng, monkeypatch, lambda rows, bucket: True, spoil=True)
    eng.submit(PROMPTS[0], SamplingParams(max_tokens=4))
    with pytest.raises(jax.errors.JaxRuntimeError) as err:
        eng.step()
    assert not isinstance(err.value, PrefillCallRefused)
    assert eng.stats["prefill_calls_failed"] == 0
    assert not eng.executor.refused_prefill_shapes
