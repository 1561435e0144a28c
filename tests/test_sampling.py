"""``sample_tokens``: one Gumbel-max draw in token space, the sort behind a cond.

* Both branches draw from ``softmax(logits / T)`` restricted as asked:
  frequencies over thousands of keys against the exact probabilities.
* Branch independence: an unrestricted row's token and log-prob are the same
  alone, among rows like it, and beside rows that set top-k / top-p, in
  ``sample_tokens`` and through the engine.
* Greedy rows equal ``argmax``; the log-prob is ``log_softmax(logits)[token]``.
* The traced function holds its sort inside one ``cond`` branch and nowhere
  else, and the engine counts the decode steps that take that branch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from dlti_tpu.serving.sampling import sample_tokens

V = 12
LOGITS = np.array([2.0, -1.0, 0.5, 1.5, -3.0, 0.0, 1.0, -0.5, 2.5, -2.0,
                   0.25, 0.75], np.float32)

# name -> (temperature, top_k, top_p)
MODES = {
    "unrestricted": (0.7, 0, 1.0),
    "top_k": (0.7, 4, 1.0),
    "top_p": (0.7, 0, 0.7),
    "both": (1.3, 5, 0.8),
}


def _exact_probs(logits, temperature, top_k, top_p):
    """The distribution asked for, in plain numpy: ranks and cumulative
    probabilities in descending order, the kept ones renormalised."""
    order = np.argsort(-logits, kind="stable")
    scaled = logits[order].astype(np.float64) / temperature
    probs = np.exp(scaled - scaled.max())
    probs /= probs.sum()
    keep = np.ones(len(logits), bool)
    if top_k > 0:
        keep &= np.arange(len(logits)) < top_k
    keep &= (np.cumsum(probs) - probs) < top_p
    out = np.zeros(len(logits))
    out[order] = np.where(keep, probs, 0.0) / probs[keep].sum()
    return out


def _keys(n, seed=0):
    return jax.vmap(jax.random.PRNGKey)(jnp.arange(seed, seed + n))


def _rows(n, temperature, top_k, top_p):
    return (jnp.full((n,), temperature, jnp.float32),
            jnp.full((n,), top_k, jnp.int32),
            jnp.full((n,), top_p, jnp.float32))


def _sample(logits, keys, temperature, top_k, top_p):
    return jax.jit(sample_tokens)(jnp.asarray(logits), keys,
                                  jnp.asarray(temperature, jnp.float32),
                                  jnp.asarray(top_k, jnp.int32),
                                  jnp.asarray(top_p, jnp.float32))


# -- (a) the distribution -----------------------------------------------------

@pytest.mark.parametrize("company", ["alike", "beside_restricted"])
@pytest.mark.parametrize("mode", list(MODES))
def test_frequencies_match_the_exact_probabilities(mode, company):
    """8,000 rows of the same logits, a key each. ``beside_restricted`` adds
    one top-p row, so that unrestricted rows go through the sorted branch."""
    n = 8000
    temperature, top_k, top_p = MODES[mode]
    t, k, p = _rows(n, temperature, top_k, top_p)
    logits = np.tile(LOGITS, (n, 1))
    if company == "beside_restricted":
        logits = np.concatenate([logits, LOGITS[None]])
        t, k, p = (jnp.append(t, 1.0), jnp.append(k, 0), jnp.append(p, 0.5))
    toks, _ = _sample(logits, _keys(len(logits)), t, k, p)
    freq = np.bincount(np.asarray(toks[:n]), minlength=V) / n
    exact = _exact_probs(LOGITS, temperature, top_k, top_p)
    assert set(np.flatnonzero(freq)) <= set(np.flatnonzero(exact))
    # sigma <= 0.0056 at n = 8,000: 0.02 is 3.5 sigma and more
    np.testing.assert_allclose(freq, exact, atol=0.02)


def test_tokens_tied_with_the_threshold_are_all_kept():
    """top_k = 1 over two equal heads keeps both (module docstring: ties)."""
    n = 200
    logits = np.tile(np.array([3.0, 3.0, 1.0, 0.0], np.float32), (n, 1))
    toks, _ = _sample(logits, _keys(n), *_rows(n, 1.0, 1, 1.0))
    assert set(np.asarray(toks).tolist()) == {0, 1}


# -- (b) branch independence --------------------------------------------------

COMPANY = {
    "alone": [],
    "among_unrestricted": [(1.0, 0, 1.0), (0.7, 0, 1.0), (0.0, 0, 1.0)],
    "beside_top_p_and_top_k": [(1.0, 0, 0.5), (1.0, 3, 1.0)],
}


@pytest.mark.parametrize("position", ["first", "last"])
@pytest.mark.parametrize("company", list(COMPANY))
def test_an_unrestricted_rows_draw_ignores_its_company(company, position):
    """The row's token is ``argmax(logits / T + gumbel(key))`` over token ids,
    and its log-prob the same, whatever shares the batch and whichever branch
    the batch took."""
    rs = np.random.RandomState(5)
    temperature = 0.8
    others = COMPANY[company]
    for trial in range(16):
        row = rs.randn(V).astype(np.float32) * 2
        key = jax.random.PRNGKey(1000 + trial)
        want = int(jnp.argmax(jnp.asarray(row) / temperature
                              + jax.random.gumbel(key, (V,), jnp.float32)))
        want_lp = float(jax.nn.log_softmax(jnp.asarray(row))[want])
        logits = [rs.randn(V).astype(np.float32) for _ in others]
        params = list(others)
        keys = [jax.random.PRNGKey(7 + i) for i in range(len(others))]
        at = 0 if position == "first" else len(others)
        logits.insert(at, row)
        params.insert(at, (temperature, 0, 1.0))
        keys.insert(at, key)
        t, k, p = zip(*params)
        toks, lps = _sample(np.stack(logits), jnp.stack(keys), t, k, p)
        assert int(toks[at]) == want
        np.testing.assert_allclose(float(lps[at]), want_lp, rtol=0, atol=1e-6)


# -- (c), (d) greedy rows and log-probs ---------------------------------------

@pytest.mark.parametrize("mode", list(MODES))
def test_greedy_rows_in_a_mixed_batch_equal_argmax(mode):
    rs = np.random.RandomState(11)
    logits = (rs.randn(6, 40) * 3).astype(np.float32)
    temperature, top_k, top_p = MODES[mode]
    t = [0.0, temperature, 0.0, temperature, temperature, 0.0]
    toks, lps = _sample(logits, _keys(6, seed=3), t, [top_k] * 6, [top_p] * 6)
    greedy = [0, 2, 5]
    np.testing.assert_array_equal(np.asarray(toks)[greedy],
                                  logits.argmax(-1)[greedy])
    expect = jax.nn.log_softmax(jnp.asarray(logits), -1)[jnp.arange(6), toks]
    np.testing.assert_allclose(np.asarray(lps), np.asarray(expect),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("mode", list(MODES))
def test_logprob_is_log_softmax_at_the_token(mode):
    """Under the unmasked, unscaled distribution, whatever the row asked."""
    rs = np.random.RandomState(13)
    logits = (rs.randn(64, 50) * 2).astype(np.float32)
    toks, lps = _sample(logits, _keys(64, seed=9), *_rows(64, *MODES[mode]))
    expect = jax.nn.log_softmax(jnp.asarray(logits), -1)[jnp.arange(64), toks]
    np.testing.assert_allclose(np.asarray(lps), np.asarray(expect),
                               rtol=0, atol=1e-5)


def test_one_key_is_split_per_row():
    """A single key still serves a batch: rows draw apart, calls agree."""
    logits = np.zeros((64, V), np.float32)
    args = (jax.random.PRNGKey(2), *_rows(64, 1.0, 0, 1.0))
    a, _ = _sample(logits, *args)
    b, _ = _sample(logits, *args)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(set(np.asarray(a).tolist())) > 1


# -- (e) where the sort lives -------------------------------------------------

SORTING = {"sort", "top_k", "approx_top_k"}


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _primitives(jaxpr, stop_at_cond):
    """Names of the primitives of ``jaxpr`` and of everything nested in it;
    with ``stop_at_cond`` a ``cond``'s branches are not entered."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if stop_at_cond and eqn.primitive.name == "cond":
            continue
        for inner in _sub_jaxprs(eqn):
            yield from _primitives(inner, stop_at_cond)


def _conds(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for inner in _sub_jaxprs(eqn):
            yield from _conds(inner)


def test_the_sort_lives_in_one_cond_branch_and_nowhere_else():
    jaxpr = jax.make_jaxpr(jax.jit(sample_tokens))(
        jnp.zeros((4, V)), _keys(4), *_rows(4, 1.0, 0, 1.0)).jaxpr
    assert not SORTING & set(_primitives(jaxpr, stop_at_cond=True))
    [cond] = list(_conds(jaxpr))
    unsorted, sorted_ = (set(_primitives(b.jaxpr, stop_at_cond=False))
                         for b in cond.params["branches"])
    assert not SORTING & unsorted  # index 0: the predicate is false
    assert "sort" in sorted_
    # the noise is drawn outside the cond: both branches feed the same draw
    assert not {"random_bits", "threefry2x32"} & (unsorted | sorted_)


# -- the engine: the counter, and (b) through the programs --------------------

CFG = MODEL_PRESETS["llama_tiny"]


@pytest.fixture(scope="module")
def tiny_params():
    return LlamaForCausalLM(CFG, None).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, **over):
    base = dict(max_seqs=4, block_size=8, num_blocks=64, max_model_len=128,
                cache_dtype="float32", eos_token_id=-1)
    base.update(over)
    return InferenceEngine(CFG, params, EngineConfig(**base))


def test_counter_stays_zero_under_unrestricted_requests(tiny_params):
    eng = _engine(tiny_params)
    eng.generate([[1, 2, 3], [4, 5], [6]],
                 SamplingParams(temperature=1.0, max_tokens=5, seed=1))
    eng.generate([[7, 8]], SamplingParams(temperature=0.0, max_tokens=3))
    assert eng.stats["decode_steps"] > 0
    assert eng.stats["decode_steps_sorted_sampling"] == 0


@pytest.mark.parametrize("speculative", ["none", "ngram"])
def test_counter_equals_the_steps_a_restricted_request_was_live(
        tiny_params, speculative):
    """A long unrestricted request and a short ``top_p < 1`` one: the steps
    counted are those dispatched while the short one held a slot, in plain
    and speculative rounds."""
    eng = _engine(tiny_params, speculative=speculative)
    long = eng.submit([1, 2, 3], SamplingParams(temperature=1.0, max_tokens=12,
                                                seed=4))
    short = eng.submit([4, 5], SamplingParams(temperature=1.0, top_p=0.9,
                                              max_tokens=5, seed=5))
    live_steps = 0
    while eng.has_work:
        before = eng.stats["decode_steps"]
        was_live = short.finish_reason is None
        eng.step()
        if was_live:
            live_steps += eng.stats["decode_steps"] - before
    assert len(long.output_token_ids) == 12
    assert 0 < live_steps < eng.stats["decode_steps"]
    assert eng.stats["decode_steps_sorted_sampling"] == live_steps


@pytest.mark.parametrize("neighbour", [
    SamplingParams(temperature=1.0, top_p=0.5, max_tokens=6, seed=8),
    SamplingParams(temperature=0.9, top_k=3, max_tokens=9, seed=8),
], ids=["beside_top_p", "beside_top_k"])
def test_a_seeded_requests_tokens_ignore_a_restricted_neighbour(
        tiny_params, neighbour):
    """Served alone (no step sorts) and beside a restricted request (its steps
    sort until the neighbour leaves): the same tokens and log-probs."""
    p = SamplingParams(temperature=1.0, max_tokens=10, seed=123)
    eng = _engine(tiny_params)
    [alone] = eng.generate([[1, 2, 3]], p)
    assert eng.stats["decode_steps_sorted_sampling"] == 0
    seeded = eng.submit([1, 2, 3], p)
    eng.submit([9, 8, 7], neighbour)
    while eng.has_work:
        eng.step()
    assert eng.stats["decode_steps_sorted_sampling"] > 0
    assert seeded.output_token_ids == alone.output_token_ids
    np.testing.assert_allclose(seeded.output_logprobs, alone.output_logprobs,
                               rtol=0, atol=1e-5)
