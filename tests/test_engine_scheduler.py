"""The scheduler half of the serving engine, driven through its seam.

``InferenceEngine`` plans every round in host (numpy) arrays and hands it to
the executor by name; nothing else in it may touch a device array. So a
scripted stand-in for ``EngineExecutor`` — no model, no program, no compile —
is enough to pin what the scheduler alone decides: admission order and slot
reuse, block growth and who is preempted when the pool runs out, when a
request retires and why, and how the counters PERF.md section 3 lists are
booked.

The stand-in's "model" is one rule, stateless so that any batching,
chunking or recompute gives the same stream: the token after ``t`` is
``t + 1``.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from dlti_tpu.serving import engine as engine_module
from dlti_tpu.serving.executor import RIDES, PrefillCallRefused

CFG = MODEL_PRESETS["llama_tiny"]
EOS = 90


class ScriptedExecutor:
    """What ``InferenceEngine`` uses of ``EngineExecutor``, in numpy. Every
    call is logged with the host arrays it was given."""

    counter_names = ()
    prefill_call_tokens = 0
    prefill_group_tokens = 0
    prefill_whole_tables = False
    prefill_kernel_counts = None
    adapter_pool = None
    pool_bytes = 0
    kv_bytes_per_context_token = 0
    recurrent_state_pool_bytes = 0
    decode_tile_tokens = 4  # keys a grid step of "the kernel" covers

    def __init__(self, model_cfg, params, engine_cfg, lora_cfg=None,
                 mesh=None, donate_params=False, stats=None):
        assert params is None, "the scheduler hands its params straight on"
        self.cfg = engine_cfg
        self.calls = []
        self.unfetched = []  # token arrays of decode rounds not fetched yet
        # (rows, bucket, table width) -> bool: a call the device refuses
        self.refuses = lambda shape: False
        self.refused_prefill_shapes = set()

    # -- what the scheduler asks besides program calls --------------------
    def register_memory_owners(self, ledger):
        pass

    def slot_key(self, seed):
        return np.full((2,), 0 if seed is None else seed, np.uint32)

    def warmup_decode_ladder(self):
        self.calls.append(("warmup",))

    # -- program calls ----------------------------------------------------
    def _counter_rows(self):
        """One row a counter of a program call or decode step: counter i
        reads i + 1 every time."""
        return np.arange(1, len(self.counter_names) + 1, dtype=np.int32)

    def prefill(self, bucket, *, input_ids, positions, block_tables,
                last_idx, adapter_ids, state_slots, sample=None):
        _all_numpy(input_ids, positions, block_tables, last_idx,
                   adapter_ids, state_slots, *(sample or {}).values())
        assert input_ids.shape == positions.shape == (len(last_idx), bucket)
        shape = (*input_ids.shape, block_tables.shape[1])
        if self.refuses(shape):   # raised by the call: nothing ran
            self.calls.append(("refused", shape))
            self.refused_prefill_shapes.add(shape)
            raise PrefillCallRefused(shape, "RESOURCE_EXHAUSTED: Used 18.28G "
                                     "of 15.75G hbm")
        self.calls.append(("prefill", {
            "bucket": bucket, "input_ids": input_ids.copy(),
            "positions": positions.copy(),
            "block_tables": block_tables.copy(),
            "state_slots": state_slots.copy(), "sampled": sample is not None}))
        if sample is None:
            return None
        tokens = input_ids[np.arange(len(last_idx)), last_idx] + 1
        if self.counter_names:
            tokens = np.concatenate([tokens, self._counter_rows()])
        return tokens.astype(np.int32), np.zeros(len(last_idx), np.float32)

    def stage_decode(self, input_ids, positions, mirrors, masked_rows):
        _all_numpy(input_ids, positions, *mirrors.values())
        self.calls.append(("decode", {
            "mirrors": sorted(mirrors),
            "input_ids": input_ids[:, 0].copy(),
            "positions": positions[:, 0].copy(),
            "block_tables": mirrors["block_tables"].copy(),
            "gen_counts": mirrors["gen_counts"].copy(),
            "state_slots": mirrors["state_slots"].copy(),
            "top_k": mirrors["top_k"].copy(),
            "masked_rows": list(masked_rows)}))
        return input_ids[:, 0].copy()

    def launch_decode(self, staged, prev=None):
        call = self.calls[-1][1]
        rides = staged == RIDES
        call["launched_ahead"] = prev is not None
        if rides.any():
            # what the program does on the device: the round before's tokens
            assert prev is not None
            staged = np.where(rides, prev[0][:len(staged)], staged)
        call["inputs"] = staged.copy()
        tokens = staged + 1  # (no step axis: a round is one step)
        if self.counter_names:
            tokens = np.concatenate([tokens, self._counter_rows()])
        logprobs = np.zeros((len(staged),), np.float32)
        tokens = tokens.astype(np.int32)
        self.unfetched.append(tokens)
        return tokens, logprobs

    def fetch(self, arrays):
        _all_numpy(*arrays)
        decode = any(t is arrays[0] for t in self.unfetched)
        self.unfetched = [t for t in self.unfetched if t is not arrays[0]]
        self.calls.append(("fetch", {"decode": decode}))
        return list(arrays)

    def of(self, kind):
        return [c[1] for c in self.calls if c[0] == kind]


def _all_numpy(*arrays):
    for a in arrays:
        assert type(a) is np.ndarray, type(a)


@pytest.fixture(autouse=True)
def no_device(monkeypatch):
    """The seam is the test's subject: any reach past it for a device array
    or a compiled program fails the test that made it."""
    import jax
    import jax.numpy as jnp

    def refuse(name):
        def f(*a, **k):
            raise AssertionError(f"the scheduler called {name}")
        return f

    for mod, name in ((jax, "jit"), (jax, "device_put"), (jax, "device_get"),
                      (jnp, "asarray"), (jnp, "array"), (jnp, "zeros"),
                      (jax.random, "PRNGKey"), (jax.random, "split")):
        monkeypatch.setattr(mod, name, refuse(f"{mod.__name__}.{name}"))
    monkeypatch.setattr(engine_module, "EngineExecutor", ScriptedExecutor)


def _engine(**over):
    kw = dict(max_seqs=2, block_size=4, num_blocks=32, max_model_len=32,
              eos_token_id=EOS, memory_ledger=False)
    kw.update(over)
    return InferenceEngine(CFG, None, EngineConfig(**kw))


def _drain(eng, limit=400):
    finished = []
    for _ in range(limit):
        if not eng.has_work:
            return finished
        finished += eng.step()
    raise AssertionError("the engine did not drain")


def _stream(prompt, n):
    return [prompt[-1] + 1 + i for i in range(n)]


def test_the_scheduler_module_knows_no_device():
    """No jax in the module at all: what the scheduler cannot name it
    cannot touch. (The autouse fixture guards the calls of the rest.)"""
    names = vars(engine_module)
    assert "jax" not in names and "jnp" not in names
    for name in ("params", "cache", "_prefill_fns", "_decode_fn",
                 "_state_cache"):
        assert not hasattr(InferenceEngine, name), name


def test_the_engine_has_no_field_for_a_round_of_several_steps():
    """A plain round is one step, always: the field that asked for a window
    of several is gone, and code that still passes it is told so."""
    assert "steps_per_sync" not in {
        f.name for f in dataclasses.fields(EngineConfig)}
    with pytest.raises(TypeError, match="steps_per_sync"):
        EngineConfig(steps_per_sync=1)


def test_the_server_refuses_the_flag_for_a_round_of_several_steps():
    """An operator who still passes ``--steps-per-sync`` is told so at start
    (argparse's own error), not served something else."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "scripts/serve.py", "--random-init", "llama_tiny",
         "--tokenizer", "byte", "--steps-per-sync", "4"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "unrecognized arguments: --steps-per-sync 4" in proc.stderr


# -- admission order and slot reuse ------------------------------------------

@pytest.mark.parametrize("over", [
    {}, dict(max_prefill_tokens_per_step=3)], ids=["throughput", "chunked"])
def test_admission_is_first_come_first_served_and_slots_are_reused(over):
    eng = _engine(**over)
    prompts = [[10, 11, 12], [20, 21], [30, 31, 32, 33, 34], [40], [50, 51]]
    lengths = [5, 2, 3, 4, 2]
    reqs = [eng.submit(p, SamplingParams(max_tokens=n))
            for p, n in zip(prompts, lengths)]
    order, slot_of = [], {}
    while eng.has_work:
        eng.step()
        for s in eng.slots:
            if s.request is not None and s.request.request_id not in slot_of:
                slot_of[s.request.request_id] = s.slot_id
                order.append(s.request.request_id)
        assert eng.num_active <= 2
    # a request sampled its whole answer inside one step: seen only finished
    order += [r.request_id for r in reqs if r.request_id not in slot_of]
    assert sorted(order) == sorted(r.request_id for r in reqs)
    admitted = [r.admitted_time for r in reqs]
    assert admitted == sorted(admitted)  # arrival order, no overtaking
    assert len(set(slot_of.values())) <= 2 < len(reqs)  # slots came round
    for r, p, n in zip(reqs, prompts, lengths):
        assert r.output_token_ids == _stream(p, n)
        assert r.finish_reason == "length"
    # everything handed back: blocks, slots, mirrors
    assert eng.block_manager.num_free == eng.cfg.num_blocks - 1
    assert all(s.free for s in eng.slots)
    assert not eng._block_tables.any() and not eng._gen_counts.any()
    assert (eng._state_slots == eng.cfg.max_seqs).all()
    assert eng.stats["requests"] == 5
    assert eng.stats["prefill_tokens"] == sum(map(len, prompts))
    assert eng.stats["generated_tokens"] == sum(lengths)
    ex = eng.executor
    if "max_prefill_tokens_per_step" in over:
        # no prefill call carries more than the step's budget, and a slot
        # still prefilling is named to the decode call as a masked row
        for call in ex.of("prefill"):
            assert (call["positions"] >= 0).sum() <= 3
        assert any(call["masked_rows"] for call in ex.of("decode"))


# -- block growth, and preemption of the youngest ----------------------------

def test_tables_grow_and_the_youngest_is_preempted_when_the_pool_runs_out():
    # 5 allocatable blocks of 4 tokens for three sequences that, together,
    # want 9: the pool runs dry while the middle one grows.
    eng = _engine(max_seqs=3, num_blocks=6, max_model_len=16)
    prompts = [[10, 11, 12, 13, 14, 15, 16], [30, 31], [50]]
    lengths = [9, 8, 8]
    old, mid, young = (eng.submit(p, SamplingParams(max_tokens=n))
                       for p, n in zip(prompts, lengths))
    assert old.arrival_time <= mid.arrival_time <= young.arrival_time
    victims = []
    while eng.has_work:
        before = [r.num_preemptions for r in (old, mid, young)]
        eng.step()
        victims += [r for r, n in zip((old, mid, young), before)
                    if r.num_preemptions > n]
    ex = eng.executor
    # growth: every decode call's table covers the positions it writes
    for call in ex.of("decode"):
        for sid in np.nonzero(call["positions"])[0]:
            last = call["positions"][sid]
            row = call["block_tables"][sid]
            assert (row[:last // 4 + 1] > 0).all(), (sid, last, row)
    # the pool ran out: the youngest paid first, the oldest never
    assert victims and victims[0] is young
    assert old.num_preemptions == 0
    assert eng.stats["preemptions"] == len(victims)
    # recompute on re-admission: prompt + answer so far is prefilled again
    again = [c for c in ex.of("prefill") if c["input_ids"][0, 0] == 50
             and (c["positions"][0] >= 0).sum() > 1]
    assert again, "the preempted request was never prefilled again"
    n = int((again[0]["positions"][0] >= 0).sum())
    assert list(again[0]["input_ids"][0, :n]) == [50] + _stream([50], n - 1)
    # and the streams are what they would have been
    for r, p, n in zip((old, mid, young), prompts, lengths):
        assert r.output_token_ids == _stream(p, n)
    assert eng.block_manager.num_free == 5
    # no event is named to the executor: every round is handed every
    # slot's row as the scheduler holds it at the launch
    assert all(c["block_tables"].shape == (3, 4) and len(c["gen_counts"]) == 3
               for c in ex.of("decode"))


def test_a_pool_with_nothing_left_to_preempt_is_an_error_not_a_hang():
    eng = _engine(max_seqs=1, num_blocks=4, max_model_len=12)
    eng.block_manager.allocate(2)  # a co-tenant: 1 block left
    eng.submit([10, 11, 12], SamplingParams(max_tokens=8))
    with pytest.raises(RuntimeError, match="KV pool exhausted"):
        _drain(eng)


# -- retirement ---------------------------------------------------------------

RETIRES = {
    # the stream reaches EOS at its third token, with its row of the round
    # behind in flight: that row is thrown away (``discarded``)
    "eos": dict(prompt=[EOS - 3], sp=dict(max_tokens=20),
                want=(3, "stop"), discarded=1),
    "eos_from_prefill": dict(prompt=[5, EOS - 1], sp=dict(max_tokens=20),
                             want=(1, "stop"), discarded=0),
    "stop_token": dict(prompt=[10], sp=dict(max_tokens=20,
                                            stop_token_ids=[14]),
                       want=(4, "stop"), discarded=1),
    # an end by length is foreseen: no row goes out behind the last one
    "max_tokens": dict(prompt=[10, 11], sp=dict(max_tokens=7),
                       want=(7, "length"), discarded=0),
    # prompt 5 + answer 7 = max_model_len 12
    "max_model_len": dict(prompt=[10, 11, 12, 13, 14],
                          sp=dict(max_tokens=100), want=(7, "length"),
                          engine=dict(max_model_len=12), discarded=0),
    # the same two ends seven tokens in, the loop long a round ahead, with
    # a request waiting: it takes the slot in the step that fetched the
    # end, and its first round goes out behind the thrown-away row
    "eos_row_in_flight": dict(prompt=[EOS - 7], sp=dict(max_tokens=20),
                              want=(7, "stop"), discarded=1,
                              waiting=[70, 71]),
    "stop_token_row_in_flight": dict(
        prompt=[10], sp=dict(max_tokens=20, stop_token_ids=[17]),
        want=(7, "stop"), discarded=1, waiting=[70, 71]),
}


@pytest.mark.parametrize("name", sorted(RETIRES))
def test_a_request_retires_when_and_why_it_should(name):
    case = RETIRES[name]
    eng = _engine(**case.get("engine", {}))
    req = eng.submit(case["prompt"], SamplingParams(**case["sp"]))
    bystander = eng.submit([60], SamplingParams(max_tokens=8))
    everyone = [req, bystander]
    waiting = case.get("waiting")
    if waiting:
        everyone.append(eng.submit(waiting, SamplingParams(max_tokens=5)))
    returned = _drain(eng)
    n, reason = case["want"]
    assert req.output_token_ids == _stream(case["prompt"], n)
    assert req.finish_reason == reason
    assert bystander.output_token_ids == _stream([60], 8)
    if waiting:
        assert everyone[2].output_token_ids == _stream(waiting, 5)
        rounds = eng.executor.of("decode")
        at = [c["input_ids"][0] for c in rounds].index(waiting[-1] + 1)
        # its first token is a host id; the round before it carried the
        # ended request's row, read on the device, given to nobody
        assert rounds[at]["launched_ahead"] and rounds[at]["positions"][0] == 2
        assert rounds[at - 1]["input_ids"][0] == RIDES
    assert list(eng.finished) == everyone
    # step() hands back what a decode round retired (a request whose first
    # token ended it never decoded)
    assert returned == [r for r in everyone if len(r.output_token_ids) > 1]
    st = eng.stats
    assert st["decode_rows_discarded"] == case["discarded"]
    # a slot counts a step for every token it kept after its prefill's, a
    # round for every launch: the thrown-away row is in the second alone
    kept = sum(len(r.output_token_ids) - 1 for r in everyone)
    assert st["decode_slot_steps"] == kept
    assert st["decode_steps"] == len(eng.executor.of("decode"))
    assert not eng.executor.unfetched
    assert eng.block_manager.num_free == eng.cfg.num_blocks - 1


def test_a_cancelled_request_leaves_at_its_next_token_or_before_admission():
    eng = _engine(max_seqs=1)
    running = eng.submit([10], SamplingParams(max_tokens=50))
    queued = eng.submit([20], SamplingParams(max_tokens=50))
    for _ in range(4):
        eng.step()
    running.cancel_requested = queued.cancel_requested = True
    _drain(eng)
    assert running.finish_reason == queued.finish_reason == "stop"
    assert 0 < len(running.output_token_ids) < 50
    assert queued.output_token_ids == [] and queued.admitted_time is None
    assert eng.block_manager.num_free == eng.cfg.num_blocks - 1


# -- the counters PERF.md section 3 reads --------------------------------------

# name: (the model counts, the first request ends on a stop token nobody
# foresees: its row of the round behind runs and is thrown away)
BOOKED = {
    "single_step": (False, False),
    "rows_discarded": (False, True),
    "model_counters": (True, False),
    "model_counters_rows_discarded": (True, True),
}


@pytest.mark.parametrize("name", sorted(BOOKED))
def test_decode_counters_are_booked_as_perf_md_says(name, monkeypatch):
    counts, stops = BOOKED[name]
    counted = ("first_counter", "second_counter") if counts else ()
    monkeypatch.setattr(ScriptedExecutor, "counter_names", counted)
    eng = _engine()
    for key in counted:
        assert eng.stats[key] == eng.stats[f"{key}_decode"] == 0
    prompts = [[10, 11, 12], [40, 41, 42, 43, 44]]
    lengths = [9, 5]
    for p, n in zip(prompts, lengths):
        eng.submit(p, SamplingParams(
            max_tokens=n, top_k=3 if p[0] == 40 else 0,
            stop_token_ids=[16] if stops and p[0] == 10 else []))
    if stops:
        lengths = [4, 5]  # 13, 14, 15, 16
    # the books, kept by hand from what the executor was asked to do
    steps = context = tile_keys = sorted_steps = 0
    seen = 0
    while eng.has_work:
        eng.step()
        calls = eng.executor.of("decode")[seen:]
        seen += len(calls)
        for call in calls:
            steps += 1
            # context: what each row of the round had cached when it began,
            # which is the position its new token is written at (a row that
            # rides in the round before stands one further than the host
            # has seen)
            cached = call["positions"][np.nonzero(call["positions"])[0]]
            context += int(cached.sum())
            # whole tiles of 4 keys over those tokens and the new one
            tile_keys += sum(4 * -(-(int(c) + 1) // 4) for c in cached)
            # the program's own predicate, over the rows it was given
            sorted_steps += bool((call["top_k"] > 0).any())
    # a slot counts a step for every token it kept: all but the prefill's
    # (a row thrown away ran, and is in the rounds' books alone)
    slot_steps = sum(lengths) - len(lengths)
    st = eng.stats
    assert st["decode_steps"] == steps > 0
    assert st["decode_slot_steps"] == slot_steps
    assert st["decode_rows_discarded"] == stops
    assert st["generated_tokens"] == sum(lengths)
    assert st["decode_context_tokens"] == context
    assert st["decode_kernel_tile_tokens"] == tile_keys > context
    assert st["decode_steps_sorted_sampling"] == sorted_steps > 0
    # the first token of each answer is the prefill's; the rest are decode's
    assert st["decode_slot_steps"] == sum(lengths) - len(lengths)
    assert st["prefill_batches"] == len(eng.executor.of("prefill"))
    for i, key in enumerate(counted):
        # counter i reads i + 1 on every prefill call and every decode step
        assert st[f"{key}_decode"] == (i + 1) * steps
        assert st[key] == (i + 1) * (steps + st["prefill_batches"])


@pytest.mark.parametrize("tile", [4, 16])
def test_live_share_of_the_kernels_tiles_on_a_hand_made_batch(tile,
                                                              monkeypatch):
    """Prompts of 3, 8 and 13 tokens, one decode step each at a time:
    ``decode_context_tokens / decode_kernel_tile_tokens`` is the share of
    the keys the kernel's live tiles hold that are live. First decode step,
    tiles of 4: contexts 3 + 8 + 13 = 24 against tiles 4 + 12 + 16 = 32 (the
    new token opens a tile for the 8 and fills one for the 3); tiles of 16:
    24 against 48."""
    monkeypatch.setattr(ScriptedExecutor, "decode_tile_tokens", tile)
    eng = _engine(max_seqs=3)
    for n in (3, 8, 13):
        eng.submit(list(range(10, 10 + n)), SamplingParams(max_tokens=3))
    while not eng.executor.of("decode"):
        eng.step()
    st = eng.stats
    # booked when a round is launched; it is fetched a step later
    assert st["decode_steps"] == 0 and len(eng.executor.of("decode")) == 1
    assert st["decode_context_tokens"] == 24
    assert st["decode_kernel_tile_tokens"] == {4: 32, 16: 48}[tile]
    _drain(eng)
    # second step: contexts 4, 9, 14 -> tiles of 4: 8 + 12 + 16; of 16: 16 x 3
    assert st["decode_context_tokens"] == 24 + 27
    assert st["decode_kernel_tile_tokens"] == {4: 32 + 36, 16: 48 + 48}[tile]
    share = st["decode_context_tokens"] / st["decode_kernel_tile_tokens"]
    assert share == {4: 51 / 68, 16: 51 / 96}[tile]


def test_warmup_needs_nothing_of_the_scheduler_and_a_round_all_mirrors():
    """No per-slot state is resident on the device, so the warm-up is the
    executor's alone; a round is handed every mirror by name, whole."""
    eng = _engine()
    eng.warmup_decode_ladder()
    assert eng.executor.calls == [("warmup",)]
    eng.submit([10, 11, 12], SamplingParams(max_tokens=3))
    while eng.has_work:
        eng.step()
    assert {tuple(c["mirrors"]) for c in eng.executor.of("decode")} == {
        tuple(sorted(["block_tables", "slot_keys", "gen_counts",
                      "temperature", "top_k", "top_p", "adapter_ids",
                      "state_slots"]))}


# -- a model that holds its prefill calls to a number of tokens --------------

@pytest.mark.parametrize("whole_tables", [False, True])
def test_a_prompt_past_the_models_call_limit_goes_as_several_calls(
        monkeypatch, whole_tables):
    """``prefill_call_tokens`` (a model's own limit): a longer prompt is cut
    into calls of the limit, each starting where the last one ended, none but
    the last sampling; ``prefill_whole_tables`` gives every call the whole
    width of the block table instead of the narrowest power of two."""
    monkeypatch.setattr(ScriptedExecutor, "prefill_call_tokens", 8)
    monkeypatch.setattr(ScriptedExecutor, "prefill_whole_tables",
                        whole_tables)
    eng = _engine(max_model_len=48, num_blocks=64)
    prompt = list(range(100, 121))                   # 21 = 8 + 8 + 5
    req = eng.submit(prompt, SamplingParams(max_tokens=3))
    short = eng.submit([7, 8, 9], SamplingParams(max_tokens=3))
    _drain(eng)
    calls = eng.executor.of("prefill")
    assert [c["bucket"] for c in calls] == [8, 8, 8, 4]
    assert [c["sampled"] for c in calls] == [False, False, True, True]
    starts = [int(c["positions"][0, 0]) for c in calls]
    assert starts == [0, 8, 16, 0]
    assert [c["input_ids"][0, :5].tolist() for c in calls[:3]] == [
        prompt[0:5], prompt[8:13], prompt[16:21]]
    widths = [c["block_tables"].shape[1] for c in calls]
    assert widths == ([12] * 4 if whole_tables else [2, 4, 8, 1])
    assert req.output_token_ids == _stream(prompt, 3)
    assert short.output_token_ids == _stream([7, 8, 9], 3)
    st = eng.stats
    assert (st["prefill_batches"], st["prefill_tokens"]) == (4, 24)
    assert st["prefill_context_tokens"] == 8 + 16


@pytest.mark.parametrize("limits", [
    {"prefill_call_tokens": 32}, {"prefill_group_tokens": 32},
    {"prefill_call_tokens": 32, "prefill_group_tokens": 64},
    {"prefill_call_tokens": 64, "prefill_group_tokens": 32}],
    ids=["model", "device", "model_under_device", "device_under_model"])
def test_an_admission_wave_past_the_call_limit_goes_as_calls_that_fit(
        limits, monkeypatch):
    """Rows of one prompt bucket waiting together go out as many rows a call
    as the model's limit (``prefill_call_tokens``) or the device's (what a
    call's logits may take of it: ``prefill_group_tokens``) holds of that
    bucket, the smaller of the two, never as one call over it: a call that
    cannot fit costs ten seconds of compiler before it is refused."""
    for name, n in limits.items():
        monkeypatch.setattr(ScriptedExecutor, name, n)
    eng = _engine(max_seqs=8, max_model_len=48, num_blocks=64)
    eng.executor.refuses = lambda shape: shape[0] * shape[1] > 32
    prompts = [list(range(100 + 20 * i, 109 + 20 * i)) for i in range(5)]
    reqs = [eng.submit(p, SamplingParams(max_tokens=2)) for p in prompts]
    _drain(eng)
    calls = eng.executor.of("prefill")
    assert [c["input_ids"].shape for c in calls] == [(2, 16), (2, 16), (1, 16)]
    assert not eng.executor.of("refused")
    assert eng.stats["prefill_calls_split"] == 0
    assert [r.output_token_ids for r in reqs] == [_stream(p, 2)
                                                  for p in prompts]
    # (a row alone always goes, whatever its bucket)
    assert [eng._prefill_rows(b) for b in (4, 8, 16, 32, 64)] == \
        [8, 4, 2, 1, 1]


@pytest.mark.parametrize("vocab,rows", [
    (152064, {512: 8, 1024: 4, 2048: 2, 4096: 1}),   # qwen2_7b
    (32000, {512: 8, 1024: 8, 2048: 8, 4096: 4})],   # mistral_7b
    ids=["qwen2_7b", "mistral_7b"])
def test_the_devices_limit_is_what_the_benchmarks_cells_warm(vocab, rows,
                                                             monkeypatch):
    """On a v5e (15.75 GiB) the logits' share holds a call of several rows
    of qwen2_7b to the shapes ``serve.qwen2_7b.batch`` warms (8 x 1,024 and
    4 x 2,048 are the ones the compiler refuses there) and leaves
    mistral_7b's 8 x 2,048 alone; an unknown device (the CPU) sets none."""
    from dlti_tpu.serving.executor import prefill_group_tokens
    assert prefill_group_tokens(vocab, 0) == 0
    monkeypatch.setattr(ScriptedExecutor, "prefill_group_tokens",
                        prefill_group_tokens(vocab, 15.75 * 2**30))
    eng = _engine()
    assert {b: eng._prefill_rows(b) for b in rows} == rows


# -- prefix caching: a prompt's blocks are matchable once prefilled ----------

def test_a_prompt_asked_again_while_it_decodes_hits_the_running_sequence():
    """The whole blocks of a prefilled prompt are published at once: a second
    ask while the first still decodes shares its blocks (and its table row
    names them), and both retire without a block lost or freed twice."""
    eng = _engine(enable_prefix_caching=True)
    prompt = list(range(10, 21))                     # 11 tokens: 2 whole blocks
    first = eng.submit(prompt, SamplingParams(max_tokens=6))
    eng.step()                                       # admitted and prefilled
    assert eng.prefix_cache.num_cached_blocks == 2
    second = eng.submit(prompt, SamplingParams(max_tokens=6))
    eng.step()
    assert eng.stats["prefix_cached_tokens"] == 8
    rows = [s for s in eng.slots if not s.free]
    assert len(rows) == 2 and rows[0].blocks[:2] == rows[1].blocks[:2]
    assert rows[0].blocks[2:] != rows[1].blocks[2:]
    calls = eng.executor.of("prefill")
    assert [int(c["positions"][0, 0]) for c in calls] == [0, 8]
    assert eng._block_tables[rows[1].slot_id, :2].tolist() == \
        rows[0].blocks[:2]
    _drain(eng)
    assert first.output_token_ids == second.output_token_ids == \
        _stream(prompt, 6)
    pc = eng.prefix_cache
    assert pc.num_free + pc.num_reclaimable == 31   # every block accounted
    assert all(e.refcount == 0 for e in pc._by_block.values())


# -- one round ahead of the host ----------------------------------------------

def _kinds(ex, start=0):
    """The call log as words: a decode round by how it was launched, a fetch
    by what it fetched."""
    out = []
    for kind, call in ex.calls[start:]:
        if kind == "decode":
            out.append("ahead" if call["launched_ahead"] else "launch")
        elif kind == "fetch":
            out.append("fetch" if call["decode"] else "fetch_prefill")
        else:
            out.append(kind)
    return out


def test_the_next_round_is_launched_before_the_round_in_flight_is_fetched():
    eng = _engine()
    req = eng.submit([10, 11, 12], SamplingParams(max_tokens=6))
    eng.step()
    assert _kinds(eng.executor) == ["prefill", "fetch_prefill"]
    eng.step()                      # nothing in flight: from the host's token
    assert _kinds(eng.executor, 2) == ["launch"]
    assert eng.executor.of("decode")[0]["input_ids"][0] == 13
    assert len(req.output_token_ids) == 1 and eng.stats["decode_steps"] == 0
    for n in range(3):              # then: the next round first, then the fetch
        eng.step()
        assert _kinds(eng.executor, 3 + 2 * n) == ["ahead", "fetch"]
        assert len(req.output_token_ids) == 2 + n
    call = eng.executor.of("decode")[-1]
    # the riding row's token is read on the device, its position is the
    # host's plus the round in flight, and so is its count
    assert call["input_ids"][0] == RIDES and call["inputs"][0] == 16
    assert call["positions"][0] == 3 + 3 and call["gen_counts"][0] == 4
    _drain(eng)
    assert req.output_token_ids == _stream([10, 11, 12], 6)
    assert not eng.executor.unfetched
    st = eng.stats
    assert (st["decode_steps"], st["decode_rounds_launched_ahead"]) == (5, 4)
    assert st["decode_rows_discarded"] == 0  # its end was known beforehand


def test_a_request_that_ends_unforeseen_has_its_next_row_thrown_away():
    """EOS in round n: the request retires at n's emission; its row in round
    n+1, launched before, is thrown away and counted; whoever takes its slot
    and blocks is dispatched after n+1 and gets none of n+1's tokens."""
    eng = _engine(max_seqs=1)
    first = eng.submit([EOS - 3], SamplingParams(max_tokens=20))
    second = eng.submit([20, 21], SamplingParams(max_tokens=4))
    _drain(eng)
    assert first.output_token_ids == [EOS - 2, EOS - 1, EOS]
    assert first.finish_reason == "stop"
    assert second.output_token_ids == _stream([20, 21], 4)
    ex = eng.executor
    log = _kinds(ex)
    # first: prefill, round 1 (EOS - 1), round 2 (EOS) with round 3 behind it
    assert log[:7] == ["prefill", "fetch_prefill", "launch", "ahead", "fetch",
                       "ahead", "fetch"]
    # ... and the step that fetched round 2 put the second request in the
    # slot, after round 3 in the log; round 3's fetch gave it nothing
    assert log[7:11] == ["prefill", "fetch_prefill", "ahead", "fetch"]
    third = ex.of("decode")[2]
    assert third["inputs"][0] == EOS and third["positions"][0] == 3
    # the second request's first round goes out behind round 3 too, with its
    # first token as a host id: nothing of it rides
    fourth = ex.of("decode")[3]
    assert fourth["input_ids"][0] == 22 and fourth["positions"][0] == 2
    assert fourth["gen_counts"][0] == 1
    st = eng.stats
    assert st["decode_rows_discarded"] == 1
    assert st["decode_slot_steps"] == 2 + 3   # kept tokens only
    assert st["decode_steps"] == 3 + 3        # the thrown-away round ran
    assert not ex.unfetched
    assert eng.block_manager.num_free == eng.cfg.num_blocks - 1


@pytest.mark.parametrize("limit", ["max_tokens", "max_model_len", "cancel"])
def test_a_request_whose_end_is_foreseen_is_left_out_and_masked(limit):
    """Ending by length (or cancelled): not a row of the round launched
    behind its last one; while it is unretired its row reads as a free
    slot's there (the trash block, default sampling, no recurrent state to
    write), never position 0 of its own table."""
    over = dict(max_model_len=8) if limit == "max_model_len" else {}
    eng = _engine(**over)
    # prompt 3 + 5 = 8 = max_model_len; or 5 = max_tokens
    short = eng.submit([10, 11, 12], SamplingParams(
        max_tokens=5 if limit == "max_tokens" else 50))
    long = eng.submit([40], SamplingParams(max_tokens=7))
    if limit == "cancel":
        while len(short.output_token_ids) < 4:
            eng.step()
        short.cancel_requested = True
    _drain(eng)
    assert short.output_token_ids == _stream([10, 11, 12], 5)
    assert long.output_token_ids == _stream([40], 7)
    sid = 0
    last = [c for c in eng.executor.of("decode") if c["positions"][sid]][-1]
    rounds = eng.executor.of("decode")
    behind = rounds[[c is last for c in rounds].index(True) + 1]
    assert last["positions"][sid] == 3 + 3       # its fifth token's round
    assert behind["launched_ahead"]
    assert behind["positions"][sid] == 0
    assert not behind["block_tables"][sid].any()
    assert behind["state_slots"][sid] == eng.cfg.max_seqs
    assert behind["block_tables"][1].any()       # (the other row decodes on)
    assert last["block_tables"][sid].any()
    assert eng.stats["decode_rows_discarded"] == 0
    assert not eng.executor.unfetched


PLANS_GIVEN_UP = {
    # three sequences that want 9 blocks of 5: the plan behind the round in
    # flight would have to preempt, which needs every token on the host
    "must_preempt": dict(
        engine=dict(max_seqs=3, num_blocks=6, max_model_len=16),
        prompts=[[10, 11, 12, 13, 14, 15, 16], [30, 31], [50]],
        want=[_stream([10, 11, 12, 13, 14, 15, 16], 9), _stream([30, 31], 8),
              _stream([50], 8)], max_tokens=[9, 8, 8], preempts=True),
    # two sequences over 3 blocks, each wanting its second behind the very
    # round in which the first samples its EOS. The plan is given up, the
    # fetch retires the first and frees its block, and the plan made from
    # the host's tokens has what it needs: nobody is preempted
    "blocks_come_with_the_fetch": dict(
        engine=dict(num_blocks=4, max_model_len=12),
        prompts=[[EOS - 4], [10]],
        want=[[EOS - 3, EOS - 2, EOS - 1, EOS], _stream([10], 8)],
        max_tokens=[20, 8], preempts=False),
}


@pytest.mark.parametrize("name", sorted(PLANS_GIVEN_UP))
def test_a_plan_that_cannot_have_its_blocks_fetches_first(name):
    case = PLANS_GIVEN_UP[name]
    eng = _engine(**case["engine"])
    reqs = [eng.submit(p, SamplingParams(max_tokens=n))
            for p, n in zip(case["prompts"], case["max_tokens"])]
    seen = 0
    given_up = []
    while eng.has_work:
        preempted = eng.stats["preemptions"]
        had_inflight = eng._inflight is not None
        eng.step()
        log = _kinds(eng.executor, seen)
        seen = len(eng.executor.calls)
        if eng.stats["preemptions"] > preempted or (
                had_inflight and "launch" in log):
            given_up.append(log)
    assert given_up
    for log in given_up:
        # no round went out behind the one in flight: it was fetched, then
        # the plan (with its preemption, where it needs one) was made from
        # the host's tokens
        assert log[:2] == ["fetch", "launch"], log
    assert bool(eng.stats["preemptions"]) == case["preempts"]
    assert [r.output_token_ids for r in reqs] == case["want"]
    assert not eng.executor.unfetched
    assert eng.stats["decode_rows_discarded"] == 0
    assert eng.block_manager.num_free == eng.cfg.num_blocks - 1


def test_a_plan_given_up_for_want_of_blocks_ticks_no_cooldown():
    """Under speculation with every greedy slot paused the rounds are plain
    and run ahead, each ticking the paused slots' cooldowns once. A plan
    behind a round in flight that the pool has no block for is given up
    before it has ticked anything: the round planned after the fetch is the
    one that ticks, so a cooldown lasts as many rounds as it says."""
    eng = _engine(max_seqs=3, num_blocks=6, max_model_len=16,
                  speculative="ngram", num_draft_tokens=2,
                  spec_min_acceptance=0.5, spec_cooldown=100)
    prompts = [[10, 11, 12, 13, 14, 15, 16], [30, 31], [50]]
    lengths = [9, 8, 8]
    reqs = [eng.submit(p, SamplingParams(max_tokens=n, temperature=0.0))
            for p, n in zip(prompts, lengths)]
    gave_up = 0
    while eng.has_work:
        for s in eng.slots:  # a zero-hit probe window closed on every slot
            eng._spec_slot_pause[s.slot_id] = 100
        rounds = len(eng.executor.of("decode"))
        ahead = eng.stats["decode_rounds_launched_ahead"]
        paused = eng.stats["spec_paused_rounds"]
        live = sum(not s.free and not s.prefilling for s in eng.slots)
        had_inflight = eng._inflight is not None
        eng.step()
        launched = len(eng.executor.of("decode")) - rounds
        if had_inflight and launched and \
                eng.stats["decode_rounds_launched_ahead"] == ahead:
            gave_up += 1
        # one tick a slot of every round that went out, and no other
        assert eng.stats["spec_paused_rounds"] - paused <= live * launched
    assert gave_up and eng.stats["preemptions"]
    assert eng.stats["spec_proposed"] == 0
    for r, p, n in zip(reqs, prompts, lengths):
        assert r.output_token_ids == _stream(p, n)
    assert not eng.executor.unfetched


@pytest.mark.parametrize("how", ["abort_all", "shutdown", "cancel",
                                 "empties", "fault"])
def test_a_round_in_flight_is_always_accounted_for(how, monkeypatch):
    """Whatever ends the engine's work with a round in flight: no request is
    left without a token that was emitted to it, none gets one that was
    not, and no round stays unfetched."""
    eng = _engine()
    a = eng.submit([10], SamplingParams(max_tokens=30))
    b = eng.submit([EOS - 4, EOS - 3] if how == "empties" else [40],
                   SamplingParams(max_tokens=30))
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None and len(eng.executor.unfetched) == 1
    kept = [list(r.output_token_ids) for r in (a, b)]
    if how == "abort_all":
        aborted = eng.abort_all(reason="error")
        assert aborted == [a, b] and a.finish_reason == "error"
        assert [r.output_token_ids for r in (a, b)] == kept
    elif how == "shutdown":
        # what the server's stop does, and then what a faulted step's
        # recovery would: the second finds nothing in flight, nobody to fail
        assert eng.abort_all(reason="shutdown") == [a, b]
        assert [r.output_token_ids for r in (a, b)] == kept
        assert not eng.executor.unfetched and eng._inflight is None
        assert eng.abort_all(reason="error") == []
        assert a.finish_reason == b.finish_reason == "shutdown"
    elif how == "cancel":
        a.cancel_requested = b.cancel_requested = True
        _drain(eng)
        # one more token each: that of the round that was in flight
        assert [len(r.output_token_ids) for r in (a, b)] == \
            [len(k) + 1 for k in kept]
        assert a.finish_reason == b.finish_reason == "stop"
    elif how == "empties":
        a.cancel_requested = True
        _drain(eng)                      # b samples EOS with a round behind it
        assert b.output_token_ids == [EOS - 2, EOS - 1, EOS]
        assert eng.stats["decode_rows_discarded"] == 1
    else:
        boom = RuntimeError("the device is gone")

        def fetch(arrays):
            eng.executor.unfetched.clear()
            raise boom
        monkeypatch.setattr(eng.executor, "fetch", fetch)
        with pytest.raises(RuntimeError, match="device is gone"):
            eng.step()
        # the step dropped the round in flight and the one behind it
        assert eng._inflight is None
        assert eng.abort_all(reason="error") == [a, b]
        assert [r.output_token_ids for r in (a, b)] == kept
    assert eng._inflight is None and not eng.executor.unfetched
    assert not eng.has_work
    assert eng.block_manager.num_free == eng.cfg.num_blocks - 1
    # and the engine serves on
    monkeypatch.undo()
    monkeypatch.setattr(engine_module, "EngineExecutor", ScriptedExecutor)
    again = eng.submit([60, 61], SamplingParams(max_tokens=5))
    _drain(eng)
    assert again.output_token_ids == _stream([60, 61], 5)
    assert not eng.executor.unfetched


def test_every_row_is_uploaded_as_of_the_round_being_launched():
    """Block growth dirties a riding row: what is uploaded for it is the
    count the device has after the round in flight (kept tokens + 1), as its
    position is. Held for every row of every round: position - prompt + 1."""
    eng = _engine(max_seqs=3, block_size=2, num_blocks=64)
    prompts = [[10, 11, 12], [40], [70, 71]]
    for p, n in zip(prompts, (9, 12, 7)):
        eng.submit(p, SamplingParams(max_tokens=n))
    eng.submit([90, 91, 92, 93], SamplingParams(max_tokens=6))  # waits
    by_first = {p[0]: len(p) for p in prompts + [[90, 91, 92, 93]]}
    prompt_len = {}
    while eng.has_work:
        eng.step()
        for s in eng.slots:
            if s.request is not None:
                prompt_len[(s.slot_id, len(eng.executor.of("decode")))] = \
                    by_first[s.request.prompt_token_ids[0]]
    ahead = grown = 0
    for i, call in enumerate(eng.executor.of("decode")):
        for sid in np.nonzero(call["positions"])[0]:
            n = prompt_len.get((sid, i)) or prompt_len[(sid, i + 1)]
            assert call["gen_counts"][sid] == call["positions"][sid] - n + 1
            rides = call["input_ids"][sid] == RIDES
            ahead += rides
            grown += rides and call["positions"][sid] % 2 == 0
    assert ahead > 20 and grown > 5   # riding rows, and some that grew a block


# -- a prefill call that cannot be built --------------------------------------

def _no_abort(eng, monkeypatch):
    def abort_all(reason="abort"):
        raise AssertionError("a refused prefill call is no fault of the "
                             "engine's: abort_all was called")
    monkeypatch.setattr(eng, "abort_all", abort_all)


def test_a_refused_call_of_several_rows_goes_again_as_one_row_calls(
        monkeypatch):
    """Four admissions of one bucket whose 4-row call the device refuses:
    four one-row calls in the same step, in order, with the tokens a
    narrower admission would have given; the books count calls that went
    out; and the scheduler does not form that shape again."""
    eng = _engine(max_seqs=4, num_blocks=64)
    _no_abort(eng, monkeypatch)
    ex = eng.executor
    ex.refuses = lambda shape: shape[0] == 4
    prompts = [[10 * i + j for j in range(5 + i % 2)] for i in range(1, 5)]
    reqs = [eng.submit(p, SamplingParams(max_tokens=3)) for p in prompts]
    eng.step()
    assert [k for k, _ in ex.calls] == ["refused"] + ["prefill",
                                                      "fetch"] * 4
    assert ex.calls[0][1] == (4, 8, 2)
    calls = ex.of("prefill")
    assert [c["input_ids"].shape for c in calls] == [(1, 8)] * 4
    assert [list(c["input_ids"][0, :len(p)]) for c, p in zip(calls, prompts)] \
        == prompts
    assert [len(r.output_token_ids) for r in reqs] == [1] * 4
    st = eng.stats
    assert (st["prefill_calls_split"], st["prefill_calls_failed"]) == (1, 0)
    assert st["prefill_batches"] == 4
    assert st["prefill_tokens"] == sum(map(len, prompts))
    assert st["prefill_widest_call_tokens"] == 8
    _drain(eng)
    # a second wave of four: four calls of one row, and nothing refused
    more = [eng.submit([p[0] + 100] + p[1:], SamplingParams(max_tokens=3))
            for p in prompts]
    _drain(eng)
    assert len(ex.of("refused")) == 1
    assert [c["input_ids"].shape for c in ex.of("prefill")[4:]] == \
        [(1, 8)] * 4
    assert st["prefill_calls_split"] == 1 and st["prefill_batches"] == 8
    assert st["prefill_widest_call_tokens"] == 8
    # (another bucket keeps its rows)
    assert eng._prefill_rows(8) == 1 and eng._prefill_rows(16) == 8
    for r in reqs + more:
        assert r.output_token_ids == _stream(r.prompt_token_ids, 3)
        assert r.finish_reason == "length"
    assert eng.block_manager.num_free == eng.cfg.num_blocks - 1


@pytest.mark.parametrize("mode", ["throughput", "chunked", "long_prompt",
                                  "prefix_cache"])
def test_a_refused_one_row_call_fails_its_own_request_alone(
        mode, monkeypatch, engine_log):
    """The running streams go on to their ends with the tokens they would
    have had; the one request ends as an error with its slot and blocks
    released; ``abort_all`` is not called; counters and one log line."""
    over = {"chunked": dict(max_prefill_tokens_per_step=8),
            "prefix_cache": dict(enable_prefix_caching=True)}.get(mode, {})
    if mode == "long_prompt":
        # a prompt that goes as calls of 8 tokens: its second call is refused
        monkeypatch.setattr(ScriptedExecutor, "prefill_call_tokens", 8)
    eng = _engine(max_seqs=4, num_blocks=64, **over)
    _no_abort(eng, monkeypatch)
    prompts = [[10, 11, 12], [30], [50, 51]]
    running = [eng.submit(p, SamplingParams(max_tokens=12)) for p in prompts]
    for _ in range(4):
        eng.step()
    assert all(1 < len(r.output_token_ids) < 12 for r in running)
    # the wide call: 20 tokens, bucket 32 (or 8, then 8, then 4 in calls of
    # 8 tokens, of which the one that starts at position 8 is refused)
    wide = list(range(60, 80))
    ex = eng.executor
    ex.refuses = (lambda shape: shape[1] == 32) if mode != "long_prompt" \
        else (lambda shape: shape[2] > 2)
    if mode == "chunked":
        ex.refuses = lambda shape: shape[2] > 2
    bad = eng.submit(wide, SamplingParams(max_tokens=5))
    fine = eng.submit([90, 91], SamplingParams(max_tokens=4))
    _drain(eng)
    assert bad.finish_reason == "error" and bad.output_token_ids == []
    assert bad in eng.finished
    for r, p in zip(running, prompts):
        assert r.output_token_ids == _stream(p, 12)
        assert r.finish_reason == "length"
    assert fine.output_token_ids == _stream([90, 91], 4)
    st = eng.stats
    assert (st["prefill_calls_split"], st["prefill_calls_failed"]) == (0, 1)
    assert len(ex.of("refused")) == 1
    lines = [r.getMessage() for r in engine_log.records
             if bad.request_id in r.getMessage()]
    assert len(lines) == 1 and "Used 18.28G of 15.75G" in lines[0]
    assert "x %d tokens" % ex.of("refused")[0][1] in lines[0]
    assert not ex.unfetched
    free = eng.block_manager.num_free
    if mode == "prefix_cache":
        free += eng.prefix_cache.num_reclaimable
        assert all(e.refcount == 0
                   for e in eng.prefix_cache._by_block.values())
    assert free == eng.cfg.num_blocks - 1
