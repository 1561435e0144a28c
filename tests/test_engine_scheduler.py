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

import numpy as np
import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from dlti_tpu.serving import engine as engine_module

CFG = MODEL_PRESETS["llama_tiny"]
EOS = 90


class ScriptedExecutor:
    """What ``InferenceEngine`` uses of ``EngineExecutor``, in numpy. Every
    call is logged with the host arrays it was given."""

    counter_names = ()
    prefill_call_tokens = 0
    prefill_whole_tables = False
    adapter_pool = None
    pool_bytes = 0
    recurrent_state_pool_bytes = 0
    decode_tile_tokens = 4  # keys a grid step of "the kernel" covers

    def __init__(self, model_cfg, params, engine_cfg, lora_cfg=None,
                 mesh=None, donate_params=False, stats=None):
        assert params is None, "the scheduler hands its params straight on"
        self.cfg = engine_cfg
        self.calls = []
        self.dirty = []

    # -- what the scheduler asks besides program calls --------------------
    def register_memory_owners(self, ledger):
        pass

    def slot_key(self, seed):
        return np.full((2,), 0 if seed is None else seed, np.uint32)

    def mark_dirty(self, slot_id):
        self.dirty.append(slot_id)

    def warmup_decode_ladder(self, mirrors, masked_rows):
        self.calls.append(("warmup", sorted(mirrors), list(masked_rows)))

    # -- program calls ----------------------------------------------------
    def _counter_rows(self, calls):
        """One row a counter, one column a program call or decode step:
        counter i reads i + 1 every time."""
        n = len(self.counter_names)
        return np.tile(np.arange(1, n + 1, dtype=np.int32)[:, None],
                       (1, calls))

    def prefill(self, bucket, *, input_ids, positions, block_tables,
                last_idx, adapter_ids, state_slots, sample=None):
        _all_numpy(input_ids, positions, block_tables, last_idx,
                   adapter_ids, state_slots, *(sample or {}).values())
        assert input_ids.shape == positions.shape == (len(last_idx), bucket)
        self.calls.append(("prefill", {
            "bucket": bucket, "input_ids": input_ids.copy(),
            "positions": positions.copy(),
            "block_tables": block_tables.copy(),
            "state_slots": state_slots.copy(), "sampled": sample is not None}))
        if sample is None:
            return None
        tokens = input_ids[np.arange(len(last_idx)), last_idx] + 1
        if self.counter_names:
            tokens = np.concatenate([tokens, self._counter_rows(1)[:, 0]])
        return tokens.astype(np.int32), np.zeros(len(last_idx), np.float32)

    def stage_decode(self, input_ids, positions, mirrors, masked_rows):
        _all_numpy(input_ids, positions, *mirrors.values())
        self.calls.append(("decode", {
            "input_ids": input_ids[:, 0].copy(),
            "positions": positions[:, 0].copy(),
            "block_tables": mirrors["block_tables"].copy(),
            "masked_rows": list(masked_rows)}))
        return input_ids[:, 0].copy()

    def launch_decode(self, staged, k_steps):
        self.calls[-1][1]["k_steps"] = k_steps
        tokens = staged[:, None] + 1 + np.arange(k_steps)[None, :]
        if self.counter_names:
            tokens = np.concatenate([tokens, self._counter_rows(k_steps)])
        return (tokens.astype(np.int32),
                np.zeros((len(staged), k_steps), np.float32))

    @staticmethod
    def fetch(arrays):
        _all_numpy(*arrays)
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
    for name in ("params", "cache", "_prefill_fns", "_multi_decode_fns",
                 "_decode_fn", "_state_cache"):
        assert not hasattr(InferenceEngine, name), name


# -- admission order and slot reuse ------------------------------------------

@pytest.mark.parametrize("over", [
    {}, dict(max_prefill_tokens_per_step=3), dict(steps_per_sync=4)],
    ids=["throughput", "chunked", "multi_step"])
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
    if "steps_per_sync" in over:
        # windows come off the halving ladder, clamped to the budget
        assert {c["k_steps"] for c in ex.of("decode")} <= {1, 2, 4}
        assert 4 in {c["k_steps"] for c in ex.of("decode")}


# -- block growth, and preemption of the youngest ----------------------------

@pytest.mark.parametrize("steps_per_sync", [1, 2], ids=["single", "window"])
def test_tables_grow_and_the_youngest_is_preempted_when_the_pool_runs_out(
        steps_per_sync):
    # 5 allocatable blocks of 4 tokens for three sequences that, together,
    # want 9: the pool runs dry while the middle one grows.
    eng = _engine(max_seqs=3, num_blocks=6, max_model_len=16,
                  steps_per_sync=steps_per_sync)
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
            last = call["positions"][sid] + call["k_steps"] - 1
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
    # admissions, growths and releases were named to the executor
    assert {0, 1, 2} <= set(ex.dirty)


def test_a_pool_with_nothing_left_to_preempt_is_an_error_not_a_hang():
    eng = _engine(max_seqs=1, num_blocks=4, max_model_len=12)
    eng.block_manager.allocate(2)  # a co-tenant: 1 block left
    eng.submit([10, 11, 12], SamplingParams(max_tokens=8))
    with pytest.raises(RuntimeError, match="KV pool exhausted"):
        _drain(eng)


# -- retirement ---------------------------------------------------------------

RETIRES = {
    # the stream reaches EOS at its third token
    "eos": dict(prompt=[EOS - 3], sp=dict(max_tokens=20),
                want=(3, "stop")),
    "eos_from_prefill": dict(prompt=[5, EOS - 1], sp=dict(max_tokens=20),
                             want=(1, "stop")),
    "stop_token": dict(prompt=[10], sp=dict(max_tokens=20,
                                            stop_token_ids=[14]),
                       want=(4, "stop")),
    "max_tokens": dict(prompt=[10, 11], sp=dict(max_tokens=7),
                       want=(7, "length")),
    # prompt 5 + answer 7 = max_model_len 12
    "max_model_len": dict(prompt=[10, 11, 12, 13, 14],
                          sp=dict(max_tokens=100), want=(7, "length"),
                          engine=dict(max_model_len=12)),
    # the same two limits met inside a 4-step window: the tail is dropped
    "max_tokens_mid_window": dict(prompt=[10, 11], sp=dict(max_tokens=6),
                                  want=(6, "length"),
                                  engine=dict(steps_per_sync=4)),
    "eos_mid_window": dict(prompt=[EOS - 7], sp=dict(max_tokens=20),
                           want=(7, "stop"), engine=dict(steps_per_sync=4)),
}


@pytest.mark.parametrize("name", sorted(RETIRES))
def test_a_request_retires_when_and_why_it_should(name):
    case = RETIRES[name]
    eng = _engine(**case.get("engine", {}))
    req = eng.submit(case["prompt"], SamplingParams(**case["sp"]))
    bystander = eng.submit([60], SamplingParams(max_tokens=8))
    returned = _drain(eng)
    n, reason = case["want"]
    assert req.output_token_ids == _stream(case["prompt"], n)
    assert req.finish_reason == reason
    assert bystander.output_token_ids == _stream([60], 8)
    assert list(eng.finished) == [req, bystander]
    # step() hands back what a decode round retired (a request whose first
    # token ended it never decoded)
    assert returned == [r for r in (req, bystander)
                        if len(r.output_token_ids) > 1]
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

BOOKED = {
    "single_step": dict(steps_per_sync=1),
    "windows": dict(steps_per_sync=4),
    "model_counters": dict(steps_per_sync=2),
}


@pytest.mark.parametrize("name", sorted(BOOKED))
def test_decode_counters_are_booked_as_perf_md_says(name, monkeypatch):
    counted = ("first_counter", "second_counter") \
        if name == "model_counters" else ()
    monkeypatch.setattr(ScriptedExecutor, "counter_names", counted)
    eng = _engine(**BOOKED[name])
    for key in counted:
        assert eng.stats[key] == eng.stats[f"{key}_decode"] == 0
    prompts = [[10, 11, 12], [40, 41, 42, 43, 44]]
    lengths = [9, 5]
    for p, n in zip(prompts, lengths):
        eng.submit(p, SamplingParams(max_tokens=n, top_k=3 if p[0] == 40
                                     else 0))
    # the books, kept by hand from what the executor was asked to do
    steps = slot_steps = context = tile_keys = sorted_steps = 0
    seen = 0
    while eng.has_work:
        live = {s.slot_id: (s.seq_len, s.request.params.max_tokens
                            - len(s.request.output_token_ids))
                for s in eng.slots if not s.free and not s.prefilling}
        sorting = bool((eng._top_k > 0).any())
        eng.step()
        calls = eng.executor.of("decode")[seen:]
        seen += len(calls)
        for call in calls:
            k = call["k_steps"]
            steps += k
            # context: what each live slot had cached when the round began
            context += k * sum(seq for seq, _ in live.values())
            # whole tiles of 4 keys over those tokens and the new one
            tile_keys += k * sum(4 * -(-(seq + 1) // 4)
                                 for seq, _ in live.values())
            # a slot counts a step until its answer is complete
            slot_steps += sum(min(k, left) for _, left in live.values())
            sorted_steps += k * sorting
    st = eng.stats
    assert st["decode_steps"] == steps > 0
    assert st["decode_slot_steps"] == slot_steps
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
    eng = _engine(steps_per_sync=1, max_seqs=3)
    for n in (3, 8, 13):
        eng.submit(list(range(10, 10 + n)), SamplingParams(max_tokens=3))
    while not eng.stats["decode_steps"]:
        eng.step()
    st = eng.stats
    assert st["decode_steps"] == 1
    assert st["decode_context_tokens"] == 24
    assert st["decode_kernel_tile_tokens"] == {4: 32, 16: 48}[tile]
    _drain(eng)
    # second step: contexts 4, 9, 14 -> tiles of 4: 8 + 12 + 16; of 16: 16 x 3
    assert st["decode_context_tokens"] == 24 + 27
    assert st["decode_kernel_tile_tokens"] == {4: 32 + 36, 16: 48 + 48}[tile]
    share = st["decode_context_tokens"] / st["decode_kernel_tile_tokens"]
    assert share == {4: 51 / 68, 16: 51 / 96}[tile]


def test_warmup_hands_the_mirrors_to_the_executor_by_name():
    eng = _engine()
    eng.warmup_decode_ladder()
    [(_, names, masked)] = eng.executor.calls
    assert names == sorted(["block_tables", "slot_keys", "gen_counts",
                            "temperature", "top_k", "top_p", "adapter_ids",
                            "state_slots"])
    assert masked == []


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
