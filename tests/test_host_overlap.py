"""Host-latency-hiding layer: equivalence + zero-upload contracts.

Two hot paths, one invariant each:

* Training (``dlti_tpu.data.prefetch``): the background prefetcher must be
  *invisible* in the numbers — bit-identical loss trajectory vs. the
  synchronous path for every (preset, packing) combination — and safe to
  shut down mid-epoch (preemption).
* Serving (``dlti_tpu.serving.decode_state``): a plain decode round goes
  up as one packed array and is one program call (the acceptance
  criterion), the packing loses no bit, and the engine serves what
  references that do not share its path say (the uncached full forward;
  each seeded request alone — including across preemption and
  re-admission, block growth, rows masked while they prefill, and a
  speculative round followed by a plain one).
"""

import dataclasses
import json

import numpy as np
import pytest

from dlti_tpu.config import (
    CheckpointConfig, Config, DataConfig, LoRAConfig, MODEL_PRESETS,
    OptimizerConfig, ParallelConfig, TelemetryConfig, TrainConfig, ZeROStage,
)
from dlti_tpu.data import TokenBatchDataset
from dlti_tpu.data.prefetch import HostPrefetcher
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from test_layer_windows import TINY as WINDOW_GROUPS
from test_looped_layers import TINY as LOOPED

CFG = MODEL_PRESETS["llama_tiny"]


# ----------------------------------------------------------------------
# Prefetcher unit contracts
# ----------------------------------------------------------------------

def test_prefetcher_preserves_order_and_values():
    items = [{"x": np.full((2, 2), i)} for i in range(17)]
    got = [hb for hb, _ in HostPrefetcher(iter(items), depth=3)]
    assert len(got) == 17
    for want, have in zip(items, got):
        assert have is want  # the host batch object passes through untouched


def test_prefetcher_place_fn_pairs_host_and_placed():
    items = [{"x": np.arange(4) + i} for i in range(5)]
    pre = HostPrefetcher(iter(items), depth=2,
                         place_fn=lambda b: {k: v * 1 for k, v in b.items()})
    for hb, placed in pre:
        assert placed is not hb
        np.testing.assert_array_equal(placed["x"], hb["x"])
    assert pre.stats["fetches"] == 5


def test_prefetcher_close_unblocks_full_queue():
    """Preemption path: the worker is parked on a full queue; close() must
    join it promptly instead of leaking a daemon thread."""
    pre = HostPrefetcher(iter([{"x": np.zeros(1)}] * 100), depth=1)
    next(iter(pre))  # ensure the worker is up and the queue cycles
    pre.close()
    assert not pre._thread.is_alive()
    pre.close()  # idempotent


def test_prefetcher_propagates_source_exception():
    def bad():
        yield {"x": np.zeros(1)}
        raise RuntimeError("dataset exploded")

    it = iter(HostPrefetcher(bad(), depth=2))
    next(it)
    with pytest.raises(RuntimeError, match="dataset exploded"):
        next(it)


def test_prefetcher_telemetry_names_and_stall_histogram():
    from dlti_tpu.data.prefetch import PREFETCH_METRIC_NAMES

    pre = HostPrefetcher(iter([{"x": np.zeros(1)}] * 3), depth=2)
    list(pre)
    assert pre.queue_depth.name == PREFETCH_METRIC_NAMES[0]
    assert pre.stall_time.name == PREFETCH_METRIC_NAMES[1]
    _, _, n = pre.stall_time.snapshot()
    assert n == 3  # one stall sample per consumed batch


# ----------------------------------------------------------------------
# Training: loss-trajectory equivalence, prefetch on vs off
# ----------------------------------------------------------------------

def _make_dataset(pack: bool, micro_bs: int, accum: int, seq_len: int = 32):
    # Enough tokens that even PACKED rows (several docs per row) cover >= 4
    # steps at every shape used below.
    rng = np.random.default_rng(7)
    chunk = micro_bs * accum
    seqs = [list(map(int, rng.integers(1, 500, size=int(rng.integers(8, 16)))))
            for _ in range(12 * chunk)]
    return TokenBatchDataset(
        sequences=seqs, seq_len=seq_len, pad_id=0,
        micro_batch_size=micro_bs, grad_accum_steps=accum, pack=pack)


def _train_losses(tmp_path, tag, par, pack, micro_bs, accum, prefetch_depth):
    from dlti_tpu.training.trainer import Trainer

    steplog = tmp_path / f"{tag}.jsonl"
    cfg = Config(
        model=CFG,
        lora=LoRAConfig(r=2, alpha=4, dropout=0.0),
        optimizer=OptimizerConfig(warmup_steps=2),
        parallel=par,
        data=DataConfig(max_seq_len=32, prefetch_depth=prefetch_depth),
        train=TrainConfig(num_epochs=1, max_steps=3, micro_batch_size=micro_bs,
                          grad_accum_steps=accum, logging_steps=100,
                          metrics_csv=str(tmp_path / f"{tag}.csv")),
        checkpoint=CheckpointConfig(save_strategy="no"),
        telemetry=TelemetryConfig(step_log_path=str(steplog)),
    )
    trainer = Trainer(cfg)
    trainer.train(dataset=_make_dataset(pack, micro_bs, accum))
    rows = [json.loads(line) for line in open(steplog)]
    losses = [r["loss"] for r in rows if r.get("type") == "step"]
    assert len(losses) == 3
    return losses


@pytest.mark.parametrize("preset_kind,pack", [
    ("baseline", False),
    ("baseline", True),
    pytest.param("zero3", False, marks=pytest.mark.slow),
    pytest.param("zero3", True, marks=pytest.mark.slow),
])
def test_prefetch_loss_trajectory_bit_identical(tmp_path, preset_kind, pack):
    """Prefetch on (default depth 2) vs off: same batches in the same
    order through the same rng schedule — the per-step losses must be
    bit-identical floats, not merely close."""
    if preset_kind == "baseline":
        par, micro_bs, accum = ParallelConfig(), 2, 2
    else:
        par, micro_bs, accum = \
            ParallelConfig(zero_stage=ZeROStage.ZERO3, fsdp=8), 8, 1
    on = _train_losses(tmp_path, f"{preset_kind}_{pack}_on", par, pack,
                       micro_bs, accum, prefetch_depth=2)
    off = _train_losses(tmp_path, f"{preset_kind}_{pack}_off", par, pack,
                        micro_bs, accum, prefetch_depth=0)
    assert on == off  # exact float equality


def test_prefetch_survives_request_stop(tmp_path):
    """Preemption mid-epoch with the worker buffering ahead: the loop must
    shut the prefetcher down cleanly (no leaked thread, no deadlock) and
    write the preemption checkpoint at an executed step."""
    import threading

    from dlti_tpu.checkpoint import latest_step
    from dlti_tpu.training.trainer import Trainer

    cfg = Config(
        model=CFG, lora=LoRAConfig(r=2, alpha=4, dropout=0.0),
        optimizer=OptimizerConfig(warmup_steps=1),
        parallel=ParallelConfig(),
        data=DataConfig(max_seq_len=16, prefetch_depth=2),
        train=TrainConfig(num_epochs=1, micro_batch_size=2,
                          grad_accum_steps=1, logging_steps=100,
                          metrics_csv=str(tmp_path / "m.csv")),
        checkpoint=CheckpointConfig(output_dir=str(tmp_path / "ckpt"),
                                    save_strategy="steps", save_steps=1000,
                                    save_total_limit=2, async_save=False),
    )
    ds = _make_dataset(False, 2, 1, seq_len=16)
    trainer = Trainer(cfg)

    class StopAfterThird:
        """Dataset proxy whose epoch generator requests a stop at the 3rd
        yield — the prefetch worker pulls it EARLY (ahead of the step
        thread), exercising the stop-while-buffered shutdown path."""

        def steps_per_epoch(self):
            return ds.steps_per_epoch()

        def epoch(self, epoch_idx=0, skip_steps=0):
            for i, b in enumerate(ds.epoch(epoch_idx, skip_steps)):
                if i == 2:
                    trainer.request_stop()
                yield b

    trainer.train(dataset=StopAfterThird())
    stopped_at = latest_step(cfg.checkpoint.output_dir)
    # At least one step ran (the loop observes the stop at a step
    # boundary) and the run never consumed the whole epoch.
    assert stopped_at is not None and 1 <= stopped_at < ds.steps_per_epoch()
    # The worker is joined on exit — no prefetch thread may outlive
    # train() (checkpoint/backend helpers may, hence the name filter).
    assert not [t for t in threading.enumerate()
                if t.name.startswith("dlti-prefetch")]


# ----------------------------------------------------------------------
# drop_remainder (satellite): honored instead of silently ignored
# ----------------------------------------------------------------------

def test_drop_remainder_false_pads_final_step():
    rng = np.random.default_rng(0)
    seqs = [list(map(int, rng.integers(1, 500, size=6))) for _ in range(7)]
    kw = dict(sequences=seqs, seq_len=8, pad_id=0, micro_batch_size=2,
              grad_accum_steps=1, shuffle_seed=None, shard_by_host=False)
    drop = TokenBatchDataset(drop_remainder=True, **kw)
    keep = TokenBatchDataset(drop_remainder=False, **kw)
    assert drop.steps_per_epoch() == 3
    assert keep.steps_per_epoch() == 4
    dropped = list(drop.epoch(0))
    kept = list(keep.epoch(0))
    assert len(dropped) == 3 and len(kept) == 4
    for a, b in zip(dropped, kept):  # shared full steps are identical
        np.testing.assert_array_equal(a["input_ids"], b["input_ids"])
    tail = kept[-1]
    assert tail["input_ids"].shape == kept[0]["input_ids"].shape
    # Row 0 is the real 7th sequence; row 1 is padding: pad_id tokens,
    # zero loss mask — no loss or gradient contribution.
    assert tail["loss_mask"][0, 0].sum() > 0
    assert (tail["input_ids"][0, 1] == 0).all()
    assert (tail["loss_mask"][0, 1] == 0).all()


def test_drop_remainder_padded_step_trains(tmp_path):
    """The padded final step must run through the Trainer without shape
    errors or NaNs (all-pad rows carry zero loss mask)."""
    from dlti_tpu.training.trainer import Trainer

    rng = np.random.default_rng(3)
    seqs = [list(map(int, rng.integers(1, 500, size=7))) for _ in range(5)]
    ds = TokenBatchDataset(sequences=seqs, seq_len=16, pad_id=0,
                           micro_batch_size=2, grad_accum_steps=1,
                           drop_remainder=False)
    cfg = Config(
        model=CFG, lora=LoRAConfig(r=2, alpha=4, dropout=0.0),
        optimizer=OptimizerConfig(warmup_steps=1),
        parallel=ParallelConfig(),
        data=DataConfig(max_seq_len=16),
        train=TrainConfig(num_epochs=1, micro_batch_size=2,
                          grad_accum_steps=1, logging_steps=100,
                          metrics_csv=str(tmp_path / "m.csv")),
        checkpoint=CheckpointConfig(save_strategy="no"),
    )
    _, record = Trainer(cfg).train(dataset=ds)
    assert np.isfinite(record.final_loss)


# ----------------------------------------------------------------------
# Serving: the packed decode round against references; one upload and one
# program call a round
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_params():
    import jax
    import jax.numpy as jnp

    from dlti_tpu.models import LlamaForCausalLM

    model = LlamaForCausalLM(CFG, None)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, **over):
    kw = dict(max_seqs=3, block_size=8, num_blocks=64, max_model_len=64,
              cache_dtype="float32", eos_token_id=-1)
    kw.update(over)
    return InferenceEngine(CFG, params, EngineConfig(**kw))


def _tokens(results):
    return [(r.output_token_ids, r.finish_reason) for r in results]


def _uncached_greedy(params, prompts, n_gen):
    """Reference that shares nothing with the engine's path: the argmax of
    a full forward over prompt + answer so far, no cache, no batch. (Rows
    are padded to one length so one compile serves; the model is causal,
    so what follows a position cannot reach it.)"""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.models import LlamaForCausalLM

    model = LlamaForCausalLM(CFG, None)
    width = max(len(p) for p in prompts) + n_gen
    forward = jax.jit(lambda ids: model.apply(
        {"params": params}, ids, deterministic=True)[0][0])
    out = []
    for prompt in prompts:
        toks = list(prompt)
        for _ in range(n_gen):
            row = np.zeros((1, width), np.int32)
            row[0, :len(toks)] = toks
            toks.append(int(jnp.argmax(forward(row)[len(toks) - 1])))
        out.append((toks[len(prompt):], "length"))
    return out


def _each_alone(params, prompts, sp, **over):
    """Reference for seeded sampling: each request alone in a one-slot
    engine with room to spare (no batch, no preemption), which the
    batch-independence promise makes equal to its stream in any batch."""
    return [_tokens(_engine(params, max_seqs=1, **over).generate([p], sp))[0]
            for p in prompts]


def test_packed_round_matches_references(tiny_params):
    """Rounds staged as one packed array serve a batch exactly as the
    references say: greedy against the uncached full forward, seeded
    sampling against each request alone (block size 8, answers of 10:
    every row grows a block on the way)."""
    prompts = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10, 11, 12]]
    greedy = _engine(tiny_params).generate(
        prompts, SamplingParams(temperature=0.0, max_tokens=10))
    assert _tokens(greedy) == _uncached_greedy(tiny_params, prompts, 10)
    sp = SamplingParams(temperature=0.9, top_k=7, seed=11, max_tokens=10)
    assert _tokens(_engine(tiny_params).generate(prompts, sp)) == \
        _each_alone(tiny_params, prompts, sp)


def test_packed_round_matches_across_preemption(tiny_params):
    """A pool small enough to force preempt → re-admission (recompute)
    still agrees with the references, seeded sampling included (gen
    counts resume mid-stream on re-admission)."""
    prompts = [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13],
               [14, 15, 16, 17, 18]]
    kw = dict(max_seqs=3, num_blocks=8, max_model_len=48)
    for sp, want in (
            (SamplingParams(temperature=0.0, max_tokens=12),
             _uncached_greedy(tiny_params, prompts, 12)),
            (SamplingParams(temperature=0.7, seed=5, max_tokens=12), None)):
        tight = _engine(tiny_params, **kw)
        got = tight.generate(prompts, sp)
        assert tight.stats["preemptions"] >= 1  # the scenario engaged
        assert _tokens(got) == (
            want or _each_alone(tiny_params, prompts, sp, max_model_len=48))


def test_packed_round_matches_while_a_row_prefills(tiny_params):
    """Chunked prefill: a slot is admitted and prefills over several steps
    while the others decode; its block-table row is packed as the trash
    block until its prompt is in, and every stream is what the references
    say."""
    prompts = [[1, 2, 3], list(range(20, 46)), [4, 5, 6, 7]]
    kw = dict(max_prefill_tokens_per_step=8, max_model_len=64)
    eng = _engine(tiny_params, **kw)
    staged = []
    stage = eng.executor.stage_decode

    def spy(ids, pos, mirrors, masked):
        staged.append(list(masked))
        return stage(ids, pos, mirrors, masked)

    eng.executor.stage_decode = spy
    got = eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=9))
    assert any(staged), "no round was launched beside a prefilling slot"
    assert _tokens(got) == _uncached_greedy(tiny_params, prompts, 9)
    sp = SamplingParams(temperature=0.8, seed=3, max_tokens=9)
    assert _tokens(_engine(tiny_params, **kw).generate(prompts, sp)) == \
        _each_alone(tiny_params, prompts, sp)


def test_a_plain_round_after_a_speculative_one_matches(tiny_params):
    """A greedy request speculates and ends; the seeded one beside it goes
    on in plain rounds. Nothing is resident on the device that the
    speculative rounds could have left stale: both streams are what a
    plain engine gives each request alone."""
    prompts = [[7, 8, 9, 7, 8, 9, 7, 8], [4, 5, 4, 5, 4, 5, 4]]
    sps = [SamplingParams(temperature=0.0, max_tokens=6),
           SamplingParams(temperature=0.9, seed=21, max_tokens=24)]
    kw = dict(max_seqs=2, max_model_len=96)
    eng = _engine(tiny_params, speculative="ngram", num_draft_tokens=4,
                  ngram_size=2, **kw)
    calls = []
    for name in ("launch_spec", "launch_decode"):
        def spy(*a, _real=getattr(eng.executor, name), _name=name):
            calls.append(_name)
            return _real(*a)
        setattr(eng.executor, name, spy)
    got = _streams(eng, prompts, sps)
    assert "launch_spec" in calls
    assert calls[-1] == "launch_decode" and \
        calls.index("launch_spec") < len(calls) - 1
    want = [_streams(_engine(tiny_params, **kw), [p], [sp])[0]
            for p, sp in zip(prompts, sps)]
    for (tok, lps, why), (wtok, wlps, wwhy) in zip(got, want):
        assert (tok, why) == (wtok, wwhy)
        np.testing.assert_allclose(lps, wlps, atol=1e-4)
    st = eng.stats
    # A spec round ships its arrays one by one; a plain one is one and one.
    assert st["decode_program_calls"] == len(calls)
    assert st["decode_host_uploads"] == \
        calls.count("launch_decode") + 10 * calls.count("launch_spec")


def _lora_engine(params, **kw):
    return _engine(params, adapter_slots=2, adapter_rank=4, **kw)


def _recurrent_engine(_params, **kw):
    """The hybrid family (Mamba-2 layers beside attention: ``state_slots``
    is the extra per-slot row), the tiny preset."""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.models import build_model

    cfg = MODEL_PRESETS["nemotron_h_tiny"]
    params = build_model(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    over = dict(max_seqs=3, block_size=8, num_blocks=64, max_model_len=64,
                cache_dtype="float32", eos_token_id=-1)
    over.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**over))


LATENT = dataclasses.replace(MODEL_PRESETS["latent_tiny"],
                             moe_scoring="sigmoid_bias")
# Every kind of cache the executor serves, at test widths:
# name: (model configuration, EngineConfig fields)
FAMILIES = {
    "dense": (CFG, {}),
    "int8_kv": (CFG, {"cache_dtype": "int8"}),
    "latent": (LATENT, {}),
    "stream_maps": (dataclasses.replace(LATENT, hc_mult=4), {}),
    "window_groups": (WINDOW_GROUPS, {}),
    "looped": (LOOPED, {}),
}
_FAMILY_PARAMS = {}


def _family_engine(family, cls=InferenceEngine, **kw):
    """An engine over one of ``FAMILIES`` (its parameters built once a
    module), at the sizes of ``_engine``."""
    cfg, over = FAMILIES[family]
    if family not in _FAMILY_PARAMS:
        import jax
        import jax.numpy as jnp

        from dlti_tpu.models import build_model

        _FAMILY_PARAMS[family] = build_model(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cls(cfg, _FAMILY_PARAMS[family], EngineConfig(**{
        **dict(max_seqs=3, block_size=4, num_blocks=128, max_model_len=64,
               cache_dtype="float32", eos_token_id=-1), **over, **kw}))


def _window_groups_engine(_params, **kw):
    """Layers of a window beside layers that see every key: a second table
    and its base ride in the packed round."""
    return _family_engine("window_groups", block_size=8, **kw)


ROUND_KINDS = {
    # name: (engine maker, engine options, rounds launched ahead?)
    "one_step": (None, {}, False),
    "riding": (_engine, {}, True),
    "multi_lora": (_lora_engine, {}, True),
    "recurrent": (_recurrent_engine, {}, True),
    "window_groups": (_window_groups_engine, {}, True),
}


@pytest.mark.parametrize("kind", sorted(ROUND_KINDS))
def test_a_plain_round_is_one_upload_and_one_program_call(
        tiny_params, fetch_first_engine, monkeypatch, kind):
    """THE acceptance criterion: whatever changed between two rounds
    (admissions, ends, block growth: block size 8 here, so rows grow all
    the time), a plain decode round's staging makes exactly one
    host-to-device transfer and its launch exactly one program call, by
    the patched ``jax.device_put`` / ``jnp.asarray`` and by the engine's
    own counters; one-step rounds fetched before the next is planned,
    rounds riding behind the round in flight, a multi-LoRA pool
    (``adapter_ids`` packed, the pool's tree after it), a recurrent model
    (``state_slots`` packed) and a model with a window group of layers
    (its table and base packed)."""
    import jax
    import jax.numpy as jnp

    make, over, ahead = ROUND_KINDS[kind]
    if make is None:
        eng = fetch_first_engine(CFG, tiny_params, EngineConfig(
            max_seqs=3, block_size=8, num_blocks=64, max_model_len=64,
            cache_dtype="float32", eos_token_id=-1))
    else:
        eng = make(tiny_params, **over)
    ex = eng.executor
    extra = {"multi_lora": "adapter_ids", "recurrent": "state_slots"}.get(kind)
    assert ex.round_packing.extra_field == extra
    window = ex.round_packing.window_blocks
    assert (window > 0) == (kind == "window_groups")
    assert ex.round_packing.width == eng.cfg.max_blocks_per_seq + 8 + \
        (extra is not None) + (window + 1 if window else 0)

    transfers = {"stage": 0, "launch": 0}
    where = [None]

    def counting(real):
        def put(x, *a, **k):
            if where[0] is not None and not isinstance(x, jax.Array):
                transfers[where[0]] += 1
            return real(x, *a, **k)
        return put

    monkeypatch.setattr(jax, "device_put", counting(jax.device_put))
    monkeypatch.setattr(jnp, "asarray", counting(jnp.asarray))
    programs = []
    for name, at in (("stage_decode", "stage"), ("launch_decode", "launch")):
        def inside(*a, _real=getattr(ex, name), _at=at):
            where[0] = _at
            try:
                return _real(*a)
            finally:
                where[0] = None
        setattr(ex, name, inside)

    def counted_program(fn):
        def call(*a):
            assert where[0] == "launch"
            # host arguments would be uploads the patch above cannot see
            assert all(isinstance(x, jax.Array)
                       for x in jax.tree_util.tree_leaves(a))
            programs.append(fn)
            return fn(*a)
        return call

    ex._decode_fn = counted_program(ex._decode_fn)

    prompts = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10, 11, 12], [13, 14],
               [15, 16, 17, 18, 19, 20, 21]]
    sps = [SamplingParams(temperature=0.0 if i % 2 else 0.9, seed=40 + i,
                          max_tokens=n)
           for i, n in enumerate([14, 5, 9, 17, 11])]
    _streams(eng, prompts, sps)
    st = eng.stats
    rounds = st["decode_program_calls"]
    assert rounds >= 8 and st["preemptions"] == 0
    assert transfers == {"stage": rounds, "launch": 0}
    assert len(programs) == rounds == st["decode_host_uploads"]
    assert (st["decode_rounds_launched_ahead"] > 0) == ahead
    assert st["decode_steps"] == rounds


def test_the_packing_round_trips_bit_for_bit():
    """float32 values and uint32 keys travel as int32 by their bits:
    1.0, a denormal, -0.0 and the largest finite float; keys with the top
    bit set; a block-table row at full width; a negative id (``RIDES``);
    masked rows read as the trash block and nothing else of them moves."""
    import jax

    from dlti_tpu.serving.decode_state import RoundPacking

    S, MB = 4, 6
    pk = RoundPacking(S, MB, "state_slots")
    assert pk.width == MB + 9
    rng = np.random.default_rng(0)
    denormal = np.float32(1e-42)
    assert denormal != 0 and denormal < np.finfo(np.float32).tiny
    mirrors = {
        "block_tables": rng.integers(1, 2**31 - 1, (S, MB)).astype(np.int32),
        "slot_keys": np.array([[0xFFFFFFFF, 0x80000000], [0, 1],
                               [0x80000001, 0x7FFFFFFF],
                               [0xDEADBEEF, 0xCAFEF00D]], np.uint32),
        "gen_counts": np.array([0, 1, 2**31 - 1, 77], np.int32),
        "temperature": np.array([1.0, 0.0, denormal, 3.4028235e38],
                                np.float32),
        "top_k": np.array([0, 7, 50, 2**31 - 1], np.int32),
        "top_p": np.array([1.0, denormal, -0.0, 0.95], np.float32),
        "state_slots": np.array([0, 1, S, 3], np.int32),
        "adapter_ids": np.zeros((S,), np.int32),   # not this layout's extra
    }
    ids = np.array([[5], [-1], [0], [2**31 - 1]], np.int32)
    pos = np.array([[0], [63], [7], [1]], np.int32)
    packed = pk.pack(ids, pos, mirrors, masked_rows=[2])
    assert packed.dtype == np.int32 and packed.shape == (S, pk.width)
    names = ("input_ids", "positions", "block_tables", "slot_keys",
             "gen_counts", "temperature", "top_k", "top_p", "state_slots")
    for unpack in (pk.unpack, jax.jit(pk.unpack)):
        out = dict(zip(names, unpack(jax.numpy.asarray(packed))))
        want = dict(mirrors, input_ids=ids, positions=pos)
        want["block_tables"] = mirrors["block_tables"].copy()
        want["block_tables"][2] = 0
        for name in names:
            got = np.asarray(out[name])
            assert got.dtype == want[name].dtype, name
            assert got.shape == want[name].shape, name
            assert got.tobytes() == want[name].tobytes(), name
    # the pack is a copy: the scheduler writes its mirrors under a round
    mirrors["gen_counts"][:] = -1
    assert (packed[:, pk.columns["gen_counts"][0]] != -1).all()
    with pytest.raises(TypeError, match="temperature"):
        pk.pack(ids, pos, dict(mirrors, temperature=np.ones(S)), ())


def test_no_decode_program_is_built_after_the_warm_up(tiny_params):
    """One program serves every round: after ``warmup_decode_ladder`` 1, 9
    and 32 slots change between two rounds (ends and admissions at once)
    and the decode calls stay on the warmed executables, with nothing
    traced or compiled for them. (The row updater this replaces was a
    program a padded count of changed rows.)"""
    eng = _engine(tiny_params, max_seqs=32, num_blocks=160, block_size=8,
                  max_model_len=32)
    eng.warmup_decode_ladder()
    call = eng.executor._decode_fn
    assert call._aot_state["aot"]

    def admit(n, max_tokens):
        for i in range(n):
            eng.submit([1 + i, 2, 3], SamplingParams(
                temperature=0.7, seed=i, max_tokens=max_tokens))

    admit(32, 4)
    for changed in (1, 9, 32):
        while eng.has_work:
            eng.step()
        admit(32 - changed, 6)      # these stay
        eng.step()
        eng.step()
        admit(changed, 3)           # these join between two rounds
        for _ in range(3):
            eng.step()
    while eng.has_work:
        eng.step()
    assert eng.stats["decode_program_calls"] == eng.stats["decode_steps"] > 10
    assert eng.executor._decode_fn is call and call._aot_state["aot"]
    assert call._jit_fn._cache_size() == 0   # never traced by a live call


# ----------------------------------------------------------------------
# Serving: the loop one round ahead of the host serves the same streams
# ----------------------------------------------------------------------

def _streams(eng, prompts, sps):
    reqs = [eng.submit(p, sp) for p, sp in zip(prompts, sps)]
    while eng.has_work:
        eng.step()
    return [(r.output_token_ids, r.output_logprobs, r.finish_reason)
            for r in reqs]


MIXED = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10, 11, 12], [13, 14],
         [15, 16, 17, 18, 19, 20, 21], [22], [23, 24, 25]]
LENGTHS = [14, 5, 9, 17, 3, 11, 8]


def _reference_logprobs(params, prompt, tokens):
    """The float32 plain reference (``benchmark/lib/reference.py``): log-probs
    of ``tokens`` after ``prompt``, a full forward, no cache, no batch."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference

    sizes = {"num_layers": CFG.num_layers, "num_heads": CFG.num_heads,
             "num_kv_heads": CFG.num_kv_heads,
             "head_dim": CFG.hidden_size // CFG.num_heads,
             "rms_norm_eps": CFG.rms_norm_eps, "rope_theta": CFG.rope_theta,
             "sliding_window": None, "tie_embeddings": False}
    ids = jnp.asarray(prompt + tokens)
    lp = jax.nn.log_softmax(reference.forward(params, sizes, ids), -1)
    return np.asarray(lp[len(prompt) - 1:len(prompt) - 1 + len(tokens)])


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("kind", ["greedy", "seeded"])
def test_running_ahead_serves_what_fetching_first_serves(
        tiny_params, fetch_first_engine, kind, family):
    """Requests of mixed lengths through three slots, so that rounds hold
    retirements, admissions into slots just freed and block growth: the
    token and log-prob streams of the loop that runs ahead equal, to the
    bit, those of the same engine fetching every round before it plans the
    next; and some of them end on a stop token nobody could foresee, whose
    row in the round behind is thrown away. Over every kind of cache: that
    row writes a key and a value (int8 rows and their scales), a latent, a
    position of every pass's entry, and a block of the window group that
    is released behind the window."""
    def params(stop=()):
        return [SamplingParams(
            temperature=0.0 if kind == "greedy" else 0.9, max_tokens=n,
            seed=None if kind == "greedy" else 100 + i,
            stop_token_ids=tuple(stop)) for i, n in enumerate(LENGTHS)]

    plain = _streams(_family_engine(family, fetch_first_engine), MIXED,
                     params())
    # a stop token that some stream reaches mid-answer: an end nobody foresees
    stop = [plain[3][0][6], plain[0][0][4]]
    for fetch_first in (True, False):
        eng = _family_engine(
            family, fetch_first_engine if fetch_first else InferenceEngine)
        got = _streams(eng, MIXED, params(stop))
        if fetch_first:
            want = got
            assert eng.stats["decode_rounds_launched_ahead"] == 0
            continue
        assert got == want
        st = eng.stats
        assert st["decode_rows_discarded"] >= 2
        assert st["decode_rounds_launched_ahead"] > 0.8 * st["decode_steps"]
        assert eng.block_manager.num_free == eng.cfg.num_blocks - 1
        if family == "window_groups":
            # sequences outgrew the window of 8 and ended: both releases
            assert eng.kv_freed["window", "window"] > 0 < \
                eng.kv_freed["window", "end"]
            assert eng.window_manager.num_free == \
                eng.window_manager.num_blocks - 1
    assert {r[2] for r in want} == {"stop", "length"}
    if kind == "greedy" and family == "dense":
        for prompt, (tokens, logprobs, _) in zip(MIXED, want):
            rows = _reference_logprobs(tiny_params, prompt, tokens)
            picked = rows[np.arange(len(tokens)), tokens]
            np.testing.assert_allclose(logprobs, picked, atol=2e-4)
            assert (rows.max(-1) - picked <= 2e-4).all()


def test_a_prefix_hit_on_a_tail_block_a_discarded_row_wrote(tiny_params):
    """Prefix cache on: a sequence ends on a stop token with its row riding
    in the round behind, which writes one position past the last kept token
    into the sequence's tail block. The blocks registered at its release are
    the whole ones before that position; the next request with the same
    prompt and answer so far hits them and serves what the reference says."""
    kw = dict(block_size=4, max_model_len=64, enable_prefix_caching=True)
    sp = SamplingParams(temperature=0.0, max_tokens=12)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    [(tokens, _, _)] = _streams(_engine(tiny_params, **kw), [prompt], [sp])
    eng = _engine(tiny_params, **kw)
    stop = tokens[7]   # prompt 8 + 8 kept = 16: the discarded write opens block 5
    first = _streams(eng, [prompt], [SamplingParams(
        temperature=0.0, max_tokens=12, stop_token_ids=(stop,))])
    assert first[0][0] == tokens[:8] and first[0][2] == "stop"
    assert eng.stats["decode_rows_discarded"] == 1
    assert eng.prefix_cache.num_cached_blocks == 3  # positions 0..11 of 0..14
    again = prompt + tokens[:8]
    got = _streams(eng, [again], [sp])
    assert eng.stats["prefix_cached_tokens"] >= 12
    want = _uncached_greedy(tiny_params, [again], 12)
    assert (got[0][0], got[0][2]) == want[0]


def test_decode_round_cost_counters_exposed(tiny_params):
    """The counters ride the engine stats dict (the /metrics scalar
    source), present before the first decode round, and the ones they
    replace are gone."""
    eng = _engine(tiny_params)
    for k in ("decode_host_uploads", "decode_program_calls"):
        assert eng.stats[k] == 0
    assert eng.executor.stats is eng.stats
    assert not [k for k in eng.stats if k.startswith("decode_state_")]


# ----------------------------------------------------------------------
# BlockManager double-free guard (satellite)
# ----------------------------------------------------------------------

def test_block_manager_double_free_raises():
    from dlti_tpu.serving.block_manager import BlockManager

    bm = BlockManager(num_blocks=16, block_size=8)
    blocks = bm.allocate(4)
    bm.free(blocks[:2])
    with pytest.raises(ValueError, match="free"):
        bm.free(blocks[:2])  # double free
    # All-or-nothing: the rejected call freed nothing, the pool is intact
    # and the still-live blocks free cleanly.
    assert bm.num_free == 15 - 2
    bm.free(blocks[2:])
    assert bm.num_free == 15


def test_block_manager_double_free_raises_python(monkeypatch):
    import dlti_tpu.serving.block_manager as bmod

    monkeypatch.setattr(bmod, "load_native_runtime", lambda: None)
    bm = bmod.BlockManager(num_blocks=8, block_size=8)
    got = bm.allocate(2)
    bm.free(got)
    with pytest.raises(ValueError, match="double free"):
        bm.free([got[0]])
    with pytest.raises(ValueError, match="freeing invalid block"):
        bm.free([0])
    # Duplicate ids within one batch are a double free too.
    more = bm.allocate(1)
    with pytest.raises(ValueError, match="double free"):
        bm.free([more[0], more[0]])
    assert more[0] not in bm._free  # rejected call freed nothing
