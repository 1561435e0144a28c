"""Host-latency-hiding layer: equivalence + zero-upload contracts.

Two hot paths, one invariant each:

* Training (``dlti_tpu.data.prefetch``): the background prefetcher must be
  *invisible* in the numbers — bit-identical loss trajectory vs. the
  synchronous path for every (preset, packing) combination — and safe to
  shut down mid-epoch (preemption).
* Serving (``dlti_tpu.serving.decode_state``): the device-resident
  decode state must serve what references that do not share its path say
  (the uncached full forward; each seeded request alone — including
  across preemption and re-admission), and a clean decode step
  — no admission/retire/preempt/growth since the last one — must issue
  ZERO host→device decode-state uploads (the acceptance criterion).
"""

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
# Serving: resident decode state against references + zero-upload clean steps
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


def test_resident_decode_state_matches_references(tiny_params):
    """The resident per-slot state serves a batch exactly as the
    references say: greedy against the uncached full forward, seeded
    sampling against each request alone."""
    prompts = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10, 11, 12]]
    greedy = _engine(tiny_params).generate(
        prompts, SamplingParams(temperature=0.0, max_tokens=10))
    assert _tokens(greedy) == _uncached_greedy(tiny_params, prompts, 10)
    sp = SamplingParams(temperature=0.9, top_k=7, seed=11, max_tokens=10)
    assert _tokens(_engine(tiny_params).generate(prompts, sp)) == \
        _each_alone(tiny_params, prompts, sp)


def test_resident_decode_state_matches_across_preemption(tiny_params):
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


def test_resident_decode_state_matches_multi_step(tiny_params):
    prompts = [[1, 2, 3, 4], [5, 6, 7]]
    eng = _engine(tiny_params, max_seqs=2, steps_per_sync=4)
    got = eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=9))
    assert _tokens(got) == _uncached_greedy(tiny_params, prompts, 9)


def test_clean_decode_step_issues_zero_uploads(tiny_params):
    """THE acceptance criterion: once the batch composition settles, every
    further decode step reuses the resident device state — zero
    host→device decode-state uploads, while decode_steps keeps advancing."""
    # One 64-token block per sequence: no block-table growth inside the
    # observation window (growth is a legitimately dirty event).
    eng = _engine(tiny_params, block_size=64, num_blocks=8)
    eng.submit([1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=30))
    eng.step()   # admission + prefill
    eng.step()   # first decode: uploads the admitted row
    settled = eng.stats["decode_state_uploads"]
    clean_before = eng.stats["decode_state_clean_syncs"]
    steps_before = eng.stats["decode_steps"]
    for _ in range(6):
        eng.step()
    assert eng.stats["decode_steps"] == steps_before + 6
    assert eng.stats["decode_state_uploads"] == settled  # ZERO new uploads
    assert eng.stats["decode_state_clean_syncs"] >= clean_before + 6
    # The account's decode_prep phase entered every dispatch, and booked.
    acct = eng.telemetry.stepper
    assert acct.entries()["engine/decode_prep"] >= 7
    assert acct.seconds()["engine/decode_prep"] > 0


def test_warm_up_runs_the_row_updater_at_every_padded_count(tiny_params):
    """The resident state's row updater is one jit that XLA specializes
    per padded count of dirty rows; the warm-up meets every count up to
    max_seqs, so a burst of ends and admissions under traffic (a count no
    quiet step forms) compiles nothing, and it leaves the rows as they
    stood."""
    eng = _engine(tiny_params, max_seqs=8)
    state = eng.executor.decode_state
    state.sync(eng._state_mirrors(), eng._masked_rows())
    counted = dict(state.stats)
    state.warm_row_counts(eng._state_mirrors(), eng._masked_rows())
    assert state.stats == counted    # the warm-up's uploads are not traffic's
    eng.warmup_decode_ladder()
    # (the jit's cache is shared by every engine of the process: what is
    # pinned is that no count of dirty rows adds to it after the warm-up)
    warmed = state._update._cache_size()
    before = [np.asarray(a) for a in state._dev]
    for dirty in range(1, 9):                        # pads to 1, 2, 4, 8
        for slot in range(dirty):
            state.mark_dirty(slot)
        state.sync(eng._state_mirrors(), eng._masked_rows())
        assert state._update._cache_size() == warmed
    for a, b in zip(before, state._dev):
        np.testing.assert_array_equal(a, np.asarray(b))
    got = eng.generate([[3, 1, 4, 1, 5, 9]],
                       SamplingParams(temperature=0.0, max_tokens=5))
    assert len(got[0].output_token_ids) == 5


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


@pytest.mark.parametrize("kind", ["greedy", "seeded"])
def test_running_ahead_serves_what_fetching_first_serves(
        tiny_params, fetch_first_engine, kind):
    """Requests of mixed lengths through three slots, so that rounds hold
    retirements, admissions into slots just freed and block growth: the
    token and log-prob streams of the loop that runs ahead equal, to the
    bit, those of the same engine fetching every round before it plans the
    next; and some of them end on a stop token nobody could foresee, whose
    row in the round behind is thrown away."""
    def params(stop=()):
        return [SamplingParams(
            temperature=0.0 if kind == "greedy" else 0.9, max_tokens=n,
            seed=None if kind == "greedy" else 100 + i,
            stop_token_ids=tuple(stop)) for i, n in enumerate(LENGTHS)]

    cfg = EngineConfig(max_seqs=3, block_size=4, num_blocks=64,
                       max_model_len=64, cache_dtype="float32",
                       eos_token_id=-1)
    plain = _streams(fetch_first_engine(CFG, tiny_params, cfg), MIXED,
                     params())
    # a stop token that some stream reaches mid-answer: an end nobody foresees
    stop = [plain[3][0][6], plain[0][0][4]]
    for fetch_first in (True, False):
        eng = (fetch_first_engine if fetch_first else InferenceEngine)(
            CFG, tiny_params, cfg)
        got = _streams(eng, MIXED, params(stop))
        if fetch_first:
            want = got
            assert eng.stats["decode_rounds_launched_ahead"] == 0
            continue
        assert got == want
        st = eng.stats
        assert st["decode_rows_discarded"] >= 2
        assert st["decode_rounds_launched_ahead"] > 0.8 * st["decode_steps"]
    assert {r[2] for r in want} == {"stop", "length"}
    if kind == "greedy":
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


def test_decode_state_upload_counters_exposed(tiny_params):
    """The counters ride the engine stats dict (the /metrics scalar
    source), present before the first decode round."""
    eng = _engine(tiny_params)
    for k in ("decode_state_uploads", "decode_state_rows",
              "decode_state_clean_syncs"):
        assert eng.stats[k] == 0


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
