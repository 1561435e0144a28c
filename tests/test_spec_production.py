"""Adaptive speculative decoding as a production citizen — tier 1.

Speculation is output-invariant by construction (greedy-exact verify);
these tests pin that invariance where it is easiest to lose — at every
production seam — plus the per-slot controller semantics themselves:

* **Per-slot gating**: a zero-ngram-hit slot pauses alone while a
  repetitive-text slot in the SAME batch keeps accepting drafts (the
  batch-wide `_spec_pause` this controller replaced would have stalled
  both).
* **Draft-length ladder**: sustained low acceptance walks dispatch k
  down the pow2 ladder; `spec_adaptive=False` pins k at
  `num_draft_tokens`.
* **Handoff carry**: the controller window/cooldown/EWMA ride
  `export_handoff` → wire envelope → `adopt_handoff` byte-exactly, so
  an adopting engine resumes the gate mid-window instead of re-probing.
* **Equivalence cells**: spec on == spec off, token-for-token, across
  {disagg on/off} × {bf16, int8 KV}, a 2-worker fleet with a planned
  mid-decode drain migration, a multi-LoRA batch vs merged-weights
  oracles, and prefix-tier restores.

The tiny random model is the test vocabulary: greedy generation after
``[6, 6, 7, 7, ...]`` locks into a period-1 loop (sustained ngram hits,
~100% acceptance) while ``[2, 7, 1, 8, 2, 8]`` emits distinct tokens
for its first several rounds (zero lookup hits) — a deterministic
favorable/adversarial pair.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import unfreeze

from dlti_tpu.config import LoRAConfig, MODEL_PRESETS
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.models.lora import merge_lora_params
from dlti_tpu.serving import (
    DisaggController, EngineConfig, InferenceEngine, SamplingParams,
)
from dlti_tpu.serving import wire
from dlti_tpu.serving.adapters import (
    get_catalog, register_adapter, save_adapter,
)

CFG = MODEL_PRESETS["llama_tiny"]

CYCLIC = [6, 6, 7, 7, 6, 6, 7, 7]      # generation loops -> accepts
ACYCLIC = [2, 7, 1, 8, 2, 8]           # no early hits -> pauses
SPEC_PROMPTS = [CYCLIC, [1, 2, 3, 4, 5], ACYCLIC, [5, 5, 5, 5]]

GREEDY = SamplingParams(max_tokens=8, temperature=0.0)


@pytest.fixture(scope="module")
def tiny_params():
    model = LlamaForCausalLM(CFG, None)
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]


def _ec(**over):
    base = dict(max_seqs=4, block_size=8, num_blocks=64, max_model_len=128,
                cache_dtype="float32", eos_token_id=-1, speculative="ngram")
    base.update(over)
    return EngineConfig(**base)


def _drain(eng, reqs):
    while eng.has_work:
        eng.step()
    return reqs


def _plain_outputs(params, prompts, sp, **over):
    eng = InferenceEngine(CFG, params, _ec(speculative="none", **over))
    return [r.output_token_ids for r in eng.generate(prompts, sp)]


# ----------------------------------------------------------------------
# Per-slot controller semantics
# ----------------------------------------------------------------------

def test_zero_hit_slot_pauses_alone(tiny_params):
    """The headline of the per-slot gate: the adversarial slot burns its
    probe window on zero-hit rounds and pauses, while the cyclic slot in
    the SAME batch keeps proposing and accepting the whole run."""
    ec = _ec(max_seqs=2, num_blocks=128, max_model_len=256,
             spec_probe_window=6, spec_cooldown=10_000)
    eng = InferenceEngine(CFG, tiny_params, ec)
    sp = SamplingParams(temperature=0.0, max_tokens=48)
    fav = eng.submit(CYCLIC, sp)
    adv = eng.submit(ACYCLIC, sp)
    eng.step()  # slots assigned at first admission step
    sid = {s.request.request_id: s.slot_id for s in eng.slots if s.request}
    fav_paused = adv_paused = False
    while eng.has_work:
        eng.step()
        fav_paused |= bool(eng._spec_slot_pause[sid[fav.request_id]] > 0)
        adv_paused |= bool(eng._spec_slot_pause[sid[adv.request_id]] > 0)
    assert adv_paused and not fav_paused
    assert eng.stats["spec_paused_rounds"] > 0
    assert eng.stats["spec_accepted"] > 0  # the cyclic slot kept winning
    # Gating is a throughput decision, never an output one.
    expect = _plain_outputs(tiny_params, [CYCLIC, ACYCLIC], sp,
                            max_seqs=2, num_blocks=128, max_model_len=256)
    assert [fav.output_token_ids, adv.output_token_ids] == expect


def test_released_slot_forgets_controller_state(tiny_params):
    """Slot reuse must not inherit the previous tenant's cooldown or a
    half-filled acceptance window."""
    ec = _ec(max_seqs=1, spec_probe_window=4, spec_cooldown=10_000)
    eng = InferenceEngine(CFG, tiny_params, ec)
    req = eng.submit(ACYCLIC, SamplingParams(temperature=0.0, max_tokens=24))
    _drain(eng, [req])
    assert req.finish_reason == "length"
    assert int(eng._spec_slot_pause[0]) == 0
    assert int(eng._spec_slot_prop[0]) == 0
    assert int(eng._spec_slot_acc[0]) == 0
    assert float(eng._spec_slot_ewma[0]) == float(ec.num_draft_tokens)


def test_adaptive_ladder_shrinks_draft_len(tiny_params):
    """Sustained low acceptance walks dispatch k down the pow2 ladder
    (compiling the smaller program lazily); spec_adaptive=False keeps
    every dispatch at num_draft_tokens."""
    sp = SamplingParams(temperature=0.0, max_tokens=48)
    ec = _ec(max_seqs=1, num_blocks=128, max_model_len=256,
             spec_min_acceptance=0.0)  # gate off: isolate the ladder
    eng = InferenceEngine(CFG, tiny_params, ec)
    eng.submit(ACYCLIC, sp)
    ks = set()
    while eng.has_work:
        eng.step()
        ks.add(int(eng.spec_draft_len))
    dispatched = ks - {0}
    assert dispatched, "speculation never dispatched"
    assert min(dispatched) < ec.num_draft_tokens
    # The smaller rung is a real compiled program in the ladder cache.
    assert set(eng.executor._spec_fns) >= {ec.num_draft_tokens,
                                           min(dispatched)}
    fixed = InferenceEngine(CFG, tiny_params,
                            _ec(max_seqs=1, num_blocks=128,
                                max_model_len=256, spec_min_acceptance=0.0,
                                spec_adaptive=False))
    fixed.submit(ACYCLIC, sp)
    fks = set()
    while fixed.has_work:
        fixed.step()
        fks.add(int(fixed.spec_draft_len))
    assert fks - {0} == {ec.num_draft_tokens}


@pytest.mark.parametrize("k", [4, 2])
def test_a_speculative_call_is_one_round(tiny_params, k):
    """One propose -> verify -> accept a program call: what the call hands
    back has no axis of rounds (tokens and log-probs ``(S, k + 1)``, the
    three counts ``(S,)``), a slot gets at most k + 1 tokens of it, and a
    call is one decode step in the books."""
    ec = _ec(max_seqs=2, num_draft_tokens=k, spec_adaptive=False,
             spec_min_acceptance=0.0)
    eng = InferenceEngine(CFG, tiny_params, ec)
    shapes, launch = [], eng.executor.launch_spec

    def spy(staged, draft):
        out = launch(staged, draft)
        shapes.append((draft, [tuple(x.shape) for x in out]))
        return out

    eng.executor.launch_spec = spy
    reqs = [eng.submit(p, SamplingParams(temperature=0.0, max_tokens=24))
            for p in (CYCLIC, ACYCLIC)]
    grew = 0
    while eng.has_work:
        before = [len(r.output_token_ids) for r in reqs]
        eng.step()
        grew = max([grew] + [len(r.output_token_ids) - n
                             for r, n in zip(reqs, before)])
    S = ec.max_seqs
    assert shapes and all(
        s == (k, [(S, k + 1), (S, k + 1), (S,), (S,), (S,)]) for s in shapes)
    assert 1 < grew <= k + 1          # several tokens a call, one round's
    assert eng.stats["decode_steps"] == eng.stats["decode_program_calls"]
    assert eng.stats["decode_steps"] >= len(shapes)
    assert [r.output_token_ids for r in reqs] == _plain_outputs(
        tiny_params, [CYCLIC, ACYCLIC], reqs[0].params, max_seqs=2)


# ----------------------------------------------------------------------
# Handoff carry: the controller rides the envelope
# ----------------------------------------------------------------------

def test_handoff_carries_spec_state_across_wire(tiny_params):
    src = InferenceEngine(CFG, tiny_params, _ec())
    src.prefill_only = True
    req = src.submit(CYCLIC, SamplingParams(temperature=0.0, max_tokens=8))
    for _ in range(50):
        src.step()
        slot = next((s for s in src.slots if s.request is req), None)
        if slot is not None and not slot.prefilling \
                and slot.last_token is not None:
            break
    else:
        pytest.fail("prefill never completed")
    # Mid-window controller state (a prefill-only engine never decodes,
    # so plant a distinctive snapshot the export must carry verbatim).
    sid = slot.slot_id
    src._spec_slot_prop[sid] = 5
    src._spec_slot_acc[sid] = 3
    src._spec_slot_pause[sid] = 2
    src._spec_slot_ewma[sid] = 1.5
    snap = src.export_handoff(slot)
    assert snap["spec"] == {"prop": 5, "acc": 3, "pause": 2, "ewma": 1.5}
    # Export released the origin slot back to the fresh-slot state.
    assert int(src._spec_slot_prop[sid]) == 0
    # The additive dict survives the generic wire envelope byte-exactly.
    snap2 = wire.unpack_handoff(wire.pack_handoff(snap))
    assert snap2["spec"] == snap["spec"]
    dst = InferenceEngine(CFG, tiny_params, _ec())
    assert dst.adopt_handoff(snap2)
    dslot = next(s for s in dst.slots if s.request.request_id
                 == req.request_id)
    did = dslot.slot_id
    assert int(dst._spec_slot_prop[did]) == 5
    assert int(dst._spec_slot_acc[did]) == 3
    assert int(dst._spec_slot_pause[did]) == 2
    assert float(dst._spec_slot_ewma[did]) == 1.5


# ----------------------------------------------------------------------
# Equivalence cells: spec on == spec off at every production seam
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_spec_outputs_identical_disagg_on_vs_off(tiny_params, devices,
                                                 kv_dtype):
    """Spec × disagg × KV dtype: the speculating decode pool finishes
    adopted prefills token-identically to a plain colocated engine."""
    sp = GREEDY
    expect = _plain_outputs(tiny_params, SPEC_PROMPTS, sp,
                            cache_dtype=kv_dtype)
    solo = InferenceEngine(CFG, tiny_params, _ec(cache_dtype=kv_dtype))
    got = [r.output_token_ids for r in solo.generate(SPEC_PROMPTS, sp)]
    assert got == expect
    assert solo.stats["spec_proposed"] > 0  # speculation genuinely ran
    ctl = DisaggController(CFG, tiny_params, _ec(cache_dtype=kv_dtype),
                           prefill_replicas=1, decode_replicas=2,
                           devices=devices[:3])
    got = [r.output_token_ids for r in ctl.generate(SPEC_PROMPTS, sp)]
    assert got == expect
    assert ctl.handoff["completed"] >= len(SPEC_PROMPTS)
    assert sum(e.stats["spec_proposed"]
               for e in ctl.decode.engines) > 0


def test_spec_fleet_migration_byte_identical(tiny_params):
    """Spec × fleet × planned drain: a speculating 2-worker fleet, one
    worker drained mid-decode, still lands the single-engine tokens —
    the controller state crosses the process-shaped boundary with the
    KV envelope."""
    import threading

    from dlti_tpu.config import FleetConfig, ReplicaLifecycleConfig
    from dlti_tpu.serving.fleet import FleetSupervisor
    from dlti_tpu.serving.worker import EngineWorker

    sp = SamplingParams(max_tokens=12, temperature=0.0)
    expect = _plain_outputs(tiny_params, SPEC_PROMPTS, sp)

    class _Handle:
        def __init__(self, worker):
            self.worker = worker
            self.pid = 990000 + worker.worker_id
            self.thread = threading.Thread(target=worker.serve_forever,
                                           daemon=True)
            self.thread.start()

        def port(self):
            return self.worker.port

        def poll(self):
            return None if self.thread.is_alive() else 0

        def wait(self, timeout=None):
            self.thread.join(timeout)
            return 0

        def terminate(self):
            self.worker.close()

        kill = terminate

    def spawn(idx, generation):
        engine = InferenceEngine(CFG, tiny_params, _ec())
        return _Handle(EngineWorker(engine, port=0, worker_id=idx))

    sup = FleetSupervisor(
        _ec(), workers=2, spawner=spawn,
        fleet_cfg=FleetConfig(workers=2, health_interval_s=0.05,
                              respawn_backoff_s=0.05,
                              respawn_backoff_max_s=0.5,
                              startup_timeout_s=120.0, rpc_timeout_s=60.0,
                              term_grace_s=2.0),
        lifecycle_cfg=ReplicaLifecycleConfig(enabled=True,
                                             probation_initial_s=0.05,
                                             probation_max_s=0.5),
        canary_vocab=CFG.vocab_size)
    try:
        reqs = [sup.submit(p, sp) for p in SPEC_PROMPTS]
        for _ in range(60):
            sup.step()
            if all(len(r.output_token_ids) >= 2 for r in reqs):
                break
        assert all(not r.done for r in reqs)
        victim = next(w for w in sup._workers if w.owned)
        errored = sup.drain_replica(victim.idx, kind="preempt",
                                    quarantine=False)
        assert errored == []
        while sup.has_work:
            sup.step()
        assert [r for r in reqs if r.num_migrations > 0], \
            "drain must migrate at least one mid-decode request"
        for p, r in zip(SPEC_PROMPTS, reqs):
            assert r.output_token_ids == expect[SPEC_PROMPTS.index(p)], \
                f"{r.request_id} (migrations={r.num_migrations})"
            assert r.finish_reason == "length"
    finally:
        sup.close()


@pytest.fixture()
def _clean_catalog():
    get_catalog().clear()
    yield
    get_catalog().clear()


def test_spec_multilora_matches_merged_engines(tmp_path, _clean_catalog):
    """Spec × multi-LoRA: a speculating shared-base engine serving a
    heterogeneous adapter batch emits the same tokens as per-adapter
    merged-weights engines running WITHOUT speculation."""
    R, ALPHA = 4, 8.0
    model = LlamaForCausalLM(CFG, LoRAConfig(r=R, alpha=int(ALPHA),
                                             dropout=0.0))
    tree = unfreeze(model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"])

    def _randomize(node, rng):
        for k in node:
            v = node[k]
            if not isinstance(v, dict):
                continue
            if "lora_a" in v and "lora_b" in v:
                v["lora_a"] = jnp.asarray(
                    rng.normal(0.0, 0.2, np.shape(v["lora_a"])), jnp.float32)
                v["lora_b"] = jnp.asarray(
                    rng.normal(0.0, 0.2, np.shape(v["lora_b"])), jnp.float32)
            else:
                _randomize(v, rng)

    _randomize(tree, np.random.RandomState(1))
    base = merge_lora_params(tree, scaling=0.0)
    merged = merge_lora_params(tree, alpha=ALPHA)
    d = str(tmp_path / "ad-s")
    save_adapter(d, tree, alpha=ALPHA)
    register_adapter("ad-s", d)

    sp = SamplingParams(temperature=0.0, max_tokens=16)
    ec = _ec(max_model_len=64, adapter_slots=2, adapter_rank=R)
    shared = InferenceEngine(CFG, base, ec)
    # The base row is the cyclic one: adapter weights steer generation
    # away from the loop, and the engagement assert below needs at least
    # one row that genuinely accepts drafts.
    assign = [(CYCLIC, ""), ([5, 5, 5, 5], "ad-s"), (ACYCLIC, "ad-s")]
    reqs = [shared.submit(p, sp, adapter=name) for p, name in assign]
    _drain(shared, reqs)
    assert shared.stats["spec_proposed"] > 0
    oracle = {
        "": InferenceEngine(CFG, base,
                            _ec(max_model_len=64, speculative="none")),
        "ad-s": InferenceEngine(CFG, merged,
                                _ec(max_model_len=64, speculative="none")),
    }
    for (prompt, name), req in zip(assign, reqs):
        want = oracle[name].generate([prompt], sp)[0]
        assert req.output_token_ids == want.output_token_ids, name


def test_spec_prefix_tier_restore_byte_identical(tiny_params, tmp_path):
    """Spec × prefix tiering: host-tier restores feed a speculating
    engine the exact cached KV, so revisited sessions stay
    token-identical to an uncached, unspeculative engine."""
    # 4 "sessions": shared 8-token block + per-session block + tail — a
    # 7-block device pool cannot hold all of them at once, so round 2
    # revisits blocks the host/disk tiers absorbed.
    sessions = [[i] * 8 + [7] * 8 + [1, 2, 3] for i in range(4)]
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    tiered = InferenceEngine(
        CFG, tiny_params,
        _ec(max_seqs=1, num_blocks=7, max_model_len=40,
            enable_prefix_caching=True, prefix_host_blocks=8,
            prefix_disk_dir=str(tmp_path), prefix_disk_blocks=16))
    plain = InferenceEngine(
        CFG, tiny_params,
        _ec(max_seqs=1, num_blocks=7, max_model_len=40,
            speculative="none"))
    for _ in range(2):  # round 2 revisits everything the pool evicted
        for p in sessions:
            [got] = tiered.generate([p], sp)
            [want] = plain.generate([p], sp)
            assert got.output_token_ids == want.output_token_ids
    assert tiered.stats["prefix_restored_tokens"] > 0
