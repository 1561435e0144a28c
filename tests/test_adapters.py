"""Multi-LoRA serving: adapter catalog, HBM pool, batched engine path.

Three layers, cheapest first:

* **Host-side units** — checkpoint format round-trip, catalog
  verification (corrupt ⇒ quarantine + unknown, so routing 404s),
  refcounted-LRU pool semantics, and the planner/pool/memledger
  byte-exact cross-check.
* **Tier-1 equivalence** (the acceptance pin): one shared-base engine
  serving a batch where every row wears a different adapter emits
  token-identical streams to per-adapter merged-weights engines —
  greedy AND seeded sampling, bf16 AND int8 base — and base requests
  stay byte-identical to an adapter-free engine.
* **Slow integration** — hot-register while the engine is mid-decode,
  replica-failover resubmit preserving each request's adapter, and the
  train → save → register → generate loop with no engine restart.
"""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import unfreeze

from dlti_tpu.checkpoint.chaos import FaultyIO
from dlti_tpu.config import LoRAConfig, MODEL_PRESETS
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.models.lora import merge_lora_params
from dlti_tpu.serving import (
    EngineConfig, InferenceEngine, ReplicatedEngine, SamplingParams,
)
from dlti_tpu.serving import adapters as adapters_mod
from dlti_tpu.serving.adapters import (
    AdapterError,
    AdapterPool,
    extract_adapter_weights,
    get_catalog,
    plan_pool_bytes,
    register_adapter,
    save_adapter,
)
from dlti_tpu.utils import durable_io

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
import memory_plan  # noqa: E402

CFG = MODEL_PRESETS["llama_tiny"]
TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj")
R, ALPHA = 4, 8.0

PROMPTS = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8], [5, 5, 5, 5],
           [11, 12, 13]]
GREEDY = SamplingParams(temperature=0.0, max_tokens=12)
SEEDED = SamplingParams(temperature=0.8, seed=1234, max_tokens=12)


@pytest.fixture(autouse=True)
def _clean_catalog():
    """The catalog is process-global by design; keep tests hermetic."""
    get_catalog().clear()
    yield
    get_catalog().clear()


def _randomize_lora(tree, rng):
    # init leaves lora_b all-zero (delta == 0); give both factors real
    # values so the adapter visibly moves the logits.
    for k in tree:
        v = tree[k]
        if not isinstance(v, dict):
            continue
        if "lora_a" in v and "lora_b" in v:
            v["lora_a"] = jnp.asarray(
                rng.normal(0.0, 0.2, np.shape(v["lora_a"])), jnp.float32)
            v["lora_b"] = jnp.asarray(
                rng.normal(0.0, 0.2, np.shape(v["lora_b"])), jnp.float32)
        else:
            _randomize_lora(v, rng)


def _lora_params(seed):
    model = LlamaForCausalLM(CFG, LoRAConfig(r=R, alpha=int(ALPHA),
                                             dropout=0.0))
    p = unfreeze(model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"])
    _randomize_lora(p, np.random.RandomState(seed))
    return p


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    """Two distinct adapters over one shared base + their merged trees."""
    root = tmp_path_factory.mktemp("adapters")
    trees = {"ad-a": _lora_params(1), "ad-b": _lora_params(2)}
    # Same init key in both trees: the base kernels are identical; a
    # zero-scale merge strips the LoRA leaves without touching them.
    base = merge_lora_params(trees["ad-a"], scaling=0.0)
    dirs, merged = {}, {}
    for name, tree in trees.items():
        d = str(root / name)
        save_adapter(d, tree, alpha=ALPHA)
        dirs[name] = d
        merged[name] = merge_lora_params(tree, alpha=ALPHA)
    return types.SimpleNamespace(base=base, trees=trees, dirs=dirs,
                                 merged=merged)


def _ec(**kw):
    d = dict(max_seqs=4, block_size=8, num_blocks=64, max_model_len=64,
             cache_dtype="float32", eos_token_id=-1)
    d.update(kw)
    return EngineConfig(**d)


def _drain(eng, reqs):
    while eng.has_work:
        eng.step()
    return [eng._result(r) for r in reqs]


def _corrupt(directory):
    """Flip bytes in the largest data file so digest verification trips."""
    files = [os.path.join(directory, f) for f in os.listdir(directory)]
    target = max((f for f in files if os.path.isfile(f)), key=os.path.getsize)
    size = os.path.getsize(target)
    with open(target, "r+b") as f:
        f.seek(size // 2)
        chunk = f.read(64)
        f.seek(size // 2)
        f.write(bytes(b ^ 0xFF for b in chunk))


def _bf16(tree):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x, tree)


def _bf16_round_base(tree):
    """Base leaves rounded through bf16 back to f32 — the exact values a
    bf16-resident base contributes under f32 accumulation. LoRA factors
    stay untouched f32 masters (the pool holds them in f32 too)."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out[k] = _bf16_round_base(v)
        elif k in ("lora_a", "lora_b"):
            out[k] = v
        else:
            out[k] = jnp.asarray(v).astype(jnp.bfloat16).astype(jnp.float32)
    return out


def _row(pool, idx):
    return jax.tree_util.tree_map(lambda x: np.asarray(x[idx]), pool.tree)


# ----------------------------------------------------------------------
# Checkpoint format + catalog
# ----------------------------------------------------------------------

def test_extract_and_save_require_lora_factors(setup, tmp_path):
    weights = extract_adapter_weights(setup.trees["ad-a"])
    # Every targeted projection of every layer made it into the subtree.
    flat = adapters_mod._flatten_lora(weights)
    names = {p[-1] for p in flat}
    assert names == set(TARGETS)
    assert len(flat) == CFG.num_layers * len(TARGETS)
    # A plain (merged / base) tree has nothing to save.
    with pytest.raises(ValueError, match="no lora"):
        save_adapter(str(tmp_path / "empty"), setup.base)


def test_catalog_register_verifies_and_lists(setup):
    cat = get_catalog()
    assert register_adapter("ad-a", setup.dirs["ad-a"]) == "ad-a"
    register_adapter("ad-b", setup.dirs["ad-b"])
    assert cat.names() == ["ad-a", "ad-b"]
    assert "ad-a" in cat and "ghost" not in cat
    assert cat.directory("ad-a") == os.path.abspath(setup.dirs["ad-a"])
    assert cat.unregister("ad-a") and not cat.unregister("ad-a")
    assert cat.names() == ["ad-b"]
    # Unreadable directory never lands in the catalog.
    with pytest.raises(AdapterError, match="unreadable|corrupt"):
        register_adapter("nope", "/does/not/exist")
    assert "nope" not in cat


@pytest.mark.parametrize("bad", ["", "has space", "a/b", "a\\b", "a\nb"])
def test_catalog_rejects_bad_names(setup, bad):
    with pytest.raises(AdapterError, match="invalid adapter name"):
        register_adapter(bad, setup.dirs["ad-a"])


def test_corrupt_checkpoint_quarantined_at_registration(setup, tmp_path):
    d = str(tmp_path / "bad")
    save_adapter(d, setup.trees["ad-a"], alpha=ALPHA)
    _corrupt(d)
    with pytest.raises(AdapterError, match="corrupt"):
        register_adapter("bad", d)
    assert "bad" not in get_catalog()
    # Quarantined for forensics, not deleted: the dir moved aside.
    qdir = os.path.join(str(tmp_path), "_quarantine")
    assert not os.path.exists(d)
    assert os.path.isdir(qdir) and os.listdir(qdir)


def test_corrupt_after_registration_unregisters_on_load(setup, tmp_path):
    """Registration verified fine; the bytes rotted later. The pool load
    quarantines, raises the request-scoped error, and drops the name so
    the next request 404s at admission instead of retrying forever."""
    d = str(tmp_path / "rots")
    save_adapter(d, setup.trees["ad-a"], alpha=ALPHA)
    register_adapter("rots", d)
    _corrupt(d)
    pool = AdapterPool(setup.base, num_slots=2, rank=R, targets=TARGETS)
    with pytest.raises(AdapterError, match="corrupt"):
        pool.acquire("rots")
    assert "rots" not in get_catalog()
    assert not pool.resident("rots")
    with pytest.raises(AdapterError, match="unknown adapter"):
        pool.acquire("rots")


# ----------------------------------------------------------------------
# Storage faults during export (durable-writer integration)
# ----------------------------------------------------------------------

@pytest.fixture()
def _clean_io():
    durable_io.reset_for_tests()
    yield
    durable_io.reset_for_tests()


def test_save_adapter_torn_write_quarantines_and_reexport_serves(
        setup, tmp_path, _clean_io):
    """A torn write mid-export leaves NOTHING at the target path and no
    stray staging dir — the partial bytes are quarantined for forensics —
    and a re-export after the fault clears loads rows byte-identical to
    an unfaulted export of the same tree."""
    d = str(tmp_path / "ad-t")
    with FaultyIO.from_spec("*.bin:torn"):
        with pytest.raises(OSError):
            save_adapter(d, setup.trees["ad-a"], alpha=ALPHA)
    assert not os.path.exists(d)
    assert not [n for n in os.listdir(tmp_path) if ".tmp-" in n]
    qdir = os.path.join(str(tmp_path), "_quarantine")
    assert os.path.isdir(qdir) and os.listdir(qdir)
    assert durable_io.is_degraded("adapter")

    save_adapter(d, setup.trees["ad-a"], alpha=ALPHA)  # fault cleared
    assert not durable_io.is_degraded("adapter")       # success heals
    register_adapter("ad-t", d)
    register_adapter("ad-a", setup.dirs["ad-a"])
    pool = AdapterPool(setup.base, num_slots=2, rank=R, targets=TARGETS)
    row_t, _ = pool.acquire("ad-t")
    row_a, _ = pool.acquire("ad-a")
    jax.tree_util.tree_map(np.testing.assert_array_equal,
                           _row(pool, row_t), _row(pool, row_a))


def test_save_adapter_enospc_reclaims_quarantine_then_lands(
        setup, tmp_path, _clean_io):
    """ENOSPC mid-export: the reclaim pass quota-evicts the quarantined
    wreckage a previous failed save left behind, then the free retry
    lands the export whole (digest-verified at registration)."""
    with FaultyIO.from_spec("*.bin:torn"):
        with pytest.raises(OSError):
            save_adapter(str(tmp_path / "ad-bad"), setup.trees["ad-a"],
                         alpha=ALPHA)
    qdir = tmp_path / "_quarantine"
    assert list(qdir.iterdir())

    d = str(tmp_path / "ad-ok")
    with FaultyIO.from_spec("*.bin:ENOSPC:1"):
        save_adapter(d, setup.trees["ad-a"], alpha=ALPHA)
    assert not qdir.exists() or not list(qdir.iterdir())
    led = durable_io.disk_ledger()["adapter"]
    assert led["reclaims"] == 1 and led["reclaimed_bytes"] > 0
    register_adapter("ad-ok", d)  # digest verification: export is whole


# ----------------------------------------------------------------------
# Pool: plan / LRU / refcounts / compatibility
# ----------------------------------------------------------------------

def test_pool_bytes_match_planner_and_memory_plan(setup):
    pool = AdapterPool(setup.base, num_slots=3, rank=R, targets=TARGETS)
    want = plan_pool_bytes(CFG, TARGETS, R, 3)
    assert pool.nbytes == want
    assert memory_plan.adapter_pool_bytes(CFG, 3, R, TARGETS) == want
    assert memory_plan.adapter_pool_bytes(CFG, 0) == 0
    with pytest.raises(ValueError, match="unknown adapter target"):
        memory_plan.adapter_pool_bytes(CFG, 2, R, ("bogus",))


def test_engine_memledger_owner_matches_plan(setup):
    """The measured lora_adapters owner equals the paper plan, byte for
    byte (the kv_block_pool cross-check pattern)."""
    eng = InferenceEngine(CFG, setup.base,
                          _ec(adapter_slots=3, adapter_rank=R))
    snap = eng.memledger.snapshot()
    measured = snap["owners"]["lora_adapters"]["bytes"]
    assert measured == eng.executor.adapter_pool.nbytes
    assert measured == memory_plan.adapter_pool_bytes(CFG, 3, R, TARGETS)
    plan = memory_plan.plan_serving(CFG, adapter_slots=3, adapter_rank=R,
                                    adapter_targets=TARGETS)
    assert plan["owners"]["lora_adapters"] == measured


def test_pool_load_evict_reload_byte_equality(setup, tmp_path):
    d3 = str(tmp_path / "ad-c")
    save_adapter(d3, _lora_params(3), alpha=ALPHA)
    for name, d in list(setup.dirs.items()) + [("ad-c", d3)]:
        register_adapter(name, d)
    pool = AdapterPool(setup.base, num_slots=2, rank=R, targets=TARGETS)
    m0 = (adapters_mod.loads_total.value, adapters_mod.evictions_total.value,
          adapters_mod.pool_hits_total.value,
          adapters_mod.pool_misses_total.value)

    row_a, loaded = pool.acquire("ad-a")
    assert (row_a, loaded) == (1, True)
    snap_a = _row(pool, row_a)
    assert pool.acquire("ad-a") == (1, False)  # hit, refcount 2
    pool.release(row_a), pool.release(row_a)
    row_b, loaded = pool.acquire("ad-b")
    assert (row_b, loaded) == (2, True)
    pool.release(row_b)
    # Pool full of unpinned rows: ad-c evicts the LRU (ad-a).
    row_c, loaded = pool.acquire("ad-c")
    assert loaded and row_c == 1
    assert not pool.resident("ad-a") and pool.resident("ad-c")
    pool.release(row_c)
    # Re-load after eviction: the scattered rows are byte-identical to
    # the first load (the digest-verified store round-trips exactly).
    row_a2, loaded = pool.acquire("ad-a")
    assert loaded
    jax.tree_util.tree_map(np.testing.assert_array_equal,
                           snap_a, _row(pool, row_a2))
    assert pool.loaded_names() == ["ad-a", "ad-c"]

    d_loads, d_evict, d_hits, d_miss = (
        adapters_mod.loads_total.value - m0[0],
        adapters_mod.evictions_total.value - m0[1],
        adapters_mod.pool_hits_total.value - m0[2],
        adapters_mod.pool_misses_total.value - m0[3])
    assert (d_loads, d_evict, d_hits, d_miss) == (4, 2, 1, 4)
    assert adapters_mod.pool_slots_gauge.value == 2
    assert adapters_mod.pool_bytes_gauge.value == pool.nbytes


def test_pool_full_of_pinned_rows_defers(setup):
    register_adapter("ad-a", setup.dirs["ad-a"])
    register_adapter("ad-b", setup.dirs["ad-b"])
    pool = AdapterPool(setup.base, num_slots=1, rank=R, targets=TARGETS)
    row, _ = pool.acquire("ad-a")
    # The only row is pinned: the caller must defer, not evict or raise.
    assert pool.acquire("ad-b") == (-1, False)
    pool.release(row)
    row_b, loaded = pool.acquire("ad-b")
    assert loaded and row_b == row
    assert not pool.resident("ad-a")


def test_pool_rejects_incompatible_adapters(setup):
    register_adapter("ad-a", setup.dirs["ad-a"])
    # Rank above the pool ceiling: refused AND unregistered (404 next).
    pool = AdapterPool(setup.base, num_slots=2, rank=R - 2, targets=TARGETS)
    with pytest.raises(AdapterError, match="exceeds the pool rank"):
        pool.acquire("ad-a")
    assert "ad-a" not in get_catalog()
    # Adapter trained on modules the pool does not cover.
    register_adapter("ad-b", setup.dirs["ad-b"])
    narrow = AdapterPool(setup.base, num_slots=2, rank=R,
                         targets=("q_proj",))
    with pytest.raises(AdapterError, match="outside this pool"):
        narrow.acquire("ad-b")


def test_gateway_adapter_map_parsing():
    from dlti_tpu.serving.gateway import parse_adapter_map

    assert parse_adapter_map("acme:ad-a, beta:ad-b") == {
        "acme": "ad-a", "beta": "ad-b"}
    assert parse_adapter_map("") == {}


# ----------------------------------------------------------------------
# Tier-1 equivalence: shared-base batched adapters == merged engines
# ----------------------------------------------------------------------

def _check_equivalence(setup, shared_base, merged, quant, logprob_atol):
    """One shared-base engine serving a heterogeneous batch vs a
    merged-weights engine per adapter (+ an adapter-free engine for base
    rows): token streams must match exactly, greedy and seeded."""
    for name, d in setup.dirs.items():
        register_adapter(name, d)
    ec_shared = _ec(adapter_slots=2, adapter_rank=R, quantization=quant)
    shared = InferenceEngine(CFG, shared_base, ec_shared)
    refs = {
        "": InferenceEngine(CFG, shared_base, _ec(quantization=quant)),
        "ad-a": InferenceEngine(CFG, merged["ad-a"],
                                _ec(quantization=quant)),
        "ad-b": InferenceEngine(CFG, merged["ad-b"],
                                _ec(quantization=quant)),
    }
    assign = [(PROMPTS[0], "ad-a"), (PROMPTS[1], "ad-b"),
              (PROMPTS[2], ""), (PROMPTS[3], "ad-a")]
    for sp in (GREEDY, SEEDED):
        reqs = [shared.submit(p, sp, adapter=name) for p, name in assign]
        shared.step()
        # The heterogeneous batch is real: both adapters resident, several
        # rows in flight in the SAME engine at once.
        assert shared.executor.adapter_pool.loaded_names() == ["ad-a", "ad-b"]
        assert shared.num_active >= 2
        got = _drain(shared, reqs)
        for (prompt, name), g in zip(assign, got):
            want = refs[name].generate([prompt], sp)[0]
            assert g.output_token_ids == want.output_token_ids, \
                (name, "seeded" if sp.seed else "greedy")
            np.testing.assert_allclose(g.output_logprobs,
                                       want.output_logprobs,
                                       atol=logprob_atol)
    # The adapters actually steer generation (zero-delta would pass the
    # equality vacuously).
    base_tok = refs[""].generate([PROMPTS[0]], GREEDY)[0].output_token_ids
    assert refs["ad-a"].generate(
        [PROMPTS[0]], GREEDY)[0].output_token_ids != base_tok
    # Unknown adapter fails THAT request (the HTTP layer 404s before it
    # ever reaches an engine; this is the engine-side backstop) — and the
    # engine keeps serving base requests byte-identically afterwards.
    bad = _drain(shared, [shared.submit(PROMPTS[0], GREEDY,
                                        adapter="ghost")])[0]
    assert bad.finish_reason == "error" and not bad.output_token_ids
    ok = _drain(shared, [shared.submit(PROMPTS[0], GREEDY)])[0]
    assert ok.output_token_ids == base_tok


def test_batched_adapters_match_merged_engines_bf16(setup):
    """bf16-resident base: the shared engine holds genuine bf16 weight
    arrays (production storage; f32 accumulation). The merged oracle
    folds the f32 delta over the SAME bf16-rounded base values without
    re-rounding the sum to bf16 — re-rounding would corrupt the oracle
    with merge-time quantization noise that has nothing to do with the
    batched-gather path under test."""
    shared_base = _bf16(setup.base)
    merged = {name: merge_lora_params(_bf16_round_base(setup.trees[name]),
                                      alpha=int(ALPHA))
              for name in setup.trees}
    _check_equivalence(setup, shared_base, merged, "none",
                       logprob_atol=1e-4)


def test_batched_adapters_match_merged_engines_int8(setup):
    """int8 base: both engines quantize the same (identical-values) base,
    so they share one int8 grid; the adapter delta rides outside it."""
    _check_equivalence(setup, setup.base, setup.merged, "int8",
                       logprob_atol=1e-4)


# ----------------------------------------------------------------------
# Slow integration: hot-register, failover, train→serve
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_hot_register_while_engine_is_mid_decode(setup):
    """A name registered AFTER engine construction, while a request is
    mid-decode, serves from the very next admission — no restart, no
    recompile-induced fault, and the in-flight stream is untouched."""
    eng = InferenceEngine(CFG, setup.base, _ec(adapter_slots=2,
                                               adapter_rank=R))
    long_req = eng.submit(PROMPTS[0], SamplingParams(temperature=0.0,
                                                     max_tokens=32))
    for _ in range(4):
        eng.step()
    assert long_req.finish_reason is None  # genuinely mid-decode
    register_adapter("ad-hot", setup.dirs["ad-a"])
    hot = eng.submit(PROMPTS[1], GREEDY, adapter="ad-hot")
    res = _drain(eng, [long_req, hot])
    assert [r.finish_reason for r in res] == ["length", "length"]
    assert eng.executor.adapter_pool.resident("ad-hot")
    want = InferenceEngine(CFG, setup.merged["ad-a"], _ec()).generate(
        [PROMPTS[1]], GREEDY)[0]
    assert res[1].output_token_ids == want.output_token_ids


@pytest.mark.slow
def test_replica_failover_resubmit_preserves_adapter(setup, devices):
    """A replica fault mid-flight: its requests resubmit on the survivor
    and finish under the SAME adapter — zero client-visible errors,
    greedy streams identical to an unfaulted engine."""
    for name, d in setup.dirs.items():
        register_adapter(name, d)
    ec = _ec(adapter_slots=2, adapter_rank=R)
    rep = ReplicatedEngine(CFG, setup.base, ec, replicas=2, tensor=1,
                           devices=devices[:2], max_retries=2,
                           fault_inject_step="0:3")
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    assign = [(PROMPTS[i % 4], ("ad-a", "ad-b")[i % 2]) for i in range(6)]
    reqs = [rep.submit(p, sp, adapter=name) for p, name in assign]
    while rep.has_work:
        rep.step()
    assert rep.failover["replica_faults"] == 1
    results = [rep.engines[r.replica]._result(r) for r in reqs]
    for (_, name), req, res in zip(assign, reqs, results):
        assert req.adapter == name  # the adapter rode the resubmit
        assert res.finish_reason == "length", res
    single = InferenceEngine(CFG, setup.base, ec)
    for (prompt, name), res in zip(assign, results):
        want = _drain(single, [single.submit(prompt, sp, adapter=name)])[0]
        assert res.output_token_ids == want.output_token_ids, name


@pytest.mark.slow
def test_train_save_register_generate_e2e(tmp_path):
    """The loop the tentpole closes: a LoRA checkpoint the Trainer just
    wrote becomes servable on a running shared-base engine via
    hot-register — and matches the merged-weights export exactly."""
    from dlti_tpu.config import (
        CheckpointConfig, Config, DataConfig, OptimizerConfig,
        ParallelConfig, TrainConfig, ZeROStage,
    )
    from dlti_tpu.data import (
        ByteTokenizer, format_conversation_for_llama2, make_batches,
    )
    from dlti_tpu.training.trainer import Trainer

    cfg = Config(
        model=MODEL_PRESETS["llama_tiny"],
        lora=LoRAConfig(r=R, alpha=int(ALPHA), dropout=0.0),
        optimizer=OptimizerConfig(warmup_steps=2),
        parallel=ParallelConfig(zero_stage=ZeROStage.ZERO2, data=8),
        data=DataConfig(max_seq_len=64, tokenizer="byte"),
        checkpoint=CheckpointConfig(output_dir=str(tmp_path / "ckpt"),
                                    save_steps=4, async_save=False),
        train=TrainConfig(max_steps=8, micro_batch_size=8,
                          grad_accum_steps=2,
                          metrics_csv=str(tmp_path / "metrics.csv")),
    )
    texts = [format_conversation_for_llama2(
        {"question": f"What is {i}?", "answer": f"It is {i}."})["text"]
        for i in range(200)]
    ds = make_batches(texts, ByteTokenizer(), seq_len=64,
                      micro_batch_size=8, grad_accum_steps=2,
                      shard_by_host=False)
    state, _ = Trainer(cfg).train(dataset=ds)
    params = jax.tree_util.tree_map(np.asarray, state.params)

    # Engine FIRST (serving the base), register AFTER: no restart.
    base = merge_lora_params(params, scaling=0.0)
    eng = InferenceEngine(CFG, base, _ec(adapter_slots=2, adapter_rank=R))
    assert _drain(eng, [eng.submit(PROMPTS[0], GREEDY)])[0].output_token_ids

    save_adapter(str(tmp_path / "trained"), params, alpha=ALPHA)
    register_adapter("trained", str(tmp_path / "trained"))
    got = _drain(eng, [eng.submit(PROMPTS[0], GREEDY,
                                  adapter="trained")])[0]
    want = InferenceEngine(CFG, merge_lora_params(params, alpha=int(ALPHA)),
                           _ec()).generate([PROMPTS[0]], GREEDY)[0]
    assert got.output_token_ids == want.output_token_ids
    assert got.finish_reason == "length"
