"""Host spans on the profiler's clock, and the counters beside them.

* Off path: with the tracer disabled an engine step, a server loop and a
  trainer step make no ``TraceAnnotation`` and no span reads the thread's
  CPU clock (``thread_time_ns``; the stepper's always-on account reads
  ``thread_time`` a fixed few times a step: tests/test_stepper_account.py),
  and importing the tracer imports no jax.
* On path: the engine's, server's and trainer's spans nest as
  ``COMPONENTS.md`` ("Host spans") says, on the speculative path too, and a
  profiler capture holds them in its host plane around the device's work.
* ``decode_context_tokens`` against a hand count; the compile listener
  against one compilation and one cache fetch of a toy function.
"""

import glob
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import (
    MODEL_PRESETS, CheckpointConfig, Config, DataConfig, LoRAConfig,
    TrainConfig,
)
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.ops.pallas.paged_attention import tile_tokens
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from dlti_tpu.serving.server import AsyncEngine
from dlti_tpu.telemetry import configure_tracer, get_tracer, startup
from dlti_tpu.telemetry import tracer as tracer_mod

CFG = MODEL_PRESETS["llama_tiny"]
GREEDY = SamplingParams(max_tokens=4, temperature=0.0)
CYCLIC = [6, 6, 7, 7, 6, 6, 7, 7]  # generation loops: n-gram drafts hit

# child -> the span it lies directly inside, on the same thread
PARENTS = {
    "engine/decode_plan": "engine/decode_prep",
    "engine/decode_assemble": "engine/decode_prep",
    "engine/decode_stage": "engine/decode_prep",
    "engine/prefill_group": "engine/admit",
    "engine/prefill_launch": "engine/prefill_group",
    "engine/prefill_wait": "engine/prefill_group",
}


@pytest.fixture(scope="module")
def tiny_params():
    return LlamaForCausalLM(CFG, None).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture()
def tracer():
    """The process-global tracer, enabled and empty; disabled afterwards."""
    tr = configure_tracer(enabled=True, capacity=8192)
    tr.clear()
    yield tr
    configure_tracer(enabled=False)
    tr.clear()


def _engine(params, **over):
    base = dict(max_seqs=4, block_size=8, num_blocks=64, max_model_len=128,
                cache_dtype="float32", eos_token_id=-1)
    base.update(over)
    return InferenceEngine(CFG, params, EngineConfig(**base))


def _train_config(**train):
    return Config(
        model=CFG, lora=LoRAConfig(enabled=False),
        data=DataConfig(max_seq_len=16),
        checkpoint=CheckpointConfig(save_strategy="no"),
        train=TrainConfig(num_epochs=1, micro_batch_size=2,
                          grad_accum_steps=1, max_steps=3, logging_steps=1,
                          **train))


def _train_batches(n=4):
    rng = np.random.default_rng(0)
    ids = [rng.integers(1, 500, (1, 2, 16), dtype=np.int32) for _ in range(n)]
    return [{"input_ids": a, "labels": a} for a in ids]


class _Counted:
    """Stands in for a callable and counts its calls."""

    def __init__(self, real):
        self.real, self.calls = real, 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.real(*args, **kwargs)


@pytest.fixture()
def counted(monkeypatch):
    """``jax.profiler.TraceAnnotation`` and ``time.thread_time_ns`` replaced
    by counting stand-ins (the tracer looks both up when a span begins)."""
    annotation = _Counted(jax.profiler.TraceAnnotation)
    cpu_clock = _Counted(time.thread_time_ns)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    monkeypatch.setattr(time, "thread_time_ns", cpu_clock)
    monkeypatch.setattr(get_tracer(), "_annotate", None)
    # Spans an earlier test file of this worker left in the process-global
    # ring are not this test's: what it asserts is what its own run records.
    get_tracer().clear()
    return annotation, cpu_clock


# -- the off path -----------------------------------------------------------

def test_importing_the_tracer_and_the_startup_series_imports_no_jax():
    code = ("import sys\n"
            "import dlti_tpu.telemetry.tracer, dlti_tpu.telemetry.startup\n"
            "bad = sorted(m for m in sys.modules if m.split('.')[0] == 'jax')\n"
            "assert not bad, bad[:5]\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_disabled_tracer_costs_an_engine_step_no_annotation_and_no_cpu_clock(
        tiny_params, counted):
    annotation, cpu_clock = counted
    assert not get_tracer().enabled
    eng = _engine(tiny_params)
    results = eng.generate([[5, 6, 7], [9, 10]], GREEDY)  # prefill + decode
    assert all(r.finish_reason == "length" for r in results)
    assert eng.stats["decode_steps"] >= 3
    assert (annotation.calls, cpu_clock.calls) == (0, 0)
    assert len(get_tracer()) == 0
    # the stand-ins do see an enabled tracer: the zeros above mean something
    # (and of an enabled tracer's spans only one whose site asks reads the
    # CPU clock, at its two ends: no span of the engine's does)
    configure_tracer(enabled=True)
    try:
        eng.generate([[5, 6, 7]], GREEDY)
        asked = annotation.calls
        with get_tracer().span("server/step", cat="server", cpu=True):
            pass
    finally:
        configure_tracer(enabled=False)
        get_tracer().clear()
    assert asked > 0 and annotation.calls == asked + 1
    assert cpu_clock.calls == 2


def test_disabled_tracer_costs_the_server_loop_no_annotation_and_no_cpu_clock(
        tiny_params, counted):
    annotation, cpu_clock = counted
    aeng = AsyncEngine(_engine(tiny_params))
    try:
        _, q = aeng.submit([3, 1, 4, 1, 5], GREEDY)
        kinds = []
        while not kinds or kinds[-1] not in ("done", "error"):
            kinds.append(q.get(timeout=120)[0])
    finally:
        aeng.shutdown()
    assert kinds[-1] == "done"
    assert (annotation.calls, cpu_clock.calls) == (0, 0)


def test_disabled_tracer_costs_a_trainer_step_no_annotation_and_no_cpu_clock(
        counted):
    from dlti_tpu.training import Trainer

    annotation, cpu_clock = counted
    Trainer(_train_config()).train(batches_per_epoch=_train_batches())
    assert (annotation.calls, cpu_clock.calls) == (0, 0)
    assert len(get_tracer()) == 0


# -- the on path ------------------------------------------------------------

def _inside(kid, parent):
    return (kid["tid"] == parent["tid"] and parent["ts"] <= kid["ts"]
            and kid["ts"] + kid["dur"] <= parent["ts"] + parent["dur"])


def _assert_nested(events, parents):
    spans = [e for e in events if e.get("ph") == "X"]
    for child, parent in parents.items():
        kids = [e for e in spans if e["name"] == child]
        assert kids, child
        for kid in kids:
            mine = [e for e in spans if e["name"] == parent
                    and _inside(kid, e)]
            assert len(mine) == 1, (child, parent)
            # nothing else lies between the two
            between = [e for e in spans if e is not kid and e is not mine[0]
                       and _inside(kid, e) and _inside(e, mine[0])
                       and (e["ts"], e["dur"]) != (kid["ts"], kid["dur"])]
            assert not between, (child, [e["name"] for e in between])
            # an engine span's site does not ask for the thread's CPU clock
            assert "cpu_us" not in kid.get("args", {})


@pytest.mark.parametrize("speculative", ["none", "ngram"])
def test_engine_spans_nest_as_documented(tiny_params, tracer, speculative):
    eng = _engine(tiny_params, speculative=speculative)
    sp = SamplingParams(max_tokens=12, temperature=0.0)
    eng.generate([CYCLIC, [1, 2, 3, 4, 5]], sp)
    if speculative == "ngram":
        assert eng.stats["spec_proposed"] > 0  # the spec program did run
    events = tracer.events()
    _assert_nested(events, PARENTS)
    group = next(e for e in events if e["name"] == "engine/prefill_group")
    assert group["args"]["rows"] == 2 and group["args"]["bucket"] >= 8
    assert group["args"]["prompt_tokens"] == len(CYCLIC) + 5
    # every round: prep, launch, wait, emit. A speculative round is fetched
    # in the step that launched it (after admission); a plain round a step
    # later, after the round behind it has been prepared and launched.
    order = [e["name"] for e in sorted(events, key=lambda e: e["ts"])
             if e["name"] in ("engine/decode_prep", "engine/decode_launch",
                              "engine/decode_wait", "engine/decode_emit")]
    round_ = ["engine/decode_prep", "engine/decode_launch",
              "engine/decode_wait", "engine/decode_emit"]
    assert order[:6] == (round_[:2] * 2 + round_[2:]
                         if speculative == "none" else round_ + round_[:2])
    # (a prep that finds nothing to plan behind the round in flight, every
    # row of which ends by length, launches nothing)
    n = order.count("engine/decode_launch")
    assert [order.count(name) for name in round_[2:]] == [n, n]
    assert order.count("engine/decode_prep") >= n


def test_server_spans_cover_the_stepper_loop(tiny_params, tracer):
    aeng = AsyncEngine(_engine(tiny_params))
    try:
        _, q = aeng.submit([3, 1, 4, 1, 5], GREEDY)
        while q.get(timeout=120)[0] not in ("done", "error"):
            pass
    finally:
        aeng.shutdown()
    events = [e for e in tracer.events() if e.get("ph") == "X"]
    names = {e["name"] for e in events}
    assert {"server/step", "server/lock_wait", "server/drain_events",
            "server/wait_work"} <= names
    steps = [e for e in events if e["name"] == "server/step"]
    stepper = {e["tid"] for e in steps}
    assert len(stepper) == 1
    # the step's span alone keeps the CPU its thread used (stepper_cpu_share)
    for e in events:
        if e["name"] == "server/step":
            assert 0 <= e["args"]["cpu_us"] <= e["dur"] + 1e3
        else:
            assert "cpu_us" not in e.get("args", {}), e["name"]
    for e in events:
        if e["name"].startswith("engine/"):
            assert e["tid"] in stepper
            if e["name"] in ("engine/admit", "engine/decode_prep",
                             "engine/decode_wait"):
                assert sum(_inside(e, s) for s in steps) == 1, e["name"]
    # the loop's own spans follow one another, never overlap
    loop = sorted((e for e in events if e["name"].startswith("server/")),
                  key=lambda e: e["ts"])
    for a, b in zip(loop, loop[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0, (a["name"], b["name"])


def _host_annotations(profile_dir):
    """{name: [(start_ns, end_ns, thread line, {stat: value})]} of the
    capture's host planes, and whether it holds Python function events."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{profile_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    out, python_events = {}, 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("server/", "engine/", "train/")):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         line.name, dict(ev.stats)))
                elif ev.name.startswith("$"):
                    python_events += 1
    return out, python_events


def test_capture_holds_the_spans_on_the_profilers_clock(
        tmp_path, tiny_params, time_limit):
    """``start_capture`` .. ``stop_capture`` on a disabled tracer: the ring
    runs for the capture only, the xplane's host plane holds the engine's
    spans nested as in the ring, on one thread, and no Python function
    events (the profiler's Python tracer is off)."""
    tr = get_tracer()
    assert not tr.enabled
    eng = _engine(tiny_params)
    eng.generate([[5, 6, 7]], GREEDY)  # compile outside the capture
    with time_limit(180):
        tr.start_capture(str(tmp_path))
        try:
            assert tr.enabled
            eng.generate([[5, 6, 7], [9, 10]], GREEDY)
        finally:
            tr.stop_capture()
        got, python_events = _host_annotations(str(tmp_path))
    try:
        assert not tr.enabled
        assert tr.span("engine/admit") is tracer_mod._NULL_SPAN
        ring = [e["name"] for e in tr.events()]
        assert ring[0] == "profiler/start" and ring[-1] == "profiler/stop"
    finally:
        tr.clear()
    assert python_events == 0
    assert set(PARENTS) | set(PARENTS.values()) <= set(got)
    assert len({a[2] for spans in got.values() for a in spans}) == 1
    for child, parent in PARENTS.items():
        for kid in got[child]:
            assert sum(p[0] <= kid[0] and kid[1] <= p[1]
                       for p in got[parent]) == 1, child
    assert got["engine/prefill_group"][0][3]["rows"] == 2


def test_profile_window_carries_the_train_spans_without_a_trace_dir(
        tmp_path, time_limit):
    """``--profile-*`` with no ``--trace-dir`` (the benchmark's traced
    training run): the window enables the tracer for itself, the capture
    holds the trainer's step phases, and the ring is off again after it."""
    from dlti_tpu.training import Trainer

    cfg = _train_config(profile_dir=str(tmp_path / "profile"),
                        profile_start_step=1, profile_num_steps=1)
    with time_limit(300):
        Trainer(cfg).train(batches_per_epoch=_train_batches())
        got, _ = _host_annotations(str(tmp_path / "profile"))
    assert not get_tracer().enabled
    get_tracer().clear()
    assert {"train/step_dispatch", "train/device_sync",
            "train/batch_fetch"} <= set(got)
    dispatch, sync = got["train/step_dispatch"][0], got["train/device_sync"][0]
    assert dispatch[1] <= sync[0]  # the sync follows the dispatch
    assert dispatch[2] == sync[2]  # on the trainer's loop thread


# -- the counters -----------------------------------------------------------

@pytest.mark.parametrize("speculative", ["none", "ngram"])
def test_decode_context_tokens_against_a_hand_count(tiny_params, speculative):
    """Three requests, four tokens each: the first comes from prefill, the
    other three from decode steps that attend over L, L+1 and L+2 cached
    tokens (L the prompt's length), whenever each was admitted. Plain
    decode: sum over requests of 3L + 3. The counter adds a round's context
    when it is dispatched, so a spec round (whose steps each emit one to
    k+1 tokens) is counted at the context it began with."""
    prompts = [[5, 6, 7], [9, 10], [11, 12, 13, 14]]
    eng = _engine(tiny_params, speculative=speculative)
    results = eng.generate(prompts, GREEDY)
    assert [len(r.output_token_ids) for r in results] == [4, 4, 4]
    got = eng.stats["decode_context_tokens"]
    if speculative == "none":
        assert got == sum(3 * len(p) + 3 for p in prompts) == 36
        assert eng.stats["decode_steps"] == 3
        assert got / eng.stats["decode_steps"] == 12.0  # mean context a step
        # the kernel's tiles over the same steps: every context (and its new
        # token) fits one tile of the executor's size
        tile = eng.executor.decode_tile_tokens
        assert tile >= 16 and tile == tile_tokens(
            eng.cfg.block_size, eng.cfg.max_blocks_per_seq)
        assert eng.stats["decode_kernel_tile_tokens"] == 3 * 3 * tile
    else:
        # every dispatched round has every live slot's context at its start
        assert got >= sum(len(p) for p in prompts)
        assert got % 1 == 0 and eng.stats["decode_steps"] >= 1


def test_compile_listener_counts_one_compilation_and_one_cache_hit(
        tmp_path, monkeypatch):
    from jax._src import compilation_cache

    def counts():
        return (startup.compilations_total.value,
                startup.compile_cache_hits_total.value)

    def seconds():
        return (startup.compile_seconds_total.value,
                startup.compile_cache_fetch_seconds_total.value)

    startup.install_compile_listener()
    startup.install_compile_listener()  # once a process, however often asked
    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        def toy(x):
            return jnp.tanh(x * 3.25 + 0.125).sum()

        x = jnp.arange(24.0).reshape(4, 6)  # made before the counts are read
        jax.block_until_ready(x)
        n0, s0 = counts(), seconds()
        jax.jit(toy)(x).block_until_ready()
        n1, s1 = counts(), seconds()
        assert (n1[0] - n0[0], n1[1] - n0[1]) == (1, 0)
        assert s1[0] > s0[0] and s1[1] == s0[1]
        jax.clear_caches()  # the next call finds the program on disk only
        jax.jit(toy)(x).block_until_ready()
        n2, s2 = counts(), seconds()
        assert (n2[0] - n1[0], n2[1] - n1[1]) == (0, 1)
        assert s2[0] == s1[0] and s2[1] > s1[1]
        jax.jit(toy)(x).block_until_ready()  # a step: no event at all
        assert counts() == n2
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_startup_gauges_read_the_process_age_and_reach_the_registry(
        tiny_params):
    from dlti_tpu.serving.server import build_registry

    age = startup.process_age_s()
    assert 0.0 < age < 24 * 3600
    time.sleep(0.02)
    assert startup.process_age_s() > age
    for phase in startup.STARTUP_PHASES:
        startup.mark_startup(phase)
    values = [startup.startup_gauges[p].value for p in startup.STARTUP_PHASES]
    assert values == sorted(values) and values[0] > age

    class _FakeAsync:  # build_registry only reads .engine
        engine = _engine(tiny_params)

    text = build_registry(_FakeAsync()).render_prometheus()
    for phase in startup.STARTUP_PHASES:
        assert f"# TYPE dlti_startup_{phase}_seconds gauge" in text
    assert "# TYPE dlti_decode_context_tokens counter" in text
