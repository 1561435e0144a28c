"""Device idle gaps put down to program spans: the arithmetic on a trace made
by hand (every number below can be checked on paper against
``hand_trace_spans.json``), the readers on what a run gives them, and on a
cut of a traced run on the v5e where one was recorded."""

import copy
import json
import os

import pytest

from bench_paths import BENCH, ROOT

import attribute_idle
import spec as spec_lib

FIXTURES = os.path.join(BENCH, "fixtures")
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SERVE_CELLS = ["serve.mistral_7b.chat", "serve.qwen2_7b.batch"]
TRAIN_CELLS = ["train.mistral_7b.lora_sft"]
NEW_SERVE = ["idle_attributed_share.serve", "idle_ms_per_step.decode_prep",
             "idle_ms_per_step.decode_wait", "idle_ms_per_step.decode_emit",
             "idle_ms_per_step.admit", "idle_ms_per_step.server",
             "stepper_cpu_share", "paged_attn_device_ms_per_step",
             "paged_attn_hbm_pct", "setup_ready_s", "setup_program_load_s"]
NEW_TRAIN = ["idle_attributed_share.train", "flash_attn_device_ms_per_step"]
NEW = NEW_SERVE + NEW_TRAIN


def load(name):
    return json.load(open(os.path.join(FIXTURES, name)))


@pytest.fixture(scope="module")
def hand():
    return attribute_idle.attribute(load("hand_trace_spans.json"))


def test_innermost_span_wins_and_parents_keep_their_self_time():
    got = attribute_idle.innermost_segments(
        [(0, 100, "a"), (10, 30, "b"), (40, 50, "c"), (45, 60, "d")])
    # "d" ends after its parent "c": cut to it (clock granularity)
    assert got == [(0, 10, ("a",)), (10, 30, ("a", "b")), (30, 40, ("a",)),
                   (40, 45, ("a", "c")), (45, 50, ("a", "c", "d")),
                   (50, 100, ("a",))]


def test_gaps_are_those_between_merged_operation_intervals():
    ops = [["x", 0, 10], ["y", 5, 10], ["z", 20, 5], ["zero", 17, 0]]
    assert attribute_idle.idle_gaps(ops) == [(15, 20)]


def test_a_gap_across_spans_is_split_over_the_innermost_of_each_part(hand):
    spans = hand["spans"]
    ns = {k: round(v * 1e9) for k, v in spans["idle_by_span_s"].items()}
    # gap [1300,1500): wait 10, emit 40, step self 10, lock 10, drain 20,
    # prep 70, launch 20, and 20 that no span covers; gap [1800,2000): wait 5,
    # emit 45, step self 20, lock 10, drain 10, admit self 10, group self 20,
    # prefill launch 70, prefill wait 10
    assert ns == {
        "engine/decode_emit": 85, "engine/decode_prep": 70,
        "engine/prefill_launch": 70, "server/step": 30,
        "server/drain_events": 30, "server/lock_wait": 20,
        "engine/prefill_group": 20, "engine/decode_launch": 20,
        "engine/decode_wait": 15, "engine/admit": 10,
        "engine/prefill_wait": 10}
    assert hand["idle_s"] == pytest.approx(400e-9)
    assert hand["gaps"] == 2
    assert spans["stepper_mark"] == "server/step"


def test_what_no_span_of_the_stepper_covers_is_unattributed(hand):
    """[1360,1370) and [1400,1410) lie between the stepper's spans. Another
    thread's spans cover both (``engine/tier_restore``, and a handler's own
    ``server/lock_wait``): they must not count."""
    spans = hand["spans"]
    assert spans["unattributed_s"] == pytest.approx(20e-9)
    assert spans["attributed_share"] == pytest.approx(380 / 400)
    assert "engine/tier_restore" not in spans["idle_by_span_s"]


def test_groups_roll_nested_spans_up_and_everything_adds_up(hand):
    spans = hand["spans"]
    ns = {k: round(v * 1e9) for k, v in spans["groups_s"].items()}
    # decode_wait: wait 15 + launch 20; admit: its self time and everything
    # nested in it; server: lock, drain and server/step's self time
    assert ns == {"decode_prep": 70, "decode_wait": 35, "decode_emit": 85,
                  "admit": 110, "server": 80}
    assert spans["other_spans_s"] == 0
    total = (sum(spans["groups_s"].values()) + spans["other_spans_s"]
             + spans["unattributed_s"])
    assert total == pytest.approx(hand["idle_s"])


def test_division_per_step_is_by_the_decode_executions(hand):
    spans = hand["spans"]
    assert hand["executions"] == {"train_step": 0, "prefill": 1, "decode": 2}
    assert spans["steps"] == 2 and spans["step_program"] == "decode"
    assert spans["idle_ms_per_step"] == pytest.approx(400e-6 / 2)
    assert spans["groups_ms_per_step"]["admit"] == pytest.approx(110e-6 / 2)
    per_step = (sum(spans["groups_ms_per_step"].values())
                + 1e3 * (spans["other_spans_s"] + spans["unattributed_s"]) / 2)
    assert per_step == pytest.approx(spans["idle_ms_per_step"])


def test_longest_gaps_are_named_by_the_span_that_covers_most(hand):
    first, second = hand["spans"]["longest_gaps"]
    assert (first["span"], second["span"]) == ("engine/decode_prep",
                                               "engine/prefill_launch")
    assert first["length_s"] == pytest.approx(200e-9)
    assert first["span_share"] == pytest.approx(70 / 200)


def test_kernel_sums_by_name_per_execution_of_their_program(hand):
    paged = hand["kernels"]["paged_attention"]
    # two calls of the kernel; the reshape that names it as its operand is
    # not one
    assert paged["events"] == 2 and paged["steps"] == 2
    assert paged["ms_per_step"] == pytest.approx(100e-6)
    assert paged["events_per_step"] == 1
    assert "flash_attention" not in hand["kernels"]  # no such kernel ran


def test_the_device_finishes_a_program_inside_the_hosts_wait_for_it(hand):
    check = hand["spans"]["clock_check"]
    assert check == {"judged": 2, "inside_share": 1.0,
                     "inside_any_wait_share": 1.0,
                     "launch_inside_execution_share": 0.0}


@pytest.mark.parametrize("shift_ns", [100, -50], ids=["ahead", "behind"])
def test_a_host_clock_that_is_off_is_caught(shift_ns):
    trace = copy.deepcopy(load("hand_trace_spans.json"))
    for line in trace["planes"][1]["lines"]:
        for ev in line["events"]:
            ev[1] += shift_ns
    check = attribute_idle.attribute(trace)["spans"]["clock_check"]
    if shift_ns > 0:  # a launch seems to begin while its program runs
        assert check["launch_inside_execution_share"] == 1.0
    else:  # a program seems to end after the wait for it returned
        assert check["inside_share"] == 0.0


def test_a_program_that_ends_in_the_wait_for_a_prefill_behind_it():
    """The device runs a prefill launched during a decode after that decode,
    so the host's wait for the prefill's results sees the decode end."""
    trace = copy.deepcopy(load("hand_trace_spans.json"))
    stepper = trace["planes"][1]["lines"][1]["events"]
    trace["planes"][0]["lines"][1]["events"].append(["jit_decode(1)", 1995, 30])
    stepper.append(["engine/decode_wait", 2125, 10])  # the judged span's end
    check = attribute_idle.attribute(trace)["spans"]["clock_check"]
    assert check["judged"] == 3
    assert check["inside_share"] == pytest.approx(2 / 3)
    assert check["inside_any_wait_share"] == 1.0


def test_training_is_attributed_to_the_loop_threads_train_spans():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = f32[] fusion()", 100, 400],
                ["%dlti_flash_attention_fwd.2 = bf16[] custom-call()", 500, 50],
                ["%dlti_flash_attention_bwd_dq.3 = bf16[] custom-call()", 550, 70],
                ["%dlti_flash_attention_bwd_dkv.4 = bf16[] custom-call()", 620, 80],
                ["%fusion.1 = f32[] fusion()", 800, 600]]},
            {"name": "XLA Modules", "events": [
                ["jit_train_step(7)", 100, 600], ["jit_train_step(7)", 800, 600]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "prefetch", "events": [["train/batch_fetch", 650, 200]]},
            {"name": "main", "events": [
                ["train/step_dispatch", 50, 30], ["train/device_sync", 80, 625],
                ["train/batch_fetch", 710, 40], ["train/host_to_device", 750, 20],
                ["train/step_dispatch", 770, 25], ["train/device_sync", 800, 700]]}]}]}
    got = attribute_idle.attribute(trace)
    spans = got["spans"]
    assert spans["stepper_mark"] == "train/step_dispatch"
    assert spans["steps"] == 2 and spans["step_program"] == "train_step"
    # the gap [700,800): sync 5, then 5 uncovered, fetch 40, upload 20,
    # dispatch 25, sync again 0 - and the prefetcher's span counts nothing
    ns = {k: round(v * 1e9) for k, v in spans["idle_by_span_s"].items()}
    assert ns == {"train/device_sync": 5, "train/batch_fetch": 40,
                  "train/host_to_device": 20, "train/step_dispatch": 25}
    assert spans["attributed_share"] == pytest.approx(0.9)
    assert spans["clock_check"]["inside_share"] == 1.0
    flash = got["kernels"]["flash_attention"]
    assert flash["events"] == 3 and flash["per"] == "train_step"
    assert flash["ms_per_step"] == pytest.approx(200e-6 / 2)


def test_without_a_host_plane_only_the_device_side_is_read():
    trace = load("hand_trace_spans.json")
    trace["planes"] = trace["planes"][:1]
    got = attribute_idle.attribute(trace)
    assert got["spans"] is None
    assert got["kernels"]["paged_attention"]["events"] == 2
    assert attribute_idle.attribute({"planes": []}) is None


def _ring():
    def span(name, ts, dur, cpu, tid=7):
        return {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": tid,
                "args": {"cpu_us": cpu}}

    return {"traceEvents": [
        {"ph": "i", "name": "profiler/start", "ts": 1000.0, "tid": 3},
        span("server/step", 900.0, 150.0, 99.0),        # began before
        span("server/step", 1000.0, 400.0, 100.0),
        span("engine/decode_sync", 1100.0, 290.0, 20.0),
        span("engine/decode_wait", 1100.0, 200.0, 10.0),
        span("server/lock_wait", 1400.0, 50.0, 5.0),
        span("server/step", 1500.0, 100.0, 50.0),
        span("engine/admit", 1500.0, 90.0, 45.0, tid=9),  # not the stepper
        span("request/decode", 1000.0, 500.0, 0.0, tid=7),
        span("server/step", 1950.0, 100.0, 50.0),       # ends after
        {"ph": "i", "name": "profiler/stop", "ts": 2000.0, "tid": 3},
    ]}


def test_stepper_cpu_share_from_the_ring_export():
    got = attribute_idle.ring_cpu(_ring())
    # the two server/step spans that lie inside the window
    assert got["wall_s"] == pytest.approx(500e-6)
    assert got["cpu_s"] == pytest.approx(150e-6)
    assert got["share"] == pytest.approx(0.3)
    assert got["by_span"]["engine/decode_wait"] == {
        "count": 1, "wall_s": pytest.approx(200e-6),
        "cpu_s": pytest.approx(10e-6)}
    assert set(got["by_span"]) == {"server/step", "engine/decode_sync",
                                   "engine/decode_wait", "server/lock_wait"}
    assert attribute_idle.ring_cpu({"traceEvents": []}) is None


def _ctx(tmp_path, result, **more):
    profile_dir = tmp_path / "trace" / "serve_profile"
    profile_dir.mkdir(parents=True)
    (profile_dir / attribute_idle.RESULT_NAME).write_text(json.dumps(result))
    return {"profile_dir": str(profile_dir), **more}


def _counters(ready=None, compile_s=None, fetch_s=None, context=None,
              steps=None):
    out = {"_t": 0.0, "dlti_requests": 4.0}
    for key, value in (("dlti_startup_ready_seconds", ready),
                       ("dlti_compile_seconds_total", compile_s),
                       ("dlti_compile_cache_fetch_seconds_total", fetch_s),
                       ("dlti_decode_context_tokens", context),
                       ("dlti_decode_steps", steps)):
        if value is not None:
            out[key] = value
    return out


QWEN = {"num_hidden_layers": 14, "num_key_value_heads": 4, "head_dim": 128,
        "hidden_size": 3584, "num_attention_heads": 28}


def test_readers_share_the_kept_result_and_read_the_counters(tmp_path, hand):
    result = {**hand, "stepper_cpu": attribute_idle.ring_cpu(_ring())}
    ctx = _ctx(
        tmp_path, result,
        metrics_before=_counters(ready=21.5, fetch_s=9.25, context=1000.0,
                                 steps=10.0),
        metrics_after=_counters(ready=21.5, fetch_s=9.25,
                                context=1000.0 + 600 * 28800.0, steps=610.0),
        config={"model": QWEN}, device={"platform": "tpu",
                                        "kind": "TPU v5 lite"},
        spec={"args": {"--kv-cache-dtype": "bfloat16"}})
    got = {name: spec_lib.load_layer_reader(name)(ctx) for name in NEW}
    assert got["idle_attributed_share.serve"] == pytest.approx(95.0)
    assert got["idle_attributed_share.train"] is None  # a serving trace
    assert got["idle_ms_per_step.decode_prep"] == pytest.approx(35e-6)
    assert got["idle_ms_per_step.decode_wait"] == pytest.approx(17.5e-6)
    assert got["idle_ms_per_step.decode_emit"] == pytest.approx(42.5e-6)
    assert got["idle_ms_per_step.admit"] == pytest.approx(55e-6)
    assert got["idle_ms_per_step.server"] == pytest.approx(40e-6)
    assert got["stepper_cpu_share"] == pytest.approx(30.0)
    assert got["paged_attn_device_ms_per_step"] == pytest.approx(100e-6)
    assert got["flash_attn_device_ms_per_step"] is None
    assert got["setup_ready_s"] == 21.5
    # no compilation was counted yet: the registry renders no such series
    assert got["setup_program_load_s"] == 9.25
    # 28,800 tokens of context a step x 28 KiB a token = 825.8 MB = 1.008 ms
    # at 819 GB/s, against a kernel that takes 100 ns a step in the fixture
    want = 100.0 * (28800 * 28672 / 819e9) / 100e-9
    assert got["paged_attn_hbm_pct"] == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_returns_none(name, tmp_path):
    """What the parent of the PR that brought the readers gives them: no
    trace; a trace without spans or kernel names; ``/metrics`` without the
    start-up series or the context counter; a reduction that failed."""
    read = spec_lib.load_layer_reader(name)
    assert read({"profile_dir": None}) is None
    assert read({}) is None
    parent = load("hand_trace_spans.json")
    parent["planes"] = parent["planes"][:1]
    for line in parent["planes"][0]["lines"]:
        for ev in line["events"]:
            ev[0] = ev[0].replace("dlti_paged_attention_decode", "attn")
    ctx = _ctx(tmp_path, {**attribute_idle.attribute(parent),
                          "stepper_cpu": None},
               metrics_before=_counters(steps=10.0),
               metrics_after=_counters(steps=610.0),
               config={"model": QWEN},
               device={"platform": "tpu", "kind": "TPU v5 lite"},
               spec={"args": {"--kv-cache-dtype": "bfloat16"}})
    assert read(ctx) is None
    failed = tmp_path / "failed"
    failed.mkdir()
    (failed / attribute_idle.RESULT_NAME).write_text('{"error": "x"}')
    assert read({"profile_dir": str(failed)}) is None


def test_new_metrics_are_appended_and_list_their_cells():
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert sorted(names[-len(NEW):]) == sorted(NEW)
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"][-len(NEW):]:
        assert m["workloads"] == (TRAIN_CELLS if m["name"] in NEW_TRAIN
                                  else SERVE_CELLS)
        # every listed cell reports the end-to-end metric it moves
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(
            moved.get("workloads", SERVE_CELLS + TRAIN_CELLS))


def test_a_cpu_capture_goes_through_the_process_and_reads_as_nothing(
        tmp_path, time_limit):
    """The whole path of ``for_run`` on a capture made here: the xplane of
    a CPU run has no TPU plane, so the process writes ``null`` and every
    reader returns None (what a rehearsal sees)."""
    import jax
    import jax.numpy as jnp

    profile_dir = tmp_path / "trace" / "serve_profile"
    with time_limit(120):
        jax.profiler.start_trace(str(profile_dir))
        try:
            with jax.profiler.TraceAnnotation("server/step", rows=1):
                jnp.ones((8, 8)).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
        ctx = {"profile_dir": str(profile_dir)}
        assert attribute_idle.for_run(ctx) is None
        assert (profile_dir / attribute_idle.RESULT_NAME).read_text() == "null"
        trace = attribute_idle.load(
            attribute_idle.reduce_trace.find_xplane(str(profile_dir)),
            attribute_idle.rules(), attribute_idle.reduce_trace.rules())
    names = [ev[0] for p in trace["planes"] for ln in p["lines"]
             for ev in ln["events"]]
    assert names == ["server/step"]  # the host lines are cut to the spans


def test_recorded_v5e_serving_trace_is_attributed():
    path = os.path.join(FIXTURES, "v5e_serve_trace_spans.json")
    if not os.path.isfile(path):
        pytest.skip("v5e_serve_trace_spans.json was not recorded")
    got = attribute_idle.attribute(load("v5e_serve_trace_spans.json"))
    spans = got["spans"]
    assert got["executions"]["decode"] >= 3
    assert spans["attributed_share"] > 0.9
    assert spans["clock_check"]["judged"] >= 3
    assert spans["clock_check"]["inside_any_wait_share"] >= 0.95
    assert all(g["span"] != "unattributed" for g in spans["longest_gaps"])
    total = (sum(spans["groups_s"].values()) + spans["other_spans_s"]
             + spans["unattributed_s"])
    assert total == pytest.approx(got["idle_s"])
    paged = got["kernels"]["paged_attention"]
    assert paged["events_per_step"] == pytest.approx(
        round(paged["events_per_step"]), abs=0.5)
