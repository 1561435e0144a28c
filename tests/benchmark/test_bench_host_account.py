"""The readers of the stepper's own books (``benchmark/lib/host_account.py``
and the eleven ``benchmark/layer_metrics`` files that use it): each on two
``/metrics`` scrapes written by hand gives the value worked by hand, gives
None where a series is missing (the parent of the PR that added them) or the
window held no decode step, and is in ``BENCHMARK.json`` for the five serving
cells with a reader file of its name."""

import json
import os

import pytest

from bench_paths import BENCH, ROOT

import host_account  # noqa: E402
import spec as spec_lib  # noqa: E402

SERVING = ["serve.mistral_7b.chat", "serve.qwen2_7b.batch",
           "serve.nemotron3_nano_30b.tool_turns",
           "serve.kanana2_30b.doc_turns", "serve.xing4_29b.fresh_docs"]
WAITS = {"server/loop": "wait", "server/wait_work": "wait",
         "engine/decode_wait": "device_wait",
         "engine/prefill_wait": "device_wait"}


class _Phase:
    """``PHASE % name``: the series of one phase, with the kind the program
    gives it (``PHASE % "*"``: the family's head, for leaving it out)."""

    def __mod__(self, name):
        head = 'dlti_stepper_phase_seconds_total{phase="'
        if name == "*":
            return head + "*"
        return f'{head}{name}",kind="{WAITS.get(name, "host")}"}}'


PHASE = _Phase()
GC = 'dlti_gc_pause_seconds_total{generation="%s"}'


def _scrapes():
    """Two scrapes 40 s apart: 2,000 decode steps, 50,000 kept tokens."""
    before = {
        "_t": 100.0,
        "dlti_decode_steps": 1000.0, "dlti_decode_slot_steps": 20000.0,
        PHASE % "server/loop": 0.5, PHASE % "server/lock_wait": 1.0,
        PHASE % "server/wait_work": 30.0, PHASE % "server/step": 2.0,
        PHASE % "server/drain_events": 3.0,
        PHASE % "engine/decode_prep": 0.25,
        PHASE % "engine/decode_plan": 4.0,
        PHASE % "engine/decode_assemble": 5.0,
        PHASE % "engine/decode_stage": 6.0,
        PHASE % "engine/decode_launch": 1.5,
        PHASE % "engine/decode_wait": 50.0,
        PHASE % "engine/decode_emit": 0.75,
        PHASE % "engine/admit": 2.5,
        PHASE % "engine/prefill_launch": 0.5,
        PHASE % "engine/prefill_wait": 9.0,
        "dlti_stepper_cpu_seconds_total": 40.0,
        "dlti_stepper_device_wait_cpu_seconds_total": 4.0,
        "dlti_stepper_marked_host_seconds_total": 60.0,
        "dlti_stepper_marked_decode_steps_total": 992.0,
        "dlti_stepper_stall_seconds_total": 0.0,
        "dlti_decode_stream_stall_seconds_prefill": 100.0,
        GC % 0: 0.5, GC % 2: 1.0,
        "dlti_sse_handler_cpu_seconds_total": 10.0,
        "dlti_sse_events_total": 20000.0,
        "dlti_compilations_total": 40.0,
        "dlti_compile_cache_hits_total": 300.0,
    }
    grows = {
        "_t": 40.0,
        "dlti_decode_steps": 2000.0, "dlti_decode_slot_steps": 50000.0,
        PHASE % "server/loop": 0.25, PHASE % "server/lock_wait": 0.5,
        PHASE % "server/wait_work": 0.125, PHASE % "server/step": 1.0,
        PHASE % "server/drain_events": 1.5,
        PHASE % "engine/decode_prep": 0.5,
        PHASE % "engine/decode_plan": 4.0,
        PHASE % "engine/decode_assemble": 6.0,
        PHASE % "engine/decode_stage": 8.0,
        PHASE % "engine/decode_launch": 2.0,
        PHASE % "engine/decode_wait": 10.0,
        PHASE % "engine/decode_emit": 1.0,
        PHASE % "engine/admit": 2.0,
        PHASE % "engine/prefill_launch": 0.25,
        PHASE % "engine/prefill_wait": 2.875,
        "dlti_stepper_cpu_seconds_total": 20.0,
        "dlti_stepper_device_wait_cpu_seconds_total": 1.0,
        "dlti_stepper_marked_host_seconds_total": 26.0,
        "dlti_stepper_marked_decode_steps_total": 2016.0,
        "dlti_stepper_stall_seconds_total": 3.0,
        "dlti_decode_stream_stall_seconds_prefill": 250.0,
        GC % 0: 0.25, GC % 2: 0.75,
        "dlti_sse_handler_cpu_seconds_total": 5.0,
        "dlti_sse_events_total": 50000.0,
        "dlti_compilations_total": 1.0,
        "dlti_compile_cache_hits_total": 2.0,
    }
    after = {k: before[k] + v for k, v in grows.items()}
    # a phase and a generation that first appear inside the window: from 0
    after[PHASE % "engine/prefill_chunks"] = 0.75
    after[GC % 1] = 0.5
    return before, after


# host phases' growth: .5 + 1 + 1.5 + .5 + 4 + 6 + 8 + 2 + 1 + 2 + .25
# + .75 (prefill_chunks) = 27.5 s over 2,000 steps
BY_HAND = {
    "host_ms_per_step.total": 1000 * 27.5 / 2000,
    "host_ms_per_step.decode_prep": 1000 * (0.5 + 4.0 + 6.0 + 8.0) / 2000,
    "host_ms_per_step.decode_stage": 1000 * 8.0 / 2000,
    "host_ms_per_step.admit": 1000 * (2.0 + 0.75 + 0.25) / 2000,
    "host_ms_per_step.server": 1000 * (0.5 + 1.5) / 2000,
    # the three totals as of the marked steps' entries, the waits' estimate
    "stepper_off_cpu_ms_per_step": 1000 * (26.0 - (20.0 - 1.0)) / 2016,
    "itl_stall_ms_per_token.prefill": 1000 * 250.0 / 50000,
    "stalled_ms_per_s": 1000 * 3.0 / 40.0,
    "gc_pause_ms_per_s": 1000 * (0.25 + 0.75 + 0.5) / 40.0,
    "handler_cpu_us_per_token": 1e6 * 5.0 / 50000,
    "compiles_in_window": 1.0 + 2.0,
}
# the series each reader cannot do without
NEEDS = {
    "host_ms_per_step.total": [PHASE % "*"],
    "host_ms_per_step.decode_prep": [PHASE % "*"],
    "host_ms_per_step.decode_stage": [PHASE % "engine/decode_stage"],
    "host_ms_per_step.admit": [PHASE % "*"],
    "host_ms_per_step.server": [PHASE % "*"],
    "stepper_off_cpu_ms_per_step": [
        "dlti_stepper_cpu_seconds_total",
        "dlti_stepper_device_wait_cpu_seconds_total",
        "dlti_stepper_marked_host_seconds_total",
        "dlti_stepper_marked_decode_steps_total"],
    "itl_stall_ms_per_token.prefill": [
        "dlti_decode_stream_stall_seconds_prefill"],
    "stalled_ms_per_s": ["dlti_stepper_stall_seconds_total", "_t"],
    "gc_pause_ms_per_s": [GC % "*", "_t"],
    "handler_cpu_us_per_token": ["dlti_sse_handler_cpu_seconds_total",
                                 "dlti_sse_events_total"],
    "compiles_in_window": ["dlti_compil*"],
}
PER_STEP = [m for m in BY_HAND if m.startswith("host_ms_per_step")]
# what a quiet window reads 0 in, and not None: nothing stood still, nothing
# was collected, nothing was compiled
QUIET = {"stalled_ms_per_s": ["dlti_stepper_stall_seconds_total"],
         "gc_pause_ms_per_s": [GC % 0, GC % 1, GC % 2],
         "compiles_in_window": ["dlti_compilations_total",
                                "dlti_compile_cache_hits_total"]}


def _ctx(before, after):
    return {"metrics_before": before, "metrics_after": after}


def _without(scrape, pattern):
    head = pattern.split("*")[0]
    return {k: v for k, v in scrape.items()
            if not (k.startswith(head) if "*" in pattern else k == pattern)}


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_reader_gives_the_value_worked_by_hand(metric):
    read = spec_lib.load_layer_reader(metric)
    assert read(_ctx(*_scrapes())) == pytest.approx(BY_HAND[metric],
                                                    rel=1e-12)


@pytest.mark.parametrize("metric, series", [
    (m, s) for m in sorted(NEEDS) for s in NEEDS[m]])
def test_reader_gives_none_when_a_series_is_missing(metric, series):
    read = spec_lib.load_layer_reader(metric)
    before, after = _scrapes()
    assert read(_ctx(_without(before, series),
                     _without(after, series))) is None
    # the parent's program: none of the series, and no scrapes at all
    plain = {k: v for k, v in after.items() if k in (
        "_t", "dlti_decode_steps", "dlti_decode_slot_steps")}
    assert read(_ctx(plain, plain)) is None
    assert read({}) is None


@pytest.mark.parametrize("metric", PER_STEP)
def test_a_window_without_a_decode_step_reads_none(metric):
    read = spec_lib.load_layer_reader(metric)
    before, after = _scrapes()
    after["dlti_decode_steps"] = before["dlti_decode_steps"]
    assert read(_ctx(before, after)) is None


@pytest.mark.parametrize("metric", sorted(QUIET))
def test_a_quiet_window_reads_zero_and_not_none(metric):
    before, after = _scrapes()
    for series in QUIET[metric]:
        before.setdefault(series, 0.0)
        after[series] = before[series]
    got = spec_lib.load_layer_reader(metric)(_ctx(before, after))
    assert got == 0.0 and got is not None


def test_a_compile_family_not_counted_into_yet_stands_at_zero():
    """A counter family is on ``/metrics`` from its first count: a server
    that fetched every program from the cache has compiled none."""
    read = spec_lib.load_layer_reader("compiles_in_window")
    before, after = _scrapes()
    del before["dlti_compilations_total"], after["dlti_compilations_total"]
    assert read(_ctx(before, after)) == 2.0
    del before["dlti_compile_cache_hits_total"]   # first fetch in the window
    assert read(_ctx(before, after)) == 302.0


def test_the_marked_steps_stand_alone_in_the_off_cpu_reading():
    read = spec_lib.load_layer_reader("stepper_off_cpu_ms_per_step")
    before, after = _scrapes()
    # no marked step held a decode step: nothing to divide by
    still = dict(after, dlti_stepper_marked_decode_steps_total=before[
        "dlti_stepper_marked_decode_steps_total"])
    assert read(_ctx(before, still)) is None
    # the window's own decode steps and phases are not read
    after["dlti_decode_steps"] = before["dlti_decode_steps"]
    assert read(_ctx(before, _without(after, PHASE % "*"))) \
        == pytest.approx(BY_HAND["stepper_off_cpu_ms_per_step"])


def test_the_quotients_need_their_denominators_to_move():
    before, after = _scrapes()
    still = dict(after, dlti_decode_slot_steps=before[
        "dlti_decode_slot_steps"], dlti_sse_events_total=before[
        "dlti_sse_events_total"], _t=before["_t"])
    for metric in ("itl_stall_ms_per_token.prefill",
                   "handler_cpu_us_per_token", "stalled_ms_per_s",
                   "gc_pause_ms_per_s"):
        assert spec_lib.load_layer_reader(metric)(_ctx(before, still)) \
            is None, metric


def test_the_host_phases_are_those_the_program_labels_host():
    before, after = _scrapes()
    host = host_account.phase_seconds(before, after)
    everything = sum(v - before.get(k, 0.0) for k, v in after.items()
                     if k.startswith("dlti_stepper_phase_seconds_total{"))
    # the waits for work and for the device, and what is outside every phase
    assert everything - host == pytest.approx(0.125 + 10.0 + 2.875 + 0.25)
    # by the label alone: a wait the reader never heard of is no host time,
    # a host phase it never heard of is
    head = 'dlti_stepper_phase_seconds_total{phase="engine/'
    after[head + 'tier_wait",kind="wait"}'] = 5.0
    assert host_account.phase_seconds(before, after) == host
    after[head + 'tier_pack",kind="host"}'] = 5.0
    assert host_account.phase_seconds(before, after) == host + 5.0
    del after[head + 'tier_wait",kind="wait"}']
    del after[head + 'tier_pack",kind="host"}']
    # the parts leave the step's own time and the launch to the total
    parts = sum(BY_HAND[f"host_ms_per_step.{p}"]
                for p in ("decode_prep", "admit", "server"))
    # server/step, engine/decode_launch, engine/decode_emit
    rest = 1000 * (1.0 + 2.0 + 1.0) / 2000
    assert parts + rest == pytest.approx(BY_HAND["host_ms_per_step.total"])
    # the difference comes out signed: where the CPU estimate overshot the
    # wall, the reading says so
    after["dlti_stepper_cpu_seconds_total"] += 10.0
    assert spec_lib.load_layer_reader("stepper_off_cpu_ms_per_step")(
        _ctx(before, after)) == pytest.approx(
            1000 * (26.0 - (30.0 - 1.0)) / 2016)


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_metric_is_in_the_benchmark_for_the_five_serving_cells(metric):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == SERVING
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower" and entry["moves"] == "itl_mean_ms"
    assert entry["unit"] == {"compiles_in_window": "programs"}.get(
        metric, entry["unit"])
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                       metric + ".py"))
    consts = spec_lib.load_layer_reader(metric).__globals__
    assert (consts["NAME"], consts["UNIT"], consts["LAYER"]) == (
        entry["name"], entry["unit"], entry["layer"])
    # appended: the eleven are the file's last entries, after what was there
    names = [m["name"] for m in bench["per_layer"]]
    assert set(names[-11:]) == set(BY_HAND)
    for cell in SERVING:
        got = spec_lib.resolve_cell(cell)
        assert metric in [m["name"] for m in got["per_layer"]]
