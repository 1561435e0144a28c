"""The reduction from a trace to numbers, on small traces kept with the
benchmark: one made by hand (so every number can be checked on paper) and
one recorded on the v5e and cut to a few hundred events."""

import json
import os

import pytest

from bench_paths import BENCH

import reduce_trace

FIXTURES = os.path.join(BENCH, "fixtures")


def load(name):
    return json.load(open(os.path.join(FIXTURES, name)))


@pytest.mark.parametrize("intervals,total", [
    ([(0, 10), (5, 15), (20, 30)], 25), ([], 0), ([(0, 10), (2, 4)], 10),
    ([(5, 6), (0, 1)], 2)], ids=["overlap", "none", "nested", "unsorted"])
def test_union_of_intervals(intervals, total):
    assert reduce_trace.union_ns(intervals) == total


def test_op_family_strips_the_serial_number_and_the_hlo_text():
    assert reduce_trace.op_family(
        "%fusion.12533 = (f32[4,2047]{1,0}) fusion(bf16[4096])") == "fusion"
    assert reduce_trace.op_family("%all-gather-start.3 = x") == \
        "all-gather-start"
    assert reduce_trace.op_family("copy") == "copy"


def test_hand_made_trace_busy_idle_and_programs():
    got = reduce_trace.reduce(load("hand_trace.json"))
    assert got["devices"] == 2
    # device 0: ops cover [0,400) u [500,900) u all-gather [900,1000)
    # device 1: ops cover [0,1000) of which all-gather [300,600) overlapped
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx((900 + 1000) / 2 * 1e-9)
    assert got["idle_share"] == pytest.approx(1 - 0.95)
    step = got["programs"]["train_step"]
    assert step["count"] == 2 and step["median_s"] == pytest.approx(450e-9)
    assert got["programs"]["decode"]["count"] == 0
    assert got["device_ops"][0][0] == "fusion"
    assert got["idle_gaps"][0][1] == pytest.approx(100e-9)


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    host_only = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert reduce_trace.reduce(host_only) == {"devices": 0}


@pytest.mark.parametrize("name,program", [
    ("v5e_train_trace.json", "train_step"),
    ("v5e_serve_trace.json", "decode"),
])
def test_recorded_v5e_trace_is_read_by_the_rules(name, program):
    path = os.path.join(FIXTURES, name)
    if not os.path.isfile(path):
        pytest.skip(f"{name} was not recorded")
    got = reduce_trace.reduce(load(name))
    assert got["devices"] == 1
    assert 0 < got["busy_s"] <= got["window_s"]
    assert 0 <= got["idle_share"] < 1
    assert got["programs"][program]["count"] >= 1
    assert got["programs"][program]["median_s"] > 0
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_an_xplane_file_written_by_the_profiler_loads(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = reduce_trace.find_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    trace = reduce_trace.load(path)
    assert trace["planes"], "the reader found no plane"
    assert all({"name", "lines"} <= set(p) for p in trace["planes"])
    # a CPU trace has no TPU plane: nothing to reduce, and it says so
    assert reduce_trace.reduce(trace) == {"devices": 0}
