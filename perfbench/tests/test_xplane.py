"""The trace reducer, on synthetic events and on a small trace recorded on
a v5e (``tools/record_small_trace.py``: three steps of a matmul + one
Pallas flash-attention call, 2 ms of host sleep after each)."""

import os

import pytest

from perfbench.harness import readers, xplane
from perfbench.harness.xplane import Ev

from conftest import DATA

SMALL = os.path.join(DATA, "small_trace.xplane.pb")


def test_union_gaps_and_self_time():
    evs = [Ev("while", 0.0, 10.0), Ev("a", 1.0, 3.0), Ev("b", 3.0, 4.0),
           Ev("a", 6.0, 7.0), Ev("c", 12.0, 13.0)]
    assert xplane.union(evs) == [[0.0, 10.0], [12.0, 13.0]]
    assert xplane.busy_seconds(evs) == 11.0
    assert xplane.gaps(evs, -1.0, 14.0) == [(-1.0, 0.0), (10.0, 12.0),
                                            (13.0, 14.0)]
    # the loop keeps only what its body does not cover
    assert xplane.self_times(evs) == {"while": 6.0, "a": 3.0, "b": 1.0,
                                      "c": 1.0}
    assert xplane.matching_seconds(evs, "^a$") == (3.0, 2)
    assert xplane.matching_seconds(evs, "while|a") == (10.0, 1)   # nested once
    assert xplane.busy_seconds(xplane.clip(evs, 2.0, 6.5)) == 4.5


def test_host_activity_names_the_innermost_span():
    host = [Ev("bench/engine.step", 0.0, 10.0), Ev("Execute", 1.0, 9.0),
            Ev("Allocate", 2.0, 3.0), Ev("bench/stamp", 10.0, 11.0)]
    assert xplane.host_activity(host, 2.5) == "bench/engine.step>Allocate"
    assert xplane.host_activity(host, 5.0) == "bench/engine.step>Execute"
    assert xplane.host_activity(host, 10.5) == "bench/stamp"
    assert xplane.host_activity(host, 12.0) == "outside bench spans"


def test_short_name():
    line = ('%fusion.126.remat = bf16[64,128,152064]{2,1,0:T(8,128)(2,1)} '
            'fusion(bf16[64,128,3584]{2,1,0} %bitcast.1277), kind=kOutput')
    assert xplane.short_name(line) == \
        "%fusion.remat fusion bf16[64,128,152064]"
    assert xplane.short_name("plain") == "plain"


@pytest.fixture(scope="module")
def small():
    return xplane.load(SMALL)


def test_recorded_trace_planes(small):
    assert ("/device:TPU:0", "XLA Ops", 18) in small.lines
    assert list(small.device_ops) == [0] and len(small.device_ops[0]) == 18
    assert sum(1 for e in small.host if e.name == "bench/engine.step") == 3


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(small):
    # three steps of 1.1 + 7.2 + 0.9 + 12.6 us of ops; with no settling span
    # the window runs from the first step span's start to the last one's end
    spans = xplane.step_spans(small.host)
    red = xplane.reduce(small, window=(min(e.start for e in spans),
                                       max(e.end for e in spans)))
    assert red["busy_s"] == pytest.approx(65.306e-6, rel=1e-3)
    assert red["window_s"] == pytest.approx(7.30839e-3, rel=1e-4)
    assert red["device_ops"][0][0] == \
        "%convolution_tanh_fusion fusion bf16[1024,1024]"
    assert red["device_ops"][0][1] == pytest.approx(37.804e-6, rel=1e-3)
    assert red["idle_gaps"][0][0] == "bench/stamp"       # the sleeps
    assert red["idle_gaps"][0][1] == pytest.approx(6.33e-3, rel=1e-2)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    idle = readers.read_idle_share({}, {"reduced": red})
    assert idle == pytest.approx(100 * (1 - 65.306e-6 / 7.30839e-3), rel=1e-4)
    assert red["steps"] == 3
    assert readers.read_device_ms_per_step({}, {"reduced": red}) == \
        pytest.approx(1e3 * 65.306e-6 / 3, rel=1e-3)


def test_the_first_step_span_after_the_profiler_starts_is_left_out(small):
    red = xplane.reduce(small)
    spans = xplane.step_spans(small.host)
    assert red["window"] == (spans[1].start, spans[2].end)
    assert red["steps"] == 2
    assert red["busy_s"] == pytest.approx(43.539e-6, rel=1e-3)
    assert red["window_s"] == pytest.approx(4.1205e-3, rel=1e-4)
    assert red["step_idle"] == [98.0, 98.1, 97.6]     # all three are shown
    # a single span is all there is to read
    one = [Ev("bench/train.step", 1.0, 2.0)]
    assert xplane.window_of(one) == (1.0, 2.0)
    assert xplane.window_of(one + [Ev("bench/train.step", 2.0, 3.5)]) == \
        (1.0, 3.5)


def test_kernel_time_by_pattern(small):
    red = xplane.reduce(small)
    evs = xplane.clip(small.device_ops[0], *red["window"])
    secs, n = xplane.matching_seconds(evs, 'custom_call_target="tpu_custom_call"')
    assert n == 2 and secs == pytest.approx(14.387e-6, rel=1e-3)
    assert xplane.matching_seconds(evs, "^%no_such_kernel") == (0.0, 0)


def test_a_reader_that_finds_nothing_returns_nothing(small):
    red = xplane.reduce(small)
    ctx = {"trace": small, "reduced": red, "cfg": {"sizes": {}}}
    spec = {"pattern": "^%no_such_kernel", "work": "flash_attention"}
    assert readers.read_kernel_roofline(spec, ctx) is None
    assert readers.read_idle_share({}, {}) is None
    assert readers.read_device_ms_per_step({}, {}) is None
