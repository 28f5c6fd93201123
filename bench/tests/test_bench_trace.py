"""The trace reduction on a small trace recorded on the CPU
(``make_trace_data.py``): three matmuls, each followed by 20 ms of host work
in a ``bench.host_attn`` span, inside ``bench.window``."""

import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(DATA, trace_reduce.CPU)


def test_window_is_the_window_span(red):
    assert red["window_s"] == pytest.approx(red["spans"]["bench.window"])
    assert 0.06 <= red["window_s"] < 1.0


def test_busy_is_the_matmuls(red):
    dots = sum(v for k, v in red["ops"].items() if k.startswith("dot_general"))
    assert 0 < dots <= red["busy_s"] < red["window_s"]
    assert red["device_ops"][0][0].startswith("dot_general")
    assert red["devices"] == 1


def test_idle_gaps_blame_the_host_span(red):
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.host_attn"] >= 0.055  # three sleeps of 20 ms
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)


def test_union_merges_overlaps():
    assert trace_reduce._union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_tpu_selector_finds_no_device_here():
    red = trace_reduce.reduce(DATA, trace_reduce.TPU)
    assert red["devices"] == 0 and red["busy_s"] == 0
