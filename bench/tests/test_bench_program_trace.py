"""The program's own spans on the profiler's clock (``bench/program_trace.py``):
the clock fit through ``SpanTracer.sync`` anchors, and the idle-gap labels and
readings of a small trace recorded on the CPU with its program spans
(``make_program_trace_data.py``)."""

import json
import os

import pytest

from bench import program_trace, trace_reduce
from repro.obs.tracer import SpanEvent

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "cpu_program_trace.xplane.pb")


def _program(**over):
    with open(os.path.join(DATA, "cpu_program_trace.events.json")) as f:
        doc = json.load(f)
    prog = {"events": [SpanEvent(*e) for e in doc["events"]], "dropped": doc["dropped"]}
    prog.update(over)
    return prog


@pytest.fixture(scope="module")
def red():
    return program_trace.reduce(XPLANE, trace_reduce.CPU, _program())


@pytest.fixture(scope="module")
def plain():
    return trace_reduce.reduce(XPLANE, trace_reduce.CPU)


# -- the clock fit ------------------------------------------------------------

def test_fit_recovers_offset_and_drift():
    a, b = 1.0 + 3e-6, 12345.678  # 3 ppm of drift, an offset of hours
    xs = [100000.0 + 0.37 * i for i in range(40)]
    fit = program_trace.fit_clock([(x, a * x + b) for x in xs])
    assert fit["ok"] and fit["anchors"] == 40
    assert fit["slope"] == pytest.approx(a, abs=1e-9)
    assert fit["residual_us"] < 0.01
    for x in (xs[0], 100003.3, xs[-1]):
        assert program_trace.to_profiler(fit, x) == pytest.approx(a * x + b, abs=1e-8)


@pytest.mark.parametrize("n", [0, 1])
def test_fit_refuses_fewer_than_two_anchors(n):
    fit = program_trace.fit_clock([(1.0, 5.0)] * n)
    assert not fit["ok"] and "two needed" in fit["reason"]


def test_fit_refuses_a_residual_over_a_millisecond():
    anchors = [(float(i), float(i) + 7.0) for i in range(5)]
    assert program_trace.fit_clock(anchors)["ok"]
    anchors[2] = (2.0, 9.0 + 3e-3)  # one anchor 3 ms off the line
    fit = program_trace.fit_clock(anchors)
    assert not fit["ok"] and fit["residual_us"] > 1000.0
    assert "residual" in fit["reason"]


# -- the reduction with program spans -----------------------------------------

def test_the_clock_maps(red):
    prog = red
    assert prog["mapped"] and prog["anchors"] == 5  # start, three steps, stop
    assert prog["events_dropped"] == 0
    assert prog["clock_residual_us"] < 1000.0


def test_gaps_carry_the_engine_threads_program_span(red, plain):
    gaps = dict(red["idle_gaps"])
    assert gaps["prefill.place"] >= 0.055  # three sleeps of 20 ms
    # outside the step span only a lane-thread span is open: bench.* label
    assert gaps["bench.host_attn"] >= 0.055
    assert not any(k.startswith("L") or k.startswith("hostattn") for k in gaps)
    assert red["window_s"] == plain["window_s"]
    assert sum(gaps.values()) + plain["busy_s"] == pytest.approx(plain["window_s"], rel=1e-6)


def test_program_readings(red):
    prog = red
    # the device was idle through each placement span (host sleeps)
    assert 0.055 <= prog["prefill_place_idle_s"] <= prog["totals"]["prefill/prefill.place"]
    assert prog["swap_idle_s"] == 0
    assert prog["totals"]["hostattn-lane1"] >= 0.06  # a host-attention track whole
    # no host callback on the CPU: waiting is idleness
    assert prog["prefill_place_callback_s"] == prog["callback_hostattn_s"] == 0


def test_readings_count_device_idle_under_engine_thread_spans():
    """A placement or swap span counts only where the device sat idle under
    it: a span that waits on queued device work moves nothing.  Spans of
    other threads count nowhere."""
    spans = [
        (0.0, 10.0, "prefill", "prefill.place", {"rid": 1}),
        (4.0, 6.0, "prefill-lane", "prefill.place", {"rid": 2}),  # lane thread
        (12.0, 14.0, "swap", "swap.stage", {"bytes": 1}),
        (13.0, 15.0, "swap", "swap.join", {}),
        (15.0, 30.0, "host0", "swap.join", {}),  # a lane's join
        (16.0, 18.0, "sched", "prefill.place", {"speculative": True}),  # planner
    ]
    busy = [(2.0, 5.0), (13.5, 14.5), (25.0, 40.0)]
    callbacks = [(2.0, 4.0), (25.0, 26.0)]
    got = program_trace._program_readings(spans, 1.0, 29.0, busy, callbacks)
    assert got["prefill_place_idle_s"] == pytest.approx(9.0 - 3.0)  # [1,10] less busy
    assert got["swap_idle_s"] == pytest.approx(3.0 - 1.0)  # [12,15] less busy
    assert got["callback_hostattn_s"] == 0  # no hostattn span
    assert got["prefill_place_callback_s"] == pytest.approx(2.0)
    assert got["totals"]["prefill/prefill.place"] == pytest.approx(9.0)


def test_unmapped_gaps_are_trace_reduces(plain):
    """Where the clock does not map, every gap keeps the label
    ``trace_reduce`` gives it."""
    red = program_trace.reduce(XPLANE, trace_reduce.CPU, _program(dropped=1))
    assert red["idle_gaps"] == plain["idle_gaps"]
    assert "prefill.place" not in dict(plain["idle_gaps"])


@pytest.mark.parametrize("over,reason", [
    ({"dropped": 3}, "dropped"),
    ("no_sync", "sync"),
])
def test_unmappable_program_falls_back_to_bench_spans(over, reason):
    prog = _program()
    if over == "no_sync":
        prog["events"] = [e for e in prog["events"] if e.ph != "i"]
    else:
        prog.update(over)
    red = program_trace.reduce(XPLANE, trace_reduce.CPU, prog)
    assert not red["mapped"] and reason in red["reason"]
    assert "prefill_place_idle_s" not in red
    gaps = dict(red["idle_gaps"])
    assert "prefill.place" not in gaps and gaps["bench.host_attn"] >= 0.055


def test_minus_and_length():
    a = [(0.0, 4.0), (6.0, 10.0)]
    b = [(1.0, 2.0), (3.0, 7.0), (9.0, 12.0)]
    assert program_trace._minus(a, b) == [(0.0, 1.0), (2.0, 3.0), (7.0, 9.0)]
    assert program_trace._length(program_trace._minus(a, b)) == 4.0
    assert program_trace._minus(a, []) == a
