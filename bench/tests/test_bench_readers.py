"""The reader of ``host_wait_share`` on small hand-made contexts, as
``bench/run.py`` builds them."""

import pytest

from bench.run import load_reader


def _ctx(platform="tpu"):
    # a 50-s window, 35 s busy; 30 s inside host callbacks, nested in loops
    trace = {"window_s": 50.0, "busy_s": 35.0, "devices": 1,
             "ops": {"jit_step_1_/while.2": 33.0, "jit_step_1_/io_callback.66": 25.0,
                     "jit__decode_graph_2_/io_callback.77": 5.0,
                     "jit__decode_graph_2_/paged_decode_pallas.6": 2.0}}
    return {"trace": trace, "platform": platform, "delta": {}}


def test_host_wait_share_holds_idle_and_callbacks():
    read = load_reader("host_wait_share")
    assert read(_ctx()) == pytest.approx(100.0 * (15.0 + 30.0) / 50.0)
    assert read(_ctx()) >= load_reader("device_idle_share")(_ctx())
    assert read(_ctx(platform="cpu")) is None
    assert read({"trace": None, "platform": "tpu", "delta": {}}) is None
