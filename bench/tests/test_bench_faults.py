"""A run whose timed path is broken underneath must come out not correct:
the harness's look for a chip is skipped (rehearsal sizes, on the CPU) and
the rest of the run is driven as on the chip, with one fault planted in the
program each time."""

import numpy as np
import pytest

from harness import rehearse  # noqa: F401 -- fixture
from repro.core.engine import NeoEngine
from repro.core.host_attention import HostAttention
from repro.core.kv_cache import PagePool

CELL = "qwen3-0.6b.offload_backlog"  # host rows and swaps both occur here


def _altered_token(monkeypatch):
    """A served token altered where it is produced (every 7th sample)."""
    sample = NeoEngine._sample
    n = {"calls": 0}

    def wrong(self, logits):
        n["calls"] += 1
        tok = sample(self, logits)
        return (tok + 1) % len(logits) if n["calls"] % 7 == 0 else tok
    monkeypatch.setattr(NeoEngine, "_sample", wrong)


def _host_rows_dropped(monkeypatch):
    """The host rows' attention left out: host attention returns zeros."""
    run_layer = HostAttention.run_layer

    def dropped(self, layer, q, *a, **k):
        return np.zeros_like(run_layer(self, layer, q, *a, **k))
    monkeypatch.setattr(HostAttention, "run_layer", dropped)


def _prefill_kv_lost(monkeypatch):
    """A step that leaves its state unchanged: pages put into the device pool
    (prefill KV, swap-ins) never land."""
    put = PagePool.put_pages

    def lost(self, pages, k, v):
        if self.backend != "device":
            put(self, pages, k, v)
    monkeypatch.setattr(PagePool, "put_pages", lost)


@pytest.mark.parametrize("fault", [_altered_token, _host_rows_dropped, _prefill_kv_lost])
def test_fault_is_not_correct(fault, rehearse, monkeypatch):  # noqa: F811
    fault(monkeypatch)
    out = rehearse(CELL)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] > out["checks"]["max_logit_gap"]["limit"]
