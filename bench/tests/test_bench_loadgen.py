"""The copied traffic generator and percentile arithmetic."""

import math

import numpy as np
import pytest

from bench import loadgen, servestats
from bench.costs import host_attn_bytes, paged_decode_bytes, prefill_work

SEED = 2 ** 31 + 12345  # more than 32 signed bits
MIXES = ["offload_backlog", "poisson"]


def _mix(name):
    """A mix file, or ``poisson``: the same sizes offered open-loop."""
    if name == "poisson":
        return dict(loadgen.load_mix("offload_backlog"), arrivals="poisson",
                    rate=8.0, lead_in_s=5, drain_cap_s=30)
    return loadgen.load_mix(name)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = _mix(mix)
    a = loadgen.make_requests(m, SEED, 1000, 20)
    b = loadgen.make_requests(m, SEED, 1000, 20)
    assert a == b
    c = loadgen.make_requests(m, SEED + 1, 1000, 20)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_sizes_and_gaps(mix):
    m = _mix(mix)
    a = loadgen.make_requests(m, 1, 1000, 20)
    b = loadgen.make_requests(m, SEED, 1000, 20)
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in b)
    assert sorted(r["output_len"] for r in a) == sorted(r["output_len"] for r in b)
    ga = np.diff([0.0] + [r["arrival"] for r in a])
    gb = np.diff([0.0] + [r["arrival"] for r in b])
    assert np.allclose(np.sort(ga), np.sort(gb))


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_within_the_mix(mix):
    m = _mix(mix)
    reqs = loadgen.make_requests(m, 7, 1000, 20)
    p = [len(r["prompt"]) for r in reqs]
    o = [r["output_len"] for r in reqs]
    assert m["prompt"]["min"] <= min(p) and max(p) <= m["prompt"]["max"]
    assert m["output"]["min"] <= min(o) and max(o) <= m["output"]["max"]
    assert all(1 <= t < 1000 for r in reqs[:5] for t in r["prompt"])
    assert np.median(p) == pytest.approx(m["prompt"]["median"], rel=0.25)


def test_backlog_is_due_at_once_and_poisson_at_its_rate():
    b = loadgen.make_requests(loadgen.load_mix("offload_backlog"), 3, 100, 20)
    assert {r["arrival"] for r in b} == {0.0}
    m = _mix("poisson")
    c = loadgen.make_requests(m, 3, 100, 20)
    assert len(c) == loadgen.request_count(m, 20)
    gaps = np.diff([r["arrival"] for r in c])
    assert np.mean(gaps) == pytest.approx(1 / m["rate"], rel=0.2)


def test_percentile_is_numpys_linear():
    vals = [5.0, 1.0, 3.0, 9.0, 7.0, 2.0]
    for pct in (50, 90, 95):
        assert servestats.percentile(vals, pct) == pytest.approx(np.percentile(vals, pct))


def test_unfinished_and_rejected_requests_are_misses():
    recs = [{"due": 0.0, "first": 0.1 * i, "finish": 1.0 + i, "output_len": 5}
            for i in range(19)]
    recs.append({"due": 0.0, "first": None, "finish": None, "output_len": 5})
    t = servestats.ttft_values(recs)
    assert math.isinf(t[-1])
    assert math.isinf(servestats.percentile(t, 99))
    assert math.isfinite(servestats.percentile(t, 90))
    # the miss pushes p95 above every finished request's value
    assert servestats.percentile(t, 95) > max(t[:-1]) or math.isinf(
        servestats.percentile(t, 95))
    p = servestats.tpot_values(recs)
    assert len(p) == 20 and math.isinf(p[-1])


def test_due_in_window():
    recs = [{"due": x} for x in (0.5, 1.0, 1.5, 2.0)]
    assert [r["due"] for r in servestats.due_in(recs, 1.0, 2.0)] == [1.0, 1.5]


def test_costs_from_shapes():
    dims = {"L": 2, "H": 4, "KV": 2, "hd": 8, "dtype": "bfloat16"}
    # one row of 17 tokens: 2 pages of 16; q and out 2*4*8*2 B; KV 2*2*16*2*8*2 B
    assert paged_decode_bytes([17], 16, dims) == 2 * (2 * 4 * 8 * 2 + 2 * 2 * 16 * 2 * 8 * 2)
    assert host_attn_bytes(np.array([9, 1]), 2, 8, 2) == 2 * 12 * 2 * 8 * 2
    assert prefill_work(4) == (4, 10)


@pytest.mark.parametrize("mix", MIXES)
def test_every_block_holds_the_same_work(mix):
    m = _mix(mix)
    a = loadgen.make_requests(m, 1, 1000, 20)
    b = loadgen.make_requests(m, SEED, 1000, 20)
    k = m["permute_block"]
    for lo in range(0, len(a), k):
        assert (sorted(len(r["prompt"]) for r in a[lo:lo + k])
                == sorted(len(r["prompt"]) for r in b[lo:lo + k]))
