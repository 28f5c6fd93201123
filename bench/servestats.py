"""Per-request latency arithmetic of the benchmark.

TTFT and TPOT per request are ``ServeMetrics.ttft``/``tpot`` of
``repro.serving.metrics`` (copied): time from the due time to the first token,
and (finish - first token) / (tokens - 1).  The population differs: a tail is
taken over every request due in the window, and a request that was rejected
or has no value by the end of the capped drain counts as a miss, beyond every
percentile (``inf``), instead of being left out.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional


def percentile(values: Iterable[float], pct: float) -> float:
    """Linear-interpolation percentile (numpy's default), with ``inf`` for a
    miss: a percentile that reaches into the misses is ``inf``."""
    vals = sorted(values)
    if not vals:
        return math.nan
    pos = (len(vals) - 1) * pct / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vals) - 1)
    if math.isinf(vals[hi]) and pos > lo:
        return math.inf
    if math.isinf(vals[lo]):
        return math.inf
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def ttft(rec: Dict) -> Optional[float]:
    if rec.get("first") is None:
        return None
    return rec["first"] - rec["due"]


def tpot(rec: Dict) -> Optional[float]:
    """None for a single-token output, which has no decode phase."""
    if rec.get("finish") is None or rec.get("first") is None:
        return None
    if rec["output_len"] <= 1:
        return None
    return (rec["finish"] - rec["first"]) / (rec["output_len"] - 1)


def due_in(records: Iterable[Dict], w0: float, w1: float) -> List[Dict]:
    return [r for r in records if w0 <= r["due"] < w1]


def ttft_values(records: Iterable[Dict]) -> List[float]:
    out = []
    for r in records:
        v = ttft(r)
        out.append(math.inf if v is None else v)
    return out


def tpot_values(records: Iterable[Dict]) -> List[float]:
    out = []
    for r in records:
        if r["output_len"] <= 1 and r.get("first") is not None:
            continue
        v = tpot(r)
        out.append(math.inf if v is None else v)
    return out
