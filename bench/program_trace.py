"""The program's own spans (``repro.obs.SpanTracer``) on a profiler trace's
clock, and what the trace's device idle time reads under them.

``reduce(path, sel, program)`` reads the ``.xplane.pb`` at ``path`` beside
``program``, the tracer's ``events`` of the traced window and its
``dropped`` count, and returns:

- the clock fit (``map_program``): each ``obs.sync`` annotation of the
  trace is paired, in order, with the tracer's ``sync`` instant, and
  ``profiler = a * perf_counter + b`` is fitted through their midpoints
  (``fit_clock``).  Nothing is mapped (``mapped`` false, ``reason``) with
  fewer than two anchors, a residual over ``MAX_RESIDUAL_US``, a count of
  annotations that differs from the count of instants, or a ring that
  dropped events;
- ``idle_gaps``: the device's idle seconds labelled by the innermost span
  the engine thread held at the gap's midpoint, else by the innermost
  ``bench.*`` host span, else ``"no span"``, largest first;
- where mapped, the device's idle seconds under the engine thread's
  ``prefill.place`` spans (``prefill_place_idle_s``) and under its
  ``swap.stage`` / ``swap.apply`` / ``swap.join`` spans (``swap_idle_s``),
  the callback seconds under placement (``prefill_place_callback_s``) and
  those that a host-attention span covers (``callback_hostattn_s``), and
  the window's seconds per track and span (``totals``).

``bench/run.py`` does not call it: the harness would attach the tracer at
the window's start and detach it at its end, call ``SpanTracer.sync`` after
``start_trace``, after each step and before ``stop_trace``, and pass
``reduce`` the xplane before the trace directory goes.  Window, busy time
and ``bench.*`` spans are read as ``bench/trace_reduce.py`` reads them.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import HOST_PLANE, Selector, _union
from repro.obs.tracer import SYNC_NAME, on_engine_thread

# how far the clock fit may miss
MAX_RESIDUAL_US = 1000.0
PLACE = "prefill.place"
SWAP_EXPOSED = ("swap.stage", "swap.apply", "swap.join")

Intervals = List[Tuple[float, float]]


def _minus(a: Intervals, b: Intervals) -> Intervals:
    """``a`` less ``b``, both sorted unions of disjoint intervals."""
    out: Intervals = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def _length(intervals: Intervals) -> float:
    return sum(e - s for s, e in intervals)


def _is_callback(name: str) -> bool:
    """A host callback in the device's program (``io_callback.N`` on a
    TPU): the graph waits there for host code."""
    return "callback" in name.split(" = ", 1)[0]


def fit_clock(anchors: List[Tuple[float, float]]) -> Dict:
    """Least-squares line ``profiler_s = a * perf_counter_s + b`` through
    ``anchors`` (pairs of the two clocks' readings of one instant).  Refused
    (``ok`` false, ``reason``) with fewer than two anchors or a residual
    (the widest miss, in microseconds) over ``MAX_RESIDUAL_US``.  Computed
    relative to the first anchor, so the seconds since either clock's origin
    lose no precision."""
    n = len(anchors)
    if n < 2:
        return {"ok": False, "reason": f"{n} clock anchor(s); two needed", "anchors": n}
    x0, y0 = anchors[0]
    xs = [x - x0 for x, _ in anchors]
    ys = [y - y0 for _, y in anchors]
    mx, my = sum(xs) / n, sum(ys) / n
    var = sum((x - mx) ** 2 for x in xs)
    if var <= 0:
        return {"ok": False, "reason": "clock anchors share one time", "anchors": n}
    a = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / var
    b = my - a * mx
    residual_us = max(abs(y - (a * x + b)) for x, y in zip(xs, ys)) * 1e6
    out = {"ok": residual_us <= MAX_RESIDUAL_US, "anchors": n, "slope": a,
           "residual_us": residual_us, "x0": x0, "y0": y0 + b}
    if not out["ok"]:
        out["reason"] = f"clock residual {residual_us:.1f} us over {MAX_RESIDUAL_US} us"
    return out


def to_profiler(fit: Dict, t: float) -> float:
    """A perf_counter reading on the profiler's clock (seconds)."""
    return fit["y0"] + fit["slope"] * (t - fit["x0"])


def map_program(program: Dict, syncs: List[Tuple[float, float]]) -> Dict:
    """Pair the trace's ``obs.sync`` annotations (``syncs``: start and
    duration, profiler seconds) in order with the tracer's ``sync`` instants,
    fit the clock through their midpoints, and put every span of the tracer
    on the profiler's clock (``spans``: start, end, track, name, args)."""
    instants = sorted((e.t0, e.t1) for e in program["events"]
                      if e.ph == "i" and e.name == "sync"
                      and (e.args or {}).get("name") == SYNC_NAME)
    base = {"events_recorded": program.get("recorded", len(program["events"])),
            "events_dropped": program["dropped"]}
    if program["dropped"] > 0:
        return {**base, "mapped": False,
                "reason": f"the tracer dropped {program['dropped']} events"}
    if len(instants) != len(syncs):
        return {**base, "mapped": False,
                "reason": f"{len(syncs)} sync annotations, {len(instants)} sync instants"}
    fit = fit_clock([(0.5 * (t0 + t1), s + 0.5 * d)
                     for (t0, t1), (s, d) in zip(instants, sorted(syncs))])
    out = {**base, "mapped": fit["ok"], "anchors": fit["anchors"],
           "clock_residual_us": fit.get("residual_us")}
    if not fit["ok"]:
        out["reason"] = fit["reason"]
        return out
    out["spans"] = [(to_profiler(fit, e.t0), to_profiler(fit, e.t1), e.track, e.name,
                     e.args or {}) for e in program["events"] if e.ph == "X"]
    return out


def _program_readings(spans, w0: float, w1: float, busy: Intervals,
                      callbacks: Intervals) -> Dict:
    """The device's idle seconds (outside ``busy``) under the engine
    thread's placement and swap spans, the callback seconds under placement
    and under a host-attention span, and the window's seconds per track and
    span in ``totals`` (a host-attention track whole)."""
    def clipped(keep):
        return _union([(max(s, w0), min(e, w1)) for s, e, t, n, a in spans
                       if keep(t, n, a) and min(e, w1) > max(s, w0)])
    totals: Dict[str, float] = {}
    for s, e, t, n, _ in spans:
        lo, hi = max(s, w0), min(e, w1)
        key = t if t.startswith("hostattn") else f"{t}/{n}"
        if hi > lo:
            totals[key] = totals.get(key, 0.0) + hi - lo
    hostattn = clipped(lambda t, n, a: t.startswith("hostattn"))
    place = clipped(lambda t, n, a: on_engine_thread(t, a) and n == PLACE)
    return {
        "prefill_place_idle_s": _length(_minus(place, busy)),
        # the device waiting on host code while placement waits on the device
        "prefill_place_callback_s": _length(place) - _length(_minus(place, callbacks)),
        "swap_idle_s": _length(_minus(clipped(
            lambda t, n, a: on_engine_thread(t, a) and n in SWAP_EXPOSED), busy)),
        "callback_hostattn_s": _length(callbacks) - _length(_minus(callbacks, hostattn)),
        "totals": dict(sorted(totals.items(), key=lambda kv: -kv[1])),
    }


def _innermost(spans, mid: float, longest: float) -> Optional[str]:
    """Name of the shortest of ``spans`` (sorted (start, end, name)) that
    holds ``mid``."""
    label, best = None, None
    lo = bisect.bisect_left(spans, (mid - longest,))
    for s, e, n in spans[lo: bisect.bisect_right(spans, (mid, float("inf")))]:
        if s <= mid <= e and (best is None or e - s < best):
            label, best = n, e - s
    return label


def reduce(path: str, sel: Selector, program: Dict, span_prefix: str = "bench.",
           window_span: str = "bench.window") -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    syncs: List[Tuple[float, float]] = []
    ops: List[Tuple[str, float, float, str]] = []
    for plane in pd.planes:
        is_dev = re.search(sel.device_plane, plane.name) is not None
        for line in plane.lines:
            dev_line = is_dev and re.search(sel.op_line, line.name) is not None
            for ev in line.events:
                s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if dev_line and d > 0:
                    ops.append((plane.name, s, s + d, ev.name))
                if plane.name == HOST_PLANE and ev.name.startswith(span_prefix):
                    spans.append((s, s + d, ev.name))
                if plane.name == HOST_PLANE and ev.name == SYNC_NAME:
                    syncs.append((s, d))
    win = [(s, e) for s, e, n in spans if n == window_span]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min((s for _, s, _, _ in ops), default=0.0)
        w1 = max((e for _, _, e, _ in ops), default=0.0)
    # the first device's busy and callback intervals inside the window
    inside = [(p, max(s, w0), min(e, w1), n) for p, s, e, n in ops
              if min(e, w1) > max(s, w0)]
    first = inside[0][0] if inside else None
    busy = _union([(s, e) for p, s, e, _ in inside if p == first])
    callbacks = _union([(s, e) for p, s, e, n in inside
                        if p == first and _is_callback(n)])

    out = map_program(program, syncs)
    engine: List[Tuple[float, float, str]] = []
    if out["mapped"]:
        mapped = out.pop("spans")
        engine = sorted((s, e, n) for s, e, t, n, a in mapped if on_engine_thread(t, a))
        out.update(_program_readings(mapped, w0, w1, busy, callbacks))
    gaps: Dict[str, float] = {}
    inner = sorted((s, e, n) for s, e, n in spans if n != window_span)
    longest = max((e - s for s, e, _ in inner), default=0.0)
    longest_engine = max((e - s for s, e, _ in engine), default=0.0)
    if first is not None:
        edges = [(w0, w0)] + busy + [(w1, w1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            label = (_innermost(engine, mid, longest_engine)
                     or _innermost(inner, mid, longest) or "no span")
            gaps[label] = gaps.get(label, 0.0) + (b - a)
    out["window_s"] = w1 - w0
    out["idle_gaps"] = sorted(([k, v] for k, v in gaps.items()), key=lambda x: -x[1])
    return out
