"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices;
- ``window_s``: the traced window, the ``bench.window`` host span;
- ``device_ops``: device seconds per operation, largest first, named
  ``<program>/<op>`` where the trace has a line of programs (``XLA
  Modules`` on a TPU): the HLO instruction's name without its text;
- ``idle_gaps``: device idle seconds labelled by the host span the gap's
  midpoint falls in (the innermost ``bench.*`` span on any host thread), or
  ``"no span"``, largest first.

A ``Selector`` says where the device operations are: on a TPU, the ``XLA
Ops`` line of each ``/device:TPU:N`` plane.  On the CPU (tests only) the
operations run on the host plane's XLA client threads.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class Selector:
    device_plane: str  # regex on plane names
    op_line: str  # regex on line names


TPU = Selector(r"^/device:TPU:\d+$", r"^XLA Ops$")
MODULE_LINE = r"^XLA Modules$"
CPU = Selector(r"^/host:CPU$", r"^tf_XLA(PjRtCpuClient|Eigen)")
HOST_PLANE = "/host:CPU"


def find_xplane(directory: str) -> Optional[str]:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _op_name(name: str, start: float,
             modules: Optional[List[Tuple[float, float, str]]]) -> str:
    """``<program>/<op>``: an HLO instruction's name (``%fusion.3 = ...``
    carries its whole text on a TPU) after the program running at its start
    (``modules``: sorted intervals of the device's programs)."""
    op = name.split(" = ", 1)[0].lstrip("%")
    if modules:
        i = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
        if i >= 0 and modules[i][0] <= start <= modules[i][1]:
            return f"{modules[i][2]}/{op}"
    return op


def reduce(path: str, sel: Selector, span_prefix: str = "bench.",
           window_span: str = "bench.window") -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    per_device: Dict[str, List[Tuple[float, float]]] = {}
    ops: Dict[str, float] = {}
    op_events: List[Tuple[str, float, float, str]] = []
    modules: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in pd.planes:
        is_dev = re.search(sel.device_plane, plane.name) is not None
        for line in plane.lines:
            dev_line = is_dev and re.search(sel.op_line, line.name) is not None
            mod_line = is_dev and re.search(MODULE_LINE, line.name) is not None
            for ev in line.events:
                s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if dev_line and d > 0:
                    op_events.append((plane.name, s, d, ev.name))
                if mod_line:
                    modules.setdefault(plane.name, []).append((s, s + d, ev.name))
                if plane.name == HOST_PLANE and ev.name.startswith(span_prefix):
                    spans.append((s, s + d, ev.name))
    for mods in modules.values():
        mods.sort()
    op_events = [(p, s, d, _op_name(n, s, modules.get(p))) for p, s, d, n in op_events]
    win = [(s, e) for s, e, n in spans if n == window_span]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min((s for _, s, _, _ in op_events), default=0.0)
        w1 = max((s + d for _, s, d, _ in op_events), default=0.0)
    for plane, s, d, name in op_events:
        lo, hi = max(s, w0), min(s + d, w1)
        if hi <= lo:
            continue
        per_device.setdefault(plane, []).append((lo, hi))
        ops[name] = ops.get(name, 0.0) + (hi - lo)
    busy = [_union(iv) for iv in per_device.values()]
    n_dev = max(1, len(busy))
    busy_s = sum(e - s for u in busy for s, e in u) / n_dev
    for k in ops:
        ops[k] /= n_dev

    # idle gaps of the first device, labelled by the innermost host span
    gaps: Dict[str, float] = {}
    inner = sorted((s, e, n) for s, e, n in spans if n != window_span)
    starts = [s for s, _, _ in inner]
    longest = max((e - s for s, e, _ in inner), default=0.0)
    if busy:
        edges = [(w0, w0)] + busy[0] + [(w1, w1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            label, best = "no span", None
            lo = bisect.bisect_left(starts, mid - longest)
            for s, e, n in inner[lo: bisect.bisect_right(starts, mid)]:
                if s <= mid <= e and (best is None or e - s < best):
                    label, best = n, e - s
            gaps[label] = gaps.get(label, 0.0) + (b - a)
    return {
        "window_s": w1 - w0,
        "busy_s": busy_s,
        "devices": len(busy),
        "ops": ops,
        "device_ops": sorted(([k, v] for k, v in ops.items()), key=lambda x: -x[1]),
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda x: -x[1]),
        "spans": _span_totals(spans, w0, w1),
        "planes": {p.name: sorted({l.name for l in p.lines})[:12] for p in pd.planes},
    }


def _span_totals(spans, w0, w1) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for s, e, n in spans:
        lo, hi = max(s, w0), min(e, w1)
        if hi > lo:
            out[n] = out.get(n, 0.0) + hi - lo
    return out


def reduce_dir(directory: str, sel: Selector, span_prefix: str = "bench.") -> Optional[Dict]:
    path = find_xplane(directory)
    return reduce(path, sel, span_prefix) if path else None
