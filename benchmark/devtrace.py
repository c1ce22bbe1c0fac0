"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device planes are those named ``/device:<KIND>:<n>`` (``/device:TPU:0``);
an operation is an event on a plane's ``XLA Ops`` line. The harness's spans
are ``TraceAnnotation`` events named ``bench.*`` on the host plane, on the
same clock: ``bench.session`` for a window's session, ``bench.warm`` and
``bench.probe`` for sessions outside it. From these:

- ``busy_ns(ops, lo, hi)``: the union of operation intervals inside [lo, hi);
- ``session_busy``: busy time inside each ``bench.session`` span;
- ``traced_window``: from the first traced session of any kind to the
  last, the window that ``busy_s`` and ``window_s`` describe;
- ``breakdown``: the device operations that took most time, and the longest
  idle gaps, each named by the innermost harness span the host was in.

A device plane on which nothing ran is kept: its sessions read 0 busy, 100%
idle, as they were. A trace with no device plane gives no device numbers
(the caller leaves the metrics out); nothing here returns 0 for what it
could not read.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."

Interval = Tuple[int, int]


def read(path: str):
    """(device operations by plane, harness spans) of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return device_ops(pd), host_spans(pd)


def op_name(text: str) -> str:
    """An XLA Ops event is named by its whole HLO instruction
    (``%fusion.3 = f32[...] fusion(...)``); keep the instruction's name."""
    return text.split(" = ", 1)[0]


def device_ops(pd) -> Dict[str, List[Tuple[str, int, int]]]:
    """plane name -> [(op name, start ns, end ns)], sorted by start."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                start = int(ev.start_ns)
                ops.append((op_name(ev.name), start,
                            start + int(ev.duration_ns)))
        out[plane.name] = sorted(ops, key=lambda o: o[1])
    return out


def host_spans(pd) -> List[Tuple[str, int, int]]:
    """[(span name, start ns, end ns)] of the harness's annotations."""
    spans = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    start = int(ev.start_ns)
                    spans.append((ev.name, start, start + int(ev.duration_ns)))
    return sorted(spans, key=lambda s: s[1])


def merge(ops) -> List[Interval]:
    """The union of [start, end) intervals, as disjoint sorted intervals."""
    merged: List[List[int]] = []
    for _, s, e in sorted(ops, key=lambda o: o[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(merged: List[Interval], lo: int, hi: int) -> int:
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def session_busy(planes, spans) -> Optional[List[Tuple[int, int]]]:
    """Per ``bench.session`` span: (span ns, device busy ns inside it),
    averaged over the device planes. None when the trace has no device
    operations."""
    if not planes:
        return None
    merged = [merge(ops) for ops in planes.values()]
    out = []
    for name, s, e in spans:
        if name == "bench.session":
            busy = sum(busy_ns(m, s, e) for m in merged) / len(merged)
            out.append((e - s, int(busy)))
    return out


def _label(spans, t: int) -> str:
    """The innermost harness span that holds time t."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0][len(SPAN_PREFIX):] if best else "between sessions"


def breakdown(planes, spans, lo: int, hi: int, top: int = 10
              ) -> Optional[dict]:
    """The traced window [lo, hi): the operations that took most device
    time (summed by name over the planes, divided by their count), and the
    longest idle gaps of the first device, each named by what the host was
    doing. Seconds, unrounded."""
    if not planes:
        return None
    by_name: Dict[str, int] = defaultdict(int)
    for ops in planes.values():
        for name, s, e in ops:
            by_name[name] += max(0, min(e, hi) - max(s, lo))
    n = len(planes)
    device = sorted(((k, v / n / 1e9) for k, v in by_name.items() if v),
                    key=lambda kv: -kv[1])[:top]
    first = merge(planes[sorted(planes)[0]])
    # a gap is cut at every span edge inside it, so each piece lies in one
    # span (or between sessions) and is named by it
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    gaps, cursor = [], lo
    for s, e in first + [(hi, hi)]:
        s, e = max(s, lo), min(e, hi)
        if s > cursor:
            cuts = [cursor] + [t for t in edges if cursor < t < s] + [s]
            gaps.extend(zip(cuts, cuts[1:]))
        cursor = max(cursor, e)
    idle = sorted(((_label(spans, (a + b) // 2), (b - a) / 1e9)
                   for a, b in gaps), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [list(x) for x in device],
            "idle_gaps": [list(x) for x in idle]}


def window_busy(planes, lo: int, hi: int) -> Optional[float]:
    """Seconds of [lo, hi) in which an operation ran, averaged over the
    device planes."""
    if not planes:
        return None
    return sum(busy_ns(merge(ops), lo, hi)
               for ops in planes.values()) / len(planes) / 1e9


SESSION_SPANS = ("bench.session", "bench.warm", "bench.probe")


def traced_window(spans) -> Optional[Tuple[int, int]]:
    """From the first traced session's start (window, warm-up or probe) to
    the last one's end."""
    s = [(a, b) for name, a, b in spans if name in SESSION_SPANS]
    if not s:
        return None
    return s[0][0], max(b for _, b in s)
