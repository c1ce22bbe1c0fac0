"""The program's own spans (``vt.*``, volcano_tpu/utils/trace.py) in a
traced run, per window session.

The program keeps every span that closed while a capture was on, as
``(name, start s, end s)`` on the ``time.perf_counter`` clock, the clock
of the harness's session records (``t0`` open, ``t1`` actions, ``t2``
close, ``t3`` end). A traced window session is one that holds such a span.
A program without that record (an older checkout) gives nothing, and every
metric read here is then left out.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import devtrace

Span = Tuple[str, float, float]

# the part of a session a metric may be limited to: (start key, end key)
PHASES = {"session": ("t0", "t3"), "actions": ("t1", "t2")}


def recorded() -> Optional[List[Span]]:
    try:
        from volcano_tpu.utils import trace
    except ImportError:
        return None
    return trace.recorded()


def covered(spans: List[Span], name: str, lo: float, hi: float) -> float:
    """Seconds of [lo, hi) inside some span named ``name``; nested or
    overlapping repeats count once."""
    return devtrace.busy_ns(devtrace.merge(s for s in spans if s[0] == name),
                            lo, hi)


def traced(sessions, spans: List[Span]) -> list:
    """The window sessions that hold a recorded span."""
    return [r for r in sessions
            if any(r["t0"] <= s and e <= r["t3"] for _, s, e in spans)]


def span_ms(sessions, spans: Optional[List[Span]], name: str,
            phase: str = "session") -> Optional[float]:
    """Mean over the traced window sessions of the time, in ms, covered by
    spans named ``name`` inside the session's ``phase``; None when no
    traced session holds such a span."""
    if not spans:
        return None
    lo_key, hi_key = PHASES[phase]
    mine = [s for s in spans if s[0] == name]
    per, ran = [], False
    for r in traced(sessions, spans):
        lo, hi = r[lo_key], r[hi_key]
        ran = ran or any(lo <= s and e <= hi for _, s, e in mine)
        per.append(covered(mine, name, lo, hi))
    return sum(per) / len(per) * 1e3 if ran else None


def read(run, name: str, phase: str = "session") -> Optional[float]:
    """``span_ms`` of a run's window sessions and the program's record."""
    return span_ms(run.sessions, recorded(), name, phase)
