"""Spans at the session's layer boundaries, on the profiler's clock.

``span(name, into=None, **counts)`` is a context manager over
``jax.profiler.TraceAnnotation``: the span ``vt.<name>`` lands on the host
plane of a profiler capture, on the same clock as the device's ``XLA Ops``,
so each idle or busy stretch of the device can be put down to the code the
host was running. Counts (tasks, nodes, jobs, bytes) become annotation
arguments only while a capture is on; ``note(**counts)`` adds counts known
only at the end. ``into=(profile, key)`` adds the span's elapsed seconds to
a profile key, so a profile number and its span are one clock reading at
the same boundaries. ``start`` (``time.perf_counter``) and ``elapsed``
(seconds, after exit) are kept whether or not a capture is on. Without JAX
nothing is annotated; the timing still works.

Spans that ran while a capture was on are also kept, newest last, as
``(name, start s, end s)`` on the ``time.perf_counter`` clock:
``recorded()`` reads them in-process without parsing a trace file.

``step(n)`` is the span of one scheduler session (``vt.session``), marked
as profiler step ``n`` so a capture groups its spans by session.

Rule: spans mark layer boundaries. No span goes inside a per-node loop, or
inside a per-task loop of the device path. The finest span allowed is one
serial predicate or prioritize call, which covers one task against all
nodes.
"""

from __future__ import annotations

import collections
import sys
import time

PREFIX = "vt."

# spans closed while a capture was on; bounded, so an operator's long live
# capture cannot grow the scheduler's heap without limit
_recorded = collections.deque(maxlen=1 << 16)

# jax.profiler, once some other module has imported JAX: no capture can be
# on before that, and the scheduler's host-only paths never import it here
_profiler = None


def enabled() -> bool:
    """Is a profiler capture on?"""
    global _profiler
    if _profiler is None:
        _profiler = sys.modules.get("jax.profiler")
        if _profiler is None:
            return False
    return _profiler.TraceAnnotation.is_enabled()


class span:
    __slots__ = ("name", "into", "counts", "start", "elapsed", "_ann")

    def __init__(self, name: str, into=None, **counts):
        self.name = PREFIX + name
        self.into = into
        self.counts = counts
        self.elapsed = 0.0
        self._ann = None

    def _annotation(self):
        return _profiler.TraceAnnotation(self.name, **self.counts)

    def __enter__(self) -> "span":
        if enabled():
            self._ann = self._annotation()
            self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def note(self, **counts) -> None:
        """Counts known only once the span's work is done."""
        if self._ann is not None:
            self._ann.set_metadata(**counts)

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self.elapsed = t1 - self.start
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
            _recorded.append((self.name, self.start, t1))
        if self.into is not None:
            profile, key = self.into
            profile[key] = profile.get(key, 0.0) + self.elapsed
        return False


class step(span):
    """One scheduler session, ``vt.session``, as profiler step ``n``."""

    __slots__ = ("n",)

    def __init__(self, n: int):
        super().__init__("session")
        self.n = n

    def _annotation(self):
        return _profiler.StepTraceAnnotation(self.name, step_num=self.n)


def recorded() -> list:
    """[(name, start s, end s)] of the spans closed while a capture was on,
    oldest first."""
    return list(_recorded)
