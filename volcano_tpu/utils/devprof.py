"""Device-interaction profiler: sync points, D2H fetches, overlap wall.

JAX dispatch is async on every backend, so a host clock read around a
dispatch measures queueing, not compute. This module is the ONE place
host<->device synchronization happens so it can be counted, and each wait
is a ``vt.device.wait`` span (utils/trace.py):

- ``start_fetch(x)`` begins the D2H copy immediately (``copy_to_host_async``
  when the array supports it) and returns a wait closure; the span between
  the two calls is host work OVERLAPPED with device compute/transfer and is
  accumulated into ``overlap_s``. The wait itself is a counted sync point.
- ``fence(x=None)`` is an explicit ``block_until_ready`` barrier — with no
  argument it drains every in-flight array registered by ``start_fetch``.
  The benchmark drains around each timed session so a timed window can
  never inherit queued work from its predecessor.
- ``session(profile)`` scopes the counters to one scheduler session; the
  collector lands ``tpu_sync_points`` / ``tpu_d2h_fetches`` /
  ``tpu_overlap_ms`` in the session profile.

The counters are honest only because every dispatch site in ops/ routes its
fetch through here (vclint VT006 guards the donation half of the contract).
Single-threaded by design, like the session loop that owns it.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional

import numpy as np

from volcano_tpu.utils import trace

# the active collector (one scheduler session at a time); counters are
# module-level so call sites need no plumbing through the action stack
_active: Optional[dict] = None

# in-flight device arrays with pending fetches/dispatches, for fence();
# entries are dropped once waited on
_inflight: List = []


def _forget(x) -> None:
    """Drop ``x`` from the in-flight list by IDENTITY. list.remove would
    compare elements with ``==``, which on device arrays broadcasts (and
    raises outright for mismatched shapes — real the moment two solves
    of different buckets are in flight, e.g. a speculative solve-ahead
    behind an uncollected predecessor)."""
    for i, t in enumerate(_inflight):
        if t is x:
            del _inflight[i]
            return


class _Collector(object):
    """Context manager installing a per-session counter dict."""

    def __init__(self, profile: dict):
        self.profile = profile
        self._prev: Optional[dict] = None

    def __enter__(self) -> dict:
        global _active
        self._prev = _active
        _active = {"sync_points": 0, "d2h_fetches": 0, "overlap_s": 0.0,
                   "fence_wait_s": 0.0, "overlappable_dispatches": 0,
                   "overlappable_rows": 0}
        return _active

    def __exit__(self, *exc) -> None:
        global _active
        counters, _active = _active, self._prev
        if counters is not None and self.profile is not None:
            self.profile["tpu_sync_points"] = counters["sync_points"]
            self.profile["tpu_d2h_fetches"] = counters["d2h_fetches"]
            self.profile["tpu_overlap_ms"] = round(
                counters["overlap_s"] * 1e3, 3)
            self.profile["tpu_fence_wait_ms"] = round(
                counters["fence_wait_s"] * 1e3, 3)
            self.profile["tpu_overlappable_dispatches"] = \
                counters["overlappable_dispatches"]
            self.profile["tpu_overlappable_rows"] = \
                counters["overlappable_rows"]


def session(profile: dict) -> _Collector:
    """Scope the counters to one session; results land in ``profile``."""
    return _Collector(profile)


def counters() -> Optional[dict]:
    """The live counter dict, or None outside any session scope."""
    return _active


def start_fetch(x) -> Callable[[], np.ndarray]:
    """Begin fetching device array ``x``; returns wait() -> np.ndarray.

    The copy starts NOW (overlapping whatever host work runs before wait),
    and the wait is the session's counted sync point. Works on plain
    numpy/host arrays too (wait degenerates to np.asarray) so callers never
    need a backend check.
    """
    t0 = time.perf_counter()  # the overlap window opens at dispatch
    if _active is not None:
        _active["d2h_fetches"] += 1
    copy_async = getattr(x, "copy_to_host_async", None)
    if copy_async is not None:
        try:
            copy_async()
        except Exception:  # pragma: no cover - backend without async copy
            pass
    _inflight.append(x)

    def wait() -> np.ndarray:
        with trace.span("device.wait", kind="fetch") as sp:
            out = np.asarray(x)
        if _active is not None:
            _active["sync_points"] += 1
            _active["overlap_s"] += sp.start - t0
            _active["fence_wait_s"] += sp.elapsed
        _forget(x)
        return out

    return wait


def note_overlappable(rows: int = 0) -> None:
    """Count an async device dispatch whose result is never fetched or
    fenced by its issuer — the replica's row scatters (ops/replica.py):
    the scatter enqueues, the session's host work continues, and the
    buffers are consumed device-side by the next solve. These are the
    opposite of sync points — item 1's floor attribution subtracts them
    from the h2d traffic a real-TPU session would have to hide."""
    if _active is not None:
        _active["overlappable_dispatches"] += 1
        _active["overlappable_rows"] += int(rows)


def register(x) -> None:
    """Track a dispatched array so a later fence() drains it (for results
    that are consumed device-side rather than fetched)."""
    _inflight.append(x)


def discard(x) -> None:
    """Forget a dispatched array WITHOUT fetching it — the pipeline's
    invalidated speculative results: the device work is abandoned, the
    value is never read (the never-applied contract), and later fence()
    calls no longer wait on it."""
    _forget(x)


def fence(x=None) -> None:
    """Explicit block_until_ready barrier (a counted sync point).

    With an argument, blocks on that array/pytree; with none, drains every
    registered in-flight array. Placed only at profiling/apply boundaries —
    the overlap scheme depends on everything else staying async.
    """
    blocked = False
    targets = [x] if x is not None else list(_inflight)
    if not targets:
        return
    with trace.span("device.wait", kind="fence") as sp:
        for t in targets:
            block = getattr(t, "block_until_ready", None)
            try:
                if block is not None:
                    block()
                else:
                    np.asarray(t)
                blocked = True
            except Exception:  # pragma: no cover - deleted/donated buffers
                pass
            if x is None:
                _forget(t)
    if _active is not None and blocked:
        _active["sync_points"] += 1
        _active["fence_wait_s"] += sp.elapsed


def drain() -> None:
    """fence() alias for benchmark call sites: drain all in-flight work."""
    fence(None)
