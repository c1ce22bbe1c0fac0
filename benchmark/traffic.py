"""The one traffic generator: a traffic file's parameters in, sessions out.

A traffic mix is ``benchmark/traffic/<name>.json``. Its ``mode`` names how
pending work reaches the cluster: the driver class ``Driver`` of
``benchmark/modes/<mode>.py``, found by that name as metric readers are,
so a new way of offering work is a new file. The rest of the traffic file
is numbers the mode reads.

Every mode draws sizes and times from fixed multisets that ``--seed`` only
reorders, so every seed asks for the same work. A mode may run a probe
session outside the window that has to take the device path; one that
leaves it ends the run before the window (``Refused``: exit code 2,
nothing printed). Between sessions, outside their spans, a driver checks
the session's output with the reference and collects garbage as the
production loop does (``LowLatencyGC.maintain``).
"""

from __future__ import annotations

import importlib.util
import os
from typing import List, Optional

from cluster import Cluster
from harness import CompileCounter, Fallbacks, Recorder, Session, new_cache
from reference import CHECKS, check_session

# the host span each phase's sessions run under; the device metrics read
# only the window's ("bench.session")
SPANS = {"window": "bench.session", "probe": "bench.probe"}


class Refused(Exception):
    """No result can be measured here (exit code 2, nothing printed)."""


class Driver:
    """Shared bookkeeping: the sessions run, their checks and compiles.

    The harness calls ``probe()`` (the trace starts before it, where the
    mode has one), then ``warm()``, then ``step()`` until the window closes,
    then ``finish()``; each step runs one session."""

    # every session should take the device path; a serial one is a fallback
    DEVICE_PATH = True
    # a device session outside the window, for a window that has none
    PROBE = False

    def __init__(self, cfg: dict, traffic: dict, seed: int, gc_policy):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.gc_policy = gc_policy
        self.records: List[dict] = []
        self.totals = {k: 0 for k in CHECKS}
        self.compiled = CompileCounter()

    # the program's objects, through this module's names (tests and the
    # control replace them here)
    @staticmethod
    def new_cache(recorder: Recorder):
        return new_cache(recorder)

    @staticmethod
    def new_session(cache, recorder: Recorder, policy: str):
        return Session(cache, recorder, policy)

    @staticmethod
    def new_fallbacks():
        return Fallbacks()

    def _session(self, cl: Cluster, sess, phase: str) -> dict:
        pending = sum(1 for t in cl.world.tasks.values() if not t.node)
        before = self.compiled.n
        rec = sess.run(SPANS.get(phase, "bench.warm"))
        rec["compiles"] = self.compiled.n - before
        rec["phase"] = phase
        rec["pending"] = pending
        rec["check"] = check_session(cl.world, rec["binds"], rec["evicts"])
        for k, v in rec["check"].items():
            self.totals[k] += v
        self.records.append(rec)
        return rec

    def probe(self) -> None:
        pass

    def warm(self) -> None:
        raise NotImplementedError

    def step(self, deadline: float) -> Optional[dict]:
        raise NotImplementedError

    def finish(self) -> None:
        pass

    def latencies(self, close: float) -> Optional[List[float]]:
        """Due-to-bind seconds of the tasks due in the window that closed at
        ``close`` (host clock); None for traffic without due times."""
        return None


def driver_class(root: str, mode: str):
    """The ``Driver`` of ``<root>/benchmark/modes/<mode>.py``."""
    path = os.path.join(root, "benchmark", "modes", mode + ".py")
    spec = importlib.util.spec_from_file_location(
        "mode_" + mode.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Driver
