"""The session the window times, and the effectors that record its output.

``Session.run`` is ``Scheduler.run_once`` as the production loop calls it
(load conf, ``open_session``, ``run_actions``, ``close_session``), with the
harness's own spans around each call: host-clock times always, and
``jax.profiler.TraceAnnotation`` spans that a traced run lines up with the
device's operations. Each session starts and ends on a drained device
(``devprof.drain``), so no queued work crosses a span's edge.

The binder and evictor are the harness's own: the program calls them as it
would call the API server, and they record each key with the host time of
the call. The reference reads only what they recorded.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple


class Recorder:
    """Binder, evictor and status updater in one: records, never refuses."""

    # the bulk writeback may hand over keys without pod objects
    KEYED_NEEDS_PODS = False

    def __init__(self):
        self.binds: List[Tuple[str, str, float]] = []
        self.evicts: List[Tuple[str, float]] = []

    @staticmethod
    def _key(pod) -> str:
        return f"{pod.metadata.namespace}/{pod.metadata.name}"

    def bind(self, pod, hostname: str) -> None:
        self.binds.append((self._key(pod), hostname, time.perf_counter()))

    def bind_many(self, pairs) -> None:
        now = time.perf_counter()
        self.binds.extend((self._key(p), h, now) for p, h in pairs)

    def bind_many_keyed(self, keys, pods, hosts) -> None:
        now = time.perf_counter()
        self.binds.extend((k, h, now) for k, h in zip(keys, hosts))

    def evict(self, pod, reason: str = "") -> None:
        self.evicts.append((self._key(pod), time.perf_counter()))

    # status writeback at close: nothing to record
    def update_pod_condition(self, pod, condition) -> None:
        pass

    def update_pod_group(self, pod_group, status=None) -> None:
        pass


class CompileCounter:
    """XLA backend compiles, counted from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1


def new_cache(recorder: Recorder):
    from volcano_tpu.scheduler.cache import SchedulerCache

    return SchedulerCache(binder=recorder, evictor=recorder,
                          status_updater=recorder)


class Fallbacks:
    """Host fallbacks the device path recorded, as chip_smoke.py reads
    them: the session profile's ``fallback``/``*_fallback`` keys, replica
    rebuilds that failed, and the process counters (read as deltas)."""

    def __init__(self):
        self._base = self._counters()

    @staticmethod
    def _counters() -> Tuple[int, int]:
        from volcano_tpu.scheduler import degrade, metrics

        return (metrics.registry().device_fallbacks.total(),
                degrade.default_ladder().counters["per_action_fallbacks"])

    def of(self, profile: dict) -> Dict[str, object]:
        found = {k: v for k, v in profile.items()
                 if k == "fallback" or k.endswith("_fallback")}
        errors = sorted(str(r) for r in (profile.get("replica_rebuilds") or {})
                        if str(r).startswith("error:"))
        if errors:
            found["replica_rebuilds"] = errors
        now = self._counters()
        if now != self._base:
            found["counters"] = [a - b for a, b in zip(now, self._base)]
            self._base = now
        if profile.get("mode") != "rounds":
            found["mode"] = profile.get("mode")
        return found


class Session:
    """One scheduler and its cache; ``run`` is one timed session."""

    def __init__(self, cache, recorder: Recorder, policy: str):
        from volcano_tpu.scheduler.scheduler import Scheduler

        self.cache = cache
        self.recorder = recorder
        self.scheduler = Scheduler(cache, scheduler_conf=policy)

    def run(self, span: str = "bench.session") -> dict:
        from jax.profiler import TraceAnnotation

        from volcano_tpu.scheduler import metrics
        from volcano_tpu.scheduler.framework import (
            close_session, open_session, run_actions)
        from volcano_tpu.utils import devprof

        sched = self.scheduler
        rec = self.recorder
        nb, ne = len(rec.binds), len(rec.evicts)
        devprof.drain()
        devc: dict = {}
        with TraceAnnotation(span):
            t0 = time.perf_counter()
            with TraceAnnotation("bench.open"):
                sched.load_conf()
                ssn = open_session(self.cache, sched.tiers)
            t1 = time.perf_counter()
            try:
                with TraceAnnotation("bench.actions"), devprof.session(devc):
                    action_ms = run_actions(ssn, sched.actions)
                for name, ms in action_ms.items():
                    metrics.update_action_duration(name, ms / 1e3)
            finally:
                t2 = time.perf_counter()
                tpu = ssn.plugins.get("tpuscore")
                profile = dict(tpu.profile) if tpu is not None else {}
                with TraceAnnotation("bench.close"):
                    close_session(ssn)
                    devprof.drain()
            t3 = time.perf_counter()
        metrics.update_e2e_duration(t3 - t0)
        profile.update(devc)
        return {"t0": t0, "t1": t1, "t2": t2, "t3": t3,
                "open_s": t1 - t0, "actions_s": t2 - t1, "close_s": t3 - t2,
                "total_s": t3 - t0, "profile": profile,
                "binds": [(k, h) for k, h, _ in rec.binds[nb:]],
                "bind_times": {k: t for k, _, t in rec.binds[nb:]},
                "evicts": [k for k, _ in rec.evicts[ne:]]}
