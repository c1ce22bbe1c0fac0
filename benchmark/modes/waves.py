"""``waves``: the configuration's pending groups land at once on its empty
nodes; one session binds them; then the wave completes (its pods and pod
groups leave through the watch handlers) and the next wave, with new names
and the same requests in another order, lands. The cache lives across the
window, as in a long-lived scheduler.

With ``path_probe_gangs`` in the traffic, wave 0 waits for a probe: the
first that many gangs of the configuration's seeded order (tagged
``probe``) land on the empty cluster and one session binds them under
``bench.probe``, checked by the reference. A probe that left the device
path ends the run there, before any full-size session; otherwise its
gangs leave and wave 0 lands. Without the key no probe runs."""

from __future__ import annotations

import json
import time

import traffic
from cluster import Cluster
from harness import Recorder


class Driver(traffic.Driver):
    def __init__(self, *a):
        super().__init__(*a)
        self.rec = Recorder()
        self.cl = Cluster(self.cfg, self.seed, self.new_cache(self.rec))
        self.node_names = self.cl.add_nodes()
        self.sess = self.new_session(self.cl.cache, self.rec,
                                     self.cfg["policy"])
        self.wave = 0

    def probe(self) -> None:
        n = self.traffic.get("path_probe_gangs")
        if n is not None:
            gangs = [(name, cls, reqs) for cls in self.cfg["groups"]
                     for name, reqs in self.cl.gangs_of(cls, "probe")][:int(n)]
            for name, cls, reqs in gangs:
                self.cl.add_gang(name, cls, reqs, "Pending")
            # chip_smoke.py's rule, as the window's fallbacks check reads it
            fallbacks = self.new_fallbacks()
            rec = self._session(self.cl, self.sess, "probe")
            off = fallbacks.of(rec["profile"])
            if off:
                raise traffic.Refused(
                    "the probe session left the device path: "
                    + json.dumps(off, default=str))
            for name, _, _ in gangs:
                self.cl.delete_gang(name)
            self.gc_policy.maintain()
        self.cl.populate(self.node_names, tag="w0")

    def _next_wave(self) -> None:
        for name in list(self.cl.world.gangs):
            self.cl.delete_gang(name)
        self.wave += 1
        self.cl.populate(self.node_names, tag=f"w{self.wave}")
        self.gc_policy.maintain()

    def warm(self) -> None:
        # the first session compiles; the second runs the incremental
        # snapshot and replica paths a long-lived cache takes
        for _ in range(2):
            self._session(self.cl, self.sess, "warm")
            self._next_wave()

    def step(self, deadline: float) -> dict:
        rec = self._session(self.cl, self.sess, "window")
        if time.perf_counter() < deadline:
            self._next_wave()
        return rec
