"""``rebuild``: every session runs on a freshly built copy of the whole
configuration (running fill included), for sessions whose effects (the
evictions) the watch surface cannot undo."""

from __future__ import annotations

import gc
import time

import traffic
from cluster import Cluster, stream
from harness import Recorder


class Driver(traffic.Driver):
    def __init__(self, *a):
        super().__init__(*a)
        self.n = 0
        self._build()

    def _build(self) -> None:
        self.cl = self.sess = None
        gc.collect()
        self.rec = Recorder()
        self.cl = Cluster(self.cfg, stream(self.seed, "rebuild", self.n)
                          .getrandbits(63), self.new_cache(self.rec))
        self.cl.populate(self.cl.add_nodes())
        self.sess = self.new_session(self.cl.cache, self.rec,
                                     self.cfg["policy"])
        self.n += 1

    def warm(self) -> None:
        self._session(self.cl, self.sess, "warm")
        self._build()

    def step(self, deadline: float) -> dict:
        rec = self._session(self.cl, self.sess, "window")
        if time.perf_counter() < deadline:
            self._build()
        return rec
