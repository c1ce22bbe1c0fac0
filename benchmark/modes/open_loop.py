"""``open_loop``: the configuration's groups start Running with a remaining
service time each; gangs of the shape of the class named ``arrivals`` (a
group prefix of the configuration) arrive as a Poisson process of
``rate_gangs_per_s``, run for an exponential service time whose mean keeps
the population at the configuration's size (Little's law) and then leave.
Sessions run every ``period_s``, back to back when one overruns; events
that fall due during a session are delivered when it closes. The window
opens after ``warm_s`` of this traffic.

Such a window's sessions stay under the device gate, so a traced run would
show no device work. Before the warm-up, with the arrival clock stopped,
``probe_gangs`` gangs land at once and one session binds them on the
device (the trace starts before it); they leave as soon as it closes.
Their requests are the class's first draws, in a seeded order, and the
running population is the configuration's, so the probe's device program
has the same shapes in every run. Nothing of the probe is in the window.
"""

from __future__ import annotations

import heapq
import random
import time
from typing import Dict, List, Optional

import traffic
from cluster import Cluster, stream
from harness import Recorder

BLOCK = 64  # arrivals per block of gaps: every seed gets each block's gaps


class Driver(traffic.Driver):
    # sessions of a few dozen tasks stay under the device gate
    DEVICE_PATH = False
    PROBE = True

    def __init__(self, *a):
        super().__init__(*a)
        tr = self.traffic
        self.rate = float(tr["rate_gangs_per_s"])
        self.period = float(tr["period_s"])
        (cls,) = [c for c in self.cfg["groups"]
                  if c["prefix"] == tr["arrivals"]]
        self.cls = cls
        population = sum(c["count"] * c["size"] for c in self.cfg["groups"])
        self.mean_service = population / (self.rate * cls["size"])
        self.rec = Recorder()
        self.cl = Cluster(self.cfg, self.seed, self.new_cache(self.rec))
        self.node_names = self.cl.add_nodes()
        self.cl.populate(self.node_names, running_phase_for_all=True)
        self.sess = self.new_session(self.cl.cache, self.rec,
                                     self.cfg["policy"])
        self.shapes = [r for _, r in self.cl.gangs_of(cls, "arrivals")]
        self.service = self._unit_exp("service", len(self.shapes))
        # the running population's remaining service times
        self.origin = time.perf_counter()
        self.completions: List = []
        rest = self._unit_exp("remaining", len(self.cl.world.gangs))
        for name, r in zip(sorted(self.cl.world.gangs), rest):
            heapq.heappush(self.completions, (r * self.mean_service, name))
        self.n_arrived = 0
        self.next_arrival = self._gap(0)
        self.next_session = 0.0
        self.due: Dict[str, float] = {}       # task key -> due time
        self.bound_at: Dict[str, float] = {}  # task key -> bind time
        self.started: set = set()  # gangs whose service has begun
        self.arrivals_open = True

    def _unit_exp(self, what: str, n: int) -> List[float]:
        base = random.Random(f"{what}-base")
        vals = [base.expovariate(1.0) for _ in range(n)]
        stream(self.seed, what).shuffle(vals)
        return vals

    def _gap(self, i: int) -> float:
        b, j = divmod(i, BLOCK)
        base = random.Random(f"gaps-{b}")
        gaps = [base.expovariate(self.rate) for _ in range(BLOCK)]
        stream(self.seed, "gaps", b).shuffle(gaps)
        return gaps[j]

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def _arrive(self, due: float, shape=None) -> str:
        i = self.n_arrived
        self.n_arrived += 1
        g = self.cl.add_gang(f"arr-{i:07d}", self.cls,
                             shape or self.shapes[i % len(self.shapes)],
                             "Pending")
        for k in g.keys:
            self.due[k] = due
        return g.name

    def _deliver(self) -> None:
        """Every event due by now: arrivals and completions."""
        now = self.now()
        while self.arrivals_open and self.next_arrival <= now:
            self._arrive(self.next_arrival)
            self.next_arrival += self._gap(self.n_arrived)
        while self.completions and self.completions[0][0] <= now:
            _, name = heapq.heappop(self.completions)
            if name in self.cl.world.gangs:
                self.cl.delete_gang(name)

    def _bound(self, rec: dict) -> None:
        """A session's binds reach the World, and the store's echo (the pod
        now Running on its node) reaches the cache; a gang that became
        ready starts its service."""
        from volcano_tpu.api import objects

        world = self.cl.world
        for key, node in rec["binds"]:
            t = world.tasks.get(key)
            if t is None or t.node or node not in world.nodes:
                continue
            self.bound_at[key] = rec["bind_times"][key] - self.origin
            old = self.cl.pod_of(key, "", "Pending")
            new = self.cl.pod_of(key, node, objects.POD_PHASE_RUNNING)
            try:
                self.cl.cache.update_pod_from_watch(old, new)
            except (RuntimeError, KeyError):
                # a bind the node cannot hold (only a faulty session makes
                # one, and the reference counted it): the cache refuses
                # the pod as a kubelet would
                pass
            t.node = node
        for key, _ in rec["binds"]:
            t = world.tasks.get(key)
            if t is None or not t.node or t.gang in self.started:
                continue
            g = world.gangs[t.gang]
            if sum(1 for k in g.keys if world.tasks[k].node) >= g.min_member:
                start = self.bound_at[key]
                self.started.add(t.gang)
                i = int(t.gang.rsplit("-", 1)[1])
                service = self.service[i % len(self.service)]
                heapq.heappush(self.completions,
                               (start + service * self.mean_service, t.gang))

    def _cycle(self, deadline_rel: float, phase: str) -> Optional[dict]:
        """Deliver events until the next session is due (or the deadline
        passes), then run it."""
        while True:
            self._deliver()
            now = self.now()
            if now >= deadline_rel:
                return None
            if now >= self.next_session:
                break
            wake = min(self.next_session, deadline_rel,
                       self.completions[0][0] if self.completions
                       else deadline_rel,
                       self.next_arrival if self.arrivals_open
                       else deadline_rel)
            time.sleep(max(wake - now, 0.0))
        rec = self._session(self.cl, self.sess, phase)
        self._bound(rec)
        self.gc_policy.maintain()
        self.next_session += self.period
        self.next_session = max(self.next_session, self.now())
        return rec

    def probe(self) -> None:
        """The device session outside the window, with the arrival clock
        stopped; its gangs leave when it closes."""
        start = time.perf_counter()
        size = self.cls["size"]
        n = int(self.traffic["probe_gangs"])
        draws = self.cl.draws[self.cls["prefix"]][:n * size]
        stream(self.seed, "probe").shuffle(draws)
        names = [self.cl.add_gang(f"probe-{g:05d}", self.cls,
                                  draws[g * size:(g + 1) * size],
                                  "Pending").name
                 for g in range(n)]
        self._bound(self._session(self.cl, self.sess, "probe"))
        for name in names:
            self.cl.delete_gang(name)
        self.gc_policy.maintain()
        self.origin += time.perf_counter() - start

    def warm(self) -> None:
        end = self.now() + float(self.traffic["warm_s"])
        while self._cycle(end, "warm") is not None:
            pass
        self.window_start = self.now()

    def step(self, deadline: float) -> Optional[dict]:
        return self._cycle(deadline - self.origin, "window")

    def latencies(self, close: float) -> List[float]:
        """Due-to-bind seconds of every task due in the window; one still
        pending at the close counts at its age then."""
        close_rel = close - self.origin
        out = []
        for key, due in self.due.items():
            if self.window_start <= due < close_rel:
                bound = self.bound_at.get(key, close_rel)
                out.append(min(bound, close_rel) - due)
        return out

    def finish(self) -> None:
        """After the window: no more arrivals; sessions run on until every
        task that arrived is bound, for at most ``drain_s``."""
        self.arrivals_open = False
        end = self.now() + float(self.traffic["drain_s"])
        while any(k not in self.bound_at for k in self.due
                  if k in self.cl.world.tasks):
            if self._cycle(end, "drain") is None:
                break
