#!/usr/bin/env python3
"""The control: the reference put in the program's place, with one
guarantee broken, driven through a cell's own traffic.

    python3 benchmark/control.py --workload cfg5.backlog --seeds 1,2,3 --seconds 20

The scheduler's session is replaced by the reference's first-fit placement
of every pending gang with the cpu check left out (memory and pod count
still checked): the shortcut of a placement that trusts one dimension too
few. Every configuration states that no node goes over its cpu; the
control has to read as not correct on the cell's own cluster and traffic.
It prints one line per seed with the numbers the benchmark compares. The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench
import traffic


class ControlSession:
    """Takes a session's place: same record, placement by the reference."""

    def __init__(self, cache, recorder, policy: str):
        self.recorder = recorder
        self.world = None

    def run(self, span: str = "bench.session") -> dict:
        world = self.world
        free = {}
        for name, (cpu, mem, pods) in world.nodes.items():
            free[name] = [mem, pods]
        for t in world.tasks.values():
            if t.node:
                free[t.node][0] -= t.mem
                free[t.node][1] -= 1
        order = sorted(free)
        keys, hosts = [], []
        t0 = time.perf_counter()
        for g in world.gangs.values():
            for k in g.keys:
                t = world.tasks[k]
                if t.node:
                    continue
                for name in order:
                    f = free[name]
                    if f[0] >= t.mem and f[1] >= 1:
                        f[0] -= t.mem
                        f[1] -= 1
                        keys.append(k)
                        hosts.append(name)
                        break
        nb = len(self.recorder.binds)
        self.recorder.bind_many_keyed(keys, None, hosts)
        t1 = time.perf_counter()
        new = self.recorder.binds[nb:]
        return {"t0": t0, "t1": t0, "t2": t1, "t3": t1, "open_s": 0.0,
                "actions_s": t1 - t0, "close_s": 0.0, "total_s": t1 - t0,
                "profile": {}, "compiles": 0,
                "binds": [(k, h) for k, h, _ in new],
                "bind_times": {k: t for k, _, t in new}, "evicts": []}


class NoDevicePath:
    """The control has no device path to leave: a probe passes it."""

    def of(self, profile: dict) -> dict:
        return {}


def install() -> None:
    """Put the control in every driver's session."""
    orig = traffic.Driver._session

    def _session(self, cl, sess, phase):
        sess.world = cl.world
        return orig(self, cl, sess, phase)

    traffic.Session = ControlSession
    traffic.Fallbacks = NoDevicePath
    traffic.Driver._session = _session


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _, cell, cfg, tr = bench.load_cell(bench.ROOT, args.workload)
    install()
    from volcano_tpu.utils.gcpolicy import LowLatencyGC

    policy = LowLatencyGC.install()
    for seed in (int(s) for s in args.seeds.split(",")):
        drv = traffic.driver_class(bench.ROOT, tr["mode"])(cfg, tr, seed,
                                                             policy)
        drv.probe()
        drv.warm()
        end = time.perf_counter() + args.seconds
        n = 0
        while time.perf_counter() < end and drv.step(end) is not None:
            n += 1
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "sessions": n, "checks": drv.totals}), flush=True)
    policy.uninstall()
    return 0


if __name__ == "__main__":
    sys.exit(main())
