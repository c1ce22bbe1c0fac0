#!/usr/bin/env python3
"""A cell's compared numbers over many seeds in one process: each sound
session's, and each with a fault planted where the session's output is
produced.

    python3 benchmark/faults.py --workload cfg4.preempt --seeds 1,2,3

Per seed it builds the cell's cluster and runs one window session of the
cell's own traffic at the cell's own size (the first seed's also loads or
compiles the programs), then prints the reference's numbers for what the
session bound and evicted, and for the same session with every fault of
``FAULTS`` planted at its evictor. The benchmark's own runs never run it;
PERF.md records what it printed and the limits set from it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List

import run as bench
import traffic
from cluster import World
from reference import check_session, permitted_victims, preemptor_priority


def evict_all(world: World, evicts: List[str]) -> List[str]:
    """The evictor also receives every other permitted victim: each running
    gang loses every member above its minMember that some pending gang of
    its queue outranks."""
    taken = set(evicts)
    lost = {}
    for key in taken:
        t = world.tasks.get(key)
        if t is not None:
            lost[t.gang] = lost.get(t.gang, 0) + 1
    extra = []
    for key in permitted_victims(world, preemptor_priority(world)):
        if key in taken:
            continue
        t = world.tasks[key]
        g = world.gangs[t.gang]
        alive = sum(1 for k in g.keys if world.tasks[k].node)
        if alive - lost.get(t.gang, 0) > g.min_member:
            lost[t.gang] = lost.get(t.gang, 0) + 1
            extra.append(key)
    return list(evicts) + extra


FAULTS = {"evict_all": evict_all}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    _, cell, cfg, tr = bench.load_cell(bench.ROOT, args.workload)
    try:
        bench.check_device(int(cell["chips"]))
    except bench.Refused as e:
        print(f"faults: {e}", file=sys.stderr)
        return 2
    bench.enable_compile_cache()

    from volcano_tpu import _native
    from volcano_tpu.utils.gcpolicy import LowLatencyGC

    _native.get_fastapply()
    _native.get_fasttrans()
    policy = LowLatencyGC.install()
    mode = traffic.driver_class(bench.ROOT, tr["mode"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        drv = mode(cfg, tr, seed, policy)
        drv.probe()
        rec = drv.step(time.perf_counter())
        world = drv.cl.world
        out = {"workload": args.workload, "seed": seed,
               "session_s": rec["total_s"], "binds": len(rec["binds"]),
               "evicts": len(rec["evicts"]),
               "mode": rec["profile"].get("mode"), "sound": rec["check"]}
        for name, fault in FAULTS.items():
            out[name] = check_session(world, rec["binds"],
                                      fault(world, rec["evicts"]))
        out["wall_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        drv = rec = world = None
    policy.uninstall()
    return 0


if __name__ == "__main__":
    sys.exit(main())
