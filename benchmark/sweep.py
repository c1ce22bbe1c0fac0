#!/usr/bin/env python3
"""The one-off rate sweep that fixed an open-loop cell's arrival rate.

    python3 benchmark/sweep.py --workload cfg5.steady --rates 2,4,8 --seconds 30

Runs the cell's traffic at each rate in turn, in this one process, and
prints per rate the sessions' pending counts and times, how many sessions
crossed onto the device, and whether the pending backlog
grew over the window. The benchmark's runs never call it; PERF.md records
what it printed and the rate chosen from it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    _, cell, cfg, traffic = bench.load_cell(bench.ROOT, args.workload)
    try:
        bench.check_device(int(cell["chips"]))
    except bench.Refused as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    bench.enable_compile_cache()

    from traffic import driver_class
    from volcano_tpu import _native
    from volcano_tpu.utils.gcpolicy import LowLatencyGC

    _native.get_fastapply()
    _native.get_fasttrans()
    policy = LowLatencyGC.install()
    for rate in (float(r) for r in args.rates.split(",")):
        t0 = time.perf_counter()
        drv = driver_class(bench.ROOT, traffic["mode"])(
            cfg, dict(traffic, rate_gangs_per_s=rate), args.seed, policy)
        drv.probe()
        drv.warm()
        window = []
        end = time.perf_counter() + args.seconds
        while time.perf_counter() < end:
            rec = drv.step(end)
            if rec is None:
                break
            window.append(rec)
        lat = sorted(drv.latencies(time.perf_counter()))
        pend = [r["pending"] for r in window]
        third = max(len(pend) // 3, 1)
        print(json.dumps({
            "rate_gangs_per_s": rate, "sessions": len(window),
            "pending": pend, "session_s": [round(r["total_s"], 4)
                                           for r in window],
            "device_sessions": sum(
                1 for r in window if r["profile"].get("mode") == "rounds"),
            "backlog_first_third": sum(pend[:third]) / third,
            "backlog_last_third": sum(pend[-third:]) / third,
            "p95_ms": lat[int(0.95 * len(lat)) - 1] * 1e3 if lat else None,
            "due": len(lat), "checks": drv.totals,
            "wall_s": time.perf_counter() - t0}), flush=True)
        del drv
    policy.uninstall()
    return 0


if __name__ == "__main__":
    sys.exit(main())
