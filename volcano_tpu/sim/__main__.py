"""CLI: ``python -m volcano_tpu.sim run <scenario> --seed 7``.

Emits a bench-style JSON summary as the LAST stdout line (the driver-tail
contract bench.py follows): sessions/sec, per-phase latency percentiles,
binds/evictions, fault and audit tallies, and the replayable event-log
hash — same scenario + same seed ⇒ identical hash. Exit code 1 when the
auditor recorded violations (repro bundles under --repro-dir), so CI can
gate on a chaos soak with plain shell.
"""

from __future__ import annotations

import argparse
import json
import sys

from volcano_tpu.sim.harness import SimCluster
from volcano_tpu.sim.workload import (
    list_scenarios,
    load_scenario,
    scale_scenario,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m volcano_tpu.sim",
        description="virtual-time cluster simulator (docs/DESIGN.md §12)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    runp = sub.add_parser("run", help="run a scenario")
    runp.add_argument("scenario",
                      help="scenario file path, or a committed scenario "
                           "name (see 'list')")
    runp.add_argument("--seed", type=int, default=1)
    runp.add_argument("--scale", type=float, default=1.0,
                      help="uniform cluster/workload scale factor")
    runp.add_argument("--duration", type=float, default=None,
                      help="override the scenario's simulated horizon "
                           "(seconds)")
    runp.add_argument("--repro-dir", default="sim_repro",
                      help="where audit-violation repro bundles land "
                           "('' disables)")
    runp.add_argument("--json", dest="json_out", default=None,
                      help="also write the summary to this file")
    runp.add_argument("--quiet", action="store_true",
                      help="suppress the stderr progress line")

    sub.add_parser("list", help="list committed scenarios")
    args = ap.parse_args(argv)

    if args.cmd == "list":
        for name in list_scenarios():
            print(name)
        return 0

    cfg = scale_scenario(load_scenario(args.scenario), args.scale)
    sim = SimCluster(cfg, seed=args.seed,
                     repro_dir=args.repro_dir or None)
    summary = sim.run(duration=args.duration)
    if not args.quiet:
        print(
            f"[sim] {summary['scenario']} seed={summary['seed']} "
            f"scale={summary['scale']}: {summary['sessions']} sessions "
            f"in {summary['wall_s']}s wall "
            f"({summary['sim_duration_s']}s simulated), "
            f"binds={summary['binds']} evictions={summary['evictions']} "
            f"violations={summary['audit']['violations']} "
            f"hash={summary['event_log_hash'][:16]}",
            file=sys.stderr)
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(summary, fh, indent=1)
            fh.write("\n")
    print(json.dumps(summary, separators=(",", ":")), flush=True)
    return 1 if summary["audit"]["violations"] else 0


if __name__ == "__main__":
    from volcano_tpu.utils.jaxcompile import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
