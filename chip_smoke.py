#!/usr/bin/env python
"""Chip smoke: the scheduler's session loop on one TPU at the headline size.

Runs in ONE process (a chip belongs to one process) and starts no child.

- Device check first: anything but a TPU exits non-zero, printing no result.
- Phase A: BASELINE.json cfg5 (50k tasks x 10k nodes) through the production
  ``Scheduler.run_once`` with ``example/scheduler-tpu.conf``, loaded the way
  ``python -m volcano_tpu.scheduler --scheduler-conf`` loads it: one cold
  cycle, then three warm cycles, each on a freshly populated identical
  cluster.
- Phase B: cfg4 (30k tasks x 8k nodes at overcommit) the same way with
  ``example/scheduler-tpu-preempt.conf`` (allocate, backfill, preempt,
  reclaim: the evict kernels and the fused chain).
- Parity (reported, never failed on): bindings that differ from the serial
  oracle on cfg5 at ``PARITY_SCALE``.

A phase fails when a cycle recorded any host fallback or did not run the
device rounds mode, when its output breaks an invariant (no binds, a node
over allocatable, a gang bound below minMember), or when a warm cycle
compiled. Per-cycle walls, binds and compiles print as observations.

``--chips 4`` runs only the multi-chip path: cfg5 at full scale with the node
axis sharded over four chips, against the same cluster on one chip; the
bindings must be identical and the shards must sit on all four chips.

The last stdout line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
CONF = os.path.join(REPO, "example", "scheduler-tpu.conf")
PREEMPT_CONF = os.path.join(REPO, "example", "scheduler-tpu-preempt.conf")
WARM_CYCLES = 3
# cfg5 x 0.2 = 10k tasks x 2k nodes: the serial loop takes about a minute
PARITY_SCALE = 0.2


class SmokeFailure(RuntimeError):
    pass


def _say(**obs) -> None:
    print(json.dumps(obs, default=str), flush=True)


def check_device():
    """(platform, kind, count); exits non-zero off the TPU."""
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {d.platform}); nothing run",
              file=sys.stderr)
        sys.exit(2)
    return d.platform, d.device_kind, len(devs)


def fallbacks(profile: dict) -> dict:
    """Every host fallback a cycle recorded, by where it was recorded;
    empty when the cycle ran on the device path throughout."""
    from volcano_tpu.scheduler import degrade, metrics

    found = {k: v for k, v in profile.items()
             if k == "fallback" or k.endswith("_fallback")}
    errors = sorted(r for r in (profile.get("replica_rebuilds") or {})
                    if str(r).startswith("error:"))
    if errors:
        found["replica_rebuilds"] = errors
    total = metrics.registry().device_fallbacks.total()
    if total:
        found["register_fallback_total"] = total
    per_action = degrade.default_ladder().counters["per_action_fallbacks"]
    if per_action:
        found["per_action_fallbacks"] = per_action
    if profile.get("mode") != "rounds":
        found["mode"] = profile.get("mode")
    return found


def snapshot(cache) -> dict:
    """What the invariant checks need from the cluster before a cycle."""
    jobs = {}
    for uid, job in cache.jobs.items():
        jobs[uid] = (job.min_available, [
            (t.key, t.node_name, t.resreq.clone())
            for t in job.tasks.values()])
    nodes = {name: n.allocatable.clone() for name, n in cache.nodes.items()
             if n.node is not None}
    return {"jobs": jobs, "nodes": nodes}


def audit(before: dict, binds: dict, evicts) -> list:
    """Invariant violations of one cycle's output (sim/auditor.py's
    node_overcommit and gang_atomicity rules over the binder's record).
    Evicted pods still hold their node: they leave only after this cycle."""
    from volcano_tpu.api.resource import Resource

    problems = []
    if not binds:
        problems.append("zero binds")
    evicted = set(evicts)
    used = {name: Resource.empty() for name in before["nodes"]}
    count = dict.fromkeys(before["nodes"], 0)
    for min_available, tasks in before["jobs"].values():
        bound = 0
        for key, node, resreq in tasks:
            target = node or binds.get(key)
            if node and key in binds:
                problems.append(f"{key} bound again (already on {node})")
            if not target:
                continue
            bound += 1
            if target not in used:
                problems.append(f"{key} on unknown node {target}")
                continue
            used[target].add(resreq)
            count[target] += 1
        if not evicted.intersection(k for k, _, _ in tasks) \
                and 0 < bound < min_available:
            problems.append(
                f"gang {tasks[0][0]}: {bound} bound < minMember "
                f"{min_available}")
    for name, alloc in before["nodes"].items():
        if not used[name].less_equal(alloc):
            problems.append(f"node {name} over allocatable")
        if alloc.max_task_num and count[name] > alloc.max_task_num:
            problems.append(f"node {name} holds {count[name]} pods > "
                            f"{alloc.max_task_num}")
    return problems


def populate(cfg: int, scale: float):
    from volcano_tpu.bench.clusters import CONFIGS, make_cache

    cache = make_cache()
    n_tasks = CONFIGS[cfg].populate(cache, scale)
    return cache, n_tasks


def run_cycle(cfg: int, scale: float, conf_path: str, mesh=None):
    """One production cycle on a freshly populated cluster: (observations,
    bindings, cache). Raises SmokeFailure on a fallback or an invariant
    violation."""
    from volcano_tpu.scheduler.scheduler import Scheduler
    from volcano_tpu.utils.jaxcompile import CompileWatcher

    t0 = time.perf_counter()
    cache, n_tasks = populate(cfg, scale)
    before = snapshot(cache)
    populate_s = time.perf_counter() - t0
    sched = Scheduler(cache, conf_path=conf_path, mesh=mesh)
    win = CompileWatcher.install().window()
    t1 = time.perf_counter()
    sched.run_once()
    wall_s = time.perf_counter() - t1
    cs = win.delta()
    prof = sched.last_profile
    binds = dict(cache.binder.binds)
    evicts = list(cache.evictor.evicts)
    out = {"cfg": cfg, "scale": scale, "tasks": n_tasks,
           "nodes": len(before["nodes"]), "populate_s": populate_s,
           "wall_s": wall_s, "binds": len(binds), "evicts": len(evicts),
           "compiles": cs.compiles, "compile_s": cs.compile_s,
           "mode": prof.get("mode"), "rounds": prof.get("rounds"),
           # where the cycle's time went, as the device path recorded it
           "profile": {k: v for k, v in prof.items()
                       if k.endswith("_s") or k.startswith("evict_")}}
    fb = fallbacks(prof)
    if fb:
        raise SmokeFailure(f"cfg{cfg}: host fallback {fb} ({out})")
    problems = audit(before, binds, evicts)
    if problems:
        raise SmokeFailure(
            f"cfg{cfg}: {len(problems)} invariant violations, first "
            f"{problems[:5]} ({out})")
    return out, binds, cache


def phase(name: str, cfg: int, conf_path: str, scale: float = 1.0) -> None:
    """One cold cycle, then WARM_CYCLES warm ones on identical clusters:
    bindings must repeat and no warm cycle may compile. Each cluster is
    dropped before the next is built (a second live cluster would double
    the heap the next cycle's collections walk)."""
    cold, ref = run_cycle(cfg, scale, conf_path)[:2]
    _say(phase=name, cycle="cold", **cold)
    for i in range(WARM_CYCLES):
        gc.collect()
        warm, binds = run_cycle(cfg, scale, conf_path)[:2]
        _say(phase=name, cycle=f"warm{i + 1}", **warm)
        if warm["compiles"]:
            raise SmokeFailure(
                f"phase {name}: warm cycle {i + 1} compiled "
                f"{warm['compiles']} programs ({warm['compile_s']} s)")
        if binds != ref:
            raise SmokeFailure(
                f"phase {name}: warm cycle {i + 1} bound differently from "
                f"the cold cycle on an identical cluster")
    gc.collect()


def parity(scale: float = PARITY_SCALE) -> None:
    """Bindings that differ between the device path and the serial oracle
    (the host default conf: the same tiers without tpuscore) on one
    cluster. Reported, never failed on: rounds mode orders placement by
    round, not task by task."""
    from volcano_tpu.scheduler.scheduler import (
        DEFAULT_SCHEDULER_CONF, Scheduler)

    device = run_cycle(5, scale, CONF)[1]
    gc.collect()
    cache, n_tasks = populate(5, scale)
    t0 = time.perf_counter()
    Scheduler(cache, scheduler_conf=DEFAULT_SCHEDULER_CONF).run_once()
    serial_s = time.perf_counter() - t0
    serial = dict(cache.binder.binds)
    differ = sum(1 for k in set(serial) | set(device)
                 if serial.get(k) != device.get(k))
    _say(parity="cfg5", scale=scale, tasks=n_tasks, serial_s=serial_s,
         serial_binds=len(serial), device_binds=len(device),
         bindings_differ=differ)


def _shard_devices(cache, n: int) -> set:
    """Device ids that hold a node-axis shard of this cache's staged
    arrays (the standing replica and the per-shard staging cache)."""
    from volcano_tpu.ops import replica as replica_mod
    from volcano_tpu.ops import shard as shard_mod

    ids = set()
    rep = replica_mod.get(cache, create=False)
    for shards in getattr(rep, "_node_shards", {}).values():
        for buf in shards:
            ids.update(d.id for d in buf.devices())
    for (name, d, s), entry in shard_mod._SHARD_CACHE.items():
        if d == n:
            ids.update(dev.id for dev in entry[2].devices())
    return ids


def multichip(n: int, scale: float = 1.0) -> None:
    """cfg5 at full scale with the node axis sharded over n chips, against
    the same cluster on one chip, each cold then warm: bindings must be
    identical."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from volcano_tpu.scheduler.plugins import tpuscore

    devs = jax.devices()
    if len(devs) < n:
        raise SmokeFailure(f"--chips {n}: only {len(devs)} devices")
    mesh = Mesh(np.array(devs[:n]), ("nodes",))
    try:
        sharded, sharded_binds, cache = run_cycle(5, scale, CONF, mesh=mesh)
        placed = _shard_devices(cache, n)
        del cache
        gc.collect()
        _say(phase="mesh", cycle="sharded", devices=n,
             shard_device_ids=sorted(placed), **sharded)
        if placed != {d.id for d in devs[:n]}:
            raise SmokeFailure(
                f"node-axis shards on devices {sorted(placed)}, expected "
                f"all of {[d.id for d in devs[:n]]}")
        warm, warm_binds = run_cycle(5, scale, CONF, mesh=mesh)[:2]
        _say(phase="mesh", cycle="sharded-warm", devices=n, **warm)
    finally:
        tpuscore.set_default_mesh(None)
    gc.collect()
    single, single_binds = run_cycle(5, scale, CONF)[:2]
    _say(phase="mesh", cycle="single", devices=1, **single)
    gc.collect()
    single_warm, single_warm_binds = run_cycle(5, scale, CONF)[:2]
    _say(phase="mesh", cycle="single-warm", devices=1, **single_warm)
    for label, binds in (("sharded", sharded_binds),
                         ("sharded-warm", warm_binds),
                         ("single-warm", single_warm_binds)):
        if binds != single_binds:
            diff = set(binds.items()) ^ set(single_binds.items())
            raise SmokeFailure(
                f"{label} vs single-chip bindings diverge: {len(diff)} "
                f"differing entries")
    _say(phase="mesh", identical_bindings=len(single_binds), devices=n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the sharded cfg5 path and its "
                         "single-chip comparison")
    args = ap.parse_args(argv)

    platform, kind, count = check_device()

    from volcano_tpu import _native
    from volcano_tpu.utils.gcpolicy import LowLatencyGC
    from volcano_tpu.utils.jaxcompile import (
        CompileWatcher, enable_compile_cache)

    _say(compile_cache=enable_compile_cache())
    CompileWatcher.install()
    _say(native={"fastapply": _native.get_fastapply() is not None,
                 "fasttrans": _native.get_fasttrans() is not None})
    t0 = time.perf_counter()
    # the production loop's GC policy (Scheduler._loop): no automatic
    # collection inside a cycle; this script collects between cycles
    policy = LowLatencyGC.install()
    try:
        if args.chips > 1:
            multichip(args.chips)
        else:
            phase("A", 5, CONF)
            phase("B", 4, PREEMPT_CONF)
            parity()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        policy.uninstall()
    _say(total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
