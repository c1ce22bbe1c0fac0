#!/usr/bin/env python
"""Benchmark driver: scheduler-session latency, serial loop vs TPU solve.

Prints a headline JSON line right after the cfg-5 run, and (in the default
all-configs mode) a final combined JSON line — TAIL LINE WINS; the early
line exists so a time-boxed harness that kills the run mid-way still
captures the headline:
    {"metric": "...", "value": N, "unit": "ms", "vs_baseline": N}

- value: TPU-backend END-TO-END session latency (open_session + actions +
  close_session — the exact span the production loop's e2e metric and the
  reference's E2eSchedulingLatency measure), warm MEDIAN across samples, at
  the headline config (BASELINE.json cfg 5: 50k tasks x 10k nodes). Compile
  excluded (the scheduler reuses the compiled program every cycle); nothing
  else is excluded — session open and the close-time mirror flush are inside
  the timed window. The full record (all configs, per-phase and per-action
  splits, every sample) is also written to BENCH_local.json.
- vs_baseline: speedup over the serial oracle loop at the same config, on
  MATCHING spans — serial full-session e2e over tpu warm-median e2e. The
  reference publishes no numbers (BASELINE.md), so the baseline is the
  serial path measured here; where the serial loop would take > --serial-budget
  seconds its actions window is measured at a reduced scale and extrapolated
  linearly in (tasks x nodes) (open/close extrapolate linearly in scale),
  reported with "serial_extrapolated": true.

Usage:
    python bench.py                     # headline (cfg 5, full scale)
    python bench.py --config 1 --scale 0.2 --backend both
    python bench.py --all --scale 0.05  # all five configs, smoke scale
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _session_once(cache, tiers, actions, mesh=None):
    """Open a session, run the actions, close; returns per-phase timings.

    The measured span is the full production cycle — open_session through
    close_session — exactly what Scheduler.run_once times into its e2e
    metric (volcano_tpu/scheduler/scheduler.py:211-223) and what the
    reference's E2eSchedulingLatency covers (reference
    pkg/scheduler/metrics/metrics.go:38-45, spanning scheduler.go:71-87).
    Work deferred to close (the cache-mirror flush) is inside the window.
    """
    import volcano_tpu.scheduler.actions  # noqa: F401 (register actions)
    from volcano_tpu.scheduler.framework import (
        close_session, open_session, run_actions)

    if mesh is not None:
        from volcano_tpu.scheduler.plugins import tpuscore

        tpuscore.set_default_mesh(mesh)
    from volcano_tpu.utils import devprof
    from volcano_tpu.utils.jaxcompile import CompileWatcher

    if _GC_POLICY is not None:
        _GC_POLICY.maintain()  # between-cycle collection, as in the loop
    win = CompileWatcher.install().window()
    # fence: the timed window must not inherit queued device work from
    # the previous build/session (jax dispatch is async on every backend —
    # without this, open_s could absorb a straggling flush)
    devprof.drain()
    devc = {}
    t0 = time.perf_counter()
    ssn = open_session(cache, tiers)
    t_open = time.perf_counter()
    with devprof.session(devc):
        action_ms = run_actions(ssn, actions)
    t_act = time.perf_counter()
    profile = dict(ssn.plugins["tpuscore"].profile) if "tpuscore" in ssn.plugins else {}
    profile.update(devc)  # tpu_sync_points / tpu_d2h_fetches / tpu_overlap_ms
    close_session(ssn)
    # fence at the close boundary: e2e ends only when the device is
    # drained, so nothing can hide past the timed window
    devprof.drain()
    t_close = time.perf_counter()
    # compile accounting: a warm session with compiles > 0 is a retrace —
    # exactly the regression the warm-sample spread is meant to expose
    cs = win.delta()
    profile["compiles"] = cs.compiles
    profile["compile_s"] = round(cs.compile_s, 3)
    return {
        "open_s": t_open - t0,
        "actions_s": t_act - t_open,
        "close_s": t_close - t_act,
        "e2e_s": t_close - t0,
        "action_ms": action_ms,
        "binds": len(cache.binder.binds),
        "profile": profile,
    }


def run_config(cfg: int, scale: float, backend: str, serial_budget: float,
               mesh=None, verbose=True, warm_iters: int = 5,
               scenario: str = None):
    warm_iters = max(warm_iters, 1)
    from volcano_tpu.bench.clusters import CONFIGS, build_config
    from volcano_tpu.bench.clusters import build_scenario

    # build the native engines BEFORE any timed window — including the
    # serial baseline, whose session transition path also reaches for
    # fasttrans: the _nowait accessors silently fall back to Python while
    # the background cc runs, which would bench the wrong implementation
    from volcano_tpu import _native

    native_ok = {"fastapply": _native.get_fastapply() is not None,
                 "fasttrans": _native.get_fasttrans() is not None}

    if scenario is None:
        name = CONFIGS[cfg].name
        build = build_config
    else:
        # --scenario: the cluster snapshot comes from a sim scenario file
        # (volcano_tpu/sim/scenarios) through the SAME populate path the
        # simulator uses — one cluster-shape source, two harnesses
        import os as _os

        name = f"scenario:{_os.path.splitext(_os.path.basename(scenario))[0]}"

        def build(_cfg, s, _ref=scenario):
            return build_scenario(_ref, s)
    out = {"config": cfg, "name": name, "scale": scale,
           "native_engines": native_ok}

    if backend in ("serial", "both", "auto"):
        # estimate serial cost before committing to it: measured at small
        # scale, the serial loop is ~linear in placed-tasks x nodes
        serial_scale = scale
        est = None
        if backend == "auto" or cfg >= 3:
            probe_scale = min(scale, 0.02)
            cache, st, _, actions, _ = build(cfg, probe_scale)
            t0 = time.perf_counter()
            probe = _session_once(cache, st, actions)
            probe_s = time.perf_counter() - t0
            unit = probe_scale * probe_scale  # tasks*nodes both scale
            est = probe_s / unit * (scale * scale)
            if est > serial_budget:
                serial_scale = max((serial_budget / (probe_s / unit)) ** 0.5, probe_scale)
        cache, serial_tiers, _, actions, n_tasks = build(cfg, serial_scale)
        r = _session_once(cache, serial_tiers, actions)
        serial_s = r["actions_s"]
        open_close_s = r["open_s"] + r["close_s"]
        if serial_scale < scale:
            factor = (scale * scale) / (serial_scale * serial_scale)
            out["serial_measured_scale"] = serial_scale
            out["serial_measured_ms"] = serial_s * 1e3
            serial_s = serial_s * factor
            # open/close walk every object once -> ~linear in scale, not
            # quadratic like the per-(task,node) action loops
            open_close_s = open_close_s * (scale / serial_scale)
            out["serial_extrapolated"] = True
        out["serial_ms"] = serial_s * 1e3
        # full-session serial span, matching tpu_e2e_*: actions plus the
        # (linearly extrapolated, when reduced-scale) open+close
        out["serial_e2e_ms"] = round((serial_s + open_close_s) * 1e3, 3)
        out["serial_binds"] = r["binds"]
        out["serial_open_ms"] = round(r["open_s"] * 1e3, 3)
        out["serial_close_ms"] = round(r["close_s"] * 1e3, 3)
        if verbose:
            print(f"[cfg{cfg}] serial: {out['serial_ms']:.1f} ms "
                  f"({'extrapolated' if out.get('serial_extrapolated') else 'measured'})",
                  file=sys.stderr)

    if backend in ("tpu", "both", "auto"):
        import gc

        cache, _, tpu_tiers, actions, n_tasks = build(cfg, scale)
        cold = _session_once(cache, tpu_tiers, actions, mesh=mesh)
        out["tpu_cold_ms"] = cold["actions_s"] * 1e3
        out["tpu_cold_profile"] = cold["profile"]
        # warm: fresh identical clusters, compiled program reused (the
        # scheduler reuses the compiled program every cycle).
        # per-sample device round-trip floor: a median-of-k no-op
        # dispatch+fetch right before each timed sample pins the floor
        # that sample ran against, with the probe spread recorded so floor
        # noise can't masquerade as a solve regression
        sample_floor = _measure_floor_ms

        samples = []        # actions window, ms (back-compat headline)
        e2e_samples = []    # open + actions + close, ms — the honest span
        floor_samples = []  # per-sample link floor (median of k probes)
        floor_spreads = []  # max-min of each sample's floor probes
        floor_notes = []    # per-sample floor cause annotations
        warm = None
        warm_compiles = []
        # one extra warm session whose sample is DISCARDED: the first
        # post-compile session still pays one-off warmup (allocator pools,
        # device-cache fills, branch-predictor state) that the production
        # steady state never sees — recording it as tpu_first_warm_ms keeps
        # it visible without letting it shape the median
        for it in range(warm_iters + 1):
            del cache
            gc.collect()
            cache, _, tpu_tiers, actions, n_tasks = build(cfg, scale)
            # building the cluster allocates heavily; collect that debt
            # BEFORE the timed window so a generational collection isn't
            # charged to whichever session phase it randomly lands in (the
            # production loop schedules between-cycle collections the same
            # way — utils/gcpolicy.py)
            gc.collect()
            f_med, f_spread, f_note = sample_floor()
            w = _session_once(cache, tpu_tiers, actions, mesh=mesh)
            if it == 0:
                out["tpu_first_warm_ms"] = round(w["e2e_s"] * 1e3, 3)
                out["tpu_first_warm_compiles"] = \
                    w["profile"].get("compiles", 0)
                continue
            floor_samples.append(f_med)
            floor_spreads.append(f_spread)
            floor_notes.append(f_note)
            samples.append(w["actions_s"] * 1e3)
            e2e_samples.append(w["e2e_s"] * 1e3)
            warm_compiles.append(w["profile"].get("compiles", 0))
            if warm is None or w["e2e_s"] * 1e3 <= min(e2e_samples):
                warm = w
        # a min-only report buries warm-path retraces/stalls — median and
        # max make the spread (and any hidden recompile) part of the record.
        # The BARS bind on median e2e: the full production span, at the
        # middle of the observed jitter, not its luckiest tail.
        import statistics

        out["tpu_ms"] = min(samples)
        out["tpu_warm_median_ms"] = round(statistics.median(samples), 3)
        out["tpu_warm_max_ms"] = round(max(samples), 3)
        out["tpu_warm_samples_ms"] = [round(s, 3) for s in samples]
        out["tpu_e2e_ms"] = round(min(e2e_samples), 3)
        out["tpu_e2e_median_ms"] = round(statistics.median(e2e_samples), 3)
        out["tpu_e2e_samples_ms"] = [round(s, 3) for s in e2e_samples]
        out["tpu_floor_samples_ms"] = floor_samples
        out["tpu_floor_spread_ms"] = floor_spreads
        # cause annotations: every probe's individual wall plus its counted
        # sync/fetch budget — a floor swing must now be attributable to a
        # specific slow round trip, not inferred from the aggregate
        out["tpu_floor_probe_notes"] = floor_notes
        # phase split of the best-e2e sample: nothing hides outside the
        # timed window anymore, but the split still shows where it went
        out["tpu_open_ms"] = round(warm["open_s"] * 1e3, 3)
        out["tpu_close_ms"] = round(warm["close_s"] * 1e3, 3)
        out["tpu_action_ms"] = warm["action_ms"]
        out["tpu_warm_compiles"] = warm_compiles
        out["tpu_binds"] = warm["binds"]
        # candidate-window round profile: the device solve is ONE fused
        # program, so per-round wall splits are not observable without
        # breaking the single-dispatch contract — the record carries the
        # device-reported placed-per-round histogram, the full-sweep
        # (exactness-fallback) round count, and the derived avg ms/round
        # from the dispatch window; the serial-tail terms come from the
        # allocate action's residue-pass timer
        wp = warm["profile"]
        if wp.get("rounds"):
            out["tpu_round_profile"] = {
                "rounds": wp["rounds"],
                "placed": wp.get("round_placed", []),
                "full_sweep_rounds": wp.get("full_sweep_rounds"),
                "window_k": wp.get("window_k"),
                "dirty_k": wp.get("dirty_k"),
                "tail_placed": wp.get("tail_placed", 0),
            }
        out["tpu_residue_ms"] = wp.get("residue_pass_ms", 0.0)
        out["tpu_residue_tasks"] = wp.get("residue_pass_tasks", 0)
        # encode split (ROADMAP item 3): one opaque encode number hides
        # whether sharding moved the bottleneck — snapshot is the
        # session->arrays encode, host_pack the grouped buffer build, h2d
        # the device staging (per-shard puts under a mesh; h2d_shard_*
        # counters in tpu_profile carry the per-shard reuse story)
        out["tpu_encode_split_ms"] = {
            "snapshot": round(wp.get("encode_s", 0.0) * 1e3, 3),
            "host_pack": round(wp.get("pack_s", 0.0) * 1e3, 3),
            "h2d": round(wp.get("h2d_s", 0.0) * 1e3, 3),
        }
        # steady-state incremental sessions: the production loop reuses ONE
        # cache across cycles, so its open/close ride the delta-maintained
        # snapshot (scheduler/cache/snapkeeper.py) instead of the wholesale
        # rebuild a first session pays. Three more sessions on the last
        # warm cache measure that: the first reconciles the placements the
        # mirror flush synced, the rest are the no-churn steady state.
        incr_open, incr_close = [], []
        steady_encode, steady_replica = [], {}
        for _ in range(3):
            w2 = _session_once(cache, tpu_tiers, actions, mesh=mesh)
            incr_open.append(round(w2["open_s"] * 1e3, 3))
            incr_close.append(round(w2["close_s"] * 1e3, 3))
            p2 = w2["profile"]
            steady_encode.append(round(p2.get("encode_s", 0.0) * 1e3, 3))
            steady_replica.update({
                k: p2[k] for k in ("encode_reused", "h2d_puts",
                                   "replica_rebuilds",
                                   "replica_scatter_rows",
                                   "tpu_replica_scatter_ms",
                                   "replica_epoch") if k in p2})
        out["tpu_incr_open_ms"] = incr_open
        out["tpu_incr_close_ms"] = incr_close
        out["tpu_incr_open_close_ms"] = round(statistics.median(
            o + c for o, c in zip(incr_open, incr_close)), 3)
        # device-replica steady state (ROADMAP item 2): the incr sessions
        # above ride the standing replica — session 1 reconciles the bulk
        # placements (a scatter/dense diff), sessions 2-3 are the no-churn
        # steady state whose encode should be ~zero (whole-prepare reuse,
        # h2d_puts == 0). The median over the stable tail is the tracked
        # steady-state encode figure.
        out["tpu_steady_encode_ms"] = steady_encode
        out["tpu_steady_state"] = dict(
            steady_replica,
            encode_ms=round(statistics.median(steady_encode[1:]
                                              or steady_encode), 3))
        out["snap_keeper_stats"] = dict(cache.snap_keeper.stats)
        out["tpu_profile"] = {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in warm["profile"].items()}
        out["tasks"] = n_tasks
        if verbose:
            p = warm["profile"]
            print(f"[cfg{cfg}] tpu warm e2e: {out['tpu_e2e_ms']:.1f} ms "
                  f"(open {out['tpu_open_ms']:.1f} actions {warm['actions_s']*1e3:.1f} "
                  f"close {out['tpu_close_ms']:.1f}) "
                  f"(encode {p.get('encode_s', 0)*1e3:.1f} "
                  f"apply {p.get('apply_s', 0)*1e3:.1f}) binds={warm['binds']} "
                  f"actions={out['tpu_action_ms']} "
                  f"e2e_samples={[round(s) for s in e2e_samples]} compiles={warm_compiles}",
                  file=sys.stderr)

    if "serial_ms" in out and "tpu_ms" in out and out["tpu_ms"] > 0:
        # actions-window min-vs-actions speedup
        out["speedup_actions_min"] = out["serial_ms"] / out["tpu_ms"]
        # the published speedup binds on MATCHING spans at matching
        # percentiles: serial full-session e2e over tpu warm MEDIAN e2e
        if out.get("tpu_e2e_median_ms", 0) > 0:
            out["speedup"] = out["serial_e2e_ms"] / out["tpu_e2e_median_ms"]
    return out


_GC_POLICY = None


def run_mesh_curve(scale: float, counts, warm_iters: int = 2, cfg: int = 7):
    """The standing mesh-scaling curve (ROADMAP item 3): cfg7 (paper-2x,
    100k tasks x 50k nodes at scale 1.0) run at each device count in
    ``counts``, recording a per-device-count warm-session curve so mesh
    efficiency is a tracked trajectory number like sessions/sec.

    Runs in this process over the devices it has; a count larger than
    that is an error. Two figures per device count:
    - ``warm_e2e_ms`` / ``encode_ms`` etc: the full warm session under that
      mesh (on CPU virtual devices, which share one host, this column is
      structural — zero warm compiles, sharded staging engaged — not a
      parallel-speedup claim);
    - ``per_device_stage_ms``: the measured wall of one shard's slice of
      the sharded stages (the rounds score refresh + the evict victim
      fold, ops/shard.probe_per_device_stage_ms) at per-shard width N/d,
      over the config's real encoded arrays: the stage's critical path up
      to the cross-shard verdict reduce when shards run concurrently
      (``sharded_stage_speedup`` is the last-vs-first ratio)."""
    import statistics

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from volcano_tpu.bench.clusters import CONFIGS, make_cache, make_tiers
    from volcano_tpu.ops import shard as shard_mod
    from volcano_tpu.ops.solver import _NODE_AXIS
    from volcano_tpu.scheduler.framework import close_session, open_session
    from volcano_tpu.scheduler.plugins import tpuscore

    devs = jax.devices()
    if max(counts) > len(devs):
        raise SystemExit(
            f"--mesh {','.join(map(str, counts))}: this process has "
            f"{len(devs)} {devs[0].platform} devices")
    bc = CONFIGS[cfg]
    # rounds mode forced: the curve's job is the sharded stages, and at
    # reduced scales auto mode would hand the session to the serial loop
    # below its task threshold
    tiers = make_tiers(["tpuscore"], *bc.tiers,
                       arguments={"tpuscore": {"tpuscore.mode": "rounds"}})

    def build():
        cache = make_cache()
        n_tasks = bc.populate(cache, scale)
        return cache, n_tasks

    # one encode of the real config feeds the per-shard stage probes
    cache, n_tasks = build()
    ssn = open_session(cache, tiers)
    prep = ssn.batch_allocator._prepare(ssn)
    probe_arrays = dict(prep["arrays"]) if prep is not None else None
    probe_spec = prep["spec"] if prep is not None else None
    close_session(ssn)

    curve = []
    try:
        for d in counts:
            mesh = Mesh(np.array(devs[:d]), ("nodes",)) if d > 1 else None
            tpuscore.set_default_mesh(mesh)
            shard_mod.clear_cache()
            cache, _ = build()
            cold = _session_once(cache, tiers, bc.actions, mesh=mesh)
            e2e, w = [], cold
            for _ in range(max(warm_iters, 1)):
                cache, _ = build()
                w = _session_once(cache, tiers, bc.actions, mesh=mesh)
                e2e.append(w["e2e_s"] * 1e3)
            p = w["profile"]
            entry = {
                "devices": d,
                "warm_e2e_ms": round(statistics.median(e2e), 3),
                "encode_ms": round(p.get("encode_s", 0.0) * 1e3, 3),
                "host_pack_ms": round(p.get("pack_s", 0.0) * 1e3, 3),
                "h2d_ms": round(p.get("h2d_s", 0.0) * 1e3, 3),
                "h2d_puts": p.get("h2d_puts", 0),
                "h2d_shard_puts": p.get("h2d_shard_puts", 0),
                "h2d_shard_cached": p.get("h2d_shard_cached", 0),
                "warm_compiles": p.get("compiles", 0),
                "binds": w["binds"],
            }
            if probe_arrays is not None:
                entry["per_device_stage_ms"] = \
                    shard_mod.probe_per_device_stage_ms(
                        probe_spec, probe_arrays, _NODE_AXIS, d)
            curve.append(entry)
    finally:
        tpuscore.set_default_mesh(None)
    out = {"config": cfg, "name": bc.name, "scale": scale,
           "tasks": n_tasks, "devices": counts, "curve": curve}
    first, last = curve[0], curve[-1]
    if "per_device_stage_ms" in first and last["devices"] > 1 \
            and last.get("per_device_stage_ms"):
        out["sharded_stage_speedup"] = round(
            first["per_device_stage_ms"] / last["per_device_stage_ms"], 3)
        out["sharded_stage_speedup_devices"] = \
            [first["devices"], last["devices"]]
    if first.get("warm_e2e_ms") and last.get("warm_e2e_ms") \
            and last["devices"] > 1:
        out["warm_e2e_speedup"] = round(
            first["warm_e2e_ms"] / last["warm_e2e_ms"], 3)
    return out


def run_express(scale: float, arrivals: int = 96, rate_per_s: float = 50.0,
                warm: int = 16, seed: int = 7):
    """--express: Poisson interactive arrivals against a warm cfg5-scale
    snapshot, through the event-driven express lane (volcano_tpu/express).

    One full session settles the backlog first (warm cfg5 snapshot), then
    each iteration submits the arrivals one ~20 ms service period accrued
    (Poisson at `rate_per_s`) and services the lane once. The first
    `warm` iterations absorb compiles and are excluded from the latency
    percentiles (recorded separately); the measured iterations must not
    retrace — `express_warm_compiles` is the proof, exactly the
    assert_no_compiles contract the tests pin. After the arrival storm, a
    full session reconciles and the confirm/revert counts land in the
    record. The PR 6 devprof counters attribute every express-path sync
    point."""
    import random
    import statistics

    from volcano_tpu.api import objects
    from volcano_tpu.bench.clusters import build_config
    from volcano_tpu.express import ExpressLane
    from volcano_tpu.scheduler.util.test_utils import (
        build_pod, build_pod_group)

    cache, _, tpu_tiers, actions, n_tasks = build_config(5, scale)
    lane = ExpressLane(cache)
    settle = _session_once(cache, tpu_tiers, actions)
    lane.run_once()  # drain the backlog notifications (all ineligible/bound)

    rng = random.Random(seed)
    period_s = 0.02
    counter = [0]

    def submit_burst():
        """Arrivals accrued over one service period of the Poisson
        process (>= 1 so every iteration measures a real batch)."""
        n = 0
        budget = period_s
        while True:
            gap = rng.expovariate(rate_per_s)
            if gap > budget and n > 0:
                break
            budget -= gap
            n += 1
        for _ in range(max(n, 1)):
            counter[0] += 1
            pg = f"xpr-{counter[0]:05d}"
            cache.add_pod_group(build_pod_group(
                pg, namespace="express", min_member=1))
            cache.add_pod(build_pod(
                "express", f"{pg}-t0", "", objects.POD_PHASE_PENDING,
                {"cpu": f"{rng.choice([100, 250])}m",
                 "memory": rng.choice(["128Mi", "256Mi"])}, pg))
        return max(n, 1)

    try:
        from volcano_tpu.utils.jaxcompile import CompileWatcher

        watcher = CompileWatcher.install()
    except Exception:
        watcher = None
    lat_ms = []
    warm_lat_ms = []
    sync_points = 0
    batch_sizes = []
    win = None
    for it in range(arrivals + warm):
        if it == warm and watcher is not None:
            win = watcher.window()
        batch_sizes.append(submit_burst())
        rep = lane.run_once()
        (lat_ms if it >= warm else warm_lat_ms).append(rep["ms"])
        if it >= warm:
            sync_points += rep["profile"].get("tpu_sync_points", 0)
    compiles = win.delta().compiles if win is not None else None

    # the reconciling full session: every optimistic bind gets a verdict
    _session_once(cache, tpu_tiers, actions)

    ordered = sorted(lat_ms)

    def pick(q):
        return round(ordered[min(int(q * len(ordered)), len(ordered) - 1)], 3)

    return {
        "scale": scale,
        "snapshot_tasks": n_tasks,
        "settle_session_ms": round(settle["e2e_s"] * 1e3, 3),
        "arrivals": counter[0],
        "batches": len(lat_ms),
        "mean_batch": round(statistics.mean(batch_sizes), 2),
        "tpu_express_p50_ms": pick(0.50),
        "tpu_express_p99_ms": pick(0.99),
        "tpu_express_max_ms": round(ordered[-1], 3),
        "tpu_express_warm_max_ms": round(max(warm_lat_ms), 3)
        if warm_lat_ms else 0.0,
        "express_placed": lane.counters["placed"],
        "express_deferred": lane.counters["deferred"],
        # deferral RATE (per arrival) — the number the serving_mix
        # auditor budget binds on, tracked here as a trajectory column
        "express_deferral_rate": round(
            lane.counters["deferred"]
            / max(lane.counters["arrivals"], 1), 4),
        "express_reconciled": lane.counters["reconciled"],
        "express_reverted": lane.counters["reverted"],
        "express_warm_compiles": compiles,
        "express_sync_points_per_batch": round(
            sync_points / max(len(lat_ms), 1), 3),
        "express_state": dict(lane.state.stats),
    }


def run_pipeline(scale: float, cycles: int = 24, warm: int = 4,
                 rate_per_cycle: float = 3.0, seed: int = 7):
    """--pipeline: back-to-back sessions under Poisson arrivals — no
    isolated warm probes — through the serial loop and the continuous
    pipeline (volcano_tpu/pipeline), on identical pregenerated arrival
    schedules, promoting SUSTAINED sessions/sec + p99 submit->bind task
    wait to the headline (ROADMAP item 2's metric switch).

    Arrivals are quantized through the pipeline's intake hook (the
    watch-ingest point), so each batch lands before the next snapshot
    seals — the speculative solve-ahead then overlaps the previous
    cycle's close instead of being invalidated by its own bench driver.
    The serial arm injects the same batch right before each cycle: both
    arms' session k sees exactly arrival batches 0..k.

    Measurement hygiene (the fence-the-lane bugfix): an express lane is
    attached (the production co-resident state) but PARKED and drained
    before the floor probes and the measured window, so background lane
    state can never interleave with a timed sample; the per-arm floor
    probe notes (probe walls + sync/fetch counts) are recorded exactly
    as the warm-latency benches record theirs."""
    import gc
    import random
    import time as _time

    import volcano_tpu.scheduler.actions  # noqa: F401 (register actions)
    from volcano_tpu.api import objects
    from volcano_tpu.bench.clusters import (
        DEFAULT_TIERS, build_config, make_tiers)
    from volcano_tpu.scheduler.util.test_utils import (
        build_pod, build_pod_group)
    from volcano_tpu.utils import devprof

    total = cycles + warm
    rng = random.Random(seed)
    batches = []
    for k in range(total):
        n, budget = 0, 1.0
        while True:
            gap = rng.expovariate(rate_per_cycle)
            if gap > budget:
                break
            budget -= gap
            n += 1
        batches.append([
            (f"arr-{k:03d}-{j:02d}", rng.choice([1, 2, 4]),
             rng.choice([250, 500, 1000])) for j in range(n)])

    actions = ["allocate", "backfill"]
    args = {"tpuscore": {"tpuscore.mode": "rounds"}}

    def _arm(pipelined: bool):
        from volcano_tpu.express import ExpressLane
        from volcano_tpu.scheduler.framework import (
            close_session, open_session, run_actions)

        cache, _, _, _, n_tasks = build_config(5, scale)
        tiers = make_tiers(["tpuscore"], *DEFAULT_TIERS, arguments=args)
        lane = ExpressLane(cache)
        submit_t = {}
        waits = []

        orig_bind = cache.binder.bind
        orig_many = cache.binder.bind_many
        orig_keyed = getattr(cache.binder, "bind_many_keyed", None)

        def _record(keys, now):
            for key in keys:
                t = submit_t.get(key)
                if t is not None:
                    waits.append(now - t)

        def bind(pod, hostname):
            orig_bind(pod, hostname)
            _record([f"{pod.metadata.namespace}/{pod.metadata.name}"],
                    _time.perf_counter())

        def bind_many(pairs):
            pairs = list(pairs)
            orig_many(pairs)
            _record([f"{p.metadata.namespace}/{p.metadata.name}"
                     for p, _h in pairs], _time.perf_counter())

        cache.binder.bind, cache.binder.bind_many = bind, bind_many
        if orig_keyed is not None:
            # the bulk writeback prefers the keyed batch entrypoint
            def bind_many_keyed(keys, pods, hosts):
                orig_keyed(keys, pods, hosts)
                _record(list(keys), _time.perf_counter())

            cache.binder.bind_many_keyed = bind_many_keyed

        def inject(batch):
            now = _time.perf_counter()
            for name, tasks, cpu in batch:
                cache.add_pod_group(build_pod_group(
                    name, namespace="arr", min_member=tasks))
                for t in range(tasks):
                    pod = build_pod(
                        "arr", f"{name}-t{t}", "",
                        objects.POD_PHASE_PENDING,
                        {"cpu": f"{cpu}m", "memory": "256Mi"}, name)
                    cache.add_pod(pod)
                    submit_t[f"arr/{name}-t{t}"] = now

        pending = list(batches)
        drv = None
        if pipelined:
            from volcano_tpu.pipeline import PipelineDriver

            def intake():
                if pending:
                    inject(pending.pop(0))

            drv = PipelineDriver(
                cache, lambda: (actions, tiers), intake=intake)
            inject(pending.pop(0))  # batch 0, visible to cycle 0

        def cycle():
            if drv is not None:
                drv.run_cycle()
                return
            inject(pending.pop(0))
            ssn = open_session(cache, tiers)
            try:
                run_actions(ssn, actions)
            finally:
                close_session(ssn)

        try:
            from volcano_tpu.utils.jaxcompile import CompileWatcher

            watcher = CompileWatcher.install()
        except Exception:
            watcher = None
        win = None
        t_start = None
        floor = (None, None, None)
        for k in range(total):
            if k == warm:
                # measurement fence: background lane parked, device
                # drained, per-arm link floor pinned with its notes
                lane.park("bench_measurement")
                gc.collect()
                devprof.drain()
                floor = _measure_floor_ms()
                if watcher is not None:
                    win = watcher.window()
                t_start = _time.perf_counter()
                # waits bind only to POST-fence submissions: a warmup
                # arrival binding after the fence would otherwise charge
                # the gc/floor-probe wall to its submit->bind span
                submit_t.clear()
                waits.clear()
            cycle()
        devprof.drain()
        wall = _time.perf_counter() - t_start
        if drv is not None:
            drv.abandon()
        compiles = win.delta().compiles if win is not None else None
        ordered = sorted(waits)

        def pick(q):
            if not ordered:
                return 0.0
            return round(
                ordered[min(int(q * len(ordered)), len(ordered) - 1)] * 1e3,
                3)

        out = {
            "sessions_per_sec": round(cycles / wall, 3) if wall > 0 else 0.0,
            "measured_cycles": cycles,
            "wall_s": round(wall, 3),
            "mean_cycle_ms": round(wall / cycles * 1e3, 3),
            "p50_task_wait_ms": pick(0.50),
            "p99_task_wait_ms": pick(0.99),
            "binds": len(cache.binder.binds),
            "snapshot_tasks": n_tasks,
            "warm_compiles": compiles,
            "express_parked": bool(lane.parked),
            "tpu_floor_probe_notes": floor[2],
            "tpu_floor_ms": floor[0],
            "tpu_floor_spread_ms": floor[1],
        }
        if drv is not None:
            out["driver"] = {k: (dict(v) if isinstance(v, dict) else v)
                             for k, v in drv.stats.items()}
        return out

    # discarded prewarm arm: replays the identical schedule once so the
    # jit bucket ladder is saturated BEFORE either measured arm — without
    # it, whichever arm runs first pays every first-compile inside its
    # measured window and the sessions/sec ratio measures compile order,
    # not the pipeline
    _arm(pipelined=False)
    serial = _arm(pipelined=False)
    pipelined = _arm(pipelined=True)
    speedup = (pipelined["sessions_per_sec"] / serial["sessions_per_sec"]
               if serial["sessions_per_sec"] else 0.0)
    churn = _pipeline_churn(scale, batches, actions, args, seed,
                            warm=warm)
    return {
        "scale": scale,
        "arrival_rate_per_cycle": rate_per_cycle,
        "serial": serial,
        "pipeline": pipelined,
        "pipeline_sessions_per_sec": pipelined["sessions_per_sec"],
        "p99_submit_bind_ms": pipelined["p99_task_wait_ms"],
        "speedup_sessions_per_sec": round(speedup, 3),
        "churn": churn,
        "pipeline_spec_commit_rate": churn["commit_rate_readset"],
    }


def _pipeline_churn(scale, batches, actions, args, seed,
                    queue_rate_per_cycle: float = 3.0,
                    node_rate_per_cycle: float = 0.35, warm: int = 4):
    """The --pipeline churn arm (PR 15): replay run_pipeline's exact
    arrival schedule with a pregenerated Poisson mix of value-neutral
    deltas injected BETWEEN each speculation's seal and its apply —
    spec echoes on bystander queues no sealed solve ever consumed (the
    other-tenant watch-noise family, the dominant steady-state delta in
    a shared cluster), salted with node status echoes. Three arms on
    identical inputs:

      serial    — the byte-for-byte oracle (echoes are placement no-ops);
      whole_fp  — pipelined with VOLCANO_TPU_READSET=0: every echoed
                  window moves the coarse fingerprint, so the sealed
                  solve is discarded on ANY movement (~0 commit rate —
                  the pre-PR-15 behavior this arm keeps measurable);
      readset   — pipelined with the read-set seal: bystander-queue
                  noise is provably disjoint from the sealed read set,
                  so those windows COMMIT; the node-echo salt shows the
                  conservative direction in the same run (cfg5's
                  homogeneous node scores leave the windowed solve no
                  provable coverage, so its touched mask is full-width
                  and a node echo honestly discards — the partial-mask
                  commit case is pinned by tests/test_continuous_pipeline
                  on a window-exact regime).

    The acceptance triplet: readset commit rate >= 0.5 under churn where
    whole_fp sits at ~0, binds byte-identical across all three arms, and
    zero warm compiles in the readset arm's measured window (the echo
    stream must never perturb bucket shapes)."""
    import copy as _copy
    import os as _os
    import random

    import volcano_tpu.scheduler.actions  # noqa: F401 (register actions)
    from volcano_tpu.api import objects
    from volcano_tpu.bench.clusters import (
        DEFAULT_TIERS, build_config, make_tiers)
    from volcano_tpu.scheduler.util.test_utils import (
        build_pod, build_pod_group, build_queue)
    from volcano_tpu.utils import devprof

    total = len(batches)
    n_bystanders = 8
    rng = random.Random(seed * 7919)

    def _poisson_burst(rate):
        n, budget = 0, 1.0
        while True:
            gap = rng.expovariate(rate)
            if gap > budget:
                return n
            budget -= gap
            n += 1

    echoes = []
    for _ in range(total):
        burst = [("queue", rng.random())
                 for _ in range(max(_poisson_burst(queue_rate_per_cycle), 1))]
        burst += [("node", rng.random())
                  for _ in range(_poisson_burst(node_rate_per_cycle))]
        # at least one echo per window: every speculation faces a delta,
        # so a commit can never be the degenerate quiet-window kind
        echoes.append(burst)

    def _inject_jobs(cache, batch):
        for name, tasks, cpu in batch:
            cache.add_pod_group(build_pod_group(
                name, namespace="arr", min_member=tasks))
            for t in range(tasks):
                cache.add_pod(build_pod(
                    "arr", f"{name}-t{t}", "", objects.POD_PHASE_PENDING,
                    {"cpu": f"{cpu}m", "memory": "256Mi"}, name))

    def _arm(mode):
        from volcano_tpu.scheduler.framework import (
            close_session, open_session, run_actions)

        prev = _os.environ.get("VOLCANO_TPU_READSET")
        if mode == "whole_fp":
            _os.environ["VOLCANO_TPU_READSET"] = "0"
        try:
            cache, _, _, _, _ = build_config(5, scale)
            tiers = make_tiers(["tpuscore"], *DEFAULT_TIERS,
                               arguments=args)
            node_names = sorted(cache.nodes)
            # bystander queues exist BEFORE the first session: later
            # re-adds are spec echoes on an existing queue (the scoped
            # mark), never a queue-SET change (wholesale invalidation)
            bystanders = [build_queue(f"bystander-{i}", weight=1)
                          for i in range(n_bystanders)]
            for q in bystanders:
                cache.add_queue(q)
            pending = list(batches)
            drv = None
            if mode != "serial":
                from volcano_tpu.pipeline import PipelineDriver

                def intake():
                    if pending:
                        _inject_jobs(cache, pending.pop(0))

                drv = PipelineDriver(
                    cache, lambda: (actions, tiers), intake=intake)
                _inject_jobs(cache, pending.pop(0))
            try:
                from volcano_tpu.utils.jaxcompile import CompileWatcher

                watcher = CompileWatcher.install()
            except Exception:
                watcher = None
            win = None
            for k in range(total):
                if k == warm:
                    devprof.drain()
                    if watcher is not None:
                        win = watcher.window()
                if drv is not None:
                    drv.run_cycle()
                else:
                    _inject_jobs(cache, pending.pop(0))
                    ssn = open_session(cache, tiers)
                    try:
                        run_actions(ssn, actions)
                    finally:
                        close_session(ssn)
                # the echo stream lands AFTER this cycle sealed the next
                # solve-ahead — between seal and apply, the window the
                # whole-fingerprint seal can never survive
                for fam, frac in echoes[k]:
                    if fam == "queue":
                        cache.add_queue(_copy.deepcopy(
                            bystanders[int(frac * n_bystanders)
                                       % n_bystanders]))
                    else:
                        name = node_names[int(frac * len(node_names))
                                          % len(node_names)]
                        cache.add_node(
                            _copy.deepcopy(cache.nodes[name].node))
            devprof.drain()
            if drv is not None:
                drv.abandon()
            out = {
                "binds": dict(cache.binder.binds),
                "warm_compiles":
                    win.delta().compiles if win is not None else None,
            }
            if drv is not None:
                st = drv.stats
                out["spec_dispatched"] = st["spec_dispatched"]
                out["spec_applied"] = st["spec_applied"]
                out["spec_commits"] = dict(st["spec_commits"])
                out["spec_discards"] = dict(st["spec_discards"])
                out["commit_rate"] = round(
                    st["spec_applied"] / max(st["spec_dispatched"], 1), 4)
            return out
        finally:
            if prev is None:
                _os.environ.pop("VOLCANO_TPU_READSET", None)
            else:
                _os.environ["VOLCANO_TPU_READSET"] = prev

    serial = _arm("serial")
    whole = _arm("whole_fp")
    scoped = _arm("readset")
    return {
        "queue_echo_rate_per_cycle": queue_rate_per_cycle,
        "node_echo_rate_per_cycle": node_rate_per_cycle,
        "echo_deltas_total": sum(len(e) for e in echoes),
        "commit_rate_readset": scoped["commit_rate"],
        "commit_rate_whole_fingerprint": whole["commit_rate"],
        "spec_commits": scoped["spec_commits"],
        "spec_discards": scoped["spec_discards"],
        "whole_fp_discards": whole["spec_discards"],
        "binds_match_serial": scoped["binds"] == serial["binds"],
        "whole_fp_binds_match_serial": whole["binds"] == serial["binds"],
        "binds": len(serial["binds"]),
        "warm_compiles_readset": scoped["warm_compiles"],
    }


def _storm_headline(scale: float, seed: int = 7, duration: float = 60.0):
    """cfg5_storm sustained-throughput headline from the sim harness: the
    scheduler loop driven by Poisson arrivals instead of isolated warm
    probes (ROADMAP item 2's headline-metric switch). Returns the two
    numbers that bind — sustained sessions/sec and p99 submit->bind task
    wait — plus enough context to rescale them."""
    from volcano_tpu.sim.harness import SimCluster
    from volcano_tpu.sim.workload import load_scenario, scale_scenario

    cfg = scale_scenario(load_scenario("cfg5_storm"), scale)
    sim = SimCluster(cfg, seed=seed, repro_dir=None)
    s = sim.run(duration=duration)
    fb = s.get("fallbacks") or {}
    return {
        "sessions_per_sec": s["sessions_per_sec"],
        "p99_task_wait_s": s["task_wait_s"]["p99"],
        "sessions": s["sessions"],
        "binds": s["binds"],
        "scale": scale,
        "sim_duration_s": s["sim_duration_s"],
        # envelope honesty as a tracked trajectory number (ROADMAP item
        # 4): the same rates the sim auditor budgets in chaos_soak /
        # serving_mix, promoted into the standing tail
        "fallback_rates": {k: v for k, v in sorted(fb.items())
                           if k.endswith("_rate")},
    }


def _front_door_headline(scale: float = 0.5, seed: int = 7,
                         duration: float = 60.0):
    """front_door_storm headline from the sim harness (ROADMAP item 3's
    admission column): offered submissions/sec vs admitted-and-scheduled
    under a heavy-tailed storm, with the shed/coalesce rates the auditor
    budgets riding along."""
    from volcano_tpu.sim.harness import SimCluster
    from volcano_tpu.sim.workload import load_scenario, scale_scenario

    cfg = scale_scenario(load_scenario("front_door_storm"), scale)
    sim = SimCluster(cfg, seed=seed, repro_dir=None)
    s = sim.run(duration=duration)
    fd = s.get("front_door") or {}
    fb = s.get("fallbacks") or {}
    return {
        "submitted_per_sim_s": fd.get("submitted_per_sim_s"),
        "admitted_per_sim_s": fd.get("admitted_per_sim_s"),
        "binds": s["binds"],
        "sessions_per_sec": s["sessions_per_sec"],
        "admission_shed_rate": fb.get("admission_shed_rate"),
        "watch_coalesce_rate": fb.get("watch_coalesce_rate"),
        "watch_demotions": ((fd.get("watch") or {}).get(
            "counters") or {}).get("demotions"),
        "violations": s["audit"]["violations"],
        "scale": scale,
    }


def run_fanout_bench(watchers: int = 10000, batches: int = 40,
                     churn: int = 96, cap: int = 4096,
                     slow_every: int = 500, slow_stride: int = 8,
                     sample: int = 64, pods: int = 512):
    """Watch fan-out at 10k+ concurrent watchers over ONE shared journal.

    Synchronous (no threads — the shared-slice fast path is what's under
    test): each batch mutates ``churn`` pods, then every watcher polls
    once through the flow-control layer. Every ``slow_every``-th watcher
    only polls every ``slow_stride`` batches — the laggard tail that must
    ride bounded retention and demotion-to-resync instead of pinning the
    ring. Reports per-event delivery latency percentiles (append-stamp to
    delivery, sampled over the first ``sample`` watchers), throughput,
    and the per-watcher memory footprint — cursor + counters only, which
    is the O(events + watchers) proof."""
    import copy

    from volcano_tpu.api import objects
    from volcano_tpu.scheduler.util.test_utils import build_pod
    from volcano_tpu.store.flowcontrol import WatchFanout, WatcherState
    from volcano_tpu.store.gateway import _WatchJournal
    from volcano_tpu.store.store import Store

    store = Store()
    journal = _WatchJournal(store, "Pod", cap=cap)
    fanout = WatchFanout(journal, demote_lag=2 * cap, pin_factor=4)

    def make(i):
        pod = build_pod("bench", f"pod-{i:06d}", "",
                        objects.POD_PHASE_PENDING,
                        {"cpu": "100m", "memory": "64Mi"}, "")
        pod.metadata.ensure_identity()
        return pod

    live = []
    for i in range(pods):
        pod = make(i)
        store.create(pod)
        live.append(pod)
    cursors = [0] * watchers
    classes = ["interactive" if i % 3 == 0 else "batch"
               for i in range(watchers)]
    latencies = []
    delivered = resyncs = 0
    next_pod = pods
    wall0 = time.perf_counter()
    for batch in range(batches):
        for k in range(churn):
            idx = (batch * churn + k) % len(live)
            if k % 7 == 0:
                pod = make(next_pod)
                next_pod += 1
                store.create(pod)
                live.append(pod)
            else:
                cur = store.try_get("Pod", "bench",
                                    live[idx].metadata.name)
                if cur is None:
                    continue
                upd = copy.deepcopy(cur)
                upd.metadata.annotations["b"] = str(batch)
                store.update(upd)
        poll_t = time.monotonic()
        for i in range(watchers):
            if slow_every and i % slow_every == slow_every - 1 \
                    and batch % slow_stride != 0:
                continue  # the deliberately slow tail
            events, nxt, reset = fanout.poll_for(
                f"w{i:05d}", cursors[i], 0.0, cls=classes[i])
            cursors[i] = nxt
            if reset:
                resyncs += 1
                continue
            delivered += len(events)
            if i < sample:
                latencies.extend(poll_t - e["ts"] for e in events
                                 if "ts" in e)
    wall = time.perf_counter() - wall0
    latencies.sort()

    def pct(q):
        if not latencies:
            return 0.0
        return round(
            latencies[min(int(q * len(latencies)), len(latencies) - 1)]
            * 1e3, 3)

    ws_bytes = sys.getsizeof(WatcherState("x", "batch", 0)) \
        + sum(sys.getsizeof(getattr(WatcherState("x", "batch", 0), s))
              for s in WatcherState.__slots__)
    stats = fanout.watch_stats()
    return {
        "watchers": watchers,
        "batches": batches,
        "events_appended": stats["journal"]["appended"],
        "deliveries": delivered,
        "fanout_p50_ms": pct(0.50),
        "fanout_p99_ms": pct(0.99),
        "polls_per_sec": round(watchers * batches / wall, 1),
        "deliveries_per_sec": round(delivered / wall, 1),
        "coalesced": stats["counters"]["coalesced"],
        "demotions": stats["counters"]["demotions"],
        "resyncs": resyncs,
        "journal_peak_occupancy": stats["journal"]["peak_occupancy"],
        "journal_hard_cap": stats["journal"]["hard_cap"],
        "per_watcher_state_bytes": ws_bytes,
        "wall_s": round(wall, 3),
        "pid_rss_mb": _rss_mb(),
    }


def _rss_mb():
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) / 1024.0, 1)
    except OSError:
        pass
    return None


_FLOOR_PROBE = None  # (jitted no-op, device operand), built on first use


def _floor_probe():
    """One compiled no-op dispatch+fetch — the device round-trip floor
    probe, built ONCE and shared by the startup [link] measurement and
    the per-sample floors (so both always measure the same thing)."""
    global _FLOOR_PROBE
    if _FLOOR_PROBE is None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        f = jax.jit(lambda x: x + 1)
        x = jnp.zeros((1,), jnp.int32)
        np.asarray(f(x))  # compile outside any timed window
        _FLOOR_PROBE = (f, x)
    return _FLOOR_PROBE


def _probe_once_ms():
    """One timed probe round trip. The probe is fenced (nothing queued may
    overlap it) and its fetch is routed through devprof so the sync/D2H
    budget lands in the floor annotations."""
    from volcano_tpu.utils import devprof

    f, x = _floor_probe()
    devprof.drain()  # fence: probe measures ONLY its own round trip
    t0 = time.perf_counter()
    devprof.start_fetch(f(x))()
    return round((time.perf_counter() - t0) * 1e3, 3)


def _measure_floor_ms(probes: int = 5):
    """Median-of-k floor measurement: (median_ms, spread_ms, annotation).

    The median of k back-to-back probes is stable against one slow round
    trip; the spread (max - min) is recorded next to it, and the
    annotation carries every probe's wall plus the counted sync-point/D2H
    budget, so a drifting floor is attributable in the record instead of
    silently reshaping the headline. The first probe after the drain fence
    is carried apart as first_probe_ms and left out of the aggregate."""
    import statistics

    from volcano_tpu.utils import devprof

    counters = {}
    with devprof.session(counters):
        raw = [_probe_once_ms() for _ in range(probes + 1)]
    first, samples = raw[0], raw[1:]
    note = {"probes_ms": samples,
            "first_probe_ms": first,
            "sync_points": counters.get("tpu_sync_points"),
            "d2h_fetches": counters.get("tpu_d2h_fetches")}
    return (round(statistics.median(samples), 3),
            round(max(samples) - min(samples), 3), note)


def main() -> int:
    global _GC_POLICY
    from volcano_tpu.utils.gcpolicy import LowLatencyGC

    # the production scheduler loop runs under this policy (Scheduler._loop);
    # measuring without it would charge random full-heap GC pauses to
    # whichever phase they land in. run_config calls maintain() between
    # sessions, mirroring the loop's between-cycle collections.
    _GC_POLICY = LowLatencyGC.install()
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, default=None,
                    choices=[1, 2, 3, 4, 5, 6, 7],
                    help="run ONE config (default: all six, headline = cfg 5; "
                         "cfg6 = cfg2 + affinity/hostPort residue; cfg7 = "
                         "paper-2x 100k tasks x 50k nodes, the mesh-curve "
                         "standing config)")
    ap.add_argument("--all", action="store_true",
                    help="run all six configs (the default when --config is absent)")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--backend", choices=["serial", "tpu", "both", "auto"], default="auto")
    ap.add_argument("--serial-budget", type=float, default=30.0,
                    help="max seconds to spend measuring the serial loop per config")
    ap.add_argument("--warm-iters", type=int, default=5,
                    help="warm TPU sessions per config (>=1); the headline "
                         "binds on the MEDIAN e2e, and 5 samples keep one "
                         "link-jitter outlier from dragging it")
    ap.add_argument("--scenario", default=None,
                    help="source the cluster snapshot from a sim scenario "
                         "file or committed scenario name "
                         "(volcano_tpu/sim/scenarios) instead of the "
                         "built-in configs")
    ap.add_argument("--mesh", nargs="?", const="all", default=None,
                    help="bare flag: shard the node axis across all local "
                         "devices for the config runs. With a device-count "
                         "list (--mesh 1,2,4): run the cfg7 mesh-scaling "
                         "sweep in this process over the devices it has, "
                         "emitting tpu_mesh_curve in the summary tail, then "
                         "exit")
    ap.add_argument("--express", action="store_true",
                    help="express-lane mode: Poisson interactive arrivals "
                         "against a warm cfg5-scale snapshot; records "
                         "tpu_express_p50/p99_ms and the placed/deferred/"
                         "reconciled/reverted counts, then exits")
    ap.add_argument("--express-arrivals", type=int, default=96,
                    help="measured express batches (after 16 warmup)")
    ap.add_argument("--express-rate", type=float, default=50.0,
                    help="Poisson arrival rate for --express, jobs/sec")
    ap.add_argument("--pipeline", action="store_true",
                    help="continuous-pipeline mode: back-to-back sessions "
                         "under Poisson arrivals through the serial loop "
                         "AND volcano_tpu/pipeline on identical arrival "
                         "schedules; reports sustained sessions/sec, p99 "
                         "submit->bind task wait, the speculation "
                         "commit/discard ledger, and the sessions/sec "
                         "speedup, then exits")
    ap.add_argument("--pipeline-cycles", type=int, default=24,
                    help="measured back-to-back cycles per arm "
                         "(after 4 warmup cycles)")
    ap.add_argument("--pipeline-rate", type=float, default=3.0,
                    help="Poisson arrival rate for --pipeline, jobs/cycle")
    ap.add_argument("--fanout", nargs="?", const=10000, default=None,
                    type=int,
                    help="run the watch fan-out bench alone at N watchers "
                         "(default 10000) and print its summary tail")
    ap.add_argument("--no-fanout", action="store_true",
                    help="skip the standing 10k-watcher fan-out column in "
                         "the all-configs summary tail")
    ap.add_argument("--no-front-door", action="store_true",
                    help="skip the front_door_storm submissions/sec "
                         "headline in the all-configs summary tail")
    ap.add_argument("--no-storm", action="store_true",
                    help="skip the cfg5_storm sustained sessions/sec + p99 "
                         "task-wait headline (runs only in all-configs mode)")
    ap.add_argument("--storm-scale", type=float, default=0.01,
                    help="cfg5_storm scale for the throughput headline "
                         "(default matches the tier-1 sim gate)")
    ap.add_argument("--storm-duration", type=float, default=60.0,
                    help="cfg5_storm simulated horizon, seconds")
    args = ap.parse_args()

    if args.fanout is not None:
        # jax-free path: the fan-out bench exercises only the store/
        # journal/flow-control layer, so it runs (and exits) before any
        # device machinery loads
        result = run_fanout_bench(watchers=args.fanout)
        print(json.dumps({
            "metric": "watch fan-out p99 delivery latency @ %d watchers"
                      % args.fanout,
            "value": result["fanout_p99_ms"],
            "unit": "ms",
        }), flush=True)
        print(json.dumps({"summary": {"watch_fanout": result}},
                         separators=(",", ":")), flush=True)
        return 0

    import jax

    from volcano_tpu.utils.jaxcompile import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    # every device-path record names the device it ran on
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    mesh_counts = None
    if args.mesh is not None and args.mesh != "all":
        mesh_counts = sorted({max(int(x), 1)
                              for x in args.mesh.split(",") if x.strip()})
    if mesh_counts is not None:
        result = run_mesh_curve(args.scale, mesh_counts,
                                warm_iters=max(args.warm_iters // 2, 1))
        print(json.dumps({
            "metric": "cfg7 (paper-2x) per-device sharded-stage wall at "
                      "%d devices, x %s scale"
                      % (result["devices"][-1], args.scale),
            "value": result["curve"][-1].get("per_device_stage_ms", 0.0),
            "unit": "ms",
            "device": device,
            "vs_baseline": result.get("sharded_stage_speedup", 0.0),
        }), flush=True)
        print(json.dumps({"summary": {"tpu_mesh_curve": result}},
                         separators=(",", ":")), flush=True)
        return 0

    if args.pipeline:
        result = run_pipeline(args.scale, cycles=args.pipeline_cycles,
                              rate_per_cycle=args.pipeline_rate)
        print(json.dumps({
            "metric": "pipelined sustained sessions/sec @ cfg5 x %s "
                      "under Poisson arrivals" % args.scale,
            "value": result["pipeline_sessions_per_sec"],
            "unit": "sessions/s",
            "device": device,
            "vs_baseline": result["speedup_sessions_per_sec"],
        }), flush=True)
        print(json.dumps({"summary": {
            "cfg5_pipeline": {
                "pipeline_sessions_per_sec":
                    result["pipeline_sessions_per_sec"],
                "serial_sessions_per_sec":
                    result["serial"]["sessions_per_sec"],
                "speedup_sessions_per_sec":
                    result["speedup_sessions_per_sec"],
                "p99_submit_bind_ms": result["p99_submit_bind_ms"],
                "serial_p99_submit_bind_ms":
                    result["serial"]["p99_task_wait_ms"],
                "pipeline_warm_compiles":
                    result["pipeline"]["warm_compiles"],
                "spec": result["pipeline"].get("driver", {}),
                "pipeline_spec_discard_rate": round(
                    result["pipeline"].get("driver", {}).get(
                        "spec_discarded", 0)
                    / max(result["pipeline"].get("driver", {}).get(
                        "spec_dispatched", 0), 1), 4),
                # the churn arm's standing column (PR 15): the read-set
                # seal committing the solve-ahead through echo churn the
                # whole-fingerprint seal discards wholesale
                "pipeline_spec_commit_rate":
                    result["pipeline_spec_commit_rate"],
                "churn": result["churn"],
            },
            "pipeline_full": result,
        }}, separators=(",", ":"), default=str), flush=True)
        return 0

    if args.express:
        result = run_express(args.scale, arrivals=args.express_arrivals,
                             rate_per_s=args.express_rate)
        print(json.dumps({
            "metric": "express placement latency p99 (ms) @ cfg5 x %s"
                      % args.scale,
            "value": result["tpu_express_p99_ms"],
            "unit": "ms",
            "device": device,
        }), flush=True)
        print(json.dumps({"summary": {"express": result}},
                         separators=(",", ":")), flush=True)
        return 0

    mesh = None
    if args.mesh:
        import numpy as np
        from jax.sharding import Mesh

        if len(devs) > 1:
            mesh = Mesh(np.array(devs), ("nodes",))

    # the device round-trip floor: one jitted no-op dispatch + 4-byte
    # fetch, the lower bound of any session's solve phase, recorded so the
    # BENCH numbers carry their own link context
    rtt_floor_ms = None
    if args.backend in ("tpu", "both", "auto"):
        rtt_floor_ms, rtt_spread, _ = _measure_floor_ms(probes=7)
        print(f"[link] device round-trip floor: {rtt_floor_ms} ms "
              f"(median of 7, spread {rtt_spread} ms)", file=sys.stderr)

    def headline_json(headline):
        # the headline value is the MEDIAN e2e session latency — the full
        # open+actions+close span the production loop and the reference both
        # measure, at the middle of the link jitter (not the luckiest min)
        value = headline.get(
            "tpu_e2e_median_ms",
            headline.get("serial_e2e_ms",     # --backend serial: same span
                         headline.get("tpu_ms",
                                      headline.get("serial_ms", 0.0))))
        final = {
            "metric": "scheduler e2e session latency, warm median (ms) @ %dk tasks x %dk nodes"
                      % (int(50 * args.scale), int(10 * args.scale))
                      if headline["config"] == 5 else
                      f"scheduler e2e session latency, warm median (ms), cfg {headline['config']} ({headline['name']})",
            "value": round(value, 3),
            "unit": "ms",
            "device": device,
            "vs_baseline": round(headline.get("speedup", 0.0), 3),
        }
        # host-side session bracket, first-session (wholesale snapshot)
        # and steady-state (delta-maintained snapshot) — the round-6
        # open/close story lives in these three numbers
        for src, dst in (("tpu_open_ms", "open_ms"),
                         ("tpu_close_ms", "close_ms"),
                         ("tpu_incr_open_close_ms", "incr_open_close_ms")):
            if src in headline:
                final[dst] = headline[src]
        # the headline baseline may be a reduced-scale serial run
        # extrapolated linearly in tasks x nodes — say so next to the
        # number it shaped
        if headline.get("serial_extrapolated"):
            final["serial_extrapolated"] = True
            final["serial_measured_scale"] = headline.get("serial_measured_scale")
        return final

    import os

    def write_record(results, final=None):
        # persist the COMPLETE record from here, re-written after EVERY
        # config: the driver keeps only the last 2,000 chars of stdout
        # (which lost cfg1/2/3/5 in rounds 3 AND 4), and a time-boxed
        # harness can kill the run mid-sweep — the file survives both
        try:
            import subprocess

            sha = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__))
            ).stdout.strip() or None
        except Exception:
            sha = None
        record = {"rtt_floor_ms": rtt_floor_ms, "git_sha": sha,
                  "device": device,
                  "argv": sys.argv[1:],
                  "complete": final is not None,
                  "results": [
                      {k: v for k, v in r.items() if k != "tpu_cold_profile"}
                      for r in results]}
        if final is not None:
            record["headline"] = {k: v for k, v in final.items()
                                  if k != "all_configs"}
        try:
            out_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_local.json")
            with open(out_path, "w") as fh:
                json.dump(record, fh, indent=1)
                fh.write("\n")
        except Exception as e:
            print(f"[bench] could not write BENCH_local.json: {e}",
                  file=sys.stderr)

    results = []
    # headline (cfg 5) runs FIRST and prints its JSON line immediately: a
    # time-boxed harness that kills the run mid-way still captures the
    # headline number in its tail; the combined line (with all_configs)
    # prints last and supersedes it when the run completes
    if args.scenario is not None:
        cfgs = [0]  # one scenario-sourced run; headline falls through to it
    else:
        cfgs = [args.config] if args.config is not None else [5, 1, 2, 3, 4, 6]
    for cfg in cfgs:
        results.append(run_config(cfg, args.scale, args.backend,
                                  args.serial_budget, mesh=mesh,
                                  warm_iters=args.warm_iters,
                                  scenario=args.scenario))
        write_record(results)
        if cfg == 5 and len(cfgs) > 1:
            print(json.dumps(headline_json(results[0])), flush=True)

    headline = results[0] if cfgs[0] == 5 else results[-1]
    final = headline_json(headline)
    if rtt_floor_ms is not None:
        final["rtt_floor_ms"] = rtt_floor_ms
    if len(results) > 1:
        # tpu_profile (warm per-phase splits incl. pack/dispatch/apply and
        # the compile counters) stays in the record — the per-hop budget is
        # part of the result, not debug noise; only the verbose cold
        # profile is dropped
        final["all_configs"] = [
            {k: v for k, v in r.items() if k != "tpu_cold_profile"}
            for r in results
        ]
    write_record(results, final=final)
    print(json.dumps(final))
    # compact trajectory line, printed LAST: the driver keeps only the final
    # ~2,000 chars of stdout (cfg1/2/3/5 records were lost in rounds 3 and
    # 4 behind the full record above), so the whole-sweep summary — and
    # cfg4's per-action eviction-path timings — must fit in the tail
    summary = {}
    for r in results:
        entry = {
            "e2e_ms": r.get("tpu_e2e_median_ms", r.get("serial_e2e_ms")),
            "speedup": round(r.get("speedup", 0.0), 3),
        }
        # steady-state encode column (device replica, ROADMAP item 2):
        # the delta-fed figure the replica work binds on, next to the
        # cold-ish warm-session headline
        st = r.get("tpu_steady_state")
        if st is not None:
            entry["steady_encode_ms"] = st.get("encode_ms")
        if r["config"] == 4 and "tpu_action_ms" in r:
            entry["action_ms"] = {
                k: v for k, v in r["tpu_action_ms"].items()
                if k in ("preempt", "reclaim", "backfill")}
        summary[f"cfg{r['config']}"] = entry
    # sustained-throughput headline (ROADMAP item 2): cfg5_storm from the
    # sim harness, promoted into the same tail line as the warm latencies —
    # sessions/sec and p99 task wait are the numbers the continuous
    # pipeline work will bind on
    if (not args.no_storm and args.scenario is None
            and args.backend in ("tpu", "both", "auto") and len(cfgs) > 1):
        summary["cfg5_storm"] = _storm_headline(
            args.storm_scale, duration=args.storm_duration)
    # the standing front-door columns (ROADMAP item 3): 10k-watcher
    # fan-out p50/p99 delivery latency + bounded per-watcher memory, and
    # the storm's offered-vs-admitted submissions/sec — tracked
    # trajectory numbers like sessions/sec
    if (not args.no_fanout and args.scenario is None and len(cfgs) > 1):
        summary["watch_fanout"] = run_fanout_bench()
    if (not args.no_front_door and args.scenario is None
            and args.backend in ("tpu", "both", "auto") and len(cfgs) > 1):
        summary["front_door_storm"] = _front_door_headline()
    print(json.dumps({"summary": summary}, separators=(",", ":")),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
