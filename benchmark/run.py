#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process holds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration is ``benchmark/configs/<config>.json``, its traffic mix
``benchmark/traffic/<traffic>.json`` (driven by ``benchmark/modes/<mode>.py``)
and each metric a reader ``benchmark/metrics/<metric>.py``, all found by
name, so a cell, a configuration, a traffic mode or a metric is added by
adding files.

One process, no child that touches JAX. In order: refuse anything but a TPU
with enough chips; put JAX's persistent compile cache at a fixed path in
the checkout; build the cell's cluster from ``--seed``; run the mode's probe
(a probe that leaves the device path ends the run: exit code 2, nothing
printed); warm up the cell's own shapes; run sessions for ``--seconds``;
check each session's output against the reference; print the result as
the last line of stdout. With
``--trace 1`` the first few sessions of the window run under the profiler
(for a mode whose window has no device work, from its probe session before
the warm-up on) and the result carries the per-layer metrics instead of the
end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from traffic import Refused  # noqa: E402

CACHE_DIR = os.path.join(HERE, ".jax_cache")


def load_cell(root: str, name: str):
    """(BENCHMARK.json, the cell, its configuration, its traffic)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["name"] == name]
    if not cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[0]
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", cell["config"] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(base, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, cfg, traffic


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def reader(root: str, name: str):
    path = os.path.join(root, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int) -> list:
    """The devices the cell runs on; refuses anything but enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"{chips} chips asked for, {len(devs)} found")
    return devs[:chips]


def device_peaks(kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def enable_compile_cache() -> None:
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # every program, however quick to compile, comes from the cache after
    # the first run: set-up is then the same work in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def read_trace(tmp: str):
    """(per-session (span ns, busy ns), busy s, window s, breakdown) of the
    trace the window wrote; device numbers are None without device ops."""
    import devtrace

    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp) for f in fs
             if f.endswith(".xplane.pb")]
    if not paths:
        return None, None, None, None
    planes, spans = devtrace.read(paths[0])
    lo_hi = devtrace.traced_window(spans)
    if not planes or lo_hi is None:
        return None, None, None, None
    lo, hi = lo_hi
    return (devtrace.session_busy(planes, spans),
            devtrace.window_busy(planes, lo, hi), (hi - lo) / 1e9,
            devtrace.breakdown(planes, spans, lo, hi))


def start_trace() -> str:
    import jax

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    return tmp


def measure(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
            trace: bool, devices: list, root: str = ROOT) -> SimpleNamespace:
    """Warm-up, window and checks of one run."""
    import jax

    from harness import Fallbacks
    from traffic import driver_class
    from volcano_tpu import _native
    from volcano_tpu.utils.gcpolicy import LowLatencyGC

    # the native apply loop builds on first use in a checkout; a session
    # that ran before it finished would take the Python loop
    _native.get_fastapply()
    _native.get_fasttrans()
    policy = LowLatencyGC.install()
    try:
        drv = driver_class(root, traffic["mode"])(cfg, traffic, seed, policy)
        # a mode whose window has no device work traces its probe too
        tmp = start_trace() if trace and drv.PROBE else None
        drv.probe()
        drv.warm()
        fallbacks = Fallbacks()
        compiled_before = drv.compiled.n
        n_warm = len(drv.records)
        if trace and tmp is None:
            tmp = start_trace()
        tracing = trace
        window = []
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            rec = drv.step(end)
            if rec is None:
                break
            window.append(rec)
            if tracing and len(window) >= int(traffic["trace_sessions"]):
                jax.profiler.stop_trace()
                tracing = False
        close = time.perf_counter()
        if tracing:
            jax.profiler.stop_trace()
        compiles = drv.compiled.n - compiled_before
        fell_back = [fallbacks.of(r["profile"]) for r in window]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        latencies = drv.latencies(close)
        drv.finish()
    finally:
        policy.uninstall()

    traced = busy_s = window_s = breakdown = None
    if tmp is not None:
        try:
            traced, busy_s, window_s, breakdown = read_trace(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    checks = dict(drv.totals)
    checks["window_compiles"] = compiles
    if drv.DEVICE_PATH:
        checks["fallbacks"] = sum(1 for f in fell_back if f)
    return SimpleNamespace(
        sessions=window, n_warm=n_warm, records=drv.records,
        setup_s=window[0]["t0"] - T_START if window else None,
        latencies=latencies, traced=traced, busy_s=busy_s, window_s=window_s,
        breakdown=breakdown, checks=checks, peak=peak,
        fell_back=[f for f in fell_back if f],
        device_sessions=sum(1 for r in window
                            if r["profile"].get("mode") == "rounds"))


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, cfg, traffic = load_cell(root, args.workload)
        devices = check_device(int(cell["chips"]))
        device_peaks(devices[0].device_kind)
    except Refused as e:
        print(f"benchmark: {e}; nothing measured", file=sys.stderr)
        return 2
    enable_compile_cache()
    try:
        run = measure(cell, cfg, traffic, args.seed, args.seconds,
                      bool(args.trace), devices, root)
    except Refused as e:
        print(f"benchmark: {e}; nothing measured", file=sys.stderr)
        return 2

    metrics = {}
    for m in cell_metrics(bench, cell, bool(args.trace)):
        value = reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.peak}
    if args.trace and run.busy_s is not None:
        device["busy_s"] = run.busy_s
        device["window_s"] = run.window_s
    checks = {k: {"value": v, "limit": 0} for k, v in run.checks.items()}
    attempted = sum(r["pending"] for r in run.sessions)
    failed = sum(sum(r["check"].values()) for r in run.sessions)
    print(json.dumps({
        "cell": cell["name"], "seed": args.seed, "sessions": len(run.sessions),
        "warm_sessions": run.n_warm, "device_sessions": run.device_sessions,
        "fallbacks": run.fell_back[:3],
        # phase, seconds, pending, binds, evictions, mode, check sum, compiles
        "per_session": [(r["phase"], round(r["total_s"], 4), r["pending"],
                         len(r["binds"]), len(r["evicts"]),
                         r["profile"].get("mode"), sum(r["check"].values()),
                         r["compiles"])
                        for r in run.records]}), file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace and run.breakdown is not None:
        result["breakdown"] = run.breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
