"""Spans at the session's layer boundaries (volcano_tpu/utils/trace.py).

A small cfg5-shaped cluster (32-cpu nodes, gangs of 8 with minMember 4,
one queue, ``enqueue, allocate, backfill``) runs whole sessions under the
profiler; the ``.xplane.pb`` it writes is read back with ``ProfileData``.
The span tree must put the device hop inside the allocate action, the
per-session span count must not grow with the node count, and with the
profiler off the profile keys the benchmark reads keep their values."""

from __future__ import annotations

import collections
import glob
import os
import random

import pytest

import volcano_tpu.scheduler.actions  # noqa: F401  (register actions)
from volcano_tpu.api import objects
from volcano_tpu.bench.clusters import DEFAULT_TIERS, make_cache, make_tiers
from volcano_tpu.scheduler.framework import (
    close_session, open_session, run_actions)
from volcano_tpu.scheduler.util.test_utils import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource_list_with_pods,
)
from volcano_tpu.utils import trace

ACTIONS = ("enqueue", "allocate", "backfill")


def _cluster(nodes: int, gangs: int, seed: int = 5):
    rng = random.Random(seed)
    cache = make_cache()
    for g in range(gangs):
        pg = f"job-{g:05d}"
        cache.add_pod_group(build_pod_group(pg, namespace="bench",
                                            min_member=4))
        for i in range(8):
            cache.add_pod(build_pod(
                "bench", f"{pg}-t{i}", "", objects.POD_PHASE_PENDING,
                {"cpu": f"{rng.choice([250, 500, 1000, 2000])}m",
                 "memory": rng.choice(["512Mi", "1Gi", "2Gi"])}, pg))
    for n in range(nodes):
        cache.add_node(build_node(
            f"node-{n:05d}",
            build_resource_list_with_pods("32", "64Gi", pods=256)))
    cache.add_queue(build_queue("default"))
    return cache


def _session(cache, mode: str) -> dict:
    """One session; the tpuscore profile it left. ``rounds`` takes the
    device path, ``auto`` below its task gate the serial one."""
    tiers = make_tiers(["tpuscore"], *DEFAULT_TIERS,
                       arguments={"tpuscore": {"tpuscore.mode": mode}})
    ssn = open_session(cache, tiers)
    try:
        run_actions(ssn, ACTIONS)
        return dict(ssn.plugins["tpuscore"].profile)
    finally:
        close_session(ssn)


def _traced(out_dir, fn):
    """(fn's result, [(name, start ns, end ns)] of the vt.* spans in the
    .xplane.pb a capture around fn wrote, {name: [counts of each span]})."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(out_dir), "**", "*.xplane.pb"),
                        recursive=True)
    spans, counts = [], collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace.PREFIX):
                    start = int(ev.start_ns)
                    spans.append((ev.name, start, start + int(ev.duration_ns)))
                    counts[ev.name].append(dict(ev.stats))
    return out, sorted(spans, key=lambda s: s[1]), counts


def _inside(spans, name, parent):
    """Every ``name`` span lies in some ``parent`` span."""
    outer = [(s, e) for n, s, e in spans if n == parent]
    mine = [(s, e) for n, s, e in spans if n == name]
    assert mine, name
    return all(any(ps <= s and e <= pe for ps, pe in outer) for s, e in mine)


def _counts(fn) -> collections.Counter:
    """Spans per name that fn's traced work closed (the in-process record
    of utils/trace.py, read around one capture)."""
    import tempfile

    import jax

    before = len(trace.recorded())
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
    new = trace.recorded()[before:]
    return collections.Counter(name for name, _, _ in new)


def test_span_tree_of_a_device_session(tmp_path):
    _session(_cluster(40, 24), "rounds")  # compile outside the capture
    prof, spans, counts = _traced(
        tmp_path, lambda: _session(_cluster(40, 24), "rounds"))
    assert prof["mode"] == "rounds", prof.get("fallback")
    names = {n for n, _, _ in spans}
    for leaf in ("vt.encode", "vt.h2d", "vt.replica.store",
                 "vt.device.wait", "vt.apply"):
        assert _inside(spans, leaf, "vt.action.allocate"), leaf
    for child in ("vt.apply.prep", "vt.apply.loop", "vt.apply.bind",
                  "vt.apply.post"):
        assert _inside(spans, child, "vt.apply"), child
    # the encoder's node matrices and per-node bound check
    assert _inside(spans, "vt.encode.nodes", "vt.encode")
    assert [c["nodes"] for c in counts["vt.encode.nodes"]] == [40]
    # the rounds solve's dispatch, to its fetched packed result: int16 up
    # to 32,766 nodes (tasks, node mask and profile tail)
    (hop,) = [c for c in counts["vt.dispatch"] if "rounds" in c]
    assert hop["rounds"] == prof["rounds"] > 0
    assert hop["full_sweeps"] == prof["full_sweep_rounds"]
    assert hop["window_k"] == prof["window_k"]
    assert hop["d2h_bytes"] > 2 * (40 + 192)
    assert hop["d2h_bytes"] % 2 == 0
    assert {"vt.open.snapshot", "vt.open.plugin.tpuscore",
            "vt.action.enqueue", "vt.action.backfill", "vt.dispatch",
            "vt.close.flush_mirror", "vt.close.job_updater",
            "vt.close.plugin.gang"} <= names
    # the device hop is not the serial loop's
    assert "vt.serial.predicate" not in names
    # no zero-request task: backfill decides so before building the view
    (plan,) = counts["vt.evict.plan"]
    assert (plan["kind"], plan["tasks"], plan["view"]) == ("backfill", 0, 0)


def test_device_path_span_count_does_not_grow_with_nodes():
    _session(_cluster(40, 24), "rounds")
    _session(_cluster(160, 24), "rounds")
    small = _counts(lambda: _session(_cluster(40, 24), "rounds"))
    large = _counts(lambda: _session(_cluster(160, 24), "rounds"))
    assert small["vt.action.allocate"] == 1
    assert small["vt.encode.nodes"] == small["vt.dispatch"] == 1
    assert small == large


def test_serial_path_spans_grow_with_tasks_not_nodes():
    few = _counts(lambda: _session(_cluster(40, 4), "auto"))
    wide = _counts(lambda: _session(_cluster(160, 4), "auto"))
    more = _counts(lambda: _session(_cluster(40, 8), "auto"))
    assert few == wide
    # one predicate and one prioritize call per placed task
    assert few["vt.serial.predicate"] == 32
    assert few["vt.serial.prioritize"] == 32
    assert more["vt.serial.predicate"] == 64
    # encoded, then held below the task gate: no device hop
    assert few["vt.encode"] == 1 and "vt.dispatch" not in few


def test_profile_keys_without_the_profiler():
    before = len(trace.recorded())
    prof = _session(_cluster(40, 24), "rounds")
    assert len(trace.recorded()) == before  # nothing kept while off
    assert prof["mode"] == "rounds"
    for key in ("encode_s", "pack_s", "h2d_s", "apply_s"):
        assert prof[key] >= 0.0, key
    for gone in ("solve_s", "dispatch_s", "apply_prep_s", "apply_loop_s",
                 "apply_bind_s", "apply_post_s", "fuse_dispatch_s"):
        assert gone not in prof


def test_evict_stage_apply_without_the_profiler(monkeypatch):
    """The fused chain's evict stages keep their ``apply_s``, which the
    benchmark's apply_ms adds to the rounds apply."""
    import volcano_tpu.ops.victimview as vv
    from tests.test_evict_kernel import TIER_SETS, _overcommit_cluster

    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    monkeypatch.setenv("VOLCANO_TPU_FUSE", "1")
    monkeypatch.setattr(vv.VictimSelector, "MIN_BATCH", 1)
    tiers = make_tiers(["tpuscore"], *TIER_SETS[0], arguments={
        "tpuscore": {"tpuscore.mode": "rounds"}})
    ssn = open_session(_overcommit_cluster(11), tiers)
    try:
        action_ms = run_actions(
            ssn, ("allocate", "backfill", "preempt", "reclaim"))
        prof = dict(ssn.plugins["tpuscore"].profile)
    finally:
        close_session(ssn)
    assert prof.get("fuse") == 1, prof.get("fuse_fallback")
    assert set(action_ms) == {"allocate", "backfill", "preempt", "reclaim"}
    assert prof["evict_preempt"]["apply_s"] >= 0.0
    assert "solve_s" not in prof["evict_preempt"]


@pytest.mark.parametrize("fuse", ["0", "1"], ids=["standalone", "fused"])
def test_evict_plan_notes_backfill_tasks_and_view(fuse, tmp_path,
                                                  monkeypatch):
    """With best-effort pods the backfill plan builds (or, fused, reuses
    the preempt plan's) dense view, and its span says so."""
    import volcano_tpu.ops.victimview as vv
    from tests.test_evict_kernel import TIER_SETS, _overcommit_cluster

    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    monkeypatch.setenv("VOLCANO_TPU_FUSE", fuse)
    monkeypatch.setattr(vv.VictimSelector, "MIN_BATCH", 1)
    tiers = make_tiers(["tpuscore"], *TIER_SETS[0], arguments={
        "tpuscore": {"tpuscore.mode": "rounds"}})

    def run():
        ssn = open_session(_overcommit_cluster(11), tiers)
        try:
            run_actions(ssn, ("allocate", "backfill", "preempt", "reclaim"))
            return dict(ssn.plugins["tpuscore"].profile)
        finally:
            close_session(ssn)

    run()  # compile outside the capture
    prof, _, counts = _traced(tmp_path, run)
    kind = "fused" if fuse == "1" else "backfill"
    (plan,) = [c for c in counts["vt.evict.plan"] if c["kind"] == kind]
    assert plan["tasks"] == prof["evict_backfill"]["tasks"] > 0
    assert plan["view"] == 1


def test_span_times_into_a_profile_key():
    prof = {"encode_s": 1.0}
    with trace.span("x", into=(prof, "encode_s"), tasks=3) as sp:
        sp.note(nodes=4)
    assert sp.elapsed >= 0.0 and sp.name == "vt.x"
    assert prof["encode_s"] == pytest.approx(1.0 + sp.elapsed)
    with trace.step(7) as st:
        pass
    assert st.name == "vt.session" and st.elapsed >= 0.0


def test_profiler_port_option(monkeypatch):
    import jax.profiler

    from volcano_tpu.scheduler import __main__ as entry

    started = []
    monkeypatch.setattr(jax.profiler, "start_server", started.append)
    assert entry.parse_args([]).profiler_port == 0
    assert entry.start_profiler(entry.parse_args([]).profiler_port) is None
    assert started == []
    assert entry.parse_args(["--profiler-port", "9012"]).profiler_port \
        == 9012
    entry.start_profiler(9012)
    assert started == [9012]
