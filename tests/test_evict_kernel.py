"""Batched device eviction (ops/evict.py) vs the serial statement walk.

The contract: within the modeled envelope the batched preempt/reclaim/
backfill actions are bindings-and-evictions-IDENTICAL to the old path
(`VOLCANO_TPU_EVICT=0`) — same evictions in the same cache-effector order,
same pipelined placements, same post-session accounting (node vectors, drf
job shares, proportion queue shares), over randomized overcommitted
clusters including gang preemptors, multi-queue reclaim tiers, and
PDB-driven minAvailable edge cases. The warm path must reuse the compiled
programs (CompileWatcher.assert_no_compiles)."""

from __future__ import annotations

import os
import random

import numpy as np
import pytest

from tests.helpers import close_session, make_cache, make_tiers, open_session
from volcano_tpu.api import objects
from volcano_tpu.api.types import TaskStatus
from volcano_tpu.scheduler.framework import get_action
from volcano_tpu.scheduler.util.test_utils import (
    build_node, build_pod, build_pod_group, build_queue,
    build_resource_list_with_pods,
)

ACTIONS = ("allocate", "backfill", "preempt", "reclaim")

# conf shapes: cfg4's two-tier default (gang decides both victim kinds),
# a reclaim-tier conf where gang ∧ proportion decide reclaim (the
# deserved-floor walk engages), and a single tier where gang ∧ drf ∧
# conformance decide preempt (the cumulative-share walk engages)
TIER_SETS = [
    (["priority", "gang"], ["drf", "predicates", "proportion", "nodeorder"]),
    (["priority"], ["gang", "proportion", "predicates", "nodeorder"]),
    (["gang", "drf", "conformance", "proportion", "predicates"],),
]


def _overcommit_cluster(seed: int, nodes: int = 6, running_jobs: int = 12,
                        tasks_per_job: int = 4, queues: int = 2,
                        hi_jobs: int = 4):
    """Dense running fill bound round-robin with almost no idle headroom,
    pending high-priority gangs (preemptors), a starved low-weight queue
    (reclaimers), best-effort pods (backfill), and PDBs overriding some
    victims' minAvailable."""
    rng = random.Random(seed)
    c = make_cache()
    for q in range(queues):
        c.add_queue(build_queue(f"q{q}", weight=1 + q))
    per_node = running_jobs * tasks_per_job // nodes + 1
    cpu = per_node + 2
    for n in range(nodes):
        c.add_node(build_node(
            f"node-{n:03d}",
            build_resource_list_with_pods(str(cpu), f"{cpu * 2}Gi", pods=64)))
    slot = 0
    for g in range(running_jobs):
        pg = f"run-{g:03d}"
        queue = f"q{g % queues}"
        min_member = rng.choice([1, 1, 2, tasks_per_job])
        c.add_pod_group(build_pod_group(
            pg, namespace="ev", min_member=min_member, queue=queue))
        if rng.random() < 0.25:
            # PDB-driven minAvailable override: the gang victim gate then
            # runs against the PDB's floor, not the PodGroup's
            c.add_pdb(objects.PodDisruptionBudget(
                metadata=objects.ObjectMeta(name=pg, namespace="ev"),
                min_available=rng.choice([1, 2, tasks_per_job])))
        for i in range(tasks_per_job):
            pod = build_pod(
                "ev", f"{pg}-t{i}", f"node-{slot % nodes:03d}",
                objects.POD_PHASE_RUNNING,
                {"cpu": "1000m", "memory": rng.choice(["1Gi", "2Gi"])},
                pg, priority=rng.choice([0, 1, 5]))
            if rng.random() < 0.1:
                # conformance-protected victims
                pod.spec.priority_class_name = objects.SYSTEM_CLUSTER_CRITICAL
            c.add_pod(pod)
            slot += 1
    for g in range(hi_jobs):
        pg = f"hi-{g:02d}"
        mm = rng.choice([1, 1, 2])
        c.add_pod_group(build_pod_group(
            pg, namespace="ev", min_member=mm, queue=f"q{g % queues}"))
        for i in range(2):
            c.add_pod(build_pod(
                "ev", f"{pg}-t{i}", "", objects.POD_PHASE_PENDING,
                {"cpu": f"{rng.choice([3000, 4000])}m",
                 "memory": rng.choice(["4Gi", "8Gi"])},
                pg, priority=100))
    # mixed jobs: RUNNING victims + PENDING preemptors in one job, so the
    # job sits in the preemptors heap while other preemptors evict its
    # running tasks — its drf-share/gang-ready heap keys mutate IN-heap,
    # which is exactly the case where heapq pop order is heap-structural
    # rather than an argmin (the kernel's sift simulation must match)
    for g in range(3):
        pg = f"mx-{g:02d}"
        c.add_pod_group(build_pod_group(
            pg, namespace="ev", min_member=1, queue=f"q{g % queues}"))
        for i in range(2):
            c.add_pod(build_pod(
                "ev", f"{pg}-r{i}", f"node-{(slot + i) % nodes:03d}",
                objects.POD_PHASE_RUNNING,
                {"cpu": "1000m", "memory": "1Gi"}, pg, priority=1))
        for i in range(2):
            c.add_pod(build_pod(
                "ev", f"{pg}-p{i}", "", objects.POD_PHASE_PENDING,
                {"cpu": "2000m", "memory": "2Gi"}, pg,
                priority=rng.choice([20, 100])))
    # starved-queue reclaimers (cross-queue eviction pressure)
    for g in range(2):
        pg = f"rc-{g:02d}"
        c.add_pod_group(build_pod_group(
            pg, namespace="ev", min_member=1, queue=f"q{queues - 1}"))
        for i in range(2):
            c.add_pod(build_pod(
                "ev", f"{pg}-t{i}", "", objects.POD_PHASE_PENDING,
                {"cpu": "2000m", "memory": "2Gi"}, pg, priority=10))
    # best-effort pods for backfill
    for g in range(2):
        pg = f"be-{g:02d}"
        c.add_pod_group(build_pod_group(
            pg, namespace="ev", min_member=1, queue="q0"))
        for i in range(2):
            c.add_pod(build_pod(
                "ev", f"{pg}-t{i}", "", objects.POD_PHASE_PENDING, {},
                pg, priority=1))
    return c


def _res_tuple(r):
    return (round(r.milli_cpu, 6), round(r.memory, 3),
            tuple(sorted((r.scalar_resources or {}).items())))


def _session_signature(ssn):
    """Everything the parity contract covers: task statuses/placements,
    node accounting, job readiness, plugin shares."""
    tasks = sorted(
        (t.uid, int(t.status), t.node_name)
        for job in ssn.jobs.values() for t in job.tasks.values())
    nodes = sorted(
        (n.name, _res_tuple(n.idle), _res_tuple(n.used),
         _res_tuple(n.releasing), len(n.tasks))
        for n in ssn.nodes.values())
    jobs = sorted(
        (j.uid, j.ready_task_num(), j.waiting_task_num())
        for j in ssn.jobs.values())
    drf = ssn.plugins.get("drf")
    shares = sorted(
        (uid, a.share, _res_tuple(a.allocated))
        for uid, a in drf.job_attrs.items()) if drf is not None else []
    prop = ssn.plugins.get("proportion")
    qshares = sorted(
        (q, a.share, _res_tuple(a.allocated))
        for q, a in prop.queue_opts.items()) if prop is not None else []
    fit_errors = sorted(
        (uid, fe.error()) for job in ssn.jobs.values()
        for uid, fe in job.nodes_fit_errors.items())
    return dict(tasks=tasks, nodes=nodes, jobs=jobs, shares=shares,
                qshares=qshares, fit_errors=fit_errors)


def _run(cache, tiers_spec, evict_on, monkeypatch, sessions: int = 1,
         actions=ACTIONS):
    import volcano_tpu.ops.victimview as vv

    from volcano_tpu.scheduler import metrics

    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1" if evict_on else "0")
    # engage victim batching on the oracle path too (its own parity is
    # pinned by test_victimview)
    monkeypatch.setattr(vv.VictimSelector, "MIN_BATCH", 1)
    reg = metrics.registry()
    m0 = (reg.preemption_victims.get(), reg.preemption_attempts.get())
    sig = None
    profs = []
    for _ in range(sessions):
        ssn = open_session(cache, make_tiers(["tpuscore"], *tiers_spec))
        try:
            for name in actions:
                get_action(name).execute(ssn)
            sig = _session_signature(ssn)
            profs.append(dict(ssn.plugins["tpuscore"].profile))
        finally:
            close_session(ssn)
    sig["metrics"] = (reg.preemption_victims.get() - m0[0],
                      reg.preemption_attempts.get() - m0[1])
    return sig, dict(cache.binder.binds), list(cache.evictor.evicts), profs


@pytest.mark.parametrize("tiers_spec", TIER_SETS)
@pytest.mark.parametrize("seed", [11, 42, 7])
def test_fuzzed_action_parity(tiers_spec, seed, monkeypatch):
    got = _run(_overcommit_cluster(seed), tiers_spec, True, monkeypatch)
    want = _run(_overcommit_cluster(seed), tiers_spec, False, monkeypatch)
    assert got[0] == want[0], (tiers_spec, seed)
    assert got[1] == want[1]          # binds
    assert got[2] == want[2]          # evictions, in effector order
    # the batched path must actually have run (not silently fallen back)
    prof = got[3][0]
    for kind in ("preempt", "reclaim", "backfill"):
        assert f"evict_{kind}" in prof, prof.get(
            f"evict_{kind}_fallback", prof)


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(100, 116)))
def test_fuzzed_action_parity_wide(seed, monkeypatch):
    """Wider fuzz band: randomized cluster shapes (fresh buckets, fresh
    compiles) across all tier sets."""
    rng = random.Random(seed * 7)
    kw = dict(nodes=rng.choice([4, 7, 9]),
              running_jobs=rng.choice([8, 14, 18]),
              tasks_per_job=rng.choice([3, 4, 5]),
              queues=rng.choice([2, 3]),
              hi_jobs=rng.choice([3, 5]))
    tiers_spec = TIER_SETS[seed % len(TIER_SETS)]
    got = _run(_overcommit_cluster(seed, **kw), tiers_spec, True,
               monkeypatch)
    want = _run(_overcommit_cluster(seed, **kw), tiers_spec, False,
                monkeypatch)
    assert got[0] == want[0], (kw, tiers_spec)
    assert got[1] == want[1]
    assert got[2] == want[2]


@pytest.mark.parametrize("seed", [21])
def test_consecutive_sessions_parity(seed, monkeypatch):
    """Two back-to-back sessions on one cache: the second one's snapshot is
    delta-maintained from the SnapshotKeeper dirty-sets the eviction
    effectors marked — accounting must stay identical to the serial arm."""
    tiers = TIER_SETS[0]
    got = _run(_overcommit_cluster(seed), tiers, True, monkeypatch,
               sessions=2)
    want = _run(_overcommit_cluster(seed), tiers, False, monkeypatch,
                sessions=2)
    assert got[0] == want[0]
    assert got[1] == want[1]
    assert got[2] == want[2]


def test_evictions_mark_snapshot_dirty_sets(monkeypatch):
    """Replayed evictions go through cache.evict, so the keeper's dirty
    sets must cover every evicted task's job and node before the next
    snapshot rebuild."""
    cache = _overcommit_cluster(11)
    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    ssn = open_session(cache, make_tiers(["tpuscore"], *TIER_SETS[0]))
    try:
        for name in ACTIONS:
            get_action(name).execute(ssn)
        evicted = [
            t for job in ssn.jobs.values() for t in job.tasks.values()
            if t.status == TaskStatus.RELEASING]
        if evicted:  # seed 11 evicts (asserted in the parity fuzz above)
            assert cache.snap_keeper.stats.get("evict_marks", 0) > 0
            for t in evicted:
                assert t.job in cache.snap_keeper.dirty_jobs
                assert t.node_name in cache.snap_keeper.dirty_nodes
    finally:
        close_session(ssn)


def test_warm_path_pins_no_compiles(monkeypatch):
    """Second identically-shaped session must reuse every compiled evict
    program (bucketed shapes + static spec)."""
    from volcano_tpu.utils.jaxcompile import CompileWatcher

    tiers = TIER_SETS[0]
    _run(_overcommit_cluster(11), tiers, True, monkeypatch)
    watcher = CompileWatcher.install()
    with watcher.assert_no_compiles("warm batched evict session"):
        _run(_overcommit_cluster(11), tiers, True, monkeypatch)


def test_env_flag_forces_old_path(monkeypatch):
    from volcano_tpu.ops import evict as evict_mod

    cache = _overcommit_cluster(11)
    monkeypatch.setenv("VOLCANO_TPU_EVICT", "0")
    ssn = open_session(cache, make_tiers(["tpuscore"], *TIER_SETS[0]))
    try:
        assert evict_mod.build(ssn, "preempt") is None
        assert evict_mod.build(ssn, "reclaim") is None
        assert evict_mod.build(ssn, "backfill") is None
    finally:
        close_session(ssn)


def test_scalar_resources_fall_back(monkeypatch):
    """Scalar dims leave the modeled envelope (Resource nil-map compare
    asymmetries): build must refuse, the action must still work serially."""
    from volcano_tpu.ops import evict as evict_mod

    cache = _overcommit_cluster(11)
    rl = build_resource_list_with_pods("8", "16Gi", pods=64)
    rl["nvidia.com/gpu"] = "4"
    cache.add_node(build_node("node-gpu", rl))
    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    ssn = open_session(cache, make_tiers(["tpuscore"], *TIER_SETS[0]))
    try:
        assert evict_mod.build(ssn, "preempt") is None
        prof = ssn.plugins["tpuscore"].profile
        assert "scalar" in prof["evict_preempt_fallback"]
        for name in ACTIONS:  # the old path still runs end-to-end
            get_action(name).execute(ssn)
    finally:
        close_session(ssn)


def test_custom_victim_plugin_falls_back(monkeypatch):
    from volcano_tpu.ops import evict as evict_mod

    cache = _overcommit_cluster(11)
    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    ssn = open_session(cache, make_tiers(["tpuscore"], *TIER_SETS[0]))
    try:
        ssn.add_preemptable_fn("priority", lambda c, cs: cs)
        assert evict_mod.build(ssn, "preempt") is None
        # reclaimable registry untouched -> still batchable
        assert evict_mod.build(ssn, "reclaim") is not None
    finally:
        close_session(ssn)


# ---------------------------------------------------------------------------
# backfill diagnostics-budget coverage (backfill.py replay_budget)
# ---------------------------------------------------------------------------


def _backfill_failure_cluster(failing: int):
    """Zero-request pods whose node selector matches nothing: every one
    fails on the dense path, exercising the bounded diagnostics replay."""
    c = make_cache()
    c.add_queue(build_queue("default"))
    for n in range(3):
        c.add_node(build_node(
            f"node-{n:03d}",
            build_resource_list_with_pods("8", "16Gi", pods=16),
            labels={"zone": "a"}))
    for g in range(failing):
        pg = f"bf-{g:03d}"
        c.add_pod_group(build_pod_group(
            pg, namespace="bf", min_member=1, queue="default"))
        c.add_pod(build_pod(
            "bf", f"{pg}-t0", "", objects.POD_PHASE_PENDING, {}, pg,
            node_selector={"zone": "nowhere"}))
    return c


@pytest.mark.parametrize("evict_on", [True, False])
def test_backfill_replay_budget_serial_fidelity(evict_on, monkeypatch):
    """A session with more view-path backfill failures than the replay
    budget (8) must keep the dense path and still produce serial-fidelity
    per-node FitErrors for the first 8 tasks; the rest get the summary
    error. Both the batched kernel path and the dense-view path honor the
    same budget, and their FitErrors match the fully serial walk."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1" if evict_on else "0")
    failing = 12
    cache = _backfill_failure_cluster(failing)
    ssn = open_session(
        cache, make_tiers(["tpuscore"], ["gang"], ["predicates"]))
    try:
        get_action("backfill").execute(ssn)
        prof = dict(ssn.plugins["tpuscore"].profile)
        errors = {}
        for job in ssn.jobs.values():
            for uid, fe in job.nodes_fit_errors.items():
                errors[uid] = fe
        assert len(errors) == failing
        detailed = [fe for fe in errors.values() if fe.nodes]
        summary = [fe for fe in errors.values() if not fe.nodes]
        assert len(detailed) == 8          # replay budget spent exactly
        assert len(summary) == failing - 8
        for fe in detailed:                # serial-fidelity per-node reasons
            assert len(fe.nodes) == 3
        for fe in summary:
            assert fe.err == "0/3 nodes are feasible for backfill"
    finally:
        close_session(ssn)

    # serial-fidelity: the serial walk's per-node reasons are identical
    cache2 = _backfill_failure_cluster(failing)
    ssn2 = open_session(cache2, make_tiers(["gang"], ["predicates"]))
    try:
        get_action("backfill").execute(ssn2)
        serial_errors = {}
        for job in ssn2.jobs.values():
            for uid, fe in job.nodes_fit_errors.items():
                serial_errors[uid] = fe
        # the serial walk records per-node reasons for EVERY task; the
        # dense/batched path's first-8 detailed errors must match it
        for uid, fe in errors.items():
            if fe.nodes:
                assert fe.error() == serial_errors[uid].error()
    finally:
        close_session(ssn2)
    if evict_on:
        assert "evict_backfill" in prof


# ---------------------------------------------------------------------------
# gang verdict over carried per-slot views (no job-table gather in a walk)
# ---------------------------------------------------------------------------


def _reference_gang_verdict(enc, st, claimees):
    """The gather-based gang verdict: each call reads minAvailable and
    ready from the job tables through vic_job."""
    import jax.numpy as jnp
    from jax import lax

    jv = enc["vic_job"]
    min_av = enc["job_min_av"][jv]
    budget0 = jnp.maximum(st["ready"][jv] - min_av, 0)

    def body(v, carry):
        used, out = carry
        allow = (min_av[:, v] == 1) | (used[:, v] < budget0[:, v])
        nominate = claimees[:, v] & allow
        out = out.at[:, v].set(nominate)
        upd = nominate[:, None] & enc["vic_samejob"][:, v, :]
        return jnp.where(upd, used + 1, used), out

    return lax.fori_loop(0, jv.shape[1], body, (
        jnp.zeros(jv.shape, jnp.int32), jnp.zeros(jv.shape, bool)))[1]


def _checked_machine(plan, monkeypatch):
    """Run ``plan``'s machine with every gang verdict compared against the
    reference and every statement discard checked. Returns (final state,
    tallies over the run)."""
    import jax
    import jax.numpy as jnp

    from volcano_tpu.ops import evict as evict_mod

    tally = dict(calls=0, differ=0, zero_budget=0, unbudgeted=0,
                 restored=0, stale_after_discard=0)

    def note(names):
        def cb(*vals):
            for k, v in zip(names, vals):
                tally[k] += int(v)
        return cb

    new_verdict, old_discard = evict_mod._gang_verdict, evict_mod._discard

    def verdict(enc, st, claimees):
        got = new_verdict(enc, st, claimees)
        want = _reference_gang_verdict(enc, st, claimees)
        jv = enc["vic_job"]
        min_av = enc["job_min_av"][jv]
        jax.debug.callback(
            note(("calls", "differ", "zero_budget", "unbudgeted")),
            1, jnp.sum(got != want),
            jnp.sum(claimees & (min_av > 1) & (st["ready"][jv] <= min_av)),
            jnp.sum(claimees & (min_av == 1)))
        return got

    def discard(enc, st, stmt_start):
        out = old_discard(enc, st, stmt_start)
        jax.debug.callback(
            note(("restored", "stale_after_discard")),
            jnp.sum(out["vic_ready"] - st["vic_ready"]),
            jnp.sum(out["vic_ready"] != out["ready"][enc["vic_job"]]))
        return out

    monkeypatch.setattr(evict_mod, "_gang_verdict", verdict)
    monkeypatch.setattr(evict_mod, "_discard", discard)
    if plan.kind == "preempt":
        machine, state0 = evict_mod.preempt_machine, evict_mod.preempt_state0
    else:
        machine, state0 = evict_mod.reclaim_machine, evict_mod.reclaim_state0
    enc = {k: jnp.asarray(v) for k, v in plan.arrays.items()}
    st = jax.jit(lambda e: machine(plan.spec, e, state0(e)))(enc)
    jax.effects_barrier()
    return jax.tree_util.tree_map(np.asarray, st), tally


def _plan_after(kind, seed, monkeypatch):
    """(session, plan) for ``kind`` on the seeded overcommit cluster once
    the actions before it have run (allocate; preempt before reclaim)."""
    from volcano_tpu.ops import evict as evict_mod

    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    ssn = open_session(_overcommit_cluster(seed),
                       make_tiers(["tpuscore"], *TIER_SETS[0]))
    get_action("allocate").execute(ssn)
    if kind == "reclaim":
        get_action("preempt").execute(ssn)
    return ssn, evict_mod.build(ssn, kind)


@pytest.mark.parametrize("seed", [0, 4, 8])
@pytest.mark.parametrize("kind", ["preempt", "reclaim"])
def test_gang_verdict_matches_job_table_reference(kind, seed, monkeypatch):
    """The gang verdict read from the carried per-slot views equals the
    job-table reference at every walk iteration, through budgets at zero,
    unbudgeted (minAvailable == 1) gangs, cuts and (preempt) discarded
    statements, and vic_ready ends equal to ready[vic_job]."""
    ssn, plan = _plan_after(kind, seed, monkeypatch)
    try:
        assert plan is not None and not plan.trivial
        assert "gang" in plan.spec.victim_fns
        st, tally = _checked_machine(plan, monkeypatch)
    finally:
        close_session(ssn)
    assert tally["calls"] > 0 and tally["differ"] == 0, tally
    assert tally["zero_budget"] > 0 and tally["unbudgeted"] > 0, tally
    np.testing.assert_array_equal(
        st["vic_ready"], st["ready"][plan.arrays["vic_job"]])
    evicted = plan.arrays["vic_valid"] & ~st["alive"]
    assert evicted.any()
    if kind == "preempt":
        # a discarded statement gave its evictions back to vic_ready
        assert tally["restored"] > 0, tally
        assert tally["stale_after_discard"] == 0, tally


def _slot_gathers(jaxpr, shape, in_loop=False, found=None):
    """One flag per gather with output ``shape`` in ``jaxpr`` and the
    sub-jaxprs of its eqns (while, scan, cond, jit): whether it runs
    inside a while or scan body."""
    from jax.extend import core as jcore

    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" and tuple(eqn.outvars[0].aval.shape) == shape:
            found.append(in_loop)
        inner = in_loop or name in ("while", "scan")
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    _slot_gathers(sub, shape, inner, found)
    return found


@pytest.mark.parametrize("tiers", range(len(TIER_SETS)))
@pytest.mark.parametrize("kind", ["preempt", "reclaim"])
def test_no_slot_gather_inside_machine_loops(kind, tiers, monkeypatch):
    """No [N, V] gather (vic_job's shape) runs inside a loop of the evict
    machines: the per-slot views are gathered once at machine entry."""
    import jax

    from volcano_tpu.ops import evict as evict_mod

    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    ssn = open_session(_overcommit_cluster(11),
                       make_tiers(["tpuscore"], *TIER_SETS[tiers]))
    try:
        get_action("allocate").execute(ssn)
        plan = evict_mod.build(ssn, kind)
    finally:
        close_session(ssn)
    assert plan is not None and not plan.trivial
    solve = evict_mod.solve_preempt if kind == "preempt" \
        else evict_mod.solve_reclaim
    jaxpr = jax.make_jaxpr(lambda e: solve(plan.spec, e))(plan.arrays)
    shape = plan.arrays["vic_job"].shape
    assert shape[0] != shape[1]  # the shape names vic_job's gathers alone
    found = _slot_gathers(jaxpr.jaxpr, shape)
    assert found.count(False) >= 1   # the entry gathers are seen
    assert found.count(True) == 0, found
