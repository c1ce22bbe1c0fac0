"""Backfill decides whether it has anything to place before it builds the
dense preempt view (ops/evict.py ``_BackfillPlan``).

A session without a zero-request PENDING task in a valid, non-Pending job
builds no view, is trivial, and records no fallback, even outside the
view's envelope. A session with such tasks builds the view and places them
exactly as the oracle path (``VOLCANO_TPU_EVICT=0``) does. The mode gates
still come first."""

from __future__ import annotations

import random

import pytest

from tests.helpers import close_session, make_cache, make_tiers, open_session
from tests.test_evict_kernel import _run
from volcano_tpu.api import objects
from volcano_tpu.ops import evict as evict_mod
from volcano_tpu.ops import preemptview
from volcano_tpu.scheduler.framework import get_action
from volcano_tpu.scheduler.util.test_utils import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource_list_with_pods,
)

TIERS = (["gang"], ["predicates"])


def _base(nodes: int = 4, pods: int = 16):
    """Running and pending pods that all request resources."""
    c = make_cache()
    c.add_queue(build_queue("default"))
    for n in range(nodes):
        c.add_node(build_node(
            f"node-{n:03d}",
            build_resource_list_with_pods("8", "16Gi", pods=pods),
            labels={"zone": "a" if n % 2 else "b"}))
    c.add_pod_group(build_pod_group("run", namespace="bf", min_member=1))
    c.add_pod(build_pod("bf", "run-t0", "node-000", objects.POD_PHASE_RUNNING,
                        {"cpu": "1", "memory": "1Gi"}, "run"))
    c.add_pod_group(build_pod_group("big", namespace="bf", min_member=2))
    for i in range(2):
        c.add_pod(build_pod("bf", f"big-t{i}", "", objects.POD_PHASE_PENDING,
                            {"cpu": "2", "memory": "2Gi"}, "big"))
    return c


def _best_effort(c, pg: str, n: int, min_member: int = 1,
                 phase: str = objects.PodGroupPhase.INQUEUE, **pod_kw):
    c.add_pod_group(build_pod_group(pg, namespace="bf", min_member=min_member,
                                    phase=phase))
    for i in range(n):
        c.add_pod(build_pod("bf", f"{pg}-t{i}", "", objects.POD_PHASE_PENDING,
                            {}, pg, **pod_kw))
    return c


def _no_best_effort():
    return _base()


def _pending_phase():
    return _best_effort(_base(), "be", 2, phase=objects.PodGroupPhase.PENDING)


def _gang_invalid():
    return _best_effort(_base(), "be", 2, min_member=3)


def _scalar_dims():
    c = _base()
    rl = build_resource_list_with_pods("8", "16Gi", pods=16)
    rl["nvidia.com/gpu"] = "4"
    c.add_node(build_node("node-gpu", rl))
    return c


@pytest.fixture
def view_builds(monkeypatch):
    """Calls of preemptview.build, counted."""
    calls = []
    real = preemptview.build

    def counted(ssn):
        calls.append(ssn)
        return real(ssn)

    monkeypatch.setattr(preemptview, "build", counted)
    return calls


@pytest.mark.parametrize("cluster", [
    _no_best_effort, _pending_phase, _gang_invalid, _scalar_dims,
], ids=["no_best_effort", "pending_phase", "gang_invalid", "scalar_dims"])
def test_nothing_to_backfill_builds_no_view(cluster, view_builds,
                                            monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    ssn = open_session(cluster(), make_tiers(["tpuscore"], *TIERS))
    try:
        plan = evict_mod.build(ssn, "backfill")
        assert plan is not None and plan.trivial
        assert plan.tasks == [] and plan.view is None
        get_action("backfill").execute(ssn)
        prof = dict(ssn.plugins["tpuscore"].profile)
    finally:
        close_session(ssn)
    assert view_builds == []
    assert prof["evict_backfill"] == {"trivial": True}
    assert "evict_backfill_fallback" not in prof, prof


def _mixed_best_effort(seed: int):
    """Zero-request tasks over several jobs, some held to one zone, on
    nodes with few pod slots: placement follows the evolving pod count,
    and some tasks find no node."""
    rng = random.Random(seed)
    c = _base(nodes=5, pods=3)
    for g in range(4):
        kw = {"node_selector": {"zone": "a"}} if rng.random() < 0.5 else {}
        _best_effort(c, f"be-{g}", rng.choice([2, 3, 4]), **kw)
    _best_effort(c, "be-held", 2, phase=objects.PodGroupPhase.PENDING)
    return c


@pytest.mark.parametrize("seed", [3, 8])
def test_zero_request_tasks_match_the_oracle(seed, view_builds,
                                             monkeypatch):
    got = _run(_mixed_best_effort(seed), TIERS, True, monkeypatch,
               actions=("backfill",))
    assert view_builds, "the plan builds the dense view"
    want = _run(_mixed_best_effort(seed), TIERS, False, monkeypatch,
                actions=("backfill",))
    assert got[0] == want[0]          # placements and fit errors, per task
    assert got[1] == want[1]          # binds
    stage = got[3][0]["evict_backfill"]
    assert stage["tasks"] > stage["placed"] > 0, stage
    assert "evict_backfill_fallback" not in got[3][0]


@pytest.mark.parametrize("mode", ["env", "tpuscore_off"])
@pytest.mark.parametrize("cluster", [_no_best_effort, _mixed_best_effort],
                         ids=["no_best_effort", "best_effort"])
def test_unarmed_modes_return_none(mode, cluster, view_builds, monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_EVICT", "0" if mode == "env" else "1")
    tiers = make_tiers(["tpuscore"], *TIERS) if mode == "env" \
        else make_tiers(*TIERS)
    c = cluster(3) if cluster is _mixed_best_effort else cluster()
    ssn = open_session(c, tiers)
    try:
        assert evict_mod.build(ssn, "backfill") is None
        if mode == "env":
            prof = ssn.plugins["tpuscore"].profile
            assert prof["evict_backfill_fallback"] == "VOLCANO_TPU_EVICT=0"
    finally:
        close_session(ssn)
    assert view_builds == []
