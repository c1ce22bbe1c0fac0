"""The chip entry points on the CPU: compile-cache placement, and where a
session that leaves the device path records it.

``benchmark/harness.py``'s ``Fallbacks`` reader holds every timed session
to zero host fallbacks. It reads the session profile's ``fallback`` and
``*_fallback`` keys, replica rebuilds named ``error:*``, the process
fallback counter, the degrade ladder's ``per_action_fallbacks`` and the
profile's ``mode``. The cases below drive each way off the device path on
small CPU clusters and check that the reader sees it.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from tests.helpers import close_session, make_cache, make_tiers, open_session
from tests.test_evict_kernel import ACTIONS, TIER_SETS, _overcommit_cluster
from tests.test_tpu_parity import DEFAULT_TIERS, gang_cluster
from volcano_tpu.api import objects
from volcano_tpu.scheduler.framework import run_actions
from volcano_tpu.scheduler.util.test_utils import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource_list_with_pods,
)
from volcano_tpu.utils import jaxcompile

REPO = Path(__file__).resolve().parents[1]

ROUNDS = {"tpuscore": {"tpuscore.mode": "rounds"}}


@pytest.mark.parametrize("env,expected", [
    ({"JAX_COMPILATION_CACHE_DIR": "/var/cache/jax"},
     ("/var/cache/jax", True)),
    ({}, (str(REPO / ".jax_cache"), False)),
])
def test_compile_cache_dir(env, expected):
    """The env var wins and nothing else is set; otherwise the fixed
    in-checkout directory (never a temp name, pid or timestamp)."""
    assert jaxcompile.compile_cache_dir(env) == expected


def _fallbacks_reader():
    spec = importlib.util.spec_from_file_location(
        "_benchmark_harness", REPO / "benchmark" / "harness.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Fallbacks()


def _session(cache, tiers_spec, args, actions=("allocate",)) -> dict:
    ssn = open_session(cache, make_tiers(["tpuscore"], *tiers_spec,
                                         arguments=args))
    try:
        run_actions(ssn, actions)
        return dict(ssn.plugins["tpuscore"].profile)
    finally:
        close_session(ssn)


def _gangs(n_nodes: int = 24):
    cache = make_cache()
    gang_cluster(n_groups=12, min_member=4, n_nodes=n_nodes)(cache)
    return cache


def _rounds(monkeypatch):
    return _session(_gangs(), DEFAULT_TIERS, ROUNDS)


def _auto(monkeypatch):
    return _session(_gangs(), DEFAULT_TIERS, {})


def _all_residue(monkeypatch):
    """Every pending pod carries a required pod-affinity term, which the
    encoder leaves to the serial residue pass."""
    cache = make_cache()
    cache.add_queue(build_queue("default"))
    for g in range(6):
        pg = f"pg{g}"
        cache.add_pod_group(build_pod_group(pg, namespace="ns1", min_member=2))
        for i in range(2):
            pod = build_pod("ns1", f"{pg}-p{i}", "", objects.POD_PHASE_PENDING,
                            {"cpu": "1", "memory": "1Gi"}, pg,
                            labels={"app": "web"})
            pod.spec.affinity = objects.Affinity(
                pod_affinity=objects.PodAffinity(required_terms=[
                    objects.PodAffinityTerm(
                        label_selector=objects.LabelSelector(
                            match_labels={"app": "web"}),
                        topology_key="zone")]))
            cache.add_pod(pod)
    for n in range(20):
        cache.add_node(build_node(
            f"node-{n:03d}", build_resource_list_with_pods("8", "16Gi"),
            labels={"zone": f"z{n % 2}"}))
    return _session(cache, DEFAULT_TIERS, ROUNDS)


def _scalar_overcommit():
    """An overcommitted cluster with one GPU node: scalar resource
    dimensions are outside the evict kernels' envelope."""
    cache = _overcommit_cluster(11, nodes=12)
    rl = build_resource_list_with_pods("8", "16Gi", pods=64)
    rl["nvidia.com/gpu"] = "4"
    cache.add_node(build_node("node-gpu", rl))
    return cache


def _fuse_outside_envelope(monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    monkeypatch.setenv("VOLCANO_TPU_FUSE", "1")
    return _session(_scalar_overcommit(), TIER_SETS[0], ROUNDS, ACTIONS)


def _evict_scalar_dimension(monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_EVICT", "1")
    monkeypatch.setenv("VOLCANO_TPU_FUSE", "0")
    return _session(_scalar_overcommit(), TIER_SETS[0], ROUNDS, ACTIONS)


def _kernel_raises(monkeypatch):
    from volcano_tpu.ops import rounds

    def boom(*args, **kwargs):
        raise RuntimeError("injected kernel failure")

    monkeypatch.setattr(rounds, "solve_rounds_packed", boom)
    return _session(_gangs(), DEFAULT_TIERS, ROUNDS)


def _replica_delta_raises(monkeypatch):
    """The standing replica's row scatter fails on a warm session: it
    restages wholesale and names the error among its rebuilds."""
    from volcano_tpu.ops import replica

    cache = _gangs()
    _session(cache, DEFAULT_TIERS, ROUNDS)
    cache.add_pod_group(build_pod_group("late", namespace="ns1",
                                        min_member=1))
    cache.add_pod(build_pod("ns1", "late-p0", "", objects.POD_PHASE_PENDING,
                            {"cpu": "1", "memory": "1Gi"}, "late"))

    def boom(self, *args, **kwargs):
        raise RuntimeError("injected scatter failure")

    monkeypatch.setattr(replica.DeviceReplica, "_delta", boom)
    return _session(cache, DEFAULT_TIERS, ROUNDS)


@pytest.fixture
def fresh_counters():
    from volcano_tpu.scheduler import degrade, metrics

    metrics.reset()
    degrade.reset()
    yield
    metrics.reset()
    degrade.reset()


EVICT_KEYS = {"evict_backfill_fallback", "evict_preempt_fallback",
              "evict_reclaim_fallback"}


# the keys the reader reports, a profile key whose reason names the cause,
# and the deltas of (fallback counter, per-action fallbacks) it reports
@pytest.mark.parametrize("drive,keys,reason_key,reason,counters", [
    (_rounds, set(), None, None, None),
    (_auto, {"fallback", "mode"}, "fallback", "below rounds threshold",
     None),
    (_all_residue, {"fallback", "mode"}, "fallback", "residue", None),
    (_fuse_outside_envelope, {"fuse_fallback", "counters"} | EVICT_KEYS,
     "fuse_fallback", "scalar", [4, 0]),
    (_evict_scalar_dimension, {"counters"} | EVICT_KEYS,
     "evict_preempt_fallback", "scalar", [3, 0]),
    (_kernel_raises, {"fallback", "mode", "counters"}, "fallback",
     "solve error", [0, 1]),
    (_replica_delta_raises, {"replica_rebuilds"}, None, None, None),
], ids=["rounds", "auto_below_gate", "all_residue", "fuse_envelope",
        "evict_scalar", "kernel_raises", "replica_error"])
def test_leaving_the_device_path_is_recorded(
        drive, keys, reason_key, reason, counters, monkeypatch,
        fresh_counters):
    from volcano_tpu.scheduler import metrics

    reader = _fallbacks_reader()
    profile = drive(monkeypatch)
    found = reader.of(profile)
    assert set(found) == keys, (found, profile)
    if reason_key is not None:
        assert reason in profile[reason_key], profile
    if counters is not None:
        assert found["counters"] == counters, found
    if drive is _rounds:
        assert profile["mode"] == "rounds", profile
    if drive is _fuse_outside_envelope:
        assert metrics.registry().device_fallbacks.get(("fuse",)) == 1
    if drive is _replica_delta_raises:
        assert found["replica_rebuilds"] == ["error:RuntimeError"], found
