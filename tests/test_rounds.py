"""Rounds-mode solver invariants.

Rounds mode trades the serial loop's visit-granular ordering for bulk
placement (ops/rounds.py), so bindings are not bit-identical to the oracle.
These tests assert what IS guaranteed: feasibility of every placement under
the epsilon arithmetic and predicate masks, node capacity and pod-count
limits, gang all-or-nothing atomicity, and placement quality (>= the serial
loop's bind count on capacity-abundant clusters, since rounds mode sees every
node where the serial loop samples).
"""

from __future__ import annotations

import random

import numpy as np

from tests.helpers import make_cache, make_tiers
from tests.test_tpu_parity import DEFAULT_TIERS, gang_cluster
from volcano_tpu.api import objects
from volcano_tpu.api.resource import Resource
from volcano_tpu.ops.encoder import _limbs
from volcano_tpu.scheduler.framework import close_session, get_action, open_session
from volcano_tpu.utils.jaxcompile import CompileWatcher
from volcano_tpu.scheduler.util.test_utils import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource_list_with_pods,
)

ROUNDS_ARGS = {"tpuscore": {"tpuscore.mode": "rounds"}}


def run_rounds(populate, tiers=DEFAULT_TIERS):
    cache = make_cache()
    populate(cache)
    ssn = open_session(
        cache, make_tiers(["tpuscore"], *tiers, arguments=ROUNDS_ARGS))
    get_action("allocate").execute(ssn)
    prof = dict(ssn.plugins["tpuscore"].profile)
    assert prof.get("mode") == "rounds", prof
    assert "fallback" not in prof, prof
    close_session(ssn)
    return cache, prof


def run_serial(populate, tiers=DEFAULT_TIERS):
    cache = make_cache()
    populate(cache)
    ssn = open_session(cache, make_tiers(*tiers))
    get_action("allocate").execute(ssn)
    close_session(ssn)
    return cache.binder.binds


def check_invariants(cache, populate_min_members):
    """Feasibility + gang atomicity over the FakeBinder result."""
    binds = cache.binder.binds
    # rebuild node capacity from the cache's own node infos
    per_node = {}
    for key, node_name in binds.items():
        per_node.setdefault(node_name, []).append(key)
    for node_name, keys in per_node.items():
        node = cache.nodes[node_name]
        total = Resource.empty()
        for key in keys:
            ns, name = key.split("/")
            pg = name.rsplit("-", 1)[0]
            job = cache.jobs[f"{ns}/{pg}"]
            task = next(t for t in job.tasks.values() if t.name == name)
            total.add(task.resreq)
        assert total.less_equal(node.allocatable), (
            f"node {node_name} over-allocated: {total} > {node.allocatable}")
        assert len(keys) <= node.allocatable.max_task_num

    # gang all-or-nothing
    counts = {}
    for key in binds:
        pg = key.split("/")[1].rsplit("-", 1)[0]
        counts[pg] = counts.get(pg, 0) + 1
    for pg, n in counts.items():
        assert n >= populate_min_members, f"gang {pg} bound {n} < min"


class TestRounds:
    def test_gang_atomicity_and_feasibility(self):
        populate = gang_cluster(n_groups=20, min_member=4, n_nodes=6)
        cache, prof = run_rounds(populate)
        check_invariants(cache, 4)
        assert prof["rounds"] >= 1

    def test_matches_serial_quality_when_abundant(self):
        # with abundant capacity both backends must place every task
        populate = gang_cluster(n_groups=10, min_member=4, n_nodes=20)
        serial = run_serial(populate)
        cache, _ = run_rounds(populate)
        assert len(cache.binder.binds) == len(serial) == 40

    def test_quality_at_contention(self):
        # tight capacity: rounds mode must bind at least as many whole gangs
        # as the serial loop does (it sees all nodes, never samples)
        populate = gang_cluster(n_groups=24, min_member=4, n_nodes=5)
        serial = run_serial(populate)
        cache, _ = run_rounds(populate)
        check_invariants(cache, 4)
        assert len(cache.binder.binds) >= len(serial) * 0.9

    def test_no_capacity_binds_nothing(self):
        def populate(c):
            c.add_queue(build_queue("default"))
            c.add_pod_group(build_pod_group("pg1", namespace="ns1", min_member=3))
            for i in range(3):
                c.add_pod(build_pod("ns1", f"pg1-p{i}", "", objects.POD_PHASE_PENDING,
                                    {"cpu": "4", "memory": "4Gi"}, "pg1"))
            c.add_node(build_node("n1", build_resource_list_with_pods("4", "8Gi")))

        cache, _ = run_rounds(populate)
        assert cache.binder.binds == {}

    def test_selectors_respected(self):
        def populate(c):
            c.add_queue(build_queue("default"))
            for g, zone in enumerate(["a", "b", "a", "b"]):
                pg = f"pg{g}"
                c.add_pod_group(build_pod_group(pg, namespace="ns1", min_member=2))
                for i in range(2):
                    c.add_pod(build_pod("ns1", f"{pg}-p{i}", "",
                                        objects.POD_PHASE_PENDING,
                                        {"cpu": "1", "memory": "1Gi"}, pg,
                                        node_selector={"zone": zone}))
            for n in range(4):
                c.add_node(build_node(
                    f"node-{n:03d}", build_resource_list_with_pods("4", "8Gi"),
                    labels={"zone": "a" if n < 2 else "b"}))

        cache, _ = run_rounds(populate)
        assert len(cache.binder.binds) == 8
        for key, node in cache.binder.binds.items():
            g = int(key.split("/")[1][2])
            want = "a" if g % 2 == 0 else "b"
            n = int(node.split("-")[1])
            assert (n < 2) == (want == "a"), f"{key} on wrong zone node {node}"

    def test_fair_share_multi_queue(self):
        # 2 queues, equal weight, demand 2x capacity: each queue should land
        # roughly half the bindings through the overused gate
        def populate(c):
            rng = random.Random(9)
            c.add_queue(build_queue("q-a", weight=1))
            c.add_queue(build_queue("q-b", weight=1))
            for g in range(16):
                q = "q-a" if g % 2 == 0 else "q-b"
                pg = f"pg{g:02d}"
                c.add_pod_group(build_pod_group(pg, namespace="ns1",
                                                min_member=2, queue=q))
                for i in range(2):
                    c.add_pod(build_pod("ns1", f"{pg}-p{i}", "",
                                        objects.POD_PHASE_PENDING,
                                        {"cpu": "1", "memory": "1Gi"}, pg))
            for n in range(4):
                c.add_node(build_node(
                    f"node-{n:03d}", build_resource_list_with_pods("4", "8Gi")))

        cache, _ = run_rounds(populate, tiers=(["priority", "gang"],
                                               ["drf", "proportion"]))
        by_queue = {"q-a": 0, "q-b": 0}
        for key in cache.binder.binds:
            g = int(key.split("/")[1][2:4])
            by_queue["q-a" if g % 2 == 0 else "q-b"] += 1
        total = sum(by_queue.values())
        assert total > 0
        assert abs(by_queue["q-a"] - by_queue["q-b"]) <= 4, by_queue


class TestInt32OverflowExactness:
    """Regression: per-segment cumulative request sums can exceed 2^31
    quantized units (e.g. 50k tasks x 64-core requests in one queue
    segment); a wrapped int32 cumsum went negative and passed the
    budget/fit comparisons. rounds._seg_limbs keeps the sums exact as
    two 15-bit limbs."""

    def test_queue_budget_exact_past_int32(self):
        import jax.numpy as jnp
        from volcano_tpu.ops import rounds as R

        t = 70
        req = 36_000_000  # 36k cores in milli-cpu: 60 of these wrap int32
        enc = {
            "is_scalar": jnp.array([False]),
            "res_unit": jnp.array([1.0]),
            "eps": jnp.array([10.0]),
            "task_req": jnp.full((t, 1), float(req)),
            # deserved 2e9 plus eps 10, as encoder limbs
            "queue_bound_limbs": jnp.asarray(
                _limbs(np.array([[2.0e9 + 10]]))),
        }
        accept = jnp.ones(t, bool)
        task_rank = jnp.arange(t, dtype=jnp.int32)
        task_queue = jnp.zeros(t, jnp.int32)
        task_job = jnp.arange(t, dtype=jnp.int32)  # one job per task
        out, _ = R._queue_budget(enc, jnp.zeros((1, 1, 2), jnp.int32),
                                 accept, task_rank, task_queue, task_job)
        got = int(jnp.sum(out))
        # jobs 0..55 see alloc_before = k*36e6 < 2e9 + 10; job 56 is the
        # first over; a wrapped cumsum would re-admit jobs >= 60
        assert got == 56, got
        assert not bool(out[60]), "wrapped cumsum re-admitted job 60"

    def test_resolve_exact_past_int32(self):
        import jax.numpy as jnp
        from volcano_tpu.ops import rounds as R
        from volcano_tpu.ops.kernels import SolveSpec

        t = 70
        spec = SolveSpec(job_order_keys=("priority",), use_drf_ns_order=False,
                         use_prop_queue_order=False, use_prop_overused=False,
                         check_pod_count=False, use_binpack=False,
                         use_nodeorder=False)
        enc = {
            "is_scalar": jnp.array([False]),
            "res_unit": jnp.array([1.0]),
            "eps": jnp.array([10.0]),
            "task_req": jnp.full((t, 1), 36_000_000.0),
            "task_has_pod": jnp.zeros(t, bool),
        }
        idle = jnp.array([[40_000_000.0]])  # fits exactly one task
        choice = jnp.zeros(t, jnp.int32)    # everyone picks node 0
        task_rank = jnp.arange(t, dtype=jnp.int32)
        accept = R._resolve(spec, enc, idle, jnp.zeros(1, jnp.int32),
                            choice, task_rank)
        assert int(jnp.sum(accept)) == 1, int(jnp.sum(accept))
        assert bool(accept[0])


class TestRoundsPluginGate:
    def test_custom_plugin_forces_serial_fallback(self):
        """A plugin outside ROUNDS_SAFE_PLUGINS (even one contributing only
        event handlers, invisible to the encoder's extension-point checks)
        must not be silently dropped by the statement-free bulk apply."""
        from volcano_tpu.scheduler.framework import plugins as plugin_registry
        from volcano_tpu.scheduler.framework.interface import Plugin

        class EventOnlyPlugin(Plugin):
            def name(self):
                return "event_only_test"

            def on_session_open(self, ssn):
                pass

            def on_session_close(self, ssn):
                pass

        plugin_registry.register_plugin_builder(
            "event_only_test", lambda args: EventOnlyPlugin())
        try:
            def populate(c):
                c.add_queue(build_queue("default"))
                c.add_pod_group(build_pod_group("pg0", namespace="ns1",
                                                min_member=2))
                for i in range(4):
                    c.add_pod(build_pod("ns1", f"pg0-p{i}", "",
                                        objects.POD_PHASE_PENDING,
                                        {"cpu": "1", "memory": "1Gi"}, "pg0"))
                c.add_node(build_node(
                    "node-000", build_resource_list_with_pods("8", "16Gi")))

            cache = make_cache()
            populate(cache)
            ssn = open_session(cache, make_tiers(
                ["tpuscore"], ["priority", "gang", "event_only_test"],
                arguments=ROUNDS_ARGS))
            get_action("allocate").execute(ssn)
            prof = dict(ssn.plugins["tpuscore"].profile)
            close_session(ssn)
            assert "fallback" in prof, prof
            assert "event_only_test" in prof["fallback"], prof
            # the serial loop still binds everything
            assert len(cache.binder.binds) == 4
        finally:
            plugin_registry._plugin_builders.pop("event_only_test", None)

    def test_seg_limbs_exact_past_lo_limb_wrap(self):
        """70k rows of 64-core requests: the naive cumsum of even the SPLIT
        lo limbs wraps int32 (~2.19e9); the carry-normalizing scan must
        report the exact total."""
        import jax.numpy as jnp
        from volcano_tpu.ops import rounds as R

        t = 70_000
        req = jnp.full((t, 1), 64_000, jnp.int32)
        start_idx = jnp.zeros(t, jnp.int32)  # one segment
        hi, lo = R._seg_limbs(req, start_idx)
        total = int(hi[-1, 0]) * 32768 + int(lo[-1, 0])
        assert total == 70_000 * 64_000, total
        assert int(lo[-1, 0]) < 32768


class TestRoundsResidue:
    """The EncoderFallback cliff is gone in rounds mode: un-modeled
    constructs degrade to a per-task serial residue pass (or host-side
    masks), never a whole-session serial outage."""

    def _affinity(self, labels):
        return objects.Affinity(
            pod_anti_affinity=objects.PodAntiAffinity(required_terms=[
                objects.PodAffinityTerm(
                    label_selector=objects.LabelSelector(match_labels=labels),
                    topology_key="kubernetes.io/hostname",
                )
            ])
        )

    def test_affinity_task_as_residue(self):
        """One anti-affinity pod among plain gangs: bulk solves the gangs,
        the serial pass places the affinity pod — no session fallback."""
        def populate(c):
            c.add_queue(build_queue("default"))
            for g in range(6):
                pg = f"pg{g}"
                c.add_pod_group(build_pod_group(pg, namespace="ns1", min_member=2))
                for i in range(2):
                    c.add_pod(build_pod("ns1", f"{pg}-p{i}", "",
                                        objects.POD_PHASE_PENDING,
                                        {"cpu": "1", "memory": "1Gi"}, pg))
            c.add_pod_group(build_pod_group("pga", namespace="ns1", min_member=1))
            pod = build_pod("ns1", "pga-p0", "", objects.POD_PHASE_PENDING,
                            {"cpu": "1", "memory": "1Gi"}, "pga",
                            labels={"app": "solo"})
            pod.spec.affinity = self._affinity({"app": "solo"})
            c.add_pod(pod)
            for n in range(4):
                c.add_node(build_node(
                    f"node-{n:03d}", build_resource_list_with_pods("8", "16Gi")))

        cache, prof = run_rounds(populate)
        # the qualifying (hostname self-anti) pod is PROMOTED into a device
        # exclusion group — no residue pass at all
        assert prof.get("residue") == 0, prof
        assert len(cache.binder.binds) == 13  # 12 gang + 1 exclusion-group
        assert "ns1/pga-p0" in cache.binder.binds

    def test_zone_affinity_task_stays_residue(self):
        """Non-hostname topology does not qualify for device exclusion
        groups: the pod goes through the serial residue pass as before."""
        def populate(c):
            c.add_queue(build_queue("default"))
            for g in range(4):
                pg = f"pg{g}"
                c.add_pod_group(build_pod_group(pg, namespace="ns1", min_member=2))
                for i in range(2):
                    c.add_pod(build_pod("ns1", f"{pg}-p{i}", "",
                                        objects.POD_PHASE_PENDING,
                                        {"cpu": "1", "memory": "1Gi"}, pg))
            c.add_pod_group(build_pod_group("pgz", namespace="ns1", min_member=1))
            pod = build_pod("ns1", "pgz-p0", "", objects.POD_PHASE_PENDING,
                            {"cpu": "1", "memory": "1Gi"}, "pgz",
                            labels={"app": "zoned"})
            pod.spec.affinity = objects.Affinity(
                pod_anti_affinity=objects.PodAntiAffinity(required_terms=[
                    objects.PodAffinityTerm(
                        label_selector=objects.LabelSelector(
                            match_labels={"app": "zoned"}),
                        topology_key="zone")]))
            c.add_pod(pod)
            for n in range(4):
                c.add_node(build_node(
                    f"node-{n:03d}",
                    build_resource_list_with_pods("8", "16Gi"),
                    labels={"zone": f"z{n % 2}"}))

        cache, prof = run_rounds(populate)
        assert prof.get("residue") == 1, prof
        assert "ns1/pgz-p0" in cache.binder.binds

    def test_host_port_tasks_as_residue(self):
        """Two pods wanting the same host port land on different nodes via
        the serial residue pass."""
        def populate(c):
            c.add_queue(build_queue("default"))
            for k in range(2):
                pg = f"pgp{k}"
                c.add_pod_group(build_pod_group(pg, namespace="ns1", min_member=1))
                pod = build_pod("ns1", f"{pg}-p0", "", objects.POD_PHASE_PENDING,
                                {"cpu": "1", "memory": "1Gi"}, pg)
                pod.spec.containers[0].ports = [
                    objects.ContainerPort(host_port=8080)]
                c.add_pod(pod)
            # filler gang so the bulk solve has work
            c.add_pod_group(build_pod_group("pgf", namespace="ns1", min_member=2))
            for i in range(2):
                c.add_pod(build_pod("ns1", f"pgf-p{i}", "",
                                    objects.POD_PHASE_PENDING,
                                    {"cpu": "1", "memory": "1Gi"}, "pgf"))
            for n in range(2):
                c.add_node(build_node(
                    f"node-{n:03d}", build_resource_list_with_pods("8", "16Gi")))

        cache, prof = run_rounds(populate)
        # single-hostPort pods are PROMOTED into a port exclusion group
        # (at most one (port, protocol) holder per node) — no residue
        assert prof.get("residue") == 0, prof
        binds = cache.binder.binds
        assert len(binds) == 4, binds
        assert binds["ns1/pgp0-p0"] != binds["ns1/pgp1-p0"], binds

    def test_port_pod_matching_label_group_demotes_it(self):
        """A port-promoted pod whose labels match a label group's selector
        is device-placed but invisible to the group's kernel occupancy —
        the closure must demote the label group to residue so the serial
        pass (which sees all residents live) enforces the anti-affinity."""
        def populate(c):
            c.add_queue(build_queue("default"))
            c.add_pod_group(build_pod_group("pga", namespace="ns1", min_member=1))
            pod = build_pod("ns1", "pga-p0", "", objects.POD_PHASE_PENDING,
                            {"cpu": "1", "memory": "1Gi"}, "pga",
                            labels={"app": "solo"})
            pod.spec.affinity = self._affinity({"app": "solo"})
            c.add_pod(pod)
            # port pod carrying the SAME label, no affinity of its own
            c.add_pod_group(build_pod_group("pgp", namespace="ns1", min_member=1))
            ppod = build_pod("ns1", "pgp-p0", "", objects.POD_PHASE_PENDING,
                             {"cpu": "1", "memory": "1Gi"}, "pgp",
                             labels={"app": "solo"})
            ppod.spec.containers[0].ports = [
                objects.ContainerPort(host_port=8080)]
            c.add_pod(ppod)
            c.add_pod_group(build_pod_group("pgf", namespace="ns1", min_member=2))
            for i in range(2):
                c.add_pod(build_pod("ns1", f"pgf-p{i}", "",
                                    objects.POD_PHASE_PENDING,
                                    {"cpu": "1", "memory": "1Gi"}, "pgf"))
            for n in range(3):
                c.add_node(build_node(
                    f"node-{n:03d}", build_resource_list_with_pods("8", "16Gi")))

        cache, prof = run_rounds(populate)
        # the label group demoted (residue); the port pod stays promoted
        assert prof.get("residue") == 1, prof
        binds = cache.binder.binds
        assert len(binds) == 4, binds
        # anti-affinity honored: the two app=solo pods are apart
        assert binds["ns1/pga-p0"] != binds["ns1/pgp-p0"], binds

    def test_multi_port_tasks_stay_residue(self):
        """A pod with TWO host ports exceeds the one-group-per-task kernel
        model and keeps the serial residue path; port conflicts against a
        device-placed single-port pod are still honored (live check)."""
        def populate(c):
            c.add_queue(build_queue("default"))
            for k in range(2):
                pg = f"pgp{k}"
                c.add_pod_group(build_pod_group(pg, namespace="ns1", min_member=1))
                pod = build_pod("ns1", f"{pg}-p0", "", objects.POD_PHASE_PENDING,
                                {"cpu": "1", "memory": "1Gi"}, pg)
                ports = [objects.ContainerPort(host_port=7070)]
                if k == 1:
                    ports.append(objects.ContainerPort(host_port=7071))
                pod.spec.containers[0].ports = ports
                c.add_pod(pod)
            c.add_pod_group(build_pod_group("pgf", namespace="ns1", min_member=2))
            for i in range(2):
                c.add_pod(build_pod("ns1", f"pgf-p{i}", "",
                                    objects.POD_PHASE_PENDING,
                                    {"cpu": "1", "memory": "1Gi"}, "pgf"))
            for n in range(2):
                c.add_node(build_node(
                    f"node-{n:03d}", build_resource_list_with_pods("8", "16Gi")))

        cache, prof = run_rounds(populate)
        assert prof.get("residue") == 1, prof  # only the two-port pod
        binds = cache.binder.binds
        assert len(binds) == 4, binds
        assert binds["ns1/pgp0-p0"] != binds["ns1/pgp1-p0"], binds

    def test_existing_anti_affinity_symmetry_masks_bulk(self):
        """An existing pod's required anti-affinity bars matching bulk pods
        from its node (host-precomputed signature mask, not fallback)."""
        def populate(c):
            c.add_queue(build_queue("default"))
            # existing running pod with anti-affinity against app=web
            c.add_pod_group(build_pod_group("pge", namespace="ns1", min_member=1))
            epod = build_pod("ns1", "pge-p0", "node-000", objects.POD_PHASE_RUNNING,
                             {"cpu": "1", "memory": "1Gi"}, "pge",
                             labels={"app": "guard"})
            epod.spec.affinity = self._affinity({"app": "web"})
            c.add_pod(epod)
            # plain bulk pods labeled app=web
            c.add_pod_group(build_pod_group("pgw", namespace="ns1", min_member=2))
            for i in range(2):
                c.add_pod(build_pod("ns1", f"pgw-p{i}", "",
                                    objects.POD_PHASE_PENDING,
                                    {"cpu": "1", "memory": "1Gi"}, "pgw",
                                    labels={"app": "web"}))
            for n in range(3):
                c.add_node(build_node(
                    f"node-{n:03d}", build_resource_list_with_pods("8", "16Gi")))

        cache, prof = run_rounds(populate)
        binds = cache.binder.binds
        assert len(binds) == 2, binds
        assert all(v != "node-000" for v in binds.values()), binds

    def test_releasing_capacity_pipelines_leftovers(self):
        """A draining node no longer aborts encoding: bulk places what idle
        allows and the serial pass pipelines the leftover onto releasing
        capacity (committed because the job reaches ready via its
        idle-fitting task, allocate.go:238-242 semantics)."""
        from volcano_tpu.api.types import TaskStatus

        def populate(c):
            c.add_queue(build_queue("default"))
            # node-000 free; node-001 fully used by a terminating pod
            c.add_node(build_node("node-000",
                                  build_resource_list_with_pods("4", "8Gi")))
            c.add_node(build_node("node-001",
                                  build_resource_list_with_pods("4", "8Gi")))
            c.add_pod_group(build_pod_group("pgr", namespace="ns1", min_member=1))
            rpod = build_pod("ns1", "pgr-p0", "node-001", objects.POD_PHASE_RUNNING,
                             {"cpu": "4", "memory": "8Gi"}, "pgr")
            rpod.metadata.deletion_timestamp = 1.0
            c.add_pod(rpod)
            # 2-task job (min=1): one task fits idle node-000, the other
            # only fits node-001 once the releasing pod drains
            c.add_pod_group(build_pod_group("pgn", namespace="ns1", min_member=1))
            for i in range(2):
                c.add_pod(build_pod("ns1", f"pgn-p{i}", "",
                                    objects.POD_PHASE_PENDING,
                                    {"cpu": "4", "memory": "8Gi"}, "pgn"))

        cache = make_cache()
        populate(cache)
        ssn = open_session(
            cache, make_tiers(["tpuscore"], *DEFAULT_TIERS, arguments=ROUNDS_ARGS))
        get_action("allocate").execute(ssn)
        prof = dict(ssn.plugins["tpuscore"].profile)
        assert prof.get("has_releasing"), prof
        # one task bound on the idle node; the other pipelined onto the
        # draining one — pipelining is session-local (no binder call), so
        # assert on the session tree before close
        assert list(cache.binder.binds.values()) == ["node-000"], cache.binder.binds
        job = ssn.jobs["ns1/pgn"]
        pip = job.task_status_index.get(TaskStatus.PIPELINED, {})
        assert len(pip) == 1, dict(job.task_status_index)
        assert next(iter(pip.values())).node_name == "node-001"
        close_session(ssn)

    def test_symmetry_distinguishes_labels_within_plain_signature(self):
        """Two plain pods differing only in labels must get independent
        symmetry verdicts (signatures alone don't encode labels; the
        encoder extends keys when symmetry terms are live)."""
        def populate(c):
            c.add_queue(build_queue("default"))
            c.add_pod_group(build_pod_group("pge", namespace="ns1", min_member=1))
            epod = build_pod("ns1", "pge-p0", "node-000", objects.POD_PHASE_RUNNING,
                             {"cpu": "1", "memory": "1Gi"}, "pge",
                             labels={"app": "guard"})
            epod.spec.affinity = self._affinity({"app": "web"})
            c.add_pod(epod)
            # unlabeled plain pod FIRST (becomes the '<plain>' rep without
            # the key extension), labeled app=web pod second
            c.add_pod_group(build_pod_group("pgu", namespace="ns1", min_member=1))
            c.add_pod(build_pod("ns1", "pgu-p0", "", objects.POD_PHASE_PENDING,
                                {"cpu": "4", "memory": "1Gi"}, "pgu"))
            c.add_pod_group(build_pod_group("pgw", namespace="ns1", min_member=1))
            c.add_pod(build_pod("ns1", "pgw-p0", "", objects.POD_PHASE_PENDING,
                                {"cpu": "4", "memory": "1Gi"}, "pgw",
                                labels={"app": "web"}))
            c.add_node(build_node("node-000", build_resource_list_with_pods("9", "16Gi")))
            c.add_node(build_node("node-001", build_resource_list_with_pods("4", "4Gi")))

        cache, prof = run_rounds(populate)
        binds = cache.binder.binds
        assert len(binds) == 2, binds
        assert binds["ns1/pgw-p0"] == "node-001", binds


class TestWarmPath:
    """Steady-state sessions must never retrace: shapes are bucket-padded
    (ops/solver.py _bucket) so identical-bucket snapshots hit the jit cache.
    CompileWatcher.assert_no_compiles makes a retrace fail HERE, not three
    rounds later as a bench regression (bench tpu_warm_compiles)."""

    def test_second_identical_session_does_not_compile(self):
        populate = gang_cluster(n_groups=20, min_member=4, n_nodes=6)
        run_rounds(populate)  # cold run: compiles allowed
        watcher = CompileWatcher.install()
        with watcher.assert_no_compiles("second identical-shape session"):
            cache, prof = run_rounds(populate)
        assert prof["rounds"] >= 1
        check_invariants(cache, 4)

    def test_same_bucket_churn_does_not_compile(self):
        # 80 -> 76 tasks and 20 -> 19 jobs both land in the same buckets
        # (128 / 32): count churn inside a bucket must reuse the program
        run_rounds(gang_cluster(n_groups=20, min_member=4, n_nodes=6))
        watcher = CompileWatcher.install()
        with watcher.assert_no_compiles("same-bucket churned session"):
            cache, _ = run_rounds(gang_cluster(n_groups=19, min_member=4,
                                               n_nodes=6))
        check_invariants(cache, 4)


class TestPolicyShape:
    """Bulk-synchronous placement must still express each scoring policy's
    intent: spreading policies distribute across tied nodes, packing
    policies consolidate (rounds._choices capacity walk + tie rotation)."""

    def _populate(self, c):
        c.add_queue(build_queue("default"))
        for n in range(6):
            c.add_node(build_node(
                f"n{n:02d}", build_resource_list_with_pods("16", "32Gi", pods=64)))
        for g in range(6):
            pg = f"pg{g}"
            c.add_pod_group(build_pod_group(pg, namespace="d", min_member=4))
            for i in range(4):
                c.add_pod(build_pod("d", f"{pg}-{i}", "", objects.POD_PHASE_PENDING,
                                    {"cpu": "1", "memory": "1Gi"}, pg))

    @staticmethod
    def _per_node(cache):
        per = {}
        for _, node in cache.binder.binds.items():
            per[node] = per.get(node, 0) + 1
        return per

    def test_least_requested_spreads_across_tied_nodes(self):
        cache, _ = run_rounds(
            self._populate,
            tiers=(["priority", "gang"],
                   ["drf", "predicates", "proportion", "nodeorder"]))
        per = self._per_node(cache)
        assert sum(per.values()) == 24
        assert len(per) == 6, per  # every identical node used

    def test_binpack_consolidates(self):
        cache, _ = run_rounds(
            self._populate,
            tiers=(["priority", "gang"],
                   ["drf", "predicates", "proportion", "binpack"]))
        per = self._per_node(cache)
        assert sum(per.values()) == 24
        assert len(per) <= 3, per  # fill node by node, not spread
