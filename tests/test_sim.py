"""volcano_tpu.sim — the virtual-time simulator gate (docs/DESIGN.md §12).

Four layers:
1. engine/clock units: event ordering, hash sensitivity, RNG stream
   independence;
2. smoke scenarios through the REAL stack (smoke_small fault-free,
   smoke_chaos with every fault family) — zero auditor violations, and
   the determinism contract: same seed ⇒ byte-identical event-log hash
   IN-PROCESS (the strictest form — global counters, jit caches, and
   helper state must all be properly reset between runs);
3. auditor self-test: a deliberately reintroduced evict-accounting-leak /
   phantom-pod corruption (the VOLCANO_TPU_EVICT=0-era bug class) MUST be
   caught, with a repro bundle dumped;
4. the cfg5-shaped scale gate: reduced-scale cfg5_storm end-to-end
   through the real TPU rounds solve with warm assert-no-compiles
   (full scale runs as slow).
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import pytest

from volcano_tpu.sim import (
    RngStreams,
    SimCluster,
    VirtualClock,
    load_scenario,
    scale_scenario,
)
from volcano_tpu.sim.engine import SimEngine

pytestmark = pytest.mark.sim


# ---------------------------------------------------------------------------
# 1. engine / clock units
# ---------------------------------------------------------------------------


class TestEngine:
    def test_event_order_is_time_then_schedule_order(self):
        clock = VirtualClock()
        engine = SimEngine(clock)
        seen = []
        engine.schedule_at(2.0, "b", lambda: seen.append("b"))
        engine.schedule_at(1.0, "a", lambda: seen.append("a"))
        engine.schedule_at(2.0, "c", lambda: seen.append("c"))
        engine.run_until(10.0)
        assert seen == ["a", "b", "c"]
        assert clock.now() == 10.0

    def test_log_hash_tracks_content_and_time(self):
        def run(detail):
            clock = VirtualClock()
            engine = SimEngine(clock)
            engine.schedule_at(1.0, "x", lambda: detail)
            engine.run_until(5.0)
            return engine.log_hash()

        assert run("same") == run("same")
        assert run("same") != run("different")

    def test_events_during_run_can_schedule_more(self):
        clock = VirtualClock()
        engine = SimEngine(clock)
        seen = []

        def tick():
            seen.append(clock.now())
            if clock.now() < 3.0:
                engine.schedule_in(1.0, "tick", tick)

        engine.schedule_at(1.0, "tick", tick)
        engine.run_until(10.0)
        assert seen == [1.0, 2.0, 3.0]

    def test_virtual_timestamps_strictly_increase(self):
        clock = VirtualClock()
        stamps = [clock.timestamp() for _ in range(5)]
        assert stamps == sorted(stamps)
        assert len(set(stamps)) == 5

    def test_rng_streams_stable_and_independent(self):
        a1 = RngStreams(7).stream("workload").random()
        # drawing from another stream first must not perturb this one
        rngs = RngStreams(7)
        rngs.stream("chaos:node_flap").random()
        a2 = rngs.stream("workload").random()
        assert a1 == a2
        assert RngStreams(8).stream("workload").random() != a1


# ---------------------------------------------------------------------------
# 2. smoke scenarios (tier-1 gates)
# ---------------------------------------------------------------------------


def _run(name, seed, duration=None, mutate=None, repro_dir=None):
    cfg = copy.deepcopy(load_scenario(name))
    if mutate is not None:
        mutate(cfg)
    sim = SimCluster(cfg, seed=seed, repro_dir=repro_dir)
    return sim.run(duration=duration)


class TestSmokeScenarios:
    def test_smoke_small_pipeline_converges_clean(self):
        s = _run("smoke_small", seed=7)
        assert s["sessions"] >= 15
        assert s["binds"] > 0
        assert s["jobs"]["completed"] > 0, s["jobs"]
        assert s["audit"]["checks"] >= 15
        assert s["audit"]["violations"] == 0, s["audit"]
        # lifecycles actually churned: some pods finished
        assert s["pods"]["succeeded"] > 0

    def test_smoke_chaos_every_fault_family_clean(self):
        s = _run("smoke_chaos", seed=3)
        assert s["audit"]["violations"] == 0, s["audit"]
        # the chaos actually happened — each seam was exercised
        assert s["faults"].get("node_flap", 0) >= 1, s["faults"]
        assert s["faults"].get("reset_storm", 0) >= 1, s["faults"]
        assert s["session_kills"] >= 1
        assert s["restarts"]["scheduler"] >= 1
        # ring overflow forced the reset/re-list path with DELETED
        # synthesis — the phantom-object protocol under test
        pod_mirror = s["mirrors"]["Pod"]
        assert pod_mirror["resets"] >= 1, s["mirrors"]
        assert pod_mirror["synthesized_deletes"] >= 1, s["mirrors"]

    def test_same_seed_identical_hash_in_process(self):
        a = _run("smoke_small", seed=12, duration=16.0)
        b = _run("smoke_small", seed=12, duration=16.0)
        assert a["event_log_hash"] == b["event_log_hash"]
        assert a["binds"] == b["binds"]
        assert (a["audit"]["checks"], a["audit"]["violations"]) \
            == (b["audit"]["checks"], b["audit"]["violations"])

    def test_chaos_same_seed_identical_hash_different_seed_differs(self):
        a = _run("smoke_chaos", seed=5, duration=40.0)
        b = _run("smoke_chaos", seed=5, duration=40.0)
        c = _run("smoke_chaos", seed=6, duration=40.0)
        assert a["event_log_hash"] == b["event_log_hash"]
        assert a["event_log_hash"] != c["event_log_hash"]

    def test_trace_replay_lifecycle(self):
        s = _run("trace_replay", seed=2)
        assert s["jobs"]["submitted"] == 5
        assert s["jobs"]["completed"] >= 2
        assert s["jobs"]["failed"] == 1      # trace-c carries fail: true
        assert s["jobs"]["cancelled"] == 1   # trace-d deleted at t=20
        assert s["audit"]["violations"] == 0, s["audit"]

    def test_queues_mix_evictions_run_clean(self):
        s = _run("queues_mix", seed=5, duration=120.0)
        assert s["audit"]["violations"] == 0, s["audit"]
        # overcommit + priority spread + weighted queues actually drove
        # the preempt/reclaim pipeline
        assert s["evictions"] > 0
        assert s["binds"] > 0

    def test_serving_mix_express_lane_clean(self):
        """serving_mix smoke: interactive arrivals ride the express lane
        between sessions, batch gangs stay with the sessions, and the
        express_reconciliation invariant (plus all standing rules) holds
        through flaps/restarts/kills."""
        cfg = scale_scenario(load_scenario("serving_mix"), 0.5)
        s = SimCluster(cfg, seed=11).run(duration=60.0)
        assert s["audit"]["violations"] == 0, s["audit"]
        ex = s["express"]
        assert ex is not None
        # the lane actually placed interactive arrivals...
        assert ex["placed"] > 0, ex
        # ...and every optimistic bind got a session verdict
        assert ex["placed"] == 0 or ex["reconciled"] + ex["reverted"] > 0 \
            or ex["outstanding"] <= ex["placed"], ex
        # sessions still own the (express-ineligible) batch gangs
        assert s["binds"] > ex["placed"], (s["binds"], ex)

    def test_serving_mix_same_seed_identical_hash(self):
        cfg = scale_scenario(load_scenario("serving_mix"), 0.25)
        a = SimCluster(cfg, seed=4).run(duration=45.0)
        b = SimCluster(cfg, seed=4).run(duration=45.0)
        assert a["event_log_hash"] == b["event_log_hash"]
        assert a["express"]["placed"] == b["express"]["placed"]
        assert a["express"]["reverted"] == b["express"]["reverted"]

    def test_ha_failover_fenced_takeovers_clean(self):
        """ha_failover smoke (reduced scale): three leader kills — one
        mid-defer-window, one mid-fused-chain, one mid-express-commit —
        each promoting the warm standby via the real resource-lock CAS,
        with the auditor holding the fencing balance and the takeover
        bounds through mirror 5xx storms."""
        cfg = scale_scenario(load_scenario("ha_failover"), 0.5)
        s = SimCluster(cfg, seed=7).run()
        assert s["audit"]["violations"] == 0, s["audit"]
        ha = s["ha"]
        assert ha is not None
        # every injected seam actually deposed a leader
        assert ha["leader_kills"].get("mid_defer", 0) >= 1, ha
        assert ha["leader_kills"].get("mid_chain", 0) >= 1, ha
        assert ha["leader_kills"].get("mid_express", 0) >= 1, ha
        assert sum(ha["leader_kills"].values()) >= 3
        assert ha["epoch"] >= 4  # epoch 1 + three takeovers
        # the fence actually fired (a deposed term's in-flight writes
        # were rejected) and the rejection ledger balances exactly
        fence = ha["fence"]
        assert fence["rejected"] >= 1, fence
        assert fence["rejected"] == fence["observed_by_effectors"], fence
        assert fence["epoch"] == ha["epoch"]
        # every takeover met the warm-standby contract: first led session
        # within <= 2 cycle periods, zero wholesale rebuilds, zero
        # recompiles, deposed-term express tokens drained
        assert len(ha["takeovers"]) == 3, ha["takeovers"]
        period = cfg["scheduler"]["period_s"]
        for t in ha["takeovers"]:
            assert t["first_session_at"] is not None, t
            assert t["first_session_at"] - t["at"] <= 2 * period + 1e-9, t
            assert t["rebuilds_delta"] == 0, t
            assert t["first_session_compiles"] == 0, t
            assert t["undrained_tokens"] == [], t
        # the 5xx storm raged (polls dropped) yet mirrors converged
        assert s["mirrors"]["Pod"]["dropped_polls"] >= 1, s["mirrors"]

    def test_ha_failover_same_seed_identical_hash(self):
        def strip_warmth(t):
            # first_session_compiles reflects process jit-cache warmth
            # (run b inherits run a's compiled buckets) — everything else
            # about a takeover must replay exactly
            return {k: v for k, v in t.items()
                    if k != "first_session_compiles"}

        cfg = scale_scenario(load_scenario("ha_failover"), 0.25)
        a = SimCluster(cfg, seed=5).run(duration=60.0)
        b = SimCluster(cfg, seed=5).run(duration=60.0)
        assert a["event_log_hash"] == b["event_log_hash"]
        assert a["ha"]["fence"] == b["ha"]["fence"]
        assert [strip_warmth(t) for t in a["ha"]["takeovers"]] \
            == [strip_warmth(t) for t in b["ha"]["takeovers"]]

    def test_pipeline_storm_speculation_and_mid_spec_kill_clean(self):
        """pipeline_storm smoke (reduced scale): the pipelined session
        loop under Poisson churn + express arrivals, with a leader kill
        landing while a speculative solve is in flight. The auditor's
        pipeline_no_stale_commit ledger (and every standing rule) must
        hold; the speculation must BOTH commit on quiet windows and
        discard on deltas; the mid_spec takeover must recover through the
        fencing path with zero wholesale rebuilds and no double-apply."""
        cfg = scale_scenario(load_scenario("pipeline_storm"), 0.25)
        s = SimCluster(cfg, seed=7).run(duration=100.0)
        assert s["audit"]["violations"] == 0, s["audit"]
        pipe = s["pipeline"]
        assert pipe is not None and pipe["cycles"] >= 20, pipe
        # both halves of the speculation contract actually exercised;
        # the read-set scope attributes every discard to the row family
        # that actually moved — post-seal arrivals land as phantoms of
        # the sealed snapshot, express placements as intersections with
        # the jobs the sealed solve encoded
        assert pipe["spec_applied"] >= 1, pipe
        assert pipe["spec_discards"].get("readset:phantom", 0) >= 1, pipe
        assert pipe["spec_discards"].get("readset:job", 0) >= 1, pipe
        # the commit-rate floor budget really ran (denominator past
        # min_n) and the gate regime clears it with margin — a rate at
        # the whole-fingerprint level (~0) fails the audit above
        fb = s["fallbacks"]
        assert fb["pipeline_spec_dispatched"] >= 25, fb
        assert fb["pipeline_spec_commit_rate"] >= 0.1, fb
        # never-applied, as accounting: zero stale commits, every
        # non-abandoned discard re-ran serially
        assert pipe["stale_commits"] == 0, pipe
        non_abandoned = sum(
            n for r, n in pipe["spec_discards"].items() if r != "abandoned")
        assert non_abandoned == pipe["spec_reruns"], pipe
        # the mid_spec kill actually deposed a leader with a solve in
        # flight, and the takeover met the warm-standby contract (both
        # snapshot buffers warm => zero wholesale rebuilds)
        ha = s["ha"]
        assert ha["leader_kills"].get("mid_spec", 0) >= 1, ha
        assert len(ha["takeovers"]) >= 1
        for t in ha["takeovers"]:
            assert t["rebuilds_delta"] == 0, t
            assert t["first_session_compiles"] == 0, t
            assert t["undrained_tokens"] == [], t

    def test_pipeline_storm_same_seed_identical_hash(self):
        cfg = scale_scenario(load_scenario("pipeline_storm"), 0.25)
        a = SimCluster(cfg, seed=11).run(duration=60.0)
        b = SimCluster(cfg, seed=11).run(duration=60.0)
        assert a["event_log_hash"] == b["event_log_hash"]
        assert a["pipeline"] == b["pipeline"]
        assert a["binds"] == b["binds"]

    def test_pipeline_commit_floor_budget_fails_when_tightened(self):
        """The commit-rate FLOOR is non-vacuous: requiring a near-1.0
        commit rate of the storm must FAIL the audit (the same
        proven-to-fire idiom as the max budgets)."""
        cfg = scale_scenario(load_scenario("pipeline_storm"), 0.25)
        cfg["audit"]["budgets"]["pipeline_spec_commit_rate"] = {
            "min": 0.99, "min_n": 10, "max_scale": 0.5}
        s = SimCluster(cfg, seed=7).run(duration=100.0)
        assert s["audit"]["violations"] > 0
        assert "fallback_budget" in s["audit"]["kinds"], s["audit"]

    def test_chaos_soak_pipelined_holds_commit_floor(self):
        """chaos_soak with the pipelined loop mutated on — the tier-1
        arming of the scenario's commit floor. The standing backlog
        keeps every solve-ahead non-empty, so the floor's denominator
        clears min_n, and under the full fault mix the scoped seal
        still converts the quiet windows the soak leaves (zero
        violations includes the floor AND the readset-disjoint rule)."""
        cfg = scale_scenario(load_scenario("chaos_soak"), 0.2)
        cfg["scheduler"]["pipeline"] = True
        s = SimCluster(cfg, seed=5).run(duration=240.0)
        assert s["audit"]["violations"] == 0, s["audit"]
        fb = s["fallbacks"]
        assert fb["pipeline_spec_dispatched"] >= 25, fb
        assert fb["pipeline_spec_commit_rate"] >= 0.02, fb
        # readset families carry the discard ledger under real chaos
        assert any(r.startswith("readset:")
                   for r in s["pipeline"]["spec_discards"]), s["pipeline"]

    def test_chaos_soak_commit_floor_budget_fails_when_tightened(self):
        cfg = scale_scenario(load_scenario("chaos_soak"), 0.2)
        cfg["scheduler"]["pipeline"] = True
        cfg["audit"]["budgets"]["pipeline_spec_commit_rate"] = {
            "min": 0.99, "min_n": 10, "max_scale": 0.5}
        s = SimCluster(cfg, seed=5).run(duration=240.0)
        assert s["audit"]["violations"] > 0
        assert "fallback_budget" in s["audit"]["kinds"], s["audit"]

    def test_front_door_storm_sheds_with_retry_and_converges(self):
        """front_door_storm smoke (reduced scale): a heavy-tailed
        submission storm against the intake gate plus a flow-controlled
        watcher fleet with a deliberately slow tail, through reset
        storms, mirror 5xx, and one leader kill. The auditor must hold
        the shed-with-retry and fan-out-convergence contracts (plus the
        shed/coalesce budgets and every standing rule) with zero
        violations — while the scheduler keeps committing sessions."""
        cfg = scale_scenario(load_scenario("front_door_storm"), 0.5)
        s = SimCluster(cfg, seed=7).run()
        assert s["audit"]["violations"] == 0, s["audit"]
        fd = s["front_door"]
        assert fd is not None
        # the storm actually shed — and every shed scheduled a retry,
        # with a real share re-admitted inside the horizon
        assert fd["shed_submissions"] > 50, fd
        assert fd["shed_submissions"] == fd["shed_retries_scheduled"]
        assert fd["shed_readmitted"] > 0, fd
        # priority-aware shedding: the batch class sheds at a strictly
        # higher rate than the interactive/express-eligible class
        intake = fd["intake"]
        batch_attempts = intake["admitted_batch"] + intake["shed_batch"]
        inter_attempts = (intake["admitted_interactive"]
                          + intake["shed_interactive"])
        assert batch_attempts > 0 and inter_attempts > 0
        assert (intake["shed_batch"] / batch_attempts
                > intake["shed_interactive"] / inter_attempts), intake
        # the slow tail was demoted to snapshot-resync AND converged
        # (auditor-verified: front_door_watchers ran with 0 violations)
        watch = fd["watch"]
        assert watch["counters"]["demotions"] >= 5, watch["counters"]
        assert watch["counters"]["promotions"] >= 5, watch["counters"]
        assert fd["fleet"]["resets"] >= 1
        assert fd["fleet"]["synthesized_deletes"] >= 1
        # bounded retention held (the journal-pinning fix)
        journal = watch["journal"]
        assert journal["peak_occupancy"] <= min(
            max(watch["demote_lag"], journal["cap"]),
            journal["hard_cap"])
        # the scheduler kept committing sessions through the storm (no
        # skips beyond the PR 8 staleness budget — sessions track the
        # horizon/period exactly)
        horizon = s["sim_duration_s"]
        period = cfg["scheduler"]["period_s"]
        assert s["sessions"] >= int(horizon / period) - 2, s["sessions"]
        assert s["binds"] > 100
        # the leader kill landed and the takeover met the HA contract
        assert sum(s["ha"]["leader_kills"].values()) >= 1
        # shed/coalesce rates are budget-metered in the summary
        rates = s["fallbacks"]
        assert 0.0 < rates["admission_shed_rate"] <= 0.75
        assert rates["watch_events_coalesced"] >= 0

    def test_front_door_storm_same_seed_identical_hash(self):
        cfg = scale_scenario(load_scenario("front_door_storm"), 0.25)
        a = SimCluster(cfg, seed=11).run(duration=60.0)
        b = SimCluster(cfg, seed=11).run(duration=60.0)
        assert a["event_log_hash"] == b["event_log_hash"]
        assert a["front_door"]["intake"] == b["front_door"]["intake"]
        assert a["front_door"]["watch"]["counters"] \
            == b["front_door"]["watch"]["counters"]
        assert a["binds"] == b["binds"]

    def test_front_door_shed_budget_fails_when_tightened(self):
        """The budget gate is non-vacuous: tightening the shed budget to
        an impossible bound must FAIL the audit (the same proven-to-fire
        idiom as PR 11's fallback budgets)."""
        def mutate(cfg):
            cfg["audit"]["budgets"]["admission_shed_rate"] = {
                "max": 0.001, "min_n": 10}

        cfg = scale_scenario(load_scenario("front_door_storm"), 0.5)
        mutate(cfg)
        s = SimCluster(cfg, seed=7).run(duration=60.0)
        assert s["audit"]["violations"] > 0
        assert "fallback_budget" in s["audit"]["kinds"], s["audit"]


# ---------------------------------------------------------------------------
# 3. auditor self-test (seeded bug fixtures)
# ---------------------------------------------------------------------------


class TestAuditorSelfTest:
    @pytest.mark.parametrize("kind,expected", [
        ("accounting_leak", "cache_accounting"),
        ("phantom_pod", "phantom_cache"),
    ])
    def test_seeded_bug_is_caught(self, tmp_path, kind, expected):
        def mutate(cfg):
            cfg["scheduler"]["conf"] = "default"
            cfg["faults"] = {"seeded_bug": {"kind": kind, "at_s": 5.0}}

        s = _run("smoke_small", seed=1, duration=12.0, mutate=mutate,
                 repro_dir=str(tmp_path))
        assert s["audit"]["violations"] > 0
        assert expected in s["audit"]["kinds"], s["audit"]
        bundles = sorted(tmp_path.glob("violation-*.json"))
        assert bundles, "violation must dump a repro bundle"
        bundle = json.loads(bundles[0].read_text())
        assert bundle["seed"] == 1
        assert bundle["violations"][0]["invariant"] == expected
        assert "repro_command" in bundle
        assert bundle["event_log_tail"], "bundle carries the log tail"

    def test_clean_run_dumps_nothing(self, tmp_path):
        s = _run("smoke_small", seed=7, duration=10.0,
                 repro_dir=str(tmp_path))
        assert s["audit"]["violations"] == 0
        assert not list(tmp_path.glob("violation-*.json"))


# ---------------------------------------------------------------------------
# 4. cfg5-shaped scale gate (reduced scale; full scale = slow)
# ---------------------------------------------------------------------------


def _run_cfg5(scale, duration, seed=7):
    cfg = scale_scenario(load_scenario("cfg5_storm"), scale)
    sim = SimCluster(cfg, seed=seed, repro_dir=None)
    return sim.run(duration=duration)


class TestCfg5Scale:
    def test_reduced_scale_real_tpu_solve_warm_no_compiles(self):
        s = _run_cfg5(scale=0.01, duration=60.0)
        # the storm placed to capacity and kept an overcommit backlog —
        # the warm re-solve regime
        assert s["binds"] > 300, s["binds"]
        assert s["pods"]["pending"] > 0
        assert s["audit"]["violations"] == 0, s["audit"]
        # the REAL device rounds path ran (it compiled at least once)...
        assert s["compiles"]["total"] >= 1, s["compiles"]
        # ...and the steady state is retrace-free: warm sessions re-solve
        # the same backlog through the SAME compiled program
        assert s["compiles"]["after_warmup"] == 0, s["compiles"]
        assert s["sessions"] >= 10

    @pytest.mark.slow
    def test_full_scale_cfg5_storm(self):
        # 50k tasks x 10k nodes end-to-end: store submit -> controllers ->
        # enqueue -> TPU rounds solve -> bind writeback, audited
        s = _run_cfg5(scale=1.0, duration=25.0)
        assert s["binds"] > 30000, s["binds"]
        assert s["audit"]["violations"] == 0, s["audit"]
        assert s["compiles"]["after_warmup"] == 0, s["compiles"]

    @pytest.mark.slow
    def test_full_scale_serving_mix(self):
        cfg = copy.deepcopy(load_scenario("serving_mix"))
        s = SimCluster(cfg, seed=11, repro_dir=None).run()
        assert s["audit"]["violations"] == 0, s["audit"]
        ex = s["express"]
        assert ex["placed"] > 20, ex
        assert s["binds"] > ex["placed"]

    @pytest.mark.slow
    def test_full_scale_ha_failover(self):
        cfg = copy.deepcopy(load_scenario("ha_failover"))
        s = SimCluster(cfg, seed=7, repro_dir=None).run()
        assert s["audit"]["violations"] == 0, s["audit"]
        assert sum(s["ha"]["leader_kills"].values()) >= 3
        assert s["ha"]["fence"]["rejected"] \
            == s["ha"]["fence"]["observed_by_effectors"]

    @pytest.mark.slow
    def test_full_scale_front_door_storm(self):
        cfg = copy.deepcopy(load_scenario("front_door_storm"))
        s = SimCluster(cfg, seed=7, repro_dir=None).run()
        assert s["audit"]["violations"] == 0, s["audit"]
        fd = s["front_door"]
        assert fd["shed_submissions"] > 100
        assert fd["shed_submissions"] == fd["shed_retries_scheduled"]
        assert fd["watch"]["counters"]["demotions"] > 50
        assert sum(s["ha"]["leader_kills"].values()) >= 1

    @pytest.mark.slow
    def test_chaos_soak_two_hours(self):
        cfg = copy.deepcopy(load_scenario("chaos_soak"))
        sim = SimCluster(cfg, seed=11, repro_dir=None)
        s = sim.run()
        assert s["sim_duration_s"] >= 7200.0
        assert s["audit"]["violations"] == 0, s["audit"]
        assert s["faults"].get("node_flap", 0) > 10
        assert s["mirrors"]["Pod"]["resets"] > 10


# ---------------------------------------------------------------------------
# 5. device replica under chaos (PR 13): the standing device copy of
#    cluster state rides the soak's rounds-pinned conf — coherence and
#    rebuild-rate budgets audited, and the replica must be INVISIBLE to
#    the event log (same seed, flag on vs off ⇒ byte-identical hash)
# ---------------------------------------------------------------------------


def _run_soak(seed, replica, duration):
    cfg = scale_scenario(load_scenario("chaos_soak"), 0.2)
    old = os.environ.get("VOLCANO_TPU_REPLICA")
    os.environ["VOLCANO_TPU_REPLICA"] = replica
    try:
        return SimCluster(cfg, seed=seed, repro_dir=None).run(
            duration=duration)
    finally:
        if old is None:
            os.environ.pop("VOLCANO_TPU_REPLICA", None)
        else:
            os.environ["VOLCANO_TPU_REPLICA"] = old


class TestDeviceReplicaSim:
    def test_soak_replica_clean_and_flag_invisible_to_event_log(self):
        """Shortened chaos_soak with the replica standing (default) vs
        killed (VOLCANO_TPU_REPLICA=0), same seed: the on-run must hold
        zero violations — which now includes replica_coherence and the
        replica_rebuild_rate budget — while serving real scatters across
        scheduler restarts; and the two event logs must be
        byte-identical, because the replica is a pure staging substrate
        that may never change WHAT gets scheduled."""
        a = _run_soak(seed=5, replica="1", duration=240.0)
        b = _run_soak(seed=5, replica="0", duration=240.0)

        assert a["audit"]["violations"] == 0, a["audit"]
        rep = a["replica"]
        assert rep and rep["serves"] > 0, rep
        # restarts/chaos exercised the rebuild ladder (every fresh cache
        # generation's first serve is cold) AND the delta path carried
        # steady state between faults
        assert rep["rebuilds"].get("cold", 0) >= 1, rep
        fb = a["fallbacks"]
        assert fb["replica_serves"] == rep["serves"]
        assert "replica_rebuild_rate" in fb, fb

        # flag-off: no replica anywhere in the run...
        assert b["replica"] is None, b["replica"]
        assert "replica_serves" not in b["fallbacks"]
        # ...and the schedule itself is untouched by the flag
        assert a["event_log_hash"] == b["event_log_hash"]
        assert a["binds"] == b["binds"]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


class TestCli:
    def test_run_emits_summary_tail_line(self, tmp_path):
        out = subprocess.run(
            [sys.executable, "-m", "volcano_tpu.sim", "run", "smoke_small",
             "--seed", "4", "--duration", "8", "--quiet",
             "--repro-dir", str(tmp_path / "repro"),
             "--json", str(tmp_path / "summary.json")],
            capture_output=True, text=True, timeout=240,
            env=dict(os.environ,
                     JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
        assert out.returncode == 0, out.stderr[-2000:]
        tail = out.stdout.strip().splitlines()[-1]
        summary = json.loads(tail)
        assert summary["scenario"] == "smoke_small"
        assert summary["event_log_hash"]
        assert (tmp_path / "summary.json").exists()

    def test_list_names_committed_scenarios(self):
        out = subprocess.run(
            [sys.executable, "-m", "volcano_tpu.sim", "list"],
            capture_output=True, text=True, timeout=60)
        names = out.stdout.split()
        for expected in ("smoke_small", "smoke_chaos", "cfg5_storm",
                         "chaos_soak", "queues_mix", "trace_replay"):
            assert expected in names, names
