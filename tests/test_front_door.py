"""Front-door overload layer — admission backpressure + overload rungs.

Four seams:
1. IntakeGate units: token-bucket determinism under an injected clock,
   typed OverloadedError with a computed retry-after, priority-ordered
   shedding (batch first on BOTH the rate and backlog axes), refill
   recovery;
2. the admission storm end-to-end: a burst of valid Jobs against an
   in-process store sheds with bounded admission (never more than
   burst + refill admitted), every rejection typed-with-retry, and the
   interactive class admitted preferentially;
3. the HTTP hop: gateway maps OverloadedError to 429 + Retry-After,
   RemoteStore re-raises it typed and honors the hint through
   degrade.Backoff;
4. the policy layer: the new ladder rungs (watch_coalesce_aggressive,
   admission_shed, snapshot_resync_only) arm/clear from front-door
   signals, and the new /metrics series render (incl. the mandatory
   +Inf bucket on the retry-after histogram).
"""

from __future__ import annotations

import json
import sys

import pytest

from volcano_tpu.admission.intake import (
    IntakeGate, classify_job, install_intake)
from volcano_tpu.api import objects
from volcano_tpu.scheduler import metrics
from volcano_tpu.scheduler.degrade import DegradeLadder
from volcano_tpu.store.gateway import ApiGateway
from volcano_tpu.store.remote import RemoteStore
from volcano_tpu.store.store import OverloadedError, Store
from volcano_tpu.utils import clock


def _job(name: str, replicas: int = 2, min_available: int = 1,
         queue: str = "") -> objects.Job:
    task = objects.TaskSpec(
        name="w", replicas=replicas,
        template=objects.PodTemplateSpec(
            spec=objects.PodSpec(containers=[objects.Container(
                name="c", image="t",
                requests={"cpu": "100m", "memory": "64Mi"})])))
    job = objects.Job(
        metadata=objects.ObjectMeta(name=name, namespace="fd"),
        spec=objects.JobSpec(min_available=min_available, tasks=[task],
                             queue=queue))
    return job


class _FakeClock:
    def __init__(self, t0: float = 1000.0):
        self.t = t0

    def __call__(self) -> float:
        return self.t


@pytest.fixture
def fake_clock():
    fc = _FakeClock()
    clock.set_source(fc)
    yield fc
    clock.set_source(None)


class TestIntakeGate:
    def test_bucket_deterministic_and_typed_retry_after(self, fake_clock):
        gate = IntakeGate(rate_per_s=2.0, burst=4.0,
                          interactive_reserve=0.0)
        for _ in range(4):
            gate.admit("batch")
        with pytest.raises(OverloadedError) as exc:
            gate.admit("batch")
        assert exc.value.reason == "rate"
        # empty bucket, rate 2/s: one token is 0.5s away — exactly
        assert exc.value.retry_after == pytest.approx(0.5)
        # refill admits again, deterministically
        fake_clock.t += 0.5
        gate.admit("batch")
        with pytest.raises(OverloadedError):
            gate.admit("batch")

    def test_priority_shedding_batch_first_on_rate(self, fake_clock):
        gate = IntakeGate(rate_per_s=1.0, burst=4.0,
                          interactive_reserve=0.5)
        # batch may not spend the reserved half: 2 tokens usable
        gate.admit("batch")
        gate.admit("batch")
        with pytest.raises(OverloadedError):
            gate.admit("batch")
        # interactive rides the reserve to the bottom
        gate.admit("interactive")
        gate.admit("interactive")
        with pytest.raises(OverloadedError):
            gate.admit("interactive")
        st = gate.stats()
        assert st["admitted_batch"] == 2
        assert st["admitted_interactive"] == 2
        assert st["shed_batch"] == 1 and st["shed_interactive"] == 1

    def test_priority_shedding_batch_first_on_backlog(self, fake_clock):
        gate = IntakeGate(rate_per_s=100.0, burst=100.0,
                          max_backlog=100, interactive_reserve=0.25,
                          backlog_retry_s=3.0)
        gate.set_backlog(80)  # >= 75 = batch bound, < 100 = interactive
        with pytest.raises(OverloadedError) as exc:
            gate.admit("batch")
        assert exc.value.reason == "backlog"
        assert exc.value.retry_after == pytest.approx(3.0)
        gate.admit("interactive")  # interactive still admitted
        gate.set_backlog(100)
        with pytest.raises(OverloadedError):
            gate.admit("interactive")

    def test_classify_job_express_envelope(self):
        assert classify_job(_job("tiny", replicas=1,
                                 min_available=1)) == "interactive"
        assert classify_job(_job("gang", replicas=24,
                                 min_available=16)) == "batch"


class TestAdmissionStorm:
    def test_storm_bounded_typed_and_priority_ordered(self, fake_clock):
        """A 60-submission burst against burst=8: admission stays
        bounded at the bucket depth, every rejection is the typed
        rejected-with-retry contract, and the interactive class is shed
        strictly less than batch."""
        store = Store()
        gate = IntakeGate(rate_per_s=2.0, burst=8.0,
                          interactive_reserve=0.25)
        install_intake(store, gate)
        admitted, shed = [], []
        for i in range(60):
            interactive = i % 2 == 0
            job = _job(f"j{i:03d}",
                       replicas=1 if interactive else 24,
                       min_available=1 if interactive else 16)
            try:
                store.create(job)
                admitted.append(job)
            except OverloadedError as e:
                shed.append(e)
        # bounded inflight: never more than the bucket depth in a burst
        assert len(admitted) <= 8
        assert len(shed) == 60 - len(admitted)
        assert all(e.retry_after > 0 for e in shed)
        assert all(e.reason == "rate" for e in shed)
        st = gate.stats()
        # priority order: batch exhausted the unreserved tranche first;
        # interactive kept admitting into the reserve
        assert st["admitted_interactive"] > st["admitted_batch"]
        shed_rate_batch = st["shed_batch"] / 30
        shed_rate_inter = st["shed_interactive"] / 30
        assert shed_rate_batch > shed_rate_inter
        # nothing dropped without a retry hint, and the ledger balances
        assert st["shed_total"] == len(shed)
        assert st["attempts"] == 60

    def test_admitted_jobs_actually_landed(self, fake_clock):
        store = Store()
        gate = IntakeGate(rate_per_s=1.0, burst=2.0,
                          interactive_reserve=0.0)
        install_intake(store, gate)
        store.create(_job("a"))
        store.create(_job("b"))
        with pytest.raises(OverloadedError):
            store.create(_job("c"))
        names = sorted(j.metadata.name for j in store.list("Job"))
        assert names == ["a", "b"], "shed submission must not land"


class TestHttpHop:
    def test_gateway_429_and_remote_typed(self):
        store = Store()
        gate = IntakeGate(rate_per_s=0.5, burst=1.0,
                          interactive_reserve=0.0)
        install_intake(store, gate)
        gateway = ApiGateway(store).start()
        try:
            remote = RemoteStore(f"127.0.0.1:{gateway.port}",
                                 overload_retries=0)
            remote.create(_job("ok"))
            with pytest.raises(OverloadedError) as exc:
                remote.create(_job("nope"))
            assert exc.value.retry_after > 0
            assert exc.value.reason == "rate"
            # the raw HTTP reply carries the Retry-After header
            import urllib.error
            import urllib.request

            from volcano_tpu.api import codec

            req = urllib.request.Request(
                f"http://127.0.0.1:{gateway.port}/apis/Job",
                data=json.dumps(codec.envelope(_job("raw"))).encode(),
                method="POST",
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as raw:
                urllib.request.urlopen(req, timeout=5)
            assert raw.value.code == 429
            assert float(raw.value.headers["Retry-After"]) > 0
        finally:
            gateway.stop()

    def test_remote_honors_retry_after_with_backoff(self):
        """overload_retries: the client pauses >= the server hint (via
        degrade.Backoff) and the re-submission succeeds."""
        store = Store()
        gate = IntakeGate(rate_per_s=20.0, burst=1.0,
                          interactive_reserve=0.0)
        install_intake(store, gate)
        gateway = ApiGateway(store).start()
        try:
            remote = RemoteStore(f"127.0.0.1:{gateway.port}",
                                 overload_retries=3)
            remote.create(_job("first"))
            # bucket empty; rate 20/s -> retry_after 0.05s: the retry
            # path must absorb it transparently
            created = remote.create(_job("second"))
            assert created.metadata.name == "second"
            st = remote.intake_stats()
            assert st["overloaded"] >= 1
            assert st["retries"] >= 1
            assert st["backoff_s"] > 0
        finally:
            gateway.stop()


class TestOverloadRungs:
    def test_admission_shed_rung_arms_and_clears(self, fake_clock):
        ladder = DegradeLadder(shed_hold_s=5.0)
        assert ladder.rung() == ""
        ladder.note_admission_shed()
        assert ladder.rung() == "admission_shed"
        fake_clock.t += 6.0
        assert ladder.rung() == ""

    def test_coalesce_rung_arms_on_lag_signal(self, fake_clock):
        ladder = DegradeLadder(coalesce_hold_s=10.0)
        ladder.note_watch_lag(10, 100)  # under half the budget: quiet
        assert ladder.rung() == ""
        assert not ladder.watch_coalesce_aggressive()
        ladder.note_watch_lag(60, 100)  # over half: armed
        assert ladder.watch_coalesce_aggressive()
        assert ladder.rung() == "watch_coalesce_aggressive"
        fake_clock.t += 11.0
        assert not ladder.watch_coalesce_aggressive()

    def test_resync_only_breaker_demotion_storm(self, fake_clock):
        ladder = DegradeLadder(frontdoor_threshold=3,
                               frontdoor_cooldown_s=10.0)
        assert not ladder.watch_resync_only()
        for _ in range(3):
            ladder.note_watch_demotion()
        assert ladder.rung() == "snapshot_resync_only"
        assert ladder.watch_resync_only()
        # open implies coalesce-hard too
        assert ladder.watch_coalesce_aggressive()
        # cooldown passes: one probe allowed, and a completed resync
        # closes the breaker
        fake_clock.t += 11.0
        assert not ladder.watch_resync_only()  # the half-open probe
        ladder.note_watch_promoted()
        assert ladder.rung() == ""
        assert not ladder.watch_resync_only()

    def test_session_skip_still_most_severe(self, fake_clock):
        ladder = DegradeLadder(frontdoor_threshold=1)
        ladder.note_watch_demotion()
        for _ in range(3):
            ladder.note_store_error()
        assert ladder.rung() == "session_skip"


class TestFrontDoorMetrics:
    def test_new_series_render_with_inf_bucket(self):
        metrics.reset()
        try:
            metrics.set_watch_queue_depth("interactive", 7)
            metrics.set_watch_queue_depth("batch", 123)
            metrics.register_watch_coalesced(41)
            metrics.register_admission_shed("rate", 3)
            metrics.register_admission_shed("backlog")
            metrics.observe_admission_retry_after(0.3)
            metrics.observe_admission_retry_after(42.0)  # beyond buckets
            body = metrics.render()
            assert ('volcano_watch_queue_depth{watcher_class='
                    '"interactive"} 7' in body)
            assert ('volcano_watch_queue_depth{watcher_class='
                    '"batch"} 123' in body)
            assert "volcano_watch_events_coalesced_total 41" in body
            assert 'volcano_admission_shed_total{reason="rate"} 3' in body
            assert ('volcano_admission_shed_total{reason="backlog"} 1'
                    in body)
            # +Inf bucket is mandatory and equals _count (2 observations,
            # one past the last finite bucket)
            assert ('volcano_admission_retry_after_seconds_bucket'
                    '{le="+Inf"} 2' in body)
            assert "volcano_admission_retry_after_seconds_count 2" in body
        finally:
            metrics.reset()


def _pod(i: int):
    from volcano_tpu.scheduler.util.test_utils import build_pod

    pod = build_pod("fan", f"pod-{i:06d}", "", objects.POD_PHASE_PENDING,
                    {"cpu": "100m", "memory": "64Mi"}, "")
    pod.metadata.ensure_identity()
    return pod


class TestWatchFanout:
    @pytest.mark.parametrize("watchers", [1, 400])
    def test_fanout_bounded(self, watchers):
        """Many watchers over ONE shared journal, polled in lockstep with
        pod churn, with a laggard tail that polls every eighth batch:
        every watcher registers and is delivered to, per-watcher state is
        a cursor and counters (no queues, no copies), and the laggards'
        pins never hold the ring past its hard cap."""
        import copy

        from volcano_tpu.store.flowcontrol import WatchFanout
        from volcano_tpu.store.gateway import _WatchJournal

        store = Store()
        journal = _WatchJournal(store, "Pod", cap=256)
        fanout = WatchFanout(journal, pin_factor=4, ladder=DegradeLadder())
        live = [_pod(i) for i in range(64)]
        for pod in live:
            store.create(pod)
        cursors = [0] * watchers
        delivered = 0
        for batch in range(12):
            for k in range(48):
                if k % 7 == 0:
                    live.append(_pod(len(live)))
                    store.create(live[-1])
                    continue
                cur = store.try_get("Pod", "fan",
                                    live[(batch * 48 + k) % len(live)]
                                    .metadata.name)
                upd = copy.deepcopy(cur)
                upd.metadata.annotations["b"] = str(batch)
                store.update(upd)
            for i in range(watchers):
                if i % 50 == 49 and batch % 8:
                    continue  # the laggard tail
                events, cursors[i], reset = fanout.poll_for(
                    f"w{i:05d}", cursors[i], 0.0,
                    cls="interactive" if i % 3 == 0 else "batch")
                if not reset:
                    delivered += len(events)
        stats = fanout.watch_stats()
        assert len(fanout.watchers) == watchers
        assert sum(c["watchers"] for c in stats["classes"].values()) \
            == watchers
        assert delivered > 0
        state_bytes = max(
            sys.getsizeof(ws) + sum(sys.getsizeof(getattr(ws, slot))
                                    for slot in type(ws).__slots__)
            for ws in fanout.watchers.values())
        assert state_bytes < 4096
        assert stats["journal"]["peak_occupancy"] \
            <= stats["journal"]["hard_cap"]
