"""Scenario files + workload generation + trace replay.

A scenario is a YAML document describing the simulated cluster (nodes,
queues), the workload (initial backlog, arrival process, gang shape, job
lifecycles incl. completion/failure/cancel/resubmit), the fault mix
(chaos.py), and the audit cadence (auditor.py). ``scale_scenario`` shrinks
any scenario uniformly so the same file serves as a tier-1 gate at 1-2%
scale and a full-scale soak under ``-m slow`` — the committed scenarios
under ``volcano_tpu/sim/scenarios/`` are the repo's canonical cluster
shapes (cfg5_storm mirrors BASELINE.json cfg 5).

Jobs are submitted as REAL vcjob objects through the store: the job
controller materializes pods gated on PodGroup enqueue admission, exactly
the production submit path — not a cache shortcut. ``populate_cache``
is the shortcut twin for bench.py --scenario: it materializes only the
t=0 snapshot (nodes + initial pending gangs) straight into a
SchedulerCache, so bench and sim share ONE cluster-shape source instead
of maintaining parallel builders.
"""

from __future__ import annotations

import copy
import json
import os
from typing import Dict, List, Optional

import yaml

from volcano_tpu.api import objects
from volcano_tpu.store.store import OverloadedError
from volcano_tpu.scheduler.util.test_utils import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
    build_resource_list_with_pods,
)

SCENARIO_DIR = os.path.join(os.path.dirname(__file__), "scenarios")

DEFAULTS: Dict = {
    "name": "unnamed",
    "duration_s": 60.0,
    "cluster": {
        "nodes": 20,
        "node_cpu": "32",
        "node_mem": "64Gi",
        "node_pods": 256,
        "gpu_every": 0,   # every Nth node carries 8 GPUs (0 = none)
        "zones": 8,
    },
    "queues": [{"name": "default", "weight": 1}],
    "scheduler": {
        "conf": "tpu",        # tpu | default | literal conf YAML
        "period_s": 1.0,
        "max_sessions": None,  # optional hard cap on sessions
    },
    "workload": {
        "kind": "generate",   # generate | trace
        "initial_jobs": 10,
        "tasks_per_job": 4,
        "min_member": 4,
        "namespaces": ["sim"],
        "cpu_choices": ["250m", "500m", "1000m"],
        "mem_choices": ["512Mi", "1Gi"],
        "gpu_prob": 0.0,
        "priorities": [1],
        "arrival": {"kind": "none"},  # none | poisson | burst | heavy_tail
        # Pareto-ish job-size tail (ROADMAP item 5 realism slice): when
        # set, `tasks` is redrawn heavy-tailed AFTER the base draws, so
        # scenarios that do not opt in keep their exact sampling streams
        # (same-seed hashes byte-identical).
        # {alpha: 1.3, min_tasks: 1, cap: 64, min_member_frac: 1.0}
        "heavy_tail_sizes": None,
        "service_s": [20.0, 120.0],
        "fail_prob": 0.0,
        "cancel_prob": 0.0,
        "resubmit_prob": 0.0,
        "resubmit_delay_s": 5.0,
        "max_jobs": None,
        "ttl_s": None,
        "trace": None,        # path (relative to the scenario file)
        # interactive sub-population (serving/inference pods riding along
        # the batch gangs — the express lane's workload class): when set,
        # each sampled job flips to the interactive shape with `prob`.
        # None keeps the sampling draw-order of every existing scenario
        # byte-identical.
        "interactive": None,
        # standing backlog: N gangs submitted once at t=0 whose per-task
        # request exceeds any node's capacity, so they stay pending for
        # the whole run — the queue depth real clusters always carry.
        # Deterministic (zero RNG draws), so scenarios that do not opt in
        # keep their exact sampling streams. Gives the pipelined loop a
        # non-empty solve-ahead even when the live workload drains every
        # cycle — without it an under-subscribed scenario never exercises
        # the speculation ledger at all.
        # {jobs: 5, tasks: 2, cpu: "16", mem: "24Gi", queue: ...}
        "standing": None,
    },
    "mirrors": {"kinds": ["Pod", "Node", "PodGroup"], "cap": 512},
    # express lane (volcano_tpu/express): event-driven placement slices
    # between sessions; period_s paces the micro-slices that drain the
    # arrival queue (production is wake-event-driven; the sim quantizes
    # to engine events for determinism)
    "express": {"enabled": False, "period_s": 0.25},
    "faults": {},
    "audit": {
        "every_sessions": 1,
        "fair_share": False,
        "fair_share_tolerance": 0.5,
    },
}


def _merge(base: Dict, override: Dict) -> Dict:
    out = dict(base)
    for key, value in override.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], value)
        else:
            out[key] = value
    return out


def scenario_file(ref: str) -> str:
    """A path that exists wins; otherwise ``ref`` names a committed
    scenario (``cfg5_storm`` -> sim/scenarios/cfg5_storm.yaml)."""
    if os.path.exists(ref):
        return ref
    name = ref if ref.endswith((".yaml", ".yml")) else ref + ".yaml"
    candidate = os.path.join(SCENARIO_DIR, name)
    if os.path.exists(candidate):
        return candidate
    raise FileNotFoundError(
        f"scenario {ref!r} is neither a file nor a committed scenario "
        f"under {SCENARIO_DIR}")


def list_scenarios() -> List[str]:
    names = [f[:-5] for f in os.listdir(SCENARIO_DIR)
             if f.endswith(".yaml")]
    return sorted(names)


def load_scenario(ref: str) -> Dict:
    path = scenario_file(ref)
    with open(path) as fh:
        raw = yaml.safe_load(fh) or {}
    cfg = _merge(DEFAULTS, raw)
    cfg["_path"] = os.path.abspath(path)
    wl = cfg["workload"]
    if wl["kind"] not in ("generate", "trace"):
        raise ValueError(f"workload.kind {wl['kind']!r} not in "
                         f"('generate', 'trace')")
    if wl["kind"] == "trace" and not wl.get("trace"):
        raise ValueError("workload.kind=trace requires workload.trace")
    return cfg


def scale_scenario(cfg: Dict, scale: float) -> Dict:
    """Uniformly shrink/grow a scenario: node and job counts, arrival and
    fault rates all scale together so the demand/capacity ratio — the
    property that makes a scenario interesting — is preserved."""
    if scale == 1.0:
        return cfg
    out = copy.deepcopy(cfg)
    out["_scale"] = scale
    cl = out["cluster"]
    cl["nodes"] = max(int(cl["nodes"] * scale), 2)
    wl = out["workload"]
    wl["initial_jobs"] = max(int(wl["initial_jobs"] * scale), 1)
    if wl.get("standing"):
        wl["standing"] = dict(wl["standing"])
        wl["standing"]["jobs"] = max(
            int(int(wl["standing"].get("jobs", 0)) * scale), 1)
    if wl["max_jobs"] is not None:
        wl["max_jobs"] = max(int(wl["max_jobs"] * scale), 1)
    arrival = wl["arrival"]
    if arrival.get("kind") in ("poisson", "heavy_tail"):
        arrival["rate_per_s"] = arrival.get("rate_per_s", 1.0) * scale
    elif arrival.get("kind") == "burst":
        arrival["jobs"] = max(int(arrival.get("jobs", 1) * scale), 1)
    for fault in out.get("faults", {}).values():
        if isinstance(fault, dict) and "burst" in fault:
            fault["burst"] = max(int(fault["burst"] * scale), 1)
    fd = out.get("front_door") or {}
    intake = fd.get("intake")
    if intake:
        # the demand scales, so the gate must scale with it or the
        # demand/capacity ratio — what makes the storm a storm — breaks
        intake["rate_per_s"] = max(
            float(intake.get("rate_per_s", 1.0)) * scale, 0.1)
        if intake.get("burst") is not None:
            intake["burst"] = max(float(intake["burst"]) * scale, 1.0)
        if intake.get("max_backlog"):
            intake["max_backlog"] = max(
                int(intake["max_backlog"] * scale), 2)
    watch = fd.get("watch")
    if watch and watch.get("fleet"):
        watch["fleet"] = max(int(watch["fleet"] * scale), 4)
        if watch.get("slow"):
            watch["slow"] = max(int(watch["slow"] * scale), 1)
    return out


# ---------------------------------------------------------------------------
# Initial-cluster object builders (shared by the sim store path and the
# bench cache path)
# ---------------------------------------------------------------------------


def iter_nodes(cfg: Dict) -> List[objects.Node]:
    cl = cfg["cluster"]
    nodes = []
    for n in range(int(cl["nodes"])):
        rl = build_resource_list_with_pods(
            str(cl["node_cpu"]), str(cl["node_mem"]),
            pods=int(cl["node_pods"]))
        if cl["gpu_every"] and n % int(cl["gpu_every"]) == 0:
            rl["nvidia.com/gpu"] = "8"
        zone = f"zone-{n % max(int(cl['zones']), 1)}"
        nodes.append(build_node(
            f"node-{n:05d}", rl, labels={"zone": zone}))
    return nodes


def iter_queues(cfg: Dict) -> List[objects.Queue]:
    return [build_queue(q["name"], weight=int(q.get("weight", 1)))
            for q in cfg["queues"]]


def sample_job_shape(cfg: Dict, rng) -> Dict:
    """One job's sampled shape + lifecycle — every random decision about a
    job is drawn HERE, in one place and one order, so the workload stream
    stays reproducible as consumers evolve."""
    wl = cfg["workload"]
    lo, hi = wl["service_s"]
    shape = {
        "tasks": int(wl["tasks_per_job"]),
        "min_member": int(wl["min_member"]),
        "namespace": rng.choice(sorted(wl["namespaces"])),
        "queue": rng.choice(sorted(q["name"] for q in cfg["queues"])),
        "cpu": rng.choice(list(wl["cpu_choices"])),
        "mem": rng.choice(list(wl["mem_choices"])),
        "gpu": 1 if (wl["gpu_prob"] and rng.random() < wl["gpu_prob"]) else 0,
        "priority": int(rng.choice(list(wl["priorities"]))),
        "service_s": rng.uniform(float(lo), float(hi)),
        "fail": rng.random() < wl["fail_prob"],
        "cancel": rng.random() < wl["cancel_prob"],
        "resubmit": rng.random() < wl["resubmit_prob"],
        "interactive": False,
    }
    ht = wl.get("heavy_tail_sizes")
    if ht:
        # heavy-tailed job width (Borg/Alibaba-shaped: most jobs tiny, a
        # fat tail of wide gangs). Draws happen ONLY when the scenario
        # opts in — existing scenarios keep their exact streams.
        alpha = float(ht.get("alpha", 1.3))
        lo_t = int(ht.get("min_tasks", 1))
        cap_t = int(ht.get("cap", 64))
        tasks = min(lo_t + int(rng.paretovariate(alpha)) - 1, cap_t)
        shape["tasks"] = max(tasks, 1)
        frac = float(ht.get("min_member_frac", 1.0))
        shape["min_member"] = max(
            1, min(shape["tasks"], int(round(shape["tasks"] * frac))))
    inter = wl.get("interactive")
    if inter:
        # extra draws happen ONLY when the scenario opts in, so existing
        # scenarios keep their exact workload streams (hash stability)
        if rng.random() < float(inter.get("prob", 0.5)):
            lo, hi = inter.get("service_s", wl["service_s"])
            shape.update(
                tasks=int(inter.get("tasks", 1)),
                min_member=int(inter.get("min_member", 1)),
                cpu=rng.choice(list(inter.get(
                    "cpu_choices", wl["cpu_choices"]))),
                mem=rng.choice(list(inter.get(
                    "mem_choices", wl["mem_choices"]))),
                service_s=rng.uniform(float(lo), float(hi)),
                interactive=True,
            )
            if inter.get("queue"):
                shape["queue"] = str(inter["queue"])
    return shape


def build_sim_job(name: str, shape: Dict, ttl_s: Optional[float]) -> objects.Job:
    requests = {"cpu": shape["cpu"], "memory": shape["mem"]}
    if shape["gpu"]:
        requests["nvidia.com/gpu"] = str(shape["gpu"])
    task = objects.TaskSpec(
        name="w", replicas=shape["tasks"],
        template=objects.PodTemplateSpec(
            spec=objects.PodSpec(
                priority=shape.get("priority"),
                containers=[objects.Container(
                    name="c", image="sim", requests=requests)])))
    job = objects.Job(
        metadata=objects.ObjectMeta(
            name=name, namespace=shape["namespace"]),
        spec=objects.JobSpec(
            min_available=shape["min_member"],
            tasks=[task],
            queue=shape["queue"],
            ttl_seconds_after_finished=ttl_s,
        ),
    )
    job.spec.scheduler_name = "volcano"
    return job


# ---------------------------------------------------------------------------
# The live workload driver (store path)
# ---------------------------------------------------------------------------


class Workload:
    """Submits jobs through the store and walks their lifecycles on the
    engine: arrival processes, completion/failure at sampled service
    times, cancels (cascading deletes), resubmits."""

    def __init__(self, sim, cfg: Dict, rng):
        self.sim = sim
        self.cfg = cfg
        self.wl = cfg["workload"]
        self.rng = rng
        self._counter = 0
        # name-key -> record {shape, state}; state walks
        # submitted -> running -> finishing -> done
        self.jobs: Dict[str, Dict] = {}
        self.submitted = 0
        self.completed = 0
        self.failed = 0
        self.cancelled = 0
        # intake-gate backpressure accounting (front-door scenarios):
        # every shed submission MUST schedule a retry — the auditor's
        # rejected-with-retry, never-dropped-silently invariant
        self.shed = 0
        self.shed_retries = 0
        self.shed_readmitted = 0

    # -- start -------------------------------------------------------------

    def start(self) -> None:
        store = self.sim.store
        for node in iter_nodes(self.cfg):
            store.create(node)
        for queue in iter_queues(self.cfg):
            if store.try_get("Queue", "", queue.metadata.name) is None:
                store.create(queue)
        if self.wl["kind"] == "trace":
            self._load_trace()
            return
        for _ in range(int(self.wl["initial_jobs"])):
            self._submit()
        std = self.wl.get("standing")
        if std:
            # the standing backlog draws NOTHING from the rng: shapes are
            # fixed by the scenario, so opting in perturbs no other
            # scenario's sampled stream
            tasks = int(std.get("tasks", 2))
            shape = {
                "tasks": tasks,
                "min_member": int(std.get("min_member", tasks)),
                "namespace": sorted(self.wl["namespaces"])[0],
                "queue": str(std.get("queue", sorted(
                    q["name"] for q in self.cfg["queues"])[0])),
                "cpu": str(std.get("cpu", "1000m")),
                "mem": str(std.get("mem", "1Gi")),
                "gpu": 0,
                "priority": int(list(self.wl["priorities"])[0]),
                "service_s": float(self.wl["service_s"][1]),
                "fail": False,
                "cancel": False,
                "resubmit": False,
                "interactive": False,
            }
            for _ in range(int(std.get("jobs", 0))):
                self._submit(shape=dict(shape))
        self._schedule_arrival()

    # -- arrivals ----------------------------------------------------------

    def _exhausted(self) -> bool:
        cap = self.wl["max_jobs"]
        return cap is not None and self.submitted >= int(cap)

    def _schedule_arrival(self) -> None:
        arrival = self.wl["arrival"]
        kind = arrival.get("kind", "none")
        if kind == "none" or self._exhausted():
            return
        if kind == "poisson":
            delay = self.rng.expovariate(float(arrival["rate_per_s"]))
            self.sim.engine.schedule_in(delay, "arrival", self._on_arrival)
        elif kind == "heavy_tail":
            # Poisson base modulated by periodic burst waves (the diurnal
            # / thundering-herd shape real cluster traces show): inside a
            # wave the instantaneous rate multiplies by wave_factor
            rate = float(arrival["rate_per_s"])
            every = float(arrival.get("wave_every_s", 30.0))
            width = float(arrival.get("wave_s", every / 4.0))
            if every > 0 and (self.sim.vclock.now() % every) < width:
                rate *= float(arrival.get("wave_factor", 5.0))
            delay = self.rng.expovariate(max(rate, 1e-9))
            self.sim.engine.schedule_in(delay, "arrival", self._on_arrival)
        elif kind == "burst":
            self.sim.engine.schedule_in(
                float(arrival["every_s"]), "arrival-burst",
                self._on_burst)
        else:
            raise ValueError(f"unknown arrival kind {kind!r}")

    def _on_arrival(self) -> str:
        name = self._submit()
        self._schedule_arrival()
        return name

    def _on_burst(self) -> str:
        jobs = int(self.wl["arrival"].get("jobs", 1))
        names = [self._submit() for _ in range(jobs) if not self._exhausted()]
        self._schedule_arrival()
        return f"burst={len(names)}"

    # -- lifecycle ---------------------------------------------------------

    def _submit(self, shape: Optional[Dict] = None,
                base: Optional[str] = None, _retry: int = 0) -> str:
        self._counter += 1
        if shape is None:
            shape = sample_job_shape(self.cfg, self.rng)
        name = base or f"sim-{self._counter:06d}"
        job = build_sim_job(name, shape, self.wl["ttl_s"])
        key = f"{shape['namespace']}/{name}"
        try:
            self.sim.store.create(job)
        except OverloadedError as e:
            # the intake gate shed this submission: rejected-with-retry.
            # Re-submit the SAME job no earlier than the server's
            # retry_after, escalating exponentially on repeat sheds (the
            # client-side backoff a RemoteStore submitter runs) so a
            # storm of shed retries cannot hold the bucket at zero —
            # and nothing is ever dropped silently (the auditor balances
            # shed == retries scheduled).
            delay = min(max(e.retry_after, 0.05) * (1.7 ** min(_retry, 8)),
                        60.0)
            self.shed += 1
            self.shed_retries += 1
            self.sim.engine.schedule_in(
                delay, "intake-retry",
                lambda s=shape, n=name, a=_retry + 1: self._submit(
                    shape=s, base=n, _retry=a))
            self.sim.engine.log_event(
                "shed",
                f"{key} reason={e.reason} "
                f"retry_in={round(delay, 3)}")
            return f"{key} shed"
        if _retry:
            self.shed_readmitted += 1
        self.jobs[key] = {"shape": shape, "state": "submitted"}
        self.submitted += 1
        self.sim.engine.log_event(
            "submit",
            f"{key} tasks={shape['tasks']} cpu={shape['cpu']} "
            f"mem={shape['mem']} q={shape['queue']}")
        if shape["cancel"]:
            self.sim.engine.schedule_in(
                self.rng.uniform(0.5, 1.0) * shape["service_s"],
                "cancel", lambda k=key: self._on_cancel(k))
        return key

    def _on_cancel(self, key: str) -> str:
        rec = self.jobs.get(key)
        if rec is None or rec["state"] == "done":
            return f"{key} already-done"
        ns, name = key.split("/", 1)
        if self.sim.store.try_delete("Job", ns, name) is not None:
            rec["state"] = "done"
            self.cancelled += 1
            return f"{key} cancelled"
        return f"{key} gone"

    def _on_finish(self, key: str) -> str:
        rec = self.jobs.get(key)
        if rec is None or rec["state"] != "finishing":
            return f"{key} skipped"
        ns, _ = key.split("/", 1)
        shape = rec["shape"]
        phase = (objects.POD_PHASE_FAILED if shape["fail"]
                 else objects.POD_PHASE_SUCCEEDED)
        flipped = 0
        for pod in self.sim.store.list("Pod", namespace=ns):
            if pod.metadata.annotations.get(objects.JOB_NAME_KEY) \
                    != key.split("/", 1)[1]:
                continue
            if pod.status.phase != objects.POD_PHASE_RUNNING:
                continue
            updated = copy.deepcopy(pod)
            updated.status.phase = phase
            if phase == objects.POD_PHASE_FAILED:
                updated.status.container_statuses = [
                    objects.ContainerStatus(name="c", exit_code=1)]
            self.sim.store.update_status(updated)
            flipped += 1
        rec["state"] = "done"
        if shape["fail"]:
            self.failed += 1
        else:
            self.completed += 1
        if shape["resubmit"] and not self._exhausted():
            fresh = sample_job_shape(self.cfg, self.rng)
            self.sim.engine.schedule_in(
                float(self.wl["resubmit_delay_s"]), "resubmit",
                lambda s=fresh: self._submit(shape=s))
        return f"{key} {phase.lower()} pods={flipped}"

    # -- per-slice sweep ---------------------------------------------------

    def on_slice(self) -> Dict[str, int]:
        """Walk the pod population once: per-job running counts drive the
        finish scheduling; the aggregate counts feed the metric gauges and
        the session log line."""
        running_by_job: Dict[str, int] = {}
        stats = {"pods": 0, "pending": 0, "running": 0, "bound": 0,
                 "succeeded": 0, "failed": 0}
        for pod in self.sim.store.list("Pod"):
            stats["pods"] += 1
            phase = pod.status.phase
            if phase == objects.POD_PHASE_PENDING:
                stats["pending"] += 1
                if pod.spec.node_name:
                    stats["bound"] += 1
            elif phase == objects.POD_PHASE_RUNNING:
                stats["running"] += 1
                job_name = pod.metadata.annotations.get(objects.JOB_NAME_KEY)
                if job_name:
                    job_key = f"{pod.metadata.namespace}/{job_name}"
                    running_by_job[job_key] = running_by_job.get(job_key, 0) + 1
            elif phase == objects.POD_PHASE_SUCCEEDED:
                stats["succeeded"] += 1
            elif phase == objects.POD_PHASE_FAILED:
                stats["failed"] += 1
        for key, n in sorted(running_by_job.items()):
            rec = self.jobs.get(key)
            if rec is None or rec["state"] != "submitted":
                continue
            if n >= rec["shape"]["tasks"]:
                rec["state"] = "finishing"
                self.sim.engine.schedule_in(
                    rec["shape"]["service_s"], "finish",
                    lambda k=key: self._on_finish(k))
        return stats

    # -- trace replay ------------------------------------------------------

    def _load_trace(self) -> None:
        path = self.wl["trace"]
        if not os.path.isabs(path):
            path = os.path.join(os.path.dirname(self.cfg["_path"]), path)
        with open(path) as fh:
            entries = [json.loads(line) for line in fh
                       if line.strip() and not line.startswith("#")]
        for entry in entries:
            at = float(entry.get("at", 0.0))
            op = entry.get("op", "submit")
            if op == "submit":
                shape = sample_job_shape(self.cfg, self.rng)
                for field in ("tasks", "min_member", "namespace", "queue",
                              "cpu", "mem", "service_s", "fail"):
                    if field in entry:
                        shape[field] = entry[field]
                shape["cancel"] = False
                name = entry.get("name")
                self.sim.engine.schedule_at(
                    at, "trace-submit",
                    lambda s=shape, n=name: self._submit(shape=s, base=n))
            elif op == "delete":
                key = f"{entry['namespace']}/{entry['name']}"
                self.sim.engine.schedule_at(
                    at, "trace-delete",
                    lambda k=key: self._on_cancel(k))
            else:
                raise ValueError(f"unknown trace op {op!r}")


# ---------------------------------------------------------------------------
# Bench snapshot twin (cache path)
# ---------------------------------------------------------------------------


def populate_cache(cache, cfg: Dict, rng) -> int:
    """Materialize a scenario's t=0 snapshot straight into a
    SchedulerCache (bench.py --scenario): nodes, queues, and the initial
    pending gangs — the same shapes the sim submits through the store,
    minus the lifecycle machinery a static latency benchmark cannot use.
    Returns the task count."""
    for node in iter_nodes(cfg):
        cache.add_node(node)
    for queue in iter_queues(cfg):
        cache.add_queue(queue)
    tasks = 0
    for j in range(int(cfg["workload"]["initial_jobs"])):
        shape = sample_job_shape(cfg, rng)
        pg_name = f"sim-{j + 1:06d}"
        cache.add_pod_group(build_pod_group(
            pg_name, namespace=shape["namespace"],
            min_member=shape["min_member"], queue=shape["queue"]))
        requests = {"cpu": shape["cpu"], "memory": shape["mem"]}
        if shape["gpu"]:
            requests["nvidia.com/gpu"] = str(shape["gpu"])
        for i in range(shape["tasks"]):
            cache.add_pod(build_pod(
                shape["namespace"], f"{pg_name}-w-{i}", "",
                objects.POD_PHASE_PENDING, requests, pg_name))
            tasks += 1
    return tasks
