"""Seeded cluster generator: one configuration file in, a populated cache out.

A configuration (``benchmark/configs/<name>.json``) lists node shapes,
queues and classes of pod groups with their sizes, minMember, queue,
priority, phase and the request values each task draws from. This module
is the one generator every configuration goes through (a seeded copy of
``volcano_tpu/bench/clusters.py``'s generators, which ignore any seed):

- the multiset of requests is what ``random.Random(draw_seed)`` draws, task
  by task, as clusters.py draws it; ``--seed`` only shuffles that multiset
  and the order of nodes, groups and running placements, so every seed
  gives the same amount of work;
- objects go in through the cache's watch handlers, the path the store
  feeds in production;
- beside the cache it keeps a plain ``World`` (integers and names only) that
  the reference reads; the reference never looks at the program's objects.

It reads ``namespace``, ``draw_seed``, ``nodes``, ``queues`` and ``groups``
and ignores every other key of the file (``assumed``, ``guarantees``, the
CPU rehearsal's ``tiny``; the modes read ``policy``).
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_BIN = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40}


def milli(q: str) -> int:
    """'250m' -> 250, '32' -> 32000."""
    q = str(q)
    return int(q[:-1]) if q.endswith("m") else int(float(q) * 1000)


def nbytes(q: str) -> int:
    """'512Mi' -> 536870912."""
    q = str(q)
    for suffix, mult in _BIN.items():
        if q.endswith(suffix):
            return int(q[:-2]) * mult
    return int(q)


def stream(seed: int, *names) -> random.Random:
    """An independent generator for one purpose of one run."""
    return random.Random(":".join(str(x) for x in (seed,) + names))


@dataclass
class Task:
    key: str            # "namespace/name", as the binder reports it
    gang: str
    cpu: int            # milli-cpu
    mem: int            # bytes
    priority: int
    queue: str
    node: str = ""      # set when running


@dataclass
class Gang:
    name: str
    min_member: int
    queue: str
    priority: int
    keys: List[str] = field(default_factory=list)


@dataclass
class World:
    """What the reference knows: node allocatable and every task's request
    and placement, as the harness generated them."""
    nodes: Dict[str, Tuple[int, int, int]] = field(default_factory=dict)
    tasks: Dict[str, Task] = field(default_factory=dict)
    gangs: Dict[str, Gang] = field(default_factory=dict)


def request_draws(cfg: dict) -> Dict[str, List[Tuple[str, str]]]:
    """Per group class, the (cpu, memory) of each task in generation order:
    the draws clusters.py makes with random.Random(draw_seed)."""
    rng = random.Random(cfg["draw_seed"])
    out = {}
    for cls in cfg["groups"]:
        cpus, mems = cls["cpu"], cls["memory"]
        draws = []
        for _ in range(cls["count"] * cls["size"]):
            c = rng.choice(cpus) if cpus else ""
            m = rng.choice(mems) if mems else ""
            draws.append((c, m))
        out[cls["prefix"]] = draws
    return out


class Cluster:
    """A cache built from one configuration, and its World."""

    def __init__(self, cfg: dict, seed: int, cache):
        from volcano_tpu.scheduler.util import scheduler_helper

        self.cfg = cfg
        self.seed = seed
        self.cache = cache
        self.ns = cfg["namespace"]
        self.world = World()
        self.draws = request_draws(cfg)
        # task key -> (group, cpu, memory, priority) as its pod states them
        self.specs: Dict[str, tuple] = {}
        # the program keeps a process-wide node sampling cursor; a fresh
        # cluster starts it at 0, as clusters.make_cache does
        scheduler_helper.reset_round_robin()

    # -- objects -----------------------------------------------------------

    def add_nodes(self) -> List[str]:
        from volcano_tpu.api import objects

        spec = self.cfg["nodes"]
        names = [f"{spec['prefix']}-{n:05d}" for n in range(spec["count"])]
        alloc = (milli(spec["cpu"]), nbytes(spec["memory"]), int(spec["pods"]))
        rl = {"cpu": spec["cpu"], "memory": spec["memory"], "pods": spec["pods"]}
        order = list(names)
        stream(self.seed, "node-order").shuffle(order)
        for name in order:
            node = objects.Node(
                metadata=objects.ObjectMeta(name=name, labels={}),
                status=objects.NodeStatus(capacity=dict(rl),
                                          allocatable=dict(rl)))
            node.metadata.ensure_identity()
            self.cache.add_node(node)
            self.world.nodes[name] = alloc
        for q in self.cfg["queues"]:
            queue = objects.Queue(
                metadata=objects.ObjectMeta(name=q["name"], namespace=""),
                spec=objects.QueueSpec(weight=q["weight"], capability=None))
            queue.metadata.ensure_identity()
            self.cache.add_queue(queue)
        return names

    def _pod(self, name: str, group: str, node: str, phase: str,
             cpu: str, mem: str, priority: Optional[int]):
        from volcano_tpu.api import objects

        request = {}
        if cpu:
            request["cpu"] = cpu
        if mem:
            request["memory"] = mem
        pod = objects.Pod(
            metadata=objects.ObjectMeta(
                name=name, namespace=self.ns, uid=f"{self.ns}-{name}",
                labels={},
                annotations={objects.GROUP_NAME_ANNOTATION_KEY: group}),
            spec=objects.PodSpec(
                node_name=node, node_selector={},
                containers=[objects.Container(name="c", requests=request)],
                priority=priority),
            status=objects.PodStatus(phase=phase))
        pod.metadata.ensure_identity()
        return pod

    def _pod_group(self, name: str, min_member: int, queue: str):
        from volcano_tpu.api import objects

        pg = objects.PodGroup(
            metadata=objects.ObjectMeta(name=name, namespace=self.ns),
            spec=objects.PodGroupSpec(min_member=min_member, queue=queue,
                                      min_resources=None),
            status=objects.PodGroupStatus(
                phase=objects.PodGroupPhase.INQUEUE))
        pg.metadata.ensure_identity()
        return pg

    def add_gang(self, name: str, cls: dict, requests, phase: str,
                 nodes: Optional[List[str]] = None) -> Gang:
        """One pod group and its pods, through the watch handlers; returns
        the World's record of it. ``nodes`` places a Running gang."""
        pri = cls["priority"]
        gang = Gang(name, cls["min_member"], cls["queue"],
                    1 if pri is None else pri)
        self.cache.add_pod_group(
            self._pod_group(name, cls["min_member"], cls["queue"]))
        self.world.gangs[name] = gang
        for i, (cpu, mem) in enumerate(requests):
            pname = f"{name}-t{i}"
            node = nodes[i] if nodes else ""
            self.cache.add_pod(self._pod(pname, name, node, phase, cpu, mem,
                                         pri))
            key = f"{self.ns}/{pname}"
            gang.keys.append(key)
            self.specs[key] = (name, cpu, mem, pri)
            self.world.tasks[key] = Task(
                key, name, milli(cpu) if cpu else 0,
                nbytes(mem) if mem else 0, gang.priority, gang.queue, node)
        return gang

    def delete_gang(self, name: str) -> None:
        """Completion: the gang's pods, then its pod group, leave through
        the watch handlers."""
        gang = self.world.gangs.pop(name)
        for key in gang.keys:
            task = self.world.tasks.pop(key)
            self.cache.delete_pod(self.pod_of(
                key, task.node, "Succeeded" if task.node else "Pending"))
            del self.specs[key]
        self.cache.delete_pod_group(
            self._pod_group(name, gang.min_member, gang.queue))

    def pod_of(self, key: str, node: str, phase: str):
        """The pod of a task the cluster holds, as the store would send it
        in a watch event: same identity and requests, this node and phase."""
        group, cpu, mem, pri = self.specs[key]
        return self._pod(key.split("/", 1)[1], group, node, phase, cpu, mem,
                         pri)

    # -- whole configurations ---------------------------------------------

    def gangs_of(self, cls: dict, tag: str) -> List[Tuple[str, list]]:
        """(name, requests) for every gang of one class, in a seeded order,
        with the class's request multiset shuffled by the seed. ``tag``
        makes names (and the shuffle) distinct per wave."""
        draws = list(self.draws[cls["prefix"]])
        stream(self.seed, "requests", cls["prefix"], tag).shuffle(draws)
        size = cls["size"]
        suffix = f"-{tag}" if tag else ""
        gangs = [(f"{cls['prefix']}{suffix}-{g:05d}",
                  draws[g * size:(g + 1) * size])
                 for g in range(cls["count"])]
        stream(self.seed, "group-order", cls["prefix"], tag).shuffle(gangs)
        return gangs

    def populate(self, node_names: List[str], tag: str = "",
                 running_phase_for_all: bool = False) -> None:
        """Every group class of the configuration. Running classes (or all
        classes, for a steady state) are bound round-robin over a seeded
        permutation of the nodes: pod i of the running fill lands on
        perm[i % nodes], so node loads keep clusters.py's counts."""
        perm = list(node_names)
        stream(self.seed, "fill-nodes", tag).shuffle(perm)
        idx = 0
        for cls in self.cfg["groups"]:
            running = running_phase_for_all or cls["phase"] == "Running"
            for name, reqs in self.gangs_of(cls, tag):
                if running:
                    nodes = [perm[(idx + i) % len(perm)]
                             for i in range(len(reqs))]
                    idx += len(reqs)
                    self.add_gang(name, cls, reqs, "Running", nodes)
                else:
                    self.add_gang(name, cls, reqs, "Pending")
