"""The benchmark's seeded generator builds the same clusters as
volcano_tpu/bench/clusters.py (the program's unseeded generators), at a
small scale: node shapes, gang counts and sizes, minMember, queues,
priorities, request multisets and the running fill's per-node counts."""

import json
import os
from collections import Counter

import pytest

from conftest import BENCH

# (configuration, clusters.py config number, scale, group counts at it)
CASES = [
    ("cfg5-full-default", 5, 0.01, 100, [62]),
    ("cfg4-overcommit", 4, 0.1, 800, [500, 175, 25, 50]),
]


def shape(cache) -> dict:
    """What a cluster is, independent of names and order."""
    nodes = Counter((n.allocatable.milli_cpu, n.allocatable.memory,
                     n.allocatable.max_task_num)
                    for n in cache.nodes.values())
    gangs, tasks, load = Counter(), Counter(), Counter()
    for job in cache.jobs.values():
        gangs[(job.min_available, job.queue, len(job.tasks))] += 1
        for t in job.tasks.values():
            tasks[(t.resreq.milli_cpu, t.resreq.memory, t.priority,
                   str(t.status), job.queue)] += 1
            if t.node_name:
                load[t.node_name] += 1
    queues = sorted((q.name, q.weight) for q in cache.queues.values())
    return {"nodes": nodes, "gangs": gangs, "tasks": tasks, "queues": queues,
            "load": Counter(load.values())}


@pytest.mark.parametrize("name,cfg_no,scale,nodes,counts", CASES)
def test_generator_matches_clusters_py(name, cfg_no, scale, nodes, counts):
    from cluster import Cluster
    from harness import Recorder, new_cache
    from volcano_tpu.bench import clusters

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg["nodes"]["count"] = nodes
    for cls, n in zip(cfg["groups"], counts):
        cls["count"] = n
    ref = clusters.make_cache()
    clusters.CONFIGS[cfg_no].populate(ref, scale)
    for seed in (1, 2 ** 33 + 5):
        cl = Cluster(cfg, seed, new_cache(Recorder()))
        cl.populate(cl.add_nodes())
        assert shape(cl.cache) == shape(ref)


def test_seed_reorders_but_keeps_the_work():
    """Two seeds: different order and placement, the same multiset."""
    from cluster import Cluster
    from harness import Recorder, new_cache

    with open(os.path.join(BENCH, "configs", "cfg4-overcommit.json")) as f:
        cfg = json.load(f)
    cfg["nodes"]["count"] = 80
    for cls, n in zip(cfg["groups"], [50, 18, 3, 5]):
        cls["count"] = n
    a = Cluster(cfg, 11, new_cache(Recorder()))
    a.populate(a.add_nodes())
    b = Cluster(cfg, 12, new_cache(Recorder()))
    b.populate(b.add_nodes())
    assert shape(a.cache) == shape(b.cache)
    place = lambda c: {k: t.node for k, t in c.world.tasks.items()}  # noqa
    assert place(a) != place(b)
    assert list(a.world.tasks) != list(b.world.tasks)
