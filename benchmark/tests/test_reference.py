"""The reference's numbers on hand-made clusters, one guarantee at a time."""

from cluster import Gang, Task, World
from reference import check_session, first_fit

GI = 2 ** 30


def world(nodes=2, running=(), pending=(), min_member=2):
    """Nodes of 4 cpu / 8Gi / 4 pods; ``running`` and ``pending`` are gangs
    as lists of (cpu milli, node, priority)."""
    w = World(nodes={f"n{i}": (4000, 8 * GI, 4) for i in range(nodes)})
    for kind, gangs in (("r", running), ("p", pending)):
        for g, members in enumerate(gangs):
            name = f"{kind}{g}"
            pri = members[0][2]
            gang = Gang(name, min_member, "q", pri)
            for i, (cpu, node, _) in enumerate(members):
                key = f"ns/{name}-t{i}"
                w.tasks[key] = Task(key, name, cpu, GI, pri, "q", node)
                gang.keys.append(key)
            w.gangs[name] = gang
    return w


def test_sound_session_reads_zero():
    w = world(pending=[[(1000, "", 1)] * 2])
    binds = [("ns/p0-t0", "n0"), ("ns/p0-t1", "n1")]
    assert check_session(w, binds, []) == {
        "violations": 0, "unbound": 0, "preempt_short": 0,
        "over_evicted": 0}


def test_capacity_gang_and_identity_violations():
    w = world(pending=[[(3000, "", 1)] * 2, [(1000, "", 1)] * 2])
    over = [("ns/p0-t0", "n0"), ("ns/p0-t1", "n0")]  # 6 cpu on a 4-cpu node
    assert check_session(w, over, [])["violations"] == 1
    partial = [("ns/p1-t0", "n1")]  # 1 of a gang of minMember 2
    assert check_session(w, partial, [])["violations"] == 1
    ghost = [("ns/nobody", "n0"), ("ns/p1-t0", "nowhere")]
    assert check_session(w, ghost, [])["violations"] == 2
    twice = [("ns/p1-t0", "n0"), ("ns/p1-t1", "n0"), ("ns/p1-t0", "n1")]
    assert check_session(w, twice, [])["violations"] == 1


def test_unbound_counts_what_first_fit_still_places():
    w = world(pending=[[(1000, "", 1)] * 2, [(1000, "", 1)] * 2])
    assert check_session(w, [], [])["unbound"] == 4
    half = [("ns/p0-t0", "n0"), ("ns/p0-t1", "n0")]
    assert check_session(w, half, [])["unbound"] == 2
    full = world(nodes=1, running=[[(2000, "n0", 1)] * 2],
                 pending=[[(1000, "", 1)] * 2])
    assert check_session(full, [], [])["unbound"] == 0


def test_eviction_rules_and_preemption_room():
    # n0, n1 full of low-priority gangs of 2 (minMember 1 -> 1 spare each)
    w = world(running=[[(2000, "n0", 1)] * 2, [(2000, "n1", 1)] * 2],
              pending=[[(2000, "", 9)] * 2], min_member=1)
    # no eviction: the preemptors find no room, though victims exist
    assert check_session(w, [], [])["preempt_short"] == 2
    one_each = ["ns/r0-t0", "ns/r1-t0"]
    assert check_session(w, [], one_each) == {
        "violations": 0, "unbound": 0, "preempt_short": 0,
        "over_evicted": 0}
    # a gang emptied below its minMember, an eviction twice, a pending task
    assert check_session(w, [], ["ns/r0-t0", "ns/r0-t1"])["violations"] == 1
    assert check_session(w, [], ["ns/r0-t0", "ns/r0-t0"])["violations"] == 1
    assert check_session(w, [], ["ns/p0-t0"])["violations"] == 1
    # a victim of the preemptors' own priority
    same = world(running=[[(2000, "n0", 9)] * 2],
                 pending=[[(2000, "", 9)] * 2], min_member=1)
    assert check_session(same, [], ["ns/r0-t0"])["violations"] == 1


def test_first_fit_gives_back_a_gang_that_falls_short():
    free = {"a": [2000, 8 * GI, 4], "b": [1000, 8 * GI, 4]}
    assert first_fit(free, [(3, [(1000, GI)] * 3), (2, [(1000, GI)] * 2)]) == 3
    assert free["a"][0] == free["b"][0] == 0
    free = {"a": [2000, 8 * GI, 4]}
    assert first_fit(free, [(3, [(1000, GI)] * 3), (1, [(1000, GI)])]) == 1
    assert free["a"][0] == 1000


def test_over_evicted_counts_victims_nobody_needs():
    # n0..n2 each run a low-priority gang of 3 x 1 cpu (minMember 1): 1 cpu
    # idle, 2 spare victims each; one preemptor gang of 1 x 2 cpu
    w = world(nodes=3, running=[[(1000, f"n{i}", 1)] * 3 for i in range(3)],
              pending=[[(2000, "", 9)]], min_member=1)
    # two victims free the preemptor's 2 cpu (idle room does not count)
    need = ["ns/r0-t0", "ns/r0-t1"]
    assert check_session(w, [], need)["over_evicted"] == 0
    # one victim more on the same node, and two on a node nobody goes to
    assert check_session(w, [], need + ["ns/r0-t2"])["over_evicted"] == 1
    assert check_session(w, [], need + ["ns/r1-t0", "ns/r1-t1"]
                         )["over_evicted"] == 2
    # evictions with no pending gang that may take them
    calm = world(nodes=1, running=[[(1000, "n0", 1)] * 3], min_member=1)
    assert check_session(calm, [], ["ns/r0-t0"])["over_evicted"] == 1
