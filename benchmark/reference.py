"""The plain reference: the guarantees a configuration states, checked on
what one session bound and evicted.

It imports nothing of the program. It reads the harness's own World (node
allocatable, every task's request, gang and placement, as generated) and the
binds and evictions the harness's own binder and evictor recorded. A
placement is not unique (the rounds kernel and the serial actions choose
different nodes for the same tasks), so the reference does not compare node
choices. It compares what every valid answer shares:

- ``violations``: broken guarantees. A bind of anything but a pending task,
  a second bind, a bind to an unknown node, a node over its cpu, memory or
  pod count (evicted tasks still hold their node until they leave), a gang
  bound below minMember, an eviction of anything but a running task, a
  second eviction, a victim whose priority is not below that of some
  pending task, a running gang left below its minMember.
- ``unbound``: tasks of pending gangs that a plain first-fit places in the
  idle capacity the session left behind: work the session should have done.
- ``preempt_short``: tasks of pending gangs that outrank running tasks,
  which first-fit places once every permitted victim is gone, less those it
  places in the room the session's evictions made.
- ``over_evicted``: victims nobody needed. A preemptor takes victims on a
  node until what they free covers its request; the node's idle room does
  not count (preempt.go's rule, as the program's serial preempt keeps it).
  First-fit places the pending gangs that may take a victim (a higher
  priority in the victim's queue, or another queue) into the room the
  victims alone free, highest priority first; a victim counts where its
  whole request still fits in what its node's freed room has left
  (smallest victims first). This catches an eviction on a node where no
  preemptor goes, and one victim too many on a node where one does.

Every number is an exact count; its limit is 0.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from cluster import Task, World

# the numbers check_session returns, each an exact count with limit 0
CHECKS = ("violations", "unbound", "preempt_short", "over_evicted")


def first_fit(free: Dict[str, List[int]], gangs: Iterable[Tuple[int, list]]
              ) -> int:
    """Place gangs task by task on the first node (by name) with room, a
    gang only when at least its minimum fits; consumes ``free``. Returns the
    number of tasks placed. ``gangs`` yields (minimum, [(cpu, mem), ...])."""
    order = sorted(free)
    # per request size, the first node that may still fit it: room only
    # shrinks, except where a gang that fell short gives its room back
    start: Dict[Tuple[int, int], int] = {}
    placed = 0
    for minimum, needs in gangs:
        taken = []
        for need in needs:
            i = start.get(need, 0)
            while i < len(order):
                f = free[order[i]]
                if f[0] >= need[0] and f[1] >= need[1] and f[2] >= 1:
                    break
                i += 1
            start[need] = i
            if i == len(order):
                continue
            f = free[order[i]]
            f[0] -= need[0]
            f[1] -= need[1]
            f[2] -= 1
            taken.append((i, need))
        if len(taken) >= max(minimum, 1):
            placed += len(taken)
            continue
        for i, need in taken:
            f = free[order[i]]
            f[0] += need[0]
            f[1] += need[1]
            f[2] += 1
            for k in start:
                start[k] = min(start[k], i)
    return placed


def preemptor_priority(world: World) -> Dict[str, int]:
    """Per queue, the highest priority of a pending gang that outranks a
    running task of its queue."""
    low: Dict[str, int] = {}
    for t in world.tasks.values():
        if t.node:
            low[t.queue] = min(low.get(t.queue, t.priority), t.priority)
    over: Dict[str, int] = {}
    for g in world.gangs.values():
        if any(world.tasks[k].node for k in g.keys):
            continue
        if g.queue in low and g.priority > low[g.queue]:
            over[g.queue] = max(over.get(g.queue, g.priority), g.priority)
    return over


def permitted_victims(world: World, over: Dict[str, int]) -> List[str]:
    """Every running task a preemptor may take: per running gang, its
    lowest-priority members above minMember, each below ``over``, the
    priority of the highest pending gang of its queue that outranks it."""
    tasks = world.tasks
    out = []
    for g in world.gangs.values():
        members = [tasks[k] for k in g.keys if tasks[k].node]
        spare = len(members) - g.min_member
        for t in sorted(members, key=lambda t: t.priority)[:max(spare, 0)]:
            if t.priority < over.get(t.queue, t.priority):
                out.append(t.key)
    return out


def check_session(world: World, binds: List[Tuple[str, str]],
                  evicts: List[str]) -> Dict[str, int]:
    """The numbers of one session. ``world`` is the cluster as the session
    opened on it: running tasks carry their node, pending ones none."""
    bad = 0
    tasks, nodes = world.tasks, world.nodes
    used = {n: [0, 0, 0] for n in nodes}
    for t in tasks.values():
        if t.node:
            u = used[t.node]
            u[0] += t.cpu
            u[1] += t.mem
            u[2] += 1

    bound: Dict[str, str] = {}
    for key, node in binds:
        t = tasks.get(key)
        if t is None or t.node or key in bound or node not in nodes:
            bad += 1
            continue
        bound[key] = node
        u = used[node]
        u[0] += t.cpu
        u[1] += t.mem
        u[2] += 1
    for name, (cpu, mem, pods) in nodes.items():
        u = used[name]
        if u[0] > cpu or u[1] > mem or u[2] > pods:
            bad += 1

    evicted = set()
    pending_pri = [t.priority for t in tasks.values() if not t.node]
    top = max(pending_pri) if pending_pri else None
    for key in evicts:
        t = tasks.get(key)
        if t is None or not t.node or key in evicted:
            bad += 1
            continue
        evicted.add(key)
        if top is None or t.priority >= top:
            bad += 1

    running_gangs = []
    for g in world.gangs.values():
        members = [tasks[k] for k in g.keys]
        if any(t.node for t in members):
            running_gangs.append(g)
            alive = sum(1 for t in members if t.node and t.key not in evicted)
            lost = sum(1 for t in members if t.key in evicted)
            if lost and alive < g.min_member:
                bad += 1
        else:
            n = sum(1 for k in g.keys if k in bound)
            if 0 < n < g.min_member:
                bad += 1

    # work left undone: first-fit of what stayed pending into idle room
    idle = {n: [c - used[n][0], m - used[n][1], p - used[n][2]]
            for n, (c, m, p) in nodes.items()}
    left = []
    for g in world.gangs.values():
        rest = [(tasks[k].cpu, tasks[k].mem) for k in g.keys
                if not tasks[k].node and k not in bound]
        if not rest:
            continue
        started = len(rest) < len(g.keys)
        left.append((g, 0 if started else g.min_member, rest))
    unbound = first_fit(idle, ((m, r) for _, m, r in left))

    # room for the preemptors: after the session's evictions, and after
    # every permitted eviction
    low: Dict[str, int] = {}
    for t in tasks.values():
        if t.node:
            low[t.queue] = min(low.get(t.queue, t.priority), t.priority)
    over: Dict[str, int] = {}
    preemptors, claimants = [], []
    for g, m, r in left:
        if g.queue in low and g.priority > low[g.queue]:
            over[g.queue] = max(over.get(g.queue, g.priority), g.priority)
            preemptors.append((m, r))
        if (g.queue in low and g.priority > low[g.queue]) or \
                any(q != g.queue for q in low):
            claimants.append((g.priority, m, r))
    short = 0
    if preemptors:
        after = {n: [c - used[n][0], m - used[n][1], p - used[n][2]]
                 for n, (c, m, p) in nodes.items()}
        for key in evicted:
            t = tasks[key]
            f = after[t.node]
            f[0] += t.cpu
            f[1] += t.mem
            f[2] += 1
        widest = {n: [c - used[n][0], m - used[n][1], p - used[n][2]]
                  for n, (c, m, p) in nodes.items()}
        for key in permitted_victims(world, over):
            t = tasks[key]
            f = widest[t.node]
            f[0] += t.cpu
            f[1] += t.mem
            f[2] += 1
        made = first_fit(after, preemptors)
        could = first_fit(widest, preemptors)
        short = max(could - made, 0)

    # victims nobody needed: a preemptor takes victims until what they free
    # covers its request (the node's idle room does not count), so the
    # gangs that may take a victim (a higher priority in its queue, or
    # another queue) go first-fit into the room the victims alone free,
    # highest priority first; a victim that still fits in what its node's
    # freed room has left was not needed
    freed = {n: [0, 0, 0] for n in nodes}
    by_node: Dict[str, List[Task]] = {}
    for key in evicted:
        t = tasks[key]
        f = freed[t.node]
        f[0] += t.cpu
        f[1] += t.mem
        f[2] += 1
        by_node.setdefault(t.node, []).append(t)
    claimants.sort(key=lambda c: -c[0])
    first_fit(freed, ((m, r) for _, m, r in claimants))
    surplus = 0
    for node, victims in by_node.items():
        f = freed[node]
        for t in sorted(victims, key=lambda t: (t.cpu, t.mem)):
            if f[0] >= t.cpu and f[1] >= t.mem and f[2] >= 1:
                surplus += 1
                f[0] -= t.cpu
                f[1] -= t.mem
                f[2] -= 1

    return {"violations": bad, "unbound": unbound, "preempt_short": short,
            "over_evicted": surplus}
