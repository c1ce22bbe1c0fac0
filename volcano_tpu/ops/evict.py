"""Batched device eviction: preempt/reclaim/backfill on the TPU kernel path.

The preempt/reclaim actions are the last host-loop holdouts (VERDICT r5:
cfg4 preempt 279 ms of per-preemptor Python): the dense views
(ops/preemptview.py, ops/victimview.py) vectorize the per-node math, but the
walk itself — candidate window, victim tiers, eviction cut, gang
commit/discard — still runs O(preemptors x visited-nodes x victims) on the
host. This module moves the WHOLE action onto the device as ONE packed
dispatch per invocation (paper §L5/L6 preempt.go/reclaim.go semantics,
SURVEY §7 "device proposes, host commits"):

- the kernel is a fused while-loop state machine that replays the serial
  control flow EXACTLY: the per-queue job priority heaps (including
  heapq's sift mechanics under mutating keys — pop order under live
  drf-share/gang-ready keys is heap-structural, not argmin), the
  round-robin candidate window + fused scores, the tiered victim masks
  (gang occupancy, conformance, drf cumulative-clone shares, proportion
  deserved-floor walk — each a vectorized [N, V] twin of the session fn),
  the reverse-task-order eviction cut (a sequential fori so float
  accumulation order matches the serial Resource walk bit-for-bit), and
  statement commit/discard as an append/rewind op log whose discard
  REPLAYS inverse ops in reverse order (a snapshot restore would be
  bit-different after float sub/add round trips);
- the device returns one packed int32 array (op log + rr/stat tail): the
  host pays a single D2H fetch, then applies the committed ops in the
  exact serial order through the REAL Statement/session mutators, so
  event handlers, cache effectors, SnapshotKeeper dirty-sets, and metrics
  see exactly what the serial walk would have produced;
- the kernel is a pure function of the encoded snapshot: any failure
  (budget overflow, drf/proportion underflow under panic mode, a device
  error) applies NOTHING and the action falls back to the old path.

Parity contract: within the modeled envelope the batched actions are
bindings-and-evictions-IDENTICAL to the serial statement walk
(tests/test_evict_kernel.py fuzzes this, `VOLCANO_TPU_EVICT=0` forces the
old path as the oracle — same env-flag discipline as VOLCANO_TPU_WINDOW).
Outside the envelope `build` returns None and the old path runs:

- scalar resource dimensions (R > 2) — the Resource nil-map comparison
  asymmetries are not mirrored;
- victim fns outside {gang, conformance, drf, proportion}, weighted-
  namespace drf, job-order plugins outside {priority, gang, drf},
  non-gang job_pipelined fns, custom task-order comparators;
- preemptor/backfill tasks carrying host ports or pod (anti-)affinity,
  or a session the dense view itself cannot model.

Exactness holds under float64 (tests force jax x64); float32 bench runs
share the allocate solver's documented approximation caveat.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from volcano_tpu.api.resource import MIN_MEMORY, MIN_MILLI_CPU
from volcano_tpu.api.types import TaskStatus
from volcano_tpu.ops import kernels
from volcano_tpu.ops.solver import _bucket
from volcano_tpu.scheduler import conf as conf_mod
from volcano_tpu.scheduler.plugins import nodeorder as nodeorder_mod
from volcano_tpu.scheduler.plugins.drf import SHARE_DELTA
from volcano_tpu.utils import trace

logger = logging.getLogger(__name__)

# op log kinds (flat int32 log of row-major [kind, a, b] rows)
# OP_EVICT carries (node, slot) as separate columns: the flat
# node * V + slot encoding overflows int32 once NODES_PAD * V_WIDTH
# crosses 2^31 (cfg7 x victim-bucket extents reach ~6.6e9)
OP_EVICT = 0      # a = node, b = slot
OP_PIPELINE = 1   # a = preemptor task index, b = node
OP_COMMIT = 2     # statement commit marker (preempt only)

# packed result tail: [log_len, rr, victims_total, attempts_total,
#                      fail, underflow]
TAIL = 6

VECTORIZED_VICTIM_FNS = frozenset(
    {"gang", "conformance", "drf", "proportion"})
SUPPORTED_JOB_ORDER = ("priority", "gang", "drf")

# preempt machine modes
M_QUEUE, M_POP_JOB, M_TASK, M_STMT_END, M_UNDER, M_DONE = 0, 1, 2, 3, 4, 5


class EvictSpec(NamedTuple):
    """Static (trace-time) eviction-solve configuration — jit key fields
    only; every churny count lives in bucketed array shapes."""

    kind: str                    # "preempt" | "reclaim" | "backfill"
    job_order_keys: tuple        # enabled job-order plugins, tier order
    victim_fns: tuple            # deciding-tier victim fn names, tier order
    check_pod_count: bool
    use_nodeorder: bool
    use_binpack: bool
    use_gang_pipelined: bool
    use_prop_overused: bool = False
    use_prop_queue_order: bool = False
    log_rows: int = 0            # op-log capacity, off the bucket ladder


class _Unsupported(Exception):
    pass


# ---------------------------------------------------------------------------
# device helpers (shared by both kernels)
# ---------------------------------------------------------------------------


def _le2(l, r, eps):
    """Resource.less_equal for scalar-free [..., 2] rows (per-dim epsilon,
    resource_info.go:267-301)."""
    return jnp.all((l < r) | (jnp.abs(l - r) < eps), axis=-1)


def _lt2(l, r):
    """Resource.less: strictly less on every dimension (scalar-free)."""
    return jnp.all(l < r, axis=-1)


def _share2(alloc, total):
    """drf._calculate_share / proportion._update_share over static [R]
    denominators: max over dims, share(l, 0) = 1 when l != 0, floored at
    the 0.0 the serial accumulator starts from."""
    s = jnp.where(total > 0, alloc / jnp.where(total > 0, total, 1.0),
                  jnp.where(alloc == 0, 0.0, 1.0))
    return jnp.maximum(jnp.max(s, axis=-1), 0.0)


def _window(elig, rr, num_to_find, real, real_n):
    """The serial round-robin sampling window (predicate_nodes /
    preemptview._window_sel): (selected mask, circular positions from rr,
    processed count). Candidate ORDER within the window is circular-from-rr
    order — exactly the stable tie order of the serial descending sort.

    ``real``/``real_n`` mask out the mesh pad (ops/shard.py appends node
    slots to reach the device multiple): padded slots never select, never
    count as processed, and the circular order wraps over the REAL axis
    exactly as the serial helper's modulo does — with no padding the
    arithmetic below is the pre-mesh roll+cumsum bit-for-bit (circ is a
    permutation and the scatter ranks eligible slots in circular order)."""
    n = elig.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    rn = jnp.maximum(real_n, 1)
    # padded slots park past every real circular position
    circ = jnp.where(real, (idx - rr) % rn, jnp.int32(n))
    er = elig & real
    cnt = jnp.zeros(n, jnp.int32).at[jnp.minimum(circ, n - 1)].add(
        jnp.where(real, er, False).astype(jnp.int32))
    c = jnp.cumsum(cnt)                       # eligible count per circ pos
    found_total = c[n - 1]
    sel = er & (c[jnp.minimum(circ, n - 1)] <= num_to_find)
    kth = jnp.argmax(c >= num_to_find).astype(jnp.int32)
    processed = jnp.where(found_total >= num_to_find, kth + 1, rn)
    return sel, circ, processed


def _heap_pop(row, size, less):
    """Exact heapq.heappop over a row of ids (python heapq sift mechanics;
    compares run under the CURRENT dynamic keys, which is why pop order is
    heap-structural rather than a clean argmin once keys mutate in-heap).
    Returns (item, row, size-1)."""
    root = row[0]
    last = row[size - 1]
    nsize = size - 1

    def sift(row):
        # _siftup(0) with newitem = last
        def down_cond(c):
            pos, _ = c
            return (2 * pos + 1) < nsize

        def down_body(c):
            pos, row = c
            child = 2 * pos + 1
            right = child + 1
            use_r = (right < nsize) & ~less(row[child],
                                            row[jnp.minimum(right, nsize - 1)])
            child = jnp.where(use_r, right, child)
            row = row.at[pos].set(row[child])
            return child, row

        pos, row = lax.while_loop(down_cond, down_body, (jnp.int32(0), row))
        row = row.at[pos].set(last)

        # _siftdown(0, pos) with newitem = last
        def up_cond(c):
            pos, row = c
            parent = (pos - 1) // 2
            return (pos > 0) & less(last, row[jnp.maximum(parent, 0)])

        def up_body(c):
            pos, row = c
            parent = (pos - 1) // 2
            row = row.at[pos].set(row[parent])
            return parent, row

        pos, row = lax.while_loop(up_cond, up_body, (pos, row))
        return row.at[pos].set(last)

    row = lax.cond(nsize > 0, sift, lambda r: r, row)
    return root, row, nsize


def _heap_push(row, size, item, less):
    """Exact heapq.heappush (append + _siftdown(0, size))."""
    row = row.at[size].set(item)

    def cond(c):
        pos, row = c
        parent = (pos - 1) // 2
        return (pos > 0) & less(item, row[jnp.maximum(parent, 0)])

    def body(c):
        pos, row = c
        parent = (pos - 1) // 2
        row = row.at[pos].set(row[parent])
        return parent, row

    pos, row = lax.while_loop(cond, body, (size, row))
    return row.at[pos].set(item), size + 1


def _live_next(live):
    """[T] bool -> [T] int32: for each flat index i, the smallest j >= i
    with live[j] (T when none). Candidate tasks are contiguous per job, so
    next_live < job_task_end decides "this job still has an unconsumed live
    task" and p_next[ptr] IS the next task the serial walk would pop —
    the device twin of the host rebuilding its pending task queues after an
    earlier stage consumed some candidates (session_fuse skip masks)."""
    t_total = live.shape[0]
    idx = jnp.arange(t_total, dtype=jnp.int32)
    cand = jnp.where(live, idx, jnp.int32(t_total))
    return jnp.flip(lax.cummin(jnp.flip(cand)))


def _has_live(enc, ptr_val, end_val):
    """ptr < end AND a live candidate remains at-or-after ptr (p_next is
    the identity permutation on the per-action path, where consumed
    candidates are exactly [start, ptr))."""
    t_total = enc["p_next"].shape[0]
    nxt = enc["p_next"][jnp.clip(ptr_val, 0, t_total - 1)]
    return (ptr_val < end_val) & (nxt < end_val)


def _job_less(spec: EvictSpec, enc, st):
    """3-way job_order_cmp as a traced less(a, b): enabled plugin keys in
    tier order (priority desc, gang non-ready-first, drf share asc), then
    the (ctime, uid) rank — total, so heap seq never decides."""
    prio = enc["job_prio"]
    min_av = enc["job_min_av"]
    tie = enc["job_tie"]
    ready = st["ready"]
    job_alloc = st["job_alloc"]

    def less(a, b):
        decided = jnp.bool_(False)
        res = jnp.bool_(False)
        for key in spec.job_order_keys:
            if key == "priority":
                neq = prio[a] != prio[b]
                lt = prio[a] > prio[b]
            elif key == "gang":
                ra = ready[a] >= min_av[a]
                rb = ready[b] >= min_av[b]
                neq = ra != rb
                lt = (~ra) & rb
            elif key == "drf":
                sa = _share2(job_alloc[a], enc["drf_total"])
                sb = _share2(job_alloc[b], enc["drf_total"])
                neq = sa != sb
                lt = sa < sb
            else:  # pragma: no cover - gated at build
                continue
            res = jnp.where(~decided & neq, lt, res)
            decided = decided | neq
        return jnp.where(decided, res, tie[a] < tie[b])

    return less


def _queue_less(spec: EvictSpec, enc, st):
    """queue_order_cmp: proportion share (vs deserved), then (ctime, uid)."""
    tie = enc["queue_tie"]
    queue_alloc = st["queue_alloc"]

    def less(a, b):
        if spec.use_prop_queue_order:
            sa = _share2(queue_alloc[a], enc["queue_deserved"][a])
            sb = _share2(queue_alloc[b], enc["queue_deserved"][b])
            return jnp.where(sa != sb, sa < sb, tie[a] < tie[b])
        return tie[a] < tie[b]

    return less


# ---------------------------------------------------------------------------
# victim tier masks ([N, V] twins of the session victim fns)
# ---------------------------------------------------------------------------


def _drf_verdict(enc, st, claimees, claimer_job, claimer_req):
    """drf.preemptable_fn (job branch; weighted namespaces are gated off at
    build): per-node cumulative-clone walk in claimee order, sequential fori
    so the float subtraction fold matches the serial clone bit-for-bit.
    Returns ([N, V] verdicts, [N] per-node sub-underflow — the Resource.sub
    assert the serial walk would raise on in panic mode)."""
    total = enc["drf_total"]
    eps = enc["eps"]
    ls = _share2(st["job_alloc"][claimer_job] + claimer_req, total)
    jv = enc["vic_job"]
    v_width = jv.shape[1]
    jobcur0 = st["job_alloc"][jv]                       # [N, V, R]

    def body(v, carry):
        jobcur, rs, under = carry
        a = claimees[:, v]                              # [N]
        req = enc["vic_req"][:, v]                      # [N, R]
        cur = jobcur[:, v]
        under = under | (a & ~_le2(req, cur, eps))
        rs = rs.at[:, v].set(_share2(cur - req, total))
        upd = (a[:, None] & enc["vic_samejob"][:, v, :])[..., None]
        jobcur = jnp.where(upd, jobcur - req[:, None, :], jobcur)
        return jobcur, rs, under

    n = jv.shape[0]
    _, rs, under = lax.fori_loop(
        0, v_width, body,
        (jobcur0, jnp.zeros(jv.shape, jobcur0.dtype), jnp.zeros(n, bool)))
    verdict = (ls < rs) | (jnp.abs(ls - rs) <= SHARE_DELTA)
    return claimees & verdict, under


def _prop_verdict(enc, st, claimees):
    """proportion.reclaimable_fn: per-node deserved-floor walk in claimee
    order with the conditional skip (a claimee whose request exceeds the
    remaining queue clone does NOT consume it)."""
    eps = enc["eps"]
    qv = enc["vic_queue"]
    v_width = qv.shape[1]
    qcur0 = st["queue_alloc"][qv]                       # [N, V, R]
    des = enc["queue_deserved"][qv]

    def body(v, carry):
        qcur, out, under = carry
        a = claimees[:, v]
        req = enc["vic_req"][:, v]
        cur = qcur[:, v]
        do = a & ~_lt2(cur, req)          # allocated.less(resreq) -> skip
        under = under | (do & ~_le2(req, cur, eps))
        out = out.at[:, v].set(do & _le2(des[:, v], cur - req, eps))
        upd = (do[:, None] & enc["vic_samequeue"][:, v, :])[..., None]
        qcur = jnp.where(upd, qcur - req[:, None, :], qcur)
        return qcur, out, under

    n = qv.shape[0]
    _, out, under = lax.fori_loop(
        0, v_width, body,
        (qcur0, jnp.zeros(qv.shape, bool), jnp.zeros(n, bool)))
    return out, under


def _gang_verdict(enc, st, claimees):
    """gang.go:82-86: per-job occupancy budget decremented per NOMINATED
    victim within one call — at most (ready - minAvailable) victims per
    gang per node row; minAvailable == 1 gangs are unbudgeted. Walked in
    claimee order like the serial fn (victimview._gang_mask twin). Reads
    the per-slot views (_slot_views), never the job tables: an [N, V]
    gather per walk iteration is the walk's dearest op on the TPU."""
    jv = enc["vic_job"]
    v_width = jv.shape[1]
    min_av = enc["vic_min_av"]                           # [N, V]
    budget0 = jnp.maximum(st["vic_ready"] - min_av, 0)

    def body(v, carry):
        used, out = carry
        a = claimees[:, v]
        allow = (min_av[:, v] == 1) | (used[:, v] < budget0[:, v])
        nominate = a & allow
        out = out.at[:, v].set(nominate)
        upd = nominate[:, None] & enc["vic_samejob"][:, v, :]
        used = jnp.where(upd, used + 1, used)
        return used, out

    _, out = lax.fori_loop(
        0, v_width, body,
        (jnp.zeros(jv.shape, jnp.int32), jnp.zeros(jv.shape, bool)))
    return out


def _victim_masks(spec: EvictSpec, enc, st, claimees, claimer_job,
                  claimer_req):
    """Deciding-tier intersection over the [N, V] claimee mask — each fn
    evaluated over the FULL claimee list exactly like session._victims.
    Returns (victims [N, V], per-node underflow [N])."""
    m = claimees
    n = enc["vic_job"].shape[0]
    under = jnp.zeros(n, bool)
    for name in spec.victim_fns:
        if name == "gang":
            m = m & _gang_verdict(enc, st, claimees)
        elif name == "conformance":
            m = m & enc["vic_conf"]
        elif name == "drf":
            dm, u = _drf_verdict(enc, st, claimees, claimer_job, claimer_req)
            m = m & dm
            under = under | u
        elif name == "proportion":
            pm, u = _prop_verdict(enc, st, claimees)
            m = m & pm
            under = under | u
    return m, under


# ---------------------------------------------------------------------------
# state mutators (session-event twins; discard reverse-replays the log)
# ---------------------------------------------------------------------------


def _log0(spec: EvictSpec) -> dict:
    """The empty op log, built on the device: one flat int32 buffer of
    ``log_rows`` row-major [kind, a, b] rows, the packed result's own
    layout. Not a [rows, 3] array: on the TPU its minor dim of 3 pads to
    128 lanes, and the compiler relaid such a log out at every machine
    step."""
    return dict(log=jnp.zeros(3 * spec.log_rows, jnp.int32),
                log_len=jnp.int32(0))


def _log_append(st, kind, a, b, active):
    rows = st["log"].shape[0] // 3
    at = 3 * jnp.minimum(st["log_len"], rows - 1)
    row = jnp.stack([jnp.int32(kind), a.astype(jnp.int32),
                     b.astype(jnp.int32)])
    old = lax.dynamic_slice(st["log"], (at,), (3,))
    st = dict(st)
    st["log"] = lax.dynamic_update_slice(
        st["log"], jnp.where(active, row, old), (at,))
    st["log_len"] = st["log_len"] + active.astype(jnp.int32)
    st["fail"] = st["fail"] | (st["log_len"] >= rows)
    return st


def _apply_evict_slot(enc, st, node, slot, active):
    """Evict victim (node, slot): the session-state effects of
    Statement.evict / ssn.evict (RUNNING -> RELEASING keeps node used/cnt;
    ready drops; drf/proportion deallocate handlers subtract). Predicated
    on `active`."""
    jv = enc["vic_job"][node, slot]
    qv = enc["vic_queue"][node, slot]
    req = enc["vic_req"][node, slot]
    ai = active.astype(jnp.int32)
    dreq = jnp.where(active, req, jnp.zeros_like(req))
    st = dict(st)
    st["alive"] = st["alive"].at[node, slot].set(
        jnp.where(active, False, st["alive"][node, slot]))
    st["ready"] = st["ready"].at[jv].add(-ai)
    st["job_alloc"] = st["job_alloc"].at[jv].add(-dreq)
    st["queue_alloc"] = st["queue_alloc"].at[qv].add(-dreq)
    return _log_append(st, OP_EVICT, node, slot, active)


def _slot_views(enc, st):
    """Machine entry, outside every loop: the per-slot views of the job
    tables the gang verdict reads. ``vic_min_av`` is static; ``vic_ready``
    is ``ready[vic_job]`` for every slot (pad slots included) and moves
    with ``ready``: once per cut (_cut_ready) and once per undone eviction
    (_discard). ``vic_job_t`` is vic_job with the node axis minor, the
    layout the TPU gives [N, V] state: transposed here, once, rather than
    relaid out inside every cut."""
    jv = enc["vic_job"]
    return (dict(enc, vic_min_av=enc["job_min_av"][jv], vic_job_t=jv.T),
            dict(st, vic_ready=st["ready"][jv]))


def _cut_ready(enc, st, node, alive_row):
    """vic_ready after a cut at ``node``: each slot the cut evicted (alive
    in ``alive_row`` before it, not after) takes one ready task from every
    slot of its job, cluster-wide — one [V, V, N] compare-and-reduce."""
    evicted = alive_row & ~st["alive"][node]
    hits = (enc["vic_job_t"][None] == enc["vic_job"][node][:, None, None]) \
        & evicted[:, None, None]
    return dict(st, vic_ready=st["vic_ready"]
                - jnp.sum(hits, axis=0, dtype=jnp.int32).T)


def _apply_pipeline(enc, st, t, node):
    """Pipeline preemptor t onto node: PENDING -> PIPELINED (node add_task
    moves used/cnt; allocate handlers add to drf/proportion shares)."""
    req = enc["p_req"][t]
    j = enc["p_job"][t]
    q = enc["job_queue"][j]
    st = dict(st)
    st["used"] = st["used"].at[node].add(req)
    st["cnt"] = st["cnt"].at[node].add(1)
    st["wait"] = st["wait"].at[j].add(1)
    st["job_alloc"] = st["job_alloc"].at[j].add(req)
    st["queue_alloc"] = st["queue_alloc"].at[q].add(req)
    # consumed-candidate mark: the fused chain hands this to the next
    # stage as its skip mask (a pipelined task is no longer PENDING)
    st["p_done"] = st["p_done"].at[t].set(True)
    return _log_append(st, OP_PIPELINE, t, node, jnp.bool_(True))


def _discard(enc, st, stmt_start):
    """Statement.discard: undo the open segment's ops in REVERSE order by
    applying inverse float ops (not a snapshot restore — the serial discard
    re-adds what it subtracted, and (x - r) + r need not equal a saved x)."""
    v_width = enc["vic_job"].shape[1]
    n = enc["node_used"].shape[0]

    def cond(st):
        return st["log_len"] > stmt_start

    def body(st):
        i = st["log_len"] - 1
        kind, a, b = lax.dynamic_slice(st["log"], (3 * i,), (3,))
        is_e = kind == OP_EVICT
        is_p = kind == OP_PIPELINE
        # evict inverse (un-evict: alive back, ready/job/queue re-add)
        node_e = jnp.clip(a, 0, n - 1)
        slot = jnp.clip(b, 0, v_width - 1)
        jv = enc["vic_job"][node_e, slot]
        qv = enc["vic_queue"][node_e, slot]
        vreq = jnp.where(is_e, enc["vic_req"][node_e, slot], 0.0)
        # pipeline inverse (un-pipeline)
        t = jnp.clip(a, 0, enc["p_req"].shape[0] - 1)
        node_p = jnp.clip(b, 0, n - 1)
        pj = enc["p_job"][t]
        pq = enc["job_queue"][pj]
        preq = jnp.where(is_p, enc["p_req"][t], 0.0)
        st = dict(st)
        st["alive"] = st["alive"].at[node_e, slot].set(
            jnp.where(is_e, True, st["alive"][node_e, slot]))
        st["ready"] = st["ready"].at[jv].add(is_e.astype(jnp.int32))
        st["vic_ready"] = st["vic_ready"] + (
            is_e & (enc["vic_job"] == jv)).astype(jnp.int32)
        st["job_alloc"] = st["job_alloc"].at[jv].add(vreq)
        st["queue_alloc"] = st["queue_alloc"].at[qv].add(vreq)
        st["used"] = st["used"].at[node_p].add(-preq)
        st["cnt"] = st["cnt"].at[node_p].add(-is_p.astype(jnp.int32))
        st["wait"] = st["wait"].at[pj].add(-is_p.astype(jnp.int32))
        st["job_alloc"] = st["job_alloc"].at[pj].add(-preq)
        st["queue_alloc"] = st["queue_alloc"].at[pq].add(-preq)
        st["p_done"] = st["p_done"].at[t].set(
            jnp.where(is_p, False, st["p_done"][t]))
        st["log_len"] = i
        return st

    return lax.while_loop(cond, body, st)


# ---------------------------------------------------------------------------
# the per-preemptor placement walk (shared by both preempt phases)
# ---------------------------------------------------------------------------


def _cut_preempt(enc, st, t, node, vmask):
    """The eviction cut at `node`: victims in reversed-task-order (the
    static per-node cut permutation restricted to the selected set),
    evicted one by one until the preemptor's init request is covered by
    the fast epsilon accumulate (preempt.py:199-229)."""
    need = enc["p_init"][t]
    eps = enc["eps"]
    v_width = vmask.shape[0]
    perm = enc["vic_cut_perm"][node]

    def body(p, carry):
        st, got, covered = carry
        slot = jnp.maximum(perm[p], 0)
        selp = (perm[p] >= 0) & vmask[slot] & ~covered
        st = _apply_evict_slot(enc, st, node, slot, selp)
        got = got + jnp.where(selp, enc["vic_req"][node, slot],
                              jnp.zeros_like(need))
        now = selp & jnp.all((need < got) | (jnp.abs(need - got) < eps))
        return st, got, covered | now

    alive_row = st["alive"][node]
    st, _, covered = lax.fori_loop(
        0, v_width, body, (st, jnp.zeros_like(need), jnp.bool_(False)))
    return _cut_ready(enc, st, node, alive_row), covered


def _preempt_walk(spec: EvictSpec, enc, st, t, j, intra):
    """_preempt (preempt.py:153-253) for one preemptor task: round-robin
    window + fused-score candidate order, then the forward node walk —
    every visited node counts its victims into the metric total, the first
    validate-passing node takes the cut (its evictions persist even
    uncovered, exactly like the serial walk), success pipelines. Returns
    (host, st)."""
    n = enc["node_used"].shape[0]
    sig = enc["p_sig"][t]
    mask = enc["sig_mask"][sig]
    if spec.check_pod_count:
        elig = mask & ((st["cnt"] < enc["node_max"]) | ~enc["p_has_pod"][t])
    else:
        elig = mask
    rr0 = st["rr"]
    sel, circ, processed = _window(elig, rr0, enc["num_to_find"],
                                   enc["node_real"], enc["real_n"])
    st = dict(st, rr=(rr0 + processed) % jnp.maximum(enc["real_n"], 1))
    score = kernels.fused_scores(
        spec, enc, st["used"], enc["p_req"][t],
        enc["p_nz_cpu"][t], enc["p_nz_mem"][t], sig)
    qj = enc["job_queue"][j]
    filt = jnp.where(intra, enc["vic_job"] == j,
                     (enc["vic_queue"] == qj) & (enc["vic_job"] != j))
    v_total = enc["vic_job"].shape[0] * enc["vic_job"].shape[1]

    def cond(c):
        return ~c["done"] & ~c["st"]["fail"]

    def body(c):
        st = c["st"]
        claim = st["alive"] & enc["vic_valid"] & filt
        vm, under = _victim_masks(spec, enc, st, claim, j, enc["p_req"][t])
        vcnt = jnp.sum(vm.astype(jnp.int32), axis=1)
        vsum = jnp.sum(jnp.where(vm[..., None], enc["vic_req"], 0.0), axis=1)
        validate = (vcnt > 0) & ~_lt2(vsum, enc["p_init"][t])
        after = c["first"] | (score < c["cs"]) \
            | ((score == c["cs"]) & (circ > c["cc"]))
        pa = sel & validate & after
        any_p = jnp.any(pa)
        best = jnp.max(jnp.where(pa, score, -jnp.inf))
        cand = pa & (score == best)
        chosen = jnp.argmin(jnp.where(cand, circ, jnp.int32(n))).astype(
            jnp.int32)
        # the serial walk visits window nodes in (score desc, circ) order up
        # to the chosen node (all remaining when none qualifies), counting
        # each visited node's victims into the metric total under the state
        # it was visited in — which is exactly this iteration's state
        vis_end = (score > score[chosen]) \
            | ((score == score[chosen]) & (circ <= circ[chosen]))
        visited = sel & after & jnp.where(any_p, vis_end, True)
        st = dict(st, victims=(st["victims"] + jnp.sum(
            jnp.where(visited, vcnt, 0))).astype(jnp.int32))
        st["underflow"] = st["underflow"] | jnp.any(visited & under)
        st["iters"] = st["iters"] + 1
        st["fail"] = st["fail"] | (st["iters"] > v_total + 2)

        def try_node(st):
            st = dict(st, attempts=st["attempts"] + 1)
            st, covered = _cut_preempt(enc, st, t, chosen, vm[chosen])

            def ok(st):
                return _apply_pipeline(enc, st, t, chosen)

            st = lax.cond(covered, ok, lambda s: s, st)
            return st, covered

        def give_up(st):
            return st, jnp.bool_(False)

        st, covered = lax.cond(any_p, try_node, give_up, st)
        done = ~any_p | covered
        host = jnp.where(covered, chosen, jnp.int32(-1))
        return dict(st=st, done=done, host=jnp.where(done, host, c["host"]),
                    first=jnp.bool_(False),
                    cs=jnp.where(any_p, score[chosen], c["cs"]),
                    cc=jnp.where(any_p, circ[chosen], c["cc"]))

    st = dict(st, iters=jnp.int32(0))
    out = lax.while_loop(cond, body, dict(
        st=st, done=jnp.bool_(False), host=jnp.int32(-1),
        first=jnp.bool_(True), cs=jnp.asarray(0.0, score.dtype),
        cc=jnp.int32(-1)))
    st = dict(out["st"])
    st.pop("iters")
    return out["host"], st


# ---------------------------------------------------------------------------
# preempt kernel: the flat action state machine
# ---------------------------------------------------------------------------


def preempt_state0(spec: EvictSpec, enc: dict) -> dict:
    """Initial preempt machine state from the encoded action arrays. The
    session-fused driver overrides the dynamic slices (used/cnt/ready/
    alloc/heaps/p_done) with carry-bridged values; the per-action entry
    uses the host-encoded initials as-is."""
    return dict(
        used=enc["node_used"], cnt=enc["node_cnt"],
        alive=enc["vic_alive0"],
        ready=enc["job_ready0"], wait=enc["job_wait0"],
        job_alloc=enc["job_alloc0"], queue_alloc=enc["queue_alloc0"],
        ptr=enc["job_task_start"],
        heap=enc["heap0"], hsize=enc["hsize0"],
        **_log0(spec),
        rr=enc["rr0"].astype(jnp.int32),
        p_done=jnp.zeros(enc["p_req"].shape[0], bool),
        mode=jnp.int32(M_QUEUE), qi=jnp.int32(0), cur_job=jnp.int32(0),
        phase2=jnp.bool_(False), assigned=jnp.bool_(False),
        stmt_start=jnp.int32(0), u2=jnp.int32(0),
        victims=jnp.int32(0), attempts=jnp.int32(0),
        fail=jnp.bool_(False), underflow=jnp.bool_(False),
        steps=jnp.int32(0),
    )


def evict_tail(st: dict):
    """Pack the machine's final state into the single-fetch int32 result:
    flat op log + [log_len, rr, victims, attempts, fail, underflow]."""
    tail = jnp.stack([
        st["log_len"], st["rr"], st["victims"], st["attempts"],
        st["fail"].astype(jnp.int32), st["underflow"].astype(jnp.int32)])
    return jnp.concatenate([st["log"], tail])


def preempt_machine(spec: EvictSpec, enc: dict, st: dict) -> dict:
    """The whole preempt action (preempt.py execute) as one fused program:
    per-queue phase 1 (job heap pops, per-job statements, gang-pipelined
    commit/discard) then phase 2 (intra-job task-vs-task, per-task commit),
    interleaved per queue exactly as the host loop runs them."""
    enc, st = _slot_views(enc, st)
    qp = enc["queue_real"].shape[0]
    ju = enc["under_jobs"].shape[0]
    t_total = enc["p_req"].shape[0]
    j_total = enc["job_prio"].shape[0]
    step_budget = jnp.int32(8 * (t_total + j_total + qp + ju) + 64)

    def pipelined(st, j):
        if not spec.use_gang_pipelined:
            return jnp.bool_(True)
        return (st["wait"][j] + st["ready"][j]) >= enc["job_min_av"][j]

    def control_step(st):
        mode = st["mode"]
        st = dict(st)

        def m_queue(st):
            st = dict(st)
            past = st["qi"] >= qp
            real = enc["queue_real"][jnp.minimum(st["qi"], qp - 1)]
            st["mode"] = jnp.where(
                past, jnp.int32(M_DONE),
                jnp.where(real, jnp.int32(M_POP_JOB), st["mode"]))
            st["qi"] = jnp.where(past | real, st["qi"], st["qi"] + 1)
            return st

        def m_pop_job(st):
            st = dict(st)
            qi = st["qi"]
            empty = st["hsize"][qi] == 0

            def pop(st):
                st = dict(st)
                less = _job_less(spec, enc, st)
                j, row, nsz = _heap_pop(st["heap"][qi], st["hsize"][qi], less)
                st["heap"] = st["heap"].at[qi].set(row)
                st["hsize"] = st["hsize"].at[qi].set(nsz)
                st["cur_job"] = j
                st["stmt_start"] = st["log_len"]
                st["assigned"] = jnp.bool_(False)
                st["phase2"] = jnp.bool_(False)
                st["mode"] = jnp.int32(M_TASK)
                return st

            def to_phase2(st):
                return dict(st, u2=jnp.int32(0), mode=jnp.int32(M_UNDER))

            return lax.cond(empty, to_phase2, pop, st)

        def m_stmt_end(st):
            st = dict(st)
            j = st["cur_job"]
            pl = pipelined(st, j)

            def commit(st):
                st = _log_append(st, OP_COMMIT, jnp.int32(0), jnp.int32(0),
                                 st["log_len"] > st["stmt_start"])

                def repush(st):
                    st = dict(st)
                    qi = st["qi"]
                    less = _job_less(spec, enc, st)
                    row, nsz = _heap_push(
                        st["heap"][qi], st["hsize"][qi], j, less)
                    st["heap"] = st["heap"].at[qi].set(row)
                    st["hsize"] = st["hsize"].at[qi].set(nsz)
                    return st

                return lax.cond(st["assigned"], repush, lambda s: s, st)

            def roll(st):
                return _discard(enc, st, st["stmt_start"])

            st = lax.cond(pl, commit, roll, st)
            return dict(st, mode=jnp.int32(M_POP_JOB))

        def m_under(st):
            st = dict(st)
            past = st["u2"] >= ju
            j = enc["under_jobs"][jnp.minimum(st["u2"], ju - 1)]
            has = ~past & (j >= 0) \
                & _has_live(enc, st["ptr"][jnp.maximum(j, 0)],
                            enc["job_task_end"][jnp.maximum(j, 0)])
            st["cur_job"] = jnp.where(has, j, st["cur_job"])
            st["phase2"] = jnp.bool_(True)
            st["mode"] = jnp.where(
                past, jnp.int32(M_QUEUE),
                jnp.where(has, jnp.int32(M_TASK), st["mode"]))
            st["qi"] = jnp.where(past, st["qi"] + 1, st["qi"])
            st["u2"] = jnp.where(past | has, st["u2"], st["u2"] + 1)
            return st

        return lax.switch(
            jnp.clip(mode, 0, 4),
            [m_queue, m_pop_job, lambda s: s, m_stmt_end, m_under], st)

    def task_step(st):
        st = dict(st)
        j = st["cur_job"]
        have = _has_live(enc, st["ptr"][j], enc["job_task_end"][j])
        phase2 = st["phase2"]

        def no_task(st):
            st = dict(st)
            st["mode"] = jnp.where(phase2, jnp.int32(M_UNDER),
                                   jnp.int32(M_STMT_END))
            st["u2"] = jnp.where(phase2, st["u2"] + 1, st["u2"])
            return st

        def do_task(st):
            st = dict(st)
            t = enc["p_next"][jnp.clip(st["ptr"][j], 0, t_total - 1)]
            st["ptr"] = st["ptr"].at[j].set(t + 1)
            st["stmt_start"] = jnp.where(phase2, st["log_len"],
                                         st["stmt_start"])
            host, st = _preempt_walk(spec, enc, st, t, j, phase2)
            st = dict(st)
            # phase 1: assigned |= placed; break to STMT_END when the gang
            # pipelines. phase 2: per-task statement commits
            # unconditionally; a miss moves to the next under-request job.
            st["assigned"] = st["assigned"] | (~phase2 & (host >= 0))
            pl = pipelined(st, j)
            st = _log_append(st, OP_COMMIT, jnp.int32(0), jnp.int32(0),
                             phase2 & (st["log_len"] > st["stmt_start"]))
            miss2 = phase2 & (host < 0)
            st["u2"] = jnp.where(miss2, st["u2"] + 1, st["u2"])
            st["mode"] = jnp.where(
                miss2, jnp.int32(M_UNDER),
                jnp.where(~phase2 & pl, jnp.int32(M_STMT_END),
                          jnp.int32(M_TASK)))
            return st

        return lax.cond(have, do_task, no_task, st)

    def body(st):
        st = dict(st, steps=st["steps"] + 1)
        st["fail"] = st["fail"] | (st["steps"] > step_budget)
        return lax.cond(st["mode"] == M_TASK, task_step, control_step, st)

    def cond(st):
        return (st["mode"] != M_DONE) & ~st["fail"]

    return lax.while_loop(cond, body, st)


@functools.partial(jax.jit, static_argnames=("spec",))
def solve_preempt(spec: EvictSpec, enc: dict):
    """Per-action packed preempt entry: host-encoded initial state, packed
    single-fetch result (evict_tail)."""
    return evict_tail(preempt_machine(spec, enc, preempt_state0(spec, enc)))


# ---------------------------------------------------------------------------
# reclaim kernel
# ---------------------------------------------------------------------------


def _cut_reclaim(enc, st, t, node, vmask):
    """Reclaim's eviction cut: victims in CLAIMEE order, evicted until the
    reclaimer's request is covered by the epsilon less_equal
    (reclaim.go:123-133)."""
    need = enc["p_init"][t]
    eps = enc["eps"]
    v_width = vmask.shape[0]

    def body(v, carry):
        st, got, covered = carry
        selp = vmask[v] & ~covered
        st = _apply_evict_slot(enc, st, node, v, selp)
        got = got + jnp.where(selp, enc["vic_req"][node, v],
                              jnp.zeros_like(need))
        now = selp & _le2(need, got, eps)
        return st, got, covered | now

    alive_row = st["alive"][node]
    st, _, covered = lax.fori_loop(
        0, v_width, body, (st, jnp.zeros_like(need), jnp.bool_(False)))
    return _cut_ready(enc, st, node, alive_row), covered


def _reclaim_walk(spec: EvictSpec, enc, st, t, j):
    """One reclaimer task over feasible nodes in name order
    (reclaim.py:84-143): the first node whose cross-queue victims validate
    takes the cut; evictions commit immediately (no statement), an
    uncovered cut persists and the walk continues strictly forward."""
    n = enc["node_used"].shape[0]
    sig = enc["p_sig"][t]
    mask = enc["sig_mask"][sig]
    if spec.check_pod_count:
        elig = mask & ((st["cnt"] < enc["node_max"]) | ~enc["p_has_pod"][t])
    else:
        elig = mask
    qj = enc["job_queue"][j]
    filt = enc["vic_queue"] != qj
    idx = jnp.arange(n, dtype=jnp.int32)
    v_total = enc["vic_job"].shape[0] * enc["vic_job"].shape[1]

    def cond(c):
        return ~c["done"] & ~c["st"]["fail"]

    def body(c):
        st = c["st"]
        claim = st["alive"] & enc["vic_valid"] & filt
        vm, under = _victim_masks(spec, enc, st, claim, j, enc["p_req"][t])
        vcnt = jnp.sum(vm.astype(jnp.int32), axis=1)
        vsum = jnp.sum(jnp.where(vm[..., None], enc["vic_req"], 0.0), axis=1)
        validate = (vcnt > 0) & ~_lt2(vsum, enc["p_init"][t])
        pa = elig & validate & (idx > c["cursor"])
        any_p = jnp.any(pa)
        chosen = jnp.argmax(pa).astype(jnp.int32)
        visited = elig & (idx > c["cursor"]) \
            & jnp.where(any_p, idx <= chosen, True)
        st = dict(st)
        st["underflow"] = st["underflow"] | jnp.any(visited & under)
        st["iters"] = st["iters"] + 1
        st["fail"] = st["fail"] | (st["iters"] > v_total + 2)

        def try_node(st):
            st, covered = _cut_reclaim(enc, st, t, chosen, vm[chosen])

            def ok(st):
                return _apply_pipeline(enc, st, t, chosen)

            return lax.cond(covered, ok, lambda s: s, st), covered

        st, covered = lax.cond(
            any_p, try_node, lambda s: (s, jnp.bool_(False)), st)
        done = ~any_p | covered
        return dict(st=st, done=done,
                    assigned=c["assigned"] | covered,
                    cursor=jnp.where(any_p, chosen, c["cursor"]))

    st = dict(st, iters=jnp.int32(0))
    out = lax.while_loop(cond, body, dict(
        st=st, done=jnp.bool_(False), assigned=jnp.bool_(False),
        cursor=jnp.int32(-1)))
    st = dict(out["st"])
    st.pop("iters")
    return out["assigned"], st


def reclaim_state0(spec: EvictSpec, enc: dict) -> dict:
    """Initial reclaim machine state (fused driver overrides the dynamic
    slices, exactly like preempt_state0)."""
    return dict(
        used=enc["node_used"], cnt=enc["node_cnt"],
        alive=enc["vic_alive0"],
        ready=enc["job_ready0"], wait=enc["job_wait0"],
        job_alloc=enc["job_alloc0"], queue_alloc=enc["queue_alloc0"],
        ptr=enc["job_task_start"],
        heap=enc["heap0"], hsize=enc["hsize0"],
        qheap=enc["qheap0"], qhsize=enc["qhsize0"],
        **_log0(spec),
        rr=enc["rr0"].astype(jnp.int32),
        p_done=jnp.zeros(enc["p_req"].shape[0], bool),
        victims=jnp.int32(0), attempts=jnp.int32(0),
        fail=jnp.bool_(False), underflow=jnp.bool_(False),
        steps=jnp.int32(0),
    )


def reclaim_machine(spec: EvictSpec, enc: dict, st: dict) -> dict:
    """The whole reclaim action (reclaim.py execute) as one fused program:
    queue heap rotation (overused queues drop out un-re-pushed), one job
    pop and one task per queue visit, direct evict/pipeline ops."""
    enc, st = _slot_views(enc, st)
    j_total = enc["job_prio"].shape[0]
    q_total = enc["queue_alloc0"].shape[0]
    t_total = enc["p_req"].shape[0]
    step_budget = jnp.int32(4 * (t_total + j_total + q_total) + 64)
    eps = enc["eps"]

    def cond(st):
        return (st["qhsize"] > 0) & ~st["fail"]

    def body(st):
        st = dict(st, steps=st["steps"] + 1)
        st["fail"] = st["fail"] | (st["steps"] > step_budget)
        qless = _queue_less(spec, enc, st)
        q, qrow, qsz = _heap_pop(st["qheap"], st["qhsize"], qless)
        st["qheap"] = qrow
        st["qhsize"] = qsz
        if spec.use_prop_overused:
            over = enc["queue_has_attr"][q] & ~_le2(
                st["queue_alloc"][q], enc["queue_deserved"][q], eps)
        else:
            over = jnp.bool_(False)

        def visit(st):
            st = dict(st)
            empty = st["hsize"][q] == 0

            def with_job(st):
                st = dict(st)
                less = _job_less(spec, enc, st)
                j, row, nsz = _heap_pop(st["heap"][q], st["hsize"][q], less)
                st["heap"] = st["heap"].at[q].set(row)
                st["hsize"] = st["hsize"].at[q].set(nsz)
                has_task = _has_live(enc, st["ptr"][j],
                                     enc["job_task_end"][j])

                def with_task(st):
                    st = dict(st)
                    t = enc["p_next"][jnp.clip(st["ptr"][j], 0,
                                               t_total - 1)]
                    st["ptr"] = st["ptr"].at[j].set(t + 1)
                    assigned, st = _reclaim_walk(spec, enc, st, t, j)

                    def repush(st):
                        st = dict(st)
                        qless2 = _queue_less(spec, enc, st)
                        qrow2, qsz2 = _heap_push(
                            st["qheap"], st["qhsize"], q, qless2)
                        st["qheap"] = qrow2
                        st["qhsize"] = qsz2
                        return st

                    return lax.cond(assigned, repush, lambda s: s, st)

                return lax.cond(has_task, with_task, lambda s: s, st)

            return lax.cond(empty, lambda s: s, with_job, st)

        return lax.cond(over, lambda s: s, visit, st)

    return lax.while_loop(cond, body, st)


@functools.partial(jax.jit, static_argnames=("spec",))
def solve_reclaim(spec: EvictSpec, enc: dict):
    """Per-action packed reclaim entry (evict_tail result)."""
    return evict_tail(reclaim_machine(spec, enc, reclaim_state0(spec, enc)))


# ---------------------------------------------------------------------------
# backfill kernel
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("spec",))
def solve_backfill(spec: EvictSpec, enc: dict):
    """Backfill's placement decisions (backfill.py:44-78): each zero-request
    task in walk order takes the first feasible node in name order; the only
    dynamic feasibility term is the pod-count headroom the previous
    placements consumed. Returns assign [T] int32 (node or -1)."""
    t_total = enc["b_sig"].shape[0]

    def body(t, carry):
        cnt, assign = carry
        mask = enc["sig_mask"][enc["b_sig"][t]]
        if spec.check_pod_count:
            mask = mask & ((cnt < enc["node_max"]) | ~enc["b_has_pod"][t])
        node = jnp.argmax(mask)
        ok = mask[node] & enc["b_real"][t]
        assign = assign.at[t].set(
            jnp.where(ok, node.astype(jnp.int32), jnp.int32(-1)))
        cnt = cnt.at[node].add(ok.astype(jnp.int32))
        return cnt, assign

    _, assign = lax.fori_loop(
        0, t_total, body,
        (enc["node_cnt"], jnp.full((t_total,), -1, jnp.int32)))
    return assign


# ---------------------------------------------------------------------------
# packed transfer (local twin of solver._pack/_stage with evict-scoped keys)
# ---------------------------------------------------------------------------

_DEVICE_CACHE: Dict[str, tuple] = {}

# node-axis position of every evict-encode array that shards across the
# mesh (ROADMAP item 3): the tiered victim folds are [N, V] walks —
# embarrassingly parallel over nodes — so these arrays stage per-shard
# (ops/shard.py) and the machines' only cross-shard traffic is the small
# verdict-boundary reduce (victim counts, arg-extrema over nodes)
_EV_NODE_AXIS = {
    "node_used": 0, "node_alloc": 0, "node_cnt": 0, "node_max": 0,
    "node_real": 0,
    "sig_mask": 1, "affinity_score": 1,
    "vic_req": 0, "vic_job": 0, "vic_queue": 0, "vic_valid": 0,
    "vic_alive0": 0, "vic_conf": 0, "vic_cut_perm": 0,
    "vic_samejob": 0, "vic_samequeue": 0,
}

# pad fills chosen so mesh-pad slots are invisible to the machines: never
# eligible (sig_mask), never claimees (vic_valid/alive), never cut
# (vic_cut_perm), never counted by the round-robin window (node_real)
_EV_PAD_FILL = {
    "sig_mask": False, "vic_valid": False, "vic_alive0": False,
    "vic_conf": False, "node_real": False, "vic_cut_perm": -1,
    "vic_samejob": False, "vic_samequeue": False,
}


def pad_node_axis(arrays: Dict[str, np.ndarray], multiple: int
                  ) -> Dict[str, np.ndarray]:
    """Pad every node-axis array to the mesh device multiple (append-only:
    real node indices — and hence the op log's node*V+slot codes — are
    unchanged)."""
    from volcano_tpu.ops import shard as shard_mod

    out = dict(arrays)
    for name, axis in _EV_NODE_AXIS.items():
        if name in out:
            out[name] = shard_mod.pad_axis_multiple(
                out[name], axis, multiple, fill=_EV_PAD_FILL.get(name, 0))
    return out


def _pack_staged(arrays: Dict[str, np.ndarray], tag: str, mesh,
                 profile: Optional[dict] = None):
    """(layout, staged) for one evict-kernel dispatch: the packed
    replicated transfer plus — under a mesh — the node-axis arrays padded
    to the device multiple and staged as per-shard sharded buffers that
    ride beside the packed groups under their plain names (merged back by
    rounds.unpack_layout, exactly like the solver's sharded encode)."""
    if mesh is None:
        with trace.span("pack", tag=tag):
            layout, bufs = _pack(arrays, tag)
        with trace.span("h2d", tag=tag):
            return layout, _stage(bufs, profile)
    from volcano_tpu.ops import shard as shard_mod

    with trace.span("pack", tag=tag):
        d = shard_mod.device_count(mesh)
        padded = pad_node_axis(arrays, d)
        node = {k: padded[k] for k in _EV_NODE_AXIS if k in padded}
        rest = {k: v for k, v in padded.items() if k not in node}
        layout, bufs = _pack(rest, tag)
    with trace.span("h2d", tag=tag):
        staged = _stage(bufs, profile, mesh=mesh)
        staged.update(shard_mod.stage_node_arrays(
            node, _EV_NODE_AXIS, mesh, profile, tag=f"ev.{tag}."))
    return layout, staged


def _pack(arrays: Dict[str, np.ndarray], tag: str):
    """Concatenate host arrays into one flat buffer per dtype class (the
    host-device hop pays per buffer, not per byte) with a static unpack layout."""
    layout = []
    parts: Dict[str, list] = {}
    offsets: Dict[str, int] = {}
    for name in sorted(arrays):
        v = np.asarray(arrays[name])
        kind = "f" if v.dtype.kind == "f" else (
            "b" if v.dtype == np.bool_ else "i")
        key = f"ev.{tag}.{kind}"
        flat = v.ravel()
        layout.append((name, key, offsets.get(key, 0), flat.size, v.shape))
        parts.setdefault(key, []).append(flat)
        offsets[key] = offsets.get(key, 0) + flat.size
    bufs = {}
    for key, ps in parts.items():
        kind = key[-1]
        if kind == "f":
            dt = np.result_type(*[p.dtype for p in ps])
        elif kind == "b":
            dt = np.bool_
        else:
            dt = np.int32
        bufs[key] = np.concatenate(ps).astype(dt, copy=False)
    return tuple(layout), bufs


def _stage(bufs: Dict[str, np.ndarray], profile: Optional[dict] = None,
           mesh=None):
    """Host buffers -> device arrays with byte-compared reuse of
    device-resident twins (same discipline as solver._stage, including the
    mesh-identity guard: a buffer committed for one mesh shape never feeds
    a program compiled for another)."""
    from volcano_tpu.ops import shard as shard_mod

    mkey = shard_mod.mesh_key(mesh)
    sharding = shard_mod.replicated_sharding(mesh) if mesh is not None \
        else None
    staged = {}
    puts = hits = 0
    for key, buf in bufs.items():
        cached = _DEVICE_CACHE.get(key)
        if (cached is not None and cached[0].dtype == buf.dtype
                and cached[0].shape == buf.shape
                and cached[2] == mkey
                and np.array_equal(cached[0], buf)):
            staged[key] = cached[1]
            hits += 1
        else:
            dev = jax.device_put(buf) if sharding is None \
                else jax.device_put(buf, sharding)
            _DEVICE_CACHE[key] = (buf, dev, mkey)
            staged[key] = dev
            puts += 1
    if profile is not None:
        profile["h2d_puts"] = puts
        profile["h2d_cached"] = hits
    return staged


@functools.partial(jax.jit, static_argnames=("spec", "layout"))
def _solve_packed(spec: EvictSpec, layout, bufs):
    from volcano_tpu.ops import rounds as rounds_mod

    enc = rounds_mod.unpack_layout(layout, bufs)
    if spec.kind == "preempt":
        return solve_preempt.__wrapped__(spec, enc)
    if spec.kind == "reclaim":
        return solve_reclaim.__wrapped__(spec, enc)
    return solve_backfill.__wrapped__(spec, enc)


# ---------------------------------------------------------------------------
# host: capability gates + session -> dense encode
# ---------------------------------------------------------------------------


def _profile(ssn) -> dict:
    p = ssn.plugins.get("tpuscore")
    return p.profile if p is not None else {}


def _note_fallback(prof: dict, key: str, reason: str) -> None:
    """Record an honesty fallback in the session profile AND the
    process-wide fallback counter (metrics.register_fallback) — the sim
    auditor budgets these as rates, so an envelope regression fails the
    gate like a parity regression (ROADMAP item 4)."""
    from volcano_tpu.scheduler import metrics

    prof[key + "_fallback"] = reason
    metrics.register_fallback(key)


def _check_armed(ssn) -> None:
    """The mode gates: the device path is not armed at all (env oracle or
    serial conf). Cheap, so they run before anything is built."""
    if os.environ.get("VOLCANO_TPU_EVICT", "1") == "0":
        raise _Unsupported("VOLCANO_TPU_EVICT=0")
    if getattr(ssn, "batch_allocator", None) is None:
        raise _Unsupported("tpuscore off")


def _common_view(ssn, view=None):
    _check_armed(ssn)
    if view is None:
        from volcano_tpu.ops import preemptview

        view = preemptview.build(ssn)
    if view is None:
        raise _Unsupported("dense view unsupported for this session")
    if len(view.rnames) != 2:
        # the Resource nil-map comparison asymmetries (less/less_equal over
        # scalar dicts) are not mirrored on device; scalar-free sessions are
        # the modeled envelope
        raise _Unsupported("scalar resource dimensions not modeled")
    return view


def _f_dtype():
    return np.float64 if jax.config.jax_enable_x64 else np.float32


def _eligible_jobs(ssn):
    """The preempt/reclaim registration filter (preempt.py:55-63), in
    ssn.jobs iteration order."""
    from volcano_tpu.api import objects

    out = []
    for job in ssn.jobs.values():
        if job.pod_group.status.phase == objects.PodGroupPhase.PENDING:
            continue
        vr = ssn.job_valid(job)
        if vr is not None and not vr.pass_:
            continue
        if ssn.queues.get(job.queue) is None:
            continue
        out.append(job)
    return out


def _check_victim_tier(ssn, kind: str, drf) -> List[str]:
    """The deciding victim tier for ``kind``, gate-checked (raises
    _Unsupported outside the vectorized envelope)."""
    decide = _deciding_victim_tier(ssn, kind)
    if any(n not in VECTORIZED_VICTIM_FNS for n in decide):
        raise _Unsupported(f"unsupported victim plugins: {decide}")
    if "drf" in decide:
        if drf is None:
            raise _Unsupported("drf victims without the drf plugin")
        if drf.namespace_opts and len(
                {j.namespace for j in ssn.jobs.values()}) > 1:
            # the weighted-namespace branch only acts on CROSS-namespace
            # claimee pairs; with one namespace it is provably a no-op
            raise _Unsupported(
                "weighted-namespace drf victims over multiple "
                "namespaces not modeled")
    return decide


def _deciding_victim_tier(ssn, kind: str) -> List[str]:
    flag = "enabled_preemptable" if kind == "preempt" \
        else "enabled_reclaimable"
    fns = ssn.preemptable_fns if kind == "preempt" else ssn.reclaimable_fns
    for tier in ssn.tiers:
        names = [p.name for p in tier.plugins
                 if conf_mod.enabled(getattr(p, flag)) and p.name in fns]
        if names:
            return names
    return []


def build(ssn, kind: str):
    """A batched-eviction plan for ``kind`` in {"preempt", "reclaim",
    "backfill"}, or None when the session leaves the modeled envelope
    (the action then runs its old path — the parity oracle)."""
    prof = _profile(ssn)
    try:
        with trace.span("evict.plan", kind=kind) as sp:
            if kind == "backfill":
                plan = _BackfillPlan(ssn)
                plan.note(sp)
                return plan
            return _EvictPlan(ssn, kind)
    except _Unsupported as e:
        reason = str(e)
        if reason in ("VOLCANO_TPU_EVICT=0", "tpuscore off"):
            # the device path is not armed at all (serial conf / env
            # oracle) — a mode choice, not an envelope miss: keep the
            # profile reason but do not charge the fallback-rate budget
            prof[f"evict_{kind}_fallback"] = reason
        else:
            _note_fallback(prof, f"evict_{kind}", reason)
        return None


class _EvictPlan:
    """One encoded preempt/reclaim action: device arrays + the decode maps
    the host replay needs. Pure until run() applies a successful solve.

    With ``fused=True`` (session_fuse driver) the encode runs BEFORE the
    allocate action instead of after it: the candidate/victim/job/queue
    axes are identical either way (allocate only flips PENDING->BINDING,
    which no axis layout depends on), but everything state-DEPENDENT —
    the initial job heaps, the under-request list, which jobs still have
    pending tasks — is left to the device stage, which rebuilds it from
    the carry under post-allocate keys. The fused extras (push orders,
    eligibility/validity vectors) encode the serial loop's STATIC
    iteration order so the device can replay its dynamic decisions."""

    def __init__(self, ssn, kind: str, fused: bool = False, view=None):
        from volcano_tpu.ops import encoder as enc_mod

        self.ssn = ssn
        self.kind = kind
        self.fused = fused
        view = _common_view(ssn, view)
        self.view = view
        # the session's mesh (tpuscore-installed): the node axis of this
        # plan's encode shards across it, so the [N, V] victim folds run
        # as per-shard [N/d, V] folds (ROADMAP item 3)
        self.mesh = getattr(
            getattr(ssn, "batch_allocator", None), "mesh", None)

        job_order = enc_mod._enabled_plugins(
            ssn, "enabled_job_order", ssn.job_order_fns)
        if any(p not in SUPPORTED_JOB_ORDER for p in job_order):
            raise _Unsupported(f"unsupported job-order plugins: {job_order}")
        pipelined_names = enc_mod._enabled_plugins(
            ssn, "enabled_job_pipelined", ssn.job_pipelined_fns)
        if any(p != "gang" for p in pipelined_names):
            raise _Unsupported(
                f"unsupported job-pipelined plugins: {pipelined_names}")
        if any(p != "proportion" for p in ssn.overused_fns):
            raise _Unsupported("unsupported overused plugins")
        queue_order = enc_mod._enabled_plugins(
            ssn, "enabled_queue_order", ssn.queue_order_fns)
        if any(p != "proportion" for p in queue_order):
            raise _Unsupported(
                f"unsupported queue-order plugins: {queue_order}")
        task_key = ssn.stock_task_order_key()
        if task_key is None:
            raise _Unsupported("custom task-order comparator")
        drf = ssn.plugins.get("drf")
        decide = _check_victim_tier(ssn, kind, drf)
        if fused and kind == "preempt":
            # one fused encode serves both evict stages; the reclaim tier
            # must clear the same gates, and the same-job/same-queue
            # adjacency matrices below must cover the union of both tiers
            self.reclaim_decide = _check_victim_tier(ssn, "reclaim", drf)
        else:
            self.reclaim_decide = ()

        fdt = _f_dtype()
        node_names = view.node_names
        nodes = view.nodes
        n = view.n
        if n == 0:
            raise _Unsupported("no nodes")

        # ---- eligible jobs + per-kind registration (exact serial order) --
        eligible = _eligible_jobs(ssn)
        jobs = list(ssn.jobs.values())
        jidx = {job.uid: i for i, job in enumerate(jobs)}
        j_real = len(jobs)
        jb = _bucket(max(j_real, 1))

        qnames: Dict[str, int] = {}
        for job in jobs:
            qnames.setdefault(job.queue, len(qnames))
        for qname in ssn.queues:
            qnames.setdefault(qname, len(qnames))
        qb = _bucket(max(len(qnames), 1))

        # ---- preemptor task axis -----------------------------------------
        pre_jobs = [job for job in eligible
                    if job.task_status_index.get(TaskStatus.PENDING)]
        self.trivial = not pre_jobs
        if self.trivial:
            return
        p_tasks: List = []
        job_task_start = np.zeros(jb, np.int32)
        job_task_end = np.zeros(jb, np.int32)
        for job in pre_jobs:
            pend = list(job.task_status_index[TaskStatus.PENDING].values())
            pend.sort(key=task_key)  # SortedTaskQueue order (stable)
            ji = jidx[job.uid]
            job_task_start[ji] = len(p_tasks)
            p_tasks.extend(pend)
            job_task_end[ji] = len(p_tasks)
        t_real = len(p_tasks)
        tb = _bucket(max(t_real, 1))

        # per-signature rows from the shared dense view (reused encodes)
        sig_ids: Dict[str, int] = {}
        sig_rows: List[np.ndarray] = []
        sig_affs: List[Optional[np.ndarray]] = []
        p_sig = np.zeros(tb, np.int32)
        p_has_pod = np.zeros(tb, bool)
        p_req = np.zeros((tb, 2), fdt)
        p_init = np.zeros((tb, 2), fdt)
        p_job = np.zeros(tb, np.int32)
        for ti, task in enumerate(p_tasks):
            rows = view._rows(task)
            if rows is None:
                raise _Unsupported(
                    "preemptor with host ports / pod affinity")
            key, mask, aff = rows
            si = sig_ids.get(key)
            if si is None:
                si = sig_ids[key] = len(sig_rows)
                sig_rows.append(mask)
                sig_affs.append(aff)
            p_sig[ti] = si
            p_has_pod[ti] = task.pod is not None
            p_req[ti] = (task.resreq.milli_cpu, task.resreq.memory)
            p_init[ti] = (task.init_resreq.milli_cpu, task.init_resreq.memory)
            p_job[ti] = jidx[task.job]
        sb = _bucket(max(len(sig_rows), 1))
        sig_mask = np.zeros((sb, n), bool)
        affinity = np.zeros((sb, n), fdt)
        for si, row in enumerate(sig_rows):
            sig_mask[si] = row
            if sig_affs[si] is not None:
                affinity[si] = sig_affs[si]
        p_nz_cpu = np.where(p_req[:, 0] != 0, p_req[:, 0],
                            nodeorder_mod.DEFAULT_MILLI_CPU_REQUEST)
        p_nz_mem = np.where(p_req[:, 1] != 0, p_req[:, 1],
                            nodeorder_mod.DEFAULT_MEMORY_REQUEST)

        # ---- victim axis (claimee order = node.tasks iteration order) ----
        vic_rows: List[List] = []
        for node in nodes:
            vic_rows.append([
                t for t in node.tasks.values()
                if t.status == TaskStatus.RUNNING and t.job in ssn.jobs])
        self.vic_rows = vic_rows
        v = _bucket(max(1, max((len(r) for r in vic_rows), default=1)))
        vic_req = np.zeros((n, v, 2), fdt)
        vic_job = np.zeros((n, v), np.int32)
        vic_valid = np.zeros((n, v), bool)
        vic_conf = np.zeros((n, v), bool)
        vic_cut_perm = np.full((n, v), -1, np.int32)
        total_victims = 0
        from volcano_tpu.api import objects

        for ni, row in enumerate(vic_rows):
            total_victims += len(row)
            for vi, t in enumerate(row):
                vic_req[ni, vi] = (t.resreq.milli_cpu, t.resreq.memory)
                vic_job[ni, vi] = jidx[t.job]
                vic_valid[ni, vi] = True
                cls = t.pod.spec.priority_class_name if t.pod else ""
                vic_conf[ni, vi] = not (
                    cls in (objects.SYSTEM_CLUSTER_CRITICAL,
                            objects.SYSTEM_NODE_CRITICAL)
                    or t.namespace == "kube-system")
            if kind == "preempt" and row:
                order = sorted(range(len(row)),
                               key=lambda i: task_key(row[i]), reverse=True)
                vic_cut_perm[ni, :len(order)] = order

        # ---- job / queue state axes --------------------------------------
        job_prio = np.zeros(jb, np.int32)
        job_min_av = np.zeros(jb, np.int32)
        job_ready0 = np.zeros(jb, np.int32)
        job_wait0 = np.zeros(jb, np.int32)
        job_queue = np.zeros(jb, np.int32)
        job_alloc0 = np.zeros((jb, 2), fdt)
        for i, job in enumerate(jobs):
            job_prio[i] = job.priority
            job_min_av[i] = job.min_available
            job_ready0[i] = job.ready_task_num()
            job_wait0[i] = job.waiting_task_num()
            job_queue[i] = qnames[job.queue]
            if drf is not None:
                attr = drf.job_attrs.get(job.uid)
                if attr is not None:
                    job_alloc0[i] = (attr.allocated.milli_cpu,
                                     attr.allocated.memory)
        job_tie = np.full(jb, np.iinfo(np.int32).max - 1, np.int32)
        if j_real:
            ctimes = np.fromiter((j.creation_timestamp for j in jobs),
                                 np.float64, j_real)
            uids = np.array([j.uid for j in jobs])
            order = np.lexsort((uids, ctimes))
            job_tie[order] = np.arange(j_real, dtype=np.int32)

        prop = ssn.plugins.get("proportion")
        queue_alloc0 = np.zeros((qb, 2), fdt)
        queue_deserved = np.zeros((qb, 2), fdt)
        queue_has_attr = np.zeros(qb, bool)
        for qname, qi in qnames.items():
            attr = prop.queue_opts.get(qname) if prop is not None else None
            if attr is not None:
                queue_alloc0[qi] = (attr.allocated.milli_cpu,
                                    attr.allocated.memory)
                queue_deserved[qi] = (attr.deserved.milli_cpu,
                                      attr.deserved.memory)
                queue_has_attr[qi] = True
        queue_tie = np.full(qb, np.iinfo(np.int32).max - 1, np.int32)
        known = [(qi, ssn.queues[qn]) for qn, qi in qnames.items()
                 if qn in ssn.queues]
        known.sort(key=lambda p: (p[1].queue.metadata.creation_timestamp,
                                  p[1].uid))
        for rank, (qi, _) in enumerate(known):
            queue_tie[qi] = rank

        # pad slots alias queue 0 (gather-safe); every use gates on valid
        vic_queue = np.where(vic_valid, job_queue[vic_job], 0).astype(
            np.int32)

        arrays = dict(
            eps=np.array([MIN_MILLI_CPU, MIN_MEMORY], fdt),
            node_used=view.used.astype(fdt).copy(),
            node_alloc=view.alloc.astype(fdt, copy=False),
            node_cnt=view.cnt.astype(np.int32).copy(),
            node_max=view.max_tasks.astype(np.int32),
            affinity_score=affinity,
            sig_mask=sig_mask,
            least_req_weight=np.asarray(view.least_req_w, fdt),
            balanced_weight=np.asarray(view.balanced_w, fdt),
            node_affinity_weight=np.asarray(view.node_aff_w, fdt),
            binpack_w=view.binpack_w.astype(fdt),
            binpack_weight=np.asarray(view.binpack_weight, fdt),
            drf_total=(np.array([drf.total_resource.milli_cpu,
                                 drf.total_resource.memory], fdt)
                       if drf is not None else np.zeros(2, fdt)),
            p_req=p_req, p_init=p_init,
            p_nz_cpu=p_nz_cpu.astype(fdt), p_nz_mem=p_nz_mem.astype(fdt),
            p_sig=p_sig, p_has_pod=p_has_pod, p_job=p_job,
            job_task_start=job_task_start, job_task_end=job_task_end,
            job_prio=job_prio, job_min_av=job_min_av,
            job_ready0=job_ready0, job_wait0=job_wait0,
            job_queue=job_queue, job_alloc0=job_alloc0, job_tie=job_tie,
            queue_alloc0=queue_alloc0, queue_deserved=queue_deserved,
            queue_has_attr=queue_has_attr, queue_tie=queue_tie,
            vic_req=vic_req, vic_job=vic_job, vic_queue=vic_queue,
            vic_valid=vic_valid, vic_alive0=vic_valid.copy(),
            vic_conf=vic_conf,
            # real-slot mask + count: the round-robin window must wrap
            # over the REAL node axis even when the mesh pad appends slots
            node_real=np.ones(n, bool),
            real_n=np.int32(n),
            rr0=np.int32(0),
            num_to_find=np.int32(0),
        )
        if kind == "preempt":
            arrays["vic_cut_perm"] = vic_cut_perm
            from volcano_tpu.scheduler.util import scheduler_helper as helper

            arrays["rr0"] = np.int32(helper._last_processed_node_index)
            arrays["num_to_find"] = np.int32(
                helper.calculate_num_of_feasible_nodes_to_find(n))
        tiers_union = set(decide) | set(self.reclaim_decide)
        if "drf" in tiers_union or "gang" in tiers_union:
            vj = np.where(vic_valid, vic_job, -1 - np.arange(v)[None, :])
            arrays["vic_samejob"] = vj[:, :, None] == vj[:, None, :]
        if "proportion" in tiers_union:
            vq = np.where(vic_valid, vic_queue, -1 - np.arange(v)[None, :])
            arrays["vic_samequeue"] = vq[:, :, None] == vq[:, None, :]
        # live-pointer permutation: identity on the per-action path (the
        # candidate axis holds exactly the still-pending tasks); the fused
        # stages overlay a device-computed next-live map instead
        arrays["p_next"] = np.arange(tb, dtype=np.int32)

        # ---- heaps (initial arrays built by the REAL PriorityQueue at
        # encode-time keys — every initial push happens before any state
        # mutation, so the extracted heap list is exact) -------------------
        from volcano_tpu.scheduler.util.priority_queue import PriorityQueue

        jcap = _bucket(max(1, max(
            (sum(1 for j in pre_jobs if j.queue == qn) for qn in qnames),
            default=1)))
        if fused:
            # the initial heaps depend on post-allocate state (which jobs
            # still have pending tasks, and their drf/gang keys), so the
            # fused chain builds them ON DEVICE from these static push
            # orders — the serial loops' iteration order, with the dynamic
            # conditions (pending-task liveness, job validity) left to the
            # stage wrappers (session_fuse)
            proc_rows: Dict[str, int] = {}
            proc_queues: List[int] = []
            push_jobs: List[int] = []
            push_rows: List[int] = []
            ev_jobs: List[int] = []
            ev_qrow: List[int] = []
            for job in eligible:
                row = proc_rows.get(job.queue)
                if row is None:
                    row = proc_rows[job.queue] = len(proc_queues)
                    proc_queues.append(qnames[job.queue])
                ev_jobs.append(jidx[job.uid])
                ev_qrow.append(qnames[job.queue])
                if job.task_status_index.get(TaskStatus.PENDING):
                    push_jobs.append(jidx[job.uid])
                    push_rows.append(row)
            qp = _bucket(max(len(proc_queues), 1))
            queue_real = np.zeros(qp, bool)
            queue_real[:len(proc_queues)] = True
            pb = _bucket(max(len(push_jobs), 1))
            f_push_jobs = np.full(pb, -1, np.int32)
            f_push_jobs[:len(push_jobs)] = push_jobs
            f_push_row = np.zeros(pb, np.int32)
            f_push_row[:len(push_rows)] = push_rows
            eb = _bucket(max(len(ev_jobs), 1))
            f_ev_jobs = np.full(eb, -1, np.int32)
            f_ev_jobs[:len(ev_jobs)] = ev_jobs
            f_ev_qrow = np.zeros(eb, np.int32)
            f_ev_qrow[:len(ev_qrow)] = ev_qrow
            f_elig0 = np.zeros(jb, bool)
            for job in eligible:
                f_elig0[jidx[job.uid]] = True
            # valid_task_num changes ONLY via evictions within the chain
            # (RELEASING is neither allocated nor pending); the reclaim
            # stage re-derives post-preempt validity as vtn0 - evicted
            f_vtn0 = np.zeros(jb, np.int32)
            f_job_attr = np.zeros(jb, bool)
            for i, job in enumerate(jobs):
                f_vtn0[i] = job.valid_task_num()
                if drf is not None:
                    f_job_attr[i] = drf.job_attrs.get(job.uid) is not None
            arrays.update(
                queue_real=queue_real,
                f_push_jobs=f_push_jobs, f_push_row=f_push_row,
                f_ev_jobs=f_ev_jobs, f_ev_qrow=f_ev_qrow,
                f_elig0=f_elig0, f_vtn0=f_vtn0, f_job_attr=f_job_attr)
            # every fused-stage jit-static size, derived HERE from the
            # bucket ladder (n is deliberately unbucketed, like the node
            # axis itself — deployment-stable, not churny; under a mesh it
            # is the device-multiple-padded extent so the fused carries
            # align with the sharded node buffers shard-for-shard)
            from volcano_tpu.ops import shard as shard_mod

            d = shard_mod.device_count(self.mesh)
            self.fuse_sizes = dict(
                qp=qp, jcap=jcap, ju=pb, qb=qb, jb=jb, tb=tb,
                n=((n + d - 1) // d) * d,
                qh=_bucket(max(len(proc_queues), 1)))
        elif kind == "preempt":
            proc_queues: List[int] = []
            seen_q: Dict[str, PriorityQueue] = {}
            under: List[int] = []
            for job in eligible:
                if job.queue not in seen_q:
                    seen_q[job.queue] = PriorityQueue(
                        cmp_fn=ssn.job_order_cmp)
                    proc_queues.append(qnames[job.queue])
                if job.task_status_index.get(TaskStatus.PENDING):
                    seen_q[job.queue].push(job)
                    under.append(jidx[job.uid])
            qp = _bucket(max(len(proc_queues), 1))
            heap0 = np.zeros((qp, jcap), np.int32)
            hsize0 = np.zeros(qp, np.int32)
            queue_real = np.zeros(qp, bool)
            for pi, (qn, pq) in enumerate(seen_q.items()):
                row = [jidx[it.value.uid] for it in pq._heap]
                heap0[pi, :len(row)] = row
                hsize0[pi] = len(row)
                queue_real[pi] = True
            ju = _bucket(max(len(under), 1))
            under_jobs = np.full(ju, -1, np.int32)
            under_jobs[:len(under)] = under
            arrays.update(heap0=heap0, hsize0=hsize0,
                          queue_real=queue_real, under_jobs=under_jobs)
        else:
            queues_pq = PriorityQueue(cmp_fn=ssn.queue_order_cmp)
            seen_qs: Dict[str, PriorityQueue] = {}
            for job in eligible:
                if job.queue not in seen_qs:
                    seen_qs[job.queue] = PriorityQueue(
                        cmp_fn=ssn.job_order_cmp)
                    queues_pq.push(ssn.queues[job.queue])
                if job.task_status_index.get(TaskStatus.PENDING):
                    seen_qs[job.queue].push(job)
            heap0 = np.zeros((qb, jcap), np.int32)
            hsize0 = np.zeros(qb, np.int32)
            for qn, pq in seen_qs.items():
                qi = qnames[qn]
                row = [jidx[it.value.uid] for it in pq._heap]
                heap0[qi, :len(row)] = row
                hsize0[qi] = len(row)
            qh = _bucket(max(len(queues_pq), 1))
            qheap0 = np.zeros(qh, np.int32)
            qrow = [qnames[it.value.uid] for it in queues_pq._heap]
            qheap0[:len(qrow)] = qrow
            arrays.update(heap0=heap0, hsize0=hsize0, qheap0=qheap0,
                          qhsize0=np.int32(len(qrow)))

        # live log ≤ committed evicts (each victim commits at most once) +
        # committed pipelines + commit markers (≤ job pops + phase-2 tasks)
        # + one open statement's ops; overflow just fails to the old path
        log_rows = _bucket(2 * total_victims + 4 * tb + jb + 64)

        self.arrays = arrays
        self.p_tasks = p_tasks
        self.node_names = node_names
        self.n = n
        self.v = v
        self.spec = EvictSpec(
            kind=kind,
            job_order_keys=tuple(job_order),
            victim_fns=tuple(decide),
            check_pod_count=view.check_pod_count,
            use_nodeorder=view.use_nodeorder,
            use_binpack=view.use_binpack,
            use_gang_pipelined="gang" in pipelined_names,
            use_prop_overused="proportion" in ssn.overused_fns,
            use_prop_queue_order="proportion" in queue_order,
            log_rows=log_rows,
        )
        if fused and kind == "preempt":
            self.reclaim_spec = self.spec._replace(
                kind="reclaim", victim_fns=tuple(self.reclaim_decide))
        self.jidx = jidx
        self.qnames = qnames
        self.t_real = t_real
        self.tb = tb

    # -- run: dispatch once, fetch once, replay committed ops --------------

    def run(self) -> bool:
        prof = _profile(self.ssn)
        key = f"evict_{self.kind}"
        if self.trivial:
            prof[key] = {"trivial": True}
            return True
        from volcano_tpu.utils import devprof

        layout, staged = _pack_staged(self.arrays, self.kind, self.mesh,
                                      prof)
        try:
            # async fetch (shared with the session-fused driver): the D2H
            # copy starts at dispatch and overlaps the host-side replay
            # scaffolding below; the wait is the action's one sync point
            with trace.span("dispatch", kind=self.kind):
                wait = devprof.start_fetch(
                    _solve_packed(self.spec, layout, staged))
            # host bookkeeping that needs no result: bind the replay
            # dependencies while the device still solves
            from volcano_tpu.scheduler import metrics  # noqa: F401
            from volcano_tpu.scheduler.util import (  # noqa: F401
                scheduler_helper)

            out = wait()
        except Exception as e:  # any device/compile failure -> old path
            logger.exception("batched %s solve failed; falling back",
                             self.kind)
            _note_fallback(prof, key, f"solve error: {e}")
            return False
        return self.consume(out)

    def consume(self, out: np.ndarray, kind: Optional[str] = None) -> bool:
        """Validate + replay a fetched packed result (shared by run() and
        the session-fused driver — which replays BOTH evict stages through
        one fused-encode plan, passing ``kind`` explicitly). False =>
        nothing was applied and the caller must run the old per-action
        path."""
        kind = kind or self.kind
        prof = _profile(self.ssn)
        key = f"evict_{kind}"
        stage: dict = {}
        with trace.span("evict.consume." + kind, into=(stage, "apply_s")):
            ok = self._consume(out, kind, prof, key, stage)
        if ok:
            prof[key] = stage
        return ok

    def _consume(self, out, kind, prof, key, stage) -> bool:
        lr = self.spec.log_rows
        tail = out[lr * 3:]
        log_len, rr, victims, attempts, fail, underflow = (
            int(tail[0]), int(tail[1]), int(tail[2]), int(tail[3]),
            int(tail[4]), int(tail[5]))
        if fail:
            _note_fallback(prof, key,
                           "kernel step/log budget exhausted")
            return False
        if underflow:
            from volcano_tpu.utils.assertions import panic_enabled

            if panic_enabled():
                # the serial walk raises AssertionViolation at the
                # offending claimee; rerun it so panic mode fails
                # identically loudly (nothing was applied)
                _note_fallback(prof, key,
                               "resource underflow under panic mode")
                return False
        log = out[:log_len * 3].reshape(log_len, 3)
        self._replay(log, victims, attempts, rr, kind=kind)
        stage.update(ops=log_len, victims=victims, attempts=attempts)
        return True

    def _replay(self, log: np.ndarray, victims: int, attempts: int,
                rr: int, kind: Optional[str] = None) -> None:
        """Apply the committed op log in exact serial order through the
        real Statement/session mutators (events, cache effectors, and
        SnapshotKeeper dirty-sets all fire as the serial walk would)."""
        from volcano_tpu.scheduler import metrics
        from volcano_tpu.scheduler.util import scheduler_helper as helper

        ssn = self.ssn
        if (kind or self.kind) == "preempt":
            stmt = None
            for kind_, a, b in log.tolist():
                if kind_ == OP_EVICT:
                    if stmt is None:
                        stmt = ssn.statement()
                    task = self.vic_rows[a][b]
                    try:
                        stmt.evict(task.shared_clone(), "preempt")
                    except Exception as e:
                        logger.error("Failed to preempt Task <%s/%s>: %s",
                                     task.namespace, task.name, e)
                elif kind_ == OP_PIPELINE:
                    if stmt is None:
                        stmt = ssn.statement()
                    stmt.pipeline(self.p_tasks[a], self.node_names[b])
                else:  # OP_COMMIT
                    if stmt is not None:
                        stmt.commit()
                        stmt = None
            if stmt is not None:  # pragma: no cover - kernel always marks
                stmt.commit()
            if victims:
                metrics.update_preemption_victims(victims)
            if attempts:
                metrics.register_preemption_attempts(attempts)
            helper._last_processed_node_index = rr % max(self.n, 1)
        else:
            for kind_, a, b in log.tolist():
                if kind_ == OP_EVICT:
                    task = self.vic_rows[a][b]
                    try:
                        ssn.evict(task.shared_clone(), "reclaim")
                    except (KeyError, RuntimeError) as e:
                        logger.error("Failed to reclaim %s/%s: %s",
                                     task.namespace, task.name, e)
                elif kind_ == OP_PIPELINE:
                    ssn.pipeline(self.p_tasks[a], self.node_names[b])


def _backfill_tasks(ssn) -> List[tuple]:
    """(job, task) of every zero-request PENDING task backfill places, in
    ssn.jobs order and each job's PENDING order (backfill.go:41-91). A job's
    phase and validity are checked only when it holds such a task:
    job_valid is memoized and pure, so the order of its calls is free."""
    from volcano_tpu.api import objects

    found = []
    for job in list(ssn.jobs.values()):
        zero = [task for task in job.task_status_index.get(
                    TaskStatus.PENDING, {}).values()
                if task.init_resreq.is_empty()]
        if not zero:
            continue
        if job.pod_group.status.phase == objects.PodGroupPhase.PENDING:
            continue
        vr = ssn.job_valid(job)
        if vr is not None and not vr.pass_:
            continue
        found.extend((job, task) for task in zero)
    return found


class _BackfillPlan:
    """Batched backfill: the device decides every zero-request placement
    (first feasible node in name order under the evolving pod-count), the
    host replays through ssn.allocate and keeps the serial-fidelity
    FitErrors machinery — including the bounded diagnostics replay."""

    def __init__(self, ssn, view=None):
        self.ssn = ssn
        self.view = None
        _check_armed(ssn)
        # triviality first: the dense view costs O(nodes + resident tasks)
        # on the host, and a session without a zero-request task has no use
        # for it (nor has it left the device path)
        found = _backfill_tasks(ssn)
        self.tasks = [task for _, task in found]
        self.jobs_of = [job for job, _ in found]
        self.trivial = not found
        if self.trivial:
            return
        view = _common_view(ssn, view)
        self.view = view
        self.mesh = getattr(
            getattr(ssn, "batch_allocator", None), "mesh", None)
        sig_ids: Dict[str, int] = {}
        sig_rows: List[np.ndarray] = []
        sigs: List[int] = []
        for task in self.tasks:
            rows = view._rows(task)
            if rows is None:
                raise _Unsupported(
                    "backfill task with host ports / pod affinity")
            key, mask, _ = rows
            si = sig_ids.get(key)
            if si is None:
                si = sig_ids[key] = len(sig_rows)
                sig_rows.append(mask)
            sigs.append(si)
        tasks = self.tasks
        n = view.n
        if n == 0:
            raise _Unsupported("no nodes")
        tb = _bucket(len(tasks))
        sb = _bucket(max(len(sig_rows), 1))
        sig_mask = np.zeros((sb, n), bool)
        for si, row in enumerate(sig_rows):
            sig_mask[si] = row
        b_sig = np.zeros(tb, np.int32)
        b_sig[:len(sigs)] = sigs
        b_has_pod = np.zeros(tb, bool)
        b_has_pod[:len(tasks)] = [t.pod is not None for t in tasks]
        b_real = np.zeros(tb, bool)
        b_real[:len(tasks)] = True
        self.arrays = dict(
            sig_mask=sig_mask,
            node_cnt=view.cnt.astype(np.int32).copy(),
            node_max=view.max_tasks.astype(np.int32),
            b_sig=b_sig, b_has_pod=b_has_pod, b_real=b_real,
        )
        self.node_names = view.node_names
        self.spec = EvictSpec(
            kind="backfill", job_order_keys=(), victim_fns=(),
            check_pod_count=view.check_pod_count,
            use_nodeorder=False, use_binpack=False,
            use_gang_pipelined=False)

    def note(self, sp) -> None:
        """The plan's counts on its ``vt.evict.plan`` span: the zero-request
        tasks found, and whether the dense view was built or reused."""
        sp.note(tasks=len(self.tasks), view=int(self.view is not None))

    def run(self) -> bool:
        from volcano_tpu.api.unschedule_info import FitErrors, FitFailure
        from volcano_tpu.scheduler.util import scheduler_helper as helper

        prof = _profile(self.ssn)
        if self.trivial:
            prof["evict_backfill"] = {"trivial": True}
            return True
        from volcano_tpu.utils import devprof

        ssn = self.ssn
        layout, staged = _pack_staged(self.arrays, "backfill", self.mesh,
                                      prof)
        try:
            with trace.span("dispatch", kind="backfill"):
                wait = devprof.start_fetch(
                    _solve_packed(self.spec, layout, staged))
            # overlap the fetch with the replay's node-list build (the one
            # host-side O(N) term on this action's critical path)
            all_nodes = helper.get_node_list(ssn.nodes)
            assign = wait()
        except Exception as e:
            logger.exception("batched backfill solve failed; falling back")
            _note_fallback(prof, "evict_backfill", f"solve error: {e}")
            return False
        return self.consume(assign, all_nodes=all_nodes)

    def consume(self, assign: np.ndarray, all_nodes=None) -> bool:
        """Replay a fetched backfill assignment (shared by run() and the
        session-fused driver)."""
        stage: dict = {}
        with trace.span("evict.consume.backfill", into=(stage, "apply_s"),
                        tasks=len(self.tasks)):
            placed = self._consume(assign, all_nodes)
        stage.update(tasks=len(self.tasks), placed=placed)
        _profile(self.ssn)["evict_backfill"] = stage
        return True

    def _consume(self, assign: np.ndarray, all_nodes) -> int:
        from volcano_tpu.api.unschedule_info import FitErrors, FitFailure
        from volcano_tpu.scheduler.util import scheduler_helper as helper

        ssn = self.ssn
        if all_nodes is None:
            all_nodes = helper.get_node_list(ssn.nodes)
        # budget for full per-node diagnostics replay on failures — same
        # contract as the dense-view path (backfill.py replay_budget)
        replay_budget = 8
        placed = 0
        for i, task in enumerate(self.tasks):
            job = self.jobs_of[i]
            ni = int(assign[i])
            allocated = False
            tried = 0
            if ni >= 0:
                tried = 1
                try:
                    ssn.allocate(task, self.node_names[ni])
                    allocated = True
                except (KeyError, RuntimeError) as err:
                    logger.error("Failed to bind Task %s on %s: %s",
                                 task.uid, self.node_names[ni], err)
                    # the serial walk continues with the next feasible
                    # node; recover through the live dense view stream
                    from volcano_tpu.ops import preemptview

                    view2 = preemptview.build(ssn)
                    cands = view2.masked_nodes_in_name_order(task) \
                        if view2 is not None else ()
                    for nd in cands or ():
                        if nd.name == self.node_names[ni]:
                            continue
                        tried += 1
                        try:
                            ssn.allocate(task, nd.name)
                            allocated = True
                            break
                        except (KeyError, RuntimeError) as err2:
                            logger.error(
                                "Failed to bind Task %s on %s: %s",
                                task.uid, nd.name, err2)
            if allocated:
                placed += 1
                continue
            fe = FitErrors()
            if tried == 0 and replay_budget > 0:
                # dense failure path: replay the serial predicate chain to
                # recover the per-node reasons the serial walk records
                replay_budget -= 1
                for nd in all_nodes:
                    try:
                        ssn.predicate_fn(task, nd)
                    except FitFailure as err:
                        fe.set_node_error(nd.name, err.fit_error(task, nd))
            if not fe.nodes:
                fe.set_error(
                    "0/%d nodes are feasible for backfill"
                    % len(all_nodes) if tried == 0 else
                    "%d feasible nodes rejected the backfill "
                    "allocation" % tried)
            job.nodes_fit_errors[task.uid] = fe
        return placed
