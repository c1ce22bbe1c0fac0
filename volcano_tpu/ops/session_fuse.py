"""Whole-session fused dispatch: one device program chain per session.

Run per action, the cfg4 overcommit chain pays four separate encode -> H2D -> dispatch ->
blocking-fetch -> host-apply round trips (allocate, backfill, preempt,
reclaim), and each boundary re-encodes session state the PREVIOUS device
stage already knew. This module fuses the remaining per-action boundary:

- ALL stages are encoded up-front from the pre-action snapshot and
  dispatched back-to-back; stage N+1 consumes stage N's **donated carry
  buffers** (used/cnt node vectors, job/queue allocation vectors, the
  consumed-candidate skip mask, the victim alive mask) directly on device,
  so XLA reuses the carry memory across stages and no packed result
  round-trips through the host between actions;
- the parts of each action's encode that DEPEND on earlier actions' results
  (which jobs still have pending tasks, the initial job/queue heaps under
  post-allocate drf/gang keys, post-preempt gang validity) are rebuilt ON
  DEVICE by the stage wrappers from static iteration-order metadata
  (ops/evict.py `fused=True` encode) — the serial loops' dynamic decisions
  replayed under the carried state, bit-identically for integral
  milli-cpu/byte quantities (scatter-add bridging of allocation vectors is
  order-free only for exact sums; same caveat class as the float32 bench
  note in ops/evict.py);
- the host then fetches the per-stage packed results IN STAGE ORDER
  (async: every copy starts at dispatch) and replays each through the real
  Statement/session mutators — events, cache effectors, SnapshotKeeper
  dirty-sets and metrics land exactly as the per-action path would — while
  the device is still executing later stages: stage N's host replay
  overlaps stage N+1's device compute. The only synchronization points are
  the counted waits at each profiling/apply boundary (utils/devprof).

Fallback contract (same discipline as ops/evict.py): `VOLCANO_TPU_FUSE=0`
forces the per-action path byte-for-byte; out-of-envelope sessions
(residue/releasing/exclusion workloads, scalar resource dims, unsupported
plugin sets) never fuse (`fuse_fallback` profile reason). A mesh-sharded
session fuses like any other: the node axis stays sharded through every
stage (the evict encodes ship per-shard beside their packed groups,
ops/evict._pack_staged) and the donated carries ride whole — the win only
exists if no stage de-shards the axis mid-session (ROADMAP item 3);
a mid-chain validation failure (allocate residue retry, kernel budget
exhaustion, panic-mode underflow) applies every stage UP TO the failure
and runs the remaining actions per-action — nothing from an invalidated
stage is ever applied. Parity is fuzz-pinned by tests/test_session_fuse.py.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from volcano_tpu.utils import trace

logger = logging.getLogger(__name__)

# the fusable chain grammar: "allocate" then a subsequence of _EVICT_ORDER
# containing "preempt" (the evict encode anchors every bridge axis)
_EVICT_ORDER = ("backfill", "preempt", "reclaim")


# ---------------------------------------------------------------------------
# device stage wrappers
# ---------------------------------------------------------------------------


def _live_job_mask(enc, p_next):
    """[J] bool: job has an unconsumed live candidate task (the device twin
    of `job.task_status_index.get(PENDING)` at action-encode time)."""
    import jax.numpy as jnp

    t_total = p_next.shape[0]
    start = enc["job_task_start"]
    end = enc["job_task_end"]
    nxt = p_next[jnp.clip(start, 0, t_total - 1)]
    return (start < end) & (nxt < end)


@functools.partial(
    jax.jit, static_argnames=("spec", "layout", "mlayout", "sizes"))
def _fuse_alloc(spec, layout, bufs, mlayout, mbufs, sizes):
    """Stage 1: the candidate-window allocate rounds (ops/rounds.py) plus
    the carry bridge — per-evict-axis deltas of everything the allocate
    apply will change host-side (node used/cnt, job ready/alloc, queue
    alloc, consumed candidates). Returns (packed result, carry)."""
    import jax.numpy as jnp

    from volcano_tpu.ops import rounds as rounds_mod

    n_ev, j_ev, q_ev, tc = sizes
    enc = rounds_mod.unpack_layout(layout, bufs)
    maps = rounds_mod.unpack_layout(mlayout, mbufs)
    raw = rounds_mod.solve_rounds.__wrapped__(spec, enc)
    packed = rounds_mod.pack_result(enc, raw)
    assign = raw[0]

    fdt = enc["cls_req"].dtype
    req = enc["cls_req"][enc["task_cls"]]                   # [T, R]
    pm = assign >= 0
    nb_r = enc["node_idle"].shape[0]
    enode = maps["r2e_node"][jnp.clip(assign, 0, nb_r - 1)]
    ejob = maps["r2e_job"][enc["task_job"]]
    ok_n = pm & (enode >= 0)
    ok_j = pm & (ejob >= 0)
    reqn = jnp.where(ok_n[:, None], req, 0).astype(fdt)
    reqj = jnp.where(ok_j[:, None], req, 0).astype(fdt)
    rdim = 2  # cpu/memory only: the fuse envelope gates scalar dims out
    used_add = jnp.zeros((n_ev, rdim), fdt).at[
        jnp.clip(enode, 0, n_ev - 1)].add(reqn)
    cnt_add = jnp.zeros(n_ev, jnp.int32).at[
        jnp.clip(enode, 0, n_ev - 1)].add(ok_n.astype(jnp.int32))
    ejc = jnp.clip(ejob, 0, j_ev - 1)
    ready_add = jnp.zeros(j_ev, jnp.int32).at[ejc].add(
        ok_j.astype(jnp.int32))
    alloc_add = jnp.zeros((j_ev, rdim), fdt).at[ejc].add(reqj)
    equeue = maps["e_job_queue"][ejc]
    qalloc_add = jnp.zeros((q_ev, rdim), fdt).at[
        jnp.clip(equeue, 0, q_ev - 1)].add(reqj)
    ct = maps["r2e_task"]
    skip = jnp.zeros(tc, bool).at[jnp.clip(ct, 0, tc - 1)].max(
        pm & (ct >= 0))
    carry = dict(used_add=used_add, cnt_add=cnt_add, ready_add=ready_add,
                 alloc_add=alloc_add, qalloc_add=qalloc_add, skip=skip)
    return packed, carry


@functools.partial(
    jax.jit, static_argnames=("spec", "layout", "mlayout"),
    donate_argnums=(5,))
def _fuse_backfill(spec, layout, bufs, mlayout, mbufs, carry):
    """Stage 2: backfill's placement decisions under the post-allocate
    pod-count headroom. Zero-request placements touch cnt/ready/skip only."""
    import jax.numpy as jnp

    from volcano_tpu.ops import evict as evict_mod
    from volcano_tpu.ops import rounds as rounds_mod

    enc = rounds_mod.unpack_layout(layout, bufs)
    maps = rounds_mod.unpack_layout(mlayout, mbufs)
    tc = carry["skip"].shape[0]
    b2c = maps["b2cand"]
    taken = carry["skip"][jnp.clip(b2c, 0, tc - 1)] & (b2c >= 0)
    enc2 = dict(enc,
                node_cnt=enc["node_cnt"] + carry["cnt_add"],
                b_real=enc["b_real"] & ~taken)
    assign = evict_mod.solve_backfill.__wrapped__(spec, enc2)
    pm = assign >= 0
    n_ev = carry["cnt_add"].shape[0]
    cnt_add = carry["cnt_add"].at[jnp.clip(assign, 0, n_ev - 1)].add(
        pm.astype(jnp.int32))
    ejob = maps["b_ejob"]
    j_ev = carry["ready_add"].shape[0]
    ok_j = pm & (ejob >= 0)
    ready_add = carry["ready_add"].at[jnp.clip(ejob, 0, j_ev - 1)].add(
        ok_j.astype(jnp.int32))
    skip = carry["skip"].at[jnp.clip(b2c, 0, tc - 1)].max(pm & (b2c >= 0))
    return assign, dict(carry, cnt_add=cnt_add, ready_add=ready_add,
                        skip=skip)


@functools.partial(
    jax.jit, static_argnames=("spec", "layout", "sizes"),
    donate_argnums=(3,))
def _fuse_preempt(spec, layout, bufs, carry, sizes):
    """Stage 3: the preempt state machine (ops/evict.py) from carry-bridged
    post-allocate state: initial job heaps + under-request list rebuilt on
    device with the REAL heap-push mechanics under the current drf/gang
    keys (the serial encode builds them with the live PriorityQueue at
    exactly this state). Returns (packed op log, full-state carry)."""
    import jax.numpy as jnp
    from jax import lax

    from volcano_tpu.ops import evict as evict_mod
    from volcano_tpu.ops import rounds as rounds_mod

    qp, jcap, pb = sizes
    enc = rounds_mod.unpack_layout(layout, bufs)
    skip = carry["skip"]
    p_next = evict_mod._live_next(~skip)
    live_job = _live_job_mask(enc, p_next)

    used = enc["node_used"] + carry["used_add"]
    cnt = enc["node_cnt"] + carry["cnt_add"]
    ready = enc["job_ready0"] + carry["ready_add"]
    job_alloc = enc["job_alloc0"] + jnp.where(
        enc["f_job_attr"][:, None], carry["alloc_add"], 0)
    queue_alloc = enc["queue_alloc0"] + jnp.where(
        enc["queue_has_attr"][:, None], carry["qalloc_add"], 0)

    less = evict_mod._job_less(
        spec, enc, {"ready": ready, "job_alloc": job_alloc})
    push_jobs = enc["f_push_jobs"]
    push_row = enc["f_push_row"]
    j_total = enc["job_prio"].shape[0]
    pushable = (push_jobs >= 0) \
        & live_job[jnp.clip(push_jobs, 0, j_total - 1)]

    def push_body(i, hv):
        heap, hsize = hv
        j = push_jobs[i]
        row = jnp.clip(push_row[i], 0, qp - 1)

        def do(hv):
            heap, hsize = hv
            rowv, nsz = evict_mod._heap_push(heap[row], hsize[row], j, less)
            return heap.at[row].set(rowv), hsize.at[row].set(nsz)

        return lax.cond(pushable[i], do, lambda x: x, hv)

    heap, hsize = lax.fori_loop(
        0, pb, push_body,
        (jnp.zeros((qp, jcap), jnp.int32), jnp.zeros(qp, jnp.int32)))
    under = jnp.where(pushable, push_jobs, -1)

    enc2 = dict(enc, p_next=p_next, under_jobs=under)
    st = dict(
        used=used, cnt=cnt, alive=enc["vic_alive0"],
        ready=ready, wait=enc["job_wait0"],
        job_alloc=job_alloc, queue_alloc=queue_alloc,
        ptr=enc["job_task_start"],
        heap=heap, hsize=hsize,
        **evict_mod._log0(spec),
        rr=enc["rr0"].astype(jnp.int32),
        p_done=skip,
        mode=jnp.int32(evict_mod.M_QUEUE), qi=jnp.int32(0),
        cur_job=jnp.int32(0),
        phase2=jnp.bool_(False), assigned=jnp.bool_(False),
        stmt_start=jnp.int32(0), u2=jnp.int32(0),
        victims=jnp.int32(0), attempts=jnp.int32(0),
        fail=jnp.bool_(False), underflow=jnp.bool_(False),
        steps=jnp.int32(0),
    )
    st = evict_mod.preempt_machine(spec, enc2, st)
    packed = evict_mod.evict_tail(st)
    carry2 = dict(used=st["used"], cnt=st["cnt"], alive=st["alive"],
                  ready=st["ready"], wait=st["wait"],
                  job_alloc=st["job_alloc"], queue_alloc=st["queue_alloc"],
                  skip=st["p_done"])
    return packed, carry2


@functools.partial(
    jax.jit, static_argnames=("spec", "layout", "sizes", "use_gang_valid"),
    donate_argnums=(3,))
def _fuse_reclaim(spec, layout, bufs, carry, sizes, use_gang_valid):
    """Stage 4: the reclaim state machine from the post-preempt carry.
    Job validity is re-derived on device (valid_task_num falls only via
    evictions: RELEASING counts as neither allocated nor pending), and the
    queue/job heaps are rebuilt in the serial registration order under the
    carried proportion/drf keys."""
    import jax.numpy as jnp
    from jax import lax

    from volcano_tpu.ops import evict as evict_mod
    from volcano_tpu.ops import rounds as rounds_mod

    qb, jcap, qh = sizes
    enc = rounds_mod.unpack_layout(layout, bufs)
    skip = carry["skip"]
    p_next = evict_mod._live_next(~skip)
    live_job = _live_job_mask(enc, p_next)
    j_total = enc["job_prio"].shape[0]

    evicted = jnp.zeros(j_total, jnp.int32).at[enc["vic_job"]].add(
        (enc["vic_valid"] & ~carry["alive"]).astype(jnp.int32))
    elig = enc["f_elig0"]
    if use_gang_valid:
        elig = elig & ((enc["f_vtn0"] - evicted) >= enc["job_min_av"])

    less_j = evict_mod._job_less(
        spec, enc, {"ready": carry["ready"], "job_alloc": carry["job_alloc"]})
    less_q = evict_mod._queue_less(
        spec, enc, {"queue_alloc": carry["queue_alloc"]})
    ev_jobs = enc["f_ev_jobs"]
    ev_qrow = enc["f_ev_qrow"]
    eb = ev_jobs.shape[0]
    elig_i = (ev_jobs >= 0) & elig[jnp.clip(ev_jobs, 0, j_total - 1)]
    live_i = elig_i & live_job[jnp.clip(ev_jobs, 0, j_total - 1)]

    def body(i, c):
        heap, hsize, qheap, qhsize, qpushed = c
        j = ev_jobs[i]
        q = jnp.clip(ev_qrow[i], 0, qb - 1)
        do_q = elig_i[i] & ~qpushed[q]

        def push_q(c):
            heap, hsize, qheap, qhsize, qpushed = c
            qrow, qsz = evict_mod._heap_push(qheap, qhsize, q, less_q)
            return heap, hsize, qrow, qsz, qpushed

        c = lax.cond(do_q, push_q, lambda x: x,
                     (heap, hsize, qheap, qhsize, qpushed))
        heap, hsize, qheap, qhsize, qpushed = c
        qpushed = qpushed.at[q].max(do_q)

        def push_j(hv):
            heap, hsize = hv
            rowv, nsz = evict_mod._heap_push(heap[q], hsize[q], j, less_j)
            return heap.at[q].set(rowv), hsize.at[q].set(nsz)

        heap, hsize = lax.cond(live_i[i], push_j, lambda x: x,
                               (heap, hsize))
        return heap, hsize, qheap, qhsize, qpushed

    heap, hsize, qheap, qhsize, _ = lax.fori_loop(
        0, eb, body,
        (jnp.zeros((qb, jcap), jnp.int32), jnp.zeros(qb, jnp.int32),
         jnp.zeros(qh, jnp.int32), jnp.int32(0), jnp.zeros(qb, bool)))

    enc2 = dict(enc, p_next=p_next)
    st = dict(
        used=carry["used"], cnt=carry["cnt"], alive=carry["alive"],
        ready=carry["ready"], wait=carry["wait"],
        job_alloc=carry["job_alloc"], queue_alloc=carry["queue_alloc"],
        ptr=enc["job_task_start"],
        heap=heap, hsize=hsize,
        qheap=qheap, qhsize=qhsize,
        **evict_mod._log0(spec),
        rr=jnp.int32(0),
        p_done=skip,
        victims=jnp.int32(0), attempts=jnp.int32(0),
        fail=jnp.bool_(False), underflow=jnp.bool_(False),
        steps=jnp.int32(0),
    )
    st = evict_mod.reclaim_machine(spec, enc2, st)
    return evict_mod.evict_tail(st)


# ---------------------------------------------------------------------------
# host driver
# ---------------------------------------------------------------------------


def _split_chain(names: Tuple[str, ...]):
    """(prefix, chain) when names embed a fusable suffix, else None.

    chain = "allocate" + an order-respecting subsequence of
    backfill/preempt/reclaim that contains "preempt"."""
    if "allocate" not in names:
        return None
    i = names.index("allocate")
    prefix, chain = list(names[:i]), list(names[i:])
    rest = chain[1:]
    order = [a for a in _EVICT_ORDER if a in rest]
    if rest != order or "preempt" not in rest:
        return None
    return prefix, chain


def try_run(ssn, names) -> Optional[Dict[str, float]]:
    """Run the session's action chain through the fused dispatcher.

    Returns the per-action timing dict, or None when the quick gates say
    this session cannot fuse at all (the caller then runs the plain
    per-action loop — byte-for-byte the pre-fuse path)."""
    if os.environ.get("VOLCANO_TPU_FUSE", "1") == "0":
        return None
    if os.environ.get("VOLCANO_TPU_EVICT", "1") == "0":
        return None
    solver = getattr(ssn, "batch_allocator", None)
    if solver is None or solver.mode not in ("rounds", "auto"):
        return None
    split = _split_chain(tuple(names))
    if split is None:
        return None
    prefix, chain = split

    action_ms: Dict[str, float] = {}
    _per_action(ssn, prefix, action_ms)
    _fuse_or_fallback(ssn, chain, action_ms)
    return action_ms


def _per_action(ssn, names: List[str], action_ms: Dict[str, float]) -> None:
    from volcano_tpu.scheduler.framework.framework import run_action

    for name in names:
        run_action(ssn, name, action_ms)


def _note_fuse_fallback(prof: dict, reason: str) -> None:
    """Profile record + process-wide fallback counter (the sim auditor
    budgets fuse-fallback RATES per scenario, ROADMAP item 4)."""
    from volcano_tpu.scheduler import metrics

    prof["fuse_fallback"] = reason
    metrics.register_fallback("fuse")


def _fuse_or_fallback(ssn, chain: List[str],
                      action_ms: Dict[str, float]) -> None:
    """Attempt the fused chain; any envelope miss records `fuse_fallback`
    and runs the (remaining) actions per-action. The allocate action's
    span holds the whole attempt: a declined or failed attempt runs the
    plain allocate inside it, so allocate is timed once either way."""
    from volcano_tpu.scheduler.framework.framework import action_span
    from volcano_tpu.scheduler.framework.plugins import get_action

    prof = ssn.batch_allocator.profile
    with action_span("allocate", action_ms):
        try:
            fused = _fused_allocate(ssn, chain)
        except Exception as e:  # pragma: no cover - device/compile failure
            logger.exception("fused session dispatch failed; falling back")
            _note_fuse_fallback(prof, f"fused dispatch error: {e}")
            fused = None
        if fused is None:
            get_action("allocate").execute(ssn)
    rest = [n for n in chain if n != "allocate"]
    if fused is None or fused.get("invalid"):
        _per_action(ssn, rest, action_ms)
        return
    try:
        _replay_stages(ssn, chain, action_ms, fused)
    except Exception as e:  # pragma: no cover - replay failure
        logger.exception("fused stage replay failed; falling back")
        _note_fuse_fallback(prof, f"fused dispatch error: {e}")
        _per_action(ssn, [n for n in rest if n not in action_ms],
                    action_ms)


def _fused_allocate(ssn, chain: List[str]) -> Optional[dict]:
    """Gate, encode and dispatch the whole chain, then apply the allocate
    stage. None: the session left the envelope before anything was
    applied (reason recorded). Otherwise the in-flight later stages, with
    ``invalid`` set when the allocate stage's serial residue pass
    invalidated them."""
    from volcano_tpu.ops import evict as evict_mod

    solver = ssn.batch_allocator
    prof = solver.profile

    prep = solver._prepare(ssn)
    if prep is None or prep["mode"] != "rounds" or prep["staged"] is None:
        # sub-threshold / unknown-plugin / encoder-fallback sessions run
        # the per-action path (allocate's own fallback ladder applies);
        # _prepare already recorded the reason
        _note_fuse_fallback(prof, prof.get(
            "fallback", "allocate not in packed rounds mode"))
        return None
    enc = prep["enc"]
    reason = None
    if enc.residue_count:
        reason = f"{enc.residue_count} residue tasks (serial pass runs " \
                 f"between actions)"
    elif enc.has_releasing:
        reason = "releasing capacity (serial pipeline pass runs " \
                 "between actions)"
    elif enc.spec.use_exclusion:
        reason = "exclusion-group workloads (resident affinity would " \
                 "poison the post-allocate evict views)"
    elif len(enc.resource_names) != 2:
        reason = "scalar resource dimensions not modeled by evict stages"
    elif set(ssn.job_valid_fns) - {"gang"}:
        reason = f"unsupported job-valid plugins: " \
                 f"{sorted(set(ssn.job_valid_fns) - {'gang'})}"
    if reason is None:
        try:
            with trace.span("evict.plan", kind="fused") as sp:
                plan = evict_mod._EvictPlan(ssn, "preempt", fused=True)
                bf = evict_mod._BackfillPlan(ssn, view=plan.view) \
                    if "backfill" in chain else None
                if bf is not None:
                    bf.note(sp)
        except evict_mod._Unsupported as e:
            reason = str(e)
        else:
            if plan.trivial:
                reason = "no pre-action preemptor candidates"
    if reason is not None:
        _note_fuse_fallback(prof, reason)
        return None
    return _run_fused(ssn, chain, prep, plan, bf)


def _build_maps(prep, plan, bf):
    """Host-side index maps between the rounds axes and the evict/backfill
    axes (uid/name joins; every padded slot maps to -1)."""
    enc = prep["enc"]
    arrays = prep["arrays"]
    tb_r = int(np.asarray(arrays["task_cls"]).shape[0])
    jb_r = int(np.asarray(arrays["job_task_start"]).shape[0])
    nb_r = int(np.asarray(arrays["node_alloc"]).shape[0])

    cand_of = {t.uid: i for i, t in enumerate(plan.p_tasks)}
    r2e_task = np.full(tb_r, -1, np.int32)
    for i, t in enumerate(enc.task_infos):
        r2e_task[i] = cand_of.get(t.uid, -1)
    r2e_job = np.full(jb_r, -1, np.int32)
    for i, job in enumerate(enc.job_infos):
        r2e_job[i] = plan.jidx.get(job.uid, -1)
    node_of = {name: i for i, name in enumerate(plan.node_names)}
    r2e_node = np.full(nb_r, -1, np.int32)
    for i, name in enumerate(enc.node_names):
        r2e_node[i] = node_of.get(name, -1)
    maps = dict(r2e_task=r2e_task, r2e_job=r2e_job, r2e_node=r2e_node,
                e_job_queue=np.asarray(plan.arrays["job_queue"], np.int32))
    bmaps = None
    if bf is not None and not bf.trivial:
        tb_b = int(np.asarray(bf.arrays["b_sig"]).shape[0])
        b2cand = np.full(tb_b, -1, np.int32)
        b_ejob = np.full(tb_b, -1, np.int32)
        for i, t in enumerate(bf.tasks):
            b2cand[i] = cand_of.get(t.uid, -1)
            b_ejob[i] = plan.jidx.get(t.job, -1)
        bmaps = dict(b2cand=b2cand, b_ejob=b_ejob)
    return maps, bmaps


def _run_fused(ssn, chain, prep, plan, bf) -> dict:
    """Dispatch the whole chain, then apply the allocate stage while the
    later stages still run; returns what ``_replay_stages`` needs."""
    from volcano_tpu.ops import evict as evict_mod
    from volcano_tpu.scheduler.actions import allocate as allocate_mod
    from volcano_tpu.utils import devprof

    solver = ssn.batch_allocator
    prof = solver.profile
    prof["fuse"] = 1
    prof["fuse_stages"] = list(chain)

    # under a mesh the evict encodes stage exactly like the sharded
    # rounds encode: node-axis arrays padded to the device multiple and
    # shipped per-shard beside the packed groups (the index MAPS stay
    # replicated — they are gathered by replicated task/assign vectors)
    mesh = solver.mesh
    with trace.span("pack", tag="fuse_maps"):
        maps, bmaps = _build_maps(prep, plan, bf)
        mlayout, mbufs = evict_mod._pack(maps, "fuse_maps")
    with trace.span("h2d", tag="fuse_maps"):
        mstaged = evict_mod._stage(mbufs, prof, mesh=mesh)
    elayout, estaged = evict_mod._pack_staged(
        plan.arrays, "fuse_ev", mesh, prof)
    do_backfill = bf is not None and not bf.trivial
    if do_backfill:
        blayout, bstaged = evict_mod._pack_staged(
            bf.arrays, "fuse_bf", mesh, prof)
        with trace.span("pack", tag="fuse_bmaps"):
            bml, bmb = evict_mod._pack(bmaps, "fuse_bmaps")
        with trace.span("h2d", tag="fuse_bmaps"):
            bmstaged = evict_mod._stage(bmb, prof, mesh=mesh)

    # jit-static stage sizes, all off the plan's bucket ladder (VT002)
    fs = plan.fuse_sizes
    sizes_a = (fs["n"], fs["jb"], fs["qb"], fs["tb"])
    sizes_p = (fs["qp"], fs["jcap"], fs["ju"])
    sizes_r = (fs["qb"], fs["jcap"], fs["qh"])
    use_gang_valid = "gang" in ssn.job_valid_fns

    # --- dispatch the whole chain eagerly (device-to-device carries) ------
    with trace.span("dispatch", stages=len(chain)):
        packed_a, carry = _fuse_alloc(
            prep["spec"], prep["layout"], prep["staged"],
            mlayout, mstaged, sizes_a)
        if do_backfill:
            assign_bf, carry = _fuse_backfill(
                bf.spec, blayout, bstaged, bml, bmstaged, carry)
        packed_p, carry = _fuse_preempt(
            plan.spec, elayout, estaged, carry, sizes_p)
        # the adoption candidate is taken BEFORE any further donation: a
        # reclaim stage consumes the carry (donate_argnums), so only a
        # preempt-terminal chain has a live full-state carry left to adopt
        adopt_carry = None if "reclaim" in chain else carry
        if "reclaim" in chain:
            packed_r = _fuse_reclaim(
                plan.reclaim_spec, elayout, estaged, carry, sizes_r,
                use_gang_valid)
        # start every D2H copy now; waits below run in stage order while
        # later stages still execute
        wait_a = devprof.start_fetch(packed_a)
        wait_bf = devprof.start_fetch(assign_bf) if do_backfill else None
        wait_p = devprof.start_fetch(packed_p)
        wait_r = devprof.start_fetch(packed_r) if "reclaim" in chain \
            else None

    # --- stage 1: allocate apply (overlaps the evict stages' compute) -----
    out_a = wait_a()
    assign, meta = solver.parse_packed(out_a)
    solver.apply_packed(ssn, prep, np.asarray(assign), meta)
    needs_residue = bool(prof.get("residue")) or (
        prof.get("has_releasing") and
        prof.get("tasks", 0) > prof.get("placed", 0))
    allocate_mod.finish_batched(ssn, solver)
    if needs_residue:
        # the serial residue pass just mutated session state the remaining
        # device stages never saw: their results are invalid — discard
        # them and run the rest per-action (nothing else was applied)
        _note_fuse_fallback(prof, "allocate residue retry invalidated "
                                  "the fused evict stages")
        return {"invalid": True}
    fused = dict(prep=prep, plan=plan, bf=bf, do_backfill=do_backfill,
                 wait_bf=wait_bf, wait_p=wait_p, wait_r=wait_r)
    # adopt_carry is None on every path where _fuse_reclaim donated the
    # carry (both sides test the same '"reclaim" in chain'), so this alias
    # only outlives a preempt-terminal chain:
    # vclint: disable=VT012 - adopt_carry proven None when the carry was donated
    fused["adopt_carry"] = adopt_carry
    return fused


def _replay_stages(ssn, chain, action_ms, fused: dict) -> None:
    """Replay the backfill, preempt and reclaim stages' results in order,
    each under its action's span."""
    from volcano_tpu.scheduler.framework.framework import (
        action_span, run_action)
    from volcano_tpu.scheduler.framework.plugins import get_action

    plan = fused["plan"]
    # --- stage 2: backfill replay ----------------------------------------
    if "backfill" in chain:
        with action_span("backfill", action_ms):
            if fused["do_backfill"]:
                fused["bf"].consume(fused["wait_bf"]())
            else:
                ssn.batch_allocator.profile["evict_backfill"] = {
                    "trivial": True}

    # --- stage 3: preempt op-log replay ----------------------------------
    with action_span("preempt", action_ms):
        ok = plan.consume(fused["wait_p"](), kind="preempt")
        if not ok:
            # consume recorded the reason and applied nothing; the
            # per-action rerun owns preempt AND reclaim (the fused reclaim
            # consumed a carry whose preempt half never landed)
            get_action("preempt").execute(ssn)
    if not ok:
        if "reclaim" in chain:
            run_action(ssn, "reclaim", action_ms)
        return

    # --- stage 4: reclaim op-log replay ----------------------------------
    if "reclaim" in chain:
        with action_span("reclaim", action_ms):
            if not plan.consume(fused["wait_r"](), kind="reclaim"):
                get_action("reclaim").execute(ssn)
    elif fused["adopt_carry"] is not None:
        # the chain ended at preempt, so its final carry was NOT donated
        # into a further stage: the post-chain node used/cnt it holds ARE
        # the cluster's next accounting state on device — hand them to the
        # standing replica instead of discarding them (ops/replica.py
        # adoption: the next serve skips re-scattering rows only this
        # chain's own placements changed)
        _offer_carry(ssn, fused["prep"], plan, fused["adopt_carry"])


def _offer_carry(ssn, prep, plan, carry) -> None:
    """Adopt a fused chain's final full-state carry into the device
    replica, when the evict node layout coincides with the rounds layout
    (same names, same order, same padded extent — the adopt() shape gate
    re-checks the extent); anything else is silently kept on the scatter
    path, which is always correct."""
    from volcano_tpu.ops import replica as replica_mod

    rep = replica_mod.get(getattr(ssn, "cache", None), create=False) \
        if getattr(ssn, "cache", None) is not None else None
    if rep is None:
        return
    enc = prep["enc"]
    names = list(plan.node_names)
    if names != list(enc.node_names)[:len(names)]:
        return
    rep.adopt({"node_used": carry["used"], "node_cnt": carry["cnt"]})
