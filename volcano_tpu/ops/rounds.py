"""Rounds-mode throughput solver: bulk-synchronous batched placement.

The parity scan (kernels.solve_allocate) reproduces the serial loop's
bindings bit-for-bit but pays one sequential device step per task — latency-
bound at ~50k steps for the headline config. This module is the TPU-native
redesign for scale (SURVEY.md §7 "hard parts": solve in *rounds* — batch-
score all pending tasks, commit gang blocks, re-score deltas on device):

Round (all on device, one jitted while_loop):
1. job-order keys -> job rank (lexsort over J), task rank = (job rank, task
   order); tasks in overused queues sit the round out (proportion.go:201).
2. (K x N) fused feasibility ∧ epsilon-fit ∧ pod-count masks and
   binpack+nodeorder scores over task equivalence CLASSES (K ~ #templates
   << T), carried ACROSS rounds in the while_loop state with dirty-column
   rescoring: a round commits onto a small node set, so the next round
   recomputes only the touched columns (a [K, dirty_k] gather-scatter)
   instead of the full chunked sweep. Node CANDIDATES come from a bounded
   top-k window per class (`lax.top_k` — a bit-identical prefix of the
   stable argsort order, ties included); each class's feasible nodes are
   ordered by descending score and the class's i-th active task takes the
   node where i falls in cumulative estimated capacity — rotated within
   equal-score groups for spreading policies, sequential (packing) when
   binpack is on, with per-class demand-share apportioning. A per-class
   COVERAGE bit proves the windowed answer equals the full-width one
   (window holds the whole feasible set, or every task's slot and final
   position land strictly before the window's possibly-truncated last
   equal-score group); any uncovered class gets a full-width nomination
   that round, so placements are bit-identical to full-width sweeps.
3. conflict resolution: sort tasks by (chosen node, task rank); per-node
   *prefix acceptance* — the longest priority-prefix whose cumulative request
   fits idle (cumsum ≤ idle + eps reproduces the serial per-step epsilon
   exactly) and pod slots; capacity estimates in step 2 are advisory only.
4. scatter-commit: idle/used/pod-count, job/queue/namespace allocation; the
   touched node columns become the next round's dirty set.
Rounds repeat while any task lands. Then a gang-rollback pass retires the
worst-ranked job still short of min_available (statement.go Discard
semantics) and rounds resume on the freed capacity — a fixpoint loop that
terminates because each rollback retires exactly one job (rollback marks
the freed columns dirty; a large rollback overflows the dirty budget and
triggers a full rescore, never a stale score).

Documented divergences from the serial oracle (and hence from parity mode):
scores are computed against round-start state (bulk-synchronous), fair-share
interleaving is round- rather than visit-grained, overused queues re-enter
when a rollback drops them below deserved, weighted-DRF NAMESPACE ordering
is not applied to the job rank (_job_rank keys on tie-rank/priority/gang/
drf-share only; ns_alloc is tracked in state but does not reorder jobs —
namespace fairness under contention is round-granular at best), the
reference's adaptive node-sampling window does not apply: the candidate
window here is a PRUNING device with an exactness fallback, not a sampling
device — every task still sees, in effect, every node, and per-cycle
placement count may fall short of the serial oracle by a bounded margin:
under tight selector/taint contention the bulk rounds can consume
a constrained node pool with a different task mix than the serial visit
order, stranding a straggler (retried next cycle). Fuzz-bounded at
max(2, serial//50) tasks — see tests/test_rounds_scale.py and
docs/DESIGN.md §3.

Invariants preserved (asserted by tests/test_rounds.py): every placement is
feasible per the predicate mask and epsilon arithmetic, no node exceeds idle
or pod capacity, gangs are all-or-nothing, queue `deserved` caps are
respected through the overused gate. Window-vs-full-width bit-identity is
fuzz-pinned by tests/test_candidate_window.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from volcano_tpu.ops.kernels import (
    MIN_MILLI_SCALAR,
    SolveSpec,
    _share,
    fused_scores,
)

CHUNK = 128

# per-round profile exported through the packed single-fetch result:
# node-count header (sizes the touched-node mask that precedes the tail),
# placed-per-round histogram slots plus the scalar tail (round-count limbs,
# tail_placed, full-sweep round count, capped flag)
PROF_SLOTS = 64
PROF_TAIL = 6 + PROF_SLOTS


def _job_rank(spec: SolveSpec, enc, job_placed, job_alloc):
    """[J] dense rank from the tiered job-order keys (low = first)."""
    keys = [enc["job_tie_rank"]]
    for name in reversed(spec.job_order_keys):
        if name == "priority":
            keys.append(-enc["job_priority"])
        elif name == "gang":
            ready = (enc["job_ready_base"] + job_placed) >= enc["job_min_available"]
            keys.append(ready.astype(jnp.int32))
        elif name == "drf":
            keys.append(_share(job_alloc, enc["drf_total"][None, :],
                               enc["drf_present"][None, :]))
    order = jnp.lexsort(tuple(keys))  # last key primary
    j = enc["job_tie_rank"].shape[0]
    return jnp.zeros(j, jnp.int32).at[order].set(jnp.arange(j, dtype=jnp.int32))


def _score_block(spec: SolveSpec, enc, req, initreq, sig, nz_cpu, nz_mem,
                 has_pod, exl, idle_c, used_c, cnt_c, occ_c, sigmask_c,
                 nmax_c, alloc_c, aff_c):
    """Masked fused feasibility+score block for a batch of class ROWS over a
    batch of node COLUMNS (the full axis, or a dirty-column gather): -inf
    where the class cannot place on the node, the fused binpack+nodeorder
    score elsewhere. Every op is column-separable (elementwise per node, or
    a reduction over the static R axis), so recomputing a gathered column
    is bit-identical to gathering a full recompute — the property that lets
    the carried score matrix be patched instead of rebuilt."""
    eps = enc["eps"]
    is_scalar = enc["is_scalar"]
    neg = jnp.array(-jnp.inf, idle_c.dtype)
    # epsilon fit of init requests against idle (resource_info.go:267)
    le = initreq[:, None, :] < idle_c[None, :, :] + eps[None, None, :]
    skip = is_scalar[None, None, :] & (initreq[:, None, :] <= MIN_MILLI_SCALAR)
    mask = jnp.all(le | skip, axis=-1) & sigmask_c[sig]       # [rows, M]
    if spec.check_pod_count:
        mask = mask & ((cnt_c[None, :] < nmax_c[None, :]) | ~has_pod[:, None])
    if spec.use_exclusion:
        # exclusion-group classes: nodes already holding a group member
        # (resident at encode, or committed in an earlier round) are
        # infeasible for the whole class
        occ = occ_c[jnp.maximum(exl, 0)]                      # [rows, M]
        mask = mask & ~(occ & (exl >= 0)[:, None])
    score = fused_scores(spec, enc, used_c, req, nz_cpu, nz_mem, sig,
                         alloc=alloc_c, aff=aff_c)
    return jnp.where(mask, score, neg)


def _refresh_scores(spec: SolveSpec, enc, idle, used, cnt, excl_occ):
    """Full-width recompute of the carried [K, N] masked score matrix,
    chunked over class rows to bound the [rows, N, R] fit/score
    temporaries. Rows are computed for EVERY class, live or not — overused
    queues can re-enter after a rollback and revive a class, and a revived
    class must find current scores, not a stale skip."""
    k_total = enc["cls_req"].shape[0]
    n_total = idle.shape[0]
    chunk = min(CHUNK, k_total)
    n_chunks = k_total // chunk

    def one_chunk(ci):
        sl = ci * chunk

        def sli(name):
            return lax.dynamic_slice_in_dim(enc[name], sl, chunk)

        return _score_block(
            spec, enc, sli("cls_req"), sli("cls_initreq"), sli("cls_sig"),
            sli("cls_nz_cpu"), sli("cls_nz_mem"), sli("cls_has_pod"),
            sli("cls_excl") if spec.use_exclusion else None,
            idle, used, cnt, excl_occ, enc["sig_mask"],
            enc["node_max_tasks"], enc["node_alloc"], enc["affinity_score"])

    if n_chunks > 1:
        return lax.map(one_chunk, jnp.arange(n_chunks)).reshape(
            k_total, n_total)
    return one_chunk(0)


def _rescore_dirty(spec: SolveSpec, enc, idle, used, cnt, excl_occ,
                   scores, dirty):
    """Dirty-column rescoring: scatter-recompute the carried score matrix
    for the <= dirty_k node columns the previous round touched
    (commit/rollback writes to idle/used/cnt/occupancy). Gathers the
    column state, recomputes the [K, dirty_k] block with the same
    column-separable kernel the full sweep uses, and scatters it back.
    Padding slots of the nonzero gather alias column 0 — they rewrite
    identical values, so duplicate scatter writes are benign."""
    cols = jnp.nonzero(dirty, size=spec.dirty_k, fill_value=0)[0].astype(
        jnp.int32)
    block = _score_block(
        spec, enc, enc["cls_req"], enc["cls_initreq"], enc["cls_sig"],
        enc["cls_nz_cpu"], enc["cls_nz_mem"], enc["cls_has_pod"],
        enc["cls_excl"] if spec.use_exclusion else None,
        idle[cols], used[cols], cnt[cols],
        excl_occ[:, cols] if spec.use_exclusion else None,
        enc["sig_mask"][:, cols], enc["node_max_tasks"][cols],
        enc["node_alloc"][cols], enc["affinity_score"][:, cols])
    return scores.at[:, cols].set(block)


def _cap_walk(spec: SolveSpec, enc, order, score_ord, req, exl, has_pod,
              frac, idle, cnt, t_cap):
    """Capacity estimates and equal-score group structure along an ORDERED
    candidate axis — either the full stable-argsort order or its lax.top_k
    prefix window (top_k breaks ties toward lower indices exactly like the
    stable sort, so the window IS a prefix, ties included).

    order/score_ord: [rows, W]. The [rows, W, R] capacity gather replaces
    the old full-axis [C, N, R] materialization: capacity is only computed
    for nominated nodes. Returns (ccap, g_start, g_size, ccap_before), all
    [rows, W]; per-(class, node) arithmetic is identical to the full-width
    walk, so windowed values are exact prefixes of it.

    Why both mechanisms (capacity walk + tie rotation): score-concentrating
    policies (binpack) would otherwise send every task of a class to the
    one best node and the bulk-synchronous round fills a single node's
    prefix (measured: 89 rounds at cfg2), while spreading policies
    (least-requested) tie whole groups of nodes whose serial behavior is
    round-robin; the capacity walk handles the former, the within-group
    rotation the latter. _resolve's exact prefix acceptance cleans up the
    optimistic tail."""
    rows, width = order.shape
    feas = score_ord > jnp.array(-jnp.inf, score_ord.dtype)
    idle_w = idle[order]                                  # [rows, W, R]
    eps = enc["eps"]
    # per-(class, node) capacity estimate from per-dim idle/req
    # (advisory only — real feasibility stays with _resolve)
    safe_req = jnp.maximum(req, eps[None, :])
    cap_dim = idle_w / safe_req[:, None, :]               # [rows, W, R]
    cap = jnp.min(
        jnp.where((req > 0)[:, None, :], cap_dim, jnp.inf), axis=-1)
    big = jnp.asarray(float(t_cap), idle.dtype)
    cap = jnp.minimum(jnp.where(jnp.isinf(cap), big, cap), big)
    if spec.use_binpack:
        cap = cap * frac[:, None]
    if spec.use_exclusion:
        # at most one group member per node, ever
        cap = jnp.where((exl >= 0)[:, None], jnp.minimum(cap, 1.0), cap)
    if spec.check_pod_count:
        pod_room = (enc["node_max_tasks"] - cnt)[order].astype(cap.dtype)
        cap = jnp.where(has_pod[:, None], jnp.minimum(cap, pod_room), cap)
    cap = jnp.where(feas, jnp.floor(cap), 0.0)
    cap = jnp.maximum(cap, jnp.where(feas, 1.0, 0.0))  # >=1 if feasible
    cap_i = cap.astype(jnp.int32)
    # SATURATING prefix sum at t_cap (> any rank): a plain int32 cumsum can
    # wrap at N*(T+1); saturating add of non-negatives is associative, so
    # the scan stays exact and monotone with every partial <= 2*t_cap
    ccap = lax.associative_scan(
        lambda a, b: jnp.minimum(a + b, jnp.int32(t_cap)), cap_i, axis=1)

    # equal-score groups along the ordered axis (for the rotation)
    pos = jnp.broadcast_to(
        jnp.arange(width, dtype=jnp.int32)[None, :], (rows, width))
    is_start = jnp.concatenate(
        [jnp.ones((rows, 1), bool),
         score_ord[:, 1:] != score_ord[:, :-1]], axis=1)
    g_start = lax.cummax(jnp.where(is_start, pos, 0), axis=1)
    starts = jnp.where(is_start, pos, jnp.int32(width))
    # next group start AFTER j: suffix-min of starts, shifted left
    sfx = jnp.flip(lax.cummin(jnp.flip(starts, axis=1), axis=1), axis=1)
    g_end = jnp.concatenate(
        [sfx[:, 1:], jnp.full((rows, 1), width, jnp.int32)], axis=1)
    g_size = g_end - g_start
    ccap_before = jnp.where(
        g_start > 0,
        jnp.take_along_axis(ccap, jnp.maximum(g_start - 1, 0), axis=1), 0)
    return ccap, g_start, g_size, ccap_before


def _nominate_full(spec: SolveSpec, enc, scores, idle, cnt, cls_frac, t_cap):
    """Full-width nomination: stable argsort over all N columns plus the
    capacity walk, chunked over class rows (bounds the [rows, N, R]
    gather). Runs when candidate windows are disabled, and as the
    exactness fallback on rounds where some class's window lacks
    coverage."""
    k_total, n_total = scores.shape
    chunk = min(CHUNK, k_total)
    n_chunks = k_total // chunk

    def one_chunk(ci):
        sl = ci * chunk

        def sli(name):
            return lax.dynamic_slice_in_dim(enc[name], sl, chunk)

        sc = lax.dynamic_slice_in_dim(scores, sl, chunk)
        order = jnp.argsort(-sc, axis=-1, stable=True).astype(jnp.int32)
        score_ord = jnp.take_along_axis(sc, order, axis=-1)
        ccap, g_start, g_size, ccap_before = _cap_walk(
            spec, enc, order, score_ord, sli("cls_req"),
            sli("cls_excl") if spec.use_exclusion else None,
            sli("cls_has_pod"),
            lax.dynamic_slice_in_dim(cls_frac, sl, chunk)
            if spec.use_binpack else None,
            idle, cnt, t_cap)
        return order, ccap, g_start, g_size, ccap_before

    if n_chunks > 1:
        outs = lax.map(one_chunk, jnp.arange(n_chunks))
        return tuple(x.reshape(k_total, n_total) for x in outs)
    return one_chunk(0)


def _excl_grank(enc, cls_live):
    """Rank of each class among its exclusion group's LIVE classes, lower
    class index first. Same-group classes (e.g. one anti-affinity
    deployment whose members differ in requests and are therefore
    SINGLETON classes) score near-identically and would all aim at the
    same argmax — one winner per (group, node) per round makes convergence
    crawl at ~group_size rounds (measured: 33 rounds on the affinity
    bench). Offsetting each class by this rank spreads the group over
    distinct ordered positions within ONE round; the winner scatter +
    occupancy mask still enforce mutual exclusion exactly. One stable
    argsort (group-major, index-ascending) + segmented prefix count —
    O(K log K), not a [K, K] compare."""
    exl_all = enc["cls_excl"]
    perm = jnp.argsort(exl_all, stable=True)
    sorted_gid = exl_all[perm]
    sorted_live = cls_live[perm].astype(jnp.int32)
    prefix = jnp.cumsum(sorted_live) - sorted_live  # live strictly before
    seg_start = jnp.concatenate(
        [jnp.ones(1, bool), sorted_gid[1:] != sorted_gid[:-1]])
    # prefix is non-decreasing, so cummax propagates each segment's
    # starting prefix down the segment
    seg_base = lax.cummax(jnp.where(seg_start, prefix, 0))
    return jnp.zeros(exl_all.shape[0], jnp.int32).at[perm].set(
        (prefix - seg_base).astype(jnp.int32))


def _rank_in_class(task_cls, active):
    """Rank of each ACTIVE task within its class, in flat order: sort by
    (class, inactive-last, flat index), take the position inside the
    (class, active) segment — O(T log T), no T x K blowup."""
    t_total = task_cls.shape[0]
    idxs = jnp.arange(t_total, dtype=jnp.int32)
    ordix = jnp.lexsort((idxs, ~active, task_cls))
    sorted_cls = task_cls[ordix]
    sorted_act = active[ordix]
    seg_start = jnp.concatenate(
        [jnp.ones(1, bool),
         (sorted_cls[1:] != sorted_cls[:-1])
         | (sorted_act[1:] != sorted_act[:-1])])
    start_idx = lax.cummax(jnp.where(seg_start, idxs, 0))
    return jnp.zeros(t_total, jnp.int32).at[ordix].set(idxs - start_idx)


def _select(spec: SolveSpec, enc, task_cls, active, rank, n_feas, grank,
            order, ccap, g_start, g_size, ccap_before):
    """Per-task node choice from an ordered per-class candidate axis of
    static width W (the full node axis, or a top-k window whose walk
    arrays are exact prefixes of the full ones).

    slot = first ordered position whose cumulative capacity exceeds the
    task's rank — a vectorized binary search over each task's class row:
    O(T log W) gathers instead of materializing a [T, W] comparison.
    Within equal-score groups the assignment rotates (spreading policies'
    serial behavior on tied nodes) unless binpack is enabled (packing
    fills node by node; serial binpack breaks round-start ties TOWARD the
    node it just filled). Exclusion classes spread by their group-live
    rank. Returns (choice, cons_choice, slot, final): slot is the raw
    capacity-walk position (un-clipped; == W when the walk ran past the
    axis), final the post-rotation/post-spread position the choice was
    gathered from — the windowed caller's coverage predicate runs on
    both. cons_choice is each task's class-best feasible node (the
    pre-capacity-walk argmax semantics), used by the stalemate-breaker
    round."""
    width = order.shape[1]
    tk = task_cls
    t_total = tk.shape[0]
    lo = jnp.zeros(t_total, jnp.int32)
    hi = jnp.full(t_total, width, jnp.int32)
    # interval [0, W] holds W+1 answers => W.bit_length() halvings cover it
    for _ in range(max(1, int(width).bit_length())):
        mid = (lo + hi) // 2
        go_right = ccap[tk, jnp.minimum(mid, width - 1)] <= rank
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    slot = lo
    # tasks whose rank exceeds total estimated capacity retry next round on
    # the refreshed state; clamp keeps the gathers in bounds
    overflow = slot >= n_feas[tk]
    slot_c = jnp.clip(slot, 0, width - 1)
    if spec.use_binpack and not spec.use_exclusion:
        final = slot_c
    else:
        gs = g_start[tk, slot_c]
        gz = jnp.maximum(g_size[tk, slot_c], 1)
        local = rank - ccap_before[tk, slot_c]
        rotated = gs + (jnp.maximum(local, 0) % gz)
        if spec.use_binpack:
            # exclusion classes are capped at one member per node, so the
            # packing walk would aim every group at the same first nodes
            # and bounce all but one per round (convergence crawl); rotate
            # THEM within tied groups, keep true packing for the rest
            is_excl = enc["cls_excl"][tk] >= 0
            final = jnp.where(is_excl, rotated, slot_c)
        else:
            final = rotated
    if spec.use_exclusion:
        is_exg = enc["cls_excl"][tk] >= 0
        spread = jnp.clip(final + grank[tk], 0,
                          jnp.maximum(n_feas[tk] - 1, 0))
        final = jnp.where(is_exg, spread, final)
    choice = order[tk, jnp.clip(final, 0, width - 1)]
    feasible = (n_feas[tk] > 0) & ~overflow & active
    cons_choice = jnp.where((n_feas[tk] > 0) & active, order[tk, 0], -1)
    return jnp.where(feasible, choice, -1), cons_choice, slot, final


def _seg_limbs(req_s, start_idx):
    """Segment-inclusive cumulative sums of int32 requests as two 15-bit
    limbs (hi, lo with lo < 2^15), exact for totals below 2^46.

    A single int32 cumsum over the flat task axis can wrap: 50k tasks of
    64-core requests put >2^31 milli-cpu in one segment, and a wrapped sum
    goes negative and passes the 'seg < bound' fit check — over-allocating
    the node. Naive cumsums of the SPLIT limbs wrap too (the lo-limb sum
    alone reaches 2^31 after ~2^16 max-size rows), so the prefix sums are
    built with a carry-normalizing associative scan: every partial keeps
    lo in [0, 2^15), and hi holds total>>15 — within int32 for any prefix
    total < 2^46 (70 billion cores / 64 EiB; the encoder falls back when
    the pending request, or a queue's allocation plus it, reaches that)."""

    def combine(a, b):
        ah, al = a
        bh, bl = b
        l = al + bl
        return ah + bh + (l >> 15), l & 0x7FFF

    chi, clo = lax.associative_scan(
        combine, (req_s >> 15, req_s & 0x7FFF), axis=0)
    prev = jnp.maximum(start_idx - 1, 0)
    has_base = (start_idx > 0)[:, None]
    base_hi = jnp.where(has_base, chi[prev], 0)
    base_lo = jnp.where(has_base, clo[prev], 0)
    # limb-wise subtraction with borrow: prefix pairs are normalized, so
    # dl in (-2^15, 2^15) and dh <= chi — no intermediate overflow
    dl = clo - base_lo
    dh = chi - base_hi
    borrow = (dl < 0).astype(jnp.int32)
    return dh - borrow, dl + (borrow << 15)


def _limbs_lt(seg_hi, seg_lo, bound):
    """Exact (seg_hi*2^15 + seg_lo) < bound for non-negative limb pairs;
    bounds <= 0 compare false (nothing non-negative is below them)."""
    b = jnp.maximum(bound, 0)
    return _pair_lt(seg_hi, seg_lo, b >> 15, b & 0x7FFF)


def _pair_lt(a_hi, a_lo, b_hi, b_lo):
    """Exact a < b for normalized limb pairs (lo in [0, 2^15))."""
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))


# queue bounds and the carried queue allocation are int32 limb pairs
# stacked on a trailing axis: [..., 0] = value >> 15, [..., 1] = value &
# 0x7FFF. A queue's deserved share may be as large as the cluster (past
# 2^31 MiB on 50k nodes of 64Gi), and float32 would round a 1e14-byte bound
# to 8 MiB; the encoder quantizes the bounds in float64 and ships limbs


def _limbs_add(a, d):
    """a + d for [..., 2] normalized limb pairs."""
    lo = a[..., 1] + d[..., 1]
    return jnp.stack([a[..., 0] + d[..., 0] + (lo >> 15), lo & 0x7FFF], -1)


def _limbs_sub(a, d):
    """a - d for [..., 2] normalized limb pairs, a >= d."""
    lo = a[..., 1] - d[..., 1]
    borrow = (lo < 0).astype(jnp.int32)
    return jnp.stack([a[..., 0] - d[..., 0] - borrow, lo + (borrow << 15)], -1)


def _limbs_sum(req_i, mask):
    """Exact sum of the masked non-negative int32 requests [T, R] as
    normalized limb pairs [R, 2].

    Each request is split into 17, 7 and 8 bits before the sum, so no
    partial sum wraps for T < 2^23 rows and totals below 2^46; the carries
    are then folded back into two 15-bit limbs."""
    v = jnp.where(mask[:, None], req_i, 0)
    hi, mid, low = (jnp.sum(x, axis=0, dtype=jnp.int32)
                    for x in (v >> 15, (v >> 8) & 0x7F, v & 0xFF))
    mid = mid + (low >> 8)
    return jnp.stack([hi + (mid >> 7), ((mid & 0x7F) << 8) | (low & 0xFF)],
                     -1)


def _queue_over(queue_alloc, bound, is_scalar):
    """[Q] bool: the queue's allocation is not below deserved + eps in some
    dimension (the negation of Resource.less_equal, proportion.go:201),
    exactly, on limb pairs."""
    hi, lo = queue_alloc[..., 0], queue_alloc[..., 1]
    le = _pair_lt(hi, lo, bound[..., 0], bound[..., 1])
    skip = is_scalar[None, :] & (hi == 0) & (lo <= MIN_MILLI_SCALAR)
    return ~jnp.all(le | skip, axis=-1)


def _resolve(spec: SolveSpec, enc, idle, cnt, choice, task_rank):
    """Per-node prefix acceptance: sort by (node, rank), accept the longest
    priority-prefix whose cumulative request fits. Returns accept [T] bool."""
    t_total = choice.shape[0]
    has_pod = enc["task_has_pod"]
    # conservative integer units (milli-cpu / MiB / milli-scalar): a float32
    # running cumsum over 50k tasks drifts past the 10 MiB memory epsilon at
    # ~1e14-byte magnitudes; two-limb int32 in these units is exact for any
    # aggregate (see _seg_limbs) and the ceil(req)/floor(idle) pairing can
    # only under-place by <1 unit, never over-allocate
    req_i = jnp.ceil(enc["task_req"] / enc["res_unit"][None, :]).astype(jnp.int32)
    idle_i = jnp.floor(idle / enc["res_unit"][None, :]).astype(jnp.int32)
    eps_i = (enc["eps"] / enc["res_unit"]).astype(jnp.int32)
    is_scalar = enc["is_scalar"]

    feas = choice >= 0
    # infeasible tasks sort to a trailing pseudo-node segment
    node_key = jnp.where(feas, choice, jnp.iinfo(jnp.int32).max)
    order = jnp.lexsort((task_rank, node_key))                # node primary
    ch_s = node_key[order]
    req_s = req_i[order]
    pod_s = has_pod[order] & (ch_s != jnp.iinfo(jnp.int32).max)

    seg_start = jnp.concatenate([jnp.ones(1, bool), ch_s[1:] != ch_s[:-1]])
    idx = jnp.arange(t_total)
    start_idx = lax.cummax(jnp.where(seg_start, idx, 0))
    seg_hi, seg_lo = _seg_limbs(req_s, start_idx)             # [T, R] incl. self

    node = jnp.clip(ch_s, 0, idle.shape[0] - 1)
    idle_s = idle_i[node]                                     # [T, R]
    # stepwise-epsilon equivalence: task k fits iff cumsum_k <= idle + eps
    le = _limbs_lt(seg_hi, seg_lo, idle_s + eps_i[None, :])
    skip = is_scalar[None, :] & (req_s <= MIN_MILLI_SCALAR)
    fits = jnp.all(le | skip, axis=-1) & (ch_s != jnp.iinfo(jnp.int32).max)

    cond = fits
    if spec.check_pod_count:
        # the pod-count cap is part of the predicates plugin; without it the
        # serial loop never checks len(node.tasks) (predicates.py:191)
        pod_rank = jnp.cumsum(pod_s.astype(jnp.int32))
        pod_base = jnp.where(start_idx > 0, pod_rank[jnp.maximum(start_idx - 1, 0)], 0)
        seg_pods = pod_rank - pod_base
        pods_ok = ~pod_s | (cnt[node] + seg_pods <= enc["node_max_tasks"][node])
        cond = fits & pods_ok

    # longest true-prefix per segment: no rejections before me in my segment
    rej = jnp.cumsum((~cond).astype(jnp.int32))
    rej_base = jnp.where(start_idx > 0, rej[jnp.maximum(start_idx - 1, 0)], 0)
    accept_s = cond & ((rej - rej_base - (~cond).astype(jnp.int32)) == 0)

    return jnp.zeros(t_total, bool).at[order].set(accept_s)


def _queue_budget(enc, queue_alloc, accept, task_rank, task_queue, task_job):
    """Job-granular queue fair-share cap inside a round.

    The serial loop checks Overused between job visits: a job is admitted
    while its queue's allocated <= deserved at the START of the job's turn,
    so queues overshoot deserved by at most one job block
    (proportion.go:201-212 + allocate.go:134-146). Reproduce that here: for
    accepted tasks ordered (queue, rank), a job's tasks survive iff
    queue_alloc + contributions of higher-ranked jobs in the same queue
    fit under deserved with the epsilon comparison. ``queue_alloc`` [Q, R,
    2] and ``enc["queue_bound_limbs"]`` (deserved + eps) are limb pairs.
    Returns the surviving accept mask [T] and each queue's admitted
    request [Q, R, 2], exact, for the carried allocation.
    """
    t_total = accept.shape[0]
    is_scalar = enc["is_scalar"]
    # same exact two-limb int32 units as _resolve (see _seg_limbs)
    unit = enc["res_unit"]
    req_i = jnp.ceil(enc["task_req"] / unit[None, :]).astype(jnp.int32)
    req = jnp.where(accept[:, None], req_i, 0)

    order = jnp.lexsort((task_rank, task_queue))  # queue primary
    req_s = req[order]
    q_s = task_queue[order]
    job_s = task_job[order]

    idx = jnp.arange(t_total)
    q_start = jnp.concatenate([jnp.ones(1, bool), q_s[1:] != q_s[:-1]])
    j_start = q_start | jnp.concatenate([jnp.ones(1, bool), job_s[1:] != job_s[:-1]])

    # exclusive-of-this-job, within-queue cumulative: segment cumsum over
    # the queue minus the segment cumsum over the job, shifted to the job
    # start (both limb-exact)
    q_base_idx = lax.cummax(jnp.where(q_start, idx, 0))
    j_base_idx = lax.cummax(jnp.where(j_start, idx, 0))
    seg_hi, seg_lo = _seg_limbs(req_s, q_base_idx)  # within-queue incl. self
    # value at the last position BEFORE my job started: 0 when my job opens
    # its queue segment, else the within-queue cumsum one row up (that row
    # is in my queue by construction)
    job_at_queue_start = q_start[j_base_idx][:, None]
    prev = jnp.maximum(j_base_idx - 1, 0)
    before_hi = jnp.where(job_at_queue_start, 0, seg_hi[prev])
    before_lo = jnp.where(job_at_queue_start, 0, seg_lo[prev])

    # total = queue_alloc + higher-ranked same-queue jobs, as limbs
    tot = _limbs_add(queue_alloc[q_s], jnp.stack([before_hi, before_lo], -1))
    tot_hi, tot_lo = tot[..., 0], tot[..., 1]
    bound = enc["queue_bound_limbs"][q_s]
    le = _pair_lt(tot_hi, tot_lo, bound[..., 0], bound[..., 1])
    skip = is_scalar[None, :] & (tot_hi == 0) & (tot_lo <= MIN_MILLI_SCALAR)
    ok = jnp.all(le | skip, axis=-1)
    # tot only grows along a queue's rows, so the admitted jobs are a
    # prefix of each queue segment (the serial loop stops a queue at its
    # first job over deserved); held as a prefix here, the queue's
    # admitted total is the within-queue cumsum at the prefix's last row
    bad = jnp.cumsum((~ok).astype(jnp.int32))
    bad_base = jnp.where(q_base_idx > 0, bad[jnp.maximum(q_base_idx - 1, 0)],
                         0)
    ok = bad == bad_base
    cnt = jnp.cumsum(ok.astype(jnp.int32))
    q_ids = jnp.arange(queue_alloc.shape[0], dtype=q_s.dtype)
    first = jnp.searchsorted(q_s, q_ids, side="left")
    end = jnp.searchsorted(q_s, q_ids, side="right")
    n_ok = jnp.where(
        end > first,
        cnt[jnp.maximum(end - 1, 0)]
        - jnp.where(first > 0, cnt[jnp.maximum(first - 1, 0)], 0), 0)
    last = jnp.clip(first + n_ok - 1, 0, t_total - 1)
    admitted = jnp.where((n_ok > 0)[:, None, None],
                         jnp.stack([seg_hi[last], seg_lo[last]], -1), 0)
    accept_s = accept[order] & ok
    return jnp.zeros(t_total, bool).at[order].set(accept_s), admitted


def unpack_layout(layout, bufs):
    """Static-slice unpack of solver._pack buffers into the enc dict —
    free under XLA fusion; shared by the packed entry below, the evict
    packed entry, and the session-fused stages (ops/session_fuse.py).

    Packed-group buffers carry dotted keys ("group.kind"); under a mesh
    the node-axis arrays ride BESIDE the packed groups as individually
    sharded buffers under their plain array names (ops/shard.py
    stage_node_arrays) — merged here, so every packed entrypoint serves
    both the single-device and the sharded layout without signature
    changes (the single-device path simply has no plain keys)."""
    enc = {
        name: lax.slice_in_dim(bufs[key], off, off + size).reshape(shape)
        for name, key, off, size, shape in layout
    }
    for key in bufs:
        if "." not in key:
            enc[key] = bufs[key]
    return enc


def pack_result(enc, raw):
    """Pack a solve_rounds result tuple into the ONE fetchable array:
    assign, the touched-node mask (which node columns the windowed solve
    actually gathered — the node half of the read-set descriptor the
    pipeline's speculative seal records), then a PROF_TAIL-long profile
    tail (node-count header sizing the mask, round-counter limbs,
    tail_placed, full-sweep round count, capped flag, the placed-per-round
    histogram); int16 when the node count allows (halves the downlink —
    assign values are node indices or -1/-2; the node count fits the int16
    limb by the same <= 32766 condition that picks it)."""
    (assign, n_rounds, tail_placed, full_sweeps, capped, placed_hist,
     touched) = raw
    n_total = enc["node_idle"].shape[0]
    # tail_placed is bounded by 8*round_min_progress+16; clamp everything to
    # the int16 limb's range so an extreme config can't silently wrap a
    # PROFILE counter (assignments are unaffected)
    tail = jnp.concatenate([
        jnp.stack([jnp.int32(n_total), n_rounds & 0x7FFF, n_rounds >> 15,
                   jnp.minimum(tail_placed, 0x7FFF),
                   jnp.minimum(full_sweeps, 0x7FFF),
                   capped.astype(jnp.int32)]),
        jnp.minimum(placed_hist, 0x7FFF)])
    if n_total <= 32766:  # static (trace-time) shape decision
        return jnp.concatenate([assign.astype(jnp.int16),
                                touched.astype(jnp.int16),
                                tail.astype(jnp.int16)])
    return jnp.concatenate([assign, touched.astype(assign.dtype), tail])


@functools.partial(jax.jit, static_argnames=("spec", "layout"))
def solve_rounds_packed(spec: SolveSpec, layout, bufs):
    """solve_rounds over packed (group x dtype-class) buffers.

    The host-device hop pays a fixed cost per transferred buffer AND per
    fetch; the encoder emits ~46 arrays, so shipping them
    individually costs more wall-clock than the solve itself. The solver
    packs them into flat per-group buffers host-side (solver._pack, with a
    device cache for unchanged groups) and this entry unpacks with static
    slices — free under XLA fusion. The result is ONE array (pack_result)
    so the host pays exactly one D2H round trip."""
    enc = unpack_layout(layout, bufs)
    raw = solve_rounds.__wrapped__(spec, enc)
    return pack_result(enc, raw)


@functools.partial(jax.jit, static_argnames=("spec",))
def solve_rounds(spec: SolveSpec, enc: dict):
    """Batched allocate session. Returns (assign [T] int32 node or -1,
    rounds used, tail_placed, full-sweep rounds, capped flag,
    placed-per-round histogram [PROF_SLOTS], touched-node mask [N] bool —
    the columns the solve consumed, all-ones on any full-width round or
    capped exit).

    Per-task request/has-pod columns are derived on device from the class
    arrays (task_req = cls_req[task_cls]); the per-task float matrices never
    cross the host->device hop in rounds mode (solver ships class arrays +
    the int32 task_cls index only)."""
    t_total = enc["task_cls"].shape[0]
    j_total = enc["job_tie_rank"].shape[0]
    k_total = enc["cls_req"].shape[0]
    n_total = enc["node_idle"].shape[0]
    dt = enc["cls_req"].dtype
    enc = dict(
        enc,
        task_req=enc["cls_req"][enc["task_cls"]],
        task_has_pod=enc["cls_has_pod"][enc["task_cls"]],
    )
    task_cls = enc["task_cls"]
    t_cap = t_total + 1  # capacity clamp: ranks never reach it
    task_excl = (enc["cls_excl"][task_cls]
                 if spec.use_exclusion else None)

    task_job = enc["task_job"]
    task_queue = enc["job_queue"][task_job]
    task_ns = enc["job_ns"][task_job]
    task_in_job = (jnp.arange(t_total, dtype=jnp.int32)
                   - enc["job_task_start"][task_job])
    # valid flat tasks (padding carries job index 0 but count excludes them)
    task_valid = (jnp.arange(t_total, dtype=jnp.int32)
                  < (enc["job_task_start"][task_job] + enc["job_task_count"][task_job])) \
        & enc["job_active0"][task_job]

    max_tasks_per_job = jnp.int32(t_total)
    # quantized requests for the exact queue-allocation carry
    req_q = jnp.ceil(enc["task_req"] / enc["res_unit"][None, :]).astype(
        jnp.int32)

    st = dict(
        idle=enc["node_idle"], used=enc["node_used"],
        cnt=enc["node_cnt"],
        assign=jnp.full((t_total,), -1, jnp.int32),
        active=task_valid,
        job_placed=jnp.zeros(j_total, jnp.int32),
        job_alloc=enc["job_alloc0"],
        ns_alloc=enc["ns_alloc0"],
        rounds=jnp.int32(0),
        progress=jnp.bool_(True),
        tried_cons=jnp.bool_(False),  # conservative retry owed after stall
        dead=jnp.bool_(False),  # outer fixpoint reached
        capped=jnp.bool_(False),  # diminishing-returns exit (min_progress)
        # carried masked score matrix + the dirty-column set: all columns
        # start dirty, so the first round always takes a full refresh (or
        # an all-column gather when dirty_k covers the whole axis)
        scores=jnp.zeros((k_total, n_total), dt),
        dirty=jnp.ones(n_total, bool),
        placed_hist=jnp.zeros(PROF_SLOTS, jnp.int32),
        full_sweeps=jnp.int32(0),
        # touched-node mask (read-set descriptor, pipeline/driver.py): the
        # node columns this solve actually consumed. Windowed rounds add
        # their top-k nominations; any full-width sweep (window_k == 0,
        # coverage-bit fallback, conservative stall retry resolved full)
        # and any capped exit (tail pass / serial residue argmax over the
        # whole axis) degrade it to all-ones — the conservative direction:
        # over-reporting reads can only shrink the commit rate, never
        # admit a stale commit
        touched=jnp.zeros(n_total, bool),
    )
    if spec.use_exclusion:
        st["excl_occ"] = enc["excl_occ0"]
    if spec.use_prop_overused:
        # exact quantized units, as limb pairs: read only by the overused
        # gate and the queue budget
        st["queue_alloc"] = enc["queue_alloc0_limbs"]
    # stall pairs cost two rounds per placement or rollback in the worst
    # case, so the runaway bound is 2(T+J)+8 (see outer_body)
    round_budget = 2 * (t_total + j_total) + 8

    def round_body(st):
        job_rank = _job_rank(spec, enc, st["job_placed"], st["job_alloc"])
        task_rank = job_rank[task_job] * max_tasks_per_job + task_in_job

        active = st["active"]
        if spec.use_prop_overused:
            over = _queue_over(st["queue_alloc"], enc["queue_bound_limbs"],
                               enc["is_scalar"])
            active = active & ~over[task_queue]

        idle, used, cnt = st["idle"], st["used"], st["cnt"]
        occ = st.get("excl_occ")
        neg = jnp.array(-jnp.inf, idle.dtype)

        # -- carried-score maintenance: dirty-column rescoring -------------
        # scores depend only on per-column state (idle/used/cnt/occupancy),
        # so patching the touched columns reproduces a full recompute
        # bit-for-bit; a touch set past the gather budget (first round,
        # bulk commits, large rollbacks) falls back to the chunked sweep
        if spec.dirty_k > 0:
            n_dirty = jnp.sum(st["dirty"].astype(jnp.int32))
            scores = lax.cond(
                n_dirty > jnp.int32(spec.dirty_k),
                lambda _: _refresh_scores(spec, enc, idle, used, cnt, occ),
                lambda _: _rescore_dirty(spec, enc, idle, used, cnt, occ,
                                         st["scores"], st["dirty"]),
                None)
        else:
            scores = _refresh_scores(spec, enc, idle, used, cnt, occ)
        n_feas = jnp.sum((scores > neg).astype(jnp.int32), axis=-1)

        # a class is live iff any of its tasks is still active (classes can
        # REVIVE when a rollback drops an overused queue below deserved);
        # per-class active demand feeds the binpack capacity apportioning:
        # with a packing policy every class walks the SAME node order, so
        # each must claim only its demand share of a node's estimated
        # capacity or the round over-commits the first nodes K-fold
        cls_live = jnp.zeros(k_total, bool).at[task_cls].max(active)
        cls_demand = jnp.zeros(k_total, jnp.int32).at[task_cls].add(
            active.astype(jnp.int32))
        cls_frac = (cls_demand.astype(idle.dtype) / jnp.maximum(
            jnp.sum(cls_demand), 1).astype(idle.dtype)) \
            if spec.use_binpack else None
        grank = _excl_grank(enc, cls_live) if spec.use_exclusion else None
        rank = _rank_in_class(task_cls, active)

        # stalemate breaker, folded into the ONE traced body: when the
        # previous round made no progress, this round uses the class-best
        # choice — the capacity walk is deterministic, so a task whose
        # assigned node keeps failing _resolve would repeat forever even
        # though other feasible nodes have room; the best-node choice
        # guarantees progress whenever anything feasible fits alone. A
        # conservative round that ALSO lands nothing sets tried_cons and
        # the loop exits to the rollback fixpoint. The class-best node is
        # the window's first element, so a stall never needs the full
        # fallback — strictly stronger than falling back would be.
        cons = ~st["progress"]

        if spec.window_k > 0:
            k_eff = spec.window_k
            top_s, top_i = lax.top_k(scores, k_eff)       # [K, k] prefix
            nom_w = _cap_walk(
                spec, enc, top_i.astype(jnp.int32), top_s, enc["cls_req"],
                enc["cls_excl"] if spec.use_exclusion else None,
                enc["cls_has_pod"], cls_frac, idle, cnt, t_cap)
            choice_w, cons_choice, slot_w, final_w = _select(
                spec, enc, task_cls, active, rank, n_feas, grank,
                top_i.astype(jnp.int32), *nom_w)
            # -- coverage bit: is the windowed answer provably full-width? --
            # exact when the window holds the class's whole feasible set, or
            # when both the capacity-walk slot and the final (rotated /
            # spread) position land strictly before the window's last
            # equal-score group — the one group the window may truncate
            # (its g_size/g_end, and hence the rotation, could differ from
            # full width). Packing classes don't rotate, so any in-window
            # slot is safe for them.
            g_start_w = nom_w[1]
            all_in = n_feas <= k_eff                        # [K]
            if spec.use_binpack and not spec.use_exclusion:
                safe_end = jnp.full(k_total, k_eff, jnp.int32)
            elif spec.use_binpack:
                safe_end = jnp.where(enc["cls_excl"] >= 0,
                                     g_start_w[:, k_eff - 1],
                                     jnp.int32(k_eff))
            else:
                safe_end = g_start_w[:, k_eff - 1]
            safe_end = jnp.where(all_in, jnp.int32(k_eff), safe_end)
            exact = all_in[task_cls] | (
                (slot_w < safe_end[task_cls]) & (final_w < safe_end[task_cls]))
            uncovered = jnp.zeros(k_total, bool).at[task_cls].max(
                active & ~exact)
            # stall rounds take cons_choice (exact by construction), so the
            # fallback only runs for real windowed rounds
            run_full = jnp.any(uncovered) & ~cons

            def full_branch(_):
                nom_f = _nominate_full(spec, enc, scores, idle, cnt,
                                       cls_frac, t_cap)
                ch_f, _, _, _ = _select(spec, enc, task_cls, active, rank,
                                        n_feas, grank, *nom_f)
                return ch_f

            choice_full = lax.cond(
                run_full, full_branch,
                lambda _: jnp.full(t_total, -1, jnp.int32), None)
            choice = jnp.where(uncovered[task_cls], choice_full, choice_w)
            did_full = run_full
            # read-set maintenance: a windowed round consumed exactly its
            # nominated columns; a coverage-bit fallback consumed them all
            touched = jnp.where(
                did_full, jnp.ones_like(st["touched"]),
                st["touched"].at[top_i.reshape(-1)].set(True))
        else:
            nom_f = _nominate_full(spec, enc, scores, idle, cnt, cls_frac,
                                   t_cap)
            choice, cons_choice, _, _ = _select(
                spec, enc, task_cls, active, rank, n_feas, grank, *nom_f)
            did_full = jnp.bool_(True)
            touched = jnp.ones_like(st["touched"])
        choice = jnp.where(cons, cons_choice, choice)
        if spec.use_exclusion:
            # within-round mutual exclusion: of the tasks of one group
            # aimed at one node this round, only the best-ranked proceeds;
            # the rest retry next round against the updated occupancy.
            # Winner-per-(group, node) via scatter-min of the task rank —
            # ranks are unique, so equality identifies exactly one winner
            # (a lexsort here costs several ms per round on host backends)
            n_nodes = st["idle"].shape[0]
            isx = (task_excl >= 0) & (choice >= 0)
            g_idx = jnp.maximum(task_excl, 0)
            n_idx = jnp.clip(choice, 0, n_nodes - 1)
            big = jnp.int32(2**30)
            winner = jnp.full(
                (enc["excl_occ0"].shape[0], n_nodes), big, jnp.int32
            ).at[g_idx, n_idx].min(jnp.where(isx, task_rank, big))
            keepm = ~isx | (task_rank == winner[g_idx, n_idx])
            choice = jnp.where(keepm, choice, -1)
        accept = _resolve(spec, enc, st["idle"], st["cnt"], choice, task_rank)
        if spec.use_prop_overused:
            accept, admitted = _queue_budget(enc, st["queue_alloc"], accept,
                                             task_rank, task_queue, task_job)

        node = jnp.clip(choice, 0, st["idle"].shape[0] - 1)
        dreq = jnp.where(accept[:, None], enc["task_req"], 0.0).astype(dt)
        idle = st["idle"].at[node].add(-dreq)
        used = st["used"].at[node].add(dreq)
        cnt = st["cnt"].at[node].add(accept.astype(jnp.int32))
        assign = jnp.where(accept, choice, st["assign"])
        placed_n = jnp.sum(accept.astype(jnp.int32))
        any_accept = placed_n > 0
        if spec.use_exclusion:
            st = dict(st, excl_occ=st["excl_occ"].at[
                jnp.maximum(task_excl, 0), node].max(
                    accept & (task_excl >= 0)))
        if spec.use_prop_overused:
            st = dict(st, queue_alloc=_limbs_add(st["queue_alloc"], admitted))
        capped = st["capped"]
        if spec.round_min_progress > 1:
            # diminishing-returns exit: a nonzero round below the progress
            # floor means the remaining stragglers cost a fixed-price
            # device round each few — the straggler rounds + serial residue
            # pass place them instead (assign=-2 marking below). Bounded:
            # only when the remainder is small (<= 8x the floor, ~3% of the
            # axis) — a large remainder is either worth more rounds or
            # unplaceable (which ends via zero progress anyway), and must
            # not be dumped on the serial pass wholesale
            remaining = jnp.sum((st["active"] & ~accept).astype(jnp.int32))
            capped = capped | (
                any_accept & (placed_n < jnp.int32(spec.round_min_progress))
                & (remaining > 0)
                & (remaining <= jnp.int32(8 * spec.round_min_progress)))
        return dict(
            st,
            idle=idle, used=used, cnt=cnt, assign=assign,
            active=st["active"] & ~accept,
            job_placed=st["job_placed"].at[task_job].add(accept.astype(jnp.int32)),
            job_alloc=st["job_alloc"].at[task_job].add(dreq),
            ns_alloc=st["ns_alloc"].at[task_ns].add(dreq),
            rounds=st["rounds"] + 1,
            progress=any_accept,
            tried_cons=cons & ~any_accept,
            capped=capped,
            scores=scores,
            # the columns this round's commit touched are next round's
            # rescore set (accept=False rows write False — a no-op)
            dirty=jnp.zeros_like(st["dirty"]).at[node].max(accept),
            placed_hist=st["placed_hist"].at[
                jnp.minimum(st["rounds"], jnp.int32(PROF_SLOTS - 1))
            ].add(placed_n.astype(jnp.int32)),  # sum promotes under x64
            full_sweeps=st["full_sweeps"] + did_full.astype(jnp.int32),
            touched=touched,
        )

    def rollback(st):
        """Retire the WORST-ranked gang still short of min_available
        (Statement.Discard semantics). One job per fixpoint iteration, like
        the serial loop discarding exactly the gang whose turn failed —
        everything it held frees up for the remaining gangs to retry."""
        short = (enc["job_ready_base"] + st["job_placed"]) < enc["job_ready_threshold"]
        cand = short & (st["job_placed"] > 0)
        job_rank = _job_rank(spec, enc, st["job_placed"], st["job_alloc"])
        worst = jnp.argmax(jnp.where(cand, job_rank, -1))
        roll_job = cand & (jnp.arange(j_total) == worst)
        roll = roll_job[task_job] & (st["assign"] >= 0)
        node = jnp.clip(st["assign"], 0, st["idle"].shape[0] - 1)
        dreq = jnp.where(roll[:, None], enc["task_req"], 0.0).astype(dt)
        dead_task = roll_job[task_job]  # the job leaves the session's queue
        if spec.use_exclusion:
            # free the rolled members' group slots (one holder per
            # (group, node), so the scatter cannot collide)
            st = dict(st, excl_occ=st["excl_occ"].at[
                jnp.maximum(task_excl, 0), node].min(
                    ~(roll & (task_excl >= 0))))
        if spec.use_prop_overused:
            # the rolled-back job's tasks all sit in its queue
            q = enc["job_queue"][worst]
            st = dict(st, queue_alloc=st["queue_alloc"].at[q].set(_limbs_sub(
                st["queue_alloc"][q], _limbs_sum(req_q, roll))))
        return dict(
            st,
            idle=st["idle"].at[node].add(dreq),
            used=st["used"].at[node].add(-dreq),
            cnt=st["cnt"].at[node].add(-roll.astype(jnp.int32)),
            assign=jnp.where(roll, -1, st["assign"]),
            active=st["active"] & ~dead_task,
            job_placed=jnp.where(roll_job, 0, st["job_placed"]),
            job_alloc=st["job_alloc"].at[task_job].add(-dreq),
            ns_alloc=st["ns_alloc"].at[task_ns].add(-dreq),
            progress=jnp.bool_(True),
            dead=~jnp.any(cand),
            # freed columns join the pending dirty set (the last round's
            # touches have not been rescored yet); a large rollback simply
            # overflows the gather budget into a full refresh
            dirty=st["dirty"] | jnp.zeros_like(st["dirty"]).at[node].max(roll),
        ), jnp.any(cand)

    def outer_cond(st):
        return ~st["dead"] & (st["rounds"] < round_budget)

    def outer_body(st):
        # inner loop runs while progressing OR a conservative retry is
        # still owed (tried_cons False after a stall); `any(active)` skips
        # the final no-op confirmation sweep when every task is placed.
        # Budget 2(T+J): each stall pair (normal + conservative) either
        # places >= 1 task or exits to a rollback that retires one job.
        # A capped (diminishing-returns) exit is terminal: no rollback —
        # the straggler rounds + serial residue pass own the stragglers AND
        # any still-short gangs, with the oracle's exact Statement
        # semantics.
        def inner_cond(s):
            return (s["progress"] | ~s["tried_cons"]) \
                & jnp.any(s["active"]) & (s["rounds"] < round_budget) \
                & ~s["capped"]

        st = lax.while_loop(inner_cond, round_body, st)
        st = lax.cond(
            st["capped"],
            lambda s: dict(s, dead=jnp.bool_(True)),
            lambda s: rollback(s)[0],
            st)
        return dict(st, tried_cons=jnp.bool_(False))

    st = lax.while_loop(outer_cond, outer_body, st)

    if spec.round_min_progress > 1 and spec.straggler_rounds > 0:
        # batched straggler rounds: the capped exit used to dump its whole
        # <= 8x-floor remainder on the one-task-per-step tail pass (cfg6:
        # a 229-step sequential tail). With carried scores + windows a
        # narrow round is cheap, so run a few more batched rounds over the
        # stragglers first — the tail then sees only what round semantics
        # genuinely cannot place. Bit-identical between windowed and
        # full-width modes because round_body is.
        def strag_cond(s):
            return s["capped"] & s["progress"] & jnp.any(s["active"]) \
                & (s["extra"] < jnp.int32(spec.straggler_rounds)) \
                & (s["rounds"] < round_budget)

        st = dict(st, extra=jnp.int32(0), progress=jnp.bool_(True))
        st = lax.while_loop(
            strag_cond,
            lambda s: dict(round_body(s), extra=s["extra"] + 1), st)
        st.pop("extra")

    # profile + score state leave the carry before the tail pass: the tail
    # is a ~hundreds-iteration scalar loop and must not drag [K, N] state
    placed_hist = st.pop("placed_hist")
    full_sweeps = st.pop("full_sweeps")
    touched = st.pop("touched")
    st.pop("scores")
    st.pop("dirty")

    def tail_pass(st):
        """Sequential per-task placement of the diminishing-returns
        remainder, on device, in the serial visit order: one task per step
        (lowest live task rank), class-row feasibility mask, fused score,
        argmax node (first-max == lowest node index, the serial tie-break),
        scatter-commit. The cap condition bounds the remainder at
        8 * round_min_progress, so a few hundred tiny [N]-vector steps
        replace a host residue pass that costs ~0.7 ms per straggler (and
        the straggler rounds above have usually shrunk it to a handful).
        Tasks the sweep cannot place are retired with assign -1 (the
        kernel's mask equals the serial predicate verdict for modeled
        tasks); gangs left short are stripped and re-enqueued below exactly
        as before."""
        tail_budget = jnp.int32(8 * max(spec.round_min_progress, 1) + 16)

        def cond(s):
            return jnp.any(s["active"]) & ~s["tail_stuck"] \
                & (s["tail_steps"] < tail_budget)

        def body(s):
            eligible = s["active"]
            if spec.use_prop_overused:
                # overused queues sit out (the serial gate between job
                # visits); their tasks stay ACTIVE so the capped -2 marking
                # below still routes them to the serial residue retry,
                # exactly as the pre-tail capped exit did
                over = _queue_over(s["queue_alloc"], enc["queue_bound_limbs"],
                                   enc["is_scalar"])
                eligible = eligible & ~over[task_queue]
            # lexicographic argmin over the SAME job-order keys _job_rank
            # sorts by, without the per-step [J] lexsort (sorts are the
            # expensive primitive on TPU; ~245 tail steps each paid one).
            # A chain of masked min-reductions selects the identical task:
            # narrow the candidate set one key level at a time, then take
            # the first surviving index — exactly lexsort-rank order with
            # the task_in_job tie-break.
            levels = []
            for name in spec.job_order_keys:
                if name == "priority":
                    levels.append((-enc["job_priority"])[task_job])
                elif name == "gang":
                    ready = ((enc["job_ready_base"] + s["job_placed"])
                             >= enc["job_min_available"])
                    levels.append(ready.astype(jnp.int32)[task_job])
                elif name == "drf":
                    share = _share(s["job_alloc"],
                                   enc["drf_total"][None, :],
                                   enc["drf_present"][None, :])
                    levels.append(share[task_job])
            levels.append(enc["job_tie_rank"][task_job])
            levels.append(task_in_job)
            cand = eligible
            for lv in levels:
                if jnp.issubdtype(lv.dtype, jnp.floating):
                    sentinel = jnp.array(jnp.inf, lv.dtype)
                else:
                    sentinel = jnp.array(jnp.iinfo(lv.dtype).max, lv.dtype)
                m = jnp.min(jnp.where(cand, lv, sentinel))
                cand = cand & (lv == m)
            t = jnp.argmax(cand)  # first-True == lowest task index
            has = jnp.any(eligible)
            c = enc["task_cls"][t]
            req = enc["cls_req"][c]
            initreq = enc["cls_initreq"][c]
            eps = enc["eps"]
            is_scalar = enc["is_scalar"]
            le = initreq[None, :] < s["idle"] + eps[None, :]
            skip = is_scalar[None, :] & (initreq[None, :] <= MIN_MILLI_SCALAR)
            mask = jnp.all(le | skip, axis=-1) & enc["sig_mask"][enc["cls_sig"][c]]
            if spec.check_pod_count:
                mask = mask & ((s["cnt"] < enc["node_max_tasks"])
                               | ~enc["cls_has_pod"][c])
            if spec.use_exclusion:
                g = task_excl[t]
                mask = mask & ~(s["excl_occ"][jnp.maximum(g, 0)] & (g >= 0))
            score = fused_scores(spec, enc, s["used"], req,
                                 enc["cls_nz_cpu"][c], enc["cls_nz_mem"][c],
                                 enc["cls_sig"][c])
            node = jnp.argmax(jnp.where(mask, score,
                                        jnp.array(-jnp.inf, score.dtype)))
            ok = has & mask[node]
            dreq = jnp.where(ok, req, jnp.zeros_like(req)).astype(dt)
            out = dict(
                s,
                idle=s["idle"].at[node].add(-dreq),
                used=s["used"].at[node].add(dreq),
                cnt=s["cnt"].at[node].add(ok.astype(jnp.int32)),
                assign=s["assign"].at[t].set(
                    jnp.where(ok, node.astype(jnp.int32), s["assign"][t])),
                # the selected task retires either way: placed now, or
                # handed to the serial residue retry (tail_failed) — the
                # post-tail gang strip can refund capacity, so an
                # infeasible-now verdict is not final for the session
                active=s["active"].at[t].set(jnp.where(has, False,
                                                       s["active"][t])),
                tail_failed=s["tail_failed"].at[t].set(
                    jnp.where(has & ~ok, True, s["tail_failed"][t])),
                tail_stuck=~has,
                job_placed=s["job_placed"].at[task_job[t]].add(
                    ok.astype(jnp.int32)),
                job_alloc=s["job_alloc"].at[task_job[t]].add(dreq),
                ns_alloc=s["ns_alloc"].at[task_ns[t]].add(dreq),
                tail_steps=s["tail_steps"] + 1,
                tail_placed=s["tail_placed"] + ok.astype(jnp.int32),
            )
            if spec.use_exclusion:
                out["excl_occ"] = s["excl_occ"].at[
                    jnp.maximum(task_excl[t], 0), node].max(
                        ok & (task_excl[t] >= 0))
            if spec.use_prop_overused:
                r = jnp.where(ok, req_q[t], 0)
                q = task_queue[t]
                out["queue_alloc"] = s["queue_alloc"].at[q].set(_limbs_add(
                    s["queue_alloc"][q], jnp.stack([r >> 15, r & 0x7FFF], -1)))
            return out

        s = dict(st, tail_steps=jnp.int32(0), tail_stuck=jnp.bool_(False),
                 tail_placed=jnp.int32(0),
                 tail_failed=jnp.zeros_like(st["active"]))
        s = lax.while_loop(cond, body, s)
        s.pop("tail_steps")
        s.pop("tail_stuck")
        return s

    if spec.round_min_progress > 1:
        st = lax.cond(st["capped"], tail_pass,
                      lambda s: dict(s, tail_placed=jnp.int32(0),
                                     tail_failed=jnp.zeros_like(s["active"])),
                      st)
    # structural gang-atomicity net: on a normal exit (dead=True) no gang
    # with placements is short, so this is a no-op; on a budget exhaustion
    # it strips partially-placed gangs instead of letting the bulk apply
    # bind them (the apply path does not re-check job readiness)
    short = (enc["job_ready_base"] + st["job_placed"]) < enc["job_ready_threshold"]
    assign = jnp.where(short[task_job], -1, st["assign"])
    # capped exit: mark the still-wanting tasks (stragglers + gangs the
    # strip above just emptied) for the serial residue retry instead of a
    # stale '0/N nodes' fit error — the solver folds -2 into residue
    # accounting. Jobs retired by the rollback fixpoint (job_placed == 0,
    # proven unplaceable) are NOT re-enqueued: dumping them on the serial
    # pass would cost far more host work than the rounds the cap saved.
    strip_retry = short & (st["job_placed"] > 0)
    want_retry = st["active"] | (strip_retry[task_job] & task_valid)
    if "tail_failed" in st:
        # tasks the device tail judged infeasible retry serially too: the
        # gang strip above may have refunded capacity they can use (the
        # tail saw idle still charged with the stripped placements)
        want_retry = want_retry | (st["tail_failed"] & task_valid)
    assign = jnp.where(
        st["capped"] & want_retry & (assign < 0),
        -2, assign)
    # a capped exit consumed the whole axis: the tail pass argmaxes over
    # every node and the serial residue retry walks the live snapshot —
    # the mask degrades to all-ones (conservative full read)
    touched = jnp.where(st["capped"], jnp.ones_like(touched), touched)
    return (assign, st["rounds"], st.get("tail_placed", jnp.int32(0)),
            full_sweeps, st["capped"], placed_hist, touched)

