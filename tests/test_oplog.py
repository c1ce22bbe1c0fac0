"""The evict machines' op log: one flat int32 buffer of row-major
[kind, a, b] rows, appended one row at a time, rewound by a statement
discard, and packed as it is with the tail (evict_tail), whose bytes the
host replay reads."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from volcano_tpu.ops import evict as evict_mod
from volcano_tpu.ops.evict import OP_COMMIT, OP_EVICT, OP_PIPELINE

N, V, T, J, Q = 6, 4, 5, 4, 2


def _cluster(seed):
    """A small session state. Requests are whole numbers, so the float
    re-adds of a discard restore them exactly."""
    rng = np.random.default_rng(seed)
    job_queue = rng.integers(0, Q, J).astype(np.int32)
    vic_job = rng.integers(0, J, (N, V)).astype(np.int32)

    def ints(lo, hi, *shape, dtype=np.int32):
        return rng.integers(lo, hi, shape).astype(dtype)

    enc = dict(vic_job=vic_job, vic_queue=job_queue[vic_job],
               vic_req=ints(1, 9, N, V, 2, dtype=np.float32),
               node_used=ints(1, 99, N, 2, dtype=np.float32),
               p_req=ints(1, 9, T, 2, dtype=np.float32),
               p_job=ints(0, J, T), job_queue=job_queue,
               job_min_av=np.full(J, 2, np.int32))
    st = dict(used=enc["node_used"], cnt=ints(0, 9, N),
              alive=np.ones((N, V), bool), ready=ints(4, 9, J),
              wait=ints(0, 3, J),
              job_alloc=ints(1, 99, J, 2, dtype=np.float32),
              queue_alloc=ints(1, 999, Q, 2, dtype=np.float32),
              p_done=np.zeros(T, bool), rr=np.int32(0), victims=np.int32(0),
              attempts=np.int32(0), fail=np.bool_(False),
              underflow=np.bool_(False))
    return enc, st


def _apply(enc, st, op):
    """One logged op: ("E", node, slot, active), ("P", task, node) or
    ("C", active)."""
    if op[0] == "E":
        node = jnp.int32(op[1])
        cut = evict_mod._apply_evict_slot(
            enc, st, node, jnp.int32(op[2]), jnp.bool_(op[3]))
        return evict_mod._cut_ready(enc, cut, node, st["alive"][node])
    if op[0] == "P":
        return evict_mod._apply_pipeline(enc, st, jnp.int32(op[1]),
                                         jnp.int32(op[2]))
    return evict_mod._log_append(st, OP_COMMIT, jnp.int32(0), jnp.int32(0),
                                 jnp.bool_(op[1]))


def _old_log(rows, ops):
    """The [rows, 3] log the machines carried before it was flat,
    appended the same way: (log, log_len, fail)."""
    log, n, fail = np.zeros((rows, 3), np.int32), 0, False
    for op in ops:
        if op[0] == "E":
            row, active = (OP_EVICT, op[1], op[2]), op[3]
        elif op[0] == "P":
            row, active = (OP_PIPELINE, op[1], op[2]), True
        else:
            row, active = (OP_COMMIT, 0, 0), op[1]
        if active:
            log[min(n, rows - 1)] = row
            n += 1
        fail |= n >= rows
    return log, n, fail


CASES = {
    "evicts": (16, [("E", 0, 1, True), ("C", True)],
               [("E", 2, 0, True), ("E", 2, 3, True), ("E", 5, 2, True)]),
    "mixed": (16, [("P", 0, 2), ("E", 1, 1, True), ("C", True)],
              [("P", 2, 0), ("C", False), ("E", 3, 2, True),
               ("E", 4, 0, False), ("P", 4, 5), ("C", True)]),
    "from_empty": (16, [], [("E", 5, 3, True), ("P", 3, 1), ("P", 1, 5)]),
    "fills_to_capacity": (6, [("E", 0, 0, True), ("P", 0, 1), ("C", True)],
                          [("E", 1, 1, True), ("P", 2, 3), ("C", True)]),
    "past_capacity": (4, [("E", 0, 0, True), ("P", 0, 1)],
                      [("E", 1, 1, True), ("P", 2, 3), ("E", 2, 2, True)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_log_round_trip(case):
    rows, prefix, segment = CASES[case]
    enc, st0 = _cluster(seed=sorted(CASES).index(case))
    spec = evict_mod.EvictSpec(
        kind="preempt", job_order_keys=(), victim_fns=(),
        check_pod_count=False, use_nodeorder=False, use_binpack=False,
        use_gang_pipelined=False, log_rows=rows)

    @jax.jit
    def run(enc, st):
        enc, st = evict_mod._slot_views(
            enc, dict(st, **evict_mod._log0(spec)))
        for op in prefix:
            st = _apply(enc, st, op)
        before = st
        for op in segment:
            st = _apply(enc, st, op)
        return before, st, evict_mod._discard(enc, st, before["log_len"])

    before, after, undone = jax.tree_util.tree_map(np.asarray,
                                                   run(enc, st0))
    old, n, fail = _old_log(rows, prefix + segment)
    assert after["log"].shape == (rows * 3,)
    assert int(after["log_len"]) == n and bool(after["fail"]) == fail
    packed = np.asarray(jax.jit(evict_mod.evict_tail)(after))
    # the packed result is the old row-major interleave, then the tail
    np.testing.assert_array_equal(packed[:rows * 3], old.reshape(-1))
    assert packed.size == rows * 3 + evict_mod.TAIL
    assert packed[rows * 3] == n and packed[rows * 3 + 4] == int(fail)

    if n > rows:
        return  # a row was overwritten: the machine stops, the host falls back
    assert not np.array_equal(after["p_done"], before["p_done"]) \
        or not np.array_equal(after["alive"], before["alive"])
    assert int(undone["log_len"]) == int(before["log_len"]) == \
        _old_log(rows, prefix)[1]
    for k, v in before.items():
        if k not in ("log", "log_len", "fail"):
            np.testing.assert_array_equal(undone[k], v, err_msg=k)
    # a discard rewinds the cursor and leaves the rows it undid in place
    np.testing.assert_array_equal(
        np.asarray(jax.jit(evict_mod.evict_tail)(undone))[:rows * 3],
        old.reshape(-1))
