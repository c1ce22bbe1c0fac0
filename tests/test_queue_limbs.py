"""Clusters past 2^31 quantized units in all, on the rounds device path.

The rounds kernel holds a queue's deserved bound and its carried
allocation as int32 limb pairs (ops/rounds.py ``_queue_budget``,
``_queue_over``, ``_limbs_sum``), quantized on the host in float64
(ops/encoder.py ``queue_limbs``); the only quantized value left as a plain
int32 is a node's idle, so the encoder's guard is per node. Checked here
against plain NumPy int64 references in the chip's precision (x64 off),
and end to end: one session on 1,100 nodes of 2Ti (2.3e9 MiB in all, past
the cluster-total guard this replaced) through the benchmark's session
loop, held to ``benchmark/reference.py``'s invariants and to the serial
oracle's bind count. Also: a node past the int32 range still falls back,
the packed result round-trips past 32,766 nodes, and
``benchmark/configs/cfg7-paper-2x.json`` builds clusters.py's cfg7."""

from __future__ import annotations

import json
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from volcano_tpu.ops import rounds as R
from volcano_tpu.ops.encoder import queue_limbs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CFG7 = os.path.join(BENCH, "configs", "cfg7-paper-2x.json")

# cpu (milli), memory (MiB units of bytes), one scalar (milli)
RES_UNIT = np.array([1.0, 2.0 ** 20, 1.0])
EPS = np.array([10.0, 10.0 * 2 ** 20, 10.0])
IS_SCALAR = np.array([False, False, True])


def _value(pairs: np.ndarray) -> np.ndarray:
    pairs = np.asarray(pairs).astype(np.int64)
    return (pairs[..., 0] << 15) + pairs[..., 1]


def limbs(v: np.ndarray) -> np.ndarray:
    return np.stack([v >> 15, v & 0x7FFF], -1).astype(np.int32)


def _queues(rng, n_queues: int = 3, n_jobs: int = 48):
    """Seeded queues and jobs whose deserved share and allocation lie past
    2^31 units, with deserved between the allocation and the allocation
    plus the queue's request, so the bound decides which jobs pass.
    Requests are whole numbers float32 holds exactly, as on the chip."""
    sizes = rng.integers(1, 7, n_jobs)
    job = np.repeat(np.arange(n_jobs), sizes).astype(np.int32)
    queue_of_job = rng.integers(0, n_queues, n_jobs).astype(np.int32)
    t = len(job)
    req = np.stack([
        rng.integers(0, 2 ** 24, t) * 1.0,                 # to 16k cores
        rng.integers(1, 2 ** 22, t) * 2.0 ** 20,           # to 4 TiB
        rng.integers(0, 6, t) * 1.0,                       # a few milli
    ], -1)
    in_queue = np.zeros((n_queues, 3))
    np.add.at(in_queue, queue_of_job[job], req)
    alloc = np.stack([
        rng.uniform(2.2e9, 6e9, n_queues),                 # milli-cpu
        rng.uniform(2.2e9, 6e9, n_queues) * 2.0 ** 20,     # MiB, fractional
        np.zeros(n_queues),
    ], -1)
    deserved = alloc + in_queue * rng.uniform(0.2, 0.8, (n_queues, 1))
    deserved[:, 2] = rng.integers(0, 40, n_queues)
    rank_of_job = rng.permutation(n_jobs)
    in_job = np.arange(t) - np.searchsorted(job, job)
    rank = (rank_of_job[job] * t + in_job).astype(np.int32)
    return dict(req=req, job=job, queue=queue_of_job[job], rank=rank,
                alloc=alloc, deserved=deserved)


def _budget_ref(req_q, accept, rank, queue, job, alloc_q, bound_q):
    """The serial gate in int64: in (queue, rank) order, a job's accepted
    tasks survive iff the queue's allocation plus the accepted requests of
    its higher-ranked jobs is below deserved + eps in every dimension (a
    scalar dimension at most 10 milli is skipped). Returns the survivors
    and each queue's admitted request."""
    out = np.zeros(len(accept), bool)
    seen = {}
    cur, before = None, None
    for i in sorted(range(len(accept)), key=lambda i: (queue[i], rank[i])):
        q = queue[i]
        if (q, job[i]) != cur:
            cur = (q, job[i])
            before = seen.get(q, np.zeros(3, np.int64)).copy()
        tot = alloc_q[q] + before
        ok = np.all((tot < bound_q[q]) | (IS_SCALAR & (tot <= 10)))
        out[i] = accept[i] and ok
        if accept[i]:
            seen[q] = seen.get(q, np.zeros(3, np.int64)) + req_q[i]
    admitted = np.zeros_like(alloc_q)
    np.add.at(admitted, queue[out], req_q[out])
    return out, admitted


@pytest.mark.parametrize("seed", [3, 17, 2 ** 31 + 5, 2 ** 40 + 1])
def test_queue_budget_matches_int64(seed):
    rng = np.random.default_rng(seed)
    d = _queues(rng)
    bound, alloc = queue_limbs(d["deserved"], d["alloc"], EPS, RES_UNIT)
    bound_q, alloc_q = _value(bound), _value(alloc)
    assert bound_q[:, :2].min() >= 2 ** 31 and alloc_q[:, :2].min() >= 2 ** 31
    req_q = np.ceil(d["req"] / RES_UNIT).astype(np.int64)
    accept = rng.random(len(d["job"])) < 0.8
    want, want_admitted = _budget_ref(req_q, accept, d["rank"], d["queue"],
                                      d["job"], alloc_q, bound_q)
    with jax.enable_x64(False):
        enc = {"is_scalar": jnp.asarray(IS_SCALAR),
               "res_unit": jnp.asarray(RES_UNIT, jnp.float32),
               "task_req": jnp.asarray(d["req"], jnp.float32),
               "queue_bound_limbs": jnp.asarray(bound)}
        got, admitted = R._queue_budget(
            enc, jnp.asarray(alloc), jnp.asarray(accept),
            jnp.asarray(d["rank"]), jnp.asarray(d["queue"]),
            jnp.asarray(d["job"]))
    # the bound decides: some accepted tasks pass, some are held back
    assert 0 < want.sum() < accept.sum()
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(_value(admitted), want_admitted)
    assert (np.asarray(admitted)[..., 1] < 2 ** 15).all()


@pytest.mark.parametrize("seed", [5, 2 ** 33 + 9])
def test_queue_carry_matches_int64(seed):
    """The carried allocation: per-queue sums of thousands of requests up
    to 2^31 - 1 units each (past any single limb's range), added and taken
    back exactly; the overused gate read from it."""
    rng = np.random.default_rng(seed)
    n_queues, t = 4, 6000
    req_q = rng.integers(0, 2 ** 31 - 1, (t, 3)).astype(np.int64)
    queue = rng.integers(0, n_queues, t).astype(np.int32)
    mask = rng.random(t) < 0.7
    alloc_q = rng.integers(0, 2 ** 40, (n_queues, 3))
    bound_q = alloc_q + rng.integers(2 ** 38, 2 ** 42, (n_queues, 3))
    bound_q[0, 2] = 0                           # an exhausted scalar share
    want = alloc_q.copy()
    np.add.at(want, queue[mask], req_q[mask])
    with jax.enable_x64(False):
        a = jnp.asarray(limbs(alloc_q))
        d = jnp.stack([R._limbs_sum(jnp.asarray(req_q, jnp.int32),
                                    jnp.asarray(mask & (queue == q)))
                       for q in range(n_queues)])
        added = R._limbs_add(a, d)
        back = R._limbs_sub(added, d)
        over = R._queue_over(added, jnp.asarray(limbs(bound_q)),
                             jnp.asarray(IS_SCALAR))
    np.testing.assert_array_equal(_value(added), want)
    np.testing.assert_array_equal(_value(back), alloc_q)
    assert (np.asarray(added)[..., 1] < 2 ** 15).all()
    want_over = ~np.all((want < bound_q) | (IS_SCALAR & (want <= 10)), -1)
    np.testing.assert_array_equal(np.asarray(over), want_over)
    assert want_over.any() and not want_over.all()


@pytest.fixture
def bench_modules(monkeypatch):
    """The benchmark's generator, session and reference (benchmark/),
    with the device path's task gate lowered so small sessions take it,
    as benchmark/tests/conftest.py does."""
    from volcano_tpu.ops.solver import BatchAllocator

    monkeypatch.syspath_prepend(BENCH)
    monkeypatch.setattr(BatchAllocator, "AUTO_ROUNDS_THRESHOLD", 96)
    import cluster
    import harness
    import reference

    return cluster, harness, reference


def _cfg7(nodes: int, gangs: int, **node_shape) -> dict:
    with open(CFG7) as f:
        cfg = json.load(f)
    cfg["nodes"].update(count=nodes, **node_shape)
    cfg["groups"][0]["count"] = gangs
    return cfg


def _one_session(mods, cfg: dict, policy: str, seed: int = 2 ** 31 + 3):
    """One session of the production loop on a fresh seeded cluster: its
    record, the reference's checks and the device path's fallbacks."""
    cluster, harness, reference = mods
    rec = harness.Recorder()
    cl = cluster.Cluster(cfg, seed, harness.new_cache(rec))
    cl.populate(cl.add_nodes())
    fallbacks = harness.Fallbacks()
    out = harness.Session(cl.cache, rec, policy).run()
    return (out, reference.check_session(cl.world, out["binds"],
                                         out["evicts"]),
            fallbacks.of(out["profile"]))


@pytest.mark.parametrize("x64", [False, True])
def test_session_past_the_old_cluster_guard(bench_modules, x64):
    """1,100 nodes of 2Ti hold 2.3e9 MiB: past 2^31 - 2^20 quantized units
    in all, each node far inside them."""
    cfg = _cfg7(1100, 40, memory="2Ti")
    with jax.enable_x64(x64):
        out, check, off = _one_session(bench_modules, cfg, cfg["policy"])
    assert out["profile"]["mode"] == "rounds", out["profile"].get("fallback")
    assert off == {}
    assert check["violations"] == 0 and check["unbound"] == 0, check
    serial = cfg["policy"].replace("- plugins:\n  - name: tpuscore\n", "")
    s_out, s_check, _ = _one_session(bench_modules, cfg, serial)
    assert "tpuscore" not in serial and "mode" not in s_out["profile"]
    assert sum(s_check.values()) == 0, s_check
    assert len(out["binds"]) == len(s_out["binds"]) == 320


def test_node_past_int32_still_falls_back(bench_modules):
    """A node of 3,000,000 cpu (3e9 milli-cpu) would wrap its int32 idle
    bound: the encoder names it and the serial loop binds."""
    cfg = _cfg7(40, 40, cpu="3000000")
    out, check, off = _one_session(bench_modules, cfg, cfg["policy"])
    assert "a node's capacity exceeds the int32 quantized-bound range" \
        in off["fallback"], off
    assert off["mode"] is None
    assert sum(check.values()) == 0, check
    assert len(out["binds"]) == 320


@pytest.mark.parametrize("n_nodes,dtype", [(1000, np.int16),
                                           (32767, np.int32),
                                           (50000, np.int32)])
def test_packed_result_round_trip(n_nodes, dtype):
    """pack_result's int16 branch up to 32,766 nodes and its int32 branch
    past them, read back by the solver's parse_packed, in the chip's
    precision."""
    from volcano_tpu.ops.solver import BatchAllocator

    rng = np.random.default_rng(n_nodes)
    assign = rng.integers(-2, n_nodes, 4096).astype(np.int32)
    assign[:3] = (n_nodes - 1, -1, -2)
    touched = rng.random(n_nodes) < 0.3
    hist = rng.integers(0, 5000, R.PROF_SLOTS).astype(np.int32)
    with jax.enable_x64(False):
        raw = (jnp.asarray(assign), jnp.int32(40_000), jnp.int32(77),
               jnp.int32(3), jnp.bool_(True), jnp.asarray(hist),
               jnp.asarray(touched))
        out = np.asarray(R.pack_result(
            {"node_idle": jnp.zeros((n_nodes, 2), jnp.float32)}, raw))
    assert out.dtype == dtype
    assert out.size == 4096 + n_nodes + R.PROF_TAIL
    got, meta = BatchAllocator().parse_packed(out)
    np.testing.assert_array_equal(got, assign)
    np.testing.assert_array_equal(meta["touched_nodes"], touched)
    np.testing.assert_array_equal(meta["placed_hist"], hist)
    assert (meta["n_rounds"], meta["tail_placed"], meta["full_sweeps"],
            meta["round_capped"]) == (40_000, 77, 3, True)


def _shape(cache) -> dict:
    """What a cluster is, independent of names and order."""
    nodes = Counter((n.allocatable.milli_cpu, n.allocatable.memory,
                     n.allocatable.max_task_num)
                    for n in cache.nodes.values())
    gangs, tasks = Counter(), Counter()
    for job in cache.jobs.values():
        gangs[(job.min_available, job.queue, len(job.tasks))] += 1
        for t in job.tasks.values():
            tasks[(t.resreq.milli_cpu, t.resreq.memory, t.priority,
                   str(t.status), job.queue)] += 1
    queues = sorted((q.name, q.weight) for q in cache.queues.values())
    return {"nodes": nodes, "gangs": gangs, "tasks": tasks, "queues": queues}


def test_cfg7_file_builds_clusters_py_cfg7(bench_modules):
    """At 1/500 of its size (100 nodes, 25 gangs), for two seeds."""
    from volcano_tpu.bench import clusters

    cluster, harness, _ = bench_modules
    cfg = _cfg7(100, 25)
    ref = clusters.make_cache()
    clusters.CONFIGS[7].populate(ref, 0.002)
    for seed in (1, 2 ** 33 + 5):
        cl = cluster.Cluster(cfg, seed, harness.new_cache(harness.Recorder()))
        cl.populate(cl.add_nodes())
        assert _shape(cl.cache) == _shape(ref)
