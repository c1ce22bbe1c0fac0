"""Mesh sharding utilities: per-shard staging of the node axis.

The node-axis shard of the rounds kernel is bit-identical to the
single-device solve (tests/test_mesh_shard.py; chip_smoke.py --chips 4 on
chips), but the surrounding stages used to de-shard the axis — the encoder staged full-
width matrices through one `jax.device_put` stream per array (no device
cache at all on the mesh path), and the evict victim folds ran unsharded.
This module is the shared staging layer that keeps the axis sharded
end-to-end:

- **per-shard device cache** (`stage_node_arrays`): each node-axis array
  is split into its per-device row slices and each slice is compared
  against the cached host copy independently — an unchanged slice reuses
  its device-resident single-device buffer, a changed one pays exactly one
  `device_put` to its own device (the puts are issued back-to-back and
  land on the devices in parallel; PJRT transfers are async per device).
  With the SnapshotKeeper's long-lived node axis the encoder hands back
  identity-stable matrices for unchanged state, so a warm session's
  refresh cost is O(changed rows) *per shard*: shards whose rows did not
  move never re-cross the link. The global array is assembled from the
  per-shard buffers without a copy (`make_array_from_single_device_arrays`),
  and its VALUES are exactly the single-device layout — the single-device
  path stays the byte-for-byte oracle;
- **mesh padding** (`pad_axis_multiple`): the node axis pads to the device
  multiple (append-only — real node indices are unchanged), with per-array
  fills chosen so padded slots are invisible (sig_mask False, victim
  validity False, round-robin windows count real slots only);
- **replicated staging** (`replicated_sharding`): the packed non-node
  buffers ride the existing grouped transfer but must commit to the SAME
  mesh (a single-device buffer cannot enter a jit call alongside a sharded
  array), so the solver/evict `_stage` caches key on the mesh identity too;
- **per-device stage probes** (`probe_per_device_stage_ms`): the bench
  mesh curve's measured per-shard critical path — the CPU proxy cannot run
  8 shards truly in parallel, so the curve times ONE shard's slice of the
  sharded stages (the rounds score refresh and the evict victim folds) at
  per-shard width N/d; on the real mesh shards execute concurrently, so
  the per-shard wall IS the stage wall up to the cross-shard reduce.

The mesh axis is always the node axis (axis name "nodes", the existing
`Mesh(devices, ("nodes",))` convention); cross-shard communication happens
only at decision boundaries (arg-extrema over nodes, int victim counts) —
reduces whose results are order-independent, which is what preserves
bit-identity under the shard.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import numpy as np

# (name, device_count, shard) -> (src array ref, host slice, device buffer).
# The source ref is held so the identity fast path (`src is arr`) stays
# sound: encoder/axis matrices are never mutated in place once handed out
# (solver._PACK_CACHE contract), so identity implies content. Bounded at
# one entry per (array name, mesh size, shard).
_SHARD_CACHE: Dict[tuple, tuple] = {}


def clear_cache() -> None:
    """Drop the per-shard device cache (tests / bench mesh sweeps)."""
    _SHARD_CACHE.clear()


def device_count(mesh) -> int:
    """Total devices in the mesh (the node-axis shard count)."""
    if mesh is None:
        return 1
    return int(np.prod(list(mesh.shape.values())))


def per_shard(extent: int, shards: int) -> int:
    """Per-shard slice width of a mesh-padded axis. The input extent must
    already be the PADDED (device-multiple) extent — per-shard shapes key
    off this value, never off a raw live node count (VT002: at 8 devices a
    shape keyed to global N re-keys every shard's program 8x too often and
    sizes per-shard work off the wrong axis)."""
    return max(extent // max(int(shards), 1), 1)


def pad_axis_multiple(a: np.ndarray, axis: int, multiple: int, fill=0):
    """Pad ``axis`` up to the next multiple of ``multiple`` (append-only:
    existing indices are unchanged, so op logs and name tables keyed on
    real indices stay valid)."""
    n = a.shape[axis]
    if multiple <= 1 or n % multiple == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, ((n + multiple - 1) // multiple) * multiple - n)
    return np.pad(a, widths, constant_values=fill)


def node_sharding(mesh, ndim: int, axis: int):
    """NamedSharding placing ``axis`` along the mesh's node dimension."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    name = tuple(mesh.shape.keys())[0]
    spec = [None] * ndim
    spec[axis] = name
    return NamedSharding(mesh, P(*spec))


def replicated_sharding(mesh):
    """Fully-replicated NamedSharding over the mesh (the packed non-node
    buffers; every device holds the whole buffer)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def mesh_key(mesh) -> Optional[tuple]:
    """Hashable mesh identity for device-cache validation: a buffer staged
    for one mesh shape must never be handed to a jit call compiled for
    another (or for the single-device path)."""
    if mesh is None:
        return None
    return (tuple(mesh.shape.items()),
            tuple(int(d.id) for d in mesh.devices.ravel()))


def stage_node_arrays(arrays: Dict[str, np.ndarray],
                      axis_of: Dict[str, int], mesh,
                      profile: Optional[dict] = None,
                      tag: str = "") -> Dict[str, object]:
    """Stage node-axis host arrays as mesh-sharded device arrays through
    the per-shard cache. ``arrays`` must already be padded to the device
    multiple along their node axis. Returns {name: global jax.Array}; the
    h2d accounting (puts vs cached shards, bytes shipped) lands in
    ``profile`` next to the packed-transfer counters."""
    import jax

    d = device_count(mesh)
    devs = list(mesh.devices.ravel())
    staged: Dict[str, object] = {}
    puts = hits = 0
    put_bytes = 0
    for name in sorted(arrays):
        arr = np.asarray(arrays[name])
        axis = axis_of[name]
        assert arr.shape[axis] % d == 0, (name, arr.shape, d)
        width = per_shard(arr.shape[axis], d)
        bufs = []
        for s in range(d):
            key = (tag + name, d, s)
            cached = _SHARD_CACHE.get(key)
            sl = None
            if cached is not None and cached[0] is arr \
                    and cached[1].shape[axis] == width:
                bufs.append(cached[2])
                hits += 1
                continue
            idx = [slice(None)] * arr.ndim
            idx[axis] = slice(s * width, (s + 1) * width)
            sl = np.ascontiguousarray(arr[tuple(idx)])
            if cached is not None and cached[1].shape == sl.shape \
                    and cached[1].dtype == sl.dtype \
                    and np.array_equal(cached[1], sl):
                # rows unchanged since last session: reuse the resident
                # buffer; re-key the source ref so the NEXT session takes
                # the identity fast path when the encoder reuses `arr`
                _SHARD_CACHE[key] = (arr, cached[1], cached[2])
                bufs.append(cached[2])
                hits += 1
                continue
            dev_buf = jax.device_put(sl, devs[s])
            _SHARD_CACHE[key] = (arr, sl, dev_buf)
            bufs.append(dev_buf)
            puts += 1
            put_bytes += sl.nbytes
        staged[name] = jax.make_array_from_single_device_arrays(
            arr.shape, node_sharding(mesh, arr.ndim, axis), bufs)
    if profile is not None:
        profile["h2d_shard_puts"] = profile.get("h2d_shard_puts", 0) + puts
        profile["h2d_shard_cached"] = \
            profile.get("h2d_shard_cached", 0) + hits
        profile["h2d_bytes"] = profile.get("h2d_bytes", 0) + put_bytes
    return staged


# ---------------------------------------------------------------------------
# bench mesh-curve probes: one shard's slice of the sharded stages
# ---------------------------------------------------------------------------


_PROBE_REPS = 16


@functools.partial(jax.jit, static_argnames=("spec",))
def _probe_refresh(spec, enc):
    """_PROBE_REPS full score refreshes (the rounds kernel's per-round
    fold) over a per-shard node slice — the dominant sharded stage of the
    allocate solve. The idle perturbation varies per iteration so XLA
    cannot hoist the loop-invariant refresh out of the rep loop (a session
    runs many rounds; the rep loop stands in for them)."""
    import jax.numpy as jnp
    from jax import lax

    from volcano_tpu.ops import rounds as rounds_mod

    occ = enc.get("excl_occ0") if spec.use_exclusion else None

    def body(i, acc):
        idle = enc["node_idle"] * (1.0 + i * 1e-12)
        sc = rounds_mod._refresh_scores(
            spec, enc, idle, enc["node_used"], enc["node_cnt"], occ)
        return acc + sc[0, 0]

    return lax.fori_loop(0, _PROBE_REPS, body,
                         jnp.asarray(0.0, enc["node_idle"].dtype))


@jax.jit
def _probe_evict_fold(vic_req, vic_queue, vic_samequeue, queue_alloc,
                      queue_deserved, eps):
    """_PROBE_REPS proportion deserved-floor victim walks
    (ops/evict._prop_verdict twin) over a per-shard [N/d, V] victim slice
    — the dominant sharded stage of the evict machines. Same
    per-iteration perturbation trick as _probe_refresh."""
    import jax.numpy as jnp
    from jax import lax

    v_width = vic_queue.shape[1]
    des = queue_deserved[vic_queue]
    claim = jnp.ones(vic_queue.shape, bool)

    def one_walk(qcur0):
        def body(v, carry):
            qcur, out = carry
            req = vic_req[:, v]
            cur = qcur[:, v]
            do = claim[:, v] & ~jnp.all(cur < req, axis=-1)
            fits = jnp.all(
                (des[:, v] < cur - req)
                | (jnp.abs(des[:, v] - (cur - req)) < eps), axis=-1)
            out = out.at[:, v].set(do & fits)
            upd = (do[:, None] & vic_samequeue[:, v, :])[..., None]
            qcur = jnp.where(upd, qcur - req[:, None, :], qcur)
            return qcur, out

        return lax.fori_loop(
            0, v_width, body, (qcur0, jnp.zeros(vic_queue.shape, bool)))[1]

    def rep(i, acc):
        qcur0 = queue_alloc[vic_queue] * (1.0 + i * 1e-12)
        return acc + jnp.sum(one_walk(qcur0).astype(jnp.int32))

    return lax.fori_loop(0, _PROBE_REPS, rep, jnp.int32(0))


def probe_per_device_stage_ms(spec, arrays: Dict[str, np.ndarray],
                              node_axis: Dict[str, int], shards: int,
                              vic_width: int = 8, iters: int = 3) -> float:
    """Measured wall of ONE shard's slice of the sharded session stages at
    per-shard width N/shards: the rounds score refresh over the real
    encoded class/node arrays, plus a proportion victim fold at the same
    node slice. On the real mesh the shards run concurrently, so this
    per-shard wall is the stage's critical path (up to the cross-shard
    verdict reduce); on the CPU proxy it is the honest measured stand-in
    for a parallelism the host cannot provide. Returns the median wall in
    ms across ``iters`` timed repetitions (first call pays the compile,
    excluded)."""
    import time

    n_total = int(np.asarray(arrays["node_idle"]).shape[0])
    width = per_shard(pad_axis_multiple(
        np.zeros(n_total, np.int8), 0, shards).shape[0], shards)
    enc = {}
    for k, v in sorted(arrays.items()):
        v = np.asarray(v)
        axis = node_axis.get(k)
        if axis is None:
            enc[k] = v
            continue
        v = pad_axis_multiple(v, axis, shards)
        idx = [slice(None)] * v.ndim
        idx[axis] = slice(0, width)
        enc[k] = np.ascontiguousarray(v[tuple(idx)])
    rng = np.random.default_rng(7)
    fdt = np.asarray(arrays["node_idle"]).dtype
    vic_req = rng.uniform(100.0, 4000.0, (width, vic_width, 2)).astype(fdt)
    vic_queue = rng.integers(0, 4, (width, vic_width)).astype(np.int32)
    samequeue = vic_queue[:, :, None] == vic_queue[:, None, :]
    queue_alloc = rng.uniform(1e4, 1e6, (4, 2)).astype(fdt)
    queue_deserved = rng.uniform(1e4, 1e6, (4, 2)).astype(fdt)
    eps = np.asarray([0.01, 0.01], fdt)

    def once():
        t0 = time.perf_counter()
        r = _probe_refresh(spec, enc)
        f = _probe_evict_fold(vic_req, vic_queue, samequeue, queue_alloc,
                              queue_deserved, eps)
        jax.block_until_ready((r, f))
        return (time.perf_counter() - t0) * 1e3

    once()  # compile, excluded from the timed reps
    walls = sorted(once() for _ in range(max(iters, 1)))
    return round(walls[len(walls) // 2], 3)
