"""Device-resident cluster state: the persistent cross-session replica
(ROADMAP item 2, DESIGN.md §19).

Every session before this module re-staged the state-dependent accounting
arrays — node idle/used/cnt, node capacity, job ready/alloc, queue and
namespace alloc — from host to device, even when the committed deltas
since the last session touched a handful of rows. The SnapshotKeeper
already knows exactly which rows those are (its dirty sets receive every
effector/watch mark), and the device already holds last session's staged
buffers (solver._DEVICE_CACHE / shard._SHARD_CACHE keep them resident).
This module closes the loop: the device copies become a STANDING REPLICA,
owned per cache, updated in place by narrow bucketed scatters instead of
wholesale re-packing.

The commit fork: effectors and watch ingestion keep mutating host state
and marking the keeper exactly as before (the host remains the source of
truth and the serial oracle). The replica subscribes to those same marks
through a keeper DirtyShadow (snapkeeper.add_shadow — the express lane's
subscription seam), so every committed mutation is forked host+device:
host now, via the normal effector; device at the next serve, as a row
scatter. Scatter rows are derived by exact comparison against the
replica's held host mirror — a subset of the keeper-marked rows (marks
over-approximate; the mirror diff is the byte-for-byte truth), which is
what keeps ``replica_scatter_rows`` proportional to rows that actually
changed. Witness mode (VOLCANO_TPU_WITNESS=1) closes the other direction:
every scattered row must be EXPLAINED by a keeper mark or an accounting-
generation movement, or the serve raises — an unexplained scatter is the
VT007 "unmarked mutation" class caught at runtime.

Families and kernels: one jitted scatter program per axis family
("node", "job", "queue", "ns" — jax.jit keyed on the family's pytree
structure), row indices padded to the solver's bucket ladder
(solver._bucket, VT002) by repeating the first dirty row — duplicate
writes of identical values, benign exactly as in express/encode.py and
rounds._rescore_dirty. Under the PR 10 mesh the node family stays
sharded: rows are grouped per shard, each changed shard scatters on its
OWN single-device buffer, and untouched shards are not even dispatched
to — the global array is reassembled without a copy
(jax.make_array_from_single_device_arrays, the ops/shard.py idiom).

Fallback kinds (``replica_rebuild{reason}``): any envelope miss
restages wholesale and counts the reason — "cold" (first serve),
"generation" (keeper wholesale invalidation), "shape"/"dtype" (padded
extent or cast changed), "mesh" (device layout changed), "axis" (node
membership/order), "fence" (lease fence epoch moved — a takeover must
not trust a replica built under the old term), "dense:<family>" (dirty
fraction past PATCH_FRACTION — a wholesale re-put is cheaper than the
scatter), "donated" (a fused chain consumed a standing buffer),
"error:<kind>". VOLCANO_TPU_REPLICA=0 disables the replica entirely; the
per-session pack+stage path it replaces is byte-for-byte identical (the
staged VALUES are equal by the mirror-diff construction), so replica-off
is the standing oracle the parity fuzz pins.

Whole-encode reuse: the replica also memoizes the previous session's full
prepare bundle (EncodedSnapshot + spec + layout + staged device dict)
keyed on the cache's pipeline fingerprint (cache.pipeline_fingerprint —
the PR 9 seal, complete per VT009) plus the encoder's session-external
inputs (round-robin cursor, tiers identity, mesh, mode). A steady-state
session whose fingerprint is unchanged re-encodes NOTHING: prepare
degenerates to the fingerprint probe, which is what drives the warm
steady-state ``encode_s`` to ~zero with ``h2d_puts == 0``. Any component
moving — a placement, a watch delta, an express commit, a policy update —
misses the token and takes the full encode honestly.

Donated-carry adoption (ops/session_fuse.py): a fused chain's final carry
holds the post-chain node used/cnt state on exactly the solve layout.
Instead of discarding it, the replica adopts the buffers; at the next
serve, changed rows that carry NO keeper mark are the chain's own
placements (bulk apply syncs, it does not mark) — the carry already holds
them, so they are not re-scattered ("no more re-patching rows the last
session placed"). Marked rows (post-session watch/effector churn) scatter
as usual. Witness mode disables the skip and scatters everything — the
adopted values then get overwritten with identical host truth, keeping
the oracle property testable.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict, List, Optional

import numpy as np

logger = logging.getLogger(__name__)

# state-dependent arrays the replica serves, by axis family. These are
# exactly the solver's "dyn" pack group (re-transferred every session
# before this module) plus the node-axis capacity arrays that ride the
# per-shard path under a mesh. Families share a row axis (axis 0) and
# scatter through one jitted program each.
FAMILIES: Dict[str, tuple] = {
    "node": ("node_idle", "node_used", "node_alloc", "node_cnt",
             "node_max_tasks"),
    "job": ("job_ready_base", "job_alloc0", "job_active0"),
    "queue": ("queue_bound_limbs", "queue_alloc0_limbs"),
    "ns": ("ns_alloc0", "ns_active0"),
}

SERVED = frozenset(n for names in FAMILIES.values() for n in names)

# only the node family is adoptable from a fused carry: the chain's final
# used/cnt ride the solve's node layout verbatim; its job/queue state
# lives on the evict axes and never matches the solve buffers
ADOPTABLE = frozenset({"node_used", "node_cnt"})

# dirty-row budget, shared rationale with express/encode.py: past this
# fraction of the axis a wholesale re-put beats the scatter
PATCH_FRACTION = 4


def enabled() -> bool:
    return os.environ.get("VOLCANO_TPU_REPLICA", "1") != "0"


def adopt_enabled() -> bool:
    return os.environ.get("VOLCANO_TPU_REPLICA_ADOPT", "1") != "0"


def get(cache, create: bool = True) -> Optional["DeviceReplica"]:
    """The cache's standing replica (one per SchedulerCache), created on
    first use. None when disabled or the cache has no snapshot keeper."""
    if not enabled():
        return None
    rep = getattr(cache, "_device_replica", None)
    if rep is None and create:
        keeper = getattr(cache, "snap_keeper", None)
        if keeper is None:
            return None
        rep = DeviceReplica(cache)
        cache._device_replica = rep
    return rep


def detach(cache) -> None:
    """Drop the cache's replica and its keeper shadow (tests/teardown)."""
    rep = getattr(cache, "_device_replica", None)
    if rep is not None:
        rep.detach()
        cache._device_replica = None


def scatter_rows(dev: Dict[str, object], idx, rows: Dict[str, object]):
    """The ONE bucketed row-scatter kernel, shared by every axis family
    (and by the express lane's column patch — express/encode.py): a
    functional ``at[idx].set`` over the family's buffer dict, jitted per
    pytree structure. ``idx`` must already be padded to a bucket width
    (solver._bucket) — the compiled program is keyed on (structure,
    shapes), so a raw live row count would retrace every churn."""
    global _scatter_jit
    if _scatter_jit is None:
        import jax

        def _scatter(bufs, idx, rows):
            return {k: bufs[k].at[idx].set(rows[k]) for k in bufs}

        _scatter_jit = jax.jit(_scatter)
    return _scatter_jit(dev, idx, rows)


_scatter_jit = None


def bucket_pad_rows(rows: List[int]) -> np.ndarray:
    """Row indices padded to the solver bucket ladder by repeating the
    first dirty row (duplicate writes of identical values are benign)."""
    from volcano_tpu.ops.solver import _bucket

    db = _bucket(max(len(rows), 1))
    return np.asarray([rows[0]] * (db - len(rows)) + list(rows), np.int32)


def _witness_on() -> bool:
    from volcano_tpu.analysis import witness

    return witness.enabled()


class DeviceReplica:
    """Standing device replica of the state-dependent solve arrays for
    one SchedulerCache, plus the whole-encode reuse memo. All methods run
    under the session (single-threaded) like the solver that calls them."""

    def __init__(self, cache):
        self.cache = cache
        # the effector fork: every keeper mark (bind/evict/status/watch)
        # lands in this shadow; in pipeline mode marks reach shadows from
        # both buffers (snapkeeper.mark_* is buffer-independent), so the
        # double-buffered keeper drives this replica's scatter queue too
        self.shadow = cache.snap_keeper.add_shadow()
        self.mirror: Dict[str, np.ndarray] = {}   # host twin of self.dev
        self.dev: Dict[str, object] = {}          # name -> global jax.Array
        self._node_shards: Dict[str, list] = {}   # name -> per-device bufs
        self._node_names: List[str] = []
        self._mesh = None
        self._mesh_key = None
        self._fence_epoch = None
        self._generation = None
        # witness-mode explanation baseline: node accounting gens and job
        # status versions as of the last serve
        self._node_gens: Dict[str, int] = {}
        self._job_vers: Dict[str, int] = {}
        self._job_uids: List[str] = []
        # invalidation channel for the replica's consumers (sealed in
        # cache.pipeline_fingerprint — VT009): bumps whenever device
        # content moves (scatter, rebuild, adoption)
        self.replica_epoch = 0
        # whole-encode reuse memo (serve_prepare / store_prepare)
        self._prep_token = None
        self._prep = None
        # donated-carry adoption (ops/session_fuse.py)
        self._adopted: set = set()
        self.stats = {
            "serves": 0, "scatters": 0, "scatter_rows": 0,
            "scatter_ms": 0.0, "rebuilds": {}, "encode_reuses": 0,
            "adoptions": 0, "adopt_rows_skipped": 0,
            "witness_violations": 0,
        }

    # -- lifecycle ---------------------------------------------------------

    def detach(self) -> None:
        self.cache.snap_keeper.drop_shadow(self.shadow)
        self.invalidate()

    def invalidate(self) -> None:
        """Drop all device state; the next serve rebuilds (counted)."""
        self.mirror.clear()
        self.dev.clear()
        self._node_shards.clear()
        self._adopted.clear()
        self._prep_token = None
        self._prep = None
        self.replica_epoch += 1

    # -- whole-encode reuse ------------------------------------------------

    def encode_token(self, ssn, mesh, mode: str) -> tuple:
        """Everything the encode reads, as a delta token: the cache's
        pipeline fingerprint (keeper dirty epoch + generation + fence +
        acct/status sums — complete per VT009) plus the encoder's
        session-external inputs: the round-robin cursor (enc.rr0), the
        tiers configuration (structural — dataclass repr, so equivalent
        confs match across fresh Tier objects), mesh layout, solve
        mode."""
        from volcano_tpu.ops import shard as shard_mod
        from volcano_tpu.scheduler.util import scheduler_helper

        return (self.cache.pipeline_fingerprint(),
                tuple(repr(t) for t in ssn.tiers),
                shard_mod.mesh_key(mesh),
                scheduler_helper._last_processed_node_index,
                mode)

    def serve_prepare(self, token: tuple) -> Optional[dict]:
        """The memoized prepare bundle when NOTHING the encode reads has
        moved since it was built — enc, spec, layout and the staged
        device dict are all still exact (device buffers are functional: a
        scatter would have moved the fingerprint first). None on miss."""
        if self._prep is None or token != self._prep_token:
            return None
        self.stats["encode_reuses"] += 1
        return dict(self._prep)

    def store_prepare(self, token: tuple, prep: dict) -> None:
        self._prep_token = token
        self._prep = dict(prep)

    def forget_prepare(self) -> None:
        """Invalidate only the whole-encode memo (the standing buffers
        stay valid — their mirror diff is state-based, not token-based)."""
        self._prep_token = None
        self._prep = None

    # -- serve -------------------------------------------------------------

    def serve(self, arrays: Dict[str, np.ndarray], ssn, enc, mesh,
              profile: Optional[dict] = None) -> Dict[str, object]:
        """Device twins of ``arrays`` (the padded+cast SERVED subset):
        standing buffers updated by bucketed row scatters where the host
        content moved, wholesale restage on any envelope miss (counted by
        reason). The returned dict merges into the solver's staged
        buffers; values are bit-identical to a fresh pack+stage of the
        same arrays by construction (the mirror diff is exact equality)."""
        t0 = time.perf_counter()
        self.stats["serves"] += 1
        reason = self._validate(arrays, enc, mesh)
        if reason is not None:
            self._rebuild(arrays, enc, mesh, reason)
        else:
            try:
                self._delta(arrays, ssn, enc)
            except Exception as e:  # defensive envelope: never wedge the
                # session on a replica bug — restage wholesale and count
                logger.exception("replica delta failed; restaging")
                self._rebuild(arrays, enc, mesh,
                              f"error:{type(e).__name__}")
        # marks are consumed once per serve whether or not they produced
        # rows (the mirror diff is the truth; the shadow is the witness)
        self.shadow.dirty_nodes.clear()
        self.shadow.dirty_jobs.clear()
        self._note_state(ssn, enc)
        if profile is not None:
            profile["replica_rebuilds"] = dict(self.stats["rebuilds"])
            profile["replica_scatter_rows"] = self.stats["scatter_rows"]
            profile["tpu_replica_scatter_ms"] = round(
                self.stats["scatter_ms"] * 1e3, 3)
            profile["replica_epoch"] = self.replica_epoch
            profile["replica_serve_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 3)
        return dict(self.dev)

    # -- envelope ----------------------------------------------------------

    def _validate(self, arrays, enc, mesh) -> Optional[str]:
        from volcano_tpu.ops import shard as shard_mod

        if not self.dev:
            return "cold"
        keeper = self.cache.snap_keeper
        if self._generation != keeper.generation:
            return "generation"
        if self._fence_epoch != getattr(self.cache, "fence_epoch", 0):
            return "fence"
        if shard_mod.mesh_key(mesh) != self._mesh_key:
            return "mesh"
        for name, arr in arrays.items():
            mir = self.mirror.get(name)
            if mir is None:
                return "cold"
            if mir.shape != arr.shape:
                return "shape"
            if mir.dtype != arr.dtype:
                return "dtype"
        if list(enc.node_names) != self._node_names:
            return "axis"
        for dev in self.dev.values():
            if getattr(dev, "is_deleted", lambda: False)():
                return "donated"
        return None

    # -- wholesale restage --------------------------------------------------

    def _rebuild(self, arrays, enc, mesh, reason: str) -> None:
        import jax

        from volcano_tpu.ops import shard as shard_mod

        rb = self.stats["rebuilds"]
        rb[reason] = rb.get(reason, 0) + 1
        self.mirror = dict(arrays)
        self.dev = {}
        self._node_shards = {}
        self._adopted.clear()
        self._mesh = mesh
        self._mesh_key = shard_mod.mesh_key(mesh)
        self._fence_epoch = getattr(self.cache, "fence_epoch", 0)
        self._generation = self.cache.snap_keeper.generation
        self._node_names = list(enc.node_names)
        if mesh is None:
            for name, arr in arrays.items():
                self.dev[name] = jax.device_put(arr)
        else:
            d = shard_mod.device_count(mesh)
            devs = list(mesh.devices.ravel())
            repl = shard_mod.replicated_sharding(mesh)
            for name, arr in arrays.items():
                if name in FAMILIES["node"]:
                    width = shard_mod.per_shard(arr.shape[0], d)
                    bufs = [jax.device_put(np.ascontiguousarray(
                        arr[s * width:(s + 1) * width]), devs[s])
                        for s in range(d)]
                    self._node_shards[name] = bufs
                    self.dev[name] = \
                        jax.make_array_from_single_device_arrays(
                            arr.shape,
                            shard_mod.node_sharding(mesh, arr.ndim, 0),
                            bufs)
                else:
                    self.dev[name] = jax.device_put(arr, repl)
        self.replica_epoch += 1

    # -- delta scatter ------------------------------------------------------

    def _changed_rows(self, family: str, arrays) -> List[int]:
        """Exact row diff against the mirror, unioned over the family's
        members (identity fast path first — the cast/pad pipeline hands
        back the same ndarray objects for untouched state)."""
        mask = None
        for name in FAMILIES[family]:
            if name not in arrays:
                continue
            arr, mir = arrays[name], self.mirror[name]
            if arr is mir:
                continue  # identity => content (pack-cache contract)
            diff = arr != mir
            if diff.ndim > 1:
                diff = diff.any(axis=tuple(range(1, diff.ndim)))
            mask = diff if mask is None else (mask | diff)
        if mask is None:
            return []
        return np.nonzero(mask)[0].tolist()

    def _delta(self, arrays, ssn, enc) -> None:
        moved = False
        for family in FAMILIES:
            rows = self._changed_rows(family, arrays)
            if not rows:
                continue
            self._witness_check(family, rows, ssn, enc)
            rows, skipped = self._strip_adopted(family, rows)
            n_rows = int(self.mirror[FAMILIES[family][0]].shape[0]) \
                if FAMILIES[family][0] in self.mirror else 0
            if rows and len(rows) * PATCH_FRACTION > max(n_rows, 1):
                self._dense_reput(family, arrays)
            elif rows:
                self._scatter_family(family, rows, arrays)
            for name in FAMILIES[family]:
                if name in arrays:
                    self.mirror[name] = arrays[name]
            moved = moved or bool(rows) or skipped
        if moved:
            self.replica_epoch += 1

    def _strip_adopted(self, family, rows):
        """Rows a donated fuse carry already holds on device (the last
        chain's own placements) are not re-scattered: bulk apply SYNCS
        the keeper (no shadow mark), so a changed row with no mark is the
        chain's own write and the adopted carry already holds its
        post-chain value (the fuse parity contract). Marked rows —
        post-session watch/effector churn — still scatter. Witness mode
        disables the skip so the oracle property stays testable."""
        if family != "node" or not self._adopted or _witness_on():
            return rows, False
        marked = self._shadow_node_rows()
        kept = [r for r in rows if r in marked]
        self.stats["adopt_rows_skipped"] += len(rows) - len(kept)
        self._adopted.clear()
        return kept, len(kept) != len(rows)

    def _shadow_node_rows(self) -> set:
        idx = {n: i for i, n in enumerate(self._node_names)}
        return {idx[n] for n in self.shadow.dirty_nodes if n in idx}

    def _dense_reput(self, family, arrays) -> None:
        """Dirty fraction past the patch budget: wholesale re-put of the
        family (counted as a rebuild reason, NOT as h2d_puts — the solver
        counter keeps meaning 'packed buffers that crossed the link')."""
        import jax

        from volcano_tpu.ops import shard as shard_mod

        rb = self.stats["rebuilds"]
        key = f"dense:{family}"
        rb[key] = rb.get(key, 0) + 1
        mesh = self._mesh
        for name in FAMILIES[family]:
            if name not in arrays:
                continue
            arr = arrays[name]
            if name in self._node_shards and mesh is not None:
                d = shard_mod.device_count(mesh)
                devs = list(mesh.devices.ravel())
                width = shard_mod.per_shard(arr.shape[0], d)
                bufs = [jax.device_put(np.ascontiguousarray(
                    arr[s * width:(s + 1) * width]), devs[s])
                    for s in range(d)]
                self._node_shards[name] = bufs
                self.dev[name] = jax.make_array_from_single_device_arrays(
                    arr.shape, shard_mod.node_sharding(mesh, arr.ndim, 0),
                    bufs)
            elif mesh is not None:
                self.dev[name] = jax.device_put(
                    arr, shard_mod.replicated_sharding(mesh))
            else:
                self.dev[name] = jax.device_put(arr)

    def _scatter_family(self, family, rows: List[int], arrays) -> None:
        """One bucketed scatter dispatch for the family (per shard under
        a mesh — untouched shards are not dispatched to)."""
        t0 = time.perf_counter()
        names = [n for n in FAMILIES[family] if n in arrays]
        if family == "node" and self._node_shards:
            self._scatter_node_shards(rows, arrays, names)
        else:
            idx = bucket_pad_rows(rows)
            vals = {n: np.ascontiguousarray(arrays[n][idx]) for n in names}
            out = scatter_rows({n: self.dev[n] for n in names}, idx, vals)
            self.dev.update(out)
        self.stats["scatters"] += 1
        self.stats["scatter_rows"] += len(rows)
        self.stats["scatter_ms"] += time.perf_counter() - t0
        _note_overlappable(len(rows))

    def _scatter_node_shards(self, rows, arrays, names) -> None:
        import jax

        from volcano_tpu.ops import shard as shard_mod

        mesh = self._mesh
        d = shard_mod.device_count(mesh)
        devs = list(mesh.devices.ravel())
        extent = int(arrays[names[0]].shape[0])
        width = shard_mod.per_shard(extent, d)
        by_shard: Dict[int, List[int]] = {}
        for r in rows:
            by_shard.setdefault(r // width, []).append(r)
        for s, srows in sorted(by_shard.items()):
            idx = bucket_pad_rows([r - s * width for r in srows])
            gidx = idx + np.int32(s * width)
            vals = {n: jax.device_put(
                np.ascontiguousarray(arrays[n][gidx]), devs[s])
                for n in names}
            didx = jax.device_put(idx, devs[s])
            out = scatter_rows(
                {n: self._node_shards[n][s] for n in names}, didx, vals)
            for n in names:
                self._node_shards[n][s] = out[n]
        for n in names:
            self.dev[n] = jax.make_array_from_single_device_arrays(
                arrays[n].shape,
                shard_mod.node_sharding(mesh, arrays[n].ndim, 0),
                self._node_shards[n])

    # -- donated-carry adoption (ops/session_fuse.py) -----------------------

    def adopt(self, buffers: Dict[str, object]) -> None:
        """A fused chain's final donated carry becomes the replica's next
        device state for the node accounting family instead of being
        discarded. Shapes/dtypes/sharding must match the standing
        buffers; anything else is ignored (the next serve's mirror diff
        re-scatters honestly)."""
        if not adopt_enabled() or not self.dev:
            return
        taken = 0
        for name, buf in buffers.items():
            dev = self.dev.get(name)
            if dev is None or name not in ADOPTABLE:
                continue
            if getattr(buf, "shape", None) != dev.shape \
                    or getattr(buf, "dtype", None) != dev.dtype \
                    or getattr(buf, "sharding", None) != \
                    getattr(dev, "sharding", None):
                continue
            self.dev[name] = buf
            self._adopted.add(name)
            # per-shard bookkeeping no longer matches the adopted global
            # buffer; rebuild the shard list from its addressable shards
            if name in self._node_shards:
                try:
                    self._node_shards[name] = [
                        sh.data for sh in sorted(
                            buf.addressable_shards,
                            key=lambda sh: sh.index[0].start or 0)]
                except Exception:
                    self._node_shards.pop(name, None)
            taken += 1
        if taken:
            self.stats["adoptions"] += 1
            self.replica_epoch += 1

    # -- witness ------------------------------------------------------------

    def _explained_rows(self, family, ssn, enc) -> Optional[set]:
        """Rows the keeper's marks / generation movements explain, in the
        encoder's row order — None when the family has no row-level
        explanation channel (queue/ns aggregates move whenever any job's
        allocation moves; their explanation is family-level)."""
        if family == "node":
            rows = self._shadow_node_rows()
            idx = {n: i for i, n in enumerate(self._node_names)}
            for name, i in idx.items():
                nd = ssn.nodes.get(name)
                if nd is not None and \
                        self._node_gens.get(name) != nd._acct_gen:
                    rows.add(i)
            return rows
        if family == "job":
            rows = set()
            marked = self.shadow.dirty_jobs
            uids = self._job_uids
            for i, j in enumerate(enc.job_infos):
                # a row whose OCCUPANT changed (membership shift — a job
                # arrived or left upstream of this row) is explained by
                # the membership delta itself, which the keeper marked on
                # the arriving/leaving job
                if j.uid in marked \
                        or i >= len(uids) or uids[i] != j.uid \
                        or self._job_vers.get(j.uid) != \
                        getattr(j, "_status_version", 0):
                    rows.add(i)
            # pad-region rows a SHRINK vacated (occupied last serve, pad
            # fill now) are likewise explained by the membership delta —
            # rows that were pad on both serves stay unexplained, since
            # pad fill is deterministic and must not move
            for i in range(len(enc.job_infos), len(uids)):
                rows.add(i)
            return rows
        return None

    def _witness_check(self, family, rows, ssn, enc) -> None:
        """VOLCANO_TPU_WITNESS=1: every scattered row must be explained
        by a keeper mark or an accounting-generation/status-version
        movement — the runtime half of VT007 for the device replica."""
        from volcano_tpu.analysis import witness

        if not witness.enabled() or not self._node_gens:
            return
        explained = self._explained_rows(family, ssn, enc)
        if explained is None:
            return  # queue/ns aggregates: family-level channel
        orphan = [r for r in rows if r not in explained]
        if orphan:
            self.stats["witness_violations"] += len(orphan)
            raise witness.WitnessViolation(
                f"replica scatter of {family} rows {orphan[:8]} has no "
                f"explaining keeper mark or generation movement — an "
                f"unmarked mutation reached the device replica")

    def _note_state(self, ssn, enc) -> None:
        """Record the explanation baseline for the next serve (witness
        bookkeeping only — skipped entirely when the witness is off)."""
        if not _witness_on():
            return
        gens: Dict[str, int] = {}
        for name in self._node_names:
            nd = ssn.nodes.get(name)
            if nd is not None:
                gens[name] = nd._acct_gen
        self._node_gens = gens
        self._job_vers = {
            j.uid: getattr(j, "_status_version", 0)
            for j in enc.job_infos}
        self._job_uids = [j.uid for j in enc.job_infos]


def _note_overlappable(rows: int) -> None:
    """Scatter dispatches are async device work that overlaps the rest of
    the host-side prepare (never fetched, never fenced here) — counted as
    overlappable dispatches, not sync points (utils/devprof.py)."""
    try:
        from volcano_tpu.utils import devprof

        devprof.note_overlappable(rows)
    except Exception:  # pragma: no cover - minimal host
        pass
