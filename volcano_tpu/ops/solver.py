"""Batch-allocator orchestration: encode -> pad -> device solve -> apply.

The solver is a drop-in for the allocate action's serial sweep: the tpuscore
plugin (volcano_tpu/scheduler/plugins/tpuscore.py) attaches a BatchAllocator
to the session, and actions/allocate.py hands the whole placement pass to it.
Placement decisions come back as a flat task->node assignment; they are
applied through the normal Statement machinery (framework/statement.py) so
event handlers, job status flips, and cache binding behave exactly as in the
serial path. Commit authority stays on the host — the device solve is a pure
function of the snapshot (SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

import numpy as np

from volcano_tpu.ops import kernels
from volcano_tpu.ops.encoder import EncodedSnapshot, EncoderFallback, encode_session
from volcano_tpu.utils import trace

logger = logging.getLogger(__name__)


def _bucket(n: int) -> int:
    """Next power-of-two-ish bucket to bound recompilations as task/job
    counts churn between sessions (SURVEY.md §7: pad-to-bucket shapes)."""
    if n <= 16:
        return 16
    b = 16
    while b < n:
        b *= 2
    return b


def _window_fields(arrays, shards: int = 1) -> Dict[str, int]:
    """Candidate-window sizing for the rounds kernel, off the bucket ladder.

    window_k bounds the per-class top-k node nomination: sized from class
    demand x capacity slack — the largest number of nodes any one class
    plausibly needs to cover its active demand (demand / mean-idle-per-node
    capacity), doubled for slack, then bucketed so the jit-static spec
    stays stable across steady-state sessions (VT002 contract: any k not
    drawn from the ladder re-keys the compiled program on every churn).
    dirty_k bounds the dirty-column rescoring gather the same way. Both 0
    (full-width sweeps, the pre-window behavior and the parity-fuzz
    reference) when the window would cover most of the node axis anyway,
    or when VOLCANO_TPU_WINDOW=0 forces the old path.

    ``shards`` is the mesh device count sharding the node axis (ROADMAP
    item 3): the windowed gathers and dirty-column rescores are
    node-parallel, so "covers most of the axis" and the dirty-gather cap
    must be judged against the PER-SHARD node count — at 8 devices a
    window that spans a whole shard's slice buys nothing on that shard,
    and a dirty_k sized off global N would gather 8x the useful columns.
    At shards=1 every value (and therefore every compiled-program bucket
    key) is identical to the pre-mesh ladder. Bindings are unaffected
    either way — the per-class coverage bit routes any truncated window
    to the full-width exactness fallback."""
    import os

    if os.environ.get("VOLCANO_TPU_WINDOW", "1") == "0":
        return {"window_k": 0, "dirty_k": 0}
    nb = int(np.asarray(arrays["node_idle"]).shape[0])
    # per-shard slice of the sharded node axis; the mesh pad made nb an
    # exact multiple of the device count (pad_encoded node_multiple)
    n_shard = max(nb // max(int(shards), 1), 1)
    task_cls = np.asarray(arrays["task_cls"])
    kb = int(np.asarray(arrays["cls_req"]).shape[0])
    demand = np.bincount(task_cls, minlength=kb).astype(np.float64)
    idle = np.asarray(arrays["node_idle"], dtype=np.float64)
    req = np.asarray(arrays["cls_req"], dtype=np.float64)
    mean_idle = idle.mean(axis=0) if idle.size else np.zeros(req.shape[1])
    with np.errstate(divide="ignore", invalid="ignore"):
        per_node = np.where(req > 0, mean_idle[None, :]
                            / np.where(req > 0, req, 1.0), np.inf)
    cap = per_node.min(axis=1)  # nodes one task-class instance needs^-1
    cap = np.where(np.isfinite(cap), np.clip(cap, 1.0, None),
                   float(max(task_cls.shape[0], 1)))
    need = int(np.ceil(demand / cap).max(initial=1.0))
    k = _bucket(max(16, 2 * need))
    if 2 * k > n_shard:
        # window would span most of (each shard's slice of) the axis:
        # pruning buys nothing and the coverage machinery would only add
        # per-round overhead
        return {"window_k": 0, "dirty_k": 0}
    return {"window_k": k,
            "dirty_k": min(_bucket(max(4 * k, 64)),
                           _bucket(max(n_shard // 8, 64)))}


def _pad_axis(a: np.ndarray, axis: int, size: int, fill=0):
    if a.shape[axis] == size:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, size - a.shape[axis])
    return np.pad(a, widths, constant_values=fill)


# plugins whose allocate-time effects the bulk writeback reproduces exactly
# (statement-free share/accounting updates in _apply_bulk); anything else in
# the conf forces the serial loop when rounds mode would otherwise run
ROUNDS_SAFE_PLUGINS = frozenset({
    "tpuscore", "priority", "gang", "drf", "proportion",
    "predicates", "nodeorder", "binpack", "conformance",
})

_NODE_AXIS = {
    "sig_mask": 1, "affinity_score": 1, "excl_occ0": 1,
    "node_idle": 0, "node_used": 0, "node_alloc": 0,
    "node_cnt": 0, "node_max_tasks": 0, "node_real": 0,
}

# arrays the rounds kernel never reads: per-task columns it re-derives from
# the class arrays on device (rounds.solve_rounds), the parity scan's
# sampling-window inputs, and the float queue bounds the rounds kernel
# reads as limbs instead — excluded from the rounds host->device transfer
_ROUNDS_SKIP = frozenset({
    "task_req", "task_initreq", "task_nz_cpu", "task_nz_mem",
    "task_sig", "task_has_pod", "node_real", "real_n",
    "queue_deserved", "queue_alloc0",
})


def pad_encoded(enc: EncodedSnapshot, node_multiple: int = 1) -> Dict[str, np.ndarray]:
    """Pad the churny axes (tasks, jobs) to buckets. The node axis is padded
    only up to `node_multiple` (mesh divisibility); padded node slots carry
    sig_mask=False and node_real=False, so the kernel's sampling window
    counts and selects over real nodes exactly as the serial helper does."""
    t, n, j, q, ns, s = enc.shape
    tb, jb = _bucket(t), _bucket(j)
    a = dict(enc.arrays)
    for name in ("task_req", "task_initreq", "task_nz_cpu", "task_nz_mem",
                 "task_sig", "task_has_pod", "task_job", "task_cls"):
        a[name] = _pad_axis(a[name], 0, tb)
    kb = _bucket(a["cls_req"].shape[0])
    for name in ("cls_req", "cls_initreq", "cls_nz_cpu", "cls_nz_mem",
                 "cls_sig", "cls_has_pod"):
        a[name] = _pad_axis(a[name], 0, kb,
                            fill=False if name == "cls_has_pod" else 0)
    a["cls_excl"] = _pad_axis(a["cls_excl"], 0, kb, fill=-1)
    # exclusion-group axis buckets so group-count churn cannot retrace
    gb = _bucket(a["excl_occ0"].shape[0])
    a["excl_occ0"] = _pad_axis(a["excl_occ0"], 0, gb, fill=False)
    for name in (
        "job_task_start", "job_task_count", "job_queue", "job_ns",
        "job_priority", "job_min_available", "job_ready_base",
        "job_ready_threshold", "job_alloc0",
    ):
        a[name] = _pad_axis(a[name], 0, jb)
    # padded jobs must never win selection and padded tasks never place:
    a["job_active0"] = _pad_axis(a["job_active0"], 0, jb, fill=False)
    a["job_tie_rank"] = _pad_axis(a["job_tie_rank"], 0, jb, fill=np.iinfo(np.int32).max - 1)
    if node_multiple > 1 and n % node_multiple:
        # the node axis deliberately pads to the MESH multiple, not a
        # power-of-two bucket: node count is deployment-stable (churn lives
        # in tasks/jobs), and bucket-padding it would change the sampling-
        # window arithmetic over real nodes.
        nb = ((n + node_multiple - 1) // node_multiple) * node_multiple
        for name, axis in _NODE_AXIS.items():
            fill = False if name in ("sig_mask", "node_real") else 0
            a[name] = _pad_axis(a[name], axis, nb, fill=fill)  # vclint: disable=VT002 - mesh-multiple node pad (see comment above)
    return a


# change-granularity groups for the packed transfer: arrays in one group
# share a packed buffer, and an unchanged buffer (byte-compared against the
# cached host copy) reuses its device-resident twin instead of re-crossing
# the host-device hop. Grouping follows churn rate: "dyn" changes every cycle,
# cluster/template topology groups only when the cluster changes. Unknown
# names land in "dyn" (always safe — just always re-transferred).
_GROUP_OF = {}
for _g, _names in {
    # only arrays that reach _pack in rounds mode (the _ROUNDS_SKIP per-task
    # matrices and sampling-window inputs are stripped before packing)
    "node": ("node_alloc", "node_max_tasks"),
    "sig": ("sig_mask", "affinity_score"),
    "cls": ("cls_req", "cls_initreq", "cls_nz_cpu", "cls_nz_mem",
            "cls_sig", "cls_has_pod", "cls_excl"),
    "sigx": ("excl_occ0",),
    "task": ("task_cls", "task_job"),
    "job": ("job_task_start", "job_task_count", "job_queue", "job_ns",
            "job_priority", "job_min_available", "job_ready_threshold",
            "job_tie_rank"),
    "conf": ("eps", "is_scalar", "res_unit", "drf_total", "drf_present",
             "binpack_w", "binpack_weight", "least_req_weight",
             "balanced_weight", "node_affinity_weight", "queue_present",
             "queue_tie_rank", "ns_rank", "ns_weight", "q_in_ns0"),
}.items():
    for _n in _names:
        _GROUP_OF[_n] = _g

# (host_bytes, device_array) per packed-buffer key; process-global because
# the BatchAllocator is rebuilt each session by the tpuscore plugin while
# the device buffers outlive sessions. ~[groups x dtype-kinds] entries, each
# replaced in place when content changes — bounded.
_DEVICE_CACHE: Dict[str, tuple] = {}

# packed-buffer reuse across sessions, keyed on the IDENTITY of the member
# arrays: with the snapshot keeper's long-lived node axis, the encoder
# returns the SAME ndarray objects for unchanged groups (node matrices,
# conf constants), so an identity-equal part list means the concatenated
# buffer is unchanged — skip the concat+astype, and _stage's byte compare
# against the device cache then degenerates to a cheap equal-array check.
# Arrays are never mutated in place once handed to the pack (the axis
# bumps its epoch and rebuilds matrices instead), which is what makes
# identity a sound proxy for content here. Holding the part refs keeps the
# ids stable; one entry per packed key — bounded like _DEVICE_CACHE.
_PACK_CACHE: Dict[str, tuple] = {}


def _pack(arrays: Dict[str, np.ndarray]):
    """Pack arrays into one flat buffer per (group, dtype class). Each
    host-to-device transfer pays a fixed cost per buffer, which for these
    small arrays outweighs the bytes, so ~15 buffers beat 46, and the
    grouped layout lets unchanged groups skip the hop entirely via
    _stage's content-validated device cache. Returns (layout, bufs): layout
    is the static tuple consumed by rounds.solve_rounds_packed; bufs maps
    "group.kind" -> flat ndarray."""
    parts: Dict[str, list] = {}
    srcs: Dict[str, list] = {}
    offsets: Dict[str, int] = {}
    layout = []
    for name in sorted(arrays):
        v = np.asarray(arrays[name])
        kind = "f" if v.dtype.kind == "f" else ("b" if v.dtype == np.bool_ else "i")
        key = _GROUP_OF.get(name, "dyn") + "." + kind
        flat = v.ravel()
        layout.append((name, key, offsets.get(key, 0), flat.size, v.shape))
        parts.setdefault(key, []).append(flat)
        srcs.setdefault(key, []).append(v)  # ravel() views get fresh ids;
        offsets[key] = offsets.get(key, 0) + flat.size  # token on sources
    bufs = {}
    for key, ps in parts.items():
        token = tuple(map(id, srcs[key]))
        cached = _PACK_CACHE.get(key)
        if cached is not None and cached[0] == token:
            bufs[key] = cached[2]
            continue
        kind = key[-1]
        if kind == "f":
            dt = np.result_type(*[p.dtype for p in ps])
        elif kind == "b":
            dt = np.bool_
        else:
            dt = np.int32
        buf = np.concatenate(ps).astype(dt, copy=False)
        _PACK_CACHE[key] = (token, srcs[key], buf)
        bufs[key] = buf
    return tuple(layout), bufs


def _stage(bufs: Dict[str, np.ndarray],
           profile: Optional[dict] = None, mesh=None) -> Dict[str, object]:
    """Host buffers -> device arrays, reusing device-resident twins whose
    bytes are unchanged since the last session (exact np.array_equal against
    the cached host copy — no hashing, no collisions). Steady-state cycles
    re-transfer only the buffers that actually changed.

    Under a ``mesh`` the buffers are committed fully-replicated over it (a
    single-device array cannot enter a jit call alongside mesh-sharded node
    buffers), and the cache entries carry the mesh identity — a buffer
    staged for one mesh shape is never handed to a program compiled for
    another (tests walk several mesh sizes in one process).

    When `profile` is given, records the H2D hop budget: how many buffers
    crossed the link (`h2d_puts`) vs were device-resident (`h2d_cached`),
    and the bytes shipped — each put is the unit of fixed transfer cost,
    so these counters ARE the per-session transfer story."""
    import jax

    from volcano_tpu.ops import shard as shard_mod

    mkey = shard_mod.mesh_key(mesh)
    sharding = shard_mod.replicated_sharding(mesh) if mesh is not None \
        else None
    staged = {}
    puts = cached_hits = 0
    put_bytes = 0
    for key, buf in bufs.items():
        cached = _DEVICE_CACHE.get(key)
        if (cached is not None and cached[0].dtype == buf.dtype
                and cached[0].shape == buf.shape
                and cached[2] == mkey
                and np.array_equal(cached[0], buf)):
            staged[key] = cached[1]
            cached_hits += 1
        else:
            dev = jax.device_put(buf) if sharding is None \
                else jax.device_put(buf, sharding)
            _DEVICE_CACHE[key] = (buf, dev, mkey)
            staged[key] = dev
            puts += 1
            put_bytes += buf.nbytes
    if profile is not None:
        profile["h2d_puts"] = puts
        profile["h2d_cached"] = cached_hits
        profile["h2d_bytes"] = put_bytes
    return staged


class BatchAllocator:
    """Callable attached to the session as ``ssn.batch_allocator``.

    Returns True when the batched solve ran; False => the caller must run
    the serial loop (EncoderFallback or no work to do).

    mode:
      - "parity": the sequential-scan kernel, bit-identical bindings to the
        serial loop (one device step per task — latency grows with T);
      - "rounds": the bulk-synchronous throughput kernel (ops/rounds.py),
        gang/feasibility/fair-share preserving but round-granular ordering;
      - "auto" (default): rounds when tasks >= AUTO_ROUNDS_THRESHOLD, else
        the serial host loop (returns False). Below the threshold the
        serial loop beats any device dispatch — the host-device round
        trip costs more than scoring a few hundred tasks on host — and the parity scan's
        per-task device steps are strictly for oracle testing.
    """

    AUTO_ROUNDS_THRESHOLD = 2048

    def __init__(self, mesh=None, dtype=None, profile: Optional[dict] = None,
                 mode: str = "auto"):
        self.mesh = mesh
        self.dtype = dtype
        self.mode = mode
        self.profile = profile if profile is not None else {}

    def _cast(self, arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        dtype = self.dtype
        if dtype is None:
            import jax

            dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
        out = {}
        for k, v in arrays.items():
            v = np.asarray(v)
            # copy=False keeps the IDENTITY of already-typed arrays stable
            # across sessions, which is what lets _pack's identity-token
            # cache recognize unchanged groups (the encoder reuses its
            # node/conf arrays between sessions when nothing moved)
            out[k] = v.astype(dtype, copy=False) \
                if v.dtype == np.float64 else v
        return out

    def _shard(self, arrays: Dict[str, np.ndarray]) -> Dict[str, object]:
        """Place node-axis arrays across the mesh; replicate the rest."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        out = {}
        for k, v in arrays.items():
            if k in _NODE_AXIS and np.asarray(v).ndim > 0:
                spec = [None] * np.asarray(v).ndim
                spec[_NODE_AXIS[k]] = "nodes"
                sh = NamedSharding(mesh, P(*spec))
            else:
                sh = NamedSharding(mesh, P())
            out[k] = jax.device_put(v, sh)
        return out

    def _prepare(self, ssn):
        """Encode + gate + (rounds, no-mesh) pack/stage, WITHOUT dispatching.

        Returns a dict bundle consumed by __call__ — and by the session-
        fused driver (ops/session_fuse.py), which dispatches the same
        spec/layout/staged through its own chained program — or None after
        recording the fallback reason in the profile (the caller then runs
        the serial loop). The ``vt.encode``, ``vt.pack`` and ``vt.h2d``
        spans feed the profile's ``encode_s``, ``pack_s`` and ``h2d_s``."""
        from volcano_tpu.scheduler import degrade

        with trace.span("encode", into=(self.profile, "encode_s")) as sp:
            prep = self._encode(ssn)
            if prep is not None:
                t, n, j, *_ = prep["enc"].shape
                sp.note(tasks=t, nodes=n, jobs=j)
        if prep is None or prep["staged"] is not None:
            return prep  # a fallback, or the reused bundle
        rep, node_multiple = prep.pop("replica"), prep.pop("node_multiple")
        if prep["mode"] != "rounds":
            return prep  # parity mode runs unstaged
        try:
            self._stage_rounds(ssn, prep, rep, node_multiple)
        except Exception as e:  # any device/compile failure -> serial oracle
            logger.exception("tpuscore prepare failed; falling back to serial")
            self.profile["fallback"] = f"solve error: {e}"
            degrade.note_kernel_failure()
            return None
        return prep

    def _encode(self, ssn):
        """The host half of _prepare: None on a fallback (reason recorded),
        the stored bundle when nothing the encode reads has moved, else a
        fresh unstaged bundle."""
        from volcano_tpu.scheduler import degrade

        if degrade.force_serial():
            # the kernel circuit breaker is OPEN (persistent device/compile
            # failure — the serial_host_solve rung): skip the doomed
            # dispatch entirely; allow()'s half-open probe re-enables the
            # device path automatically after the cooldown
            self.profile["fallback"] = (
                "degraded: kernel circuit open; serial host solve")
            return None
        if self.mode in ("rounds", "auto"):
            # the bulk writeback (_apply_bulk) bypasses the Statement event
            # machinery and hardcodes drf/proportion share updates; a
            # custom plugin registered through the public seam — even one
            # that only adds event handlers or allocatable fns, which the
            # encoder's extension-point checks cannot see — would silently
            # lose its allocate-event effects. Gate on plugin names BEFORE
            # paying the encode cost (in auto mode unknown plugins make
            # rounds unreachable regardless of the task-count threshold,
            # and sub-threshold sessions go serial anyway).
            unknown = {
                p.name for tier in ssn.tiers for p in tier.plugins
            } - ROUNDS_SAFE_PLUGINS
            if unknown:
                self.profile["fallback"] = (
                    f"rounds apply cannot honor custom plugins: {sorted(unknown)}")
                return None
        # whole-encode reuse (ops/replica.py): when NOTHING the encode
        # reads has moved since the last prepare — the cache's pipeline
        # fingerprint, the tiers identity, the round-robin cursor, mesh
        # and mode — the previous session's entire prepare bundle (enc +
        # spec + layout + staged device buffers) is still exact. This is
        # the steady-state fast path: prepare degenerates to the
        # fingerprint probe, encode_s ~ 0 with zero transfers.
        from volcano_tpu.ops import replica as replica_mod

        rep = replica_mod.get(getattr(ssn, "cache", None)) \
            if getattr(ssn, "cache", None) is not None else None
        if rep is not None:
            prev = rep.serve_prepare(rep.encode_token(ssn, self.mesh,
                                                      self.mode))
            if prev is not None:
                self.profile["encode_reused"] = True
                self.profile["h2d_puts"] = 0
                self.profile["h2d_cached"] = 0
                self.profile["replica_epoch"] = rep.replica_epoch
                return prev
        try:
            # rounds mode tolerates un-modeled constructs as a serial
            # residue (affinity/port tasks stay PENDING; releasing capacity
            # serves leftovers) — parity mode must stay bit-exact, so it
            # keeps the session-wide fallback
            enc = encode_session(
                ssn, allow_residue=self.mode in ("rounds", "auto"))
        except EncoderFallback as e:
            logger.info("tpuscore falling back to serial allocate: %s", e)
            self.profile["fallback"] = str(e)
            return None
        t, n, j, *_ = enc.shape
        if t == 0 or n == 0 or j == 0:
            # nothing for the device to place (possibly everything pending
            # is residue); the serial loop handles whatever remains
            if enc.residue_count:
                self.profile["fallback"] = (
                    f"all {enc.residue_count} pending tasks are residue "
                    f"(affinity/ports); serial loop handles them")
            return None

        mode = self.mode
        if mode == "auto":
            if t < self.AUTO_ROUNDS_THRESHOLD:
                self.profile["fallback"] = (
                    f"auto: {t} tasks below rounds threshold; serial loop "
                    f"is cheaper than a device hop")
                return None
            mode = "rounds"

        try:
            node_multiple = 1
            if self.mesh is not None:
                node_multiple = int(np.prod(list(self.mesh.shape.values())))
            arrays = self._cast(pad_encoded(enc, node_multiple))
            spec = None
            if mode == "rounds":
                from volcano_tpu.ops import rounds as rounds_mod

                # diminishing-returns floor: keyed to the PADDED buckets
                # so the spec (and the compiled program) stays stable
                # across steady-state sessions of the same shape. Only
                # worth it when the class axis spans multiple sweep
                # chunks — those are the sessions whose fixed per-round
                # cost dwarfs a few host-side residue placements;
                # single-chunk rounds are cheaper than the serial pass
                # they would shed
                tb = int(np.asarray(arrays["task_cls"]).shape[0])
                kb = int(np.asarray(arrays["cls_req"]).shape[0])
                wf = _window_fields(arrays, shards=node_multiple)
                spec = enc.spec._replace(
                    round_min_progress=(
                        max(2, tb // 128) if kb > rounds_mod.CHUNK else 0),
                    # a few cheap narrow rounds over the capped remainder
                    # before the sequential tail (rounds.py straggler
                    # rounds); each costs one windowed round (~no full
                    # sweep) and typically halves the tail
                    straggler_rounds=4 if kb > rounds_mod.CHUNK else 0,
                    window_k=wf["window_k"], dirty_k=wf["dirty_k"])
                arrays = {k: v for k, v in arrays.items()
                          if k not in _ROUNDS_SKIP}
            elif self.mesh is not None:
                # parity mode keeps the per-array sharded puts (its
                # sequential-scan kernel is strictly an oracle surface);
                # rounds mode stages through the per-shard device cache
                # (_stage_rounds)
                arrays = self._shard(arrays)
        except Exception as e:  # any device/compile failure -> serial oracle
            logger.exception("tpuscore prepare failed; falling back to serial")
            self.profile["fallback"] = f"solve error: {e}"
            degrade.note_kernel_failure()
            return None
        return dict(mode=mode, enc=enc, arrays=arrays, spec=spec,
                    layout=None, staged=None,
                    node_multiple=node_multiple, replica=rep,
                    # host half of the read-set descriptor the pipeline
                    # seals at speculative dispatch (the node half is
                    # the kernel's touched mask, parse_packed): the job
                    # uids the solve encoded, the queue/namespace ids
                    # whose policy rows it consumed, and the
                    # conservatism flag — residue/releasing sessions
                    # run a serial pass over the whole snapshot at
                    # apply, so the node read set degrades to the full
                    # axis (driver side)
                    readset=dict(
                        job_uids=[j.uid for j in enc.job_infos],
                        queue_ids=list(enc.queue_uids),
                        ns_ids=list(enc.ns_names),
                        read_all_nodes=bool(
                            enc.residue_count or enc.has_releasing),
                    ))

    def _stage_rounds(self, ssn, prep: dict, rep, node_multiple: int
                      ) -> None:
        """Rounds mode: grouped pack and device staging of a fresh bundle,
        in place; the bundle is then stored for whole-encode reuse."""
        from volcano_tpu.ops import replica as replica_mod

        enc, arrays = prep["enc"], prep["arrays"]
        with trace.span("pack", into=(self.profile, "pack_s")):
            # grouped packed transfer + device cache: unchanged groups
            # never re-cross the host-device hop, and the solve
            # returns ONE fetchable array (assign + rounds limbs) so
            # the session pays a single D2H round trip. Under a mesh
            # the node-axis arrays leave the pack and ride beside it
            # as per-shard sharded buffers (ops/shard.py): unchanged
            # shards stay device-resident, changed shards pay one put
            # each — in parallel across the devices — and the merged
            # dict feeds the SAME solve_rounds_packed entry (plain
            # keys folded back in by rounds.unpack_layout)
            # the state-dependent accounting arrays leave the pack and
            # ride the standing device replica (ops/replica.py):
            # committed deltas since the last session become bucketed
            # row scatters against the persistent buffers instead of a
            # host re-pack + device_put, and unpack_layout folds the
            # plain-keyed replica buffers back in beside the packed
            # groups exactly like the mesh path's sharded node arrays
            rep_part = {}
            if rep is not None:
                rep_part = {k: v for k, v in arrays.items()
                            if k in replica_mod.SERVED}
            node_part = {}
            if self.mesh is not None:
                node_part = {k: arrays[k] for k in _NODE_AXIS
                             if k in arrays and k not in rep_part}
            rest = {k: v for k, v in arrays.items()
                    if k not in node_part and k not in rep_part}
            prep["layout"], bufs = _pack(rest)
        with trace.span("h2d", into=(self.profile, "h2d_s")) as sp:
            staged = _stage(bufs, self.profile, mesh=self.mesh)
            if self.mesh is not None:
                from volcano_tpu.ops import shard as shard_mod

                staged.update(shard_mod.stage_node_arrays(
                    node_part, _NODE_AXIS, self.mesh, self.profile))
                self.profile["mesh_devices"] = node_multiple
            if rep_part:
                staged.update(rep.serve(
                    rep_part, ssn, enc, self.mesh, self.profile))
            sp.note(h2d_bytes=self.profile.get("h2d_bytes", 0))
        prep["staged"] = staged
        if rep is not None:
            # token recomputed AFTER the serve: the serve bumps the
            # replica epoch (a fingerprint component), and the
            # stored token must describe the state this bundle was
            # built against so an unchanged next session hits. The
            # store releases the previous session's bundle.
            with trace.span("replica.store"):
                rep.store_prepare(
                    rep.encode_token(ssn, self.mesh, self.mode), prep)

    def parse_packed(self, out: np.ndarray):
        """Split the packed single-fetch result into (assign, meta dict)."""
        from volcano_tpu.ops import rounds as rounds_mod

        pt = rounds_mod.PROF_TAIL
        meta = out[-pt:].astype(np.int64)
        nb = int(meta[0])  # padded node count: sizes the touched mask
        assign = out[:-(pt + nb)].astype(np.int32, copy=False)
        return assign, dict(
            n_rounds=int(meta[1]) | (int(meta[2]) << 15),
            tail_placed=int(meta[3]),
            full_sweeps=int(meta[4]),
            round_capped=bool(meta[5]),
            placed_hist=meta[6:],
            # touched-node mask (read-set descriptor): which node columns
            # the solve consumed, padded-axis indexed; all-ones whenever
            # the kernel could not prove a narrower read
            touched_nodes=np.asarray(out[-(pt + nb):-pt]) != 0,
        )

    def apply_packed(self, ssn, prep: dict, assign: np.ndarray,
                     meta: dict) -> bool:
        """Profile + bulk-apply a rounds result (shared by the per-action
        dispatch below and the session-fused driver, so both land identical
        session state and profile keys)."""
        from volcano_tpu.ops import rounds as rounds_mod

        enc = prep["enc"]
        spec = prep["spec"]
        self.profile["rounds"] = int(meta["n_rounds"])
        # candidate-window round profile: how many rounds needed the
        # full-width exactness fallback, the jit-static window/dirty
        # buckets, and the placed-per-round histogram (clamped to
        # PROF_SLOTS slots, values to the int16 limb)
        self.profile["full_sweep_rounds"] = meta["full_sweeps"]
        self.profile["window_k"] = spec.window_k
        self.profile["dirty_k"] = spec.dirty_k
        self.profile["round_capped"] = meta["round_capped"]
        self.profile["round_placed"] = [
            int(x) for x in meta["placed_hist"][
                :min(int(meta["n_rounds"]), rounds_mod.PROF_SLOTS)]]
        # always emitted (0 when the tail never ran) so bench
        # consumers need no existence checks. This is a count of
        # tail placement ATTEMPTS: the post-tail gang-atomicity
        # strip may later revoke placements of gangs that stayed
        # short, and those revocations are not subtracted here —
        # treat as an upper bound on tail contribution, not a net
        # figure
        self.profile["tail_placed"] = meta["tail_placed"]
        self.profile["mode"] = "rounds"
        self._applied(ssn, enc, assign, self._apply_bulk)
        return True

    def _applied(self, ssn, enc: EncodedSnapshot, assign: np.ndarray,
                 apply_fn) -> None:
        """Write the placements back under the ``vt.apply`` span (the
        profile's ``apply_s``) and record the session's solve counts."""
        t, n, j, *_ = enc.shape
        placed = int((assign[: len(enc.task_infos)] >= 0).sum())
        with trace.span("apply", into=(self.profile, "apply_s"),
                        binds=placed):
            apply_fn(ssn, enc, assign)
        self.profile.update(
            tasks=t, nodes=n, jobs=j, placed=placed,
            residue=enc.residue_count,
            has_releasing=enc.has_releasing,
        )

    def __call__(self, ssn) -> bool:
        from volcano_tpu.scheduler.util import scheduler_helper
        from volcano_tpu.utils import devprof

        prep = self._prepare(ssn)
        if prep is None:
            return False
        mode = prep["mode"]
        enc = prep["enc"]
        try:
            if mode == "rounds":
                from volcano_tpu.ops import rounds as rounds_mod

                # async fetch: the copy starts at dispatch, and the
                # wait is the session's counted sync point (devprof).
                # One entry serves both layouts: under a mesh the staged
                # dict carries the sharded node buffers beside the packed
                # groups (unpack_layout merges them), so the sharded
                # session is byte-for-byte the single-device program over
                # identical values
                with trace.span("dispatch") as sp:
                    wait = devprof.start_fetch(rounds_mod.solve_rounds_packed(
                        prep["spec"], prep["layout"], prep["staged"]))
                    out = wait()
                    assign, meta = self.parse_packed(out)
                    # the packed result is int16 up to 32,766 nodes, int32
                    # past them (rounds.pack_result)
                    sp.note(rounds=meta["n_rounds"],
                            full_sweeps=meta["full_sweeps"],
                            window_k=prep["spec"].window_k,
                            d2h_bytes=int(out.nbytes))
                assign = np.asarray(assign)
            else:
                with trace.span("dispatch", mode=mode):
                    assign, rr = kernels.solve_allocate(
                        enc.spec, prep["arrays"], np.int32(enc.rr0),
                        np.int32(enc.num_to_find)
                    )
                    assign = np.asarray(assign)
                # round-robin index continues across sessions exactly like
                # the serial helper (scheduler_helper.go:38)
                scheduler_helper._last_processed_node_index = int(rr)
        except Exception as e:  # any device/compile failure -> serial oracle
            logger.exception("tpuscore solve failed; falling back to serial")
            self.profile["fallback"] = f"solve error: {e}"
            from volcano_tpu.scheduler import degrade

            degrade.note_kernel_failure()
            return False
        from volcano_tpu.scheduler import degrade

        degrade.note_kernel_ok()

        if mode == "rounds":
            return self.apply_packed(ssn, prep, assign, meta)
        self.profile["mode"] = mode
        self._applied(ssn, enc, assign, self._apply)
        return True

    def _apply(self, ssn, enc: EncodedSnapshot, assign: np.ndarray) -> None:
        """Replay device placements through per-job statements; every
        committed job is gang-ready by construction, so stmt.commit()
        dispatches binds exactly as the serial path would."""
        from volcano_tpu.api.unschedule_info import FitErrors

        start = enc.arrays["job_task_start"]
        count = enc.arrays["job_task_count"]
        for ji, job in enumerate(enc.job_infos):
            lo, hi = int(start[ji]), int(start[ji]) + int(count[ji])
            placed = [
                (ti, int(assign[ti])) for ti in range(lo, hi) if assign[ti] >= 0
            ]
            if len(placed) < hi - lo and not job.ready():
                # the solve left this gang short: record a fit error for the
                # first unplaced task so gang.on_session_close emits the same
                # Unschedulable condition structure as the serial path
                for ti in range(lo, hi):
                    if assign[ti] < 0:
                        fe = FitErrors()
                        fe.set_error(
                            "0/%d nodes are available in the batched "
                            "feasibility/fit solve" % len(enc.node_names))
                        job.nodes_fit_errors[enc.task_infos[ti].uid] = fe
                        break
            if not placed:
                continue
            stmt = ssn.statement()
            ok = True
            for ti, ni in placed:
                task = enc.task_infos[ti]
                try:
                    stmt.allocate(task, enc.node_names[ni])
                except (KeyError, RuntimeError) as e:  # pragma: no cover
                    logger.error(
                        "tpuscore apply failed for %s -> %s: %s",
                        task.uid, enc.node_names[ni], e,
                    )
                    ok = False
                    break
            if ok and ssn.job_ready(job):
                stmt.commit()
            else:  # pragma: no cover - device decisions are gang-consistent
                stmt.discard()

    def _apply_bulk(self, ssn, enc: EncodedSnapshot, assign: np.ndarray) -> None:
        """Bulk writeback for rounds mode: same end state as the statement
        path (session + cache task/node/job status, binder calls, plugin
        shares) but with all resource accounting vectorized and the
        remaining per-task work reduced to attribute writes + dict moves.

        Bumps the session placement generation: these writes bypass the
        Session/Statement mutators, so any cached dense view must rebuild
        (preemptview.build's generation gate).

        The statement path costs ~40us/task in event handlers, epsilon
        asserts, and per-task Resource arithmetic; at 50k tasks that is the
        session bottleneck, not the device solve. Here each placement costs
        ~2us: status/node_name on the session + cache task, the index-bucket
        move on both JobInfos, one shared status-frozen clone into both node
        task-maps, and the batch binder/event entries."""
        from volcano_tpu.api.resource import Resource
        from volcano_tpu.api.types import TaskStatus
        from volcano_tpu.api.unschedule_info import FitErrors
        from volcano_tpu.scheduler.cache.interface import BindManyError

        ssn._placement_gen += 1
        with trace.span("apply.prep"):
            a = enc.arrays
            t_real = len(enc.task_infos)
            assign = assign[:t_real]
            capped = assign == -2
            if capped.any():
                # diminishing-returns leftovers (rounds.py capped exit) fold
                # into residue accounting: the serial pass retries exactly
                # these tasks, and the fit-error stamping below skips their
                # jobs — no stale '0/N nodes' error outlives the retry
                cap_counts = np.bincount(
                    a["task_job"][:t_real][capped],
                    minlength=len(enc.job_infos)).astype(np.int32)
                if enc.job_residue is None:
                    enc.job_residue = cap_counts
                else:
                    enc.job_residue = enc.job_residue + cap_counts
                enc.residue_count += int(capped.sum())
                self.profile["round_capped_tasks"] = int(capped.sum())
                assign = np.where(capped, np.int32(-1), assign)
            placed_mask = assign >= 0

            # --- vectorized per-node / per-job resource deltas ----------------
            node_ids = assign[placed_mask]
            reqs = a["task_req"][:t_real][placed_mask]
            n_count = len(enc.node_names)
            j_count = len(enc.job_infos)
            sums = np.zeros((n_count, reqs.shape[1]))
            np.add.at(sums, node_ids, reqs)
            counts = np.bincount(node_ids, minlength=n_count)
            job_ids = a["task_job"][:t_real][placed_mask]
            job_sums = np.zeros((j_count, reqs.shape[1]))
            np.add.at(job_sums, job_ids, reqs)
            job_placed_n = np.bincount(job_ids, minlength=j_count)

            # resource dim names recovered from the encoder's layout
            scalar_names = enc.resource_names[2:]

            def apply_delta(res: Resource, vec, sign: float) -> None:
                res.milli_cpu += sign * vec[0]
                res.memory += sign * vec[1]
                for si, name in enumerate(scalar_names):
                    q = vec[2 + si]
                    if q:
                        res.add_scalar(name, sign * q)

            BINDING = TaskStatus.BINDING
            PENDING = TaskStatus.PENDING
            task_infos = enc.task_infos
            job_infos = enc.job_infos
            node_names = enc.node_names
            cache = ssn.cache
            ssn_nodes = ssn.nodes
            cache_nodes = cache.nodes
            vb = cache.volume_binder
            # volume calls are skippable when the binder is a declared no-op
            # OR no pod in the cache references a PVC (counter maintained by
            # the cache's task handlers) — a real StoreVolumeBinder then costs
            # nothing on PVC-free sessions and the native loop stays eligible
            vols_noop = getattr(vb, "IS_NOOP", False) or (
                getattr(cache, "_pvc_pod_count", 1) == 0)
            alloc_vols = vb.allocate_volumes
            bind_vols = vb.bind_volumes

            placed_arr = np.nonzero(placed_mask)[0]
            job_nz_arr = np.nonzero(job_placed_n)[0]
            seg_ends_arr = np.cumsum(job_placed_n[job_nz_arr])
            job_nz = job_nz_arr.tolist()

            # tasks are contiguous per job on the flat axis, so placed visits
            # each job's placements as one contiguous run. The loop allocates
            # ~1 object + a few dict entries per task; suppress the cyclic GC so
            # gen-promotion scans of the (multi-million-object) session heap
            # don't fire mid-apply.
            import gc
        with trace.span("apply.loop"):
            gc_was = gc.isenabled()
            gc.disable()
            bind_tasks: list = []
            bind_pods: list = []
            bind_hosts: list = []
            bind_keys: list = []
            # native batched loop (volcano_tpu/_native/fastapply.c): identical
            # semantics to the Python body below, which remains the fallback
            # and oracle; volumes force the Python path (effector calls)
            # non-blocking: a cold process compiles on a background thread
            # and THIS session runs the Python loop; never wait on cc here
            from volcano_tpu._native import get_fastapply_nowait

            mod = get_fastapply_nowait()
            fast_all = getattr(mod, "apply_all_jobs", None) \
                if (mod is not None and vols_noop) else None
            # a keyed binder that declares it does not consume pod objects
            # (KEYED_NEEDS_PODS = False — the k8s Bind subresource needs only
            # name + target) lets the writeback skip 50k .pod extractions;
            # the BindManyError retry path still reads task.pod lazily
            binder0 = cache.binder
            want_pods = not (
                getattr(binder0, "bind_many_keyed", None) is not None
                and getattr(binder0, "KEYED_NEEDS_PODS", True) is False)
            # cache-mirror deferral: the reference's Bind is an async goroutine
            # and its scheduler cache learns pod statuses from LATER watch
            # events (cache.go:123-135,597-613) — only the SESSION state must be
            # current inside the cycle. The cache-side half of this writeback
            # (status flips, bucket moves, node maps, allocated sums on the
            # cache twins) is therefore queued on the cache and applied at
            # session close / before the next snapshot (cache.flush_mirror),
            # halving the per-task work on the measured path. Bulk-bound tasks
            # are disjoint from anything later actions touch through the cache
            # effectors (they bind/evict PENDING/RUNNING tasks, never this
            # session's BINDING set), and the deferred node deltas touch
            # idle/used while evictions touch releasing — commutative.
            defer_mirror = getattr(cache, "defer_mirror", None)
            do_cache_inline = defer_mirror is None
            try:
                if fast_all is not None:
                    fast_all(
                        job_nz_arr, seg_ends_arr, placed_arr,
                        assign.astype(np.int64),
                        task_infos, node_names, ssn_nodes,
                        cache_nodes if do_cache_inline else None,
                        job_infos,
                        cache.jobs if do_cache_inline else None,
                        PENDING, BINDING,
                        np.ascontiguousarray(job_sums),
                        tuple(scalar_names),
                        bind_tasks, bind_pods, bind_hosts, bind_keys,
                        int(want_pods))
                    loop_jobs = ()  # the batched call covered every job
                else:
                    loop_jobs = job_nz
                    assign_l = assign.tolist()
                    placed_l = placed_arr.tolist()
                    job_sums_l = job_sums.tolist()
                lo = 0
                for ji, hi in zip(loop_jobs, seg_ends_arr.tolist()):
                    tis = placed_l[lo:hi]
                    lo = hi
                    job = job_infos[ji]
                    cache_job = cache.jobs.get(job.uid) if do_cache_inline else None
                    job._status_version += 1  # direct index surgery below
                    idx = job.task_status_index
                    s_pending = idx.get(PENDING)
                    # wholesale bucket move when the whole PENDING set placed
                    # (the common all-or-nothing gang case): O(1) instead of
                    # per-task pop+insert
                    if s_pending is not None and len(s_pending) == len(tis):
                        s_binding = idx.get(BINDING)
                        if s_binding is None:
                            idx[BINDING] = s_pending
                        else:
                            s_binding.update(s_pending)
                        del idx[PENDING]
                        s_pending = None
                        s_binding = idx[BINDING]
                    else:
                        s_binding = idx.get(BINDING)
                        if s_binding is None:
                            s_binding = idx[BINDING] = {}
                    if cache_job is not None:
                        c_tasks = cache_job.tasks
                        cache_job._status_version += 1  # direct index surgery
                        cidx = cache_job.task_status_index
                        c_pending = cidx.get(PENDING)
                        if c_pending is not None and len(c_pending) == len(tis):
                            c_binding = cidx.get(BINDING)
                            if c_binding is None:
                                cidx[BINDING] = c_pending
                            else:
                                c_binding.update(c_pending)
                            del cidx[PENDING]
                            c_pending = None
                            c_binding = cidx[BINDING]
                        else:
                            c_binding = cidx.get(BINDING)
                            if c_binding is None:
                                c_binding = cidx[BINDING] = {}
                    else:
                        c_tasks = c_pending = c_binding = None

                    for ti in tis:
                        task = task_infos[ti]
                        host = node_names[assign_l[ti]]
                        task.node_name = host
                        task.status = BINDING
                        uid = task.uid
                        if s_pending is not None:
                            s_pending.pop(uid, None)
                            s_binding[uid] = task
                        # the session task itself is shared into both node
                        # task-maps (the serial path stores clones so LATER
                        # status flips can't corrupt node accounting;
                        # nothing flips a BINDING task in place for the
                        # rest of this session, and cache watch events
                        # REPLACE node entries rather than mutate them, so
                        # the share is safe and saves one object per
                        # placement)
                        key = task.key
                        node = ssn_nodes[host]
                        node._acct_gen += 1  # invalidate snapshot node-axis
                        node.tasks[key] = task
                        if c_tasks is not None:
                            ctask = c_tasks.get(uid)
                            if ctask is not None:
                                ctask.node_name = host
                                ctask.status = BINDING
                                if c_pending is not None:
                                    c_pending.pop(uid, None)
                                    c_binding[uid] = ctask
                                cnode = cache_nodes.get(host)
                                if cnode is not None:
                                    cnode._acct_gen += 1
                                    cnode.tasks[key] = task
                        # effector contract matches session.dispatch ->
                        # cache.bind (cache.py:374-395): volumes, binder
                        if not vols_noop:
                            alloc_vols(task, host)
                            bind_vols(task)
                        bind_tasks.append(task)
                        if want_pods:
                            bind_pods.append(task.pod)
                        bind_hosts.append(host)
                        bind_keys.append(key)

                    # PENDING -> BINDING leaves total_request unchanged;
                    # allocated grows by the job's placed sum, pending_sum
                    # shrinks by it (every placed task left the PENDING bucket)
                    vec = job_sums_l[ji]
                    apply_delta(job.allocated, vec, +1.0)
                    apply_delta(job.pending_sum, vec, -1.0)
                    if cache_job is not None:
                        apply_delta(cache_job.allocated, vec, +1.0)
                        apply_delta(cache_job.pending_sum, vec, -1.0)
            finally:
                if gc_was:
                    gc.enable()

        with trace.span("apply.bind"):

            # --- bulk node accounting (session tree; cache tree deferred) -----
            # runs BEFORE the mirror defer so the payload can capture the final
            # session-side node generations (the keeper's sync point)
            node_nz = np.nonzero(counts)[0]
            fast_nodes = getattr(mod, "apply_node_deltas", None) \
                if mod is not None else None
            if fast_nodes is not None:
                fast_nodes(node_nz, np.ascontiguousarray(sums),
                           node_names, ssn_nodes,
                           cache_nodes if do_cache_inline else None,
                           tuple(scalar_names))
            else:
                sums_l = sums.tolist()
                for ni in node_nz.tolist():
                    vec = sums_l[ni]
                    name = node_names[ni]
                    nodes_pair = (ssn_nodes.get(name), cache_nodes.get(name)) \
                        if do_cache_inline else (ssn_nodes.get(name),)
                    for node in nodes_pair:
                        if node is None:
                            continue
                        node._acct_gen += 1  # invalidate snapshot node-axis
                        apply_delta(node.idle, vec, -1.0)
                        apply_delta(node.used, vec, +1.0)

            if not do_cache_inline:
                # queued only after the session-side loop SUCCEEDED (a loop
                # failure must not leave the cache applying phantom
                # placements), and before any effector runs — a store-backed
                # binder can fire synchronous watch events whose handlers
                # flush_mirror(), and they must land on a synced mirror.
                # job_vers/node_gens are the session-side versions at this
                # point (all bulk mutations applied): after an exact flush the
                # cache twins equal these objects, so the snapshot keeper can
                # re-record them as in-sync and reuse them next open.
                # placed_req rows let the flush subtract any placement it had
                # to skip (pod deleted in the defer window) from the node sums.
                defer_mirror(dict(
                    job_nz=job_nz_arr, seg_ends=seg_ends_arr, placed=placed_arr,
                    assign=assign, task_infos=task_infos, node_names=node_names,
                    job_infos=job_infos, job_sums=job_sums,
                    scalar_names=tuple(scalar_names),
                    node_nz=node_nz, node_sums=sums,
                    placed_req=reqs,
                    job_vers=[job_infos[ji]._status_version
                              for ji in job_nz],
                    node_gens=[ssn_nodes[node_names[ni]]._acct_gen
                               for ni in node_nz.tolist()]))
                self.profile["mirror_deferred"] = 1

            # --- batch binder + events ----------------------------------------
            binder = cache.binder
            retry_from = None
            keyed_bind = getattr(binder, "bind_many_keyed", None)
            if keyed_bind is not None:
                # the apply loop already derived each placement's ns/name key;
                # a keyed binder skips 50k metadata re-derivations (pods is
                # None when the binder declared KEYED_NEEDS_PODS = False)
                try:
                    keyed_bind(bind_keys, bind_pods if want_pods else None,
                               bind_hosts)
                except BindManyError as e:
                    retry_from = e.done
                except Exception:
                    retry_from = 0
            elif hasattr(binder, "bind_many"):
                try:
                    # pods were extracted during the apply loop; zip streams the
                    # pairs without materializing another 50k-tuple list
                    binder.bind_many(zip(bind_pods, bind_hosts))
                except BindManyError as e:
                    retry_from = e.done
                except Exception:
                    # bind_many contract: partial progress => BindManyError; a
                    # bare exception means nothing was bound
                    retry_from = 0
            else:
                retry_from = 0
            failed_binds: set = set()
            if retry_from is not None:
                # per-task so one bad pod degrades to resync, not a lost
                # session (cache.go:597-599 semantics); failures are tracked
                # so the event record below stays bind-exact — a fenced
                # (deposed-leader) or otherwise failed bind must not leave a
                # phantom Scheduled event behind
                for k, (task, host) in enumerate(
                        zip(bind_tasks[retry_from:], bind_hosts[retry_from:]),
                        start=retry_from):
                    try:
                        binder.bind(task.pod, host)
                    except Exception:
                        cache.resync_task(task)
                        failed_binds.add(k)
            if cache.store is not None:
                event_keys, event_hosts, event_tasks = (
                    bind_keys, bind_hosts, bind_tasks)
                if failed_binds:
                    event_keys = [k for i, k in enumerate(bind_keys)
                                  if i not in failed_binds]
                    event_hosts = [h for i, h in enumerate(bind_hosts)
                                   if i not in failed_binds]
                    event_tasks = [t for i, t in enumerate(bind_tasks)
                                   if i not in failed_binds]
                record_scheduled = getattr(cache.store, "record_scheduled", None)
                if record_scheduled is not None:
                    # lazy batch record: the Scheduled message materializes on
                    # read, not on the session's critical path (the reference
                    # recorder is an async broadcaster — cache.go:601-611)
                    record_scheduled(event_keys, event_hosts)
                else:
                    cache.store.record_events(
                        (task.pod, "Normal", "Scheduled",
                         f"Successfully assigned "
                         f"{task.namespace}/{task.name} to {host}")
                        for task, host in zip(event_tasks, event_hosts))

            if enc.spec.use_exclusion:
                # device-placed exclusion-group pods carry required
                # anti-affinity: later serial phases (residue, backfill,
                # preempt) must see them in the predicates plugin's resident
                # index, which the bulk writeback's event bypass would miss
                pred = ssn.plugins.get("predicates")
                note = getattr(pred, "note_resident", None)
                if note is not None:
                    from volcano_tpu.api.pod_traits import has_pod_affinity

                    for task in bind_tasks:
                        if task.pod is not None and has_pod_affinity(task.pod):
                            note(task)

        with trace.span("apply.post"):

            # --- bulk plugin share updates (drf / proportion) -----------------
            # per-job DRF shares must be exact per job; namespace/queue shares
            # aggregate across jobs, so accumulate the deltas in numpy and touch
            # each namespace/queue attr once
            drf = ssn.plugins.get("drf")
            prop = ssn.plugins.get("proportion")
            if drf is not None:
                fast_drf = getattr(mod, "update_drf_shares", None) \
                    if mod is not None else None
                if fast_drf is not None:
                    attrs = [drf.job_attrs.get(job_infos[ji].uid)
                             for ji in job_nz]
                    tnames = tuple(drf.total_resource.resource_names())
                    tvals = np.array([drf.total_resource.get(n) for n in tnames])
                    fast_drf(np.asarray(job_nz, np.int64),
                             np.ascontiguousarray(job_sums),
                             attrs, tnames, tvals, tuple(scalar_names))
                else:
                    job_sums_rows = job_sums_l if fast_all is None else \
                        job_sums.tolist()
                    for ji in job_nz:
                        job = job_infos[ji]
                        attr = drf.job_attrs.get(job.uid)
                        if attr is not None:
                            apply_delta(attr.allocated, job_sums_rows[ji], +1.0)
                            drf._update_share(attr)
            if (drf is not None and drf.namespace_opts) or prop is not None:
                ns_count_enc = int(a["ns_active0"].shape[0])
                q_count_enc = int(a["queue_deserved"].shape[0])
                ns_sums = np.zeros((ns_count_enc, job_sums.shape[1]))
                q_sums = np.zeros((q_count_enc, job_sums.shape[1]))
                np.add.at(ns_sums, a["job_ns"][job_nz], job_sums[job_nz])
                np.add.at(q_sums, a["job_queue"][job_nz], job_sums[job_nz])
                ns_sums_l = ns_sums.tolist()
                q_sums_l = q_sums.tolist()
                if drf is not None and drf.namespace_opts:
                    for nsi in np.nonzero(ns_sums.any(axis=1))[0].tolist():
                        ns_opt = drf.namespace_opts.get(enc.ns_names[nsi])
                        if ns_opt is not None:
                            apply_delta(ns_opt.allocated, ns_sums_l[nsi], +1.0)
                            drf._update_share(ns_opt)
                if prop is not None:
                    for qi in np.nonzero(q_sums.any(axis=1))[0].tolist():
                        attr = prop.queue_opts.get(enc.queue_uids[qi])
                        if attr is not None:
                            apply_delta(attr.allocated, q_sums_l[qi], +1.0)
                            prop._update_share(attr)

            # --- fit errors for gangs the solve could not complete ------------
            start, count = a["job_task_start"], a["job_task_count"]
            job_residue = enc.job_residue
            for ji in np.nonzero(job_placed_n < count)[0].tolist():
                job = job_infos[ji]
                lo, hi = int(start[ji]), int(start[ji]) + int(count[ji])
                if lo == hi or job.ready():
                    continue
                if (job_residue is not None and job_residue[ji]) or enc.has_releasing:
                    # the serial pass retries this job (residue tasks, or
                    # releasing capacity it may pipeline onto) with full
                    # predicate fidelity; it records its own fit errors —
                    # mirror allocate.py's retry condition so no stale
                    # '0/N nodes' error outlives a successful retry
                    continue
                first = lo + int(np.argmax(assign[lo:hi] < 0))
                fe = FitErrors()
                fe.set_error(
                    "0/%d nodes are available in the batched "
                    "feasibility/fit solve" % n_count)
                job.nodes_fit_errors[task_infos[first].uid] = fe


