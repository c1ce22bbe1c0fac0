"""The chip path's kernels compile for a described TPU v5e (no chip needed).

Each test drives a real session to the kernel dispatch the scheduler would
make, captures the arguments there, and compiles that program for one chip
of a described ``v5e:2x2`` topology with the chip path's dtypes (float32 /
int32: x64 is off, as on the chip). A compile that passes is not a chip
run; it catches what the TPU compiler refuses (tiling, memory) at no chip
time. The topology is described only inside the fixture below: one process
at a time may load the TPU library, and the driver's workers import every
test file (on-chip-measurement guide, section 2).
"""

from __future__ import annotations

import contextlib
import os
import time

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import volcano_tpu.scheduler.actions  # noqa: F401 (register actions)
from volcano_tpu.bench.clusters import CONFIGS, make_cache, make_tiers
from volcano_tpu.ops import evict as evict_mod
from volcano_tpu.ops import rounds as rounds_mod
from volcano_tpu.ops import session_fuse
from volcano_tpu.scheduler.framework import (
    close_session, get_action, open_session, run_actions)

# reduced buckets of the two chip-smoke clusters; scripts that rehearse the
# full size call the helpers below with scale 1.0
CFG5_SCALE = 0.02   # 1k tasks x 200 nodes
CFG4_SCALE = 0.02   # 600 tasks x 160 nodes
_ROUNDS = {"tpuscore": {"tpuscore.mode": "rounds"}}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # the chip path's dtypes
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_x64", prev)


class _Captured(BaseException):
    """Stops a session at the kernel dispatch. A BaseException, so the
    solver's `except Exception` fallbacks cannot run the host path."""


@contextlib.contextmanager
def capture(targets, stop):
    """Replace each jitted (module, name) with a stand-in that records its
    arguments and returns abstract shapes (so a chain of stages dispatches
    without computing); the stand-in for ``stop`` ends the session."""
    calls, saved = {}, []

    def stand_in(name, fn):
        def call(*args, **kw):
            calls[name] = (fn, args, kw)
            if name == stop:
                raise _Captured
            return fn.eval_shape(*args, **kw)
        return call

    for mod, name in targets:
        fn = getattr(mod, name)
        saved.append((mod, name, fn))
        setattr(mod, name, stand_in(name, fn))
    try:
        yield calls
    except _Captured:
        pass
    finally:
        for mod, name, fn in saved:
            setattr(mod, name, fn)


def compile_for(sharding, fn, args, kw):
    """(seconds, memory_analysis) of compiling ``fn`` for ``sharding``'s
    device over the abstract shapes of the captured arguments."""
    def abstract(x):
        if isinstance(x, (np.ndarray, jax.Array, jax.ShapeDtypeStruct)):
            assert x.dtype not in (np.float64, np.int64), x.dtype
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return x

    args, kw = jax.tree_util.tree_map(abstract, (args, kw))
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **kw).compile()
    return time.perf_counter() - t0, compiled.memory_analysis()


def _session(cfg, scale):
    cache = make_cache()
    CONFIGS[cfg].populate(cache, scale)
    tiers = make_tiers(["tpuscore"], *CONFIGS[cfg].tiers, arguments=_ROUNDS)
    return open_session(cache, tiers)


def rounds_call(scale=CFG5_SCALE):
    """The rounds kernel the allocate action dispatches on cfg5."""
    ssn = _session(5, scale)
    with capture([(rounds_mod, "solve_rounds_packed")],
                 "solve_rounds_packed") as calls:
        get_action("allocate").execute(ssn)
    close_session(ssn)
    return calls["solve_rounds_packed"]


def fused_calls(scale=CFG4_SCALE):
    """The fused allocate -> backfill -> preempt -> reclaim chain on cfg4."""
    ssn = _session(4, scale)
    stages = ("_fuse_alloc", "_fuse_backfill", "_fuse_preempt",
              "_fuse_reclaim")
    with capture([(session_fuse, s) for s in stages],
                 "_fuse_reclaim") as calls:
        run_actions(ssn, CONFIGS[4].actions)
    close_session(ssn)
    return calls


def evict_call(kind, scale=CFG4_SCALE):
    """The per-action evict kernel (the unfused path) for ``kind``."""
    ssn = _session(4, scale)
    with capture([(evict_mod, "_solve_packed")], "_solve_packed") as calls:
        get_action(kind).execute(ssn)
    close_session(ssn)
    return calls["_solve_packed"]


def express_call(n_nodes, batch=8):
    """solve_express over an n-node axis and one arrival batch."""
    from volcano_tpu.express import place
    from volcano_tpu.ops.solver import _bucket

    tb = jb = _bucket(batch)
    spec = place.ExpressSpec(tb=tb, jb=jb,
                             window_k=place.window_for(n_nodes, tb))
    f32, i32 = np.float32, np.int32
    args = (spec, np.zeros((n_nodes, 2), f32), np.zeros((n_nodes, 2), f32),
            np.zeros(n_nodes, i32), np.zeros(n_nodes, bool),
            np.zeros(n_nodes, i32), np.zeros((tb, 2), f32),
            np.zeros((tb, 2), f32), np.zeros(tb, f32), np.zeros(tb, f32),
            np.zeros(tb, bool), np.zeros(tb, i32), np.zeros(tb, bool),
            np.zeros(jb, i32), np.zeros(2, f32))
    return place.solve_express, args, {}


def test_rounds_kernel_compiles(one_chip):
    secs, mem = compile_for(one_chip, *rounds_call())
    assert mem is None or mem.temp_size_in_bytes < 16 << 30, secs


def test_fused_chain_compiles(one_chip):
    calls = fused_calls()
    assert {"_fuse_alloc", "_fuse_preempt", "_fuse_reclaim"} <= set(calls)
    for name in sorted(calls):
        compile_for(one_chip, *calls[name])


@pytest.mark.parametrize("kind", ["preempt", "reclaim"])
def test_evict_kernel_compiles(one_chip, kind):
    fn, args, kw = evict_call(kind)
    assert args[0].kind == kind
    compile_for(one_chip, fn, args, kw)


def test_express_kernel_compiles(one_chip):
    compile_for(one_chip, *express_call(int(10000 * CFG5_SCALE)))
