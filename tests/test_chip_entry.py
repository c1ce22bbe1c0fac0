"""The chip entry points on the CPU: compile-cache placement, and
chip_smoke.py's refusals (no TPU, a host fallback, a broken invariant)."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from volcano_tpu.api.resource import Resource
from volcano_tpu.utils import jaxcompile

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402


@pytest.mark.parametrize("env,expected", [
    ({"JAX_COMPILATION_CACHE_DIR": "/var/cache/jax"},
     ("/var/cache/jax", True)),
    ({}, (str(REPO / ".jax_cache"), False)),
])
def test_compile_cache_dir(env, expected):
    """The env var wins and nothing else is set; otherwise the fixed
    in-checkout directory (never a temp name, pid or timestamp)."""
    assert jaxcompile.compile_cache_dir(env) == expected


def test_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("profile,found", [
    ({"mode": "rounds"}, set()),
    ({"mode": "rounds", "fallback": "solve error: x"}, {"fallback"}),
    ({"mode": "rounds", "fuse_fallback": "envelope"}, {"fuse_fallback"}),
    ({"mode": "rounds", "evict_preempt_fallback": "x"},
     {"evict_preempt_fallback"}),
    ({"mode": "rounds", "replica_rebuilds": {"cold": 1, "error:KeyError": 1}},
     {"replica_rebuilds"}),
    ({"fallback": "auto: 10 tasks below rounds threshold"},
     {"fallback", "mode"}),
])
def test_smoke_fallback_check(profile, found):
    from volcano_tpu.scheduler import metrics

    metrics.reset()
    assert set(chip_smoke.fallbacks(profile)) == found


def test_smoke_fallback_check_reads_counters():
    from volcano_tpu.scheduler import degrade, metrics

    metrics.reset()
    metrics.register_fallback("fuse")
    degrade.default_ladder().note_kernel_failure()
    found = chip_smoke.fallbacks({"mode": "rounds"})
    assert set(found) == {"register_fallback_total", "per_action_fallbacks"}
    metrics.reset()


def _res(cpu_m, pods=0):
    r = Resource(milli_cpu=cpu_m, memory=0)
    r.max_task_num = pods
    return r


def test_smoke_audit():
    before = {
        "nodes": {"n0": _res(1000, pods=2), "n1": _res(1000)},
        "jobs": {
            # running 600m on n0; 600m more bound there -> overcommit
            "a": (1, [("ns/a0", "n0", _res(600)), ("ns/a1", "", _res(600))]),
            # gang of min 3 with one task bound
            "b": (3, [("ns/b0", "", _res(100)), ("ns/b1", "", _res(100)),
                      ("ns/b2", "", _res(100))]),
            # partial after an eviction: not a gang violation
            "c": (2, [("ns/c0", "n1", _res(100)), ("ns/c1", "n1", _res(100))]),
        },
    }
    problems = chip_smoke.audit(
        before, {"ns/a1": "n0", "ns/b0": "n1"}, ["ns/c1"])
    assert any("n0 over allocatable" in p for p in problems)
    assert any(p.startswith("gang ns/b0") for p in problems)
    assert not any("ns/c" in p for p in problems)
    assert chip_smoke.audit(before, {}, []) == ["zero binds"]
