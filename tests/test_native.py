"""Native fast-apply (volcano_tpu/_native): build, fallback, and exact
equivalence with the Python oracle loop in ops/solver.py::_apply_bulk."""

from __future__ import annotations

import os

import pytest

from volcano_tpu.api.types import TaskStatus
from volcano_tpu.bench.clusters import build_config
import volcano_tpu.scheduler.actions  # noqa: F401
from volcano_tpu.scheduler.framework import close_session, get_action, open_session


def _run_cfg5(no_native: bool):
    if no_native:
        os.environ["VOLCANO_TPU_NO_NATIVE"] = "1"
    else:
        os.environ.pop("VOLCANO_TPU_NO_NATIVE", None)
    # reset the once-per-process memo so the env var takes effect
    import volcano_tpu._native as native

    native._reset()
    if not no_native:
        # block on the build so the native path is genuinely exercised
        # (the solver's nowait call would otherwise fall back this session)
        if native.get_fastapply() is None:
            pytest.skip("native module unavailable; fallback covered elsewhere")
    try:
        cache, _, tiers, actions, _ = build_config(5, 0.02)
        ssn = open_session(cache, tiers)
        ssn.batch_allocator.mode = "rounds"
        for name in actions:
            get_action(name).execute(ssn)
        binds = dict(cache.binder.binds)
        # full cache/session state fingerprints
        node_state = {
            name: (round(n.idle.milli_cpu, 6), round(n.used.milli_cpu, 6),
                   len(n.tasks))
            for name, n in cache.nodes.items()
        }
        statuses = {
            t.uid: (t.status, t.node_name)
            for job in cache.jobs.values() for t in job.tasks.values()
        }
        ssn_statuses = {
            t.uid: (t.status, t.node_name)
            for job in ssn.jobs.values() for t in job.tasks.values()
        }
        close_session(ssn)
        return binds, node_state, statuses, ssn_statuses
    finally:
        os.environ.pop("VOLCANO_TPU_NO_NATIVE", None)
        native._reset()


class TestNativeFastApply:
    def test_builds_and_loads(self):
        import shutil
        import sysconfig

        import volcano_tpu._native as native

        cc = (sysconfig.get_config_var("CC") or "cc").split()[0]
        if shutil.which(cc) is None:
            pytest.skip(f"no C toolchain ({cc}); Python fallback covers this")
        native._reset()
        mod = native.get_fastapply()
        assert mod is not None, "toolchain present; native module must build"
        assert hasattr(mod, "apply_job_tasks")

    def test_loads_only_the_build_of_the_committed_source(self):
        """The built module's path carries a digest of the C source, so a
        module built from any other source is never the one loaded."""
        import hashlib
        import sysconfig

        import volcano_tpu._native as native

        native._reset()
        mod = native.get_fastapply()
        if mod is None:
            pytest.skip("native module unavailable; fallback covered elsewhere")
        src = os.path.join(os.path.dirname(native.__file__), "fastapply.c")
        ext = sysconfig.get_config_var("EXT_SUFFIX")
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read() + ext.encode()).hexdigest()[:16]
        assert mod.__file__ == os.path.join(
            os.path.dirname(src), "build", digest, "_fastapply" + ext)

    def test_native_equals_python_oracle(self):
        """Same bindings, node accounting, and task statuses (session +
        cache trees) from the native loop and the Python loop."""
        py = _run_cfg5(no_native=True)
        nat = _run_cfg5(no_native=False)
        assert py[0] == nat[0], "bindings diverge"
        assert py[1] == nat[1], "node accounting diverges"
        assert py[2] == nat[2], "cache task statuses diverge"
        assert py[3] == nat[3], "session task statuses diverge"
        assert len(py[0]) > 0

    def test_env_gate_disables_native(self, monkeypatch):
        import volcano_tpu._native as native

        monkeypatch.setenv("VOLCANO_TPU_NO_NATIVE", "1")
        native._reset()
        assert native.get_fastapply() is None
        assert native.get_fasttrans() is None
        native._reset()
