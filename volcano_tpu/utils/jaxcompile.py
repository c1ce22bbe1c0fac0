"""Compile-event accounting for steady-state guarantees.

The scheduler's latency story depends on XLA compiling each program variant
ONCE: a retrace in a warm session turns a ~100 ms cycle into a multi-second
stall (the reference never pays anything like this — its hot loop is
pre-compiled Go — so the rebuild must prove compilation is out of the
steady-state path). This watcher hooks `jax.monitoring`'s duration events
and exposes per-window deltas, so a test that wraps a warm session in
`assert_no_compiles` fails on any retrace.

Thread-safe for the single-writer / many-reader pattern JAX uses (listener
callbacks fire on whichever thread compiles).

`enable_compile_cache` places JAX's persistent compilation cache; the entry
points (benchmark/run.py, ``python -m volcano_tpu.scheduler``, the sim
CLI) call it once at start-up, never at import time.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass
from typing import Mapping, Optional

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed and inside the checkout (git-ignored): the cache key includes the
# path, so a directory that moves between runs never hits
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"


@dataclass
class CompileStats:
    compiles: int = 0
    compile_s: float = 0.0
    traces: int = 0
    trace_s: float = 0.0


class CompileWatcher:
    """Process-global counter of XLA backend compiles + jaxpr traces.

    install() is idempotent; `window()` returns an object whose `delta()`
    yields the stats accumulated since the window was opened."""

    _instance: "CompileWatcher | None" = None
    _lock = threading.Lock()

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._stats = CompileStats()

    @classmethod
    def install(cls) -> "CompileWatcher":
        with cls._lock:
            if cls._instance is None:
                inst = cls()
                from jax import monitoring

                def on_duration(event: str, duration: float, **kw) -> None:
                    if event == _BACKEND_COMPILE:
                        with inst._mu:
                            inst._stats.compiles += 1
                            inst._stats.compile_s += duration
                    elif event == _TRACE:
                        with inst._mu:
                            inst._stats.traces += 1
                            inst._stats.trace_s += duration

                monitoring.register_event_duration_secs_listener(on_duration)
                cls._instance = inst
            return cls._instance

    def snapshot(self) -> CompileStats:
        with self._mu:
            return CompileStats(**self._stats.__dict__)

    def window(self) -> "_Window":
        return _Window(self)

    @contextlib.contextmanager
    def assert_no_compiles(self, what: str = "warm path"):
        """Fail loudly if any XLA backend compile lands inside the block.

        Wrap a steady-state session in this and a retrace fails the TEST
        that introduced it, instead of surfacing as a multi-second stall
        in the next benchmark run. Yields the window so callers can also
        inspect trace counts."""
        win = self.window()
        yield win
        d = win.delta()
        if d.compiles:
            raise AssertionError(
                f"{what}: {d.compiles} XLA compile(s) ({d.compile_s:.3f}s, "
                f"{d.traces} retrace(s)) inside a no-compile window — the "
                f"session solve must stay ONE pre-compiled program "
                f"(docs/static-analysis.md; BENCH tpu_warm_compiles)")


def compile_cache_dir(environ: Mapping[str, str] = os.environ
                      ) -> tuple[str, bool]:
    """(directory, from_env): the directory JAX's persistent compilation
    cache should use. ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX
    reads it itself); otherwise ``<repo>/.jax_cache``."""
    env = environ.get(_CACHE_ENV)
    if env:
        return env, True
    return _REPO_CACHE_DIR, False


def enable_compile_cache(environ: Optional[Mapping[str, str]] = None) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and no
    other directory is set here. Call once from an entry point, before the
    first compile; never at import time."""
    path, from_env = compile_cache_dir(
        os.environ if environ is None else environ)
    if not from_env:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


class _Window:
    def __init__(self, watcher: CompileWatcher):
        self._w = watcher
        self._base = watcher.snapshot()

    def delta(self) -> CompileStats:
        now = self._w.snapshot()
        b = self._base
        return CompileStats(
            compiles=now.compiles - b.compiles,
            compile_s=now.compile_s - b.compile_s,
            traces=now.traces - b.traces,
            trace_s=now.trace_s - b.trace_s,
        )
