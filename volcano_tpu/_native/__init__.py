"""Native runtime components, compiled lazily at first use.

The control plane is Python with the solve on TPU; the few remaining
interpreted hot loops (the bulk-apply writeback, the per-operation
preempt/reclaim transitions) have native equivalents here, compiled on
demand with the system toolchain into ``build/<source digest>/`` under this
package directory and imported from there. Every native path has a pure-Python fallback — a
missing compiler, failed build, or failed import degrades to the oracle
implementation, never to an error.
"""

from __future__ import annotations

import functools
import hashlib
import importlib.util
import logging
import os
import subprocess
import sys
import sysconfig

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
# per-module load state: name -> {"mod": module|None, "tried": bool,
# "done": bool, "thread": Thread|None}. "tried" gates re-attempts;
# "done" means the attempt fully finished (build+import) — the two differ
# while a build is in flight.
_STATE: dict = {}
# per-module build locks, deliberately OUTSIDE _STATE: _reset() must not
# clear them, or a reset mid-compile would let a second cc race the first
# on the shared .so.tmp output
_LOCKS: dict = {}


def _lock(modname: str):
    import threading

    lk = _LOCKS.get(modname)
    if lk is None:
        lk = _LOCKS.setdefault(modname, threading.Lock())
    return lk


@functools.lru_cache(maxsize=None)
def _paths(src: str, modname: str):
    """(source, built module): the build lands under ``build/<digest>/``,
    where the digest is of the committed C source and the interpreter's
    extension suffix. A module built from any other source (a stale file
    copied along with the tree) has another path and is never loaded."""
    ext = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    src_path = os.path.join(_DIR, src)
    with open(src_path, "rb") as f:
        digest = hashlib.sha256(f.read() + ext.encode()).hexdigest()[:16]
    return src_path, os.path.join(_DIR, "build", digest, modname + ext)


def _build(src: str, modname: str) -> bool:
    src_path, out = _paths(src, modname)
    if os.path.exists(out):
        return True
    os.makedirs(os.path.dirname(out), exist_ok=True)
    tmp = f"{out}.{os.getpid()}.tmp"  # concurrent builders never share it
    cc = sysconfig.get_config_var("CC") or "cc"
    include = sysconfig.get_paths()["include"]
    cmd = [*cc.split(), "-O2", "-fPIC", "-shared",
           f"-I{include}", src_path, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:  # toolchain absent
        logger.info("native build unavailable (%s); using Python fallback", e)
        return False
    if proc.returncode != 0:
        logger.warning("native build failed; using Python fallback:\n%s",
                       proc.stderr[-2000:])
        return False
    os.replace(tmp, out)
    return True


def _load(src: str, modname: str):
    _, out = _paths(src, modname)
    mod = sys.modules.get(modname)
    if mod is not None and getattr(mod, "__file__", None) == out:
        return mod
    spec = importlib.util.spec_from_file_location(modname, out)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[modname] = mod
    return mod


def _get(src: str, modname: str):
    """The compiled module, or None (callers keep the Python loop).
    Build+import attempted once per process per module. BLOCKS on the
    compiler the first time — latency-critical callers use _get_nowait.
    The per-module lock serializes a blocking call racing the background
    thread (only one cc ever writes the .so.tmp)."""
    with _lock(modname):
        st = _STATE.setdefault(
            modname, {"mod": None, "tried": False, "done": False, "thread": None})
        if st["tried"]:
            return st["mod"]
        st["tried"] = True
        try:
            if os.environ.get("VOLCANO_TPU_NO_NATIVE"):
                return None
            try:
                if _build(src, modname):
                    st["mod"] = _load(src, modname)
            except Exception:
                logger.exception(
                    "native %s unavailable; using Python fallback", modname)
                st["mod"] = None
        finally:
            st["done"] = True
        return st["mod"]


def _get_nowait(src: str, modname: str):
    """Non-blocking variant for critical paths: returns the module if it is
    already available (cached .so imports in milliseconds), else kicks the
    compile off on a background thread ONCE and returns None — the first
    session runs the Python fallback instead of waiting on cc."""
    st = _STATE.setdefault(
        modname, {"mod": None, "tried": False, "done": False, "thread": None})
    if st["done"]:
        return st["mod"]
    if os.environ.get("VOLCANO_TPU_NO_NATIVE"):
        return None
    if os.path.exists(_paths(src, modname)[1]):
        return _get(src, modname)  # import only — no compiler run
    if st["thread"] is None:
        import threading

        st["thread"] = threading.Thread(
            target=_get, args=(src, modname), daemon=True)
        st["thread"].start()
    return None


def _reset() -> None:
    """Forget load state so the next get_* re-evaluates the env gate and
    build (tests poke this; the .so cache on disk is untouched). The build
    locks survive, so a reset cannot let two compiles race."""
    _STATE.clear()


def settled(modname: str) -> bool:
    """True once a load attempt for `modname` fully finished (module built,
    failed, or env-disabled); False while a build is still in flight."""
    if os.environ.get("VOLCANO_TPU_NO_NATIVE"):
        return True
    st = _STATE.get(modname)
    return bool(st and st["done"])


def get_fastapply():
    return _get("fastapply.c", "_fastapply")


def get_fastapply_nowait():
    return _get_nowait("fastapply.c", "_fastapply")


def get_fasttrans():
    return _get("fasttrans.c", "_fasttrans")


def get_fasttrans_nowait():
    return _get_nowait("fasttrans.c", "_fasttrans")
