"""vclint v3 — abstract interpretation over the device kernels.

vclint v1/v2 check the HOST program (purity, bucket shapes, locks,
mutation->invalidation effects). Nothing statically checked the kernels'
numerics, yet every number in the real-TPU campaign rides on int32 packed
op logs, milli-scaled accumulators, and mesh-padded axes: a silent int32
overflow or a pad row leaking into a cross-row reduce corrupts binds
without failing a single CPU-proxy parity test (PR 10 had to rewrite
``_window`` by hand for exactly that reason). This module turns those two
bug classes — plus donated-buffer lifetimes — into machine-checked rules
by running a small abstract interpreter over each kernel function.

Abstract domain (per value)
---------------------------
- ``[lo, hi]``     integer value range, seeded from the bucket-ladder
                   worst case (cfg7: 100k tasks x 50k nodes, padded to the
                   8-device mesh multiple; see ``EXTENTS``);
- ``kind``         ``pyint`` (host int, arbitrary precision — never
                   overflows), ``i32``/``i64``/``bool``/``float``/``obj``;
- ``taint``        pad-slot lattice CLEAN < GUARD < PAD. Rows past
                   ``node_real``/``real_n`` are PAD until masked; ``real``
                   masks (any ``*_real`` name) and ``real_n`` comparisons
                   are GUARD; ``PAD & GUARD``, ``PAD * GUARD`` and
                   ``where(GUARD, ..)`` sanitize;
- ``axis``         worst-case extent of the leading (pad) axis;
- ``total``        bound on the SUM over the pad axis for non-negative
                   arrays (an indicator array has total <= axis even
                   though ``hi * axis`` would be quadratic) — this is what
                   keeps the sanctioned scatter+cumsum window idiom from
                   flagging.

Transfer functions: add/mul widen ranges; cumsum/sum multiply by the axis
extent (or use ``total``); ``top_k``/gather/scatter propagate taint;
``lax.cond`` joins branch states; loop results are TOP. Recognized
overflow mitigations: ``.astype(jnp.int64)`` widening, ``& 0x7FFF``
masks, ``jnp.minimum``/``clip`` clamps, saturating
``lax.associative_scan(lambda a, b: minimum(a+b, cap), ..)``, and the
two-15-bit-limb tuple scan (``_seg_limbs``).

Rules
-----
- **VT010** int32 overflow: an ``i32`` value whose DERIVED range at the
  maximal bucket shapes exceeds 2^31-1. Blessed by a machine-checked
  ``# vclint: headroom(<arith over EXTENTS names>)`` proof on the line
  (or the line above) whose value must stay < 2^31 — an invalid, empty
  or failing proof is itself a finding.
- **VT011** pad taint: a PAD value reaching an unmasked cross-row reduce
  (cumsum/sum/argmax/argsort/top_k/max/min/any/all over the pad axis) or
  the packed D2H tail (``jnp.concatenate``).
- **VT012** donation lifetime: a read through an ALIAS of a donated
  buffer after its dispatch (generalizes VT006's decorator-lexical check
  to dataflow: ``x = carry``/``x = carry["k"]``/ternary aliases die with
  the root; rebinding from the dispatch result revives only the rebound
  name).

Soundness caveats (documented, deliberate)
------------------------------------------
- Under-approximating on ranges: unknown values are TOP and NEVER flag —
  only derivations from seeded bounds fire, so absent seeds mean silence,
  not noise. Loop-carried accumulators (fori/while/scan results) are TOP.
- The analysis is intra-procedural: results of local helper calls are
  TOP/CLEAN; a pad leak laundered through a helper boundary is caught
  when the helper itself is analyzed (it sees its own params seeded).
- Name-based seeding: the pad axis is recognized via the repo's naming
  contract (``real``/``node_real``/``real_n``/``vic_*``/``node_*``); a
  function is pad-aware iff it touches a guard name.
- The headroom bless checks the ARITHMETIC of the claimed bound, not its
  correspondence to the code — that obligation stays with the reviewer,
  like ``neutral(...)`` for VT007.
- VT012 alias tracking is name-versioned like VT006: an alias taken
  BEFORE a donate-then-rebind of its root is not tracked across the
  rebind.

Summaries are memoized per (path, content-hash) so repeated analysis of
an unchanged file (rule pairs sharing one interpretation, warm lint runs
in one process) is a dict hit.
"""

from __future__ import annotations

import ast
import hashlib
import io
import re
import tokenize
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Set, Tuple

from volcano_tpu.analysis.core import Finding, Rule, register_rule
from volcano_tpu.analysis.rules import DonatedBufferReuse, dotted

INF = float("inf")
INT32_MAX = 2 ** 31 - 1

# Canonical worst-case extents: cfg7 (100k tasks x 50k nodes — 2x the
# paper's 50k x 10k target) on an 8-device mesh, through the bucket
# ladder (ops/solver.py _bucket: 16, then doubling powers of two).
EXTENTS: Dict[str, int] = {
    "TASKS": 100_000,        # live tasks, cfg7
    "NODES": 50_000,         # real nodes, cfg7
    "MESH_DEV": 8,           # devices in the mesh
    "NODES_PAD": 50_048,     # node axis padded to the mesh multiple
    "TB": 131_072,           # _bucket(100_000) — task/job/queue bucket
    "V_WIDTH": 131_072,      # victim bucket: no per-node cap, <= _bucket(tasks)
    "LOG_ROWS": 262_144,     # packed op-log rows
    "INT32_MAX": INT32_MAX,
}

_TASKS = EXTENTS["TASKS"]
_NODES = EXTENTS["NODES"]
_NP = EXTENTS["NODES_PAD"]
_TB = EXTENTS["TB"]
_VW = EXTENTS["V_WIDTH"]
_LOG = EXTENTS["LOG_ROWS"]
_AXIS_DEFAULT = _TB          # largest ladder bucket: unknown reduce extent

CLEAN, GUARD, PAD = 0, 1, 2


# ---------------------------------------------------------------------------
# abstract values
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AbsVal:
    lo: float = -INF
    hi: float = INF
    kind: str = "obj"               # pyint | i32 | i64 | bool | float | obj
    taint: int = CLEAN
    axis: Optional[int] = None      # leading (pad) axis extent
    axis1: Optional[int] = None     # second-axis extent (vic_* tables)
    total: Optional[float] = None   # bound on sum over the pad axis
    chain: Tuple[str, ...] = ()     # provenance, for --explain

    @property
    def known(self) -> bool:
        return self.hi < INF and self.lo > -INF


TOP = AbsVal()

_INT_KINDS = ("pyint", "i32", "i64", "bool")


def _const(v: int, kind: str = "pyint") -> AbsVal:
    return AbsVal(v, v, kind)


def _tmax(a: int, b: int) -> int:
    """Taint join for plain data flow: PAD dominates, then GUARD."""
    return max(a, b)


def _sanitize(a: int, b: int) -> int:
    """Taint for '&' / '*' / where(GUARD,..): a guard masks a pad."""
    if {a, b} >= {PAD, GUARD}:
        return CLEAN
    return max(a, b)


def _kind_join(a: str, b: str) -> str:
    for k in ("obj", "float", "i64"):
        if k in (a, b):
            return k
    if a == b == "pyint":
        return "pyint"
    return "i32"


def _join(a: AbsVal, b: AbsVal) -> AbsVal:
    return AbsVal(
        min(a.lo, b.lo), max(a.hi, b.hi), _kind_join(a.kind, b.kind),
        _tmax(a.taint, b.taint),
        a.axis if a.axis == b.axis else (a.axis or b.axis),
        a.axis1 if a.axis1 == b.axis1 else (a.axis1 or b.axis1),
        None if (a.total is None or b.total is None)
        else max(a.total, b.total),
        (a.chain or b.chain)[:6])


def _chain(v: AbsVal, entry: str) -> Tuple[str, ...]:
    c = v.chain + (entry,)
    if len(c) > 6:
        c = c[:2] + c[-4:]
    return c


def _src(node: ast.AST, limit: int = 56) -> str:
    try:
        s = ast.unparse(node)
    except Exception:
        s = type(node).__name__
    s = " ".join(s.split())
    return s if len(s) <= limit else s[:limit - 2] + ".."


# ---------------------------------------------------------------------------
# headroom bless grammar: # vclint: headroom(<arith over EXTENTS names>)
# ---------------------------------------------------------------------------

_HEADROOM_RE = re.compile(r"vclint:\s*headroom\(([^()]*)\)")


def headroom_lines(src: str) -> Dict[int, str]:
    """line -> proof expression, from comments only (tokenizer-based, so
    a 'headroom(' inside a string can never bless anything)."""
    out: Dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type != tokenize.COMMENT:
                continue
            m = _HEADROOM_RE.search(tok.string)
            if m is not None:
                out[tok.start[0]] = m.group(1).strip()
    except tokenize.TokenError:
        pass
    return out


def eval_headroom(expr: str):
    """(ok, value_or_reason). The proof must be closed arithmetic over
    EXTENTS names (+ - * // % and min/max) evaluating below 2^31."""
    if not expr:
        return False, "empty proof — write headroom(<bound arithmetic>)"
    try:
        tree = ast.parse(expr, mode="eval")
    except SyntaxError:
        return False, f"unparseable proof {expr!r}"

    def ev(n):
        if isinstance(n, ast.Expression):
            return ev(n.body)
        if isinstance(n, ast.Constant) and isinstance(n.value, int) \
                and not isinstance(n.value, bool):
            return n.value
        if isinstance(n, ast.Name):
            if n.id in EXTENTS:
                return EXTENTS[n.id]
            raise ValueError(f"unknown name {n.id!r} "
                             f"(allowed: {', '.join(sorted(EXTENTS))})")
        if isinstance(n, ast.UnaryOp) and isinstance(n.op, ast.USub):
            return -ev(n.operand)
        if isinstance(n, ast.BinOp):
            l, r = ev(n.left), ev(n.right)
            if isinstance(n.op, ast.Add):
                return l + r
            if isinstance(n.op, ast.Sub):
                return l - r
            if isinstance(n.op, ast.Mult):
                return l * r
            if isinstance(n.op, ast.FloorDiv):
                return l // r
            if isinstance(n.op, ast.Mod):
                return l % r
            raise ValueError("only + - * // % arithmetic is allowed")
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                and n.func.id in ("min", "max") and not n.keywords:
            vals = [ev(a) for a in n.args]
            return min(vals) if n.func.id == "min" else max(vals)
        raise ValueError(f"disallowed syntax {type(n).__name__}")

    try:
        val = ev(tree)
    except (ValueError, ZeroDivisionError) as e:
        return False, str(e)
    if not isinstance(val, int):
        return False, f"proof is not an integer: {val!r}"
    if val > INT32_MAX:
        return False, (f"proof evaluates to {val} > 2**31-1 — the bound "
                       f"does not fit int32")
    return True, val


# ---------------------------------------------------------------------------
# seeding: the repo's naming contract -> worst-case abstract values
# ---------------------------------------------------------------------------

_SCALAR_SEEDS: Dict[str, Tuple[int, int, str]] = {
    "rr": (0, _NP - 1, "round-robin cursor < NODES_PAD"),
    "node": (0, _NP - 1, "node index < NODES_PAD"),
    "slot": (0, _VW - 1, "victim slot < V_WIDTH"),
    "t": (0, _TB - 1, "task index < TB"),
    "j": (0, _TB - 1, "job index < TB"),
    "q": (0, _TB - 1, "queue index < TB"),
    "num_to_find": (0, _NODES, "window width <= NODES"),
    "t_cap": (0, _TASKS, "per-step task cap <= TASKS"),
    "n_rounds": (0, _TASKS, "round counter <= TASKS"),
    "log_len": (0, _LOG, "op-log cursor <= LOG_ROWS"),
    "kind": (0, 7, "op-log kind tag"),
}

_BOOL_ARRAYS = frozenset((
    "elig", "mask", "ok", "valid", "alive", "sel", "fit", "cand", "vm",
    "win", "live", "claim", "vic_valid", "dirty", "gang_valid",
))

# per-node counters WITHOUT mass conservation (per-node caps): cumsum is
# genuinely quadratic, so no `total` bound
_CAP_ARRAYS = frozenset(("maxt", "node_maxt", "node_max_tasks"))

# per-node counters WITH mass conservation (each task counted once):
# total <= TASKS even though per-element hi is TASKS
_COUNT_ARRAYS = frozenset(("cnt", "node_cnt", "counts"))

_VIC_IDX_ARRAYS = frozenset(("vic_job", "vic_queue", "vic_task"))

# node-axis float payloads: rows past node_real hold stale/garbage values
_NODE_FLOAT_ARRAYS = frozenset((
    "used", "idle", "alloc", "node_used", "node_idle", "node_alloc",
    "sig_mask",
))


def _seed(name: str, pad_aware: bool) -> AbsVal:
    if name == "real_n" or name.endswith("_real_n"):
        return AbsVal(1, _NODES, "i32", GUARD,
                      chain=(f"{name}: real row count in [1, NODES]",))
    if name in ("real", "node_real") or name.endswith("_real"):
        return AbsVal(0, 1, "bool", GUARD, _NP, None, _NODES,
                      (f"{name}: validity mask (guard, <= NODES ones)",))
    if name in _SCALAR_SEEDS:
        lo, hi, why = _SCALAR_SEEDS[name]
        return AbsVal(lo, hi, "i32", CLEAN,
                      chain=(f"{name}: seeded [{lo}, {hi}] ({why})",))
    t = PAD if pad_aware else CLEAN
    if name in _BOOL_ARRAYS:
        return AbsVal(0, 1, "bool", t, _NP, None, _NP,
                      (f"{name}: node-axis mask (rows past node_real "
                       f"are pad)",))
    if name in _CAP_ARRAYS:
        return AbsVal(0, _TASKS, "i32", t, _NP, None, None,
                      (f"{name}: per-node cap <= TASKS, no mass bound",))
    if name in _COUNT_ARRAYS:
        return AbsVal(0, _TASKS, "i32", t, _NP, None, _TASKS,
                      (f"{name}: per-node count, sum <= TASKS",))
    if name in _VIC_IDX_ARRAYS:
        return AbsVal(0, _TB - 1, "i32", t, _NP, _VW, None,
                      (f"{name}: victim table [NODES_PAD, V_WIDTH]",))
    if name == "vic_req":
        return AbsVal(-INF, INF, "float", t, _NP, _VW, None,
                      (f"{name}: victim requests (float)",))
    if name in _NODE_FLOAT_ARRAYS:
        return AbsVal(-INF, INF, "float", t, _NP, None, None,
                      (f"{name}: node-axis payload (rows past node_real "
                       f"are pad)",))
    if name == "log":
        return AbsVal(-INF, INF, "i32", CLEAN, 3 * _LOG, None, None,
                      (f"{name}: flat op log [3 * LOG_ROWS]",))
    # unknown: TOP and CLEAN — the analysis under-approximates, so an
    # unrecognized name means silence, never noise (see module docstring)
    return AbsVal(chain=(f"{name}: unknown (top)",))


_GUARD_KEYS = ("real", "node_real", "real_n")


def _pad_aware(fn: ast.AST) -> bool:
    """A function is pad-aware iff it touches the node-validity contract:
    a guard param name or a guard dict key anywhere in its body."""
    args = getattr(fn, "args", None)
    if args is not None:
        names = [a.arg for a in args.args + args.kwonlyargs + args.posonlyargs]
        if any(n in _GUARD_KEYS or n.endswith("_real") for n in names):
            return True
    for node in ast.walk(fn):
        if isinstance(node, ast.Subscript) \
                and isinstance(node.slice, ast.Constant) \
                and node.slice.value in _GUARD_KEYS:
            return True
    return False


# ---------------------------------------------------------------------------
# events + module summary
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AbsEvent:
    rule: str                 # VT010 | VT011 | VT012 | bless
    line: int
    col: int
    msg: str
    fn: str = ""
    detail: Tuple[str, ...] = ()


_SUMMARY_CACHE: Dict[str, Tuple[str, Tuple[AbsEvent, ...]]] = {}


def summarize(tree: ast.AST, src: str, path: str) -> Tuple[AbsEvent, ...]:
    """Abstract summary of one module, memoized by content hash."""
    key = hashlib.sha256(src.encode("utf-8", "replace")).hexdigest()
    hit = _SUMMARY_CACHE.get(path)
    if hit is not None and hit[0] == key:
        return hit[1]
    events = tuple(_ModuleInterp(tree, src, path).run())
    _SUMMARY_CACHE[path] = (key, events)
    return events


# reduce-style callables: name -> is_accumulating (VT010 surface)
_REDUCES = {
    "cumsum": True, "sum": True, "nansum": True, "cumprod": True,
    "max": False, "min": False, "amax": False, "amin": False,
    "argmax": False, "argmin": False, "argsort": False, "lexsort": False,
    "sort": False, "top_k": False, "any": False, "all": False,
    "cummax": False, "nanargmax": False, "median": False,
}

_PASSTHROUGH = frozenset((
    "roll", "flip", "asarray", "array", "abs", "ravel", "reshape",
    "broadcast_to", "stop_gradient", "squeeze", "expand_dims", "copy",
    "transpose", "sign", "tile", "repeat", "mod", "remainder",
))

_DTYPE_KINDS = (
    ("int64", "i64"), ("int32", "i32"), ("int16", "i32"), ("int8", "i32"),
    ("uint32", "i32"), ("float64", "float"), ("float32", "float"),
    ("bfloat16", "float"), ("float16", "float"), ("bool_", "bool"),
    ("bool", "bool"),
)


def _dtype_kind(node: Optional[ast.AST]) -> Optional[str]:
    name = dotted(node) if node is not None else None
    if not name:
        return None
    leaf = name.split(".")[-1]
    for suffix, kind in _DTYPE_KINDS:
        if leaf == suffix:
            return kind
    return None


class _ModuleInterp:
    """Drives one _FnInterp per function (methods and nested defs get
    their own scope, closing over the enclosing abstract env)."""

    def __init__(self, tree: ast.AST, src: str, path: str):
        self.tree = tree
        self.path = path
        self.headroom = headroom_lines(src)
        self.events: List[AbsEvent] = []
        self.flagged: Set[Tuple[str, int]] = set()
        self.consts: Dict[str, AbsVal] = {}

    def run(self) -> List[AbsEvent]:
        body = getattr(self.tree, "body", [])
        for stmt in body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                try:
                    val = ast.literal_eval(stmt.value)
                except (ValueError, SyntaxError, TypeError):
                    continue
                if isinstance(val, int) and not isinstance(val, bool):
                    self.consts[stmt.targets[0].id] = _const(val)
        for stmt in body:
            self._walk_defs(stmt, {})
        return self.events

    def _walk_defs(self, stmt: ast.AST, closure: Dict[str, AbsVal]):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _FnInterp(self, stmt, dict(closure)).run()
        elif isinstance(stmt, ast.ClassDef):
            for sub in stmt.body:
                self._walk_defs(sub, closure)

    def emit(self, rule: str, node: ast.AST, msg: str, fn: str,
             detail: Sequence[str] = ()):
        if (rule, node.lineno) in self.flagged:
            return
        self.flagged.add((rule, node.lineno))
        self.events.append(AbsEvent(rule, node.lineno, node.col_offset,
                                    msg, fn, tuple(detail)))

    def headroom_at(self, line: int) -> Optional[str]:
        if line in self.headroom:
            return self.headroom[line]
        return self.headroom.get(line - 1)


class _FnInterp:
    """Abstract interpretation of one function body."""

    def __init__(self, ow: _ModuleInterp, fn, closure: Dict[str, AbsVal],
                 pad_parent: bool = False):
        self.ow = ow
        self.fn = fn
        self.pad = pad_parent or _pad_aware(fn)
        # widest safe i32 bound derived in this body (explain-only)
        self.peak: Optional[Tuple[float, int, Tuple[str, ...]]] = None
        self.env: Dict[str, AbsVal] = dict(ow.consts)
        self.env.update(closure)
        a = fn.args
        for arg in a.posonlyargs + a.args + a.kwonlyargs:
            self.env[arg.arg] = _seed(arg.arg, self.pad)
        if a.vararg:
            self.env[a.vararg.arg] = TOP
        if a.kwarg:
            self.env[a.kwarg.arg] = TOP

    # ---- statements ------------------------------------------------------

    def run(self):
        self.exec_block(self.fn.body)
        if self.peak is not None:
            bound, line, chain = self.peak
            self.ow.events.append(AbsEvent(
                "range", line, 0,
                f"widest i32 bound {bound:.4g} "
                f"(headroom {INT32_MAX / max(bound, 1):.1f}x)",
                self.fn.name, chain))

    def exec_block(self, stmts):
        for s in stmts:
            self.exec_stmt(s)

    def exec_stmt(self, s):
        if isinstance(s, ast.Assign):
            val = self.ev(s.value)
            for t in s.targets:
                self.assign(t, val, s.value)
        elif isinstance(s, ast.AugAssign):
            synth = ast.BinOp(left=ast.Name(id=getattr(s.target, "id", "_"),
                                            ctx=ast.Load()),
                              op=s.op, right=s.value)
            ast.copy_location(synth, s)
            ast.fix_missing_locations(synth)
            if isinstance(s.target, ast.Name):
                self.env[s.target.id] = self.ev(synth)
            else:
                self.ev(s.value)
        elif isinstance(s, ast.AnnAssign):
            if s.value is not None:
                val = self.ev(s.value)
                self.assign(s.target, val, s.value)
        elif isinstance(s, (ast.Expr, ast.Return)):
            if s.value is not None:
                self.ev(s.value)
        elif isinstance(s, ast.If):
            self.ev(s.test)
            saved = dict(self.env)
            self.exec_block(s.body)
            then_env = self.env
            self.env = saved
            self.exec_block(s.orelse)
            self.env = self._join_envs(then_env, self.env)
        elif isinstance(s, ast.For):
            it = self.ev(s.iter)
            if isinstance(s.target, ast.Name):
                rng = self._range_of(s.iter)
                self.env[s.target.id] = rng if rng is not None else \
                    replace(it, axis=None, total=None)
            self.exec_block(s.body)
            self.exec_block(s.orelse)
        elif isinstance(s, ast.While):
            self.ev(s.test)
            self.exec_block(s.body)
            self.exec_block(s.orelse)
        elif isinstance(s, ast.With):
            for item in s.items:
                self.ev(item.context_expr)
            self.exec_block(s.body)
        elif isinstance(s, ast.Try):
            self.exec_block(s.body)
            for h in s.handlers:
                self.exec_block(h.body)
            self.exec_block(s.orelse)
            self.exec_block(s.finalbody)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _FnInterp(self.ow, s, dict(self.env), self.pad).run()
        elif isinstance(s, (ast.Assert, ast.Raise)):
            for child in ast.iter_child_nodes(s):
                if isinstance(child, ast.expr):
                    self.ev(child)
        elif isinstance(s, ast.Delete):
            for t in s.targets:
                if isinstance(t, ast.Name):
                    self.env.pop(t.id, None)

    def assign(self, target, val: AbsVal, rhs):
        if isinstance(target, ast.Name):
            self.env[target.id] = replace(
                val, chain=_chain(val, f"{target.id} = {_src(rhs)}"))
        elif isinstance(target, (ast.Tuple, ast.List)):
            if isinstance(rhs, (ast.Tuple, ast.List)) \
                    and len(rhs.elts) == len(target.elts):
                for t, r in zip(target.elts, rhs.elts):
                    self.assign(t, self.ev(r), r)
            else:
                for t in target.elts:
                    if isinstance(t, ast.Name):
                        self.env[t.id] = replace(val, chain=val.chain)
        # subscript/attribute stores: no env effect

    @staticmethod
    def _join_envs(a: Dict[str, AbsVal], b: Dict[str, AbsVal]):
        out: Dict[str, AbsVal] = {}
        for k in set(a) | set(b):
            if k in a and k in b:
                out[k] = _join(a[k], b[k])
            else:
                out[k] = a.get(k, b.get(k, TOP))
        return out

    def _range_of(self, it) -> Optional[AbsVal]:
        if isinstance(it, ast.Call) and dotted(it.func) == "range" \
                and it.args:
            hi = self.ev(it.args[-1])
            if hi.known:
                return AbsVal(0, max(0, hi.hi - 1), "pyint")
        return None

    # ---- expressions -----------------------------------------------------

    def ev(self, node) -> AbsVal:
        handler = getattr(self, "_ev_" + type(node).__name__, None)
        if handler is not None:
            return handler(node)
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self.ev(child)
        return TOP

    def _ev_Constant(self, node) -> AbsVal:
        v = node.value
        if isinstance(v, bool):
            return AbsVal(int(v), int(v), "bool")
        if isinstance(v, int):
            return _const(v)
        if isinstance(v, float):
            return AbsVal(v, v, "float")
        return TOP

    def _ev_Name(self, node) -> AbsVal:
        return self.env.get(node.id, TOP)

    def _ev_Tuple(self, node) -> AbsVal:
        vals = [self.ev(e) for e in node.elts]
        out = TOP
        for v in vals:
            out = _join(out, v) if out is not TOP else v
        return out if vals else TOP

    _ev_List = _ev_Tuple

    def _ev_Attribute(self, node) -> AbsVal:
        base = self.ev(node.value)
        if node.attr == "T":
            return replace(base, axis=base.axis1, axis1=base.axis)
        return TOP

    def _ev_Subscript(self, node) -> AbsVal:
        # x.shape[k] -> static extent
        if isinstance(node.value, ast.Attribute) \
                and node.value.attr == "shape":
            base = self.ev(node.value.value)
            k = node.slice.value if isinstance(node.slice, ast.Constant) \
                else None
            ext = {0: base.axis, 1: base.axis1}.get(k)
            if ext is not None:
                return AbsVal(ext, ext, "pyint",
                              chain=_chain(base, f"shape[{k}] = {ext}"))
            return AbsVal(1, _AXIS_DEFAULT, "pyint")
        # dict read by string key: seed by the repo naming contract
        if isinstance(node.slice, ast.Constant) \
                and isinstance(node.slice.value, str):
            self.ev(node.value)
            v = _seed(node.slice.value, self.pad)
            return replace(v, chain=_chain(v, f"{_src(node)}"))
        # gather: drops the leading axis, propagates taint
        base = self.ev(node.value)
        idx = self.ev(node.slice)
        return AbsVal(base.lo, base.hi, base.kind,
                      _tmax(base.taint, idx.taint), base.axis1, None, None,
                      _chain(base, f"gather {_src(node)}"))

    def _ev_UnaryOp(self, node) -> AbsVal:
        v = self.ev(node.operand)
        if isinstance(node.op, ast.USub):
            return replace(v, lo=-v.hi, hi=-v.lo, total=None)
        if isinstance(node.op, (ast.Invert, ast.Not)):
            # ~real selects exactly the pad rows: an inverted guard is
            # a pad selector, not a guard
            t = PAD if v.taint == GUARD else v.taint
            return AbsVal(0, 1, "bool", t, v.axis, v.axis1, v.axis,
                          _chain(v, f"~{_src(node.operand, 32)}"))
        return v

    def _ev_BoolOp(self, node) -> AbsVal:
        vals = [self.ev(v) for v in node.values]
        t = CLEAN
        for v in vals:
            t = _sanitize(t, v.taint)
        out = vals[0]
        for v in vals[1:]:
            out = _join(out, v)
        return replace(out, taint=t)

    def _ev_Compare(self, node) -> AbsVal:
        t = CLEAN
        for v in [self.ev(node.left)] + [self.ev(c) for c in
                                         node.comparators]:
            t = _tmax(t, GUARD if v.taint == GUARD else v.taint)
        return AbsVal(0, 1, "bool", t)

    def _ev_IfExp(self, node) -> AbsVal:
        test = self.ev(node.test)
        a, b = self.ev(node.body), self.ev(node.orelse)
        out = _join(a, b)
        if test.taint == GUARD:
            return replace(out, taint=GUARD)
        return replace(out, taint=_tmax(out.taint, test.taint))

    def _ev_BinOp(self, node) -> AbsVal:
        l, r = self.ev(node.left), self.ev(node.right)
        kind = _kind_join(l.kind, r.kind)
        op = node.op
        lo, hi = -INF, INF
        total = None
        taint = _tmax(l.taint, r.taint)
        if isinstance(op, (ast.Add, ast.Sub)):
            if l.known and r.known:
                if isinstance(op, ast.Add):
                    lo, hi = l.lo + r.lo, l.hi + r.hi
                else:
                    lo, hi = l.lo - r.hi, l.hi - r.lo
            if isinstance(op, ast.Add) and l.total is not None \
                    and r.total is not None and l.lo >= 0 and r.lo >= 0:
                total = l.total + r.total
        elif isinstance(op, ast.Mult):
            taint = _sanitize(l.taint, r.taint)
            if l.known and r.known:
                cands = (l.lo * r.lo, l.lo * r.hi, l.hi * r.lo, l.hi * r.hi)
                lo, hi = min(cands), max(cands)
            scal, arr = (l, r) if l.axis is None else (r, l)
            if arr.total is not None and scal.known and scal.lo >= 0 \
                    and arr.lo >= 0:
                total = arr.total * scal.hi
        elif isinstance(op, ast.FloorDiv):
            if l.known and r.known and r.lo >= 1:
                lo, hi = min(l.lo // r.lo, l.lo // r.hi, 0), \
                    max(l.hi // r.lo, 0)
        elif isinstance(op, ast.Mod):
            if r.known and r.lo >= 1:
                lo, hi = 0, r.hi - 1
        elif isinstance(op, ast.BitAnd):
            taint = _sanitize(l.taint, r.taint)
            if kind == "bool":
                lo, hi = 0, 1
                if l.total is not None or r.total is not None:
                    total = min(x for x in (l.total, r.total)
                                if x is not None)
            elif r.known and r.lo >= 0:
                lo, hi = 0, r.hi          # masking: x & 0x7FFF
            elif l.known and l.lo >= 0:
                lo, hi = 0, l.hi
        elif isinstance(op, ast.BitOr):
            if kind == "bool":
                lo, hi = 0, 1
        elif isinstance(op, ast.RShift):
            if l.known and r.known and r.lo >= 0:
                lo, hi = min(l.lo, 0), max(int(l.hi) >> int(r.lo), 0)
        elif isinstance(op, ast.LShift):
            if l.known and r.known:
                lo, hi = min(l.lo, 0), int(l.hi) << int(r.hi)
        elif isinstance(op, (ast.Div, ast.Pow)):
            kind = "float" if isinstance(op, ast.Div) else kind
            if isinstance(op, ast.Pow) and l.known and r.known \
                    and 0 <= r.hi <= 64 and abs(l.hi) <= 2 ** 20:
                hi = max(abs(l.lo), abs(l.hi)) ** r.hi
                lo = 0 if l.lo >= 0 else -hi
        out = AbsVal(lo, hi, kind, taint,
                     l.axis or r.axis, l.axis1 or r.axis1, total)
        if out.known:
            out = replace(out, chain=_chain(
                l if l.chain else r,
                f"L{node.lineno}: {_src(node)} -> [{lo:g}, {hi:g}]"))
        return self._chk32(out, node)

    # ---- overflow check --------------------------------------------------

    def _chk32(self, val: AbsVal, node, what: str = "") -> AbsVal:
        if val.kind != "i32" or val.hi == INF \
                or (val.hi <= INT32_MAX and val.lo >= -INT32_MAX - 1):
            if val.kind == "i32" and val.hi != INF and val.chain \
                    and (self.peak is None
                         or max(val.hi, -val.lo) > self.peak[0]):
                self.peak = (max(val.hi, -val.lo), node.lineno, val.chain)
            return val
        bound = max(val.hi, -val.lo)
        proof = self.ow.headroom_at(node.lineno)
        if proof is not None:
            ok, res = eval_headroom(proof)
            if ok:
                self.ow.events.append(AbsEvent(
                    "bless", node.lineno, node.col_offset,
                    f"headroom({proof}) = {res} < 2**31 — blessed",
                    self.fn.name, val.chain))
                return replace(val, lo=max(val.lo, -res), hi=min(val.hi, res))
            self.ow.emit(
                "VT010", node,
                f"headroom proof rejected: {res} — the int32 range here "
                f"derives to {bound:.4g} at cfg7 extents and the bless "
                f"must prove a bound < 2**31", self.fn.name, val.chain)
            return replace(val, lo=-INF, hi=INF, total=None)
        self.ow.emit(
            "VT010", node,
            f"int32 overflow: {what or _src(node)!r} spans "
            f"[{val.lo:.4g}, {val.hi:.4g}] at cfg7 x mesh extents "
            f"(|range| > 2**31-1); widen to int64, saturate/limb-split, "
            f"or prove '# vclint: headroom(<bound>)'",
            self.fn.name, val.chain)
        return replace(val, lo=-INF, hi=INF, total=None)

    # ---- calls -----------------------------------------------------------

    def _ev_Call(self, node) -> AbsVal:
        f = node.func
        # x.at[idx].add(v) scatter family
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Subscript) \
                and isinstance(f.value.value, ast.Attribute) \
                and f.value.value.attr == "at":
            return self._scatter(node, f.attr, f.value.value.value,
                                 f.value.slice)
        name = dotted(f)
        head = name.split(".")[-1] if name else \
            (f.attr if isinstance(f, ast.Attribute) else None)
        # module-namespace call vs method call on a value
        is_module_call = name is not None and (
            "." not in name or name.split(".")[0] in
            ("jnp", "np", "lax", "jax", "numpy", "jsp"))

        if head == "where" and len(node.args) == 3:
            return self._where(node)
        if head in ("cond", "select") and name and "lax" in name:
            return self._cond(node)
        if head in ("while_loop", "fori_loop", "scan", "switch") \
                and name and ("lax" in name or is_module_call):
            for a in node.args:
                if isinstance(a, ast.Lambda):
                    _FnInterp(self.ow, _lambda_fn(a), dict(self.env),
                              self.pad).run()
                else:
                    self.ev(a)
            return TOP
        if head == "associative_scan":
            return self._assoc_scan(node)
        if head in _REDUCES and (is_module_call
                                 or isinstance(f, ast.Attribute)):
            operand = node.args[0] if is_module_call and node.args else \
                (f.value if isinstance(f, ast.Attribute)
                 and not is_module_call else None)
            if operand is not None:
                return self._reduce(node, head, operand)
        if head == "astype" and isinstance(f, ast.Attribute):
            return self._cast(node, f.value,
                              _dtype_kind(node.args[0]) if node.args
                              else None)
        if head and is_module_call and _dtype_kind(f) and node.args:
            return self._cast(node, node.args[0], _dtype_kind(f))
        if head in ("minimum", "maximum", "clip") and node.args:
            vals = [self.ev(a) for a in node.args]
            for kw in node.keywords:
                self.ev(kw.value)
            out = vals[0]
            if head == "minimum" and len(vals) >= 2:
                out = replace(_join(vals[0], vals[1]),
                              hi=min(vals[0].hi, vals[1].hi),
                              taint=_sanitize(vals[0].taint, vals[1].taint))
            elif head == "maximum" and len(vals) >= 2:
                out = replace(_join(vals[0], vals[1]),
                              lo=max(vals[0].lo, vals[1].lo))
            elif head == "clip" and len(vals) >= 3:
                out = replace(vals[0], lo=max(vals[0].lo, vals[1].lo),
                              hi=min(vals[0].hi, vals[2].hi))
            return replace(out, chain=_chain(vals[0],
                                             f"L{node.lineno}: {head}"))
        if head == "arange" and node.args:
            n = self.ev(node.args[-1])
            if n.known:
                ext = int(n.hi)
                return AbsVal(0, max(ext - 1, 0), "i32", CLEAN, ext,
                              chain=(f"arange({ext})",))
            return AbsVal(0, _AXIS_DEFAULT - 1, "i32", CLEAN, _AXIS_DEFAULT)
        if head in ("zeros", "ones", "full", "zeros_like", "ones_like",
                    "full_like"):
            return self._fill(node, head)
        if head in ("concatenate", "stack", "hstack", "vstack"):
            return self._concat(node, head)
        if head in _PASSTHROUGH and (node.args
                                     or isinstance(f, ast.Attribute)):
            base = node.args[0] if node.args else f.value
            out = self.ev(base)
            for a in node.args[1:]:
                self.ev(a)
            for kw in node.keywords:
                self.ev(kw.value)
            if head in ("reshape", "ravel"):
                out = replace(out, axis1=None)
            return out
        if head in ("take", "take_along_axis", "gather", "dynamic_slice",
                    "dynamic_update_slice") and node.args:
            vals = [self.ev(a) for a in node.args]
            t = CLEAN
            for v in vals:
                t = _tmax(t, v.taint)
            return replace(vals[0], taint=t, total=None)
        if head in ("logical_and", "logical_or") and len(node.args) >= 2:
            a, b = self.ev(node.args[0]), self.ev(node.args[1])
            t = _sanitize(a.taint, b.taint) if head == "logical_and" \
                else _tmax(a.taint, b.taint)
            return replace(_join(a, b), taint=t, kind="bool", lo=0, hi=1)
        # unknown / local helper: evaluate args (nested sinks still fire),
        # result TOP-clean (intra-procedural: the helper is analyzed on
        # its own with seeded params)
        for a in node.args:
            if isinstance(a, ast.Lambda):
                continue
            self.ev(a)
        for kw in node.keywords:
            self.ev(kw.value)
        if isinstance(f, ast.Attribute) and not name:
            self.ev(f.value)
        return TOP

    def _where(self, node) -> AbsVal:
        cond = self.ev(node.args[0])
        a, b = self.ev(node.args[1]), self.ev(node.args[2])
        out = _join(a, b)
        if cond.taint == GUARD:
            taint = GUARD      # pads deliberately parked at the fill value
        elif cond.taint == PAD:
            taint = PAD
        else:
            taint = out.taint
        total = None
        if a.total is not None and b.known and b.lo >= 0 and b.hi == 0:
            total = a.total
        elif b.total is not None and a.known and a.lo >= 0 and a.hi == 0:
            total = b.total
        elif cond.taint == GUARD and cond.total is not None \
                and out.known and out.lo >= 0:
            total = cond.total * out.hi
        return replace(out, taint=taint, total=total,
                       chain=_chain(out, f"L{node.lineno}: where("
                                         f"{_src(node.args[0], 28)}, ..)"))

    def _cond(self, node) -> AbsVal:
        out = None
        for a in node.args:
            if isinstance(a, ast.Lambda):
                v = self.ev(a.body)
                out = v if out is None else _join(out, v)
            else:
                self.ev(a)
        return out if out is not None else TOP

    def _assoc_scan(self, node) -> AbsVal:
        """lax.associative_scan: limb-tuple operand and saturating-minimum
        combiners are recognized mitigations; a plain additive combiner is
        a cumsum."""
        if len(node.args) < 2:
            return TOP
        comb, operand = node.args[0], node.args[1]
        if isinstance(operand, (ast.Tuple, ast.List)):
            for e in operand.elts:
                self.ev(e)
            return AbsVal(-INF, INF, "i32", CLEAN,
                          chain=("limb-tuple associative_scan "
                                 "(carry-normalizing, exact)",))
        x = self.ev(operand)
        if isinstance(comb, ast.Lambda):
            body = comb.body
            if isinstance(body, ast.Call) \
                    and (dotted(body.func) or "").endswith("minimum") \
                    and len(body.args) == 2:
                cap = self.ev(body.args[1])
                hi = cap.hi if cap.known else INF
                return AbsVal(min(x.lo, 0), hi, x.kind, x.taint, x.axis,
                              chain=_chain(x, f"L{node.lineno}: saturating "
                                              f"scan capped at "
                                              f"{_src(body.args[1], 24)}"))
        return self._reduce(node, "cumsum", operand, pre=x)

    def _axis_of(self, node, skip_args: int) -> Optional[object]:
        for kw in node.keywords:
            if kw.arg == "axis":
                if isinstance(kw.value, ast.Constant):
                    return kw.value.value
                return "dyn"
        rest = node.args[skip_args:]
        if rest and isinstance(rest[0], ast.Constant) \
                and isinstance(rest[0].value, int):
            return rest[0].value
        return None

    def _reduce(self, node, head: str, operand, pre=None) -> AbsVal:
        x = pre if pre is not None else self.ev(operand)
        for a in node.args:
            if a is not operand and not isinstance(a, ast.Lambda):
                self.ev(a)
        for kw in node.keywords:
            if kw.arg != "axis":
                self.ev(kw.value)
        skip = 1 if node.args and node.args[0] is operand else \
            (2 if head == "associative_scan" else 0)
        axis = self._axis_of(node, skip)
        over_pad_axis = axis in (None, 0, -1)
        if x.taint == PAD and over_pad_axis:
            self.ow.emit(
                "VT011", node,
                f"pad-tainted value reaches '{head}' without a "
                f"real/real_n guard — rows past node_real contaminate the "
                f"cross-row result; mask with '& node_real' or "
                f"'jnp.where(real, .., fill)' first "
                f"(source: {x.chain[0] if x.chain else 'unknown'})",
                self.fn.name, x.chain)
            x = replace(x, taint=CLEAN)
        elif self.pad and over_pad_axis:
            # explain-only trace: a cross-row reduce in a pad-aware
            # kernel whose operand arrived sanitized
            self.ow.events.append(AbsEvent(
                "reduce", node.lineno, node.col_offset,
                f"'{head}' operand {'guard-masked' if x.taint == GUARD else 'clean'}",
                self.fn.name, x.chain))
        if head in ("argmax", "argmin", "argsort", "lexsort"):
            ext = x.axis or _AXIS_DEFAULT
            return AbsVal(0, ext - 1, "i32", CLEAN, x.axis,
                          chain=_chain(x, f"L{node.lineno}: {head} index"))
        if head in ("any", "all"):
            return AbsVal(0, 1, "bool", x.taint if not over_pad_axis
                          else CLEAN)
        if head in ("max", "min", "amax", "amin", "median", "sort",
                    "cummax", "top_k", "nanargmax"):
            return replace(x, total=None)
        # cumsum/sum family: the accumulation surface
        ext = x.axis or _AXIS_DEFAULT
        if not x.known:
            return AbsVal(-INF, INF, _acc_kind(x.kind), x.taint)
        if x.lo >= 0 and x.total is not None:
            hi, lo = x.total, 0
        else:
            hi = max(x.hi * ext, x.hi)
            lo = min(x.lo * ext, x.lo)
        out = AbsVal(lo, hi, _acc_kind(x.kind), x.taint,
                     x.axis if head.startswith("cum") else None,
                     None, x.total if x.lo >= 0 else None,
                     _chain(x, f"L{node.lineno}: {head} over axis extent "
                               f"{ext} -> [{lo:g}, {hi:g}]"))
        return self._chk32(out, node, what=_src(node))

    def _scatter(self, node, mode: str, base, idx) -> AbsVal:
        b = self.ev(base)
        i = self.ev(idx)
        v = self.ev(node.args[0]) if node.args else TOP
        for a in node.args[1:]:
            self.ev(a)
        taint = b.taint
        if PAD in (v.taint, i.taint):
            taint = PAD
        elif GUARD in (v.taint, i.taint):
            taint = _tmax(taint, GUARD) if taint != PAD else taint
        if mode == "add":
            if v.known and v.lo >= 0 and b.known and b.lo >= 0:
                mass = v.total if v.total is not None else \
                    (v.hi * (v.axis or _AXIS_DEFAULT))
                out = AbsVal(b.lo, b.hi + mass, _acc_kind(
                    _kind_join(b.kind, v.kind)), taint, b.axis, b.axis1,
                    (b.total + mass) if b.total is not None else None,
                    _chain(v, f"L{node.lineno}: scatter-add mass "
                              f"<= {mass:g}"))
                return self._chk32(out, node, what=_src(node))
            return AbsVal(-INF, INF, _acc_kind(_kind_join(b.kind, v.kind)),
                          taint, b.axis, b.axis1)
        if mode in ("set", "max", "min"):
            return replace(_join(b, v), taint=taint, total=None)
        return replace(b, taint=taint, total=None)

    def _fill(self, node, head: str) -> AbsVal:
        """zeros/ones/full(+_like): constant arrays with a static shape."""
        axis = axis1 = None
        if node.args:
            shape = node.args[0]
            if head.endswith("_like"):
                ref = self.ev(shape)
                axis, axis1 = ref.axis, ref.axis1
            elif isinstance(shape, (ast.Tuple, ast.List)) and shape.elts:
                dims = [self.ev(e) for e in shape.elts]
                if dims[0].known:
                    axis = int(dims[0].hi)
                if len(dims) > 1 and dims[1].known:
                    axis1 = int(dims[1].hi)
            else:
                n = self.ev(shape)
                if n.known:
                    axis = int(n.hi)
        kind = "float"
        for kw in node.keywords:
            if kw.arg == "dtype":
                kind = _dtype_kind(kw.value) or "obj"
        for a in node.args[1:]:
            dk = _dtype_kind(a)
            if dk:
                kind = dk
        if head.startswith("zeros"):
            lo = hi = 0
        elif head.startswith("ones"):
            lo = hi = 1
        elif head.startswith("full") and len(node.args) > 1:
            v = self.ev(node.args[1])
            lo, hi = v.lo, v.hi
        else:
            lo, hi = -INF, INF
        total = hi * (axis or 1) if hi != INF and hi >= 0 and lo >= 0 \
            else None
        return AbsVal(lo, hi, kind, CLEAN, axis, axis1, total,
                      (f"L{node.lineno}: {head} fill [{lo:g}, {hi:g}]"
                       if hi != INF else f"L{node.lineno}: {head}",))

    def _concat(self, node, head: str) -> AbsVal:
        """concatenate/stack: the packed D2H tail — a PAD element here is
        a VT011 sink (pad rows ship to the host verbatim)."""
        elts = []
        if node.args and isinstance(node.args[0], (ast.Tuple, ast.List)):
            elts = [self.ev(e) for e in node.args[0].elts]
        elif node.args:
            elts = [self.ev(node.args[0])]
        for a in node.args[1:]:
            self.ev(a)
        out = TOP
        for i, v in enumerate(elts):
            out = v if i == 0 else _join(out, v)
        if head == "concatenate" and self.pad \
                and any(v.taint == PAD for v in elts):
            bad = next(v for v in elts if v.taint == PAD)
            self.ow.emit(
                "VT011", node,
                f"pad-tainted rows reach the packed D2H tail "
                f"(jnp.{head}) unmasked — the host decodes pad garbage; "
                f"park pads with 'jnp.where(real, .., fill)' before "
                f"packing (source: "
                f"{bad.chain[0] if bad.chain else 'unknown'})",
                self.fn.name, bad.chain)
            out = replace(out, taint=CLEAN)
        return replace(out, total=None,
                       chain=_chain(out, f"L{node.lineno}: {head}"))

    def _cast(self, node, operand, kind: Optional[str]) -> AbsVal:
        x = self.ev(operand)
        if kind is None:
            return replace(x, kind="obj")
        if kind == "bool":
            return AbsVal(0, 1, "bool", x.taint, x.axis, x.axis1, x.axis,
                          x.chain)
        out = replace(x, kind=kind,
                      chain=_chain(x, f"L{node.lineno}: cast to {kind}"))
        if kind == "i32":
            return self._chk32(out, node, what=_src(node))
        return out


def _acc_kind(kind: str) -> str:
    if kind in ("bool", "pyint", "i32"):
        return "i32"
    return kind


def _lambda_fn(lam: ast.Lambda) -> ast.FunctionDef:
    fn = ast.FunctionDef(
        name="<lambda>", args=lam.args,
        body=[ast.Return(value=lam.body)], decorator_list=[])
    ast.copy_location(fn, lam)
    ast.fix_missing_locations(fn)
    return fn


# ---------------------------------------------------------------------------
# VT012 — donation lifetimes (may-alias dataflow over VT006's decorators)
# ---------------------------------------------------------------------------


def donation_events(tree: ast.AST) -> List[dict]:
    """Statement-ordered may-alias donation timeline, per function."""
    donating = DonatedBufferReuse._donated_positions(tree)
    if not donating:
        return []
    events: List[dict] = []
    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        _DonationFlow(fn.name, donating, events).scan(fn.body)
    return events


class _DonationFlow:
    def __init__(self, fn_name: str, donating, events: List[dict]):
        self.fn = fn_name
        self.donating = donating
        self.events = events
        # buffers are tracked per GENERATION ('carry#0', 'carry#1', ...):
        # rebinding a donated name starts a new live generation, but the
        # old one stays dead — aliases captured before the donation keep
        # pointing at it, so their reads still flag
        self.donated: Dict[str, Tuple[str, int]] = {}
        self.alias: Dict[str, Set[str]] = {}
        self.ver: Dict[str, int] = {}

    def vkey(self, name: str) -> str:
        return f"{name}#{self.ver.get(name, 0)}"

    def scan(self, stmts):
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            for expr in self._value_exprs(s):
                self._scan_expr(expr)
            self._apply_stores(s)
            for body in (getattr(s, "body", None),
                         getattr(s, "orelse", None),
                         getattr(s, "finalbody", None)):
                if isinstance(body, list):
                    self.scan(body)
            for h in getattr(s, "handlers", ()) or ():
                self.scan(h.body)

    @staticmethod
    def _value_exprs(s):
        if isinstance(s, (ast.Assign, ast.AugAssign, ast.AnnAssign,
                          ast.Return, ast.Expr)):
            return [s.value] if s.value is not None else []
        if isinstance(s, (ast.If, ast.While)):
            return [s.test]
        if isinstance(s, ast.For):
            return [s.iter]
        if isinstance(s, ast.With):
            return [i.context_expr for i in s.items]
        return []

    def _scan_expr(self, node):
        # identity checks against None are host metadata, not buffer reads
        if isinstance(node, ast.Compare) \
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in node.ops):
            return
        for child in ast.iter_child_nodes(node):
            self._scan_expr(child)
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if self.vkey(node.id) in self.donated:
                return    # direct read of the donated name: VT006 territory
            roots = self.alias.get(node.id, ())
            dead = [r for r in roots if r in self.donated]
            if dead:
                callee, line = self.donated[dead[0]]
                self.events.append(dict(
                    kind="read", fn=self.fn, line=node.lineno,
                    col=node.col_offset, name=node.id,
                    root=dead[0].split("#")[0],
                    callee=callee, donate_line=line))
                self.alias.pop(node.id, None)
        elif isinstance(node, ast.Call):
            callee = (dotted(node.func) or "").split(".")[-1]
            for p in self.donating.get(callee, ()):
                if p >= len(node.args):
                    continue
                for nm in self._arg_names(node.args[p]):
                    kills = {self.vkey(nm)} | self.alias.get(nm, set())
                    for k in kills:
                        self.donated[k] = (callee, node.lineno)
                    self.events.append(dict(
                        kind="donate", fn=self.fn, line=node.lineno,
                        name=nm, callee=callee))

    @staticmethod
    def _arg_names(arg) -> Set[str]:
        if isinstance(arg, ast.Name):
            return {arg.id}
        return {n.id for n in ast.walk(arg)
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}

    def _roots_of(self, rhs) -> Set[str]:
        if isinstance(rhs, ast.Name):
            return self.alias.get(rhs.id, None) or {self.vkey(rhs.id)}
        if isinstance(rhs, ast.IfExp):
            return self._roots_of(rhs.body) | self._roots_of(rhs.orelse)
        if isinstance(rhs, ast.Attribute):
            if rhs.attr in ("shape", "dtype", "ndim", "size"):
                return set()    # host metadata, not a buffer handle
            return self._roots_of(rhs.value)
        if isinstance(rhs, ast.Subscript):
            return self._roots_of(rhs.value)
        if isinstance(rhs, ast.BoolOp):
            out: Set[str] = set()
            for v in rhs.values:
                out |= self._roots_of(v)
            return out
        return set()

    def _apply_stores(self, s):
        if isinstance(s, ast.Assign):
            for t in s.targets:
                self._store(t, s.value)
        elif isinstance(s, (ast.AugAssign, ast.AnnAssign)):
            self._store(s.target, None)
        elif isinstance(s, ast.For):
            self._store(s.target, None)

    def _store(self, target, rhs):
        if isinstance(target, (ast.Tuple, ast.List)):
            for t in target.elts:
                self._store(t, None)
            return
        if not isinstance(target, ast.Name):
            return
        name = target.id
        roots = self._roots_of(rhs) if rhs is not None else set()
        if self.vkey(name) in self.donated:
            # new generation: the rebound name is alive again, the dead
            # generation stays recorded for aliases that captured it
            self.events.append(dict(kind="rebind", fn=self.fn,
                                    line=target.lineno, name=name))
            self.ver[name] = self.ver.get(name, 0) + 1
        roots.discard(self.vkey(name))
        if roots:
            self.alias[name] = roots
        else:
            self.alias.pop(name, None)


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------

_KERNEL_SCOPE = ("*/ops/*.py", "*/express/place.py")


class _AbsIntRule(Rule):
    def check(self, tree, src, path):
        return [Finding(self.id, path, e.line, e.col, e.msg)
                for e in summarize(tree, src, path) if e.rule == self.id]


@register_rule
class IntRangeOverflow(_AbsIntRule):
    """int32 value whose derived range at cfg7 x mesh extents exceeds
    2^31-1 (see module docstring; bless grammar: headroom(<proof>))."""

    id = "VT010"
    title = "int32 range overflow at maximal bucket shapes"
    patterns = _KERNEL_SCOPE


@register_rule
class PadTaintLeak(_AbsIntRule):
    """Pad-slot rows reaching an unmasked cross-row reduce / argsort /
    packed D2H tail (the pre-PR-10 _window bug class)."""

    id = "VT011"
    title = "pad rows reach an unmasked cross-row reduce"
    patterns = _KERNEL_SCOPE


@register_rule
class DonationLifetime(Rule):
    """Reads through may-aliases of donated buffers after dispatch —
    the dataflow generalization of VT006's decorator-lexical check."""

    id = "VT012"
    title = "aliased read of a donated buffer after dispatch"
    patterns = DonatedBufferReuse.patterns

    def check(self, tree, src, path):
        out: List[Finding] = []
        for e in donation_events(tree):
            if e["kind"] != "read":
                continue
            out.append(Finding(
                self.id, path, e["line"], e["col"],
                f"'{e['name']}' may alias '{e['root']}', donated to "
                f"device dispatch '{e['callee']}' (line "
                f"{e['donate_line']}); a post-dispatch read dereferences "
                f"freed device memory — rebind from the dispatch result "
                f"or refetch before reuse"))
        return out


# ---------------------------------------------------------------------------
# --explain plumbing
# ---------------------------------------------------------------------------


def explain(rule_id: str, norm_paths) -> int:
    """Print derivation chains (VT010), taint paths (VT011) or donation
    timelines (VT012) over the rule's scope, VT007-explain style."""
    import os

    from volcano_tpu.analysis.core import iter_py_files

    rule = {"VT010": IntRangeOverflow, "VT011": PadTaintLeak,
            "VT012": DonationLifetime}[rule_id]()
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [p for p in iter_py_files([pkg]) if rule.applies_to(p)]
    if norm_paths:
        files = [p for p in files
                 if any(p.replace(os.sep, "/").endswith(n)
                        or n in p.replace(os.sep, "/")
                        for n in norm_paths)]
    for path in sorted(files):
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        rel = path.replace(os.sep, "/")
        idx = rel.find("volcano_tpu/")
        rel = rel[idx:] if idx >= 0 else rel
        if rule_id == "VT012":
            for e in donation_events(tree):
                if e["kind"] == "donate":
                    print(f"{rel}:{e['line']} [{e['fn']}] donate   "
                          f"'{e['name']}' -> {e['callee']} (buffer dead)")
                elif e["kind"] == "rebind":
                    print(f"{rel}:{e['line']} [{e['fn']}] rebind   "
                          f"'{e['name']}' (alive again)")
                else:
                    print(f"{rel}:{e['line']} [{e['fn']}] READ     "
                          f"'{e['name']}' aliasing dead '{e['root']}' "
                          f"(donated at L{e['donate_line']})")
            continue
        for e in summarize(tree, src, path):
            if rule_id == "VT010" and e.rule in ("VT010", "bless", "range"):
                verdict = {"VT010": "OVERFLOW", "bless": "blessed",
                           "range": "checked"}[e.rule]
                print(f"{rel}:{e.line} [{e.fn}] {verdict}: {e.msg}")
                if e.rule != "range":
                    for step in e.detail:
                        print(f"    {step}")
            elif rule_id == "VT011" and e.rule in ("VT011", "reduce"):
                if e.rule == "reduce":
                    print(f"{rel}:{e.line} [{e.fn}] ok: {e.msg}")
                    continue
                print(f"{rel}:{e.line} [{e.fn}] TAINT SINK: {e.msg}")
                for step in e.detail:
                    print(f"    {step}")
    return 0
