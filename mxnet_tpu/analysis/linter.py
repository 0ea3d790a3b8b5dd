"""mxlint: AST-based static analysis for TPU-hazard patterns.

The deferred-compute design (CachedOp / TrainStep / serve bucketing)
makes four classes of bug invisible at the call site: a hidden host sync
stalls the dispatch pipeline, an unstable trace signature silently
recompiles, a tracer stored outside its trace poisons later calls, and a
host buffer mutated while aliased into an in-flight dispatch corrupts
device data (the PR-4 serve bug). A fifth — lock discipline across the
background-thread subsystems (metrics registry, serve engine,
DevicePrefetcher, async CheckpointManager) — turns into deadlocks or
multi-millisecond critical sections. None of these are checked by the
runtime; this module surfaces them from source.

Rules
-----
- **MX001 host-sync-in-traced/hot code** — ``.item()`` / ``.asnumpy()`` /
  ``float()`` / ``np.asarray`` / ``block_until_ready`` on values inside a
  traced function (jit-decorated, or passed to ``jax.jit`` / ``lax.scan``
  / ``while_loop`` / ...) or inside a loop that dispatches a known-jitted
  callable (a "hot loop").
- **MX002 recompile hazard** — a jit wrapper constructed inside a loop
  (fresh trace cache every iteration), or an unhashable literal (list /
  dict / set) passed in a ``static_argnums`` / ``static_argnames``
  position of a known-jitted callable.
- **MX003 tracer leak** — storing values from inside a traced function
  onto ``self``, globals/nonlocals, or free (closure) containers: the
  tracer outlives its trace and poisons the next call.
- **MX004 numpy-alias hazard** — passing a slice (or the whole) of a
  mutable host numpy buffer (``self._x = np.zeros(...)`` and mutated
  elsewhere in the class) into a dispatch without ``.copy()``: CPU-jit
  argument conversion can zero-copy-alias the buffer, so a later mutation
  corrupts the in-flight computation.
- **MX005 lock discipline** — blocking work (device sync, file I/O,
  ``queue.get``, ``time.sleep``, thread joins — directly, or one call
  deep through a method of the same class) performed while holding a
  lock, nested re-acquisition of the same non-reentrant lock, and
  inconsistent lock acquisition order across the analyzed files (a cycle
  in the static acquisition graph).
- **MX101/MX102/MX103 Pallas kernel family** — DMA lifecycle (every
  ``make_async_copy`` start reaches a wait on all paths, no scratch-slot
  reuse before its in-flight copy lands), memory-space discipline (an
  HBM-resident ``pl.ANY`` ref only feeds async copies), and the
  static VMEM budget cross-check against the runtime ``fusable_*``
  gates. Implemented in :mod:`analysis.kernels`; the rules only fire on
  files containing a ``pallas_call`` site.

Suppressions
------------
Deliberate violations carry an inline justification::

    fn(self._buf[s])   # mxlint: disable=MX004 -- slot-keyed reuse is
                       # race-free: refill postdates the tok0 force

A whole file opts out with ``# mxlint: skip-file``. Everything else is
matched against the committed baseline (``tools/mxlint_baseline.json``)
by a content fingerprint that survives line drift; only NEW findings
fail CI (see ``tools/mxlint.py``).
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
import io
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "RULES", "lint_source", "lint_file", "lint_paths",
           "find_cycles"]

RULES = {
    "MX001": "host sync inside traced/hot code",
    "MX002": "recompile hazard (unstable jit signature)",
    "MX003": "tracer leak out of a traced function",
    "MX004": "numpy buffer aliased into a dispatch then mutated",
    "MX005": "lock discipline (blocking under lock / ordering)",
    # MX1xx: Pallas kernel family (analysis/kernels.py, loaded lazily —
    # the rules only fire on files that contain a pallas_call site)
    "MX101": "DMA lifecycle (unwaited / slot-reuse-before-wait copy)",
    "MX102": "memory-space discipline (direct use of an ANY/HBM ref)",
    "MX103": "VMEM footprint disagrees with the runtime fusable gate",
}

_KERNEL_RULES = {"MX101", "MX102", "MX103"}


def _kernel_analyzer():
    """Import analysis/kernels.py lazily. Works both as a package
    relative import and — for the standalone tools/mxlint.py loader,
    which execs this file outside the package — by path."""
    try:
        from . import kernels  # type: ignore
        return kernels
    except ImportError:
        import importlib.util
        import sys
        kpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "kernels.py")
        mod = sys.modules.get("_mxlint_kernels")
        if mod is not None:
            return mod
        spec = importlib.util.spec_from_file_location(
            "_mxlint_kernels", kpath)
        mod = importlib.util.module_from_spec(spec)
        # register before exec: dataclasses resolves the module by name
        sys.modules["_mxlint_kernels"] = mod
        spec.loader.exec_module(mod)
        return mod

# entry points whose function arguments become traced code
_TRACE_ENTRIES = {
    "jit", "pjit", "scan", "while_loop", "fori_loop", "cond", "switch",
    "vmap", "pmap", "grad", "value_and_grad", "checkpoint", "remat",
    "custom_jvp", "custom_vjp", "named_call",
}
_JIT_NAMES = {"jit", "pjit"}

# attribute calls that force a device->host sync
_SYNC_ATTRS = {"item", "asnumpy", "asscalar", "block_until_ready",
               "wait_to_read"}
# dotted callables that force a sync
_SYNC_FUNCS = {"jax.block_until_ready", "jax.device_get"}
_NUMPY_MODULES = {"np", "onp", "numpy", "jnp"}
_NUMPY_CONVERTERS = {"asarray", "array", "asanyarray"}
_NUMPY_CTORS = {"zeros", "ones", "empty", "full", "arange", "array",
                "asarray", "zeros_like", "ones_like", "empty_like"}

# callees through which passing a buffer is NOT a dispatch (MX004)
_MX004_SAFE_BUILTINS = {
    "int", "float", "bool", "len", "str", "repr", "list", "tuple", "set",
    "min", "max", "sum", "sorted", "enumerate", "zip", "range", "print",
    "isinstance", "id", "type", "abs", "hash", "format",
}
_MX004_SAFE_ATTRS = {"copy", "astype", "tolist", "fill", "append", "get",
                     "setdefault", "observe", "set", "inc", "dec", "labels",
                     "update", "extend", "add", "mean", "sum", "reshape",
                     "item", "view"}

# containers whose mutation from a traced fn leaks the tracer
_MX003_MUTATORS = {"append", "extend", "add", "insert", "update",
                   "setdefault", "__setitem__"}

_LOCK_NAME_RE = re.compile(r"(^|_)(lock|cond|mutex|rlock|sem)\w*$",
                           re.IGNORECASE)

# dotted-prefix blocking calls under a lock (MX005)
_BLOCKING_PREFIXES = (
    "open", "os.rename", "os.replace", "os.makedirs", "os.unlink",
    "os.remove", "os.listdir", "os.walk", "os.stat", "os.rmdir",
    "shutil.", "json.dump", "json.load", "pickle.dump", "pickle.load",
    "tempfile.", "subprocess.", "urllib.", "requests.", "socket.",
    "time.sleep", "select.select",
)
_BLOCKING_NP_IO = {"save", "savez", "savez_compressed", "load", "loadtxt",
                   "savetxt"}
_QUEUE_RE = re.compile(r"(^|_)(q|queue)\d*$", re.IGNORECASE)
_THREAD_RE = re.compile(r"thread", re.IGNORECASE)


@dataclasses.dataclass
class Finding:
    """One lint hit. ``fingerprint`` identifies the finding by content
    (rule + file + enclosing scope + source text), not by line number, so
    a committed baseline survives unrelated edits to the file."""
    rule: str
    path: str
    line: int
    col: int
    message: str
    context: str = ""
    snippet: str = ""

    @property
    def fingerprint(self) -> str:
        key = "|".join((self.rule, self.path.replace(os.sep, "/"),
                        self.context, " ".join(self.snippet.split())))
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    def format(self) -> str:
        loc = f"{self.path}:{self.line}:{self.col}"
        ctx = f" [{self.context}]" if self.context else ""
        return f"{loc}: {self.rule} {self.message}{ctx}"

    def to_dict(self) -> Dict[str, object]:
        return {"rule": self.rule, "path": self.path.replace(os.sep, "/"),
                "line": self.line, "col": self.col,
                "message": self.message, "context": self.context,
                "snippet": self.snippet, "fingerprint": self.fingerprint}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _callee(call: ast.Call) -> Optional[str]:
    return _dotted(call.func)


def _is_jit_call(call: ast.Call) -> bool:
    """jax.jit(...) / jit(...) / partial(jax.jit, ...)."""
    name = _callee(call)
    if name is None:
        return False
    last = name.rsplit(".", 1)[-1]
    if last in _JIT_NAMES:
        return True
    if last == "partial" and call.args:
        inner = _dotted(call.args[0])
        if inner and inner.rsplit(".", 1)[-1] in _JIT_NAMES:
            return True
    return False


def _suppressions(source: str) -> Tuple[Dict[int, Set[str]], bool]:
    """line -> suppressed rule set from ``# mxlint: disable=...`` comments,
    plus the file-level skip flag."""
    per_line: Dict[int, Set[str]] = {}
    skip = False
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            text = tok.string
            if "mxlint:" not in text:
                continue
            if "skip-file" in text:
                skip = True
                continue
            m = re.search(r"mxlint:\s*disable=([\w,]+)", text)
            if m:
                rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
                per_line.setdefault(tok.start[0], set()).update(rules)
    except (tokenize.TokenError, IndentationError):
        pass
    # a suppression on a standalone comment line covers the next code
    # line (chaining through consecutive comment-only lines), so a long
    # justification can sit ABOVE the flagged statement
    lines = source.splitlines()

    def comment_only(i: int) -> bool:
        return 1 <= i <= len(lines) and lines[i - 1].lstrip().startswith("#")

    for ln in sorted(per_line):
        if not comment_only(ln):
            continue
        nxt = ln + 1
        while comment_only(nxt):
            nxt += 1
        if nxt <= len(lines):
            per_line.setdefault(nxt, set()).update(per_line[ln])
    return per_line, skip


# ---------------------------------------------------------------------------
# pass 1: module index (traced defs, jitted names, class buffer maps)
# ---------------------------------------------------------------------------

class _ClassInfo:
    def __init__(self, name: str):
        self.name = name
        self.host_buffers: Set[str] = set()     # self.X = np.zeros(...)
        self.mutated: Set[str] = set()          # self.X[..] = / self.X +=
        self.methods: Dict[str, ast.AST] = {}
        self.blocking_methods: Set[str] = set() # direct blocking call in body


class _ModuleIndex:
    def __init__(self):
        self.traced_defs: Set[ast.AST] = set()
        self.traced_names: Set[str] = set()
        self.jitted_names: Set[str] = set()     # f = jax.jit(g)
        self.jit_static: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {}
        self.classes: Dict[ast.AST, _ClassInfo] = {}


def _numpy_ctor_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    name = _callee(node)
    if not name or "." not in name:
        return False
    mod, _, last = name.rpartition(".")
    return mod.rsplit(".", 1)[-1] in (_NUMPY_MODULES - {"jnp"}) and \
        last in _NUMPY_CTORS


def _holds_numpy_buffers(node: ast.AST) -> bool:
    """RHS allocates host numpy storage: a ctor call, or a list/listcomp/
    dict of ctor calls (per-slot staging buffer idiom)."""
    if _numpy_ctor_call(node):
        return True
    if isinstance(node, (ast.List, ast.Tuple)):
        return any(_numpy_ctor_call(e) for e in node.elts)
    if isinstance(node, (ast.ListComp, ast.GeneratorExp)):
        return _numpy_ctor_call(node.elt)
    if isinstance(node, ast.DictComp):
        return _numpy_ctor_call(node.value)
    return False


def _self_attr(node: ast.AST) -> Optional[str]:
    """'X' when node is exactly ``self.X``."""
    if (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _direct_blocking(call: ast.Call, held: Sequence[str] = ()) -> Optional[str]:
    """Reason string when this call blocks (MX005 vocabulary)."""
    name = _callee(call)
    if name:
        for p in _BLOCKING_PREFIXES:
            if name == p.rstrip(".") or name.startswith(p):
                if name.startswith("os.path."):
                    return None
                return f"blocking call {name}()"
        mod, _, last = name.rpartition(".")
        if mod.rsplit(".", 1)[-1] in (_NUMPY_MODULES - {"jnp"}) \
                and last in _BLOCKING_NP_IO:
            return f"file I/O {name}()"
        if name in _SYNC_FUNCS or last == "block_until_ready":
            return f"device sync {name}()"
    if isinstance(call.func, ast.Attribute):
        attr = call.func.attr
        recv = _dotted(call.func.value)
        if attr in _SYNC_ATTRS and not isinstance(call.func.value,
                                                  ast.Constant):
            return f"device sync .{attr}()"
        if attr in ("get", "put") and recv and \
                _QUEUE_RE.search(recv.rsplit(".", 1)[-1]):
            return f"queue .{attr}() (blocks on empty/full)"
        if attr in ("wait", "result", "join"):
            if recv and recv in held:
                return None        # cond.wait on the HELD lock releases it
            if attr == "join" and not (recv and _THREAD_RE.search(recv)):
                return None        # str.join / os.path.join noise
            if attr == "wait" and recv is None:
                return None
            return f"blocking .{attr}()"
    return None


def _mark_traced_defs(tree: ast.Module, idx: _ModuleIndex):
    """Mark FunctionDefs handed to trace entries, resolving names with
    lexical scoping (a method sharing its name with a jitted local must
    not be marked — kvstore's eager ``pack`` vs its jitted inner
    ``pack``). Class bodies do not contribute a lookup frame, matching
    Python name resolution inside methods."""
    _FunctionTypes = (ast.FunctionDef, ast.AsyncFunctionDef)

    def hoist(body, frame):
        for stmt in body:
            if isinstance(stmt, _FunctionTypes):
                frame[stmt.name] = stmt
            elif isinstance(stmt, (ast.If, ast.For, ast.AsyncFor, ast.While,
                                   ast.With, ast.AsyncWith, ast.Try)):
                for field in ("body", "orelse", "finalbody"):
                    hoist(getattr(stmt, field, []) or [], frame)
                for h in getattr(stmt, "handlers", []) or []:
                    hoist(h.body, frame)

    def check_call(node: ast.Call, frames):
        name = _callee(node)
        if not (name and name.rsplit(".", 1)[-1] in _TRACE_ENTRIES):
            return
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            argname = _dotted(arg)
            if not argname or "." in argname:
                continue
            for frame in reversed(frames):
                fn = frame.get(argname)
                if fn is not None:
                    idx.traced_defs.add(fn)
                    idx.traced_names.add(argname)
                    break

    def walk(node, frames):
        if isinstance(node, _FunctionTypes):
            for dec in node.decorator_list:
                walk(dec, frames)
            frame: Dict[str, ast.AST] = {}
            hoist(node.body, frame)
            sub = frames + [frame]
            for stmt in node.body:
                walk(stmt, sub)
            return
        if isinstance(node, ast.ClassDef):
            for stmt in node.body:
                walk(stmt, frames)     # class frame invisible to methods
            return
        if isinstance(node, ast.Call):
            check_call(node, frames)
        for child in ast.iter_child_nodes(node):
            walk(child, frames)

    top: Dict[str, ast.AST] = {}
    hoist(tree.body, top)
    for stmt in tree.body:
        walk(stmt, [top])


def _build_index(tree: ast.Module) -> _ModuleIndex:
    idx = _ModuleIndex()

    _mark_traced_defs(tree, idx)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if _is_jit_call(node.value):
                for t in node.targets:
                    tname = _dotted(t)
                    if tname:
                        idx.jitted_names.add(tname)
                        static = _static_spec(node.value)
                        if static:
                            idx.jit_static[tname] = static

    # jit-decorated defs are traced regardless of how they are called
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and _is_jit_call(dec):
                    idx.traced_defs.add(node)
                elif (_dotted(dec) or "").rsplit(".", 1)[-1] in _JIT_NAMES:
                    idx.traced_defs.add(node)

    # class maps
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        info = _ClassInfo(node.name)
        idx.classes[node] = info
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info.methods.setdefault(sub.name, sub)
                for inner in ast.walk(sub):
                    if isinstance(inner, ast.Call) and \
                            _direct_blocking(inner):
                        info.blocking_methods.add(sub.name)
                        break
            if isinstance(sub, ast.Assign):
                for t in sub.targets:
                    attr = _self_attr(t)
                    if attr and _holds_numpy_buffers(sub.value):
                        info.host_buffers.add(attr)
            # mutations: self.X[..] = / self.X[..][..] = / self.X += ...
            targets: List[ast.AST] = []
            if isinstance(sub, ast.Assign):
                targets = list(sub.targets)
            elif isinstance(sub, ast.AugAssign):
                targets = [sub.target]
            for t in targets:
                base = t
                while isinstance(base, ast.Subscript):
                    base = base.value
                attr = _self_attr(base)
                if attr and base is not t:          # subscript store
                    info.mutated.add(attr)
                elif attr and isinstance(sub, ast.AugAssign):
                    info.mutated.add(attr)
    return idx


def _static_spec(call: ast.Call) -> Optional[Tuple[Tuple[int, ...],
                                                   Tuple[str, ...]]]:
    nums: Tuple[int, ...] = ()
    names: Tuple[str, ...] = ()
    for kw in call.keywords:
        if kw.arg == "static_argnums" and isinstance(kw.value,
                                                     (ast.Tuple, ast.List)):
            nums = tuple(e.value for e in kw.value.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, int))
        elif kw.arg == "static_argnums" and isinstance(kw.value, ast.Constant):
            if isinstance(kw.value.value, int):
                nums = (kw.value.value,)
        elif kw.arg == "static_argnames":
            if isinstance(kw.value, ast.Constant):
                names = (str(kw.value.value),)
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                names = tuple(str(e.value) for e in kw.value.elts
                              if isinstance(e, ast.Constant))
    if nums or names:
        return nums, names
    return None


# ---------------------------------------------------------------------------
# pass 2: rule visitor
# ---------------------------------------------------------------------------

class _Scope:
    def __init__(self, node, traced: bool, locals_: Set[str], qualname: str):
        self.node = node
        self.traced = traced
        self.locals = locals_
        self.qualname = qualname


def _collect_locals(fn) -> Set[str]:
    out: Set[str] = set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs):
        out.add(a.arg)
    if args.vararg:
        out.add(args.vararg.arg)
    if args.kwarg:
        out.add(args.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
                elif isinstance(t, (ast.Tuple, ast.List)):
                    for e in t.elts:
                        if isinstance(e, ast.Name):
                            out.add(e.id)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            if isinstance(node.target, ast.Name):
                out.add(node.target.id)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            if isinstance(node.target, ast.Name):
                out.add(node.target.id)
            elif isinstance(node.target, (ast.Tuple, ast.List)):
                for e in node.target.elts:
                    if isinstance(e, ast.Name):
                        out.add(e.id)
        elif isinstance(node, ast.comprehension):
            if isinstance(node.target, ast.Name):
                out.add(node.target.id)
        elif isinstance(node, ast.With):
            for item in node.items:
                if isinstance(item.optional_vars, ast.Name):
                    out.add(item.optional_vars.id)
        elif isinstance(node, ast.ExceptHandler) and node.name:
            out.add(node.name)
    return out


class _RuleVisitor(ast.NodeVisitor):
    def __init__(self, path: str, source: str, idx: _ModuleIndex):
        self.path = path
        self.lines = source.splitlines()
        self.idx = idx
        self.findings: List[Finding] = []
        self.scopes: List[_Scope] = []
        self.classes: List[ast.ClassDef] = []
        self.loops: List[bool] = []             # is each enclosing loop hot?
        self.locks: List[Tuple[str, ast.AST]] = []
        # acquisition edges for the cross-file order graph:
        # (outer_key, inner_key, Finding-location info)
        self.lock_edges: List[Tuple[str, str, int, int, str]] = []

    # ------------------------------------------------------------- utils
    def _snippet(self, node: ast.AST) -> str:
        line = getattr(node, "lineno", 0)
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def _emit(self, rule: str, node: ast.AST, message: str):
        self.findings.append(Finding(
            rule=rule, path=self.path, line=getattr(node, "lineno", 0),
            col=getattr(node, "col_offset", 0), message=message,
            context=self._qualname(), snippet=self._snippet(node)))

    def _qualname(self) -> str:
        parts = [c.name for c in self.classes]
        parts += [s.node.name for s in self.scopes
                  if hasattr(s.node, "name")]
        return ".".join(parts)

    def _in_traced(self) -> bool:
        return any(s.traced for s in self.scopes)

    def _traced_scope(self) -> Optional[_Scope]:
        for s in self.scopes:
            if s.traced:
                return s
        return None

    def _lock_key(self, text: str) -> str:
        cls = self.classes[-1].name if self.classes else "<module>"
        return f"{cls}:{text}" if text.startswith("self.") else text

    def _class_info(self) -> Optional[_ClassInfo]:
        if self.classes:
            return self.idx.classes.get(self.classes[-1])
        return None

    # ----------------------------------------------------------- scoping
    def visit_ClassDef(self, node: ast.ClassDef):
        self.classes.append(node)
        self.generic_visit(node)
        self.classes.pop()

    def _visit_fn(self, node):
        traced = node in self.idx.traced_defs or self._in_traced()
        self.scopes.append(_Scope(node, traced, _collect_locals(node),
                                  self._qualname()))
        self.generic_visit(node)
        self.scopes.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    # ------------------------------------------------------------- loops
    def _visit_loop(self, node):
        hot = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                name = _callee(sub)
                if name and name in self.idx.jitted_names:
                    hot = True
                    break
        self.loops.append(hot)
        self.generic_visit(node)
        self.loops.pop()

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop

    def _in_hot_loop(self) -> bool:
        return any(self.loops)

    def _in_loop(self) -> bool:
        return bool(self.loops)

    # -------------------------------------------------------------- with
    def visit_With(self, node: ast.With):
        pushed = 0
        for item in node.items:
            text = _dotted(item.context_expr)
            if text is None and isinstance(item.context_expr, ast.Call):
                # with threading.Lock(): / with self._lock_for(x):
                text = _callee(item.context_expr)
            if text is None:
                continue
            last = text.rsplit(".", 1)[-1]
            if not _LOCK_NAME_RE.search(last):
                continue
            key = self._lock_key(text)
            for held_text, _ in self.locks:
                held_key = self._lock_key(held_text)
                if held_key == key:
                    self._emit("MX005", item.context_expr,
                               f"re-acquiring non-reentrant lock {text} "
                               "already held (self-deadlock)")
                else:
                    self.lock_edges.append(
                        (held_key, key, item.context_expr.lineno,
                         item.context_expr.col_offset, self._qualname()))
            self.locks.append((text, node))
            pushed += 1
        self.generic_visit(node)
        for _ in range(pushed):
            self.locks.pop()

    # ------------------------------------------------------------- calls
    def visit_Call(self, node: ast.Call):
        self._check_mx001(node)
        self._check_mx002(node)
        self._check_mx003_call(node)
        self._check_mx004(node)
        self._check_mx005_call(node)
        self.generic_visit(node)

    def _check_mx001(self, node: ast.Call):
        traced = self._in_traced()
        hot = self._in_hot_loop()
        if not traced and not hot:
            return
        where = "traced function" if traced else "hot loop (dispatches a " \
                                                 "jitted callable)"
        name = _callee(node)
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _SYNC_ATTRS:
            self._emit("MX001", node,
                       f"host sync .{node.func.attr}() inside {where}")
            return
        if name:
            last = name.rsplit(".", 1)[-1]
            if name in _SYNC_FUNCS or last == "block_until_ready" or \
                    last == "device_get":
                self._emit("MX001", node,
                           f"host sync {name}() inside {where}")
                return
        if not traced:
            return
        if name in ("float", "int", "bool") and node.args and not \
                isinstance(node.args[0], ast.Constant):
            self._emit("MX001", node,
                       f"{name}() on a traced value forces a host sync "
                       "(and fails under jit)")
            return
        if name and "." in name:
            mod, _, last = name.rpartition(".")
            if mod.rsplit(".", 1)[-1] in (_NUMPY_MODULES - {"jnp"}) and \
                    last in _NUMPY_CONVERTERS and node.args:
                self._emit("MX001", node,
                           f"{name}() materializes a traced value on host")

    def _check_mx002(self, node: ast.Call):
        if _is_jit_call(node) and self._in_loop():
            self._emit("MX002", node,
                       "jit wrapper constructed inside a loop: a fresh "
                       "trace cache every iteration recompiles every call")
            return
        name = _callee(node)
        if name in self.idx.jit_static:
            nums, names = self.idx.jit_static[name]
            for i, arg in enumerate(node.args):
                if i in nums and isinstance(arg, (ast.List, ast.Dict,
                                                  ast.Set)):
                    self._emit("MX002", arg,
                               f"unhashable literal passed as static arg "
                               f"{i} of jitted {name}: every call "
                               "re-traces")
            for kw in node.keywords:
                if kw.arg in names and isinstance(kw.value,
                                                  (ast.List, ast.Dict,
                                                   ast.Set)):
                    self._emit("MX002", kw.value,
                               f"unhashable literal passed as static arg "
                               f"{kw.arg!r} of jitted {name}: every call "
                               "re-traces")

    # ----------------------------------------------------------- MX003
    def visit_Assign(self, node: ast.Assign):
        self._check_mx003_store(node.targets, node)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        self._check_mx003_store([node.target], node)
        self.generic_visit(node)

    def visit_Global(self, node: ast.Global):
        if self._in_traced():
            self._emit("MX003", node,
                       f"global {', '.join(node.names)} inside a traced "
                       "function: assigning leaks the tracer across traces")
        self.generic_visit(node)

    def visit_Nonlocal(self, node: ast.Nonlocal):
        if self._in_traced():
            self._emit("MX003", node,
                       f"nonlocal {', '.join(node.names)} inside a traced "
                       "function: assigning leaks the tracer across traces")
        self.generic_visit(node)

    def _check_mx003_store(self, targets: List[ast.AST], node: ast.AST):
        scope = self._traced_scope()
        if scope is None:
            return
        for t in targets:
            base = t
            while isinstance(base, ast.Subscript):
                base = base.value
            if isinstance(base, ast.Attribute):
                root = base
                while isinstance(root, ast.Attribute):
                    root = root.value
                rootname = root.id if isinstance(root, ast.Name) else None
                if rootname == "self" or (rootname and
                                          rootname not in scope.locals):
                    self._emit("MX003", t,
                               f"storing onto {_dotted(base) or 'object'} "
                               "from inside a traced function leaks the "
                               "tracer past its trace")
            elif isinstance(base, ast.Name) and t is not base:
                # container[...] = x  on a free (closure/global) name
                if base.id not in scope.locals:
                    self._emit("MX003", t,
                               f"writing into free variable {base.id!r} "
                               "from inside a traced function leaks the "
                               "tracer")

    def _check_mx003_call(self, node: ast.Call):
        scope = self._traced_scope()
        if scope is None or not isinstance(node.func, ast.Attribute):
            return
        if node.func.attr not in _MX003_MUTATORS or not node.args:
            return
        recv = node.func.value
        if isinstance(recv, ast.Name) and recv.id not in scope.locals:
            self._emit("MX003", node,
                       f"mutating free container {recv.id!r} "
                       f"(.{node.func.attr}) from inside a traced function "
                       "leaks the tracer")
        else:
            attr = _self_attr(recv)
            if attr is not None:
                self._emit("MX003", node,
                           f"mutating self.{attr} (.{node.func.attr}) from "
                           "inside a traced function leaks the tracer")

    # ----------------------------------------------------------- MX004
    def _check_mx004(self, node: ast.Call):
        info = self._class_info()
        if info is None or not self.scopes:
            return
        name = _callee(node)
        if name:
            last = name.rsplit(".", 1)[-1]
            if name in _MX004_SAFE_BUILTINS:
                return
            mod = name.rpartition(".")[0]
            if mod.rsplit(".", 1)[-1] in _NUMPY_MODULES or \
                    mod in ("onp.testing", "np.testing"):
                return
            if isinstance(node.func, ast.Attribute) and \
                    last in _MX004_SAFE_ATTRS:
                return
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            inner = arg
            if isinstance(inner, ast.Starred):
                inner = inner.value
            base = inner
            while isinstance(base, ast.Subscript):
                base = base.value
            attr = _self_attr(base)
            if attr is None:
                continue
            if attr in info.host_buffers and attr in info.mutated:
                self._emit(
                    "MX004", arg,
                    f"self.{attr} (mutable host numpy buffer) passed into "
                    f"a dispatch without .copy(): jit argument conversion "
                    "can zero-copy-alias it, and this class mutates it — "
                    "snapshot at dispatch or seal with the alias sentinel")

    # ----------------------------------------------------------- MX005
    def _check_mx005_call(self, node: ast.Call):
        if not self.locks:
            return
        held = [t for t, _ in self.locks]
        reason = _direct_blocking(node, held)
        if reason:
            self._emit("MX005", node,
                       f"{reason} while holding lock "
                       f"{held[-1]} — move the blocking work outside the "
                       "critical section")
            return
        # one-level inlining: self.method() that itself blocks
        info = self._class_info()
        if info is not None and isinstance(node.func, ast.Attribute):
            attr = _self_attr(node.func)
            if attr and attr in info.blocking_methods:
                self._emit("MX005", node,
                           f"self.{attr}() performs blocking work (I/O or "
                           f"sync) and is called while holding lock "
                           f"{held[-1]} — move it outside the critical "
                           "section")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def lint_source(source: str, path: str = "<string>",
                select: Optional[Iterable[str]] = None
                ) -> Tuple[List[Finding],
                           List[Tuple[str, str, int, int, str]]]:
    """Lint one source text. Returns (findings, lock-acquisition edges);
    the edges feed the cross-file order graph in :func:`lint_paths`."""
    per_line, skip = _suppressions(source)
    if skip:
        return [], []
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(rule="MX000", path=path, line=e.lineno or 0,
                        col=e.offset or 0,
                        message=f"syntax error: {e.msg}")], []
    idx = _build_index(tree)
    visitor = _RuleVisitor(path, source, idx)
    visitor.visit(tree)
    wanted = set(select) if select else None
    if "pallas_call" in source and (wanted is None
                                    or wanted & _KERNEL_RULES):
        kmod = _kernel_analyzer()
        rep = kmod.analyze_source(source, path=path)
        for kf in rep.findings:
            visitor.findings.append(Finding(
                rule=kf["rule"], path=path, line=kf["line"],
                col=kf["col"], message=kf["message"],
                context=kf["context"], snippet=kf["snippet"]))
    out = []
    for f in visitor.findings:
        if wanted is not None and f.rule not in wanted:
            continue
        if f.rule in per_line.get(f.line, ()):
            continue
        out.append(f)
    # an MX005 suppression at an acquisition site also removes that edge
    # from the cross-file order graph (the justification covers the
    # nesting recorded there)
    edges = [(path, a, b, line, col, ctx)
             for a, b, line, col, ctx in visitor.lock_edges
             if "MX005" not in per_line.get(line, ())]
    return out, edges


def lint_file(path: str, select: Optional[Iterable[str]] = None):
    with open(path, encoding="utf-8") as f:
        source = f.read()
    return lint_source(source, path, select)


def find_cycles(pairs: Iterable[Tuple[str, str]]) -> List[List[str]]:
    """Cycles in a directed graph given as (a, b) edge pairs. Shared by
    the static MX005 order check and the runtime LockOrderWitness
    (guards.py imports this — keep it pure stdlib)."""
    graph: Dict[str, Set[str]] = {}
    for a, b in pairs:
        graph.setdefault(a, set()).add(b)
        graph.setdefault(b, set())
    cycles: List[List[str]] = []
    seen_cycles: Set[Tuple[str, ...]] = set()
    color: Dict[str, int] = {}
    stack: List[str] = []

    def dfs(u: str):
        color[u] = 1
        stack.append(u)
        for v in graph[u]:
            if color.get(v, 0) == 0:
                dfs(v)
            elif color.get(v) == 1:
                cyc = stack[stack.index(v):] + [v]
                key = tuple(sorted(cyc))
                if key not in seen_cycles:
                    seen_cycles.add(key)
                    cycles.append(cyc)
        stack.pop()
        color[u] = 2

    for node in list(graph):
        if color.get(node, 0) == 0:
            dfs(node)
    return cycles


def lint_paths(paths: Sequence[str],
               select: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint files/directories; adds cross-file MX005 lock-order-cycle
    findings on top of per-file rule findings."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                for n in sorted(names):
                    if n.endswith(".py"):
                        files.append(os.path.join(root, n))
        elif os.path.isfile(p) and p.endswith(".py"):
            files.append(p)
        else:
            # a typo'd path must not turn the gate silently green
            raise FileNotFoundError(
                f"mxlint: no such file or directory (or not .py): {p}")
    findings: List[Finding] = []
    all_edges = []
    for fp in files:
        f, edges = lint_file(fp, select)
        findings.extend(f)
        all_edges.extend(edges)
    wanted = set(select) if select else None
    if wanted is None or "MX005" in wanted:
        cycles = find_cycles((a, b) for _p, a, b, _l, _c, _x in all_edges)
        for cyc in cycles:
            participants = set(cyc)
            sites = [(path, a, b, line, col, ctx)
                     for path, a, b, line, col, ctx in all_edges
                     if a in participants and b in participants]
            for path, a, b, line, col, ctx in sites:
                findings.append(Finding(
                    rule="MX005", path=path, line=line, col=col,
                    message=("inconsistent lock order: acquiring "
                             f"{b} after {a} participates in cycle "
                             f"{' -> '.join(cyc)}"),
                    # the edge names the finding content-wise: distinct
                    # edges in one function baseline independently
                    context=ctx, snippet=f"{a} -> {b}"))
    return findings
