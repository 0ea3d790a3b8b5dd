"""mxkernlint: static verification of the Pallas kernel family.

Hand-written Pallas kernels (today: the int4/int8 weight GEMVs, the
fused LM-head sampler, flash attention; the rules were written for
kernels that also issue their own DMAs and launch behind a ``fusable*``
VMEM gate) carry three invariant classes that no CPU interpret-mode
parity test can see: an async copy that is started but never waited
corrupts VMEM on real hardware only; a double-buffer scratch slot
re-started before its in-flight gather lands is a data race that
interpret mode serialises away; and the ``fusable*`` runtime gates
promise XLA a VMEM footprint that nothing checks against what the
kernel actually allocates — gate drift surfaces as VMEM OOM (gate too
small) or as silently refusing the fast path (gate too large).

This module analyses kernel *source* with a pure-stdlib AST dataflow
machine (no jax import — ``tools/mxlint.py`` loads it standalone):

- **MX101 DMA lifecycle** — every ``pltpu.make_async_copy(...).start()``
  must be covered by a ``.wait()`` on the same (dst, semaphore) pair
  whose guard conditions are a prefix of the start's
  (``lax.fori_loop`` / ``lax.cond`` / ``pl.when`` bodies are walked as
  one-level inlined regions); no copy may be re-started into the same
  scratch slot without an intervening wait; and rotating-slot starts
  inside a loop (``slot = i % depth``) must be provably safe: either a
  same-key wait with the same modulus rotates through every slot in the
  same loop, or the loop's trip count provably never exceeds the slot
  count (the warm-up pattern ``range(min(depth - 1, nt))``).
- **MX102 memory-space discipline** — an HBM-resident ref
  (``pl.BlockSpec(memory_space=pl.ANY)``) may only feed async copies
  (``ref.at[...]`` inside ``make_async_copy``) or be ``del``-ed; any
  direct load/store or compute use reads HBM from inside the kernel.
- **MX103 static VMEM budget** — each kernel's VMEM-resident footprint
  (scratch + blocks) is summed symbolically and cross-checked against
  the ``fusable*`` gate expression that guards the kernel's launch.

Footprint convention (matches the shipped gates' own arithmetic):
``pltpu.VMEM`` scratch is counted exactly (shape x dtype itemsize;
semaphores and SMEM excluded); rank>=3 VMEM in/out blocks are counted
exactly with the operand's dtype itemsize (cache/pool residency);
streamed rank-2 input blocks whose leading dim is not 1 (the weight
stream — the index_map moves with the grid) contribute the *max* of
their element counts at one byte per element, mirroring the gates'
``bn * max(D, 4 * D)`` term; pinned rank-2 blocks, single-lane rows and
rank-2 outputs are glue and excluded.

Symbolic terms are compared by *deterministic numeric probing*: both
sides are expression trees over leaves like ``xv.shape[2]`` or
``itemsize[kp.dtype]``; leaves get reproducible hash-seeded sample
values (all ``itemsize[...]`` leaves share one value per sample, depth
leaves sample >=2) and the sides must agree on every sample.  Two
access-pattern witnesses unify gate parameters with kernel block dims:
a full-extent slice ``pl.ds(0, X)`` on an opaque block axis assumes the
axis is ``X``, and a modular index ``pl.ds(i % m, 1)`` assumes the axis
is ``m`` (both are assumptions, documented here, not proofs).

Findings flow through mxlint's fingerprint baseline and inline
``# mxlint: disable=MXnnn -- why`` suppressions (see ``linter.py``);
analysis *notes* (constructs the walker could not model) are reported
separately so exotic-but-correct code degrades loudly, not silently.
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
from collections import ChainMap
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

RULES = {
    "MX101": "DMA lifecycle (unwaited / slot-reuse-before-wait copy)",
    "MX102": "memory-space discipline (direct use of an ANY/HBM ref)",
    "MX103": "VMEM footprint disagrees with the runtime fusable gate",
}

_MAX_DEPTH = 14
_N_SAMPLES = 5
_ITEMSIZE_SAMPLES = (4, 2, 4, 2, 4)
_DTYPE_SIZES = {
    "float32": 4, "int32": 4, "uint32": 4, "float64": 8, "int64": 8,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
}
_OPSYM = {"add": "+", "sub": "-", "mul": "*", "floordiv": "//",
          "mod": "%", "pow": "**"}


# ---------------------------------------------------------------------------
# value model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Func:
    name: str
    node: ast.AST          # FunctionDef or Lambda
    env: ChainMap


@dataclasses.dataclass
class _BlockSpecV:
    shape: Any             # value tree tuple, or None (space-only spec)
    index: Any             # _Func / None
    space: str             # "vmem" | "smem" | "any"


@dataclasses.dataclass
class _ScratchV:
    space: str             # "vmem" | "smem" | "sema"
    shape: Any
    dtype: Any


@dataclasses.dataclass
class _ShapeStructV:
    shape: Any
    dtype: Any


@dataclasses.dataclass
class _CopyV:
    src: Any
    dst: Any
    sem: Any
    line: int


@dataclasses.dataclass
class _WhenV:
    cond: Any


@dataclasses.dataclass
class _RefV:
    name: str
    role: str              # "in" | "out" | "scratch"
    space: str
    block: Any             # tuple of value trees, or None
    dtype: Any


@dataclasses.dataclass
class _Loop:
    uid: int
    var: str               # canonical loop-var atom string
    trip: Any              # value tree, or None


@dataclasses.dataclass
class _Event:
    kind: str              # "start" | "wait"
    key: Tuple[str, str]   # (dst base canon, sem base canon)
    slot: Any              # value tree or None
    dst_index: Any         # full dst index tuple (value trees) or None
    regions: Tuple         # snapshot of ("when", cond) / ("loop", _Loop)
    seq: int
    line: int
    desc: str


@dataclasses.dataclass
class _PallasCallable:
    kernel: Any
    kwargs: Dict[str, Any]
    line: int


@dataclasses.dataclass
class _KernelSite:
    wrapper: str
    kernel: Optional[_Func]
    in_specs: List[List[_BlockSpecV]]   # one or more branches
    out_specs: List[_BlockSpecV]
    out_shape: List[Any]
    scratch: List[Any]
    operands: List[Any]
    line: int
    gate: Optional[Tuple[str, List[Any]]] = None
    param_map: Dict[str, _RefV] = dataclasses.field(default_factory=dict)
    events: List[_Event] = dataclasses.field(default_factory=list)
    witness: Dict[str, Any] = dataclasses.field(default_factory=dict)
    walk_ok: bool = True


@dataclasses.dataclass
class GatePair:
    gate: str
    wrapper: str
    agree: bool
    detail: str = ""


@dataclasses.dataclass
class KernelReport:
    path: str
    kernels: List[_KernelSite]
    pairs: List[GatePair]
    findings: List[Dict[str, Any]]
    notes: List[str]

    def to_dict(self) -> Dict[str, Any]:
        return {
            "path": self.path,
            "kernels": [{"wrapper": s.wrapper,
                         "kernel": s.kernel.name if s.kernel else None,
                         "line": s.line,
                         "gate": s.gate[0] if s.gate else None}
                        for s in self.kernels],
            "pairs": [dataclasses.asdict(p) for p in self.pairs],
            "findings": list(self.findings),
            "notes": list(self.notes),
        }


def _atom(s: str):
    return ("atom", s)


def _is_tag(v, tag: str) -> bool:
    return isinstance(v, tuple) and len(v) > 0 and v[0] == tag


def _canon(v) -> str:
    if v is None or isinstance(v, bool):
        return repr(v)
    if isinstance(v, (int, float)):
        return repr(v)
    if isinstance(v, str):
        return repr(v)
    if isinstance(v, _RefV):
        return v.name
    if isinstance(v, _Func):
        return f"<fn {v.name}>"
    if isinstance(v, (_BlockSpecV, _ScratchV, _ShapeStructV, _CopyV,
                      _WhenV, _PallasCallable, _Loop)):
        return f"<{type(v).__name__}>"
    if not isinstance(v, tuple):
        return repr(v)
    tag = v[0]
    if tag == "atom":
        return v[1]
    if tag in _OPSYM:
        return f"({_canon(v[1])}{_OPSYM[tag]}{_canon(v[2])})"
    if tag == "neg":
        return f"(-{_canon(v[1])})"
    if tag in ("min", "max"):
        return f"{tag}({', '.join(_canon(x) for x in v[1])})"
    if tag == "attr":
        return f"{_canon(v[1])}.{v[2]}"
    if tag == "dtype":
        return v[1]
    if tag == "dtypeof":
        return f"dtype({_canon(v[1])})"
    if tag == "ds":
        return f"ds({_canon(v[1])},{_canon(v[2])})"
    if tag == "tuple":
        return "(" + ", ".join(_canon(x) for x in v[1]) + ")"
    if tag == "list":
        return "[" + ", ".join(_canon(x) for x in v[1]) + "]"
    if tag == "cmp":
        return v[1]
    if tag == "callv":
        inner = ", ".join(_canon(x) for x in v[2])
        s = f"{v[1]}({inner})"
        if len(s) > 160:
            s = s[:140] + "~" + hashlib.sha1(s.encode()).hexdigest()[:8]
        return s
    if tag == "branches":
        return "|".join(_canon(x) for x in v[1])
    if tag == "refat":
        return f"{_canon(v[1])}.at[{_canon(v[2])}]"
    if tag in ("space", "range", "slice", "ellipsis", "deleted"):
        return f"<{tag}:{','.join(_canon(x) for x in v[1:])}>"
    return f"<{tag}>"


def _bin(op: str, a, b):
    num = (int, float)
    if isinstance(a, bool):
        a = int(a)
    if isinstance(b, bool):
        b = int(b)
    if isinstance(a, num) and isinstance(b, num):
        try:
            if op == "add":
                return a + b
            if op == "sub":
                return a - b
            if op == "mul":
                return a * b
            if op == "floordiv" and b != 0:
                return a // b
            if op == "mod" and b != 0:
                return a % b
            if op == "pow":
                return a ** b
        except Exception:
            pass
    return (op, a, b)


def _is_seq(v) -> bool:
    return _is_tag(v, "list") or _is_tag(v, "tuple")


def _concat(a, b):
    """List concatenation over value trees; distributes over branches
    (the int4/int8 ``_weight_specs`` fork inside an in_specs sum)."""
    if _is_tag(a, "branches"):
        return ("branches", tuple(_concat(x, b) for x in a[1]))
    if _is_tag(b, "branches"):
        return ("branches", tuple(_concat(a, x) for x in b[1]))
    if _is_seq(a) and _is_seq(b):
        return ("list", a[1] + b[1])
    return ("add", a, b)


def _refs_atom(v, name: str) -> bool:
    """True if the value tree contains the atom leaf ``name``."""
    if _is_tag(v, "atom"):
        return v[1] == name
    if isinstance(v, tuple):
        return any(_refs_atom(x, name) for x in v
                   if isinstance(x, (tuple, list)))
    if isinstance(v, list):
        return any(_refs_atom(x, name) for x in v)
    return False


# ---------------------------------------------------------------------------
# deterministic numeric probing
# ---------------------------------------------------------------------------

def _leafval(leaf: str, k: int) -> int:
    h = int(hashlib.sha1(f"{leaf}|{k}".encode()).hexdigest()[:8], 16)
    if leaf.startswith("itemsize["):
        return _ITEMSIZE_SAMPLES[k % len(_ITEMSIZE_SAMPLES)]
    if "_dma_depth" in leaf or leaf == "depth" or leaf.endswith(".depth"):
        return 2 + h % 3
    return 3 + h % 17


def _nume(v, k: int, defs: Dict[str, Any], stack: Tuple[str, ...] = ()):
    """Numeric evaluation of a value tree under sample ``k``."""
    if isinstance(v, bool):
        return int(v)
    if isinstance(v, (int, float)):
        return v
    if _is_tag(v, "atom"):
        s = v[1]
        if s in defs and s not in stack:
            return _nume(defs[s], k, defs, stack + (s,))
        return _leafval(s, k)
    if isinstance(v, tuple) and v and v[0] in _OPSYM:
        a = _nume(v[1], k, defs, stack)
        b = _nume(v[2], k, defs, stack)
        op = v[0]
        if op == "add":
            return a + b
        if op == "sub":
            return a - b
        if op == "mul":
            return a * b
        if op == "floordiv":
            return a // b if b else a
        if op == "mod":
            return a % b if b else 0
        return a ** min(b, 8)
    if _is_tag(v, "neg"):
        return -_nume(v[1], k, defs, stack)
    if _is_tag(v, "min"):
        return min(_nume(x, k, defs, stack) for x in v[1])
    if _is_tag(v, "max"):
        return max(_nume(x, k, defs, stack) for x in v[1])
    return _leafval(_canon(v), k)


def _forall_samples(pred) -> bool:
    return all(pred(k) for k in range(_N_SAMPLES))


# ---------------------------------------------------------------------------
# the abstract machine
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


_TRANSPARENT_CALLS = set(_DTYPE_SIZES) | {"asarray", "int", "array"}


class _WalkError(Exception):
    pass


class _Machine:
    def __init__(self, tree: ast.Module, path: str, source: str):
        self.tree = tree
        self.path = path
        self.source = source
        self.notes: List[str] = []
        self.sites: List[_KernelSite] = []
        self.funcs: Dict[str, ast.FunctionDef] = {}
        self.module_env: ChainMap = ChainMap({})
        self.uid = 0
        # kernel-walk state
        self.events: Optional[List[_Event]] = None
        self.witness: Dict[str, Any] = {}
        self.regions: List[Tuple] = []
        self.seq = 0
        self.shape_ranks: Dict[str, int] = {}
        # pairing state
        self.gate_names: Set[str] = set()
        self.wrapper_names: Set[str] = set()
        self.last_gate: Optional[Tuple[str, List[Any]]] = None
        self.gate_stack: List[Optional[Tuple[str, List[Any]]]] = []
        self.usevar_gate: Dict[str, Tuple[str, List[Any]]] = {}
        self.fn_stack: List[str] = []

    def _uid(self) -> int:
        self.uid += 1
        return self.uid

    def note(self, msg: str):
        if msg not in self.notes:
            self.notes.append(msg)

    # -- module classification / driver ------------------------------------

    def run(self):
        for stmt in self.tree.body:
            if isinstance(stmt, ast.FunctionDef):
                self.funcs[stmt.name] = stmt
                self.module_env[stmt.name] = _Func(stmt.name, stmt,
                                                  self.module_env)
            elif isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                try:
                    self.module_env[stmt.targets[0].id] = self.eval(
                        stmt.value, self.module_env, 0)
                except Exception:
                    pass
        for name, node in self.funcs.items():
            if self._is_gate(node):
                self.gate_names.add(name)
            elif any(isinstance(n, ast.Attribute) and
                     n.attr == "pallas_call" for n in ast.walk(node)):
                self.wrapper_names.add(name)
        routers = []
        for name, node in self.funcs.items():
            if name in self.gate_names or name in self.wrapper_names:
                continue
            names = {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}
            if names & self.gate_names and names & self.wrapper_names:
                routers.append(name)
        for name in sorted(routers, key=lambda n: self.funcs[n].lineno):
            self._exec_top(name)
        done = {s.wrapper for s in self.sites}
        for name in sorted(self.wrapper_names - done,
                           key=lambda n: self.funcs[n].lineno):
            self._exec_top(name)
        n_calls = sum(1 for n in ast.walk(self.tree)
                      if isinstance(n, ast.Attribute)
                      and n.attr == "pallas_call")
        if n_calls != len(self.sites):
            self.note(f"{self.path}: {n_calls} pallas_call site(s) in "
                      f"source but {len(self.sites)} analyzed — some "
                      "kernels were not reached by the dataflow walk")

    @staticmethod
    def _is_gate(node: ast.FunctionDef) -> bool:
        rets = [s for s in node.body if isinstance(s, ast.Return)]
        if not rets or not isinstance(rets[-1].value, ast.Compare):
            return False
        cmp = rets[-1].value
        if len(cmp.ops) != 1 or not isinstance(cmp.ops[0],
                                               (ast.LtE, ast.Lt)):
            return False
        return any(isinstance(n, ast.Call)
                   for n in ast.walk(cmp.comparators[0]))

    def _exec_top(self, name: str):
        node = self.funcs[name]
        fv = self.module_env[name]
        args = [_atom(a.arg) for a in node.args.posonlyargs + node.args.args]
        try:
            self._call(fv, args, {}, 1)
        except _WalkError:
            raise
        except RecursionError:
            self.note(f"{name}: analysis recursion limit")
        except Exception as e:  # degrade loudly, never crash the linter
            self.note(f"{name}: analysis failed: {type(e).__name__}: {e}")

    # -- statement execution -----------------------------------------------

    def _call(self, fv, args: List[Any], kwargs: Dict[str, Any],
              depth: int):
        if depth > _MAX_DEPTH:
            self.note(f"inline depth limit in {getattr(fv, 'name', '?')}")
            return _atom(f"deep:{getattr(fv, 'name', '?')}")
        node = fv.node
        env = fv.env.new_child({})
        if isinstance(node, ast.Lambda):
            self._bind(node.args, args, kwargs, env, depth)
            return self.eval(node.body, env, depth)
        self._bind(node.args, args, kwargs, env, depth)
        is_wrapper = fv.name in self.wrapper_names
        if is_wrapper:
            self.fn_stack.append(fv.name)
        try:
            frame = {"returns": [], "done": False, "base": len(self.regions)}
            self._exec(node.body, env, depth, frame)
            rets = frame["returns"]
        finally:
            if is_wrapper:
                self.fn_stack.pop()
        if not rets:
            return None
        if len(rets) == 1:
            return rets[0]
        return ("branches", tuple(rets))

    def _bind(self, a: ast.arguments, args, kwargs, env, depth):
        params = [p.arg for p in a.posonlyargs + a.args]
        defaults = list(a.defaults)
        for i, p in enumerate(params):
            if i < len(args):
                env[p] = args[i]
            elif p in kwargs:
                env[p] = kwargs.pop(p)
            else:
                di = i - (len(params) - len(defaults))
                if 0 <= di < len(defaults):
                    env[p] = self.eval(defaults[di], env, depth)
                else:
                    env[p] = _atom(p)
        if a.vararg:
            env[a.vararg.arg] = ("tuple", tuple(args[len(params):]))
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if p.arg in kwargs:
                env[p.arg] = kwargs.pop(p.arg)
            elif d is not None:
                env[p.arg] = self.eval(d, env, depth)
            else:
                env[p.arg] = _atom(p.arg)
        if a.kwarg and kwargs:
            env[a.kwarg.arg] = _atom(a.kwarg.arg)

    def _exec(self, stmts: Sequence[ast.stmt], env, depth, frame):
        for stmt in stmts:
            if frame["done"]:
                return
            self._stmt(stmt, env, depth, frame)

    def _stmt(self, stmt, env, depth, frame):
        if isinstance(stmt, ast.FunctionDef):
            whens = []
            for dec in stmt.decorator_list:
                try:
                    dv = self.eval(dec, env, depth)
                except Exception:
                    dv = None
                if isinstance(dv, _WhenV):
                    whens.append(dv)
            fv = _Func(stmt.name, stmt, env)
            if whens:
                for w in whens:
                    self.regions.append(("when", _canon(w.cond)))
                try:
                    self._call(fv, [], {}, depth + 1)
                finally:
                    for _ in whens:
                        self.regions.pop()
            else:
                env[stmt.name] = fv
        elif isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            self._assign(stmt, env, depth)
        elif isinstance(stmt, ast.Return):
            val = (self.eval(stmt.value, env, depth)
                   if stmt.value is not None else None)
            frame["returns"].append(val)
            if len(self.regions) <= frame["base"]:
                frame["done"] = True
        elif isinstance(stmt, ast.Expr):
            self.eval(stmt.value, env, depth)
        elif isinstance(stmt, ast.If):
            self.last_gate = None
            test = self.eval(stmt.test, env, depth)
            gate = self.last_gate or self._gate_from_test(stmt.test)
            self.last_gate = None
            if test is True:
                self._exec(stmt.body, env, depth, frame)
            elif test is False:
                self._exec(stmt.orelse, env, depth, frame)
            else:
                cond = _canon(test)
                self.gate_stack.append(gate)
                self.regions.append(("when", cond + "#t"))
                try:
                    self._exec(stmt.body, env, depth, frame)
                finally:
                    self.regions.pop()
                self.regions.append(("when", cond + "#f"))
                try:
                    self._exec(stmt.orelse, env, depth, frame)
                finally:
                    self.regions.pop()
                    self.gate_stack.pop()
        elif isinstance(stmt, ast.For):
            self._for(stmt, env, depth, frame)
        elif isinstance(stmt, ast.While):
            loop = _Loop(self._uid(), f"while@{stmt.lineno}", None)
            self.regions.append(("loop", loop))
            try:
                self._exec(stmt.body, env, depth, frame)
            finally:
                self.regions.pop()
        elif isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    env[t.id] = ("deleted",)
        elif isinstance(stmt, ast.With):
            self._exec(stmt.body, env, depth, frame)
        elif isinstance(stmt, ast.Try):
            self._exec(stmt.body, env, depth, frame)
        # Pass / Import / Assert / Raise / Global / Nonlocal: no-ops here

    def _gate_from_test(self, test: ast.AST):
        for n in ast.walk(test):
            if isinstance(n, ast.Name) and n.id in self.usevar_gate:
                return self.usevar_gate[n.id]
        return None

    def _assign(self, stmt, env, depth):
        if isinstance(stmt, ast.AugAssign):
            if isinstance(stmt.target, ast.Name):
                old = env.get(stmt.target.id, _atom(stmt.target.id))
                val = self.eval(stmt.value, env, depth)
                op = self._binop_name(stmt.op)
                env[stmt.target.id] = (_bin(op, old, val) if op
                                       else _atom(stmt.target.id))
            return
        value = stmt.value
        if value is None:
            return
        self.last_gate = None
        val = self.eval(value, env, depth)
        gate = self.last_gate
        self.last_gate = None
        targets = (stmt.targets if isinstance(stmt, ast.Assign)
                   else [stmt.target])
        for t in targets:
            self._bind_target(t, val, env, depth)
            if gate and isinstance(t, ast.Name):
                self.usevar_gate[t.id] = gate

    def _bind_target(self, t, val, env, depth):
        if isinstance(t, ast.Name):
            env[t.id] = val
        elif isinstance(t, (ast.Tuple, ast.List)):
            parts = self._unpack(val, len(t.elts))
            for sub, pv in zip(t.elts, parts):
                self._bind_target(sub, pv, env, depth)
        elif isinstance(t, ast.Subscript):
            base = self.eval(t.value, env, depth)
            if isinstance(base, _RefV):
                idx = self.eval(t.slice, env, depth)
                self._access(base, idx, "store", t.lineno)
        # Attribute targets: ignored

    def _unpack(self, val, n: int) -> List[Any]:
        if _is_tag(val, "tuple") or _is_tag(val, "list"):
            items = list(val[1])
            if len(items) == n:
                return items
        if _is_tag(val, "attr") and val[2] == "shape":
            self.shape_ranks[_canon(val)] = n
            return [_atom(f"{_canon(val)}[{i}]") for i in range(n)]
        if _is_tag(val, "branches"):
            for b in val[1]:
                got = self._unpack(b, n)
                if all(not _is_tag(x, "opaque") for x in got):
                    return got
        c = _canon(val)
        return [_atom(f"{c}[{i}]") for i in range(n)]

    @staticmethod
    def _binop_name(op) -> Optional[str]:
        return {ast.Add: "add", ast.Sub: "sub", ast.Mult: "mul",
                ast.FloorDiv: "floordiv", ast.Mod: "mod",
                ast.Pow: "pow"}.get(type(op))

    def _for(self, stmt: ast.For, env, depth, frame):
        it = self.eval(stmt.iter, env, depth)
        trip = None
        if _is_tag(it, "range"):
            rargs = it[1]
            if len(rargs) == 1:
                trip = rargs[0]
            elif len(rargs) >= 2:
                trip = _bin("sub", rargs[1], rargs[0])
        var = None
        if isinstance(stmt.target, ast.Name):
            # line-keyed, not uid-keyed: re-inlining the same helper must
            # yield identical leaves so gate and kernel sample alike
            var = f"{stmt.target.id}@L{stmt.lineno}"
            env[stmt.target.id] = _atom(var)
        loop = _Loop(self._uid(), var or f"for@{stmt.lineno}", trip)
        self.regions.append(("loop", loop))
        try:
            self._exec(stmt.body, env, depth, frame)
        finally:
            self.regions.pop()

    # -- expression evaluation ---------------------------------------------

    def eval(self, node, env, depth):
        if node is None:
            return None
        if isinstance(node, ast.Constant):
            return node.value if not isinstance(node.value, type(...)) \
                else ("ellipsis",)
        if isinstance(node, ast.Name):
            for m in (env, self.module_env):
                try:
                    return m[node.id]
                except KeyError:
                    continue
            return _atom(node.id)
        if isinstance(node, ast.Attribute):
            return self._attr(node, env, depth)
        if isinstance(node, ast.Subscript):
            return self._subscript(node, env, depth)
        if isinstance(node, ast.BinOp):
            op = self._binop_name(node.op)
            a = self.eval(node.left, env, depth)
            b = self.eval(node.right, env, depth)
            if op == "add" and (_is_seq(a) or _is_seq(b)
                                or _is_tag(a, "branches")
                                or _is_tag(b, "branches")):
                return _concat(a, b)
            if op == "mul" and _is_seq(a) and isinstance(b, int):
                return (a[0], a[1] * b)
            if op:
                return _bin(op, a, b)
            return _atom(f"({_canon(a)}?{_canon(b)})")
        if isinstance(node, ast.UnaryOp):
            v = self.eval(node.operand, env, depth)
            if isinstance(node.op, ast.USub):
                if isinstance(v, (int, float)):
                    return -v
                return ("neg", v)
            if isinstance(node.op, ast.Not):
                if isinstance(v, bool):
                    return not v
                if v is None:
                    return True
                return ("cmp", f"not {_canon(v)}")
            return v
        if isinstance(node, ast.Compare):
            return self._compare(node, env, depth)
        if isinstance(node, ast.BoolOp):
            vals = [self.eval(v, env, depth) for v in node.values]
            if all(isinstance(v, bool) for v in vals):
                return (all(vals) if isinstance(node.op, ast.And)
                        else any(vals))
            if isinstance(node.op, ast.And) and any(v is False for v in vals):
                return False
            if isinstance(node.op, ast.Or) and any(v is True for v in vals):
                return True
            j = " and " if isinstance(node.op, ast.And) else " or "
            return ("cmp", j.join(_canon(v) for v in vals))
        if isinstance(node, ast.IfExp):
            test = self.eval(node.test, env, depth)
            if test is True:
                return self.eval(node.body, env, depth)
            if test is False:
                return self.eval(node.orelse, env, depth)
            a = self.eval(node.body, env, depth)
            b = self.eval(node.orelse, env, depth)
            s = f"({_canon(a)} if {_canon(test)} else {_canon(b)})"
            if len(s) > 120:
                s = s[:100] + "~" + hashlib.sha1(s.encode()).hexdigest()[:8]
            return _atom(s)
        if isinstance(node, (ast.Tuple, ast.List)):
            tag = "tuple" if isinstance(node, ast.Tuple) else "list"
            return (tag, tuple(self.eval(e, env, depth) for e in node.elts))
        if isinstance(node, ast.Call):
            return self._callnode(node, env, depth)
        if isinstance(node, ast.Lambda):
            return _Func(f"<lambda:{node.lineno}>", node, env)
        if isinstance(node, ast.Slice):
            return ("slice",
                    self.eval(node.lower, env, depth),
                    self.eval(node.upper, env, depth),
                    self.eval(node.step, env, depth))
        if isinstance(node, ast.Dict):
            return _atom(f"dict@{node.lineno}:{node.col_offset}")
        if isinstance(node, ast.Starred):
            return self.eval(node.value, env, depth)
        try:
            return _atom(ast.unparse(node)[:80])
        except Exception:
            return _atom(f"<expr@{getattr(node, 'lineno', 0)}>")

    def _compare(self, node: ast.Compare, env, depth):
        left = self.eval(node.left, env, depth)
        rights = [self.eval(c, env, depth) for c in node.comparators]
        if len(node.ops) == 1:
            op, r = node.ops[0], rights[0]
            lv, rv = left, r
            concrete = ((lv is None or isinstance(lv, (int, float, str,
                                                       bool))) and
                        (rv is None or isinstance(rv, (int, float, str,
                                                       bool))))
            if concrete:
                try:
                    if isinstance(op, ast.Is):
                        return lv is rv
                    if isinstance(op, ast.IsNot):
                        return lv is not rv
                    if isinstance(op, ast.Eq):
                        return lv == rv
                    if isinstance(op, ast.NotEq):
                        return lv != rv
                    if isinstance(op, ast.Lt):
                        return lv < rv
                    if isinstance(op, ast.LtE):
                        return lv <= rv
                    if isinstance(op, ast.Gt):
                        return lv > rv
                    if isinstance(op, ast.GtE):
                        return lv >= rv
                except TypeError:
                    pass
        sym = {ast.Eq: "==", ast.NotEq: "!=", ast.Lt: "<", ast.LtE: "<=",
               ast.Gt: ">", ast.GtE: ">=", ast.Is: "is",
               ast.IsNot: "is not", ast.In: "in", ast.NotIn: "not in"}
        parts = [_canon(left)]
        for op, r in zip(node.ops, rights):
            parts.append(sym.get(type(op), "?"))
            parts.append(_canon(r))
        return ("cmp", " ".join(parts))

    def _attr(self, node: ast.Attribute, env, depth):
        base = self.eval(node.value, env, depth)
        attr = node.attr
        if isinstance(base, _RefV):
            if attr == "at":
                return ("refat0", base)
            if attr == "shape" and base.block is not None:
                return ("tuple", tuple(base.block))
            if attr == "dtype":
                return base.dtype if base.dtype is not None \
                    else ("attr", _atom(base.name), "dtype")
        if attr in ("ANY", "SMEM", "VMEM") and _is_tag(base, "atom"):
            return ("space", attr.lower())
        if attr == "itemsize":
            return _itemsize_of(base)
        if attr in _DTYPE_SIZES and _is_tag(base, "atom") \
                and base[1] in ("jnp", "np", "jax", "numpy"):
            return ("dtype", attr)
        return ("attr", base, attr)

    def _subscript(self, node: ast.Subscript, env, depth):
        base = self.eval(node.value, env, depth)
        sl = self.eval(node.slice, env, depth)
        if isinstance(base, _RefV):
            self._access(base, sl, "load", node.lineno)
            return _atom(f"{base.name}[{_canon(sl)}]")
        if _is_tag(base, "refat0"):
            ref = base[1]
            self._access(ref, sl, "dma", node.lineno)
            return ("refat", ref, sl)
        if (_is_tag(base, "tuple") or _is_tag(base, "list")) \
                and isinstance(sl, int):
            items = base[1]
            if -len(items) <= sl < len(items):
                return items[sl]
        if _is_tag(base, "attr") and base[2] == "shape" \
                and isinstance(sl, int):
            return _atom(f"{_canon(base)}[{sl}]")
        return _atom(f"{_canon(base)}[{_canon(sl)}]")

    # -- calls --------------------------------------------------------------

    def _callnode(self, node: ast.Call, env, depth):
        # method-style events first: <copy>.start() / <copy>.wait()
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in ("start", "wait"):
            base = self.eval(node.func.value, env, depth)
            if isinstance(base, _CopyV):
                self._event(node.func.attr, base, node.lineno)
                return None
        args: List[Any] = []
        for a in node.args:
            if isinstance(a, ast.Starred):
                v = self.eval(a.value, env, depth)
                if _is_tag(v, "tuple") or _is_tag(v, "list"):
                    args.extend(v[1])
                else:
                    args.append(v)
            else:
                args.append(self.eval(a, env, depth))
        kwargs: Dict[str, Any] = {}
        for kw in node.keywords:
            if kw.arg is not None:
                kwargs[kw.arg] = self.eval(kw.value, env, depth)
        dotted = _dotted(node.func)
        last = dotted.rsplit(".", 1)[-1] if dotted else None

        if last == "pallas_call":
            kernel = args[0] if args else kwargs.get("kernel")
            return _PallasCallable(kernel, kwargs, node.lineno)
        if last == "BlockSpec":
            shape = args[0] if args else kwargs.get("block_shape")
            index = args[1] if len(args) > 1 else kwargs.get("index_map")
            space = "vmem"
            ms = kwargs.get("memory_space")
            if _is_tag(ms, "space"):
                space = ms[1]
            return _BlockSpecV(shape, index, space)
        if last in ("VMEM", "SMEM") and (dotted or "").find("pltpu") >= 0 \
                or last in ("VMEM", "SMEM") and len(args) == 2:
            return _ScratchV(last.lower(),
                             args[0] if args else kwargs.get("shape"),
                             args[1] if len(args) > 1
                             else kwargs.get("dtype"))
        if last == "DMA" and dotted and "SemaphoreType" in dotted:
            return _ScratchV("sema", args[0] if args else ("tuple", ()),
                             None)
        if last == "make_async_copy":
            a = args + [None] * 3
            return _CopyV(a[0], a[1],
                          kwargs.get("sem", a[2]), node.lineno)
        if last in ("ds", "dslice"):
            return ("ds", args[0], args[1] if len(args) > 1 else None)
        if last == "when":
            return _WhenV(args[0] if args else True)
        if last == "program_id":
            return _atom(f"pl.program_id({_canon(args[0]) if args else ''})")
        if last == "fori_loop":
            return self._fori(args, node, depth)
        if last == "while_loop" and len(args) >= 3:
            loop = _Loop(self._uid(), f"while@{node.lineno}", None)
            body = args[1]
            self.regions.append(("loop", loop))
            try:
                if isinstance(body, _Func):
                    return self._call(body, [args[2]], {}, depth + 1)
            finally:
                self.regions.pop()
            return _atom(f"while@{node.lineno}")
        if last == "cond" and dotted and "lax" in dotted and len(args) >= 3:
            pred = _canon(args[0])
            ops = args[3:]
            for tag, fn in (("#t", args[1]), ("#f", args[2])):
                if isinstance(fn, _Func):
                    self.regions.append(("when", pred + tag))
                    try:
                        self._call(fn, list(ops), {}, depth + 1)
                    finally:
                        self.regions.pop()
            return _atom(f"cond({pred})")
        if last == "scan" and dotted and "lax" in dotted and len(args) >= 2:
            loop = _Loop(self._uid(), f"scan@{node.lineno}", None)
            self.regions.append(("loop", loop))
            try:
                if isinstance(args[0], _Func):
                    var = _atom(f"x@{self._uid()}")
                    carry = self._call(args[0],
                                       [args[1], var], {}, depth + 1)
                else:
                    carry = _atom(f"scan@{node.lineno}")
            finally:
                self.regions.pop()
            return ("tuple", (carry, _atom(f"ys@{node.lineno}")))
        if last in ("rem", "remainder", "mod"):
            return _bin("mod", args[0], args[1])
        if last == "minimum":
            return ("min", tuple(args))
        if last == "maximum":
            return ("max", tuple(args))
        if last == "min" and dotted == "min":
            return args[0] if len(args) == 1 else ("min", tuple(args))
        if last == "max" and dotted == "max":
            return args[0] if len(args) == 1 else ("max", tuple(args))
        if last == "len" and dotted == "len" and args:
            if _is_tag(args[0], "tuple") or _is_tag(args[0], "list"):
                return len(args[0][1])
        if last == "range" and dotted == "range":
            return ("range", tuple(args))
        if last == "dtype" and args:
            return ("dtypeof", args[0])
        if last == "ShapeDtypeStruct":
            return _ShapeStructV(args[0] if args else kwargs.get("shape"),
                                 args[1] if len(args) > 1
                                 else kwargs.get("dtype"))
        if last in _TRANSPARENT_CALLS and args:
            return args[0]
        if last == "astype" and isinstance(node.func, ast.Attribute):
            return self.eval(node.func.value, env, depth)

        fv = None
        if isinstance(node.func, ast.Name):
            fv = env.get(node.func.id) or self.module_env.get(node.func.id)
        elif isinstance(node.func, ast.Call) or not dotted:
            fv = self.eval(node.func, env, depth)
        if isinstance(fv, _PallasCallable):
            return self._finish_site(fv, args)
        if isinstance(fv, _Func):
            gate_rec = None
            if fv.name in self.gate_names:
                gate_rec = (fv.name,
                            self._gate_args(fv.node, args, dict(kwargs),
                                            depth))
            out = self._call(fv, args, kwargs, depth + 1)
            if gate_rec is not None:
                # set after the inline: the gate body's own `if`s clear
                # the capture flag while executing
                self.last_gate = gate_rec
            return out
        name = dotted or _canon(fv) if fv is not None else (dotted or "?")
        return ("callv", name, tuple(args))

    def _gate_args(self, node: ast.FunctionDef, args, kwargs, depth):
        env = self.module_env.new_child({})
        self._bind(node.args, list(args), dict(kwargs), env, depth)
        return [(p.arg, env[p.arg])
                for p in node.args.posonlyargs + node.args.args
                + node.args.kwonlyargs]

    def _fori(self, args, node, depth):
        if len(args) < 4:
            return _atom(f"fori@{node.lineno}")
        lo, hi, body, init = args[:4]
        if not isinstance(body, _Func):
            self.note(f"fori_loop body at line {node.lineno} is not a "
                      "local function — loop not walked")
            return _atom(f"fori@{node.lineno}")
        pnames = [p.arg for p in body.node.args.posonlyargs
                  + body.node.args.args]
        vname = f"{pnames[0] if pnames else 'i'}@L{body.node.lineno}"
        loop = _Loop(self._uid(), vname, _bin("sub", hi, lo))
        self.regions.append(("loop", loop))
        try:
            return self._call(body, [_atom(vname), init], {}, depth + 1)
        finally:
            self.regions.pop()

    # -- kernel-walk hooks ---------------------------------------------------

    def _event(self, kind: str, copy: _CopyV, line: int):
        if self.events is None:
            return
        dst_base, dst_idx = self._base_and_index(copy.dst)
        sem_base, _ = self._base_and_index(copy.sem)
        key = (dst_base, sem_base)
        slot = None
        if dst_idx is not None:
            first = dst_idx[0] if isinstance(dst_idx, list) else dst_idx
            slot = first[1] if _is_tag(first, "ds") else first
        self.seq += 1
        self.events.append(_Event(
            kind=kind, key=key, slot=slot, dst_index=dst_idx,
            regions=tuple(self.regions), seq=self.seq, line=line,
            desc=f"{_canon(copy.src)} -> {_canon(copy.dst)} "
                 f"sem {_canon(copy.sem)}"))

    @staticmethod
    def _base_and_index(v) -> Tuple[str, Optional[List[Any]]]:
        if _is_tag(v, "refat"):
            ref, idx = v[1], v[2]
            items = list(idx[1]) if _is_tag(idx, "tuple") else [idx]
            return _canon(ref), items
        if isinstance(v, _RefV):
            return v.name, None
        if _is_tag(v, "refat0"):
            return _canon(v[1]), None
        return _canon(v), None

    def _access(self, ref: _RefV, idx, kind: str, line: int):
        if self.events is None or ref.block is None:
            return
        items = list(idx[1]) if _is_tag(idx, "tuple") else [idx]
        for axis, it in enumerate(items):
            if axis >= len(ref.block):
                break
            dim = ref.block[axis]
            if not _is_tag(dim, "atom"):
                continue
            leaf = dim[1]
            if leaf in self.witness:
                continue
            if _is_tag(it, "ds"):
                start, size = it[1], it[2]
                if start == 0 and size is not None \
                        and _canon(size) != leaf:
                    self.witness[leaf] = size       # full-extent witness
                elif size == 1 and _is_tag(start, "mod"):
                    self.witness[leaf] = start[2]   # modular witness

    # -- pallas site construction -------------------------------------------

    def _spec_branches(self, v) -> List[List[_BlockSpecV]]:
        if _is_tag(v, "branches"):
            out = []
            for b in v[1]:
                out.extend(self._spec_branches(b))
            return out
        if _is_tag(v, "list") or _is_tag(v, "tuple"):
            flat: List[_BlockSpecV] = []
            for x in v[1]:
                if isinstance(x, _BlockSpecV):
                    flat.append(x)
                elif _is_tag(x, "list") or _is_tag(x, "tuple"):
                    flat.extend(y for y in x[1]
                                if isinstance(y, _BlockSpecV))
            return [flat]
        if isinstance(v, _BlockSpecV):
            return [[v]]
        return [[]]

    def _finish_site(self, pc: _PallasCallable, operands: List[Any]):
        kw = pc.kwargs
        in_branches = self._merge_branches(kw.get("in_specs"))
        out_specs = self._flat_specs(kw.get("out_specs"))
        out_shape = self._flat_any(kw.get("out_shape"))
        scratch = self._flat_any(kw.get("scratch_shapes"))
        site = _KernelSite(
            wrapper=self.fn_stack[-1] if self.fn_stack else "<module>",
            kernel=pc.kernel if isinstance(pc.kernel, _Func) else None,
            in_specs=in_branches, out_specs=out_specs,
            out_shape=out_shape, scratch=scratch,
            operands=operands, line=pc.line,
            gate=self._current_gate())
        self.sites.append(site)
        self._walk_kernel(site)
        n_out = max(len(out_shape), 1)
        return ("tuple", tuple(
            _atom(f"{site.wrapper}.out[{i}]@{pc.line}")
            for i in range(n_out))) if n_out > 1 else \
            _atom(f"{site.wrapper}.out@{pc.line}")

    def _merge_branches(self, v) -> List[List[_BlockSpecV]]:
        """in_specs may be list-of-specs with a Branches sublist (the
        int4/int8 ``_weight_specs`` fork): expand to full branch lists."""
        if v is None:
            return [[]]
        if _is_tag(v, "branches"):
            out = []
            for b in v[1]:
                out.extend(self._merge_branches(b))
            return out
        if not (_is_tag(v, "list") or _is_tag(v, "tuple")):
            return self._spec_branches(v)
        branches: List[List[_BlockSpecV]] = [[]]
        for x in v[1]:
            if isinstance(x, _BlockSpecV):
                for b in branches:
                    b.append(x)
            elif _is_tag(x, "branches"):
                new: List[List[_BlockSpecV]] = []
                for alt in x[1]:
                    sub = self._spec_branches(alt)
                    for b in branches:
                        for s in sub:
                            new.append(b + s)
                branches = new
            elif _is_tag(x, "list") or _is_tag(x, "tuple"):
                for b in branches:
                    b.extend(y for y in x[1] if isinstance(y, _BlockSpecV))
        return branches

    def _flat_specs(self, v) -> List[_BlockSpecV]:
        bs = self._spec_branches(v) if v is not None else [[]]
        return bs[0]

    @staticmethod
    def _flat_any(v) -> List[Any]:
        if v is None:
            return []
        if _is_tag(v, "list") or _is_tag(v, "tuple"):
            return list(v[1])
        return [v]

    def _current_gate(self):
        for g in reversed(self.gate_stack):
            if g is not None:
                return g
        return None

    def _as_shape_tuple(self, v) -> Optional[List[Any]]:
        if v is None:
            return None
        if _is_tag(v, "tuple") or _is_tag(v, "list"):
            return list(v[1])
        if _is_tag(v, "attr") and v[2] == "shape":
            c = _canon(v)
            rank = self.shape_ranks.get(c)
            if rank is not None:
                return [_atom(f"{c}[{i}]") for i in range(rank)]
        return None

    def _walk_kernel(self, site: _KernelSite):
        kernel = site.kernel
        if kernel is None or not isinstance(kernel.node, ast.FunctionDef):
            self.note(f"{site.wrapper}: pallas_call kernel is not a local "
                      "function — body not analyzed")
            site.walk_ok = False
            return
        specs0 = site.in_specs[0] if site.in_specs else []
        refs: List[_RefV] = []
        for i, spec in enumerate(specs0):
            op = site.operands[i] if i < len(site.operands) else None
            dt = (("attr", op, "dtype") if op is not None else None)
            refs.append(_RefV(f"in{i}", "in", spec.space,
                              self._as_shape_tuple(spec.shape), dt))
        for i, spec in enumerate(site.out_specs):
            sh = site.out_shape[i] if i < len(site.out_shape) else None
            dt = sh.dtype if isinstance(sh, _ShapeStructV) else None
            refs.append(_RefV(f"out{i}", "out", spec.space,
                              self._as_shape_tuple(spec.shape), dt))
        for i, sc in enumerate(site.scratch):
            if isinstance(sc, _ScratchV):
                refs.append(_RefV(f"scratch{i}", "scratch", sc.space,
                                  self._as_shape_tuple(sc.shape), sc.dtype))
            else:
                refs.append(_RefV(f"scratch{i}", "scratch", "vmem",
                                  None, None))
        a = kernel.node.args
        pnames = [p.arg for p in a.posonlyargs + a.args]
        for i, ref in enumerate(refs):
            if i < len(pnames):
                ref.name = pnames[i]
        if len(pnames) != len(refs) and not a.vararg:
            self.note(f"{site.wrapper}/{kernel.name}: {len(pnames)} kernel "
                      f"params vs {len(refs)} refs — alignment is "
                      "best-effort")
        if a.vararg and any(r.space == "any" for r in refs[len(pnames):]):
            self.note(f"{site.wrapper}/{kernel.name}: an ANY-space ref "
                      "maps into *varargs — MX102 cannot check it")
        bound = {ref.name: ref for ref in refs[:max(len(pnames), 0)]
                 if ref.name in pnames}
        site.param_map = {r.name: r for r in refs}
        env = kernel.env.new_child(dict(bound))
        if a.vararg:
            env[a.vararg.arg] = ("tuple", tuple(refs[len(pnames):]))
        saved_events, saved_regions = self.events, self.regions
        saved_witness, saved_seq = self.witness, self.seq
        self.events, self.regions = [], []
        self.witness, self.seq = {}, 0
        try:
            frame = {"returns": [], "done": False, "base": 0}
            self._exec(kernel.node.body, env, _MAX_DEPTH // 2, frame)
        except RecursionError:
            site.walk_ok = False
            self.note(f"{site.wrapper}/{kernel.name}: recursion limit "
                      "during kernel walk")
        except Exception as e:
            site.walk_ok = False
            self.note(f"{site.wrapper}/{kernel.name}: kernel walk failed: "
                      f"{type(e).__name__}: {e}")
        finally:
            site.events = self.events
            site.witness = self.witness
            self.events, self.regions = saved_events, saved_regions
            self.witness, self.seq = saved_witness, saved_seq


def _itemsize_of(v):
    if _is_tag(v, "dtypeof"):
        return _itemsize_of(v[1])
    if _is_tag(v, "dtype"):
        return _DTYPE_SIZES.get(v[1], 4)
    if _is_tag(v, "attr") and v[2] in _DTYPE_SIZES:
        return _DTYPE_SIZES[v[2]]
    return _atom(f"itemsize[{_canon(v)}]")


# ---------------------------------------------------------------------------
# MX101 — DMA lifecycle
# ---------------------------------------------------------------------------

def _cond_path(ev: _Event, base: int = 0) -> Tuple[str, ...]:
    return tuple(r[1] for r in ev.regions[base:] if r[0] == "when")


def _loop_path(ev: _Event) -> Tuple[_Loop, ...]:
    return tuple(r[1] for r in ev.regions if r[0] == "loop")


def _is_prefix(a: Tuple, b: Tuple) -> bool:
    return len(a) <= len(b) and tuple(b[:len(a)]) == tuple(a)


def _rel_conds(ev: _Event, loop: _Loop) -> Tuple[str, ...]:
    """Conditions acquired after entering ``loop``."""
    out, inside = [], False
    for r in ev.regions:
        if r[0] == "loop" and r[1] is loop:
            inside = True
            continue
        if inside and r[0] == "when":
            out.append(r[1])
    return tuple(out)


def _mx101(site: _KernelSite) -> List[Dict[str, Any]]:
    findings: List[Dict[str, Any]] = []
    seen: Set[Tuple[str, int, str]] = set()

    def add(line: int, msg: str, snip: str):
        k = ("MX101", line, snip)
        if k in seen:
            return
        seen.add(k)
        findings.append({"rule": "MX101", "line": line, "col": 0,
                         "message": msg,
                         "context": site.wrapper, "snippet": snip})

    events = site.events
    starts = [e for e in events if e.kind == "start"]
    waits = [e for e in events if e.kind == "wait"]
    by_key_w: Dict[Tuple[str, str], List[_Event]] = {}
    for w in waits:
        by_key_w.setdefault(w.key, []).append(w)

    # 1. coverage: every start needs a wait whose guard set is a prefix
    for s in starts:
        ws = by_key_w.get(s.key, [])
        if not any(_is_prefix(_cond_path(w), _cond_path(s)) for w in ws):
            why = ("is never waited" if not ws else
                   "has no wait covering all paths (every wait on this "
                   "(dst, sem) pair sits under a different guard)")
            add(s.line,
                f"async copy {s.desc} started here {why}",
                f"start {s.key[0]}@{s.key[1]}")

    # 2. same-slot double start without an intervening wait (linear scan)
    live: Dict[Tuple[Tuple[str, str], str], _Event] = {}
    for e in sorted(events, key=lambda e: e.seq):
        slot_c = _canon(e.slot) if e.slot is not None else "<whole>"
        if e.kind == "wait":
            for k in [k for k in live if k[0] == e.key]:
                del live[k]
            continue
        k = (e.key, slot_c)
        if k in live:
            add(e.line,
                f"async copy {e.desc} re-started into slot {slot_c} "
                f"with no intervening wait (previous start at line "
                f"{live[k].line})",
                f"double-start {e.key[0]}[{slot_c}]")
        live[k] = e

    # 3. per-loop slot rotation
    defs = site.witness
    loops: List[_Loop] = []
    for e in events:
        for lp in _loop_path(e):
            if lp not in loops:
                loops.append(lp)
    for loop in loops:
        if loop.var is None:
            continue
        s_l = [s for s in starts if loop in _loop_path(s)]
        w_l = [w for w in waits if loop in _loop_path(w)]
        for s in s_l:
            ws = [w for w in w_l if w.key == s.key]
            slot = s.slot
            rotating = slot is not None and _refs_atom(slot, loop.var)
            if not rotating:
                # constant slot within this loop: disjoint addressing via
                # any loop-var-dependent index component is fine
                idx = s.dst_index or []
                if any(_refs_atom(c, lv.var)
                       for c in idx
                       for lv in _loop_path(s)):
                    continue
                if slot is None and not idx:
                    # whole-ref copy with loop-var-free addressing may
                    # still be iteration-disjoint through the semaphore
                    # array or source side; require a same-key wait
                    pass
                if not ws:
                    add(s.line,
                        f"async copy {s.desc} starts into the same slot "
                        f"every iteration of loop '{loop.var}' with no "
                        "wait on that (dst, sem) pair inside the loop",
                        f"loop-reuse {s.key[0]}")
                continue
            if _is_tag(slot, "mod") and _refs_atom(slot[1], loop.var) \
                    and not _refs_atom(slot[2], loop.var):
                d = slot[2]
                if not ws:
                    trip = loop.trip
                    if trip is not None and _forall_samples(
                            lambda k: _nume(trip, k, defs)
                            <= _nume(d, k, defs)):
                        continue  # warm-up: fills <= depth distinct slots
                    add(s.line,
                        f"rotating async copy {s.desc} (slot "
                        f"{_canon(slot)}) re-uses each slot after "
                        f"{_canon(d)} iterations of loop '{loop.var}' "
                        "but the loop contains no wait on that "
                        "(dst, sem) pair and its trip count is not "
                        "provably <= the slot count",
                        f"rotate-unwaited {s.key[0]}")
                    continue
                ok = False
                for w in ws:
                    if not _is_prefix(_rel_conds(w, loop),
                                      _rel_conds(s, loop)):
                        continue
                    wslot = w.slot
                    if wslot is None:
                        ok = True
                        break

                    def _safe_distance(k, wslot=wslot):
                        # same modulus AND bounded prefetch distance:
                        # with the wait retiring slot (w_expr % d) each
                        # iteration, a start into (s_expr % d) reuses a
                        # slot whose previous occupant was waited iff
                        # 0 <= s_expr - w_expr <= d (distance d is the
                        # classic double buffer, d-1 the shipped
                        # warm-by-depth-1 pipeline; d+1 would overwrite
                        # a copy still in flight).
                        dd = _nume(d, k, defs)
                        if _nume(wslot[2], k, defs) != dd:
                            return False
                        diff = _nume(slot[1], k, defs) \
                            - _nume(wslot[1], k, defs)
                        return 0 <= diff <= dd

                    if _is_tag(wslot, "mod") and \
                            _forall_samples(_safe_distance):
                        ok = True
                        break
                if not ok:
                    add(s.line,
                        f"cannot prove slot rotation safe for {s.desc}: "
                        f"starts rotate modulo {_canon(d)} in loop "
                        f"'{loop.var}' but no unconditional same-key "
                        "wait rotates with the same modulus",
                        f"rotate-unproven {s.key[0]}")
            else:
                # slot varies with the loop but is not i%d — require an
                # unconditional same-key wait in the loop
                if not any(_is_prefix(_rel_conds(w, loop),
                                      _rel_conds(s, loop)) for w in ws):
                    add(s.line,
                        f"cannot prove slot safety for {s.desc}: slot "
                        f"{_canon(slot)} varies with loop '{loop.var}' "
                        "and no unconditional wait on that (dst, sem) "
                        "pair runs in the loop",
                        f"slot-unproven {s.key[0]}")
    return findings


# ---------------------------------------------------------------------------
# MX102 — memory-space discipline
# ---------------------------------------------------------------------------

def _mx102(site: _KernelSite) -> List[Dict[str, Any]]:
    if site.kernel is None:
        return []
    anyrefs = {n for n, r in site.param_map.items() if r.space == "any"}
    if not anyrefs:
        return []
    body = site.kernel.node
    shadowed: Set[str] = set()
    for n in ast.walk(body):
        if isinstance(n, (ast.FunctionDef, ast.Lambda)) and n is not body:
            for p in n.args.posonlyargs + n.args.args + n.args.kwonlyargs:
                if p.arg in anyrefs:
                    shadowed.add(p.arg)
    allowed: Set[int] = set()
    for n in ast.walk(body):
        if isinstance(n, ast.Call):
            d = _dotted(n.func)
            if d and d.rsplit(".", 1)[-1] == "make_async_copy":
                for arg in list(n.args) + [kw.value for kw in n.keywords]:
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Name) and sub.id in anyrefs:
                            allowed.add(id(sub))
        elif isinstance(n, ast.Delete):
            for t in n.targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        allowed.add(id(sub))
    findings = []
    for n in ast.walk(body):
        if isinstance(n, ast.Name) and n.id in anyrefs \
                and n.id not in shadowed and id(n) not in allowed:
            findings.append({
                "rule": "MX102", "line": n.lineno, "col": n.col_offset,
                "message": (f"HBM-resident (pl.ANY) ref '{n.id}' used "
                            "outside an async copy — direct loads/stores "
                            "or compute on an ANY ref read HBM from "
                            "inside the kernel"),
                "context": site.wrapper, "snippet": f"any-use {n.id}"})
    return findings


# ---------------------------------------------------------------------------
# MX103 — static VMEM budget vs the runtime gate
# ---------------------------------------------------------------------------

def _kernel_bytes(site: _KernelSite, branch: List[_BlockSpecV],
                  machine: _Machine) -> Tuple[Any, List[Tuple[str, Any]]]:
    comps: List[Tuple[str, Any]] = []
    names = list(site.param_map)

    def block_term(i, spec, operand_dtype, role, label):
        if spec.space != "vmem":
            return None
        shape = machine._as_shape_tuple(spec.shape)
        if shape is None:
            return ("unknown", label)
        rank = len(shape)
        prod: Any = 1
        for d in shape:
            prod = _bin("mul", prod, d)
        if rank >= 3:
            return ("exact", label, _bin("mul", prod,
                                         _itemsize_of(operand_dtype)
                                         if operand_dtype is not None
                                         else 4))
        if rank == 2 and role == "in" and shape[0] != 1 \
                and _streamed(spec, machine, site):
            return ("stream", label, prod)
        return None

    stream_terms: List[Any] = []
    total: Any = 0
    for i, spec in enumerate(branch):
        op = site.operands[i] if i < len(site.operands) else None
        dt = ("attr", op, "dtype") if op is not None else None
        label = names[i] if i < len(names) else f"in{i}"
        t = block_term(i, spec, dt, "in", f"in:{label}")
        if t is None:
            continue
        if t[0] == "unknown":
            return None, [("unknown-shape", t[1])]
        if t[0] == "stream":
            stream_terms.append(t[2])
            comps.append((t[1] + " (stream)", t[2]))
        else:
            total = _bin("add", total, t[2])
            comps.append((t[1], t[2]))
    for i, spec in enumerate(site.out_specs):
        sh = site.out_shape[i] if i < len(site.out_shape) else None
        dt = sh.dtype if isinstance(sh, _ShapeStructV) else None
        t = block_term(i, spec, dt, "out", f"out{i}")
        if t is None:
            continue
        if t[0] == "unknown":
            return None, [("unknown-shape", t[1])]
        if t[0] == "exact":
            total = _bin("add", total, t[2])
            comps.append((t[1], t[2]))
    n_in = len(branch)
    n_out = len(site.out_specs)
    for i, sc in enumerate(site.scratch):
        if not isinstance(sc, _ScratchV) or sc.space != "vmem":
            continue
        shape = machine._as_shape_tuple(sc.shape)
        pi = n_in + n_out + i
        label = names[pi] if pi < len(names) else f"scratch{i}"
        if shape is None:
            return None, [("unknown-shape", f"scratch:{label}")]
        prod: Any = 1
        for d in shape:
            prod = _bin("mul", prod, d)
        term = _bin("mul", prod, _itemsize_of(sc.dtype)
                    if sc.dtype is not None else 4)
        total = _bin("add", total, term)
        comps.append((f"scratch:{label}", term))
    if stream_terms:
        wt = stream_terms[0] if len(stream_terms) == 1 \
            else ("max", tuple(stream_terms))
        total = _bin("add", total, wt)
    return total, comps


def _streamed(spec: _BlockSpecV, machine: _Machine,
              site: _KernelSite) -> bool:
    idx = spec.index
    if not isinstance(idx, _Func):
        return False
    a = idx.node.args
    arity = len(a.posonlyargs + a.args) or 1
    try:
        r0 = machine._call(idx, [0] * arity, {}, _MAX_DEPTH - 2)
        r1s = [machine._call(idx,
                             [1 if j == p else 0 for j in range(arity)],
                             {}, _MAX_DEPTH - 2)
               for p in range(arity)]
    except Exception:
        return True  # assume streamed when the index map resists analysis
    defs = site.witness
    for r1 in r1s:
        if _canon(r0) == _canon(r1):
            continue
        for k in range(_N_SAMPLES):
            if _nume(r0, k, defs) != _nume(r1, k, defs):
                return True
    return False


def _gate_bytes(machine: _Machine, gate: str,
                bound: List[Tuple[str, Any]]
                ) -> Tuple[Optional[Any], List[Tuple[str, Any]]]:
    node = machine.funcs.get(gate)
    if node is None:
        return None, []
    env = machine.module_env.new_child(
        {name: val for name, val in bound})
    frame = {"returns": [], "done": False, "base": len(machine.regions)}
    try:
        machine._exec(node.body, env, 2, frame)
    except Exception as e:
        machine.note(f"gate {gate}: evaluation failed: "
                     f"{type(e).__name__}: {e}")
        return None, []
    rets = [s for s in node.body if isinstance(s, ast.Return)]
    if not rets or not isinstance(rets[-1].value, ast.Compare):
        return None, []
    try:
        lhs = machine.eval(rets[-1].value.left, env, 2)
    except Exception as e:
        machine.note(f"gate {gate}: byte expression failed: "
                     f"{type(e).__name__}: {e}")
        return None, []
    locals_ = [(k, v) for k, v in env.maps[0].items()
               if not isinstance(v, (_Func, _RefV))]
    return lhs, locals_


def _leaves(v, out: Set[str]):
    if _is_tag(v, "atom"):
        out.add(v[1])
        return
    if isinstance(v, tuple):
        for x in v:
            if isinstance(x, (tuple, list)):
                _leaves(x, out)
    elif isinstance(v, list):
        for x in v:
            _leaves(x, out)


def _mx103(site: _KernelSite, machine: _Machine
           ) -> Tuple[Optional[GatePair], List[Dict[str, Any]]]:
    if site.gate is None:
        return None, []
    gate_name, bound = site.gate
    gate_expr, gate_locals = _gate_bytes(machine, gate_name, bound)
    if gate_expr is None:
        machine.note(f"{site.wrapper}: gate {gate_name} byte arithmetic "
                     "could not be evaluated — MX103 skipped")
        return None, []
    defs = site.witness
    branch_results = []
    for branch in site.in_specs:
        total, comps = _kernel_bytes(site, branch, machine)
        if total is None:
            machine.note(f"{site.wrapper}: {comps[0][1]} has no statically "
                         "known shape — MX103 skipped")
            return None, []
        branch_results.append((total, comps))
    agree_branch = None
    for total, comps in branch_results:
        if _forall_samples(lambda k: _nume(total, k, defs)
                           == _nume(gate_expr, k, defs)):
            agree_branch = (total, comps)
            break
    if agree_branch is not None:
        return GatePair(gate_name, site.wrapper, True), []
    total, comps = branch_results[0]
    bad_k = next(k for k in range(_N_SAMPLES)
                 if _nume(total, k, defs) != _nume(gate_expr, k, defs))
    leaves: Set[str] = set()
    _leaves(total, leaves)
    _leaves(gate_expr, leaves)
    assign = ", ".join(f"{l}={_nume(_atom(l), bad_k, defs)}"
                       for l in sorted(leaves))
    kparts = "; ".join(f"{n}={_nume(v, bad_k, defs)}" for n, v in comps)
    gparts = "; ".join(
        f"{n}={_nume(v, bad_k, defs)}" for n, v in gate_locals
        if isinstance(_nume(v, bad_k, defs), (int, float))
        and not _is_tag(v, "cmp"))
    detail = (f"kernel={_nume(total, bad_k, defs)} vs "
              f"gate={_nume(gate_expr, bad_k, defs)} at {{{assign}}}; "
              f"kernel terms: {kparts}; gate locals: {gparts}")
    finding = {
        "rule": "MX103", "line": site.line, "col": 0,
        "message": (f"kernel VMEM footprint disagrees with runtime gate "
                    f"{gate_name}(): {detail}"),
        "context": site.wrapper,
        "snippet": f"budget {gate_name}~{site.wrapper}"}
    return GatePair(gate_name, site.wrapper, False, detail), [finding]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def analyze_source(source: str, path: str = "<string>",
                   select: Optional[Sequence[str]] = None) -> KernelReport:
    """Analyze one module's Pallas kernels. ``select`` limits rules to a
    subset of MX101/MX102/MX103 (None means all three)."""
    rep = KernelReport(path=path, kernels=[], pairs=[], findings=[],
                       notes=[])
    wanted = set(select) if select else set(RULES)
    if "pallas_call" not in source:
        return rep
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        rep.notes.append(f"{path}: syntax error: {e.msg}")
        return rep
    m = _Machine(tree, path, source)
    m.run()
    rep.kernels = m.sites
    findings: List[Dict[str, Any]] = []
    for site in m.sites:
        if "MX101" in wanted and site.walk_ok:
            findings.extend(_mx101(site))
        if "MX102" in wanted:
            findings.extend(_mx102(site))
        if "MX103" in wanted:
            pair, fs = _mx103(site, m)
            if pair is not None:
                rep.pairs.append(pair)
            findings.extend(fs)
    for f in findings:
        f["path"] = path
    rep.findings = [f for f in findings if f["rule"] in wanted]
    rep.notes.extend(m.notes)
    return rep


def analyze_file(path: str,
                 select: Optional[Sequence[str]] = None) -> KernelReport:
    with open(path, encoding="utf-8") as fh:
        return analyze_source(fh.read(), path, select)
